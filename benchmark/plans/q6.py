"""Plan ``q6``: TPC-H q6, one numeric WHERE and one decimal sum over the
rows it keeps, as one fused region: the plainest scan there is."""

from benchmark.reference_q6 import (  # noqa: F401  (the plan's interface)
    BINDING,
    LIMITS,
    TABLE,
    compare,
    control,
    min_bytes,
    oracle,
    read_answer,
)


def plan():
    from spark_rapids_jni_tpu.models import tpch

    return tpch._q6_plan()
