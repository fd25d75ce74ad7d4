"""Plan ``q1_planned``: TPC-H q1 with the flag domains declared, so the
groupby is the sort-free bounded-domain reduction (one fused region)."""

from benchmark.reference_q1 import (  # noqa: F401  (the plan's interface)
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

    return tpch._q1_planned_plan()
