"""Plan ``q1_general``: TPC-H q1 with no declared domain, so the groupby
is the general sort-based one every undeclared groupby takes."""

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

    return tpch._q1_plan()
