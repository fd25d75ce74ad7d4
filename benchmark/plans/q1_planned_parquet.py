"""Plan ``q1_planned_parquet``: planned TPC-H q1 (``q1_planned``'s plan)
whose scan is bound to a Parquet split, not to a resident table: the
server reads the footer, admits, decodes and stages the seven columns
before the same fused region runs."""

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
# the binding this plan's cell sends: a program without it cannot run the
# cell, and says so here, before a file is written
from spark_rapids_jni_tpu.parquet.split import ParquetSplit  # noqa: F401


def plan():
    from spark_rapids_jni_tpu.models import tpch

    return tpch._q1_planned_plan()
