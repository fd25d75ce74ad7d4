"""Plan ``q1_distributed``: TPC-H q1 as a cluster runs it, one ``Plan``
bound to a lineitem whose rows are sharded over the four chips of a host:
a partial aggregate a chip, a shuffle by the group key, a merge, the
driver's collect and sort. The reference is the three q1 cells' plain
numpy one: it knows nothing of chips, so the same rows give the same
answer.

The plan maker is imported here, at the top, so that a program without it
fails when this file is resolved: within seconds, before any table is made.
"""

from benchmark import resolve
from benchmark.reference_q1 import (  # noqa: F401  (the plan's interface)
    BINDING,
    LIMITS,
    TABLE,
    compare,
    control,
    oracle,
    read_answer,
)
from spark_rapids_jni_tpu.models.tpch import _q1_distributed_plan

CHIPS = 4
ROW_BYTES = resolve.module("tables", "lineitem").ROW_BYTES


def plan():
    return _q1_distributed_plan()


def min_bytes(rows: int) -> int:
    """The least ONE chip must move for one answer: one pass over its
    quarter of the rows, the seven columns q1 reads (38 bytes a row). The
    roofline reader divides by one chip's bandwidth and by the busy time
    averaged over the chips, so a chip's share is what it needs."""
    return ROW_BYTES * (int(rows) // CHIPS)
