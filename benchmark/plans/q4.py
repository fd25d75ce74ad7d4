"""Plan ``q4``: TPC-H q4, whole, as one fused region whose ``EXISTS`` is
the general join: ``Join(how="left_semi")`` on order keys nobody declared
anything about (dbgen's are sparse), between the two ``WHERE``s and a
groupby keyed by the priority string.

It scans two tables and both are new every request: a general join does
not care where a row lies, so rolling either side keeps the answer, and a
task of a shuffled join gets a fresh partition of both sides."""

# The cell does not run on a program without the whole q4 as a Plan: an
# ImportError here, at ``resolve.module``, before any table is made.
from spark_rapids_jni_tpu.models.tpch import _q4_plan  # noqa: F401

from benchmark.reference_q4 import (  # noqa: E402,F401  (the interface)
    LIMITS,
    QUARTER,
    compare,
    control,
    min_bytes,
    oracle,
    read_answer,
)

BINDINGS = {"orders": "orders", "lineitem": "lineitem"}   # scan -> table
FRESH = ("orders", "lineitem")           # both rolled for every request


def plan():
    return _q4_plan(*QUARTER)
