"""Plan ``q13_planned``: TPC-H q13, whole, with customer's dense clustered
primary key declared, as one fused region: the ``NOT LIKE`` over the
comment column as a Filter, a groupby of up to |customer| groups on the
sort path under the key's declared range, the outer join as a positional
fill of the customers' slots, a small in-place groupby and the result's
two-key sort.

It scans two tables. ``customer`` is the outer join's preserved side:
resident, bound to every request as the same device arrays (the positional
fill needs it in load order, which a roll would break). ``orders`` is new
every request."""

# The cell does not run on a program without the whole q13 as a Plan: an
# ImportError here, at ``resolve.module``, before any table is made.
from spark_rapids_jni_tpu.models.tpch import _q13_plan  # noqa: F401

from benchmark.reference_q13 import (  # noqa: E402,F401  (the interface)
    LIMITS,
    WORDS,
    compare,
    control,
    min_bytes,
    oracle,
    read_answer,
)

BINDINGS = {"customer": "customer", "orders": "orders"}   # scan -> table
FRESH = ("orders",)                      # rolled for every request


def plan():
    return _q13_plan(*(w.decode() for w in WORDS))
