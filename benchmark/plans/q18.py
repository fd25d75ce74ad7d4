"""Plan ``q18``: TPC-H q18, whole, as one fused region: ``sum(l_quantity)``
by an ``l_orderkey`` nobody declared a range for (the general sort path,
a row in four a group), the HAVING as a ``Filter`` over the groups, the
IN as ``Join(how="left_semi")`` of orders against them, two joins that lay
rows out (customer, then lineitem again: the same scan the first groupby
reads), the outer groupby on five keys one of them a string, the ORDER BY
and the LIMIT.

The two joins' ``out_rows`` is a capacity the plan states: dbgen's Q18
answer holds 57 orders at SF1 and some hundreds at SF10, each with seven
lineitems, and the next power of two with room for ten times that is
65,536 rows at either scale. It is the configuration's ``join_out_rows``
(listed under its ``assumed``), passed to the program as an int. A request
whose join outgrows it fails (``CapacityOverflow``).

``customer`` stays resident, as a deployment holds a broadcast relation:
the same ``Table`` every request, digested once, admitted and scanned
every time. The lineitem and orders batches are new every request: both
fact tables are exchanged on the order key, so a task gets a fresh
partition of each."""

# The cell does not run on a program without the whole q18 as a Plan: an
# ImportError here, at ``resolve.module``, before any table is made.
from spark_rapids_jni_tpu.models.tpch import _q18_plan  # noqa: F401

from benchmark import resolve  # noqa: E402
from benchmark.reference_q18 import (  # noqa: E402,F401  (the interface)
    LIMIT,
    LIMITS,
    QUANTITY,
    compare,
    control,
    min_bytes,
    oracle,
    read_answer,
)

BINDINGS = {"lineitem": "lineitem", "orders": "orders",
            "customer": "customer"}      # scan -> table
FRESH = ("lineitem", "orders")           # customer is bound once, resident


def plan():
    config = resolve.data("configs", "tpch_q18_large_orders")
    return _q18_plan(QUANTITY, out_rows=int(config["join_out_rows"]),
                     limit=LIMIT)
