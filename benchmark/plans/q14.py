"""Plan ``q14``: TPC-H q14, whole, as one fused region whose join is the
general one that lays rows out: ``Join(how="inner")`` on part keys nobody
declared anything about, a fresh lineitem batch probing a resident
``part`` (in a seeded permutation), ``p_type`` carried through the join
and the ``CASE ... LIKE 'PROMO%'`` above it, two decimal sums.

The join's ``out_rows`` is a capacity the plan states, as a cost-based
optimiser would from the date column's range: the month holds 30 of
about 2,400 days, about 1.25% of the lineitems (750,000 of 59,986,052 at
SF10), and the next power of two with room is 2,097,152 rows (262,144 at
SF1). It is the configuration's ``join_out_rows`` (listed under its
``assumed``), passed to the program as an int. A request whose join
outgrows it fails (``CapacityOverflow``).

``part`` stays resident, as a deployment holds a broadcast relation: the
same ``Table`` every request, digested once, admitted and scanned every
time. The lineitem batch is new every request."""

# The cell does not run on a program without the whole q14 as a Plan: an
# ImportError here, at ``resolve.module``, before any table is made.
from spark_rapids_jni_tpu.models.tpch import _q14_plan  # noqa: F401

from benchmark import resolve  # noqa: E402
from benchmark.reference_q14 import (  # noqa: E402,F401  (the interface)
    LIMITS,
    MONTH,
    compare,
    control,
    min_bytes,
    oracle,
    read_answer,
)

BINDINGS = {"lineitem": "lineitem", "part": "part"}   # scan -> table
FRESH = ("lineitem",)                    # part is bound once, resident


def plan():
    config = resolve.data("configs", "tpch_q14_lineitem_part")
    return _q14_plan(*MONTH, out_rows=int(config["join_out_rows"]))
