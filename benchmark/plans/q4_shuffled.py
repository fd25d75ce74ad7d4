"""Plan ``q4_shuffled``: TPC-H q4 as a cluster runs it: the SAME ``Plan`` as
``q4`` (``tpch._q4_plan``, unchanged) bound to an orders and a lineitem
whose rows are sharded over the four chips of a host. The sharding of the
bound buffers is the only signal: the ``EXISTS`` then lowers as a shuffled
join (both sides exchanged by the hash of the order key, an ``all_to_all``
of rows over ICI, the semi join where the rows land), the bounded groupby
as a partial a chip and one merge across them. The reference is the
one-chip cell's plain numpy one: it knows nothing of chips, so the same
rows give the same answer.

Both tables are new every request, as in ``q4``: a chip's partition of
either side is a fresh one.
"""

# The cell does not run on a program without the lowering of a join over a
# mesh: an ImportError here, at ``resolve.module``, before any table is made.
from spark_rapids_jni_tpu.parallel.distributed import shuffled_join  # noqa: F401
from spark_rapids_jni_tpu.models.tpch import _q4_plan  # noqa: E402

from benchmark import reference_q4  # noqa: E402
from benchmark.reference_q4 import (  # noqa: E402,F401  (the interface)
    LIMITS,
    QUARTER,
    compare,
    control,
    oracle,
    read_answer,
)

CHIPS = 4
BINDINGS = {"orders": "orders", "lineitem": "lineitem"}   # scan -> table
FRESH = ("orders", "lineitem")           # both rolled for every request


def plan():
    return _q4_plan(*QUARTER)


def min_bytes(rows: dict) -> int:
    """The least ONE chip must move for one answer: one pass over its
    quarter of the columns q4 reads of each table (``{table name: rows}``).
    The roofline reader divides by one chip's bandwidth and by the busy
    time averaged over the chips, so a chip's share is what it needs."""
    return reference_q4.min_bytes(
        {t: int(n) // CHIPS for t, n in rows.items()})
