"""TPC-H workload pipelines — the "model family" layer of this framework.

The reference's flagship workloads are Spark SQL queries running through the
RAPIDS accelerator (BASELINE.json configs: RowConversion on the lineitem
schema; TPC-H q1 groupby-aggregate + sort). Here the same queries are
expressed directly against the operator substrate, serving three roles:
the plans ``benchmark/`` and ``chip_smoke.py`` serve, the driver's compile
check (__graft_entry__.py), and integration tests of the operator stack.

TPC-H q1 (pricing summary report):

    SELECT l_returnflag, l_linestatus,
           sum(l_quantity), sum(l_extendedprice),
           sum(l_extendedprice*(1-l_discount)),
           sum(l_extendedprice*(1-l_discount)*(1+l_tax)),
           avg(l_quantity), avg(l_extendedprice), avg(l_discount), count(*)
    FROM lineitem WHERE l_shipdate <= date '1998-12-01' - 90 days
    GROUP BY l_returnflag, l_linestatus ORDER BY l_returnflag, l_linestatus

Money columns use decimal64(-2) (the TPC-H spec's DECIMAL(12,2)) — integer
backing, which is exactly what the TPU wants (the MXU/VPU have no fast f64;
int64 arithmetic is emulated but exact).
"""

from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp
import numpy as np

from spark_rapids_jni_tpu import types as t
from spark_rapids_jni_tpu.columnar import Column, Table
from spark_rapids_jni_tpu.ops.groupby import GroupByResult, groupby_aggregate
from spark_rapids_jni_tpu.ops.sort import sort_table
from spark_rapids_jni_tpu.runtime import fusion, rtfilter
from spark_rapids_jni_tpu.utils.tracing import func_range

# lineitem columns used by q1 (positions in the table below)
L_QUANTITY = 0
L_EXTENDEDPRICE = 1
L_DISCOUNT = 2
L_TAX = 3
L_RETURNFLAG = 4
L_LINESTATUS = 5
L_SHIPDATE = 6

# 1998-12-01 minus 90 days, in days since epoch (Spark DateType encoding)
_Q1_CUTOFF_DAYS = 10560

# q1 groups by two one-byte flags: at most 3*2 real groups plus the null-key
# pseudo-group. A tiny static group budget keeps every downstream shape
# (groupby output, final ORDER BY, shuffle payload) at m rows instead of n —
# and switches groupby_aggregate onto its small-m boundary path (no
# full-length scans).
_Q1_GROUP_BUDGET = 64

# The q1 aggregate plan over _q1_work_table's column layout, shared by the
# jitted pipeline and the checked host wrapper so they cannot diverge.
_Q1_AGGS = [
    (2, "sum"),    # sum_qty
    (3, "sum"),    # sum_base_price
    (5, "sum"),    # sum_disc_price
    (6, "sum"),    # sum_charge
    (2, "mean"),   # avg_qty
    (3, "mean"),   # avg_price
    (4, "mean"),   # avg_disc
    (2, "count"),  # count_order
]

LINEITEM_SCHEMA = [
    t.decimal64(-2),      # l_quantity  DECIMAL(12,2)
    t.decimal64(-2),      # l_extendedprice
    t.decimal64(-2),      # l_discount
    t.decimal64(-2),      # l_tax
    t.INT8,               # l_returnflag  ('A','N','R' as bytes)
    t.INT8,               # l_linestatus  ('F','O')
    t.TIMESTAMP_DAYS,     # l_shipdate
]


def lineitem_table(num_rows: int, seed: int = 0) -> Table:
    """Synthetic lineitem batch with TPC-H-like value distributions."""
    rng = np.random.default_rng(seed)
    qty = rng.integers(100, 51_00, num_rows).astype(np.int64)       # 1..50 qty
    price = rng.integers(90_000, 10_500_000, num_rows).astype(np.int64)
    disc = rng.integers(0, 11, num_rows).astype(np.int64)           # 0.00-0.10
    tax = rng.integers(0, 9, num_rows).astype(np.int64)             # 0.00-0.08
    rflag = rng.choice(np.frombuffer(b"ANR", dtype=np.int8), num_rows)
    lstatus = rng.choice(np.frombuffer(b"FO", dtype=np.int8), num_rows)
    shipdate = rng.integers(8400, 10957, num_rows).astype(np.int32)
    return Table(
        [
            Column.from_numpy(qty, t.decimal64(-2)),
            Column.from_numpy(price, t.decimal64(-2)),
            Column.from_numpy(disc, t.decimal64(-2)),
            Column.from_numpy(tax, t.decimal64(-2)),
            Column.from_numpy(rflag, t.INT8),
            Column.from_numpy(lstatus, t.INT8),
            Column.from_numpy(shipdate, t.TIMESTAMP_DAYS),
        ]
    )


def lineitem_table_strings(num_rows: int, seed: int = 0) -> Table:
    """Lineitem variant with REAL STRING returnflag/linestatus columns —
    the schema shape Spark actually has before dictionary tricks (flags are
    CHAR(1) STRINGs in TPC-H). Runs through the same q1 pipeline: string
    keys sort, group, and shuffle natively."""
    base = lineitem_table(num_rows, seed)
    rf = np.asarray(base.column(L_RETURNFLAG).data).astype(np.uint8)
    ls = np.asarray(base.column(L_LINESTATUS).data).astype(np.uint8)
    cols = list(base.columns)
    cols[L_RETURNFLAG] = Column.from_pylist(
        [chr(b) for b in rf], t.STRING
    )
    cols[L_LINESTATUS] = Column.from_pylist(
        [chr(b) for b in ls], t.STRING
    )
    return Table(cols)


class Q1Result(NamedTuple):
    result: GroupByResult  # grouped aggregates, padded; sorted by flag/status


def _q1_work_table(lineitem: Table) -> Table:
    """Shared q1 front half: WHERE filter + derived decimal columns.

    The filter keeps static shapes by masking validity instead of compacting
    rows (masked rows fall out of every null-skipping aggregate), the
    standard XLA trick for data-dependent filtering.
    """
    ship = lineitem.column(L_SHIPDATE)
    keep = (ship.data <= _Q1_CUTOFF_DAYS) & ship.valid_mask()

    def masked(col: Column) -> Column:
        return Column(col.dtype, col.data, col.valid_mask() & keep)

    qty = masked(lineitem.column(L_QUANTITY))
    price = masked(lineitem.column(L_EXTENDEDPRICE))
    disc = masked(lineitem.column(L_DISCOUNT))
    tax = masked(lineitem.column(L_TAX))

    # disc_price = price * (1 - disc): decimal multiply at scale -4.
    # Null in any operand nulls the product (SQL three-valued arithmetic).
    dp_valid = price.valid_mask() & disc.valid_mask()
    disc_price = Column(
        t.decimal64(-4), price.data * (100 - disc.data), dp_valid
    )
    # charge = disc_price * (1 + tax): scale -6
    charge = Column(
        t.decimal64(-6), disc_price.data * (100 + tax.data),
        dp_valid & tax.valid_mask(),
    )

    # Masked rows must not create key groups: zero out key bytes for them.
    def masked_key(c: Column) -> Column:
        if c.dtype.is_string:
            from spark_rapids_jni_tpu.ops.strings import pad_strings

            p = pad_strings(c)
            return Column(
                p.dtype,
                jnp.where(keep, p.data, 0),
                keep,
                chars=jnp.where(keep[:, None], p.chars, jnp.uint8(0)),
            )
        return Column(c.dtype, jnp.where(keep, c.data, 0), keep)

    return Table(
        [
            masked_key(lineitem.column(L_RETURNFLAG)),
            masked_key(lineitem.column(L_LINESTATUS)),
            qty,
            price,
            disc,
            disc_price,
            charge,
        ]
    )


def _q1_plan(max_groups=_Q1_GROUP_BUDGET) -> fusion.Plan:
    """q1 as ONE fusible region: filter+derive -> groupby -> sort. The
    filtered-out pseudo-group has null keys; q1's ORDER BY puts real
    groups first (nulls last) so the compacted head is the answer.
    ``max_groups`` is the bound the planner states for the groupby (q1's
    own 64 by default): a bound over ``ops/groupby.py``'s ``_SMALL_M``
    (1,024), or None for no bound at all, is what a planner that could not
    bound the cardinality sends, and takes the groupby that sorts its rows
    and moves every value word into key order (``groupby.in_place`` 0)."""
    return fusion.Plan("tpch_q1", fusion.Sort(
        fusion.GroupBy(
            fusion.Project(fusion.Scan("lineitem"), _q1_work_table),
            (0, 1), tuple(_Q1_AGGS), max_groups=max_groups,
            label="groupby"),
        (0, 1), nulls_first=(False, False)))


@func_range("tpch_q1")
def tpch_q1(lineitem: Table) -> Table:
    """Single-executor q1: filter -> derived columns -> groupby -> sort,
    compiled as one fused executable (runtime/fusion.py).

    The group budget is part of the query plan, the way Spark's planner
    carries a cardinality estimate: q1 groups by two CHAR(1) flags, <= 7
    groups including the null-key pseudo-group, so 64 is a 9x margin. On
    data outside that contract (>=64 distinct byte pairs) the excess
    groups are dropped — jitted code cannot raise on a device predicate;
    use ``tpch_q1_checked`` from host code to turn overflow into an error.
    """
    return fusion.execute(_q1_plan(), {"lineitem": lineitem}).table


# TPC-H DDL domains for the q1 flags (the spec fixes returnflag to
# 'A'/'N'/'R' and linestatus to 'F'/'O'); a real planner gets the same
# facts from dictionary/column statistics.
_Q1_RF_DOMAIN = (ord("A"), ord("N"), ord("R"))
_Q1_LS_DOMAIN = (ord("F"), ord("O"))


def _q1_planned_plan() -> fusion.Plan:
    """q1 with planner-declared flag domains as ONE fusible region:
    filter+derive -> bounded-domain groupby (no sort, static order)."""
    from spark_rapids_jni_tpu.ops.planner import scalar_domain

    return fusion.Plan("tpch_q1_planned", fusion.GroupBy(
        fusion.Project(fusion.Scan("lineitem"), _q1_work_table),
        (0, 1), tuple(_Q1_AGGS),
        domains=(scalar_domain(_Q1_RF_DOMAIN),
                 scalar_domain(_Q1_LS_DOMAIN)),
        label="plan"))


@func_range("tpch_q1_planned_result")
def tpch_q1_planned_result(lineitem: Table):
    """q1 with PLANNER-DECLARED key domains: the flag domains come from
    the TPC-H DDL (CHAR(1) check constraints / dictionary stats), so
    grouping needs no sort, no gather, no scan — one streaming masked-
    reduction pass (groupby_aggregate_bounded), and the output order is
    static (real groups lexicographic, null groups last), so the final
    ORDER BY costs nothing. Returns the planner result so jitted callers
    can observe ``domain_miss``; the single shared call path for the
    checked and unchecked wrappers below. Lowered through the general
    planner facility (ops/planner.plan_groupby) — q1 is just the first
    client of the declared-domain plan, not a special case."""
    from spark_rapids_jni_tpu.ops.planner import PlannedGroupBy

    out = fusion.execute(_q1_planned_plan(), {"lineitem": lineitem})
    res = PlannedGroupBy(out.table, out.meta["plan.present"],
                         out.meta["plan.domain_miss"],
                         out.meta["plan.lowered"],
                         out.meta["plan.overflowed"])
    assert res.lowered == "bounded"  # static plan fact, not a data check
    return res


def tpch_q1_planned(lineitem: Table) -> Table:
    """Planned q1, table only — same output schema as ``tpch_q1``.
    Out-of-domain key bytes fold into the null-key group WITHOUT signal
    here (jitted code cannot raise); callers that must detect that use
    ``tpch_q1_planned_result().domain_miss`` or the checked wrapper."""
    return tpch_q1_planned_result(lineitem).table


def tpch_q1_planned_checked(lineitem: Table) -> Table:
    """Host wrapper for the planned q1: domain misses re-plan onto the
    general sort-based pipeline instead of dropping rows."""
    res = tpch_q1_planned_result(lineitem)
    if bool(res.domain_miss):
        return tpch_q1_checked(lineitem)
    return res.table


def tpch_q1_checked(lineitem: Table) -> Table:
    """Host-side q1 wrapper that enforces the plan's group-budget contract
    (raises instead of silently dropping groups on out-of-contract data)."""
    res = fusion.execute(_q1_plan(), {"lineitem": lineitem})
    if bool(res.meta["groupby.overflowed"]):
        raise ValueError(
            f"q1 key domain exceeded the plan's group budget "
            f"({int(res.meta['groupby.num_groups'])} > {_Q1_GROUP_BUDGET}): "
            "the returnflag/linestatus bytes are outside the TPC-H contract"
        )
    return res.table


# TPC-H q6 predicate constants: shipdate in [1994-01-01, 1995-01-01) as
# days since epoch (8766 = 24*365 + 6 leap days; 9131 = 8766 + 365),
# discount in [0.05, 0.07] at scale -2, quantity < 24 at scale -2.
_Q6_DATE_LO = 8766
_Q6_DATE_HI = 9131
_Q6_DISC_LO = 5
_Q6_DISC_HI = 7
_Q6_QTY_HI = 2400


def _q6_where(lineitem: Table) -> jnp.ndarray:
    """q6's WHERE as a fusion Filter's predicate: the four columns it
    reads non-null, shipdate in the year, discount and quantity in their
    bounds. Region-padded phantom rows have null validity everywhere, so
    it is False on them."""
    qty = lineitem.column(L_QUANTITY)
    price = lineitem.column(L_EXTENDEDPRICE)
    disc = lineitem.column(L_DISCOUNT)
    ship = lineitem.column(L_SHIPDATE)
    return (
        qty.valid_mask() & price.valid_mask() & disc.valid_mask()
        & ship.valid_mask()
        & (ship.data >= _Q6_DATE_LO) & (ship.data < _Q6_DATE_HI)
        & (disc.data >= _Q6_DISC_LO) & (disc.data <= _Q6_DISC_HI)
        & (qty.data < _Q6_QTY_HI)
    )


def _q6_reduce(lineitem: Table, row_valid) -> Table:
    """q6's masked multiply-accumulate as a fusion Project (rowwise=False
    — the 1-row output is its own space) over the rows ``_q6_where`` kept:
    the Filter above nulled every other row in every column, padding
    included, so ``row_valid`` needs no explicit fold."""
    price = lineitem.column(L_EXTENDEDPRICE)
    disc = lineitem.column(L_DISCOUNT)
    sel = price.valid_mask() & disc.valid_mask()
    prod = jnp.where(sel, price.data * disc.data, jnp.int64(0))
    total = jnp.sum(prod).reshape(1)
    any_row = jnp.any(sel).reshape(1)
    return Table([Column(t.decimal64(-4), total, any_row)])


def _q6_plan() -> fusion.Plan:
    """q6 as one fused region of two nodes: the WHERE (a ``Filter``, which
    reports the rows it saw and kept) and the multiply-accumulate over the
    rows it kept. One pass over the four columns: XLA fuses the predicate
    into the reduction."""
    return fusion.Plan("tpch_q6", fusion.Project(
        fusion.Filter(fusion.Scan("lineitem"), _q6_where, label="where"),
        _q6_reduce, rowwise=False))


@func_range("tpch_q6")
def tpch_q6(lineitem: Table) -> Column:
    """TPC-H q6: SELECT sum(l_extendedprice * l_discount) WHERE shipdate
    in a year AND discount BETWEEN 0.05 AND 0.07 AND quantity < 24.

    The pure streaming query: no groupby, no sort, no join — ONE masked
    multiply-accumulate over three predicate columns, the shape that
    exposes raw HBM bandwidth (the cuDF/libcudf capability family's
    filter+reduce fast path, SURVEY.md section 2.2). The product of two
    scale -2 decimals is scale -4; the int64 accumulator is exact up to
    ~9e18, i.e. ~8.7e10 matched rows at TPC-H value ranges — far beyond
    any single-chip batch, so no 128-bit lanes are needed (unlike the
    general DECIMAL128 SUM path, which this plan deliberately avoids).
    As a one-node fused region the whole scan+reduce is a single bucketed
    executable instead of a chain of eager XLA calls.

    Returns a 1-row DECIMAL64(scale -4) column (null iff no row matched).
    """
    return fusion.execute(
        _q6_plan(), {"lineitem": lineitem}).table.column(0)


def tpch_q6_numpy(lineitem: Table) -> int:
    """Host oracle for q6 (exact int arithmetic, scale -4 result)."""
    qty = np.asarray(lineitem.column(L_QUANTITY).data)
    price = np.asarray(lineitem.column(L_EXTENDEDPRICE).data)
    disc = np.asarray(lineitem.column(L_DISCOUNT).data)
    ship = np.asarray(lineitem.column(L_SHIPDATE).data)
    valid = np.ones(lineitem.num_rows, dtype=bool)
    for c in (L_QUANTITY, L_EXTENDEDPRICE, L_DISCOUNT, L_SHIPDATE):
        valid &= np.asarray(lineitem.column(c).valid_mask())
    sel = (valid & (ship >= _Q6_DATE_LO) & (ship < _Q6_DATE_HI)
           & (disc >= _Q6_DISC_LO) & (disc <= _Q6_DISC_HI)
           & (qty < _Q6_QTY_HI))
    return int((price[sel].astype(object) * disc[sel].astype(object)).sum())


def tpch_q1_numpy(lineitem: Table) -> dict:
    """Host oracle: same query in numpy, keyed by (returnflag, linestatus)."""
    qty = np.asarray(lineitem.column(L_QUANTITY).data)
    price = np.asarray(lineitem.column(L_EXTENDEDPRICE).data)
    disc = np.asarray(lineitem.column(L_DISCOUNT).data)
    tax = np.asarray(lineitem.column(L_TAX).data)
    rf = np.asarray(lineitem.column(L_RETURNFLAG).data)
    ls = np.asarray(lineitem.column(L_LINESTATUS).data)
    ship = np.asarray(lineitem.column(L_SHIPDATE).data)
    keep = ship <= _Q1_CUTOFF_DAYS
    out = {}
    for f in np.unique(rf[keep]):
        for s in np.unique(ls[keep]):
            m = keep & (rf == f) & (ls == s)
            if not m.any():
                continue
            dp = price[m] * (100 - disc[m])
            out[(int(f), int(s))] = {
                "sum_qty": int(qty[m].sum()),
                "sum_base_price": int(price[m].sum()),
                "sum_disc_price": int(dp.sum()),
                "sum_charge": int((dp * (100 + tax[m])).sum()),
                # true values: unscaled decimal(scale -2) means x 10^-2
                "avg_qty": qty[m].mean() * 1e-2,
                "avg_price": price[m].mean() * 1e-2,
                "avg_disc": disc[m].mean() * 1e-2,
                "count": int(m.sum()),
            }
    return out


# ---- distributed q1 over the executor mesh --------------------------------

# Partial (per-executor) aggregates: SUMs and COUNTs only, because those
# merge associatively across the shuffle; AVGs are finalized from the merged
# sums/counts. Indices refer to the work-table layout in _q1_work_table.
_Q1_PARTIAL_AGGS = [
    (2, "sum"),    # sum_qty
    (3, "sum"),    # sum_base_price
    (5, "sum"),    # sum_disc_price
    (6, "sum"),    # sum_charge
    (2, "count"),  # count_qty (also count_order)
    (3, "count"),  # count_price
    (4, "sum"),    # sum_disc
    (4, "count"),  # count_disc
]



def _q1_finalize(merged: Table) -> Table:
    """Merged sums/counts -> the q1 output schema (avgs = sum/count)."""
    rf, ls, sq, sp, sdp, sch, cq, cp, sd, cd = merged.columns

    def avg(total: Column, count: Column) -> Column:
        denom = jnp.maximum(count.data, 1).astype(jnp.float64)
        # 10^scale rescale so the FLOAT64 avg carries the true value, same
        # contract as groupby_aggregate's decimal mean.
        return Column(
            t.FLOAT64,
            total.data.astype(jnp.float64) / denom * (10.0 ** total.dtype.scale),
            count.valid_mask() & (count.data > 0),
        )

    return Table(
        [rf, ls, sq, sp, sdp, sch, avg(sq, cq), avg(sp, cp), avg(sd, cd), cq]
    )


# Merge-side aggregates over the partial layout: every partial lane sums
# associatively across the shuffle / chunk axis.
_Q1_MERGE_AGGS = tuple((i, "sum") for i in range(2, 10))


def _q1_partial_plan() -> fusion.Plan:
    """Per-chunk / per-executor q1 partial: work-table projection + the
    budget-bounded partial groupby, fused. ``min_rows_of`` reproduces the
    staged ``min(_Q1_GROUP_BUDGET, work.num_rows)`` budget from the TRUE
    chunk row count (never the bucket)."""
    return fusion.Plan("tpch_q1_partial", fusion.GroupBy(
        fusion.Project(fusion.Scan("chunk"), _q1_work_table),
        (0, 1), tuple(_Q1_PARTIAL_AGGS),
        max_groups=fusion.min_rows_of("chunk", _Q1_GROUP_BUDGET),
        label="partial"))


def _q1_merge_plan() -> fusion.Plan:
    """Merge the stacked partials: sum-merge groupby -> finalize
    (avgs = sum/count) -> output order, fused."""
    return fusion.Plan("tpch_q1_merge", fusion.Sort(
        fusion.Project(
            fusion.GroupBy(fusion.Scan("partials"), (0, 1), _Q1_MERGE_AGGS,
                           label="merge"),
            _q1_finalize),
        (0, 1), nulls_first=(False, False)))


def q1_row_chunked_fns():
    """The (partial_fn, merge_fn) pair for running q1 over IN-MEMORY row
    chunks of a lineitem table — the algebra ``run_chunked_aggregate``
    (and the degradation ladder's out-of-core rung, runtime/degrade.py)
    consumes. Same plans as :func:`tpch_q1_outofcore`, minus the Parquet
    retype: ``lineitem_table`` chunks already carry the decimal dtypes.
    """
    from spark_rapids_jni_tpu.ops.table_ops import trim_table

    def partial_fn(chunk: Table) -> Table:
        res = fusion.execute(_q1_partial_plan(), {"chunk": chunk},
                             donate_inputs=True)
        if bool(res.meta["partial.overflowed"]):
            raise ValueError(
                "q1 chunk exceeded the plan's group budget "
                f"({_Q1_GROUP_BUDGET}): flag bytes outside the contract")
        return trim_table(res.table, int(res.meta["partial.num_groups"]))

    def merge_fn(partials: Table) -> Table:
        # NOT donated: the SpillStore may still hold the partials buffer
        return fusion.execute(_q1_merge_plan(), {"partials": partials}).table

    return partial_fn, merge_fn


def _q1_distributed_plan() -> fusion.Plan:
    """q1 as a cluster runs it, as ONE plan a client submits: the
    work-table projection, a groupby of the aggregates that merge across a
    shuffle (sums and counts: ``_q1_partial_plan``'s), the finalize that
    makes the averages of them (``_q1_merge_plan``'s) and the ORDER BY.
    Bound to a table whose rows are sharded over a mesh axis
    (``parallel/mesh.py``), ``fusion.execute`` lowers the groupby as a
    partial a chip, ``hash_shuffle``, merge and collect; bound to a table
    on one chip it is one more general q1. Same result layout as
    ``_q1_plan`` (two keys, eight aggregates, the group budget's rows)."""
    return fusion.Plan("tpch_q1_distributed", fusion.Sort(
        fusion.Project(
            fusion.GroupBy(
                fusion.Project(fusion.Scan("lineitem"), _q1_work_table),
                (0, 1), tuple(_Q1_PARTIAL_AGGS),
                max_groups=_Q1_GROUP_BUDGET, label="groupby"),
            _q1_finalize),
        (0, 1), nulls_first=(False, False)))


def q1_distributed_step(local: Table) -> Table:
    """One executor's part of the distributed q1, for a caller already
    inside ``shard_map`` over ``EXEC_AXIS`` (a mesh that spans processes):
    ``_q1_distributed_plan`` lowered over the axis. Every executor returns
    the whole sorted answer."""
    from spark_rapids_jni_tpu.parallel.mesh import EXEC_AXIS

    return fusion.mesh_step(
        _q1_distributed_plan(), {"lineitem": local}, EXEC_AXIS).table


def tpch_q1_distributed(lineitem: Table, mesh) -> Table:
    """Multi-executor q1: ``_q1_distributed_plan`` bound to ``lineitem``
    with its rows sharded over ``mesh`` (sharded here unless it already
    is). The served path takes the same plan and bindings."""
    from spark_rapids_jni_tpu.parallel.distributed import shard_table
    from spark_rapids_jni_tpu.parallel.mesh import table_row_mesh

    if table_row_mesh(lineitem) is None:
        lineitem = shard_table(lineitem, mesh)
    return fusion.execute(
        _q1_distributed_plan(), {"lineitem": lineitem}).table


def tpch_q1_outofcore(path, *, budget_bytes: int,
                      chunk_read_limit: int,
                      spill_budget_bytes: int | None = None,
                      compress_spill: bool = False,
                      prefetch_depth: int = 0,
                      pipeline: bool | None = None):
    """q1 over a Parquet file LARGER than the device budget: chunked
    row-group reads -> per-chunk partial aggregates -> SpillStore'd
    partials -> merge -> finalize. The partial->merge algebra is the
    distributed q1's (q1_distributed_step), run over the chunk sequence
    instead of the device mesh — same plan, different axis.

    File schema: the 7 q1 lineitem columns with the 4 money columns as
    unscaled int64 (the bench parquet_q1 layout); they are re-typed to
    DECIMAL64(-2) on read. Returns OutOfCoreResult; ``.table`` matches
    ``tpch_q1`` of the fully-materialized file.

    ``budget_bytes`` must cover one chunk (plus the merge window) when
    ``prefetch_depth == 0``; with prefetch, ``prefetch_depth + 2``
    chunks are resident at once (the read/compute overlap window) and
    the budget must cover them. ``pipeline`` selects the async
    multi-stage executor (None follows ``pipeline.enabled``): host
    decode overlaps device compute through the reader's chunk thunks,
    exact-bytes admission blocks instead of raising, and results stay
    bit-identical to the serial path.

    Both device halves are fused regions (the q1 partial / merge plans
    shared with the distributed step); the host-side ``trim_table``
    compaction between them is the genuine region boundary. Chunk tables
    are DEAD after their partial (nothing else reads them), so the
    partial region donates them back to XLA.
    """
    from spark_rapids_jni_tpu.parquet.reader import ParquetChunkedReader
    from spark_rapids_jni_tpu.runtime.memory import MemoryLimiter, SpillStore
    from spark_rapids_jni_tpu.runtime.outofcore import run_chunked_aggregate

    money = t.decimal64(-2)
    limiter = MemoryLimiter(budget_bytes)
    spill = SpillStore(
        spill_budget_bytes if spill_budget_bytes is not None
        else budget_bytes, compress_spill=compress_spill)

    def _retype(chunk: Table) -> Table:
        cols = list(chunk.columns)
        for i in range(4):
            cols[i] = Column(money, cols[i].data, cols[i].validity)
        return Table(cols)

    def partial_fn(chunk: Table) -> Table:
        from spark_rapids_jni_tpu.ops.table_ops import trim_table

        res = fusion.execute(_q1_partial_plan(),
                             {"chunk": _retype(chunk)},
                             donate_inputs=True)
        if bool(res.meta["partial.overflowed"]):
            raise ValueError(
                "q1 chunk exceeded the plan's group budget "
                f"({_Q1_GROUP_BUDGET}): flag bytes outside the contract")
        # host-side compaction between fused regions: only real groups
        # cross into the merge (chunk boundaries are where dynamic
        # shapes cost nothing — the q1_distributed_step row_valid idea)
        return trim_table(res.table, int(res.meta["partial.num_groups"]))

    def merge_fn(partials: Table) -> Table:
        # NOT donated: the SpillStore may still hold the partials buffer
        return fusion.execute(_q1_merge_plan(), {"partials": partials}).table

    reader = ParquetChunkedReader(path, chunk_read_limit=chunk_read_limit)
    # the reader (not iter(reader)) so the pipelined executor can pick up
    # its per-chunk decode thunks; the serial path just iterates it
    return run_chunked_aggregate(
        reader, partial_fn, merge_fn, limiter=limiter, spill=spill,
        prefetch_depth=prefetch_depth, pipeline=pipeline)


# ---- TPC-H q3 (shipping priority): join + groupby + order-by ---------------
#
#   SELECT l_orderkey, sum(l_extendedprice*(1-l_discount)) AS revenue,
#          o_orderdate, o_shippriority
#   FROM customer, orders, lineitem
#   WHERE c_mktsegment = :seg AND c_custkey = o_custkey
#     AND l_orderkey = o_orderkey
#     AND o_orderdate < :cutoff AND l_shipdate > :cutoff
#   GROUP BY l_orderkey, o_orderdate, o_shippriority
#   ORDER BY revenue DESC, o_orderdate LIMIT 10

_Q3_CUTOFF_DAYS = 9204  # 1995-03-15
N_SEGMENTS = 5          # TPC-H market segments

# orders columns
O_ORDERKEY, O_CUSTKEY, O_ORDERDATE, O_SHIPPRIORITY = 0, 1, 2, 3
# customer columns
C_CUSTKEY, C_MKTSEGMENT = 0, 1
# q3 lineitem columns
L3_ORDERKEY, L3_EXTENDEDPRICE, L3_DISCOUNT, L3_SHIPDATE = 0, 1, 2, 3


def customer_table(num_rows: int, seed: int = 0) -> Table:
    rng = np.random.default_rng(seed)
    return Table([
        Column.from_numpy(np.arange(1, num_rows + 1, dtype=np.int64)),
        Column.from_numpy(
            rng.integers(0, N_SEGMENTS, num_rows).astype(np.int8), t.INT8
        ),
    ])


def orders_table(num_rows: int, num_customers: int, seed: int = 1) -> Table:
    rng = np.random.default_rng(seed)
    return Table([
        Column.from_numpy(np.arange(1, num_rows + 1, dtype=np.int64)),
        Column.from_numpy(
            rng.integers(1, num_customers + 1, num_rows).astype(np.int64)
        ),
        Column.from_numpy(
            rng.integers(8400, 10957, num_rows).astype(np.int32),
            t.TIMESTAMP_DAYS,
        ),
        Column.from_numpy(rng.integers(0, 2, num_rows).astype(np.int32)),
    ])


def lineitem_q3_table(num_rows: int, num_orders: int, seed: int = 2) -> Table:
    rng = np.random.default_rng(seed)
    return Table([
        Column.from_numpy(
            rng.integers(1, num_orders + 1, num_rows).astype(np.int64)
        ),
        Column.from_numpy(
            rng.integers(90_000, 10_500_000, num_rows).astype(np.int64),
            t.decimal64(-2),
        ),
        Column.from_numpy(
            rng.integers(0, 11, num_rows).astype(np.int64), t.decimal64(-2)
        ),
        Column.from_numpy(
            rng.integers(8400, 10957, num_rows).astype(np.int32),
            t.TIMESTAMP_DAYS,
        ),
    ])


def _null_where(c: Column, drop: jnp.ndarray) -> Column:
    return Column(c.dtype, c.data, c.valid_mask() & ~drop,
                  chars=c.chars, children=c.children)


def _q3_cust_fn(customer: Table, segment: int) -> Table:
    """Segment-filtered customer keys (q3 plan Project node)."""
    return Table([_null_where(
        customer.column(C_CUSTKEY),
        customer.column(C_MKTSEGMENT).data != jnp.int8(segment),
    )])


def _q3_orders_fn(orders: Table, cutoff: int) -> Table:
    """Date-filtered orders with custkey join lane (q3 plan Project)."""
    okey = _null_where(
        orders.column(O_CUSTKEY),
        orders.column(O_ORDERDATE).data >= jnp.int32(cutoff),
    )
    return Table([okey, orders.column(O_ORDERKEY),
                  orders.column(O_ORDERDATE),
                  orders.column(O_SHIPPRIORITY)])


def _q3_probe_fn(lineitem: Table, cutoff: int) -> Table:
    """Shipdate-filtered lineitem probe with its revenue lane."""
    lkey = _null_where(
        lineitem.column(L3_ORDERKEY),
        lineitem.column(L3_SHIPDATE).data <= jnp.int32(cutoff),
    )
    price = lineitem.column(L3_EXTENDEDPRICE)
    disc = lineitem.column(L3_DISCOUNT)
    revenue = Column(
        t.decimal64(-4), price.data * (100 - disc.data),
        price.valid_mask() & disc.valid_mask(),
    )
    return Table([lkey, revenue])


def _q3_inputs(customer: Table, orders: Table, lineitem: Table,
               segment: int, cutoff: int):
    """Shared q3 filtered inputs for BOTH plans (single change point for
    predicates/scales): segment-filtered customer keys, date-filtered
    orders, and the shipdate-filtered lineitem probe with its revenue
    lane. Returns (cust, ord_t, probe). The per-table pieces are the
    module-level fns above so the fusion plans can reference them as
    Project nodes."""
    return (_q3_cust_fn(customer, segment),
            _q3_orders_fn(orders, cutoff),
            _q3_probe_fn(lineitem, cutoff))


def _q3_build_fn(oc: Table) -> Table:
    """orders x customer join output -> second-join build side:
    [orderkey (nulled where unmatched), orderdate, shippriority]."""
    # oc: [o_custkey, o_orderkey, o_orderdate, o_shippriority, c_custkey]
    matched = oc.column(4).valid_mask()
    oc_key = _null_where(oc.column(1), ~matched)
    return Table([oc_key, oc.column(2), oc.column(3)])


def _q3_keyed_fn(j: Table) -> Table:
    """lineitem x orders join output -> groupby-keyed table
    [l_orderkey, o_orderdate, o_shippriority, revenue], unmatched rows
    nulled in every lane."""
    # j: [l_orderkey, revenue, o_orderkey, o_orderdate, o_shippriority]
    matched = j.column(2).valid_mask()
    return Table([
        _null_where(j.column(0), ~matched),
        _null_where(j.column(3), ~matched),
        _null_where(j.column(4), ~matched),
        Column(j.column(1).dtype, j.column(1).data,
               j.column(1).valid_mask() & matched),
    ])


def _q3_plan(segment: int, cutoff: int, out_factor: int) -> fusion.Plan:
    """Single-executor q3 as ONE fused region: filter all three inputs,
    orders x customer join, lineitem x orders join, groupby, order-by —
    nine nodes, one executable (the staged path compiled five)."""
    cust = fusion.Project(fusion.Scan("customer"), _q3_cust_fn, (segment,))
    ord_n = fusion.Project(fusion.Scan("orders"), _q3_orders_fn, (cutoff,))
    probe = fusion.Project(fusion.Scan("lineitem"), _q3_probe_fn, (cutoff,))
    j1 = fusion.Join(ord_n, cust, (0,), (0,), fusion.rows_of("orders"),
                     label="join1")
    build = fusion.Project(j1, _q3_build_fn)
    j2 = fusion.Join(probe, build, (0,), (0,),
                     fusion.rows_of("lineitem", out_factor), label="join2")
    g = fusion.GroupBy(fusion.Project(j2, _q3_keyed_fn), (0, 1, 2),
                       ((3, "sum"),), label="groupby")
    return fusion.Plan("tpch_q3", fusion.Sort(
        g, (3, 1), ascending=(False, True), nulls_first=(False, False)))


class Q3Result(NamedTuple):
    result: GroupByResult  # [l_orderkey, o_orderdate, o_shippriority, rev]
    join_total: jnp.ndarray  # true lineitem-x-orders match count
    out_cap: int             # static join output bound (check total <= cap)


@func_range("tpch_q3")
def tpch_q3(customer: Table, orders: Table, lineitem: Table,
            segment: int = 0, cutoff: int = _Q3_CUTOFF_DAYS,
            out_factor: int = 2) -> Q3Result:
    """Single-executor q3. Grouped rows
    [l_orderkey, o_orderdate, o_shippriority, revenue] padded; callers
    compact + head for the LIMIT, and check ``join_total <= out_cap`` on
    host (join_auto pattern) — exceeding it means matches were dropped."""
    res = fusion.execute(
        _q3_plan(segment, cutoff, out_factor),
        {"customer": customer, "orders": orders, "lineitem": lineitem})
    return Q3Result(
        GroupByResult(res.table, res.meta["groupby.num_groups"]),
        res.meta["join2.total"], lineitem.num_rows * out_factor)


class Q3PlannedResult(NamedTuple):
    result: GroupByResult  # [l_orderkey, o_orderdate, o_shippriority, rev]
    join_total: jnp.ndarray
    # planner-contract check: any dense-PK declaration violated (caller
    # re-plans on tpch_q3 — the domain_miss posture)
    pk_violation: jnp.ndarray


def _q3_build2_fn(j1t: Table) -> Table:
    """orders-x-customer dense-PK output -> second-lookup build side.
    dense_pk_join folds its matched mask into the gathered build column's
    validity, so column 4's validity IS ``matched1``."""
    # j1t: [o_custkey, o_orderkey, o_orderdate, o_shippriority, c_custkey]
    matched1 = j1t.column(4).valid_mask()
    return Table([
        _null_where(j1t.column(1), ~matched1),  # orderkey
        j1t.column(2),                          # orderdate
        j1t.column(3),                          # shippriority
    ])


def _q3_planned_keyed_fn(jt: Table) -> Table:
    """Dense-PK lineitem x orders output -> groupby-keyed table. Build
    columns already carry the matched mask from the gather."""
    # jt: [l_orderkey, revenue, o_orderkey, o_orderdate, o_shippriority]
    matched = jt.column(2).valid_mask()
    return Table([
        _null_where(jt.column(0), ~matched),
        jt.column(3),
        jt.column(4),
        Column(jt.column(1).dtype, jt.column(1).data,
               jt.column(1).valid_mask() & matched),
    ])


def _q3_planned_revenue_fn(jt: Table) -> Table:
    """The keyed table's key and revenue alone. Inside a region the
    join's gathers of o_orderdate and o_shippriority by the lineitem rows
    then have no user, and XLA compiles none of them."""
    keyed = _q3_planned_keyed_fn(jt)
    return Table([keyed.column(0), keyed.column(3)])


def _q3_planned_result_fn(lt: Table) -> Table:
    """The late look-up's output in q3's column order."""
    # lt: [l_orderkey, revenue, o_orderkey, o_orderdate, o_shippriority]
    return Table([lt.column(0), lt.column(3), lt.column(4), lt.column(1)])


def _q3_planned_plan(segment: int, cutoff: int) -> fusion.Plan:
    """q3 with planner-declared dense clustered PKs, as one fused region.
    The clustered build sides (customer, the orders-aligned lookup table)
    ride UNBUCKETED scans: dense_pk_join's clustered layout declares
    ``build rows == key_hi - key_lo + 1``, which padding would break."""
    cust = fusion.Project(fusion.Scan("customer", bucket=False),
                          _q3_cust_fn, (segment,))
    ord_n = fusion.Project(fusion.Scan("orders", bucket=False),
                           _q3_orders_fn, (cutoff,))
    probe = fusion.Project(fusion.Scan("lineitem"), _q3_probe_fn, (cutoff,))
    # join 1: each ORDER row looks up its customer (clustered custkey);
    # ord_n rows are orders rows in load order, custkey domain 1..|C|
    j1 = fusion.DensePkJoin(ord_n, cust, 0, 0, 1,
                            fusion.rows_of("customer"), clustered=True,
                            label="pk1")
    build2 = fusion.Project(j1, _q3_build2_fn)
    # join 2: each LINEITEM row looks up its order (clustered orderkey,
    # build2 rows still in orders load order = orderkey order) and needs
    # one bit of it: did the order survive the date and segment filters
    # (the Project over it drops the order's date and priority, so the
    # region gathers neither by the lineitem rows)
    j2 = fusion.DensePkJoin(probe, build2, 0, 0, 1,
                            fusion.rows_of("orders"), clustered=True,
                            label="pk2")
    # the dense keys declared above make two more facts the planner's:
    # o_orderdate and o_shippriority are functions of l_orderkey (the
    # order's primary key), so the groupby keys on it alone (one 64-bit
    # sort key and not three keys) and the two are wanted once a group,
    # not once a lineitem row: ``late`` fetches them below;
    # and there are at most |orders| groups and the null group of the
    # unmatched rows, so the groupby's look-ups, its results and the
    # result's sort run over that many rows, not over the lineitem bucket;
    # and the key is no 64-bit number: pk2 matched a row only where its
    # l_orderkey lies in the dense key's [1, |orders|] (``in_range`` is
    # part of ``matched``) and ``_q3_planned_keyed_fn`` nulls the key of
    # every other row, so every non-null key has 21 bits at SF1 and sorts
    # as one word, in one sort with its null rank and the row-valid bit
    g = fusion.GroupBy(fusion.Project(j2, _q3_planned_revenue_fn), (0,),
                       ((1, "sum"),),
                       max_groups=fusion.groups_of("orders"),
                       label="groupby",
                       key_ranges=((1, fusion.rows_of("orders")),))
    # the group's key looks its order up once more, against the same
    # build2 (still clustered by that very key): date and priority at
    # |orders| + 1 rows. The null group's key is out of range, so its two
    # are null, as the first row of the unmatched rows had them
    late = fusion.DensePkJoin(g, build2, 0, 0, 1, fusion.rows_of("orders"),
                              clustered=True, label="late")
    return fusion.Plan("tpch_q3_planned", fusion.Sort(
        fusion.Project(late, _q3_planned_result_fn), (3, 1),
        ascending=(False, True), nulls_first=(False, False)))


@func_range("tpch_q3_planned")
def tpch_q3_planned(customer: Table, orders: Table, lineitem: Table,
                    segment: int = 0,
                    cutoff: int = _Q3_CUTOFF_DAYS) -> Q3PlannedResult:
    """q3 with PLANNER-DECLARED dense clustered PKs: custkey = 1..|C|
    clustered in customer, orderkey = 1..|O| clustered in orders (the
    TPC-H DDL + load-order facts). Both joins collapse to arithmetic +
    gather — the join phase compiles with ZERO sorts (HLO-pinned in
    tests), where the general q3 pays two build-side lexsorts + probe
    searchsorteds on the sort-based machinery. On a v5e at SF1 (PERF.md
    section 5, traced runs of PR 47) the joins take 0.15 s of a 0.355 s
    request: pk2's one gather of the match bit by 6,001,215 positions
    0.068 s (0.40 s while it also gathered the order's key, date and
    priority there), the look-up ``late`` that fetches date and priority
    at the 1,500,001 group rows 0.068 s, pk1 0.012 s; the result's sort
    0.014 s (0.34 s while it ordered all 1,500,001 group rows: it orders
    the 131,072 that hold the 88,500 groups, ``fusion.Sort``); the
    groupby 0.16 s: its look-ups at 1,500,001 rows 0.07 s, the
    key and the revenue brought into key order as four packed words
    0.06 s, its key sort 0.02 s (0.27 s while the key was sorted as the
    64-bit number its type says: three passes that each gathered a
    word). The
    orderkey groupby stays on the general (sort-based) path: its
    cardinality is data-dependent, which is exactly the boundary of
    what a planner can declare; what the declared keys do give it is
    one key to sort on, a bound on its groups and the key's range
    (``_q3_planned_plan``).

    Output rows are one per LINEITEM row (PK fanout <= 1): no join
    capacity estimate, no overflow retry — the static shape is the
    probe's.
    """
    res = fusion.execute(
        _q3_planned_plan(segment, cutoff),
        {"customer": customer, "orders": orders, "lineitem": lineitem})
    return Q3PlannedResult(
        GroupByResult(res.table, res.meta["groupby.num_groups"]),
        res.meta["pk2.total"],
        res.meta["pk1.pk_violation"] | res.meta["pk2.pk_violation"]
        | res.meta["late.pk_violation"])


def tpch_q3_numpy(customer: Table, orders: Table, lineitem: Table,
                  segment: int = 0, cutoff: int = _Q3_CUTOFF_DAYS) -> dict:
    """Host oracle: {orderkey: (revenue, orderdate, shippriority)}."""
    seg = np.asarray(customer.column(C_MKTSEGMENT).data)
    ckey = np.asarray(customer.column(C_CUSTKEY).data)
    good_cust = set(ckey[seg == segment].tolist())
    okey = np.asarray(orders.column(O_ORDERKEY).data)
    ocust = np.asarray(orders.column(O_CUSTKEY).data)
    odate = np.asarray(orders.column(O_ORDERDATE).data)
    oprio = np.asarray(orders.column(O_SHIPPRIORITY).data)
    good_orders = {}
    for k, c, d, p in zip(okey, ocust, odate, oprio):
        if d < cutoff and int(c) in good_cust:
            good_orders[int(k)] = (int(d), int(p))
    lkey = np.asarray(lineitem.column(L3_ORDERKEY).data)
    price = np.asarray(lineitem.column(L3_EXTENDEDPRICE).data)
    disc = np.asarray(lineitem.column(L3_DISCOUNT).data)
    ldate = np.asarray(lineitem.column(L3_SHIPDATE).data)
    out = {}
    for k, p, dc, d in zip(lkey, price, disc, ldate):
        k = int(k)
        if d > cutoff and k in good_orders:
            rev = int(p) * (100 - int(dc))
            date, prio = good_orders[k]
            if k in out:
                out[k] = (out[k][0] + rev, date, prio)
            else:
                out[k] = (rev, date, prio)
    return out


def _q3_group_plan() -> fusion.Plan:
    """Per-device q3 group step (exchange-2 output -> keyed groupby)."""
    return fusion.Plan("tpch_q3_group", fusion.GroupBy(
        fusion.Project(fusion.Scan("joined"), _q3_keyed_fn), (0, 1, 2),
        ((3, "sum"),), label="groupby"))


def _q3_group_step(j: Table):
    """Shard-local tail of the distributed q3 (runs inside shard_map, so
    fusion.execute takes its staged walk on the tracer input — the plan
    still pins the node structure shared with the fused single-chip q3)."""
    res = fusion.execute(_q3_group_plan(), {"joined": j})
    return res.table, res.meta["groupby.num_groups"].reshape(1)


def _q3_partial_plan(cutoff: int) -> fusion.Plan:
    """Out-of-core q3 per-chunk region: probe projection + clustered-PK
    lookup against the resident build2 (an exact scan — the clustered
    layout declares build rows == declared key range, which padding would
    break) + revenue partial groupby. ``rows_of`` specs resolve from TRUE
    row counts: the groupby budget is the chunk's row count (the staged
    ``max_groups=keyed.num_rows`` shape) and key_hi is |orders|."""
    probe = fusion.Project(fusion.Scan("chunk"), _q3_probe_fn, (cutoff,))
    j2 = fusion.DensePkJoin(probe, fusion.Scan("build2", bucket=False),
                            0, 0, 1, fusion.rows_of("build2"),
                            clustered=True, label="pk2")
    return fusion.Plan("tpch_q3_partial", fusion.GroupBy(
        fusion.Project(j2, _q3_planned_keyed_fn), (0, 1, 2), ((3, "sum"),),
        max_groups=fusion.rows_of("chunk"), label="partial"))


def _q3_merge_plan() -> fusion.Plan:
    """Merge the stacked q3 partials: sum-merge + output order, fused.
    (Final null-key compaction happens on host — dynamic shape.)"""
    return fusion.Plan("tpch_q3_merge", fusion.Sort(
        fusion.GroupBy(fusion.Scan("partials"), (0, 1, 2), ((3, "sum"),),
                       label="merge"),
        (3, 1), ascending=(False, True), nulls_first=(False, False)))


def tpch_q3_distributed(customer: Table, orders: Table, lineitem: Table,
                        mesh, segment: int = 0,
                        cutoff: int = _Q3_CUTOFF_DAYS,
                        out_factor: int = 4) -> Table:
    """Multi-executor q3: the REPARTITIONED two-exchange plan. Exchange 1
    co-locates orders and customers by custkey hash; exchange 2 co-locates
    the qualifying orders with lineitem by orderkey hash. After exchange 2
    every orderkey lives on exactly one device, so the per-device groupby
    partitions the global answer; collect + one tiny host sort finishes."""
    import jax as _jax
    from jax.sharding import PartitionSpec as P

    from spark_rapids_jni_tpu.parallel.distributed import (
        _mesh_fingerprint,
        collect,
        distributed_join,
        shard_table,
    )
    from spark_rapids_jni_tpu.parallel.mesh import EXEC_AXIS
    from spark_rapids_jni_tpu.runtime import dispatch

    d = int(np.prod(list(mesh.shape.values())))
    n_ord, n_li = orders.num_rows, lineitem.num_rows

    cust, ord_t, probe = _q3_inputs(customer, orders, lineitem, segment,
                                    cutoff)

    so, orv = shard_table(ord_t, mesh, return_row_valid=True)
    sc, crv = shard_table(cust, mesh, return_row_valid=True)
    res1 = distributed_join(
        so, sc, 0, 0, mesh,
        out_size_per_device=max(1, n_ord // max(d // 2, 1)),
        left_capacity=max(1, n_ord // d * 2),
        right_capacity=max(1, customer.num_rows // d * 2),
        left_row_valid=orv, right_row_valid=crv,
    )
    if np.asarray(res1.overflowed).any():
        raise ValueError("q3 exchange 1 overflowed; raise capacities")
    oc = res1.table  # sharded: [o_custkey, o_orderkey, o_date, o_prio, c_custkey]
    matched = oc.column(4).valid_mask()
    build = Table([
        Column(oc.column(1).dtype, oc.column(1).data,
               oc.column(1).valid_mask() & matched),
        oc.column(2), oc.column(3),
    ])

    sp, prv = shard_table(probe, mesh, return_row_valid=True)
    # inner join: null-key build rows never match, so key validity doubles
    # as the row mask (saves shuffle capacity on exchange-1 padding)
    res2 = distributed_join(
        sp, build, 0, 0, mesh,
        out_size_per_device=max(1, n_li * out_factor // max(d // 2, 1)),
        left_capacity=max(1, n_li // d * 2),
        right_capacity=max(1, build.num_rows // d * 2),
        left_row_valid=prv, right_row_valid=build.column(0).valid_mask(),
    )
    if np.asarray(res2.overflowed).any():
        raise ValueError("q3 exchange 2 overflowed; raise capacities")

    out, num_groups = dispatch.sharded_call(
        "tpch_q3_distributed.group_step",
        lambda: _jax.shard_map(
            _q3_group_step, mesh=mesh, in_specs=(P(EXEC_AXIS),),
            out_specs=(P(EXEC_AXIS), P(EXEC_AXIS)),
        ),
        (res2.table,),
        statics=(_mesh_fingerprint(mesh),),
    )
    result = collect(out, num_groups, mesh)
    srt = sort_table(result, [3, 1], ascending=[False, True],
                     nulls_first=[False, False])
    # drop the null-key pseudo-groups (unmatched/padding)
    kv = np.asarray(srt.column(0).valid_mask())
    k = int(kv.sum())
    return Table([
        Column(c.dtype, c.data[:k],
               None if c.validity is None else c.validity[:k])
        for c in srt.columns
    ])


class Q10Result(NamedTuple):
    result: GroupByResult   # [c_custkey, c_nationkey, revenue] rev desc
    join_total: jnp.ndarray
    pk_violation: jnp.ndarray


_Q10_QTR_START = 8582   # 1993-07-01
_Q10_QTR_END = 8674     # 1993-10-01


@func_range("tpch_q10")
def tpch_q10(customer: Table, orders: Table, lineitem: Table,
             qtr_start: int = _Q10_QTR_START,
             qtr_end: int = _Q10_QTR_END) -> Q10Result:
    """q10 (returned-item reporting): lineitem filtered to returns,
    joined through orders (quarter filter pushed into the build keys)
    to the customer, grouped by customer, revenue-desc — the LIMIT 20
    head is the caller's compact+head.

    The plan mixes both machineries deliberately: the joins are dense
    clustered-PK lookups (sort-free, probe-aligned), while the
    customer groupby is HIGH-cardinality — outside every declared-
    domain trick — so it rides the general sort-based groupby. This is
    the realistic SF-scale shape: planner facts kill the join costs,
    the one irreducible data-dependent grouping remains.

    ``lineitem`` here is the q3 layout + a returnflag column appended:
    [l_orderkey, l_extendedprice, l_discount, l_shipdate,
    l_returnflag]."""
    from spark_rapids_jni_tpu.ops.planner import dense_pk_join

    n_cust, n_ord = customer.num_rows, orders.num_rows
    rf = lineitem.column(4)
    returned = rf.valid_mask() & (rf.data == jnp.int8(ord("R")))
    price = lineitem.column(L3_EXTENDEDPRICE)
    disc = lineitem.column(L3_DISCOUNT)
    revenue = Column(
        t.decimal64(-4), price.data * (100 - disc.data),
        price.valid_mask() & disc.valid_mask() & returned)
    probe = Table([
        _null_where(lineitem.column(L3_ORDERKEY), ~returned),
        revenue,
    ])
    od = orders.column(O_ORDERDATE)
    in_qtr = (od.valid_mask() & (od.data >= jnp.int32(qtr_start))
              & (od.data < jnp.int32(qtr_end)))
    ord_build = Table([
        _null_where(orders.column(O_ORDERKEY), ~in_qtr),
        orders.column(O_CUSTKEY),
    ])
    j_o = dense_pk_join(probe, ord_build, 0, 0, 1, n_ord,
                        clustered=True)
    o_cust = j_o.table.column(3)
    j_c = dense_pk_join(Table([o_cust]), customer, 0, C5_CUSTKEY,
                        1, n_cust, clustered=True)
    c_key = j_c.table.column(1)
    c_nat = j_c.table.column(2)
    keep = j_o.matched & j_c.matched
    keyed = Table([
        _null_where(c_key, ~keep),
        c_nat,
        Column(revenue.dtype, revenue.data,
               revenue.valid_mask() & keep),
    ])
    g = groupby_aggregate(keyed, keys=[0, 1], aggs=[(2, "sum")])
    srt = sort_table(g.table, [2], ascending=[False],
                     nulls_first=[False])
    return Q10Result(
        GroupByResult(srt, g.num_groups),
        jnp.sum(keep.astype(jnp.int64)),
        j_o.pk_violation | j_c.pk_violation)


def tpch_q10_numpy(customer: Table, orders: Table, lineitem: Table,
                   qtr_start: int = _Q10_QTR_START,
                   qtr_end: int = _Q10_QTR_END) -> dict:
    """Host oracle: {c_custkey: (nationkey, revenue)}."""
    c_nat = {int(k): int(v) for k, v in zip(
        np.asarray(customer.column(C5_CUSTKEY).data),
        np.asarray(customer.column(C5_NATIONKEY).data))}
    o_cust = {}
    for k, c, d in zip(np.asarray(orders.column(O_ORDERKEY).data),
                       np.asarray(orders.column(O_CUSTKEY).data),
                       np.asarray(orders.column(O_ORDERDATE).data)):
        if qtr_start <= int(d) < qtr_end:
            o_cust[int(k)] = int(c)
    out: dict = {}
    lkey = np.asarray(lineitem.column(L3_ORDERKEY).data)
    price = np.asarray(lineitem.column(L3_EXTENDEDPRICE).data)
    disc = np.asarray(lineitem.column(L3_DISCOUNT).data)
    rf = np.asarray(lineitem.column(4).data)
    for i in range(lineitem.num_rows):
        if rf[i] != ord("R"):
            continue
        cu = o_cust.get(int(lkey[i]))
        if cu is None or cu not in c_nat:
            continue
        rev = int(price[i]) * (100 - int(disc[i]))
        prev = out.get(cu, (c_nat[cu], 0))
        out[cu] = (c_nat[cu], prev[1] + rev)
    return out


def tpch_q3_outofcore(path, customer: Table, orders: Table, *,
                      budget_bytes: int, chunk_read_limit: int,
                      segment: int = 0, cutoff: int = _Q3_CUTOFF_DAYS,
                      prefetch_depth: int = 0,
                      pipeline: bool | None = None):
    """q3 over a lineitem Parquet file larger than the device budget:
    the JOIN side of the SF-scale story (q1 covered pure aggregation).
    customer and orders stay resident (the small sides — the broadcast
    plan's premise); lineitem streams in row-group chunks, each chunk
    joins through the dense clustered-PK lookups (probe-aligned, no
    join machinery to size) and partial-aggregates revenue by orderkey;
    host-compacted partials merge at the end. The partial->merge
    algebra is tpch_q3_planned_distributed's, run over TIME instead of
    the mesh.

    File schema: [l_orderkey int64, l_extendedprice int64,
    l_discount int64, l_shipdate date32]. Returns OutOfCoreResult;
    ``.table`` matches tpch_q3's compacted output of the materialized
    file.

    The per-chunk device step is ONE fused region (probe projection +
    clustered-PK lookup + partial groupby); the resident build2 rides
    the region as an exact (unbucketed) scan, and the dead chunk tables
    are donated. The host ``trim_table`` compaction and the final merge
    plan are the region boundaries."""
    from spark_rapids_jni_tpu.ops.planner import dense_pk_join
    from spark_rapids_jni_tpu.parquet.reader import ParquetChunkedReader
    from spark_rapids_jni_tpu.runtime.memory import MemoryLimiter, SpillStore
    from spark_rapids_jni_tpu.runtime.outofcore import run_chunked_aggregate

    n_cust = customer.num_rows
    limiter = MemoryLimiter(budget_bytes)
    spill = SpillStore(budget_bytes)

    # the resident build side, computed once: orders |x| customer via
    # the clustered custkey lookup, date/segment predicates pushed in
    cust = _q3_cust_fn(customer, segment)
    ord_t = _q3_orders_fn(orders, cutoff)
    j1 = dense_pk_join(ord_t, cust, 0, 0, 1, n_cust, clustered=True)
    if bool(j1.pk_violation):
        raise ValueError("customer PK declaration violated")
    build2 = _q3_build2_fn(j1.table)

    # runtime bloom filter: the resident build side's orderkeys, built
    # once, prune every streamed lineitem chunk on the HOST side before
    # the chunk is reserved/staged (compaction is free at the chunk
    # boundary) — fewer bytes reserved and spilled, bit-identical bytes
    # out. Gated per plan signature by the learned selectivity EMA.
    decision = rtfilter.decide("tpch_q3_outofcore", "pk2",
                               build2.num_rows)
    chunk_filter = None
    if decision.apply:
        bcol = build2.column(0)
        chunk_filter = rtfilter.build_filter(
            bcol.data, bcol.valid_mask(),
            expected_items=build2.num_rows)

    def partial_fn(chunk: Table) -> Table:
        from spark_rapids_jni_tpu.ops.table_ops import trim_table

        cols = list(chunk.columns)
        cols[1] = Column(t.decimal64(-2), cols[1].data, cols[1].validity)
        cols[2] = Column(t.decimal64(-2), cols[2].data, cols[2].validity)
        # pk2 is decided once, above, for the host side of the staging
        # boundary: a chunk is not probed again inside its region, where
        # masking drops no row
        res = fusion.execute(
            _q3_partial_plan(cutoff),
            {"chunk": Table(cols), "build2": build2},
            donate_inputs=True, runtime_filters=False)
        if bool(res.meta["pk2.pk_violation"]):
            raise ValueError("orders PK declaration violated")
        return trim_table(res.table, int(res.meta["partial.num_groups"]))

    def merge_fn(partials: Table) -> Table:
        srt = fusion.execute(_q3_merge_plan(), {"partials": partials}).table
        kv = np.asarray(srt.column(0).valid_mask())
        k = int(kv.sum())
        return Table([
            Column(c.dtype, c.data[:k],
                   None if c.validity is None else c.validity[:k])
            for c in srt.columns
        ])

    reader = ParquetChunkedReader(path, chunk_read_limit=chunk_read_limit)
    chunks = reader if chunk_filter is None else rtfilter.pruned_chunks(
        reader, chunk_filter, 0, plan_name="tpch_q3_outofcore",
        label="pk2")
    return run_chunked_aggregate(
        chunks, partial_fn, merge_fn, limiter=limiter, spill=spill,
        prefetch_depth=prefetch_depth, pipeline=pipeline)


def tpch_q3_planned_distributed(customer: Table, orders: Table,
                                lineitem: Table, mesh, segment: int = 0,
                                cutoff: int = _Q3_CUTOFF_DAYS) -> Table:
    """Multi-executor planned q3: the BROADCAST plan the dense-PK
    declarations unlock. customer and orders replicate to every device
    (they are the small sides); each device runs both clustered-PK
    lookups on its lineitem shard — sort-free, no join exchange at all —
    then partial-aggregates revenue by orderkey locally. The ONLY
    exchange in the whole plan is the partial-aggregate shuffle (m
    partial rows per device, not n), where the general distributed q3
    pays two full row exchanges before it even reaches that point.
    Returns the collected, sorted, compacted global result (same
    contract as tpch_q3_distributed)."""
    import jax as _jax
    from jax.sharding import PartitionSpec as P

    from spark_rapids_jni_tpu.ops.planner import dense_pk_join
    from spark_rapids_jni_tpu.parallel.distributed import (
        _mesh_fingerprint,
        collect,
        shard_table,
    )
    from spark_rapids_jni_tpu.parallel.mesh import EXEC_AXIS
    from spark_rapids_jni_tpu.parallel.shuffle import hash_shuffle
    from spark_rapids_jni_tpu.runtime import dispatch

    cust, ord_t, probe = _q3_inputs(customer, orders, lineitem, segment,
                                    cutoff)
    sp, prv = shard_table(probe, mesh, return_row_valid=True)
    n_cust, n_ord = customer.num_rows, orders.num_rows

    def step(local: Table, rv, cust_r: Table, ord_r: Table):
        j1 = dense_pk_join(ord_r, cust_r, 0, 0, 1, n_cust,
                           clustered=True)
        build2 = Table([
            _null_where(j1.table.column(1), ~j1.matched),
            j1.table.column(2), j1.table.column(3),
        ])
        j2 = dense_pk_join(local, build2, 0, 0, 1, n_ord,
                           clustered=True)
        jt = j2.table
        matched = j2.matched & rv
        keyed = Table([
            _null_where(jt.column(0), ~matched),
            jt.column(3), jt.column(4),
            Column(jt.column(1).dtype, jt.column(1).data,
                   jt.column(1).valid_mask() & matched),
        ])
        local_n = keyed.num_rows
        partial = groupby_aggregate(keyed, keys=[0, 1, 2],
                                    aggs=[(3, "sum")],
                                    max_groups=local_n)
        real = (jnp.arange(local_n, dtype=jnp.int32)
                < partial.num_groups)
        # a sender holds <= local_n real partial rows total, so the
        # per-receiver lane capacity local_n can never overflow
        sh = hash_shuffle(partial.table, [0], EXEC_AXIS,
                          capacity=local_n, row_valid=real)
        merged = groupby_aggregate(sh.table, keys=[0, 1, 2],
                                   aggs=[(3, "sum")])
        viol = (j1.pk_violation | j2.pk_violation)
        return (merged.table, merged.num_groups.reshape(1),
                viol.reshape(1))

    out, num_groups, viol = dispatch.sharded_call(
        "tpch_q3_planned_distributed.step",
        lambda: _jax.shard_map(
            step, mesh=mesh,
            in_specs=(P(EXEC_AXIS), P(EXEC_AXIS), P(), P()),
            out_specs=(P(EXEC_AXIS), P(EXEC_AXIS), P(EXEC_AXIS)),
        ),
        (sp, prv, cust, ord_t),
        statics=(n_cust, n_ord, _mesh_fingerprint(mesh)),
    )
    if bool(np.asarray(viol).any()):
        raise ValueError(
            "dense-PK declaration violated — re-plan with "
            "tpch_q3_distributed")
    result = collect(out, num_groups, mesh)
    srt = sort_table(result, [3, 1], ascending=[False, True],
                     nulls_first=[False, False])
    kv = np.asarray(srt.column(0).valid_mask())
    k = int(kv.sum())
    return Table([
        Column(c.dtype, c.data[:k],
               None if c.validity is None else c.validity[:k])
        for c in srt.columns
    ])


# ---------------------------------------------------------------------------
# q5 — local supplier volume: the six-table join (customer, orders,
# lineitem, supplier, nation, region) grouped by nation. The TPU plan is
# built ENTIRELY from planner facts: every join is a dense clustered-PK
# lookup, the region predicate pushes into the nation build side, the
# c_nationkey = s_nationkey condition is a post-lookup filter, and the
# GROUP BY nation is the bounded masked-reduction over the 25-value DDL
# domain — no sort touches an n-sized array anywhere.
# ---------------------------------------------------------------------------

_Q5_NATIONS = (
    "ALGERIA", "ARGENTINA", "BRAZIL", "CANADA", "EGYPT", "ETHIOPIA",
    "FRANCE", "GERMANY", "INDIA", "INDONESIA", "IRAN", "IRAQ", "JAPAN",
    "JORDAN", "KENYA", "MOROCCO", "MOZAMBIQUE", "PERU", "CHINA",
    "ROMANIA", "SAUDI ARABIA", "VIETNAM", "RUSSIA", "UNITED KINGDOM",
    "UNITED STATES",
)
_Q5_N_REGIONS = 5
_Q5_YEAR_START = 8766   # 1994-01-01
_Q5_YEAR_END = 9131     # 1995-01-01

# nation columns
N_NATIONKEY, N_REGIONKEY = 0, 1
# supplier columns
S_SUPPKEY, S_NATIONKEY = 0, 1
# q5 customer columns
C5_CUSTKEY, C5_NATIONKEY = 0, 1
# q5 lineitem columns
L5_ORDERKEY, L5_SUPPKEY, L5_EXTENDEDPRICE, L5_DISCOUNT = 0, 1, 2, 3


def nation_table(seed: int = 0) -> Table:
    rng = np.random.default_rng(seed)
    return Table([
        Column.from_numpy(np.arange(1, 26, dtype=np.int64)),
        Column.from_numpy(
            rng.integers(1, _Q5_N_REGIONS + 1, 25).astype(np.int64)),
    ])


def supplier_table(num_rows: int, seed: int = 9) -> Table:
    rng = np.random.default_rng(seed)
    return Table([
        Column.from_numpy(np.arange(1, num_rows + 1, dtype=np.int64)),
        Column.from_numpy(rng.integers(1, 26, num_rows).astype(np.int64)),
    ])


def customer_q5_table(num_rows: int, seed: int = 10) -> Table:
    rng = np.random.default_rng(seed)
    return Table([
        Column.from_numpy(np.arange(1, num_rows + 1, dtype=np.int64)),
        Column.from_numpy(rng.integers(1, 26, num_rows).astype(np.int64)),
    ])


def lineitem_q5_table(num_rows: int, num_orders: int,
                      num_suppliers: int, seed: int = 11) -> Table:
    rng = np.random.default_rng(seed)
    return Table([
        Column.from_numpy(
            rng.integers(1, num_orders + 1, num_rows).astype(np.int64)),
        Column.from_numpy(
            rng.integers(1, num_suppliers + 1, num_rows).astype(np.int64)),
        Column.from_numpy(
            rng.integers(90_000, 10_500_000, num_rows).astype(np.int64),
            t.decimal64(-2)),
        Column.from_numpy(
            rng.integers(0, 11, num_rows).astype(np.int64),
            t.decimal64(-2)),
    ])


class Q5Result(NamedTuple):
    table: Table              # [n_nationkey, revenue, n_name], rev desc
    present: jnp.ndarray
    pk_violation: jnp.ndarray
    domain_miss: jnp.ndarray


@func_range("tpch_q5")
def tpch_q5(customer: Table, orders: Table, lineitem: Table,
            supplier: Table, nation: Table, region_of_interest: int = 1,
            year_start: int = _Q5_YEAR_START,
            year_end: int = _Q5_YEAR_END) -> Q5Result:
    """q5 as the all-planner-facts plan (module header). Row flow, one
    output row per LINEITEM row at every stage (PK fanout <= 1):

    lineitem -> supplier (suppkey lookup) -> s_nationkey
             -> orders   (orderkey lookup; date filter pushed into the
                          build key) -> o_custkey
             -> customer (custkey lookup on the gathered o_custkey)
                          -> c_nationkey
             -> nation   (s_nationkey lookup; region filter pushed into
                          the build key) -> survives iff in region
    keep = all matches & c_nationkey == s_nationkey; revenue sums into
    the 25-slot bounded nation groupby.
    """
    from spark_rapids_jni_tpu.ops.planner import (
        dense_pk_join,
        plan_groupby,
        scalar_domain,
    )

    n_supp = supplier.num_rows
    n_ord = orders.num_rows
    n_cust = customer.num_rows

    j_s = dense_pk_join(lineitem, supplier, L5_SUPPKEY, S_SUPPKEY,
                        1, n_supp, clustered=True)
    s_nation = j_s.table.column(lineitem.num_columns + 1)

    od = orders.column(O_ORDERDATE)
    date_ok = (od.valid_mask() & (od.data >= jnp.int32(year_start))
               & (od.data < jnp.int32(year_end)))
    ord_build = Table([
        _null_where(orders.column(O_ORDERKEY), ~date_ok),
        orders.column(O_CUSTKEY),
    ])
    j_o = dense_pk_join(lineitem, ord_build, L5_ORDERKEY, 0,
                        1, n_ord, clustered=True)
    o_cust = j_o.table.column(lineitem.num_columns + 1)

    # dense_pk_join already folded `matched` into the gathered column's
    # validity — the mask is ready to re-probe with
    cust_probe = Table([o_cust])
    j_c = dense_pk_join(cust_probe, customer, 0, C5_CUSTKEY,
                        1, n_cust, clustered=True)
    c_nation = j_c.table.column(2)

    nat_build = Table([
        _null_where(nation.column(N_NATIONKEY),
                    nation.column(N_REGIONKEY).data
                    != jnp.int64(region_of_interest)),
    ])
    nat_probe = Table([s_nation])
    j_n = dense_pk_join(nat_probe, nat_build, 0, 0, 1, 25,
                        clustered=True)

    keep = (j_s.matched & j_o.matched & j_c.matched & j_n.matched
            & (c_nation.data == s_nation.data))
    price = lineitem.column(L5_EXTENDEDPRICE)
    disc = lineitem.column(L5_DISCOUNT)
    rev_ok = keep & price.valid_mask() & disc.valid_mask()
    revenue = Column(
        t.decimal64(-4),
        jnp.where(rev_ok, price.data * (100 - disc.data), 0), rev_ok)
    keyed = Table([
        Column(s_nation.dtype,
               jnp.where(keep, s_nation.data, 0), keep),
        revenue,
    ])
    g = plan_groupby(keyed, [0], [(1, "sum")],
                     [scalar_domain(range(1, 26))])
    assert g.lowered == "bounded"
    # n_name attaches statically BEFORE the tiny ORDER BY: bounded slot
    # i (< 25) is nation key i+1 -> _Q5_NATIONS[i]; the string column
    # then rides the 26-row sort like any other column
    name_w = max(len(nm) for nm in _Q5_NATIONS)
    name_mat = np.zeros((g.table.num_rows, name_w), np.uint8)
    name_len = np.zeros(g.table.num_rows, np.int32)
    for i, nm in enumerate(_Q5_NATIONS):
        b = nm.encode()
        name_mat[i, : len(b)] = np.frombuffer(b, np.uint8)
        name_len[i] = len(b)
    names = Column(t.STRING, jnp.asarray(name_len),
                   g.table.column(0).valid_mask(),
                   chars=jnp.asarray(name_mat))
    srt = sort_table(Table(list(g.table.columns) + [names]),
                     [1], ascending=[False], nulls_first=[False])
    # the 26-row ORDER BY permutes the slot table; present travels as
    # the key validity (bounded output: key valid <=> slot present)
    present = srt.column(0).valid_mask()
    pk_viol = (j_s.pk_violation | j_o.pk_violation | j_c.pk_violation
               | j_n.pk_violation)
    return Q5Result(srt, present, pk_viol, g.domain_miss)


def tpch_q5_numpy(customer: Table, orders: Table, lineitem: Table,
                  supplier: Table, nation: Table,
                  region_of_interest: int = 1,
                  year_start: int = _Q5_YEAR_START,
                  year_end: int = _Q5_YEAR_END) -> dict:
    """Host oracle: {n_nationkey: revenue}."""
    s_nat = {int(k): int(v) for k, v in zip(
        np.asarray(supplier.column(S_SUPPKEY).data),
        np.asarray(supplier.column(S_NATIONKEY).data))}
    c_nat = {int(k): int(v) for k, v in zip(
        np.asarray(customer.column(C5_CUSTKEY).data),
        np.asarray(customer.column(C5_NATIONKEY).data))}
    in_region = {int(k) for k, r in zip(
        np.asarray(nation.column(N_NATIONKEY).data),
        np.asarray(nation.column(N_REGIONKEY).data))
        if int(r) == region_of_interest}
    o_info = {}
    for k, c, d in zip(np.asarray(orders.column(O_ORDERKEY).data),
                       np.asarray(orders.column(O_CUSTKEY).data),
                       np.asarray(orders.column(O_ORDERDATE).data)):
        if year_start <= int(d) < year_end:
            o_info[int(k)] = int(c)
    out: dict = {}
    lkey = np.asarray(lineitem.column(L5_ORDERKEY).data)
    lsupp = np.asarray(lineitem.column(L5_SUPPKEY).data)
    price = np.asarray(lineitem.column(L5_EXTENDEDPRICE).data)
    disc = np.asarray(lineitem.column(L5_DISCOUNT).data)
    for i in range(lineitem.num_rows):
        ok = int(lkey[i])
        if ok not in o_info:
            continue
        sn = s_nat.get(int(lsupp[i]))
        if sn is None or sn not in in_region:
            continue
        if c_nat.get(o_info[ok]) != sn:
            continue
        out[sn] = out.get(sn, 0) + int(price[i]) * (100 - int(disc[i]))
    return out


def tpch_q5_distributed(customer: Table, orders: Table, lineitem: Table,
                        supplier: Table, nation: Table, mesh,
                        region_of_interest: int = 1,
                        year_start: int = _Q5_YEAR_START,
                        year_end: int = _Q5_YEAR_END) -> Q5Result:
    """Multi-executor q5 with ZERO shuffles: lineitem shards row-wise,
    all four dimension tables replicate, each device runs the five
    dense-PK lookups + the 25-slot bounded nation groupby on its shard,
    and the global merge is one psum over the 26-slot sum vector —
    208 bytes on the wire per device. The single-device tpch_q5 IS the
    per-device step; only the merge differs (the bounded-slot
    associativity that makes distributed_groupby_bounded shuffle-free).
    Result is replicated; same schema as tpch_q5."""
    import jax as _jax
    from jax.sharding import PartitionSpec as P

    from spark_rapids_jni_tpu.ops.planner import (
        dense_pk_join,
        plan_groupby,
        scalar_domain,
    )
    from spark_rapids_jni_tpu.parallel.distributed import shard_table
    from spark_rapids_jni_tpu.parallel.mesh import EXEC_AXIS

    n_supp, n_ord = supplier.num_rows, orders.num_rows
    n_cust = customer.num_rows
    sl, rv = shard_table(lineitem, mesh, return_row_valid=True)

    def step(local: Table, lrv, cust_r, ord_r, supp_r, nat_r):
        j_s = dense_pk_join(local, supp_r, L5_SUPPKEY, S_SUPPKEY,
                            1, n_supp, clustered=True)
        s_nation = j_s.table.column(local.num_columns + 1)
        od = ord_r.column(O_ORDERDATE)
        date_ok = (od.valid_mask() & (od.data >= jnp.int32(year_start))
                   & (od.data < jnp.int32(year_end)))
        ord_build = Table([
            _null_where(ord_r.column(O_ORDERKEY), ~date_ok),
            ord_r.column(O_CUSTKEY),
        ])
        j_o = dense_pk_join(local, ord_build, L5_ORDERKEY, 0,
                            1, n_ord, clustered=True)
        o_cust = j_o.table.column(local.num_columns + 1)
        j_c = dense_pk_join(Table([o_cust]), cust_r, 0, C5_CUSTKEY,
                            1, n_cust, clustered=True)
        c_nation = j_c.table.column(2)
        nat_build = Table([
            _null_where(nat_r.column(N_NATIONKEY),
                        nat_r.column(N_REGIONKEY).data
                        != jnp.int64(region_of_interest)),
        ])
        j_n = dense_pk_join(Table([s_nation]), nat_build, 0, 0, 1, 25,
                            clustered=True)
        keep = (lrv & j_s.matched & j_o.matched & j_c.matched
                & j_n.matched & (c_nation.data == s_nation.data))
        price = local.column(L5_EXTENDEDPRICE)
        disc = local.column(L5_DISCOUNT)
        rev_ok = keep & price.valid_mask() & disc.valid_mask()
        keyed = Table([
            Column(s_nation.dtype,
                   jnp.where(keep, s_nation.data, 0), keep),
            Column(t.decimal64(-4),
                   jnp.where(rev_ok, price.data * (100 - disc.data), 0),
                   rev_ok),
        ])
        g = plan_groupby(keyed, [0], [(1, "sum")],
                         [scalar_domain(range(1, 26))], row_valid=lrv)
        # the 26-slot partials merge with ONE collective
        sums = _jax.lax.psum(
            jnp.where(g.table.column(1).valid_mask(),
                      g.table.column(1).data, 0), EXEC_AXIS)
        valid_g = _jax.lax.psum(
            g.table.column(1).valid_mask().astype(jnp.int32),
            EXEC_AXIS) > 0
        viol = _jax.lax.psum(
            (j_s.pk_violation | j_o.pk_violation | j_c.pk_violation
             | j_n.pk_violation).astype(jnp.int32), EXEC_AXIS) > 0
        miss = _jax.lax.psum(
            g.domain_miss.astype(jnp.int32), EXEC_AXIS) > 0
        return (g.table.column(0).data, sums, valid_g,
                viol, miss)

    keys, sums, valid_g, viol, miss = _jax.jit(_jax.shard_map(
        step, mesh=mesh,
        in_specs=(P(EXEC_AXIS), P(EXEC_AXIS), P(), P(), P(), P()),
        out_specs=(P(), P(), P(), P(), P()),
    ))(sl, rv, customer, orders, supplier, nation)

    out = Table([
        Column(t.INT64, keys, valid_g),
        Column(t.decimal64(-4), sums, valid_g),
    ])
    name_w = max(len(nm) for nm in _Q5_NATIONS)
    name_mat = np.zeros((out.num_rows, name_w), np.uint8)
    name_len = np.zeros(out.num_rows, np.int32)
    for i, nm in enumerate(_Q5_NATIONS):
        b = nm.encode()
        name_mat[i, : len(b)] = np.frombuffer(b, np.uint8)
        name_len[i] = len(b)
    names = Column(t.STRING, jnp.asarray(name_len), valid_g,
                   chars=jnp.asarray(name_mat))
    srt = sort_table(Table(list(out.columns) + [names]), [1],
                     ascending=[False], nulls_first=[False])
    return Q5Result(srt, srt.column(0).valid_mask(), viol, miss)


# ---------------------------------------------------------------------------
# q12 — shipping modes and order priority (join + string-key groupby with
# conditional counts). Reference workload family: BASELINE.json config #4's
# "hash-join + reader" shape; predicates are Spark CASE WHEN lowering onto
# masked integer lanes.
# ---------------------------------------------------------------------------

# q12 lineitem columns
L12_ORDERKEY, L12_SHIPMODE, L12_COMMITDATE = 0, 1, 2
L12_RECEIPTDATE, L12_SHIPDATE = 3, 4
# q12 orders columns
O12_ORDERKEY, O12_ORDERPRIORITY = 0, 1

_Q12_MODES = ("MAIL", "SHIP", "AIR", "RAIL", "TRUCK", "FOB", "REG AIR")
_Q12_PRIORITIES = ("1-URGENT", "2-HIGH", "3-MEDIUM",
                   "4-NOT SPECIFIED", "5-LOW")
_Q12_YEAR_START = 8766   # 1994-01-01 in days
_Q12_YEAR_END = 9131     # 1995-01-01


def lineitem_q12_table(num_rows: int, num_orders: int,
                       seed: int = 3) -> Table:
    rng = np.random.default_rng(seed)
    ship = rng.integers(8400, 10957, num_rows).astype(np.int32)
    commit = ship + rng.integers(-30, 60, num_rows).astype(np.int32)
    receipt = commit + rng.integers(-20, 40, num_rows).astype(np.int32)
    return Table([
        Column.from_numpy(
            rng.integers(1, num_orders + 1, num_rows).astype(np.int64)),
        Column.from_pylist(
            [_Q12_MODES[i] for i in rng.integers(0, len(_Q12_MODES),
                                                 num_rows)], t.STRING),
        Column.from_numpy(commit, t.TIMESTAMP_DAYS),
        Column.from_numpy(receipt, t.TIMESTAMP_DAYS),
        Column.from_numpy(ship, t.TIMESTAMP_DAYS),
    ])


def orders_q12_table(num_rows: int, seed: int = 4) -> Table:
    rng = np.random.default_rng(seed)
    return Table([
        Column.from_numpy(np.arange(1, num_rows + 1, dtype=np.int64)),
        Column.from_pylist(
            [_Q12_PRIORITIES[i]
             for i in rng.integers(0, len(_Q12_PRIORITIES), num_rows)],
            t.STRING),
    ])


def _q12_keep(lineitem: Table, mode_c: Column, modes: tuple,
              year_start: int, year_end: int) -> jnp.ndarray:
    """Shared q12 WHERE (single change point for single-device and
    distributed plans, the _q3_inputs convention): mode IN list + date
    sanity predicates, null operands not-TRUE (every valid_mask ANDed)."""
    from spark_rapids_jni_tpu.ops import strings as s

    in_modes = jnp.zeros((lineitem.num_rows,), jnp.bool_)
    for mname in modes:
        in_modes = in_modes | (s.like(mode_c, mname).data != 0)
    commit_c = lineitem.column(L12_COMMITDATE)
    receipt_c = lineitem.column(L12_RECEIPTDATE)
    ship_c = lineitem.column(L12_SHIPDATE)
    return (in_modes & mode_c.valid_mask() & commit_c.valid_mask()
            & receipt_c.valid_mask() & ship_c.valid_mask()
            & (commit_c.data < receipt_c.data)
            & (ship_c.data < commit_c.data)
            & (receipt_c.data >= jnp.int32(year_start))
            & (receipt_c.data < jnp.int32(year_end)))


def _q12_priority_lanes(prio: Column, matched: jnp.ndarray):
    """Shared CASE WHEN o_orderpriority IN ('1-URGENT','2-HIGH') lanes."""
    from spark_rapids_jni_tpu.ops import strings as s

    urgent = ((s.like(prio, "1-URGENT").data != 0)
              | (s.like(prio, "2-HIGH").data != 0))
    high = Column(t.INT64,
                  jnp.where(matched & urgent, jnp.int64(1), jnp.int64(0)),
                  matched)
    low = Column(t.INT64,
                 jnp.where(matched & ~urgent, jnp.int64(1), jnp.int64(0)),
                 matched)
    return high, low


class Q12Result(NamedTuple):
    result: GroupByResult    # [l_shipmode, high_line_count, low_line_count]
    join_total: jnp.ndarray


@func_range("tpch_q12")
def tpch_q12(orders: Table, lineitem: Table,
             modes: tuple = ("MAIL", "SHIP"),
             year_start: int = _Q12_YEAR_START,
             year_end: int = _Q12_YEAR_END) -> Q12Result:
    """q12: lineitem filtered on mode/date sanity predicates, joined to
    orders on orderkey, grouped by shipmode with CASE-WHEN priority
    counts. Static shapes: the WHERE lowers to a nulled join key (the
    q3 idiom), CASE WHEN to masked int lanes."""
    from spark_rapids_jni_tpu.ops import strings as s
    from spark_rapids_jni_tpu.ops.join import apply_join_maps, join

    mode_c = lineitem.column(L12_SHIPMODE)
    keep = _q12_keep(lineitem, mode_c, modes, year_start, year_end)
    probe = Table([
        _null_where(lineitem.column(L12_ORDERKEY), ~keep),
        mode_c,
    ])
    maps = join(probe, orders, 0, 0, out_size=lineitem.num_rows)
    j = apply_join_maps(probe, orders, maps)
    # j: [l_orderkey, l_shipmode, o_orderkey, o_orderpriority]
    matched = j.column(2).valid_mask()
    high, low = _q12_priority_lanes(j.column(3), matched)
    keyed = Table([
        _null_where(j.column(1), ~matched), high, low,
    ])
    g = groupby_aggregate(keyed, keys=[0], aggs=[(1, "sum"), (2, "sum")])
    srt = sort_table(g.table, [0], nulls_first=[False])
    return Q12Result(GroupByResult(srt, g.num_groups), maps.total)


def tpch_q12_numpy(orders: Table, lineitem: Table,
                   modes: tuple = ("MAIL", "SHIP"),
                   year_start: int = _Q12_YEAR_START,
                   year_end: int = _Q12_YEAR_END) -> dict:
    prio = {int(k): p for k, p in zip(
        np.asarray(orders.column(O12_ORDERKEY).data).tolist(),
        orders.column(O12_ORDERPRIORITY).to_pylist())}
    out: dict = {}
    lmode = lineitem.column(L12_SHIPMODE).to_pylist()
    lkey = np.asarray(lineitem.column(L12_ORDERKEY).data).tolist()
    commit = np.asarray(lineitem.column(L12_COMMITDATE).data).tolist()
    receipt = np.asarray(lineitem.column(L12_RECEIPTDATE).data).tolist()
    ship = np.asarray(lineitem.column(L12_SHIPDATE).data).tolist()
    for i in range(lineitem.num_rows):
        if lmode[i] not in modes:
            continue
        if not (commit[i] < receipt[i] and ship[i] < commit[i]
                and year_start <= receipt[i] < year_end):
            continue
        p = prio.get(lkey[i])
        if p is None:
            continue
        hi, lo = out.setdefault(lmode[i], [0, 0])
        if p in ("1-URGENT", "2-HIGH"):
            out[lmode[i]][0] += 1
        else:
            out[lmode[i]][1] += 1
    return out


@func_range("tpch_q12_planned_result")
def tpch_q12_planned_result(orders: Table, lineitem: Table,
                            modes: tuple = ("MAIL", "SHIP"),
                            year_start: int = _Q12_YEAR_START,
                            year_end: int = _Q12_YEAR_END):
    """q12 on the sort-free plan: the l_shipmode GROUP BY key's domain is
    the query's own IN-list (a planner fact, like q1's DDL flag domains),
    so the post-join aggregation lowers to the bounded masked-reduction
    pass — the shipmode strings are dictionary-encoded on device and the
    output keys decode to static strings at trace time. Join unchanged
    (it is the sort-based machinery); the groupby stage carries no sort,
    scan, or scatter (HLO-pinned in tests)."""
    from spark_rapids_jni_tpu.ops import strings as s
    from spark_rapids_jni_tpu.ops.join import apply_join_maps, join
    from spark_rapids_jni_tpu.ops.planner import plan_groupby, string_domain

    mode_c = s.pad_strings(lineitem.column(L12_SHIPMODE))
    keep = _q12_keep(lineitem, mode_c, modes, year_start, year_end)
    probe = Table([
        _null_where(lineitem.column(L12_ORDERKEY), ~keep),
        mode_c,
    ])
    maps = join(probe, orders, 0, 0, out_size=lineitem.num_rows)
    j = apply_join_maps(probe, orders, maps)
    # j: [l_orderkey, l_shipmode, o_orderkey, o_orderpriority]
    matched = j.column(2).valid_mask()
    high, low = _q12_priority_lanes(j.column(3), matched)
    mode_j = j.column(1)
    keyed = Table([
        Column(mode_j.dtype,
               jnp.where(matched, mode_j.data, 0), matched,
               chars=jnp.where(matched[:, None], mode_j.chars,
                               jnp.uint8(0))),
        high, low,
    ])
    return plan_groupby(keyed, keys=[0], aggs=[(1, "sum"), (2, "sum")],
                        domains=[string_domain(modes)])


# ---------------------------------------------------------------------------
# q14 — promotion effect (join + LIKE + global conditional ratio)
# ---------------------------------------------------------------------------

P_PARTKEY, P_TYPE, P_BRAND, P_CONTAINER, P_SIZE = 0, 1, 2, 3, 4

# Six of clause 4.2.3's 150 three-syllable types: a toy for the eager q14 /
# q19 functions and the tests. The served q14 is ``_q14_plan`` over the
# benchmark's makers (``benchmark/tables/part_q14.py``: all 6 x 5 x 5 types
# in the padded layout, 2,000,000 rows in a seeded permutation).
_P_TYPES = ("PROMO BURNISHED COPPER", "PROMO PLATED BRASS",
            "STANDARD POLISHED TIN", "MEDIUM BRUSHED NICKEL",
            "ECONOMY ANODIZED STEEL", "SMALL PLATED COPPER")
_P_BRANDS = ("Brand#11", "Brand#12", "Brand#23", "Brand#34", "Brand#55")
_P_CONTAINERS = ("SM CASE", "SM BOX", "SM PACK", "SM PKG",
                 "MED BAG", "MED BOX", "MED PKG", "MED PACK",
                 "LG CASE", "LG BOX", "LG PACK", "LG PKG")


def part_table(num_rows: int, seed: int = 5) -> Table:
    rng = np.random.default_rng(seed)
    return Table([
        Column.from_numpy(np.arange(1, num_rows + 1, dtype=np.int64)),
        Column.from_pylist(
            [_P_TYPES[i] for i in rng.integers(0, len(_P_TYPES),
                                               num_rows)], t.STRING),
        Column.from_pylist(
            [_P_BRANDS[i] for i in rng.integers(0, len(_P_BRANDS),
                                                num_rows)], t.STRING),
        Column.from_pylist(
            [_P_CONTAINERS[i]
             for i in rng.integers(0, len(_P_CONTAINERS), num_rows)],
            t.STRING),
        Column.from_numpy(rng.integers(1, 51, num_rows).astype(np.int32)),
    ])


# q14/q19 lineitem columns
L14_PARTKEY, L14_EXTENDEDPRICE, L14_DISCOUNT, L14_SHIPDATE = 0, 1, 2, 3


def lineitem_q14_table(num_rows: int, num_parts: int,
                       seed: int = 6) -> Table:
    rng = np.random.default_rng(seed)
    return Table([
        Column.from_numpy(
            rng.integers(1, num_parts + 1, num_rows).astype(np.int64)),
        Column.from_numpy(
            rng.integers(90_000, 10_500_000, num_rows).astype(np.int64),
            t.decimal64(-2)),
        Column.from_numpy(
            rng.integers(0, 11, num_rows).astype(np.int64),
            t.decimal64(-2)),
        Column.from_numpy(
            rng.integers(8400, 10957, num_rows).astype(np.int32),
            t.TIMESTAMP_DAYS),
    ])


_Q14_MONTH_START = 9374  # 1995-09-01
_Q14_MONTH_END = 9404    # 1995-10-01


def _q14_month_where(lineitem: Table, month_start: int,
                     month_end: int) -> jnp.ndarray:
    ship = lineitem.column(L14_SHIPDATE)
    return (ship.valid_mask() & (ship.data >= jnp.int32(month_start))
            & (ship.data < jnp.int32(month_end)))


class Q14Result(NamedTuple):
    promo_revenue: jnp.ndarray   # int64 unscaled decimal(-4)
    total_revenue: jnp.ndarray   # int64 unscaled decimal(-4)
    join_total: jnp.ndarray

    def ratio(self) -> float:
        """100 * promo/total (the published q14 metric), host-side."""
        tot = int(self.total_revenue)
        return 100.0 * int(self.promo_revenue) / tot if tot else 0.0


@func_range("tpch_q14")
def tpch_q14(part: Table, lineitem: Table,
             month_start: int = _Q14_MONTH_START,
             month_end: int = _Q14_MONTH_END) -> Q14Result:
    """q14: shipdate-month lineitem joined to part; promo share of
    revenue. The CASE WHEN p_type LIKE 'PROMO%' lane runs the device
    LIKE engine on the join-gathered strings; revenue stays exact
    int64 decimal(-4) to the end (the q6 posture).

    An eager function a caller picks, op by op. The served q14 is
    ``_q14_plan``: the same query as one ``fusion.Plan`` through
    ``Session.submit``, its join a ``fusion.Join(how="inner")`` with a
    stated capacity (cell ``q14_broadcast_join_fresh``)."""
    from spark_rapids_jni_tpu.ops import strings as s
    from spark_rapids_jni_tpu.ops.join import apply_join_maps, join

    keep = _q14_month_where(lineitem, month_start, month_end)
    price = lineitem.column(L14_EXTENDEDPRICE)
    disc = lineitem.column(L14_DISCOUNT)
    revenue = price.data * (100 - disc.data)   # decimal(-4), exact
    rev_ok = price.valid_mask() & disc.valid_mask() & keep
    probe = Table([
        _null_where(lineitem.column(L14_PARTKEY), ~keep),
    ])
    build = Table([part.column(P_PARTKEY), part.column(P_TYPE)])
    maps = join(probe, build, 0, 0, out_size=lineitem.num_rows)
    # gather the probe-side revenue lanes by the join's left map instead
    # of materializing them as table columns (they are derived, not data)
    li = jnp.clip(maps.left_index, 0, max(lineitem.num_rows - 1, 0))
    j = apply_join_maps(probe, build, maps)
    matched = j.column(1).valid_mask() & maps.row_valid
    rev_j = jnp.where(matched & rev_ok[li], revenue[li], 0)
    promo = s.like(j.column(2), "PROMO%").data != 0
    return Q14Result(
        jnp.sum(jnp.where(promo, rev_j, 0)),
        jnp.sum(rev_j),
        maps.total,
    )


class Q14PlannedResult(NamedTuple):
    promo_revenue: jnp.ndarray   # int64 unscaled decimal(-4)
    total_revenue: jnp.ndarray   # int64 unscaled decimal(-4)
    join_total: jnp.ndarray
    pk_violation: jnp.ndarray    # declared clustered PK was a lie

    def ratio(self) -> float:
        tot = int(self.total_revenue)
        return 100.0 * int(self.promo_revenue) / tot if tot else 0.0


@func_range("tpch_q14_planned")
def tpch_q14_planned(part: Table, lineitem: Table,
                     month_start: int = _Q14_MONTH_START,
                     month_end: int = _Q14_MONTH_END) -> Q14PlannedResult:
    """q14 with the part join as a planner-declared dense clustered PK
    lookup: the WHOLE query compiles sort-free (HLO-pinned) — the join
    is arithmetic + gather, the aggregate is two global masked sums.
    Bonus simplification over the general plan: dense-PK output rows
    are probe-aligned (row i IS lineitem row i), so the revenue lanes
    need no left-map gather at all."""
    from spark_rapids_jni_tpu.ops import strings as s
    from spark_rapids_jni_tpu.ops.planner import dense_pk_join

    keep = _q14_month_where(lineitem, month_start, month_end)
    price = lineitem.column(L14_EXTENDEDPRICE)
    disc = lineitem.column(L14_DISCOUNT)
    revenue = price.data * (100 - disc.data)   # decimal(-4), exact
    rev_ok = price.valid_mask() & disc.valid_mask() & keep
    probe = Table([
        _null_where(lineitem.column(L14_PARTKEY), ~keep),
    ])
    build = Table([part.column(P_PARTKEY),
                   s.pad_strings(part.column(P_TYPE))])
    j = dense_pk_join(probe, build, 0, 0, 1, part.num_rows,
                      clustered=True)
    # j.table: [l_partkey, p_partkey, p_type] — probe-aligned
    matched = j.matched
    rev_j = jnp.where(matched & rev_ok, revenue, 0)
    promo = s.like(j.table.column(2), "PROMO%").data != 0
    return Q14PlannedResult(
        jnp.sum(jnp.where(promo, rev_j, 0)),
        jnp.sum(rev_j),
        j.total,
        j.pk_violation,
    )


def tpch_q14_numpy(part: Table, lineitem: Table,
                   month_start: int = _Q14_MONTH_START,
                   month_end: int = _Q14_MONTH_END) -> tuple:
    ptype = {int(k): v for k, v in zip(
        np.asarray(part.column(P_PARTKEY).data).tolist(),
        part.column(P_TYPE).to_pylist())}
    lkey = np.asarray(lineitem.column(L14_PARTKEY).data).tolist()
    price = np.asarray(lineitem.column(L14_EXTENDEDPRICE).data).tolist()
    disc = np.asarray(lineitem.column(L14_DISCOUNT).data).tolist()
    ship = np.asarray(lineitem.column(L14_SHIPDATE).data).tolist()
    promo = total = 0
    for i in range(lineitem.num_rows):
        if not month_start <= ship[i] < month_end:
            continue
        tp = ptype.get(lkey[i])
        if tp is None:
            continue
        rev = price[i] * (100 - disc[i])
        total += rev
        if tp.startswith("PROMO"):
            promo += rev
    return promo, total


def _q14_part_fn(part: Table) -> Table:
    """What q14 reads of ``part``: the key and ``p_type``."""
    return Table([part.column(P_PARTKEY), part.column(P_TYPE)])


# the joined table: the four lineitem columns, then p_partkey and p_type
_J14_TYPE = L14_SHIPDATE + 1 + P_TYPE


def _q14_lanes_fn(j: Table) -> Table:
    """Above the join, as the query text has it: ``l_extendedprice * (1 -
    l_discount)`` (decimal(-4), exact) over every joined row, and the same
    under ``CASE WHEN p_type LIKE 'PROMO%'`` over the ``p_type`` the join
    brought from ``part``. A row the join did not lay out reads NULL."""
    from spark_rapids_jni_tpu.ops import strings as s

    price = j.column(L14_EXTENDEDPRICE)
    disc = j.column(L14_DISCOUNT)
    ptype = j.column(_J14_TYPE)
    ok = price.valid_mask() & disc.valid_mask()
    revenue = price.data * (100 - disc.data)
    promo = ptype.valid_mask() & (s.like(ptype, "PROMO%").data != 0)
    return Table([
        Column(t.decimal64(-4), jnp.where(promo, revenue, jnp.int64(0)), ok),
        Column(t.decimal64(-4), revenue, ok),
    ])


def _q14_sum_fn(lanes: Table, row_valid) -> Table:
    """The two sums, one row: NULL iff no row was joined (the q6
    posture). The join's output is a row space of its own whose padding
    reads NULL, so ``row_valid`` needs no fold."""
    ok = lanes.column(1).valid_mask()
    any_row = jnp.any(ok).reshape(1)
    return Table([
        Column(t.decimal64(-4),
               jnp.sum(jnp.where(ok, c.data, jnp.int64(0))).reshape(1),
               any_row)
        for c in lanes.columns])


def _q14_plan(month_start: int = _Q14_MONTH_START,
              month_end: int = _Q14_MONTH_END,
              out_rows=None) -> fusion.Plan:
    """TPC-H q14, whole, as one fused region. The plan declares nothing
    about ``l_partkey`` or ``p_partkey`` but their type: the join is the
    general one, ``fusion.Join(how="inner")`` (a lineitem counts once for
    every ``part`` row that holds its key; a NULL key on either side, a
    row the ``WHERE`` dropped and a bucket's padding match nothing), and
    ``tpch_q14_planned``'s dense clustered look-up is what it is not.

    * ``month``: the ``WHERE`` on ``l_shipdate`` as a Filter.
    * ``part_join``: lineitem INNER JOIN part on the part key, laid out
      in ``out_rows`` rows: a capacity the planner states (an int, or a
      ``fusion.rows_of`` spec; by default the lineitem's rows, which a
      unique ``p_partkey`` cannot pass). An inner join with nothing
      declared has no static bound on its output: the node reports
      ``part_join.capacity`` and ``part_join.overflowed``, and the served
      path refuses a result that outgrew it (``CapacityOverflow``).
    * ``p_type`` travels through the join and the ``CASE ... LIKE
      'PROMO%'`` is evaluated above it, over the gathered strings.
    * the two sums: one row, ``promo_revenue`` and ``total_revenue`` as
      exact unscaled int64 of scale -4; the ratio is the caller's."""
    if out_rows is None:
        out_rows = fusion.rows_of("lineitem")
    joined = fusion.Join(
        fusion.Filter(fusion.Scan("lineitem"), _q14_month_where,
                      (int(month_start), int(month_end)), label="month"),
        fusion.Project(fusion.Scan("part"), _q14_part_fn),
        (L14_PARTKEY,), (P_PARTKEY,), out_rows, how="inner",
        label="part_join")
    return fusion.Plan("tpch_q14", fusion.Project(
        fusion.Project(joined, _q14_lanes_fn), _q14_sum_fn, rowwise=False))


# ---------------------------------------------------------------------------
# q19 — discounted revenue (join + OR-of-ANDs compound predicate)
# ---------------------------------------------------------------------------

L19_PARTKEY, L19_QUANTITY, L19_EXTENDEDPRICE = 0, 1, 2
L19_DISCOUNT, L19_SHIPMODE, L19_SHIPINSTRUCT = 3, 4, 5

_Q19_INSTRUCTS = ("DELIVER IN PERSON", "COLLECT COD", "NONE",
                  "TAKE BACK RETURN")


def lineitem_q19_table(num_rows: int, num_parts: int,
                       seed: int = 7) -> Table:
    rng = np.random.default_rng(seed)
    return Table([
        Column.from_numpy(
            rng.integers(1, num_parts + 1, num_rows).astype(np.int64)),
        Column.from_numpy(
            rng.integers(100, 51_00, num_rows).astype(np.int64),
            t.decimal64(-2)),
        Column.from_numpy(
            rng.integers(90_000, 10_500_000, num_rows).astype(np.int64),
            t.decimal64(-2)),
        Column.from_numpy(
            rng.integers(0, 11, num_rows).astype(np.int64),
            t.decimal64(-2)),
        Column.from_pylist(
            ["AIR" if i == 0 else ("AIR REG" if i == 1 else "TRUCK")
             for i in rng.integers(0, 3, num_rows)], t.STRING),
        Column.from_pylist(
            [_Q19_INSTRUCTS[i]
             for i in rng.integers(0, len(_Q19_INSTRUCTS), num_rows)],
            t.STRING),
    ])


# (brand, container prefix, qty_lo in whole units, size_hi)
_Q19_BRANCHES = (
    ("Brand#12", "SM", 1, 5),
    ("Brand#23", "MED", 10, 10),
    ("Brand#34", "LG", 20, 15),
)


class Q19Result(NamedTuple):
    revenue: jnp.ndarray     # int64 unscaled decimal(-4)
    join_total: jnp.ndarray


@func_range("tpch_q19")
def tpch_q19(part: Table, lineitem: Table,
             branches: tuple = _Q19_BRANCHES) -> Q19Result:
    """q19: the OR-of-ANDs predicate over joined lineitem x part —
    every branch is a vectorized mask over join-gathered part columns
    and probe-side lanes; revenue is the exact int64 masked sum."""
    from spark_rapids_jni_tpu.ops import strings as s
    from spark_rapids_jni_tpu.ops.join import apply_join_maps, join

    n = lineitem.num_rows
    probe = Table([lineitem.column(L19_PARTKEY)])
    build = Table([part.column(P_PARTKEY), part.column(P_BRAND),
                   part.column(P_CONTAINER), part.column(P_SIZE)])
    maps = join(probe, build, 0, 0, out_size=n)
    li = jnp.clip(maps.left_index, 0, max(n - 1, 0))
    j = apply_join_maps(probe, build, maps)
    # j: [l_partkey, p_partkey, p_brand, p_container, p_size]
    matched = j.column(1).valid_mask() & maps.row_valid

    qty_c = lineitem.column(L19_QUANTITY)
    price_c = lineitem.column(L19_EXTENDEDPRICE)
    disc_c = lineitem.column(L19_DISCOUNT)
    qty = qty_c.data[li]                              # decimal(-2)
    price = price_c.data[li]
    disc = disc_c.data[li]
    lane_ok = (qty_c.valid_mask() & price_c.valid_mask()
               & disc_c.valid_mask()
               & lineitem.column(L19_SHIPMODE).valid_mask()
               & lineitem.column(L19_SHIPINSTRUCT).valid_mask())[li]
    mode = s.gather_strings(
        s.pad_strings(lineitem.column(L19_SHIPMODE)), li)
    instr = s.gather_strings(
        s.pad_strings(lineitem.column(L19_SHIPINSTRUCT)), li)
    mode_c = Column(t.STRING, mode.data, None, chars=mode.chars)
    instr_c = Column(t.STRING, instr.data, None, chars=instr.chars)

    air = ((s.like(mode_c, "AIR").data != 0)
           | (s.like(mode_c, "AIR REG").data != 0))
    person = s.like(instr_c, "DELIVER IN PERSON").data != 0
    brand_c, cont_c, size = j.column(2), j.column(3), j.column(4).data

    pred = jnp.zeros((j.num_rows,), jnp.bool_)
    for brand, cont_prefix, qty_lo, size_hi in branches:
        b = (s.like(brand_c, brand).data != 0)
        cont = s.like(cont_c, cont_prefix + "%").data != 0
        qlo = jnp.int64(qty_lo * 100)
        qhi = jnp.int64((qty_lo + 10) * 100)
        qok = (qty >= qlo) & (qty <= qhi)
        sok = (size >= 1) & (size <= jnp.int32(size_hi))
        pred = pred | (b & cont & qok & sok)
    pred = pred & air & person & matched & lane_ok
    revenue = jnp.where(pred, price * (100 - disc), 0)
    return Q19Result(jnp.sum(revenue), maps.total)


class Q19PlannedResult(NamedTuple):
    revenue: jnp.ndarray     # int64 unscaled decimal(-4)
    join_total: jnp.ndarray
    pk_violation: jnp.ndarray


@func_range("tpch_q19_planned")
def tpch_q19_planned(part: Table, lineitem: Table,
                     branches: tuple = _Q19_BRANCHES) -> Q19PlannedResult:
    """q19 with the part join as a dense clustered PK lookup: whole
    query sort-free, and the probe-aligned output removes every
    left-map gather the general plan pays for the lineitem lanes
    (qty/price/disc/shipmode/shipinstruct read directly)."""
    from spark_rapids_jni_tpu.ops import strings as s
    from spark_rapids_jni_tpu.ops.planner import dense_pk_join

    probe = Table([lineitem.column(L19_PARTKEY)])
    build = Table([
        part.column(P_PARTKEY),
        s.pad_strings(part.column(P_BRAND)),
        s.pad_strings(part.column(P_CONTAINER)),
        part.column(P_SIZE),
    ])
    j = dense_pk_join(probe, build, 0, 0, 1, part.num_rows,
                      clustered=True)
    # j: [l_partkey, p_partkey, p_brand, p_container, p_size] — row i
    # IS lineitem row i
    matched = j.matched

    qty_c = lineitem.column(L19_QUANTITY)
    price_c = lineitem.column(L19_EXTENDEDPRICE)
    disc_c = lineitem.column(L19_DISCOUNT)
    lane_ok = (qty_c.valid_mask() & price_c.valid_mask()
               & disc_c.valid_mask()
               & lineitem.column(L19_SHIPMODE).valid_mask()
               & lineitem.column(L19_SHIPINSTRUCT).valid_mask())
    mode_c = s.pad_strings(lineitem.column(L19_SHIPMODE))
    instr_c = s.pad_strings(lineitem.column(L19_SHIPINSTRUCT))

    air = ((s.like(mode_c, "AIR").data != 0)
           | (s.like(mode_c, "AIR REG").data != 0))
    person = s.like(instr_c, "DELIVER IN PERSON").data != 0
    brand_c = j.table.column(2)
    cont_c = j.table.column(3)
    size = j.table.column(4).data

    pred = jnp.zeros((lineitem.num_rows,), jnp.bool_)
    for brand, cont_prefix, qty_lo, size_hi in branches:
        b = (s.like(brand_c, brand).data != 0)
        cont = s.like(cont_c, cont_prefix + "%").data != 0
        qlo = jnp.int64(qty_lo * 100)
        qhi = jnp.int64((qty_lo + 10) * 100)
        qok = (qty_c.data >= qlo) & (qty_c.data <= qhi)
        sok = (size >= 1) & (size <= jnp.int32(size_hi))
        pred = pred | (b & cont & qok & sok)
    pred = pred & air & person & matched & lane_ok
    revenue = jnp.where(pred, price_c.data * (100 - disc_c.data), 0)
    return Q19PlannedResult(jnp.sum(revenue), j.total, j.pk_violation)


def tpch_q19_numpy(part: Table, lineitem: Table,
                   branches: tuple = _Q19_BRANCHES) -> int:
    pinfo = {}
    pk = np.asarray(part.column(P_PARTKEY).data).tolist()
    pb = part.column(P_BRAND).to_pylist()
    pc = part.column(P_CONTAINER).to_pylist()
    ps = np.asarray(part.column(P_SIZE).data).tolist()
    for i in range(part.num_rows):
        pinfo[pk[i]] = (pb[i], pc[i], ps[i])
    lkey = np.asarray(lineitem.column(L19_PARTKEY).data).tolist()
    qty = np.asarray(lineitem.column(L19_QUANTITY).data).tolist()
    price = np.asarray(lineitem.column(L19_EXTENDEDPRICE).data).tolist()
    disc = np.asarray(lineitem.column(L19_DISCOUNT).data).tolist()
    mode = lineitem.column(L19_SHIPMODE).to_pylist()
    instr = lineitem.column(L19_SHIPINSTRUCT).to_pylist()
    total = 0
    for i in range(lineitem.num_rows):
        info = pinfo.get(lkey[i])
        if info is None:
            continue
        if mode[i] not in ("AIR", "AIR REG"):
            continue
        if instr[i] != "DELIVER IN PERSON":
            continue
        ok = False
        for brand, cont_prefix, qty_lo, size_hi in branches:
            if (info[0] == brand and info[1].startswith(cont_prefix)
                    and qty_lo * 100 <= qty[i] <= (qty_lo + 10) * 100
                    and 1 <= info[2] <= size_hi):
                ok = True
                break
        if ok:
            total += price[i] * (100 - disc[i])
    return total


_Q12_GROUP_BUDGET = 16  # |shipmode domain| = 7 plus the null pseudo-group


def tpch_q12_distributed(orders: Table, lineitem: Table, mesh,
                         modes: tuple = ("MAIL", "SHIP"),
                         year_start: int = _Q12_YEAR_START,
                         year_end: int = _Q12_YEAR_END) -> Table:
    """Multi-executor q12: repartitioned orderkey join, then the classic
    two-phase aggregation — per-device partial groupby on the (tiny)
    shipmode domain, partial rows shuffled by key hash, merged, collected
    and shipmode-sorted on the driver. The partial->shuffle->merge shape
    is the q1 distributed plan; the join is the q3 repartition exchange —
    q12 composes both."""
    import jax as _jax
    from jax.sharding import PartitionSpec as P

    from spark_rapids_jni_tpu.ops import strings as s
    from spark_rapids_jni_tpu.parallel.distributed import (
        collect,
        distributed_join,
        shard_table,
    )
    from spark_rapids_jni_tpu.parallel.mesh import EXEC_AXIS
    from spark_rapids_jni_tpu.parallel.shuffle import hash_shuffle

    if len(modes) + 1 > _Q12_GROUP_BUDGET:
        raise ValueError(
            f"q12 mode domain {len(modes)} exceeds the partial-groupby "
            f"budget {_Q12_GROUP_BUDGET}")
    # WHERE -> nulled join key (shared predicate helper, single change
    # point with the single-device plan)
    mode_c = s.pad_strings(lineitem.column(L12_SHIPMODE))
    keep = _q12_keep(lineitem, mode_c, modes, year_start, year_end)
    probe = Table([
        _null_where(lineitem.column(L12_ORDERKEY), ~keep),
        mode_c,
    ])
    build = Table([
        orders.column(O12_ORDERKEY),
        s.pad_strings(orders.column(O12_ORDERPRIORITY)),
    ])
    sl, lrv = shard_table(probe, mesh, return_row_valid=True)
    sr, rrv = shard_table(build, mesh, return_row_valid=True)
    nl = probe.num_rows
    d = mesh.devices.size
    # per-device capacities (the q3 sizing): 2x skew headroom; overflow
    # is checked below and is the caller's retry signal
    res = distributed_join(
        sl, sr, [0], [0], mesh,
        out_size_per_device=max(1, nl // d * 2),
        left_capacity=max(1, nl // d * 2),
        right_capacity=max(1, orders.num_rows // d * 2),
        left_row_valid=lrv, right_row_valid=rrv,
    )
    if bool(np.asarray(res.overflowed).any()):
        raise ValueError(
            "q12 join exchange overflowed its per-device capacity "
            "(key skew); retry with a larger capacity factor")

    def agg_step(j: Table):
        # j: [l_orderkey, l_shipmode, o_orderkey, o_orderpriority]
        matched = j.column(2).valid_mask()
        high, low = _q12_priority_lanes(j.column(3), matched)
        mode_j = j.column(1)
        keyed = Table([
            Column(mode_j.dtype,
                   jnp.where(matched, mode_j.data, 0),
                   matched,
                   chars=jnp.where(matched[:, None], mode_j.chars,
                                   jnp.uint8(0))),
            high, low,
        ])
        budget = min(_Q12_GROUP_BUDGET, keyed.num_rows)
        partial = groupby_aggregate(
            keyed, keys=[0], aggs=[(1, "sum"), (2, "sum")],
            max_groups=budget)
        real = jnp.arange(budget, dtype=jnp.int32) < partial.num_groups
        sh = hash_shuffle(partial.table, [0], EXEC_AXIS, capacity=budget,
                          row_valid=real)
        merged = groupby_aggregate(
            sh.table, keys=[0], aggs=[(1, "sum"), (2, "sum")])
        return merged.table, merged.num_groups.reshape(1)

    per_dev, num_groups = _jax.jit(_jax.shard_map(
        agg_step, mesh=mesh, in_specs=(P(EXEC_AXIS),),
        out_specs=(P(EXEC_AXIS), P(EXEC_AXIS)),
    ))(res.table)
    result = collect(per_dev, num_groups, mesh)
    srt = sort_table(result, [0], nulls_first=[False])
    kv = np.asarray(srt.column(0).valid_mask())
    k = int(kv.sum())
    from spark_rapids_jni_tpu.ops.table_ops import trim_table

    return trim_table(srt, k)


# ---------------------------------------------------------------------------
# q4 — order priority checking (EXISTS -> left-semi join + groupby)
# ---------------------------------------------------------------------------

# q4 orders columns
O4_ORDERKEY, O4_ORDERDATE, O4_ORDERPRIORITY = 0, 1, 2
_Q4_QTR_START = 8582   # 1993-07-01
_Q4_QTR_END = 8674     # 1993-10-01


def orders_q4_table(num_rows: int, seed: int = 8) -> Table:
    rng = np.random.default_rng(seed)
    return Table([
        Column.from_numpy(np.arange(1, num_rows + 1, dtype=np.int64)),
        Column.from_numpy(
            rng.integers(8400, 8800, num_rows).astype(np.int32),
            t.TIMESTAMP_DAYS),
        Column.from_pylist(
            [_Q12_PRIORITIES[i]
             for i in rng.integers(0, len(_Q12_PRIORITIES), num_rows)],
            t.STRING),
    ])


class Q4Result(NamedTuple):
    result: GroupByResult   # [o_orderpriority, order_count]
    join_total: jnp.ndarray


@func_range("tpch_q4")
def tpch_q4(orders: Table, lineitem: Table,
            qtr_start: int = _Q4_QTR_START,
            qtr_end: int = _Q4_QTR_END) -> Q4Result:
    """q4: orders in the quarter with EXISTS(lineitem late delivery),
    counted per priority — the EXISTS lowers to a LEFT-SEMI join (the
    round-4 join surface), then a string-key groupby."""
    from spark_rapids_jni_tpu.ops.join import apply_join_maps, join

    od = orders.column(O4_ORDERDATE)
    keep_o = (od.valid_mask()
              & (od.data >= jnp.int32(qtr_start))
              & (od.data < jnp.int32(qtr_end)))
    probe = Table([
        _null_where(orders.column(O4_ORDERKEY), ~keep_o),
        orders.column(O4_ORDERPRIORITY),
    ])
    commit_c = lineitem.column(L12_COMMITDATE)
    receipt_c = lineitem.column(L12_RECEIPTDATE)
    late = (commit_c.valid_mask() & receipt_c.valid_mask()
            & (commit_c.data < receipt_c.data))
    build = Table([
        _null_where(lineitem.column(L12_ORDERKEY), ~late),
    ])
    maps = join(probe, build, 0, 0, out_size=orders.num_rows,
                how="left_semi")
    j = apply_join_maps(probe, build, maps)
    matched = maps.row_valid
    keyed = Table([
        _null_where(j.column(1), ~matched),
        Column(t.INT64, jnp.where(matched, jnp.int64(1), jnp.int64(0)),
               matched),
    ])
    g = groupby_aggregate(keyed, keys=[0], aggs=[(1, "sum")])
    srt = sort_table(g.table, [0], nulls_first=[False])
    return Q4Result(GroupByResult(srt, g.num_groups), maps.total)


def tpch_q4_numpy(orders: Table, lineitem: Table,
                  qtr_start: int = _Q4_QTR_START,
                  qtr_end: int = _Q4_QTR_END) -> dict:
    late_keys = set()
    lkey = np.asarray(lineitem.column(L12_ORDERKEY).data).tolist()
    commit = np.asarray(lineitem.column(L12_COMMITDATE).data).tolist()
    receipt = np.asarray(lineitem.column(L12_RECEIPTDATE).data).tolist()
    for i in range(lineitem.num_rows):
        if commit[i] < receipt[i]:
            late_keys.add(lkey[i])
    out: dict = {}
    okey = np.asarray(orders.column(O4_ORDERKEY).data).tolist()
    odate = np.asarray(orders.column(O4_ORDERDATE).data).tolist()
    prio = orders.column(O4_ORDERPRIORITY).to_pylist()
    for i in range(orders.num_rows):
        if not qtr_start <= odate[i] < qtr_end:
            continue
        if okey[i] in late_keys:
            out[prio[i]] = out.get(prio[i], 0) + 1
    return out


@func_range("tpch_q4_planned_result")
def tpch_q4_planned_result(orders: Table, lineitem: Table,
                           qtr_start: int = _Q4_QTR_START,
                           qtr_end: int = _Q4_QTR_END):
    """q4 on the sort-free plan: o_orderpriority is a 5-value DDL enum
    ('1-URGENT'..'5-LOW' — the dictionary a real planner reads from
    column stats), so the post-semi-join COUNT(*) GROUP BY lowers to the
    bounded masked-reduction pass with on-device dictionary encoding.
    The EXISTS stays a LEFT-SEMI join; only the aggregation changes."""
    from spark_rapids_jni_tpu.ops import strings as s
    from spark_rapids_jni_tpu.ops.join import apply_join_maps, join
    from spark_rapids_jni_tpu.ops.planner import plan_groupby, string_domain

    od = orders.column(O4_ORDERDATE)
    keep_o = (od.valid_mask()
              & (od.data >= jnp.int32(qtr_start))
              & (od.data < jnp.int32(qtr_end)))
    prio_c = s.pad_strings(orders.column(O4_ORDERPRIORITY))
    probe = Table([
        _null_where(orders.column(O4_ORDERKEY), ~keep_o),
        prio_c,
    ])
    commit_c = lineitem.column(L12_COMMITDATE)
    receipt_c = lineitem.column(L12_RECEIPTDATE)
    late = (commit_c.valid_mask() & receipt_c.valid_mask()
            & (commit_c.data < receipt_c.data))
    build = Table([
        _null_where(lineitem.column(L12_ORDERKEY), ~late),
    ])
    maps = join(probe, build, 0, 0, out_size=orders.num_rows,
                how="left_semi")
    j = apply_join_maps(probe, build, maps)
    matched = maps.row_valid
    prio_j = j.column(1)
    keyed = Table([
        Column(prio_j.dtype,
               jnp.where(matched, prio_j.data, 0), matched,
               chars=jnp.where(matched[:, None], prio_j.chars,
                               jnp.uint8(0))),
        Column(t.INT64, jnp.where(matched, jnp.int64(1), jnp.int64(0)),
               matched),
    ])
    return plan_groupby(keyed, keys=[0], aggs=[(1, "sum")],
                        domains=[string_domain(_Q12_PRIORITIES)])


# ---- TPC-H q4 whole as one served Plan ------------------------------------
#
#   SELECT o_orderpriority, count(*) AS order_count
#   FROM orders
#   WHERE o_orderdate >= date ':1' AND o_orderdate < date ':1' + 3 months
#     AND EXISTS (SELECT * FROM lineitem
#                 WHERE l_orderkey = o_orderkey
#                   AND l_commitdate < l_receiptdate)
#   GROUP BY o_orderpriority ORDER BY o_orderpriority
#
# over its own two tables: orders (o_orderkey, o_orderdate, o_orderpriority
# CHAR(15) in the padded layout) and lineitem (l_orderkey, l_commitdate,
# l_receiptdate). ``tpch_q4`` / ``tpch_q4_planned_result`` above stay the
# eager pipelines over the q12 tables (Arrow-layout strings, the maps-based
# join): what this plan's semi join is held against.

L4_ORDERKEY, L4_COMMITDATE, L4_RECEIPTDATE = 0, 1, 2


def _q4_orders_where(orders: Table, qtr_start: int,
                     qtr_end: int) -> jnp.ndarray:
    od = orders.column(O4_ORDERDATE)
    return (od.valid_mask() & (od.data >= jnp.int32(qtr_start))
            & (od.data < jnp.int32(qtr_end)))


def _q4_late_where(lineitem: Table) -> jnp.ndarray:
    commit = lineitem.column(L4_COMMITDATE)
    receipt = lineitem.column(L4_RECEIPTDATE)
    return (commit.valid_mask() & receipt.valid_mask()
            & (commit.data < receipt.data))


def _q4_plan(qtr_start: int = _Q4_QTR_START,
             qtr_end: int = _Q4_QTR_END) -> fusion.Plan:
    """TPC-H q4, whole, as one fused region. The plan declares nothing
    about ``o_orderkey`` or ``l_orderkey`` but their type: the ``EXISTS``
    is the general join, ``how="left_semi"`` (an order counts once however
    many of its lineitems are late; a NULL key on either side, a row a
    ``WHERE`` dropped and a bucket's padding match nothing).

    * ``quarter`` / ``late``: the two ``WHERE``s as Filters.
    * ``exists``: orders LEFT SEMI JOIN lineitem on the order key; the
      orders stay where they lie under a row mask.
    * ``groupby``: ``count(*)`` by the string column ``o_orderpriority``
      under the five values the DDL states (clause 4.2.2.13), so the
      bounded lowering: a row the join dropped reads a NULL priority and
      counts in the null slot, which is no group of the answer. A priority
      outside the five sets ``groupby.domain_miss``.
    * the ORDER BY, over the six slots."""
    from spark_rapids_jni_tpu.ops.planner import string_domain

    exists = fusion.Join(
        fusion.Filter(fusion.Scan("orders"), _q4_orders_where,
                      (int(qtr_start), int(qtr_end)), label="quarter"),
        fusion.Filter(fusion.Scan("lineitem"), _q4_late_where, label="late"),
        (O4_ORDERKEY,), (L4_ORDERKEY,), None, how="left_semi",
        label="exists")
    counts = fusion.GroupBy(
        exists, (O4_ORDERPRIORITY,), ((O4_ORDERKEY, "count"),),
        domains=(string_domain(_Q12_PRIORITIES),), label="groupby")
    return fusion.Plan("tpch_q4", fusion.Sort(
        counts, (0,), nulls_first=(False,)))


# ---------------------------------------------------------------------------
# q17 — small-quantity-order revenue (correlated AVG subquery ->
# groupby mean + join + filtered exact sum)
# ---------------------------------------------------------------------------


class Q17Result(NamedTuple):
    yearly_total: jnp.ndarray    # int64 unscaled decimal(-2) * 10 (sum/0.7... see ratio)
    join_total: jnp.ndarray

    def avg_yearly(self) -> float:
        """sum(l_extendedprice)/7.0 in display units."""
        return int(self.yearly_total) / 100.0 / 7.0


@func_range("tpch_q17")
def tpch_q17(part: Table, lineitem: Table,
             brand: str = "Brand#23", container: str = "MED BOX") -> Q17Result:
    """q17: lineitem x part filtered to one brand/container, keeping rows
    with l_quantity < 0.2 * avg(l_quantity) OVER the part — the
    correlated subquery lowers to a per-part groupby mean joined back
    (two joins on partkey share the rank encoding), then an exact sum."""
    from spark_rapids_jni_tpu.ops import strings as s
    from spark_rapids_jni_tpu.ops.join import apply_join_maps, join

    sel_part = ((s.like(part.column(P_BRAND), brand).data != 0)
                & (s.like(part.column(P_CONTAINER), container).data != 0)
                & part.column(P_PARTKEY).valid_mask())
    build = Table([
        _null_where(part.column(P_PARTKEY), ~sel_part),
    ])
    n = lineitem.num_rows
    probe = Table([lineitem.column(L19_PARTKEY)])
    maps = join(probe, build, 0, 0, out_size=n)
    li = jnp.clip(maps.left_index, 0, max(n - 1, 0))
    j = apply_join_maps(probe, build, maps)
    matched = j.column(1).valid_mask() & maps.row_valid

    qty_c = lineitem.column(L19_QUANTITY)
    price_c = lineitem.column(L19_EXTENDEDPRICE)
    qty = qty_c.data[li]
    price = price_c.data[li]
    # the correlated AVG(l_quantity) is over every selected row with a
    # non-null QUANTITY — price nulls only drop rows from the final sum
    avg_ok = qty_c.valid_mask()[li] & matched

    # per-part avg quantity over the SELECTED rows: groupby mean on the
    # joined rows (keys = partkey), then gathered back via a second
    # join (the correlated-subquery lowering)
    keyed = Table([
        _null_where(Column(j.column(0).dtype, j.column(0).data,
                           j.column(0).valid_mask()), ~avg_ok),
        Column(qty_c.dtype, qty, avg_ok),
    ])
    g = groupby_aggregate(keyed, keys=[0], aggs=[(1, "mean")])
    # map each row to its group's mean: join rows back on partkey
    gt = g.table
    m2 = join(keyed, gt, 0, 0, out_size=n)
    li2 = jnp.clip(m2.left_index, 0, max(n - 1, 0))
    j2 = apply_join_maps(keyed, gt, m2)
    # j2: [l_partkey, l_quantity, g_partkey, g_mean]
    ok2 = j2.column(2).valid_mask() & m2.row_valid
    q2 = j2.column(1)
    mean2 = j2.column(3)
    # l_quantity < 0.2 * avg: quantity is decimal(-2) -> value*100;
    # mean is FLOAT64 in VALUE units
    pred = (q2.data.astype(jnp.float64)
            < 0.2 * mean2.data * 100.0) & ok2 & q2.valid_mask()
    price2 = price_c.data[li][li2]
    price_ok = price_c.valid_mask()[li][li2]
    total = jnp.sum(jnp.where(pred & price_ok, price2, 0))
    return Q17Result(total, maps.total)


def tpch_q17_numpy(part: Table, lineitem: Table,
                   brand: str = "Brand#23",
                   container: str = "MED BOX") -> int:
    sel = set()
    pk = np.asarray(part.column(P_PARTKEY).data).tolist()
    pb = part.column(P_BRAND).to_pylist()
    pc = part.column(P_CONTAINER).to_pylist()
    for i in range(part.num_rows):
        if pb[i] == brand and pc[i] == container:
            sel.add(pk[i])
    lkey = np.asarray(lineitem.column(L19_PARTKEY).data).tolist()
    qty = np.asarray(lineitem.column(L19_QUANTITY).data).tolist()
    price = np.asarray(lineitem.column(L19_EXTENDEDPRICE).data).tolist()
    by_part: dict = {}
    for i in range(lineitem.num_rows):
        if lkey[i] in sel:
            by_part.setdefault(lkey[i], []).append(i)
    total = 0
    for k, rows in by_part.items():
        avg = sum(qty[i] for i in rows) / len(rows)
        for i in rows:
            if qty[i] < 0.2 * avg:
                total += price[i]
    return total


# ---- TPC-H q13-shaped customer-key aggregation: the general-cardinality ----
# distributed groupby over the exchange
#
#   SELECT o_custkey, count(o_orderkey) FROM orders GROUP BY o_custkey
#
# The inner aggregation of q13 (customer distribution): order counts per
# customer key. Customer keys are HIGH cardinality — no slot table, no
# domain plan, no psum merge can cover them — which is exactly the query
# shape the bounded-slot distributed plans could not run. The distributed
# form is partial-counts per shard -> hash-partitioned all-to-all exchange
# by custkey (runtime/exchange.py) -> per-destination sum-merge; the merge
# algebra is re-applicable (sum of counts), so the exchange's spill-aware
# chunked merge composes with it unchanged.


def q13_partial_plan() -> fusion.Plan:
    """Per-shard q13 partial: order counts per customer key, general
    cardinality (``max_groups=None`` pads to the shard's row count and
    can never overflow — no static slot table)."""
    return fusion.Plan("tpch_q13_partial", fusion.GroupBy(
        fusion.Scan("orders"), (O_CUSTKEY,), ((O_ORDERKEY, "count"),),
        max_groups=None, label="partial"))


def q13_merge_plan() -> fusion.Plan:
    """Per-destination q13 merge: sum the partial counts per customer
    key — re-applicable (``merge(merge(a) + merge(b)) == merge(a + b)``),
    the property the exchange's chunked spill merge relies on."""
    return fusion.Plan("tpch_q13_merge", fusion.GroupBy(
        fusion.Scan("partials"), (0,), ((1, "sum"),),
        max_groups=None, label="merge"))


def q13_exchange_plans(parts: int):
    """The (pack_plan, merge_plan) pair for the distributed q13-shaped
    aggregation: the pack plan roots an ``Exchange`` node over the
    partial (keys = the custkey output column, ``valid_meta`` trims the
    unbounded groupby's padding before any row rides the wire); the
    merge plan scans ``partials``. Drive through
    ``QueryCluster.submit_exchange`` — or locally via
    :func:`tpch_q13_local`, which is the bit-identity oracle."""
    pack = fusion.Plan("tpch_q13_pack", fusion.Exchange(
        q13_partial_plan().root, keys=(0,), parts=int(parts),
        valid_meta="partial.num_groups", label="exchange"))
    return pack, q13_merge_plan()


def q13_midplan_plan(parts: int) -> fusion.Plan:
    """The q13-shaped aggregation as ONE plan with a planner-placed
    interior ``Exchange``: partial groupby -> exchange by custkey ->
    sum-merge, the region -> exchange -> region shape
    ``fusion.split_at_exchange`` breaks into exactly the hand-split
    (pack, merge) plan pair of :func:`q13_exchange_plans`. ``parts=0``
    defers the partition count to the learned-selectivity store
    (``exchange.choose_parts``)."""
    return fusion.Plan("tpch_q13_midplan", fusion.GroupBy(
        fusion.Exchange(
            q13_partial_plan().root, keys=(0,), parts=int(parts),
            valid_meta="partial.num_groups", label="exchange"),
        (0,), ((1, "sum"),), max_groups=None, label="merge"))


def tpch_q13_local(orders: Table, parts: int = 1, *,
                   shard_keys=(O_ORDERKEY,)) -> Table:
    """Single-host oracle for the distributed q13-shaped aggregation:
    the SAME plans over the SAME shard split (``shard_keys`` must match
    the cluster's ``register_table`` keys) and the same
    source-then-flight regroup order — bit-identical to what
    ``submit_exchange(...).result()`` returns over a live mesh."""
    from spark_rapids_jni_tpu.ops.table_ops import _slice_rows, concatenate
    from spark_rapids_jni_tpu.parallel import dcn
    from spark_rapids_jni_tpu.runtime import exchange as xch

    parts = int(parts)
    pack, merge = q13_exchange_plans(parts)
    shards = (dcn.partition_for_slices(orders, list(shard_keys), parts)
              if parts > 1 else [orders])
    per_dest: list = [[] for _ in range(parts)]
    empty = None
    for shard in shards:
        fused = fusion.execute(pack, {"orders": shard})
        rc = fused.meta["exchange.row_counts"]
        empty = _slice_rows(fused.table, 0, 0)
        for p, fls in enumerate(xch.split_wire(fused.table, rc, parts)):
            per_dest[p].extend(fls)
    outs = []
    for flights in per_dest:
        if not flights:
            continue
        dest_in = (flights[0] if len(flights) == 1
                   else concatenate(flights))
        res = fusion.execute(merge, {"partials": dest_in})
        outs.append(_slice_rows(
            res.table, 0, int(np.asarray(res.meta["merge.num_groups"]))))
    if not outs:
        res = fusion.execute(merge, {"partials": empty})
        return _slice_rows(res.table, 0, 0)
    return outs[0] if len(outs) == 1 else concatenate(outs)


def tpch_q13_reference(orders: Table) -> Table:
    """Naive single-pass reference (one global groupby): the value-level
    check behind the oracle — same groups and counts as
    :func:`tpch_q13_local` up to row order."""
    from spark_rapids_jni_tpu.ops.table_ops import trim_table

    g = groupby_aggregate(orders, [O_CUSTKEY], [(O_ORDERKEY, "count")],
                          max_groups=None)
    return trim_table(g.table, int(np.asarray(g.num_groups)))


# ---- TPC-H q13 whole (customer distribution) as one served Plan -----------
#
#   SELECT c_count, count(*) AS custdist
#   FROM (SELECT c_custkey, count(o_orderkey)
#         FROM customer LEFT OUTER JOIN orders
#              ON c_custkey = o_custkey
#             AND o_comment NOT LIKE '%:word1%:word2%'
#         GROUP BY c_custkey) AS c_orders (c_custkey, c_count)
#   GROUP BY c_count ORDER BY custdist DESC, c_count DESC
#
# The q13-shaped plans above leave the LIKE and the outer join out (they
# are the cluster's exchange workload and stay as they are). This is the
# query, over its own two tables.

# q13 orders columns: the three the query reads; o_comment in the padded
# layout (VARCHAR(79): chars uint8[n, 79])
O13_ORDERKEY, O13_CUSTKEY, O13_COMMENT = 0, 1, 2
# The bound of the outer groupby: the distinct values of c_count. No
# planner's fact (a customer may hold any number of orders), so it is a
# budget with ``overflowed`` as its guard, like q1's: a customer of dbgen's
# holds at most 41 orders at any scale factor, and 1,024 is the largest
# bound at which the groupby takes its counts over the rows where they lie
# (``ops/groupby.py _SMALL_M``).
_Q13_DIST_BUDGET = 1024


def _q13_where(orders: Table, pattern: str) -> jnp.ndarray:
    """``o_comment NOT LIKE pattern`` as the join's condition on orders:
    three-valued, so an order whose comment is NULL is not kept either."""
    from spark_rapids_jni_tpu.ops import strings as s

    comment = orders.column(O13_COMMENT)
    return comment.valid_mask() & (s.like(comment, pattern).data == 0)


def _q13_cust_fn(customer: Table) -> Table:
    """The preserved side of the outer join: the customer's key alone."""
    return Table([customer.column(C_CUSTKEY)])


def _q13_counts_fn(j: Table) -> Table:
    """The outer join's output as ``c_orders(c_count, c_custkey)``: a
    customer no order joined reads a NULL count there, and
    ``count(o_orderkey)`` over such a group is 0."""
    # j: [c_custkey, o_custkey (the group's key), count(o_orderkey)]
    ckey, cnt = j.column(0), j.column(2)
    return Table([
        Column(cnt.dtype,
               jnp.where(cnt.valid_mask(), cnt.data, jnp.int64(0)),
               ckey.valid_mask()),
        ckey,
    ])


def _q13_plan(word1: str = "special", word2: str = "requests") -> fusion.Plan:
    """TPC-H q13, whole, as one fused region. What the planner declares
    is what ``_q3_planned_plan`` does: ``c_custkey`` is customer's dense
    primary key, clustered 1..|customer| in load order, and ``o_custkey``
    is a foreign key into it.

    * ``where``: the join's condition on orders alone, ``o_comment NOT
      LIKE '%word1%word2%'`` (``ops/strings.py like``, in row blocks), as a
      Filter: an order it drops keeps its row and loses its validity.
    * ``c_orders``: ``count(o_orderkey)`` by ``o_custkey`` over the kept
      orders, which is the aggregate of the outer join pushed below it (the
      join's key is the preserved side's primary key, so a group of the
      join is one customer's orders): the sort path under the key's
      declared range, at most |customer| groups and the null group of the
      dropped rows.
    * ``outer``: customer LEFT OUTER JOIN those groups. The preserved side
      is the table laid out by the key, so every group writes its row
      number into its customer's slot and every customer reads its own
      (``dense_pk_join`` ``probe_clustered``): no sort, no search. A
      customer no group wrote to reads NULL, and its count is 0.
    * ``custdist``: ``count(*)`` by ``c_count``, a small bound over an
      integer key, then the ORDER BY.

    A declaration that does not hold (an ``o_custkey`` outside 1..|customer|:
    ``c_orders.key_out_of_range``; a customer row that is not at its key's
    place: ``outer.pk_violation``; more distinct counts than the budget:
    ``custdist.overflowed``) is in the result's meta, and the served path
    refuses the result."""
    pattern = f"%{word1}%{word2}%"
    kept = fusion.Filter(fusion.Scan("orders"), _q13_where, (pattern,),
                         label="where", like_columns=(O13_COMMENT,))
    c_orders = fusion.GroupBy(
        kept, (O13_CUSTKEY,), ((O13_ORDERKEY, "count"),),
        max_groups=fusion.groups_of("customer"), label="c_orders",
        key_ranges=((1, fusion.rows_of("customer")),))
    cust = fusion.Project(fusion.Scan("customer", bucket=False),
                          _q13_cust_fn)
    outer = fusion.DensePkJoin(cust, c_orders, 0, 0, 1,
                               fusion.rows_of("customer"),
                               probe_clustered=True, label="outer")
    dist = fusion.GroupBy(fusion.Project(outer, _q13_counts_fn), (0,),
                          ((1, "count"),), max_groups=_Q13_DIST_BUDGET,
                          label="custdist")
    return fusion.Plan("tpch_q13", fusion.Sort(
        dist, (1, 0), ascending=(False, False),
        nulls_first=(False, False)))


def tpch_q13_numpy(customer: Table, orders: Table,
                   word1: str = "special", word2: str = "requests") -> list:
    """Host oracle for q13: ``[(c_count, custdist)]`` in the query's order,
    by Python's ``re`` and ``collections.Counter`` over the host copies."""
    import collections
    import re

    pat = re.compile(re.escape(word1) + ".*" + re.escape(word2), re.S)
    comments = orders.column(O13_COMMENT).to_pylist()
    okeys = orders.column(O13_ORDERKEY).to_pylist()
    ckeys = orders.column(O13_CUSTKEY).to_pylist()
    per_customer = collections.Counter()
    for okey, ckey, text in zip(okeys, ckeys, comments):
        if text is None or pat.search(text) or ckey is None:
            continue
        per_customer[ckey] += okey is not None
    dist = collections.Counter(
        per_customer.get(k, 0)
        for k in customer.column(C_CUSTKEY).to_pylist())
    return sorted(dist.items(), key=lambda kv: (-kv[1], -kv[0]))


# ---- TPC-H q18 whole (large volume customer) as one served Plan -----------
#
#   SELECT c_name, c_custkey, o_orderkey, o_orderdate, o_totalprice,
#          sum(l_quantity)
#   FROM customer, orders, lineitem
#   WHERE o_orderkey IN (SELECT l_orderkey FROM lineitem
#                        GROUP BY l_orderkey HAVING sum(l_quantity) > 300)
#     AND c_custkey = o_custkey AND o_orderkey = l_orderkey
#   GROUP BY c_name, c_custkey, o_orderkey, o_orderdate, o_totalprice
#   ORDER BY o_totalprice DESC, o_orderdate LIMIT 100
#
# over its own three tables: lineitem (l_orderkey, l_quantity DECIMAL(15,2)),
# orders (o_orderkey, o_custkey, o_orderdate, o_totalprice DECIMAL(15,2))
# and customer (c_custkey, c_name VARCHAR(25) in the padded layout).

L18_ORDERKEY, L18_QUANTITY = 0, 1
O18_ORDERKEY, O18_CUSTKEY, O18_ORDERDATE, O18_TOTALPRICE = 0, 1, 2, 3
C18_CUSTKEY, C18_NAME = 0, 1
# item_join's output: the lineitem's two columns, then cust_join's six
# (the orders' four, then the customer's two)
_J18_QUANTITY = L18_QUANTITY
_J18_ORDERKEY, _J18_ORDERDATE, _J18_TOTALPRICE = 2, 4, 5
_J18_CUSTKEY, _J18_NAME = 6, 7
# the answer's columns, as the SELECT lists them
Q18_NAME, Q18_CUSTKEY, Q18_ORDERKEY, Q18_ORDERDATE = 0, 1, 2, 3
Q18_TOTALPRICE, Q18_QUANTITY = 4, 5

_Q18_QUANTITY = 300
_Q18_LIMIT = 100
_Q18_OUT_ROWS = 1 << 16


def _q18_having(groups: Table, threshold: int) -> jnp.ndarray:
    """``HAVING sum(l_quantity) > threshold`` over ``order_qty``'s output
    (the key, then the sum): strictly greater, and a NULL sum (a group of
    NULL quantities, a row past the groups) is not kept."""
    total = groups.column(1)
    return total.valid_mask() & (total.data > jnp.int64(threshold))


def _q18_plan(quantity: int = _Q18_QUANTITY, out_rows: int = _Q18_OUT_ROWS,
              limit: int = _Q18_LIMIT) -> fusion.Plan:
    """TPC-H q18, whole, as one fused region, as the query text has it.
    The plan declares ONE thing about any key: ``l_orderkey`` is a foreign
    key into orders (clause 1.4.2), so ``order_qty`` holds at most
    |orders| groups and the null group (``fusion.groups_of``); a lineitem
    batch that breaks that sets ``order_qty.overflowed`` and the served
    path refuses the result. No range, density, clustering or uniqueness
    of ``l_orderkey``, ``o_orderkey``, ``o_custkey`` or ``c_custkey``.

    * ``order_qty``: ``sum(l_quantity)`` by ``l_orderkey`` over every
      lineitem: the general sort path with a 64-bit key nobody declared a
      range for, a row in four a group.
    * ``having``: ``sum > quantity`` (DECIMAL(15,2) as unscaled int64) as a
      ``Filter`` over the groups; ``having.rows_in`` counts the groups.
    * ``in_heavy``: ``o_orderkey IN (...)`` as orders LEFT SEMI JOIN the
      kept groups (a NULL key, the null group's among them, matches
      nothing).
    * ``cust_join``: those orders INNER JOIN customer on the customer key
      (``c_name`` travels through the join); ``item_join``: lineitem INNER
      JOIN that on the order key, over the SAME lineitem scan
      ``order_qty`` reads. Each lays its rows out in ``out_rows`` rows, a
      capacity the planner states: the node reports ``.capacity`` and
      ``.overflowed`` and the served path refuses a result that outgrew
      it (``CapacityOverflow`` with the true total).
    * ``groupby``: the outer GROUP BY on the five keys, one of them the
      padded string, with ``sum(l_quantity)`` taken again from the joined
      lineitems; it cannot hold more groups than the join has rows.
    * the ORDER BY ``o_totalprice`` descending then ``o_orderdate``
      ascending, NULLs last in both; ``o_orderkey`` breaks what ties are
      left, which SQL leaves open, so that the rows the joins did not
      fill (NULL in every column) rank strictly last; then the LIMIT.

    The answer: ``limit`` rows of ``c_name``, ``c_custkey``,
    ``o_orderkey``, ``o_orderdate``, ``o_totalprice``, ``sum(l_quantity)``;
    a row whose ``o_orderkey`` reads NULL is no row of the answer (fewer
    heavy orders than the limit)."""
    out_rows = int(out_rows)
    items = fusion.Scan("lineitem")
    order_qty = fusion.GroupBy(
        items, (L18_ORDERKEY,), ((L18_QUANTITY, "sum"),),
        max_groups=fusion.groups_of("orders"), label="order_qty")
    having = fusion.Filter(order_qty, _q18_having, (int(quantity) * 100,),
                           label="having")
    in_heavy = fusion.Join(
        fusion.Scan("orders"), having, (O18_ORDERKEY,), (0,), None,
        how="left_semi", label="in_heavy")
    cust_join = fusion.Join(
        in_heavy, fusion.Scan("customer"), (O18_CUSTKEY,), (C18_CUSTKEY,),
        out_rows, how="inner", label="cust_join")
    item_join = fusion.Join(
        items, cust_join, (L18_ORDERKEY,), (O18_ORDERKEY,), out_rows,
        how="inner", label="item_join")
    grouped = fusion.GroupBy(
        item_join, (_J18_NAME, _J18_CUSTKEY, _J18_ORDERKEY, _J18_ORDERDATE,
                    _J18_TOTALPRICE), ((_J18_QUANTITY, "sum"),),
        max_groups=out_rows, label="groupby")
    ordered = fusion.Sort(
        grouped, (Q18_TOTALPRICE, Q18_ORDERDATE, Q18_ORDERKEY),
        ascending=(False, True, True), nulls_first=(False, False, False))
    return fusion.Plan("tpch_q18", fusion.Limit(ordered, int(limit)))


def tpch_q18_numpy(customer: Table, orders: Table, lineitem: Table,
                   quantity: int = _Q18_QUANTITY,
                   limit: int = _Q18_LIMIT) -> list:
    """Host oracle for q18, the query evaluated literally in Python:
    ``[(c_name, c_custkey, o_orderkey, o_orderdate, o_totalprice,
    sum(l_quantity))]`` in the plan's order (``_q18_plan``: NULLs last, the
    order key breaking ties), the decimals unscaled, ``c_name`` as text."""
    import collections

    def cells(col):
        return col.to_pylist() if col.dtype.is_string else [
            None if not ok else int(v) for v, ok in zip(
                np.asarray(col.data).tolist(),
                np.asarray(col.valid_mask()).tolist())]

    lkey, lqty = (cells(lineitem.column(i))
                  for i in (L18_ORDERKEY, L18_QUANTITY))
    sums: dict = {}
    for k, q in zip(lkey, lqty):
        if k is not None and q is not None:
            sums[k] = sums.get(k, 0) + q
    heavy = {k for k, v in sums.items() if v > int(quantity) * 100}
    items = collections.defaultdict(list)
    for k, q in zip(lkey, lqty):
        if k in heavy:
            items[k].append(q)
    names = collections.defaultdict(list)
    for ck, name in zip(cells(customer.column(C18_CUSTKEY)),
                        cells(customer.column(C18_NAME))):
        if ck is not None:
            names[ck].append(name)
    groups: dict = {}
    for ok, ck, date, price in zip(*(cells(orders.column(i)) for i in (
            O18_ORDERKEY, O18_CUSTKEY, O18_ORDERDATE, O18_TOTALPRICE))):
        if ok not in heavy:
            continue
        for name in names.get(ck, ()):
            for q in items[ok]:
                key = (name, ck, ok, date, price)
                had = groups.get(key)
                groups[key] = had if q is None else (had or 0) + q
    big = float("inf")
    # what ties after the order key stays in the groupby's key order
    rows = sorted(groups.items(), key=lambda kv: (
        big if kv[0][4] is None else -kv[0][4],
        big if kv[0][3] is None else kv[0][3], kv[0][2],
        kv[0][0] is not None, kv[0][0] or "", kv[0][1]))
    return [key + (total,) for key, total in rows[:int(limit)]]


# ---------------------------------------------------------------------------
# AOT warmup registration (runtime/server.QueryServer.warmup)
# ---------------------------------------------------------------------------
#
# The learned-estimate file records plan signatures ``<plan>@<bucket>``;
# a booting replica replays the costliest ones through these builders at
# the signature's bucket rows so the first real query finds its
# executables already compiled. Only single-table plans register here:
# their signature bucket maps 1:1 onto synthetic input rows, so the
# warmed executable IS the one live traffic will hit (a multi-table plan
# like q3 has no unique rows-per-table split for a total-row bucket, and
# a wrong split would warm a bucket nobody queries).

def _register_warmup_builders() -> None:
    from spark_rapids_jni_tpu.runtime.server import register_warmup_builder

    register_warmup_builder(
        "tpch_q1", lambda rows: tpch_q1(lineitem_table(rows)))
    register_warmup_builder(
        "tpch_q1_planned",
        lambda rows: tpch_q1_planned(lineitem_table(rows)))
    register_warmup_builder(
        "tpch_q6", lambda rows: tpch_q6(lineitem_table(rows)))


_register_warmup_builders()
