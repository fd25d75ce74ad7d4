"""Whole-stage fusion (runtime/fusion, ISSUE 5).

Four invariant families:

1. **Bit-identity** — a fused region must be byte-for-byte identical to
   the staged op-by-op reference: ``fusion.enabled = False`` runs the
   SAME plan through the same node walk with each op dispatching itself,
   so the comparison holds the query constant and flips only the fusion
   layer. Pinned at 1, 2^k-1, 2^k, 2^k+1 rows with null tails for
   q1/q3/q6, the planned q3, and the planned-q1 ``domain_miss``
   fallback.

2. **Executable economy** — the acceptance claim: one compile per fused
   REGION per bucket (``dispatch.compile.fusion.<plan>``), not one per
   op, and strictly fewer executables than the staged path compiles for
   the same work.

3. **Donation** — ``donate_inputs=True`` accounts freed intermediate
   bytes (``dispatch.donated_bytes``) and never changes results;
   ``fusion.donate = False`` turns the accounting off.

4. **IR discipline** — unbound scans, inconsistent bucket flags, local
   callables, and unresolvable row specs fail loud at plan-build /
   execute time, never inside a trace.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from spark_rapids_jni_tpu import types as t
from spark_rapids_jni_tpu.columnar import Column, Table
from spark_rapids_jni_tpu.models import tpch
from spark_rapids_jni_tpu.ops import sort as so
from spark_rapids_jni_tpu.runtime import dispatch, fusion
from spark_rapids_jni_tpu.runtime.server import QueryServer
from spark_rapids_jni_tpu.telemetry import REGISTRY
from spark_rapids_jni_tpu.utils.config import reset_option, set_option

# row counts straddling the power-of-two bucket edges of the default
# base-16 schedule (same family test_dispatch.py pins)
EDGE_COUNTS = (1, 15, 16, 17, 33)


@pytest.fixture(autouse=True)
def _isolated_fusion():
    """Each test sees a fresh executable cache and counter namespace and
    leaves the fusion/dispatch config at its defaults."""
    dispatch.clear()
    REGISTRY.reset()
    yield
    for k in ("fusion.enabled", "fusion.donate", "dispatch.enabled"):
        reset_option(k)
    dispatch.clear()


def _staged(fn):
    """Run ``fn()`` on the staged op-by-op path (same plan, fusion off)."""
    set_option("fusion.enabled", False)
    dispatch.clear()
    try:
        return fn()
    finally:
        reset_option("fusion.enabled")


def _with_null_tail(tbl: Table, cols=(0,)) -> Table:
    """Null the LAST row's validity in ``cols`` — nulls adjacent to where
    bucket-padding phantoms live, the spot a masking bug corrupts first."""
    out = list(tbl.columns)
    for i in cols:
        c = out[i]
        v = np.asarray(c.valid_mask()).copy()
        v[-1] = False
        out[i] = Column(c.dtype, c.data, v, chars=c.chars)
    return Table(out)


def _assert_cols_identical(a: Column, b: Column, label=""):
    av, bv = np.asarray(a.valid_mask()), np.asarray(b.valid_mask())
    assert np.array_equal(av, bv), f"{label}: validity diverged"
    ad = np.where(av, np.asarray(a.data), 0)
    bd = np.where(bv, np.asarray(b.data), 0)
    assert np.array_equal(ad, bd), f"{label}: data diverged"


def _assert_tables_identical(a: Table, b: Table, label=""):
    assert a.num_columns == b.num_columns
    assert a.num_rows == b.num_rows
    for i in range(a.num_columns):
        _assert_cols_identical(a.column(i), b.column(i), f"{label} col {i}")


# ---------------------------------------------------------------------------
# bit-identity: fused == staged at the bucket edges, null tails included
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n", EDGE_COUNTS)
def test_q1_fused_matches_staged(n):
    li = _with_null_tail(tpch.lineitem_table(n), cols=(0, 3))
    fused = tpch.tpch_q1(li)
    staged = _staged(lambda: tpch.tpch_q1(li))
    _assert_tables_identical(fused, staged, f"q1 n={n}")


@pytest.mark.parametrize("n", EDGE_COUNTS)
def test_q6_fused_matches_staged(n):
    li = _with_null_tail(tpch.lineitem_table(n), cols=(2,))
    fused = tpch.tpch_q6(li)
    staged = _staged(lambda: tpch.tpch_q6(li))
    _assert_cols_identical(fused, staged, f"q6 n={n}")
    if bool(np.asarray(fused.valid_mask())[0]):
        assert int(fused.data[0]) == tpch.tpch_q6_numpy(li)


@pytest.mark.parametrize("n", (1, 15, 16, 17))
def test_q3_fused_matches_staged(n):
    cust = tpch.customer_table(max(n // 2, 1))
    orders = tpch.orders_table(n, cust.num_rows)
    li = _with_null_tail(
        tpch.lineitem_q3_table(2 * n, n), cols=(1,))

    fused = tpch.tpch_q3(cust, orders, li)
    staged = _staged(lambda: tpch.tpch_q3(cust, orders, li))
    _assert_tables_identical(fused.result.table, staged.result.table,
                             f"q3 n={n}")
    assert int(fused.result.num_groups) == int(staged.result.num_groups)
    assert int(fused.join_total) == int(staged.join_total)
    assert fused.out_cap == staged.out_cap


# (the last size puts the groupby's bound over ``ops/sort.py``'s floor: the
# result's sort takes the rows before the padding alone, fused and staged)
@pytest.mark.parametrize("n", (1, 16, 17, 16 * so._MIN_RUNG))
def test_q3_planned_fused_matches_staged(n):
    cust = tpch.customer_table(max(n // 2, 1))
    orders = tpch.orders_table(n, cust.num_rows)
    li = tpch.lineitem_q3_table(2 * n, n)

    fused = tpch.tpch_q3_planned(cust, orders, li)
    staged = _staged(lambda: tpch.tpch_q3_planned(cust, orders, li))
    _assert_tables_identical(fused.result.table, staged.result.table,
                             f"q3_planned n={n}")
    assert int(fused.join_total) == int(staged.join_total)
    assert bool(fused.pk_violation) == bool(staged.pk_violation)
    assert not bool(fused.pk_violation)
    if n > 17:
        assert REGISTRY.counters()["dispatch.compile.sort_before_padding"] == 1


def test_q1_planned_domain_miss_replans_identically():
    """Out-of-domain flag bytes must raise domain_miss on BOTH paths, and
    the checked wrapper's re-plan onto the general pipeline must stay
    bit-identical fused vs staged."""
    li = tpch.lineitem_table(33)
    rf = np.asarray(li.column(tpch.L_RETURNFLAG).data).copy()
    rf[5] = ord("X")  # outside the declared 'A'/'N'/'R' domain
    cols = list(li.columns)
    cols[tpch.L_RETURNFLAG] = Column.from_numpy(rf, t.INT8)
    li = Table(cols)

    fused = tpch.tpch_q1_planned_result(li)
    staged = _staged(lambda: tpch.tpch_q1_planned_result(li))
    assert bool(fused.domain_miss) and bool(staged.domain_miss)
    assert fused.lowered == staged.lowered == "bounded"

    replanned = tpch.tpch_q1_planned_checked(li)
    replanned_staged = _staged(lambda: tpch.tpch_q1_planned_checked(li))
    _assert_tables_identical(replanned, replanned_staged, "q1 re-plan")


def test_q1_in_domain_planned_has_no_miss():
    li = tpch.lineitem_table(64)
    res = tpch.tpch_q1_planned_result(li)
    assert not bool(res.domain_miss)
    _assert_tables_identical(
        tpch.tpch_q1_planned_checked(li),
        _staged(lambda: tpch.tpch_q1_planned_checked(li)),
        "q1 planned")


def test_fused_query_composes_under_jit():
    """Inside an outer jit the bindings are tracers: dispatch's inline
    path folds the whole region into the caller's trace, same results."""
    li = tpch.lineitem_table(48)
    eager = tpch.tpch_q1(li)
    jitted = jax.jit(tpch.tpch_q1)(li)
    _assert_tables_identical(eager, jitted, "q1 under jit")


# ---------------------------------------------------------------------------
# executable economy: one compile per region per bucket, not per op
# ---------------------------------------------------------------------------


def test_one_executable_per_region_per_bucket():
    """Row counts inside one bucket (17..31 pad to 32) must compile the q1
    region exactly ONCE — the fused region inherits dispatch's shape
    bucketing wholesale. A table ON its bucket (32) is the other form of
    the boundary: its int64 columns arrive as the caller's own buffers, not
    as the pad's two uint32 planes, and the region compiles once more."""
    for n in (17, 20, 31):
        tpch.tpch_q1(tpch.lineitem_table(n))
    st = fusion.stats()
    assert st["regions"] == 3 and st["staged_regions"] == 0
    assert st["executables"] == 1, st
    tpch.tpch_q1(tpch.lineitem_table(32))
    tpch.tpch_q1(tpch.lineitem_table(32))
    st = fusion.stats()
    assert st["regions"] == 5 and st["staged_regions"] == 0
    assert st["executables"] == 2, st
    assert st["executables_per_query"] == {"tpch_q1": 2}
    c = REGISTRY.counters("dispatch.")
    assert c["dispatch.hit.fusion.tpch_q1"] == 3
    # one pad an exact row count; 32 sits on its bucket (masks only)
    assert (c["dispatch.compile.pad"], c["dispatch.hit.pad"]) == (4, 1)
    assert (c["dispatch.pad.jitted"], c["dispatch.pad.passthrough"]) == (3, 2)


def test_fused_compiles_fewer_executables_than_staged():
    """The whole point: the staged q1 pays one executable per op
    (groupby machinery, sort, gather...); the fused region pays ONE."""
    def compiles():   # (the ops' executables, the pads' beside them)
        c = REGISTRY.counters("dispatch.compile.")
        pads = c.pop("dispatch.compile.pad", 0)
        return sum(c.values()), pads

    li = tpch.lineitem_table(40)
    tpch.tpch_q1(li)
    fused_compiles, pads = compiles()
    assert (fused_compiles, pads) == (1, 1)

    REGISTRY.reset()
    _staged(lambda: tpch.tpch_q1(li))
    staged_compiles, _ = compiles()
    assert staged_compiles > fused_compiles, (
        f"staged path compiled {staged_compiles} executables; fusion "
        f"must beat it (got {fused_compiles})")


def test_staged_region_counter_accounts_disabled_runs():
    li = tpch.lineitem_table(16)
    _staged(lambda: tpch.tpch_q1(li))
    st = fusion.stats()
    assert st["staged_regions"] == 1 and st["regions"] == 0


# ---------------------------------------------------------------------------
# donation
# ---------------------------------------------------------------------------


def _double_col(tbl: Table) -> Table:
    c = tbl.column(0)
    return Table([Column(c.dtype, c.data * 2, c.valid_mask())])


def test_donated_intermediates_are_accounted():
    """donate_inputs=True on a caller-owned intermediate accounts the
    donated buffer bytes and leaves results identical."""
    vals = np.arange(64, dtype=np.int64)
    plan = fusion.Plan("donate_probe", fusion.Project(
        fusion.Scan("t"), _double_col))

    expected = vals * 2
    res = fusion.execute(
        plan, {"t": Table([Column.from_numpy(vals.copy())])},
        donate_inputs=True)
    got = np.asarray(res.table.column(0).data)
    assert np.array_equal(got, expected)
    assert fusion.stats()["donated_bytes"] > 0


def test_fusion_donate_config_gates_donation():
    set_option("fusion.donate", False)
    vals = np.arange(64, dtype=np.int64)
    plan = fusion.Plan("donate_probe", fusion.Project(
        fusion.Scan("t"), _double_col))
    fusion.execute(plan, {"t": Table([Column.from_numpy(vals)])},
                   donate_inputs=True)
    assert fusion.stats()["donated_bytes"] == 0


def test_undeclared_inputs_are_never_donated():
    vals = np.arange(64, dtype=np.int64)
    plan = fusion.Plan("donate_probe", fusion.Project(
        fusion.Scan("t"), _double_col))
    fusion.execute(plan, {"t": Table([Column.from_numpy(vals)])})
    assert fusion.stats()["donated_bytes"] == 0


# ---------------------------------------------------------------------------
# IR discipline: misuse fails loud, outside any trace
# ---------------------------------------------------------------------------


def _keep_evens(tbl: Table) -> jax.Array:
    return tbl.column(0).data % 2 == 0


def test_filter_and_limit_nodes_fused_match_staged():
    vals = np.arange(1, 41, dtype=np.int64)
    tbl = Table([Column.from_numpy(vals)])
    plan = fusion.Plan("filter_limit", fusion.Limit(
        fusion.Filter(fusion.Scan("t"), _keep_evens), 100))

    fused = fusion.execute(plan, {"t": tbl}).table
    staged = _staged(lambda: fusion.execute(plan, {"t": tbl}).table)
    # Limit clamps to the TRUE row count, not the bucket
    assert fused.num_rows == staged.num_rows == 40
    _assert_tables_identical(fused, staged, "filter+limit")
    valid = np.asarray(fused.column(0).valid_mask())
    assert np.array_equal(valid, vals % 2 == 0)


def test_unbound_scan_raises():
    plan = fusion.Plan("p", fusion.Scan("missing"))
    with pytest.raises(KeyError, match="unbound table 'missing'"):
        fusion.execute(plan, {})


def test_inconsistent_bucket_flags_raise():
    a, b = fusion.Scan("t"), fusion.Scan("t", bucket=False)
    plan = fusion.Plan("p", fusion.Join(
        a, b, (0,), (0,), fusion.rows_of("t")))
    with pytest.raises(ValueError, match="both bucketed and exact"):
        fusion.execute(plan, {"t": Table([Column.from_numpy(
            np.arange(4, dtype=np.int64))])})


def test_local_callables_are_rejected():
    plan = fusion.Plan("p", fusion.Project(
        fusion.Scan("t"), lambda tbl: tbl))
    with pytest.raises(ValueError, match="module-level"):
        fusion.execute(plan, {"t": Table([Column.from_numpy(
            np.arange(4, dtype=np.int64))])})


def test_unresolvable_row_spec_raises():
    plan = fusion.Plan("p", fusion.Join(
        fusion.Scan("t"), fusion.Scan("t"), (0,), (0,),
        ("bogus_spec", "t", 1)))
    with pytest.raises(ValueError, match="unresolvable row spec"):
        fusion.execute(plan, {"t": Table([Column.from_numpy(
            np.arange(4, dtype=np.int64))])})


def test_row_specs_resolve_from_true_rows():
    assert fusion._resolve(fusion.rows_of("t", 3), {"t": 10}) == 30
    assert fusion._resolve(fusion.min_rows_of("t", 7), {"t": 10}) == 7
    assert fusion._resolve(fusion.min_rows_of("t", 7), {"t": 4}) == 4
    assert fusion._resolve(None, {}) is None
    assert fusion._resolve(12, {}) == 12


# ---------------------------------------------------------------------------
# a Sort over a bounded groupby's padded result: the rows before the padding
# ---------------------------------------------------------------------------

_BOUND = 256     # the rung of so many rows is 16, and the floor dropped to it
_SORT_KEYS, _SORT_ASC, _SORT_NF = (1, 2), (False, True), (False, False)


def _padded_groups_plan(sort: bool = True) -> fusion.Plan:
    """[key, sum, max] of at most ``_BOUND`` groups, ORDER BY sum DESC, max."""
    groups = fusion.GroupBy(fusion.Scan("t"), (0,), ((1, "sum"), (2, "max")),
                            max_groups=_BOUND, label="groupby")
    return fusion.Plan("sorted_groups", fusion.Sort(
        groups, _SORT_KEYS, _SORT_ASC, _SORT_NF) if sort else groups)


def _rows_of_groups(groups: int, null_group: int | None = None) -> Table:
    """Three rows a group in a seeded order; every value of ``null_group``
    is null, so that group's sum and max, the two sort keys, are null."""
    rng = np.random.default_rng(groups)
    key = rng.permutation(np.repeat(np.arange(groups, dtype=np.int64), 3))
    real = key != (-1 if null_group is None else null_group)
    return Table([
        Column(t.INT64, key),
        Column(t.decimal64(-2), rng.integers(-10**6, 10**6, len(key)), real),
        Column(t.INT32, rng.integers(0, 4, len(key)).astype(np.int32), real)])


# case -> (the groupby's input, whether the head alone is sorted)
PADDED_GROUPS = {
    "well_under_the_rung": (_rows_of_groups(5), 1),
    "the_rung_exactly": (_rows_of_groups(16), 1),
    "one_row_over_the_rung": (_rows_of_groups(17), 0),
    "no_row": (_rows_of_groups(0), 1),
    "a_group_null_in_every_sort_key": (_rows_of_groups(9, null_group=3), 1),
}


@pytest.mark.parametrize("case", list(PADDED_GROUPS))
def test_sort_over_padded_groups_is_the_stable_whole_sort(case, monkeypatch):
    """Value for value ``sort_table`` of the groupby's result, fused, staged
    and served; ``sort.prefix_sorted`` says which branch ran. A real group
    whose sort keys are null stands after the others and before the
    padding, where the stable sort of all rows puts it."""
    monkeypatch.setattr(so, "_MIN_RUNG", _BOUND // 16)
    table, took = PADDED_GROUPS[case]
    plan, bindings = _padded_groups_plan(), {"t": table}
    groups = fusion.execute(_padded_groups_plan(sort=False), bindings)
    want = so.sort_table(groups.table, _SORT_KEYS, _SORT_ASC, _SORT_NF)
    fused = fusion.execute(plan, bindings)
    staged = _staged(lambda: fusion.execute(plan, bindings))
    for got, label in ((fused, "fused"), (staged, "staged")):
        assert got.table.num_rows == _BOUND
        _assert_tables_identical(got.table, want, f"{case} {label}")
        assert int(got.meta["sort.prefix_sorted"]) == took, label
        assert fusion.meta_facts(plan, got.meta)["sort.prefix_sorted"] == took
    if case == "a_group_null_in_every_sort_key":
        key, total = fused.table.column(0), fused.table.column(1)
        assert key.to_pylist()[8] == 3 and total.to_pylist()[8] is None
        assert key.to_pylist()[9] is None
    with QueryServer(budget_bytes=4 << 30) as srv:
        served = srv.session("t").submit(plan, bindings).result()
    _assert_tables_identical(served.table, want, f"{case} served")
    assert REGISTRY.counters()["sort.prefix_sorted"] == took


# ---------------------------------------------------------------------------
# declared key ranges: a ranged GroupBy gives the table an unranged one gives
# ---------------------------------------------------------------------------


def _ranged_table(np_dt, lo, hi, n, groups, seed=3):
    """[key over ``groups`` values of [lo, hi] with both ends and nulls,
    int32 payload with nulls, decimal payload]."""
    rng = np.random.default_rng([seed, n, groups])
    pool = np.unique(np.r_[lo, hi, rng.integers(lo, hi + 1, groups)])
    key = pool[rng.integers(0, len(pool), n)].astype(np_dt)
    return Table([
        Column(t.DType.from_numpy(np.dtype(np_dt)), key, rng.random(n) > 0.1),
        Column(t.INT32, rng.integers(-50, 50, n).astype(np.int32),
               rng.random(n) > 0.2),
        Column(t.decimal64(-2), rng.integers(-10**9, 10**9, n))])


def _ranged_plan(key_ranges, aggs, max_groups, name="ranged_groupby"):
    return fusion.Plan(name, fusion.GroupBy(
        fusion.Scan("t"), (0,), aggs, max_groups=max_groups,
        label="groupby", key_ranges=key_ranges))


_WORD_MOVING = ((1, "first_include_nulls"), (2, "sum"), (1, "count"))
_IN_PLACE = ((2, "sum"), (1, "count"), (1, "sum"))


@pytest.mark.parametrize("np_dt, lo, hi", [
    (np.int64, 1, 1_500_000), (np.int64, -40_000, 90_000),
    (np.int64, 1, 200), (np.int32, -7, 60_000),
], ids=["i64_from_1", "i64_lo_negative", "i64_8bit", "i32_lo_negative"])
@pytest.mark.parametrize("aggs, max_groups, groups", [
    (_WORD_MOVING, 3000, 2000),      # over _SMALL_M: permute's words
    (_WORD_MOVING, None, 150),       # no bound: padded to the rows
    (_IN_PLACE, 64, 40),             # under it: the sums where the rows lie
], ids=["word_moving", "unbounded", "in_place"])
@pytest.mark.parametrize("n", [4096, 5000], ids=["on_bucket", "phantom_rows"])
def test_ranged_groupby_matches_unranged(np_dt, lo, hi, aggs, max_groups,
                                         groups, n):
    """Bit for bit: the key's dtype, validity and values (the null group
    among them), every aggregate, the groups' order, the side outputs;
    fused (a bucket's phantom rows where n is off it) and staged."""
    from spark_rapids_jni_tpu.ops import groupby as gb

    table = _ranged_table(np_dt, lo, hi, n, min(groups, hi - lo))
    ranged = _ranged_plan(((lo, hi),), aggs, max_groups)
    plain = _ranged_plan(None, aggs, max_groups)
    got = fusion.execute(ranged, {"t": table})
    want = fusion.execute(plain, {"t": table})
    assert [c.dtype for c in got.table.columns] == [
        c.dtype for c in want.table.columns]
    _assert_tables_identical(got.table, want.table, "ranged vs unranged")
    for fact in ("num_groups", "overflowed", "sum_overflow", "in_place"):
        assert np.array_equal(got.meta[f"groupby.{fact}"],
                              want.meta[f"groupby.{fact}"]), fact
    assert not bool(got.meta["groupby.overflowed"])
    assert bool(got.meta["groupby.in_place"]) == (
        aggs is _IN_PLACE and max_groups <= gb._SMALL_M)
    assert bool(got.meta["groupby.key_narrowed"])
    assert not bool(got.meta["groupby.key_out_of_range"])
    assert "groupby.key_narrowed" not in want.meta
    # the null group is there, first
    first = got.table.column(0)
    assert not bool(np.asarray(first.valid_mask())[0])
    staged = _staged(lambda: fusion.execute(ranged, {"t": table}))
    _assert_tables_identical(staged.table, got.table, "staged vs fused")
    assert bool(staged.meta["groupby.key_narrowed"])
    facts = fusion.meta_facts(ranged, got.meta)
    assert facts["groupby.key_narrowed"] == 1
    assert facts["groupby.key_out_of_range"] == 0
    assert fusion.meta_facts(plain, want.meta)["groupby.key_narrowed"] == 0


def test_ranged_groupby_over_a_mesh_matches_one_chip():
    """The rebase is rowwise, so a chip's share is rebased as the whole
    is: the same groups with the same sums through partial, shuffle (of
    the narrowed key), merge and collect. A mesh's groups come chip after
    chip (the key's hash places them), so they are compared in key
    order."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from spark_rapids_jni_tpu.parallel.mesh import EXEC_AXIS, executor_mesh

    lo, hi, n = -40_000, 90_000, 4096
    table = _ranged_table(np.int64, lo, hi, n, 40)
    aggs = ((2, "sum"), (1, "count"))
    sharding = NamedSharding(executor_mesh(4), P(EXEC_AXIS))
    sharded = Table([Column(c.dtype, jax.device_put(c.data, sharding),
                            jax.device_put(c.valid_mask(), sharding))
                     for c in table.columns])
    ranged = _ranged_plan(((lo, hi),), aggs, 64, name="ranged_mesh")
    got = fusion.execute(ranged, {"t": sharded})
    assert "groupby.shuffle_rows" in got.meta      # it crossed the chips
    assert bool(got.meta["groupby.key_narrowed"])
    assert not bool(got.meta["groupby.key_out_of_range"])
    want = fusion.execute(_ranged_plan(None, aggs, 64), {"t": table})
    groups = int(want.meta["groupby.num_groups"])
    assert int(got.meta["groupby.num_groups"]) == groups

    def rows(res):
        assert res.table.column(0).dtype == t.INT64
        cols = [c.to_pylist()[:groups] for c in res.table.columns]
        return sorted(zip(*cols), key=lambda r: (r[0] is not None, r[0] or 0))

    assert rows(got) == rows(want)
    # a key outside the range on ONE chip's rows is every chip's fact
    narrow = _ranged_plan(((lo, hi - 70_000),), aggs, 64, name="ranged_mesh")
    assert bool(fusion.execute(
        narrow, {"t": sharded}).meta["groupby.key_out_of_range"])


def test_key_outside_its_declared_range_is_reported():
    table = _ranged_table(np.int64, 1, 1000, 600, 30)
    held = _ranged_plan(((1, 1000),), _WORD_MOVING, 64)
    broken = _ranged_plan(((2, 1000),), _WORD_MOVING, 64)   # key 1 exists
    for run in (fusion.execute, lambda p, b: _staged(
            lambda: fusion.execute(p, b))):
        assert not bool(run(held, {"t": table}).meta[
            "groupby.key_out_of_range"])
        res = run(broken, {"t": table})
        assert bool(res.meta["groupby.key_out_of_range"])
        assert fusion.meta_facts(broken, res.meta)[
            "groupby.key_out_of_range"] == 1


def test_served_key_out_of_range_is_a_failed_request():
    """The declaration is verified, not trusted: the server refuses the
    result as it refuses a ``pk_violation``, and nothing of it stays in
    the result cache (the same request fails again and is no hit)."""
    from spark_rapids_jni_tpu.runtime import resilience
    from spark_rapids_jni_tpu.runtime.server import QueryServer

    table = _ranged_table(np.int64, 1, 1000, 600, 30)
    held = _ranged_plan(((1, fusion.rows_of("t", 2)),), _IN_PLACE, 64)
    broken = _ranged_plan(((2, fusion.rows_of("t", 2)),), _IN_PLACE, 64)
    with QueryServer(budget_bytes=4 << 30) as srv:
        ticket = srv.session("s").submit(held, {"t": table})
        assert ticket.result() is not None and ticket.status == "served"
        counters = REGISTRY.counters()
        assert counters["groupby.key_narrowed"] == 1
        assert counters.get("groupby.key_out_of_range", 0) == 0
        entries = srv.result_cache.stats()["entries"]
        for again in (1, 2):
            ticket = srv.session("s").submit(broken, {"t": table})
            with pytest.raises(resilience.FatalExecutionError,
                               match="key_out_of_range.*not the query's"):
                ticket.result()
            assert ticket.status == "failed"
            counters = REGISTRY.counters()
            assert counters["groupby.key_out_of_range"] == again
            assert counters.get("cache.hit", 0) == 0
            assert srv.result_cache.stats()["entries"] == entries == 1
        assert counters["server.served"] == 1


def test_key_ranges_are_a_static_of_the_plan():
    """They ride the plan's fingerprint (the dispatch key, the result
    cache's plan half) with their row specs resolved; with ``domains``
    they are refused, as is one entry too few."""
    from spark_rapids_jni_tpu.ops.planner import scalar_domain

    table = _ranged_table(np.int64, 1, 1000, 600, 30)
    b = {"t": table}
    prints = [fusion.plan_fingerprint(_ranged_plan(r, _IN_PLACE, 64), b)
              for r in (None, ((1, 1000),), ((1, 1200),), ((0, 1000),),
                        ((1, fusion.rows_of("t", 2)),))]
    assert len(set(prints[:4])) == 4
    assert prints[4] == prints[2]          # 2 x 600 rows
    assert prints[0] == fusion.plan_fingerprint(
        _ranged_plan((None,), _IN_PLACE, 64), b)
    before = REGISTRY.counters().get("dispatch.compile.fusion.ranged_groupby", 0)
    for r in (((1, 1000),), ((1, 1200),), ((1, 1000),)):
        fusion.execute(_ranged_plan(r, _IN_PLACE, 64), b)
    assert REGISTRY.counters()[
        "dispatch.compile.fusion.ranged_groupby"] == before + 2
    with_domains = fusion.Plan("p", fusion.GroupBy(
        fusion.Scan("t"), (0,), ((2, "sum"),),
        domains=(scalar_domain(range(1, 9)),), key_ranges=((1, 8),)))
    with pytest.raises(ValueError, match="key_ranges with domains"):
        fusion.execute(with_domains, b)
    with pytest.raises(ValueError, match="key_ranges with domains"):
        fusion.plan_fingerprint(with_domains, b)
    two_keys = fusion.Plan("p", fusion.GroupBy(
        fusion.Scan("t"), (0, 1), ((2, "sum"),), key_ranges=((1, 8),)))
    with pytest.raises(ValueError, match="1 key_ranges for 2 keys"):
        fusion.execute(two_keys, b)


# ---------------------------------------------------------------------------
# semi and anti joins inside a region: one bit a left row, rows where they lay
# ---------------------------------------------------------------------------


def _semi_tables(nl: int, nr: int):
    rng = np.random.default_rng(nl * 131 + nr)
    left = Table([
        Column.from_numpy(rng.integers(0, 12, nl).astype(np.int64)
                          * (2 ** 35 + 3)),
        Column.from_numpy(rng.integers(0, 4, nl).astype(np.int32)),
        Column.from_numpy(np.arange(nl, dtype=np.int64))])
    right = Table([Column.from_numpy(
        rng.integers(3, 20, nr).astype(np.int64) * (2 ** 35 + 3))])
    return (_with_null_tail(left, cols=(0,)),
            _with_null_tail(right, cols=(0,)))


def _semi_plan(how: str, grouped: bool = True) -> fusion.Plan:
    joined = fusion.Join(fusion.Scan("l"), fusion.Scan("r"), (0,), (0,),
                         None, how=how, label="semi")
    if not grouped:
        return fusion.Plan("semi_rows", joined)
    return fusion.Plan("semi_counts", fusion.GroupBy(
        joined, (1,), ((2, "count"), (2, "sum")), max_groups=16,
        label="groupby"))


@pytest.mark.parametrize("how", ["left_semi", "left_anti"])
@pytest.mark.parametrize("n", EDGE_COUNTS)
def test_semi_join_hands_a_row_mask_to_the_node_above(how, n):
    """The left columns alone, every row where it lay: a dropped row
    loses its validity in every column, so the GroupBy above counts the
    kept rows and the root slices back to the left side's true rows; the
    fused region is the staged walk bit for bit, and both are the
    maps-based join's rows."""
    from spark_rapids_jni_tpu.ops.join import join

    left, right = _semi_tables(n, 2 * n + 1)
    b = {"l": left, "r": right}
    rows = fusion.execute(_semi_plan(how, grouped=False), b)
    staged = _staged(lambda: fusion.execute(_semi_plan(how, False), b))
    _assert_tables_identical(rows.table, staged.table, f"{how} n={n}")
    assert rows.table.num_rows == n and rows.table.num_columns == 3
    maps = join(left, right, 0, 0, out_size=n, how=how)
    total = int(maps.total)
    kept = np.asarray(maps.left_index)[:total]
    assert int(rows.meta["semi.total"]) == total
    assert int(rows.meta["semi.probe_rows"]) == n
    assert int(rows.meta["semi.build_rows"]) == int(
        np.asarray(right.column(0).valid_mask()).sum())
    for i in range(3):
        want = np.zeros(n, bool)
        want[kept] = np.asarray(left.column(i).valid_mask())[kept]
        assert np.array_equal(
            np.asarray(rows.table.column(i).valid_mask()), want)
        assert np.array_equal(np.asarray(rows.table.column(i).data),
                              np.asarray(left.column(i).data))
    counts = fusion.execute(_semi_plan(how), b)
    staged = _staged(lambda: fusion.execute(_semi_plan(how), b))
    _assert_tables_identical(counts.table, staged.table, f"{how} n={n}")
    groups = int(counts.meta["groupby.num_groups"])
    got = {int(k): int(v) for k, v, ok in zip(
        np.asarray(counts.table.column(0).data)[:groups],
        np.asarray(counts.table.column(1).data)[:groups],
        np.asarray(counts.table.column(0).valid_mask())[:groups]) if ok}
    flags = np.asarray(left.column(1).data)[kept]
    assert got == {int(f): int((flags == f).sum()) for f in set(flags)}


def test_runtime_filters_leave_semi_joins_alone():
    """The planner pass builds a bloom filter for single-key INNER joins
    only: with it on, a semi or anti join's plan is the plan."""
    left, right = _semi_tables(33, 70)
    b = {"l": left, "r": right}
    set_option("rtfilter.enabled", True)
    try:
        for how in ("left_semi", "left_anti"):
            plan = _semi_plan(how)
            assert fusion.inject_runtime_filters(plan, b) is plan
            on = fusion.execute(plan, b)
            assert not any(k.startswith("rtf_") for k in on.meta)
        inner = fusion.Plan("inner", fusion.Join(
            fusion.Scan("l"), fusion.Scan("r"), (0,), (0,),
            fusion.rows_of("l", 8), label="semi"))
        assert fusion.execute(inner, b).meta["semi.build_rows"] is not None
    finally:
        reset_option("rtfilter.enabled")


def test_estimate_hbm_bytes_of_a_semi_join_plan():
    """A semi join lays out no rows of its own: the estimate is the
    inputs and the groupby's slots above it (the join's sort is for the
    server's headroom to hold: PERF.md says how far that is)."""
    from spark_rapids_jni_tpu.runtime.memory import _table_nbytes

    left, right = _semi_tables(1000, 4000)
    b = {"l": left, "r": right}
    inputs = _table_nbytes(left) + _table_nbytes(right)
    width = max(1, inputs // 5000)
    assert fusion.estimate_hbm_bytes(_semi_plan("left_semi", False), b) \
        == inputs
    assert fusion.estimate_hbm_bytes(_semi_plan("left_semi"), b) \
        == inputs + 16 * width
    q4 = tpch._q4_plan()
    nodes = fusion._topo(q4.root)
    assert fusion._spaces(nodes)[id(nodes[-1])] is None     # six slots
    semi = next(n for n in nodes if isinstance(n, fusion.Join))
    assert fusion._spaces(nodes)[id(semi)] == "orders"


# ---------------------------------------------------------------------------
# a Filter over a GroupBy's output (HAVING), a Join whose build side is that
# filter, and what a sort-path groupby says entered it
# ---------------------------------------------------------------------------


def _over_ten(groups: Table, least: int) -> jnp.ndarray:
    total = groups.column(1)
    return total.valid_mask() & (total.data > jnp.int64(least))


def _having_tables(n: int, keys: int, seed: int = 3):
    rng = np.random.default_rng(seed)
    facts = Table([
        Column.from_numpy(rng.integers(0, keys, n).astype(np.int64)),
        Column.from_numpy(rng.integers(1, 9, n).astype(np.int64))])
    dims = Table([
        Column.from_numpy(rng.permutation(keys + 5).astype(np.int64)),
        Column.from_numpy(rng.integers(0, 99, keys + 5).astype(np.int32))])
    return facts, dims


def _having_plan(bound, least: int = 10) -> fusion.Plan:
    sums = fusion.GroupBy(fusion.Scan("facts"), (0,), ((1, "sum"),),
                          max_groups=bound, label="sums")
    having = fusion.Filter(sums, _over_ten, (least,), label="having")
    return fusion.Plan("having_in", fusion.Join(
        fusion.Scan("dims"), having, (0,), (0,), None, how="left_semi",
        label="in_list"))


@pytest.mark.parametrize("n, keys", [(1, 1), (17, 3), (700, 40), (5000, 900)])
def test_filter_over_a_groupby_and_a_join_that_builds_on_it(n, keys):
    """``WHERE k IN (SELECT k ... GROUP BY k HAVING sum(v) > 10)`` against
    numpy, fused and staged: the HAVING counts the groups it saw (not the
    rows of the groupby's bound), the semi join's build rows are the
    groups it kept."""
    facts, dims = _having_tables(n, keys)
    k, v = (np.asarray(facts.column(i).data) for i in (0, 1))
    sums = np.bincount(k, weights=v, minlength=keys).astype(np.int64)
    seen = int((np.bincount(k, minlength=keys) > 0).sum())
    kept = np.flatnonzero(sums > 10)
    want = np.isin(np.asarray(dims.column(0).data), kept)
    plan = _having_plan(fusion.groups_of("dims"))
    b = {"facts": facts, "dims": dims}
    fused = fusion.execute(plan, b)
    staged = _staged(lambda: fusion.execute(plan, b))
    for got in (fused, staged):
        assert np.array_equal(
            np.asarray(got.table.column(0).valid_mask()), want)
        assert got.table.num_rows == keys + 5
        meta = {key: int(val) for key, val in got.meta.items()}
        assert meta["sums.num_groups"] == seen
        assert meta["sums.capacity"] == keys + 6
        assert meta["having.rows_in"] == seen          # not keys + 6
        assert meta["having.rows_kept"] == kept.size
        assert meta["in_list.build_rows"] == kept.size
        assert meta["in_list.total"] == int(want.sum())
        # what entered the groupby: a key and a value, a validity byte each
        assert meta["sums.rows_in"] == n
        assert meta["sums.read_bytes"] == n * 18
    _assert_tables_identical(fused.table, staged.table)


def test_groupby_facts_are_summed_and_counted_once_a_request():
    """``meta_facts`` sums what the sort-path groupbys say entered them
    and what their bounds have room for (a groupby with no bound states no
    capacity), and the server counts each once a request."""
    facts, dims = _having_tables(700, 40)
    b = {"facts": facts, "dims": dims}
    plan = _having_plan(64)
    got = fusion.execute(plan, b)
    found = fusion.meta_facts(plan, got.meta)
    assert (found["groupby.rows_in"], found["groupby.read_bytes"],
            found["groupby.capacity_groups"]) == (700, 700 * 18, 64)
    unbounded = _having_plan(None)
    loose = fusion.execute(unbounded, b)
    assert "sums.capacity" not in loose.meta
    assert fusion.meta_facts(unbounded, loose.meta)[
        "groupby.capacity_groups"] == 0
    # with no bound the groups' rows are the input's, still positional
    assert int(loose.meta["having.rows_in"]) == 700
    two = fusion.Plan("two_groupbys", fusion.GroupBy(
        fusion.GroupBy(fusion.Scan("facts"), (0,), ((1, "sum"),),
                       max_groups=2048, label="first"),
        (1,), ((0, "count"),), max_groups=128, label="second"))
    both = fusion.execute(two, b)
    found = fusion.meta_facts(two, both.meta)
    assert found["groupby.rows_in"] == 700 + 2048
    assert found["groupby.read_bytes"] == (700 + 2048) * 18
    assert found["groupby.capacity_groups"] == 2048 + 128
    with QueryServer(budget_bytes=4 << 30) as srv:
        for served in (1, 2):
            ticket = srv.session("s").submit(
                plan, {"facts": _having_tables(700, 40, seed=served)[0],
                       "dims": dims})
            assert ticket.result() is not None
            counters = REGISTRY.counters()
            assert counters["groupby.rows_in"] == 700 * served
            assert counters["groupby.read_bytes"] == 700 * 18 * served
            assert counters["groupby.capacity_groups"] == 64 * served
            assert counters["filter.rows_in"] == 40 * served
