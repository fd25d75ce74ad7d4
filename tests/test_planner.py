"""Bounded-domain groupby planner (ops/planner.py) — VERDICT r4 item 3.

The 125x q1 win came from planner-declared key domains; these tests pin
the generalized facility: domain sources (DDL, observed stats, month
buckets), on-device string dictionary encoding, bounded-vs-general
lowering parity against numpy oracles, the domain_miss escape hatch, and
the sort-free HLO contract on the new planned queries (q12, q4).
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from spark_rapids_jni_tpu import types as t
from spark_rapids_jni_tpu.columnar import Column, Table
from spark_rapids_jni_tpu.ops.planner import (
    Domain,
    encode_string_key,
    month_bucket,
    month_code,
    month_domain,
    observed_domain,
    plan_groupby,
    scalar_domain,
    string_domain,
)


def _groups(table, present=None, nkeys=1):
    """{key tuple: agg tuple} over valid (present) group rows."""
    cols = [c.to_pylist() for c in table.columns]
    out = {}
    for i in range(len(cols[0])):
        if present is not None and not bool(np.asarray(present)[i]):
            continue
        key = tuple(cols[k][i] for k in range(nkeys))
        if any(k is None for k in key):
            continue
        out[key] = tuple(cols[k][i] for k in range(nkeys, len(cols)))
    return out


# ---------------------------------------------------------------------------
# domain sources
# ---------------------------------------------------------------------------


def test_scalar_domain_sorted_deduped():
    d = scalar_domain([3, 1, 3, 2])
    assert d.values == (1, 2, 3) and d.kind == "scalar"


def test_string_domain_byte_order():
    d = string_domain(["SHIP", "AIR", "MAIL"])
    assert d.values == ("AIR", "MAIL", "SHIP")


def test_observed_domain_scalar(rng):
    col = Column.from_numpy(
        rng.integers(0, 5, 200).astype(np.int32))
    d = observed_domain(col)
    assert d.kind == "scalar" and set(d.values) <= set(range(5))


def test_observed_domain_respects_nulls_and_cap(rng):
    vals = rng.integers(0, 1000, 2000).astype(np.int64)
    col = Column.from_numpy(vals)
    assert observed_domain(col, max_size=10) is None  # not boundable


def test_observed_domain_strings():
    col = Column.from_pylist(["b", "a", None, "b"], t.STRING)
    d = observed_domain(col)
    assert d.values == ("a", "b") and d.kind == "string"


def test_month_domain_and_code():
    d = month_domain(1995, 11, 1996, 2)
    assert d.values == tuple(
        month_code(1995, 11) + i for i in range(4))


def test_month_bucket_matches_calendar():
    import datetime as pydt

    days = [9131, 8400, 0, 10956]  # various epochs-days
    col = Column.from_numpy(np.asarray(days, np.int32), t.TIMESTAMP_DAYS)
    got = np.asarray(month_bucket(col).data)
    for i, dday in enumerate(days):
        d = pydt.date(1970, 1, 1) + pydt.timedelta(days=dday)
        assert got[i] == month_code(d.year, d.month)


# ---------------------------------------------------------------------------
# string encoding
# ---------------------------------------------------------------------------


def test_encode_string_key_codes_and_miss():
    col = Column.from_pylist(
        ["MAIL", "SHIP", "AIR", None, "MAIL"], t.STRING)
    dom = string_domain(["MAIL", "SHIP"])
    code = encode_string_key(col, dom)
    # sorted domain: MAIL=0, SHIP=1; AIR (out of domain) -> k=2
    assert np.asarray(code.data).tolist() == [0, 1, 2, 2, 0]
    assert np.asarray(code.valid_mask()).tolist() == [
        True, True, True, False, True]


def test_encode_prefix_not_equal():
    # "AIR" must not match "AIR REG" and vice versa (padded-bytes
    # equality is exact, not prefix)
    col = Column.from_pylist(["AIR", "AIR REG"], t.STRING)
    dom = string_domain(["AIR REG"])
    code = encode_string_key(col, dom)
    assert np.asarray(code.data).tolist() == [1, 0]


# ---------------------------------------------------------------------------
# plan_groupby lowering parity
# ---------------------------------------------------------------------------


def test_bounded_scalar_matches_general_and_oracle(rng):
    n = 500
    k1 = rng.integers(0, 3, n).astype(np.int8)
    k2 = rng.integers(10, 12, n).astype(np.int32)
    v = rng.integers(-50, 50, n).astype(np.int64)
    kv1 = rng.random(n) > 0.1
    tbl = Table([
        Column.from_numpy(k1, validity=kv1),
        Column.from_numpy(k2),
        Column.from_numpy(v),
    ])
    doms = [scalar_domain([0, 1, 2]), scalar_domain([10, 11])]
    b = plan_groupby(tbl, [0, 1], [(2, "sum")], doms)
    assert b.lowered == "bounded" and not bool(b.domain_miss)
    g = plan_groupby(tbl, [0, 1], [(2, "sum")], [None, None])
    assert g.lowered == "general"
    got_b = _groups(b.table, b.present, nkeys=2)
    got_g = _groups(g.table, g.present, nkeys=2)
    oracle = {}
    for i in range(n):
        if not kv1[i]:
            continue
        key = (int(k1[i]), int(k2[i]))
        oracle[key] = (oracle.get(key, (0,))[0] + int(v[i]),)
    assert got_b == oracle and got_g == oracle


def test_bounded_string_key_decodes_to_strings(rng):
    n = 300
    modes = ["AIR", "MAIL", "SHIP", "RAIL"]
    idx = rng.integers(0, 4, n)
    vals = rng.integers(0, 100, n).astype(np.int64)
    tbl = Table([
        Column.from_pylist([modes[i] for i in idx], t.STRING),
        Column.from_numpy(vals),
    ])
    res = plan_groupby(tbl, [0], [(1, "sum"), (1, "count")],
                       [string_domain(modes)])
    assert res.lowered == "bounded"
    got = _groups(res.table, res.present)
    oracle = {}
    for i in range(n):
        key = (modes[idx[i]],)
        s, c = oracle.get(key, (0, 0))
        oracle[key] = (s + int(vals[i]), c + 1)
    assert got == oracle
    # static output order: lexicographic keys, nulls last
    present = np.asarray(res.present)
    live = [k for k, p in zip(res.table.column(0).to_pylist(), present)
            if p and k is not None]
    assert live == sorted(live)


def test_domain_miss_flags_out_of_domain_value():
    tbl = Table([
        Column.from_pylist(["MAIL", "TRUCK"], t.STRING),
        Column.from_numpy(np.asarray([1, 2], np.int64)),
    ])
    res = plan_groupby(tbl, [0], [(1, "sum")],
                       [string_domain(["MAIL", "SHIP"])])
    assert bool(res.domain_miss)


def test_budget_overflow_falls_back_to_general():
    tbl = Table([
        Column.from_numpy(np.arange(100, dtype=np.int32)),
        Column.from_numpy(np.ones(100, np.int64)),
    ])
    res = plan_groupby(tbl, [0], [(1, "sum")],
                       [scalar_domain(range(100))], budget=50)
    assert res.lowered == "general"
    # the budget capped the general groupby: dropped groups must SIGNAL
    # (the caller's grow-and-retry cue), never silently truncate
    assert bool(res.overflowed)
    got = _groups(res.table, res.present)
    assert len(got) == 50
    assert all(v == (1,) for v in got.values())


def test_general_plan_under_budget_not_overflowed():
    tbl = Table([
        Column.from_numpy(np.asarray([1, 2, 1], np.int32)),
        Column.from_numpy(np.asarray([5, 6, 7], np.int64)),
    ])
    res = plan_groupby(tbl, [0], [(1, "sum")], [None])
    assert res.lowered == "general" and not bool(res.overflowed)
    assert _groups(res.table, res.present) == {(1,): (12,), (2,): (6,)}


def test_unsupported_agg_falls_back():
    tbl = Table([
        Column.from_numpy(np.asarray([0, 0, 1], np.int32)),
        Column.from_numpy(np.asarray([5, 7, 9], np.int64)),
    ])
    res = plan_groupby(tbl, [0], [(1, "var")], [scalar_domain([0, 1])])
    assert res.lowered == "general"


def test_month_bucket_rollup_on_sort_free_path(rng):
    """Date-bucketed revenue rollup: unbounded date cardinality, tiny
    month-bucket domain — the date-bucket aggregation pattern VERDICT r4
    item 3 names (q3 date buckets / q14 months)."""
    n = 400
    days = rng.integers(9131, 9131 + 120, n).astype(np.int32)  # ~4 months
    rev = rng.integers(0, 1000, n).astype(np.int64)
    dates = Column.from_numpy(days, t.TIMESTAMP_DAYS)
    tbl = Table([month_bucket(dates), Column.from_numpy(rev)])
    dom = month_domain(1995, 1, 1995, 6)
    res = plan_groupby(tbl, [0], [(1, "sum")], [dom])
    assert res.lowered == "bounded" and not bool(res.domain_miss)
    got = _groups(res.table, res.present)
    import datetime as pydt

    oracle = {}
    for i in range(n):
        d = pydt.date(1970, 1, 1) + pydt.timedelta(days=int(days[i]))
        key = (month_code(d.year, d.month),)
        oracle[key] = oracle.get(key, 0) + int(rev[i])
    assert {k: v[0] for k, v in got.items()} == oracle


def test_bounded_string_plan_is_sort_free(rng):
    """HLO pin (the test_tpch.py:239 contract, now for string keys):
    encode + bounded groupby + decode lowers with zero sorts and zero
    scatters."""
    n = 256
    modes = ["AIR", "MAIL", "SHIP"]
    idx = rng.integers(0, 3, n)
    from spark_rapids_jni_tpu.ops.strings import pad_strings

    col = pad_strings(Column.from_pylist(
        [modes[i] for i in idx], t.STRING))
    vals = Column.from_numpy(rng.integers(0, 9, n).astype(np.int64))
    dom = string_domain(modes)

    def digest(mode_col, val_col):
        res = plan_groupby(Table([mode_col, val_col]), [0],
                           [(1, "sum")], [dom])
        acc = jnp.float64(0)
        for c in res.table.columns:
            acc = acc + jnp.sum(c.data).astype(jnp.float64)
            acc = acc + jnp.sum(c.valid_mask())
            if c.chars is not None:
                acc = acc + jnp.sum(c.chars)
        return acc + jnp.sum(res.present) + res.domain_miss

    hlo = jax.jit(digest).lower(col, vals).compile().as_text()
    assert not [l for l in hlo.splitlines()
                if re.search(r"= \S+ sort\(", l)]
    assert not [l for l in hlo.splitlines() if " scatter(" in l]


# ---------------------------------------------------------------------------
# planned q12 / q4 — two more queries on the sort-free path
# ---------------------------------------------------------------------------


def test_q12_planned_matches_oracle():
    from spark_rapids_jni_tpu.models.tpch import (
        lineitem_q12_table,
        orders_q12_table,
        tpch_q12_numpy,
        tpch_q12_planned_result,
    )

    li = lineitem_q12_table(800, 300)
    orders = orders_q12_table(300)
    res = tpch_q12_planned_result(orders, li)
    assert res.lowered == "bounded" and not bool(res.domain_miss)
    got = {k[0]: list(v) for k, v in
           _groups(res.table, res.present).items()}
    oracle = tpch_q12_numpy(orders, li)
    assert got == oracle


def test_q4_planned_matches_oracle():
    from spark_rapids_jni_tpu.models.tpch import (
        lineitem_q12_table,
        orders_q4_table,
        tpch_q4_numpy,
        tpch_q4_planned_result,
    )

    orders = orders_q4_table(400)
    li = lineitem_q12_table(900, 400)
    res = tpch_q4_planned_result(orders, li)
    assert res.lowered == "bounded" and not bool(res.domain_miss)
    got = {k[0]: v[0] for k, v in
           _groups(res.table, res.present).items()}
    oracle = tpch_q4_numpy(orders, li)
    assert got == oracle


def test_q12_planned_agg_stage_sort_free():
    """The aggregation stage of planned q12 (post-join keyed table ->
    grouped output) compiles with zero sorts/scatters. The join itself
    is sort-based machinery and is outside this pin."""
    from spark_rapids_jni_tpu.models.tpch import _Q12_MODES
    from spark_rapids_jni_tpu.ops.planner import plan_groupby, string_domain
    from spark_rapids_jni_tpu.ops.strings import pad_strings

    rng = np.random.default_rng(0)
    n = 256
    modes = ["MAIL", "SHIP"]
    idx = rng.integers(0, 2, n)
    keyed = Table([
        pad_strings(Column.from_pylist(
            [modes[i] for i in idx], t.STRING)),
        Column.from_numpy(rng.integers(0, 2, n).astype(np.int64)),
        Column.from_numpy(rng.integers(0, 2, n).astype(np.int64)),
    ])

    def digest(tb):
        res = plan_groupby(tb, [0], [(1, "sum"), (2, "sum")],
                           [string_domain(modes)])
        acc = jnp.float64(0)
        for c in res.table.columns:
            acc = acc + jnp.sum(c.data).astype(jnp.float64)
            if c.chars is not None:
                acc = acc + jnp.sum(c.chars)
        return acc

    hlo = jax.jit(digest).lower(keyed).compile().as_text()
    assert not [l for l in hlo.splitlines()
                if re.search(r"= \S+ sort\(", l)]
    assert not [l for l in hlo.splitlines() if " scatter(" in l]


def test_q1_planned_still_lowers_bounded():
    """q1 rewired through the planner facility keeps its contract."""
    from spark_rapids_jni_tpu.models.tpch import (
        lineitem_table,
        tpch_q1_numpy,
        tpch_q1_planned,
    )
    from tests.test_tpch import _q1_groups

    li = lineitem_table(512, seed=3)
    out = tpch_q1_planned(li)
    oracle = tpch_q1_numpy(li)
    got = _q1_groups(out)
    assert got.keys() == oracle.keys()


def test_bounded_plan_on_empty_table():
    """Lowering is a static plan fact: empty tables take the bounded
    plan too (regression: an n>0 eligibility gate broke
    tpch_q1_planned on empty partitions)."""
    tbl = Table([
        Column.from_numpy(np.zeros(0, np.int8)),
        Column.from_numpy(np.zeros(0, np.int64)),
    ])
    res = plan_groupby(tbl, [0], [(1, "sum")], [scalar_domain([0, 1])])
    assert res.lowered == "bounded"
    assert not bool(np.asarray(res.present).any())

    from spark_rapids_jni_tpu.models.tpch import (
        lineitem_table,
        tpch_q1_planned,
    )

    out = tpch_q1_planned(lineitem_table(0))
    assert out.num_rows == 12  # the static slot table, nothing present


# ---------------------------------------------------------------------------
# dense-PK joins (planner-declared clustered primary keys)
# ---------------------------------------------------------------------------


def test_dense_pk_join_clustered_matches_bruteforce(rng):
    from spark_rapids_jni_tpu.ops.planner import dense_pk_join

    nb, n = 50, 300
    bkeys = np.arange(1, nb + 1, dtype=np.int64)
    bvals = rng.integers(0, 100, nb).astype(np.int64)
    bvalid = rng.random(nb) > 0.2  # filtered build rows (WHERE idiom)
    build = Table([
        Column.from_numpy(bkeys, validity=bvalid),
        Column.from_numpy(bvals),
    ])
    pkeys = rng.integers(-3, nb + 4, n).astype(np.int64)  # some OOR
    probe = Table([Column.from_numpy(pkeys)])
    res = dense_pk_join(probe, build, 0, 0, 1, nb, clustered=True)
    assert not bool(res.pk_violation)
    got_k = res.table.column(1).to_pylist()
    got_v = res.table.column(2).to_pylist()
    matched = np.asarray(res.matched)
    cnt = 0
    for i in range(n):
        k = int(pkeys[i])
        if 1 <= k <= nb and bvalid[k - 1]:
            assert matched[i] and got_k[i] == k
            assert got_v[i] == int(bvals[k - 1])
            cnt += 1
        else:
            assert not matched[i]
            assert got_k[i] is None and got_v[i] is None
    assert int(res.total) == cnt


@pytest.mark.parametrize("width, build_key", [
    (1, 0), (3, 0), (3, 1), (3, 2)],
    ids=["key_only", "key_first", "key_middle", "key_last"])
def test_dense_pk_join_clustered_key_column_is_the_probe_key(
        rng, width, build_key):
    """The brute force of the test above wherever the build key stands:
    its column of the output is the probe key under ``matched`` (no
    gather reads it), in the build key's dtype and place, and every other
    build column is gathered as before."""
    from spark_rapids_jni_tpu.ops.planner import dense_pk_join

    nb, n = 50, 300
    bvalid = rng.random(nb) > 0.2  # filtered build rows (WHERE idiom)
    payload = [rng.integers(0, 100, nb).astype(dt)
               for dt in (np.int64, np.int32)][:width - 1]
    cols = [Column.from_numpy(v) for v in payload]
    cols.insert(build_key, Column.from_numpy(
        np.arange(1, nb + 1, dtype=np.int32), validity=bvalid))
    build = Table(cols)
    pkeys = rng.integers(-3, nb + 4, n).astype(np.int64)  # some OOR
    pvalid = rng.random(n) > 0.1                          # some null
    probe = Table([Column.from_numpy(pkeys, validity=pvalid)])
    res = dense_pk_join(probe, build, 0, build_key, 1, nb, clustered=True)
    assert not bool(res.pk_violation)
    assert res.table.num_columns == 1 + width
    want = (pvalid & (pkeys >= 1) & (pkeys <= nb)
            & bvalid[np.clip(pkeys - 1, 0, nb - 1)])
    assert np.asarray(res.matched).tolist() == want.tolist()
    assert int(res.total) == int(want.sum())
    values = [v[np.clip(pkeys - 1, 0, nb - 1)] for v in payload]
    values.insert(build_key, pkeys)
    for at, vals in enumerate(values):
        got = res.table.column(1 + at)
        assert got.dtype == build.column(at).dtype
        assert got.to_pylist() == [
            int(v) if m else None for v, m in zip(vals, want)]


@pytest.mark.parametrize("misplaced_valid, violation", [
    (True, True), (False, False)], ids=["valid_key", "null_key"])
def test_dense_pk_join_clustered_checks_the_build_side(misplaced_valid,
                                                       violation):
    """The layout is verified where the build lives: a misplaced key that
    NO probe row touches raises ``pk_violation`` (the probe-side compare
    this join made before PR 40 let it pass); a null build key is a
    filtered row whatever lies under it."""
    from spark_rapids_jni_tpu.ops.planner import dense_pk_join

    build = Table([
        Column.from_numpy(np.asarray([1, 99, 3], np.int64),
                          validity=np.asarray([True, misplaced_valid, True])),
        Column.from_numpy(np.asarray([7, 8, 9], np.int64)),
    ])
    probe = Table([Column.from_numpy(np.asarray([1, 3, 3], np.int64))])
    res = dense_pk_join(probe, build, 0, 0, 1, 3, clustered=True)
    assert bool(res.pk_violation) == violation
    assert res.table.column(2).to_pylist() == [7, 9, 9]


@pytest.mark.parametrize("keep, payload", [
    (False, []), (True, [("pred", 4096), ("s32", 4096)])],
    ids=["payload_dropped", "payload_kept"])
def test_dense_pk_join_clustered_gathers_what_is_read(keep, payload):
    """Jitted, the probe's rows gather ONE bit, the build key's validity,
    and a build column's data and mask only where something reads the
    column: never the key's words, and the layout check is a pass over
    the build's rows. No knob says so: a caller that drops a column from
    the join's table leaves its gathers without a user."""
    from spark_rapids_jni_tpu.ops.planner import dense_pk_join

    nb, n = 64, 4096
    build = Table([
        Column.from_numpy(np.arange(1, nb + 1, dtype=np.int64),
                          validity=np.arange(nb) % 3 > 0),
        Column.from_numpy(np.arange(nb, dtype=np.int32),
                          validity=np.arange(nb) % 5 > 0),
    ])
    probe = Table([Column.from_numpy(
        (np.arange(n, dtype=np.int64) * 7) % (nb + 2))])

    def join(p, b):
        r = dense_pk_join(p, b, 0, 0, 1, nb, clustered=True)
        table = r.table if keep else Table(r.table.columns[:2])
        return table, r.total, r.pk_violation

    hlo = jax.jit(join).lower(probe, build).compile().as_text()
    gathers = sorted(
        (kind, *[int(d) for d in dims.split(",") if d != "1"])
        for kind, dims in re.findall(
            r"= (\w+)\[([\d,]*)\]\S* gather\(", hlo))
    assert gathers == sorted([("pred", n)] + payload), gathers
    assert not re.search(r"= \S+ sort\(", hlo) and " scatter(" not in hlo


def test_dense_pk_join_sorted_mode_matches(rng):
    from spark_rapids_jni_tpu.ops.planner import dense_pk_join

    nb, n = 40, 200
    bkeys = rng.permutation(np.arange(1, nb + 1)).astype(np.int64)
    bvals = np.arange(nb, dtype=np.int64) * 10
    build = Table([Column.from_numpy(bkeys), Column.from_numpy(bvals)])
    pkeys = rng.integers(1, nb + 1, n).astype(np.int64)
    probe = Table([Column.from_numpy(pkeys)])
    res = dense_pk_join(probe, build, 0, 0, 1, nb, clustered=False)
    assert not bool(res.pk_violation)
    pos_of = {int(k): i for i, k in enumerate(bkeys)}
    got_v = res.table.column(2).to_pylist()
    for i in range(n):
        assert got_v[i] == pos_of[int(pkeys[i])] * 10


def test_dense_pk_join_clustered_violation_flags():
    from spark_rapids_jni_tpu.ops.planner import dense_pk_join

    # slot 1 holds key 99 — the clustered declaration is a lie
    build = Table([
        Column.from_numpy(np.asarray([1, 99, 3], np.int64)),
        Column.from_numpy(np.asarray([7, 8, 9], np.int64)),
    ])
    probe = Table([Column.from_numpy(np.asarray([2], np.int64))])
    res = dense_pk_join(probe, build, 0, 0, 1, 3, clustered=True)
    assert bool(res.pk_violation)


def test_dense_pk_join_sorted_duplicate_flags():
    from spark_rapids_jni_tpu.ops.planner import dense_pk_join

    build = Table([
        Column.from_numpy(np.asarray([1, 2, 2], np.int64)),
        Column.from_numpy(np.asarray([7, 8, 9], np.int64)),
    ])
    probe = Table([Column.from_numpy(np.asarray([2], np.int64))])
    res = dense_pk_join(probe, build, 0, 0, 1, 3, clustered=False)
    assert bool(res.pk_violation)


def test_dense_pk_join_sorted_rejects_sentinel_key_range():
    """Sorted mode overwrites null keys with iinfo(dtype).max; a
    declared range reaching dtype max would let a legitimate key alias
    the null sentinel (advisor r5 / tpulint sentinel-safety class), so
    the declaration must be rejected up front."""
    from spark_rapids_jni_tpu.ops.planner import dense_pk_join

    hi = np.iinfo(np.int64).max
    build = Table([
        Column.from_numpy(np.asarray([hi - 1, hi], np.int64)),
        Column.from_numpy(np.asarray([7, 8], np.int64)),
    ])
    probe = Table([Column.from_numpy(np.asarray([hi], np.int64))])
    with pytest.raises(ValueError, match="sentinel"):
        dense_pk_join(probe, build, 0, 0, hi - 1, hi, clustered=False)
    # a range strictly below dtype max stays accepted
    res = dense_pk_join(
        Table([Column.from_numpy(np.asarray([5], np.int64))]),
        Table([Column.from_numpy(np.asarray([4, 5, 6], np.int64)),
               Column.from_numpy(np.asarray([7, 8, 9], np.int64))]),
        0, 0, 4, 6, clustered=False)
    assert not bool(res.pk_violation)
    assert res.table.column(2).to_pylist() == [8]


def test_q3_planned_matches_general_and_oracle():
    from spark_rapids_jni_tpu.models.tpch import (
        customer_table,
        lineitem_q3_table,
        orders_table,
        tpch_q3_numpy,
        tpch_q3_planned,
    )

    n_cust, n_ord, n = 40, 160, 1200
    c = customer_table(n_cust)
    o = orders_table(n_ord, n_cust)
    li = lineitem_q3_table(n, n_ord)
    res = tpch_q3_planned(c, o, li)
    assert not bool(res.pk_violation)
    oracle = tpch_q3_numpy(c, o, li)
    tbl = res.result.table
    keys = tbl.column(0).to_pylist()
    dates = tbl.column(1).to_pylist()
    prios = tbl.column(2).to_pylist()
    revs = tbl.column(3).to_pylist()
    got = {}
    for i in range(tbl.num_rows):
        if keys[i] is None:
            continue
        got[keys[i]] = (revs[i], dates[i], prios[i])
    assert got == oracle
    # date and priority are looked up by the group's key (PR 40): the null
    # group's key names no order, so its two are null, as every pad row's
    assert int(res.result.num_groups) == len(oracle) + 1
    assert all(dates[i] is None and prios[i] is None
               for i in range(tbl.num_rows) if keys[i] is None)
    assert int(res.join_total) == sum(
        int(k) in oracle for k, d in zip(
            np.asarray(li.column(0).data), np.asarray(li.column(3).data))
        if d > 9204)
    # ORDER BY revenue DESC: the live prefix is non-increasing, and
    # every null-key row strictly follows every real row
    first_null = next((i for i in range(tbl.num_rows)
                       if keys[i] is None), tbl.num_rows)
    assert all(keys[i] is None for i in range(first_null, tbl.num_rows))
    live = revs[:first_null]
    assert all(live[i] >= live[i + 1] for i in range(len(live) - 1))


def test_q3_planned_join_phase_sort_free():
    """The dense-PK join phase (both joins, pre-groupby) compiles with
    zero sorts — the general q3's two build lexsorts are gone."""
    from spark_rapids_jni_tpu.models.tpch import (
        _q3_inputs,
        customer_table,
        lineitem_q3_table,
        orders_table,
    )
    from spark_rapids_jni_tpu.ops.planner import dense_pk_join

    n_cust, n_ord, n = 16, 64, 256
    c = customer_table(n_cust)
    o = orders_table(n_ord, n_cust)
    li = lineitem_q3_table(n, n_ord)

    def join_phase(cu, orr, lit):
        cust, ord_t, probe = _q3_inputs(cu, orr, lit, 0, 9204)
        j1 = dense_pk_join(ord_t, cust, 0, 0, 1, n_cust, clustered=True)
        build2 = Table([
            Column(j1.table.column(1).dtype, j1.table.column(1).data,
                   j1.table.column(1).valid_mask() & j1.matched),
            j1.table.column(2), j1.table.column(3),
        ])
        j2 = dense_pk_join(probe, build2, 0, 0, 1, n_ord, clustered=True)
        acc = jnp.float64(0)
        for col in j2.table.columns:
            acc = acc + jnp.sum(col.data).astype(jnp.float64)
            acc = acc + jnp.sum(col.valid_mask())
        return acc + j2.total + j2.pk_violation

    hlo = jax.jit(join_phase).lower(c, o, li).compile().as_text()
    assert not [l for l in hlo.splitlines()
                if re.search(r"= \S+ sort\(", l)]
    assert not [l for l in hlo.splitlines() if " scatter(" in l]


def test_dense_pk_join_sorted_mode_null_build_keys(rng):
    """Regression: null build keys (the _null_where WHERE idiom) sorted
    by raw data broke the binary search's monotonicity and silently
    dropped matches for large valid keys."""
    from spark_rapids_jni_tpu.ops.planner import dense_pk_join

    bkeys = np.asarray([5, 10, 1, 2], np.int64)
    bvalid = np.asarray([True, True, False, False])
    build = Table([
        Column.from_numpy(bkeys, validity=bvalid),
        Column.from_numpy(np.asarray([50, 100, 10, 20], np.int64)),
    ])
    probe = Table([Column.from_numpy(np.asarray([10, 5, 1], np.int64))])
    res = dense_pk_join(probe, build, 0, 0, 1, 10, clustered=False)
    assert not bool(res.pk_violation)
    assert np.asarray(res.matched).tolist() == [True, True, False]
    assert res.table.column(2).to_pylist() == [100, 50, None]


def test_dense_pk_join_sorted_mode_out_of_range_build_key_flags():
    from spark_rapids_jni_tpu.ops.planner import dense_pk_join

    build = Table([
        Column.from_numpy(np.asarray([1, 100], np.int64)),
        Column.from_numpy(np.asarray([7, 8], np.int64)),
    ])
    probe = Table([Column.from_numpy(np.asarray([1], np.int64))])
    res = dense_pk_join(probe, build, 0, 0, 1, 40, clustered=False)
    assert bool(res.pk_violation)  # declared range was a lie


def test_dense_id_counts_matches_bincount(rng):
    from spark_rapids_jni_tpu.ops.planner import dense_id_counts

    m, n = 37, 5000
    gid = rng.integers(0, m + 1, n)  # m = "counts nowhere"
    got = np.asarray(dense_id_counts(jnp.asarray(gid), m, block=512))
    want = np.bincount(gid[gid < m], minlength=m)
    assert (got == want).all()
    assert np.asarray(
        dense_id_counts(jnp.zeros((0,), jnp.int32), m)).sum() == 0


def test_q14_planned_matches_oracle_and_whole_query_sort_free():
    from spark_rapids_jni_tpu.models.tpch import (
        lineitem_q14_table,
        part_table,
        tpch_q14_numpy,
        tpch_q14_planned,
    )
    from spark_rapids_jni_tpu.ops.strings import pad_strings

    n_part, n = 64, 1024
    part = part_table(n_part)
    pcols = list(part.columns)
    pcols[1] = pad_strings(pcols[1])
    part = Table(pcols)
    li = lineitem_q14_table(n, n_part)
    res = tpch_q14_planned(part, li)
    assert not bool(res.pk_violation)
    promo, total = tpch_q14_numpy(part, li)
    assert int(res.promo_revenue) == promo
    assert int(res.total_revenue) == total

    def digest(p, l):
        r = tpch_q14_planned(p, l)
        return (r.promo_revenue + 3 * r.total_revenue
                + 7 * r.join_total.astype(jnp.int64) + r.pk_violation)

    hlo = jax.jit(digest).lower(part, li).compile().as_text()
    # the ENTIRE q14 plan is sort-free: join is arithmetic+gather,
    # aggregate is two global masked sums
    assert not [l for l in hlo.splitlines()
                if re.search(r"= \S+ sort\(", l)]
    assert not [l for l in hlo.splitlines() if " scatter(" in l]


def test_q72_planned_matches_oracle():
    from spark_rapids_jni_tpu.models import tpcds

    n = 3000
    cs = tpcds.catalog_sales_table(n, num_items=50, num_days=400)
    dd = tpcds.date_dim_table(400)
    it = tpcds.item_table(50)
    inv = tpcds.inventory_table(num_items=50, num_weeks=60)
    res = tpcds.tpcds_q72_planned(cs, dd, it, inv)
    assert not bool(res.pk_violation)
    oracle = tpcds.tpcds_q72_numpy(cs, dd, it, inv)
    tbl = res.table
    sk = tbl.column(0).to_pylist()
    br = tbl.column(1).to_pylist()
    ct = tbl.column(2).to_pylist()
    got = {}
    for i in range(tbl.num_rows):
        if sk[i] is None or ct[i] is None or ct[i] == 0:
            continue
        got[(sk[i], br[i])] = ct[i]
    assert got == oracle
    # ORDER BY count desc on the live head
    live = [ct[i] for i in range(tbl.num_rows) if sk[i] is not None]
    assert all(live[i] >= live[i + 1] for i in range(len(live) - 1))


def test_q72_planned_no_probe_length_sorts():
    """Every remaining sort in the planned q72 is over the num_items
    output (the final ORDER BY), never over the n-sized probe path."""
    from spark_rapids_jni_tpu.models import tpcds

    n, items = 4096, 64
    cs = tpcds.catalog_sales_table(n, num_items=items, num_days=200)
    dd = tpcds.date_dim_table(200)
    it = tpcds.item_table(items)
    inv = tpcds.inventory_table(num_items=items, num_weeks=30)

    def digest(a, b, c, d):
        r = tpcds.tpcds_q72_planned(a, b, c, d)
        acc = jnp.float64(0)
        for col in r.table.columns:
            acc = acc + jnp.sum(col.data).astype(jnp.float64)
            acc = acc + jnp.sum(col.valid_mask())
        return acc + jnp.sum(r.present) + r.pk_violation

    hlo = jax.jit(digest).lower(cs, dd, it, inv).compile().as_text()
    sort_lines = [l for l in hlo.splitlines()
                  if re.search(r"= \S+ sort\(", l)]
    assert all(str(n) not in l for l in sort_lines), sort_lines
    assert not [l for l in hlo.splitlines() if " scatter(" in l]


def test_q64_planned_join_elimination_matches_oracle(rng):
    from spark_rapids_jni_tpu.models import tpcds

    ss = tpcds.store_sales_table(4000)
    res = tpcds.tpcds_q64_planned(ss)
    oracle = tpcds.tpcds_q64_numpy(ss)
    tbl = res.result.table
    sk = tbl.column(0).to_pylist()
    ct = tbl.column(1).to_pylist()
    got = {sk[i]: ct[i] for i in range(tbl.num_rows)
           if sk[i] is not None and ct[i] and ct[i] > 0}
    assert got == oracle
    assert int(res.join_total) == sum(oracle.values())
    # general plan agrees too (both against the same oracle)
    gen = tpcds.tpcds_q64(ss)
    assert int(gen.join_total) == int(res.join_total)


def test_q19_planned_matches_oracle_and_sort_free():
    from spark_rapids_jni_tpu.models.tpch import (
        lineitem_q19_table,
        part_table,
        tpch_q19_numpy,
        tpch_q19_planned,
    )
    from spark_rapids_jni_tpu.ops.strings import pad_strings

    n_part, n = 48, 900
    part = part_table(n_part)
    pcols = list(part.columns)
    pcols[2] = pad_strings(pcols[2])
    pcols[3] = pad_strings(pcols[3])
    part = Table(pcols)
    li = lineitem_q19_table(n, n_part)
    lcols = list(li.columns)
    lcols[4] = pad_strings(lcols[4])  # jit needs static string widths
    lcols[5] = pad_strings(lcols[5])
    li = Table(lcols)
    res = tpch_q19_planned(part, li)
    assert not bool(res.pk_violation)
    assert int(res.revenue) == tpch_q19_numpy(part, li)

    def digest(p, l):
        r = tpch_q19_planned(p, l)
        return (r.revenue + 3 * r.join_total.astype(jnp.int64)
                + r.pk_violation)

    hlo = jax.jit(digest).lower(part, li).compile().as_text()
    assert not [l for l in hlo.splitlines()
                if re.search(r"= \S+ sort\(", l)]


def test_q5_six_table_plan_matches_oracle_and_sort_free():
    from spark_rapids_jni_tpu.models.tpch import (
        customer_q5_table,
        lineitem_q5_table,
        nation_table,
        orders_table,
        supplier_table,
        tpch_q5,
        tpch_q5_numpy,
    )

    n_cust, n_ord, n_supp, n = 64, 200, 32, 1500
    c = customer_q5_table(n_cust)
    o = orders_table(n_ord, n_cust)
    li = lineitem_q5_table(n, n_ord, n_supp)
    su = supplier_table(n_supp)
    na = nation_table()
    res = tpch_q5(c, o, li, su, na)
    assert not bool(res.pk_violation) and not bool(res.domain_miss)
    oracle = tpch_q5_numpy(c, o, li, su, na)
    keys = res.table.column(0).to_pylist()
    revs = res.table.column(1).to_pylist()
    present = np.asarray(res.present)
    got = {keys[i]: revs[i] for i in range(res.table.num_rows)
           if present[i] and keys[i] is not None and revs[i]}
    assert got == {k: v for k, v in oracle.items() if v}
    # revenue desc on the live prefix
    live = [revs[i] for i in range(len(keys)) if present[i] and keys[i]]
    assert all(live[i] >= live[i + 1] for i in range(len(live) - 1))
    # static n_name decode rides the tiny sort with its key
    from spark_rapids_jni_tpu.models.tpch import _Q5_NATIONS

    names = res.table.column(2).to_pylist()
    for i in range(res.table.num_rows):
        if present[i] and keys[i] is not None:
            assert names[i] == _Q5_NATIONS[keys[i] - 1]

    def digest(a, b, d, e, f):
        r = tpch_q5(a, b, d, e, f)
        acc = jnp.float64(0)
        for col in r.table.columns:
            acc = acc + jnp.sum(col.data).astype(jnp.float64)
            acc = acc + jnp.sum(col.valid_mask())
        return acc + r.pk_violation + r.domain_miss

    hlo = jax.jit(digest).lower(c, o, li, su, na).compile().as_text()
    sort_lines = [l for l in hlo.splitlines()
                  if re.search(r"= \S+ sort\(", l)]
    # only the 26-slot final ORDER BY may sort; nothing n-sized
    assert all(str(n) not in l for l in sort_lines), sort_lines
    assert not [l for l in hlo.splitlines() if " scatter(" in l]


def test_dense_id_sums_matches_bincount_weights(rng):
    from spark_rapids_jni_tpu.ops.planner import dense_id_sums

    m, n = 29, 4000
    gid = rng.integers(0, m + 2, n)  # some out of range
    vals = rng.integers(-10**9, 10**9, n)
    got = np.asarray(dense_id_sums(
        jnp.asarray(gid), jnp.asarray(vals), m, block=512))
    want = np.bincount(gid[gid < m], weights=vals[gid < m].astype(float),
                       minlength=m).astype(np.int64)
    assert (got == want).all()


def test_tpcds_q3_star_plan_matches_oracle():
    from spark_rapids_jni_tpu.models import tpcds

    # 730 days: month 11 exists in BOTH years — pins the (d_year,
    # brand) two-level grouping (a single-level brand key would merge
    # the years' November revenue)
    dd = tpcds.date_dim_table(730)
    ss = tpcds.store_sales_q3_table(3000, num_items=80, num_days=730)
    it = tpcds.item_q3_table(80)
    res = tpcds.tpcds_q3(dd, ss, it)
    assert not bool(res.pk_violation)
    assert not bool(res.brand_domain_miss)
    oracle = tpcds.tpcds_q3_numpy(dd, ss, it)
    years = res.table.column(0).to_pylist()
    keys = res.table.column(1).to_pylist()
    revs = res.table.column(2).to_pylist()
    present = np.asarray(res.present)
    got = {(years[i], keys[i]): revs[i]
           for i in range(res.table.num_rows)
           if present[i] and keys[i] is not None}
    # count-derived presence: EVERY group with a kept row is emitted,
    # including any whose revenue nets to zero
    assert got == oracle
    assert len({y for y, _ in got}) == 2  # both years really present
    live = [revs[i] for i in range(len(keys)) if present[i]]
    assert all(live[i] >= live[i + 1] for i in range(len(live) - 1))


def test_tpcds_q3_zero_revenue_group_is_present():
    """A group whose revenue nets to exactly zero (refund offsets the
    sale) must still be emitted: presence is dense_id_counts > 0, not
    sums != 0 (advisor r5 / tpulint bitmask-via-helpers class)."""
    from spark_rapids_jni_tpu.models import tpcds

    dd = tpcds.date_dim_table(365)  # year 2000; month 11 = sk 311..341
    it = Table([
        Column.from_numpy(np.asarray([1, 2], np.int64)),    # i_item_sk
        Column.from_numpy(np.asarray([3, 5], np.int64)),    # i_brand_id
        Column.from_numpy(np.asarray([7, 7], np.int64)),    # i_manufact_id
    ])
    ss = Table([
        Column.from_numpy(np.asarray([311, 312, 311], np.int64)),
        Column.from_numpy(np.asarray([1, 1, 2], np.int64)),
        Column.from_numpy(np.asarray([500, -500, 250], np.int64),
                          t.decimal64(-2)),
    ])
    res = tpcds.tpcds_q3(dd, ss, it)
    assert not bool(res.pk_violation)
    years = res.table.column(0).to_pylist()
    keys = res.table.column(1).to_pylist()
    revs = res.table.column(2).to_pylist()
    present = np.asarray(res.present)
    got = {(years[i], keys[i]): revs[i]
           for i in range(res.table.num_rows) if present[i]}
    assert got == tpcds.tpcds_q3_numpy(dd, ss, it)
    assert got[(2000, 3)] == 0  # the refund group survives


def test_tpcds_q3_brand_domain_miss_flags():
    from spark_rapids_jni_tpu.models import tpcds

    dd = tpcds.date_dim_table(365)
    ss = tpcds.store_sales_q3_table(500, num_items=20, num_days=365)
    it = tpcds.item_q3_table(20)
    # every item passes the manufacturer filter so kept rows certainly
    # exist; declare a brand bound smaller than the data's: revenue
    # would be dropped, so the miss flag must fire
    icols = list(it.columns)
    icols[2] = Column.from_numpy(np.full(20, 7, np.int64))
    it = Table(icols)
    res = tpcds.tpcds_q3(dd, ss, it, num_brands=5)
    assert bool(res.brand_domain_miss)


def test_tpcds_q3_no_probe_length_sorts():
    import re as _re

    from spark_rapids_jni_tpu.models import tpcds

    n = 4096
    dd = tpcds.date_dim_table(200)
    ss = tpcds.store_sales_q3_table(n, num_items=64, num_days=200)
    it = tpcds.item_q3_table(64)

    def digest(a, b, c):
        r = tpcds.tpcds_q3(a, b, c)
        acc = jnp.float64(0)
        for col in r.table.columns:
            acc = acc + jnp.sum(col.data).astype(jnp.float64)
            acc = acc + jnp.sum(col.valid_mask())
        return acc + r.pk_violation

    hlo = jax.jit(digest).lower(dd, ss, it).compile().as_text()
    sort_lines = [l for l in hlo.splitlines()
                  if _re.search(r"= \S+ sort\(", l)]
    assert all(str(n) not in l for l in sort_lines), sort_lines
    assert not [l for l in hlo.splitlines() if " scatter(" in l]


def test_q10_mixed_plan_matches_oracle(rng):
    from spark_rapids_jni_tpu.models.tpch import (
        customer_q5_table,
        lineitem_q3_table,
        orders_table,
        tpch_q10,
        tpch_q10_numpy,
    )

    n_cust, n_ord, n = 40, 150, 1200
    c = customer_q5_table(n_cust)
    o = orders_table(n_ord, n_cust)
    li3 = lineitem_q3_table(n, n_ord)
    flags = Column.from_numpy(
        rng.choice(np.frombuffer(b"ANR", np.int8), n))
    li = Table(list(li3.columns) + [flags])
    res = tpch_q10(c, o, li)
    assert not bool(res.pk_violation)
    oracle = tpch_q10_numpy(c, o, li)
    tbl = res.result.table
    keys = tbl.column(0).to_pylist()
    nats = tbl.column(1).to_pylist()
    revs = tbl.column(2).to_pylist()
    got = {keys[i]: (nats[i], revs[i]) for i in range(tbl.num_rows)
           if keys[i] is not None}
    assert got == oracle
    live = [revs[i] for i in range(tbl.num_rows) if keys[i] is not None]
    assert all(live[i] >= live[i + 1] for i in range(len(live) - 1))


def test_domain_from_parquet_drives_bounded_plan(tmp_path):
    """The reader -> planner loop: derive a key domain from a Parquet
    sample, lower the groupby to the bounded plan with it, and rely on
    domain_miss as the backstop when the sample missed values."""
    pa = pytest.importorskip("pyarrow")
    pq = pytest.importorskip("pyarrow.parquet")

    from spark_rapids_jni_tpu.ops.planner import domain_from_parquet
    from spark_rapids_jni_tpu.parquet.reader import read_table

    rng = np.random.default_rng(3)
    keys = rng.integers(0, 4, 2000).astype(np.int64)
    vals = rng.integers(0, 50, 2000).astype(np.int64)
    path = str(tmp_path / "f.parquet")
    pq.write_table(pa.table({"k": keys, "v": vals}), path,
                   row_group_size=500)
    dom = domain_from_parquet(path, 0)
    assert dom is not None and dom.source == "observed"
    tbl = read_table(path)
    res = plan_groupby(tbl, [0], [(1, "sum")], [dom])
    assert res.lowered == "bounded"
    # the first row group almost surely saw all 4 keys; if not, the
    # miss flag is the documented re-plan signal — assert coherence
    got = _groups(res.table, res.present)
    oracle = {}
    for k, v in zip(keys, vals):
        oracle[(int(k),)] = (oracle.get((int(k),), (0,))[0] + int(v),)
    if not bool(res.domain_miss):
        assert got == oracle

    # a sample that provably misses values must raise the flag
    keys2 = np.concatenate([np.zeros(500, np.int64),
                            np.full(500, 9, np.int64)])
    path2 = str(tmp_path / "g.parquet")
    pq.write_table(pa.table({"k": keys2, "v": keys2}), path2,
                   row_group_size=500)
    dom2 = domain_from_parquet(path2, 0)  # sample sees only key 0
    assert dom2.values == (0,)
    tbl2 = read_table(path2)
    res2 = plan_groupby(tbl2, [0], [(1, "sum")], [dom2])
    assert bool(res2.domain_miss)  # the backstop fires


def test_plan_groupby_auto_grows_until_complete(rng):
    from spark_rapids_jni_tpu.ops.planner import plan_groupby_auto

    n = 300
    tbl = Table([
        Column.from_numpy(np.arange(n, dtype=np.int32)),
        Column.from_numpy(np.ones(n, np.int64)),
    ])
    res = plan_groupby_auto(tbl, [0], [(1, "sum")], [None], budget=16)
    assert res.lowered == "general" and not bool(res.overflowed)
    assert len(_groups(res.table, res.present)) == n

    with pytest.raises(ValueError, match="max_budget"):
        plan_groupby_auto(tbl, [0], [(1, "sum")], [None], budget=16,
                          max_budget=64)


def test_plan_groupby_auto_budget_clamps():
    from spark_rapids_jni_tpu.ops.planner import plan_groupby_auto

    tbl = Table([
        Column.from_numpy(np.arange(100, dtype=np.int32)),
        Column.from_numpy(np.ones(100, np.int64)),
    ])
    # sub-positive budget must terminate (raise at the cap), not spin
    res = plan_groupby_auto(tbl, [0], [(1, "sum")], [None], budget=0)
    assert not bool(res.overflowed)
    # a starting budget above max_budget must still honor the cap
    with pytest.raises(ValueError, match="max_budget"):
        plan_groupby_auto(tbl, [0], [(1, "sum")], [None],
                          budget=4096, max_budget=64)


# ---------------------------------------------------------------------------
# declared key ranges: a groupby key at the width its range takes
# ---------------------------------------------------------------------------


def _ranged_key(np_dt, lo, hi, n=500, seed=9):
    """A key column over [lo, hi] with ties, both ends, and nulls."""
    rng = np.random.default_rng(seed)
    pool = np.r_[lo, hi, rng.integers(lo, hi + 1, 12)].astype(np_dt)
    vals = pool[rng.integers(0, len(pool), n)]
    return vals, rng.random(n) > 0.15


@pytest.mark.parametrize("np_dt, lo, hi, narrow", [
    (np.int64, 1, 1_500_000, t.UINT32),
    (np.int64, 1, 65_536, t.UINT16),
    (np.int64, -40_000, 25_535, t.UINT16),
    (np.int64, -(1 << 40), -(1 << 40) + 255, t.UINT8),
    (np.int64, 0, (1 << 32) - 1, t.UINT32),
    (np.int32, -7, 200, t.UINT8),
    (np.int32, 1, 65_536, t.UINT16),
], ids=["i64_21bit", "i64_16bit", "i64_lo_negative", "i64_far_from_zero",
        "i64_32bit", "i32_8bit", "i32_16bit"])
def test_narrow_group_keys_rebases_to_the_ranges_width(np_dt, lo, hi, narrow):
    from spark_rapids_jni_tpu.ops.planner import (narrow_group_keys,
                                                  widen_group_keys)

    vals, valid = _ranged_key(np_dt, lo, hi)
    dt = t.DType.from_numpy(np.dtype(np_dt))
    other = Column(t.INT64, jnp.arange(len(vals), dtype=jnp.int64))
    table = Table([other, Column(dt, jnp.asarray(vals), jnp.asarray(valid))])
    keyed = narrow_group_keys(table, (1,), ((lo, hi),))
    assert not bool(keyed.out_of_range)
    assert keyed.narrowed == ((0, dt, lo),)
    key = keyed.table.column(1)
    assert key.dtype == narrow
    assert np.array_equal(np.asarray(key.valid_mask()), valid)
    assert np.array_equal(np.asarray(key.data).astype(object),
                          vals.astype(object) - lo)
    assert keyed.table.column(0) is other      # what has no range stays
    # order is kept: the rebased key sorts as the key does
    assert np.array_equal(np.argsort(np.asarray(key.data), kind="stable"),
                          np.argsort(vals, kind="stable"))
    # a groupby's result has its keys first: the way back
    back = widen_group_keys(Table([key, other]), keyed.narrowed).column(0)
    assert back.dtype == dt
    assert np.array_equal(np.asarray(back.data), vals)   # nulls' bytes too
    assert np.array_equal(np.asarray(back.valid_mask()), valid)


def test_narrow_group_keys_checks_the_declaration():
    """A real row's non-null key outside the range breaks it; a null key
    and a phantom row's key are no keys."""
    from spark_rapids_jni_tpu.ops.planner import narrow_group_keys

    vals = np.array([1, 5, 9, 10, 0, 11, -3], np.int64)

    def broke(valid, row_valid=None, rng=(1, 10)):
        table = Table([Column(t.INT64, jnp.asarray(vals),
                              jnp.asarray(np.array(valid, bool)))])
        rv = None if row_valid is None else jnp.asarray(
            np.array(row_valid, bool))
        return bool(narrow_group_keys(table, (0,), (rng,), rv).out_of_range)

    assert not broke([1, 1, 1, 1, 0, 0, 0])
    assert broke([1, 1, 1, 1, 1, 0, 0])            # 0 < lo
    assert broke([1, 1, 1, 1, 0, 1, 0])            # 11 > hi
    assert broke([1, 1, 1, 1, 0, 0, 1])            # -3 wraps when rebased
    assert not broke([1] * 7, row_valid=[1, 1, 1, 1, 0, 0, 0])
    assert broke([1] * 7, row_valid=[1, 1, 1, 1, 0, 1, 0])
    assert not broke([1] * 7, rng=(-3, 11))


def test_narrow_group_keys_leaves_what_a_range_cannot_narrow():
    """A range past 32 bits, or one as wide as the key's own type, rebases
    nothing (and is not checked: nothing rests on it); a key with no range
    is not looked at."""
    from spark_rapids_jni_tpu.ops.planner import narrow_group_keys

    table = Table([
        Column(t.INT64, jnp.asarray(np.array([5, 1 << 40], np.int64))),
        Column(t.INT32, jnp.asarray(np.array([7, 70_000], np.int32))),
        Column(t.INT8, jnp.asarray(np.array([1, 2], np.int8))),
        Column.from_pylist(["a", "b"], t.STRING)])
    keyed = narrow_group_keys(
        table, (0, 1, 2, 3),
        ((0, 1 << 32), (0, 65_536), (0, 3), None))
    assert keyed.narrowed == () and not bool(keyed.out_of_range)
    assert all(a is b for a, b in zip(keyed.table.columns, table.columns))


@pytest.mark.parametrize("keys, ranges, match", [
    ((3,), ((0, 9),), "integer key"),
    ((4,), ((0, 9),), "integer key"),
    ((0,), ((9, 0),), "empty"),
    ((1,), ((0, 1 << 40),), "leaves key"),
    ((0, 1), ((0, 9),), "1 entries for 2 keys"),
], ids=["string", "float", "empty", "outside_the_type", "count"])
def test_narrow_group_keys_refuses_a_range_that_cannot_hold(keys, ranges,
                                                            match):
    from spark_rapids_jni_tpu.ops.planner import narrow_group_keys

    table = Table([
        Column(t.INT64, jnp.zeros(2, jnp.int64)),
        Column(t.INT32, jnp.zeros(2, jnp.int32)),
        Column(t.INT8, jnp.zeros(2, jnp.int8)),
        Column.from_pylist(["a", "b"], t.STRING),
        Column(t.FLOAT64, jnp.zeros(2, jnp.float64))])
    with pytest.raises(ValueError, match=match):
        narrow_group_keys(table, keys, ranges)
