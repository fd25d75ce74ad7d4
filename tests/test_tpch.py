"""TPC-H q1 integration test: the full pipeline vs the numpy oracle."""

import numpy as np
import pytest

import jax.numpy as jnp

from spark_rapids_jni_tpu import types as t
from spark_rapids_jni_tpu.columnar import Column, Table

from spark_rapids_jni_tpu.models.tpch import (
    lineitem_table,
    tpch_q1,
    tpch_q1_numpy,
)


def test_q1_matches_numpy_oracle():
    li = lineitem_table(20_000, seed=7)
    got_tbl = tpch_q1(li)
    want = tpch_q1_numpy(li)

    rf = np.asarray(got_tbl.column(0).data)
    ls = np.asarray(got_tbl.column(1).data)
    kvalid = np.asarray(got_tbl.column(0).valid_mask())
    rows = {}
    for i in range(len(rf)):
        if not kvalid[i]:
            continue
        rows[(int(rf[i]), int(ls[i]))] = i

    assert set(rows) == set(want)
    for key, w in want.items():
        i = rows[key]
        assert int(np.asarray(got_tbl.column(2).data)[i]) == w["sum_qty"]
        assert int(np.asarray(got_tbl.column(3).data)[i]) == w["sum_base_price"]
        assert int(np.asarray(got_tbl.column(4).data)[i]) == w["sum_disc_price"]
        assert int(np.asarray(got_tbl.column(5).data)[i]) == w["sum_charge"]
        assert np.isclose(np.asarray(got_tbl.column(6).data)[i], w["avg_qty"])
        assert np.isclose(np.asarray(got_tbl.column(7).data)[i], w["avg_price"])
        assert np.isclose(np.asarray(got_tbl.column(8).data)[i], w["avg_disc"])
        assert int(np.asarray(got_tbl.column(9).data)[i]) == w["count"]


def test_q1_groups_sorted_first():
    li = lineitem_table(5_000, seed=3)
    out = tpch_q1(li)
    kvalid = np.asarray(out.column(0).valid_mask())
    # real groups lead, padding/null-key tail follows
    n_real = int(kvalid.sum())
    assert n_real <= 6  # 3 flags x 2 statuses
    assert kvalid[:n_real].all()
    rf = np.asarray(out.column(0).data)[:n_real]
    ls = np.asarray(out.column(1).data)[:n_real]
    order = np.lexsort((ls, rf))
    assert np.array_equal(order, np.arange(n_real))


def test_q1_null_discount_tax_propagate():
    import numpy as np
    from spark_rapids_jni_tpu import types as t
    from spark_rapids_jni_tpu.columnar import Column, Table
    from spark_rapids_jni_tpu.models.tpch import tpch_q1

    n = 4
    cols = [
        Column.from_numpy(np.full(n, 100, dtype=np.int64), t.decimal64(-2)),
        Column.from_numpy(np.full(n, 2000, dtype=np.int64), t.decimal64(-2)),
        Column.from_numpy(np.array([5, 999999, 5, 5], dtype=np.int64),
                          t.decimal64(-2),
                          validity=np.array([True, False, True, True])),
        Column.from_numpy(np.full(n, 3, dtype=np.int64), t.decimal64(-2)),
        Column.from_numpy(np.full(n, 65, dtype=np.int8)),
        Column.from_numpy(np.full(n, 70, dtype=np.int8)),
        Column.from_numpy(np.full(n, 9000, dtype=np.int32), t.TIMESTAMP_DAYS),
    ]
    out = tpch_q1(Table(cols))
    # sum_disc_price must skip the null-discount row: 3 * 2000*(100-5)
    assert int(np.asarray(out.column(4).data)[0]) == 3 * 2000 * 95
    assert int(np.asarray(out.column(5).data)[0]) == 3 * 2000 * 95 * 103


def test_tpch_q1_checked_rejects_out_of_contract_key_domain(rng):
    # >64 distinct (returnflag, linestatus) byte pairs violate the plan's
    # group-budget contract; the host wrapper must raise, not drop groups
    from spark_rapids_jni_tpu.models.tpch import lineitem_table, tpch_q1_checked

    li = lineitem_table(4096)
    cols = list(li.columns)
    rf = rng.integers(0, 16, 4096).astype(np.int8)
    ls = rng.integers(0, 8, 4096).astype(np.int8)
    cols[4] = Column.from_numpy(rf, t.INT8)
    cols[5] = Column.from_numpy(ls, t.INT8)
    with pytest.raises(ValueError, match="group budget"):
        tpch_q1_checked(Table(cols))


def test_tpch_q1_checked_matches_oracle(rng):
    from spark_rapids_jni_tpu.models.tpch import (
        lineitem_table, tpch_q1_checked, tpch_q1_numpy)

    li = lineitem_table(3000)
    out = tpch_q1_checked(li)
    oracle = tpch_q1_numpy(li)
    vm = (np.asarray(out.column(0).valid_mask())
          & np.asarray(out.column(1).valid_mask()))
    got = {}
    for i in np.nonzero(vm)[0]:
        got[(int(np.asarray(out.column(0).data)[i]),
             int(np.asarray(out.column(1).data)[i]))] = (
            int(np.asarray(out.column(2).data)[i]),
            int(np.asarray(out.column(9).data)[i]),
        )
    want = {k: (v["sum_qty"], v["count"]) for k, v in oracle.items()}
    assert got == want


# ---- q3 --------------------------------------------------------------------


def _q3_tables(n_cust=64, n_ord=512, n_li=2048):
    from spark_rapids_jni_tpu.models.tpch import (
        customer_table, lineitem_q3_table, orders_table)

    c = customer_table(n_cust)
    o = orders_table(n_ord, n_cust)
    li = lineitem_q3_table(n_li, n_ord)
    return c, o, li


def test_tpch_q3_matches_oracle():
    import jax

    from spark_rapids_jni_tpu.models.tpch import tpch_q3, tpch_q3_numpy

    c, o, li = _q3_tables()
    res = jax.jit(lambda a, b, d: tpch_q3(a, b, d))(c, o, li)
    assert int(res.join_total) <= res.out_cap
    out = res.result.table
    want = tpch_q3_numpy(c, o, li)
    kv = np.asarray(out.column(0).valid_mask())
    got = {}
    for i in np.nonzero(kv)[0]:
        got[int(np.asarray(out.column(0).data)[i])] = (
            int(np.asarray(out.column(3).data)[i]),
            int(np.asarray(out.column(1).data)[i]),
            int(np.asarray(out.column(2).data)[i]),
        )
    assert got == want
    # ORDER BY revenue desc among real groups (sorted nulls-last, so the
    # real groups are the head)
    revs = np.asarray(out.column(3).data)[: int(kv.sum())]
    assert np.all(np.diff(revs.astype(np.int64)) <= 0)


@pytest.mark.slow
def test_tpch_q3_distributed_matches_oracle():
    from spark_rapids_jni_tpu.models.tpch import (
        tpch_q3_distributed, tpch_q3_numpy)
    from spark_rapids_jni_tpu.parallel import executor_mesh

    c, o, li = _q3_tables(n_cust=48, n_ord=256, n_li=1024)
    mesh = executor_mesh(8)
    out = tpch_q3_distributed(c, o, li, mesh)
    want = tpch_q3_numpy(c, o, li)
    got = {}
    for i in range(out.num_rows):
        got[int(np.asarray(out.column(0).data)[i])] = (
            int(np.asarray(out.column(3).data)[i]),
            int(np.asarray(out.column(1).data)[i]),
            int(np.asarray(out.column(2).data)[i]),
        )
    assert got == want
    revs = np.asarray(out.column(3).data)
    assert np.all(np.diff(revs.astype(np.int64)) <= 0)


# ---- bounded-domain / planned q1 (VERDICT r3 item 2) -----------------------


def _q1_groups(out):
    rf = out.column(0).to_pylist()
    ls = out.column(1).to_pylist()
    got = {}
    for i in range(out.num_rows):
        if rf[i] is None or ls[i] is None:
            continue
        got[(rf[i], ls[i])] = dict(
            sum_qty=out.column(2).to_pylist()[i],
            sum_base_price=out.column(3).to_pylist()[i],
            sum_disc_price=out.column(4).to_pylist()[i],
            sum_charge=out.column(5).to_pylist()[i],
            count=out.column(9).to_pylist()[i],
        )
    return got


def _assert_q1_matches_oracle(out, oracle):
    got = _q1_groups(out)
    assert set(got) == set(oracle)
    for k, w in oracle.items():
        for f in got[k]:
            assert got[k][f] == w[f], (k, f)
    rf = out.column(0).to_pylist()
    ls = out.column(1).to_pylist()
    for i in range(out.num_rows):
        if rf[i] is None or ls[i] is None:
            continue
        w = oracle[(rf[i], ls[i])]
        np.testing.assert_allclose(
            out.column(6).to_pylist()[i], w["avg_qty"], rtol=1e-12)
        np.testing.assert_allclose(
            out.column(8).to_pylist()[i], w["avg_disc"], rtol=1e-12)


def test_q1_planned_matches_oracle_and_is_sort_free():
    from spark_rapids_jni_tpu.models.tpch import tpch_q1_planned

    li = lineitem_table(8192, seed=5)
    out = tpch_q1_planned(li)
    _assert_q1_matches_oracle(out, tpch_q1_numpy(li))
    # output ordering is static: real groups lexicographic, nulls last
    keys = [(a, b) for a, b in zip(out.column(0).to_pylist(),
                                   out.column(1).to_pylist())
            if a is not None and b is not None]
    assert keys == sorted(keys)
    # the whole plan lowers with zero sorts and zero scatters
    import re

    import jax
    import jax.numpy as jnp

    def digest(tb):
        o = tpch_q1_planned(tb)
        return sum(jnp.sum(c.data).astype(jnp.float64)
                   + jnp.sum(c.valid_mask()) for c in o.columns)

    hlo = jax.jit(digest).lower(li).compile().as_text()
    assert not [l for l in hlo.splitlines()
                if re.search(r"= \S+ sort\(", l)]
    assert not [l for l in hlo.splitlines() if " scatter(" in l]


def test_q1_planned_checked_replans_on_domain_miss():
    from spark_rapids_jni_tpu.models.tpch import tpch_q1_planned_checked

    li = lineitem_table(512, seed=2)
    # corrupt one flag byte outside the TPC-H domain
    import jax.numpy as jnp

    from spark_rapids_jni_tpu.columnar import Column

    cols = list(li.columns)
    bad = jnp.asarray(np.where(np.arange(512) == 7, ord("X"),
                               np.asarray(cols[4].data)).astype(np.int8))
    cols[4] = Column(cols[4].dtype, bad, cols[4].validity)
    li_bad = Table(cols)
    out = tpch_q1_planned_checked(li_bad)  # falls back to general plan
    oracle = tpch_q1_numpy(li_bad)
    assert _q1_groups(out).keys() == oracle.keys()


def test_bounded_groupby_oracle_and_miss_flag(rng):
    from spark_rapids_jni_tpu.ops.groupby import groupby_aggregate_bounded

    keys = rng.integers(0, 3, 500).astype(np.int32) * 5  # domain {0,5,10}
    vals = rng.integers(-100, 100, 500).astype(np.int64)
    kvalid = rng.random(500) > 0.1
    tbl = Table([
        Column.from_numpy(keys, validity=kvalid),
        Column.from_numpy(vals),
    ])
    res = groupby_aggregate_bounded(
        tbl, [0], [(1, "sum"), (1, "count"), (1, "min"), (1, "max"),
                   (1, "mean")],
        key_domains=[(0, 5, 10)])
    assert not bool(res.domain_miss)
    out = res.table
    kcol = out.column(0).to_pylist()
    for i, k in enumerate(kcol):
        sel = vals[(keys == k) & kvalid] if k is not None else \
            vals[~kvalid]
        if not len(sel):
            continue
        assert out.column(1).to_pylist()[i] == int(sel.sum())
        assert out.column(2).to_pylist()[i] == len(sel)
        assert out.column(3).to_pylist()[i] == int(sel.min())
        assert out.column(4).to_pylist()[i] == int(sel.max())
    # null-key group exists and sits last
    assert kcol[-1] is None or None not in kcol[:-1]

    # a key value outside the domain raises the miss flag
    tbl2 = Table([
        Column.from_numpy(np.array([0, 5, 7], np.int32)),
        Column.from_numpy(np.array([1, 2, 3], np.int64)),
    ])
    res2 = groupby_aggregate_bounded(
        tbl2, [0], [(1, "sum")], key_domains=[(0, 5, 10)])
    assert bool(res2.domain_miss)


def test_bounded_groupby_float32_sum_dtype():
    from spark_rapids_jni_tpu.ops.groupby import groupby_aggregate_bounded

    tbl = Table([
        Column.from_numpy(np.array([0, 5, 0], np.int32)),
        Column.from_numpy(np.array([1.5, 2.5, 3.0], np.float32)),
    ])
    res = groupby_aggregate_bounded(
        tbl, [0], [(1, "sum")], key_domains=[(0, 5, 10)])
    out = res.table.column(1)
    assert out.dtype == t.FLOAT32
    assert out.to_pylist()[0] == 4.5


def test_tpch_q6_matches_numpy_oracle():
    from spark_rapids_jni_tpu.models.tpch import (
        lineitem_table, tpch_q6, tpch_q6_numpy)

    li = lineitem_table(5000, seed=9)
    out = tpch_q6(li)
    assert out.dtype.scale == -4
    # decimal to_pylist yields the raw scaled integer representation
    got = out.to_pylist()[0]
    want = tpch_q6_numpy(li)
    assert want != 0 and got == want


def test_tpch_q6_nulls_and_empty_match():
    from spark_rapids_jni_tpu.models.tpch import (
        _Q6_DATE_LO, lineitem_table, tpch_q6, tpch_q6_numpy)

    li = lineitem_table(64, seed=1)
    # null out some discount values: those rows must not contribute
    cols = list(li.columns)
    disc = cols[2]
    valid = np.ones(64, dtype=bool)
    valid[::3] = False
    cols[2] = Column(disc.dtype, disc.data, jnp.asarray(valid))
    li2 = Table(cols)
    want2 = tpch_q6_numpy(li2)
    got2 = tpch_q6(li2).to_pylist()[0]
    # SQL SUM over zero rows is NULL
    assert got2 == (want2 if want2 != 0 else None)
    # no matching rows -> null result
    cols[6] = Column(
        cols[6].dtype,
        jnp.zeros((64,), cols[6].data.dtype) + (_Q6_DATE_LO - 100),
        None)
    assert tpch_q6(Table(cols)).to_pylist() == [None]


def test_tpch_q12_vs_numpy():
    from spark_rapids_jni_tpu.models.tpch import (
        lineitem_q12_table, orders_q12_table, tpch_q12, tpch_q12_numpy)

    orders = orders_q12_table(300)
    lineitem = lineitem_q12_table(1500, 400)  # some orderkeys unmatched
    res = tpch_q12(orders, lineitem)
    want = tpch_q12_numpy(orders, lineitem)
    m = int(res.result.num_groups)
    tbl = res.result.table
    got = {}
    for i in range(m):
        k = tbl.column(0).to_pylist()[i]
        if k is None:
            continue
        got[k] = [tbl.column(1).to_pylist()[i],
                  tbl.column(2).to_pylist()[i]]
    assert got == want
    # output is shipmode-sorted (the ORDER BY)
    ks = [k for k in tbl.column(0).to_pylist()[:m] if k is not None]
    assert ks == sorted(ks)


def test_tpch_q14_vs_numpy():
    from spark_rapids_jni_tpu.models.tpch import (
        lineitem_q14_table, part_table, tpch_q14, tpch_q14_numpy)

    part = part_table(200)
    lineitem = lineitem_q14_table(2000, 250)
    res = tpch_q14(part, lineitem)
    promo, total = tpch_q14_numpy(part, lineitem)
    assert int(res.promo_revenue) == promo
    assert int(res.total_revenue) == total
    if total:
        assert res.ratio() == 100.0 * promo / total


def test_tpch_q19_vs_numpy():
    from spark_rapids_jni_tpu.models.tpch import (
        lineitem_q19_table, part_table, tpch_q19, tpch_q19_numpy)

    part = part_table(150)
    lineitem = lineitem_q19_table(2500, 180)
    res = tpch_q19(part, lineitem)
    want = tpch_q19_numpy(part, lineitem)
    assert int(res.revenue) == want
    assert want > 0  # the synthetic distributions must actually hit


@pytest.mark.slow
def test_tpch_q12_distributed_matches_numpy():
    from spark_rapids_jni_tpu.parallel import executor_mesh

    mesh = executor_mesh(8)
    from spark_rapids_jni_tpu.models.tpch import (
        lineitem_q12_table,
        orders_q12_table,
        tpch_q12_distributed,
        tpch_q12_numpy,
    )

    orders = orders_q12_table(160)
    lineitem = lineitem_q12_table(800, 200)
    out = tpch_q12_distributed(orders, lineitem, mesh)
    want = tpch_q12_numpy(orders, lineitem)
    kcol = out.column(0).to_pylist()
    hcol = out.column(1).to_pylist()
    lcol = out.column(2).to_pylist()
    got = {k: [h, lo] for k, h, lo in zip(kcol, hcol, lcol)
           if k is not None}
    assert got == want


def test_tpch_q4_vs_numpy():
    from spark_rapids_jni_tpu.models.tpch import (
        lineitem_q12_table,
        orders_q4_table,
        tpch_q4,
        tpch_q4_numpy,
    )

    orders = orders_q4_table(400)
    lineitem = lineitem_q12_table(1200, 500)
    res = tpch_q4(orders, lineitem)
    want = tpch_q4_numpy(orders, lineitem)
    m = int(res.result.num_groups)
    tbl = res.result.table
    got = {k: v for k, v in zip(tbl.column(0).to_pylist()[:m],
                                tbl.column(1).to_pylist()[:m])
           if k is not None}
    assert got == want
    assert want  # the synthetic quarter must actually select orders


def test_tpch_q17_vs_numpy():
    from spark_rapids_jni_tpu.models.tpch import (
        lineitem_q19_table,
        part_table,
        tpch_q17,
        tpch_q17_numpy,
    )

    part = part_table(120)
    lineitem = lineitem_q19_table(3000, 120)
    res = tpch_q17(part, lineitem)
    want = tpch_q17_numpy(part, lineitem)
    assert int(res.yearly_total) == want
    assert want > 0
    assert res.avg_yearly() == want / 100.0 / 7.0
