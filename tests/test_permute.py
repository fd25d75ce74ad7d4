"""``ops/sort.py::permute`` (columns brought into a permutation's order as
packed 32-bit words, moved once) against numpy fancy indexing, and the
sort-path groupby that reads through it against the formulation it
replaced, kept here as the oracle: ``gather`` of every column, then the
same aggregate read off the sorted rows."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from spark_rapids_jni_tpu import types as t
from spark_rapids_jni_tpu.columnar import Column, Table
from spark_rapids_jni_tpu.ops import groupby as gb
from spark_rapids_jni_tpu.ops import sort as so
from spark_rapids_jni_tpu.ops.strings import pad_strings
from spark_rapids_jni_tpu.parallel import EXEC_AXIS, executor_mesh

DTYPES = {
    "int8": t.INT8, "int16": t.INT16, "int32": t.INT32, "int64": t.INT64,
    "decimal64": t.decimal64(-2), "decimal128": t.decimal128(-3),
    "float32": t.FLOAT32, "float64": t.FLOAT64, "bool": t.BOOL8,
}


@pytest.fixture(scope="module")
def mesh():
    return executor_mesh(8)


def _data(rng, dt, n):
    """Values over the type's whole range, its extremes among them."""
    if dt.is_decimal128:
        return rng.integers(-2**63, 2**63 - 1, (n, 2), dtype=np.int64)
    np_dt = dt.storage_dtype
    if np_dt.kind == "f":
        pool = np.array([0.0, -0.0, np.inf, -np.inf, np.nan, 1.5, -2.25],
                        np_dt)
        vals = rng.standard_normal(n).astype(np_dt)
        pick = rng.random(n) < 0.2
        vals[pick] = pool[rng.integers(0, len(pool), int(pick.sum()))]
        return vals
    if dt == t.BOOL8:
        return rng.integers(0, 2, n).astype(np.uint8)
    info = np.iinfo(np_dt)
    vals = rng.integers(info.min, info.max, n, dtype=np_dt, endpoint=True)
    if n > 2:
        vals[0], vals[1] = info.min, info.max
    return vals


def _column(rng, dt, n, nulls):
    validity = jnp.asarray(rng.random(n) > 0.3) if nulls else None
    return Column(dt, jnp.asarray(_data(rng, dt, n)), validity)


def _same_bits(got, want):
    got, want = np.asarray(got), np.asarray(want)
    assert got.dtype == want.dtype and got.shape == want.shape
    assert got.tobytes() == want.tobytes()


@pytest.fixture(params=["gather", "sort"])
def path(request, monkeypatch):
    """Both ways ``_move_words`` has: the threshold lifted out of reach
    (gathers) and dropped to nothing (sort passes), whatever n."""
    monkeypatch.setattr(so, "_SORT_MOVE_MIN_WORDS",
                        1 << 62 if request.param == "gather" else 0)
    return request.param


@pytest.mark.parametrize("n", [0, 1, 777])
@pytest.mark.parametrize("nulls", [False, True])
@pytest.mark.parametrize("dtype", sorted(DTYPES))
def test_permute_is_numpy_fancy_indexing(dtype, nulls, n, path):
    rng = np.random.default_rng([sorted(DTYPES).index(dtype), nulls, n])
    col = _column(rng, DTYPES[dtype], n, nulls)
    order = rng.permutation(n).astype(np.int32)
    (got,), masks = so.permute([col], jnp.asarray(order))
    assert masks == [] and got.dtype == col.dtype
    _same_bits(got.data, np.asarray(col.data)[order])
    if nulls:
        _same_bits(got.validity, np.asarray(col.validity)[order])
    else:
        assert got.validity is None     # no mask is made up


@pytest.mark.parametrize("n", [0, 1, 1000])
def test_permute_moves_a_whole_table_and_the_row_valid_bit(n, path):
    """Every type at once, with and without validity, two masks beside
    them: the small fields and the bits share words, and each comes back
    where it belongs."""
    rng = np.random.default_rng(n)
    cols = [_column(rng, dt, n, nulls)
            for dt in DTYPES.values() for nulls in (True, False)]
    row_valid, other = rng.random(n) > 0.4, rng.random(n) > 0.5
    order = rng.permutation(n).astype(np.int32)
    got, masks = so.permute(cols, jnp.asarray(order),
                            [jnp.asarray(row_valid), jnp.asarray(other)])
    want = so.gather(Table(cols), jnp.asarray(order))
    for g, w in zip(got, want.columns):
        assert g.dtype == w.dtype
        _same_bits(g.data, w.data)
        assert (g.validity is None) == (w.validity is None)
        if g.validity is not None:
            _same_bits(g.validity, w.validity)
    _same_bits(masks[0], row_valid[order])
    _same_bits(masks[1], other[order])


def test_permute_packs_the_bits_into_as_few_words_as_they_take():
    """Two int8 keys, seven validities and the row-valid bit are 24 bits:
    one word; eleven words with five int64 columns (general q1)."""
    n = 64
    rng = np.random.default_rng(1)
    fields = ([jnp.asarray(rng.integers(0, 255, n).astype(np.uint8))] * 2
              + [jnp.asarray(rng.random(n) > 0.5)] * 8)
    words, places = so._pack_fields(fields)
    assert len(words) == 1 and words[0].dtype == jnp.uint32
    assert sorted(p[1] for p in places) == [0, 8] + list(range(16, 24))
    for f, place in zip(fields, places):
        _same_bits(so._unpack_field(words, place, f), f)
    wide = [jnp.zeros(n, jnp.uint32)] * 10 + fields
    assert len(so._pack_fields(wide)[0]) == 11
    # 16 + 16 + 8 bits: the third field opens a second word
    assert len(so._pack_fields(
        [jnp.zeros(n, jnp.uint16)] * 2 + [jnp.zeros(n, jnp.uint8)])[0]) == 2


def test_permute_passes_strings_through(path):
    rng = np.random.default_rng(4)
    words = ["", "a", "bb", "spark", "tpu", "x" * 9, "nul\0l"]
    vals = [words[i] for i in rng.integers(0, len(words), 50)]
    vals[3] = None
    col = Column.from_pylist(vals, t.STRING)
    ints = _column(rng, t.INT32, 50, True)
    order = rng.permutation(50).astype(np.int32)
    (gs, gi), _ = so.permute([col, ints], jnp.asarray(order))
    assert gs.to_pylist() == [vals[i] for i in order]
    _same_bits(gi.data, np.asarray(ints.data)[order])


def test_permute_under_shard_map(mesh, path):
    """Each device moves its own shard by its own permutation: the loop's
    carries vary over the mesh axis going in as out."""
    rng = np.random.default_rng(8)
    per, devs = 96, mesh.devices.size
    n = per * devs
    cols = [_column(rng, t.INT64, n, True), _column(rng, t.INT8, n, False),
            _column(rng, t.FLOAT32, n, True)]
    row_valid = rng.random(n) > 0.3
    order = np.concatenate([rng.permutation(per) for _ in range(devs)]
                           ).astype(np.int32)

    def step(table, o, rv):
        moved, masks = so.permute(table.columns, o, [rv])
        return Table(moved), masks[0]

    got, got_rv = jax.jit(jax.shard_map(
        step, mesh=mesh, in_specs=(P(EXEC_AXIS),) * 3,
        out_specs=(P(EXEC_AXIS), P(EXEC_AXIS))))(
            Table(cols), jnp.asarray(order), jnp.asarray(row_valid))
    glob = order + np.repeat(np.arange(devs) * per, per)
    for g, c in zip(got.columns, cols):
        _same_bits(g.data, np.asarray(c.data)[glob])
        if c.validity is not None:
            _same_bits(g.validity, np.asarray(c.validity)[glob])
    _same_bits(got_rv, row_valid[glob])


# ---------------------------------------------------------------------------
# the groupby: the parent's formulation as the oracle
# ---------------------------------------------------------------------------

def _run(table, keys, aggs, max_groups, row_valid):
    """The sort path as ``groupby_aggregate`` dispatches it: traced whole,
    so the ``sort_order`` inside runs inline on the rows it is given."""
    return jax.jit(lambda tb, rv: gb._groupby_aggregate_impl(
        ((tb, rv),), None, None, keys=tuple(keys), aggs=tuple(aggs),
        max_groups=max_groups))(table, row_valid)


def _oracle_groupby(table, keys, aggs, max_groups, row_valid):
    """``_groupby_aggregate_impl`` with what this file's subject replaced
    put back: every column and mask brought into key order by a gather of
    its own, and the first / last picks read off the sorted columns."""
    def every_column(columns, order, masks=()):
        moved = so.gather(Table(list(columns)), order)
        return list(moved.columns), [m[order] for m in masks]

    saved = gb.permute, gb._row_reads
    gb.permute = every_column
    gb._row_reads = lambda keys_, aggs_: (list(range(table.num_columns)), [])
    try:
        return _sorted_pick_oracle(table, keys, aggs, max_groups, row_valid)
    finally:
        gb.permute, gb._row_reads = saved


def _sorted_pick_oracle(table, keys, aggs, max_groups, row_valid):
    """first / last (and their include_nulls forms) are computed here from
    the gathered table alone, with numpy, and every other aggregate by the
    implementation over that table."""
    picks = [i for i, (_, op) in enumerate(aggs) if op in gb._ONE_ROW_AGGS]
    rest = [a for a in aggs if a[1] not in gb._ONE_ROW_AGGS]
    res = _run(table, keys, rest, max_groups, row_valid)
    if not picks:
        return res
    order = np.asarray(so._sort_order_impl(
        ((table, row_valid),), None, None, keys=tuple(keys),
        ascending=(True,) * len(keys), nulls_first=(True,) * len(keys))[0])
    sorted_tbl = so.gather(table, jnp.asarray(order))
    n = table.num_rows
    m = n if max_groups is None else max_groups
    keyed = Table([sorted_tbl.column(k) for k in keys])
    same = np.asarray(gb._rows_equal_prev(keyed, range(len(keys))))
    if row_valid is not None:
        same = same | ~np.asarray(row_valid)[order]
    starts = np.flatnonzero(~same)
    ends = np.r_[starts[1:], n] if len(starts) else starts
    out = list(res.table.columns[:len(keys)])
    others = iter(res.table.columns[len(keys):])
    for i, (col_idx, op) in enumerate(aggs):
        if i not in picks:
            out.append(next(others))
            continue
        c = sorted_tbl.column(col_idx)
        cells, valid = c.to_pylist(), np.asarray(c.valid_mask())
        got = [None] * m
        for g, (lo, hi) in enumerate(zip(starts[:m], ends[:m])):
            rows = np.arange(lo, hi)
            if not op.endswith("_include_nulls"):
                rows = rows[valid[lo:hi]]
            if len(rows):
                got[g] = cells[rows[0] if op.startswith("first")
                               else rows[-1]]
        out.append(got)
    return res._replace(table=out)


ALL_AGGS = [(2, "sum"), (2, "count"), (2, "min"), (2, "max"), (2, "mean"),
            (2, "var"), (2, "std"), (2, "var_pop"), (2, "std_pop"),
            (2, "nunique"), (3, "first"), (3, "last"),
            (3, "first_include_nulls"), (4, "first"),
            (5, "sum"), (5, "mean"), (5, "min"), (5, "max"),
            (5, "first_include_nulls"), (6, "count"), (6, "first"),
            (2, ("covar_samp", 5)), (2, ("covar_pop", 5)), (2, ("corr", 5)),
            (8, "sum"), (8, "min"), (8, "first"), (9, "max"), (9, "last")]


def _wide_table(rng, n, groups):
    """Two keys (the first with a null group), then columns of every width
    an aggregate takes; column 7 is named by nothing. Row 0 of every
    group's first-row column is null somewhere: column 3's validity is
    False wherever the row is the first of its key in input order."""
    k0 = rng.integers(0, groups, n).astype(np.int64) * 1_000_003 - 7
    k1 = rng.integers(0, 2, n).astype(np.int8)
    k0_valid = rng.random(n) > 0.1
    seen, first_of_key = set(), np.zeros(n, bool)
    for i in range(n):
        key = (int(k0[i]) if k0_valid[i] else None, int(k1[i]))
        if key not in seen:
            seen.add(key)
            first_of_key[i] = True
    return Table([
        Column(t.INT64, jnp.asarray(k0), jnp.asarray(k0_valid)),
        Column(t.INT8, jnp.asarray(k1)),
        Column(t.decimal64(-2), jnp.asarray(
            rng.integers(-10**9, 10**9, n)), jnp.asarray(rng.random(n) > 0.2)),
        Column(t.INT32, jnp.asarray(rng.integers(-99, 99, n).astype(
            np.int32)), jnp.asarray(~first_of_key & (rng.random(n) > 0.3))),
        Column(t.INT16, jnp.asarray(rng.integers(-9, 9, n).astype(np.int16))),
        Column(t.FLOAT64, jnp.asarray(rng.standard_normal(n)),
               jnp.asarray(rng.random(n) > 0.2)),
        Column(t.INT8, jnp.asarray(rng.integers(-5, 5, n).astype(np.int8)),
               jnp.asarray(rng.random(n) > 0.5)),
        Column(t.INT64, jnp.asarray(rng.integers(0, 9, n))),   # unnamed
        Column(t.decimal128(-2), jnp.asarray(
            rng.integers(-2**40, 2**40, (n, 2))),
            jnp.asarray(rng.random(n) > 0.2)),
        pad_strings(Column.from_pylist(     # padded on the host, for jit
            [None if rng.random() < 0.2 else "s%d" % rng.integers(0, 30)
             for _ in range(n)], t.STRING)),
    ])


def _check_against_oracle(table, keys, aggs, max_groups, row_valid):
    got = _run(table, keys, aggs, max_groups, row_valid)
    want = _oracle_groupby(table, keys, aggs, max_groups, row_valid)
    assert int(got.num_groups) == int(want.num_groups)
    assert bool(got.overflowed) == bool(want.overflowed)
    g = min(int(got.num_groups), got.table.num_rows)
    assert got.table.num_columns == len(want.table)
    for i, (gc, wc) in enumerate(zip(got.table.columns, want.table)):
        if isinstance(wc, list):        # a first / last pick, from numpy
            assert gc.to_pylist()[:g] == wc[:g], (i, aggs[i - len(keys)])
            continue
        assert gc.dtype == wc.dtype, i
        _same_bits(gc.valid_mask(), wc.valid_mask())
        valid = np.asarray(wc.valid_mask())
        if not gc.dtype.is_string:
            _same_bits(np.asarray(gc.data)[valid], np.asarray(wc.data)[valid])
    return got


@pytest.mark.parametrize("phantoms", [False, True])
@pytest.mark.parametrize("groups,max_groups", [
    (5, 16),        # the block path: m <= 1,024 and 2 * m * 32 <= n
    (5, None),      # the cumsum path, padded to n
    (300, 2048),    # the cumsum path under a bound
])
def test_groupby_every_aggregate_equals_the_gathered_formulation(
        groups, max_groups, phantoms, path):
    rng = np.random.default_rng([groups, phantoms])
    n = 1500
    table = _wide_table(rng, n, groups)
    row_valid = jnp.asarray(rng.random(n) > 0.15) if phantoms else None
    small = max_groups is not None and max_groups <= gb._SMALL_M and \
        2 * max_groups * gb._MIN_BLOCK <= n
    assert small == (max_groups == 16)
    _check_against_oracle(table, [0, 1], ALL_AGGS, max_groups, row_valid)


@pytest.mark.parametrize("op", ["first_include_nulls", "last_include_nulls"])
def test_include_nulls_pick_of_a_null_row_is_null(op, path):
    """The group's first (last) ROW, null or not: read at one row a group
    from the unsorted column, with its validity."""
    keys = np.array([3, 1, 3, 2, 1, 2, 3], np.int64)
    vals = np.array([70, 10, 71, 20, 11, 21, 72], np.int32)
    valid = np.array([0, 1, 1, 1, 0, 1, 0], bool)
    table = Table([Column(t.INT64, jnp.asarray(keys)),
                   Column(t.INT32, jnp.asarray(vals), jnp.asarray(valid))])
    res = _run(table, [0], [(1, op)], None, None)
    want = {"first_include_nulls": [10, 20, None],
            "last_include_nulls": [None, 21, None]}[op]
    assert res.table.column(0).to_pylist()[:3] == [1, 2, 3]
    assert res.table.column(1).to_pylist()[:3] == want
    _check_against_oracle(table, [0], [(1, op)], None, None)


def test_groupby_moves_no_column_it_does_not_read(monkeypatch):
    """Of six columns the sort path hands ``permute`` the key and the sum's
    operand, the counted column's validity and the row-valid mask; the
    first-row column, the column whose first non-null is taken (validity
    only) and the unnamed column stay where they are."""
    rng = np.random.default_rng(12)
    n = 400
    cols = [_column(rng, t.INT64, n, False) for _ in range(6)]
    cols[0] = Column(t.INT64, jnp.asarray(rng.integers(0, 9, n)))
    cols[3] = _column(rng, t.INT64, n, True)
    cols[4] = _column(rng, t.INT64, n, True)
    seen = []
    real = gb.permute

    def spy(columns, order, masks=()):
        seen.append((list(columns), list(masks)))
        return real(columns, order, masks)

    monkeypatch.setattr(gb, "permute", spy)
    table, rv = Table(cols), jnp.asarray(rng.random(n) > 0.1)
    aggs = ((1, "sum"), (2, "first_include_nulls"), (3, "count"),
            (4, "first"), (2, "count"))
    _run(table, [0], aggs, 32, rv)
    (columns, masks), = seen
    # the key and the sum's operand; column 2 has no validity, so its
    # count needs nothing moved; then the two validities and row_valid
    assert len(columns) == 2 and len(masks) == 3
    assert all(c.validity is None for c in columns)
    monkeypatch.undo()
    _check_against_oracle(table, [0], list(aggs), 32, rv)


def test_first_picks_open_no_count_lane():
    """Planned q3's aggregates: two first-row picks and a sum. The int64
    stack that is summed holds the sum's two lanes, not four."""
    assert gb._row_reads((0,), ((1, "first_include_nulls"),
                                (2, "first_include_nulls"),
                                (3, "sum"))) == ([0, 3], [])
    table = Table([Column(t.INT64, jnp.arange(64, dtype=jnp.int64) // 4),
                   Column(t.INT32, jnp.arange(64, dtype=jnp.int32)),
                   Column(t.INT32, jnp.arange(64, dtype=jnp.int32)),
                   Column(t.decimal64(-2), jnp.arange(64, dtype=jnp.int64),
                          jnp.arange(64) % 3 > 0)])

    def run(tb):
        return gb._groupby_aggregate_impl(
            ((tb, None),), None, None, keys=(0,),
            aggs=((1, "first_include_nulls"), (2, "first_include_nulls"),
                  (3, "sum")), max_groups=None).table

    jaxpr = str(jax.make_jaxpr(run)(table))
    assert "i64[64,2]" in jaxpr and "i64[64,4]" not in jaxpr


@pytest.mark.parametrize("q", [0.0, 0.5, 0.9])
def test_groupby_percentile_is_unchanged(q, path):
    rng = np.random.default_rng(21)
    n = 600
    table = _wide_table(rng, n, 7)
    got = gb.groupby_percentile(table, [0, 1], 2, [q])
    order = so.sort_order(table, [0, 1, 2],
                          nulls_first=[True, True, False])
    sorted_tbl = so.gather(table, order)
    keyed = Table([sorted_tbl.column(0), sorted_tbl.column(1)])
    same = np.asarray(gb._rows_equal_prev(keyed, [0, 1]))
    starts = np.flatnonzero(~same)
    ends = np.r_[starts[1:], n]
    vals = np.asarray(sorted_tbl.column(2).data).astype(np.float64) * 0.01
    valid = np.asarray(sorted_tbl.column(2).valid_mask())
    assert int(got.num_groups) == len(starts)
    out = got.table.column(2).to_pylist()
    for g, (lo, hi) in enumerate(zip(starts, ends)):
        v = vals[lo:hi][valid[lo:hi]]
        if len(v) == 0:
            assert out[g] is None
        else:
            assert out[g] == pytest.approx(np.quantile(v, q), rel=1e-12)
