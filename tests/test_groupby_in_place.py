"""The sort-path groupby under a small group bound (``ops/groupby.py``:
``_aggregates_in_place``, ``_KeySlots``): the aggregates are taken over the
rows where they lie, matched to the first m groups by their key words, no
value word brought into key order. Against the same call without a bound
(the word-moving path) trimmed to m rows and against a plain dict oracle,
bit for bit on integer lanes; how the groups are found (under a bound of
``_MIN_LOOP_M`` or fewer by repeated minimum over the key words,
``_least_groups``, the words sorted only to count past a broken bound);
the shape of the lowered regions; the ``groupby.in_place`` and
``groupby.key_sorted`` counters of the served path."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from spark_rapids_jni_tpu import types as t
from spark_rapids_jni_tpu.columnar import Column, Table
from spark_rapids_jni_tpu.models import tpch
from spark_rapids_jni_tpu.ops import groupby as gb
from spark_rapids_jni_tpu.ops import sort as so
from spark_rapids_jni_tpu.parallel.mesh import EXEC_AXIS, executor_mesh
from spark_rapids_jni_tpu.runtime import dispatch, fusion, resilience
from spark_rapids_jni_tpu.runtime.server import QueryServer
from spark_rapids_jni_tpu.telemetry import REGISTRY
from tests.test_sort_words import _gathers, _q3_tables, _region_hlo, _sorts

M = 8
N = 2048      # the gate: 2 * m * 32 <= n


def _col(dt, values, valid=None):
    return Column(dt, jnp.asarray(values),
                  None if valid is None else jnp.asarray(valid))


def _keys(rng, groups, n=N, dt=(t.INT8, np.int8)):
    return rng.integers(0, groups, n).astype(dt[1]) - dt[1](groups // 2)


def _case(name):
    """``(table, key columns, aggregates, row_valid, bound, true groups or
    None, in place?)`` of one named case."""
    rng = np.random.default_rng(sorted(CASES).index(name))
    val = rng.integers(-10**9, 10**9, N).astype(np.int64)
    vvalid = rng.random(N) > 0.2
    sums = ((1, "sum"), (1, "count"), (1, "mean"), (1, "min"), (1, "max"))
    one_key = lambda k, kv=None: Table(  # noqa: E731
        [_col(t.INT8, k, kv), _col(t.INT64, val, vvalid)])
    if name == "null_keys":
        return (one_key(_keys(rng, 5), rng.random(N) > 0.3), [0], sums,
                None, M, 6, True)
    if name == "phantom_rows":
        return (one_key(_keys(rng, 5)), [0], sums, rng.random(N) > 0.4, M, 5,
                True)
    if name == "all_phantom":
        return (one_key(_keys(rng, 5)), [0], sums, np.zeros(N, bool), M, 0,
                True)
    if name == "empty":
        return (Table([_col(t.INT8, np.zeros(0, np.int8)),
                       _col(t.INT64, np.zeros(0, np.int64))]), [0], sums,
                None, M, 0, False)
    if name in ("exactly_m", "m_plus_1", "ten_m"):
        groups = {"exactly_m": M, "m_plus_1": M + 1, "ten_m": 10 * M}[name]
        k = np.concatenate([np.arange(groups), rng.integers(
            0, groups, N - groups)]).astype(np.int8)
        return (one_key(rng.permutation(k) - 40), [0], sums,
                rng.random(N) > 0.1, M, None, True)
    if name == "wrapping_sums":
        big = rng.integers(2**62, 2**63 - 1, N).astype(np.int64)
        big[::3] = -2**63
        return (Table([_col(t.INT8, _keys(rng, 3)), _col(t.INT64, big),
                       _col(t.UINT64, big.astype(np.uint64) * 2 + 1)]),
                [0], ((1, "sum"), (2, "sum"), (1, "count")), None, M, 3, True)
    if name == "decimal_sums":
        d64 = rng.integers(-10**15, 10**15, N).astype(np.int64)
        d128 = rng.integers(-2**63, 2**63 - 1, (N, 2), dtype=np.int64)
        return (Table([_col(t.INT8, _keys(rng, 4)),
                       _col(t.decimal64(-2), d64, vvalid),
                       _col(t.decimal128(-3), d128, vvalid),
                       _col(t.INT32, rng.integers(-2**31, 2**31 - 1, N)
                            .astype(np.int32), vvalid)]),
                [0], ((1, "sum"), (1, "mean"), (2, "sum"), (2, "mean"),
                      (2, "var"), (3, "sum"), (3, "count"),
                      (1, ("covar_samp", 2))), None, M, 4, True)
    if name == "minmax_all_null_groups":
        k = _keys(rng, 6)
        return (Table([_col(t.INT8, k),
                       _col(t.INT64, val, vvalid & (k > 0)),
                       _col(t.FLOAT64, rng.standard_normal(N), k % 2 == 0),
                       _col(t.INT16, rng.integers(-2**15, 2**15 - 1, N)
                            .astype(np.int16), vvalid)]),
                [0], ((1, "min"), (1, "max"), (2, "min"), (2, "max"),
                      (3, "min"), (3, "max"), (1, "count")), None, M, 6, True)
    if name == "float_lanes":
        f = rng.standard_normal(N) * 10.0 ** rng.integers(-3, 6, N)
        return (Table([_col(t.INT8, _keys(rng, 5)),
                       _col(t.FLOAT64, f, vvalid),
                       _col(t.FLOAT32, f.astype(np.float32), vvalid),
                       _col(t.INT64, val, vvalid)]),
                [0], ((1, "sum"), (1, "mean"), (2, "sum"), (3, "var"),
                      (3, "std_pop"), (1, "std"), (1, ("corr", 3)),
                      (3, ("covar_pop", 1))), None, M, 5, True)
    if name == "two_keys":
        return (Table([_col(t.INT8, _keys(rng, 2), rng.random(N) > 0.1),
                       _col(t.INT16, _keys(rng, 3, dt=(t.INT16, np.int16))),
                       _col(t.INT64, val, vvalid)]),
                [0, 1], ((2, "sum"), (2, "count"), (2, "max")), None, M,
                None, True)
    if name == "three_keys_wide":     # over two words: sorted word by word
        return (Table([_col(t.INT64, rng.integers(-2, 0, N) * 2**40,
                            rng.random(N) > 0.1),
                       _col(t.INT32, rng.integers(0, 2, N).astype(np.int32)),
                       _col(t.UINT16, rng.integers(0, 2, N).astype(np.uint16)),
                       _col(t.INT64, val, vvalid)]),
                [0, 1, 2], ((3, "sum"), (3, "count"), (3, "min")),
                rng.random(N) > 0.2, 16, None, True)
    if name == "all_keys_null":
        return (one_key(_keys(rng, 5), np.zeros(N, bool)), [0], sums,
                rng.random(N) > 0.3, M, 1, True)
    if name == "one_wide_key":     # 64 bits, a null rank, the row-valid bit
        return (Table([_col(t.INT64, rng.integers(-3, 3, N) * 2**33,
                            rng.random(N) > 0.1),
                       _col(t.INT64, val, vvalid)]),
                [0], ((1, "sum"), (1, "count"), (1, "max")),
                rng.random(N) > 0.2, M, 7, True)
    if name.startswith("bound_"):   # bound_<m>_groups_<g>: the loop's steps
        m, groups = (int(x) for x in name.split("_")[1::2])
        n = max(N, 2 * m * gb._MIN_BLOCK)
        g = rng.permutation(np.concatenate(
            [np.arange(groups), rng.integers(0, groups, n - groups)]))
        # two words; groups 0 and 1 are (null, 0) and (null, 1)
        return (Table([_col(t.INT16, (g // 2 - 100).astype(np.int16),
                            g // 2 != 0),
                       _col(t.INT8, (g % 2).astype(np.int8)),
                       _col(t.INT64, rng.integers(-10**9, 10**9, n),
                            rng.random(n) > 0.2)]),
                [0, 1], ((2, "sum"), (2, "count"), (2, "min")),
                rng.random(n) > 0.1, m, groups, True)
    if name in ("first_last", "nunique", "float_key", "string_minmax"):
        k = _keys(rng, 4)
        if name == "float_key":     # -0.0 and 0.0: one group, two words
            fk = np.array([-0.0, 0.0, 1.5, np.nan], np.float32)[k + 2]
            return (Table([_col(t.FLOAT32, fk), _col(t.INT64, val, vvalid)]),
                    [0], sums, None, M, 3, False)
        if name == "string_minmax":
            words = ["a", "bb", "", "zz", "m"]
            return (Table([_col(t.INT8, k), Column.from_pylist(
                [words[i] for i in rng.integers(0, 5, N)], t.STRING)]),
                [0], ((1, "min"), (1, "max")), None, M, 4, False)
        extra = {"first_last": ((1, "first"), (1, "last"),
                                (1, "first_include_nulls")),
                 "nunique": ((1, "nunique"),)}[name]
        small = rng.integers(0, 7, N).astype(np.int64)
        return (Table([_col(t.INT8, k), _col(t.INT64, small, vvalid)]),
                [0], sums + extra, rng.random(N) > 0.2, M, 4, False)
    raise KeyError(name)


CASES = ("null_keys", "phantom_rows", "all_phantom", "empty", "exactly_m",
         "m_plus_1", "ten_m", "wrapping_sums", "decimal_sums",
         "minmax_all_null_groups", "float_lanes", "two_keys",
         "three_keys_wide", "first_last", "nunique", "float_key",
         "string_minmax", "all_keys_null", "one_wide_key",
         # fewer groups than the bound, exactly m, m + 1, many more
         "bound_1_groups_1", "bound_1_groups_2", "bound_1_groups_40",
         "bound_5_groups_2", "bound_5_groups_5", "bound_5_groups_6",
         "bound_5_groups_90", "bound_64_groups_9", "bound_64_groups_64",
         "bound_64_groups_65", "bound_64_groups_300")


def _same_columns(got: Table, want: Table, rows: int):
    """The first ``rows`` rows: validity equal; where valid, integers and
    decimals bit for bit and floats within 1e-9 (NaNs alike)."""
    assert got.num_columns == want.num_columns
    for i, (g, w) in enumerate(zip(got.columns, want.columns)):
        assert g.dtype == w.dtype, i
        gv = np.asarray(g.valid_mask())[:rows]
        wv = np.asarray(w.valid_mask())[:rows]
        assert gv.tolist() == wv.tolist(), (i, g.dtype)
        if g.dtype.is_string:
            assert [x for x, v in zip(g.to_pylist()[:rows], gv) if v] == [
                x for x, v in zip(w.to_pylist()[:rows], wv) if v], i
            continue
        gd, wd = np.asarray(g.data)[:rows][gv], np.asarray(w.data)[:rows][wv]
        if gd.dtype.kind == "f":
            np.testing.assert_allclose(gd, wd, rtol=1e-9, atol=0,
                                       err_msg=f"column {i}")
        else:
            assert gd.tobytes() == wd.tobytes(), (i, g.dtype)


def _oracle(table, keys, aggs, row_valid):
    """``{key tuple: {(column, op): value}}`` in key order (nulls first,
    then ascending), sums modulo 2**64, for sum / count / min / max of
    integer columns; the other aggregates have the unbounded path alone."""
    cols = [(np.asarray(c.data), np.asarray(c.valid_mask()))
            for c in table.columns]
    groups: dict = {}
    for r in range(table.num_rows):
        if row_valid is not None and not row_valid[r]:
            continue
        key = tuple((1, int(cols[k][0][r])) if cols[k][1][r] else (0, 0)
                    for k in keys)
        acc = groups.setdefault(key, {})
        for ci, op in aggs:
            data, valid = cols[ci]
            if (isinstance(op, tuple) or op not in ("sum", "count", "min",
                                                    "max")
                    or data.dtype.kind not in "iu" or data.ndim != 1
                    or table.column(ci).dtype.is_string):
                continue
            slot = acc.setdefault((ci, op), [])
            if valid[r]:
                slot.append(int(data[r]))
    out = {}
    for key in sorted(groups):
        out[key] = {}
        for (ci, op), vals in groups[key].items():
            if op == "count":
                out[key][ci, op] = len(vals)
            elif not vals:
                out[key][ci, op] = None
            elif op == "sum":
                total = sum(vals) % 2**64
                out[key][ci, op] = total - 2**64 if total >= 2**63 else total
            else:
                out[key][ci, op] = min(vals) if op == "min" else max(vals)
    return out


@pytest.fixture(params=["one_chunk", "many_chunks"])
def chunks(request, monkeypatch):
    """The MXU accumulate over one chunk of all the rows, and (its size
    dropped) over seven chunks with a padded tail."""
    if request.param == "many_chunks":
        monkeypatch.setattr(gb, "_MXU_CHUNK_ROWS", 300)
        dispatch.clear()
    yield request.param
    dispatch.clear()


@pytest.mark.parametrize("case", CASES)
def test_small_bound_against_unbounded_and_a_dict(case, chunks):
    table, keys, aggs, rv, m, groups, in_place = _case(case)
    row_valid = None if rv is None else jnp.asarray(rv)
    if rv is not None:
        # a phantom row's cells are null, as a padded tail's and a masked
        # shuffle slot's are (the word-moving path folds phantom rows into
        # the last real group and counts on it; the slots take no notice)
        table = Table([c if i in keys else Column(
            c.dtype, c.data, c.valid_mask() & row_valid, chars=c.chars)
            for i, c in enumerate(table.columns)])
    got = gb.groupby_aggregate(table, keys, aggs, max_groups=m,
                               row_valid=row_valid)
    want = gb.groupby_aggregate(table, keys, aggs, row_valid=row_valid)
    assert bool(got.in_place) == in_place and not bool(want.in_place)
    true_groups = int(want.num_groups)
    if groups is not None:
        assert true_groups == groups
    # the true count even past the bound; the first m groups in key order
    assert int(got.num_groups) == true_groups
    assert bool(got.overflowed) == (true_groups > m)
    # the words are sorted only to count past a broken bound of the loop
    assert bool(got.key_sorted) == (
        in_place and m <= gb._MIN_LOOP_M and true_groups > m)
    assert not bool(want.key_sorted)
    assert got.table.num_rows == m
    _same_columns(got.table, want.table, min(m, true_groups))
    for c in got.table.columns:     # nothing past the last group
        assert not np.asarray(c.valid_mask())[true_groups:].any()
    assert bool(got.sum_overflow) == bool(want.sum_overflow)

    if table.column(keys[0]).dtype.storage_dtype.kind == "f":
        return      # -0.0 == 0.0 == one group: the unbounded path's word
    oracle = _oracle(table, keys, aggs, rv)
    assert len(oracle) == true_groups
    nk = len(keys)
    for row, (key, acc) in enumerate(list(oracle.items())[:m]):
        for pos in range(nk):
            c = got.table.column(pos)
            valid = bool(np.asarray(c.valid_mask())[row])
            assert valid == bool(key[pos][0])
            if valid:
                assert int(np.asarray(c.data)[row]) == key[pos][1]
        for j, (ci, op) in enumerate(aggs):
            if (ci, op) not in acc:
                continue
            c = got.table.column(nk + j)
            if acc[ci, op] is None:
                assert not np.asarray(c.valid_mask())[row], (key, ci, op)
            else:
                assert np.asarray(c.valid_mask())[row], (key, ci, op)
                assert int(np.asarray(c.data).astype(np.int64)[row]) == (
                    acc[ci, op] if acc[ci, op] < 2**63
                    else acc[ci, op] - 2**64), (key, ci, op)


@pytest.mark.parametrize("keys", ["one_word", "two_words", "three_words",
                                  "no_words"])
def test_sort_key_words_is_sort_order_with_its_words(keys):
    """The order is ``sort_order``'s, the sorted words are the rows' words
    in that order, and equal words are equal keys; a float64 key raises."""
    rng = np.random.default_rng(len(keys))
    n = 3000
    cols = {"one_word": [_col(t.INT8, _keys(rng, 5, n), rng.random(n) > 0.2)],
            "two_words": [_col(t.INT8, _keys(rng, 3, n)),
                          _col(t.INT16, _keys(rng, 4, n, (t.INT16, np.int16)),
                               rng.random(n) > 0.2)],
            "three_words": [_col(t.INT64, rng.integers(-3, 3, n) * 2**33),
                            _col(t.INT32, rng.integers(0, 3, n)
                                 .astype(np.int32), rng.random(n) > 0.2)],
            "no_words": [_col(t.FLOAT64, rng.standard_normal(n))],
            }[keys]
    table, at = Table(cols), list(range(len(cols)))
    rv = jnp.asarray(rng.random(n) > 0.3)
    if keys == "no_words":
        with pytest.raises(TypeError):
            so.sort_key_words(table, at, rv)
        return
    order, words, sorted_words = so.sort_key_words(table, at, rv)
    real = int(np.asarray(rv).sum())
    want = np.asarray(so.sort_order(table, at, row_valid=rv))
    assert np.asarray(order)[:real].tolist() == want[:real].tolist()
    assert len(words) == len(sorted_words) == {
        "one_word": 1, "two_words": 2, "three_words": 4}[keys]
    for w, sw in zip(words, sorted_words):
        assert w.dtype == jnp.uint32
        assert np.asarray(sw).tolist() == np.asarray(w)[
            np.asarray(order)].tolist()
    tuples = [tuple((bool(np.asarray(c.valid_mask())[r]),
                     int(np.asarray(c.data)[r]) if np.asarray(
                         c.valid_mask())[r] else 0) for c in cols)
              for r in range(n)]
    stacked = np.stack([np.asarray(w) for w in words], axis=1)
    rows = [r for r in range(n) if bool(rv[r])][:400]
    for a, b in zip(rows, rows[1:]):
        assert (stacked[a] == stacked[b]).all() == (tuples[a] == tuples[b])
    assert not (stacked[np.asarray(rv)][:, None] == stacked[
        ~np.asarray(rv)][None, :40]).all(axis=2).any()    # phantom words


@pytest.mark.parametrize("m", [1, 64, 65, 1024, 1025])
def test_the_gate_is_the_small_bound_and_its_aggregates(m, monkeypatch):
    """In place up to ``_SMALL_M`` groups where the rows pay for the block
    path; with a minimum or a float sum beside the integer sums only up to
    ``_SLOT_REDUCE_M``; never without a bound. In place the groups come
    from the loop up to ``_MIN_LOOP_M`` (64) and from the key sort over
    it (65): the loop sorts no key."""
    n = 2 * 1024 * 32
    rng = np.random.default_rng(m)
    table = Table([_col(t.INT32, rng.integers(0, 50, n).astype(np.int32)),
                   _col(t.INT64, rng.integers(0, 9, n)),
                   _col(t.FLOAT64, rng.random(n))])
    key_sorts = []
    monkeypatch.setattr(gb, "sort_key_words", lambda *a: (
        key_sorts.append(1), so.sort_key_words(*a))[1])
    dispatch.clear()
    run = lambda aggs, bound: bool(gb.groupby_aggregate(  # noqa: E731
        table, [0], aggs, max_groups=bound).in_place)
    assert run(((1, "sum"), (1, "count")), m) == (m <= gb._SMALL_M)
    assert len(key_sorts) == (gb._MIN_LOOP_M < m <= gb._SMALL_M)
    assert run(((1, "sum"), (1, "min")), m) == (m <= gb._SLOT_REDUCE_M)
    assert run(((2, "sum"),), m) == (m <= gb._SLOT_REDUCE_M)
    assert len(key_sorts) == (gb._MIN_LOOP_M < m <= gb._SMALL_M)
    if m == 1:
        assert not run(((1, "sum"),), None)
        few = Table([_col(c.dtype, np.asarray(c.data)[:20])    # a bucket of 32
                     for c in table.columns])
        assert not bool(gb.groupby_aggregate(
            few, [0], ((1, "sum"),), max_groups=1).in_place)
    dispatch.clear()


@pytest.mark.parametrize("m", [1, 5, 64, 65])
def test_a_groups_key_cells_are_its_first_rows(m):
    """The loop hands on, a group, the least row that holds its words: the
    row a stable sort puts first. A null key's stored bytes differ from
    row to row, and the group's key cell is the first row's, as on the
    sort's side of the gate (65) and without a bound."""
    n = 2 * 65 * gb._MIN_BLOCK
    rng = np.random.default_rng(m)
    g = rng.integers(0, 4, n)
    stored = rng.integers(-2**31, 2**31 - 1, n).astype(np.int32)
    key = np.where(g == 0, stored, g).astype(np.int32)     # group 0: null
    rv = rng.random(n) > 0.3
    table = Table([_col(t.INT32, key, g != 0),
                   _col(t.INT64, rng.integers(0, 9, n))])
    got = gb.groupby_aggregate(table, [0], ((1, "sum"),), max_groups=m,
                               row_valid=jnp.asarray(rv))
    want = gb.groupby_aggregate(table, [0], ((1, "sum"),),
                                row_valid=jnp.asarray(rv))
    rows = min(m, 4)
    first = [int(np.flatnonzero(rv & (g == i))[0]) for i in range(rows)]
    cells = np.asarray(got.table.column(0).data)[:rows]
    assert cells.tolist() == key[first].tolist()
    assert cells.tolist() == np.asarray(want.table.column(0).data)[
        :rows].tolist()
    assert not np.asarray(got.table.column(0).valid_mask())[0]

    words = so.key_words(table, [0], jnp.asarray(rv))
    group_words, first_row, found = jax.jit(
        lambda w, v: gb._least_groups(w, v, m))(words, jnp.asarray(rv))
    assert int(found) == min(4, m + 1)       # it stops at group m + 1
    assert np.asarray(first_row)[:rows].tolist() == first
    assert (np.asarray(first_row)[rows:] == n).all()
    for w, gw in zip(words, group_words):
        assert np.asarray(gw)[:rows].tolist() == np.asarray(w)[first].tolist()


def test_auto_grows_past_the_gate_and_says_so():
    """``groupby_aggregate_auto``: its first small bounds take the slots
    and overflow; the bound it ends on is over the gate."""
    n = 2 * 1024 * 32
    rng = np.random.default_rng(9)
    table = Table([_col(t.INT32, rng.integers(0, 3000, n).astype(np.int32)),
                   _col(t.INT64, rng.integers(-9, 9, n))])
    first = gb.groupby_aggregate(table, [0], ((1, "sum"),), max_groups=16)
    assert bool(first.in_place) and bool(first.overflowed)
    assert int(first.num_groups) == 3000
    res = gb.groupby_aggregate_auto(table, [0], ((1, "sum"),), 16)
    want = gb.groupby_aggregate(table, [0], ((1, "sum"),))
    assert not bool(res.overflowed) and not bool(res.in_place)
    _same_columns(res.table, want.table, 3000)


@pytest.mark.parametrize("keys", ["one_high_word", "two_high_words"])
@pytest.mark.parametrize("m", [1024, 1025])
def test_a_lone_int64_key_in_place_keeps_its_words(m, keys):
    """q13's ``custdist`` shape (a count of a count under 1,024 slots): in
    place the rows are matched against the words ``sort_key_words`` sorted
    by, so the key is never ordered as one word (``key_one_word`` False,
    no conditional in the region) whatever its values; one group over the
    gate the word-moving path orders the same key as ONE word where its
    values allow, and both find the same groups."""
    rng = np.random.default_rng(m)
    n = 2 * 1024 * 32 + 500
    pool = np.arange(37, dtype=np.int64) * 3 + (
        2**32 - 50 if keys == "two_high_words" else 5)
    table = Table([_col(t.INT64, pool[rng.integers(0, 37, n)],
                        rng.random(n) > 0.05)])
    plan = fusion.Plan("custdist_like", fusion.GroupBy(
        fusion.Scan("t"), (0,), ((0, "count"),), max_groups=m,
        label="custdist"))
    res = fusion.execute(plan, {"t": table})
    in_place = m <= gb._SMALL_M
    assert bool(res.meta["custdist.in_place"]) == in_place
    assert bool(res.meta["custdist.key_one_word"]) == (
        not in_place and keys == "one_high_word")
    facts = fusion.meta_facts(plan, res.meta)
    assert facts["groupby.key_one_word"] == int(
        res.meta["custdist.key_one_word"])
    assert (" conditional(" in _region_hlo(plan, {"t": table})) == (
        not in_place)
    valid = np.asarray(table.column(0).valid_mask())
    values, counts = np.unique(np.asarray(table.column(0).data)[valid],
                               return_counts=True)
    assert int(res.meta["custdist.num_groups"]) == 38      # and the nulls'
    got = [c.to_pylist()[:38] for c in res.table.columns]
    assert got == [[None] + values.tolist(), [0] + counts.tolist()]


# -- through fusion.execute: the table and every side output the parent's --

@pytest.fixture
def parent_path(monkeypatch):
    """Call it to run with the small-bound path off, as the parent ran."""
    def switch(off: bool):
        if off:
            monkeypatch.setattr(gb, "_aggregates_in_place",
                                lambda *a: False)
        else:
            monkeypatch.undo()
        dispatch.clear()
    yield switch
    monkeypatch.undo()
    dispatch.clear()


def _same_result(got, want, skip=("groupby.in_place",)):
    rows = got.table.num_rows
    assert rows == want.table.num_rows
    _same_columns(got.table, want.table, rows)
    assert sorted(got.meta) == sorted(want.meta)
    for name in got.meta:
        if name not in skip:
            assert np.asarray(got.meta[name]).tolist() == np.asarray(
                want.meta[name]).tolist(), name


def test_general_q1_through_fusion_is_the_parents(parent_path):
    lineitem = tpch.lineitem_table(6000, seed=33)
    got = fusion.execute(tpch._q1_plan(), {"lineitem": lineitem})
    assert bool(got.meta["groupby.in_place"])
    parent_path(True)
    want = fusion.execute(tpch._q1_plan(), {"lineitem": lineitem})
    assert not bool(want.meta["groupby.in_place"])
    _same_result(got, want)
    assert int(got.meta["groupby.num_groups"]) == 7


def test_distributed_q1_on_four_devices_is_the_parents(parent_path):
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = executor_mesh(4)
    lineitem = tpch.lineitem_table(4 * 4096, seed=34)
    sharded = jax.device_put(lineitem, NamedSharding(mesh, P(EXEC_AXIS)))
    plan = tpch._q1_distributed_plan()
    got = fusion.execute(plan, {"lineitem": sharded})
    assert bool(got.meta["groupby.in_place"])
    assert int(got.meta["groupby.shuffle_rows"]) > 0    # it went over the mesh
    parent_path(True)
    want = fusion.execute(plan, {"lineitem": sharded})
    assert not bool(want.meta["groupby.in_place"])
    _same_result(got, want)
    one_chip = fusion.execute(plan, {"lineitem": lineitem})
    _same_columns(got.table, one_chip.table, got.table.num_rows)


# -- the lowered regions: what a later edit must not bring back unseen -----

def _loops_that_sort(hlo: str) -> list:
    """Names of the computations a ``while`` runs that hold a sort."""
    bodies = set(re.findall(r"body=%?([\w.\-]+)", hlo))
    found = []
    for name in bodies:
        text = re.search(
            rf"^%?{re.escape(name)} [^\n]*\{{\n(.*?)^\}}", hlo, re.S | re.M)
        if text and " sort(" in text.group(1):
            found.append(name)
    return found


def _computations(hlo: str) -> dict:
    """The text of every computation of an HLO module by its name."""
    return {m.group(1): m.group(2) for m in re.finditer(
        r"^(?:ENTRY )?%?([\w.\-]+) [^\n]*\{\n(.*?)^\}", hlo, re.S | re.M)}


def _under_a_conditional(hlo: str) -> set:
    """Names of the computations that run only inside a branch of a
    ``conditional``: the branches and whatever they call."""
    comps = _computations(hlo)
    todo = []
    for text in comps.values():
        for line in text.splitlines():
            if " conditional(" in line:
                todo += re.findall(
                    r"(?:branch_computations=\{|true_computation=|"
                    r"false_computation=|, )%?([\w.\-]+)",
                    line[line.index(" conditional("):].split(
                        "metadata=")[0])
    inside = set()
    while todo:
        name = todo.pop()
        if name in comps and name not in inside:
            inside.add(name)
            todo += re.findall(r"%([\w.\-]+)", comps[name])
    return inside


def test_general_q1_region_sorts_its_keys_and_nothing_else():
    """One bucket of the served general q1, whose bound (64) is the
    loop's: the groupby's only n-row sort is the words-only one that
    counts the groups past a broken bound, and it lies inside the
    ``conditional``'s branch, not on the taken path (the parent sorted the
    words and an iota of every row there); no ``while`` sorts, the loop
    that finds the groups least of all; no n-row gather."""
    n = 5000      # under an outer trace the region is walked unpadded
    hlo = _region_hlo(tpch._q1_plan(), {"lineitem": tpch.lineitem_table(n)})
    comps, inside = _computations(hlo), _under_a_conditional(hlo)
    where = {name: [s for s in _sorts(text) if f"[{n}]" in s]
             for name, text in comps.items()}
    # two int8 flags and their null ranks: one word, and no iota with it
    assert sorted(s for found in where.values() for s in found) == [
        f"u32[{n}]{{0}}"]
    assert [name for name, found in where.items()
            if found and name not in inside] == []
    assert inside and _loops_that_sort(hlo) == []
    assert [g for g in _gathers(hlo, "groupby") if n in g[1]] == []


def test_planned_q3_region_keeps_its_sorts(monkeypatch):
    """The bound of planned q3 (70,001 here, 1,500,001 in the cell) is a
    groups-of-orders spec over the block path's gate: the region keeps the
    word-moving path, its sorts what they were in number, and ``permute``'s
    loop is still there. The key sort's loop is not (PR 37): the key's
    declared range makes it one sort of two words and an iota. The ORDER
    BY's loop stands once a branch of its conditional (PR 47): over the
    rung of the 70,001 group rows, and over all of them."""
    monkeypatch.setattr(so, "_SORT_MOVE_MIN_WORDS", 0)
    hlo = _region_hlo(tpch._q3_planned_plan(0, 9204),
                      _q3_tables(n_ord=70_000))
    sorts = _sorts(hlo)
    assert len(sorts) == 5 and sorts.count("u32[4000]{0}") == 1, sorts
    assert sorts.count(
        "(u32[4000]{0}, u32[4000]{0}, s32[4000]{0})") == 1, sorts
    for rows in (8192, 70_001):
        assert sorts.count(f"(u32[{rows}]{{0}}, s32[{rows}]{{0}})") == 1, sorts
    assert len(_loops_that_sort(hlo)) == 3     # permute, ORDER BY's two


# -- the served path's counter --------------------------------------------

def _served(plan, bindings):
    names = ("groupby.in_place", "groupby.key_sorted")
    before = [REGISTRY.counters().get(name, 0) for name in names]
    with QueryServer(budget_bytes=4 << 30) as srv:
        ticket = srv.session("t").submit(plan, bindings)
        try:
            result, exc = ticket.result(), None
        except Exception as caught:
            result, exc = None, caught
    return (ticket, result, exc, *(
        REGISTRY.counters().get(name, 0) - was
        for name, was in zip(names, before)))


@pytest.mark.parametrize("plan,moves", [
    ("general_q1", 1), ("planned_q1", 0), ("planned_q3", 0), ("overflow", 1)])
def test_served_requests_count_groupby_in_place(plan, moves):
    """Once a request, like ``groupby.groups``: 1 for general q1, 0 for
    the declared-domain plan and for q3's bound over the gate; a bound
    that overflowed in place is still a refused request. Only that one
    sorted its key words (``groupby.key_sorted``), for the true count its
    ``CapacityOverflow`` gives."""
    if plan == "overflow":
        rng = np.random.default_rng(4)
        table = Table([_col(t.INT64, rng.integers(0, 50, N)),
                       _col(t.INT64, rng.integers(0, 9, N))])
        probe = fusion.Plan("in_place_overflow", fusion.GroupBy(
            fusion.Scan("t"), (0,), ((1, "sum"),), max_groups=M,
            label="groupby"))
        ticket, result, exc, moved, sorted_keys = _served(probe, {"t": table})
        assert isinstance(exc, resilience.CapacityOverflow)
        assert exc.context["groups"] == 50
        assert ticket.status == "failed" and moved == moves
        assert sorted_keys == 1
        return
    made = {"general_q1": (tpch._q1_plan(),
                           {"lineitem": tpch.lineitem_table(5000, seed=1)}),
            "planned_q1": (tpch._q1_planned_plan(),
                           {"lineitem": tpch.lineitem_table(5000, seed=2)}),
            "planned_q3": (tpch._q3_planned_plan(0, 9204),
                           _q3_tables(n_ord=2100, n=70000))}[plan]
    ticket, result, exc, moved, sorted_keys = _served(*made)
    assert exc is None and ticket.status == "served"
    assert (ticket.tier, ticket.rung, ticket.steps) == ("fused", 0, 0)
    assert moved == moves and sorted_keys == 0
    facts = fusion.meta_facts(made[0], result.meta)
    assert facts["groupby.in_place"] == moves
    assert facts["groupby.key_sorted"] == 0
