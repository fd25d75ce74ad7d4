"""``ops/groupby.py::_group_bounds`` (the group bounds of the sort path from
one compaction of the group-start mask) against the definition it
replaced, kept here as the oracle: a per-row group id ``cumsum(~same) - 1``
and two binary searches over it. First the helper alone on plain masks,
then every caller (``groupby_aggregate`` under a bound off the block path,
``nunique``, ``groupby_percentile``, the list collect) with the searches
put back, buffer for buffer."""

import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from spark_rapids_jni_tpu import types as t
from spark_rapids_jni_tpu.columnar import Column, Table
from spark_rapids_jni_tpu.ops import groupby as gb
from spark_rapids_jni_tpu.ops import lists as ls
from tests.test_permute import ALL_AGGS, _run, _same_bits, _wide_table


def _searched_numpy(same, m):
    """The old definition in numpy: ``searchsorted`` left / right of
    0..m-1 in the per-row group id; nothing to search when n == 0."""
    if len(same) == 0:
        return 0, np.zeros(m, np.int32), np.zeros(m, np.int32)
    gid = np.cumsum(~same) - 1
    g = np.arange(m)
    return (int(gid[-1]) + 1, np.searchsorted(gid, g, "left"),
            np.searchsorted(gid, g, "right"))


def _searched(same, m):
    """The same as the package had it (``_dense_group_bounds``)."""
    n = same.shape[0]
    garange = jnp.arange(m, dtype=jnp.int32)
    if n == 0:
        return (jnp.int32(0), jnp.zeros((m,), jnp.int32),
                jnp.zeros((m,), jnp.int32))
    gid = (jnp.cumsum(~same) - 1).astype(jnp.int32)
    return ((gid[-1] + 1).astype(jnp.int32),
            jnp.searchsorted(gid, garange, side="left").astype(jnp.int32),
            jnp.searchsorted(gid, garange, side="right").astype(jnp.int32))


@contextlib.contextmanager
def _searches_put_back():
    saved = gb._group_bounds, ls._group_bounds
    gb._group_bounds = ls._group_bounds = _searched
    try:
        yield
    finally:
        gb._group_bounds, ls._group_bounds = saved


def _mask(kind, n, rng):
    """``(same, m)``: ``same[i]`` False where sorted row i starts a group.
    A phantom row is one whose ``same`` was forced True (it starts none)."""
    same = rng.random(n) > 0.3          # about 0.3 * n groups
    if n:
        same[0] = False
    m = max(n // 2, 1)
    if kind == "overflow":              # more groups than the bound
        m = max(int((~same).sum()) // 3, 1)
    elif kind == "m_eq_n":
        m = n
    elif kind == "m_gt_n":
        m = n + 37
    elif kind == "phantom_tail":
        same[n - n * 2 // 5:] = True
    elif kind == "all_phantom":
        same[:] = True
    elif kind == "one_group":
        same[1:] = True
    elif kind == "every_row":
        same[:] = False
        m = n + 1
    return same, m


@pytest.mark.parametrize("n", [0, 1, 777, 4096])
@pytest.mark.parametrize("kind", [
    "overflow", "m_eq_n", "m_gt_n", "phantom_tail", "all_phantom",
    "one_group", "every_row"])
def test_group_bounds_are_the_searched_bounds(kind, n):
    same, m = _mask(kind, n, np.random.default_rng([n, len(kind)]))
    total, lo, hi = _searched_numpy(same, m)
    got = jax.jit(gb._group_bounds, static_argnums=1)(jnp.asarray(same), m)
    assert int(got[0]) == total == int((~same).sum())
    if kind == "overflow" and n > 3:
        assert total > m
    for arr, want in zip(got[1:], (lo, hi)):
        assert arr.dtype == jnp.int32 and arr.shape == (m,)
        assert np.asarray(arr).tolist() == want.tolist()
    # and the searches as the package had them agree with numpy's
    old = _searched(jnp.asarray(same), m)
    assert int(old[0]) == total
    assert np.asarray(old[1]).tolist() == lo.tolist()
    assert np.asarray(old[2]).tolist() == hi.tolist()


def _same_table(got, want):
    assert got.num_columns == want.num_columns
    for gc, wc in zip(got.columns, want.columns):
        assert gc.dtype == wc.dtype
        _same_bits(gc.data, wc.data)
        _same_bits(gc.valid_mask(), wc.valid_mask())
        if gc.dtype.is_string:
            _same_bits(gc.chars, wc.chars)
        for gk, wk in zip(gc.children or (), wc.children or ()):
            _same_table(Table([gk]), Table([wk]))


FAMILIES = {
    "sums_counts": [a for a in ALL_AGGS if a[1] in ("sum", "count")],
    "means": [a for a in ALL_AGGS if a[1] == "mean"],
    "min_max": [a for a in ALL_AGGS if a[1] in ("min", "max")],
    "variances": [a for a in ALL_AGGS
                  if a[1] in ("var", "std", "var_pop", "std_pop")],
    "covariances": [a for a in ALL_AGGS if isinstance(a[1], tuple)],
    "first_last": [a for a in ALL_AGGS if a[1] in gb._ONE_ROW_AGGS],
    "nunique": [(2, "nunique"), (5, "nunique"), (9, "nunique")],
    "all": ALL_AGGS,
}


@pytest.mark.parametrize("phantoms", [False, True])
@pytest.mark.parametrize("groups,max_groups", [
    (300, 2000),    # under a bound, off the block path
    (900, 200),     # more groups than the bound: overflowed
    (40, None),     # m = n
])
@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_groupby_off_the_block_path_equals_the_searched_formulation(
        family, groups, max_groups, phantoms):
    rng = np.random.default_rng([groups, phantoms])
    n = 1500
    table = _wide_table(rng, n, groups)
    row_valid = jnp.asarray(rng.random(n) > 0.15) if phantoms else None
    m = n if max_groups is None else max_groups
    assert not (m <= gb._SMALL_M and 2 * m * gb._MIN_BLOCK <= n)
    aggs = FAMILIES[family]
    got = _run(table, [0, 1], aggs, max_groups, row_valid)
    with _searches_put_back():
        want = _run(table, [0, 1], aggs, max_groups, row_valid)
    assert int(got.num_groups) == int(want.num_groups)
    assert bool(got.overflowed) == bool(want.overflowed) == (
        max_groups == 200)
    assert bool(got.sum_overflow) == bool(want.sum_overflow)
    _same_table(got.table, want.table)


@pytest.mark.parametrize("phantoms", [False, True])
@pytest.mark.parametrize("max_groups", [16, 2000, None])
def test_nunique_counts_the_distinct_values_of_each_group(
        max_groups, phantoms):
    """``nunique`` sorts by (keys, value) a second time and reads its
    counts at the bounds of the first sort: against Python sets, on the
    block path (16), under a bound off it, and at m = n."""
    rng = np.random.default_rng([7, phantoms])
    n = 1500
    keys = rng.integers(0, 11, n).astype(np.int64)
    kvalid = rng.random(n) > 0.1
    vals = rng.integers(-6, 6, n).astype(np.int32)
    vvalid = rng.random(n) > 0.25
    rv = rng.random(n) > 0.2 if phantoms else np.ones(n, bool)
    vvalid &= rv        # a phantom row's cells are null, as a bucket's tail
    table = Table([Column(t.INT64, jnp.asarray(keys), jnp.asarray(kvalid)),
                   Column(t.INT32, jnp.asarray(vals), jnp.asarray(vvalid))])
    res = _run(table, [0], [(1, "nunique"), (1, "count")], max_groups,
               jnp.asarray(rv) if phantoms else None)
    want: dict = {}
    for i in np.flatnonzero(rv):
        seen = want.setdefault(int(keys[i]) if kvalid[i] else None, set())
        if vvalid[i]:
            seen.add(int(vals[i]))
    g = int(res.num_groups)
    assert g == len(want) == 12 and not bool(res.overflowed)
    got = dict(zip(res.table.column(0).to_pylist()[:g],
                   res.table.column(1).to_pylist()[:g]))
    assert got == {k: len(v) for k, v in want.items()}


@pytest.mark.parametrize("max_groups", [None, 2000, 5])
def test_groupby_percentile_equals_the_searched_formulation(max_groups):
    rng = np.random.default_rng(21)
    table = _wide_table(rng, 600, 7)
    got = gb.groupby_percentile(table, [0, 1], 2, [0.0, 0.5, 0.9],
                                max_groups=max_groups)
    with _searches_put_back():
        want = gb.groupby_percentile(table, [0, 1], 2, [0.0, 0.5, 0.9],
                                     max_groups=max_groups)
    assert int(got.num_groups) == int(want.num_groups) > 5
    assert bool(got.overflowed) == bool(want.overflowed) == (max_groups == 5)
    _same_table(got.table, want.table)


@pytest.mark.parametrize("n", [0, 1, 400])
@pytest.mark.parametrize("distinct", [False, True])
def test_groupby_collect_equals_the_searched_formulation(distinct, n):
    rng = np.random.default_rng(n)
    table = Table([
        Column(t.INT64, jnp.asarray(rng.integers(0, 7, n)),
               jnp.asarray(rng.random(n) > 0.1)),
        Column(t.INT32, jnp.asarray(rng.integers(-20, 20, n).astype(
            np.int32)), jnp.asarray(rng.random(n) > 0.2))])
    got = ls.groupby_collect(table, [0], 1, distinct=distinct)
    with _searches_put_back():
        want = ls.groupby_collect(table, [0], 1, distinct=distinct)
    assert int(got.num_groups) == int(want.num_groups) == min(n, 8)
    _same_table(got.table, want.table)
    assert got.table.column(1).to_pylist() == want.table.column(1).to_pylist()
