"""Shape-bucketed dispatch & executable cache (runtime/dispatch, ISSUE 3).

Three invariant families:

1. **Bit-identity** — bucketed results must be byte-for-byte identical to
   the unbucketed path (``dispatch.enabled = False``) at the row counts
   where padding is most likely to leak: 1, 2^k-1, 2^k, 2^k+1 around the
   bucket edges, including null validity tails, reductions, sort
   permutations and groupby outputs. Values are integers (or
   integer-valued floats), so "identical" means exact equality.

2. **Executable reuse** — the acceptance micro-benchmark: >=8 distinct
   row counts inside one bucket compile exactly ONCE (telemetry
   ``dispatch.compile`` counter), while distinct statics / dtypes / ops
   recompile.

3. **Bucket schedule** — bucket_for / quantize_capacity arithmetic and
   the config knobs that drive them.
"""

import collections

import numpy as np
import pytest

from spark_rapids_jni_tpu import types as t
from spark_rapids_jni_tpu.columnar import Column, Table
from spark_rapids_jni_tpu.ops import elementwise as e
from spark_rapids_jni_tpu.ops import reduce as red
from spark_rapids_jni_tpu.ops.groupby import groupby_aggregate
from spark_rapids_jni_tpu.ops.hash import table_xxhash64
from spark_rapids_jni_tpu.ops.sort import sort_order
from spark_rapids_jni_tpu.runtime import dispatch
from spark_rapids_jni_tpu.telemetry import REGISTRY
from spark_rapids_jni_tpu.utils.config import reset_option, set_option

# row counts straddling the power-of-two bucket edges of the default
# base-16 schedule: 1, 2^k-1, 2^k, 2^k+1 for the 16/32/64 buckets
EDGE_COUNTS = (1, 15, 16, 17, 31, 32, 33, 63, 64, 65)


@pytest.fixture(autouse=True)
def _isolated_dispatch():
    """Each test sees a fresh executable cache and counter namespace and
    leaves the dispatch config at its defaults."""
    dispatch.clear()
    REGISTRY.reset()
    yield
    for k in ("dispatch.enabled", "dispatch.bucket_base",
              "dispatch.max_waste_frac"):
        reset_option(k)
    dispatch.clear()


def _int_col(rng, n, null_tail=True):
    vals = rng.integers(-1000, 1000, n).astype(np.int64)
    validity = np.ones(n, bool)
    if null_tail and n > 2:
        # nulls at the END of the column — adjacent to where padding
        # phantoms live, the spot a masking bug would corrupt first
        validity[-2:] = False
        validity[rng.integers(0, n)] = False
    return Column.from_numpy(vals, validity=validity)


def _both_paths(fn):
    """Run ``fn()`` bucketed then unbucketed, return both results."""
    bucketed = fn()
    set_option("dispatch.enabled", False)
    try:
        unbucketed = fn()
    finally:
        set_option("dispatch.enabled", True)
    return bucketed, unbucketed


def _assert_cols_identical(a: Column, b: Column):
    assert np.array_equal(np.asarray(a.valid_mask()),
                          np.asarray(b.valid_mask()))
    av, bv = np.asarray(a.data), np.asarray(b.data)
    mask = np.asarray(a.valid_mask())
    if av.ndim > 1:  # decimal128 limb pairs and the like
        mask = mask.reshape((-1,) + (1,) * (av.ndim - 1))
    # invalid slots hold unspecified bytes by the Column contract
    assert np.array_equal(np.where(mask, av, 0), np.where(mask, bv, 0))


# ---------------------------------------------------------------------------
# 1. bit-identity at bucket edges
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n", EDGE_COUNTS)
def test_elementwise_bit_identical_at_edges(rng, n):
    col = _int_col(rng, n)
    other = _int_col(rng, n)
    for op in (lambda: e.abs_(col),
               lambda: e.coalesce([col, other]),
               lambda: e.nullif(col, other),
               lambda: e.greatest([col, other])):
        got, want = _both_paths(op)
        _assert_cols_identical(got, want)


@pytest.mark.parametrize("n", EDGE_COUNTS)
def test_reductions_bit_identical_at_edges(rng, n):
    col = _int_col(rng, n)
    fcol = Column.from_numpy(
        rng.integers(-50, 50, n).astype(np.float64),  # integer-exact floats
        validity=np.asarray(col.valid_mask()))

    for fn in (lambda: red.sum_(col), lambda: red.sum_(fcol),
               lambda: red.min_(col), lambda: red.max_(col),
               lambda: red.mean(fcol)):
        (gv, gok), (wv, wok) = _both_paths(fn)
        assert bool(gok) == bool(wok)
        if bool(wok):
            assert np.asarray(gv) == np.asarray(wv)
    gc_, wc_ = _both_paths(lambda: red.count(col))  # count: bare scalar
    assert int(gc_) == int(wc_)


@pytest.mark.parametrize("n", EDGE_COUNTS)
def test_sort_order_bit_identical_at_edges(rng, n):
    keys = _int_col(rng, n)
    ties = Column.from_numpy(rng.integers(0, 3, n).astype(np.int64))
    tbl = Table([ties, keys])
    for kwargs in ({"ascending": [True, True]},
                   {"ascending": [False, True]},
                   {"nulls_first": [True, True]}):
        got, want = _both_paths(
            lambda: sort_order(tbl, [0, 1], **kwargs))
        # a stable sort has exactly one correct permutation: exact match
        assert np.array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("n", EDGE_COUNTS)
def test_groupby_bit_identical_at_edges(rng, n):
    keys = Column.from_numpy(rng.integers(0, 4, n).astype(np.int64))
    vals = _int_col(rng, n)
    tbl = Table([keys, vals])
    aggs = [(1, "sum"), (1, "count"), (1, "min"), (1, "max")]

    got, want = _both_paths(lambda: groupby_aggregate(tbl, [0], aggs))
    assert int(got.num_groups) == int(want.num_groups)
    m = int(want.num_groups)
    for gc, wc in zip(got.table.columns, want.table.columns):
        gm = np.asarray(gc.valid_mask())[:m]
        assert np.array_equal(gm, np.asarray(wc.valid_mask())[:m])
        assert np.array_equal(
            np.where(gm, np.asarray(gc.data)[:m], 0),
            np.where(gm, np.asarray(wc.data)[:m], 0))


@pytest.mark.parametrize("n", EDGE_COUNTS)
def test_hash_bit_identical_at_edges(rng, n):
    tbl = Table([_int_col(rng, n), _int_col(rng, n)])
    got, want = _both_paths(lambda: table_xxhash64(tbl, [0, 1], seed=7))
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_groupby_all_null_tail_rows(rng):
    """Rows whose caller row_valid is False must vanish from the grouped
    output exactly as the unbucketed path drops them."""
    n = 33  # 2^5+1: two pad rows in the 64 bucket... no: bucket 64, 31 pads
    keys = Column.from_numpy(rng.integers(0, 3, n).astype(np.int64))
    vals = Column.from_numpy(rng.integers(-9, 9, n).astype(np.int64))
    rv = np.ones(n, bool)
    rv[-5:] = False
    tbl = Table([keys, vals])
    got, want = _both_paths(
        lambda: groupby_aggregate(tbl, [0], [(1, "sum"), (1, "count")],
                                  row_valid=np.asarray(rv)))
    assert int(got.num_groups) == int(want.num_groups)
    m = int(want.num_groups)
    for gc, wc in zip(got.table.columns, want.table.columns):
        assert np.array_equal(np.asarray(gc.data)[:m],
                              np.asarray(wc.data)[:m])


# ---------------------------------------------------------------------------
# 2. executable reuse (the acceptance micro-benchmark)
# ---------------------------------------------------------------------------


def test_one_bucket_compiles_exactly_once(rng):
    """>=8 distinct row counts inside one bucket -> the op compiles exactly
    ONCE; the un-migrated path would have compiled once per row count. The
    pad compiles eight times: it is one executable an exact row count (a
    copy, a fraction of a second each), as the eager ``zeros`` and
    ``concatenate`` it replaces compiled once a shape unseen by any
    counter. 1024 sits on its bucket: its pad builds masks only, and its
    int64 column is handed on as the caller's own buffer where the seven
    off the bucket arrive as two uint32 planes, so the op compiles a second
    time for it (one executable for each form of the boundary)."""
    counts = (513, 600, 649, 700, 801, 900, 1000, 1024)  # all -> bucket 1024
    results = []
    for n in counts:
        col = Column.from_numpy(np.arange(n, dtype=np.int64))
        total, ok = red.sum_(col)
        results.append(int(total))
        assert bool(ok)
    assert results == [n * (n - 1) // 2 for n in counts]
    c = REGISTRY.counters("dispatch.")
    assert c["dispatch.compile.reduce_sum"] == 2
    assert c["dispatch.hit.reduce_sum"] == len(counts) - 2
    assert c["dispatch.compile.pad"] == len(counts)
    assert "dispatch.hit.pad" not in c
    assert c["dispatch.compile"] == 2 + len(counts)
    assert c["dispatch.pad.word_leaves"] == len(counts) - 1
    assert (c["dispatch.pad.jitted"], c["dispatch.pad.passthrough"]) == (
        len(counts) - 1, 1)


def test_distinct_buckets_and_dtypes_compile_separately():
    a = Column.from_numpy(np.arange(10, dtype=np.int64))
    b = Column.from_numpy(np.arange(100, dtype=np.int64))  # other bucket
    c = Column.from_numpy(np.arange(10, dtype=np.int32))   # other dtype
    for col in (a, b, c):
        red.sum_(col)
    counters = REGISTRY.counters("dispatch.")
    assert counters["dispatch.compile.reduce_sum"] == 3
    assert counters["dispatch.compile.pad"] == 3
    # same shapes again: all hits, of the op and of its pad
    for col in (a, b, c):
        red.sum_(col)
    counters = REGISTRY.counters("dispatch.")
    assert counters["dispatch.compile"] == 6
    assert counters["dispatch.hit.reduce_sum"] == 3
    assert counters["dispatch.hit.pad"] == 3


def test_statics_change_recompiles(rng):
    tbl = Table([Column.from_numpy(
        rng.integers(0, 100, 20).astype(np.int64))])
    sort_order(tbl, [0], ascending=[True])
    before = REGISTRY.counter("dispatch.compile").value
    # same shapes + op, different static (sort direction): a fresh compile
    # of the op; the pad knows no statics and is a hit
    sort_order(tbl, [0], ascending=[False])
    assert REGISTRY.counter("dispatch.compile").value == before + 1
    assert REGISTRY.counter("dispatch.compile.pad").value == 1
    # and re-running either direction is a pure hit (the op's and the pad's)
    hits = REGISTRY.counter("dispatch.hit").value
    sort_order(tbl, [0], ascending=[True])
    sort_order(tbl, [0], ascending=[False])
    assert REGISTRY.counter("dispatch.compile").value == before + 1
    assert REGISTRY.counter("dispatch.hit").value == hits + 4
    assert REGISTRY.counter("dispatch.hit.pad").value == 3


def test_the_environment_is_no_part_of_an_executables_key(rng, monkeypatch):
    """A key is the call's own arguments, the bucket configuration and the
    backend: a variable that once chose a kernel per op, set between two
    calls of the same op and shapes, compiles nothing."""
    tbl = Table([Column.from_numpy(
        rng.integers(0, 100, 20).astype(np.int64))])
    sort_order(tbl, [0], ascending=[True])
    before = REGISTRY.counter("dispatch.compile").value
    monkeypatch.setenv("SPARK_RAPIDS_TPU_KERNEL_TIER", "auto")
    sort_order(tbl, [0], ascending=[True])
    assert REGISTRY.counter("dispatch.compile").value == before
    assert REGISTRY.counter("dispatch.hit.sort_order").value == 1


def test_disabled_dispatch_never_compiles(rng):
    set_option("dispatch.enabled", False)
    col = _int_col(rng, 20)
    red.sum_(col)
    e.abs_(col)
    assert REGISTRY.counter("dispatch.compile").value == 0
    assert REGISTRY.counter("dispatch.inline.disabled").value == 2
    assert dispatch.cache_size() == 0


def test_padded_waste_accounted(rng):
    col = Column.from_numpy(np.arange(17, dtype=np.int64))  # bucket 32
    red.sum_(col)
    c = REGISTRY.counters("dispatch.")
    assert c["dispatch.padded_waste_bytes"] > 0
    assert 0.0 < (c["dispatch.padded_waste_bytes"]
                  / c["dispatch.row_bytes_total"]) < 1.0


# ---------------------------------------------------------------------------
# 3. bucket schedule arithmetic
# ---------------------------------------------------------------------------


def test_bucket_schedule_defaults():
    assert dispatch.bucket_for(1) == 16
    assert dispatch.bucket_for(16) == 16
    assert dispatch.bucket_for(17) == 32
    assert dispatch.bucket_for(1000) == 1024
    assert dispatch.quantize_capacity(17) == 32


def test_bucket_schedule_waste_knob():
    # max_waste_frac bounds the growth ratio: at 0.25 the schedule grows
    # by at most 1.25x per step, so buckets are much denser than 2x
    set_option("dispatch.max_waste_frac", 0.25)
    n = 100
    b = dispatch.bucket_for(n)
    assert b >= n
    assert (b - n) / n <= 0.25 + 16 / n  # base-multiple rounding slack
    set_option("dispatch.bucket_base", 8)
    assert dispatch.bucket_for(1) == 8
    reset_option("dispatch.bucket_base")
    reset_option("dispatch.max_waste_frac")


def test_quantize_capacity_disabled_is_identity():
    set_option("dispatch.enabled", False)
    assert dispatch.quantize_capacity(17) == 17


def test_concurrent_first_compile_is_single_flight():
    """N threads racing the FIRST compile of one key: exactly one thread
    compiles (the leader), the rest block on the in-flight marker and
    reuse its executable. The old code let every racer compile the same
    key (last store wins), so dispatch.compile would read N here. A
    sleeping probe at the dispatch.compile seam holds the leader inside
    _compile long enough that every racer is genuinely concurrent."""
    import threading
    import time

    from spark_rapids_jni_tpu.runtime import faults

    def slow_compile(seam, seq, ctx):
        if seam == "dispatch.compile":
            time.sleep(0.3)

    n_threads = 8
    col = Column.from_numpy(np.arange(1000, dtype=np.int64))
    barrier = threading.Barrier(n_threads)
    results = [None] * n_threads
    errors = []

    def racer(i):
        barrier.wait()
        try:
            total, ok = red.sum_(col)
            assert bool(ok)
            results[i] = int(total)
        except BaseException as exc:  # noqa: B036 - surfaced to the test
            errors.append(exc)

    with faults.inject(slow_compile):
        threads = [threading.Thread(target=racer, args=(i,))
                   for i in range(n_threads)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(60)
    assert not errors
    assert results == [1000 * 999 // 2] * n_threads
    # the op and, before it, its pad: one leader each
    c = REGISTRY.counters("dispatch.")
    assert (c["dispatch.compile.reduce_sum"], c["dispatch.compile.pad"]) == (
        1, 1)
    assert c["dispatch.hit.reduce_sum"] == n_threads - 1
    assert c["dispatch.hit.pad"] == n_threads - 1
    assert c["dispatch.compile"] == 2


def _total(x):
    import jax.numpy as jnp

    return jnp.sum(x, dtype=jnp.int64)


@pytest.mark.parametrize("n", [5, 33])
def test_compiled_memoizes_one_executable_an_exact_shape(n):
    """``compiled``: no bucketing (a digest reads every element), the
    same cache and the same counters as ``call``."""
    import jax.numpy as jnp

    x = jnp.arange(n, dtype=jnp.int64)
    first = dispatch.compiled("total", _total, x)
    assert int(first(x)) == n * (n - 1) // 2
    assert dispatch.compiled("total", _total, x + 1) is first
    c = REGISTRY.counters()
    assert (c["dispatch.compile"], c["dispatch.compile.total"]) == (1, 1)
    assert (c["dispatch.hit"], c["dispatch.hit.total"]) == (1, 1)
    # another length in the same bucket, another dtype: new executables
    dispatch.compiled("total", _total, jnp.arange(n + 1, dtype=jnp.int64))
    dispatch.compiled("total", _total, x.astype(jnp.int32))
    assert REGISTRY.counters()["dispatch.compile.total"] == 3
    assert dispatch.cache_size() == 3


# ---------------------------------------------------------------------------
# 4. the pad: one cached executable a call, equal to the eager pad
# ---------------------------------------------------------------------------


def _ints(n, dtype=np.int64, seed=7):
    return np.random.default_rng(seed + n).integers(-99, 99, n).astype(dtype)


def _col(n, nulls):
    validity = (np.arange(n) % 5 != 3) if nulls else None
    return Column.from_numpy(_ints(n), validity=validity)


def _strings(n):
    from spark_rapids_jni_tpu.ops.strings import pad_strings

    return pad_strings(Column.from_pylist(
        [None if i % 7 == 2 else "ab" * (i % 4) for i in range(n)], t.STRING))


def _arrays(n):
    import jax.numpy as jnp

    return (jnp.asarray(_ints(n, np.int32)),
            jnp.asarray(_ints(2 * n).reshape(n, 2).astype(np.float64)))


_Pair = collections.namedtuple("_Pair", "left right")

# name -> the row groups of one call (built inside the test: device arrays)
_PAD_CASES = {
    "column_with_validity": lambda: (_col(21, True),),
    "column_without_validity": lambda: (_col(21, False),),
    "padded_string_column": lambda: (_strings(19),),
    "table": lambda: (Table([_col(40, True), _col(40, False),
                             Column.from_numpy(_ints(40, np.int32))]),),
    "tuple": lambda: (_arrays(33) + (None,),),
    "namedtuple": lambda: (_Pair(*_arrays(33)),),
    "list": lambda: (list(_arrays(17)),),
    "dict": lambda: (dict(zip("ba", _arrays(100))),),
    "numpy_leaf": lambda: ((_ints(50), _arrays(50)[0]),),
    "two_groups": lambda: (Table([_col(21, True)]), _col(100, False)),
    "on_its_bucket": lambda: (Table([_col(32, True), _col(32, False),
                                     _strings(32)]), _arrays(64)),
    "one_group_on_its_bucket": lambda: ((_col(64, False), _arrays(64)[0]),
                                        _col(65, False)),
}


@pytest.mark.parametrize("case", sorted(_PAD_CASES))
def test_jitted_pad_equals_the_eager_pad_leaf_for_leaf(case):
    """``_pad_groups`` (one executable) against ``_pad_tree`` run eagerly,
    the pad as it was: once the words of its 64-bit integer leaves are
    assembled (``_join_words``, what ``call``'s executable does first) the
    same tree, every leaf the same shape, dtype and bits, the same masks;
    the bytes ``call`` reckons from the shapes are those the eager pad
    counted; a group on its bucket keeps its buffers."""
    import jax

    row_args = _PAD_CASES[case]()
    ns = tuple(dispatch._group_rows(g) for g in row_args)
    buckets = tuple(dispatch.bucket_for(n) for n in ns)
    handed, row_valids, row_bytes, word_leaves = dispatch._pad_groups(
        row_args, ns, buckets)
    for out, n in zip(handed, ns):   # the planes' tails are zeros too
        for got in jax.tree_util.tree_leaves(out):
            assert not np.asarray(got)[n:].any()
    padded = dispatch._join_words(handed)

    acc = dispatch._PadStats()
    want = tuple(dispatch._pad_tree(g, n, B, acc)
                 for g, n, B in zip(row_args, ns, buckets))
    got_leaves, got_tree = jax.tree_util.tree_flatten(padded)
    want_leaves, want_tree = jax.tree_util.tree_flatten(want)
    assert got_tree == want_tree
    for got, leaf in zip(got_leaves, want_leaves):
        assert isinstance(got, jax.Array)
        assert (got.shape, got.dtype) == (leaf.shape, leaf.dtype)
        assert np.array_equal(np.asarray(got), np.asarray(leaf))
    for out, n in zip(padded, ns):   # the tail is zeros: NULL rows
        for got in jax.tree_util.tree_leaves(out):
            assert not np.asarray(got)[n:].any()
    assert len(row_valids) == len(row_args)
    for mask, n, B in zip(row_valids, ns, buckets):
        assert mask.dtype == np.bool_
        assert np.array_equal(np.asarray(mask), np.arange(B) < n)

    sized = list(zip(ns, buckets, row_bytes))
    assert sum(B * row for _, B, row in sized) == acc.total_bytes
    assert sum((B - n) * row for n, B, row in sized) == acc.padded_bytes
    assert sum(B * row for n, B, row in sized if B != n) == acc.copied_bytes

    for group, out, n, B in zip(row_args, padded, ns, buckets):
        if n == B:   # handed on, not copied: donate_rows relies on it
            held = {id(x) for x in jax.tree_util.tree_leaves(out)}
            assert all(id(x) in held
                       for x in jax.tree_util.tree_leaves(group))
    on_bucket = all(n == B for n, B in zip(ns, buckets))
    c = REGISTRY.counters("dispatch.pad.")
    assert c == {"dispatch.pad.passthrough" if on_bucket
                 else "dispatch.pad.jitted": 1,
                 "dispatch.pad.word_leaves": word_leaves}
    assert word_leaves == sum(
        dispatch._is_words(x) for x in jax.tree_util.tree_leaves(
            handed, is_leaf=dispatch._is_words))
    assert REGISTRY.counter("dispatch.compile.pad").value == 1
    # again: the same executable, whatever the values
    dispatch._pad_groups(row_args, ns, buckets)
    assert REGISTRY.counter("dispatch.compile.pad").value == 1
    assert REGISTRY.counter("dispatch.hit.pad").value == 1


def _unbucketable(case):
    import jax.numpy as jnp

    if case == "nested":
        return Column(t.DType(t.TypeId.LIST),
                      jnp.asarray([0, 2, 2, 5], jnp.int32), None,
                      children=[Column.from_numpy(_ints(5))])
    if case == "arrow_string":
        return Column.from_pylist(["a", "bc", None], t.STRING)
    if case == "mismatched_rows":
        return (_arrays(20)[0], _arrays(21)[0])
    return (_arrays(20)[0], 3)   # a leaf that is no array


@pytest.mark.parametrize("case", ["nested", "arrow_string",
                                  "mismatched_rows", "scalar_leaf"])
def test_unbucketable_inputs_still_go_inline(case):
    """What the pad cannot represent is found on the host before anything
    is traced: the op runs inline, nothing compiles."""
    group = _unbucketable(case)
    out = dispatch.call("probe", lambda rows, aux, rvs: (rows, rvs), (group,))
    assert out[0][0] is group and out[1] is None
    c = REGISTRY.counters("dispatch.")
    assert c["dispatch.inline.unbucketable"] == 1
    assert "dispatch.compile" not in c and "dispatch.pad.jitted" not in c


def test_two_ops_over_one_column_share_one_pad(rng):
    """The pad is keyed on the rows it pads, not on the op."""
    col = _int_col(rng, 600)
    red.sum_(col)
    red.min_(col)
    e.abs_(col)
    c = REGISTRY.counters("dispatch.")
    assert (c["dispatch.compile.pad"], c["dispatch.hit.pad"]) == (1, 2)
    assert c["dispatch.pad.jitted"] == 3


def test_a_pad_that_fails_runs_the_op_inline(rng, monkeypatch):
    """``call`` never raises on the pad's behalf and keeps no second pad:
    the op answers un-jitted over the rows as they came."""
    col = _int_col(rng, 600)
    want = red.sum_(col)
    real = dispatch.compiled

    def broken(op, fn, *args, **kw):
        if op == "pad":
            raise RuntimeError("injected pad failure")
        return real(op, fn, *args, **kw)

    monkeypatch.setattr(dispatch, "compiled", broken)
    got = red.sum_(col)
    assert (int(got[0]), bool(got[1])) == (int(want[0]), bool(want[1]))
    c = REGISTRY.counters("dispatch.")
    assert (c["dispatch.pad_error"], c["dispatch.inline.pad_error"]) == (1, 1)
    assert c["dispatch.pad.jitted"] == 1   # the first call's


# ---------------------------------------------------------------------------
# 4b. the boundary: a 64-bit integer leaf off its bucket crosses as words
# ---------------------------------------------------------------------------

# the bits a split or an assembly could lose: both ends of the range, a low
# word of 2**31 and over (a sign the narrowing must not extend), a high word
# alone, both words full
_EDGES = np.array(
    [0, -1, 1, np.iinfo(np.int64).min, np.iinfo(np.int64).max,
     2**31, 2**31 + 5, 2**32 - 1, 2**32, -2**32, 2**63 - 2**32,
     -2**31, -2**31 - 1, 0x7FFFFFFF80000000, -0x7FFFFFFF80000000,
     0x123456789ABCDEF], dtype=np.int64)


def _edge_values(n, dtype=np.int64):
    vals = np.resize(_EDGES, n)
    vals[len(_EDGES):] ^= np.arange(n - len(_EDGES), dtype=np.int64) << 29
    return vals.view(dtype) if dtype != np.int64 else vals


def _edge_col(dtype, nulls, n=21, storage=np.int64):
    validity = (np.arange(n) % 5 != 3) if nulls else None
    return Column.from_numpy(_edge_values(n, storage), dtype,
                             validity=validity)


def _device(values):
    import jax.numpy as jnp

    return jnp.asarray(values)


# name -> (the row group, how many of its leaves cross as words)
_WORD_CASES = {
    "int64": lambda: (_edge_col(t.INT64, False), 1),
    "int64_with_validity": lambda: (_edge_col(t.INT64, True), 1),
    "uint64": lambda: (_edge_col(t.UINT64, False, storage=np.uint64), 1),
    "uint64_with_validity": lambda: (
        _edge_col(t.UINT64, True, storage=np.uint64), 1),
    "decimal64": lambda: (_edge_col(t.decimal64(-2), False), 1),
    "decimal64_with_validity": lambda: (_edge_col(t.decimal64(-2), True), 1),
    "timestamp": lambda: (_edge_col(t.TIMESTAMP_MICROSECONDS, False), 1),
    "timestamp_with_validity": lambda: (
        _edge_col(t.TIMESTAMP_MICROSECONDS, True), 1),
    "bare_int64_array": lambda: (_edge_values(33), 1),
    "trailing_dimension": lambda: (_edge_values(40).reshape(20, 2), 1),
    "decimal128_limbs": lambda: (Column(
        t.decimal128(-2), _device(_edge_values(40).reshape(20, 2))), 1),
    "table": lambda: (Table([
        _edge_col(t.INT64, True), _edge_col(t.decimal64(-3), False),
        Column.from_numpy(_ints(21, np.int32)),
        _edge_col(t.UINT64, False, storage=np.uint64)]), 3),
}

# leaves that must cross in their own dtype, bit for bit
_NARROW_CASES = {
    "float64": lambda: np.linspace(-1e300, 1e300, 21),
    "int32": lambda: _ints(21, np.int32),
    "int8": lambda: _ints(21, np.int8),
    "bool": lambda: np.arange(21) % 3 == 0,
    "uint32": lambda: _ints(21, np.int64).astype(np.uint32),
    "padded_string": lambda: _strings(19),
}


def _probe(rows, aux, row_valids):
    """What ``fn`` sees, and something computed from it."""
    import jax

    return rows, jax.tree_util.tree_map(
        lambda x: x * 3 + 1 if x.dtype.kind in "iu" else x, rows)


def _same_bits(got, want):
    """Two row groups (a Column, a Table of them or one array) hold the same
    dtypes, shapes and bits; a Column without a validity equals one whose
    mask is all true (the pad always writes one)."""
    def columns(x):
        return x.columns if isinstance(x, Table) else [x]

    for g, w in zip(columns(got), columns(want)):
        pairs = [(g, w)]
        if isinstance(w, Column):
            assert g.dtype == w.dtype
            pairs = [(g.data, w.data), (g.valid_mask(), w.valid_mask())]
            if w.chars is not None:
                pairs.append((g.chars, w.chars))
        for a, b in pairs:
            a, b = np.asarray(a), np.asarray(b)
            assert (a.shape, a.dtype) == (b.shape, b.dtype)
            assert np.array_equal(a, b)


def _executable_arguments(op):
    """The leaves of the row groups the executable cached for ``op`` was
    compiled over: ``(dtype, id)`` of what crossed the boundary."""
    (key,) = [k for k in dispatch._EXEC_CACHE if k[0] == op]
    return key[3][1]


@pytest.mark.parametrize("case", sorted(_WORD_CASES))
def test_words_cross_the_boundary_and_fn_sees_the_same_tree(case):
    """``call`` over a group off its bucket: ``fn`` gets the pytree it gets
    inline (the same tree, dtypes and bits, no ``_Words``), what it computes
    is bit-identical to the inline path, the executable's own arguments
    hold no 64-bit integer, and the counter says how many crossed."""
    import jax

    group, crossing = _WORD_CASES[case]()
    (seen, computed), (seen_inline, computed_inline) = _both_paths(
        lambda: dispatch.call("probe", _probe, (group,)))
    assert seen_inline[0] is group   # the inline path: the caller's arrays
    _same_bits(seen[0], group)
    _same_bits(computed[0], computed_inline[0])
    assert not any(dispatch._is_words(x) for x in jax.tree_util.tree_leaves(
        seen, is_leaf=dispatch._is_words))
    dtypes = [d for _, d, _ in _executable_arguments("probe")]
    assert "int64" not in dtypes and "uint64" not in dtypes
    assert dtypes.count("uint32") == 2 * crossing
    assert REGISTRY.counter("dispatch.pad.word_leaves").value == crossing


@pytest.mark.parametrize("case", sorted(_NARROW_CASES))
def test_narrower_leaves_and_float64_cross_as_they_are(case):
    """Only the 64-bit INTEGERS are split: a ``float64`` (a float32 pair on
    the chip, no bitcast), every narrower leaf, a mask and a padded string
    reach the executable in their own dtype, and nothing is counted."""
    import jax

    group = _NARROW_CASES[case]()
    (seen, computed), (_, computed_inline) = _both_paths(
        lambda: dispatch.call("probe", _probe, (group,)))
    _same_bits(seen[0], group)
    _same_bits(computed[0], computed_inline[0])
    handed, _, _, word_leaves = dispatch._pad_groups(
        (group,), (dispatch._group_rows(group),),
        (dispatch.bucket_for(dispatch._group_rows(group)),))
    assert word_leaves == 0
    assert not any(dispatch._is_words(x) for x in jax.tree_util.tree_leaves(
        handed, is_leaf=dispatch._is_words))
    assert REGISTRY.counter("dispatch.pad.word_leaves").value == 0


@pytest.mark.parametrize("donate", [False, True], ids=["kept", "donated"])
def test_a_group_on_its_bucket_crosses_as_the_callers_int64(donate):
    """On its bucket nothing is split (it would be a new pass) and nothing
    copied: the executable takes the caller's own int64 buffer, which is
    what ``donate_rows`` gives away; off its bucket, in the same bucket,
    the op compiles a second executable over the planes."""
    import jax.numpy as jnp

    on = jnp.asarray(_edge_values(64))
    taken = []

    def fn(rows, aux, rvs):
        taken.append(rows[0])
        return rows[0] * 3

    want = _edge_values(64) * 3
    got = dispatch.call("on_bucket", fn, (on,), donate_rows=donate)
    assert np.array_equal(np.asarray(got), want)
    assert [d for _, d, _ in _executable_arguments("on_bucket")][0] == "int64"
    c = REGISTRY.counters("dispatch.")
    assert c["dispatch.pad.word_leaves"] == 0
    assert c["dispatch.pad.passthrough"] == 1
    handed, _, _, _ = dispatch._pad_groups((on,), (64,), (64,))
    assert handed[0] is on
    got = dispatch.call("on_bucket", fn, (jnp.asarray(_edge_values(50)),),
                        donate_rows=donate)
    assert np.array_equal(np.asarray(got), _edge_values(50) * 3)
    assert REGISTRY.counter("dispatch.compile.on_bucket").value == 2
    assert REGISTRY.counter("dispatch.pad.word_leaves").value == 1
    assert all(x.dtype == np.int64 for x in taken)   # fn never sees planes


def test_the_pad_span_says_how_many_leaves_crossed_as_words(observed):
    """``dispatch.pad.word_leaves`` is also an attribute of the span."""
    from spark_rapids_jni_tpu.telemetry import spans

    with spans.span("query.seam"):
        dispatch.call("probe", _probe, (_WORD_CASES["table"]()[0],))
        dispatch.call("probe", _probe, (_NARROW_CASES["int32"](),))
    pads = _records(observed, "dispatch.pad")
    assert [r["word_leaves"] for r in pads] == [3, 0]


def test_the_compiled_module_keeps_the_ops_name():
    """The assembly sits behind ``fn``'s own name: a trace tells
    ``jit_region_<plan>`` from everything else by it."""
    def region_q(rows, aux, rvs):
        return rows[0] + 1

    wrapped = dispatch._on_words(region_q)
    assert (wrapped.__name__, wrapped.__qualname__) == (
        region_q.__name__, region_q.__qualname__)
    dispatch.call("named", region_q, (_edge_values(21),))
    (entry,) = [v for k, v in dispatch._EXEC_CACHE.items() if k[0] == "named"]
    assert "jit_region_q" in entry[0].as_text()[:200]


def test_pad_sharded_hands_int64_on_as_int64():
    """Over a mesh the pad is as it was: the region's ``shard_map`` step
    takes int64, and the counter moves by 0 (but exists)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from spark_rapids_jni_tpu.parallel.mesh import executor_mesh

    mesh = executor_mesh(4)
    n = 4 * 21
    shard = NamedSharding(mesh, P("exec"))
    col = Column(t.INT64, jax.device_put(_edge_values(n), shard),
                 jax.device_put(np.arange(n) % 5 != 3, shard))
    (padded,), (row_valid,) = dispatch.pad_sharded(
        "probe", (Table([col]),), mesh, "exec")
    data = padded.columns[0].data
    assert data.dtype == np.int64 and data.shape == (4 * 32,)
    got = np.asarray(data).reshape(4, 32)
    assert np.array_equal(got[:, :21], _edge_values(n).reshape(4, 21))
    assert not got[:, 21:].any()
    assert REGISTRY.counters("dispatch.pad.") == {
        "dispatch.pad.word_leaves": 0}


# ---------------------------------------------------------------------------
# 5. the compile seam: one span a compile, with where its seconds went,
#    what the executable needs, and why it failed
# ---------------------------------------------------------------------------

_MEMORY = ("argument_bytes", "output_bytes", "alias_bytes", "temp_bytes",
           "code_bytes", "need_bytes")
_SECONDS = ("trace_s", "lower_s", "backend_s", "cache_load_s")


@pytest.fixture
def observed():
    from spark_rapids_jni_tpu import telemetry

    set_option("telemetry.enabled", True)
    telemetry.drain()
    yield telemetry
    telemetry.drain()
    reset_option("telemetry.enabled")


def _records(telemetry, op):
    return [r for r in telemetry.events()
            if r.get("kind") == "span" and r["op"] == op]


def _scaled(rows, aux, row_valid):
    import jax.numpy as jnp

    return jnp.cumsum(rows[0] * 3)


def _via_call(x):
    return dispatch.call("seam_call", _scaled, (x,), bucket_rows=False)


def _via_compiled(x):
    return dispatch.compiled("seam_compiled", lambda v: v * 3, x)(x)


def _via_sharded_call(x):
    return dispatch.sharded_call(
        "seam_sharded", lambda: (lambda v: v * 3), (x,))


@pytest.mark.parametrize("run, op, executes", [
    (_via_call, "seam_call", True), (_via_compiled, "seam_compiled", False),
    (_via_sharded_call, "seam_sharded", True)],
    ids=["call", "compiled", "sharded_call"])
def test_a_compile_leaves_one_span_with_its_facts(run, op, executes,
                                                  observed):
    """Each of the three ways in goes through ``_lower_and_compile``: ONE
    ``dispatch.compile`` record an executable (``call`` makes its pad's
    first) with XLA's buffer assignment and JAX's own seconds, the facts
    kept beside the executable, and the need repeated on every
    ``dispatch.execute``, compile or hit."""
    import jax.numpy as jnp

    from spark_rapids_jni_tpu.telemetry import spans

    x = jnp.arange(48, dtype=jnp.int64)
    with spans.span("query.seam"):
        run(x)
        run(x + 1)   # a hit
    c = REGISTRY.counters("dispatch.")
    *pads, rec = _records(observed, "dispatch.compile")
    assert len(pads) == c.get("dispatch.compile.pad", 0) == (op == "seam_call")
    assert (c[f"dispatch.compile.{op}"], c[f"dispatch.hit.{op}"]) == (1, 1)
    assert rec["status"] == "ok" and "error" not in rec
    assert all(isinstance(rec[k], int) for k in _MEMORY)
    assert rec["need_bytes"] == (rec["argument_bytes"] + rec["output_bytes"]
                                 + rec["temp_bytes"] - rec["alias_bytes"])
    assert rec["argument_bytes"] >= x.nbytes
    assert all(rec[k] >= 0 for k in _SECONDS)
    assert rec["trace_s"] > 0 and rec["lower_s"] > 0
    assert rec["trace_s"] + rec["lower_s"] + rec["backend_s"] <= (
        rec["t1"] - rec["t0"])
    assert rec["cache_load_s"] <= rec["backend_s"]
    assert rec["persistent"] in ("hit", "miss", "unasked")
    ((_, facts),) = [v for k, v in dispatch._EXEC_CACHE.items() if k[0] == op]
    assert {k: rec[k] for k in _MEMORY} == {k: facts[k] for k in _MEMORY}
    ran = _records(observed, "dispatch.execute")
    assert len(ran) == (2 if executes else 0)
    assert all((r["need_bytes"], r["temp_bytes"]) == (
        facts["need_bytes"], facts["temp_bytes"]) for r in ran)
    assert c["dispatch.xla.trace_lower_ns"] == sum(
        int((r["trace_s"] + r["lower_s"]) * 1e9) for r in pads + [rec])
    assert c["dispatch.xla.backend_ns"] == sum(
        int(r["backend_s"] * 1e9) for r in pads + [rec])
    assert not any(r["kind"] == "compile_cache" for r in observed.events())
    assert "compile_cache.hit" not in REGISTRY.counters()


def test_two_threads_compiling_at_once_keep_their_own_seconds(observed):
    """The listener adds to the compile open on ITS thread: a slow trace on
    one thread is not the other's, whose whole compile ran meanwhile."""
    import threading
    import time

    import jax.numpy as jnp

    from spark_rapids_jni_tpu.telemetry import spans

    tracing, errors = threading.Event(), []

    def slow(v):
        tracing.set()
        time.sleep(0.6)    # inside the trace of "seam_slow"
        return v + 1

    def compile_on_a_thread(op, fn, wait):
        try:
            assert wait is None or wait.wait(30)
            with spans.span(f"query.{op}"):
                dispatch.compiled(op, fn, jnp.arange(8))
        except BaseException as exc:  # noqa: B036 - surfaced to the test
            errors.append(exc)

    threads = [
        threading.Thread(target=compile_on_a_thread,
                         args=("seam_slow", slow, None)),
        threading.Thread(target=compile_on_a_thread,
                         args=("seam_quick", lambda v: v * 2, tracing))]
    for th in threads:
        th.start()
    for th in threads:
        th.join(60)
        assert not th.is_alive()
    assert not errors
    slow_rec, quick_rec = sorted(_records(observed, "dispatch.compile"),
                                 key=lambda r: -r["trace_s"])
    assert slow_rec["trace_s"] >= 0.6 > quick_rec["trace_s"]
    # the quick one closed inside the slow one's trace: at once, not after
    assert slow_rec["t0"] < quick_rec["t1"] < slow_rec["t1"]
    assert getattr(dispatch._compiling, "open", None) is None


def test_a_compile_that_raises_leaves_failed_with_its_reason(observed):
    import jax.numpy as jnp

    from spark_rapids_jni_tpu.telemetry import spans

    def bad(v):
        raise ValueError("no such lowering: " + "x" * 300)

    with spans.span("query.seam"):
        with pytest.raises(ValueError):
            dispatch.compiled("seam_bad", bad, jnp.arange(4))
        # ``call`` answers inline, and the ring still says why
        out = dispatch.call(
            "seam_bad_call", lambda rows, aux, valid: bad(rows)
            if valid is not None else rows[0] + 1, (jnp.arange(4),))
    assert out.tolist() == [1, 2, 3, 4]
    first, pad, second = _records(observed, "dispatch.compile")
    assert pad["status"] == "ok" and pad["need_bytes"] > 0
    for rec in (first, second):
        assert rec["status"] == "failed" and rec["error"] == "ValueError"
        assert rec["error_message"] == ("no such lowering: " + "x" * 300)[:200]
        assert "need_bytes" not in rec and rec["trace_s"] >= 0
    assert REGISTRY.counters()["dispatch.compile_error"] == 1
    assert dispatch.cache_size() == 1   # the pad's; nothing failed is kept
    assert getattr(dispatch._compiling, "open", None) is None


def test_with_telemetry_off_nothing_is_written_and_the_facts_are_kept():
    import jax.numpy as jnp

    from spark_rapids_jni_tpu import telemetry
    from spark_rapids_jni_tpu.telemetry import spans

    assert not telemetry.enabled()
    telemetry.drain()
    with spans.span("query.seam"):
        _via_call(jnp.arange(48, dtype=jnp.int64))
    assert telemetry.events() == []
    assert not REGISTRY.counters("dispatch.xla.")
    ((_, facts),) = [v for k, v in dispatch._EXEC_CACHE.items()
                     if k[0] == "seam_call"]
    assert facts["need_bytes"] == (
        facts["argument_bytes"] + facts["output_bytes"]
        + facts["temp_bytes"] - facts["alias_bytes"]) > 0
    assert REGISTRY.counters()["dispatch.compile.seam_call"] == 1


_PERSISTENT_PROBE = """
import jax.numpy as jnp
from spark_rapids_jni_tpu import telemetry
from spark_rapids_jni_tpu.runtime import dispatch
from spark_rapids_jni_tpu.utils.config import set_option
set_option("telemetry.enabled", True)
with telemetry.spans.span("query.probe"):
    dispatch.compiled("probe", lambda v: jnp.cumsum(v) * 2, jnp.arange(64))
(rec,) = [r for r in telemetry.events() if r["op"] == "dispatch.compile"]
xla = telemetry.REGISTRY.counters("dispatch.xla.persistent_")
print("PROBE", rec["persistent"], rec["cache_load_s"] > 0, sorted(xla.items()))
"""


def test_a_second_process_reads_the_persistent_cache_as_a_hit(tmp_path):
    """The running test process initialised its cache long ago, so two
    children against a cache directory of their own: the first writes
    (``miss``), the second loads (``hit``, with the load's seconds)."""
    import os
    import subprocess
    import sys

    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="-1")
    env.pop("JAX_ENABLE_COMPILATION_CACHE", None)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    said = []
    for _ in range(2):
        out = subprocess.run([sys.executable, "-c", _PERSISTENT_PROBE],
                             cwd=root, env=env, capture_output=True,
                             text=True, timeout=120)
        assert out.returncode == 0, out.stderr[-2000:]
        said.append([ln for ln in out.stdout.splitlines()
                     if ln.startswith("PROBE")][-1])
    assert said == [
        "PROBE miss False [('dispatch.xla.persistent_miss', 1)]",
        "PROBE hit True [('dispatch.xla.persistent_hit', 1)]"]
