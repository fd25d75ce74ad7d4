"""64-bit sort keys as 32-bit words (``ops/sort.py``): ``sort_order`` and
the sort-path groupby against plain oracles, the shape of the sorts XLA is
given (what a cold compile of the planned q3 region costs goes with it),
every plan node named in the lowered region, and a served request whose
plan declaration broke resolving as a failure."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from spark_rapids_jni_tpu import types as t
from spark_rapids_jni_tpu.columnar import Column, Table
from spark_rapids_jni_tpu.models import tpch
from spark_rapids_jni_tpu.ops import sort as so
from spark_rapids_jni_tpu.ops.groupby import groupby_aggregate
from spark_rapids_jni_tpu.runtime import fusion, resilience
from spark_rapids_jni_tpu.runtime.server import QueryServer
from spark_rapids_jni_tpu.telemetry import REGISTRY

DTYPES = {"int64": (t.INT64, np.int64), "decimal64": (t.decimal64(-2), np.int64),
          "int32": (t.INT32, np.int32), "int8": (t.INT8, np.int8)}


def _values(rng, np_dt, n):
    """Few distinct values (ties), negatives, and the type's extremes."""
    info = np.iinfo(np_dt)
    pool = np.array([info.min, info.min + 1, -2, -1, 0, 1, 2, info.max - 1,
                     info.max] + list(rng.integers(
                         info.min, info.max, 6, dtype=np_dt)), dtype=np_dt)
    return pool[rng.integers(0, len(pool), n)]


def _oracle_order(cols, ascending, nulls_first, row_valid):
    """The stable order by Python tuples: phantom rows last, then per key
    its null rank and its value (a null's value counts as equal)."""
    def key(i):
        out = [0 if row_valid is None or row_valid[i] else 1]
        for (vals, valid), asc, nf in zip(cols, ascending, nulls_first):
            if valid[i]:
                v = int(vals[i])
                out += [1 if nf else 0, v if asc else -v]
            else:
                out += [0 if nf else 1, 0]
        return tuple(out)
    n = len(cols[0][0])
    return sorted(range(n), key=key)


@pytest.mark.parametrize("phantoms", [False, True])
@pytest.mark.parametrize("nulls_first", [True, False])
@pytest.mark.parametrize("ascending", [True, False])
@pytest.mark.parametrize("dtype", sorted(DTYPES))
def test_sort_order_matches_a_stable_lexicographic_oracle(
        dtype, ascending, nulls_first, phantoms):
    dt, np_dt = DTYPES[dtype]
    rng = np.random.default_rng([sorted(DTYPES).index(dtype), ascending, nulls_first])
    n = 700
    major = (_values(rng, np_dt, n), rng.random(n) > 0.15)
    minor = (rng.integers(-3, 3, n).astype(np.int32), rng.random(n) > 0.1)
    table = Table([Column(dt, jnp.asarray(major[0]), jnp.asarray(major[1])),
                   Column(t.INT32, jnp.asarray(minor[0]),
                          jnp.asarray(minor[1]))])
    row_valid = rng.random(n) > 0.2 if phantoms else None
    got = so.sort_order(
        table, [0, 1], [ascending, not ascending],
        [nulls_first, not nulls_first],
        row_valid=None if row_valid is None else jnp.asarray(row_valid))
    want = _oracle_order([major, minor], [ascending, not ascending],
                         [nulls_first, not nulls_first], row_valid)
    # phantom rows rank after every real row, in no promised order
    real = n if row_valid is None else int(row_valid.sum())
    assert np.asarray(got).tolist()[:real] == want[:real]


def test_sort_order_single_int64_key_matches_numpy_lexsort():
    """No nulls, no phantoms: numpy's own stable lexsort is the oracle."""
    rng = np.random.default_rng(11)
    a = _values(rng, np.int64, 1000)
    b = rng.integers(-2, 2, 1000).astype(np.int64)
    table = Table([Column(t.INT64, jnp.asarray(a)),
                   Column(t.INT64, jnp.asarray(b))])
    got = np.asarray(so.sort_order(table, [0, 1]))
    assert got.tolist() == np.lexsort((b, a)).tolist()


def _lone(np_dt, values, valid=None, row_valid=None, dtype=None):
    values = np.asarray(values, dtype=np_dt)
    return (dtype or (t.INT64 if np_dt == np.int64 else t.UINT64), values,
            None if valid is None else np.asarray(valid, bool),
            None if row_valid is None else np.asarray(row_valid, bool))


def _lone_key_cases() -> dict:
    """case -> ((dtype, values, valid, row_valid), sorted as one word)."""
    rng = np.random.default_rng(49)
    n = 300
    sparse = rng.integers(1, 6_000_000, 80)[rng.integers(0, 80, n)]
    nulls = rng.random(n) > 0.15
    real = rng.random(n) > 0.2
    far = np.where(rng.random(n) > 0.5, 2**62, -2**61)
    base = 5 * 2**32 + 2**31        # low words that end at a word's end
    span = np.r_[base, base + 2**30 - 1, rng.integers(
        base, base + 2**30, n - 2)]
    return {
        "dbgen_sparse_order_keys": (_lone(np.int64, sparse), True),
        "a_validity_mask_with_nulls": (
            # (a null's stored bytes lie far outside the range)
            _lone(np.int64, np.where(nulls, sparse, far), nulls), True),
        "phantom_rows_far_outside_the_range": (
            _lone(np.int64, np.where(real, sparse, far), None, real), True),
        "nulls_and_phantoms": (
            _lone(np.int64, np.where(real & nulls, sparse, far),
                  nulls | ~real, real), True),
        "negative_keys": (_lone(np.int64, -sparse), True),
        "a_decimal64_key_around_zero_straddles_a_high_word": (
            _lone(np.int64, sparse - 3_000_000, nulls,
                  dtype=t.decimal64(-2)), False),
        "uint64_keys_past_the_sign_bit": (
            _lone(np.uint64, sparse.astype(np.uint64) + np.uint64(2**63),
                  nulls), True),
        "a_span_of_2**30_less_one": (_lone(np.int64, span), True),
        "a_span_of_2**30": (
            _lone(np.int64, np.r_[span[:-1], base + 2**30]), False),
        "keys_that_straddle_a_high_word": (
            _lone(np.int64, 2**32 + rng.integers(-5, 5, n)), False),
        "a_phantoms_key_does_not_widen_the_span": (
            # one high word among the rows that hold a key; a phantom
            # whose validity reads True holds another
            _lone(np.int64, np.r_[2**40, sparse[1:]], None,
                  np.r_[False, np.ones(n - 1, bool)]), True),
        "no_keyed_row": (_lone(np.int64, sparse, np.zeros(n, bool)), False),
        "every_row_a_phantom": (
            _lone(np.int64, sparse, None, np.zeros(n, bool)), False),
        "one_row": (_lone(np.int64, [-7]), True),
    }


LONE_KEY = _lone_key_cases()


@pytest.mark.parametrize("nulls_first", [True, False])
@pytest.mark.parametrize("ascending", [True, False])
@pytest.mark.parametrize("case", list(LONE_KEY))
def test_a_lone_64_bit_key_sorts_as_one_word_where_the_data_allows(
        case, ascending, nulls_first):
    """``sort_order`` on one int64 / uint64 / decimal64 key: on the real
    rows numpy's stable lexsort, bit for bit, whichever form ran, and the
    form it reports: ONE word where the rows that hold a key hold one high
    word and low words under 2**30 apart, the word loop otherwise."""
    (dtype, values, valid, row_valid), one_word = LONE_KEY[case]
    table = Table([Column(dtype, jnp.asarray(values),
                          None if valid is None else jnp.asarray(valid))])
    rv = None if row_valid is None else jnp.asarray(row_valid)
    got, form = so.sort_order_and_form(
        table, [0], [ascending], [nulls_first], row_valid=rv)
    assert form.dtype == jnp.bool_ and form.shape == ()
    assert bool(form) == one_word
    # the oracle: a value's dense rank (a null's counts as equal), the null
    # rank over it, the phantom rank over both
    n = len(values)
    valid = np.ones(n, bool) if valid is None else valid
    rank = np.unique(values, return_inverse=True)[1].astype(np.int64)
    rank = np.where(valid, rank if ascending else -rank, 0)
    null_rank = valid if nulls_first else ~valid
    phantom = np.zeros(n, bool) if row_valid is None else ~row_valid
    want = np.lexsort((rank, null_rank, phantom))
    # (phantom rows rank after every real row, in no promised order)
    real = n - int(phantom.sum())
    assert np.asarray(got).dtype == np.int32
    assert np.asarray(got).tolist()[:real] == want.tolist()[:real]
    assert np.asarray(so.sort_order(
        table, [0], [ascending], [nulls_first], row_valid=rv)).tolist() == (
            np.asarray(got).tolist())


def test_pack_words_is_the_keys_bit_string():
    """Keys of 8, 32, 32, 8 and 8 bits are 88 bits: three words, the
    second key straddling the first two."""
    rng = np.random.default_rng(5)
    keys = [rng.integers(0, 2**w, 50, dtype=np.uint64).astype(dt)
            for w, dt in ((8, np.uint8), (32, np.uint32), (32, np.uint32),
                          (8, np.uint8), (8, np.uint8))]
    words = [np.asarray(w) for w in so._pack_words(
        [jnp.asarray(k) for k in keys])]
    assert len(words) == 3 and all(w.dtype == np.uint32 for w in words)
    for i in range(50):
        whole, shift = 0, 0
        for k in keys:
            whole |= int(k[i]) << shift
            shift += k.dtype.itemsize * 8
        assert [int(w[i]) for w in words] == [
            (whole >> s) & 0xFFFFFFFF for s in (0, 32, 64)]


def _mask_of(n: int, rows) -> np.ndarray:
    mask = np.zeros(n, bool)
    mask[np.asarray(rows, dtype=np.int64)] = True
    return mask


_RNG = np.random.default_rng(46)
# case -> (mask, slots)
POSITIONS = {
    "an_empty_mask": (np.zeros(300, bool), 40),
    "a_full_mask": (np.ones(128, bool), 128),
    "as_many_as_slots": (_mask_of(1000, _RNG.choice(1000, 50, False)), 50),
    "one_more_than_slots": (_mask_of(1000, _RNG.choice(1000, 51, False)),
                            50),
    "a_run_of_32_rows": (_mask_of(400, range(64, 96)), 40),
    # (a word is one lane of a tile of 4,096 rows: 32 rows 128 apart)
    "every_kept_row_in_one_word": (_mask_of(4500, range(5, 4096, 128)), 40),
    "rows_no_multiple_of_the_word": (_RNG.random(1237) < 0.1, 200),
    "the_last_row_alone": (_mask_of(1237, [1236]), 3),
    "more_slots_than_rows": (_RNG.random(50) < 0.5, 80),
    "dense_and_far_over_the_slots": (_RNG.random(4096) < 0.7, 64),
    "the_last_slot_in_the_middle_of_a_tile": (_RNG.random(20000) < 0.5,
                                              5000),
    "sparse_over_many_tiles": (_RNG.random(70001) < 0.01, 1024),
}


@pytest.mark.parametrize("case", list(POSITIONS))
def test_positions_of_against_flatnonzero(case):
    """``positions_of``: the set rows' positions, ascending, padded with
    n; the count true past the slots, the first ``k`` positions right."""
    mask, k = POSITIONS[case]
    want = np.flatnonzero(mask)
    pos, count = jax.jit(so.positions_of, static_argnums=1)(
        jnp.asarray(mask), k)
    assert pos.dtype == jnp.int32 and pos.shape == (k,)
    assert int(count) == len(want)
    padded = np.full(k, len(mask))
    padded[:min(k, len(want))] = want[:k]
    assert np.asarray(pos).tolist() == padded.tolist()


@pytest.mark.parametrize("max_groups", [None, 64])
def test_groupby_three_keys_int64_int32_int32_matches_a_dict(max_groups):
    rng = np.random.default_rng(7)
    n = 900
    k0 = _values(rng, np.int64, n)[rng.integers(0, 5, n)]
    k1 = rng.integers(-2, 2, n).astype(np.int32)
    k2 = rng.integers(0, 2, n).astype(np.int32)
    v0 = rng.random(n) > 0.1
    val = rng.integers(-10**6, 10**6, n).astype(np.int64)
    table = Table([Column(t.INT64, jnp.asarray(k0), jnp.asarray(v0)),
                   Column(t.INT32, jnp.asarray(k1)),
                   Column(t.INT32, jnp.asarray(k2)),
                   Column(t.decimal64(-2), jnp.asarray(val))])
    res = groupby_aggregate(table, [0, 1, 2], [(3, "sum"), (3, "count")],
                            max_groups=max_groups)
    want: dict = {}
    for i in range(n):
        key = (int(k0[i]) if v0[i] else None, int(k1[i]), int(k2[i]))
        s, c = want.get(key, (0, 0))
        want[key] = (s + int(val[i]), c + 1)
    assert not bool(res.overflowed)
    g = int(res.num_groups)
    assert g == len(want)
    cols = [c.to_pylist() for c in res.table.columns]
    got = {(cols[0][i], cols[1][i], cols[2][i]): (cols[3][i], cols[4][i])
           for i in range(g)}
    assert got == want
    # groups come out in key order, the null key first
    keys = [(cols[0][i] is not None, cols[0][i] or 0, cols[1][i], cols[2][i])
            for i in range(g)]
    assert keys == sorted(keys)


def _sorts(hlo: str) -> list:
    """The result types of the sort instructions of an HLO text."""
    return [m.group(1) for m in re.finditer(
        r"= (\([^)]*\)|\S+) sort\(", hlo)]


def _q3_tables(n_cust=64, n_ord=256, n=4000):
    return {"customer": tpch.customer_table(n_cust),
            "orders": tpch.orders_table(n_ord, n_cust),
            "lineitem": tpch.lineitem_q3_table(n, n_ord)}


def _region_hlo(plan, bindings) -> str:
    def region(b):
        res = fusion.execute(plan, b)
        return res.table, res.meta

    return jax.jit(region).lower(bindings).compile().as_text()


@pytest.fixture(params=["gathers", "sort_passes"])
def moved_by(request, monkeypatch):
    """``permute``'s two ways to move its words, at the tests' sizes: the
    one gather of k-word rows a small table takes, and (the threshold
    dropped to nothing) the sort passes a table of millions of rows takes."""
    if request.param == "sort_passes":
        monkeypatch.setattr(so, "_SORT_MOVE_MIN_WORDS", 0)
    return request.param


# orders enough that the key's declared range [1, |orders|] takes more
# than 16 bits, as the cell's 1,500,000 do: the key is grouped as a uint32
N_ORD = 70_000


def test_planned_q3_region_sorts_one_word_at_a_time(moved_by):
    """XLA's TPU compiler takes about the square of a sort's operand words
    in compile time: the result's sort (in either branch of its
    conditional) and, where the groupby's words move by sort passes, that
    loop's one are each two operands of 32 bits, in a loop; the groupby's
    key sort is ONE sort of the key narrowed to its
    declared range, the word of its null rank and row-valid bit, and a
    32-bit iota, in no loop (it was a loop of three passes that each
    gathered a word by the running order); the groupby's compaction of
    its group starts is one operand of 32 bits; none has a 64-bit
    operand."""
    n = 4000
    hlo = _region_hlo(tpch._q3_planned_plan(0, 9204),
                      _q3_tables(n_ord=N_ORD, n=n))
    sorts = _sorts(hlo)
    # (the result's sort stands twice, once a branch of its conditional:
    # ``test_planned_q3_sort_takes_the_rows_before_the_padding``)
    assert len(sorts) == (5 if moved_by == "sort_passes" else 4), sorts
    assert sorts.count(f"u32[{n}]{{0}}") == 1, sorts
    key_sort = f"(u32[{n}]{{0}}, u32[{n}]{{0}}, s32[{n}]{{0}})"
    assert sorts.count(key_sort) == 1, sorts
    for result in sorts:
        assert result == key_sort or re.fullmatch(
            r"\(u32\[\d+\]\{0\}, [us]32\[\d+\]\{0\}\)|u32\[\d+\]\{0\}",
            result), result
    assert not re.search(r"[us]64\[[^\]]*\][^=\n]* sort\(", hlo)
    for rows in (N_ORD + 1, 8192):      # all the group rows | the rung
        assert sorts.count(f"(u32[{rows}]{{0}}, s32[{rows}]{{0}})") == 1, sorts
    # the key sort and the compaction stand under the node, in no loop
    flat = [name for name in _scoped(hlo, "sort", "groupby")
            if "/while/" not in name]
    assert len(flat) == 2, flat


def _scoped(hlo: str, kind: str, node: str) -> list:
    """The op names of the ``kind`` instructions under a plan node."""
    return [name for name in re.findall(
        rf'= [^\n]*? {kind}\([^\n]*?op_name="([^"]*)"', hlo)
        if re.search(rf"region\.[^/]+/{node}/", name)]


def _sort_rows(hlo: str, node: str = "sort") -> dict:
    """branch of the node's conditional -> the rows of the sorts in it."""
    rows: dict = {}
    for dims, name in re.findall(
            r'= \([us]32\[(\d+)\][^\n]*? sort\([^\n]*?op_name="([^"]*)"', hlo):
        if re.search(rf"region\.[^/]+/{node}/", name):
            branch = re.search(r"/cond/(branch_\d)_fun/", name)
            rows.setdefault(branch and branch.group(1), []).append(int(dims))
    return rows


def test_planned_q3_sort_takes_the_rows_before_the_padding():
    """70,001 group rows are over the floor: the node lowers with ONE
    conditional, whose taken branch sorts and gathers at the rung (the
    power of two at or over a sixteenth: 8,192) and whose other branch is
    the sort of all rows; each a loop of one two-operand sort."""
    m = N_ORD + 1
    rung = so.padding_rung(
        Table([Column(t.INT32, jnp.zeros(m, jnp.int32), jnp.ones(m, bool))]),
        (0,), (False,))
    assert rung == 8192 >= so._MIN_RUNG
    hlo = _region_hlo(tpch._q3_planned_plan(0, 9204), _q3_tables(n_ord=N_ORD))
    assert len(_scoped(hlo, "conditional", "sort")) == 1
    assert _sort_rows(hlo) == {"branch_1": [rung], "branch_0": [m]}
    # a pass's word by the running order in the loop; then the four
    # columns' data and their masks (the late look-up's two share one) by
    # the sort's order: the same in either branch, at its rows
    moved = sorted([("pred", False)] * 3 + [("s32", False)] * 2
                   + [("s64", False)] * 2 + [("u32", True)])
    found = _gathers(hlo, "sort")
    for rows in (rung, m):
        assert sorted((kind, inside) for kind, dims, inside in found
                      if dims == [rows]) == moved, (rows, found)
    assert len(found) == 2 * len(moved), found


def _unmask_total(tbl: Table) -> Table:
    """The groups' sum with no validity mask: a key that is never null."""
    total = tbl.column(1)
    return Table([tbl.column(0), Column(total.dtype, jnp.where(
        total.valid_mask(), total.data, 0))])


def _padded_groups_plan(bound: int, nulls_first=(False, False),
                        fn=None) -> fusion.Plan:
    groups = fusion.GroupBy(fusion.Scan("t"), (0,), ((1, "sum"),),
                            max_groups=bound, label="groupby")
    return fusion.Plan("sorted_groups", fusion.Sort(
        groups if fn is None else fusion.Project(groups, fn), (1, 0),
        ascending=(False, True), nulls_first=nulls_first))


# what keeps a Sort out of ``padding_rung``'s gate -> the plan
_OVER = 16 * so._MIN_RUNG
OUTSIDE_THE_GATE = {
    "a_key_sorts_its_nulls_first": _padded_groups_plan(_OVER, (False, True)),
    "nulls_first_left_to_its_default": _padded_groups_plan(_OVER, None),
    "a_key_column_without_a_mask": _padded_groups_plan(
        _OVER, fn=_unmask_total),
    "a_rung_under_the_floor": _padded_groups_plan(_OVER // 2),
}


@pytest.mark.parametrize("case", list(OUTSIDE_THE_GATE))
def test_a_sort_outside_the_gate_lowers_as_it_always_has(case):
    """No ``conditional`` under the node's scope, no ``sort.prefix_sorted``
    in the result's meta; the same groups under the floor's bound and
    nulls last are inside the gate (the control)."""
    rng = np.random.default_rng(8)
    bindings = {"t": Table([
        Column(t.INT64, jnp.asarray(rng.integers(0, 300, 2000))),
        Column(t.decimal64(-2), jnp.asarray(rng.integers(-99, 99, 2000)),
               jnp.asarray(rng.random(2000) > 0.1))])}
    plan = OUTSIDE_THE_GATE[case]
    assert not _scoped(_region_hlo(plan, bindings), "conditional", "sort")
    res = fusion.execute(plan, bindings)
    assert "sort.prefix_sorted" not in res.meta
    assert fusion.meta_facts(plan, res.meta)["sort.prefix_sorted"] == 0
    if case == "a_rung_under_the_floor":
        inside = _padded_groups_plan(_OVER)
        assert len(_scoped(_region_hlo(inside, bindings),
                           "conditional", "sort")) == 1
        assert bool(fusion.execute(inside, bindings).meta[
            "sort.prefix_sorted"])


def _lone_key_table(n: int = 2000) -> Table:
    rng = np.random.default_rng(9)
    return Table([
        Column(t.INT64, jnp.asarray(rng.integers(1, 300, n)),
               jnp.asarray(rng.random(n) > 0.1)),
        Column(t.INT32, jnp.asarray(rng.integers(0, 9, n, dtype=np.int32)))])


def _order_hlo(keys) -> str:
    def order(tb, rv):
        return so._sort_order_impl(
            ((tb, rv),), None, None, keys=keys,
            ascending=(True,) * len(keys), nulls_first=(True,) * len(keys))

    tbl = _lone_key_table()
    return jax.jit(order).lower(
        tbl, jnp.ones(tbl.num_rows, bool)).compile().as_text()


def _ranged_groupby_hlo() -> str:
    plan = fusion.Plan("ranged", fusion.GroupBy(
        fusion.Scan("t"), (0,), ((1, "sum"),), max_groups=2048,
        key_ranges=((1, 300),), label="groupby"))
    return _region_hlo(plan, {"t": _lone_key_table()})


def _sort_key_words_hlo() -> str:
    tbl = _lone_key_table()
    return jax.jit(lambda tb, rv: so.sort_key_words(tb, [0], rv)).lower(
        tbl, jnp.ones(tbl.num_rows, bool)).compile().as_text()


# what keeps a sort out of the lone 64-bit key's gate -> its lowered text
OUTSIDE_THE_ONE_WORD_GATE = {
    "two_key_columns": lambda: _order_hlo((0, 1)),
    "an_int32_key": lambda: _order_hlo((1,)),
    "a_groupby_key_with_a_declared_range": _ranged_groupby_hlo,
    "sort_key_words_on_an_int64_key": _sort_key_words_hlo,
}


@pytest.mark.parametrize("case", list(OUTSIDE_THE_ONE_WORD_GATE))
def test_a_key_outside_the_one_word_gate_lowers_as_it_always_has(case):
    """No ``conditional`` anywhere in the lowered text: the gate is what a
    trace knows (one key column, 64-bit integers, no declared range), so
    every other sort is the parent's."""
    assert " conditional(" not in OUTSIDE_THE_ONE_WORD_GATE[case]()


def test_a_lone_int64_key_lowers_with_one_conditional_of_both_forms():
    """The control: ONE conditional; one branch holds one sort of two
    operands (the word, a 32-bit iota) and no loop, the other the word
    loop with its one two-operand sort; neither a 64-bit operand."""
    hlo = _order_hlo((0,))
    assert hlo.count(" conditional(") == 1
    n = _lone_key_table().num_rows
    sorts = re.findall(
        r'= (\([^)]*\)|\S+) sort\([^\n]*?op_name="([^"]*)"', hlo)
    assert sorted(kind for kind, _ in sorts) == [
        f"(u32[{n}]{{0}}, s32[{n}]{{0}})"] * 2, sorts
    in_loop = ["/while/" in name for _, name in sorts]
    assert sorted(in_loop) == [False, True], sorts
    branches = {re.search(r"/cond/(branch_\d)_fun/", name).group(1)
                for _, name in sorts}
    assert branches == {"branch_0", "branch_1"}, sorts
    # the taken form's branch gathers nothing: the word is built in place
    assert not [g for g in re.findall(
        r'gather\([^\n]*?op_name="([^"]*)"', hlo) if "/while/" not in g]


def test_planned_q3_groupby_finds_its_bounds_by_one_compaction(moved_by):
    """The bounds of the 70,001 groups come from one sort of the
    group-start mask and a slice: under the node's scope no ``while``
    steps a binary search over per-row group ids (there were two, of 24
    gathers of 1,500,001 each in the cell), the one loop left is
    ``permute``'s (the key sort's went with the key's declared range),
    and beside it stand the key sort and the one sort more."""
    hlo = _region_hlo(tpch._q3_planned_plan(0, 9204),
                      _q3_tables(n_ord=N_ORD))
    loops = _scoped(hlo, "while", "groupby")
    assert not [name for name in loops if "searchsorted" in name], loops
    assert len(loops) == (1 if moved_by == "sort_passes" else 0), loops
    assert len(_scoped(hlo, "sort", "groupby")) == len(loops) + 2


def _gathers(hlo: str, node: str | None = None) -> list:
    """(element type, dimensions but the 1s, inside a loop?) of the
    gathers' results, all of them or those under a plan node's scope."""
    out = []
    for m in re.finditer(
            r'= (\w+)\[([\d,]*)\]\S* gather\([^\n]*?op_name="([^"]*)"', hlo):
        if node is None or re.search(rf"region\.[^/]+/{node}/", m.group(3)):
            dims = sorted(int(d) for d in m.group(2).split(",") if d != "1")
            out.append((m.group(1), dims, "/while/" in m.group(3)))
    return out


def test_planned_q3_groupby_moves_no_column_it_reads_at_one_row(moved_by):
    """The groupby brings into key order the key (one word since its
    range is declared), the revenue and their bits: four words. Under the
    node's scope no gather has n rows, in a loop or out of one (the key
    sort's three went with its loop), but the one of the four packed
    words, and with sort passes none at all. ``o_orderdate`` /
    ``o_shippriority`` are no aggregates of it (PR 40): the look-up
    ``late`` reads them by the group's key at m = |orders| + 1 rows, and
    ``pk2`` gathers at the n probe rows one bit and nothing else."""
    n, m = 4000, N_ORD + 1
    hlo = _region_hlo(tpch._q3_planned_plan(0, 9204),
                      _q3_tables(n_ord=N_ORD, n=n))
    found = _gathers(hlo, "groupby")
    rows_n = [(t, dims) for t, dims, _ in found if n in dims]
    assert rows_n == ([] if moved_by == "sort_passes"
                      else [("u32", [4, n])]), rows_n
    assert not [t for t, _, _ in found if t == "s32"], found
    # the late look-up: the build key's validity and two int32 columns
    assert sorted(_gathers(hlo, "late")) == [
        ("pred", [m], False), ("s32", [m], False), ("s32", [m], False)]
    assert _gathers(hlo, "pk2") == [("pred", [n], False)]
    assert _gathers(hlo, "pk1") == [("pred", [N_ORD], False)]


def test_general_q1_sort_keeps_its_two_packed_words():
    """General q1's keys (two int8 flags, their null ranks, the row-valid
    bit: 40 bits) pack into two uint32 words sorted by one variadic sort,
    as before 64-bit keys became words. ``sort_order``'s two-word sort is
    the one ``sort_key_words`` runs, a 32-bit iota its third operand and
    not ``jnp.lexsort``'s int64 one (PR 37): no cell sorts through this
    branch without a declared key range (general q1's groupby reads
    ``sort_key_words``, its ORDER BY is one word)."""
    n = 4096
    rng = np.random.default_rng(3)
    flags = Table([Column(t.INT8, jnp.asarray(
        rng.integers(65, 70, n).astype(np.int8)), jnp.asarray(
            rng.random(n) > 0.1)) for _ in range(2)])

    def order(tb, rv):
        return so._sort_order_impl(
            ((tb, rv),), None, None, keys=(0, 1), ascending=(True, True),
            nulls_first=(True, True))[0]

    rv = jnp.asarray(rng.random(n) > 0.2)
    hlo = jax.jit(order).lower(flags, rv).compile().as_text()
    assert _sorts(hlo) == [f"(u32[{n}]{{0}}, u32[{n}]{{0}}, s32[{n}]{{0}})"]
    rows = np.asarray(order(flags, rv))
    keys = [np.asarray(c.data) for c in flags.columns]
    valid = [np.asarray(c.valid_mask()) for c in flags.columns]
    want = _oracle_order(list(zip(keys, valid)), (True, True), (True, True),
                         np.asarray(rv))
    assert rows.tolist()[:int(np.asarray(rv).sum())] == want[:int(
        np.asarray(rv).sum())]


def _mesh_region_hlo(plan, table) -> str:
    """The region as one chip of four runs it (``fusion.mesh_step`` under
    ``shard_map``): the lowering ``sf10_q1_distributed_4chip`` compiles."""
    from jax.sharding import PartitionSpec as P

    from spark_rapids_jni_tpu.parallel.mesh import EXEC_AXIS, executor_mesh

    def step(local):
        res = fusion.mesh_step(plan, {"lineitem": local}, EXEC_AXIS)
        return res.table, res.meta

    region = jax.shard_map(step, mesh=executor_mesh(4), in_specs=P(EXEC_AXIS),
                           out_specs=P(), check_vma=False)
    return jax.jit(region).lower(table).compile().as_text()


@pytest.mark.parametrize("cell", ["general", "distributed"])
def test_q1_regions_lower_as_without_the_key_ranges_field(cell):
    """The no-change side of the declared key range: the two q1 plans
    whose groupby takes the sort path and declares no range (the cells
    ``sf1_q1_general_fresh`` and, over a mesh of four,
    ``sf10_q1_distributed_4chip``). A ``GroupBy`` that declares none,
    None for every key, lowers to the text of the default, with the same
    side outputs and the same fingerprint: the cells compile what they
    compiled."""
    table = tpch.lineitem_table(4096)
    if cell == "general":
        plan = tpch._q1_plan()
        lower = lambda p: _region_hlo(p, {"lineitem": table})  # noqa: E731
    else:
        plan = tpch._q1_distributed_plan()
        lower = lambda p: _mesh_region_hlo(p, table)  # noqa: E731
    (g,) = [n for n in fusion._topo(plan.root)
            if isinstance(n, fusion.GroupBy)]
    explicit = fusion.Plan(plan.name, fusion.replace_node(
        plan.root, g, g._replace(key_ranges=(None,) * len(g.keys))))
    # lowered from one line: the text names the frames it was traced from
    assert g.key_ranges is None
    hlo, none_a_key = [lower(p) for p in (plan, explicit)]
    assert hlo == none_a_key
    bindings = {"lineitem": table}
    res = fusion.execute(explicit, bindings)
    assert not [k for k in res.meta if k.endswith(
        (".key_narrowed", ".key_out_of_range"))], sorted(res.meta)
    assert fusion.plan_fingerprint(plan, bindings) == \
        fusion.plan_fingerprint(explicit, bindings)


@pytest.mark.parametrize("bound", [tpch._Q1_GROUP_BUDGET, 2049])
def test_general_q1_groupby_keeps_its_key_sort(moved_by, bound):
    """General q1's groupby over a padded batch. Bounded at the plan's 64
    groups it finds its groups by repeated minimum (PR 42): its one sort is
    of the two packed words alone, no iota, and counts the groups past a
    broken bound inside a ``conditional``; it moves nothing (PR 33: the
    aggregates are taken where the rows lie). Bounded over the small-bound
    gate (2,049 groups) the key
    sort is ``sort_order``'s variadic sort of the same two words and, since
    PR 37, the same 32-bit iota (``(u32, u32, s64)`` with ``jnp.lexsort``'s:
    60 s of cold compile on the chip) and it moves eleven words: two int8 keys with seven
    validities and the row-valid bit in one, five int64 columns in ten. No
    column and no mask has a gather of its own."""
    from spark_rapids_jni_tpu.ops import groupby as gb

    n = 4096
    work = tpch._q1_work_table(tpch.lineitem_table(n))
    rv = jnp.arange(n) < 4000

    def groupby(tb, row_valid):
        return gb._groupby_aggregate_impl(
            ((tb, row_valid),), None, None, keys=(0, 1),
            aggs=tuple(tpch._Q1_AGGS), max_groups=bound)

    hlo = jax.jit(groupby).lower(work, rv).compile().as_text()
    sorts = _sorts(hlo)
    moved = [(t, dims) for t, dims, _ in _gathers(hlo) if n in dims]
    if bound == tpch._Q1_GROUP_BUDGET:
        assert moved == [] and sorts == [
            f"(u32[{n}]{{0}}, u32[{n}]{{0}})"], (moved, sorts)
        assert " conditional(" in hlo
        return
    assert f"(u32[{n}]{{0}}, u32[{n}]{{0}}, s32[{n}]{{0}})" in sorts, sorts
    if moved_by == "sort_passes":
        assert moved == [] and f"(u32[{n}]{{0}}, u32[{n}]{{0}})" in sorts
    else:
        assert moved == [("u32", [11, n])], moved


def test_every_plan_node_names_its_heavy_operations():
    """Each node of a region lowers under ``region.<plan>/<node scope>``:
    the sorts, the sorts' loops and the gathers of planned q3 carry the
    scope of the node they belong to."""
    plan = tpch._q3_planned_plan(0, 9204)
    nodes = fusion._topo(plan.root)
    scopes = fusion.node_scopes(nodes)
    assert [scopes[id(n)] for n in nodes] == [
        "scan.0", "project.1", "scan.2", "project.3", "scan.4", "project.5",
        "pk1", "project.7", "pk2", "project.9", "groupby", "late",
        "project.12", "sort"]
    # the staged walk of fusion.execute under an outer trace has the nodes'
    # scopes too; the served region adds its own ``region.<plan>`` above
    hlo = _region_hlo(plan, _q3_tables())
    ops = re.findall(r'= [^\n]*? (sort|while|gather)\([^\n]*?op_name="([^"]*)"',
                     hlo)
    where = {}
    for kind, name in ops:
        match = re.search(r"region\.tpch_q3_planned/([^/]+)/", name)
        where.setdefault(kind, set()).add(match.group(1) if match else None)
    assert where["sort"] == {"groupby", "sort"}
    # the loop left is the result's sort's (the groupby's key sort has
    # none since its key's range is declared, and ``permute`` moves a
    # table this small by one gather)
    assert where["while"] >= {"sort"} and None not in where["while"]
    assert where["gather"] >= {"pk1", "pk2", "groupby", "late", "sort"}
    assert None not in where["gather"]


def _serve(plan, bindings):
    with QueryServer(budget_bytes=4 << 30) as srv:
        ticket = srv.session("t").submit(plan, bindings)
        try:
            return ticket, ticket.result(), None
        except Exception as exc:   # what the client sees
            return ticket, None, exc


def test_served_pk_violation_is_a_failed_request():
    """An ``orders`` table whose keys are not 1..n in load order breaks the
    dense clustered primary key planned q3 declares: the request must not
    resolve as served at ("fused", 0, 0)."""
    tables = _q3_tables()
    before = REGISTRY.counters().get("join.pk_violation", 0)
    ticket, result, exc = _serve(tpch._q3_planned_plan(0, 9204), tables)
    assert exc is None and ticket.status == "served"
    assert REGISTRY.counters().get("join.pk_violation", 0) == before
    assert REGISTRY.counters()["join.probe_rows"] >= 256 + 4000

    orders = tables["orders"]
    keys = np.asarray(orders.column(0).data)[::-1].copy()   # n..1
    tables["orders"] = Table([Column(t.INT64, jnp.asarray(keys))]
                             + list(orders.columns[1:]))
    served = REGISTRY.counters().get("server.served", 0)
    ticket, result, exc = _serve(tpch._q3_planned_plan(0, 9204), tables)
    assert result is None and ticket.status == "failed"
    assert REGISTRY.counters().get("server.served", 0) == served
    assert isinstance(exc, resilience.FatalExecutionError)
    assert "pk_violation" in str(exc)
    # the counter counts the nodes that said so: two look the broken build
    # side up, pk2 and the late look-up of date and priority
    assert REGISTRY.counters()["join.pk_violation"] == before + 2


def test_planned_q3_late_look_up_is_no_join_of_the_query():
    """``late`` fetches the order's date and priority at the groups' rows.
    It probes with groups, not with a scan's rows, so it reports no
    ``probe_rows`` and ``join.probe_rows`` / ``join.matched_rows`` stay the
    rows of the query's two joins; every real group finds its order."""
    tables = _q3_tables()
    plan = tpch._q3_planned_plan(0, 9204)
    probed = REGISTRY.counters().get("join.probe_rows", 0)
    ticket, result, exc = _serve(plan, tables)
    assert exc is None and ticket.status == "served"
    meta = result.meta
    assert "late.probe_rows" not in meta
    assert not bool(meta["late.pk_violation"])
    facts = fusion.meta_facts(plan, meta)
    assert facts["join.probe_rows"] == 256 + 4000
    assert facts["join.matched_rows"] == int(meta["pk1.total"]) + int(
        meta["pk2.total"])
    assert int(meta["late.total"]) == int(meta["groupby.num_groups"]) - 1
    assert REGISTRY.counters()["join.probe_rows"] == probed + 256 + 4000


@pytest.mark.parametrize("cache", [True, False])
def test_served_groupby_overflow_is_a_failed_request(cache):
    """With the result cache on the meta is read inside ``cache.put``
    (nothing stays cached: the same request fails again and is no hit);
    with it off the server reads it itself."""
    from spark_rapids_jni_tpu.utils.config import reset_option, set_option

    set_option("cache.enabled", cache)
    try:
        _overflow_is_a_failed_request()
    finally:
        reset_option("cache.enabled")


def _overflow_is_a_failed_request():
    rng = np.random.default_rng(2)
    table = Table([Column(t.INT64, jnp.asarray(rng.integers(0, 50, 500))),
                   Column(t.INT64, jnp.asarray(rng.integers(0, 9, 500)))])

    def plan(bound):
        return fusion.Plan("overflow_probe", fusion.GroupBy(
            fusion.Scan("t"), (0,), ((1, "sum"),), max_groups=bound,
            label="groupby"))

    before = REGISTRY.counters().get("groupby.overflowed", 0)
    ticket, result, exc = _serve(plan(64), {"t": table})
    assert exc is None and ticket.status == "served"
    assert int(result.meta["groupby.num_groups"]) == 50
    hits = REGISTRY.counters().get("cache.hit", 0)
    with QueryServer(budget_bytes=4 << 30) as srv:
        for again in (1, 2):
            ticket = srv.session("t").submit(plan(8), {"t": table})
            with pytest.raises(resilience.CapacityOverflow):
                ticket.result()
            assert ticket.status == "failed"
            assert REGISTRY.counters()[
                "groupby.overflowed"] == before + again
    assert REGISTRY.counters().get("cache.hit", 0) == hits


def test_groups_of_bounds_planned_q3_by_its_orders():
    """``fusion.groups_of("orders")``: |orders| groups and the null group;
    with every order matched and an unmatched lineitem row the bound is
    met exactly and nothing overflows."""
    n_ord = 32
    customer = Table([Column(t.INT64, jnp.arange(1, 5, dtype=jnp.int64)),
                      Column(t.INT8, jnp.zeros(4, jnp.int8))])
    orders = Table([
        Column(t.INT64, jnp.arange(1, n_ord + 1, dtype=jnp.int64)),
        Column(t.INT64, jnp.asarray(np.arange(n_ord) % 4 + 1)),
        Column(t.TIMESTAMP_DAYS, jnp.full(n_ord, 9000, jnp.int32)),
        Column(t.INT32, jnp.zeros(n_ord, jnp.int32))])
    keys = np.r_[np.arange(1, n_ord + 1), np.arange(1, n_ord + 1)]
    ship = np.full(2 * n_ord, 9300, np.int32)
    ship[-1] = 9000      # one row the shipdate filter drops: the null group
    lineitem = Table([
        Column(t.INT64, jnp.asarray(keys)),
        Column(t.decimal64(-2), jnp.full(2 * n_ord, 100_000, jnp.int64)),
        Column(t.decimal64(-2), jnp.full(2 * n_ord, 5, jnp.int64)),
        Column(t.TIMESTAMP_DAYS, jnp.asarray(ship))])
    res = tpch.tpch_q3_planned(customer, orders, lineitem)
    assert res.result.table.num_rows == n_ord + 1
    assert int(res.result.num_groups) == n_ord + 1
    assert not bool(res.pk_violation)
    ticket, result, exc = _serve(
        tpch._q3_planned_plan(0, 9204),
        {"customer": customer, "orders": orders, "lineitem": lineitem})
    assert exc is None and ticket.status == "served"
    assert not bool(result.meta["groupby.overflowed"])
