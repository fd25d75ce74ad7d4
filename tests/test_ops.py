"""Operator substrate tests: sort, groupby-aggregate, join, xxhash64, bloom
filter — each against an independent host oracle (numpy / pure-python),
the reference's round-trip/golden-equality test shape (SURVEY.md section 4).
"""

import numpy as np
import jax.numpy as jnp
import pytest

from spark_rapids_jni_tpu import types as t
from spark_rapids_jni_tpu.columnar import Column, Table
from spark_rapids_jni_tpu.ops.sort import sort_table, sort_order, gather
from spark_rapids_jni_tpu.ops.groupby import groupby_aggregate
from spark_rapids_jni_tpu.ops.join import join, apply_join_maps
from spark_rapids_jni_tpu.ops.hash import (
    table_xxhash64,
    partition_hash,
    xxhash64_int,
    xxhash64_long,
)
from spark_rapids_jni_tpu.ops.bloom_filter import (
    BloomFilter,
    bloom_put,
    bloom_might_contain,
    bloom_merge,
)
from tests.xxh64_ref import xxh64


# ---- sort ------------------------------------------------------------------


def test_sort_single_int_key(rng):
    vals = rng.integers(-1000, 1000, 500).astype(np.int64)
    tbl = Table([Column.from_numpy(vals)])
    out = sort_table(tbl, [0])
    assert np.array_equal(np.asarray(out.column(0).data), np.sort(vals))


def test_sort_descending(rng):
    vals = rng.integers(0, 100, 200).astype(np.int32)
    tbl = Table([Column.from_numpy(vals)])
    out = sort_table(tbl, [0], ascending=[False])
    assert np.array_equal(np.asarray(out.column(0).data), np.sort(vals)[::-1])


def test_sort_multi_key_stable(rng):
    a = rng.integers(0, 5, 300).astype(np.int32)
    b = rng.integers(0, 5, 300).astype(np.int32)
    payload = np.arange(300, dtype=np.int64)
    tbl = Table([Column.from_numpy(a), Column.from_numpy(b),
                 Column.from_numpy(payload)])
    out = sort_table(tbl, [0, 1])
    oa = np.asarray(out.column(0).data)
    ob = np.asarray(out.column(1).data)
    order = np.lexsort((b, a))  # numpy: last key primary
    assert np.array_equal(oa, a[order])
    assert np.array_equal(ob, b[order])
    assert np.array_equal(np.asarray(out.column(2).data), payload[order])


def test_sort_nulls_first_and_last(rng):
    vals = np.array([5, 1, 3, 2, 4], dtype=np.int32)
    valid = np.array([True, False, True, False, True])
    tbl = Table([Column.from_numpy(vals, validity=valid)])
    first = sort_table(tbl, [0], nulls_first=[True])
    fv = np.asarray(first.column(0).valid_mask())
    assert list(fv) == [False, False, True, True, True]
    assert list(np.asarray(first.column(0).data)[2:]) == [3, 4, 5]
    last = sort_table(tbl, [0], nulls_first=[False])
    lv = np.asarray(last.column(0).valid_mask())
    assert list(lv) == [True, True, True, False, False]
    assert list(np.asarray(last.column(0).data)[:3]) == [3, 4, 5]


def test_sort_float_nan_greatest():
    vals = np.array([1.5, np.nan, -2.0, np.inf, -np.inf], dtype=np.float32)
    tbl = Table([Column.from_numpy(vals)])
    out = np.asarray(sort_table(tbl, [0]).column(0).data)
    assert np.isnan(out[-1])
    assert np.array_equal(out[:4], np.array([-np.inf, -2.0, 1.5, np.inf],
                                            dtype=np.float32))
    # descending: NaN first
    out_d = np.asarray(sort_table(tbl, [0], ascending=[False]).column(0).data)
    assert np.isnan(out_d[0])


def test_sort_f64_key():
    vals = np.array([3.5, -1.25, np.nan, 0.5], dtype=np.float64)
    tbl = Table([Column.from_numpy(vals)])
    out = np.asarray(sort_table(tbl, [0]).column(0).data)
    assert np.array_equal(out[:3], np.array([-1.25, 0.5, 3.5]))
    assert np.isnan(out[-1])


# ---- groupby ---------------------------------------------------------------


def test_groupby_sum_count_vs_numpy(rng):
    keys = rng.integers(0, 37, 2000).astype(np.int32)
    vals = rng.integers(-100, 100, 2000).astype(np.int64)
    tbl = Table([Column.from_numpy(keys), Column.from_numpy(vals)])
    res = groupby_aggregate(tbl, [0], [(1, "sum"), (1, "count"), (1, "min"),
                                       (1, "max"), (1, "mean")])
    out = res.compact()
    assert int(res.num_groups) == len(np.unique(keys))
    got_keys = np.asarray(out.column(0).data)
    assert np.array_equal(got_keys, np.unique(keys))
    for i, k in enumerate(got_keys):
        sel = vals[keys == k]
        assert np.asarray(out.column(1).data)[i] == sel.sum()
        assert np.asarray(out.column(2).data)[i] == len(sel)
        assert np.asarray(out.column(3).data)[i] == sel.min()
        assert np.asarray(out.column(4).data)[i] == sel.max()
        assert np.isclose(np.asarray(out.column(5).data)[i], sel.mean())


def test_groupby_null_values_skipped():
    keys = np.array([1, 1, 2, 2, 2], dtype=np.int32)
    vals = np.array([10, 20, 30, 40, 50], dtype=np.int64)
    vvalid = np.array([True, False, False, False, False])
    tbl = Table([Column.from_numpy(keys),
                 Column.from_numpy(vals, validity=vvalid)])
    out = groupby_aggregate(tbl, [0], [(1, "sum"), (1, "count")]).compact()
    sums = out.column(1)
    counts = out.column(2)
    assert np.asarray(sums.data)[0] == 10
    assert np.asarray(sums.valid_mask())[0]
    # group 2 all-null: sum is null, count is 0
    assert not np.asarray(sums.valid_mask())[1]
    assert list(np.asarray(counts.data)) == [1, 0]


def test_groupby_null_keys_form_group():
    keys = np.array([1, 1, 7], dtype=np.int32)
    kvalid = np.array([True, False, False])
    vals = np.array([5, 6, 7], dtype=np.int64)
    tbl = Table([Column.from_numpy(keys, validity=kvalid),
                 Column.from_numpy(vals)])
    res = groupby_aggregate(tbl, [0], [(1, "sum")])
    assert int(res.num_groups) == 2  # {1} and {null}
    out = res.compact()
    kv = np.asarray(out.column(0).valid_mask())
    sums = np.asarray(out.column(1).data)
    by_null = {bool(v): s for v, s in zip(kv, sums)}
    assert by_null[True] == 5
    assert by_null[False] == 13  # both null-key rows grouped together


def test_groupby_decimal_sum_keeps_scale():
    keys = np.array([1, 1], dtype=np.int32)
    vals = np.array([150, 250], dtype=np.int64)  # decimal64 scale -2
    tbl = Table([Column.from_numpy(keys),
                 Column.from_numpy(vals, t.decimal64(-2))])
    out = groupby_aggregate(tbl, [0], [(1, "sum")]).compact()
    assert out.column(1).dtype.scale == -2
    assert np.asarray(out.column(1).data)[0] == 400


# ---- join ------------------------------------------------------------------


def test_inner_join_vs_numpy(rng):
    lk = rng.integers(0, 50, 300).astype(np.int64)
    rk = rng.integers(0, 50, 200).astype(np.int64)
    lt = Table([Column.from_numpy(lk),
                Column.from_numpy(np.arange(300, dtype=np.int64))])
    rt = Table([Column.from_numpy(rk),
                Column.from_numpy(np.arange(200, dtype=np.int64) * 10)])
    expected = sorted(
        (i, j) for i in range(300) for j in range(200) if lk[i] == rk[j]
    )
    maps = join(lt, rt, 0, 0, out_size=len(expected) + 8)
    assert int(maps.total) == len(expected)
    got = sorted(
        (int(li), int(ri))
        for li, ri, ok in zip(
            np.asarray(maps.left_index), np.asarray(maps.right_index),
            np.asarray(maps.row_valid))
        if ok
    )
    assert got == expected


def test_left_join_unmatched_rows():
    lt = Table([Column.from_numpy(np.array([1, 2, 3], dtype=np.int64))])
    rt = Table([Column.from_numpy(np.array([2, 2], dtype=np.int64)),
                Column.from_numpy(np.array([20, 21], dtype=np.int64))])
    maps = join(lt, rt, 0, 0, out_size=8, how="left")
    assert int(maps.total) == 4  # 1->null, 2->two matches, 3->null
    out = apply_join_maps(lt, rt, maps)
    lvals = np.asarray(out.column(0).data)[np.asarray(maps.row_valid)]
    rvalid = np.asarray(out.column(2).valid_mask())[np.asarray(maps.row_valid)]
    assert sorted(lvals.tolist()) == [1, 2, 2, 3]
    assert sorted(rvalid.tolist()) == [False, False, True, True]


def test_join_null_keys_never_match():
    lk = Column.from_numpy(np.array([1, 2], dtype=np.int64),
                           validity=np.array([True, False]))
    rk = Column.from_numpy(np.array([1, 2], dtype=np.int64),
                           validity=np.array([True, False]))
    maps = join(Table([lk]), Table([rk]), 0, 0, out_size=8)
    assert int(maps.total) == 1  # only 1==1


def test_join_overflow_reports_total():
    lt = Table([Column.from_numpy(np.zeros(4, dtype=np.int64))])
    rt = Table([Column.from_numpy(np.zeros(4, dtype=np.int64))])
    maps = join(lt, rt, 0, 0, out_size=5)
    assert int(maps.total) == 16  # caller can detect truncation
    assert int(np.asarray(maps.row_valid).sum()) == 5


# ---- xxhash64 --------------------------------------------------------------


def test_xxhash64_long_matches_reference(rng):
    vals = rng.integers(-(2**62), 2**62, 64).astype(np.int64)
    seeds = rng.integers(0, 2**63, 64).astype(np.uint64)
    got = np.asarray(xxhash64_long(jnp.asarray(vals), jnp.asarray(seeds)))
    for v, s, g in zip(vals, seeds, got):
        want = xxh64(int(np.uint64(v)).to_bytes(8, "little"), int(s))
        assert int(np.uint64(g)) == want


def test_xxhash64_int_matches_reference(rng):
    vals = rng.integers(-(2**31), 2**31, 64).astype(np.int32)
    seeds = rng.integers(0, 2**63, 64).astype(np.uint64)
    got = np.asarray(xxhash64_int(jnp.asarray(vals), jnp.asarray(seeds)))
    for v, s, g in zip(vals, seeds, got):
        want = xxh64(int(np.uint32(v)).to_bytes(4, "little"), int(s))
        assert int(np.uint64(g)) == want


def test_table_hash_null_passthrough():
    c1 = Column.from_numpy(np.array([7, 7], dtype=np.int64),
                           validity=np.array([True, False]))
    tbl = Table([c1])
    h = np.asarray(table_xxhash64(tbl))
    want0 = xxh64((7).to_bytes(8, "little"), 42)
    assert int(np.uint64(h[0])) == want0
    assert int(np.uint64(h[1])) == 42  # null: seed passes through


def test_table_hash_chains_columns():
    tbl = Table([
        Column.from_numpy(np.array([3], dtype=np.int64)),
        Column.from_numpy(np.array([9], dtype=np.int32)),
    ])
    h = np.asarray(table_xxhash64(tbl))
    step1 = xxh64((3).to_bytes(8, "little"), 42)
    step2 = xxh64((9).to_bytes(4, "little"), step1)
    assert int(np.uint64(h[0])) == step2


def test_partition_hash_range(rng):
    tbl = Table([Column.from_numpy(rng.integers(0, 10**9, 1000))])
    parts = np.asarray(partition_hash(tbl, [0], 16))
    assert parts.min() >= 0 and parts.max() < 16
    # roughly uniform
    counts = np.bincount(parts, minlength=16)
    assert counts.min() > 20


# ---- bloom filter ----------------------------------------------------------


def test_bloom_no_false_negatives(rng):
    items = rng.integers(0, 2**60, 5000).astype(np.int64)
    bf = BloomFilter.optimal(len(items), fpp=0.03)
    bf = bloom_put(bf, jnp.asarray(items))
    hit = np.asarray(bloom_might_contain(bf, jnp.asarray(items)))
    assert hit.all()


def test_bloom_fpp_reasonable(rng):
    items = rng.integers(0, 2**60, 5000).astype(np.int64)
    others = rng.integers(2**61, 2**62, 5000).astype(np.int64)
    bf = BloomFilter.optimal(len(items), fpp=0.03)
    bf = bloom_put(bf, jnp.asarray(items))
    fp = np.asarray(bloom_might_contain(bf, jnp.asarray(others))).mean()
    assert fp < 0.08


def test_bloom_null_values_skipped():
    bf = BloomFilter.empty(1024, 3)
    vals = jnp.asarray(np.array([5, 6], dtype=np.int64))
    bf = bloom_put(bf, vals, valid=jnp.asarray([True, False]))
    got = np.asarray(bloom_might_contain(bf, vals))
    assert got[0]
    assert not got[1]


def test_bloom_merge_union(rng):
    a_items = rng.integers(0, 2**40, 100).astype(np.int64)
    b_items = rng.integers(2**41, 2**42, 100).astype(np.int64)
    a = bloom_put(BloomFilter.empty(8192, 3), jnp.asarray(a_items))
    b = bloom_put(BloomFilter.empty(8192, 3), jnp.asarray(b_items))
    m = bloom_merge(a, b)
    assert np.asarray(bloom_might_contain(m, jnp.asarray(a_items))).all()
    assert np.asarray(bloom_might_contain(m, jnp.asarray(b_items))).all()


def test_bloom_packed_round_trip(rng):
    items = rng.integers(0, 2**40, 50).astype(np.int64)
    bf = bloom_put(BloomFilter.empty(512, 3), jnp.asarray(items))
    packed = bf.to_packed()
    assert packed.shape[0] == 64
    back = BloomFilter.from_packed(packed, 512, 3)
    assert np.array_equal(np.asarray(back.bits), np.asarray(bf.bits))


def test_murmur3_hash_long_matches_java_oracle():
    """Vectorized Murmur3_x86_32.hashLong vs a plain-int transcription of the
    Java algorithm (Spark util.sketch / Guava hashLong)."""
    from spark_rapids_jni_tpu.ops.bloom_filter import murmur3_hash_long

    M = 0xFFFFFFFF

    def oracle(v: int, seed: int) -> int:
        def rotl(x, r):
            return ((x << r) | (x >> (32 - r))) & M

        low, high = v & M, (v >> 32) & M  # two's-complement uint64 view
        h1 = seed & M
        for w in (low, high):
            k1 = (rotl((w * 0xCC9E2D51) & M, 15) * 0x1B873593) & M
            h1 = ((rotl(h1 ^ k1, 13) * 5) + 0xE6546B64) & M
        h1 ^= 8
        h1 = ((h1 ^ (h1 >> 16)) * 0x85EBCA6B) & M
        h1 = ((h1 ^ (h1 >> 13)) * 0xC2B2AE35) & M
        return h1 ^ (h1 >> 16)

    vals = [0, 1, -1, 42, -42, 2**62, -(2**62), 0x123456789ABCDEF]
    got = np.asarray(
        murmur3_hash_long(jnp.asarray(np.array(vals, dtype=np.int64)), 0)
    )
    for i, v in enumerate(vals):
        assert int(got[i]) == oracle(v & 0xFFFFFFFFFFFFFFFF, 0), v
    # seeded variant (h2 = hashLong(item, h1))
    got_seeded = np.asarray(
        murmur3_hash_long(
            jnp.asarray(np.array(vals, dtype=np.int64)), np.uint32(7)
        )
    )
    for i, v in enumerate(vals):
        assert int(got_seeded[i]) == oracle(v & 0xFFFFFFFFFFFFFFFF, 7), v


def test_bloom_bit_positions_match_spark_impl():
    """Bit indexes replicate BloomFilterImpl.putLong: i=1..k, signed int32
    combine, bitwise-NOT on negative, mod bitSize."""
    from spark_rapids_jni_tpu.ops.bloom_filter import (
        _bit_positions,
        murmur3_hash_long,
    )

    vals = np.array([0, 1, -1, 99, 2**50], dtype=np.int64)
    m, k = 65536, 5
    got = np.asarray(_bit_positions(jnp.asarray(vals), m, k))
    h1 = np.asarray(murmur3_hash_long(jnp.asarray(vals), 0)).astype(np.int64)
    h2 = np.asarray(
        murmur3_hash_long(jnp.asarray(vals), jnp.asarray(h1, dtype=jnp.uint32))
    ).astype(np.int64)
    for r in range(len(vals)):
        for i in range(1, k + 1):
            c = (h1[r] + i * h2[r]) & 0xFFFFFFFF
            if c >= 2**31:  # negative as int32
                c = (~c) & 0xFFFFFFFF  # Java ~ on int32
                c &= 0x7FFFFFFF
            assert got[r, i - 1] == c % m


def test_bloom_spark_prehash_wrappers(rng):
    from spark_rapids_jni_tpu.ops.bloom_filter import (
        bloom_might_contain_spark,
        bloom_put_spark,
        spark_prehash,
    )
    from tests.xxh64_ref import xxh64

    items = rng.integers(-(2**60), 2**60, 100).astype(np.int64)
    # prehash == xxhash64(8-byte LE value, seed 42)
    ph = np.asarray(spark_prehash(jnp.asarray(items)))
    for v in items[:5]:
        want = xxh64(int(np.uint64(np.int64(v))).to_bytes(8, "little"), 42)
        assert int(np.uint64(ph[list(items).index(v)])) == want
    bf = BloomFilter.optimal(len(items), fpp=0.03)
    bf = bloom_put_spark(bf, jnp.asarray(items))
    assert np.asarray(bloom_might_contain_spark(bf, jnp.asarray(items))).all()


def test_sort_float32_negative_nan_greatest():
    """Both NaN signs sort greatest (Spark order) and form ONE group."""
    from spark_rapids_jni_tpu.ops.sort import sort_table

    neg_nan = np.frombuffer(np.uint32(0xFFC00000).tobytes(), dtype=np.float32)[0]
    vals = np.array([1.5, neg_nan, -2.0, np.nan, 7.0], dtype=np.float32)
    tbl = Table([Column.from_numpy(vals, t.FLOAT32)])
    out = np.asarray(sort_table(tbl, [0]).column(0).data)
    assert np.array_equal(out[:3], np.array([-2.0, 1.5, 7.0], dtype=np.float32))
    assert np.isnan(out[3]) and np.isnan(out[4])

    res = groupby_aggregate(tbl, keys=[0], aggs=[(0, "count")])
    assert int(res.num_groups) == 4  # -2, 1.5, 7, one unified NaN group


# ---- small-m boundary path (blocked group starts + boundary prefix) --------


def _groupby_tables_equal(a, b):
    assert a.num_columns == b.num_columns
    for i in range(a.num_columns):
        ca, cb = a.column(i), b.column(i)
        va, vb = np.asarray(ca.valid_mask()), np.asarray(cb.valid_mask())
        assert np.array_equal(va, vb), f"col {i} validity"
        da, db = np.asarray(ca.data), np.asarray(cb.data)
        if da.dtype.kind == "f":
            # float lanes sum in an unspecified parallel order, which
            # differs between the blocked-boundary and scan paths (int
            # lanes stay bit-exact in both)
            assert np.allclose(
                da[va], db[vb], rtol=1e-9, atol=0), f"col {i} data"
        else:
            assert np.array_equal(da[va], db[vb]), f"col {i} data"


def test_groupby_small_m_matches_default_path(rng):
    # n deliberately not a multiple of the block size; spans >1 block
    n = 4000
    k1 = rng.integers(0, 5, n).astype(np.int8)
    k2 = rng.integers(0, 3, n).astype(np.int8)
    kvalid = rng.random(n) > 0.05
    vals = rng.integers(-1000, 1000, n).astype(np.int64)
    vvalid = rng.random(n) > 0.2
    fvals = rng.normal(size=n)
    tbl = Table([
        Column.from_numpy(k1, validity=kvalid),
        Column.from_numpy(k2),
        Column.from_numpy(vals, validity=vvalid),
        Column.from_numpy(fvals),
    ])
    aggs = [(2, "sum"), (2, "count"), (2, "mean"), (2, "min"), (2, "max"),
            (3, "sum")]
    # max_groups=32 passes the blocked-boundary gate (2*32*32 <= 4000);
    # max_groups=None (m=n=4000 > _SMALL_M) takes the scan path
    fast = groupby_aggregate(tbl, [0, 1], aggs, max_groups=32)
    slow = groupby_aggregate(tbl, [0, 1], aggs)
    assert int(fast.num_groups) == int(slow.num_groups)
    assert not bool(fast.overflowed)
    _groupby_tables_equal(fast.compact(), slow.compact())


def test_groupby_small_m_exact_fit_and_overflow(rng):
    n = 700  # > one block, < two
    keys = rng.integers(0, 10, n).astype(np.int32)
    vals = rng.integers(0, 100, n).astype(np.int64)
    tbl = Table([Column.from_numpy(keys), Column.from_numpy(vals)])
    true_k = len(np.unique(keys))
    exact = groupby_aggregate(tbl, [0], [(1, "sum")], max_groups=true_k)
    assert not bool(exact.overflowed)
    assert int(exact.num_groups) == true_k
    over = groupby_aggregate(tbl, [0], [(1, "sum")], max_groups=true_k - 1)
    assert bool(over.overflowed)
    # overflow still computes the exact total and the first m groups exactly
    assert int(over.num_groups) == true_k
    uniq = np.unique(keys)
    got = np.asarray(over.table.column(0).data)[: true_k - 1]
    assert np.array_equal(got, uniq[: true_k - 1])
    want = [vals[keys == u].sum() for u in uniq[: true_k - 1]]
    assert np.array_equal(
        np.asarray(over.table.column(1).data)[: true_k - 1], want
    )


def test_groupby_small_m_group_spanning_blocks():
    # one giant group crossing many blocks + a tiny one at the end: the
    # boundary-prefix path must sum across full blocks + a partial block
    from spark_rapids_jni_tpu.ops.groupby import _MAX_BLOCK

    n = 3 * _MAX_BLOCK + 17
    keys = np.zeros(n, dtype=np.int32)
    keys[-5:] = 9
    vals = np.arange(n, dtype=np.int64)
    tbl = Table([Column.from_numpy(keys), Column.from_numpy(vals)])
    res = groupby_aggregate(tbl, [0], [(1, "sum"), (1, "count")],
                            max_groups=4)
    out = res.compact()
    assert int(res.num_groups) == 2
    assert list(np.asarray(out.column(1).data)) == [
        int(vals[:-5].sum()), int(vals[-5:].sum())
    ]
    assert list(np.asarray(out.column(2).data)) == [n - 5, 5]


def test_sort_packed_key_matches_multikey(rng):
    # two int8 keys + null ranks pack into one uint32 argsort; verify the
    # permutation matches numpy's stable lexsort on the same keys
    n = 513
    k1 = rng.integers(-3, 3, n).astype(np.int8)
    k2 = rng.integers(0, 4, n).astype(np.int8)
    valid = rng.random(n) > 0.1
    tbl = Table([Column.from_numpy(k1, validity=valid),
                 Column.from_numpy(k2)])
    order = np.asarray(sort_order(tbl, [0, 1]))
    # numpy oracle mirroring the key encoding: null rank most significant
    # (nulls first), then the k1 value key with null rows forced to one
    # constant (they tie and fall through to k2), then k2; stable
    k1_masked = np.where(valid, k1, np.int8(0))
    oracle = np.lexsort((k2, k1_masked, valid.astype(np.int8)))
    assert np.array_equal(order, oracle)


def test_sort_packed_key_32bit_primary_with_nulls(rng):
    # regression: [int32 key, int8 key] produces a 40-bit high run (uint32
    # value + uint8 null rank) that must NOT be folded into one uint32 —
    # doing so drops the primary null rank and interleaves null rows by
    # their stored garbage values
    n = 400
    k1 = rng.integers(-(2**31), 2**31 - 1, n).astype(np.int32)
    k2 = rng.integers(0, 5, n).astype(np.int8)
    valid = rng.random(n) > 0.3
    tbl = Table([Column.from_numpy(k1, validity=valid),
                 Column.from_numpy(k2)])
    order = np.asarray(sort_order(tbl, [0, 1]))
    sv = valid[order]
    # nulls first (default): all null rows precede all valid rows
    assert not np.any(np.diff(sv.astype(np.int8)) < 0) or sv[0] == False  # noqa: E712
    nnull = int((~valid).sum())
    assert not sv[:nnull].any() and sv[nnull:].all()
    # valid rows ordered by k1 then k2
    vk1 = k1[order][nnull:]
    assert np.all(np.diff(vk1.astype(np.int64)) >= 0)


def test_groupby_null_keys_with_garbage_storage_form_one_group(rng):
    # regression: null cells carry unspecified stored bytes; rows with
    # DIFFERENT garbage under null keys must still form ONE null group
    # (the sort masks null value keys to a constant — without that, later
    # sort keys reset between garbage clusters and the null group splits)
    n = 200
    keys = rng.integers(-(10**9), 10**9, n).astype(np.int64)  # garbage
    valid = rng.random(n) > 0.5
    sub = rng.integers(0, 3, n).astype(np.int8)  # secondary key
    vals = rng.integers(0, 100, n).astype(np.int64)
    tbl = Table([Column.from_numpy(keys, validity=valid),
                 Column.from_numpy(sub),
                 Column.from_numpy(vals)])
    res = groupby_aggregate(tbl, [0, 1], [(2, "sum"), (2, "count")])
    want = {}
    for k, ok, sb, v in zip(keys, valid, sub, vals):
        kk = (int(k) if ok else None, int(sb))
        want[kk] = want.get(kk, 0) + int(v)
    assert int(res.num_groups) == len(want)
    out = res.compact()
    c0, c1, c2 = (out.column(i).to_pylist() for i in range(3))
    got = {(c0[i], c1[i]): c2[i] for i in range(out.num_rows)}
    assert got == want


def test_groupby_var_std_vs_numpy(rng):
    keys = rng.integers(0, 9, 1500).astype(np.int32)
    vals = rng.normal(scale=50, size=1500)
    vvalid = rng.random(1500) > 0.2
    tbl = Table([Column.from_numpy(keys),
                 Column.from_numpy(vals, validity=vvalid)])
    out = groupby_aggregate(
        tbl, [0], [(1, "var"), (1, "std"), (1, "count")]).compact()
    got_k = np.asarray(out.column(0).data)
    for i, k in enumerate(got_k):
        sel = vals[(keys == k) & vvalid]
        if len(sel) >= 2:
            assert np.isclose(np.asarray(out.column(1).data)[i],
                              sel.var(ddof=1), rtol=1e-5)
            assert np.isclose(np.asarray(out.column(2).data)[i],
                              sel.std(ddof=1), rtol=1e-5)
        else:
            assert not np.asarray(out.column(1).valid_mask())[i]


def test_groupby_var_decimal_rescales():
    keys = np.zeros(4, np.int32)
    vals = np.array([100, 200, 300, 400], np.int64)  # 1.00..4.00 @ scale -2
    tbl = Table([Column.from_numpy(keys),
                 Column.from_numpy(vals, t.decimal64(-2))])
    out = groupby_aggregate(tbl, [0], [(1, "var")]).compact()
    want = np.array([1.0, 2.0, 3.0, 4.0]).var(ddof=1)
    assert np.isclose(np.asarray(out.column(1).data)[0], want, rtol=1e-6)


def test_groupby_nunique_vs_python(rng):
    n = 1200
    keys = rng.integers(0, 7, n).astype(np.int64)
    vals = rng.integers(0, 15, n).astype(np.int32)
    vvalid = rng.random(n) > 0.25
    tbl = Table([Column.from_numpy(keys),
                 Column.from_numpy(vals, validity=vvalid)])
    out = groupby_aggregate(tbl, [0], [(1, "nunique")]).compact()
    got = dict(zip(out.column(0).to_pylist(), out.column(1).to_pylist()))
    want = {}
    for k, v, ok in zip(keys.tolist(), vals.tolist(), vvalid):
        want.setdefault(k, set())
        if ok:
            want[k].add(v)
    assert got == {k: len(s) for k, s in want.items()}


def test_groupby_nunique_strings(rng):
    keys = np.array([1, 1, 1, 2, 2, 2, 2], np.int32)
    svals = ["a", "bb", "a", None, "x", "x", "y"]
    tbl = Table([Column.from_numpy(keys),
                 Column.from_pylist(svals, t.STRING)])
    from spark_rapids_jni_tpu.ops.strings import pad_strings

    cols = list(tbl.columns)
    cols[1] = pad_strings(cols[1])
    out = groupby_aggregate(Table(cols), [0], [(1, "nunique")]).compact()
    assert out.column(1).to_pylist() == [2, 2]


def test_groupby_var_rejects_strings():
    tbl = Table([Column.from_numpy(np.zeros(3, np.int32)),
                 Column.from_pylist(["a", "b", "c"], t.STRING)])
    with pytest.raises(TypeError, match="numeric"):
        groupby_aggregate(tbl, [0], [(1, "var")])


def test_groupby_and_q1_compile_scatter_free():
    """VERDICT r3 item 9: every aggregate (incl. var/std, float mean,
    nunique, numeric and string min/max) and the full q1 plan must lower
    with ZERO scatter instructions — scatters serialize on the TPU
    (1.6-4x behind the scan forms on a v5e in 2026-07; not measured
    since). `.at[static_slice].set`
    lowers to pad/dynamic-update-slice, which is fine; this counts real
    scatter HLO ops."""
    import re

    import jax

    from spark_rapids_jni_tpu.models.tpch import lineitem_table, tpch_q1
    from spark_rapids_jni_tpu.ops.strings import pad_strings

    def real_scatters(hlo):
        # ' scatter(' also catches variadic scatters whose result type is
        # a spaced tuple, which '\\S+' would miss
        return [ln for ln in hlo.splitlines() if " scatter(" in ln]

    tbl = Table([
        Column.from_pylist([1, 2, 1, 3] * 64, t.INT64),
        Column.from_pylist([1.5, 2.5, 3.5, 4.5] * 64, t.FLOAT64),
        Column.from_pylist([10, 20, 30, 40] * 64, t.INT32),
        pad_strings(Column.from_pylist(["a", "bb", "a", "c"] * 64, t.STRING)),
    ])

    def g(tb):
        r = groupby_aggregate(
            tb, [0],
            [(1, "sum"), (1, "mean"), (1, "var"), (1, "std"), (2, "min"),
             (2, "max"), (2, "nunique"), (1, "count"), (3, "min"),
             (3, "max"), (1, "first"), (3, "last")])
        out = jnp.float64(0)
        for c in r.table.columns:
            out = out + jnp.sum(c.data).astype(jnp.float64)
            if c.chars is not None:
                out = out + jnp.sum(c.chars)
        return out + r.num_groups

    hlo = jax.jit(g).lower(tbl).compile().as_text()
    assert real_scatters(hlo) == []

    li = lineitem_table(2048)

    def q1_digest(tb):
        out = tpch_q1(tb)
        return sum(jnp.sum(c.data).astype(jnp.float64)
                   + jnp.sum(c.valid_mask()) for c in out.columns)

    hlo_q1 = jax.jit(q1_digest).lower(li).compile().as_text()
    assert real_scatters(hlo_q1) == []


def test_groupby_float_small_group_after_large_group():
    """Float group sums must be accurate to each group's OWN magnitude: a
    tiny group following a huge one would vanish entirely under global
    prefix differencing (the segmented-scan path prevents that)."""
    keys = np.array([1] * 1000 + [2] * 4, dtype=np.int32)
    vals = np.concatenate([
        np.full(1000, 1e12), np.full(4, 1e-3)]).astype(np.float64)
    tbl = Table([Column.from_numpy(keys), Column.from_numpy(vals)])
    out = groupby_aggregate(
        tbl, [0], [(1, "sum"), (1, "mean"), (1, "var")]).compact()
    sums = np.asarray(out.column(1).data)
    means = np.asarray(out.column(2).data)
    assert np.isclose(sums[0], 1e15, rtol=1e-12)
    assert np.isclose(sums[1], 4e-3, rtol=1e-12), sums[1]
    assert np.isclose(means[1], 1e-3, rtol=1e-12)
    # variance of a constant group is 0 (to the group's own magnitude)
    var = np.asarray(out.column(3).data)
    assert abs(var[1]) < 1e-18


def test_empty_table_groupby_every_agg():
    """n == 0 must trace and run for EVERY aggregate (the scatter-free
    nunique path once crashed here)."""
    tbl = Table([
        Column.from_numpy(np.zeros(0, dtype=np.int64)),
        Column.from_numpy(np.zeros(0, dtype=np.float64)),
    ])
    res = groupby_aggregate(
        tbl, [0],
        [(1, "sum"), (1, "count"), (1, "mean"), (1, "min"), (1, "max"),
         (1, "var"), (1, "std"), (1, "nunique")],
        max_groups=4)
    assert int(res.num_groups) == 0
    for c in res.table.columns:
        assert not np.asarray(c.valid_mask()).any()


def test_groupby_first_last_vs_oracle(rng):
    """first/last (ignoreNulls semantics) across int, string, and
    DECIMAL128 columns — input order within each group is preserved by
    the stable key sort."""
    n = 500
    keys = [int(v) for v in rng.integers(0, 11, n)]
    ints = [int(v) if rng.random() > 0.3 else None
            for v in rng.integers(-99, 99, n)]
    strs = [f"s{v}" if rng.random() > 0.3 else None
            for v in rng.integers(0, 50, n)]
    wide = [((1 << 80) + int(v)) if rng.random() > 0.3 else None
            for v in rng.integers(0, 1000, n)]
    tbl = Table([
        Column.from_pylist(keys, t.INT64),
        Column.from_pylist(ints, t.INT32),
        Column.from_pylist(strs, t.STRING),
        Column.from_pylist(wide, t.decimal128(0)),
    ])
    res = groupby_aggregate(
        tbl, [0],
        [(1, "first"), (1, "last"), (2, "first"), (2, "last"),
         (3, "first"), (3, "last")])
    out = res.compact()
    gk = out.column(0).to_pylist()
    for i, k in enumerate(gk):
        for col_idx, vals, out_first, out_last in (
                (1, ints, 1, 2), (2, strs, 3, 4), (3, wide, 5, 6)):
            seq = [v for kk, v in zip(keys, vals)
                   if kk == k and v is not None]
            want_first = seq[0] if seq else None
            want_last = seq[-1] if seq else None
            assert out.column(out_first).to_pylist()[i] == want_first, (
                k, col_idx, "first")
            assert out.column(out_last).to_pylist()[i] == want_last, (
                k, col_idx, "last")


def test_groupby_first_last_include_nulls():
    """*_include_nulls = Spark's DEFAULT First/Last (ignoreNulls=false):
    the group's first/last ROW, null result when that row's value is
    null."""
    keys = [1, 1, 2, 2]
    vals = [None, 5, 7, None]
    tbl = Table([
        Column.from_pylist(keys, t.INT64),
        Column.from_pylist(vals, t.INT64),
    ])
    out = groupby_aggregate(
        tbl, [0],
        [(1, "first_include_nulls"), (1, "last_include_nulls"),
         (1, "first"), (1, "last")]).compact()
    assert out.column(1).to_pylist() == [None, 7]   # first row as-is
    assert out.column(2).to_pylist() == [5, None]   # last row as-is
    assert out.column(3).to_pylist() == [5, 7]      # first non-null
    assert out.column(4).to_pylist() == [5, 7]      # last non-null


def test_groupby_percentile_vs_numpy(rng):
    """Exact percentiles (linear interpolation) vs numpy.percentile per
    group, with null keys and null values."""
    from spark_rapids_jni_tpu.ops.groupby import groupby_percentile

    n = 400
    keys = rng.integers(0, 11, n).astype(np.int64)
    kvalid = rng.random(n) > 0.1
    vals = rng.integers(-500, 500, n).astype(np.int64)
    vvalid = rng.random(n) > 0.2
    tbl = Table([
        Column.from_numpy(keys, validity=kvalid),
        Column.from_numpy(vals, validity=vvalid),
    ])
    qs = [0.0, 0.25, 0.5, 0.9, 1.0]
    res = groupby_percentile(tbl, [0], 1, qs)
    out = res.compact()
    got_keys = out.column(0).to_pylist()
    groups = {}
    for i in range(n):
        k = int(keys[i]) if kvalid[i] else None
        if vvalid[i]:
            groups.setdefault(k, []).append(int(vals[i]))
        else:
            groups.setdefault(k, [])
    assert sorted(got_keys, key=lambda x: (x is None, x)) == sorted(
        groups, key=lambda x: (x is None, x))
    for r, k in enumerate(got_keys):
        sel = groups[k]
        for qi, q in enumerate(qs):
            got = out.column(1 + qi).to_pylist()[r]
            if not sel:
                assert got is None, (k, q)
            else:
                assert got == pytest.approx(
                    float(np.percentile(sel, q * 100))), (k, q)


def test_groupby_percentile_median_decimal_and_errors():
    from spark_rapids_jni_tpu.ops.groupby import groupby_percentile

    # DECIMAL64 scale -2: 1.50, 3.00, 2.25 -> median 2.25
    d = [150, 300, 225]
    tbl = Table([
        Column.from_pylist([1, 1, 1], t.INT64),
        Column.from_pylist(d, t.DType(t.TypeId.DECIMAL64, scale=-2)),
    ])
    res = groupby_percentile(tbl, [0], 1, [0.5])
    assert res.compact().column(1).to_pylist() == [pytest.approx(2.25)]
    with pytest.raises(ValueError):
        groupby_percentile(tbl, [0], 1, [1.5])
    with pytest.raises(ValueError):
        groupby_percentile(tbl, [0], 1, [])
    s = Table([Column.from_pylist([1], t.INT64),
               Column.from_pylist(["x"], t.STRING)])
    with pytest.raises(NotImplementedError):
        groupby_percentile(s, [0], 1, [0.5])


def test_groupby_var_pop_std_pop_vs_numpy(rng):
    """Population variants (Spark var_pop/stddev_pop): denominator n, and
    singleton groups are 0.0 (valid), not null — only empty/all-null
    groups are null."""
    keys = rng.integers(0, 8, 900).astype(np.int32)
    keys[0] = 99  # guaranteed singleton group
    vals = rng.normal(scale=12, size=900)
    vvalid = rng.random(900) > 0.2
    vvalid[0] = True
    tbl = Table([Column.from_numpy(keys),
                 Column.from_numpy(vals, validity=vvalid)])
    out = groupby_aggregate(
        tbl, [0], [(1, "var_pop"), (1, "std_pop")]).compact()
    got_k = np.asarray(out.column(0).data)
    for i, k in enumerate(got_k):
        sel = vals[(keys == k) & vvalid]
        if len(sel) >= 1:
            assert np.isclose(np.asarray(out.column(1).data)[i],
                              sel.var(ddof=0), rtol=1e-5, atol=1e-12), k
            assert np.isclose(np.asarray(out.column(2).data)[i],
                              sel.std(ddof=0), rtol=1e-5, atol=1e-12), k
            assert bool(np.asarray(out.column(1).valid_mask())[i])
        else:
            assert not np.asarray(out.column(1).valid_mask())[i]


def test_groupby_covar_corr_vs_numpy(rng):
    """covar_samp/covar_pop/corr two-column aggregates: Spark counts only
    rows where BOTH operands are non-null; corr of a constant series is
    NaN (0/0), empty groups null."""
    n = 1100
    keys = rng.integers(0, 7, n).astype(np.int64)
    x = rng.normal(size=n) * 3.0
    y = 0.6 * x + rng.normal(size=n)
    xv = rng.random(n) > 0.15
    yv = rng.random(n) > 0.15
    tbl = Table([Column.from_numpy(keys),
                 Column.from_numpy(x, validity=xv),
                 Column.from_numpy(y, validity=yv)])
    out = groupby_aggregate(tbl, [0], [
        (1, ("covar_samp", 2)), (1, ("covar_pop", 2)), (1, ("corr", 2)),
    ]).compact()
    got_k = np.asarray(out.column(0).data)
    for i, k in enumerate(got_k):
        sel = (keys == k) & xv & yv
        xs, ys = x[sel], y[sel]
        m = len(xs)
        cpop = float(np.mean((xs - xs.mean()) * (ys - ys.mean()))) if m \
            else None
        if m > 1:
            assert np.isclose(np.asarray(out.column(1).data)[i],
                              float(np.cov(xs, ys, ddof=1)[0, 1]),
                              rtol=1e-5), k
            assert np.isclose(np.asarray(out.column(3).data)[i],
                              float(np.corrcoef(xs, ys)[0, 1]),
                              rtol=1e-5), k
        else:
            assert not np.asarray(out.column(1).valid_mask())[i]
        if m >= 1:
            assert np.isclose(np.asarray(out.column(2).data)[i], cpop,
                              rtol=1e-5, atol=1e-12), k
        else:
            assert not np.asarray(out.column(2).valid_mask())[i]


def test_groupby_corr_constant_series_nan():
    tbl = Table([Column.from_numpy(np.zeros(3, np.int32)),
                 Column.from_numpy(np.array([5.0, 5.0, 5.0])),
                 Column.from_numpy(np.array([1.0, 2.0, 3.0]))])
    out = groupby_aggregate(tbl, [0], [(1, ("corr", 2))]).compact()
    assert bool(np.asarray(out.column(1).valid_mask())[0])
    assert np.isnan(np.asarray(out.column(1).data)[0])


def test_groupby_binary_agg_validation():
    tbl = Table([Column.from_numpy(np.zeros(2, np.int32)),
                 Column.from_numpy(np.ones(2, np.int64)),
                 Column.from_pylist(["a", "b"], t.STRING)])
    with pytest.raises(ValueError, match="binary"):
        groupby_aggregate(tbl, [0], [(1, ("cov", 1))])
    with pytest.raises(ValueError, match="binary"):
        groupby_aggregate(tbl, [0], [(1, ("corr", -1))])  # no wraparound
    with pytest.raises(ValueError, match="binary"):
        groupby_aggregate(tbl, [0], [(1, ("corr", 3))])   # out of range
    with pytest.raises(TypeError, match="numeric"):
        groupby_aggregate(tbl, [0], [(1, ("corr", 2))])
