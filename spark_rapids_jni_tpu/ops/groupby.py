"""Hash-groupby-aggregate equivalent (cuDF groupby is part of the vendored
capability surface, SURVEY.md section 2.2; TPC-H q1 is the canonical
workload, BASELINE.json config #3).

TPU-first design: no device hash table (no CUDA-style concurrent hash map
idiom on the VPU — SURVEY.md section 7 "hard parts" calls this out). Instead
sort-based grouping: stable-sort rows by the encoded keys, bring the words
the aggregates read at every row into that order (``ops/sort.py permute``:
packed 32-bit words moved once; what is read at one row a group stays where
it is), mark segment boundaries, and reduce between them with prefix sums
and segmented scans, no scatter — all static-shape, all fused by XLA.
Under a small group bound whose aggregates are sums, counts and extrema
(``_aggregates_in_place``) no value word moves: the rows stay where they
lie and are summed by slot (``_KeySlots``), the integer lanes on the MXU;
the groups come from a sort of the key words alone or, bounded at
``_MIN_LOOP_M`` or fewer, from no sort at all (``_least_groups``: repeated
minimum over the words, a step a group the data holds). Output is padded
to the input row count with ``num_groups`` reported alongside (static
shapes are the price of jit; callers slice on host).

Null semantics are Spark's: null keys form their own group; aggregates skip
null values; COUNT counts non-null; an all-null group's SUM/MIN/MAX/MEAN is
null.
"""

from __future__ import annotations

import contextlib
import numbers
from functools import partial
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from spark_rapids_jni_tpu.columnar import Column, Table
from spark_rapids_jni_tpu.ops.sort import (key_words, permute,
                                            sort_key_words, sort_order,
                                            sort_order_and_form,
                                            words_in_order)
from spark_rapids_jni_tpu.types import DType, TypeId, decimal128
from spark_rapids_jni_tpu.utils.tracing import func_range

SUPPORTED_AGGS = ("sum", "count", "min", "max", "mean", "var", "std",
                  "var_pop", "std_pop", "nunique", "first", "last",
                  "first_include_nulls", "last_include_nulls")
# two-column aggregates: the agg spec is (col_x, (op, col_y))
SUPPORTED_BINARY_AGGS = ("covar_samp", "covar_pop", "corr")


class GroupByResult(NamedTuple):
    table: Table          # keys then aggregates, padded to max_groups rows
    num_groups: jnp.ndarray  # scalar int32
    # True when num_groups exceeded the caller's max_groups bound: groups
    # past the bound were dropped; the caller re-plans with a larger bound
    # (grow-and-retry lives in the host wrapper, not here).
    overflowed: jnp.ndarray | bool = False
    # True when a DECIMAL128 SUM exceeded 128 bits in some group: the
    # affected group's sum is null, never a silently wrapped value (the
    # Spark ANSI overflow posture, surfaced like the shuffle codec's
    # narrowing_overflow rather than corrupting data).
    sum_overflow: jnp.ndarray | bool = False
    # True when the aggregates were taken over the rows where they lie (a
    # small group bound: ``_KeySlots``), False when the value words were
    # brought into key order first. A fact of the lowering, not of the data.
    in_place: jnp.ndarray | bool = False
    # True when the rows' key words were sorted to count the groups: under
    # a bound of ``_MIN_LOOP_M`` or fewer the groups are found by repeated
    # minimum and only a broken bound pays that sort, for the true count.
    # A fact of the data.
    key_sorted: jnp.ndarray | bool = False
    # True when the word-moving path ordered a lone 64-bit key as ONE
    # uint32 (``ops/sort.py _lone_key_order``: the keyed rows hold one high
    # word and low words under 2**30 apart). A fact of the data; False for
    # every other key and in place (``sort_key_words`` keeps its words).
    key_one_word: jnp.ndarray | bool = False

    def compact(self) -> Table:
        """Host-side trim to the real group count."""
        if bool(self.overflowed):
            raise ValueError(
                "groupby output overflowed max_groups (groups were dropped); "
                "grow and retry (groupby_aggregate_auto) before compacting"
            )
        from spark_rapids_jni_tpu.ops.table_ops import trim_table

        return trim_table(self.table, int(self.num_groups))


def _col_values_equal_prev(c: Column) -> jnp.ndarray:
    """bool[n-1]: row i+1's VALUE equals row i's (validity ignored here;
    NaNs compare equal — the grouping convention)."""
    if c.dtype.is_string:
        from spark_rapids_jni_tpu.ops import strings as s

        return s.strings_equal_prev(c)
    if c.dtype.is_decimal128:
        return jnp.all(c.data[1:] == c.data[:-1], axis=-1)
    eq_val = c.data[1:] == c.data[:-1]
    if c.dtype.storage_dtype.kind == "f":
        eq_val = eq_val | (jnp.isnan(c.data[1:]) & jnp.isnan(c.data[:-1]))
    return eq_val


def _rows_equal_prev(table: Table, keys: Sequence[int]) -> jnp.ndarray:
    """bool[n]: row i has the same key tuple (incl. null-ness) as row i-1."""
    n = table.num_rows
    same = jnp.ones((n,), dtype=jnp.bool_)
    if n == 0:
        return same
    for k in keys:
        c = table.column(k)
        valid = c.valid_mask()
        eq_val = _col_values_equal_prev(c)
        eq_valid = valid[1:] == valid[:-1]
        both_null = ~valid[1:] & ~valid[:-1]
        eq = (eq_val & valid[1:] & eq_valid) | both_null
        same = same.at[1:].set(same[1:] & eq)
    return same.at[0].set(n == 0)


# Below this group-count bound (and when the boundary work is actually
# smaller than the scan it replaces — see the gate in groupby_aggregate)
# the boundary machinery switches from full-length scans to block-level
# reductions (see _group_starts / _boundary_prefix): a cumsum over n rows
# is latency-bound on the TPU (68ms for 4M int64 lanes, ~0.9 GB/s effective,
# on a v5e in 2026-07, before the runtime stack; not measured since), while
# a block-sum pass is bandwidth-bound and
# the per-boundary partials are O(m * block).
_SMALL_M = 1024
_MIN_BLOCK = 32
_MAX_BLOCK = 512


def _pick_block(n: int, m: int) -> int:
    """Block size balancing the two costs of the boundary path: the block-sum
    pass reads n rows; the per-boundary partials read ~2*m*block rows. Cap
    block so boundary work stays under the streaming pass."""
    b = _MIN_BLOCK
    while b < _MAX_BLOCK and 2 * m * (b * 2) <= n:
        b *= 2
    return b


def _group_starts(same: jnp.ndarray, q: int,
                  block: int) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Positions of the first ``q`` group starts over sorted keys, plus the
    exact total group count — without materializing per-row group ids.

    ``same[i]`` is True when sorted row i has the same key as row i-1, so
    group starts are the set bits of ``~same``. The g-th start is located
    with per-block popcounts: a tiny cumsum over block counts finds the
    block containing it, then a (q, BLOCK) within-block scan finds the bit.
    Absent groups (g >= total) report position n.
    """
    n = same.shape[0]
    flags = (~same).astype(jnp.int32)
    nb = -(-n // block)
    pad = nb * block - n
    fb = jnp.pad(flags, (0, pad)).reshape(nb, block)
    bpre = jnp.cumsum(fb.sum(axis=1))            # (nb,) inclusive
    total = bpre[-1].astype(jnp.int32)
    g = jnp.arange(q, dtype=jnp.int32)
    ib = jnp.clip(jnp.searchsorted(bpre, g, side="right"), 0, nb - 1)
    prev = jnp.where(ib > 0, bpre[jnp.maximum(ib - 1, 0)], 0)
    rank = g - prev                              # g-th start's rank in block
    rows = fb[ib]                                # (q, BLOCK) gather
    within = jnp.cumsum(rows, axis=1)
    hit = (within == (rank + 1)[:, None]) & (rows > 0)
    idx_in = jnp.argmax(hit, axis=1).astype(jnp.int32)
    starts = ib.astype(jnp.int32) * block + idx_in
    return jnp.where(g < total, starts, n).astype(jnp.int32), total


def _boundary_prefix(stack: jnp.ndarray, idx: jnp.ndarray,
                     block: int) -> jnp.ndarray:
    """Prefix sums of ``stack`` (n, k) evaluated only at the ``idx`` (q,)
    boundaries: per-block sums (one bandwidth pass) + a tiny block-level
    cumsum + a (q, BLOCK, k) masked partial for each boundary's own block.
    Replaces the full-length (n, k) cumsum when boundaries are few.
    int64-only: tree reductions of int64 are exact, so this matches the
    scan path bit-for-bit (float lanes take _segmented_sum_scan instead —
    prefix differencing would cancel catastrophically across groups)."""
    n, k = stack.shape
    nb = -(-n // block)
    pad = nb * block - n
    sp = jnp.pad(stack, ((0, pad), (0, 0))).reshape(nb, block, k)
    bpre = jnp.cumsum(sp.sum(axis=1), axis=0)    # (nb, k) inclusive
    ib = jnp.clip(idx // block, 0, nb - 1)
    r = idx - ib * block                         # may equal block at idx == n
    base = jnp.where((ib > 0)[:, None], bpre[jnp.maximum(ib - 1, 0)], 0)
    rows = sp[ib]                                # (q, block, k)
    mask = jnp.arange(block, dtype=jnp.int32)[None, :, None] < r[:, None, None]
    return base + jnp.sum(jnp.where(mask, rows, 0), axis=1)


def _range_sums_from_cumsum(cs: jnp.ndarray, lo: jnp.ndarray,
                            hi: jnp.ndarray) -> jnp.ndarray:
    """Per-range sums over rows [lo, hi) from an inclusive cumsum ``cs``
    of shape (n,) or (n, k); empty ranges (hi <= lo) give 0. The shared
    boundary-difference idiom of the int lane path and nunique."""
    n = cs.shape[0]
    upper = cs[jnp.clip(hi - 1, 0, n - 1)]
    lower_raw = cs[jnp.clip(lo - 1, 0, n - 1)]
    if cs.ndim == 2:
        lower = jnp.where((lo > 0)[:, None], lower_raw, 0)
        return jnp.where((hi > lo)[:, None], upper - lower, 0)
    lower = jnp.where(lo > 0, lower_raw, 0)
    return jnp.where(hi > lo, upper - lower, 0)


def _segmented_sum_scan(stack: jnp.ndarray,
                        seg_start: jnp.ndarray) -> jnp.ndarray:
    """Inclusive segmented running sum along sorted rows: the accumulator
    resets wherever ``seg_start`` is True, so each group's sum only ever
    adds that group's own values — the error of a group's float sum scales
    with the group's magnitude, like ``segment_sum``, NOT with the global
    prefix (prefix differencing cancels the running total and loses small
    groups that follow large ones entirely). The (sum, flag) combine is
    the segmented-sum monoid (associative) -> log-depth scan, no scatter.
    ``stack`` is (n, k); read per-group results at each group's last row."""

    def combine(a, b):
        av, af = a
        bv, bf = b
        return jnp.where(bf, bv, av + bv), af | bf

    v, _ = jax.lax.associative_scan(
        combine, (stack, seg_start[:, None] | jnp.zeros(
            stack.shape, jnp.bool_)))
    return v


def _segmented_extremum(vv: jnp.ndarray, seg_start: jnp.ndarray,
                        op: str) -> jnp.ndarray:
    """Inclusive segmented running min/max along sorted rows: the value
    resets wherever ``seg_start`` is True. The (value, start-flag) combine
    is the segmented-reduce monoid (associative), so
    ``lax.associative_scan`` compiles it to a log-depth scan — replacing
    ``jax.ops.segment_min/max``, whose scatter formulation serializes on
    the TPU (1.6-4x behind the scan forms on a v5e in 2026-07; not
    measured since). Read the
    per-group result at each group's last row."""
    pick = jnp.minimum if op == "min" else jnp.maximum

    def combine(a, b):
        av, af = a
        bv, bf = b
        return jnp.where(bf, bv, pick(av, bv)), af | bf

    v, _ = jax.lax.associative_scan(combine, (vv, seg_start))
    return v


_U32 = jnp.uint64(0xFFFFFFFF)


def _mean128_exact(lo: jnp.ndarray, hi: jnp.ndarray,
                   count: jnp.ndarray):
    """Exact DECIMAL128 mean: (S * 10^4) / count with HALF_UP rounding,
    computed entirely in integer limb arithmetic (TPU f64 is f32-pair
    emulated, so a float mean would silently lose precision — this path
    never touches floats). ``lo``/``hi`` are the exact 128-bit group sums
    (two's complement int64 pair), ``count`` the per-group non-null
    counts. Works because counts fit 32 bits: limb-wise long division
    with 32-bit limbs keeps every intermediate inside uint64.

    Returns (limbs (m, 2) int64, overflow bool[m]) — overflow when the
    widened value exceeds signed 128 bits (Spark ANSI: null + flag)."""
    ulo = lo.astype(jnp.uint64)
    uhi = hi.astype(jnp.uint64)
    neg = hi < 0
    # |S|: two's-complement negate the 128-bit pair where negative
    nlo = (~ulo) + jnp.uint64(1)
    nhi = (~uhi) + jnp.where(ulo == 0, jnp.uint64(1), jnp.uint64(0))
    mlo = jnp.where(neg, nlo, ulo)
    mhi = jnp.where(neg, nhi, uhi)
    m = [mlo & _U32, mlo >> 32, mhi & _U32, mhi >> 32]

    # |S| * 10^4 with carry propagation (limb * 1e4 < 2^46, in-range)
    ten4 = jnp.uint64(10_000)
    t, carry = [], jnp.zeros_like(mlo)
    for limb in m:
        cur = limb * ten4 + carry
        t.append(cur & _U32)
        carry = cur >> 32
    t.append(carry)  # 5th limb

    c = count.astype(jnp.uint64)
    count_too_big = c > _U32
    c_safe = jnp.maximum(jnp.where(count_too_big, jnp.uint64(1), c),
                         jnp.uint64(1))
    # + c//2: HALF_UP (away from zero on the magnitude)
    add = c_safe >> 1
    for i in range(5):
        cur = t[i] + add
        t[i] = cur & _U32
        add = cur >> 32

    # long division top -> bottom; r < c <= 2^32 keeps cur inside uint64
    q = [None] * 5
    r = jnp.zeros_like(mlo)
    for i in range(4, -1, -1):
        cur = (r << 32) | t[i]
        q[i] = cur // c_safe
        r = cur - q[i] * c_safe
    overflow = (q[4] != 0) | (q[3] >> 31 != 0) | count_too_big

    qlo = q[0] | (q[1] << 32)
    qhi = q[2] | (q[3] << 32)
    # negate back where the sum was negative
    rlo = jnp.where(neg, (~qlo) + jnp.uint64(1), qlo)
    rhi = jnp.where(
        neg, (~qhi) + jnp.where(qlo == 0, jnp.uint64(1), jnp.uint64(0)),
        qhi)
    limbs = jnp.stack(
        [rlo.astype(jnp.int64), rhi.astype(jnp.int64)], axis=-1)
    return limbs, overflow


# ---------------------------------------------------------------------------
# Exact DECIMAL128 variance: base-2^16 limb arithmetic.
#
# var_samp over unscaled 128-bit integers U is
#     (n * ΣU² − (ΣU)²) / (n(n−1)) * 10^(2·scale) (scale here follows the columnar convention value = unscaled·10^scale)
# The numerator is computed EXACTLY in 16-bit limbs (up to 2^316 — both
# terms are ≤ n²·2^254) and rounded to float64 once at the end, so the
# result carries none of the cancellation the two-pass float form suffers
# under TPU's f32-pair float64 (~49-bit mantissa, documented posture).
# 16-bit limbs keep every intermediate inside int64: per-row squared limbs
# are < 2^16, so per-group lane sums are < 2^16·n ≤ 2^47; convolution
# partial sums are < 24·2^32 < 2^37; limb×count products are < 2^47.
# ---------------------------------------------------------------------------

_M16 = jnp.int64(0xFFFF)


def _i128_mag_limbs16(lo: jnp.ndarray, hi: jnp.ndarray):
    """(8 magnitude limbs base 2^16, int64 each in [0, 2^16)) plus the
    negative mask of a two's-complement (lo, hi) int64 pair."""
    ulo = lo.astype(jnp.uint64)
    uhi = hi.astype(jnp.uint64)
    neg = hi < 0
    nlo = (~ulo) + jnp.uint64(1)
    nhi = (~uhi) + jnp.where(ulo == 0, jnp.uint64(1), jnp.uint64(0))
    mlo = jnp.where(neg, nlo, ulo)
    mhi = jnp.where(neg, nhi, uhi)
    u16 = jnp.uint64(0xFFFF)
    limbs = [((mlo >> (16 * k)) & u16).astype(jnp.int64) for k in range(4)]
    limbs += [((mhi >> (16 * k)) & u16).astype(jnp.int64) for k in range(4)]
    return limbs, neg


def _carry_norm16(vals: list, width: int):
    """Carry-normalize base-2^16 limbs (possibly signed / un-normalized
    int64) into ``width`` limbs in [0, 2^16) + the final arithmetic carry
    (0 when the value is non-negative and fits; -1 when negative)."""
    carry = jnp.int64(0)
    out = []
    for k in range(width):
        v = (vals[k] + carry) if k < len(vals) else (
            carry if k else jnp.int64(0))
        out.append(v & _M16)
        carry = v >> 16  # arithmetic shift == floor division: signed-safe
    return out, carry


def _negate_limbs16_if(limbs: list, neg: jnp.ndarray) -> list:
    """Two's-complement negate a normalized limb vector where ``neg``."""
    out = []
    carry = jnp.int64(1)
    for l in limbs:
        v = (_M16 - l) + carry
        out.append(jnp.where(neg, v & _M16, l))
        carry = v >> 16
    return out


def _conv_limbs16(a: list, b: list) -> list:
    """Un-normalized convolution c_p = Σ_{i+j=p} a_i·b_j (schoolbook
    multiply of two normalized limb vectors)."""
    c = [None] * (len(a) + len(b) - 1)
    for i, ai in enumerate(a):
        for j, bj in enumerate(b):
            t = ai * bj
            c[i + j] = t if c[i + j] is None else c[i + j] + t
    return c


def _sub_limbs16(a: list, b: list) -> list:
    """Exact a − b over normalized limb vectors, a ≥ b elementwise-wide."""
    out = []
    borrow = jnp.int64(0)
    for x, y in zip(a, b):
        v = x - y - borrow
        out.append(v & _M16)
        borrow = jnp.where(v < 0, jnp.int64(1), jnp.int64(0))
    return out


def _limbs16_to_f64(limbs: list) -> jnp.ndarray:
    """Round a normalized limb vector to float64 (top-down fold: one
    rounding per limb, ~len ulps total — vastly tighter than squaring in
    floats)."""
    acc = jnp.zeros_like(limbs[-1], dtype=jnp.float64)
    for l in reversed(limbs):
        acc = acc * 65536.0 + l.astype(jnp.float64)
    return acc


def _sq_limbs16_rows(lo: jnp.ndarray, hi: jnp.ndarray) -> list:
    """Per-row U² as 16 normalized base-2^16 limbs (U² < 2^254 always
    fits). These become int64 lanes for the streaming group-sum pass."""
    mag, _ = _i128_mag_limbs16(lo, hi)  # sign squares away
    sq, _carry = _carry_norm16(_conv_limbs16(mag, mag), 16)
    return sq


def _cmp_limbs16(a: list, b: list) -> jnp.ndarray:
    """int32 sign of (a - b) over equal-length normalized limb vectors:
    lexicographic from the top limb, vectorized."""
    cmp = jnp.zeros_like(a[0], dtype=jnp.int32)
    for x, y in zip(reversed(a), reversed(b)):
        here = jnp.sign(x - y).astype(jnp.int32)
        cmp = jnp.where(cmp != 0, cmp, here)
    return cmp


def _add_limbs16(a: list, b: list) -> list:
    """Exact a + b over normalized limb vectors (same length; the caller
    sizes the vectors so the sum cannot carry out of the top limb)."""
    out = []
    carry = jnp.int64(0)
    for x, y in zip(a, b):
        v = x + y + carry
        out.append(v & _M16)
        carry = v >> 16
    return out


def _signed_sub_limbs16(a_mag: list, a_neg: jnp.ndarray,
                        b_mag: list, b_neg: jnp.ndarray):
    """Sign-magnitude a − b over normalized limb vectors: returns
    (magnitude limbs, negative mask). Same-sign operands subtract the
    smaller magnitude from the larger; opposite signs add magnitudes."""
    same_sign = a_neg == b_neg
    cmp = _cmp_limbs16(a_mag, b_mag)      # sign of |a| - |b|
    a_ge = cmp >= 0
    hi_ = [jnp.where(a_ge, x, y) for x, y in zip(a_mag, b_mag)]
    lo_ = [jnp.where(a_ge, y, x) for x, y in zip(a_mag, b_mag)]
    diff = _sub_limbs16(hi_, lo_)
    added = _add_limbs16(a_mag, b_mag)
    mag = [jnp.where(same_sign, d, s) for d, s in zip(diff, added)]
    # same sign: result sign follows the dominant operand (a if |a|>=|b|
    # else flipped); opposite signs: a - (-|b|-ish) keeps a's sign when
    # a is the positive one... spelled out: a + (-b) where b_neg
    # flipped — the sum's sign is a's sign (magnitudes add).
    neg_same = jnp.where(a_ge, a_neg, ~b_neg)
    neg = jnp.where(same_sign, neg_same, a_neg)
    # canonical zero: non-negative
    is_zero = jnp.ones_like(a_neg)
    for l in mag:
        is_zero = is_zero & (l == 0)
    return mag, neg & ~is_zero


def split_sum128_lanes(lo: jnp.ndarray, hi: jnp.ndarray) -> list:
    """Four 32-bit limb lanes of a masked (lo, hi) int64 pair — int64
    lane sums over up to 2^31 rows cannot overflow. Shared by the
    groupby, reduction, and window exact-SUM paths."""
    m32 = jnp.int64(0xFFFFFFFF)
    return [lo & m32, (lo >> 32) & m32, hi & m32, hi >> 32]


def recombine_sum128(s0, s1, s2, s3):
    """(lo, hi, overflow) from four limb-lane sums: carry recombination
    with the signed-128-bit overflow check (`top` must be the sign
    extension of its own low 32 bits). The ONE implementation all three
    exact-sum paths share — a carry-math fix lands everywhere."""
    m32 = jnp.int64(0xFFFFFFFF)
    c0 = s0 & m32
    t = s1 + (s0 >> 32)
    lo = c0 | ((t & m32) << 32)
    u = s2 + (t >> 32)
    top = s3 + (u >> 32)
    hi = (u & m32) + (top << 32)
    ovf = top != ((top << 32) >> 32)
    return lo, hi, ovf


def minmax_sentinel(dt: DType, op: str):
    """The null-neutral fill for a min/max reduction over ``dt``: the
    dtype's +inf/max for ``min``, -inf/min for ``max``. One definition
    shared by the local bounded/general paths and the distributed merge
    (a dtype rule fixed in one place must apply to all three)."""
    np_dt = dt.storage_dtype
    if np_dt.kind == "f":
        lo, hi = -jnp.inf, jnp.inf
    else:
        info = np.iinfo(np_dt)
        lo, hi = info.min, info.max
    return hi if op == "min" else lo


def _sum_dtype(dt: DType) -> DType:
    """Spark widens SUM: integral -> INT64, decimal keeps scale (wider
    precision), floats stay floating."""
    if dt.is_decimal128:
        raise NotImplementedError(
            "DECIMAL128 aggregation is not supported yet (limb-pair "
            "arithmetic); cast to DECIMAL64 first if the values fit"
        )
    kind = dt.storage_dtype.kind
    if dt.is_decimal:
        return DType(TypeId.DECIMAL64, dt.scale)
    if kind in ("i", "u", "b"):
        return DType(TypeId.INT64)
    return dt


def _group_bounds(same: jnp.ndarray, m: int) -> tuple:
    """``(num_groups, g_lo, g_hi)`` of the first ``m`` groups over sorted
    keys, from ONE compaction of the group-start mask ``~same``
    (``same[i]``: sorted row i has the key of row i-1, or is a phantom
    row). With ``starts`` the ascending positions where ``same`` is False
    and ``s[g] = starts[g]`` for ``g < num_groups``, else n: ``g_lo =
    s[:m]``, ``g_hi = s[1:m + 1]``; ``num_groups`` counts every start, so
    it exceeds ``m`` on overflow. Absent groups are empty at n; phantom
    rows start no group and end the last real one at n. What
    ``_group_starts(same, m + 1, block)`` returns for few groups.

    The compaction is a one-operand sort of ``where(same, n, iota)``: the
    starts come first, ascending, then n's (all equal, so the sort need
    not be stable: left at ``is_stable=True`` XLA adds an iota operand).
    On a v5e (PERF.md section 6, PR 31: alone in a jit, 8,388,608 rows,
    m = 1,500,001, 88,000 starts; device s / cold compile s): this sort
    0.0061 / 5.9 (stable 0.0140 / 20.2); the pair of binary searches over
    ``cumsum(~same) - 1`` it replaced 0.5180 / 3.2 (24 steps of a gather
    of m each; 0.513 s inside the planned q3 region, the sort 0.0049); a
    stable two-operand ``sort((same, iota))`` 0.0229 / 29.1;
    ``jnp.nonzero`` 0.5850 / 14.6; a scatter of ``iota`` at the group id
    of the start rows (``unique_indices``, ``mode="drop"``) 0.0416 / 1.6;
    ``_group_starts`` at m + 1 0.8466 / 37.8; ``searchsorted(method=
    "sort")`` 0.2597 / 42.9. With m = 2,000 of 6,001,215 rows the
    searches (0.0030) and ``_group_starts`` (0.0018) are ahead of the
    sort (0.0066)."""
    n = same.shape[0]
    num_groups = jnp.sum(~same, dtype=jnp.int32)
    starts = jax.lax.sort(jnp.where(
        same, jnp.uint32(n), jax.lax.iota(jnp.uint32, n)),
        is_stable=False)[:m + 1]
    starts = jnp.pad(starts.astype(jnp.int32), (0, m + 1 - starts.shape[0]),
                     constant_values=n)
    return num_groups, starts[:m], starts[1:]


def _gather_group_keys(sorted_tbl: Table, keys: Sequence[int],
                       first_idx: jnp.ndarray, m: int,
                       n: int) -> list[Column]:
    """One output row per group: each key column gathered at its group's
    first sorted row (absent groups carry first_idx == n -> null)."""
    out_cols: list[Column] = []
    for k in keys:
        c = sorted_tbl.column(k)
        valid = jnp.zeros((m,), jnp.bool_)
        if n == 0:
            # nothing to gather from — emit all-null keys (num_groups = 0)
            if c.dtype.is_string:
                out_cols.append(Column(
                    c.dtype, jnp.zeros((m,), jnp.int32), valid,
                    chars=jnp.zeros((m, 1), jnp.uint8),
                ))
            elif c.dtype.is_decimal128:
                out_cols.append(
                    Column(c.dtype, jnp.zeros((m, 2), jnp.int64), valid)
                )
            else:
                out_cols.append(
                    Column(c.dtype, jnp.zeros((m,), c.dtype.jnp_dtype), valid)
                )
            continue
        safe_first = jnp.clip(first_idx, 0, n - 1)
        valid = c.valid_mask()[safe_first] & (first_idx < n)
        if c.dtype.is_string:
            from spark_rapids_jni_tpu.ops import strings as s

            g = s.gather_strings(c, safe_first)
            out_cols.append(Column(c.dtype, g.data, valid, chars=g.chars))
        else:
            out_cols.append(Column(c.dtype, c.data[safe_first], valid))
    return out_cols


# aggregates that pick one row a group and read the column there alone
_ONE_ROW_AGGS = ("first", "last", "first_include_nulls",
                 "last_include_nulls")


def _row_reads(keys, aggs) -> tuple:
    """What the sort path reads at EVERY row in key order: ``(columns
    whose data and validity are read, columns whose validity alone is)``.
    Keys, for the group boundaries; the operands of every aggregate that
    runs over the rows; the bare validity for ``count`` and for the
    non-null scan of ``first`` / ``last``. The ``*_include_nulls`` picks
    read nothing there, and ``nunique`` sorts its own copy."""
    data, masks = list(keys), []
    for col_idx, op in aggs:
        if isinstance(op, tuple):
            data += [col_idx, op[1]]
        elif op in ("first", "last", "count"):
            masks.append(col_idx)
        elif op not in _ONE_ROW_AGGS and op != "nunique":
            data.append(col_idx)
    return list(dict.fromkeys(data)), list(dict.fromkeys(masks))


# aggregates a sum, a count, a minimum or a maximum by slot makes: every
# one but those that need the rows in key order or one row a group
_SLOT_AGGS = ("sum", "count", "mean", "var", "std", "var_pop", "std_pop",
              "min", "max")


def _aggregates_in_place(table: Table, keys, aggs, m: int) -> bool:
    """Whether a groupby bounded at ``m`` groups (small: the caller's gate)
    takes its aggregates over the rows where they lie (``_KeySlots``):
    the keys are integers of fixed width (their sort words then say
    "same key" exactly as ``_rows_equal_prev`` does; a float key's -0.0
    and 0.0 are one group and two words), and every aggregate is a sum, a
    count, a minimum or a maximum by slot. What must see the rows in key
    order (``first`` / ``last``, ``nunique``, a string's or decimal128's
    ``min`` / ``max`` by rank) keeps the word-moving path. Sums of
    integers go through the MXU at any such ``m``; float sums and
    extrema are one masked reduction a slot and lane, whose cost goes
    with ``m``: those are held to ``_SLOT_REDUCE_M``."""
    for k in keys:
        dt = table.column(k).dtype
        if (dt.is_string or dt.is_decimal128
                or dt.storage_dtype.kind not in "iu"):
            return False
    reduces = False
    for col_idx, op in aggs:
        c = table.column(col_idx)
        if isinstance(op, tuple):
            # the centered moments are float lanes; the decimal128 form is
            # exact integer lanes throughout
            reduces |= not (c.dtype.is_decimal128
                            or table.column(op[1]).dtype.is_decimal128)
            continue
        if op not in _SLOT_AGGS:
            return False
        if op in ("min", "max"):
            if c.dtype.is_string or c.dtype.is_decimal128:
                return False
            reduces = True
        elif op in ("var", "std", "var_pop", "std_pop"):
            reduces |= not c.dtype.is_decimal128
        elif op in ("sum", "mean"):
            reduces |= (not c.dtype.is_decimal128
                        and c.dtype.storage_dtype.kind == "f")
    return m <= _SLOT_REDUCE_M or not reduces


# The MXU accumulate's chunk: partial sums of 8-bit limbs over this many
# rows stay under 2**24, exact in float32 (255 * 65,536 < 16,777,216). One
# step of the accumulate's loop is one chunk: what it holds of the one-hot
# and the limbs is a chunk's, not n rows', and one plain matrix product a
# step was ahead of eight batched (PERF.md section 6, PR 33).
_MXU_CHUNK_ROWS = 1 << 16
# Largest bound at which float sums and extrema are reduced slot by slot
# (``_KeySlots.float_sums`` / ``.extremum``).
_SLOT_REDUCE_M = 64


class _KeySlots:
    """The rows of a table matched to the first m groups of its keys, where
    the rows lie: row r belongs to slot g when its key words
    (``ops/sort.py sort_key_words``) equal group g's. Phantom rows (their
    row-valid bit is one of the words) and the rows of groups past the
    bound equal no slot's words and join none. Every method is a pass over
    the rows in their own order; none needs a group id a row."""

    def __init__(self, words, group_words, real):
        self._words = words            # k x uint32[n], minor -> major
        self._group_words = group_words  # k x uint32[m]
        self._real = real              # bool[m]: the slot holds a group
        self._n = words[0].shape[0]
        self._m = real.shape[0]

    def _match(self, words=None) -> jnp.ndarray:
        """bool[..., m, rows] for words of [..., rows]: the row's words
        are the slot's."""
        hit = self._real[:, None]
        for w, g in zip(words or self._words, self._group_words):
            hit = hit & (w[..., None, :] == g[:, None])
        return hit

    def int_sums(self, lanes, kinds) -> jnp.ndarray:
        """int64[m, k]: the sum by slot of each ``lanes[j]`` (int64[n]),
        exact modulo 2**64 as an int64 cumsum is. A one-hot contraction on
        the MXU, a chunk of ``_MXU_CHUNK_ROWS`` rows a loop step: the lanes
        as unsigned 8-bit limbs in bf16 (exact) against ``one_hot(slot)``
        in bf16, accumulated in float32 (every partial sum an integer
        under 2**24), the chunks added in int64 and the limbs recombined
        in wrapping int64. ``kinds[j]`` is the dtype the lane was widened
        from: a bool has one limb, an unsigned of b bytes b, a signed one
        all eight (its sign extension)."""
        n, m = self._n, self._m
        limbs_of = [1 if k == jnp.bool_ else
                    k.itemsize if k.kind == "u" else 8 for k in kinds]
        rows = min(_MXU_CHUNK_ROWS, n)
        chunks = -(-n // rows)
        pad = chunks * rows - n             # 0 for a bucket's power of two

        def cut(a):     # (chunks, rows); the tail's zero limbs add 0
            return jnp.pad(a, (0, pad)).reshape(chunks, rows)

        words = [cut(w) for w in self._words]
        cut_lanes = [cut(lane) for lane in lanes]

        def one_chunk(i, acc):
            hot = self._match([w[i] for w in words]).astype(jnp.bfloat16)
            limbs = jnp.stack([
                ((lane[i] >> (8 * b)) & 0xFF).astype(jnp.int32)
                .astype(jnp.bfloat16)
                for lane, nb in zip(cut_lanes, limbs_of) for b in range(nb)])
            part = jnp.einsum("gr,lr->gl", hot, limbs,
                              preferred_element_type=jnp.float32)
            return acc + part.astype(jnp.int32).astype(jnp.int64)

        # the carry starts from a word, so that under shard_map it varies
        # over the same mesh axes going in as out
        start = jnp.zeros((m, sum(limbs_of)), jnp.int64) + (
            self._words[0][:1] & jnp.uint32(0)).astype(jnp.int64)
        by_limb = jax.lax.fori_loop(0, chunks, one_chunk, start)
        out, at = [], 0
        for nb in limbs_of:
            total = by_limb[:, at]
            for b in range(1, nb):
                total = total + (by_limb[:, at + b] << (8 * b))
            out.append(total)
            at += nb
        return jnp.stack(out, axis=1)

    def float_sums(self, stack: jnp.ndarray) -> jnp.ndarray:
        """float64[m, k]: the sum by slot of each column of ``stack``
        (float64[n, k]); one masked reduction a slot and lane, each adding
        its own group's values only, in an order of XLA's choosing."""
        hit = self._match()
        return jnp.stack([
            jnp.sum(jnp.where(hit, stack[None, :, j], 0.0), axis=1)
            for j in range(stack.shape[1])], axis=1)

    def extremum(self, vv: jnp.ndarray, op: str, sentinel) -> jnp.ndarray:
        """[m]: the minimum / maximum by slot of ``vv`` ([n], nulls
        already at ``sentinel``); an empty slot reads ``sentinel``."""
        pick = jnp.min if op == "min" else jnp.max
        return pick(jnp.where(self._match(), vv[None, :],
                              jnp.asarray(sentinel, vv.dtype)), axis=1)

    def at_rows(self, per_slot: jnp.ndarray) -> jnp.ndarray:
        """[n]: each row's own slot's value of ``per_slot`` ([m]); 0 for a
        row in no slot. A select a slot, no gather."""
        return jnp.sum(jnp.where(self._match(), per_slot[:, None],
                                 jnp.zeros((), per_slot.dtype)), axis=0)


# Largest bound at which the in-place path finds its groups by repeated
# minimum (``_least_groups``) and not from a sort of the rows' key words.
# The loop runs one step a group the DATA holds and one more, at most
# m + 1; a step is one pass over the words.
_MIN_LOOP_M = 64


def _least_groups(words, rv, m: int):
    """The first ``m`` groups of rows with key ``words`` (k x uint32[n],
    minor -> major: ``ops/sort.py key_words``) in key order, by repeated
    minimum over the rows where they lie: ``(group_words k x uint32[m],
    first_row int32[m], found int32)``. Step g of a ``while_loop`` is ONE
    variadic reduction over the rows: the least (not a candidate, major
    word, ..., minor word, row index), the candidates being the real rows
    whose words lie above group g - 1's. That is group g's words and the
    least row that holds them, the row a stable sort puts first in its
    group. It stops when no candidate is left or group m + 1 has been
    found: ``found`` is the number of groups up to m + 1, ``first_row`` is
    n and ``group_words`` is arbitrary past it. A pass with fused reads a
    step: no sort, no gather, no scatter."""
    n = words[0].shape[0]
    iota = jax.lax.iota(jnp.int32, n)
    # the carry starts from a word, so that under shard_map it varies over
    # the same mesh axes going in as out
    zero = words[0][0] & jnp.uint32(0)

    def lesser(x, y):
        less, tie = False, True
        for a, b in zip(x, y):
            less = less | (tie & (a < b))
            tie = tie & (a == b)
        return tuple(jnp.where(less, a, b) for a, b in zip(x, y))

    def step(carry):
        g, _, prev, group_words, first_row = carry
        above = jnp.zeros((n,), jnp.bool_)
        for w, p in zip(words, prev):
            above = (w > p) | ((w == p) & above)
        cand = above | (g == 0)
        if rv is not None:
            cand = cand & rv
        out_of, *least, row = jax.lax.reduce(
            ((~cand).astype(jnp.uint32), *words[::-1], iota),
            (jnp.uint32(1), *[jnp.uint32(0xFFFFFFFF)] * len(words),
             jnp.int32(n)), lesser, (0,))
        more = out_of == 0
        least = least[::-1]
        return (g + more.astype(jnp.int32), more, least,
                [gw.at[g].set(lw) for gw, lw in zip(group_words, least)],
                first_row.at[g].set(jnp.where(more, row, n)))

    found, _, _, group_words, first_row = jax.lax.while_loop(
        lambda carry: (carry[0] <= m) & carry[1], step,
        (zero.astype(jnp.int32), zero == 0, [zero] * len(words),
         [jnp.zeros((m + 1,), jnp.uint32) + zero] * len(words),
         jnp.full((m + 1,), n, jnp.int32) + zero.astype(jnp.int32)))
    return [gw[:m] for gw in group_words], first_row[:m], found


def _words_equal_prev(in_order, rv) -> jnp.ndarray:
    """bool[n]: row i of the key words ``in_order`` (in key order) has the
    words of row i - 1, or is a phantom row: they sort last and start no
    group."""
    n = in_order[0].shape[0]
    eq_prev = in_order[0][1:] == in_order[0][:-1]
    for w in in_order[1:]:
        eq_prev = eq_prev & (w[1:] == w[:-1])
    same = jnp.concatenate([jnp.zeros((1,), jnp.bool_), eq_prev])
    if rv is not None:
        same = same | (jax.lax.iota(jnp.int32, n)
                       >= jnp.sum(rv, dtype=jnp.int32))
    return same


def _groupby_aggregate_impl(row_args, aux, rvs, *, keys, aggs,
                            max_groups) -> GroupByResult:
    # the word-moving branch names its three stages for a device trace
    # (``key_sort``, ``move``, ``reduce``): the last is open to the end
    with contextlib.ExitStack() as scopes:
        return _aggregate(row_args, rvs, scopes, keys=keys, aggs=aggs,
                          max_groups=max_groups)


def _aggregate(row_args, rvs, scopes, *, keys, aggs,
               max_groups) -> GroupByResult:
    ((table, row_valid),) = row_args
    rv = row_valid
    if rv is None and rvs is not None:
        rv = rvs[0]
    n = table.num_rows
    m = n if max_groups is None else int(max_groups)
    # Few groups (the small-m path): the group starts come from block
    # popcounts, gated on the boundary work (2*m*block rows) undercutting
    # a pass over the rows; and where every aggregate is a sum, a count or
    # an extremum by slot, nothing but the keys comes into key order.
    small = n > 0 and m <= _SMALL_M and 2 * m * _MIN_BLOCK <= n
    in_place = small and _aggregates_in_place(table, keys, aggs, m)
    data_at, mask_at = _row_reads(keys, aggs)
    mask_at = [i for i in mask_at
               if i not in data_at and table.column(i).validity is not None]
    # The sum of a group does not depend on the order of its rows: in
    # place the rows stay where they lie and are matched to the m groups
    # by their key words (``_KeySlots``). No value word moves: at
    # 8,388,608 rows and eleven value words that was twelve sort passes,
    # 0.15 of the 0.19 s this function took; it takes 0.039 s (PERF.md
    # section 6, PR 33). What is left to find is the groups' words, in key
    # order, and a row of each group: under a bound of ``_MIN_LOOP_M`` or
    # fewer by repeated minimum over the words (``_least_groups``: as many
    # steps as the data holds groups, and one), over it from the key sort,
    # whose cost does not go with the bound.
    by_loop = in_place and m <= _MIN_LOOP_M
    key_sorted = key_one_word = False
    if in_place:
        read_col = {i: table.column(i) for i in data_at}
        read_mask = {i: table.column(i).validity for i in mask_at}
    if by_loop:
        words = key_words(table, keys, rv)
    elif in_place:
        order, words, sorted_words = sort_key_words(table, keys, rv)
        same = _words_equal_prev(sorted_words, rv)
    else:
        with jax.named_scope("key_sort"):
            order, key_one_word = sort_order_and_form(
                table, keys, row_valid=rv)
        # Only what is read at every row comes into key order, as packed
        # words moved once (ops/sort.py ``permute``): the keys, the
        # operands of the aggregates that run over the rows, and the bare
        # validity of a column that is only counted or scanned for its
        # first / last non-null row. What is read at one row a group (the
        # cells first / last pick) is fetched from the unsorted table
        # through ``order`` at m rows; a column no key and no aggregate
        # names does not move at all.
        with jax.named_scope("move"):
            moved, moved_masks = permute(
                [table.column(i) for i in data_at], order,
                [table.column(i).validity for i in mask_at]
                + ([] if rv is None else [rv]))
        # boundaries, segmented sums, the gathers at the bound's rows
        scopes.enter_context(jax.named_scope("reduce"))
        read_col = dict(zip(data_at, moved))
        read_mask = dict(zip(mask_at, moved_masks))
        sorted_keys = Table([read_col[k] for k in keys])
        same = _rows_equal_prev(sorted_keys, range(len(keys)))
        if rv is not None:
            # phantom rows (bucketed padding tails / masked shuffle slots)
            # sort LAST and never start a group: they merge into the final
            # real group, where their all-null cells are neutral for every
            # aggregate (sums add 0, counts skip, min/max see sentinels,
            # first/last skip-null scans pass over them). The one
            # positional exception, last_include_nulls, is kept off the
            # bucketed path by the public wrapper (bucket_rows=False).
            same = same | ~moved_masks[-1]

    def read_valid(col_idx: int) -> jnp.ndarray:
        """The validity of column ``col_idx`` as the aggregates read it:
        in key order, or where ``in_place`` as the rows lie."""
        if col_idx in read_col:
            return read_col[col_idx].valid_mask()
        if col_idx in read_mask:
            return read_mask[col_idx]
        return jnp.ones((n,), jnp.bool_)     # a column without nulls

    # The first m + 1 group starts give every bound; neither way builds a
    # per-row group id. Few groups: block popcounts (_group_starts).
    # Otherwise one sort of the start mask (_group_bounds: 0.005 s in the
    # planned q3 region where two binary searches of 1,500,001 bounds took
    # 0.513 s; PERF.md section 6, PR 31).
    block = _pick_block(n, m) if small else 0
    garange = jnp.arange(m, dtype=jnp.int32)
    if by_loop:
        group_words, first_row, found = _least_groups(words, rv, m)
        overflowed = key_sorted = found > m
        # the loop knows the first m groups and that there are more: the
        # true count past the bound is the only thing a sort is still for
        # (of the words alone, each compared with the row before it), and
        # a request whose bound holds never runs it
        num_groups = jax.lax.cond(
            overflowed, lambda: jnp.sum(
                ~_words_equal_prev(words_in_order(words), rv),
                dtype=jnp.int32),
            lambda: found)
    else:
        if small:
            starts, num_groups = _group_starts(same, m + 1, block)
            g_lo, g_hi = starts[:m], starts[1:]
        else:
            num_groups, g_lo, g_hi = _group_bounds(same, m)
        overflowed = num_groups > m
        # first row of each group (n = absent, matching the old scatter-min)
        first_idx = jnp.where(g_hi > g_lo, g_lo, n)
        if in_place:
            # a group's first row where it lies
            first_row = jnp.where(
                first_idx < n, order[jnp.clip(first_idx, 0, n - 1)], n)
            group_words = [w[jnp.clip(first_row, 0, n - 1)] for w in words]
    if in_place:
        # a group's keys are read at its first row, and its words are what
        # the rows are matched against
        out_cols = _gather_group_keys(table, keys, first_row, m, n)
        slots = _KeySlots(words, group_words, first_row < n)
    else:
        out_cols = _gather_group_keys(
            sorted_keys, range(len(keys)), first_idx, m, n)

    # Sum-form reductions (sums of ints/decimals/floats, all counts) batch
    # into ONE (n, k) prefix pass per accumulator dtype + per-group
    # boundary differences: one streaming pass, zero scatters. int64 lanes
    # are exact; float lanes carry parallel-reduction rounding (summation
    # order is unspecified, like any parallel float sum — Spark makes the
    # same non-guarantee). Min/max ride a segmented log-depth scan
    # (_segmented_extremum) instead of segment_* scatters.
    int_lanes: list[jnp.ndarray] = []    # (n,) int64 each
    int_kinds: list = []                 # the dtype each was widened from
    float_lanes: list[jnp.ndarray] = []  # (n,) float64 each
    # sibling aggs on one column (sum+mean+var, every agg's count) must
    # share lanes, not stack identical copies into the streaming pass
    _lane_memo: dict = {}

    def lane(arr: jnp.ndarray, memo_key=None) -> tuple[str, int]:
        if memo_key is not None and memo_key in _lane_memo:
            return _lane_memo[memo_key]
        int_lanes.append(arr.astype(jnp.int64))
        int_kinds.append(arr.dtype)
        spec = ("i", len(int_lanes) - 1)
        if memo_key is not None:
            _lane_memo[memo_key] = spec
        return spec

    def flane(arr: jnp.ndarray, memo_key=None) -> tuple[str, int]:
        if memo_key is not None and memo_key in _lane_memo:
            return _lane_memo[memo_key]
        float_lanes.append(arr.astype(jnp.float64))
        spec = ("f", len(float_lanes) - 1)
        if memo_key is not None:
            _lane_memo[memo_key] = spec
        return spec

    def _seg_sums(stack: jnp.ndarray) -> jnp.ndarray:
        """(n, k) lane stack -> (m, k) per-group sums. int64 lanes ride
        prefix differencing (exact, so cancellation is a non-issue): block
        prefixes when small, a full cumsum read at the group bounds
        otherwise. Float lanes instead ride a segmented scan that resets
        at group boundaries — prefix differencing would cancel the global
        running total and absorb small groups that follow large ones
        (catastrophic cancellation, worse under TPU's f32-pair f64)."""
        if n == 0:
            return jnp.zeros((m, stack.shape[1]), stack.dtype)
        if in_place:      # the float lanes; the int lanes: slots.int_sums
            return slots.float_sums(stack)
        if stack.dtype.kind == "f":
            run = _segmented_sum_scan(stack, ~same)
            out = run[jnp.clip(g_hi - 1, 0, n - 1)]
            return jnp.where((g_hi > g_lo)[:, None], out, 0)
        if small:
            # empty groups have g_lo == g_hi == n so their difference is 0
            pref = _boundary_prefix(
                stack, jnp.concatenate([g_hi, g_lo]), block)
            return pref[:m] - pref[m:]
        return _range_sums_from_cumsum(
            jnp.cumsum(stack, axis=0), g_lo, g_hi)

    _M32 = jnp.int64(0xFFFFFFFF)

    plan = []  # (op, column, acc_dt / other column, lane ids / None)
    for col_idx, op in aggs:
        if op in _ONE_ROW_AGGS:
            # the UNSORTED column, read at one row a group through
            # ``order``; first / last scan the validity in key order
            plan.append((op, table.column(col_idx), None,
                         None if op.endswith("_include_nulls")
                         else read_valid(col_idx), None))
            continue
        if op == "nunique":     # sorts its own copy of keys and values
            plan.append((op, None, DType(TypeId.INT64), col_idx, None))
            continue
        if op == "count":       # reads the validity alone
            plan.append((op, None, None, None, lane(
                read_valid(col_idx), memo_key=(col_idx, "count"))))
            continue
        c = read_col[col_idx]
        valid = c.valid_mask()
        if isinstance(op, tuple):
            # binary aggregates (covar_samp/covar_pop/corr): Spark counts
            # only rows where BOTH operands are non-null, so these ride
            # dedicated pairwise-masked sum + count lanes (memoized per
            # column pair — corr shares them with sibling covar aggs).
            kind, oidx = op
            cy = read_col[oidx]
            for cc in (c, cy):
                if cc.dtype.is_string or (
                        not cc.dtype.is_decimal128
                        and cc.dtype.storage_dtype.kind not in
                        ("i", "u", "f")):
                    raise TypeError(
                        f"{kind} needs numeric columns, got {cc.dtype}")
            both = valid & cy.valid_mask()
            pair = (id(c), id(cy))
            both_lane = lane(both, memo_key=(pair, "count2"))
            if c.dtype.is_decimal128 or cy.dtype.is_decimal128:
                # exact wide path: both operands must have integral
                # storage (a float partner has no exact form — cast it
                # to a decimal first)
                for cc in (c, cy):
                    if (not cc.dtype.is_decimal128
                            and cc.dtype.storage_dtype.kind not in
                            ("i", "u")):
                        raise TypeError(
                            f"{kind} with a DECIMAL128 operand needs an "
                            f"integral-storage partner, got {cc.dtype}")

                def _as_i128(cc):
                    if cc.dtype.is_decimal128:
                        lo_ = jnp.where(both, cc.data[:, 0], jnp.int64(0))
                        hi_ = jnp.where(both, cc.data[:, 1], jnp.int64(0))
                    else:
                        v = jnp.where(
                            both, cc.data.astype(jnp.int64), jnp.int64(0))
                        if cc.dtype.storage_dtype.kind == "u":
                            # unsigned: the int64 cast keeps the BITS;
                            # zero-extend (v >> 63 would sign-wrap
                            # values >= 2^63)
                            hi_ = jnp.zeros_like(v)
                        else:
                            hi_ = v >> 63       # sign extension
                        lo_ = v
                    return lo_, hi_

                lox, hix = _as_i128(c)
                loy, hiy = _as_i128(cy)
                magx, negx = _i128_mag_limbs16(lox, hix)
                magy, negy = _i128_mag_limbs16(loy, hiy)
                sx_specs = tuple(
                    lane(jnp.where(negx, -magx[k], magx[k]),
                         memo_key=(pair, "cx128", k)) for k in range(8))
                sy_specs = tuple(
                    lane(jnp.where(negy, -magy[k], magy[k]),
                         memo_key=(pair, "cy128", k)) for k in range(8))
                xy, _ = _carry_norm16(_conv_limbs16(magx, magy), 16)
                neg_xy = negx != negy
                sxy_specs = tuple(
                    lane(jnp.where(neg_xy, -xy[k], xy[k]),
                         memo_key=(pair, "cxy128", k)) for k in range(16))
                if kind == "corr":
                    sqx = _sq_limbs16_rows(lox, hix)
                    sqy = _sq_limbs16_rows(loy, hiy)
                    sq_specs = (
                        tuple(lane(sqx[k], memo_key=(pair, "cqx128", k))
                              for k in range(16)),
                        tuple(lane(sqy[k], memo_key=(pair, "cqy128", k))
                              for k in range(16)),
                    )
                else:
                    sq_specs = None
                plan.append((kind + "128pair", c, cy,
                             (sx_specs, sy_specs, sxy_specs, sq_specs),
                             both_lane))
                continue
            specs = []
            for cc, tag in ((c, "sx"), (cy, "sy")):
                vv = jnp.where(both, cc.data, jnp.zeros_like(cc.data))
                mk = (pair, tag)
                specs.append(
                    lane(vv, memo_key=mk)
                    if cc.dtype.storage_dtype.kind in ("i", "u")
                    else flane(vv, memo_key=mk))
            plan.append((kind, c, cy, tuple(specs), both_lane))
            continue
        count_lane = lane(valid, memo_key=(col_idx, "count"))
        if op in ("sum", "mean") and c.dtype.is_decimal128:
            # exact 128-bit sum: split (lo, hi) into four 32-bit limb
            # lanes so no int64 lane can overflow (sums bounded by
            # 2^32 * n), recombined with carry propagation below; totals
            # beyond 128 bits null the group and set sum_overflow.
            # mean128 divides the exact sum by the count with limb-wise
            # long division (exact, no f64) — see the consume branch.
            lo = jnp.where(valid, c.data[:, 0], jnp.int64(0))
            hi = jnp.where(valid, c.data[:, 1], jnp.int64(0))
            lanes128 = tuple(
                lane(l, memo_key=(id(c), "s128", k))
                for k, l in enumerate(split_sum128_lanes(lo, hi)))
            if op == "mean":
                # Spark avg(decimal) carries 4 extra fractional digits
                plan.append(("mean128", c, decimal128(c.dtype.scale - 4),
                             lanes128, count_lane))
            else:
                plan.append(("sum128", c, c.dtype, lanes128, count_lane))
            continue
        if op in ("var", "std", "var_pop", "std_pop"):
            if c.dtype.is_decimal128:
                # exact wide second moments: 8 signed ±|U| limb lanes for
                # ΣU plus 16 per-row U² limb lanes for ΣU² — every lane
                # sum is exact int64; the variance numerator is combined
                # in wide limb arithmetic in the consume loop and rounded
                # to float64 once.
                lo = jnp.where(valid, c.data[:, 0], jnp.int64(0))
                hi = jnp.where(valid, c.data[:, 1], jnp.int64(0))
                mag, negr = _i128_mag_limbs16(lo, hi)
                key128 = id(c)
                sum_specs = tuple(
                    lane(jnp.where(negr, -mag[k], mag[k]),
                         memo_key=(key128, "v128s", k))
                    for k in range(8))
                sq = _sq_limbs16_rows(lo, hi)
                sq_specs = tuple(
                    lane(sq[k], memo_key=(key128, "v128q", k))
                    for k in range(16))
                plan.append((op + "128", c, None, (sum_specs, sq_specs),
                             count_lane))
                continue
            if c.dtype.is_string or \
                    c.dtype.storage_dtype.kind not in ("i", "u", "f"):
                raise TypeError(
                    f"var/std need a numeric column, got {c.dtype}"
                )
            # first pass (the per-group sum for the mean) rides the lane
            # machinery: exact int64 for integral/decimal storage, a float
            # lane otherwise; the centered second pass is a _seg_sums call
            # in the consume loop (no scatter either way)
            vv = jnp.where(valid, c.data, jnp.zeros_like(c.data))
            if c.dtype.storage_dtype.kind in ("i", "u"):
                sum_spec = lane(vv, memo_key=(id(c), "sum_i"))
            else:
                sum_spec = flane(vv, memo_key=(id(c), "sum_f"))
            plan.append((op, c, None, sum_spec, count_lane))
            continue
        if op in ("sum", "mean"):
            acc_dt = _sum_dtype(c.dtype)
            vv = jnp.where(valid, c.data, jnp.zeros_like(c.data))
            if acc_dt.storage_dtype.kind in ("i", "u"):
                plan.append((op, c, acc_dt,
                             lane(vv, memo_key=(id(c), "sum_i")), count_lane))
            else:  # float accumulation rides a float lane — no scatter
                plan.append((op, c, acc_dt,
                             flane(vv, memo_key=(id(c), "sum_f")), count_lane))
        else:
            plan.append((op, c, None, None, count_lane))

    _rank_order_cache: dict = {}  # value-sort order per column, shared
                                  # between a column's min and max aggs
    _var_cache: dict = {}         # per-column variance, shared var<->std
    _covar_cache: dict = {}       # per-pair centered moments, shared
                                  # between covar_samp/covar_pop/corr

    def _rank_minmax(c: Column, op: str, vcount: jnp.ndarray) -> Column:
        """MIN/MAX of a column with no elementwise-reducible storage
        (strings, DECIMAL128 limb pairs): rank rows by value order (one
        sort of the value column), segment-reduce the int ranks, gather
        the winning row — order statistics via ranks instead of per-group
        comparator loops."""
        if n == 0:
            if c.dtype.is_string:
                return Column(c.dtype, jnp.zeros((m,), jnp.int32),
                              jnp.zeros((m,), jnp.bool_),
                              chars=jnp.zeros((m, 1), jnp.uint8))
            return Column(c.dtype, jnp.zeros((m, 2), jnp.int64),
                          jnp.zeros((m,), jnp.bool_))
        cache_key = id(c)
        if cache_key not in _rank_order_cache:
            order_c = sort_order(
                Table([c]), [0], nulls_first=[False]  # nulls last
            )
            # inverse permutation via argsort (a sort, not a scatter —
            # scatters serialize on TPU); cached so a column's min and max
            # share both sorts
            _rank_order_cache[cache_key] = (
                order_c, jnp.argsort(order_c).astype(jnp.int32))
        order_v, rank = _rank_order_cache[cache_key]
        # null values never win: give them the worst rank for the op
        sentinel = jnp.int32(n if op == "min" else -1)
        rank = jnp.where(c.valid_mask(), rank, sentinel)
        # segmented log-depth scan over the key-sorted rows, read at each
        # group's last row — replaces the .at[gid].min/max scatter
        run = _segmented_extremum(rank, ~same, op)
        best = run[jnp.clip(g_hi - 1, 0, n - 1)]
        has_any = vcount > 0
        winner_row = order_v[jnp.clip(best, 0, max(n - 1, 0))]
        if c.dtype.is_string:
            from spark_rapids_jni_tpu.ops import strings as s

            g = s.gather_strings(c, winner_row)
            return Column(c.dtype, g.data, has_any, chars=g.chars)
        return Column(c.dtype, c.data[winner_row], has_any)

    if in_place and int_lanes:
        seg_i = slots.int_sums(int_lanes, int_kinds)
    else:
        seg_i = (_seg_sums(jnp.stack(int_lanes, axis=1)) if int_lanes
                 else jnp.zeros((m, 1), jnp.int64))
    seg_f = (_seg_sums(jnp.stack(float_lanes, axis=1)) if float_lanes
             else jnp.zeros((m, 1), jnp.float64))

    def seg_col(spec: tuple[str, int]) -> jnp.ndarray:
        kind, idx = spec
        return seg_i[:, idx] if kind == "i" else seg_f[:, idx]

    _gid_cache: list = []

    def per_row(per_group: jnp.ndarray) -> jnp.ndarray:
        """[n]: each row's own group's value of ``per_group`` ([m]), for
        the centered variance / covariance pass (the bounds need no group
        id a row). In key order a dense group id is built once: in the
        small-m path group starts are already known, so a searchsorted
        replaces the full-length cumsum scan. Where the rows lie
        (``in_place``) it is a select a slot."""
        if in_place:
            return slots.at_rows(per_group)
        if not _gid_cache:
            _gid_cache.append((jnp.searchsorted(
                g_lo, jnp.arange(n, dtype=jnp.int32), side="right"
            ) - 1 if small else jnp.cumsum(~same) - 1).astype(jnp.int32))
        return per_group[_gid_cache[0]]

    sum128_overflow = jnp.bool_(False)
    for op, c, acc_dt, val_lane, count_lane in plan:
        # first / last and nunique open no count lane: they never read it
        vcount = None if count_lane is None else seg_col(count_lane)
        if op in ("sum128", "mean128"):
            s0, s1, s2, s3 = (seg_col(i) for i in val_lane)
            # shared carry recombination + Spark-ANSI overflow check
            lo, hi, ovf = recombine_sum128(s0, s1, s2, s3)
            ovf_g = ovf & (vcount > 0)
            if op == "mean128":
                limbs, div_ovf = _mean128_exact(lo, hi, vcount)
                ovf_g = ovf_g | (div_ovf & (vcount > 0))
                out = limbs
            else:
                out = jnp.stack([lo, hi], axis=-1)
            sum128_overflow = sum128_overflow | jnp.any(
                ovf_g & (garange < num_groups))
            out_cols.append(Column(
                acc_dt, out, (vcount > 0) & ~ovf_g
            ))
            continue
        if op == "count":
            out_cols.append(
                Column(DType(TypeId.INT64), vcount,
                       jnp.arange(m) < num_groups)
            )
            continue
        if op in ("sum", "mean"):
            has_any = vcount > 0
            total = seg_col(val_lane).astype(acc_dt.jnp_dtype)
            if op == "sum":
                out_cols.append(Column(acc_dt, total, has_any))
            else:
                denom = jnp.maximum(vcount, 1).astype(jnp.float64)
                mean = total.astype(jnp.float64) / denom
                if c.dtype.is_decimal:
                    # Rescale so the FLOAT64 result carries the true value:
                    # the unscaled-integer mean alone is off by 10^-scale
                    # and the float dtype has no scale field to recover it.
                    mean = mean * (10.0 ** c.dtype.scale)
                out_cols.append(Column(DType(TypeId.FLOAT64), mean, has_any))
            continue
        if op in ("var128", "std128", "var_pop128", "std_pop128"):
            # exact DECIMAL128 variance: combine the 8+16 exact lane sums
            # into n·ΣU² − (ΣU)² with base-2^16 limb arithmetic (≤ 2^316,
            # every intermediate in int64), round to float64 once, then
            # divide by n(n−1) (sample) or n² (population) and apply
            # 10^(2·scale). The exact numerator is cached per column and
            # shared by all four variants.
            cache_key = id(c)
            if cache_key not in _var_cache:
                sum_specs, sq_specs = val_lane
                s_lanes = [seg_col(i) for i in sum_specs]
                q_lanes = [seg_col(i) for i in sq_specs]
                # exact ΣU: signed lane sums → 12 normalized limbs + sign
                # (|ΣU| < 2^16·2^31·2^112 = 2^159 < 2^192); the final
                # carry is the sign (-1 ⟺ negative)
                sl, s_carry = _carry_norm16(s_lanes, 12)
                sl = _negate_limbs16_if(sl, s_carry < 0)
                # (ΣU)²: 12×12 convolution → 24 normalized limbs
                bsq, _ = _carry_norm16(_conv_limbs16(sl, sl), 24)
                # n·ΣU²: lane sums (< 2^47) → 20 limbs, × count (< 2^31
                # keeps limb·n < 2^47), renormalized to 24
                ql, _ = _carry_norm16(q_lanes, 20)
                nq, _ = _carry_norm16([q * vcount for q in ql], 24)
                # numerator is ≥ 0 by Cauchy–Schwarz — exact subtraction
                num = _limbs16_to_f64(_sub_limbs16(nq, bsq))
                _var_cache[cache_key] = num * (10.0 ** (2 * c.dtype.scale))
            pop = "pop" in op
            denom = (vcount * vcount if pop
                     else vcount * (vcount - 1))
            var = _var_cache[cache_key] / jnp.maximum(
                denom, 1).astype(jnp.float64)
            out_val = jnp.sqrt(var) if op.startswith("std") else var
            out_cols.append(Column(
                DType(TypeId.FLOAT64), out_val,
                vcount > (0 if pop else 1)
            ))
            continue
        if op in ("var", "std", "var_pop", "std_pop"):
            # variance (Spark var_samp/stddev_samp/var_pop/stddev_pop):
            # two-pass centered form in float64 for numerical robustness;
            # the centered second moment M2 is computed once per column
            # and shared by all four variants (the _rank_order_cache
            # pattern). The group sum came from the lane machinery (exact
            # int64 for integral/decimal storage); the centered second
            # pass is one more _seg_sums lane — zero scatters end to end.
            # NB: TPU f64 is f32-pair emulated (~49-bit mantissa) —
            # documented precision posture, matching the mean contract.
            cache_key = id(c)
            if cache_key not in _var_cache:
                scale_f = (10.0 ** c.dtype.scale) if c.dtype.is_decimal \
                    else 1.0
                denom = jnp.maximum(vcount, 1).astype(jnp.float64)
                mean_g = seg_col(val_lane).astype(jnp.float64) * scale_f \
                    / denom
                if n:
                    x = c.data.astype(jnp.float64) * scale_f
                    centered = jnp.where(
                        c.valid_mask(), x - per_row(mean_g), 0.0)
                    m2 = _seg_sums((centered * centered)[:, None])[:, 0]
                else:
                    m2 = jnp.zeros((m,), jnp.float64)
                _var_cache[cache_key] = m2
            pop = op.endswith("_pop")
            var = _var_cache[cache_key] / jnp.maximum(
                vcount - (0 if pop else 1), 1).astype(jnp.float64)
            out_val = jnp.sqrt(var) if op.startswith("std") else var
            out_cols.append(Column(
                DType(TypeId.FLOAT64), out_val,
                vcount > (0 if pop else 1)
            ))
            continue
        if op in ("covar_samp128pair", "covar_pop128pair",
                  "corr128pair"):
            # exact DECIMAL128(-compatible) covariance/correlation: the
            # numerator n·ΣXY − ΣX·ΣY is assembled in sign-magnitude
            # base-2^16 limb arithmetic (|terms| ≤ n²·2^254 < 2^317,
            # 25-limb vectors) and rounded to float64 once. corr divides
            # by the exact variance numerators, so the decimal scales
            # cancel identically.
            cy = acc_dt
            sx_specs, sy_specs, sxy_specs, sq_specs = val_lane
            WIDTH = 25
            pair_key = (id(c), id(cy), "128pair")

            def _norm_sums():
                # normalized sign-magnitude ΣX / ΣY (shared by the
                # numerator and corr's variance terms)
                if (pair_key, "sums") not in _covar_cache:
                    sxl, cxc = _carry_norm16(
                        [seg_col(i) for i in sx_specs], 12)
                    sx_neg = cxc < 0
                    sxl = _negate_limbs16_if(sxl, sx_neg)
                    syl, cyc = _carry_norm16(
                        [seg_col(i) for i in sy_specs], 12)
                    sy_neg = cyc < 0
                    syl = _negate_limbs16_if(syl, sy_neg)
                    _covar_cache[(pair_key, "sums")] = (
                        sxl, sx_neg, syl, sy_neg)
                return _covar_cache[(pair_key, "sums")]

            if (pair_key, "num") not in _covar_cache:
                sxl, sx_neg, syl, sy_neg = _norm_sums()
                sxyl, cxyc = _carry_norm16(
                    [seg_col(i) for i in sxy_specs], 20)
                sxy_neg = cxyc < 0
                sxyl = _negate_limbs16_if(sxyl, sxy_neg)
                # A = n·|ΣXY| (sign sxy_neg), B = |ΣX|·|ΣY| (sign xor)
                a_mag, _ = _carry_norm16(
                    [l * vcount for l in sxyl], WIDTH)
                b_mag, _ = _carry_norm16(_conv_limbs16(sxl, syl), WIDTH)
                n_mag, n_neg = _signed_sub_limbs16(
                    a_mag, sxy_neg, b_mag, sx_neg != sy_neg)
                _covar_cache[(pair_key, "num")] = (
                    jnp.where(n_neg, -1.0, 1.0) * _limbs16_to_f64(n_mag))
            num = _covar_cache[(pair_key, "num")]
            var_nums = None
            if sq_specs is not None:
                if (pair_key, "varnums") not in _covar_cache:
                    sxl, _sxn, syl, _syn = _norm_sums()
                    vn = []
                    for sq, sl in ((sq_specs[0], sxl),
                                   (sq_specs[1], syl)):
                        ql, _ = _carry_norm16(
                            [seg_col(i) for i in sq], 20)
                        nq, _ = _carry_norm16(
                            [q * vcount for q in ql], WIDTH)
                        bsq, _ = _carry_norm16(
                            _conv_limbs16(sl, sl), WIDTH)
                        vn.append(
                            _limbs16_to_f64(_sub_limbs16(nq, bsq)))
                    _covar_cache[(pair_key, "varnums")] = vn
                var_nums = _covar_cache[(pair_key, "varnums")]
            scale = sum((cc.dtype.scale if cc.dtype.is_decimal else 0)
                        for cc in (c, cy))
            if op == "corr128pair":
                # scales cancel between numerator and the sqrt of the
                # variance-numerator product
                out_val = num / jnp.sqrt(var_nums[0] * var_nums[1])
                validity = vcount > 0
            elif op == "covar_pop128pair":
                out_val = num / jnp.maximum(
                    vcount * vcount, 1).astype(jnp.float64) \
                    * (10.0 ** scale)
                validity = vcount > 0
            else:
                out_val = num / jnp.maximum(
                    vcount * (vcount - 1), 1).astype(jnp.float64) \
                    * (10.0 ** scale)
                validity = vcount > 1
            out_cols.append(
                Column(DType(TypeId.FLOAT64), out_val, validity))
            continue
        if op in ("covar_samp", "covar_pop", "corr"):
            # pairwise centered moments Σcx·cy, Σcx², Σcy² in one
            # _seg_sums pass (float64 two-pass form, the var posture),
            # cached per column pair so corr + sibling covar aggs share
            # it. vcount here is the BOTH-non-null count (Spark's
            # Covariance/Corr row semantics).
            cy = acc_dt
            spec_x, spec_y = val_lane
            cache_key = (id(c), id(cy))
            if cache_key not in _covar_cache:
                sfx = (10.0 ** c.dtype.scale) if c.dtype.is_decimal else 1.0
                sfy = (10.0 ** cy.dtype.scale) if cy.dtype.is_decimal \
                    else 1.0
                denom = jnp.maximum(vcount, 1).astype(jnp.float64)
                mean_x = seg_col(spec_x).astype(jnp.float64) * sfx / denom
                mean_y = seg_col(spec_y).astype(jnp.float64) * sfy / denom
                if n:
                    both = c.valid_mask() & cy.valid_mask()
                    cxv = jnp.where(
                        both,
                        c.data.astype(jnp.float64) * sfx - per_row(mean_x),
                        0.0)
                    cyv = jnp.where(
                        both,
                        cy.data.astype(jnp.float64) * sfy - per_row(mean_y),
                        0.0)
                    moments = _seg_sums(jnp.stack(
                        [cxv * cyv, cxv * cxv, cyv * cyv], axis=1))
                else:
                    moments = jnp.zeros((m, 3), jnp.float64)
                _covar_cache[cache_key] = moments
            sxy, sxx, syy = (
                _covar_cache[cache_key][:, i] for i in range(3))
            if op == "corr":
                # constant series / singleton groups give 0/0 → NaN, the
                # Spark Corr value posture; only empty groups are null
                out_val = sxy / jnp.sqrt(sxx * syy)
                validity = vcount > 0
            elif op == "covar_pop":
                out_val = sxy / jnp.maximum(vcount, 1).astype(jnp.float64)
                validity = vcount > 0
            else:  # covar_samp: n ≤ 1 is null (the var_samp posture)
                out_val = sxy / jnp.maximum(
                    vcount - 1, 1).astype(jnp.float64)
                validity = vcount > 1
            out_cols.append(
                Column(DType(TypeId.FLOAT64), out_val, validity))
            continue
        if op == "nunique":
            # distinct non-null values per group: secondary sort by
            # (keys, value) with value nulls last; count positions that
            # start a new valid value run within their group
            col_idx2 = val_lane  # original column index stashed in plan
            nf = [True] * len(keys) + [False]
            order2 = sort_order(table, list(keys) + [col_idx2],
                                nulls_first=nf, row_valid=rv)
            sub_cols, sub_rv = permute(
                [table.column(k) for k in keys] + [table.column(col_idx2)],
                order2, [] if rv is None else [rv])
            sub = Table(sub_cols)
            kix = list(range(len(keys)))
            same_k = _rows_equal_prev(sub, kix)
            if rv is not None:
                # phantom rows merge into the last group here too
                same_k = same_k | ~sub_rv[0]
            vcol = sub.column(len(keys))
            vvalid2 = vcol.valid_mask()
            eqv = _col_values_equal_prev(vcol)
            prev_same_valid = jnp.concatenate(
                [jnp.zeros((1,), jnp.bool_), eqv & vvalid2[:-1]])
            flag = vvalid2 & (~same_k | ~prev_same_valid)
            # this sort orders the keys as the first did and only the rows
            # inside a group differently, so its groups lie at the same
            # rows: per-group flag counts are cumsum differences at the
            # bounds the function already holds, no scatter
            if n:
                cnt = _range_sums_from_cumsum(
                    jnp.cumsum(flag.astype(jnp.int64)), g_lo, g_hi)
            else:
                cnt = jnp.zeros((m,), jnp.int64)
            out_cols.append(
                Column(acc_dt, cnt, garange < num_groups)
            )
            continue
        if op in ("first", "last", "first_include_nulls",
                  "last_include_nulls"):
            # "first"/"last" skip nulls (Spark First/Last with
            # ignoreNulls=true): a segmented first-valid scan over row
            # indices finds the winning row — one mechanism for every
            # dtype, gathered afterwards. The *_include_nulls variants
            # (Spark's DEFAULT ignoreNulls=false) are simply the group's
            # first/last ROW: g_lo / g_hi - 1, no scan at all. Rows are
            # key-sorted STABLY, so order within a group is input order.
            # ``c`` is the column as it came, unsorted: the winning sorted
            # row ``row`` is its row ``order[row]``, so its data and (for
            # the *_include_nulls variants) its validity are read at one
            # row a group and never brought into key order.
            valid = val_lane
            if op.endswith("_include_nulls"):
                if op.startswith("first"):
                    win = jnp.where(g_hi > g_lo, g_lo.astype(jnp.int64),
                                    jnp.int64(-1))
                else:
                    win = jnp.where(g_hi > g_lo,
                                    (g_hi - 1).astype(jnp.int64),
                                    jnp.int64(-1))
                has = (win >= 0)
                if n:
                    row = order[jnp.clip(win, 0, n - 1).astype(jnp.int32)]
                    if c.validity is not None:
                        has = has & c.validity[row]
                else:
                    row = jnp.zeros((m,), jnp.int32)
                    has = jnp.zeros((m,), jnp.bool_)
            elif n:
                row_idx = jnp.arange(n, dtype=jnp.int64)
                cand = jnp.where(valid, row_idx, jnp.int64(-1))

                if op == "first":
                    def combine(a, b):
                        av, af = a
                        bv, bf = b
                        return jnp.where(
                            bf, bv, jnp.where(av >= 0, av, bv)), af | bf
                else:
                    def combine(a, b):
                        av, af = a
                        bv, bf = b
                        return jnp.where(
                            bf, bv, jnp.where(bv >= 0, bv, av)), af | bf

                run, _ = jax.lax.associative_scan(combine, (cand, ~same))
                win = run[jnp.clip(g_hi - 1, 0, n - 1)]
                has = (win >= 0) & (g_hi > g_lo)
                row = order[jnp.clip(win, 0, n - 1).astype(jnp.int32)]
            else:
                has = jnp.zeros((m,), jnp.bool_)
                row = jnp.zeros((m,), jnp.int32)
            if c.dtype.is_string:
                from spark_rapids_jni_tpu.ops import strings as s

                if n:
                    g = s.gather_strings(c, row)
                    out_cols.append(Column(c.dtype, g.data, has,
                                           chars=g.chars))
                else:
                    out_cols.append(Column(
                        c.dtype, jnp.zeros((m,), jnp.int32), has,
                        chars=jnp.zeros((m, 1), jnp.uint8)))
            elif n:
                out_cols.append(Column(c.dtype, c.data[row], has))
            else:
                shape = (m, 2) if c.dtype.is_decimal128 else (m,)
                out_cols.append(Column(
                    c.dtype, jnp.zeros(shape, c.data.dtype), has))
            continue
        # min / max with null-neutral sentinels
        if c.dtype.is_string or c.dtype.is_decimal128:
            out_cols.append(_rank_minmax(c, op, vcount))
            continue
        sentinel = minmax_sentinel(c.dtype, op)
        vv = jnp.where(c.valid_mask(), c.data,
                       jnp.asarray(sentinel, c.data.dtype))
        if in_place:
            red = slots.extremum(vv, op, sentinel)
        elif n:
            run = _segmented_extremum(vv, ~same, op)
            red = run[jnp.clip(g_hi - 1, 0, n - 1)]
        else:
            red = jnp.zeros((m,), c.data.dtype)
        out_cols.append(Column(c.dtype, red, vcount > 0))

    return GroupByResult(Table(out_cols), num_groups, overflowed,
                         sum128_overflow, in_place, key_sorted, key_one_word)


@func_range("groupby_aggregate")
def groupby_aggregate(
    table: Table,
    keys: Sequence[int],
    aggs: Sequence[tuple[int, str]],
    max_groups: int | None = None,
    row_valid: jnp.ndarray | None = None,
) -> GroupByResult:
    """Group by `keys`; compute [(value_col, op)] aggregates.

    Returns keys + one column per agg, in order, padded to ``max_groups``
    rows (default: n, which can never overflow). A smaller ``max_groups``
    bounds output memory for high-cardinality aggregation; if the true
    group count exceeds it, rows of the excess groups are dropped and
    ``overflowed`` is set so the host can grow and retry
    (``groupby_aggregate_auto``).

    Rows where ``row_valid`` is False are phantom rows (masked shuffle
    slots): they contribute to no group and no aggregate.
    """
    for _, op in aggs:
        if isinstance(op, tuple):
            if (len(op) != 2 or op[0] not in SUPPORTED_BINARY_AGGS
                    or not isinstance(op[1], numbers.Integral)
                    or not 0 <= op[1] < table.num_columns):
                raise ValueError(
                    f"unsupported binary aggregation {op!r}; expected "
                    f"(op, col_y) with op in {SUPPORTED_BINARY_AGGS} and "
                    f"col_y a column index of the input table")
        elif op not in SUPPORTED_AGGS:
            raise ValueError(f"unsupported aggregation {op!r}")
    keys_t = tuple(int(k) for k in keys)
    aggs_t = tuple(
        (int(c), (tuple(op) if isinstance(op, tuple) else op))
        for c, op in aggs)
    # last_include_nulls is POSITIONAL (the group's literal last row):
    # a padded tail row would be that last row, so such plans run at
    # exact shape (memoized, just not bucketed)
    bucket = not any(op == "last_include_nulls" for _, op in aggs_t)
    from spark_rapids_jni_tpu.runtime import dispatch

    return dispatch.call(
        "groupby_aggregate",
        partial(_groupby_aggregate_impl, keys=keys_t, aggs=aggs_t,
                max_groups=max_groups),
        ((table, row_valid),),
        statics=(keys_t, aggs_t, max_groups),
        slice_rows=(max_groups is None),
        bucket_rows=bucket)


def groupby_aggregate_auto(
    table: Table,
    keys: Sequence[int],
    aggs: Sequence[tuple[int, str]],
    initial_max_groups: int,
    growth: int = 4,
) -> GroupByResult:
    """Host-level grow-and-retry around the cardinality bound: start at
    ``initial_max_groups`` and multiply by ``growth`` until the result fits
    (capped at n, which always fits). Each retry recompiles for the new
    static bound — the bucketed-padding discipline, applied to output
    cardinality. Growth runs through the shared resilience ladder
    (``runtime/resilience.escalate``, rung ``grow_capacity``) with the
    capacity schedule — min(initial·growth^k, n) — preserved exactly; with
    ``resilience.enabled=false`` the pre-resilience loop runs verbatim."""
    from spark_rapids_jni_tpu.runtime import resilience

    n = table.num_rows
    m = max(1, int(initial_max_groups))
    if not resilience.enabled() or n < 1:
        while True:
            res = groupby_aggregate(table, keys, aggs, max_groups=min(m, n))
            if m >= n or not bool(res.overflowed):
                return res
            m *= growth

    def _attempt(cap):
        res = groupby_aggregate(table, keys, aggs, max_groups=cap)
        # cap == n always fits (distinct groups <= rows): never grow past it
        return res, cap < n and bool(res.overflowed), None

    return resilience.escalate(
        "groupby_aggregate_auto", _attempt, seam="dispatch.execute",
        initial=m, growth=growth, max_capacity=n, rows=n)


@func_range("groupby_percentile")
def groupby_percentile(
    table: Table,
    keys: Sequence[int],
    value_col: int,
    qs: Sequence[float],
    max_groups: int | None = None,
) -> GroupByResult:
    """Exact per-group percentiles (Spark `percentile` semantics: linear
    interpolation between closest ranks over non-null values; median is
    qs=[0.5]). Output: keys + one FLOAT64 column per q.

    Sort-based order statistics: ONE sort by (keys..., value) with value
    nulls last, so each group's valid values occupy a contiguous run
    [g_lo, g_lo + cnt); every percentile is then two gathers at computed
    offsets — no scatters, no per-group loops. Exact, unlike HLL-style
    sketches; the reference's capability family is cuDF's
    quantile/median groupby (vendored surface, SURVEY.md section 2.2).
    """
    qs = [float(q) for q in qs]
    if not qs or any(q < 0.0 or q > 1.0 for q in qs):
        raise ValueError("percentile fractions must be in [0, 1]")
    c_in = table.column(value_col)
    if c_in.dtype.is_string or c_in.dtype.is_decimal128:
        raise NotImplementedError(
            "groupby_percentile needs fixed-width numeric values")
    n = table.num_rows
    m = n if max_groups is None else int(max_groups)
    sort_keys = list(keys) + [value_col]
    order = sort_order(
        table, sort_keys,
        nulls_first=[True] * len(keys) + [False])
    # the keys and the value in that order; no other column moves
    *sorted_keys, c = permute(
        [table.column(i) for i in sort_keys], order)[0]
    sorted_keys, key_at = Table(sorted_keys), range(len(keys))
    same = _rows_equal_prev(sorted_keys, key_at)
    num_groups, g_lo, g_hi = _group_bounds(same, m)
    overflowed = num_groups > m
    first_idx = jnp.where(g_hi > g_lo, g_lo, n)
    out_cols = _gather_group_keys(sorted_keys, key_at, first_idx, m, n)

    if n:
        vcum = jnp.cumsum(c.valid_mask().astype(jnp.int64))
        upper = vcum[jnp.clip(g_hi - 1, 0, n - 1)]
        base = jnp.where(g_lo > 0, vcum[jnp.clip(g_lo - 1, 0, n - 1)], 0)
        cnt = jnp.where(g_hi > g_lo, upper - base, 0)
    else:
        cnt = jnp.zeros((m,), jnp.int64)
    vals = c.data.astype(jnp.float64)
    if c.dtype.is_decimal:
        vals = vals * (10.0 ** c.dtype.scale)
    group_ok = cnt > 0
    for q in qs:
        p = q * (cnt - 1).astype(jnp.float64)
        lo_off = jnp.floor(p).astype(jnp.int64)
        frac = p - lo_off.astype(jnp.float64)
        i0 = g_lo.astype(jnp.int64) + lo_off
        i1 = g_lo.astype(jnp.int64) + jnp.minimum(lo_off + 1, cnt - 1)
        safe = lambda i: jnp.clip(i, 0, max(n - 1, 0)).astype(jnp.int32)
        if n:
            v0 = vals[safe(i0)]
            v1 = vals[safe(i1)]
            out = v0 * (1.0 - frac) + v1 * frac
        else:
            out = jnp.zeros((m,), jnp.float64)
        out_cols.append(Column(DType(TypeId.FLOAT64), out, group_ok))
    return GroupByResult(Table(out_cols), num_groups, overflowed)


def bounded_group_layout(domain_lens: Sequence[int]):
    """Static (trace-time) layout of the bounded-groupby output.

    One slot per combination of (domain value | null) per key:
    ``m = prod(len+1)``. Returns ``(sizes, m, codes, order)`` where
    ``codes[g, pos]`` is key ``pos``'s domain index for group ``g``
    (``== domain_lens[pos]`` means the null slot) and ``order`` is the
    output permutation — real-key groups first in lexicographic key
    order, null-key groups after (the ORDER BY ... NULLS LAST every
    consumer wants, at zero device cost). Shared by
    ``groupby_aggregate_bounded`` and the planner's string-key decoding
    (ops/planner.py) so the two can never disagree about slot layout.
    """
    sizes = [int(l) + 1 for l in domain_lens]
    m = int(np.prod(sizes)) if sizes else 1
    codes = np.zeros((m, len(sizes)), dtype=np.int64)
    for pos, size in enumerate(sizes):
        stride = int(np.prod(sizes[pos + 1:])) or 1
        codes[:, pos] = (np.arange(m) // stride) % size
    has_null = (codes == (np.asarray(sizes) - 1)).any(axis=1) \
        if sizes else np.zeros((m,), bool)
    order = np.asarray(
        sorted(range(m), key=lambda g: (bool(has_null[g]), g)),
        dtype=np.int64)
    return sizes, m, codes, order


class _XlaBoundedAccumulator:
    """The accumulate of the bounded groupby: one masked whole-column
    reduction per (group, lane), which XLA fuses into a single pass
    over the rows."""

    def __init__(self, table: Table, gid: jnp.ndarray, n: int, m: int):
        self._table = table
        self._n = n
        self._m = m
        # one (n,) bool per group, built once and shared by all
        # aggregates
        self._masks = [gid == g for g in range(m)] if n else None

    def _per_group(self, vals: jnp.ndarray, reduce_fn, neutral):
        if self._n == 0:
            return jnp.full((self._m,), neutral, vals.dtype)
        return jnp.stack([
            reduce_fn(jnp.where(self._masks[g], vals, neutral))
            for g in range(self._m)
        ])

    def rows_per_group(self) -> jnp.ndarray:
        return self._per_group(
            jnp.ones((self._n,), jnp.int64), jnp.sum, jnp.int64(0))

    def vcount(self, col_idx: int) -> jnp.ndarray:
        valid = self._table.column(col_idx).valid_mask()
        return self._per_group(
            valid.astype(jnp.int64), jnp.sum, jnp.int64(0))

    def sum_int(self, col_idx: int) -> jnp.ndarray:
        c = self._table.column(col_idx)
        vv_zero = jnp.where(c.valid_mask(), c.data, jnp.zeros_like(c.data))
        return self._per_group(
            vv_zero.astype(jnp.int64), jnp.sum, jnp.int64(0))

    def sum_float(self, col_idx: int) -> jnp.ndarray:
        c = self._table.column(col_idx)
        vv_zero = jnp.where(c.valid_mask(), c.data, jnp.zeros_like(c.data))
        return self._per_group(
            vv_zero.astype(jnp.float64), jnp.sum, jnp.float64(0))

    def minmax(self, col_idx: int, op: str) -> jnp.ndarray:
        c = self._table.column(col_idx)
        sentinel = minmax_sentinel(c.dtype, op)
        vv = jnp.where(
            c.valid_mask(), c.data, jnp.asarray(sentinel, c.data.dtype))
        return self._per_group(vv, jnp.min if op == "min" else jnp.max,
                               jnp.asarray(sentinel, c.data.dtype))


class BoundedGroupByResult(NamedTuple):
    """Output of groupby_aggregate_bounded: one row per domain combination
    (null slots included), in a STATIC order — real-key groups first in
    lexicographic key order, null-key groups after (the q1 ORDER BY comes
    free). Empty combinations carry validity False everywhere."""

    table: Table
    # bool[m]: at least one input row landed in this group
    present: jnp.ndarray
    # scalar bool: some row's key value was outside its declared domain
    # (and not null) — that row is in NO group; the caller must re-plan
    # with the general groupby (the narrowing_overflow posture)
    domain_miss: jnp.ndarray


@func_range("groupby_aggregate_bounded")
def groupby_aggregate_bounded(
    table: Table,
    keys: Sequence[int],
    aggs: Sequence[tuple[int, str]],
    key_domains: Sequence[Sequence[int]],
    row_valid: Optional[jnp.ndarray] = None,
) -> BoundedGroupByResult:
    """Groupby with PLANNER-DECLARED key domains: zero sort, zero gather,
    zero scan, zero scatter — one streaming pass.

    The general groupby's cost on TPU is the key sort + row gather +
    boundary machinery (sort 55 ms + gather 32 ms of the ~280 ms q1
    iteration at 4M rows on a v5e in 2026-07, before the runtime stack;
    since PR 33 the general path bounded at q1's 64 groups takes 0.039 s
    of device time for 8,388,608 padded rows, 0.023 s of it the key sort,
    the planned region 0.0058 s: PERF.md section 5). When the planner
    knows each key column's candidate values (dictionary stats; CHAR(1)
    flag domains in TPC-H q1), dense group ids come from a searchsorted against the tiny
    sorted domain and every aggregate is a masked whole-column reduction
    per group — XLA fuses the per-group masked sums into one multi-output
    reduction pass over the lanes.

    ``key_domains``: one sorted sequence of candidate raw values per key
    column. Each key also gets an implicit NULL slot (Spark: null keys
    form their own group), so m = prod(len(d)+1). Supported aggs: sum,
    count, mean, min, max (the associative single-pass set). Rows whose
    key value is outside its domain land in no group and raise
    ``domain_miss``.

    ``row_valid``: bool[n] marking rows that EXIST — False rows (e.g.
    shard_table padding) join NO group, not even the null slot, and
    never raise ``domain_miss`` (a padding row is not a null-key row —
    the shard_table return_row_valid contract).
    """
    for _, op in aggs:
        if op not in ("sum", "count", "mean", "min", "max"):
            raise ValueError(
                f"groupby_aggregate_bounded supports sum/count/mean/min/"
                f"max, not {op!r} (use groupby_aggregate)"
            )
    if len(key_domains) != len(keys):
        raise ValueError("one domain per key column required")
    n = table.num_rows
    sizes, m, slot_codes, order = bounded_group_layout(
        [len(d) for d in key_domains])

    # dense gid over the domain cross product; miss detection per key
    gid = jnp.zeros((n,), jnp.int32)
    domain_miss = jnp.bool_(False)
    for k, dom in zip(keys, key_domains):
        c = table.column(k)
        if c.dtype.is_string or c.dtype.is_decimal128:
            raise NotImplementedError(
                "bounded-domain keys are fixed-width scalars (pack string "
                "dictionary codes first)"
            )
        dom_arr = jnp.asarray(sorted(dom), c.data.dtype)
        valid = c.valid_mask()
        code = jnp.searchsorted(dom_arr, c.data).astype(jnp.int32)
        hit = (dom_arr[jnp.clip(code, 0, len(dom) - 1)] == c.data)
        miss_rows = valid & ~hit
        if row_valid is not None:
            miss_rows = miss_rows & row_valid
        domain_miss = domain_miss | jnp.any(miss_rows)
        # null slot = len(dom); missed rows park there too but are
        # excluded from every group by the miss flag contract
        code = jnp.where(valid & hit, jnp.clip(code, 0, len(dom) - 1),
                         len(dom))
        gid = gid * (len(dom) + 1) + code
    if row_valid is not None:
        # non-rows (shard padding) match NO group mask, not even null
        gid = jnp.where(row_valid, gid, jnp.int32(m))

    out_cols: list[Column] = []

    acc = _XlaBoundedAccumulator(table, gid, n, m)

    rows_per_group = acc.rows_per_group()
    present = rows_per_group > 0

    # static key materialization: group g's key tuple is known at trace
    # time; null slot -> validity False
    for pos, (k, dom) in enumerate(zip(keys, key_domains)):
        c = table.column(k)
        vals = np.zeros((m,), dtype=np.dtype(c.dtype.storage_dtype))
        kvalid = np.zeros((m,), dtype=bool)
        dom_sorted = sorted(dom)
        for g in range(m):
            code = slot_codes[g, pos]
            if code < len(dom_sorted):
                vals[g] = dom_sorted[code]
                kvalid[g] = True
        out_cols.append(Column(
            c.dtype, jnp.asarray(vals), jnp.asarray(kvalid) & present))

    for col_idx, op in aggs:
        c = table.column(col_idx)
        vcount = acc.vcount(col_idx)
        if op == "count":
            out_cols.append(Column(DType(TypeId.INT64), vcount, present))
            continue
        if op in ("sum", "mean"):
            acc_dt = _sum_dtype(c.dtype)
            if acc_dt.storage_dtype.kind in ("i", "u"):
                total = acc.sum_int(col_idx).astype(acc_dt.jnp_dtype)
            else:
                total = acc.sum_float(col_idx)
            if op == "sum":
                out_cols.append(Column(
                    acc_dt, total.astype(acc_dt.jnp_dtype), vcount > 0))
            else:
                denom = jnp.maximum(vcount, 1).astype(jnp.float64)
                mean = total.astype(jnp.float64) / denom
                if c.dtype.is_decimal:
                    mean = mean * (10.0 ** c.dtype.scale)
                out_cols.append(
                    Column(DType(TypeId.FLOAT64), mean, vcount > 0))
            continue
        # min / max
        red = acc.minmax(col_idx, op)
        out_cols.append(Column(c.dtype, red, vcount > 0))

    # static reorder from the shared layout: real-key groups first
    # (lexicographic), null-key groups after — zero device sort (the
    # permutation is a trace-time constant)
    perm = jnp.asarray(order, jnp.int32)
    out_cols = [
        Column(c.dtype, c.data[perm],
               None if c.validity is None else c.validity[perm])
        for c in out_cols
    ]
    return BoundedGroupByResult(
        Table(out_cols), present[perm], domain_miss)
