"""Multi-key table sort — the cuDF ``sort``/``order_by`` equivalent of the
vendored operator substrate (SURVEY.md section 2.2: libcudf sort is part of
the capability surface; exercised by TPC-H q1's final ORDER BY).

TPU-first design: no comparator kernels. Each key column is *encoded* into
order-preserving unsigned integer words (floats via sign-magnitude flip,
signed ints via sign-bit flip, a 64-bit integer as its two 32-bit halves,
with a null indicator folded in). Keys that pack into two 32-bit words
are one ``jnp.argsort`` / ``jnp.lexsort`` (XLA's variadic sort, a
comparator over every operand); wider keys are sorted one word at a time
(``_radix_order``), because the TPU compiler's time for a variadic sort
grows with about the square of its operand words (PERF.md section 6, PR
28). A gather follows. Encoded keys also give Spark-compatible total
float order (NaN sorts greatest, -0.0 == 0.0 is NOT collapsed: -0.0 < 0.0
bitwise — documented deviation from Java's Double.compare only for -0.0).
"""

from __future__ import annotations

from functools import partial
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from spark_rapids_jni_tpu.columnar import Column, Table
from spark_rapids_jni_tpu.types import DType
from spark_rapids_jni_tpu.utils.tracing import func_range


def _as_unsigned_key(col_data: jnp.ndarray, dtype: DType) -> jnp.ndarray:
    """Encode one column as an order-preserving uint key (uint32 or uint64)."""
    np_dt = dtype.storage_dtype
    if np_dt.kind == "u":
        return col_data
    if np_dt.kind == "i":
        bits = np_dt.itemsize * 8
        u = col_data.astype(jnp.dtype(f"uint{bits}"))
        return u ^ jnp.asarray(1 << (bits - 1), dtype=u.dtype)
    if np_dt == np.float32:
        u = jax.lax.bitcast_convert_type(col_data, jnp.uint32)
        sign = (u >> 31).astype(jnp.uint32)
        # negative: flip all bits; positive: flip sign bit
        enc = u ^ jnp.where(sign == 1, jnp.uint32(0xFFFFFFFF), jnp.uint32(0x80000000))
        # Canonicalize every NaN (either sign) above +inf: Spark treats NaN
        # as one greatest value; a negative NaN's payload would otherwise
        # sort smallest and split NaN groups in groupby.
        return jnp.where(jnp.isnan(col_data), jnp.uint32(0xFFFFFFFF), enc)
    # float64 never reaches here: _key_arrays routes it to the value-level
    # two-key encoding (no 64-bit bitcast on TPU).
    raise TypeError(f"unsupported sort key type {dtype}")


def _key_arrays(col: Column, ascending: bool, nulls_first: bool):
    """Return the lexsort key(s) for one column, minor-to-major order.

    Null rows' VALUE keys are forced to a constant: a null cell's stored
    bytes are unspecified (Column contract), and letting them order the
    null run would split the null group across clusters once later sort
    keys reset between them — adjacent-equality consumers (groupby,
    distinct, rank encoding) would then see several "null groups" where
    SQL semantics require one. With the constant, null rows tie on this
    column and order by the remaining keys, like any other equal run.
    """
    dtype = col.dtype
    valid = col.valid_mask()

    def null_const(keys):
        return [jnp.where(valid, k, jnp.zeros((), k.dtype)) for k in keys]

    if dtype.is_decimal128:
        # limb-pair compare: unsigned low limb minor, sign-flipped high limb
        # major — uint ordering on the pair == 128-bit integer ordering
        lo_u = col.data[:, 0].astype(jnp.uint64)
        hi_u = col.data[:, 1].astype(jnp.uint64) ^ jnp.uint64(1 << 63)
        value_keys = [lo_u, hi_u]
        if not ascending:
            value_keys = [~k for k in value_keys]
        null_key = jnp.where(valid, jnp.uint8(1), jnp.uint8(0))
        null_rank = null_key if nulls_first else jnp.uint8(1) - null_key
        return null_const(value_keys) + [null_rank]
    if dtype.is_string:
        from spark_rapids_jni_tpu.ops import strings as s

        value_keys = s.packed_sort_keys(col)
        if not ascending:
            value_keys = [~k for k in value_keys]
        null_key = jnp.where(valid, jnp.uint8(1), jnp.uint8(0))
        null_rank = null_key if nulls_first else jnp.uint8(1) - null_key
        return null_const(value_keys) + [null_rank]

    np_dt = dtype.storage_dtype
    n = col.size

    if np_dt == np.float64:
        # value-level key: works on all backends, Spark order for NaN
        v = col.data
        neg = jnp.where(jnp.isnan(v), jnp.inf, v)
        key = -neg if not ascending else neg
        # NaN: +inf surrogate already sorts greatest ascending; descending
        # -(+inf) = -inf sorts first, matching Spark's NaN-greatest order.
        nan_rank = jnp.isnan(v)
        value_keys = [key, (~nan_rank if not ascending else nan_rank)]
    elif np_dt.kind in "iu" and np_dt.itemsize == 8:
        # a 64-bit integer key as its low and high 32-bit words (sign flip
        # on the high word): uint order on the pair is the 64-bit order,
        # with no emulated 64-bit compare, and _pack_lex_keys can fold
        # null ranks and the row-valid bit into the words
        flip = jnp.uint32(0x80000000 if np_dt.kind == "i" else 0)
        value_keys = [col.data.astype(jnp.uint32),
                      (col.data >> 32).astype(jnp.uint32) ^ flip]
        if not ascending:
            value_keys = [~k for k in value_keys]
    else:
        u = _as_unsigned_key(col.data, dtype)
        if not ascending:
            u = ~u
        value_keys = [u]

    null_key = jnp.where(valid, jnp.uint8(1), jnp.uint8(0))
    if nulls_first:
        null_rank = null_key  # nulls (0) first
    else:
        null_rank = jnp.uint8(1) - null_key  # valids (0) first
    del n
    return null_const(value_keys) + [null_rank]  # null rank most significant


def _key_bits(arr: jnp.ndarray) -> int | None:
    """Bit width of a lexsort key array, or None if not a packable uint."""
    return {
        jnp.dtype(jnp.bool_): 1,
        jnp.dtype(jnp.uint8): 8,
        jnp.dtype(jnp.uint16): 16,
        jnp.dtype(jnp.uint32): 32,
    }.get(jnp.dtype(arr.dtype))


def _pack_lex_keys(lex_keys: list[jnp.ndarray]) -> list[jnp.ndarray]:
    """Fuse minor->major unsigned lex keys into as few words as possible.

    A variadic lexsort pays a multi-operand comparator per sort pass; when
    the combined key fits one machine word (the common relational case:
    a couple of flag/dictionary/date keys plus null ranks), packing them
    into a single uint32 collapses the whole thing to one single-key
    argsort, which XLA sorts substantially faster. 64-bit packs use a
    (hi, lo) uint32 pair rather than uint64 — int64 is emulated on the
    TPU VPU, and two 32-bit keys lexsort faster than one emulated 64-bit.
    """
    widths = [_key_bits(a) for a in lex_keys]
    if any(w is None for w in widths) or sum(widths) > 64:
        return lex_keys
    total = sum(widths)

    def fold(keys: list[jnp.ndarray]) -> jnp.ndarray:
        # keys are minor -> major: the LAST is the most significant field
        acc = None
        for a in reversed(keys):
            w = _key_bits(a)
            a32 = a.astype(jnp.uint32)
            acc = a32 if acc is None else (acc << w) | a32
        return acc

    if total <= 32:
        return [fold(lex_keys)]
    # split the minor->major run into a low word and a high word, each
    # <=32 bits; if the high run cannot fit its own word (e.g. a 32-bit
    # value key + null rank landing together), packing is not possible
    lo_run, bits = [], 0
    for i, a in enumerate(lex_keys):
        w = _key_bits(a)
        if bits + w > 32:
            if sum(widths[i:]) > 32:
                return lex_keys
            return [fold(lo_run), fold(lex_keys[i:])]
        lo_run.append(a)
        bits += w
    raise AssertionError("unreachable: total > 32 must split")


def _pack_words(lex_keys: list[jnp.ndarray]) -> list[jnp.ndarray]:
    """Minor->major packable keys (``_key_bits``) as one bit string, low
    bits first, cut into uint32 words, minor -> major: as few words as the
    keys' widths allow, a key straddling two words where it must. uint
    order on the words, the last the most significant, is the keys'
    lexicographic order."""
    words, acc, used = [], None, 0
    for a in lex_keys:
        w, a32 = _key_bits(a), a.astype(jnp.uint32)
        if acc is None or used == 32:
            if acc is not None:
                words.append(acc)
            acc, used = a32, w
        elif used + w <= 32:
            acc, used = acc | (a32 << used), used + w
        else:   # the key's low bits fill this word, the rest open the next
            words.append(acc | (a32 << used))
            acc, used = a32 >> (32 - used), used + w - 32
    return words + [acc]


def _radix_order(words: list[jnp.ndarray]) -> jnp.ndarray:
    """The stable order by uint32 ``words`` (minor -> major) as one stable
    single-key sort a word, least significant first, each over the rows
    in the order the passes before it left: the same permutation as one
    sort comparing every word, from one two-operand sort in a loop. XLA's
    TPU compiler takes about the square of a sort's operand words in
    compile time whatever the row count (chip readings: PERF.md section
    6, PR 28), so a variadic sort of eight operands does not compile in a
    time a cold start can pay; this compiles in the same time for any
    number of words."""
    stacked = jnp.stack(words)

    def one_pass(i, order):
        word = jax.lax.dynamic_index_in_dim(stacked, i, 0, keepdims=False)
        return jax.lax.sort((word[order], order), num_keys=1,
                            is_stable=True)[1]

    # the rows' own order to start from; taken through a word so that under
    # shard_map the carry varies over the same mesh axes going in as out
    start = jax.lax.iota(jnp.int32, stacked.shape[1]) + (
        words[0] & jnp.uint32(0)).astype(jnp.int32)
    return jax.lax.fori_loop(0, len(words), one_pass, start)


def _sort_order_impl(row_args, aux, rvs, *, keys, ascending, nulls_first):
    ((table, row_valid),) = row_args
    # phantom rows (padded tails, masked shuffle slots): rank them AFTER
    # every real row with one extra most-significant key; the sort is
    # stable, so the leading entries are exactly the real rows' stable
    # permutation — bit-identical to the unpadded sort after slicing.
    rv = row_valid
    if rv is None and rvs is not None:
        rv = rvs[0]
    lex_keys: list[jnp.ndarray] = []
    # jnp.lexsort treats the LAST key as primary; build minor -> major.
    for k, asc, nf in zip(reversed(list(keys)), reversed(list(ascending)),
                          reversed(list(nulls_first))):
        lex_keys.extend(_key_arrays(table.column(k), asc, nf))
    if rv is not None:
        lex_keys.append(jnp.where(rv, jnp.uint8(0), jnp.uint8(1)))
    packable = all(_key_bits(a) is not None for a in lex_keys)
    lex_keys = _pack_lex_keys(lex_keys)
    if len(lex_keys) == 1:
        return jnp.argsort(lex_keys[0], stable=True).astype(jnp.int32)
    if packable and len(lex_keys) > 2:
        # wider than the two words one variadic sort takes well
        return _radix_order(_pack_words(lex_keys))
    return jnp.lexsort(tuple(lex_keys)).astype(jnp.int32)


@func_range("sort_order")
def sort_order(
    table: Table,
    keys: Sequence[int],
    ascending: Sequence[bool] | None = None,
    nulls_first: Sequence[bool] | None = None,
    row_valid: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Stable sort permutation (int32) ordering rows by the key columns.
    Rows where ``row_valid`` is False sort after every real row (used by
    callers that carry phantom rows, e.g. bounded shuffles)."""
    if ascending is None:
        ascending = [True] * len(keys)
    if nulls_first is None:
        nulls_first = [True] * len(keys)
    from spark_rapids_jni_tpu.runtime import dispatch

    return dispatch.call(
        "sort_order",
        partial(_sort_order_impl, keys=tuple(keys),
                ascending=tuple(ascending), nulls_first=tuple(nulls_first)),
        ((table, row_valid),),
        statics=(tuple(keys), tuple(ascending), tuple(nulls_first)))


def gather(table: Table, indices: jnp.ndarray) -> Table:
    """Row gather — the cuDF gather primitive. Out-of-range indices are
    clamped by XLA (callers pass valid permutations)."""
    cols = []
    for c in table.columns:
        if c.dtype.is_string:
            from spark_rapids_jni_tpu.ops import strings as s

            cols.append(s.gather_strings(c, indices))
            continue
        validity = None if c.validity is None else c.validity[indices]
        cols.append(Column(c.dtype, c.data[indices], validity))
    return Table(cols)


@func_range("sort_table")
def sort_table(
    table: Table,
    keys: Sequence[int],
    ascending: Sequence[bool] | None = None,
    nulls_first: Sequence[bool] | None = None,
) -> Table:
    return gather(table, sort_order(table, keys, ascending, nulls_first))
