"""Multi-key table sort — the cuDF ``sort``/``order_by`` equivalent of the
vendored operator substrate (SURVEY.md section 2.2: libcudf sort is part of
the capability surface; exercised by TPC-H q1's final ORDER BY).

TPU-first design: no comparator kernels. Each key column is *encoded* into
order-preserving unsigned integer words (floats via sign-magnitude flip,
signed ints via sign-bit flip, a 64-bit integer as its two 32-bit halves,
with a null indicator folded in). Keys that pack into one 32-bit word are
one ``jnp.argsort``, into two one variadic sort of the words and a 32-bit
iota (``_sort_words``: XLA's variadic sort, a comparator over every
operand); wider keys are sorted one word at a time (``_radix_order``),
because the TPU compiler's time for a variadic sort grows with about the
square of its operand words (PERF.md section 6, PR 28). A 64-bit key
whose values a plan declares to span 32 bits or fewer is narrowed before
it gets here (``ops/planner.py narrow_group_keys``); a lone 64-bit key
nobody declared anything about is sorted as ONE word where its values
allow, a fact of the data decided inside the trace (``_lone_key_order``).
Encoded keys also
give Spark-compatible total float order (NaN sorts greatest, -0.0 == 0.0
is NOT collapsed: -0.0 < 0.0 bitwise — documented deviation from Java's
Double.compare only for -0.0).

What follows the sort is a row permutation. ``gather`` takes any indices
and moves every buffer and every byte-wide mask by an element gather of
its own; on a v5e each costs 0.17-0.18 s over 8,388,608 rows inside a
region, whatever its width (PERF.md section 5, PR 29). ``permute`` takes
a permutation only (``sort_order``'s result) and moves packed 32-bit
words once: the sort-path groupby reads through it.
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


def _is_int64(dtype: DType) -> bool:
    """The type's storage is an 8-byte integer (``_key_arrays`` cuts it
    into two words: int64, uint64, decimal64, the 64-bit timestamps)."""
    if dtype.is_decimal128 or dtype.is_string:
        return False
    np_dt = dtype.storage_dtype
    return np_dt.kind in "iu" and np_dt.itemsize == 8


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
    elif _is_int64(dtype):
        # a 64-bit integer key as its low and high 32-bit words (sign flip
        # on the high word): uint order on the pair is the 64-bit order,
        # with no emulated 64-bit compare, and _pack_lex_keys can fold
        # null ranks and the row-valid bit into the words
        flip = jnp.uint32(0x80000000 if np_dt.kind == "i" else 0)
        lo, hi = _split64(col.data)
        value_keys = [lo, hi ^ flip]
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


def _sort_words(words: list[jnp.ndarray]) -> tuple:
    """One stable variadic sort of one or two key words (minor -> major)
    and a 32-bit iota: ``(order, the words in that order)``. What
    ``jnp.argsort`` / ``jnp.lexsort`` run, but for the iota: theirs is
    int64 under x64, two words more to compare and move (8,388,608 rows of
    two key words on a v5e, PERF.md section 6, PR 33: 0.0366 s and 63 s of
    cold compile with an int64 iota, 0.0240 s and 41 s with this one)."""
    iota = jax.lax.iota(jnp.int32, words[0].shape[0])
    *major_first, order = jax.lax.sort(
        (*words[::-1], iota), num_keys=len(words))
    return order, major_first[::-1]


def _radix_order(words: list[jnp.ndarray]) -> jnp.ndarray:
    """The stable order by uint32 ``words`` (minor -> major) as one stable
    single-key sort a word, least significant first, each over the rows
    in the order the passes before it left: the same permutation as one
    sort comparing every word, from one two-operand sort in a loop. XLA's
    TPU compiler takes about the square of a sort's operand words in
    compile time whatever the row count (chip readings: PERF.md section
    6, PR 28), so a variadic sort of eight operands does not compile in a
    time a cold start can pay; this compiles in the same time for any
    number of words. Every pass gathers its word by the running order
    (0.072 s a pass at 8,388,608 rows inside a region, beside 0.018 s for
    the sort itself), so it is for keys that are truly wider than two
    words: several key columns, the sort of planned q3's result (a 64-bit
    revenue and a date), and a lone 64-bit key with no declared range
    only where its values straddle a high word or lie 2**30 or further
    apart (``_lone_key_order``'s other branch)."""
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


def _lex_keys(table: Table, keys, ascending, nulls_first, rv) -> tuple:
    """``(lex keys minor -> major, packed where they pack into 64 bits,
    whether every key is a packable unsigned field)``."""
    # phantom rows (padded tails, masked shuffle slots): rank them AFTER
    # every real row with one extra most-significant key; the sort is
    # stable, so the leading entries are exactly the real rows' stable
    # permutation — bit-identical to the unpadded sort after slicing.
    lex_keys: list[jnp.ndarray] = []
    # jnp.lexsort treats the LAST key as primary; build minor -> major.
    for k, asc, nf in zip(reversed(list(keys)), reversed(list(ascending)),
                          reversed(list(nulls_first))):
        lex_keys.extend(_key_arrays(table.column(k), asc, nf))
    if rv is not None:
        lex_keys.append(jnp.where(rv, jnp.uint8(0), jnp.uint8(1)))
    packable = all(_key_bits(a) is not None for a in lex_keys)
    return _pack_lex_keys(lex_keys), packable


def one_word_span(hi: jnp.ndarray, lo: jnp.ndarray, keyed: jnp.ndarray,
                  bits: int) -> tuple:
    """Whether the 64-bit keys whose uint32 words are ``(hi, lo)`` order
    as their low words alone, rebased, in ``bits`` bits: ``(scalar bool,
    the least low word)`` over the rows ``keyed`` marks, a minimum and a
    maximum of each word. True where those rows hold ONE high word
    between them and low words less than ``2**bits`` apart; with no such
    row every least lies above its most and it is False."""
    least = [jnp.min(jnp.where(keyed, w, jnp.uint32(0xFFFFFFFF)))
             for w in (hi, lo)]
    most = [jnp.max(jnp.where(keyed, w, jnp.uint32(0)))
            for w in (hi, lo)]
    return ((least[0] == most[0]) & (most[1] - least[1] < 1 << bits),
            least[1])


# the bits of a lone 64-bit key's one-word form that hold its rebased low
# word; the null rank and the row-valid rank stand above them
_ONE_WORD_BITS = 30


def _lone_key_order(lex_keys: list, keyed: jnp.ndarray) -> tuple:
    """``(the stable order by a lone 64-bit integer key's lex keys, scalar
    bool: it was sorted as ONE word)``. ``lex_keys`` are ``_key_arrays``'
    encoded low word, high word and null rank and, with a row-valid mask,
    its rank: 72 or 80 bits, three words for ``_radix_order``, each pass a
    gather of a word by the running order. ``keyed`` marks the rows that
    hold a key: valid, and real where a row-valid mask is given.

    A fact of the data decides, inside the trace, by a ``lax.cond``
    (``one_word_span``: two reductions over words the sort reads anyway).
    Where the keyed rows hold one high word and low words less than 2**30
    apart, the order is that of ONE uint32: the low word less the least
    one in bits 0-29 (0 on a row without a key, as ``_key_arrays`` makes a
    null's value), the null rank in bit 30, the row-valid rank in bit 31.
    It is sorted with a 32-bit iota as its second key, so no two rows tie
    and the sort need not be stable (a stable one gets a third operand
    from XLA): two operands, no gather. Descending and unsigned keys are
    the encoded words' business and need no case here; negative keys (one
    high word too) take it as well. Otherwise (keys that straddle a high
    word, keys further apart, no keyed row) ``_radix_order`` runs as ever.

    On the real rows both forms give the same permutation, bit for bit:
    stable, nulls where their rank puts them, ties in row order. Rows a
    row-valid mask calls no rows rank after every real row in both; among
    themselves the one-word form leaves them by null rank and row, not by
    their stored bytes. Both callers that pass a mask merge or mask them
    (``ops/groupby.py _aggregate``: they start no group; ``fusion.Sort``:
    its output's mask is positional)."""
    lo, hi, null_rank, *rv_rank = lex_keys
    fits, lo_least = one_word_span(hi, lo, keyed, _ONE_WORD_BITS)

    def one_word():
        word = (jnp.where(keyed, lo - lo_least, jnp.uint32(0))
                | (null_rank.astype(jnp.uint32) << _ONE_WORD_BITS))
        for rank in rv_rank:
            word = word | (rank.astype(jnp.uint32) << (_ONE_WORD_BITS + 1))
        iota = jax.lax.iota(jnp.int32, word.shape[0])
        return jax.lax.sort((word, iota), num_keys=2, is_stable=False)[1]

    return jax.lax.cond(
        fits, one_word, lambda: _radix_order(_pack_words(lex_keys))), fits


def _sort_order_impl(row_args, aux, rvs, *, keys, ascending, nulls_first):
    """``(order, scalar bool: a lone 64-bit key was sorted as one word)``;
    the flag is False, a constant, for every key outside that gate."""
    ((table, row_valid),) = row_args
    rv = row_valid
    if rv is None and rvs is not None:
        rv = rvs[0]
    lex_keys, packable = _lex_keys(table, keys, ascending, nulls_first, rv)
    one_word = jnp.zeros((), jnp.bool_)
    if len(lex_keys) == 1:
        return jnp.argsort(lex_keys[0], stable=True).astype(jnp.int32), one_word
    if packable and len(lex_keys) == 2:
        # 33 to 64 bits of key, null ranks and row-valid bit: one variadic
        # sort (a groupby key narrowed to its declared range lands here)
        return _sort_words(lex_keys)[0], one_word
    if packable and len(keys) == 1 and table.num_rows and _is_int64(
            table.column(keys[0]).dtype):
        # known while tracing: one key column of 64-bit integers (low
        # word, high word, null rank and row-valid bit never pack)
        keyed = table.column(keys[0]).valid_mask()
        return _lone_key_order(lex_keys, keyed if rv is None else keyed & rv)
    if packable:
        # wider than the two words one variadic sort takes well
        return _radix_order(_pack_words(lex_keys)), one_word
    return jnp.lexsort(tuple(lex_keys)).astype(jnp.int32), one_word


def sort_order(
    table: Table,
    keys: Sequence[int],
    ascending: Sequence[bool] | None = None,
    nulls_first: Sequence[bool] | None = None,
    row_valid: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Stable sort permutation (int32) ordering rows by the key columns.
    Rows where ``row_valid`` is False sort after every real row (used by
    callers that carry phantom rows, e.g. bounded shuffles), in no
    promised order among themselves."""
    return sort_order_and_form(
        table, keys, ascending, nulls_first, row_valid)[0]


@func_range("sort_order")
def sort_order_and_form(
    table: Table,
    keys: Sequence[int],
    ascending: Sequence[bool] | None = None,
    nulls_first: Sequence[bool] | None = None,
    row_valid: jnp.ndarray | None = None,
) -> tuple:
    """``(sort_order(...), scalar bool)``: the permutation, and whether a
    lone 64-bit key was sorted as one word (``_lone_key_order``: a fact of
    the data; False for every other key)."""
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


def key_words(table: Table, keys: Sequence[int],
              row_valid: jnp.ndarray | None = None) -> list:
    """The keys of every row (null ranks and the row-valid bit folded in)
    as uint32 words, minor -> major, where the rows lie: what
    ``sort_order(table, keys, row_valid=row_valid)`` sorts by. uint order
    on the words, the last the most significant, is the keys' order. Two
    rows have the same words exactly when they have the same key tuple,
    null-ness included; a phantom row's words equal no real row's. For keys
    of fixed-width fields only (``_key_bits``): float64 and decimal128 have
    no such words and raise."""
    ones = [True] * len(keys)
    lex_keys, packable = _lex_keys(table, keys, ones, ones, row_valid)
    if not packable:
        raise TypeError("key_words: a key has no fixed-width sort word")
    return lex_keys if len(lex_keys) <= 2 else _pack_words(lex_keys)


def sort_key_words(table: Table, keys: Sequence[int],
                   row_valid: jnp.ndarray | None = None) -> tuple:
    """``sort_order(table, keys, row_valid=row_valid)`` with what it sorted
    by: ``(order, words, sorted_words)``. ``words`` are ``key_words`` of
    the rows where they lie; ``sorted_words`` the same in the order
    ``order``.

    Keys of one or two words are the operands of the one variadic sort
    ``sort_order`` runs, which brings them into order whether or not
    anybody reads them: here they are read. Wider keys are sorted word by
    word (``_radix_order``) and moved after it (``_move_words``)."""
    words = key_words(table, keys, row_valid)
    if len(words) <= 2:
        order, sorted_words = _sort_words(words)
        return order, words, sorted_words
    order = _radix_order(words)
    return order, words, _move_words(words, order)


def words_in_order(words: list) -> list:
    """``words`` (uint32, minor -> major) in their own order and nothing
    else: no row index comes along, so equal rows need no order among
    themselves and the sort is unstable (a stable one gets an iota operand
    from XLA). For who counts or compares neighbours and reads no row."""
    if len(words) <= 2:
        return list(jax.lax.sort(tuple(words[::-1]), num_keys=len(words),
                                 is_stable=False))[::-1]
    return _move_words(words, _radix_order(words))


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


def _kth_set_bit(word: jnp.ndarray, k: jnp.ndarray) -> jnp.ndarray:
    """int32: the place of the ``k``-th (from 0) set bit of a uint32
    ``word`` that holds more than ``k``: five halvings, each a
    ``population_count`` of the lower half of what is left."""
    at = jnp.zeros_like(k)
    for width in (16, 8, 4, 2, 1):
        below = jax.lax.population_count(
            (word >> at.astype(jnp.uint32)) & jnp.uint32((1 << width) - 1)
        ).astype(jnp.int32)
        above = k >= below
        k = jnp.where(above, k - below, k)
        at = jnp.where(above, at + width, at)
    return at


# ``positions_of`` packs its mask into words of one lane of a (32, 128)
# tile: 32 rows 128 apart, so that a word is a reduction over sublanes of
# the mask where it lies (no relayout, no padded minor axis).
_TILE = 32 * 128


def positions_of(mask: jnp.ndarray, k: int) -> tuple:
    """``(int32[k], int32 scalar)``: the positions where ``mask`` (bool[n])
    is set, ascending, in ``k`` slots with ``n`` in the slots past them,
    and how many are set. The count is true whatever ``k``; where it
    passes ``k`` the slots hold the first ``k`` positions.

    A compaction of positions only, with no pass over the n rows but the
    one that reads them: the mask is packed into 32-bit words (a row a
    bit), a prefix sum over the words' bit counts gives every word its
    first output slot, each word that holds a bit writes its number there
    (distinct slots: the scatter need not be ordered) and a running
    maximum hands it to the word's other slots; a slot's position is then
    the ``slot - first slot``-th set bit of its word (``_kth_set_bit``).
    A word's rows lie 128 apart inside a tile of 4,096 (``_TILE``), so the
    slots come out in the rows' order tile by tile only: one sort of the
    slots puts them right, and 4,096 slots more than ``k`` hold the whole
    of the tile in which the ``k``-th set row lies, so that the first
    ``k`` are exact. What an n-row sort or an n-row scatter of the
    positions would do (PERF.md section 6, PR 46, has the forms timed
    alone in a jit)."""
    n = mask.shape[0]
    if n == 0 or k == 0:
        return jnp.full((k,), n, jnp.int32), jnp.zeros((), jnp.int32)
    tiles = -(-n // _TILE)
    if n + _TILE >= 1 << 31:
        raise ValueError(f"positions of {n} rows: a position takes 31 bits")
    if tiles * _TILE != n:
        mask = jnp.pad(mask, (0, tiles * _TILE - n))
    word = jnp.sum(mask.reshape(tiles, 32, 128).astype(jnp.uint32)
                   << jax.lax.iota(jnp.uint32, 32)[None, :, None],
                   axis=1, dtype=jnp.uint32).reshape(tiles * 128)
    held = jax.lax.population_count(word).astype(jnp.int32)
    ends = jnp.cumsum(held)
    first, count = ends - held, ends[-1]
    number = jax.lax.iota(jnp.int32, tiles * 128)
    slots = k + _TILE
    # (a word with no bit, or one whose slots lie past the last, writes
    # nowhere: distinct places out of range, as the scatter was promised)
    word_of = jax.lax.cummax(jnp.zeros((slots,), jnp.int32).at[jnp.where(
        (held > 0) & (first < slots), first,
        jnp.int32(2**31 - 1) - number)].set(
            number, mode="drop", unique_indices=True))
    slot = jax.lax.iota(jnp.int32, slots)
    bit = _kth_set_bit(word[word_of], slot - first[word_of])
    row = (word_of >> 7) * _TILE + bit * 128 + (word_of & 127)
    return jax.lax.sort(jnp.where(slot < count, row, n),
                        is_stable=False)[:k], count


# ``_move_words`` brings uint32 words into a permutation's order by one
# gather of k-word rows, or, from this many words in all (rows times words
# a row, 64 MiB of them) by sort passes. On a v5e (PERF.md section 6, PR
# 29: k words of n rows, gather / sort passes, seconds) the gather's time
# a word triples once the words no longer fit: 5 words of 2,097,152 rows
# 0.0123 / 0.0145 and of 4,194,304 rows 0.0634 / 0.0347; 11 words of
# 2,097,152 rows 0.0487 / 0.0318; 2 words of 8,388,608 rows 0.0524 /
# 0.0456, 5 words 0.126 / 0.073, 11 words 0.191 / 0.145. A sort takes the
# time of the next power of two of its rows, so between two powers the
# gather may still be ahead (5 words of 6,001,215 rows 0.045 / 0.079); a
# region's rows are a bucket's, a power of two.
_SORT_MOVE_MIN_WORDS = 1 << 24


def _data_words(c: Column):
    """A fixed-width column's data as bit fields for ``permute``:
    ``(fields, rebuild)`` with ``fields`` a list of unsigned arrays of 8,
    16 or 32 bits a row (a 64-bit integer is two of 32, a decimal128 four)
    and ``rebuild(fields) -> data``; ``None`` for data that has no exact
    32-bit form here (float64: the TPU has no 64-bit float bitcast)."""
    data, dt = c.data, c.data.dtype
    if c.dtype.is_decimal128:
        halves = [data[:, 0], data[:, 1]]

        def rebuild128(f):
            return jnp.stack([_join64(f[0], f[1], dt), _join64(f[2], f[3], dt)],
                             axis=-1)

        return [w for h in halves for w in _split64(h)], rebuild128
    if data.ndim != 1 or dt.kind not in "iuf" or dt == jnp.float64:
        return None
    if dt.itemsize == 8:
        return _split64(data), lambda f: _join64(f[0], f[1], dt)
    unsigned = jnp.dtype(f"uint{dt.itemsize * 8}")
    return ([jax.lax.bitcast_convert_type(data, unsigned)],
            lambda f: jax.lax.bitcast_convert_type(f[0], dt))


def _split64(x: jnp.ndarray) -> list:
    return [x.astype(jnp.uint32), (x >> 32).astype(jnp.uint32)]


def _join64(lo: jnp.ndarray, hi: jnp.ndarray, dt) -> jnp.ndarray:
    return ((hi.astype(jnp.uint64) << 32) | lo.astype(jnp.uint64)).astype(dt)


def _pack_fields(fields: list) -> tuple:
    """Bit fields (bool = 1 bit, uint8, uint16, uint32) packed into as few
    uint32 words as their bits take: ``(words, places)`` with ``places[i]
    = (word, shift, bits)`` of field ``i``. Widest first, so every field
    starts on a multiple of its own width and none straddles two words."""
    by_width = sorted(range(len(fields)),
                      key=lambda i: -_key_bits(fields[i]))
    words, places, used = [], [None] * len(fields), 32
    for i in by_width:
        bits = _key_bits(fields[i])
        f32 = fields[i].astype(jnp.uint32)
        if used + bits > 32:
            words.append(f32)
            used = 0
        else:
            words[-1] = words[-1] | (f32 << used)
        places[i] = (len(words) - 1, used, bits)
        used += bits
    return words, places


def _unpack_field(words: list, place: tuple, like: jnp.ndarray) -> jnp.ndarray:
    word, shift, bits = place
    w = words[word] >> shift if shift else words[word]
    if bits == 1:
        return (w & jnp.uint32(1)).astype(jnp.bool_)
    return w.astype(like.dtype)      # the cast keeps the low ``bits`` bits


def _move_words(words: list, order: jnp.ndarray) -> list:
    """uint32 ``words`` of n rows each, in the order ``order`` (a
    permutation of 0..n-1): ``[w[order] for w in words]``, exactly."""
    n = order.shape[0]
    if not words:
        return []
    stacked = jnp.stack(words)
    if n * len(words) < _SORT_MOVE_MIN_WORDS:
        moved = stacked[:, order]             # one gather of k-word rows
        return [moved[i] for i in range(len(words))]
    # rank[j] is where row j goes (order's inverse); a sort of (rank, w)
    # by rank then leaves w[order[i]] at i. Pass 0 sorts (order, iota) and
    # gives the rank, pass i moves word i - 1: one two-operand sort
    # instruction whatever the number of words (what a sort instruction
    # costs the TPU compiler: ``_radix_order``). Pass 0 leaves the rank in
    # the first word's place, which pass 1 then fills. The carries start
    # from ``order`` and the words themselves, so under shard_map they
    # vary over the same mesh axes going in as out.
    key0 = order.astype(jnp.uint32)
    iota = jax.lax.iota(jnp.uint32, n)

    def one_pass(i, carry):
        rank, out = carry
        at = jnp.maximum(i - 1, 0)
        first = i == 0
        word = jax.lax.dynamic_index_in_dim(stacked, at, 0, keepdims=False)
        moved = jax.lax.sort(
            (jnp.where(first, key0, rank), jnp.where(first, iota, word)),
            num_keys=1, is_stable=False)[1]
        return (jnp.where(first, moved, rank),
                jax.lax.dynamic_update_index_in_dim(out, moved, at, 0))

    _, out = jax.lax.fori_loop(0, len(words) + 1, one_pass, (key0, stacked))
    return [out[i] for i in range(len(words))]


def permute(columns: Sequence[Column], order: jnp.ndarray,
            masks: Sequence[jnp.ndarray] = ()) -> tuple:
    """``columns`` and the row masks ``masks`` (bool[n]: a row-valid mask,
    a validity wanted without its data) in the order ``order``, which must
    be a permutation of 0..n-1 as ``sort_order`` returns one: ``(columns,
    masks)``, each what ``gather`` / ``mask[order]`` gives, bit for bit.

    Where ``gather`` moves every data buffer and every byte-wide mask by a
    gather of its own, this cuts the fixed-width data into 32-bit words (a
    64-bit integer is two, a decimal128 four), packs the 8- and 16-bit
    data and every validity and mask bit together into as few words as
    their bits take, materialises the words once and moves them once
    (``_move_words``). ``validity is None`` stays ``None``. Strings keep
    ``gather_strings``; float64 and nested data keep their gather."""
    out_cols: list = [None] * len(columns)
    fields: list = []     # every bit field that moves, data and masks
    parts = []            # (column, validity's field, data's fields, rebuild)
    for i, c in enumerate(columns):
        if c.dtype.is_string:
            from spark_rapids_jni_tpu.ops import strings as s

            out_cols[i] = s.gather_strings(c, order)
            continue
        vfield = None
        if c.validity is not None:
            vfield = len(fields)
            fields.append(c.validity)
        cut, rebuild = (None if c.children is not None
                        else _data_words(c)) or ([], None)
        parts.append((i, vfield, slice(len(fields), len(fields) + len(cut)),
                      rebuild))
        fields.extend(cut)
    mask_at = len(fields)
    fields.extend(masks)
    words, places = _pack_fields(fields)
    moved_words = _move_words(words, order)
    moved = [_unpack_field(moved_words, place, f)
             for place, f in zip(places, fields)]
    for i, vfield, span, rebuild in parts:
        c = columns[i]
        out_cols[i] = Column(
            c.dtype,
            c.data[order] if rebuild is None else rebuild(moved[span]),
            None if vfield is None else moved[vfield])
    return out_cols, moved[mask_at:]


# ``sort_before_padding`` sorts a table whose rows are padding from some row
# on (a bounded groupby's result: the groups, then null rows up to the
# bound) at a rung of about a sixteenth of its rows. The smallest rung worth
# a conditional, from a chip reading (v5e, PERF.md section 6, PR 47: q3's
# four masked columns by two keys alone in a jit, rows: all of them sorted |
# the conditional's taken branch, s): 1,025: 0.00144 | 0.00128 (a tie);
# 16,384: 0.00314 | 0.00139; 65,536: 0.00890 | 0.00157; 1,500,001: 0.3037 |
# 0.0171 (0.0336 at an eighth). From a rung of 1,024 on the conditional is
# over twice as quick and 0.0005 s ahead, the rule set before the reading;
# q13's ORDER BY over 1,024 slots and every smaller sort stay under it. The
# other branch is NOT free: 131,073 real rows of 1,500,001 read 0.3992 s
# under the conditional and 0.3065 with none.
_MIN_RUNG = 1024


def padding_rung(table: Table, keys: Sequence[int],
                 nulls_first: Sequence[bool] | None) -> int | None:
    """The rows ``sort_before_padding`` would sort of ``table``: the power
    of two at or over a sixteenth of its rows; None where the table may
    not be sorted that way, all of it decided on what a trace knows. Every
    key sorts its nulls last and has a validity mask (a row null in every
    key then ranks after every row that has one, and a stable sort leaves
    such rows in the order they stood in), every column is one of row
    buffers (``gather``'s columns: fixed-width, or a padded string), and
    the rung is ``_MIN_RUNG`` rows at least."""
    if nulls_first is None or any(nulls_first):
        return None
    if any(table.column(k).validity is None for k in keys):
        return None
    if any(c.children is not None
           or (c.dtype.is_string and not c.is_padded_string)
           for c in table.columns):
        return None
    rung = 1 << (-(-table.num_rows // 16) - 1).bit_length()
    return rung if _MIN_RUNG <= rung < table.num_rows else None


def _sort_before_padding_impl(row_args, aux, rvs, *, keys, ascending,
                              nulls_first, rung):
    ((table,),) = row_args

    def in_order(tbl):
        return gather(tbl, sort_order(tbl, keys, ascending, nulls_first))

    def head_in_order(tbl):
        head = in_order(jax.tree.map(lambda buf: buf[:rung], tbl))
        return jax.tree.map(
            lambda ordered, buf: jnp.concatenate([ordered, buf[rung:]]),
            head, tbl)

    valid_past = [table.column(k).validity[rung:] for k in keys]
    padding = ~jnp.any(jnp.stack(valid_past))
    return jax.lax.cond(padding, head_in_order, in_order, table), padding


def sort_before_padding(table: Table, keys: Sequence[int],
                        ascending: Sequence[bool] | None,
                        nulls_first: Sequence[bool], rung: int) -> tuple:
    """``(sort_table(table, keys, ascending, nulls_first), whether the
    first ``rung`` rows alone were sorted)``, for a ``rung`` that
    ``padding_rung`` gave. Where no row from ``rung`` on is valid in any
    key (one reduction over the keys' masks), those rows tie on every key
    and rank last: the stable sort of the whole table is the stable sort
    of the rows before them with the rest left where it lies, value for
    value, and a ``lax.cond`` runs that; otherwise it runs the whole
    sort."""
    from spark_rapids_jni_tpu.runtime import dispatch

    keys, nulls_first = tuple(keys), tuple(nulls_first)
    ascending = (True,) * len(keys) if ascending is None else tuple(ascending)
    return dispatch.call(
        "sort_before_padding",
        partial(_sort_before_padding_impl, keys=keys, ascending=ascending,
                nulls_first=nulls_first, rung=rung),
        ((table,),), statics=(keys, ascending, nulls_first, rung),
        slice_rows=False, bucket_rows=False)


@func_range("sort_table")
def sort_table(
    table: Table,
    keys: Sequence[int],
    ascending: Sequence[bool] | None = None,
    nulls_first: Sequence[bool] | None = None,
) -> Table:
    return gather(table, sort_order(table, keys, ascending, nulls_first))
