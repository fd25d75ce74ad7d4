"""Equi-join — the cuDF hash-join equivalent (vendored capability surface,
SURVEY.md section 2.2; exercised by TPC-DS q64/q72, BASELINE.json config #4).

TPU-first design: no device hash table (SURVEY.md section 7: partitioned/
sort designs instead of chaining hash maps), and no binary search a probe
row either (a search of many needles is a chain of gathers, the slowest
thing this chip does: 0.28 us a probe). This is a merged-sort join. Both
sides' keys go through ONE sort, as the uint32 words they need, with a
last word that holds a row's place and flags: inside a key's run the valid
build rows come first. Two running passes over that order then give every
row the count of valid build rows ahead of it and its run's first such
count; for a probe row their difference is its matches and the first is
where they start in the build's own key order (``_build_order``: a sort of
the build side alone, by the same words). Output pairs are laid out in the
merged order: a prefix sum gives every emitting row its first output
position, a second sort by that position brings the emitting rows to the
front, each writes its number at its first position and a running maximum
over the ``out_size`` positions says which row emits which pair. A last
sort, of those ``out_size`` rows, brings the pairs into the promised order
(the probe's rows in order, a row's matches by build row): nothing travels
back to the probe's rows and no position is searched for.
Everything is static-shape; the caller supplies ``out_size`` (capacity)
and gets back gather maps plus the true match count — the
bucketed-padding discipline XLA wants. SQL semantics: NULL keys never
match; left join emits unmatched probe rows with an invalid right index.

Multi-column and string/float keys are **exact**, not hashed: both sides'
key tuples are dense-rank encoded over their union (one sort of the
concatenated key columns + boundary scan — the same machinery groupby
uses), after which the join runs on a single collision-free int32 rank
column. cuDF's hash join is exact on composite keys; rank encoding is the
sort-based TPU equivalent (no collision-at-hash wrong answers, unlike the
round-1 "pre-hash into one column" recipe this replaces).

A semi or anti join asks one bit a probe row, so ``semi_join_mask`` gives
that and no maps: the same merged sort, a running maximum that tells every
row whether its key's run opened with a build row, and a second sort that
brings the bits back to the probe's row order. No offsets, no gather: what
a fused region lowers ``Join(how="left_semi" | "left_anti")`` to
(``runtime/fusion.py``). ``join(..., how="left_semi")`` keeps the maps.
The merged sort (``_merged_sort``, shared by both) carries the key words
the keys it was handed need: where the rows with a key hold ONE high word
between them and low words less than 2**31 apart (dbgen's keys, any 64-bit
surrogate under 2**31) a 64-bit key sorts as one word, decided inside the
region from the data.

The joins that keep maps run at the probe rows that can emit, not at the
padded batch, where that is far fewer (``_join_maps_impl``): an
``out_size`` ``_COMPACT_FACTOR`` times or more under the probe side's rows
(a static gate: every other join lowers with no conditional) and, counted
inside the region, no more probe rows that can emit than ``out_size`` has
slots (a row that is real and holds a key; under ``left`` / ``full`` /
``left_anti`` a real row). Their positions are compacted into ``out_size``
slots (``ops/sort.py positions_of``: positions only, no column moves), the
key is fetched there, everything above runs on ``out_size + n_right`` rows
and ``left_index`` is read back through the positions, which ascend, so
the promised order needs no sort more. ``JoinMaps.probe_compacted`` says
that this ran: a fact of the data, as ``key_narrowed`` is. A probe side
with more such rows than slots (keys that mostly miss) takes the other
branch, the whole join over all rows.

Scopes (``jax.named_scope``, under the plan node's own inside a region; a
device trace splits the join's time by them): ``build`` is everything that
orders or indexes the build side (``_build_order``, and the merged sort,
which orders the probe's keys with it), ``probe`` everything else that
makes the maps or the mask: the count and the positions of the probe rows
that can emit and the key's fetch there, the runs' heads, the running
passes, the offsets, the emitting rows' sort, the sort into output order;
the running maximum, the sort back and the mask. ``apply_join_maps``'
gathers lie under ``gather_rows`` where a region calls it (``fusion.Join``;
not ``gather``, which is also the primitive's name and ends the op name of
every gather under ``probe``).
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from spark_rapids_jni_tpu.columnar import Column, Table
from spark_rapids_jni_tpu.ops.sort import (
    _split64, gather, one_word_span, positions_of, sort_order)
from spark_rapids_jni_tpu.utils.tracing import func_range


class JoinMaps(NamedTuple):
    """Gather maps describing join output rows (padded to out_size)."""

    left_index: jnp.ndarray   # int32[out_size] into the left table
    right_index: jnp.ndarray  # int32[out_size] into the right table
    right_valid: jnp.ndarray  # bool: False on left-join unmatched rows
    row_valid: jnp.ndarray    # bool: False on padding rows
    total: jnp.ndarray        # scalar int64: true number of output rows
    # bool: False on right/full-join rows with no left match (null left)
    left_valid: jnp.ndarray
    # scalar bool: the join ran on the probe rows that can emit alone
    probe_compacted: jnp.ndarray


def _key_words(key: jnp.ndarray) -> list:
    """An integral key as uint32 words, major first: equal words exactly
    for equal keys (their order is not the keys', which nobody needs)."""
    if key.dtype.itemsize == 8:
        return _split64(key)[::-1]
    return [key.astype(jnp.uint32)]


def _sorted_narrow(hi, lo, place, lo_least) -> tuple:
    """The merged sort where the high word says nothing and the low words
    span less than 2**31: ONE key word, ``(low - least low) << 1 | not a
    valid build row``, the place word its payload. ``(the high word
    changes at this row: never, rebased low words, places)`` in that
    order."""
    key = ((lo - lo_least) << 1) | (place >> 31)
    key, place = jax.lax.sort((key, place), num_keys=1, is_stable=False)
    # (never, spelt over ``hi`` so that under a ``shard_map`` it varies
    # over the mesh as the other branch's does; XLA folds it to a constant)
    return hi[1:] != hi[1:], key >> 1, place


def _sorted_wide(hi, lo, place, lo_least) -> tuple:
    """The same in (high, low, place) order: three key words."""
    hi, lo, place = jax.lax.sort((hi, lo, place), num_keys=3, is_stable=False)
    return hi[1:] != hi[:-1], lo, place


def _merged_sort(left_key, left_valid, right_key, right_valid,
                 place) -> tuple:
    """Both sides' keys in ONE sort, ``[probe rows, build rows]`` by their
    key and then by ``place``: a uint32 a row whose top bit is 0 only on a
    valid build row (inside a key's run those come first) and whose other
    bits are the caller's (a row's index, so that no two rows tie; flags
    above it). ``(the high word changes at this row or None, low words,
    places, scalar bool: a 64-bit key was sorted as one word)``, all but
    the last in sorted order.

    The operands are the key's uint32 words and the place word, every one
    a key: no two rows tie, so the sort need not be stable (a stable one
    gets an iota operand more from XLA). A 4-byte key is one word. A
    64-bit key is two, and where the rows with a key (``left_valid`` /
    ``right_valid``) hold ONE high word between them and low words less
    than 2**31 apart (a minimum and a maximum of each word, over words the
    sort reads anyway) a ``lax.cond`` sorts one key word, the rebased low
    word with the place word's top bit under it, and the place word as its
    payload: equal low words are then equal keys among the rows that decide
    anything, and rows that tie in the key are a run's valid build rows or
    its others, whose order nobody reads. A row without a key may land in
    any run: it never counts as a build row and what it reads there is
    masked by the caller. Keys that straddle a high word, or lie further
    apart, sort all three words."""
    narrowed = jnp.zeros((), jnp.bool_)
    *major, minor = [jnp.concatenate([lw, rw]) for lw, rw in zip(
        _key_words(left_key), _key_words(right_key))]
    if not major:
        minor, place = jax.lax.sort(
            (minor, place), num_keys=2, is_stable=False)
        return None, minor, place, narrowed
    (hi,) = major
    keyed = jnp.concatenate([left_valid, right_valid])
    # (with no keyed row at all the wide sort runs and decides nothing)
    narrowed, lo_least = one_word_span(hi, minor, keyed, 31)
    hi_changes, minor, place = jax.lax.cond(
        narrowed, _sorted_narrow, _sorted_wide,
        hi, minor, place, lo_least)
    return hi_changes, minor, place, narrowed


def _run_heads(hi_changes, minor) -> jnp.ndarray:
    """bool[n]: the row opens a key's run of the merged order."""
    differs = minor[1:] != minor[:-1]
    if hi_changes is not None:
        differs = differs | hi_changes
    return jnp.concatenate([jnp.ones((1,), jnp.bool_), differs])


def _probe_matches(left_key: jnp.ndarray, left_valid: jnp.ndarray,
                   right_key: jnp.ndarray,
                   right_valid: jnp.ndarray) -> tuple:
    """``(bool[n_left], scalar bool)``: the probe row has ``left_valid``
    and its key equals that of a build row with ``right_valid``; and
    whether a 64-bit key was sorted as one word.

    ``_merged_sort`` with a place word that holds a row's place in
    ``[probe rows, build rows]`` under the bit that is 0 only on a valid
    build row. A run then holds a match for its probe rows exactly when
    its head is one, which a running maximum over ``2 * (head's place in
    the order) + (head is a build row)`` hands to every row of the run. A
    second sort, of ``2 * place + bit`` alone, brings the bits back: the
    probe's rows lead. A row without a key never opens a run as a build
    row and its own bit is masked here."""
    n_left, n_right = left_key.shape[0], right_key.shape[0]
    if n_left == 0 or n_right == 0:
        return jnp.zeros((n_left,), jnp.bool_), jnp.zeros((), jnp.bool_)
    n = n_left + n_right
    if n >= 1 << 31:
        raise ValueError(f"semi join of {n} rows: a place takes 31 bits")
    with jax.named_scope("build"):
        other = jnp.concatenate([jnp.ones((n_left,), jnp.bool_), ~right_valid])
        place = jax.lax.iota(jnp.uint32, n) | (other.astype(jnp.uint32) << 31)
        hi_changes, minor, place, narrowed = _merged_sort(
            left_key, left_valid, right_key, right_valid, place)
    with jax.named_scope("probe"):
        head = _run_heads(hi_changes, minor)
        at = jax.lax.iota(jnp.uint32, n) << 1
        opened_by_build = jax.lax.cummax(jnp.where(
            head, at | (place >> 31 == 0).astype(jnp.uint32),
            jnp.uint32(0))) & 1
        back = jax.lax.sort(
            ((place & jnp.uint32(0x7FFFFFFF)) << 1) | opened_by_build,
            is_stable=False)
        return ((back[:n_left] & 1) == 1) & left_valid, narrowed


# the place word of the maps-based join: a row's place in [probe rows,
# build rows] in 29 bits, and above it (probe rows only) whether the row
# exists at all and whether it holds a key; the top bit is ``_merged_sort``'s
_PLACE_BITS = 29
_REAL, _KEYED = np.uint32(1 << 29), np.uint32(1 << 30)


def _build_order(key: jnp.ndarray, valid: jnp.ndarray) -> jnp.ndarray:
    """int32[n_right]: the build rows with a key in the order the merged
    sort gives them (by the key's uint32 words, major first; equal keys by
    row), then the rows without one."""
    operands = ((~valid).astype(jnp.uint32), *_key_words(key),
                jax.lax.iota(jnp.int32, key.shape[0]))
    *_, perm = jax.lax.sort(operands, num_keys=len(operands),
                            is_stable=False)
    return perm


# A join whose ``out_size`` lies this many times or more under its probe
# side's rows also compiles the form that runs on the probe rows that can
# emit alone (``_join_maps_impl``); every other join lowers with no
# conditional. On a v5e (PERF.md section 6, PR 46: an inner join of
# 67,108,864 probe rows and 2,097,152 build rows alone in a jit, 0.9 of
# ``out_size`` emitting; all rows / the emitting rows, seconds) the
# emitting rows' form loses while the capacity's rows are many, because
# what it adds are gathers at ``out_size`` positions (20 ns a position,
# whatever they read from) and what it spares are two n-row sorts: 2.221 /
# 3.197 at a factor of 4, 1.303 / 1.501 at 8, 0.893 / 0.717 at 16, 0.692 /
# 0.359 at 32. Where the count passes ``out_size`` the conditional costs
# 0.0025 s (0.7097 / 0.7122 at 32).
_COMPACT_FACTOR = 16
# the join types whose probe row emits only where it holds a key; under
# the others a real row without one emits a row too
_EMITS_BY_KEY = ("inner", "right", "left_semi")


def _join_maps_impl(
    left_key: jnp.ndarray,
    left_valid: jnp.ndarray,
    right_key: jnp.ndarray,
    right_valid: jnp.ndarray,
    out_size: int,
    how: str,
    left_row_valid: jnp.ndarray | None = None,
    right_row_valid: jnp.ndarray | None = None,
) -> JoinMaps:
    # Rows that are not rows at all (padding/phantom shuffle slots) must
    # never match, regardless of what their key bytes and key validity
    # happen to hold — fold row existence into key validity up front.
    if left_row_valid is not None:
        left_valid = left_valid & left_row_valid
    if right_row_valid is not None:
        right_valid = right_valid & right_row_valid
    n_left, n_right = left_key.shape[0], right_key.shape[0]
    n = n_left + n_right
    if n >= 1 << _PLACE_BITS:
        raise ValueError(
            f"join of {n} rows: a place takes {_PLACE_BITS} bits")
    if n == 0:
        none = jnp.zeros((out_size,), jnp.bool_)
        zero = jnp.zeros((out_size,), jnp.int32)
        return JoinMaps(zero, zero, none, none, jnp.int64(0), none,
                        jnp.zeros((), jnp.bool_))
    sides = (left_key, left_valid, left_row_valid,
             right_key, right_valid, right_row_valid)
    can_emit = left_valid if how in _EMITS_BY_KEY else left_row_valid
    if can_emit is None or not 0 < out_size * _COMPACT_FACTOR <= n_left:
        return _maps_of_rows(*sides, out_size=out_size, how=how)
    # The capacity lies far under the probe's rows (a WHERE below the join
    # dropped most of them, and static shapes carried them here): where no
    # more rows can emit than the capacity has slots, the join runs on
    # those rows alone; a probe side with more of them (keys that mostly
    # miss) takes today's path whole.
    with jax.named_scope("probe"):
        fits = jnp.sum(can_emit, dtype=jnp.int32) <= out_size
    return jax.lax.cond(
        fits, partial(_maps_of_emitting_rows, out_size=out_size, how=how),
        partial(_maps_of_rows, out_size=out_size, how=how), *sides)


def _maps_of_emitting_rows(left_key, left_valid, left_row_valid,
                           right_key, right_valid, right_row_valid, *,
                           out_size: int, how: str) -> JoinMaps:
    """The join over the probe rows that can emit, ``out_size`` of them at
    the most, and nothing else of the probe side: their positions in
    ``out_size`` slots, ascending (so the promised order holds with no
    sort more), the key and its bits fetched there, ``_maps_of_rows`` over
    ``out_size + n_right`` rows, and its ``left_index`` read through the
    positions."""
    n_left = left_key.shape[0]
    with jax.named_scope("probe"):
        by_key = how in _EMITS_BY_KEY
        pos, count = positions_of(
            left_valid if by_key else left_row_valid, out_size)
        pos = jnp.minimum(pos, n_left - 1)
        held = jax.lax.iota(jnp.int32, out_size) < count
        keyed = held if by_key else left_valid[pos] & held
        key = left_key[pos]
    maps = _maps_of_rows(key, keyed, held,
                         right_key, right_valid, right_row_valid,
                         out_size=out_size, how=how)
    with jax.named_scope("probe"):
        return maps._replace(left_index=pos[maps.left_index],
                             probe_compacted=jnp.ones((), jnp.bool_))


def _maps_of_rows(left_key, left_valid, left_row_valid,
                  right_key, right_valid, right_row_valid, *,
                  out_size: int, how: str) -> JoinMaps:
    """The maps over the rows as they come (row existence already folded
    into ``left_valid`` / ``right_valid``)."""
    n_left, n_right = left_key.shape[0], right_key.shape[0]
    n = n_left + n_right
    with jax.named_scope("build"):
        perm = _build_order(right_key, right_valid)
        # a phantom probe row emits nothing; a real one without a key
        # matches nothing and still counts under left / full / anti
        real = _REAL if left_row_valid is None else jnp.where(
            left_row_valid, _REAL, np.uint32(0))
        flags = jnp.concatenate([
            jnp.where(left_valid, _KEYED, np.uint32(0)) | real
            | np.uint32(1 << 31),
            (~right_valid).astype(jnp.uint32) << 31])
        hi_changes, minor, place, _ = _merged_sort(
            left_key, left_valid, right_key, right_valid,
            jax.lax.iota(jnp.uint32, n) | flags)
    with jax.named_scope("probe"):
        maps = _probe_maps(hi_changes, minor, place, perm, n_left, n_right,
                           out_size, how)
    if how in ("right", "full"):
        maps = _with_unmatched_build_rows(
            maps, left_key, left_valid, right_key, right_valid,
            right_row_valid)
    return maps


def _probe_maps(hi_changes, minor, place, perm, n_left: int, n_right: int,
                out_size: int, how: str) -> JoinMaps:
    """The maps of the probe's rows from the merged order: no row runs a
    search, and nothing is carried back to the probe's rows."""
    # valid build rows ahead of every row of the merged order: for a probe
    # row, all of its own run's among them (they lead the run); the same
    # count at the run's head is where the run's build rows start in
    # ``perm``. Both are running passes.
    is_build = place >> 31 == 0
    ahead = jnp.cumsum(is_build.astype(jnp.int32)) - is_build
    lo = jax.lax.cummax(jnp.where(_run_heads(hi_changes, minor), ahead, 0))
    real = place & _REAL != 0
    counts = jnp.where(place & _KEYED != 0, ahead - lo, 0)
    if how in ("left", "full"):
        out_per_row = jnp.maximum(counts, 1)  # unmatched probe row emits one
    elif how == "left_semi":
        out_per_row = (counts > 0).astype(counts.dtype)
    elif how == "left_anti":
        # no match at all — a NULL probe key matches nothing, so it
        # qualifies (Spark NOT EXISTS / cuDF left_anti semantics)
        out_per_row = (counts == 0).astype(counts.dtype)
    else:  # inner, right
        out_per_row = counts
    if how != "inner" and how != "right":
        # only real probe rows get the unmatched-row / semi / anti
        # treatment (a real row with a NULL key still counts): a phantom
        # probe row and every build row emit nothing. inner/right emission
        # is already 0 for them: they hold no key here, so counts == 0.
        out_per_row = jnp.where(real, out_per_row, 0)
    offsets = jnp.cumsum(out_per_row)
    probe_total = offsets[-1].astype(jnp.int64)

    # The rows that emit, brought to the front in the order they stand in:
    # a sort by a row's first output position (distinct among them; the
    # others behind, their order unread), the row's place in the merged
    # order its payload. A search of the output positions over the offsets
    # would be a chain of gathers (1.26 s for 2,097,152 positions over
    # 69,206,016 offsets on a v5e, where this sort takes 0.2).
    n = offsets.shape[0]
    start, at = jax.lax.sort(
        (jnp.where(out_per_row > 0, offsets - out_per_row,
                   jnp.int32(2**31 - 1)), jax.lax.iota(jnp.int32, n)),
        num_keys=1, is_stable=False)
    m = min(n, out_size)
    start, at = start[:m], at[:m]
    # output pair j: which of those rows emits it (the last whose first
    # position is at or before j: each writes its number there, a running
    # maximum fills the rest), and which of the row's matches it is
    j = jnp.arange(out_size, dtype=jnp.int32)
    slot = jax.lax.iota(jnp.int32, m)
    emitter = jax.lax.cummax(jnp.zeros((out_size,), jnp.int32).at[
        jnp.where(start < out_size, start, jnp.int32(2**31 - 1) - slot)].set(
            slot, mode="drop", unique_indices=True))
    at = at[emitter]
    matched = counts[at] > 0
    right_pos = jnp.clip(lo[at] + (j - start[emitter]), 0,
                         max(n_right - 1, 0))
    right_row = perm[right_pos] if n_right else jnp.zeros_like(right_pos)
    row_valid = j < probe_total
    left_row = (place[at] & np.uint32((1 << _PLACE_BITS) - 1)).astype(
        jnp.int32)
    # into the promised order: the probe's rows in order, a row's matches
    # by build row (their order in the merged order already); the rows
    # past the total stay behind
    left_row, _, right_row, matched = jax.lax.sort(
        (jnp.where(row_valid, left_row, jnp.int32(2**31 - 1)), j,
         right_row, matched), num_keys=2, is_stable=False)
    return JoinMaps(
        left_index=jnp.minimum(left_row, max(n_left - 1, 0)),
        right_index=right_row,
        right_valid=matched & row_valid & (how != "left_anti"),
        row_valid=row_valid,
        total=probe_total,
        left_valid=row_valid,
        probe_compacted=jnp.zeros((), jnp.bool_),
    )


def _with_unmatched_build_rows(maps: JoinMaps, left_key, left_valid,
                               right_key, right_valid,
                               right_row_valid) -> JoinMaps:
    """right/full outer: append build rows no valid probe row matched,
    with a null left side. A build row is matched iff its key is valid and
    appears among the valid probe keys: the mirror of the probe phase, one
    bit a build row (``_probe_matches`` with the sides exchanged)."""
    n_right = right_key.shape[0]
    out_size = maps.row_valid.shape[0]
    matched, _ = _probe_matches(right_key, right_valid, left_key, left_valid)
    with jax.named_scope("probe"):
        unmatched = ~matched
        if right_row_valid is not None:   # phantom slots emit nothing
            unmatched = unmatched & right_row_valid
        r_off = jnp.cumsum(unmatched.astype(jnp.int64))
        extra_total = r_off[-1] if n_right else jnp.int64(0)
        total = maps.total + extra_total

        j = jnp.arange(out_size, dtype=jnp.int64)
        is_extra = (j >= maps.total) & (j < total)
        k = jnp.clip(j - maps.total, 0, None)
        extra_right = jnp.clip(
            jnp.searchsorted(r_off, k, side="right").astype(jnp.int32),
            0, max(n_right - 1, 0))
        row_valid = j < total
        return JoinMaps(
            left_index=maps.left_index,
            right_index=jnp.where(is_extra, extra_right, maps.right_index),
            right_valid=(maps.right_valid | is_extra) & row_valid,
            row_valid=row_valid,
            total=total,
            left_valid=row_valid & ~is_extra,
            probe_compacted=maps.probe_compacted,
        )


def _concat_key_columns(lc: Column, rc: Column) -> Column:
    """Stack one key column from both tables into a combined column (left
    rows first) for union rank encoding."""
    if lc.dtype.is_string != rc.dtype.is_string:
        raise TypeError("join key types must match (string vs non-string)")
    lv, rv = lc.valid_mask(), rc.valid_mask()
    validity = jnp.concatenate([lv, rv])
    if lc.dtype.is_decimal or rc.dtype.is_decimal:
        # unscaled storage comparison is only sound at equal scales
        if lc.dtype != rc.dtype:
            raise TypeError(
                f"decimal join keys must have identical type+scale, got "
                f"{lc.dtype} vs {rc.dtype} (rescale first)"
            )
    if lc.dtype.is_string:
        from spark_rapids_jni_tpu.ops import strings as s

        lp, rp = s.pad_strings(lc), s.pad_strings(rc)
        width = max(int(lp.chars.shape[1]), int(rp.chars.shape[1]))

        def widen(p):
            w = int(p.chars.shape[1])
            if w == width:
                return p.chars
            return jnp.pad(p.chars, ((0, 0), (0, width - w)))

        return Column(
            lc.dtype,
            jnp.concatenate([lp.data, rp.data]),
            validity,
            chars=jnp.concatenate([widen(lp), widen(rp)]),
        )
    if lc.dtype.is_decimal128:
        # limb-pair storage concatenates along the row axis like any other
        return Column(lc.dtype, jnp.concatenate([lc.data, rc.data]), validity)
    if lc.dtype.storage_dtype != rc.dtype.storage_dtype:
        raise TypeError("join key storage types must match")
    return Column(lc.dtype, jnp.concatenate([lc.data, rc.data]), validity)


@func_range("rank_encode_keys")
def rank_encode_keys(
    left: Table, right: Table,
    left_on: Sequence[int], right_on: Sequence[int],
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Exact join-key encoding: dense ranks of the key tuples over the union
    of both tables. ``lkey[i] == rkey[j]`` iff the tuples are equal (nulls
    compare equal to nulls here; null-match exclusion stays in the join's
    validity masks). One lexsort of nl+nr rows — collision-free, unlike
    hashing."""
    from spark_rapids_jni_tpu.ops.groupby import _rows_equal_prev

    nl = left.num_rows
    combined = Table([
        _concat_key_columns(left.column(i), right.column(j))
        for i, j in zip(left_on, right_on)
    ])
    n = combined.num_rows
    ks = list(range(combined.num_columns))
    order = sort_order(combined, ks)
    sorted_tbl = gather(combined, order)
    same = _rows_equal_prev(sorted_tbl, ks)
    gid = (jnp.cumsum(~same) - 1).astype(jnp.int32)
    # scatter-free permutation inverse: ranks[order[i]] = gid[i] is the
    # gather ranks = gid[argsort(order)] (argsort of a permutation is its
    # inverse; scatters serialize on TPU)
    ranks = gid[jnp.argsort(order)]
    return ranks[:nl], ranks[nl:]


_JOIN_TYPES = ("inner", "left", "left_semi", "left_anti", "right", "full")


def key_valid(table: Table, keys: Sequence[int],
              row_valid: jnp.ndarray | None = None) -> jnp.ndarray:
    """bool[n]: no key column of the row is NULL (SQL: such a row matches
    nothing), and with ``row_valid`` the row exists at all."""
    valid = table.column(keys[0]).valid_mask()
    for k in keys[1:]:
        valid = valid & table.column(k).valid_mask()
    return valid if row_valid is None else valid & row_valid


def _encoded_keys(row_args, row_valids, lkeys, rkeys) -> tuple:
    """``(left key, left key valid, right key, right key valid, left row
    valid, right row valid)`` of a join's two row groups: one exact
    integral key a side (the column itself, or the key tuples' dense ranks
    over both sides)."""
    ((left, left_row_valid), (right, right_row_valid)) = row_args
    if row_valids is not None:
        # Row-dim padding happened: a caller-supplied row_valid was padded
        # with False (phantom rows already excluded); with no caller mask
        # the bucket mask itself marks the phantoms.
        lrv, rrv = row_valids
        if left_row_valid is None:
            left_row_valid = lrv
        if right_row_valid is None:
            right_row_valid = rrv

    lvalid, rvalid = key_valid(left, lkeys), key_valid(right, rkeys)

    lc = left.column(lkeys[0])
    rc0 = right.column(rkeys[0])
    single_integral = (
        len(lkeys) == 1
        and lc.dtype == rc0.dtype  # incl. decimal scale — unscaled values
        and not lc.dtype.is_string  # only compare at identical scales
        and not lc.dtype.is_decimal128  # limb pairs go via rank encoding
        and lc.dtype.storage_dtype.kind in ("i", "u")
    )
    if single_integral:
        # fast path: integral values are their own exact encoding
        lkey, rkey = lc.data, rc0.data
    else:
        lkey, rkey = rank_encode_keys(left, right, list(lkeys), list(rkeys))
    return lkey, lvalid, rkey, rvalid, left_row_valid, right_row_valid


def _join_impl(row_args, aux_args, row_valids, *, lkeys, rkeys,
               out_size, how) -> JoinMaps:
    lkey, lvalid, rkey, rvalid, left_row_valid, right_row_valid = \
        _encoded_keys(row_args, row_valids, lkeys, rkeys)
    return _join_maps_impl(
        lkey, lvalid, rkey, rvalid, out_size, how, left_row_valid,
        right_row_valid,
    )


def _key_tuples(left_on, right_on) -> tuple:
    left_keys = [left_on] if isinstance(left_on, int) else list(left_on)
    right_keys = [right_on] if isinstance(right_on, int) else list(right_on)
    if len(left_keys) != len(right_keys) or not left_keys:
        raise ValueError("left_on and right_on must be equal-length, non-empty")
    return (tuple(int(k) for k in left_keys),
            tuple(int(k) for k in right_keys))


@func_range("join")
def join(
    left: Table,
    right: Table,
    left_on: int | Sequence[int],
    right_on: int | Sequence[int],
    out_size: int,
    how: str = "inner",
    left_row_valid: jnp.ndarray | None = None,
    right_row_valid: jnp.ndarray | None = None,
) -> JoinMaps:
    """Equi-join returning gather maps; single- or multi-column keys of any
    supported type (integral, float, decimal, string). ``out_size`` caps the
    output (check ``total`` <= out_size on host if exactness matters, or use
    ``join_auto``). ``left_row_valid`` / ``right_row_valid`` mark which rows
    exist at all (False = padding/shuffle phantom, emits nothing even under
    an outer join).

    Join types (the cuDF surface, reference build-libcudf.xml:34-60
    capability): ``inner``, ``left``, ``left_semi`` (one row per probe row
    with >=1 match; right side = first match), ``left_anti`` (one row per
    probe row with NO match — null keys qualify; right side null),
    ``right`` (inner + unmatched build rows with null left), ``full``
    (left + unmatched build rows with null left).

    Runs through the shape-bucketed dispatch cache: each side's row count
    is padded up to its own bucket, so nearby (n_left, n_right) pairs share
    one executable per (out_size, how) instead of compiling per exact
    shape. Phantom pad rows ride the existing ``*_row_valid`` contract and
    emit nothing. The ``JoinMaps`` output is sized by ``out_size`` (a
    static), never by the buckets, so no output slicing is needed; index
    values in the ``~row_valid`` region are unspecified either way.

    SQL semantics: a NULL in ANY key column makes the row match nothing."""
    if how not in _JOIN_TYPES:
        raise ValueError(
            f"unsupported join type {how!r}; valid: {_JOIN_TYPES}")
    lkeys_t, rkeys_t = _key_tuples(left_on, right_on)
    out_size = int(out_size)

    from spark_rapids_jni_tpu.runtime import dispatch

    return dispatch.call(
        "join",
        partial(_join_impl, lkeys=lkeys_t, rkeys=rkeys_t,
                out_size=out_size, how=how),
        ((left, left_row_valid), (right, right_row_valid)),
        statics=(lkeys_t, rkeys_t, out_size, how),
        slice_rows=False,
    )


class SemiJoinMask(NamedTuple):
    """A semi or anti join's answer where the probe's rows lie."""

    keep: jnp.ndarray        # bool[n_left]: the probe row is in the result
    total: jnp.ndarray       # scalar int64: how many are
    build_rows: jnp.ndarray  # scalar int64: real build rows, non-null key
    # scalar bool: a 64-bit key was sorted as one word
    key_narrowed: jnp.ndarray


def _semi_join_impl(row_args, aux_args, row_valids, *, lkeys, rkeys,
                    how) -> SemiJoinMask:
    lkey, lvalid, rkey, rvalid, lrv, rrv = _encoded_keys(
        row_args, row_valids, lkeys, rkeys)
    if rrv is not None:    # a row that is none holds no key
        rvalid = rvalid & rrv
    matched, narrowed = _probe_matches(
        lkey, lvalid if lrv is None else lvalid & lrv, rkey, rvalid)
    with jax.named_scope("probe"):
        # a NULL probe key matches nothing: out of a semi join, in an anti
        # join's result (Spark NOT EXISTS / cuDF left_anti), as the maps say
        keep = matched if how == "left_semi" else ~matched
        if lrv is not None:
            keep = keep & lrv
        return SemiJoinMask(keep, jnp.sum(keep, dtype=jnp.int64),
                            jnp.sum(rvalid, dtype=jnp.int64), narrowed)


@func_range("semi_join_mask")
def semi_join_mask(
    left: Table,
    right: Table,
    left_on: int | Sequence[int],
    right_on: int | Sequence[int],
    how: str = "left_semi",
    left_row_valid: jnp.ndarray | None = None,
    right_row_valid: jnp.ndarray | None = None,
) -> SemiJoinMask:
    """``left_semi`` / ``left_anti`` as a mask over the probe's rows where
    they lie: ``keep[i]`` exactly where ``join(..., how=how)`` has a
    ``left_index`` of ``i`` among its real rows (its first ``total``, in
    row order), for any key ``join`` takes, duplicates on both sides, NULL
    keys and phantom rows. No ``out_size``: nothing is laid out.
    ``key_narrowed`` says whether a 64-bit key was sorted as one word
    (``_probe_matches``: the rows with a key held one high word between
    them and low words less than 2**31 apart; never for a 4-byte key or
    the dense ranks of a composite one, which are one word as they come).
    Runs through the dispatch cache as ``join`` does, a bucket a side."""
    if how not in ("left_semi", "left_anti"):
        raise ValueError(f"semi_join_mask: {how!r} is no semi or anti join")
    lkeys_t, rkeys_t = _key_tuples(left_on, right_on)

    from spark_rapids_jni_tpu.runtime import dispatch

    return dispatch.call(
        "semi_join_mask",
        partial(_semi_join_impl, lkeys=lkeys_t, rkeys=rkeys_t, how=how),
        ((left, left_row_valid), (right, right_row_valid)),
        statics=(lkeys_t, rkeys_t, how),
    )


def _gather_out(c: Column, idx: jnp.ndarray, validity: jnp.ndarray) -> Column:
    if c.dtype.is_string:
        from spark_rapids_jni_tpu.ops import strings as s

        g = s.gather_strings(c, idx)
        return Column(c.dtype, g.data, validity, chars=g.chars)
    return Column(c.dtype, c.data[idx], validity)


def apply_join_maps(
    left: Table, right: Table, maps: JoinMaps
) -> Table:
    """Materialize the joined table: left columns then right columns.
    Padding rows carry validity False everywhere; unmatched right sides
    (left/full join) and unmatched left sides (right/full join) are null.
    String columns come back in the padded device layout
    (ops.strings.unpad_strings restores Arrow)."""
    cols: list[Column] = []
    for c in left.columns:
        validity = (
            c.valid_mask()[maps.left_index] & maps.left_valid & maps.row_valid
        )
        cols.append(_gather_out(c, maps.left_index, validity))
    for c in right.columns:
        validity = (
            c.valid_mask()[maps.right_index] & maps.right_valid & maps.row_valid
        )
        cols.append(_gather_out(c, maps.right_index, validity))
    return Table(cols)


def join_auto(
    left: Table,
    right: Table,
    left_on: int | Sequence[int],
    right_on: int | Sequence[int],
    initial_out_size: int | None = None,
    how: str = "inner",
    growth: int = 4,
) -> tuple[JoinMaps, Table]:
    """Host-level grow-and-retry around the output capacity: run with a
    guessed ``out_size``, and if ``total`` exceeded it, grow by ``growth``
    and rerun until exact. Each retry recompiles for the new static bound —
    output capacity is a planning parameter on TPU, and this wrapper is the
    planner's feedback loop. Growth runs through the shared resilience
    ladder (``runtime/resilience.escalate``): the overflowed attempt
    reports its exact requirement (``total``), so the schedule —
    max(total, out_size·growth) — converges on the second attempt exactly
    as the pre-resilience loop did. Returns (maps, materialized table)."""
    from spark_rapids_jni_tpu.runtime import resilience

    n = max(left.num_rows, 1)
    out_size = int(initial_out_size) if initial_out_size else n
    if not resilience.enabled():
        while True:
            maps = join(left, right, left_on, right_on, out_size, how=how)
            total = int(maps.total)
            if total <= out_size:
                return maps, apply_join_maps(left, right, maps)
            out_size = max(total, out_size * growth)

    def _attempt(cap):
        maps = join(left, right, left_on, right_on, cap, how=how)
        total = int(maps.total)
        if total <= cap:
            return (maps, apply_join_maps(left, right, maps)), False, None
        return None, True, total

    return resilience.escalate(
        "join_auto", _attempt, seam="dispatch.execute",
        initial=out_size, growth=growth, rows=n)
