"""Equi-join — the cuDF hash-join equivalent (vendored capability surface,
SURVEY.md section 2.2; exercised by TPC-DS q64/q72, BASELINE.json config #4).

TPU-first design: no device hash table (SURVEY.md section 7: partitioned/
sort designs instead of chaining hash maps). This is a sort + binary-search
join: sort the build side once, then for every probe row locate its match
run with vectorized ``searchsorted`` (lower/upper bound), lay output pairs
out with a prefix sum, and resolve pair j -> (probe row, match ordinal) with
one more searchsorted over the offsets. Everything is static-shape; the
caller supplies ``out_size`` (capacity) and gets back gather maps plus the
true match count — the bucketed-padding discipline XLA wants. SQL semantics:
NULL keys never match; left join emits unmatched probe rows with an invalid
right index.

Multi-column and string/float keys are **exact**, not hashed: both sides'
key tuples are dense-rank encoded over their union (one sort of the
concatenated key columns + boundary scan — the same machinery groupby
uses), after which the join runs on a single collision-free int32 rank
column. cuDF's hash join is exact on composite keys; rank encoding is the
sort-based TPU equivalent (no collision-at-hash wrong answers, unlike the
round-1 "pre-hash into one column" recipe this replaces).

A semi or anti join asks one bit a probe row, so ``semi_join_mask`` gives
that and no maps: both sides' keys in ONE sort (a key's valid build rows
ahead of everybody else who holds it), a running maximum that tells every
row whether its key's run opened with a build row, and a second sort that
brings the bits back to the probe's row order. No search, no offsets, no
gather: what a fused region lowers ``Join(how="left_semi" | "left_anti")``
to (``runtime/fusion.py``). ``join(..., how="left_semi")`` keeps the maps.
The merged sort carries the key words the keys it was handed need: where
the rows with a key hold ONE high word between them and low words less
than 2**31 apart (dbgen's order keys, any 64-bit surrogate under 2**31) a
64-bit key sorts as one word, decided inside the region from the data
(``_probe_matches``).

Scopes (``jax.named_scope``, under the plan node's own inside a region; a
device trace splits the join's time by them): ``build`` is everything that
orders or indexes the build side (``_sorted_valid_keys``; the merged sort
of ``semi_join_mask``, which orders the probe's keys with it), ``probe``
everything else of the join: the searches, the prefix sum and the maps,
``apply_join_maps``' gathers; the runs' heads, the running maximum, the
sort back and the mask.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from spark_rapids_jni_tpu.columnar import Column, Table
from spark_rapids_jni_tpu.ops.hash import probe_sorted_lo_hi
from spark_rapids_jni_tpu.ops.sort import _split64, gather, sort_order
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


def _sorted_valid_keys(
    key: jnp.ndarray, valid: jnp.ndarray
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Sort one side with nulls banished past the valid prefix (null_rank
    is the primary lexsort key), then overwrite the tail with the dtype's
    max so a binary search over it stays sound even though null rows carry
    arbitrary key bytes. Returns (sorted_key, n_valid, perm)."""
    n = key.shape[0]
    null_rank = (~valid).astype(jnp.uint8)
    perm = jnp.lexsort((key, null_rank)).astype(jnp.int32)
    n_valid = jnp.sum(valid.astype(jnp.int64))
    info = np.iinfo(np.dtype(key.dtype.name))
    sorted_key = jnp.where(
        jnp.arange(n, dtype=jnp.int64) < n_valid,
        key[perm],
        jnp.asarray(info.max, dtype=key.dtype),
    )
    return sorted_key, n_valid, perm


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
    with jax.named_scope("build"):
        sorted_key, n_valid_right, perm = _sorted_valid_keys(
            right_key, right_valid)
    with jax.named_scope("probe"):
        return _probe_maps(left_key, left_valid, right_key, right_valid,
                           sorted_key, n_valid_right, perm, out_size, how,
                           left_row_valid, right_row_valid)


def _probe_maps(left_key, left_valid, right_key, right_valid, sorted_key,
                n_valid_right, perm, out_size, how, left_row_valid,
                right_row_valid) -> JoinMaps:
    """Everything of the maps-based join after the build side's sort."""
    n_left = left_key.shape[0]
    n_right = right_key.shape[0]
    # Match runs per probe row (empty when the probe key is null).
    lo, hi = probe_sorted_lo_hi(sorted_key, left_key)
    hi = jnp.minimum(hi, n_valid_right)  # the sentinel tail never matches
    lo = jnp.minimum(lo, hi)
    counts = jnp.where(left_valid, hi - lo, 0)
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
    if left_row_valid is not None and how != "inner" and how != "right":
        # phantom probe rows must emit nothing — only real probe rows get
        # the unmatched-row / semi / anti treatment (a real row with a
        # NULL key still counts). inner/right emission is already 0 for
        # phantom rows: left_valid was masked above, so counts == 0.
        out_per_row = jnp.where(left_row_valid, out_per_row, 0)
    offsets = jnp.cumsum(out_per_row)
    probe_total = offsets[-1] if n_left else jnp.int64(0)

    j = jnp.arange(out_size, dtype=jnp.int64)
    left_row = jnp.searchsorted(offsets, j, side="right").astype(jnp.int32)
    left_row = jnp.clip(left_row, 0, max(n_left - 1, 0))
    base = jnp.where(left_row > 0, offsets[jnp.maximum(left_row - 1, 0)], 0)
    ordinal = j - base
    matched = counts[left_row] > 0
    right_pos = jnp.clip(
        lo[left_row] + ordinal, 0, max(n_right - 1, 0)
    ).astype(jnp.int32)
    right_row = perm[right_pos] if n_right else jnp.zeros_like(right_pos)

    if how not in ("right", "full"):
        row_valid = j < probe_total
        right_ok = matched & row_valid & (how != "left_anti")
        return JoinMaps(
            left_index=left_row,
            right_index=right_row,
            right_valid=right_ok,
            row_valid=row_valid,
            total=probe_total,
            left_valid=row_valid,
        )

    # right/full outer: append build rows no valid probe row matched, with
    # a null left side. A build row is matched iff its key is valid and
    # appears among the valid probe keys — one more sort + binary search,
    # the mirror of the probe phase (scatter-free).
    sorted_left, n_valid_left, _ = _sorted_valid_keys(left_key, left_valid)
    l_lo, l_hi = probe_sorted_lo_hi(sorted_left, right_key)
    l_hi = jnp.minimum(l_hi, n_valid_left)
    exists_in_left = jnp.minimum(l_lo, l_hi) < l_hi
    unmatched = ~(right_valid & exists_in_left)
    if right_row_valid is not None:
        unmatched = unmatched & right_row_valid  # phantom slots emit nothing
    r_off = jnp.cumsum(unmatched.astype(jnp.int64))
    extra_total = r_off[-1] if n_right else jnp.int64(0)
    total = probe_total + extra_total

    is_extra = (j >= probe_total) & (j < total)
    k = jnp.clip(j - probe_total, 0, None)
    extra_right = jnp.searchsorted(r_off, k, side="right").astype(jnp.int32)
    extra_right = jnp.clip(extra_right, 0, max(n_right - 1, 0))
    row_valid = j < total
    return JoinMaps(
        left_index=left_row,
        right_index=jnp.where(is_extra, extra_right, right_row),
        right_valid=(matched | is_extra) & row_valid,
        row_valid=row_valid,
        total=total,
        left_valid=row_valid & ~is_extra,
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
    return jnp.zeros((hi.shape[0] - 1,), jnp.bool_), key >> 1, place


def _sorted_wide(hi, lo, place, lo_least) -> tuple:
    """The same in (high, low, place) order: three key words."""
    hi, lo, place = jax.lax.sort((hi, lo, place), num_keys=3, is_stable=False)
    return hi[1:] != hi[:-1], lo, place


def _probe_matches(left_key: jnp.ndarray, left_valid: jnp.ndarray,
                   right_key: jnp.ndarray,
                   right_valid: jnp.ndarray) -> tuple:
    """``(bool[n_left], scalar bool)``: the probe row has ``left_valid``
    and its key equals that of a build row with ``right_valid``; and
    whether a 64-bit key was sorted as one word.

    One sort of both sides' keys with a last word that holds a row's place
    in ``[probe rows, build rows]`` and, above it, a bit that is 0 only on
    a valid build row: inside a key's run those come first. A run then
    holds a match for its probe rows exactly when its head is one, which a
    running maximum over ``2 * (head's place in the order) + (head is a
    build row)`` hands to every row of the run. A second sort, of ``2 *
    place + bit`` alone, brings the bits back: the probe's rows lead.

    The merged sort's operands are the key's uint32 words and the place
    word, every one a key: no two rows tie, so it need not be stable (a
    stable one gets an iota operand more from XLA). A 4-byte key is one
    word. A 64-bit key is two, and where the rows with a key (``left_valid``
    / ``right_valid``) hold ONE high word between them and low words less
    than 2**31 apart (a minimum and a maximum of each word, over words the
    sort reads anyway) a ``lax.cond`` sorts one key word, the rebased low
    word with the place word's top bit under it, and the place word as its
    payload: equal low words are then equal keys among the rows that decide
    anything, and rows that tie in the key are a run's valid build rows or
    its others, whose order nobody reads. A row without a key may land in
    any run: it never opens one as a build row and its own bit is masked
    here. Keys that straddle a high word, or lie further apart, sort all
    three words."""
    n_left, n_right = left_key.shape[0], right_key.shape[0]
    narrowed = jnp.zeros((), jnp.bool_)
    if n_left == 0 or n_right == 0:
        return jnp.zeros((n_left,), jnp.bool_), narrowed
    n = n_left + n_right
    if n >= 1 << 31:
        raise ValueError(f"semi join of {n} rows: a place takes 31 bits")
    with jax.named_scope("build"):
        *major, minor = [jnp.concatenate([lw, rw]) for lw, rw in zip(
            _key_words(left_key), _key_words(right_key))]
        other = jnp.concatenate([jnp.ones((n_left,), jnp.bool_), ~right_valid])
        place = jax.lax.iota(jnp.uint32, n) | (other.astype(jnp.uint32) << 31)
        if major:
            (hi,) = major
            keyed = jnp.concatenate([left_valid, right_valid])
            least = [jnp.min(jnp.where(keyed, w, jnp.uint32(0xFFFFFFFF)))
                     for w in (hi, minor)]
            most = [jnp.max(jnp.where(keyed, w, jnp.uint32(0)))
                    for w in (hi, minor)]
            # (with no keyed row at all every least lies above its most:
            # the wide sort runs and decides nothing)
            narrowed = (least[0] == most[0]) & (most[1] - least[1] < 1 << 31)
            hi_changes, minor, place = jax.lax.cond(
                narrowed, _sorted_narrow, _sorted_wide,
                hi, minor, place, least[1])
        else:
            minor, place = jax.lax.sort(
                (minor, place), num_keys=2, is_stable=False)
    with jax.named_scope("probe"):
        differs = minor[1:] != minor[:-1]
        if major:
            differs = differs | hi_changes
        head = jnp.concatenate([jnp.ones((1,), jnp.bool_), differs])
        at = jax.lax.iota(jnp.uint32, n) << 1
        opened_by_build = jax.lax.cummax(jnp.where(
            head, at | (place >> 31 == 0).astype(jnp.uint32),
            jnp.uint32(0))) & 1
        back = jax.lax.sort(
            ((place & jnp.uint32(0x7FFFFFFF)) << 1) | opened_by_build,
            is_stable=False)
        return ((back[:n_left] & 1) == 1) & left_valid, narrowed


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
