"""String columns in the relational core — sort keys, equality, hashing,
gather, and the padded device layout.

The reference's relational substrate handles STRING keys everywhere (cuDF
sort/groupby/join capability surface, built by build-libcudf.xml:34-60).
cuDF's device layout is Arrow (offsets + chars); its kernels walk the ragged
buffers with per-thread char loops. That shape is hostile to the TPU: ragged
gathers serialize on the VPU and defeat XLA tiling.

TPU-first design — two layouts, one conversion boundary:

- **Arrow layout** (offsets int32[n+1], chars uint8[m]) at rest and in IO —
  what the Parquet/ORC readers produce and `collect` returns.
- **Padded layout** (lengths int32[n], bytes uint8[n, W]) on device for
  relational ops. W is a planner-chosen static width (max row length). Every
  string op becomes a dense, vectorized pass over the matrix: sort keys are
  big-endian packed uint32 words (memcmp order, length as tiebreak), row
  equality is one masked compare, and xxhash64 runs the *full* variable-length
  algorithm with masked lane updates — no per-row loops anywhere.

Conversions are single gathers (static shapes both ways; Arrow->padded pads,
padded->Arrow compacts into an n*W char buffer with the real total tracked by
offsets). Width is computed on host where data is host-visible, or passed
statically by the planner inside jit.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.layout import Layout, with_layout_constraint

from spark_rapids_jni_tpu import telemetry
from spark_rapids_jni_tpu.columnar import Column
from spark_rapids_jni_tpu.types import DType, TypeId
from spark_rapids_jni_tpu.utils.tracing import func_range

STRING = DType(TypeId.STRING)


# ---------------------------------------------------------------------------
# Layout predicates / conversions
# ---------------------------------------------------------------------------

def is_padded(col: Column) -> bool:
    """True when a string column carries the padded (n, W) device layout."""
    return col.is_padded_string


def max_string_width(col: Column) -> int:
    """Host-side max row length (0 for an all-empty column). Only valid
    outside jit: forces a device->host read of the offsets."""
    if is_padded(col):
        return int(col.chars.shape[1])
    offsets = np.asarray(col.data)
    if offsets.shape[0] <= 1:
        return 0
    return int(np.max(offsets[1:] - offsets[:-1]))


def pad_strings(col: Column, width: int | None = None) -> Column:
    """Arrow -> padded layout. ``width`` must be >= every row length (rows
    longer than width would corrupt; callers use max_string_width or a
    planner bound). Cells past a row's length are zero."""
    if is_padded(col):
        return col
    if width is None:
        try:
            width = max_string_width(col)
        except jax.errors.TracerArrayConversionError:
            raise ValueError(
                "pad_strings inside jit needs an explicit static width — "
                "convert string columns to the padded layout (pad_strings) "
                "on host before entering jit, or pass width="
            ) from None
    width = max(int(width), 1)
    offsets = col.data
    chars = col.chars
    n = int(offsets.shape[0]) - 1
    if n == 0 or int(chars.shape[0]) == 0:
        return Column(
            STRING,
            jnp.zeros((n,), jnp.int32),
            col.validity,
            chars=jnp.zeros((n, width), jnp.uint8),
        )
    starts = offsets[:-1]
    lengths = (offsets[1:] - starts).astype(jnp.int32)
    idx = starts[:, None] + jnp.arange(width, dtype=jnp.int32)[None, :]
    present = jnp.arange(width, dtype=jnp.int32)[None, :] < lengths[:, None]
    cap = int(chars.shape[0]) - 1
    mat = jnp.where(present, chars[jnp.clip(idx, 0, cap)], jnp.uint8(0))
    return Column(STRING, lengths, col.validity, chars=mat)


def unpad_strings(col: Column) -> Column:
    """Padded -> Arrow layout. The chars buffer is allocated at the static
    bound n*W; offsets[-1] carries the true total (slack bytes at the end
    are dead, which the Arrow contract allows)."""
    if not is_padded(col):
        return col
    lengths = col.data
    mat = col.chars
    n, width = int(mat.shape[0]), int(mat.shape[1])
    if n == 0:
        return Column(
            STRING,
            jnp.zeros((1,), jnp.int32),
            col.validity,
            chars=jnp.zeros((0,), jnp.uint8),
        )
    offsets = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(lengths).astype(jnp.int32)]
    )
    # Compact gather: output char position c belongs to the row r with
    # offsets[r] <= c < offsets[r+1]; its source byte is mat[r, c - offsets[r]].
    total_cap = max(n * width, 1)
    c = jnp.arange(total_cap, dtype=jnp.int32)
    row = jnp.searchsorted(offsets[1:], c, side="right").astype(jnp.int32)
    row = jnp.clip(row, 0, max(n - 1, 0))
    delta = c - offsets[row]
    inside = c < offsets[-1]
    flat = mat.reshape(-1)
    src = jnp.clip(row * width + delta, 0, max(n * width - 1, 0))
    chars = jnp.where(inside, flat[src], jnp.uint8(0))
    return Column(STRING, offsets, col.validity, chars=chars)


def pad_to_common_width(cols):
    """Pad several string columns to one shared (max) padded width —
    the normalization concatenate/coalesce need before mixing rows."""
    ps = [pad_strings(c) for c in cols]
    w = max(int(p.chars.shape[1]) for p in ps)
    return [
        p if int(p.chars.shape[1]) == w else Column(
            p.dtype, p.data, p.validity,
            chars=jnp.pad(p.chars, ((0, 0), (0, w - p.chars.shape[1]))))
        for p in ps
    ]


def gather_strings(col: Column, indices: jnp.ndarray) -> Column:
    """Row gather of a padded string column (padded layout makes this the
    same two-array gather as fixed-width columns)."""
    col = pad_strings(col)
    validity = None if col.validity is None else col.validity[indices]
    return Column(STRING, col.data[indices], validity, chars=col.chars[indices])


# ---------------------------------------------------------------------------
# Sort keys / equality
# ---------------------------------------------------------------------------

def packed_sort_keys(col: Column) -> list[jnp.ndarray]:
    """Order-preserving lexsort keys for a padded string column, minor to
    major: [length, word_k-1, ..., word_0]. Each word packs 4 bytes
    big-endian into uint32, so uint32 comparison == memcmp on those bytes;
    zero padding ties equal prefixes and the length key breaks them
    (shorter first) — exactly memcmp-then-length string order, correct for
    embedded NUL bytes too."""
    col = pad_strings(col)
    mat = col.chars
    lengths = col.data
    width = int(mat.shape[1])
    n_words = (width + 3) // 4
    pad_w = n_words * 4 - width
    if pad_w:
        mat = jnp.pad(mat, ((0, 0), (0, pad_w)))
    u = mat.astype(jnp.uint32).reshape(mat.shape[0], n_words, 4)
    words = (
        (u[:, :, 0] << 24) | (u[:, :, 1] << 16) | (u[:, :, 2] << 8) | u[:, :, 3]
    )
    keys = [words[:, i] for i in range(n_words - 1, -1, -1)]
    return [lengths.astype(jnp.uint32)] + keys


def strings_equal_prev(col: Column) -> jnp.ndarray:
    """bool[n-1]: row i+1's bytes equal row i's (groupby boundary test)."""
    col = pad_strings(col)
    mat, lengths = col.chars, col.data
    eq_len = lengths[1:] == lengths[:-1]
    eq_bytes = jnp.all(mat[1:] == mat[:-1], axis=1)
    return eq_len & eq_bytes


# ---------------------------------------------------------------------------
# Variable-length xxhash64 (Spark hashUnsafeBytes parity)
# ---------------------------------------------------------------------------

from spark_rapids_jni_tpu.ops.hash import (  # noqa: E402 — shared primitives
    _P1, _P2, _P3, _P4, _P5, _avalanche, _rotl,
)


def _le_words(mat: jnp.ndarray, n_lanes: int, lane_bytes: int) -> jnp.ndarray:
    """(n, n_lanes) little-endian words of ``lane_bytes`` each from the
    leading n_lanes*lane_bytes columns of the byte matrix."""
    u = mat[:, : n_lanes * lane_bytes].astype(jnp.uint64)
    u = u.reshape(mat.shape[0], n_lanes, lane_bytes)
    shifts = jnp.asarray(
        [np.uint64(8 * i) for i in range(lane_bytes)], dtype=jnp.uint64
    )
    return jnp.sum(u << shifts[None, None, :], axis=2, dtype=jnp.uint64)


@func_range("xxhash64_bytes")
def xxhash64_bytes(
    mat: jnp.ndarray, lengths: jnp.ndarray, seeds: jnp.ndarray
) -> jnp.ndarray:
    """Full XXH64 of each row's first ``lengths[i]`` bytes, vectorized over
    rows with per-row seeds — the exact algorithm Spark's hashUnsafeBytes /
    the reference family's string xxhash64 kernel computes, expressed as a
    static number of masked elementwise passes (width/8 lane updates), not
    per-row loops. Rows' bytes past their length MUST be zero-padded (they
    are masked out, but the packing helpers guarantee it anyway)."""
    width = int(mat.shape[1])
    lengths = lengths.astype(jnp.int64)
    seeds = seeds.astype(jnp.uint64)

    # Stripe phase: process 32-byte stripes for rows with length >= 32.
    n_stripes = width // 32
    n_rows_u64 = (width + 7) // 8
    padded_w = n_rows_u64 * 8
    if padded_w != width:
        mat8 = jnp.pad(mat, ((0, 0), (0, padded_w - width)))
    else:
        mat8 = mat
    lanes = _le_words(mat8, n_rows_u64, 8)  # (n, n_rows_u64) uint64 LE lanes

    full_stripes = jnp.where(lengths >= 32, lengths // 32, 0)
    v1 = seeds + _P1 + _P2
    v2 = seeds + _P2
    v3 = seeds
    v4 = seeds - _P1
    for s in range(n_stripes):
        active = s < full_stripes
        l0, l1 = lanes[:, 4 * s], lanes[:, 4 * s + 1]
        l2, l3 = lanes[:, 4 * s + 2], lanes[:, 4 * s + 3]
        v1 = jnp.where(active, _rotl(v1 + l0 * _P2, 31) * _P1, v1)
        v2 = jnp.where(active, _rotl(v2 + l1 * _P2, 31) * _P1, v2)
        v3 = jnp.where(active, _rotl(v3 + l2 * _P2, 31) * _P1, v3)
        v4 = jnp.where(active, _rotl(v4 + l3 * _P2, 31) * _P1, v4)
    h_long = (
        _rotl(v1, 1) + _rotl(v2, 7) + _rotl(v3, 12) + _rotl(v4, 18)
    )
    for v in (v1, v2, v3, v4):
        h_long = (h_long ^ (_rotl(v * _P2, 31) * _P1)) * _P1 + _P4
    h = jnp.where(lengths >= 32, h_long, seeds + _P5)
    h = h + lengths.astype(jnp.uint64)

    consumed = full_stripes * 32  # bytes already absorbed per row

    # 8-byte tail lanes: up to width//8 of them, masked per row.
    full_words = lengths // 8
    for w in range(n_rows_u64):
        active = (w >= consumed // 8) & (w < full_words)
        upd = (h ^ (_rotl(lanes[:, w] * _P2, 31) * _P1))
        upd = _rotl(upd, 27) * _P1 + _P4
        h = jnp.where(active, upd, h)

    # One optional 4-byte lane.
    word4 = _le_words(mat8, n_rows_u64 * 2, 4)  # (n, 2*n_rows_u64) uint32-in-u64
    pos4 = full_words * 2  # index of the 4-byte word at offset full_words*8
    has4 = (lengths % 8) >= 4
    lane4 = jnp.take_along_axis(
        word4, jnp.clip(pos4, 0, word4.shape[1] - 1)[:, None], axis=1
    )[:, 0]
    upd = (h ^ (lane4 * _P1))
    upd = _rotl(upd, 23) * _P2 + _P3
    h = jnp.where(has4, upd, h)

    # Up to 7 single-byte tail updates (3 if the 4-byte lane fired).
    tail_start = full_words * 8 + jnp.where(has4, 4, 0)
    n_tail_max = min(7, width) if width else 0
    matu = mat8.astype(jnp.uint64)
    for b in range(n_tail_max):
        pos = tail_start + b
        active = pos < lengths
        byte = jnp.take_along_axis(
            matu, jnp.clip(pos, 0, padded_w - 1).astype(jnp.int32)[:, None], axis=1
        )[:, 0]
        upd = _rotl(h ^ (byte * _P5), 11) * _P1
        h = jnp.where(active, upd, h)

    return _avalanche(h)


def hash_string_column(col: Column, seeds: jnp.ndarray) -> jnp.ndarray:
    """Chainable per-row hash of a string column: full XXH64 over each row's
    UTF-8 bytes with the running hash as seed; null rows pass the seed
    through (Spark HashExpression chaining semantics)."""
    col = pad_strings(col)
    hashed = xxhash64_bytes(col.chars, col.data, seeds)
    if col.validity is None:
        return hashed
    return jnp.where(col.validity, hashed, seeds)


# ---- search predicates (cuDF strings::contains/find, Spark LIKE) -----------


def _needle_windows(col: Column, needle: bytes) -> jnp.ndarray:
    """bool (n, W): position j starts a full match of ``needle`` (callers
    special-case empty needles; f >= 1 here)."""
    assert needle, "empty needles are the caller's fast path"
    p = pad_strings(col)
    mat, lengths = p.chars, p.data
    w = int(mat.shape[1])
    f = len(needle)
    if f > w:
        return jnp.zeros((p.size, w), jnp.bool_)
    jdx = jnp.arange(w, dtype=jnp.int32)
    win = jnp.ones((p.size, w), jnp.bool_)
    for off, byte in enumerate(needle):
        win = win & (jnp.roll(mat, -off, axis=1) == byte)
    return win & (jdx[None, :] + f <= lengths[:, None])


def _bool8_result(hit: jnp.ndarray, col: Column) -> Column:
    """BOOL8 predicate result; validity passes through untouched (None
    stays None — the no-null-mask fast path)."""
    return Column(DType(TypeId.BOOL8), hit.astype(jnp.uint8), col.validity)


@func_range("string_contains")
def contains(col: Column, needle: str) -> Column:
    """BOOL8: row contains ``needle`` (empty needle matches everything,
    Java String.contains). Null rows stay null."""
    nb = needle.encode("utf-8")
    if not nb:
        hit = jnp.ones((col.size,), jnp.bool_)
    else:
        hit = jnp.any(_needle_windows(col, nb), axis=1)
    return _bool8_result(hit, col)


@func_range("string_starts_with")
def starts_with(col: Column, prefix: str) -> Column:
    nb = prefix.encode("utf-8")
    if not nb:
        hit = jnp.ones((col.size,), jnp.bool_)
    else:
        hit = _needle_windows(col, nb)[:, 0]
    return _bool8_result(hit, col)


@func_range("string_ends_with")
def ends_with(col: Column, suffix: str) -> Column:
    nb = suffix.encode("utf-8")
    p = pad_strings(col)
    if not nb:
        hit = jnp.ones((col.size,), jnp.bool_)
    else:
        win = _needle_windows(p, nb)
        pos = jnp.clip(p.data - len(nb), 0, max(int(p.chars.shape[1]) - 1, 0))
        hit = jnp.take_along_axis(win, pos[:, None], axis=1)[:, 0]
        hit = hit & (p.data >= len(nb))
    return _bool8_result(hit, col)


@func_range("string_like")
def like(col: Column, pattern: str, escape: str = "\\") -> Column:
    """SQL LIKE: '%' any run, '_' any single CHARACTER, escape char
    literal-izes the next char. Compiled to a literal-segment plan
    evaluated with vectorized window matches + a per-gap reachability
    step ('%': one reduction to the first position it can start from)
    — no regex engine, no per-row host work.

    '_' advances one UTF-8 CHARACTER (Spark semantics) via character-
    boundary tracking; '%' and literals are byte-exact for any UTF-8
    data (a valid-UTF-8 literal cannot match at a continuation byte, so
    byte- and char-anchoring agree). On INVALID UTF-8, continuation
    bytes (0x80-0xBF) always extend the preceding character — e.g. a
    lone b"\\x80\\x80" row counts as one character — matching how a
    byte-oriented UTF-8 scanner segments garbage; behavior on such data
    is unspecified in Spark."""
    esc = escape.encode("utf-8")
    if len(esc) != 1:
        raise ValueError("LIKE escape must be one byte")
    # compile: list of (literal bytes, min_gap, floating) segments
    segs: list[bytes] = []
    gaps: list[tuple[int, bool]] = []  # (min single-char count, saw %)
    cur = bytearray()
    pend_gap = [0, False]
    i = 0
    pb = pattern.encode("utf-8")
    while i < len(pb):
        c = pb[i:i + 1]
        if c == esc:
            # Spark's checkLikePattern posture: the escape char must be
            # followed by %, _, or the escape char itself; a trailing
            # escape (or escaping an ordinary char) is an invalid pattern,
            # not a silent literal.
            nxt = pb[i + 1:i + 2]
            if not nxt or nxt not in (b"%", b"_", esc):
                raise ValueError(
                    f"invalid LIKE pattern {pattern!r}: the escape "
                    f"character must be followed by '%', '_', or the "
                    f"escape character itself"
                )
            cur += nxt
            i += 2
            continue
        if c in (b"%", b"_"):
            if cur:
                segs.append(bytes(cur))
                gaps.append(tuple(pend_gap))
                cur = bytearray()
                pend_gap = [0, False]
            if c == b"%":
                pend_gap[1] = True
            else:
                pend_gap[0] += 1
            i += 1
            continue
        cur += c
        i += 1
    segs.append(bytes(cur))
    gaps.append(tuple(pend_gap))
    tail_gap = (0, False)
    if not segs[-1] and len(segs) > 1:
        tail_gap = gaps.pop()
        segs.pop()

    p = pad_strings(col)
    plan = (tuple(segs), tuple(gaps), tail_gap)
    n, block = p.size, _LIKE_BLOCK_ROWS
    if n <= block:
        return _bool8_result(_like_rows(p.chars, p.data, *plan), col)
    # Row blocks: a literal segment is a window of (rows, w) booleans over a
    # shifted slice of the bytes for each byte of the needle, and a '_' gap
    # or an anchored segment some twenty (rows, w + 1) booleans more; a '%'
    # between literals is one int32 a row (``_like_rows``' ``first``). Over
    # 16,777,216 rows of 79 bytes one such array is 1.3 GB; a block's are
    # 5 MB each. The rows stand alone, so the blocks' answers side by side
    # are the column's.
    full = n // block
    chars, lengths = p.chars, p.data
    hit = jax.lax.map(
        lambda blk: _like_rows(blk[0], blk[1], *plan),
        (chars[:full * block].reshape(full, block, chars.shape[1]),
         lengths[:full * block].reshape(full, block))).reshape(-1)
    if n > full * block:
        hit = jnp.concatenate([hit, _like_rows(
            chars[full * block:], lengths[full * block:], *plan)])
    return _bool8_result(hit, col)


# Rows a step of ``like``'s loop over a long column: large enough that the
# loop's 256 steps at 16,777,216 rows are no cost beside a step's work
# (0.18 ms each on a v5e for '%special%requests%', PERF.md section 5, PR
# 51; 0.83 while each '%' was a scan), small enough that a step's
# temporaries (q13's: the block and fourteen shifted slices of it, 5 MB
# each at 79 bytes a row, no (rows, w + 1) booleans at all; twenty-odd of
# those where a pattern holds a '_' or an anchored segment) leave the
# chip's 16 GB to the tables. The chip holds uint8[n, 79] column-major, 80
# bytes a row, and XLA copies it into the loop's [80, blocks, rows] order
# once before the loop: that copy is the column's size whatever the block.
_LIKE_BLOCK_ROWS = 1 << 16


def _like_rows(chars: jnp.ndarray, lengths: jnp.ndarray, segs: tuple,
               gaps: tuple, tail_gap: tuple) -> jnp.ndarray:
    """bool[n]: which rows of a padded string block (``chars`` uint8[n, w],
    ``lengths`` int32[n]) match ``like``'s compiled pattern: literal
    ``segs``, each after its gap ``(single characters, saw %)``, and the
    gap after the last."""
    if any(floating for _, floating in gaps):
        # The bytes with the rows along the lanes and a position a slab, as
        # the column rests in HBM: a needle's byte shifts then move whole
        # slabs. Left to itself XLA:TPU lays a block's 79 positions along
        # the 128 lanes once no scan along them stands in its way, every
        # shift becomes a lane shift of the block, and q13's predicate
        # takes 0.145 s a request for 0.050 (PERF.md section 6, PR 51).
        chars = with_layout_constraint(chars, Layout(major_to_minor=(1, 0)))
    p = Column(STRING, lengths, None, chars=chars)
    n = p.size
    w = int(p.chars.shape[1])
    jdx = jnp.arange(w + 1, dtype=jnp.int32)
    # '_' advances one CHARACTER (Spark semantics): position j in [0, w]
    # is a character boundary iff j == 0 or the byte at j is not a UTF-8
    # continuation byte (0x80-0xBF); one-char advance moves each boundary
    # to the NEXT boundary via a prev-boundary gather. On pure-ASCII data
    # every position is a boundary and this degenerates to the one-byte
    # shift. '%' gaps stay byte-based: a valid-UTF-8 literal can never
    # match starting at a continuation byte (lead bytes are < 0x80 or
    # >= 0xC0), so byte-anchoring and char-anchoring agree.
    # boundary at position j <=> the byte AT j starts a character (j = 0
    # and j = w are always boundaries; chars past a row's length are
    # zero-padded, i.e. non-continuation, so the row end works out too)
    if any(g[0] for g in gaps) or tail_gap[0]:
        # only '_'-bearing patterns pay for the boundary machinery
        cont = (p.chars & 0xC0) == 0x80                  # (n, w)
        is_b = jnp.concatenate(
            [jnp.ones((n, 1), jnp.bool_), ~cont[:, 1:],
             jnp.ones((n, 1), jnp.bool_)], axis=1)       # (n, w+1)
        pos_if_b = jnp.where(is_b, jdx[None, :], -1)
        pb_incl = jax.lax.associative_scan(jnp.maximum, pos_if_b, axis=1)
        prev_b = jnp.concatenate(
            [jnp.full((n, 1), -1, jdx.dtype), pb_incl[:, :-1]], axis=1)

        def advance_chars(r, k):
            for _ in range(k):
                r = (is_b & (prev_b >= 0) & jnp.take_along_axis(
                    r, jnp.clip(prev_b, 0, w), axis=1))
            return r
    else:
        def advance_chars(r, k):  # pragma: no cover - zero-count gaps
            return r

    # reach[j] True: pattern consumed so far can end exactly at byte j. After
    # a floating gap reach is its own prefix-or, which one number a row says
    # whole: ``first``, the least position set (w + 1: none), reach[j] being
    # j >= first. It is carried as that int32[n] while the pattern allows
    # (``first is not None``), and spread into booleans only for a step that
    # needs them: a '_' gap, an anchored tail.
    reach = jnp.zeros((n, w + 1), jnp.bool_).at[:, 0].set(True)
    # nothing consumed yet ends at byte 0: a leading '%' floats from there
    first = jnp.zeros((n,), jnp.int32) if gaps[0] == (0, True) else None
    after = gaps[1:] + (tail_gap,)  # the gap that follows each segment
    for seg, (mincnt, floating), nxt in zip(segs, gaps, after):
        # gap: advance exactly mincnt chars (then any amount if floating)
        if first is None:
            if mincnt:
                reach = advance_chars(reach, mincnt)
            reach = reach & (jdx[None, :] <= p.data[:, None])
            if floating:
                first = _first_set(reach, w + 1)
        if not seg:
            continue
        win = _needle_windows(p, seg)  # (n, w): match starting at j
        if first is not None and nxt == (0, True):
            # between two floating gaps only the leftmost match matters: it
            # ends at first + len(seg) <= the row's length, or past w + 1
            first = _first_set(win & (jdx[:w][None, :] >= first[:, None]),
                               w + 1) + len(seg)
            continue
        if first is not None:
            reach, first = jdx[None, :] >= first[:, None], None
        ok_start = jnp.concatenate(
            [win, jnp.zeros((n, 1), jnp.bool_)], axis=1)
        moved = jnp.roll(reach & ok_start, len(seg), axis=1)
        reach = moved & (jdx[None, :] >= len(seg))
    mincnt, floating = tail_gap
    if first is not None:
        if tail_gap == (0, True):
            return first <= p.data
        reach = jdx[None, :] >= first[:, None]
    if mincnt:
        reach = advance_chars(reach, mincnt)
    reach = reach & (jdx[None, :] <= p.data[:, None])
    if floating:
        hit = jnp.any(reach, axis=1)
    else:
        hit = jnp.take_along_axis(
            reach, jnp.clip(p.data, 0, w)[:, None], axis=1)[:, 0]
    return hit


def _first_set(mask: jnp.ndarray, none: int) -> jnp.ndarray:
    """int32[n]: the least position set in a row of ``mask``, ``none``
    where no position is."""
    jdx = jnp.arange(mask.shape[1], dtype=jnp.int32)
    return jnp.min(jnp.where(mask, jdx[None, :], jnp.int32(none)), axis=1,
                   initial=none)


# ---- transforms ------------------------------------------------------------


@func_range("substring")
def substring(col: Column, start: int, length: int | None = None) -> Column:
    """Byte-range substring (cuDF strings::slice_strings with fixed
    bounds): 0-based ``start``, optional ``length`` (None = to end).
    Negative ``start`` counts from the row end, Spark substr semantics.
    Byte-based: callers ensure boundaries are character-aligned for
    multi-byte UTF-8 (the cuDF kernel's posture)."""
    p = pad_strings(col)
    mat, lengths = p.chars, p.data
    w = int(mat.shape[1])
    if start < 0:
        # Spark substringSQL: the end is computed from the UNCLAMPED
        # position, so substr('abc', -5, 2) is '' (end = -2+2 = 0), not 'ab'
        raw = lengths + start
        begin = jnp.clip(raw, 0, lengths)
        if length is None:
            out_len = lengths - begin
        else:
            end = jnp.clip(raw + length, 0, lengths)
            out_len = jnp.maximum(end - begin, 0)
    else:
        begin = jnp.minimum(jnp.full_like(lengths, start), lengths)
        if length is None:
            out_len = lengths - begin
        else:
            out_len = jnp.clip(jnp.full_like(lengths, length), 0,
                               lengths - begin)
    src = begin[:, None] + jnp.arange(w, dtype=jnp.int32)[None, :]
    keep = jnp.arange(w, dtype=jnp.int32)[None, :] < out_len[:, None]
    out = jnp.where(keep, jnp.take_along_axis(
        mat, jnp.clip(src, 0, w - 1), axis=1), jnp.uint8(0))
    return Column(STRING, out_len.astype(jnp.int32), col.validity, chars=out)


def _host_case(col: Column, to_upper: bool) -> Column:
    """Full Unicode case mapping on host (Python's str.upper/lower applies
    the same Unicode full case mapping Java uses under Locale.ROOT, incl.
    one-to-many expansions like ß -> SS). The price is a device->host
    round trip — only taken when the column actually holds non-ASCII."""
    vals = col.to_pylist()
    out = [None if v is None else (v.upper() if to_upper else v.lower())
           for v in vals]
    return pad_strings(Column.from_pylist(out, STRING))


def _ascii_case(col: Column, to_upper: bool) -> Column:
    p = pad_strings(col)
    mat = p.chars
    if bool(jnp.any(mat >= 0x80)):
        # non-ASCII: the Unicode device engine (per-position classify +
        # case-LUT gather + in-place re-encode) handles every row whose
        # characters have 1:1 length-preserving mappings; only rows with
        # SPECIAL characters (ß→SS expansions, length-changing maps,
        # astral chars, invalid UTF-8) take the host engine
        from spark_rapids_jni_tpu.ops.unicode_case_device import (
            case_map_device,
        )

        out, row_special = case_map_device(mat, to_upper)
        spec_np = np.asarray(row_special)
        if col.validity is not None:
            # null rows' bytes are don't-care: never decode them
            spec_np = spec_np & np.asarray(col.validity)
        spec_idx = np.flatnonzero(spec_np)
        if spec_idx.size == 0:
            return Column(STRING, p.data, col.validity, chars=out)
        # per-row merge: only the SPECIAL rows (expansions, length-
        # changing maps, final sigma, invalid sequences) cross to the
        # host — the device mapping for every other row is kept
        lens_np = np.asarray(p.data)
        spec_rows = np.asarray(mat[jnp.asarray(spec_idx)])
        mapped_vals = []
        for row_i, i in enumerate(spec_idx):
            raw = spec_rows[row_i, : lens_np[i]].tobytes().decode()
            mapped_vals.append(raw.upper() if to_upper else raw.lower())
        mapped_bytes = [v.encode() for v in mapped_vals]
        w_out = max(int(mat.shape[1]),
                    max(len(b) for b in mapped_bytes))
        if w_out > mat.shape[1]:
            out = jnp.concatenate(
                [out, jnp.zeros((out.shape[0], w_out - mat.shape[1]),
                                jnp.uint8)], axis=1)
        host_mat = np.zeros((spec_idx.size, w_out), np.uint8)
        host_lens = np.zeros(spec_idx.size, np.int32)
        for row_i, b in enumerate(mapped_bytes):
            host_mat[row_i, : len(b)] = np.frombuffer(b, np.uint8)
            host_lens[row_i] = len(b)
        idx = jnp.asarray(spec_idx.astype(np.int32))
        out = out.at[idx].set(jnp.asarray(host_mat))
        lengths = p.data.at[idx].set(jnp.asarray(host_lens))
        return Column(STRING, lengths, col.validity, chars=out)
    if to_upper:
        out = jnp.where((mat >= ord("a")) & (mat <= ord("z")), mat - 32, mat)
    else:
        out = jnp.where((mat >= ord("A")) & (mat <= ord("Z")), mat + 32, mat)
    return Column(STRING, p.data, col.validity, chars=out)


@func_range("string_upper")
def upper(col: Column) -> Column:
    """Spark upper: ASCII and 1:1 length-preserving Unicode mappings ride
    the device path; rows with special characters fall back to the host
    Unicode engine."""
    return _ascii_case(col, True)


@func_range("string_lower")
def lower(col: Column) -> Column:
    """Spark lower: ASCII and 1:1 length-preserving Unicode mappings ride
    the device path; rows with special characters fall back to the host
    Unicode engine."""
    return _ascii_case(col, False)


# ---- regexp (host engine) --------------------------------------------------
#
# Spark's regexp functions compile java.util.regex patterns per-row on the
# GPU in cuDF; a device regex VM is out of scope here, so these run the
# HOST engine (Python `re`) — the documented two-engine posture
# (get_json_object precedent): correct results, device->host round trip.
# Java-compat measures: patterns compile with re.ASCII so \d/\w/\s/\b are
# the ASCII classes java.util.regex defaults to; possessive quantifiers
# (a*+) work natively on Python 3.11+; \p{...} classes are rejected by
# compile (fail loudly, never silently different).


def _java_replacement_to_python(rep: str, n_groups: int) -> str:
    """Java Matcher.appendReplacement syntax -> Python sub template.
    ``\\x`` in Java means LITERAL x (so ``\\n`` is the letter n, not a
    newline); ``$digits`` binds greedily to the longest prefix that is a
    valid group number <= ``n_groups`` (Java's rule — '$10' with two
    groups is group 1 then literal '0')."""
    out = []
    i = 0
    while i < len(rep):
        c = rep[i]
        if c == "\\":
            if i + 1 >= len(rep):
                raise ValueError(
                    "invalid regexp replacement: trailing backslash")
            nxt = rep[i + 1]
            out.append("\\\\" if nxt == "\\" else nxt)
            i += 2
            continue
        if c == "$":
            j = i + 1
            if j >= len(rep) or not rep[j].isdigit():
                raise ValueError(
                    f"invalid regexp replacement {rep!r}: '$' must be "
                    f"followed by a group number (escape literal '$' "
                    f"with a backslash)")
            # greedy: extend while the accumulated number stays a valid
            # group reference
            g = int(rep[j])
            j += 1
            while j < len(rep) and rep[j].isdigit()                     and g * 10 + int(rep[j]) <= n_groups:
                g = g * 10 + int(rep[j])
                j += 1
            if g > n_groups:
                raise ValueError(
                    f"invalid regexp replacement {rep!r}: group {g} "
                    f"exceeds the pattern's {n_groups} group(s)")
            out.append(f"\\g<{g}>")
            i = j
            continue
        out.append(c)  # backslashes were consumed by the branch above
        i += 1
    return "".join(out)


def _compile_java_regex(pattern: str):
    """Compile with re.ASCII so \\d/\\w/\\s/\\b mean what java.util.regex
    means by default ([0-9] etc.) — Python's Unicode-aware classes would
    silently match differently than Spark. Java-only character-class
    syntax Python would silently mis-parse (``[a-z&&[b]]`` intersection,
    nested classes) is rejected up front."""
    import re as _re

    # scan for class intersection / nesting inside [...] — Python re
    # compiles both without error but with different semantics
    depth = 0
    i = 0
    while i < len(pattern):
        c = pattern[i]
        if c == "\\":
            i += 2
            continue
        if c == "[":
            if depth > 0:
                raise ValueError(
                    f"unsupported java.util.regex syntax in {pattern!r}: "
                    f"nested character class (Python re would silently "
                    f"parse it differently)")
            depth = 1
        elif c == "]" and depth:
            depth = 0
        elif depth and pattern.startswith("&&", i):
            raise ValueError(
                f"unsupported java.util.regex syntax in {pattern!r}: "
                f"character-class intersection '&&' (Python re would "
                f"silently parse it differently)")
        i += 1
    return _re.compile(pattern, _re.ASCII)


def _host_regexp(col: Column, rx, fn):
    vals = col.to_pylist()
    return [None if v is None else fn(rx, v) for v in vals]


@func_range("regexp_contains", record=True)
def regexp_contains(col: Column, pattern: str) -> Column:
    """RLIKE / regexp-find (cuDF contains_re): True when the pattern
    matches anywhere in the string.

    Two engines (the get_json_object posture): patterns inside the
    DFA-compilable subset run ON DEVICE — a host-compiled byte DFA
    executed as one int32 gather per char column over the padded layout
    (``ops/regex_device.py``); everything else (backrefs, lookaround,
    class intersection, …) falls back to the host java.util.regex
    emulation. Rows with embedded NUL bytes would alias the device
    engine's end-of-row sentinel, so such columns are detected with one
    device reduction and routed to the host engine whole.

    Config ``regex.force_engine`` pins "device" (raises on unsupported
    patterns) or "host" for testing."""
    from spark_rapids_jni_tpu.types import BOOL8
    from spark_rapids_jni_tpu.utils.config import get_option

    validity = col.valid_mask() if col.validity is not None else None
    force = get_option("regex.force_engine")
    if force == "host":
        telemetry.record_fallback(
            "regexp_contains", "regex.force_engine=host pin", rows=col.size)
    else:
        from spark_rapids_jni_tpu.ops import regex_device as rd

        try:
            comp = rd.compile_pattern(pattern)
        except rd.RegexUnsupported as exc:
            if force == "device":
                raise
            telemetry.record_fallback(
                "regexp_contains", f"unsupported regex atom: {exc}",
                rows=col.size)
            comp = None
        if comp is not None:
            pc = pad_strings(col)
            # eligibility: zero count per row must equal the pad tail,
            # i.e. no NUL inside the content bytes
            w = pc.chars.shape[1]
            nzeros = jnp.sum((pc.chars == 0).astype(jnp.int32), axis=1)
            clean = bool(jnp.all(nzeros == (w - pc.data)))
            if clean:
                # the NUL check already synced lengths; reuse them to
                # skip run_dfa's defensive extra zero column when the
                # widest row leaves padding slack
                n_rows = pc.chars.shape[0]
                needs_pad = bool(
                    n_rows and int(jnp.max(pc.data)) >= w)
                flags = rd.run_dfa(
                    pc.chars, comp,
                    ensure_sentinel=needs_pad).astype(jnp.uint8)
                return Column(BOOL8, flags, validity)
            if force == "device":
                raise ValueError(
                    "regex.force_engine=device but the column has "
                    "embedded NUL bytes (sentinel alias)")
            telemetry.record_fallback(
                "regexp_contains",
                "embedded NUL bytes alias the 0x00 padding sentinel",
                rows=col.size)
    rx = _compile_java_regex(pattern)
    out = _host_regexp(col, rx, lambda r, v: r.search(v) is not None)
    flags = jnp.asarray([bool(v) for v in out], jnp.uint8)
    return Column(BOOL8, flags, validity)


def _device_capture_eligible(col: Column, pattern: str, op: str):
    """Shared extract/replace device-path gate: the pattern parses into
    the linear capture subset AND the column is all-ASCII with no
    embedded NULs (byte-level ``.``/negated classes equal char-level
    exactly on ASCII data; NULs alias the padding sentinel). Returns
    (compiled, padded_col) or (None, None) for host fallback; respects
    ``regex.force_engine`` like regexp_contains. Every (None, None)
    return records a telemetry fallback under ``op`` (the dispatcher
    the gate is deciding for)."""
    from spark_rapids_jni_tpu.utils.config import get_option

    force = get_option("regex.force_engine")
    if force == "host":
        telemetry.record_fallback(
            op, "regex.force_engine=host pin", rows=col.size)
        return None, None
    from spark_rapids_jni_tpu.ops import regex_capture_device as rc

    try:
        comp = rc.compile_linear(pattern)
    except rc.RegexUnsupported as exc:
        if force == "device":
            raise
        telemetry.record_fallback(
            op, f"unsupported linear-capture atom: {exc}", rows=col.size)
        return None, None
    pc = pad_strings(col)
    n, w = pc.chars.shape
    if n == 0:
        telemetry.record_fallback(
            op, "empty column: no rows to run on device", rows=0)
        return None, None
    nzeros = jnp.sum((pc.chars == 0).astype(jnp.int32), axis=1)
    clean = bool(jnp.all(nzeros == (w - pc.data))
                 & jnp.all(pc.chars < 0x80))
    if not clean:
        if force == "device":
            raise ValueError(
                "regex.force_engine=device but the column has embedded "
                "NULs or non-ASCII bytes (outside the capture engine's "
                "correctness scope)")
        telemetry.record_fallback(
            op,
            "embedded NULs or non-ASCII bytes (sentinel alias / outside "
            "the byte-level capture engine's correctness scope)",
            rows=col.size)
        return None, None
    # the boundary walk reads positions up to W inclusive: guarantee a
    # sentinel column (same rule as run_dfa's ensure_sentinel)
    if int(jnp.max(pc.data)) >= w:
        pc = Column(pc.dtype, pc.data, pc.validity, chars=jnp.concatenate(
            [pc.chars, jnp.zeros((n, 1), jnp.uint8)], axis=1))
    return comp, pc


@func_range("regexp_extract", record=True)
def regexp_extract(col: Column, pattern: str, group: int = 1) -> Column:
    """Spark regexp_extract: the group'th capture of the first match,
    '' when the pattern does not match (Spark returns empty string, not
    null).

    Two engines: LINEAR patterns (concatenated literals/classes with
    flat capture groups) over ASCII data run ON DEVICE via the
    reverse-feasibility capture engine (ops/regex_capture_device.py) —
    scatter-free, O(elements * n * W); everything else takes the host
    java.util.regex emulation."""
    rx = _compile_java_regex(pattern)
    if not 0 <= group <= rx.groups:
        # validate up front like regexp_replace — otherwise an invalid
        # index only crashes on rows that happen to match (Spark raises)
        raise ValueError(
            f"regexp_extract group {group} out of range: pattern has "
            f"{rx.groups} group(s)")
    comp, pc = _device_capture_eligible(col, pattern, "regexp_extract")
    if comp is not None:
        from spark_rapids_jni_tpu.ops import regex_capture_device as rc

        lengths, chars = rc.extract_device(pc.chars, comp, group,
                                           dispatch_key=pattern)
        return Column(STRING, lengths, pc.validity, chars=chars)

    def ext(r, v):
        m = r.search(v)
        if m is None:
            return ""
        g = m.group(group)
        return "" if g is None else g

    out = _host_regexp(col, rx, ext)
    return pad_strings(Column.from_pylist(out, STRING))


@func_range("regexp_replace", record=True)
def regexp_replace(col: Column, pattern: str, replacement: str) -> Column:
    """Spark regexp_replace: every match replaced; Java $N group refs
    (greedy multi-digit) and \\x literal escapes supported.

    Literal replacements of LINEAR patterns over ASCII data run ON
    DEVICE (bounded match rounds; rows with more matches than the
    round budget re-route the whole column to the host engine via the
    overflow flag — the narrowing_overflow posture). Group-ref
    replacements and non-linear patterns take the host engine."""
    rx = _compile_java_regex(pattern)
    rep = _java_replacement_to_python(replacement, rx.groups)
    literal_rep = "$" not in replacement and "\\" not in replacement
    if literal_rep:
        comp, pc = _device_capture_eligible(col, pattern, "regexp_replace")
        if comp is not None and all(
                el.lo == 0 for el in comp.pattern.elements):
            # a pattern that can match empty matches at EVERY position:
            # any row longer than the round budget is guaranteed to
            # overflow, so the device pass would be dead work
            telemetry.record_fallback(
                "regexp_replace",
                "empty-matching pattern: every position matches, device "
                "round budget would always overflow", rows=col.size)
            comp = None
        if comp is not None:
            from spark_rapids_jni_tpu.ops import regex_capture_device as rc

            out_len, out_chars, overflowed = rc.replace_device(
                pc.chars, pc.data, comp, replacement.encode(),
                dispatch_key=pattern)
            if not bool(overflowed):
                return Column(STRING, out_len, pc.validity,
                              chars=out_chars)
            # else: some row had more matches than the round budget —
            # fall through to the host engine for the whole column
            telemetry.record_fallback(
                "regexp_replace",
                "match-round budget overflow: a row exceeded the device "
                "replace rounds; rerouting whole column to host",
                rows=col.size)
    else:
        telemetry.record_fallback(
            "regexp_replace",
            "group-ref/escape replacement: device engine handles literal "
            "replacements only", rows=col.size)
    out = _host_regexp(col, rx, lambda r, v: r.sub(rep, v))
    return pad_strings(Column.from_pylist(out, STRING))
