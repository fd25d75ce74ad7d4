"""Ragged-row transpose kernel: column->row byte interleave (the
``row_conversion`` hot path) as one streaming Pallas pass.

The XLA path builds the fixed-width row image by a wide lane
concatenation of per-column byte pieces (+ alignment zero-pads + packed
validity bytes). This kernel replaces the interleave. Rows ride the LANE
dimension on the way in: each piece arrives transposed, (width, n) int32
(byte values are exact in int32), so a grid step reads a dense
(width, 256) slab — a (256, width) block would pad its 1/4/8-byte last
dimension to 128 lanes, 16-128x the bytes, which at 6M rows exhausted
HBM on the v5e. The step assembles the (row_width, 256) image by
broadcasted_iota where-selects, one static output byte row at a time,
and stores its transpose, the (256, row_width) output tile. Alignment
gaps and the trailing 64-bit row pad fall out of the zero-initialized
accumulator, so the result is byte-for-byte
``jnp.concatenate(pieces, axis=1)``.

Rows are "ragged" across schemas, not within a batch: the kernel closure
is specialized per (starts, widths) layout — exactly the static schema
information ``compute_fixed_width_layout`` derives — and dispatch caches
one executable per schema x bucket like every other row-wise op.

Wide rows fall back to the oracle with reason ``row_too_wide``: the
select-assembly unrolls one op per row byte, so the tier caps the row
image at MAX_ROW_BYTES (two 128-lane tiles; the reference's shared-
memory row limit lives in the same order of magnitude).
"""

from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp

from spark_rapids_jni_tpu.ops.pallas import register_kernel

_ROWS = 256          # rows per grid step (two 128-lane tiles)
MAX_ROW_BYTES = 256  # row-image cap (select-assembly unrolls per byte)

register_kernel(
    "row_conversion.to_rows",
    oracle="spark_rapids_jni_tpu.ops.row_conversion._to_rows_impl "
           "(tier=xla lane concatenation of byte pieces)",
    doc="column->row byte interleave of fixed-width pieces + packed "
        "validity into the uint8 row image, 256 rows per grid step",
)


def unsupported_reason(n: int, size_per_row: int) -> str | None:
    """Static (trace-time) eligibility; non-None routes to the oracle."""
    if n == 0:
        return "empty_input"
    if size_per_row > MAX_ROW_BYTES:
        return "row_too_wide"
    return None


def _round_up(x: int, mult: int) -> int:
    return -(-x // mult) * mult


def _make_kernel(starts_widths: tuple[tuple[int, int], ...], total: int):
    """Kernel closure over the static row layout: piece ``pi`` lands at
    byte offset ``starts_widths[pi][0]``; untouched bytes stay zero
    (alignment gaps, trailing row pad)."""

    def kernel(*refs):
        out_ref = refs[-1]
        byte_ids = jax.lax.broadcasted_iota(jnp.int32, (total, _ROWS), 0)
        acc = jnp.zeros((total, _ROWS), jnp.int32)
        for pi, (start, width) in enumerate(starts_widths):
            piece = refs[pi][...]              # (width, _ROWS): rows on lanes
            for k in range(width):
                row = piece[k:k + 1, :]        # (1, _ROWS) keepdims slice
                acc = jnp.where(byte_ids == start + k, row, acc)
        out_ref[...] = acc.T                   # (_ROWS, total)

    return kernel


def _lane_block(i):
    """index_map of an input piece: all of its byte rows, lane block i
    (explicit int32 zero: see ``block_index``)."""
    return (jnp.int32(0), i)


def _row_block(i):
    """index_map of the output: row block i, every byte column."""
    return (i, jnp.int32(0))


def assemble_rows(
    pieces: Sequence[jnp.ndarray],
    starts: Sequence[int],
    size_per_row: int,
    *,
    interpret: bool,
) -> jnp.ndarray:
    """Interleave uint8 ``pieces`` (each (n, w_i)) into the row image
    uint8[n, size_per_row], piece i starting at byte ``starts[i]``.
    Byte-identical to concatenating the pieces with zero-fill gaps."""
    from jax.experimental import pallas as pl

    n = pieces[0].shape[0]
    total = _round_up(size_per_row, 128)
    pad = (-n) % _ROWS
    nb = (n + pad) // _ROWS
    ins = []
    starts_widths = []
    for start, piece in zip(starts, pieces):
        a = piece.astype(jnp.int32).T          # bytes are exact in int32
        if pad:
            a = jnp.concatenate(
                [a, jnp.zeros((a.shape[0], pad), jnp.int32)], axis=1)
        ins.append(a)
        starts_widths.append((int(start), int(piece.shape[1])))
    out = pl.pallas_call(
        _make_kernel(tuple(starts_widths), total),
        out_shape=jax.ShapeDtypeStruct((nb * _ROWS, total), jnp.int32),
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((w, _ROWS), _lane_block) for _, w in starts_widths
        ],
        out_specs=pl.BlockSpec((_ROWS, total), _row_block),
        interpret=interpret,
    )(*ins)
    return out[:n, :size_per_row].astype(jnp.uint8)
