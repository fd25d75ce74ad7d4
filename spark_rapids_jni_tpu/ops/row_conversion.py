"""Row-major <-> column-major table conversion (component C1' — the TPU
equivalent of reference src/main/cpp/src/row_conversion.cu).

The byte-level row format is contract-identical to the reference
(RowConversion.java:40-99):

  * columns packed in schema order, each aligned to its own element size
    (row_conversion.cu:432-446);
  * one validity byte per 8 columns appended directly after the last column,
    byte-aligned, bit ``col % 8`` of byte ``col // 8`` set <=> valid
    (row_conversion.cu:158-165,255-272);
  * each row zero-padded to a 64-bit boundary (row_conversion.cu:454-455);
  * output split into batches of < 2**31 bytes, batch row counts a multiple
    of 32 (row_conversion.cu:476-511);
  * fixed-width types only (row_conversion.cu:515,573);
  * rows larger than ~1.5KB rejected — the reference's shared-memory limit
    (row_conversion.cu:334-347; documented as "1KB" in
    RowConversion.java:98-99). TPU has no such hardware limit; the check
    keeps API-contract parity and can be lifted via ``enforce_row_limit``.

The *implementation* is nothing like the CUDA kernel. The reference stages
row images through 48KB of shared memory with a 2-D thread grid and warp
ballots. On TPU the whole conversion is expressed as a static byte-layout
transform — per-column ``bitcast_convert_type`` to bytes, zero-pad columns,
validity packed via an (n,8)x(8,) weighted sum, and a single concatenate —
which XLA fuses into one HBM-bandwidth-bound copy. No scalar loops, no
dynamic shapes, so it tiles cleanly onto the VPU.

One deliberate difference: padding bytes are 0 (the reference leaves
whatever was in shared memory — i.e. garbage — in pad slots). Deterministic
output makes rows byte-comparable, which Spark range-partition sort needs.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from spark_rapids_jni_tpu.columnar import Column, Table
from spark_rapids_jni_tpu.columnar.bitmask import pack_bits_last_axis
from spark_rapids_jni_tpu.ops.bytecast import from_bytes, to_bytes
from spark_rapids_jni_tpu.types import DType
from spark_rapids_jni_tpu.utils.config import get_option
from spark_rapids_jni_tpu.utils.tracing import func_range

INT32_MAX = 2**31 - 1
# (48KB shared mem / 32-thread minimum block) in the reference sets the max
# row size; we enforce the same documented contract.
MAX_ROW_SIZE = 1536


def _align(offset: int, alignment: int) -> int:
    return (offset + alignment - 1) & ~(alignment - 1)


def compute_fixed_width_layout(
    schema: Sequence[DType],
) -> tuple[list[int], list[int], int]:
    """Return (column_start, column_size, size_per_row) for the packed row.

    Contract-identical to reference row_conversion.cu:432-456: each column is
    aligned to its own size, validity bytes ((ncols+7)//8) follow the last
    column unaligned, and the row is padded to 8 bytes.
    """
    column_start: list[int] = []
    column_size: list[int] = []
    at_offset = 0
    for dt in schema:
        if not (dt.is_fixed_width or dt.is_decimal128):
            raise TypeError("Only fixed width types are currently supported")
        # DECIMAL128 rows: 16-byte element, 16-byte alignment — the
        # reference's generic rule (alignment == element size,
        # row_conversion.cu:439-443) applied to __int128_t
        s = dt.size_bytes
        at_offset = _align(at_offset, s)
        column_start.append(at_offset)
        column_size.append(s)
        at_offset += s
    validity_bytes = (len(schema) + 7) // 8
    at_offset += validity_bytes
    return column_start, column_size, _align(at_offset, 8)


@dataclass
class RowsColumn:
    """One output batch: the LIST<INT8> column of the reference
    (row_conversion.cu:405-406) — ``data`` is the flat byte child, offsets
    are the implicit arithmetic sequence ``i * row_size``."""

    num_rows: int
    row_size: int
    data: jnp.ndarray  # uint8[num_rows * row_size]

    @property
    def offsets(self) -> jnp.ndarray:
        return jnp.arange(self.num_rows + 1, dtype=jnp.int32) * self.row_size

    @property
    def size_bytes(self) -> int:
        return self.num_rows * self.row_size


def _pack_validity_bytes(valids: jnp.ndarray) -> jnp.ndarray:
    """(n, ncols) bool -> (n, (ncols+7)//8) uint8, bit col%8 of byte col//8."""
    return pack_bits_last_axis(valids)


def _to_rows_impl(
    datas: list[jnp.ndarray],
    valids: list[jnp.ndarray],
    schema: tuple[DType, ...],
) -> jnp.ndarray:
    """Jittable core: full-table row image as uint8[n, size_per_row]."""
    column_start, column_size, size_per_row = compute_fixed_width_layout(schema)
    n = datas[0].shape[0]
    pieces: list[jnp.ndarray] = []
    starts: list[int] = []  # byte offset of each piece in the row image
    cursor = 0
    for i, dt in enumerate(schema):
        start, size = column_start[i], column_size[i]
        starts.append(start)
        pieces.append(to_bytes(datas[i], dt))
        cursor = start + size
    starts.append(cursor)
    pieces.append(_pack_validity_bytes(jnp.stack(valids, axis=1)))

    # interleave by lane concatenation: alignment gaps and the trailing
    # row pad are explicit zero pieces
    padded: list[jnp.ndarray] = []
    cursor = 0
    for start, piece in zip(starts, pieces):
        if start > cursor:  # alignment padding before this piece
            padded.append(jnp.zeros((n, start - cursor), dtype=jnp.uint8))
        padded.append(piece)
        cursor = start + piece.shape[1]
    if size_per_row > cursor:  # trailing pad to the 64-bit row boundary
        padded.append(jnp.zeros((n, size_per_row - cursor), dtype=jnp.uint8))
    return jnp.concatenate(padded, axis=1)


def _to_rows_dispatch(row_args, aux, rvs, *, schema):
    ((datas, valids),) = row_args
    return _to_rows_impl(datas, valids, schema)


@func_range("convert_to_rows")
def convert_to_rows(
    table: Table, *, enforce_row_limit: bool | None = None
) -> list[RowsColumn]:
    """Columnar -> packed rows. Returns one or more RowsColumn batches, each
    under 2**31 bytes with a 32-row-multiple row count (except the last),
    matching reference row_conversion.cu:458-517.

    ``enforce_row_limit`` defaults to the ``row_conversion.enforce_row_limit``
    config option (env SPARK_RAPIDS_TPU_ROW_CONVERSION_ENFORCE_ROW_LIMIT).
    """
    if enforce_row_limit is None:
        enforce_row_limit = get_option("row_conversion.enforce_row_limit")
    if table.num_columns == 0:
        raise ValueError("table must have at least one column")
    schema = tuple(table.schema())
    _, _, size_per_row = compute_fixed_width_layout(schema)
    if enforce_row_limit and size_per_row > MAX_ROW_SIZE:
        raise ValueError("Row size is too large to fit in shared memory")

    datas = [c.data for c in table.columns]
    valids = [c.valid_mask() for c in table.columns]
    from spark_rapids_jni_tpu.runtime import dispatch

    # padded tail rows pack to all-zero row images and are sliced off
    rows = dispatch.rowwise(
        "convert_to_rows", partial(_to_rows_dispatch, schema=schema),
        (datas, valids), statics=(schema,))  # (n, size_per_row)

    num_rows = table.num_rows
    max_rows_per_batch = (INT32_MAX // size_per_row) // 32 * 32
    out: list[RowsColumn] = []
    for row_start in range(0, max(num_rows, 1), max_rows_per_batch):
        count = min(num_rows - row_start, max_rows_per_batch)
        batch = rows[row_start : row_start + count].reshape(-1)
        out.append(RowsColumn(count, size_per_row, batch))
    return out


def _from_rows_impl(
    rows: jnp.ndarray, schema: tuple[DType, ...]
) -> tuple[list[jnp.ndarray], list[jnp.ndarray]]:
    """Jittable core over the 2-D row image uint8[n, size_per_row]."""
    column_start, column_size, size_per_row = compute_fixed_width_layout(schema)
    rows = rows.reshape(-1, size_per_row)
    datas, valids = [], []
    vld_base = column_start[-1] + column_size[-1] if schema else 0
    for i, dt in enumerate(schema):
        start, size = column_start[i], column_size[i]
        datas.append(from_bytes(rows[:, start : start + size], dt))
        vbyte = rows[:, vld_base + i // 8]
        valids.append(((vbyte >> (i % 8)) & 1).astype(jnp.bool_))
    return datas, valids


def _from_rows_dispatch(row_args, aux, rvs, *, schema):
    ((rows,),) = row_args
    return _from_rows_impl(rows, schema)


@func_range("convert_from_rows")
def convert_from_rows(rows: RowsColumn, schema: Sequence[DType]) -> Table:
    """Packed rows -> columnar. Validates the byte length against the layout
    like reference row_conversion.cu:536-542, and returns columns that always
    carry a validity mask (the reference allocates masks unconditionally,
    row_conversion.cu:551-555)."""
    schema_t = tuple(schema)
    for dt in schema_t:
        if not (dt.is_fixed_width or dt.is_decimal128):
            raise TypeError("Only fixed width types are currently supported")
    _, _, size_per_row = compute_fixed_width_layout(schema_t)
    if size_per_row != rows.row_size or rows.data.shape[0] != rows.num_rows * size_per_row:
        raise ValueError("The layout of the data appears to be off")
    from spark_rapids_jni_tpu.runtime import dispatch

    rows2d = rows.data.reshape(rows.num_rows, size_per_row)
    datas, valids = dispatch.rowwise(
        "convert_from_rows", partial(_from_rows_dispatch, schema=schema_t),
        (rows2d,), statics=(schema_t,))
    return Table(
        [Column(dt, d, v) for dt, d, v in zip(schema_t, datas, valids)]
    )
