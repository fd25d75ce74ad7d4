"""A table that arrives as a file: one Spark scan task's Parquet split as
a binding the server resolves, admits, decodes and stages itself.

A Spark task of a scan stage holds a ``PartitionedFile(path, start,
length)`` and a read schema; the reference's ``ParquetFooter.
readAndFilter(buffer, partOffset, partLength, names, ...)`` exists for
exactly that call. ``ParquetSplit`` is that pair as a value a client binds
to a plan's scan (``session.submit(plan, {"lineitem": split})``), and
``ParquetScan`` is what the footer makes of it, once a request:

* **names to column indices**, in the order the plan's scan expects (the
  prune keeps the request's order, whatever the file's); a name the file
  lacks is a classified ``MalformedInputError``;
* **the byte range to row groups**, by ``read_and_filter``'s midpoint rule
  (a negative ``part_length`` keeps every group, as the reference
  documents), in the file's own numbering, which ``read_table`` takes;
* **rows and decoded bytes** from the row groups' metadata, before a page
  is read: what the server admits, and what the cache key's plan half
  needs (``fusion.plan_fingerprint`` resolves statics from row counts).

``ParquetScan.stage`` runs after admission, on the server's worker: every
column chunk (a column of a row group) is one ``read_table(..., columns=
[c], row_groups=[g], stage="host")`` on the shared decode pool, and each
decoded row group is copied to the device and written into its rows of ONE
preallocated buffer a column (a donated ``dynamic_update_slice``: in
place) while the pool decodes the next. No second full copy exists on the
host (a group's chunks are dropped once written) or on the device. A chunk
that fails to decode fails the scan with the reader's
``MalformedFileError``: the chunks not yet started are cancelled, the
running ones awaited, and no partial table leaves.

Flat fixed-width columns only: a STRING, a DECIMAL128 or a nested column
in the read schema is a ``NotImplementedError`` at ``resolve`` (no caller
binds one yet; the columns a projection skips may be of any type).

Spans, under the request's trees: ``scan.footer`` (read, prune, filter; on
the submitting thread, where the cache key and the estimate need it) and,
on the worker, ``scan`` with ``scan.decode`` (its ``scan.decode.chunk``
children run on the pool's threads) and one ``scan.stage`` a row group
plus the last, which waits until the table is ready. Counters:
``scan.row_groups``, ``scan.columns_read``, ``scan.columns_pruned``,
``scan.file_bytes`` (compressed bytes of the column chunks read),
``scan.decoded_bytes`` (bytes staged to the device).
"""

from __future__ import annotations

import concurrent.futures
import functools
import os
import struct
from dataclasses import dataclass
from typing import Optional

import numpy as np

from spark_rapids_jni_tpu.columnar import Column, Table
from spark_rapids_jni_tpu.parquet.footer import (
    MalformedFileError,
    ParquetFooter,
)
from spark_rapids_jni_tpu.parquet.reader import (
    _map_dtype,
    _validate_parquet_envelope,
    read_table,
)
from spark_rapids_jni_tpu.runtime import integrity
from spark_rapids_jni_tpu.telemetry import spans
from spark_rapids_jni_tpu.telemetry.registry import REGISTRY
from spark_rapids_jni_tpu.types import DType

__all__ = ["ParquetSplit", "ParquetScan"]

_OPTIONAL = 1   # parquet.thrift FieldRepetitionType


@dataclass(frozen=True)
class ParquetSplit:
    """``path``'s bytes ``[part_offset, part_offset + part_length)`` read as
    ``columns`` (names, in the order the plan's scan expects). A negative
    ``part_length`` is the whole file. ``dtypes`` gives a logical type
    where the plan's differs from what the file's annotations map to (an
    unannotated INT64 the plan reads as ``decimal64(-2)``); ``None``, or a
    ``None`` entry, takes the file's."""

    path: str
    columns: tuple
    part_offset: int = 0
    part_length: int = -1
    dtypes: Optional[tuple] = None

    def __post_init__(self) -> None:
        object.__setattr__(self, "path", os.fspath(self.path))
        object.__setattr__(self, "columns", tuple(self.columns))
        if self.dtypes is not None:
            object.__setattr__(self, "dtypes", tuple(self.dtypes))
            if len(self.dtypes) != len(self.columns):
                raise ValueError("dtypes and columns differ in length")
        if not self.columns or len(set(self.columns)) != len(self.columns):
            raise ValueError(
                f"a split reads at least one column, each once: "
                f"{self.columns!r}")

    def resolve(self) -> "ParquetScan":
        """Read the footer and turn the split into what a reader takes."""
        names = list(self.columns)
        with ParquetFooter.read_and_filter(
                _footer_bytes(self.path), int(self.part_offset),
                int(self.part_length), names, [0] * len(names),
                len(names)) as footer:
            leaves = footer.leaves()
            groups = footer.row_groups()
            file_bytes = footer.compressed_bytes
            file_columns = footer.file_columns
        found = {leaf[0] for leaf in leaves}
        missing = [n for k, n in enumerate(names) if k not in found]
        if missing:
            raise integrity.reject_malformed(
                "parquet.split",
                f"{self.path}: the file has no flat column named {missing}",
                columns=missing)
        dtypes = []
        for k, (_, _, phys, conv, scale, tlen, _) in enumerate(leaves):
            dtype = _map_dtype(phys, conv, scale, tlen)
            if self.dtypes is not None and self.dtypes[k] is not None:
                dtype = self.dtypes[k]
            if not dtype.is_fixed_width:
                raise NotImplementedError(
                    f"column {names[k]!r} is {dtype}: a split stages flat "
                    f"fixed-width columns only")
            dtypes.append(dtype)
        # a row's decoded bytes: its values, and a validity byte for every
        # optional column (staged only where a group holds a null)
        row_bytes = sum(d.storage_dtype.itemsize for d in dtypes) + sum(
            leaf[6] == _OPTIONAL for leaf in leaves)
        num_rows = sum(rows for _, rows in groups)
        return ParquetScan(
            self, tuple(groups), tuple(leaf[1] for leaf in leaves),
            tuple(dtypes), num_rows, num_rows * row_bytes,
            file_bytes, file_columns - len(leaves))


def _footer_bytes(path: str) -> bytes:
    """The raw thrift footer of the file (no PAR1 framing), its envelope
    checked first: a truncated or clobbered file is rejected classified."""
    _validate_parquet_envelope(path)
    with open(path, "rb") as fh:
        fh.seek(-8, os.SEEK_END)
        (length,) = struct.unpack("<I", fh.read(4))
        fh.seek(-8 - length, os.SEEK_END)
        return fh.read(length)


@functools.lru_cache(maxsize=None)
def _write_rows():
    """``buf`` with ``piece`` written at row ``start``; ``buf`` is donated,
    so the write is in place and the column is never copied whole."""
    import jax
    from jax import lax

    return jax.jit(
        lambda buf, piece, start: lax.dynamic_update_slice_in_dim(
            buf, piece, start, 0),
        donate_argnums=0)


@dataclass(frozen=True)
class ParquetScan:
    """A ``ParquetSplit`` resolved against its file's footer: what the
    server admits (``nbytes``: the decoded bytes the footer states, an
    optional column counted with its validity), keys its cache by and,
    after admission, decodes and stages."""

    split: ParquetSplit
    row_groups: tuple       # (file's row-group index, rows), in file order
    leaves: tuple           # file's leaf index of each column read
    dtypes: tuple           # the DType each column is staged as
    num_rows: int
    nbytes: int
    file_bytes: int         # compressed bytes of the column chunks read
    columns_pruned: int     # leaves of the file the projection skips

    def _decode(self, group: int, column: int, parent, cancel_token):
        """One column chunk (a column of a row group) to a one-column
        ``HostTableChunk``, on a decode-pool thread."""
        with spans.child("scan.decode.chunk", parent=parent,
                         row_group=group, column=column):
            if cancel_token is not None:
                cancel_token.check("scan.decode")
            return read_table(self.split.path, [self.leaves[column]],
                              [group], stage="host")

    def stage(self, pool, cancel_token=None) -> Table:
        """Decode every column chunk on ``pool`` and assemble the row
        groups on the device as they come; returns once the table is
        ready. The caller holds the reservation (``nbytes`` was admitted).

        A task is one column chunk, not one row group: seven row groups
        leave a pool of four two uneven rounds and a larger pool threads
        idle, and one process's scans then differ from the next's
        by 14% (0.267-0.310 s on four threads, decode alone on the TPU VM);
        49 chunks keep every thread busy to the end (my chip runs, PR
        34)."""
        import jax

        with spans.child("scan", path=self.split.path, rows=self.num_rows,
                         row_groups=len(self.row_groups)) as scan_span:
            columns = [_ColumnAssembly(d, self.num_rows) for d in self.dtypes]
            with spans.child("scan.decode") as decode_span:
                futures = [
                    [pool.submit(self._decode, g, k, decode_span or None,
                                 cancel_token)
                     for k in range(len(columns))]
                    for g, _ in self.row_groups]
                try:
                    start = 0
                    for (group, rows), of_group in zip(self.row_groups,
                                                       futures):
                        chunks = [f.result() for f in of_group]
                        with spans.child("scan.stage", parent=scan_span,
                                         row_group=group):
                            for column, chunk in zip(columns, chunks):
                                self._check(chunk, group, rows)
                                column.add(chunk.cols[0], start)
                        del chunks
                        start += rows
                except BaseException:
                    waiting = [f for of_group in futures for f in of_group]
                    for future in waiting:
                        future.cancel()
                    concurrent.futures.wait(waiting)
                    raise
            with spans.child("scan.stage", ready=True):
                table = Table([c.finish() for c in columns])
                jax.block_until_ready(
                    [b for c in table.columns for b in (c.data, c.validity)
                     if b is not None])
        staged = sum(c.nbytes for c in columns)
        REGISTRY.counter("scan.row_groups").inc(len(self.row_groups))
        REGISTRY.counter("scan.columns_read").inc(len(self.leaves))
        REGISTRY.counter("scan.columns_pruned").inc(self.columns_pruned)
        REGISTRY.counter("scan.file_bytes").inc(self.file_bytes)
        REGISTRY.counter("scan.decoded_bytes").inc(staged)
        return table

    def _check(self, chunk, group: int, rows: int) -> None:
        if chunk.num_rows != rows or len(chunk.cols) != 1:
            raise integrity.reject_malformed(
                "parquet.split",
                f"{self.split.path}: a column chunk of row group {group} "
                f"decoded to {chunk.num_rows} rows of {len(chunk.cols)} "
                f"columns, the footer states {rows} of one",
                exc_type=MalformedFileError)


class _ColumnAssembly:
    """One column of a scan on its way to the device: one buffer of the
    scan's rows, each row group written into its rows as it arrives."""

    def __init__(self, dtype: DType, num_rows: int):
        self.dtype = dtype
        self.num_rows = int(num_rows)
        self.data = None
        self.validity = None
        self.nbytes = 0

    def add(self, snap, start: int) -> None:
        import jax
        import jax.numpy as jnp

        _, data, validity, _, _ = snap
        data = data.astype(self.dtype.storage_dtype, copy=False)
        self.nbytes += data.nbytes
        at = np.int32(start)
        if self.data is None:
            self.data = jnp.zeros((self.num_rows,), data.dtype)
        self.data = _write_rows()(self.data, jax.device_put(data), at)
        if validity is not None:
            if self.validity is None:
                self.validity = jnp.ones((self.num_rows,), jnp.bool_)
                self.nbytes += self.num_rows
            self.validity = _write_rows()(
                self.validity, jax.device_put(validity), at)

    def finish(self) -> Column:
        import jax.numpy as jnp

        if self.data is None:   # the split selected no row group
            self.data = jnp.zeros((0,), self.dtype.storage_dtype)
        return Column(self.dtype, self.data, self.validity)
