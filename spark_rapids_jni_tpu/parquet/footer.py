"""Parquet footer prune/filter — Python surface over the native engine.

API parity with com.nvidia.spark.rapids.jni.ParquetFooter (reference
src/main/java/.../ParquetFooter.java:24-114): readAndFilter, getNumRows,
getNumColumns, serializeThriftFile, AutoCloseable semantics. The heavy
lifting is C++ (src/native/src/parquet_footer.cpp); objects cross the
boundary as int64 handles like the reference's jlong handles.
"""

from __future__ import annotations

import ctypes
from typing import Sequence

from spark_rapids_jni_tpu.runtime import load_native
from spark_rapids_jni_tpu.runtime.resilience import MalformedInputError
from spark_rapids_jni_tpu.utils.tracing import func_range


class NativeError(RuntimeError):
    """Raised when the native core reports a failure — the CudfException
    equivalent of the reference's CATCH_STD bridge."""


class MalformedFileError(MalformedInputError, NativeError):
    """Untrusted Parquet/ORC input failed structural validation.

    Dual-parented on purpose: :class:`MalformedInputError` classifies it
    for the serving stack (the server rejects that one query cleanly —
    never retried, never degraded, zero leaked reservations), while the
    :class:`NativeError` base keeps every legacy ``except NativeError``
    caller working — hardening the readers reclassifies failures, it
    does not change who catches them."""


class ParquetFooter:
    def __init__(self, handle: int):
        if handle == 0:
            raise ValueError("null footer handle")
        self._handle = handle

    @classmethod
    @func_range("ParquetFooter.readAndFilter")
    def read_and_filter(
        cls,
        buffer: bytes,
        part_offset: int,
        part_length: int,
        names: Sequence[str],
        num_children: Sequence[int],
        parent_num_children: int,
        ignore_case: bool = False,
    ) -> "ParquetFooter":
        """Parse a raw thrift footer (no PAR1 framing), prune to the
        requested depth-first column tree, and filter row groups to the
        partition byte range (negative part_length keeps all groups).
        Names should be pre-lowercased by the caller when ignore_case is
        set, as the reference documents (ParquetFooter.java:78-79)."""
        from spark_rapids_jni_tpu.runtime import integrity

        if len(names) != len(num_children):
            raise ValueError("names and num_children must have equal length")
        if integrity.enabled():
            # untrusted-input preflight, before any native parse
            if len(buffer) == 0:
                raise integrity.reject_malformed(
                    "parquet.footer", "empty thrift footer buffer",
                    exc_type=MalformedFileError)
            if part_offset < 0:
                raise integrity.reject_malformed(
                    "parquet.footer",
                    "negative partition offset",
                    exc_type=MalformedFileError, part_offset=part_offset)
        lib = load_native()
        c_names = (ctypes.c_char_p * len(names))(
            *[n.encode() for n in names]
        )
        c_children = (ctypes.c_int32 * len(num_children))(*num_children)
        handle = lib.tpudf_footer_read_and_filter(
            buffer,
            len(buffer),
            part_offset,
            part_length,
            c_names,
            c_children,
            len(names),
            parent_num_children,
            1 if ignore_case else 0,
        )
        if handle == 0:
            # the native thrift parser rejected the bytes: malformed
            # input, classified for the server, NativeError for legacy
            raise integrity.reject_malformed(
                "parquet.footer", lib.last_error(),
                exc_type=MalformedFileError)
        return cls(handle)

    def _require_open(self) -> int:
        if self._handle == 0:
            raise ValueError("footer is closed")
        return self._handle

    @property
    def num_rows(self) -> int:
        lib = load_native()
        out = lib.tpudf_footer_num_rows(self._require_open())
        if out < 0:
            raise NativeError(lib.last_error())
        return out

    @property
    def num_columns(self) -> int:
        lib = load_native()
        out = lib.tpudf_footer_num_columns(self._require_open())
        if out < 0:
            raise NativeError(lib.last_error())
        return out

    @property
    def file_columns(self) -> int:
        """The leaves the file's schema had before the prune."""
        lib = load_native()
        out = lib.tpudf_footer_file_leaves(self._require_open())
        if out < 0:
            raise NativeError(lib.last_error())
        return out

    def row_groups(self) -> list:
        """``[(index, num_rows)]`` of the row groups the split filter
        kept, ``index`` being the FILE's own numbering: what
        ``read_table(row_groups=...)`` takes."""
        lib = load_native()
        cap = 64
        while True:
            index = (ctypes.c_int32 * cap)()
            rows = (ctypes.c_int64 * cap)()
            n = lib.tpudf_footer_row_groups(self._require_open(), index,
                                            rows, cap)
            if n < 0:
                raise NativeError(lib.last_error())
            if n <= cap:
                return [(index[i], rows[i]) for i in range(n)]
            cap = n

    def leaves(self) -> list:
        """One ``(request position, file leaf index, physical, converted,
        scale, type_length, repetition)`` per leaf the prune kept, in the
        REQUEST's order; a position that is absent is a requested name the
        file lacks. The leaf index is what ``read_table(columns=...)``
        takes."""
        lib = load_native()
        cap = 64
        while True:
            asked = (ctypes.c_int32 * cap)()
            index = (ctypes.c_int32 * cap)()
            meta = (ctypes.c_int32 * (5 * cap))()
            n = lib.tpudf_footer_leaves(self._require_open(), asked, index,
                                        meta, cap)
            if n < 0:
                raise NativeError(lib.last_error())
            if n <= cap:
                return [(asked[i], index[i], *meta[5 * i:5 * i + 5])
                        for i in range(n)]
            cap = n

    @property
    def compressed_bytes(self) -> int:
        """Compressed bytes of the column chunks the prune and the split
        filter kept: what a reader of exactly those will touch."""
        lib = load_native()
        out = lib.tpudf_footer_compressed_bytes(self._require_open())
        if out < 0:
            raise NativeError(lib.last_error())
        return out

    @func_range("ParquetFooter.serializeThriftFile")
    def serialize_thrift_file(self) -> bytes:
        """Emit a legal footer file image: PAR1 + thrift + length + PAR1
        (reference NativeParquetJni.cpp:603-620)."""
        lib = load_native()
        out = ctypes.POINTER(ctypes.c_uint8)()
        out_len = ctypes.c_uint64()
        rc = lib.tpudf_footer_serialize(
            self._require_open(), ctypes.byref(out), ctypes.byref(out_len)
        )
        if rc != 0:
            raise NativeError(lib.last_error())
        try:
            return ctypes.string_at(out, out_len.value)
        finally:
            lib.tpudf_free_buffer(out)

    def close(self) -> None:
        if self._handle != 0:
            load_native().tpudf_footer_close(self._handle)
            self._handle = 0

    def __enter__(self) -> "ParquetFooter":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass
