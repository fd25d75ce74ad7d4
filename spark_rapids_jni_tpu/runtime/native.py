"""Native library loader — the NativeDepsLoader equivalent.

The reference extracts per-platform .so resources from the jar and
System.load()s them on first API touch (reference RowConversion.java:23-25,
packaging scheme pom.xml:385-421). Here the equivalent search order is:

  1. ``SPARK_RAPIDS_TPU_NATIVE_LIB`` env var (explicit path);
  2. a packaged ``_lib/libtpudf.so`` next to this module;
  3. in a checkout (``src/native`` present): configure and build it with
     cmake/ninja into ``build/native`` and load that. The build runs on
     every first touch — a no-op when current — so a library left over from
     other sources is never loaded; a failed build raises with the
     compiler's output (the reference drives the same step from Maven at
     the validate phase, pom.xml:306-333).

Loading is lazy and memoized; errors carry the full search trail.
"""

from __future__ import annotations

import ctypes
import fcntl
import os
import pathlib
import shutil
import subprocess
import threading
from typing import Optional

_REPO_ROOT = pathlib.Path(__file__).resolve().parents[2]
_LIB_NAME = "libtpudf.so"

_lock = threading.Lock()
_loaded: Optional["NativeLib"] = None


class NativeLib:
    """ctypes surface of libtpudf with argtypes pinned."""

    def __init__(self, cdll: ctypes.CDLL, path: pathlib.Path):
        self.path = path
        self._c = cdll
        c = cdll
        c.tpudf_last_error.restype = ctypes.c_char_p
        c.tpudf_footer_read_and_filter.restype = ctypes.c_int64
        c.tpudf_footer_read_and_filter.argtypes = [
            ctypes.c_char_p,
            ctypes.c_uint64,
            ctypes.c_int64,
            ctypes.c_int64,
            ctypes.POINTER(ctypes.c_char_p),
            ctypes.POINTER(ctypes.c_int32),
            ctypes.c_int32,
            ctypes.c_int32,
            ctypes.c_int32,
        ]
        c.tpudf_footer_num_rows.restype = ctypes.c_int64
        c.tpudf_footer_num_rows.argtypes = [ctypes.c_int64]
        c.tpudf_footer_num_columns.restype = ctypes.c_int32
        c.tpudf_footer_num_columns.argtypes = [ctypes.c_int64]
        c.tpudf_footer_serialize.restype = ctypes.c_int32
        c.tpudf_footer_serialize.argtypes = [
            ctypes.c_int64,
            ctypes.POINTER(ctypes.POINTER(ctypes.c_uint8)),
            ctypes.POINTER(ctypes.c_uint64),
        ]
        c.tpudf_free_buffer.argtypes = [ctypes.POINTER(ctypes.c_uint8)]
        c.tpudf_footer_row_groups.restype = ctypes.c_int32
        c.tpudf_footer_row_groups.argtypes = [
            ctypes.c_int64,
            ctypes.POINTER(ctypes.c_int32),
            ctypes.POINTER(ctypes.c_int64),
            ctypes.c_int32,
        ]
        c.tpudf_footer_leaves.restype = ctypes.c_int32
        c.tpudf_footer_leaves.argtypes = [
            ctypes.c_int64,
            ctypes.POINTER(ctypes.c_int32),
            ctypes.POINTER(ctypes.c_int32),
            ctypes.POINTER(ctypes.c_int32),
            ctypes.c_int32,
        ]
        c.tpudf_footer_file_leaves.restype = ctypes.c_int32
        c.tpudf_footer_file_leaves.argtypes = [ctypes.c_int64]
        c.tpudf_footer_compressed_bytes.restype = ctypes.c_int64
        c.tpudf_footer_compressed_bytes.argtypes = [ctypes.c_int64]
        c.tpudf_footer_close.restype = ctypes.c_int32
        c.tpudf_footer_close.argtypes = [ctypes.c_int64]
        c.tpudf_open_handles.restype = ctypes.c_int64
        # Parquet data reader
        c.tpudf_parquet_read.restype = ctypes.c_int64
        c.tpudf_parquet_read.argtypes = [
            ctypes.c_char_p,
            ctypes.c_uint64,
            ctypes.POINTER(ctypes.c_int32),
            ctypes.c_int32,
            ctypes.POINTER(ctypes.c_int32),
            ctypes.c_int32,
        ]
        c.tpudf_parquet_row_groups.restype = ctypes.c_int32
        c.tpudf_parquet_row_groups.argtypes = [
            ctypes.c_char_p,
            ctypes.c_uint64,
            ctypes.POINTER(ctypes.c_int64),
            ctypes.POINTER(ctypes.c_int64),
            ctypes.c_int32,
        ]
        c.tpudf_read_num_rows.restype = ctypes.c_int64
        c.tpudf_read_num_rows.argtypes = [ctypes.c_int64]
        c.tpudf_read_num_columns.restype = ctypes.c_int32
        c.tpudf_read_num_columns.argtypes = [ctypes.c_int64]
        c.tpudf_read_col_meta.restype = ctypes.c_int32
        c.tpudf_read_col_meta.argtypes = [
            ctypes.c_int64,
            ctypes.c_int32,
            ctypes.POINTER(ctypes.c_int32),
            ctypes.POINTER(ctypes.c_int64),
        ]
        c.tpudf_parquet_read_path.restype = ctypes.c_int64
        c.tpudf_parquet_read_path.argtypes = [
            ctypes.c_char_p,
            ctypes.POINTER(ctypes.c_int32),
            ctypes.c_int32,
            ctypes.POINTER(ctypes.c_int32),
            ctypes.c_int32,
        ]
        c.tpudf_parquet_row_groups_path.restype = ctypes.c_int32
        c.tpudf_parquet_row_groups_path.argtypes = [
            ctypes.c_char_p,
            ctypes.POINTER(ctypes.c_int64),
            ctypes.POINTER(ctypes.c_int64),
            ctypes.c_int32,
        ]
        c.tpudf_read_col_meta2.restype = ctypes.c_int32
        c.tpudf_read_col_meta2.argtypes = [
            ctypes.c_int64,
            ctypes.c_int32,
            ctypes.POINTER(ctypes.c_int32),
            ctypes.POINTER(ctypes.c_int64),
        ]
        c.tpudf_read_col_levels.restype = ctypes.c_int32
        c.tpudf_read_col_levels.argtypes = [
            ctypes.c_int64,
            ctypes.c_int32,
            ctypes.c_void_p,
            ctypes.c_void_p,
        ]
        c.tpudf_read_schema_desc.restype = ctypes.c_char_p
        c.tpudf_read_schema_desc.argtypes = [ctypes.c_int64]
        c.tpudf_read_col_name.restype = ctypes.c_char_p
        c.tpudf_read_col_name.argtypes = [ctypes.c_int64, ctypes.c_int32]
        c.tpudf_read_col_copy.restype = ctypes.c_int32
        c.tpudf_read_col_copy.argtypes = [
            ctypes.c_int64,
            ctypes.c_int32,
            ctypes.c_void_p,
            ctypes.c_void_p,
            ctypes.c_void_p,
            ctypes.c_void_p,
        ]
        c.tpudf_read_close.restype = ctypes.c_int32
        c.tpudf_read_close.argtypes = [ctypes.c_int64]
        # ORC reader
        c.tpudf_orc_read.restype = ctypes.c_int64
        c.tpudf_orc_read.argtypes = [
            ctypes.c_char_p,
            ctypes.c_uint64,
            ctypes.POINTER(ctypes.c_int32),
            ctypes.c_int32,
            ctypes.POINTER(ctypes.c_int32),
            ctypes.c_int32,
        ]
        c.tpudf_orc_stripes.restype = ctypes.c_int32
        c.tpudf_orc_stripes.argtypes = [
            ctypes.c_char_p,
            ctypes.c_uint64,
            ctypes.POINTER(ctypes.c_int64),
            ctypes.POINTER(ctypes.c_int64),
            ctypes.c_int32,
        ]
        c.tpudf_orc_num_columns.restype = ctypes.c_int32
        c.tpudf_orc_num_columns.argtypes = [ctypes.c_int64]
        c.tpudf_orc_num_rows.restype = ctypes.c_int64
        c.tpudf_orc_num_rows.argtypes = [ctypes.c_int64]
        c.tpudf_orc_col_meta.restype = ctypes.c_int32
        c.tpudf_orc_col_meta.argtypes = [
            ctypes.c_int64,
            ctypes.c_int32,
            ctypes.POINTER(ctypes.c_int32),
            ctypes.POINTER(ctypes.c_int64),
        ]
        c.tpudf_orc_col_name.restype = ctypes.c_char_p
        c.tpudf_orc_col_name.argtypes = [ctypes.c_int64, ctypes.c_int32]
        c.tpudf_orc_writer_timezone.restype = ctypes.c_char_p
        c.tpudf_orc_writer_timezone.argtypes = [ctypes.c_int64]
        c.tpudf_orc_read_path.restype = ctypes.c_int64
        c.tpudf_orc_read_path.argtypes = [
            ctypes.c_char_p,
            ctypes.POINTER(ctypes.c_int32), ctypes.c_int32,
            ctypes.POINTER(ctypes.c_int32), ctypes.c_int32,
        ]
        c.tpudf_orc_stripes_path.restype = ctypes.c_int32
        c.tpudf_orc_stripes_path.argtypes = [
            ctypes.c_char_p,
            ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int64),
            ctypes.c_int32,
        ]
        c.tpudf_orc_col_copy.restype = ctypes.c_int32
        c.tpudf_orc_col_copy.argtypes = [
            ctypes.c_int64,
            ctypes.c_int32,
            ctypes.c_void_p,
            ctypes.c_void_p,
            ctypes.c_void_p,
            ctypes.c_void_p,
        ]
        c.tpudf_orc_close.restype = ctypes.c_int32
        c.tpudf_orc_close.argtypes = [ctypes.c_int64]
        c.tpudf_orc_decode_rle2.restype = ctypes.c_int32
        c.tpudf_orc_decode_rle2.argtypes = [
            ctypes.c_char_p,
            ctypes.c_uint64,
            ctypes.c_int64,
            ctypes.c_int32,
            ctypes.c_void_p,
        ]
        # host packed-row codec
        c.tpudf_rows_layout.restype = ctypes.c_int32
        c.tpudf_rows_layout.argtypes = [
            ctypes.POINTER(ctypes.c_int32),
            ctypes.c_int32,
            ctypes.POINTER(ctypes.c_int32),
        ]
        c.tpudf_to_rows.restype = ctypes.c_int32
        c.tpudf_to_rows.argtypes = [
            ctypes.POINTER(ctypes.c_void_p),
            ctypes.POINTER(ctypes.c_void_p),
            ctypes.POINTER(ctypes.c_int32),
            ctypes.c_int32,
            ctypes.c_int64,
            ctypes.c_void_p,
        ]
        c.tpudf_from_rows.restype = ctypes.c_int32
        c.tpudf_from_rows.argtypes = [
            ctypes.c_void_p,
            ctypes.c_int64,
            ctypes.POINTER(ctypes.c_int32),
            ctypes.c_int32,
            ctypes.POINTER(ctypes.c_void_p),
            ctypes.POINTER(ctypes.c_void_p),
        ]
        # get_json_object
        c.tpudf_get_json_object.restype = ctypes.c_int32
        c.tpudf_get_json_object.argtypes = [
            ctypes.c_void_p,                          # chars
            ctypes.c_void_p,                          # offsets
            ctypes.c_void_p,                          # valid (nullable)
            ctypes.c_int64,                           # n_rows
            ctypes.c_char_p,                          # path
            ctypes.POINTER(ctypes.POINTER(ctypes.c_uint8)),
            ctypes.POINTER(ctypes.c_int64),
            ctypes.c_void_p,                          # out offsets
            ctypes.c_void_p,                          # out valid
        ]

    def __getattr__(self, name):
        return getattr(self._c, name)

    def last_error(self) -> str:
        return self._c.tpudf_last_error().decode(errors="replace")


def _candidate_paths() -> list[pathlib.Path]:
    out = []
    env = os.environ.get("SPARK_RAPIDS_TPU_NATIVE_LIB")
    if env:
        out.append(pathlib.Path(env))
    out.append(pathlib.Path(__file__).parent / "_lib" / _LIB_NAME)
    return out


def _cache_matches(build: pathlib.Path, src: pathlib.Path) -> bool:
    """Was ``build/CMakeCache.txt`` configured for THIS source and build
    directory? A cache copied in from another path (a relocated checkout)
    makes cmake refuse to run; it is discarded instead."""
    want = {"CMAKE_HOME_DIRECTORY": str(src), "CMAKE_CACHEFILE_DIR": str(build)}
    try:
        text = (build / "CMakeCache.txt").read_text(errors="replace")
    except OSError:
        return False
    for line in text.splitlines():
        key, _, rest = line.partition(":")
        if key in want and rest.partition("=")[2] != want.pop(key):
            return False
    return not want


def _run_build_step(cmd: list[str]) -> None:
    try:
        proc = subprocess.run(cmd, capture_output=True, text=True)
    except OSError as exc:
        raise OSError(f"native build: cannot run {cmd[0]}: {exc}") from exc
    if proc.returncode != 0:
        raise OSError(
            f"native build failed (rc={proc.returncode}): {' '.join(cmd)}\n"
            f"{proc.stdout}{proc.stderr}")


def _build_native() -> Optional[pathlib.Path]:
    """Configure (when needed) and build ``src/native`` into
    ``build/native``; None outside a checkout. Serialized across processes
    (fleet workers and test processes all land here on first touch)."""
    src = (_REPO_ROOT / "src" / "native").resolve()
    build = (_REPO_ROOT / "build" / "native").resolve()
    if not src.exists():
        return None
    build.parent.mkdir(parents=True, exist_ok=True)
    with open(build.parent / ".native.lock", "a") as lock:
        fcntl.flock(lock.fileno(), fcntl.LOCK_EX)
        try:
            if build.exists() and not _cache_matches(build, src):
                shutil.rmtree(build)
            if not (build / "build.ninja").exists():
                _run_build_step(
                    ["cmake", "-S", str(src), "-B", str(build), "-G", "Ninja"])
            _run_build_step(["ninja", "-C", str(build)])
        finally:
            fcntl.flock(lock.fileno(), fcntl.LOCK_UN)
    return build / _LIB_NAME


def load_native() -> NativeLib:
    global _loaded
    with _lock:
        if _loaded is not None:
            return _loaded
    # find or build OUTSIDE _lock: the build can take seconds and waits on
    # a cross-process file lock; racing threads serialize on that instead
    tried = []
    found = None
    for path in _candidate_paths():
        if path.exists():
            found = path
            break
        tried.append(str(path))
    if found is None:
        found = _build_native()
    if found is None:
        raise OSError(
            f"could not locate {_LIB_NAME}; searched: {tried}, and there "
            f"is no src/native under {_REPO_ROOT} to build it from")
    with _lock:
        if _loaded is None:
            _loaded = NativeLib(ctypes.CDLL(str(found)), found)
            _keep_freed_memory()
        return _loaded


def _keep_freed_memory() -> None:
    """Have glibc's malloc keep what the decoders free instead of handing
    it back to the kernel. A decode allocates and frees megabyte-sized
    vectors page after page (and numpy the buffers they are copied out
    to): by default glibc maps each anew or trims the heap after it, so
    every request pays the page faults again: 28,000-48,000 a scan of SF1
    lineitem's seven q1 columns, a number that differs from process to
    process with the allocator's self-adjusting thresholds, against
    16,200 (the mapped file's own pages) once freed memory is kept (the
    sandbox, PR 34). Fixed thresholds switch that adjusting off. A libc
    without ``mallopt`` is left as it is."""
    mallopt = getattr(ctypes.CDLL(None), "mallopt", None)
    if mallopt is not None:
        mallopt(-3, 1 << 30)          # M_MMAP_THRESHOLD: serve from the heap
        mallopt(-1, (1 << 31) - 1)    # M_TRIM_THRESHOLD: never trim it
