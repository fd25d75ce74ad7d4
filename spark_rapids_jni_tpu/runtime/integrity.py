"""End-to-end data integrity: checksum trailers on every managed byte
boundary.

The serving stack retries, degrades, checkpoints and replays bytes
through spill entries, DCN wire frames and out-of-core partials — and
until this layer it trusted every byte it read back. A torn spill
write, a flipped bit on the interconnect, or a malformed customer file
produced silently wrong results or an unclassified crash. The
reference's defensive posture is its hardened Thrift footer parsing
(NativeParquetJni.cpp); this module is the TPU runtime's generalization
of that posture to every at-rest and on-wire payload:

- ``seal``/``verify`` wrap a payload in a 16-byte trailer
  (magic + u64 length + masked crc32) so truncation, bit flips and
  length-field lies are all detected before any byte is decoded.
- ``write_payload_file``/``read_payload_file`` are the crash-safe
  binary analogue of utils/atomic_io: tmp file + fsync + ``os.replace``
  + read-back compare, so a crash mid-write can never leave a
  half-written payload a later read trusts.
- ``snaps_checksum``/``verify_snaps`` checksum in-memory host column
  snapshots (SpillStore's packed ``_col_to_host`` tuples) without
  materializing a serialized copy.
- Verification failure raises the classified
  :class:`~spark_rapids_jni_tpu.runtime.resilience.CorruptDataError` —
  refetchable at transport seams (a fresh copy exists on the peer),
  fatal at rest (the bytes are gone; the caller replays or dies with a
  flight record). Malformed *untrusted input* is the separate
  :class:`MalformedInputError` so the server rejects that one query
  cleanly.

The checksum is crc32c-style masking over ``zlib.crc32``: the raw crc
is rotated and offset (the classic LevelDB/crc32c mask) so a payload
that happens to embed its own crc32 — or a trailer fed back through
``checksum`` — never verifies by accident. Zero dependencies beyond
the stdlib; no jax imports (this module runs on the control plane).

Ordering contract with the columnar codec (``runtime/compress.py``):
**compress → seal** on every write, **verify → decompress →
post-decode length/shape check** on every read. The trailer always
covers the stored (compressed) bytes — the seal is the OUTERMOST wrapper
— so verification never spends decode work on bytes that fail the crc,
and a corruption injected after a successful verify (a bad codec frame)
is still a classified ``CorruptDataError`` from the codec's own header
and per-scheme length checks. ARQ refetch at the wire seam re-seals the
pristine compressed blob per resend; nothing is recompressed.

Disabled (``integrity.enabled=false`` or ``SPARK_RAPIDS_TPU_INTEGRITY=0``)
every seam is byte-for-byte today's behavior: no trailer, no
verification, no wire acknowledgements.
"""

from __future__ import annotations

import os
import struct
import tempfile
import zlib
from typing import Any, List, Optional, Sequence

from spark_rapids_jni_tpu import telemetry
from spark_rapids_jni_tpu.runtime.resilience import (
    CorruptDataError,
    MalformedInputError,
)
from spark_rapids_jni_tpu.telemetry import REGISTRY

__all__ = [
    "TRAILER_MAGIC",
    "TRAILER_SIZE",
    "checksum",
    "enabled",
    "read_payload_file",
    "reject_malformed",
    "seal",
    "snaps_checksum",
    "verify",
    "verify_snaps",
    "write_payload_file",
]

# Trailer layout: 4-byte magic + u64 payload length + u32 masked crc.
TRAILER_MAGIC = b"TPIC"
_TRAILER_FMT = "<4sQI"
TRAILER_SIZE = struct.calcsize(_TRAILER_FMT)

# crc32c-style mask constant (LevelDB's): rotate the raw crc and add a
# fixed offset so checksum(x) never equals zlib.crc32(x) and nested
# checksums of checksum-bearing blobs don't collide with the payload's.
_MASK_DELTA = 0xA282EAD8
_ENV = "SPARK_RAPIDS_TPU_INTEGRITY"


def enabled() -> bool:
    """Is integrity verification on? The short env var
    SPARK_RAPIDS_TPU_INTEGRITY is checked first, then the
    ``integrity.enabled`` option."""
    env = os.environ.get(_ENV)
    if env is not None:
        return env.strip().lower() in ("1", "true", "yes", "on")
    from spark_rapids_jni_tpu.utils.config import get_option

    return bool(get_option("integrity.enabled"))


def checksum(data: Any) -> int:
    """Masked crc32 of ``data`` (anything supporting the buffer
    protocol). Always available regardless of :func:`enabled` — callers
    gate, the primitive doesn't."""
    crc = zlib.crc32(data) & 0xFFFFFFFF
    return (((crc >> 15) | (crc << 17)) + _MASK_DELTA) & 0xFFFFFFFF


def seal(payload: bytes) -> bytes:
    """Append the length+checksum trailer to ``payload``."""
    return payload + struct.pack(
        _TRAILER_FMT, TRAILER_MAGIC, len(payload), checksum(payload)
    )


def _mismatch(reason: str, *, seam: str, op: str, **context: Any) -> CorruptDataError:
    REGISTRY.counter("integrity.mismatch").inc()
    REGISTRY.counter(f"integrity.mismatch.{seam}").inc()
    telemetry.record_integrity(op, "mismatch", seam=seam, reason=reason, **context)
    return CorruptDataError(reason, seam=seam, op=op, **context)


def verify(blob: bytes, *, seam: str, op: str = "verify", **context: Any) -> bytes:
    """Strip and check the trailer of a sealed ``blob``; return the
    payload. Raises the classified :class:`CorruptDataError` (with the
    seam and caller context embedded) on truncation, magic clobber,
    length-field lies, or checksum mismatch — before a single payload
    byte reaches a decoder."""
    n = len(blob)
    if n < TRAILER_SIZE:
        raise _mismatch(
            "payload shorter than integrity trailer", seam=seam, op=op, size=n, **context
        )
    magic, length, crc = struct.unpack(_TRAILER_FMT, blob[n - TRAILER_SIZE :])
    if magic != TRAILER_MAGIC:
        raise _mismatch(
            "integrity trailer magic clobbered", seam=seam, op=op, size=n, **context
        )
    if length != n - TRAILER_SIZE:
        raise _mismatch(
            "payload length disagrees with trailer",
            seam=seam,
            op=op,
            declared=length,
            actual=n - TRAILER_SIZE,
            **context,
        )
    payload = blob[: n - TRAILER_SIZE]
    actual = checksum(payload)
    if actual != crc:
        raise _mismatch(
            "payload checksum mismatch",
            seam=seam,
            op=op,
            declared=crc,
            actual=actual,
            **context,
        )
    REGISTRY.counter("integrity.bytes_verified").inc(len(payload))
    REGISTRY.counter(f"integrity.verified.{seam}").inc()
    return payload


def snaps_checksum(snaps: Sequence[Any]) -> int:
    """Checksum a list of packed host column snapshots (SpillStore's
    ``_col_to_host`` tuples: (dtype, data, validity, chars, children),
    where each buffer is a contiguous numpy array, a
    ("zstd", dtype, shape, blob) pack, or None). Folds every buffer into
    one running crc without serializing the snapshot."""
    crc = 0

    def _fold(buf: Any) -> None:
        nonlocal crc
        if buf is None:
            return
        if isinstance(buf, tuple):  # ("zstd", dtype_str, shape, blob)
            crc = zlib.crc32(buf[3], crc)
            return
        crc = zlib.crc32(memoryview(buf).cast("B"), crc)

    def _walk(snap: Any) -> None:
        _dtype, data, validity, chars, children = snap
        _fold(data)
        _fold(validity)
        _fold(chars)
        for child in children or ():
            _walk(child)

    for snap in snaps:
        _walk(snap)
    crc &= 0xFFFFFFFF
    return (((crc >> 15) | (crc << 17)) + _MASK_DELTA) & 0xFFFFFFFF


def verify_snaps(
    snaps: Sequence[Any], expected: int, *, seam: str, op: str = "verify_snaps", **context: Any
) -> None:
    """Check an in-memory snapshot list against the checksum taken when
    it was spilled; raise classified CorruptDataError on drift."""
    nbytes = 0
    for snap in snaps:
        for buf in (snap[1], snap[2], snap[3]):
            if isinstance(buf, tuple):
                nbytes += len(buf[3])
            elif buf is not None:
                nbytes += memoryview(buf).nbytes
    actual = snaps_checksum(snaps)
    if actual != expected:
        raise _mismatch(
            "host snapshot checksum mismatch",
            seam=seam,
            op=op,
            declared=expected,
            actual=actual,
            **context,
        )
    REGISTRY.counter("integrity.bytes_verified").inc(nbytes)
    REGISTRY.counter(f"integrity.verified.{seam}").inc()


def write_payload_file(path: str, blob: bytes) -> int:
    """Crash-safe binary payload write: tmp file in the same directory +
    flush + fsync + atomic ``os.replace`` + directory fsync, then a
    read-back compare of length and checksum against exactly the bytes
    handed in. A crash at any point leaves either the old file or the
    new one — never a torn hybrid — and a write the storage silently
    dropped or mangled is detected *now*, not at unspill time.

    ``blob`` is written verbatim (callers seal before calling when
    integrity is enabled), so the write-verify holds even when a fault
    script injected latent corruption upstream: the check is "did the
    bytes I was given land on disk", not "are the bytes valid"."""
    directory = os.path.dirname(os.path.abspath(path)) or "."
    fd, tmp = tempfile.mkstemp(dir=directory, prefix=".integrity-", suffix=".tmp")
    try:
        with os.fdopen(fd, "wb") as fh:
            fh.write(blob)
            fh.flush()
            os.fsync(fh.fileno())
        os.replace(tmp, path)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise
    try:
        dfd = os.open(directory, os.O_RDONLY)
        try:
            os.fsync(dfd)
        finally:
            os.close(dfd)
    except OSError:
        pass  # platform without directory fsync
    with open(path, "rb") as fh:
        landed = fh.read()
    if len(landed) != len(blob) or zlib.crc32(landed) != zlib.crc32(blob):
        raise _mismatch(
            "write-verify failed: bytes on disk differ from bytes written",
            seam="integrity.spill",
            op="write_payload_file",
            path=path,
            written=len(blob),
            landed=len(landed),
        )
    return len(blob)


def read_payload_file(
    path: str, *, seam: str, sealed: bool, op: str = "read_payload_file", **context: Any
) -> bytes:
    """Read a managed payload file back; when it was written sealed,
    verify the trailer before returning a single payload byte."""
    with open(path, "rb") as fh:
        blob = fh.read()
    if not sealed:
        return blob
    return verify(blob, seam=seam, op=op, path=path, **context)


def reject_malformed(
    op: str,
    message: str,
    *,
    exc_type: Optional[type] = None,
    **context: Any,
) -> MalformedInputError:
    """Count + record one malformed-input rejection and return the
    classified exception for the caller to raise
    (``raise integrity.reject_malformed(...)``). ``exc_type`` lets file
    readers substitute their NativeError-compatible subclass."""
    REGISTRY.counter("integrity.malformed").inc()
    REGISTRY.counter(f"integrity.malformed.{op}").inc()
    telemetry.record_integrity(op, "malformed", seam="integrity.ingest", reason=message, **context)
    cls = exc_type or MalformedInputError
    return cls(message, op=op, **context)
