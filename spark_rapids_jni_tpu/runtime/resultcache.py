"""Plan-signature result & subplan cache.

Production query traffic is wildly repetitive — dashboards re-issue the
same plans against slowly-changing data — yet every admitted query pays
admission, compile, and full execution even when an identical plan ran
seconds ago. Sparkle (PAPERS.md) makes the case that sharing materialized
intermediates across queries dominates once kernels are fast; Flare shows
plan-level specialization only pays when repeated plans amortize it. This
module cashes both in for the serving runtime (``runtime/server.py``):

* **Final results** — a :class:`ResultCache` memoizes whole-query
  ``FusedResult``s keyed by :class:`CacheKey` ``(plan signature, input
  fingerprint)``. A hit in ``QueryServer.submit`` short-circuits
  admission, compile, and execution, returning the cached table
  bit-identically under a ``cache.hit`` span.
* **Subplan intermediates** — :func:`apply_subplans` hashes canonicalized
  scan+filter+project prefixes (``fusion.scan_prefix_chains``), so two
  distinct plans sharing a prefix execute the shared region exactly once
  and the second reuses the materialized intermediate.

Keying. The signature half is a sha256 over the fusion IR's structural
fingerprint (node kinds, qualified callable names, static params, resolved
row specs — ``fusion.plan_fingerprint``); the fingerprint half digests the
bound input CONTENT (every column buffer, dtype and shape, memoized per
Table object), so slowly-changing data invalidates exactly when it
changes. ``source_fingerprint`` offers the cheap path+size+mtime digest
for file-backed scans. Both halves are mandatory: a ``get``/``put`` whose
key lacks the input fingerprint raises (tpulint rule 16
``cache-key-must-fingerprint`` enforces the static half at call sites).

The content fingerprint. A table's fingerprint is a sha256 over its
buffers in column order. What a buffer gives the sha256 is decided by its
dtype and size alone, so the fingerprint is a function of content and
never of where the bytes live (a CPU-pinned fleet supervisor compares its
own with the one a replica took on its chip):

* a buffer of ``_DIGEST_MIN_BYTES`` (1 MiB) or more of an integer, bool,
  float32 or narrower dtype gives dtype, shape, byte length and a
  ``DIGEST_BITS`` = 128 bit digest, computed where the buffer lives.
  The digest (``_lane_sums``) is 32-bit integer arithmetic only: each
  of four lanes sums, mod 2**32, a bijective multiply/xorshift mix of
  ``word + index * A + S`` over every 32-bit word of the buffer, with
  the lane's own constants. A sum is order-free, so XLA on the TPU, XLA
  on the CPU and numpy give the same bits; every word's term depends on
  its index, so a rolled or permuted buffer (the same multiset of words)
  is a different buffer. A single-device ``jax.Array`` is digested by the
  jit ``cache_digest`` on its device and 16 bytes come back; one whose
  rows are sharded over a mesh axis is digested shard by shard, each on
  the chip that holds it with its words indexed from the shard's place in
  the buffer, and the lanes of the shards summed mod 2**32 are the lanes
  of the whole (16 bytes a chip come back); a host array, a
  ``HostTableChunk`` snapshot or an array placed any other way (brought
  to the host) by the same function in numpy. All of a table's device
  digests are enqueued before the first is waited for. One executable a
  dtype, shape and placement (``dispatch.compiled``: the dispatch layer's
  cache, counted as a ``dispatch.compile``).
* a smaller buffer gives dtype, shape and its bytes, as it always has (a
  copy and a sha256 of under 1 MiB cost less than a dispatch, let alone
  a compile for a shape seen once); so does float64, which the chip holds
  as a float32 pair and cannot bitcast.

The digest is NOT a cryptographic hash: an accidental collision is out of
reach (2**-128 a pair), a constructed one is not. The cache is shared by
the sessions of one executor; it is not a boundary between tenants.

Storage. Entries live in the server's shared :class:`SpillStore` under
the ``integrity.cache`` seam: a fresh entry shares the just-computed
result's device buffers (zero copy) and rides the store's integrity-sealed
host/disk tiers under pressure, verifying at read — a corrupt cached
payload is a classified discard-and-recompute, never wrong bytes served.

Accounting. Resident entries are charged against the shared
``MemoryLimiter`` so cached results can never starve live queries, and
they are the FIRST thing pressure evicts: the limiter's high-watermark
reaction sheds cache entries (demote to host tier + release charge)
before any live query's working set spills, and a parked query's drain
threshold discounts evictable cache bytes (``memory.py``). Capacity is an
LRU in RESIDENT (stored) bytes (``cache.max_bytes``; by default an eighth
of the limiter's budget, at least 256 MiB): entries demoted to
the host/disk tier count at their codec-compressed footprint
(``compress.py``), so the same budget holds more results; ``stats()``
reports both ``bytes`` (logical) and ``stored_bytes`` (resident).

Config: ``cache.enabled`` / ``cache.max_bytes`` / ``cache.subplan_enabled``
(env ``SPARK_RAPIDS_TPU_CACHE_*``). Off restores today's serving path
byte-for-byte.
"""

from __future__ import annotations

import collections
import hashlib
import os
import threading
from typing import NamedTuple, Optional

import numpy as np

from spark_rapids_jni_tpu.runtime import dispatch, fusion, resilience
from spark_rapids_jni_tpu.runtime.memory import (
    HostTableChunk,
    MemoryLimitExceeded,
    MemoryLimiter,
    SpillStore,
    _table_nbytes,
)
from spark_rapids_jni_tpu.telemetry.events import (
    record_cache,
    record_integrity,
)
from spark_rapids_jni_tpu.telemetry import spans
from spark_rapids_jni_tpu.telemetry.registry import REGISTRY
from spark_rapids_jni_tpu.utils.config import get_option
from spark_rapids_jni_tpu.utils.log import get_logger

__all__ = [
    "CacheKey",
    "ResultCache",
    "enabled",
    "subplan_enabled",
    "cache_key",
    "plan_signature",
    "input_fingerprint",
    "table_fingerprint",
    "source_fingerprint",
    "apply_subplans",
]

_log = get_logger("spark_rapids_jni_tpu.resultcache")


def enabled() -> bool:
    """True when the ``cache.enabled`` option is on."""
    return bool(get_option("cache.enabled"))


def subplan_enabled() -> bool:
    return enabled() and bool(get_option("cache.subplan_enabled"))


class CacheKey(NamedTuple):
    """The two-part cache key. BOTH halves are mandatory: ``signature``
    identifies the computation (structural plan digest), ``fingerprint``
    identifies the input content — a key missing either would serve a
    stale result the moment the data (or the plan) changed."""

    signature: str
    fingerprint: str

    @property
    def short(self) -> str:
        return f"{self.signature[:12]}@{self.fingerprint[:12]}"


# ---------------------------------------------------------------------------
# key derivation
# ---------------------------------------------------------------------------


def plan_signature(plan: fusion.Plan, bindings: dict) -> str:
    """sha256 over the fusion IR's canonical structural fingerprint
    (``fusion.plan_fingerprint``): node kinds, qualified callable names,
    static params, resolved row-count statics. Excludes the plan's
    display name — identically-traced plans share results. Raises
    ``ValueError`` for plans whose callables are not module-level (they
    cannot be canonically named) and ``KeyError`` for unbound scans."""
    fp = fusion.plan_fingerprint(plan, bindings)
    return hashlib.sha256(repr(fp).encode()).hexdigest()


# -- the buffer digest ------------------------------------------------------
# One definition, two evaluations (``xp`` is numpy or jax.numpy): every
# 32-bit word of the buffer, with its index, goes through a per-lane
# bijective mixer and the lane sums the results mod 2**32. A sum of uint32
# is the same in any order, so the chip, XLA's CPU backend and numpy agree
# to the bit. Lane l mixes ``word + index * A_l + S_l``: A_0 - A_3 is odd,
# so two lanes' inputs already determine (word, index), and a word moved to
# another index changes every lane (a rolled or permuted buffer is a new
# buffer). Rows are (A, S, M1, M2); the mixer is the xorshift-multiply
# finalizer of murmur3 / lowbias32 with each lane's own multipliers.
_LANES = (
    (0x9E3779B1, 0x7F4A7C15, 0x7FEB352D, 0x846CA68B),
    (0x85EBCA77, 0x165667B1, 0x21F0AAAD, 0x735A2D97),
    (0xC2B2AE3D, 0x27D4EB2F, 0x85EBCA6B, 0xC2B2AE35),
    (0x27D4EB2E, 0x9E3779B9, 0x2C1B3C6D, 0x297A2D39),
)
DIGEST_BITS = 32 * len(_LANES)
# below this a copy to the host and a sha256 cost less than a dispatch (or,
# for a shape not seen before, a compile): small buffers keep the host path
_DIGEST_MIN_BYTES = 1 << 20
# the word index is 32 bits: beyond 2**32 words positions would alias
_DIGEST_MAX_BYTES = 1 << 34
# the numpy evaluation's block: its temporaries stay in the host's cache
_HOST_BLOCK_WORDS = 1 << 15


def _lane_sums(xp, words, index) -> list:
    """The lanes' sums over ``words`` (uint32) at ``index`` (uint32)."""
    sums = []
    for a, s, m1, m2 in _LANES:
        h = words + index * xp.uint32(a) + xp.uint32(s)
        h = (h ^ (h >> 16)) * xp.uint32(m1)
        h = (h ^ (h >> 15)) * xp.uint32(m2)
        sums.append(xp.sum(h ^ (h >> 16), dtype=xp.uint32))
    return sums


def cache_digest(x, first=0):
    """The digest of a device buffer, as uint32[lanes], computed where the
    buffer lives (traced: this is the jit's body, and its name the
    module's, ``jit_cache_digest``). Words are the buffer's bytes in
    memory order: an 8-byte element is its low then its high half (the
    chip keeps int64 as such a pair, so neither is a copy), a 4-byte
    element is itself, a 1- or 2-byte element is widened to one word.
    ``first`` is the index of the buffer's first word where ``x`` is a
    part of it: the lanes of the parts, summed mod 2**32, are the lanes of
    the whole."""
    import jax.numpy as jnp
    from jax import lax

    x = x.reshape(-1)
    size = x.dtype.itemsize
    if x.dtype == jnp.bool_:
        parts = (x.astype(jnp.uint32),)
    elif size == 8:
        parts = (x.astype(jnp.uint32), (x >> 32).astype(jnp.uint32))
    elif size == 4:
        parts = (lax.bitcast_convert_type(x, jnp.uint32),)
    else:
        parts = (lax.bitcast_convert_type(
            x, jnp.uint8 if size == 1 else jnp.uint16).astype(jnp.uint32),)
    index = (lax.iota(jnp.uint32, x.shape[0]) * jnp.uint32(len(parts))
             + jnp.asarray(first, jnp.uint32))
    lanes = [_lane_sums(jnp, words, index + jnp.uint32(k))
             for k, words in enumerate(parts)]
    return jnp.stack([sum(lane[1:], lane[0]) for lane in zip(*lanes)])


def _sharded_digest(mesh, axis: str):
    """``cache_digest`` of a buffer whose rows are sharded over ``axis`` of
    ``mesh``: every chip digests the shard it holds, its words indexed
    from where the shard starts in the buffer, and uint32[chips, lanes]
    comes back, 16 bytes a chip; no element leaves its chip."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    def cache_digest_sharded(x):
        def shard(local):
            words = local.size * max(1, local.dtype.itemsize // 4)
            first = jax.lax.axis_index(axis).astype(jnp.uint32) * jnp.uint32(
                words)
            return cache_digest(local, first)[None, :]

        return jax.shard_map(shard, mesh=mesh, in_specs=P(axis),
                             out_specs=P(axis))(x)

    cache_digest_sharded.__name__ = cache_digest_sharded.__qualname__ = \
        "cache_digest"
    return cache_digest_sharded


def _digest_numpy(arr: np.ndarray) -> np.ndarray:
    """The same digest of a host buffer, in blocks of plain numpy."""
    flat = np.ascontiguousarray(arr).reshape(-1)
    words = flat.view(f"<u{min(flat.dtype.itemsize, 4)}")
    total = np.zeros(len(_LANES), dtype=np.uint32)
    for lo in range(0, words.size, _HOST_BLOCK_WORDS):
        block = words[lo:lo + _HOST_BLOCK_WORDS].astype(
            np.uint32, copy=False)
        index = np.arange(lo, lo + block.size, dtype=np.uint32)
        total += np.array(_lane_sums(np, block, index), dtype=np.uint32)
    return total


def _takes_digest(dtype, nbytes: int) -> bool:
    """Whether a buffer is digested or hashed byte for byte: decided by
    dtype and size alone, never by where the bytes live. float64 stays out
    (the chip holds it as a float32 pair and cannot bitcast it)."""
    dtype = np.dtype(dtype)
    return (_DIGEST_MIN_BYTES <= nbytes < _DIGEST_MAX_BYTES
            and (dtype.kind in "iub"
                 or (dtype.kind == "f" and dtype.itemsize <= 4)))


def _device_digest(buf):
    """How to enqueue the digest of ``buf`` where it lives, as a function
    of no arguments: uint32[lanes] of a ``jax.Array`` on a single device,
    uint32[chips, lanes] of one whose rows are sharded over a mesh axis
    (``parallel/mesh.py`` ``row_mesh``). None for a host array and for
    any other placement: those are digested over a host copy."""
    import jax

    from spark_rapids_jni_tpu.parallel.mesh import row_mesh

    if not isinstance(buf, jax.Array):
        return None
    if len(buf.sharding.device_set) == 1:
        return lambda: dispatch.compiled(
            "cache_digest", cache_digest, buf)(buf)
    over = row_mesh(buf)
    if over is None:
        return None
    return lambda: dispatch.compiled(
        "cache_digest", _sharded_digest(*over), buf,
        statics=(dispatch.mesh_fingerprint(over[0]),))(buf)


def _stage_buffer(buf):
    """First half of a buffer's fingerprint; returns the second half,
    ``finish(h)``, which feeds the table's sha256. Staging a large
    ``jax.Array`` on one device, or row-sharded over a mesh axis, enqueues
    its digest where it lives and starts the digest's copy to the host,
    so a table's columns are all in flight before ``finish`` waits for
    the first. Everything else is done in ``finish``: a host array, or a
    device array placed any other way (brought to the host first), is
    digested by numpy to the same value, and a small or float64 buffer
    feeds its bytes to the sha256 as it always has.

    Spans, one pair a buffer (a sharded buffer's shards are one enqueue
    and one copy): ``cache.fingerprint.hash`` is the enqueue of
    the digest, or the hashing on the host (``nbytes``: bytes
    fingerprinted); ``cache.fingerprint.copy`` is what crosses to the
    host, the digest or the whole buffer, and the wait for it
    (``nbytes``: bytes that crossed)."""
    if buf is None:
        return lambda h: h.update(b"\xff")
    if isinstance(buf, tuple):  # packed ("zstd", dtype_str, shape, blob)
        def finish_packed(h):
            h.update(buf[1].encode())
            h.update(repr(buf[2]).encode())
            h.update(buf[3])
        return finish_packed
    nbytes = int(buf.nbytes)
    digested = _takes_digest(buf.dtype, nbytes)
    pending = None
    enqueue = _device_digest(buf) if digested else None
    if enqueue is not None:
        with spans.child("cache.fingerprint.hash", nbytes=nbytes):
            try:
                pending = enqueue()
                pending.copy_to_host_async()
            except Exception as exc:
                # no room for the fusion's temporaries, a compile that
                # fails: numpy gives the same digest over a host copy, and
                # the counter says the work left the device
                REGISTRY.counter("dispatch.exec_error").inc()
                _log.warning("cache_digest of %s%s failed on the device, "
                             "digesting a host copy: %s",
                             buf.dtype, tuple(buf.shape), exc)
                pending = None
    REGISTRY.counter("cache.fingerprint_bytes").inc(nbytes)
    REGISTRY.counter("cache.fingerprint_device_bytes").inc(
        nbytes if pending is not None else 0)

    def finish(h):
        crossed = pending if pending is not None else buf
        with spans.child("cache.fingerprint.copy") as sp:
            arr = np.ascontiguousarray(np.asarray(crossed))
            sp.annotate(nbytes=0 if isinstance(buf, np.ndarray)
                        else int(arr.nbytes))
        h.update(str(np.dtype(buf.dtype)).encode())
        h.update(repr(tuple(buf.shape)).encode())
        if pending is not None:
            # a sharded buffer's lanes: the sum of its shards', mod 2**32
            h.update(_digest_tag(nbytes, arr.reshape(-1, len(_LANES)).sum(
                axis=0, dtype=np.uint32)))
            return
        with spans.child("cache.fingerprint.hash", nbytes=nbytes):
            h.update(_digest_tag(nbytes, _digest_numpy(arr)) if digested
                     else arr.tobytes())
    return finish


def _digest_tag(nbytes: int, lanes: np.ndarray) -> bytes:
    """What the sha256 takes for a digested buffer: byte length, lanes."""
    return (b"digest" + nbytes.to_bytes(8, "little")
            + lanes.astype("<u4").tobytes())


def _stage_column(col) -> list:
    """The staged parts of a column, or of a host snapshot of one (the
    same five fields as a tuple), children included, in hashing order."""
    dtype, data, validity, chars, children = (
        col if isinstance(col, tuple)
        else (col.dtype, col.data, col.validity, col.chars, col.children))
    out = [repr(dtype).encode()]
    out += [_stage_buffer(b) for b in (data, validity, chars)]
    for child in (children or ()):
        out += _stage_column(child)
    return out


def _finish(staged: list) -> str:
    """The sha256 over a table's staged parts, in order: header bytes as
    they are, buffers through their ``finish``."""
    h = hashlib.sha256()
    for part in staged:
        if isinstance(part, bytes):
            h.update(part)
        else:
            part(h)
    return h.hexdigest()


def table_fingerprint(table) -> str:
    """Content digest of a device Table: every column's data/validity/
    chars buffers plus dtype and shape, recursively. Every buffer is
    staged before any is finished, so the device digests column after
    column while the host dispatches the next, and a few hundred bytes
    come back. Memoized on the Table object (JAX arrays are immutable, so
    a table's content never drifts under its fingerprint) — repeat
    submissions of the same bound table hash once."""
    cached = getattr(table, "_resultcache_fp", None)
    if cached is not None:
        return cached
    fp = _finish([p for col in table.columns for p in _stage_column(col)])
    try:
        table._resultcache_fp = fp
    except (AttributeError, TypeError):
        pass  # slotted/frozen table: recompute next time
    return fp


def _chunk_fingerprint(chunk: HostTableChunk) -> str:
    return _finish([p for snap in chunk.cols for p in _stage_column(snap)])


def source_fingerprint(path: str) -> str:
    """Cheap file-backed-scan fingerprint: path + size + mtime digest —
    the invalidation handle for bindings too large to content-hash on
    every submit (pass it as ``submit(..., cache_fingerprint=...)``).
    Any rewrite of the source file changes it."""
    st = os.stat(path)
    token = f"{os.path.abspath(path)}\0{st.st_size}\0{st.st_mtime_ns}"
    return hashlib.sha256(token.encode()).hexdigest()


def split_fingerprint(split) -> str:
    """The key of a file-backed binding (``parquet.split.ParquetSplit``):
    :func:`source_fingerprint` of its path with the projection, the types
    it is read as and the split's byte range mixed in. No byte of the file
    is digested: a rewrite of the file (size, mtime), another path, another
    projection or another range is another key; the same source again is
    the same key."""
    token = repr((source_fingerprint(split.path), split.columns,
                  split.dtypes, int(split.part_offset),
                  int(split.part_length)))
    return hashlib.sha256(token.encode()).hexdigest()


def input_fingerprint(bindings: dict) -> str:
    """Content digest over every bound input, name-keyed and
    order-independent. Device tables hash their buffers (memoized);
    host-decoded chunks hash their snapshots; a Parquet split (resolved
    or not) is keyed by its source (:func:`split_fingerprint`). Raises
    ``TypeError`` for bindings that are none of these."""
    # imported here, not at the top: a line added above ``cache_digest``
    # would move its source lines, which the persistent compile cache keys on
    from spark_rapids_jni_tpu.parquet.split import ParquetScan, ParquetSplit

    h = hashlib.sha256()
    for name in sorted(bindings):
        value = bindings[name]
        h.update(str(name).encode())
        h.update(b"\0")
        if isinstance(value, HostTableChunk):
            h.update(_chunk_fingerprint(value).encode())
        elif isinstance(value, (ParquetScan, ParquetSplit)):
            h.update(split_fingerprint(
                getattr(value, "split", value)).encode())
        elif hasattr(value, "columns"):
            h.update(table_fingerprint(value).encode())
        else:
            raise TypeError(
                f"binding {name!r} is not fingerprintable: "
                f"{type(value).__name__}")
    return h.hexdigest()


def cache_key(plan: fusion.Plan, bindings: dict,
              fingerprint: Optional[str] = None) -> CacheKey:
    """Derive the full two-part key for one submission. ``fingerprint``
    overrides the content digest (e.g. a ``source_fingerprint`` the
    caller maintains for file-backed scans)."""
    fp = str(fingerprint) if fingerprint else input_fingerprint(bindings)
    if not fp:
        raise ValueError("cache key requires a non-empty input fingerprint")
    return CacheKey(plan_signature(plan, bindings), fp)


# ---------------------------------------------------------------------------
# meta snapshots — FusedResult.meta holds jax scalars; cached copies must
# not pin device buffers beyond the table the SpillStore manages
# ---------------------------------------------------------------------------


def _snap_meta(meta: dict) -> dict:
    """A result's meta with every device value read back to the host.
    Every value's copy is started before the first is waited for, so a
    result pays one transfer's latency and not one a value (0.42 ms each on
    a v5e, PERF.md section 5: planned q13 reports sixteen)."""
    meta = meta or {}
    for v in meta.values():
        start = getattr(v, "copy_to_host_async", None)
        if start is not None:
            start()
    return {k: np.asarray(v) if hasattr(v, "dtype") and hasattr(v, "shape")
            else v for k, v in meta.items()}


def _rehydrate_meta(meta: dict) -> dict:
    import jax.numpy as jnp

    out = {}
    for k, v in (meta or {}).items():
        if isinstance(v, np.ndarray):
            out[k] = jnp.asarray(v)
        else:
            out[k] = v
    return out


# ---------------------------------------------------------------------------
# the cache
# ---------------------------------------------------------------------------


class ResultCache:
    """LRU of ``FusedResult``s stored through an integrity-sealed
    :class:`SpillStore`, byte-charged against a shared
    :class:`MemoryLimiter`.

    Locking: the cache's own RLock is taken FIRST, then (inside put/get/
    shed) the store's and limiter's locks — the limiter never takes the
    cache lock (it reads the lock-free ``evictable_bytes`` int and calls
    ``shed()`` outside its own lock; see
    ``MemoryLimiter.attach_result_cache``), so the ordering is acyclic.
    Reentrancy matters: a ``limiter.reserve`` inside ``put`` can cross the
    high watermark and call straight back into ``shed`` on this thread.
    """

    def __init__(self, store: SpillStore, limiter: MemoryLimiter,
                 max_bytes: Optional[int] = None):
        self._store = store
        self._limiter = limiter
        self._max_bytes_override = max_bytes
        self._lock = threading.RLock()
        # key -> {handle, nbytes, stored, meta, charged}; insertion order
        # IS the LRU order (move_to_end on touch)
        self._entries: "collections.OrderedDict[CacheKey, dict]" = (
            collections.OrderedDict())
        # two byte sums: _bytes is LOGICAL (uncompressed HBM-equivalent)
        # payload across all tiers; _stored_bytes is the RESIDENT
        # footprint (codec-compressed once an entry leaves the device
        # tier) and is what the LRU capacity bound charges — compressed
        # entries make the same cache.max_bytes hold more results
        self._bytes = 0
        self._stored_bytes = 0
        # resident limiter-charged bytes a pressure event could reclaim;
        # a PLAIN int read lock-free by the limiter (under ITS lock), so
        # it must always be updated in the same critical section as the
        # charge it mirrors
        self.evictable_bytes = 0

    def _max_bytes(self) -> int:
        if self._max_bytes_override is not None:
            return int(self._max_bytes_override)
        # 0 (the default): a share of the budget entries are charged to
        return (int(get_option("cache.max_bytes"))
                or max(256 << 20, self._limiter.budget // 8))

    @staticmethod
    def _validate_key(key) -> CacheKey:
        # the runtime half of tpulint rule 16: a signature-only key would
        # serve stale results across data changes — reject it loudly
        if not isinstance(key, CacheKey):
            raise ValueError(
                f"result-cache keys must be CacheKey instances, got "
                f"{type(key).__name__}")
        if not key.fingerprint or not str(key.fingerprint).strip():
            raise ValueError(
                "result-cache key is missing its input fingerprint "
                "(signature-only keying serves stale results)")
        if not key.signature or not str(key.signature).strip():
            raise ValueError("result-cache key is missing its plan signature")
        return key

    def _count(self, event: str) -> None:
        # unconditional, like the server's admission counters: hit/miss
        # accounting must hold whether or not telemetry is watching
        REGISTRY.counter(f"cache.{event}").inc()

    def _refresh_stored_locked(self, entry: dict) -> None:
        """Re-read one entry's resident footprint from the store (it
        shrinks to the codec-compressed size when the entry is demoted
        off the device tier, and grows back to logical on re-stage) and
        fold the delta into the LRU accounting."""
        try:
            stored = self._store.stored_nbytes(entry["handle"])
        except KeyError:
            return  # store closed / entry dropped under us: keep last
        self._stored_bytes += stored - entry["stored"]
        entry["stored"] = stored

    def _reconcile_locked(self, entry: dict) -> None:
        """The SpillStore's OWN LRU may have demoted a charged entry
        while making room for live working sets; fold that into the
        charge so the limiter never counts bytes HBM no longer holds."""
        self._refresh_stored_locked(entry)
        if not entry["charged"]:
            return
        try:
            state = self._store.state(entry["handle"])
        except KeyError:
            state = "host"  # store closed under us: treat as not resident
        if state != "device":
            entry["charged"] = False
            self.evictable_bytes -= entry["nbytes"]
            self._limiter.release(entry["nbytes"])

    def _uncharge_locked(self, entry: dict) -> None:
        if entry["charged"]:
            entry["charged"] = False
            self.evictable_bytes -= entry["nbytes"]
            self._limiter.release(entry["nbytes"])

    def _discard_locked(self, key: CacheKey, entry: dict,
                        event: str) -> None:
        self._uncharge_locked(entry)
        self._entries.pop(key, None)
        self._bytes -= entry["nbytes"]
        self._stored_bytes -= entry["stored"]
        try:
            self._store.drop(entry["handle"])
        except KeyError:
            pass
        self._count(event)

    def _shed_locked(self, nbytes: int) -> int:
        """Demote resident charged entries (coldest first) to the store's
        host/disk tier, releasing their limiter charges. Entries SURVIVE
        a shed — a later hit stages them back verified."""
        freed = 0
        for key, entry in list(self._entries.items()):
            if freed >= nbytes:
                break
            self._reconcile_locked(entry)
            if not entry["charged"]:
                continue
            try:
                self._store.spill(entry["handle"])
            except KeyError:
                self._discard_locked(key, entry, "eviction")
                continue
            self._uncharge_locked(entry)
            self._refresh_stored_locked(entry)
            freed += entry["nbytes"]
            record_cache("result_cache", "shed", key=key.short,
                         nbytes=entry["nbytes"])
        if freed:
            REGISTRY.counter("cache.shed_bytes").inc(freed)
        return freed

    def shed(self, nbytes: int) -> int:
        """The limiter's pressure hook: free up to ``nbytes`` of resident
        cache HBM before any live query's working set is spilled."""
        with self._lock:
            return self._shed_locked(max(int(nbytes), 0))

    def make_room(self, nbytes: int) -> int:
        """Displacement before an admission reserve: if ``nbytes`` does
        not currently fit the limiter's budget, shed enough resident
        cache bytes that it could — cached results never make a live
        query wait."""
        need = int(nbytes) - (self._limiter.budget - self._limiter.used)
        if need <= 0:
            return 0
        with self._lock:
            return self._shed_locked(need)

    def _charge_locked(self, nbytes: int) -> bool:
        """Reserve ``nbytes`` for a resident entry, shedding own colder
        entries to make room; False when the budget genuinely cannot
        take it (the entry then lives uncharged in the spilled tier)."""
        try:
            self._limiter.reserve(nbytes)
            return True
        except MemoryLimitExceeded:
            pass
        need = nbytes - (self._limiter.budget - self._limiter.used)
        if need > 0:
            self._shed_locked(need)
        try:
            self._limiter.reserve(nbytes)
            return True
        except MemoryLimitExceeded:
            return False

    def put(self, key: CacheKey, result: fusion.FusedResult,
            accept=None) -> bool:
        """Memoize one result. The entry shares the result's device
        buffers (zero copy) and is charged against the limiter while
        resident; when the charge cannot fit it is demoted to the
        integrity-sealed host tier immediately instead of starving live
        queries. Returns True when the entry was stored.

        ``accept(meta)``, if given, gets the host copy of the result's
        meta the entry keeps (taking it is the wait for the device) before
        any lookup can see the entry; if it raises, nothing stays stored
        and the exception propagates. It is not called where nothing new
        is stored (cache off, the key already there, too big)."""
        if not enabled():
            return False
        self._validate_key(key)
        table = result.table
        nbytes = _table_nbytes(table)
        with self._lock:
            existing = self._entries.get(key)
            if existing is not None:
                self._entries.move_to_end(key)
                return True
            if nbytes > self._max_bytes():
                self._count("too_big")
                return False
            # LRU capacity bound charges RESIDENT (stored) bytes: demoted
            # entries count at their codec-compressed footprint, so the
            # same cache.max_bytes holds more results once the compress
            # seam shrinks the spilled tier. The incoming entry starts
            # device-resident, i.e. at its full logical size.
            while (self._stored_bytes + nbytes > self._max_bytes()
                   and self._entries):
                old_key, old = next(iter(self._entries.items()))
                self._discard_locked(old_key, old, "eviction")
                record_cache("result_cache", "evict", key=old_key.short,
                             nbytes=old["nbytes"])
            charged = self._charge_locked(nbytes)
            handle = self._store.put(table, integrity_seam="integrity.cache")
            if not charged:
                # no budget for residency: keep only the sealed host copy
                self._store.spill(handle)
            entry = {
                "handle": handle, "nbytes": nbytes, "stored": nbytes,
                "meta": _snap_meta(result.meta), "charged": charged,
            }
            self._entries[key] = entry
            self._bytes += nbytes
            self._stored_bytes += nbytes
            if charged:
                self.evictable_bytes += nbytes
            else:
                # already demoted: account the compressed footprint now
                self._refresh_stored_locked(entry)
            if accept is not None:
                try:
                    accept(entry["meta"])
                except BaseException:
                    self._discard_locked(key, entry, "refused")
                    raise
        self._count("put")
        record_cache("result_cache", "put", key=key.short, nbytes=nbytes)
        return True

    def get(self, key: CacheKey) -> Optional[fusion.FusedResult]:
        """Probe for a bit-identical memoized result. A spilled entry is
        re-charged and staged back through the store's verify-before-
        decode read; a corrupt payload (classified ``CorruptDataError``)
        discards the entry and returns a miss — the caller recomputes,
        with zero reservation left behind."""
        if not enabled():
            return None
        self._validate_key(key)
        with self._lock:
            entry = self._entries.get(key)
            if entry is None:
                self._count("miss")
                record_cache("result_cache", "miss", key=key.short)
                return None
            nbytes = entry["nbytes"]
            self._reconcile_locked(entry)
            reserved = False
            if not entry["charged"]:
                # staging back needs HBM: charge (shedding colder entries
                # if needed) BEFORE the host->device copy, the same
                # reserve-first contract as SpillStore.get_reserved
                if not self._charge_locked(nbytes):
                    self._count("bypass")
                    record_cache("result_cache", "miss", key=key.short,
                                 reason="no budget to stage")
                    return None
                reserved = True
            try:
                table = self._store.get(entry["handle"])
            except resilience.CorruptDataError as exc:
                # verified-at-read caught a corrupt cached payload:
                # classified discard, then the caller recomputes from
                # source — never serve wrong bytes, never leak the charge
                if reserved:
                    self._limiter.release(nbytes)
                    entry["charged"] = False
                else:
                    self._uncharge_locked(entry)
                entry["charged"] = False
                self._discard_locked(key, entry, "corrupt_discard")
                record_integrity(
                    "result_cache", "mismatch", seam="integrity.cache",
                    nbytes=nbytes, reason=str(exc))
                record_cache("result_cache", "corrupt_discard",
                             key=key.short, nbytes=nbytes)
                _log.warning("corrupt cached entry %s discarded: %s",
                             key.short, exc)
                return None
            except KeyError:
                if reserved:
                    self._limiter.release(nbytes)
                self._entries.pop(key, None)
                self._bytes -= nbytes
                self._stored_bytes -= entry["stored"]
                self._count("miss")
                return None
            if reserved:
                entry["charged"] = True
                self.evictable_bytes += nbytes
            # staged back to the device tier: resident footprint is the
            # full logical size again
            self._refresh_stored_locked(entry)
            self._entries.move_to_end(key)
            meta = _rehydrate_meta(entry["meta"])
        self._count("hit")
        record_cache("result_cache", "hit", key=key.short, nbytes=nbytes)
        return fusion.FusedResult(table, meta)

    def invalidate(self, key: CacheKey) -> bool:
        """Drop one entry (e.g. the caller knows its source changed)."""
        with self._lock:
            entry = self._entries.get(key)
            if entry is None:
                return False
            self._discard_locked(key, entry, "invalidated")
        return True

    def clear(self) -> None:
        with self._lock:
            for key, entry in list(self._entries.items()):
                self._discard_locked(key, entry, "cleared")

    def close(self) -> None:
        self.clear()

    def stats(self) -> dict:
        c = REGISTRY.counters("cache.")
        with self._lock:
            entries = len(self._entries)
            total = self._bytes
            stored = self._stored_bytes
            resident = self.evictable_bytes
        hits = c.get("cache.hit", 0)
        misses = c.get("cache.miss", 0)
        return {
            "entries": entries,
            "bytes": total,
            "stored_bytes": stored,
            "resident_bytes": resident,
            "max_bytes": self._max_bytes(),
            "hits": hits,
            "misses": misses,
            "hit_rate": round(hits / (hits + misses), 4)
            if hits + misses else None,
            "puts": c.get("cache.put", 0),
            "evictions": c.get("cache.eviction", 0),
            "shed_bytes": c.get("cache.shed_bytes", 0),
            "corrupt_discards": c.get("cache.corrupt_discard", 0),
            "subplan_hits": c.get("cache.subplan_hit", 0),
            "subplan_materializations": c.get(
                "cache.subplan_materialize", 0),
        }


# ---------------------------------------------------------------------------
# subplan-prefix reuse
# ---------------------------------------------------------------------------

# a prefix must carry at least this many non-Scan nodes to be worth a
# separate region dispatch + materialization (a lone Project re-executes
# faster than it round-trips the cache)
_MIN_PREFIX_NODES = 2


def apply_subplans(cache: Optional[ResultCache], plan: fusion.Plan,
                   bindings: dict, *, cancel_token=None):
    """Rewrite ``plan`` so every cacheable scan+filter+project prefix is
    served from (or materialized into) ``cache``.

    For each maximal Filter/rowwise-Project chain over a bucketed Scan
    (``fusion.scan_prefix_chains``, at least ``_MIN_PREFIX_NODES`` deep),
    the chain's canonical digest + its scan binding's content fingerprint
    key a cached intermediate: on a hit the subtree collapses to a Scan
    bound to the cached table; on a miss the prefix executes ONCE as its
    own fused region, is cached, and then collapses the same way — so two
    plans sharing the prefix execute it exactly once between them.

    Bit-identity holds because Filter masks validity in place and a
    rowwise Project stays in the scan's row space: the materialized
    intermediate is, content-for-content, exactly what the consumer node
    would have seen mid-region, and fused==staged per region is already
    the repo's core contract.

    Returns ``(plan, bindings, rewritten)``; when ``rewritten`` the
    caller MUST NOT donate inputs (the injected binding is cache-owned).
    A pressure/compile failure while materializing a prefix leaves that
    chain unrewritten — the degradation ladder handles the full plan.
    """
    if cache is None or not subplan_enabled():
        return plan, bindings, False
    chains = fusion.scan_prefix_chains(plan.root)
    root = plan.root
    out_bindings = dict(bindings)
    rewritten = False
    for scan, top, length in chains:
        if length < _MIN_PREFIX_NODES or scan.name not in out_bindings:
            continue
        binding = out_bindings[scan.name]
        sub_plan = fusion.Plan(f"{plan.name}.prefix.{scan.name}", top)
        try:
            key = cache_key(sub_plan, {scan.name: binding})
        except (ValueError, KeyError, TypeError):
            continue  # unfingerprintable prefix (e.g. local callables)
        hit = cache.get(key)
        if hit is not None:
            REGISTRY.counter("cache.subplan_hit").inc()
            record_cache(sub_plan.name, "subplan_hit", key=key.short)
            table = hit.table
        else:
            try:
                with spans.child(f"cache.subplan.{scan.name}",
                                 mode="materialize"):
                    res = fusion.execute(
                        sub_plan, {scan.name: binding},
                        donate_inputs=False, cancel_token=cancel_token)
            except resilience.QueryCancelled:
                raise
            except Exception:
                REGISTRY.counter("cache.subplan_abort").inc()
                continue
            REGISTRY.counter("cache.subplan_materialize").inc()
            record_cache(sub_plan.name, "subplan_materialize",
                         key=key.short)
            cache.put(key, res)
            table = res.table
        alias = f"__subplan_{key.signature[:12]}"
        root = fusion.replace_node(root, top, fusion.Scan(alias, True))
        out_bindings[alias] = table
        rewritten = True
    if not rewritten:
        return plan, bindings, False
    return fusion.Plan(plan.name, root), out_bindings, True
