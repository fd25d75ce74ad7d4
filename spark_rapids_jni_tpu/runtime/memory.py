"""Device/host memory management — the RMM-equivalent layer.

The reference leans on RMM for device memory pools, per-thread streams and
an ``RMM_LOGGING_LEVEL`` knob (reference pom.xml:82, CMakeLists.txt:56-63;
rmm::device_uvector use throughout row_conversion.cu). On TPU the HBM
allocator itself belongs to XLA — JAX arrays live in XLA's BFC arena, and
re-implementing that would fight the runtime. What this layer provides is
the part of RMM's surface a Spark executor actually interacts with:

  * ``device_memory_stats()`` — live/peak/limit HBM numbers per device
    (RMM's ``mr.get_info`` role) for spill decisions and telemetry;
  * ``MemoryLimiter`` — a soft budget gate: reserve/release accounting
    with the same fail-fast contract as a capped RMM pool, used by the
    chunked reader to size batches;
  * ``HostStagingPool`` — recycled pinned-style host buffers for the
    parquet/IO staging path (the role of RMM's pinned-host pool), a size-
    class freelist so repeated chunked reads stop hammering the allocator;
  * allocation logging behind ``memory.log_level``
    (env SPARK_RAPIDS_TPU_MEMORY_LOG_LEVEL) — RMM_LOGGING_LEVEL parity.
"""

from __future__ import annotations

import collections
import os
import pickle
import threading
import time
from dataclasses import dataclass
from typing import NamedTuple, Optional

import numpy as np

from spark_rapids_jni_tpu import telemetry
from spark_rapids_jni_tpu.runtime import compress, faults, integrity
from spark_rapids_jni_tpu.telemetry import spans
from spark_rapids_jni_tpu.utils.config import get_option
from spark_rapids_jni_tpu.utils.log import get_logger

_log = get_logger("spark_rapids_jni_tpu.memory")


@dataclass(frozen=True)
class DeviceMemoryStats:
    bytes_in_use: int
    peak_bytes_in_use: int
    bytes_limit: int

    @property
    def bytes_free(self) -> int:
        return max(self.bytes_limit - self.bytes_in_use, 0)


def device_memory_stats(device=None) -> DeviceMemoryStats:
    """Live HBM stats from the XLA allocator (zeros when the backend does
    not report — e.g. some CPU builds)."""
    import jax

    if device is None:
        device = jax.devices()[0]
    stats = {}
    try:
        stats = device.memory_stats() or {}
    except (RuntimeError, AttributeError):
        pass
    return DeviceMemoryStats(
        bytes_in_use=int(stats.get("bytes_in_use", 0)),
        peak_bytes_in_use=int(stats.get("peak_bytes_in_use", 0)),
        bytes_limit=int(stats.get("bytes_limit", 0)),
    )


class MemoryLimitExceeded(MemoryError):
    pass


class _Waiter:
    """One blocked ``reserve_blocking`` ticket. The ``admission`` flag is
    what lets the head-of-line check distinguish a pressure-parked
    admission (which must NOT hold the FIFO line — the in-flight work
    behind it is what drains the pressure) from an ordinarily blocked
    reservation (which must)."""

    __slots__ = ("admission",)

    def __init__(self, admission: bool):
        self.admission = bool(admission)


class MemoryLimiter:
    """Soft budget gate with capped-pool semantics: ``reserve`` beyond the
    budget raises (fail-fast, like a capped RMM pool) instead of letting a
    giant batch OOM the device mid-kernel.

    Pressure watermarks (``memory.high_watermark`` / ``memory.low_watermark``
    fractions of the budget, overridable per instance): a grant that lifts
    usage across the high watermark enters the *pressure* state — the
    ``memory.pressure`` fault seam fires, a ``degrade.pressure`` telemetry
    event is emitted, the coldest entries of an attached :class:`SpillStore`
    are proactively spilled, and ``reserve_blocking(..., admission=True)``
    callers (the serving runtime's admission gate) park until usage drains
    back below the low watermark. Non-admission reservations (pipeline
    chunks of already-running queries) are never paused — a pressure-parked
    admission ticket does not even hold the FIFO line against them — so
    in-flight work keeps draining toward the low watermark instead of
    deadlocking behind the very admission that is waiting for it.
    """

    def __init__(self, budget_bytes: int, *,
                 high_watermark: "float | None" = None,
                 low_watermark: "float | None" = None):
        if budget_bytes <= 0:
            raise ValueError("budget must be positive")
        self.budget = int(budget_bytes)
        self._used = 0
        self._peak = 0
        self._high_frac = None if high_watermark is None else float(high_watermark)
        self._low_frac = None if low_watermark is None else float(low_watermark)
        self._pressure = False
        self._pressure_crossings = 0
        self._spill_store: "SpillStore | None" = None
        self._result_cache = None
        # a Condition so reserve_blocking can sleep until release() frees
        # budget; plain reserve/release take the same underlying lock
        self._lock = threading.Condition()
        # FIFO queue of blocked reserve_blocking tickets: budget freed by a
        # release is offered to the longest-waiting reserver first, so a
        # small late request cannot barge past a large early one forever
        self._waiters: "collections.deque[_Waiter]" = collections.deque()

    @property
    def used(self) -> int:
        return self._used

    @property
    def peak(self) -> int:
        return self._peak

    @property
    def pressure(self) -> bool:
        """True between a high-watermark crossing and the drain below low."""
        return self._pressure

    @property
    def pressure_crossings(self) -> int:
        """How many times usage has crossed the high watermark (the seq the
        ``memory.pressure`` fault seam fires with — lets a FaultScript
        target the Nth crossing deterministically)."""
        return self._pressure_crossings

    def attach_spill_store(self, store: "SpillStore | None") -> None:
        """Register the SpillStore whose coldest entries a high-watermark
        crossing proactively spills (None detaches)."""
        self._spill_store = store

    def attach_result_cache(self, cache) -> None:
        """Register a ResultCache (runtime/resultcache.py) whose entries a
        high-watermark crossing sheds BEFORE any live query's working set
        is spilled, and whose evictable resident bytes do not count as
        "held" for drain waits (None detaches). The limiter only ever
        reads the cache's lock-free ``evictable_bytes`` int under its own
        lock and calls ``shed()`` outside it — the cache takes its own
        lock then the limiter's (release), never the reverse, so the two
        locks cannot deadlock."""
        self._result_cache = cache

    def _evictable_cache_bytes(self) -> int:
        """Resident limiter-charged cache bytes a pressure event could
        reclaim. Lock-free read of a plain int attribute — safe under the
        limiter lock (see attach_result_cache)."""
        cache = self._result_cache
        if cache is None:
            return 0
        return max(int(cache.evictable_bytes), 0)

    def watermarks(self) -> dict:
        """One consistent snapshot of the limiter's watermark state —
        live introspection (QueryServer.inspect(), flight-recorder
        dumps). Read under the lock so used/waiters/pressure cohere."""
        with self._lock:
            return {
                "used": self._used,
                "budget": self.budget,
                "peak": self._peak,
                "pressure": self._pressure,
                "pressure_crossings": self._pressure_crossings,
                "high_bytes": self._high_bytes(),
                "low_bytes": self._low_bytes(),
                "waiters": len(self._waiters),
                "admission_waiters": sum(
                    1 for w in self._waiters if w.admission),
            }

    def _high_bytes(self) -> int:
        frac = self._high_frac
        if frac is None:
            frac = float(get_option("memory.high_watermark"))
        return int(self.budget * frac)

    def _low_bytes(self) -> int:
        frac = self._low_frac
        if frac is None:
            frac = float(get_option("memory.low_watermark"))
        # a misconfigured low > high would make pressure un-clearable the
        # moment it is entered; clamp instead of wedging admission
        return min(int(self.budget * frac), self._high_bytes())

    def _held_back_locked(self, ticket: "_Waiter") -> bool:
        """Under the lock: is an EARLIER waiter legitimately holding the
        FIFO line against ``ticket``? Pressure-parked admission tickets
        (admission waiters while the limiter is in the pressure state) do
        not hold the line — the non-admission reservations behind them
        belong to in-flight queries whose releases are the only thing that
        can drain the pressure, so blocking them would wedge the limiter
        until the admission timeout. Parked admissions keep their queue
        position: the moment pressure clears they are the head again and
        ordinary no-barge FIFO resumes."""
        for w in self._waiters:
            if w is ticket:
                return False
            if not (w.admission and self._pressure):
                return True
        return False

    def _note_grant_locked(self) -> bool:
        """Called under the lock after ``_used`` grew; returns True exactly
        when this grant crossed the high watermark (caller reacts outside
        the lock — the pressure reaction spills and fires fault seams)."""
        # doubly gated: on degrade.enabled (with degradation off the
        # limiter is byte-for-byte the pre-watermark accounting — the PR-7
        # parity contract) AND on an attached spill store — watermarks are
        # a managed-limiter feature (the serving runtime attaches its
        # store); a bare limiter shared with external holders would
        # otherwise park admission on pressure nothing can ever drain
        if (not self._pressure and self._spill_store is not None
                and self._used >= self._high_bytes()
                and get_option("degrade.enabled")):
            self._pressure = True
            self._pressure_crossings += 1
            return True
        return False

    def _enter_pressure(self) -> None:
        """React to a high-watermark crossing: fault seam, telemetry,
        proactive spill of the attached store's coldest entries. Runs
        OUTSIDE the lock; an injected ``memory.pressure`` fault propagates
        to the reserving caller (which rolls back its grant)."""
        faults.fire("memory.pressure", self._pressure_crossings,
                    used=self._used, budget=self.budget,
                    watermark=self._high_bytes())
        freed = 0
        shed = 0
        target = max(self._used - self._low_bytes(), 1)
        # eviction ordering: cached results are the FIRST thing to go —
        # shedding a cache entry demotes it to the host/disk tier and
        # releases its limiter charge, so live queries' working sets are
        # only spilled for whatever pressure the cache could not absorb
        cache = self._result_cache
        if cache is not None:
            shed = cache.shed(target)
        store = self._spill_store
        if store is not None and shed < target:
            # ambition: drain resident spill-store bytes by as much as the
            # limiter is above its low watermark, coldest entries first
            freed = store.spill_coldest(target - shed)
        telemetry.record_degrade(
            "memory_limiter", "pressure", tier="high", trigger="watermark",
            rung=0, used=self._used, budget=self.budget,
            proactive_spill_bytes=freed, cache_shed_bytes=shed)
        if get_option("memory.log_level") >= 1:
            _log.info("memory pressure: %d/%d in use (high watermark %d), "
                      "proactively spilled %d bytes", self._used, self.budget,
                      self._high_bytes(), freed)

    def reserve(self, nbytes: int) -> None:
        # fault seam BEFORE the lock: an injected reservation failure must
        # leave the accounting untouched, like a real allocator rejection
        faults.fire("memory.reserve", nbytes, blocking=False)
        with self._lock:
            if self._used + nbytes > self.budget:
                raise MemoryLimitExceeded(
                    f"reservation of {nbytes} bytes exceeds budget "
                    f"({self._used}/{self.budget} in use)"
                )
            self._used += nbytes
            self._peak = max(self._peak, self._used)
            crossed = self._note_grant_locked()
            if get_option("memory.log_level") >= 2:
                _log.info("reserve %d bytes (%d in use)", nbytes, self._used)
        if crossed:
            try:
                self._enter_pressure()
            except BaseException:
                # an injected pressure fault must not leak the grant it
                # was reacting to
                self.release(nbytes)
                raise

    def reserve_blocking(self, nbytes: int, cancel=None,
                         timeout: "float | None" = None,
                         admission: bool = False) -> bool:
        """Wait until ``nbytes`` fits inside the budget, then reserve it.

        The pipeline's backpressure primitive: where ``reserve`` fails
        loud, this form parks the producer until a consumer ``release``
        frees room, so a tight budget degrades throughput toward serial
        instead of raising mid-run. A request larger than the WHOLE
        budget can never fit and raises ``MemoryLimitExceeded``
        immediately (same contract as ``reserve``). Returns True on
        success, False if ``cancel`` (a threading.Event) was set or
        ``timeout`` seconds elapsed first — cancellation is polled, so
        a cancelled producer wakes within ~50ms.

        Ordering contract: concurrent blocked reservers are served FIFO —
        freed budget goes to the longest-waiting request first, and a
        later (even smaller) request never barges past an earlier blocked
        one. A plain ``reserve`` keeps its fail-fast semantics and does
        not queue.

        ``admission=True`` marks this reservation as a NEW unit of work
        (the serving runtime's admission gate): while the limiter is in
        the pressure state, admission reservations park until usage
        drains below the low watermark even if the bytes would fit.
        Plain reservations (chunks of already-admitted queries) ignore
        pressure AND flow past pressure-parked admission tickets in the
        queue — in-flight work keeps draining; the parked admission keeps
        its FIFO position for when pressure clears.
        """
        faults.fire("memory.reserve", nbytes, blocking=True)
        if nbytes > self.budget:
            raise MemoryLimitExceeded(
                f"reservation of {nbytes} bytes exceeds the whole budget "
                f"({self.budget}): can never fit"
            )
        deadline = None if timeout is None else time.monotonic() + timeout
        ticket = _Waiter(admission)
        with self._lock:
            self._waiters.append(ticket)
            try:
                # grant only when no earlier ticket holds the line AND the
                # bytes fit: a blocked earlier ticket holds back every
                # later one (the no-barge property) — except a pressure-
                # parked admission, which in-flight reservations bypass
                while (self._held_back_locked(ticket)
                       or self._used + nbytes > self.budget
                       or (admission and self._pressure)):
                    if cancel is not None and cancel.is_set():
                        return False
                    wait = 0.05
                    if deadline is not None:
                        remaining = deadline - time.monotonic()
                        if remaining <= 0:
                            return False
                        wait = min(wait, remaining)
                    self._lock.wait(wait)
                self._used += nbytes
                self._peak = max(self._peak, self._used)
                crossed = self._note_grant_locked()
                if get_option("memory.log_level") >= 2:
                    _log.info(
                        "reserve %d bytes (%d in use)", nbytes, self._used)
            finally:
                # leaving for ANY reason (granted, cancelled, timed out)
                # unblocks the next ticket in line
                self._waiters.remove(ticket)
                self._lock.notify_all()
        if crossed:
            try:
                self._enter_pressure()
            except BaseException:
                self.release(nbytes)
                raise
        return True

    def wait_below_low(self, timeout: "float | None" = None,
                       cancel=None, own_held: int = 0) -> bool:
        """Park until usage drains below the low watermark — the
        park-and-retry ladder rung's drain wait (runtime/degrade.py).
        ``own_held`` is the caller's OWN outstanding reservation (the
        serving runtime's admission estimate): it is subtracted from the
        drain threshold, because a query whose own hold exceeds the low
        watermark could otherwise never observe the drain it is waiting
        for. Evictable result-cache bytes (attach_result_cache) are also
        subtracted: they are reclaimable on demand, so a parked query must
        not wait out a drain the next pressure event would provide for
        free. Returns True once drained, False if ``cancel`` (anything
        with ``is_set()``) fired or ``timeout`` seconds elapsed first;
        cancellation is polled (~50ms), same as ``reserve_blocking``."""
        deadline = None if timeout is None else time.monotonic() + timeout
        own = max(int(own_held), 0)
        with self._lock:
            while (self._used - own - self._evictable_cache_bytes()
                   > self._low_bytes()):
                if cancel is not None and cancel.is_set():
                    return False
                wait = 0.05
                if deadline is not None:
                    remaining = deadline - time.monotonic()
                    if remaining <= 0:
                        return False
                    wait = min(wait, remaining)
                self._lock.wait(wait)
        return True

    def reclaim_cache(self, nbytes: "int | None" = None) -> int:
        """Turn the drain ``wait_below_low`` promised into real free
        bytes: shed evictable result-cache entries (demote + release
        charge) for up to ``nbytes`` (default: whatever stands between
        current usage and the low watermark). Called OUTSIDE the limiter
        lock — the parked rung (runtime/degrade.py) invokes it after a
        drain wait returns, so a resumed query's retry reserve finds the
        budget the evictable discount counted on."""
        cache = self._result_cache
        if cache is None:
            return 0
        target = (max(self._used - self._low_bytes(), 0)
                  if nbytes is None else max(int(nbytes), 0))
        if target <= 0:
            return 0
        return cache.shed(target)

    def release(self, nbytes: int) -> None:
        with self._lock:
            self._used = max(self._used - nbytes, 0)
            cleared = self._pressure and self._used <= self._low_bytes()
            if cleared:
                self._pressure = False
            self._lock.notify_all()
            if get_option("memory.log_level") >= 2:
                _log.info("release %d bytes (%d in use)", nbytes, self._used)
        if cleared:
            telemetry.record_degrade(
                "memory_limiter", "pressure", tier="low", trigger="watermark",
                rung=0, used=self._used, budget=self.budget)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        with self._lock:
            self._used = 0
            self._pressure = False
            self._lock.notify_all()
        return False


class HostStagingPool:
    """Freelist of host staging buffers, bucketed by power-of-two size.

    ``take(nbytes)`` returns a uint8 array of at least nbytes (callers
    slice); ``give(buf)`` recycles it. Thread-safe; bounded per bucket so a
    burst cannot pin unbounded host memory."""

    def __init__(self, max_buffers_per_class: int = 8):
        self._free: dict[int, list[np.ndarray]] = {}
        self._max = max_buffers_per_class
        self._lock = threading.Lock()
        self.hits = 0
        self.misses = 0

    @staticmethod
    def _size_class(nbytes: int) -> int:
        return 1 << max(int(nbytes - 1).bit_length(), 6)  # min 64B

    def take(self, nbytes: int) -> np.ndarray:
        cls = self._size_class(max(nbytes, 1))
        with self._lock:
            bucket = self._free.get(cls)
            if bucket:
                self.hits += 1
                return bucket.pop()
            self.misses += 1
        if get_option("memory.log_level") >= 1:
            _log.info("staging alloc %d bytes (class %d)", nbytes, cls)
        return np.empty(cls, dtype=np.uint8)

    def give(self, buf: np.ndarray) -> None:
        cls = int(buf.nbytes)
        # only recycle buffers this pool could have produced: uint8,
        # power-of-two size, at least the minimum size class
        if buf.dtype != np.uint8 or cls < 64 or cls & (cls - 1):
            return
        with self._lock:
            bucket = self._free.setdefault(cls, [])
            if len(bucket) < self._max:
                bucket.append(buf)

    def clear(self) -> None:
        with self._lock:
            self._free.clear()


_default_pool: Optional[HostStagingPool] = None
_default_pool_lock = threading.Lock()


def default_staging_pool() -> HostStagingPool:
    global _default_pool
    with _default_pool_lock:
        if _default_pool is None:
            _default_pool = HostStagingPool()
        return _default_pool


# ---- spill store (the RMM arena's overflow valve) --------------------------


def _col_nbytes(c, chip: bool = False) -> int:
    """A column's bytes, children included; with ``chip`` what ONE chip
    holds of them: a buffer sharded over a mesh counts a shard
    (``sharding.shard_shape``), any other buffer counts whole."""
    def nbytes(buf) -> int:
        if buf is None:
            return 0
        shape = buf.shape
        sharding = getattr(buf, "sharding", None)
        if chip and sharding is not None and len(sharding.device_set) > 1:
            shape = sharding.shard_shape(shape)
        return int(np.prod(shape)) * buf.dtype.itemsize

    return (sum(nbytes(b) for b in (c.data, c.validity, c.chars))
            + sum(_col_nbytes(child, chip) for child in (c.children or ())))


def _table_nbytes(table) -> int:
    return sum(_col_nbytes(c) for c in table.columns)


def table_chip_nbytes(table) -> int:
    """The bytes of ``table`` that ONE chip holds: all of them for a table
    on one device or on the host, a shard's for buffers row-sharded over a
    mesh. What admission reserves: the limiter's budget is one chip's
    memory, and a table sharded over four chips costs each a quarter."""
    return sum(_col_nbytes(c, chip=True) for c in table.columns)


def _pack_array(arr, cctx, codec_seam=None):
    """Re-encode one host buffer for the spilled tiers (the nvcomp role
    for the HOST path). ``codec_seam`` routes it through the columnar
    codec (runtime/compress.py) as a self-describing ``("tpcc", ...)``
    pack; otherwise ``cctx`` keeps the legacy whole-buffer zstd pack, and
    with both off the plain array passes through — byte-for-byte the
    pre-codec snapshot."""
    if arr is None:
        return None
    if codec_seam is not None:
        return compress.pack_array(arr, codec_seam)
    a = np.ascontiguousarray(arr)
    if cctx is None:
        return a
    # compress() takes buffer-protocol objects — no tobytes() copy
    return ("zstd", a.dtype.str, a.shape, cctx.compress(a))


def _unpack_array(obj, dctx, seam="integrity.spill"):
    if obj is None or not isinstance(obj, tuple):
        return obj
    if compress.is_codec_pack(obj):
        # runs after the seam's trailer/crc verified; the codec re-checks
        # the frame itself so a corrupt-after-decompress header is still
        # a classified CorruptDataError, never garbage staged to HBM
        return compress.unpack_array(obj, seam=seam, op="spill_store.unpack")
    _, dtype_str, shape, blob = obj
    return np.frombuffer(
        dctx.decompress(blob), dtype=np.dtype(dtype_str)).reshape(shape)


def _packed_nbytes(obj) -> int:
    if obj is None:
        return 0
    if isinstance(obj, tuple):
        return len(obj[3])
    return obj.nbytes


def _col_to_host(c, cctx=None, codec_seam=None) -> tuple:
    """Recursive host snapshot of a column (incl. LIST/STRUCT children)."""
    return (
        c.dtype,
        _pack_array(np.asarray(c.data), cctx, codec_seam),
        None if c.validity is None
        else _pack_array(np.asarray(c.validity), cctx, codec_seam),
        None if c.chars is None
        else _pack_array(np.asarray(c.chars), cctx, codec_seam),
        None if not c.children
        else [_col_to_host(ch, cctx, codec_seam) for ch in c.children],
    )


def _col_from_host(snap, dctx=None, seam="integrity.spill"):
    import jax.numpy as jnp

    from spark_rapids_jni_tpu.columnar import Column

    dtype, data, validity, chars, children = snap
    return Column(
        dtype, jnp.asarray(_unpack_array(data, dctx, seam)),
        None if validity is None
        else jnp.asarray(_unpack_array(validity, dctx, seam)),
        chars=None if chars is None
        else jnp.asarray(_unpack_array(chars, dctx, seam)),
        children=None if children is None
        else [_col_from_host(ch, dctx, seam) for ch in children],
    )


class HostTableChunk(NamedTuple):
    """A host-decoded table chunk awaiting device staging.

    ``cols`` holds column snapshots in the ``_col_to_host`` format
    (dtype, data, validity, chars, children — all numpy); ``nbytes`` is
    the exact device footprint ``stage()`` will allocate. The pipelined
    executor decodes chunks to this form in its read/decode stage so the
    MemoryLimiter reservation can be taken on exact bytes BEFORE the
    host->device copy — backpressure that cannot over-commit the budget
    on a size guess."""

    cols: tuple
    nbytes: int
    num_rows: int

    def stage(self):
        """Host->device copy. Callers reserve ``nbytes`` first."""
        from spark_rapids_jni_tpu.columnar import Table

        return Table([_col_from_host(snap) for snap in self.cols])


def host_table_chunk(snaps, num_rows: int) -> HostTableChunk:
    snaps = tuple(snaps)
    return HostTableChunk(
        snaps, sum(_host_snap_nbytes(s) for s in snaps), int(num_rows))


def _host_snap_nbytes(snap) -> int:
    _, data, validity, chars, children = snap
    n = (_packed_nbytes(data) + _packed_nbytes(validity)
         + _packed_nbytes(chars))
    for ch in (children or []):
        n += _host_snap_nbytes(ch)
    return n


def _unlink_quiet(path: "str | None") -> None:
    if not path:
        return
    try:
        os.unlink(path)
    except OSError:
        pass


def _inject_snap_corruption(snaps: list, seam: str, eid: int) -> None:
    """Fault-script corruption window for IN-MEMORY spill snapshots:
    route the first packed host buffer through :func:`faults.fire_corrupt`
    so the chaos suite can plant latent corruption that unspill must
    detect. Live numpy arrays cannot shrink, so only length-preserving
    mutations land on raw buffers; zstd packs accept any mutation. One
    ``is None`` check when no injector is installed."""
    if faults.active_injector() is None:
        return
    for si, snap in enumerate(snaps):
        dtype, data, validity, chars, children = snap
        for bi, buf in enumerate((data, validity, chars)):
            if buf is None:
                continue
            if isinstance(buf, tuple):  # ("zstd", dtype_str, shape, blob)
                blob = buf[3]
                mutated = faults.fire_corrupt(seam, eid, blob)
                if mutated is blob:
                    continue
                new_buf = (buf[0], buf[1], buf[2], mutated)
            else:
                raw = buf.tobytes()
                mutated = faults.fire_corrupt(seam, eid, raw)
                if mutated is raw or len(mutated) != len(raw):
                    continue
                new_buf = np.frombuffer(
                    bytearray(mutated), dtype=buf.dtype).reshape(buf.shape)
            bufs = [data, validity, chars]
            bufs[bi] = new_buf
            snaps[si] = (dtype, bufs[0], bufs[1], bufs[2], children)
            return


class SpillStore:
    """HBM pressure valve — the role RMM's spillable pool plays for the
    Spark plugin: registered tables count against a device budget; when a
    new registration would exceed it, least-recently-used tables SPILL to
    host numpy copies (freeing their HBM the moment the JAX arrays drop),
    and touching a spilled table stages it back, spilling others if needed.

    Deliberate scope: inter-OPERATOR working sets (shuffle partitions,
    chunked-read batches, cached build sides) — not intra-kernel memory,
    which belongs to XLA's own arena. Thread-safe; spill/unspill events log
    under ``memory.log_level`` >= 1.
    """

    def __init__(self, budget_bytes: int, compress_spill: bool = False,
                 compress_level: int = 3,
                 spill_dir: "str | None" = None):
        """``compress_spill`` zstd-compresses spilled host buffers (the
        nvcomp general-codec role on the host path): logical HBM bytes
        stay the accounting unit; ``stats()['host_stored_bytes']``
        reports the actual compressed footprint.

        ``spill_dir`` (default: the ``memory.spill_dir`` option; "" =
        off) moves spilled payloads from host memory to files in that
        directory. Files are written crash-safe — tmp + ``os.replace``
        + fsync + read-back verify — and carry the integrity trailer
        when ``integrity.enabled``, so a torn write or bitrot on the
        spill device is a classified ``CorruptDataError`` at unspill,
        never silently wrong bytes staged back to HBM."""
        if budget_bytes <= 0:
            raise ValueError("budget must be positive")
        self.budget = int(budget_bytes)
        if spill_dir is None:
            spill_dir = str(get_option("memory.spill_dir")) or None
        self._spill_dir = spill_dir
        if self._spill_dir:
            os.makedirs(self._spill_dir, exist_ok=True)
            # stores may share a directory: namespace this store's files
            self._spill_prefix = f"spill-{os.getpid()}-{id(self):x}"
        else:
            self._spill_dir = None
            self._spill_prefix = ""
        self._lock = threading.Lock()
        self._next_id = 1
        # id -> dict(state="device"|"host", table|host_cols, nbytes, tick)
        self._entries: dict[int, dict] = {}
        self._tick = 0
        self.spill_count = 0
        self.unspill_count = 0
        # cumulative bytes moved across the PCIe-equivalent boundary
        self.spilled_bytes = 0
        self.unspilled_bytes = 0
        self._cctx = None
        self._dctx = None
        if compress_spill:
            # the shared availability guard (runtime/compress.py) — wire
            # and spill can never disagree on whether zstandard exists
            self._cctx, self._dctx = compress.zstd_codec(compress_level)

    def _device_bytes_locked(self) -> int:
        return sum(e["nbytes"] for e in self._entries.values()
                   if e["state"] == "device")

    @property
    def device_bytes(self) -> int:
        with self._lock:
            return self._device_bytes_locked()

    def _coldest_device_locked(self) -> "int | None":
        """Handle of the least-recently-used resident entry, or None."""
        candidates = [
            (e["tick"], eid) for eid, e in self._entries.items()
            if e["state"] == "device"
        ]
        if not candidates:
            return None
        _, eid = min(candidates)
        return eid

    def _spill_entry_locked(self, eid: int, reason: str) -> int:
        """Spill one resident entry to host; returns its device bytes."""
        e = self._entries[eid]
        # fire before mutating the entry: an injected spill-IO failure
        # must leave the victim resident and the store consistent
        faults.fire("spill.spill", eid, nbytes=e["nbytes"])
        seam = e.get("iseam", "integrity.spill")
        # compress -> seal ordering: the codec re-encode happens INSIDE
        # the snapshot (per buffer), before the crc / trailer is taken
        # over it, so verification always covers the compressed bytes
        codec_seam = seam if compress.seam_enabled(seam) else None
        with spans.child("spill", handle=eid, nbytes=e["nbytes"]):
            e["host_cols"] = [
                _col_to_host(c, self._cctx, codec_seam)
                for c in e["table"].columns]
            if self._spill_dir is not None:
                # disk tier: pickle the snapshot, seal it, write it
                # crash-safe (tmp + os.replace + read-back verify)
                payload = pickle.dumps(
                    e["host_cols"], protocol=pickle.HIGHEST_PROTOCOL)
                sealed = integrity.enabled()
                blob = integrity.seal(payload) if sealed else payload
                blob = faults.fire_corrupt(seam, eid, blob, nbytes=e["nbytes"])
                path = os.path.join(
                    self._spill_dir, f"{self._spill_prefix}-{eid}.bin")
                integrity.write_payload_file(path, blob)
                e["host_cols"] = None
                e["path"] = path
                e["sealed"] = sealed
                e["stored_bytes"] = len(blob)
            elif integrity.enabled():
                # in-memory tier: checksum the packed snapshot now so
                # unspill can prove the host copy never drifted
                e["crc"] = integrity.snaps_checksum(e["host_cols"])
                _inject_snap_corruption(e["host_cols"], seam, eid)
        e["table"] = None  # drop the device arrays -> XLA frees HBM
        e["state"] = "disk" if self._spill_dir is not None else "host"
        self.spill_count += 1
        self.spilled_bytes += e["nbytes"]
        telemetry.record_spill(
            "spill_store", reason,
            bytes_moved=e["nbytes"], direction="device_to_host")
        if get_option("memory.log_level") >= 1:
            _log.info("spill table %d (%d bytes) to host", eid,
                      e["nbytes"])
        return e["nbytes"]

    def _spill_lru_locked(self, need: int) -> None:
        """Spill least-recently-used device entries until ``need`` fits."""
        while self._device_bytes_locked() + need > self.budget:
            eid = self._coldest_device_locked()
            if eid is None:
                raise MemoryLimitExceeded(
                    f"table of {need} bytes exceeds the spill budget "
                    f"({self.budget}) even with everything spilled"
                )
            self._spill_entry_locked(
                eid, "device spill budget exceeded: LRU eviction to host")

    def spill_coldest(self, nbytes: int) -> int:
        """Proactively spill coldest-first resident entries until at least
        ``nbytes`` device bytes are freed (or nothing is left resident).

        The memory-pressure valve: a :class:`MemoryLimiter` crossing its
        high watermark calls this on its attached store so HBM held by
        idle inter-operator working sets drains before new admissions
        resume. Returns the bytes actually freed."""
        freed = 0
        with self._lock:
            while freed < nbytes:
                eid = self._coldest_device_locked()
                if eid is None:
                    break
                freed += self._spill_entry_locked(
                    eid, "memory pressure: proactive spill of coldest entry")
        return freed

    def spill(self, handle: int) -> int:
        """Demote ONE entry to the host/disk tier (no-op if already
        spilled). The result cache's shed path: evicting a cached result
        from HBM must keep the integrity-sealed host copy so a later hit
        can stage it back verified. Returns the device bytes freed."""
        with self._lock:
            e = self._entries.get(handle)
            if e is None:
                raise KeyError(f"unknown spill-store handle {handle}")
            if e["state"] != "device":
                return 0
            return self._spill_entry_locked(
                handle, "result cache shed: demote cached entry to host")

    def state(self, handle: int) -> str:
        """Residency tier of an entry ("device" | "host" | "disk") without
        touching its LRU tick — lets the result cache reconcile limiter
        charges after this store's own LRU spilled a cache entry."""
        with self._lock:
            e = self._entries.get(handle)
            if e is None:
                raise KeyError(f"unknown spill-store handle {handle}")
            return e["state"]

    def put(self, table, *, integrity_seam: str = "integrity.spill") -> int:
        """Register a device table; returns its handle. May spill others.

        ``integrity_seam`` tags which verification boundary this entry's
        payload belongs to (``integrity.spill`` for plain working sets,
        ``integrity.checkpoint`` for out-of-core partials) — it routes
        both the corruption-injection window and the mismatch
        classification, so a corrupt checkpoint is distinguishable from
        a corrupt spill in telemetry and recovery."""
        nbytes = _table_nbytes(table)
        with self._lock:
            self._spill_lru_locked(nbytes)
            self._tick += 1
            eid = self._next_id
            self._next_id += 1
            self._entries[eid] = {
                "state": "device", "table": table, "host_cols": None,
                "nbytes": nbytes, "tick": self._tick,
                "iseam": str(integrity_seam),
            }
            return eid

    def get(self, handle: int):
        """Fetch a table, staging it back to device if it was spilled."""
        from spark_rapids_jni_tpu.columnar import Table

        with self._lock:
            e = self._entries.get(handle)
            if e is None:
                raise KeyError(f"unknown spill-store handle {handle}")
            self._tick += 1
            e["tick"] = self._tick
            if e["state"] == "device":
                return e["table"]
            # fire before any staging: an injected unspill failure must
            # leave the entry spilled (host copy intact, retryable)
            faults.fire("spill.unspill", handle, nbytes=e["nbytes"])
            seam = e.get("iseam", "integrity.spill")
            with spans.child("unspill", handle=handle, nbytes=e["nbytes"]):
                # verify BEFORE any byte is decoded or staged: a corrupt
                # payload raises classified CorruptDataError with the
                # entry still spilled (file/host copy untouched, so the
                # owning seam can replay from source or die with a
                # flight record — never stage garbage to HBM)
                if e["state"] == "disk":
                    blob = integrity.read_payload_file(
                        e["path"], seam=seam, sealed=e["sealed"],
                        op="spill_store.get", handle=handle)
                    snaps = pickle.loads(blob)
                elif e.get("crc") is not None:
                    snaps = e["host_cols"]
                    integrity.verify_snaps(
                        snaps, e["crc"], seam=seam,
                        op="spill_store.get", handle=handle)
                else:
                    snaps = e["host_cols"]
                self._spill_lru_locked(e["nbytes"])
                cols = [
                    _col_from_host(snap, self._dctx, seam)
                    for snap in snaps]
            e["table"] = Table(cols)
            e["host_cols"] = None
            e["crc"] = None
            if e["state"] == "disk":
                _unlink_quiet(e.pop("path"))
                e.pop("stored_bytes", None)
            e["state"] = "device"
            self.unspill_count += 1
            self.unspilled_bytes += e["nbytes"]
            telemetry.record_spill(
                "spill_store",
                "spilled table touched: staging back to device",
                bytes_moved=e["nbytes"], direction="host_to_device")
            if get_option("memory.log_level") >= 1:
                _log.info("unspill table %d (%d bytes)", handle, e["nbytes"])
            return e["table"]

    def get_reserved(self, handle: int, limiter: MemoryLimiter):
        """Fetch a table with its device bytes reserved against
        ``limiter`` BEFORE the host->device copy runs.

        Ordering contract: a spilled entry that would not fit the budget
        must raise ``MemoryLimitExceeded`` before ANY device staging
        happens — reserving after ``get`` would let the unspill allocate
        first and account later, exactly the over-commit window the
        limiter exists to close (and the window a prefetching pipeline
        widens, since unspills race concurrent chunk admissions there).
        Returns ``(table, nbytes)``; on success the CALLER owns the
        reservation. On any failure — including the reserve itself —
        no reservation is left behind.
        """
        nb = self.nbytes(handle)
        limiter.reserve(nb)
        try:
            return self.get(handle), nb
        except BaseException:
            limiter.release(nb)
            raise

    def nbytes(self, handle: int) -> int:
        """Logical (device) size of a stored table WITHOUT staging it —
        lets callers reserve budget before a ``get`` faults bytes in."""
        with self._lock:
            if handle not in self._entries:
                raise KeyError(f"unknown spill handle {handle}")
            return self._entries[handle]["nbytes"]

    def stored_nbytes(self, handle: int) -> int:
        """RESIDENT footprint of one entry in its current tier: logical
        HBM bytes while device-resident, the (possibly codec-compressed)
        packed snapshot bytes on the host tier, the sealed file size on
        the disk tier. The result cache's LRU charges this — compressed
        entries make the same ``cache.max_bytes`` hold more results."""
        with self._lock:
            e = self._entries.get(handle)
            if e is None:
                raise KeyError(f"unknown spill handle {handle}")
            if e["state"] == "device":
                return e["nbytes"]
            if e["state"] == "disk":
                return int(e.get("stored_bytes", 0))
            return sum(_host_snap_nbytes(s) for s in e["host_cols"])

    def drop(self, handle: int) -> None:
        with self._lock:
            e = self._entries.pop(handle, None)
            if e is not None and e["state"] == "disk":
                _unlink_quiet(e.get("path"))

    def close(self) -> None:
        """Release every entry and unlink this store's spill files."""
        with self._lock:
            for e in self._entries.values():
                if e["state"] == "disk":
                    _unlink_quiet(e.get("path"))
            self._entries.clear()

    def stats(self) -> dict:
        with self._lock:
            device = self._device_bytes_locked()
            host = sum(e["nbytes"] for e in self._entries.values()
                       if e["state"] == "host")
            stored = sum(
                sum(_host_snap_nbytes(s) for s in e["host_cols"])
                for e in self._entries.values() if e["state"] == "host"
            )
            disk = sum(e["nbytes"] for e in self._entries.values()
                       if e["state"] == "disk")
            disk_stored = sum(
                e.get("stored_bytes", 0)
                for e in self._entries.values() if e["state"] == "disk")
            return {
                "device_bytes": device, "host_bytes": host,
                "host_stored_bytes": stored,  # compressed footprint
                "disk_bytes": disk,  # logical HBM bytes parked on disk
                "disk_stored_bytes": disk_stored,  # file footprint
                "spill_dir": self._spill_dir or "",
                "budget_bytes": self.budget,
                "spills": self.spill_count, "unspills": self.unspill_count,
                "spilled_bytes": self.spilled_bytes,
                "unspilled_bytes": self.unspilled_bytes,
                "tables": len(self._entries),
            }
