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

from spark_rapids_jni_tpu.runtime import fusion, resilience
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


def _hash_buffer(h, buf) -> None:
    if buf is None:
        h.update(b"\xff")
        return
    if isinstance(buf, tuple):  # packed ("zstd", dtype_str, shape, blob)
        h.update(buf[1].encode())
        h.update(repr(buf[2]).encode())
        h.update(buf[3])
        return
    # the two halves of a fresh batch's fingerprint, timed apart: bringing
    # the buffer to the host, and hashing it there
    with spans.child("cache.fingerprint.copy") as sp:
        arr = np.ascontiguousarray(np.asarray(buf))
        nbytes = int(arr.nbytes)
        sp.annotate(nbytes=nbytes)
    with spans.child("cache.fingerprint.hash", nbytes=nbytes):
        h.update(str(arr.dtype).encode())
        h.update(repr(arr.shape).encode())
        h.update(arr.tobytes())
    REGISTRY.counter("cache.fingerprint_bytes").inc(nbytes)


def _hash_column(h, col) -> None:
    h.update(repr(col.dtype).encode())
    _hash_buffer(h, col.data)
    _hash_buffer(h, col.validity)
    _hash_buffer(h, col.chars)
    for child in (col.children or ()):
        _hash_column(h, child)


def _hash_snap(h, snap) -> None:
    dtype, data, validity, chars, children = snap
    h.update(repr(dtype).encode())
    _hash_buffer(h, data)
    _hash_buffer(h, validity)
    _hash_buffer(h, chars)
    for ch in (children or ()):
        _hash_snap(h, ch)


def table_fingerprint(table) -> str:
    """Content digest of a device Table: every column's data/validity/
    chars buffers plus dtype and shape, recursively. Memoized on the
    Table object (JAX arrays are immutable, so a table's content never
    drifts under its fingerprint) — repeat submissions of the same bound
    table hash once."""
    cached = getattr(table, "_resultcache_fp", None)
    if cached is not None:
        return cached
    h = hashlib.sha256()
    for col in table.columns:
        _hash_column(h, col)
    fp = h.hexdigest()
    try:
        table._resultcache_fp = fp
    except (AttributeError, TypeError):
        pass  # slotted/frozen table: recompute next time
    return fp


def _chunk_fingerprint(chunk: HostTableChunk) -> str:
    h = hashlib.sha256()
    for snap in chunk.cols:
        _hash_snap(h, snap)
    return h.hexdigest()


def source_fingerprint(path: str) -> str:
    """Cheap file-backed-scan fingerprint: path + size + mtime digest —
    the invalidation handle for bindings too large to content-hash on
    every submit (pass it as ``submit(..., cache_fingerprint=...)``).
    Any rewrite of the source file changes it."""
    st = os.stat(path)
    token = f"{os.path.abspath(path)}\0{st.st_size}\0{st.st_mtime_ns}"
    return hashlib.sha256(token.encode()).hexdigest()


def input_fingerprint(bindings: dict) -> str:
    """Content digest over every bound input, name-keyed and
    order-independent. Device tables hash their buffers (memoized);
    host-decoded chunks hash their snapshots. Raises ``TypeError`` for
    bindings that are neither."""
    h = hashlib.sha256()
    for name in sorted(bindings):
        value = bindings[name]
        h.update(str(name).encode())
        h.update(b"\0")
        if isinstance(value, HostTableChunk):
            h.update(_chunk_fingerprint(value).encode())
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
    out = {}
    for k, v in (meta or {}).items():
        if hasattr(v, "dtype") and hasattr(v, "shape"):
            out[k] = np.asarray(v)
        else:
            out[k] = v
    return out


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

    def put(self, key: CacheKey, result: fusion.FusedResult) -> bool:
        """Memoize one result. The entry shares the result's device
        buffers (zero copy) and is charged against the limiter while
        resident; when the charge cannot fit it is demoted to the
        integrity-sealed host tier immediately instead of starving live
        queries. Returns True when the entry was stored."""
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
