"""Structured JSONL event log: dispatches, fallbacks, cache hits, staleness.

Every record answers the question the round-5 bench could not: *where did
this op actually run, and why?* The event kinds:

- ``dispatch``      — an op ran on its intended engine (``engine`` says which);
                      carries ``wall_ms`` when timed via ``trace_range(record=)``.
- ``fallback``      — a device path handed the row set to the host engine.
                      ``reason`` is mandatory and must be non-empty: a fallback
                      without a reason is unaccountable and raises ValueError
                      at the call site (enforced even when telemetry is off, so
                      the bug surfaces in tests, not production).
- ``compile_cache`` — hit/miss on a pattern-compile cache (regex DFA / linear).
- ``spill``         — device→host spill under memory pressure; carries
                      ``bytes_moved``.
- ``span``          — one closed node of a query's causal span tree
                      (telemetry/spans.py): id/parent/root, monotonic t0/t1,
                      status (ok/degraded/cancelled/failed).
- ``gc``            — one collection of the host's garbage collector that was
                      of generation 2 or paused the process for over 1 ms
                      (telemetry/gcwatch.py): monotonic t0/t1, ``generation``,
                      ``collected``.

Each record is stamped with ``ts`` (epoch seconds), ``platform`` (jax backend
if jax is already imported — telemetry itself never imports jax, keeping the
zero-dep/no-backend-init contract of tests/test_import_hygiene.py), and the
caller-supplied ``op`` / ``rows`` / ``dtype_widths``.

Sink: when ``telemetry.path`` is set, records append to that JSONL file (one
json object per line, crash-tolerant — a torn final line is skipped by the
reader). Always, the last 65,536 records are kept in an in-process ring for the
bench summary, the benchmark's span readers and tests (a served request
writes some 40; a 51 s window of a few hundred requests has to fit whole).
Emission never raises on I/O failure; dropped writes are counted in
``telemetry.dropped_writes``.
"""

from __future__ import annotations

import collections
import json
import os
import sys
import threading
import time
from typing import Any, Deque, Dict, Iterable, List, Optional, Sequence

from spark_rapids_jni_tpu.telemetry.registry import REGISTRY
from spark_rapids_jni_tpu.utils.config import get_option

__all__ = [
    "enabled",
    "record_dispatch",
    "record_fallback",
    "record_compile_cache",
    "record_spill",
    "record_resilience",
    "record_server",
    "record_degrade",
    "record_integrity",
    "record_cache",
    "record_fleet",
    "session_scope",
    "current_session",
    "events",
    "drain",
    "summary",
]

_RING_MAX = 65536
_ring: Deque[Dict[str, Any]] = collections.deque(maxlen=_RING_MAX)
_ring_lock = threading.Lock()

# Ambient session attribution (runtime/server.py): while a served query
# executes inside session_scope(sid), every record emitted on that thread —
# including fallbacks/spills/resilience events from layers that know nothing
# about sessions — is stamped with ``session``.
_session_ctx = threading.local()


class session_scope:
    """Attribute every telemetry record emitted on this thread to a session.

    Re-entrant in the shadowing sense: nesting restores the outer session
    on exit. Explicit ``session=`` kwargs on record_* calls win over the
    ambient scope (``_emit`` uses ``setdefault``).
    """

    def __init__(self, session_id: str):
        if not session_id or not str(session_id).strip():
            raise ValueError("session_scope: session_id must be non-empty")
        self._sid = str(session_id)
        self._outer: Optional[str] = None

    def __enter__(self) -> "session_scope":
        self._outer = getattr(_session_ctx, "sid", None)
        _session_ctx.sid = self._sid
        return self

    def __exit__(self, *exc) -> bool:
        _session_ctx.sid = self._outer
        return False


def current_session() -> Optional[str]:
    """The session id attributed to this thread, or None outside a scope."""
    return getattr(_session_ctx, "sid", None)


def enabled() -> bool:
    """True when the ``telemetry.enabled`` option is on."""
    return bool(get_option("telemetry.enabled"))


_backend: Optional[str] = None   # jax's backend, once it has told it


def _platform() -> str:
    # Never import jax from here: telemetry is zero-dep and must not trigger
    # backend init (test_import_hygiene.py). If the workload already imported
    # jax, report its backend; otherwise "none". The backend of a process
    # does not change: asked once, not once a record.
    global _backend
    if _backend is not None:
        return _backend
    jax = sys.modules.get("jax")
    if jax is None:
        return "none"
    try:
        _backend = str(jax.default_backend())
    except Exception:
        return "unknown"
    return _backend


def _replica() -> str:
    """The replica identity this process stamps onto every record/span
    (fleet workers get it via SPARK_RAPIDS_TPU_TELEMETRY_REPLICA in
    their environment); "" = unstamped single-process operation."""
    return str(get_option("telemetry.replica") or "")


def _host() -> str:
    """The mesh host identity stamped next to the replica stamp
    (cluster workers get it via SPARK_RAPIDS_TPU_TELEMETRY_HOST in
    their environment); "" = unstamped single-host operation."""
    return str(get_option("telemetry.host") or "")


def _emit(rec: Dict[str, Any]) -> Dict[str, Any]:
    rec.setdefault("ts", time.time())
    rec.setdefault("platform", _platform())
    sid = current_session()
    if sid is not None:
        rec.setdefault("session", sid)
    rid = _replica()
    if rid:
        rec.setdefault("replica", rid)
    hid = _host()
    if hid:
        rec.setdefault("host", hid)
    with _ring_lock:
        _ring.append(rec)
    REGISTRY.counter("events_total").inc()
    path = get_option("telemetry.path")
    if path:
        # N fleet replicas share one JSONL path: each record must land as
        # ONE O_APPEND os.write so a reader (report/trace) can never see
        # two processes' lines torn into each other. Buffered file-object
        # writes flush in arbitrary chunks; a single write(2) of a line
        # that fits a pipe/page is atomic on POSIX.
        line = (json.dumps(rec, sort_keys=True) + "\n").encode("utf-8")
        try:
            fd = os.open(path, os.O_WRONLY | os.O_APPEND | os.O_CREAT, 0o644)
            try:
                os.write(fd, line)
            finally:
                os.close(fd)
        except OSError:
            # telemetry must never take the workload down with it
            REGISTRY.counter("dropped_writes").inc()
    return rec


def _base(
    kind: str,
    op: str,
    rows: Optional[int],
    dtype_widths: Optional[Sequence[int]],
    extra: Dict[str, Any],
) -> Dict[str, Any]:
    rec: Dict[str, Any] = {"kind": kind, "op": op}
    if rows is not None:
        rec["rows"] = int(rows)
    if dtype_widths is not None:
        rec["dtype_widths"] = [int(w) for w in dtype_widths]
    rec.update(extra)
    return rec


def record_dispatch(
    op: str,
    *,
    engine: str = "device",
    rows: Optional[int] = None,
    dtype_widths: Optional[Sequence[int]] = None,
    wall_ms: Optional[float] = None,
    **extra: Any,
) -> bool:
    """An op executed on ``engine``; optionally timed. Returns True if recorded."""
    if not enabled():
        return False
    rec = _base("dispatch", op, rows, dtype_widths, extra)
    rec["engine"] = engine
    if wall_ms is not None:
        rec["wall_ms"] = float(wall_ms)
        REGISTRY.histogram(f"wall_ms.{op}").observe(float(wall_ms))
    REGISTRY.counter(f"dispatch.{op}").inc()
    _emit(rec)
    return True


def record_fallback(
    op: str,
    reason: str,
    *,
    rows: Optional[int] = None,
    dtype_widths: Optional[Sequence[int]] = None,
    **extra: Any,
) -> bool:
    """A device path handed execution to the host engine, because ``reason``."""
    if not reason or not str(reason).strip():
        # validated even when disabled: an unaccountable fallback is a bug
        raise ValueError(f"record_fallback({op!r}): reason must be non-empty")
    if not enabled():
        return False
    rec = _base("fallback", op, rows, dtype_widths, extra)
    rec["reason"] = str(reason)
    rec["engine"] = "host"
    REGISTRY.counter(f"fallback.{op}").inc()
    REGISTRY.counter("fallbacks_total").inc()
    _emit(rec)
    return True


def record_compile_cache(op: str, *, hit: bool, **extra: Any) -> bool:
    """A pattern-compile cache was consulted (regex DFA / linear-capture)."""
    if not enabled():
        return False
    rec = _base("compile_cache", op, None, None, extra)
    rec["hit"] = bool(hit)
    REGISTRY.counter("compile_cache.hit" if hit else "compile_cache.miss").inc()
    _emit(rec)
    return True


def record_spill(
    op: str,
    reason: str,
    *,
    bytes_moved: int = 0,
    rows: Optional[int] = None,
    **extra: Any,
) -> bool:
    """Device→host spill under memory pressure; ``reason`` mandatory."""
    if not reason or not str(reason).strip():
        raise ValueError(f"record_spill({op!r}): reason must be non-empty")
    if not enabled():
        return False
    rec = _base("spill", op, rows, None, extra)
    rec["reason"] = str(reason)
    rec["bytes_moved"] = int(bytes_moved)
    REGISTRY.counter(f"spill.{op}").inc()
    REGISTRY.counter("spill_bytes_total").inc(max(0, int(bytes_moved)))
    _emit(rec)
    return True


def record_resilience(
    op: str,
    event: str,
    *,
    seam: str,
    attempt: int,
    rung: str,
    rows: Optional[int] = None,
    **extra: Any,
) -> bool:
    """A resilience-policy decision: retry, recovery, escalation, or fatal.

    ``event`` is one of ``retry`` / ``recovered`` / ``escalate`` / ``fatal``;
    ``seam`` names the instrumented boundary (runtime/faults.py registry);
    ``rung`` is the degradation-ladder rung taken (``same_capacity``,
    ``grow_capacity``, ``replay_chunk``, ``staged_fallback``, ...). Like
    fallback reasons, seam and rung are mandatory even when telemetry is off —
    an unaccountable recovery is a bug.
    """
    if not seam or not str(seam).strip():
        raise ValueError(f"record_resilience({op!r}): seam must be non-empty")
    if not rung or not str(rung).strip():
        raise ValueError(f"record_resilience({op!r}): rung must be non-empty")
    if "kind" in extra or "op" in extra:
        raise ValueError(
            f"record_resilience({op!r}): 'kind'/'op' are reserved record "
            "fields; pass the classified error as error_kind")
    if not enabled():
        return False
    rec = _base("resilience", op, rows, None, extra)
    rec["event"] = str(event)
    rec["seam"] = str(seam)
    rec["attempt"] = int(attempt)
    rec["rung"] = str(rung)
    REGISTRY.counter(f"resilience.{event}").inc()
    REGISTRY.counter(f"resilience.rung.{rung}").inc()
    _emit(rec)
    return True


def record_server(
    op: str,
    event: str,
    *,
    session: str,
    rows: Optional[int] = None,
    **extra: Any,
) -> bool:
    """A serving-runtime decision for one query of one session.

    ``event`` is one of ``submitted`` / ``queued`` / ``rejected`` /
    ``admitted`` / ``served`` / ``failed``; ``session`` is mandatory and
    must be non-empty even when telemetry is off — an unattributable
    serving event is a bug (tpulint rule 12 enforces the static half of
    this contract on the server path).
    """
    if not session or not str(session).strip():
        raise ValueError(f"record_server({op!r}): session must be non-empty")
    if not enabled():
        return False
    rec = _base("server", op, rows, None, extra)
    rec["event"] = str(event)
    rec["session"] = str(session)
    # no counter side effects here: the serving runtime owns the
    # ``server.*`` counters and counts unconditionally (admission
    # accounting must hold even with telemetry off, like the limiter's)
    _emit(rec)
    return True


def record_degrade(
    op: str,
    event: str,
    *,
    tier: str,
    trigger: str,
    rung: int,
    rows: Optional[int] = None,
    **extra: Any,
) -> bool:
    """A graceful-degradation decision for one query (runtime/degrade.py).

    ``event`` is one of ``step`` / ``completed`` / ``parked`` / ``resumed``
    / ``exhausted`` / ``pressure`` / ``cancelled`` / ``state_discarded``;
    ``tier`` names the execution tier the ladder is moving to (``fused``,
    ``staged``, ``outofcore``, ``parked``); ``trigger`` is what forced the
    move (the classified error kind, ``deadline``, ``watermark``); ``rung``
    is the 0-based ladder position. Tier and trigger are mandatory even when
    telemetry is off — an unaccountable degradation is a bug (same contract
    as fallback reasons).
    """
    if not tier or not str(tier).strip():
        raise ValueError(f"record_degrade({op!r}): tier must be non-empty")
    if not trigger or not str(trigger).strip():
        raise ValueError(f"record_degrade({op!r}): trigger must be non-empty")
    if not enabled():
        return False
    rec = _base("degrade", op, rows, None, extra)
    rec["event"] = str(event)
    rec["tier"] = str(tier)
    rec["trigger"] = str(trigger)
    rec["rung"] = int(rung)
    REGISTRY.counter(f"degrade.{event}").inc()
    REGISTRY.counter(f"degrade.tier.{tier}").inc()
    _emit(rec)
    return True


def record_integrity(
    op: str,
    event: str,
    *,
    seam: str,
    nbytes: Optional[int] = None,
    **extra: Any,
) -> bool:
    """An integrity-layer event (runtime/integrity.py and its call sites).

    ``event`` is one of ``mismatch`` (a checksum trailer failed
    verification) / ``refetch`` (a corrupt wire frame was NAK'd for
    resend) / ``recovered`` (a refetch or checkpoint replay produced good
    bytes) / ``replay`` (a corrupt checkpoint partial was discarded and
    its chunk recomputed) / ``malformed`` (untrusted input rejected at
    ingestion). ``seam`` names the verification boundary
    (``integrity.spill`` / ``integrity.wire`` / ``integrity.checkpoint``
    / ``integrity.ingest``) and is mandatory even when telemetry is off —
    an unattributable corruption event is a bug, same contract as
    fallback reasons and resilience seams.
    """
    if not seam or not str(seam).strip():
        raise ValueError(f"record_integrity({op!r}): seam must be non-empty")
    if "kind" in extra or "op" in extra:
        raise ValueError(
            f"record_integrity({op!r}): 'kind'/'op' are reserved record "
            "fields; pass caller context under other names")
    if not enabled():
        return False
    rec = _base("integrity", op, None, None, extra)
    rec["event"] = str(event)
    rec["seam"] = str(seam)
    if nbytes is not None:
        rec["nbytes"] = int(nbytes)
    # no counter side effects here: integrity.verify owns the
    # ``integrity.*`` counters and counts unconditionally (verification
    # accounting must hold even with telemetry off, like the limiter's)
    _emit(rec)
    return True


def record_rtfilter(
    op: str,
    event: str,
    *,
    reason: str,
    **extra: Any,
) -> bool:
    """A runtime-filter planner decision or observation
    (runtime/rtfilter.py).

    ``event`` is one of ``apply`` / ``skip`` / ``observed`` /
    ``state_discarded`` / ``prune``; ``reason`` says WHY (``selective``,
    ``no_history_optimistic``, ``learned_nonselective``,
    ``build_too_large``, ``disabled``, ``corrupt``, ...) and is
    mandatory even when telemetry is off — an unexplained filter
    decision is a bug (tpulint rule 24 enforces the static half of this
    contract on the rtfilter path)."""
    if not reason or not str(reason).strip():
        raise ValueError(f"record_rtfilter({op!r}): reason must be non-empty")
    if not enabled():
        return False
    rec = _base("rtfilter", op, None, None, extra)
    rec["event"] = str(event)
    rec["reason"] = str(reason)
    # no counter side effects: rtfilter owns its ``rtfilter.*`` counters
    # and counts unconditionally (decision accounting must hold whether
    # or not anyone is watching, like the server's admission counters)
    _emit(rec)
    return True


def record_cache(
    op: str,
    event: str,
    *,
    key: str,
    nbytes: Optional[int] = None,
    **extra: Any,
) -> bool:
    """A result/subplan-cache decision (runtime/resultcache.py).

    ``event`` is one of ``hit`` / ``miss`` / ``put`` / ``evict`` /
    ``shed`` / ``corrupt_discard`` / ``subplan_hit`` /
    ``subplan_materialize``. ``key`` is the entry's short composite key
    (``<signature12>@<fingerprint12>``) and is mandatory even when
    telemetry is off — a cache record without the fingerprinted key is
    unattributable to an entry, the same contract tpulint rule 16
    enforces statically on cache call sites.
    """
    if not key or not str(key).strip():
        raise ValueError(f"record_cache({op!r}): key must be non-empty")
    if not enabled():
        return False
    rec = _base("cache", op, None, None, extra)
    rec["event"] = str(event)
    rec["key"] = str(key)
    if nbytes is not None:
        rec["nbytes"] = int(nbytes)
    # no counter side effects here: the result cache owns the ``cache.*``
    # counters and counts unconditionally (hit/miss accounting must hold
    # even with telemetry off, like the server's admission counters)
    _emit(rec)
    return True


def record_fleet(
    op: str,
    event: str,
    *,
    replica: str,
    **extra: Any,
) -> bool:
    """A serving-fleet supervision event (runtime/fleet.py).

    ``event`` is one of ``boot`` / ``live`` / ``dispatch`` / ``served`` /
    ``replica_death`` / ``failover`` / ``duplicate_drop`` / ``memo_hit``
    / ``restart`` / ``quarantine`` / ``drain`` / ``identity_mismatch``.
    ``replica`` names the replica the event is about and is mandatory
    even when telemetry is off — an unattributable fleet event is a bug,
    the same contract record_server enforces for sessions (tpulint rule
    18 enforces the classification half on worker-exit reaping sites).
    """
    if not replica or not str(replica).strip():
        raise ValueError(f"record_fleet({op!r}): replica must be non-empty")
    if "kind" in extra or "op" in extra:
        raise ValueError(
            f"record_fleet({op!r}): 'kind'/'op' are reserved record "
            "fields; pass caller context under other names")
    if not enabled():
        return False
    rec = _base("fleet", op, None, None, extra)
    rec["event"] = str(event)
    rec["replica"] = str(replica)
    # no counter side effects here: the fleet supervisor owns the
    # ``fleet.*`` counters and counts unconditionally (supervision
    # accounting must hold even with telemetry off, like admission's)
    _emit(rec)
    return True


def record_exchange(
    op: str,
    event: str,
    *,
    rows: Optional[int] = None,
    **extra: Any,
) -> bool:
    """A distributed-exchange lifecycle event (runtime/exchange.py).

    ``event`` is one of ``pack`` / ``flight`` / ``overflow_escalate`` /
    ``chunked_flights`` / ``spill_demote`` / ``merge`` / ``recovered``.
    ``rows`` is the row count the event is about (routed rows for
    ``pack``, flight rows for ``flight``, ...). Byte/flight context
    rides in ``extra`` (``wire_bytes`` / ``raw_bytes`` / ``flights`` /
    ``capacity`` / ``partition``). Like record_fleet, no counter side
    effects: runtime/exchange.py owns the ``exchange.*`` counters and
    counts unconditionally (transport accounting must hold even with
    telemetry off).
    """
    if not event or not str(event).strip():
        raise ValueError(f"record_exchange({op!r}): event must be non-empty")
    if "kind" in extra or "op" in extra:
        raise ValueError(
            f"record_exchange({op!r}): 'kind'/'op' are reserved record "
            "fields; pass caller context under other names")
    if not enabled():
        return False
    rec = _base("exchange", op, rows, None, extra)
    rec["event"] = str(event)
    _emit(rec)
    return True


def events(n: Optional[int] = None) -> List[Dict[str, Any]]:
    """The last ``n`` (default: all buffered) records, oldest first."""
    with _ring_lock:
        buf = list(_ring)
    return buf if n is None else buf[-n:]


def drain() -> List[Dict[str, Any]]:
    """Return and clear the in-process ring (test isolation)."""
    with _ring_lock:
        buf = list(_ring)
        _ring.clear()
    return buf


def summary(records: Optional[Iterable[Dict[str, Any]]] = None) -> Dict[str, Any]:
    """Aggregate counts for the bench telemetry block.

    With no argument, summarizes the in-process ring; pass parsed JSONL
    records to summarize a file written by another process (bench children).
    """
    recs = list(records) if records is not None else events()
    # the columnar codec (runtime/compress.py) is counter-based — its
    # hot path never emits per-array event records — so its section is
    # derived from the in-process REGISTRY and only meaningful for the
    # no-argument (same-process) view; summarizing another process's
    # JSONL keeps the key with an empty dict
    compress: Dict[str, Any] = {}
    if records is None:
        comp = REGISTRY.counters("compress.")
        if comp:
            bytes_in = comp.get("compress.bytes_in", 0)
            bytes_out = comp.get("compress.bytes_out", 0)
            compress = {
                "bytes_in": bytes_in,
                "bytes_out": bytes_out,
                "ratio": round(bytes_in / bytes_out, 3)
                if bytes_out else None,
                "encode_us": comp.get("compress.encode_us", 0),
                "decode_us": comp.get("compress.decode_us", 0),
                "bytes_decoded": comp.get("compress.bytes_decoded", 0),
                "mismatches": comp.get("compress.mismatch", 0),
                "schemes": {
                    k.split(".", 2)[2]: v for k, v in sorted(comp.items())
                    if k.startswith("compress.scheme.")
                },
                "seams": {
                    seam: {
                        "bytes_in": comp.get(f"compress.{seam}.bytes_in", 0),
                        "bytes_out": comp.get(f"compress.{seam}.bytes_out", 0),
                    }
                    for seam in ("spill", "wire", "checkpoint", "cache")
                    if f"compress.{seam}.bytes_in" in comp
                },
            }
    fallbacks: Dict[str, int] = {}
    spills: Dict[str, int] = {}
    cache = {"hit": 0, "miss": 0}
    resilience: Dict[str, int] = {}
    server: Dict[str, int] = {}
    degrade: Dict[str, int] = {}
    degrade_tiers: Dict[str, int] = {}
    integrity: Dict[str, int] = {}
    integrity_seams: Dict[str, int] = {}
    result_cache: Dict[str, int] = {}
    fleet: Dict[str, int] = {}
    replicas: set = set()
    cluster: Dict[str, int] = {}
    hosts: set = set()
    per_host: Dict[str, int] = {}
    dispatches = 0
    spill_bytes = 0
    spans = 0
    span_status: Dict[str, int] = {}
    for r in recs:
        kind = r.get("kind")
        if r.get("replica"):
            replicas.add(str(r["replica"]))
        if r.get("host"):
            h = str(r["host"])
            hosts.add(h)
            per_host[h] = per_host.get(h, 0) + 1
        if kind == "span":
            spans += 1
            st = str(r.get("status", "?"))
            span_status[st] = span_status.get(st, 0) + 1
            continue
        if kind == "resilience":
            ev = str(r.get("event", "?"))
            resilience[ev] = resilience.get(ev, 0) + 1
        elif kind == "server":
            ev = str(r.get("event", "?"))
            server[ev] = server.get(ev, 0) + 1
        elif kind == "degrade":
            ev = str(r.get("event", "?"))
            degrade[ev] = degrade.get(ev, 0) + 1
            if ev == "step":
                tier = str(r.get("tier", "?"))
                degrade_tiers[tier] = degrade_tiers.get(tier, 0) + 1
        elif kind == "integrity":
            ev = str(r.get("event", "?"))
            integrity[ev] = integrity.get(ev, 0) + 1
            if ev == "mismatch":
                seam = str(r.get("seam", "?"))
                integrity_seams[seam] = integrity_seams.get(seam, 0) + 1
        elif kind == "cache":
            ev = str(r.get("event", "?"))
            result_cache[ev] = result_cache.get(ev, 0) + 1
        elif kind == "fleet":
            ev = str(r.get("event", "?"))
            fleet[ev] = fleet.get(ev, 0) + 1
            # the mesh supervisor emits its cross-host events through
            # record_fleet under cluster.* ops: aggregate them as their
            # own section so the cluster view needs no second pass
            if str(r.get("op", "")).startswith("cluster."):
                cluster[ev] = cluster.get(ev, 0) + 1
        elif kind == "fallback":
            op = str(r.get("op", "?"))
            fallbacks[op] = fallbacks.get(op, 0) + 1
        elif kind == "spill":
            op = str(r.get("op", "?"))
            spills[op] = spills.get(op, 0) + 1
            spill_bytes += int(r.get("bytes_moved", 0))
        elif kind == "compile_cache":
            cache["hit" if r.get("hit") else "miss"] += 1
        elif kind == "dispatch":
            dispatches += 1
    return {
        "events": len(recs),
        "dispatches": dispatches,
        "fallbacks": dict(sorted(fallbacks.items())),
        "fallbacks_total": sum(fallbacks.values()),
        "spills": dict(sorted(spills.items())),
        "spill_bytes_total": spill_bytes,
        "compile_cache": cache,
        "resilience": dict(sorted(resilience.items())),
        "server": dict(sorted(server.items())),
        "degrade": dict(sorted(degrade.items())),
        "degrade_tiers": dict(sorted(degrade_tiers.items())),
        "integrity": dict(sorted(integrity.items())),
        "integrity_seams": dict(sorted(integrity_seams.items())),
        "result_cache": dict(sorted(result_cache.items())),
        "fleet": dict(sorted(fleet.items())),
        "replicas": sorted(replicas),
        "cluster": dict(sorted(cluster.items())),
        "hosts": sorted(hosts),
        "per_host": dict(sorted(per_host.items())),
        "compress": compress,
        "spans": spans,
        "span_status": dict(sorted(span_status.items())),
    }
