"""Multi-query serving runtime: admission, fair scheduling, session budgets.

Every layer below this one executes exactly one query at a time; the
ROADMAP north star is heavy concurrent traffic on one shared device.
Sparkle (PAPERS.md) shows Spark-shaped work on a single shared machine is
won or lost at the admission/queueing layer; Flare shows that once kernels
are fused the marginal cost of a query is dominated by plan reuse — which
is exactly what the bucketed executable cache already gives concurrent
queries at ragged row counts. This module is the layer that cashes that
in: N sessions submit fusion plans (``runtime/fusion.py`` IR) and share
the dispatch executable cache, one ``MemoryLimiter``, and the pipeline's
shared decode pool.

Contracts, in order of importance:

* **No overcommit** — every query's HBM estimate is reserved through the
  shared ``MemoryLimiter`` BEFORE execution starts. A query whose
  estimate exceeds the whole budget, or whose session queue is full, is
  rejected at submit; one that merely does not fit *right now* waits its
  turn (the limiter's FIFO blocking reserve), bounded by
  ``server.admission_timeout_s``.
* **Fairness** — queued work is drained round-robin across sessions with
  at most ``server.max_inflight`` queries executing concurrently, so one
  heavy session cannot starve the rest: each scheduling turn takes the
  next session's oldest query, not the globally oldest.
* **Attribution** — end-to-end latency and queue wait land in per-session
  histograms (``server.latency_ms.<sid>`` / ``server.queue_wait_ms.<sid>``),
  admitted/queued/rejected/served/failed counters count per session and
  globally, and the whole execution runs inside
  ``telemetry.session_scope(sid)`` so fallback/spill/resilience events
  emitted by ANY inner layer carry ``session`` attribution. Every ticket
  has a ``request`` id and three span trees joined by it
  (``telemetry/spans.py``): ``submit.<plan>`` on the client's thread
  (``cache.fingerprint`` with its per-buffer ``.copy`` / ``.hash``,
  ``cache.lookup``, ``admission.enqueue``), ``query.<plan>`` on the
  worker's (``admission.queue`` from the enqueue to the pickup,
  ``admission.wait``, ``server.stage_bindings``, the degrade rungs and
  regions, ``server.record_actual``, ``cache.put``, ``ticket.resolve``)
  and ``query.result.<plan>`` on the thread that first calls
  ``ticket.result()`` (``ticket.wait`` up to the worker's resolve,
  ``ticket.wake`` from there to the return), so nothing between
  ``submit`` and the client's return runs outside a named span.
  ``QueryTicket.queue_wait_s`` is the deadline's clock and starts at
  submit, fingerprint included; the true wait is the spans';
  ``QueryTicket.wake_s`` is the wake-up.
* **A request that ran long keeps its trees** — with telemetry on the
  server holds, per plan signature, the latencies of the last 64 served
  requests (submit to the return of ``result()``; to the resolve for a
  ticket nobody awaits). One that took more than twice their median and
  at least 0.010 s over it, with 16 known, is *slow*: counter
  ``server.slow_requests``, one flight record ``slow`` (its three trees,
  the ``gc`` records that overlap it, the server's state; kept apart
  from the ring of completed trees) and one warning line naming the
  largest self times. The host's collector pauses are on record meanwhile
  (``telemetry/gcwatch.py``: ``host.gc_pauses``, ``host.gc_pause_ns``).
* **A table may arrive as a file** — a scan bound to a
  ``parquet.split.ParquetSplit`` (one Spark scan task: path, byte range,
  read schema by name) is served like a bound ``Table``; the binding's type
  is the only signal. ``submit`` reads the footer (``scan.footer``) and
  binds what it resolves to, so the cache key (the source's: path, size,
  mtime, projection, range; no content digest), the plan signature and the
  estimate (the decoded bytes the footer states) exist before a page is
  read; an oversize split is rejected like any oversize request, a footer
  that cannot be read fails the ticket classified. After admission
  ``_stage_bindings`` decodes the row groups on the shared pool and writes
  each into the device table as it is ready (``scan``); a group that fails
  to decode fails that request alone, reservation released.
* **No leaks** — a query that dies, however it dies, releases its
  reservation and its in-flight slot; the failure is classified through
  ``resilience.classify`` and recorded before the ticket resolves.
* **Bend, don't break** — classified pressure failures
  (``ResourceExhausted`` / ``CapacityOverflow`` beyond the retry budget)
  step the query down the bit-identical execution-tier ladder
  (``runtime/degrade.py``: fused -> staged -> out-of-core -> park) instead
  of killing it; the limiter's high watermark proactively spills the
  server's coldest SpillStore entries and pauses NEW admissions (in-flight
  queries keep draining) until usage falls below the low watermark.
* **Deadlines are cooperative** — ``server.deadline_ms`` (or a per-submit
  ``deadline_ms``) arms a ``CancelToken`` checked at region/chunk
  boundaries and inside the pipeline decode pool; expiry (or an explicit
  ``ticket.cancel()``) resolves the ticket ``cancelled`` with the
  classified ``QueryCancelled``, releasing reservation and queue slot in
  the same ``finally`` as every other exit.
* **Admission learns** — after each served query the measured working set
  (input + result device bytes) is blended (EMA, ``server.estimate_alpha``)
  into a per-plan-signature estimate that replaces the static
  ``fusion.estimate_hbm_bytes`` base for future submits, persisted
  crash-safely beside the compile cache (``server.estimate_path``, else
  ``utils/config.cache_dir()``), so a fresh process admits from measured
  truth. Persistence is debounced off the hot path (at most one write
  per ``server.estimate_save_interval_s``; ``close()`` flushes).
* **The budget is one chip's** — every estimate, learned or static, is the
  bytes the request needs on ONE chip (``memory.table_chip_nbytes``): a
  binding row-sharded over a mesh costs each chip its shard, so a server
  in front of four chips is given one chip's ``bytes_limit``, admits a
  sharded table whose shard fits it, and rejects as oversize one whose
  shard does not. A request on one device reckons as it always has.

Config knobs (utils/config.py, env ``SPARK_RAPIDS_TPU_SERVER_*``):
``server.max_inflight``, ``server.hbm_budget_bytes``,
``server.admission_timeout_s``, ``server.queue_depth``,
``server.estimate_headroom``, ``server.deadline_ms``,
``server.estimate_alpha``, ``server.estimate_path``,
``server.estimate_save_interval_s``; the ladder's own knobs are
``degrade.*`` (utils/config.py).
"""

from __future__ import annotations

import collections
import itertools
import os
import statistics
import threading

try:  # POSIX advisory locks for the shared learned-estimate file
    import fcntl
except ImportError:  # non-POSIX: merge-on-load still runs, unlocked
    fcntl = None  # type: ignore[assignment]
import time
import weakref
from typing import Any, Callable, Optional

from spark_rapids_jni_tpu.parquet.split import ParquetScan, ParquetSplit
from spark_rapids_jni_tpu.runtime import (
    degrade,
    faults,
    fusion,
    pipeline,
    resilience,
    resultcache,
)
from spark_rapids_jni_tpu.runtime.memory import (
    HostTableChunk,
    MemoryLimiter,
    SpillStore,
    table_chip_nbytes,
)
from spark_rapids_jni_tpu.telemetry.events import (
    enabled as _telemetry_enabled,
    events as _ring_events,
    record_degrade,
    record_integrity,
    record_server,
    session_scope,
)
from spark_rapids_jni_tpu.utils.atomic_io import atomic_write_json, load_json
from spark_rapids_jni_tpu.telemetry import gcwatch, spans
from spark_rapids_jni_tpu.telemetry.registry import REGISTRY
from spark_rapids_jni_tpu.utils.config import cache_dir, get_option
from spark_rapids_jni_tpu.utils.log import get_logger

__all__ = ["QueryRejected", "QueryTicket", "Session", "QueryServer",
           "live_servers", "register_warmup_builder", "warmup_builders"]

_log = get_logger("spark_rapids_jni_tpu.server")

# Open servers in this process, for live introspection: ``python -m
# spark_rapids_jni_tpu.telemetry top`` renders inspect() of each. Weak so
# the registry never keeps a dropped server (and its limiter) alive.
_LIVE_SERVERS: "weakref.WeakSet[QueryServer]" = weakref.WeakSet()


# bindings that reach the device only after admission (``_stage_bindings``):
# ``nbytes`` is the device footprint the reservation has to cover
_HOST_SIDE = (HostTableChunk, ParquetScan)

# one id per submitted request, process-wide: what joins the client
# thread's ``submit.<plan>`` span tree to the worker's ``query.<plan>``
_REQUEST_IDS = itertools.count(1)

# a served request is *slow* when, with _SLOW_MIN_KNOWN latencies of its plan
# signature known (of the last _SLOW_KNOWN served), it took more than twice
# their median and at least _SLOW_OVER_S over it
_SLOW_KNOWN = 64
_SLOW_MIN_KNOWN = 16
_SLOW_OVER_S = 0.010

# who awaits and who judges a ticket, and every server's known latencies: held
# for a flag's flip or one median, never around anything that waits
_TICKET_LOCK = threading.Lock()


def live_servers() -> list:
    """The not-yet-closed QueryServers of this process."""
    return [s for s in list(_LIVE_SERVERS) if not s._closed]


# ---------------------------------------------------------------------------
# AOT warmup builders
# ---------------------------------------------------------------------------
#
# The learned-estimate file keys plans by SIGNATURE (``<plan>@<bucket>``)
# — exactly the granularity at which dispatch memoizes executables — so a
# fresh replica already knows which executables its predecessors spent
# the most HBM on. ``QueryServer.warmup`` replays the top-N signatures
# against synthetic inputs at the signature's bucket BEFORE the replica
# advertises boot_ok, converting first-query compile stalls into boot
# work. A builder takes the bucket row count and runs its plan end to end
# (filling the dispatch/fusion executable caches); models register
# builders for the plans they own (models/tpch.py).

_WARMUP_BUILDERS: dict = {}


def register_warmup_builder(plan_name: str, builder: Callable[[int], Any],
                            ) -> None:
    """Register the warmup entrypoint for one plan name. ``builder(rows)``
    must build synthetic bindings at ``rows`` input rows and execute the
    plan through its normal path; its return value is discarded."""
    if not plan_name or not str(plan_name).strip():
        raise ValueError("register_warmup_builder: plan_name is required")
    if not callable(builder):
        raise TypeError(f"warmup builder for {plan_name!r} is not callable")
    _WARMUP_BUILDERS[str(plan_name)] = builder


def warmup_builders() -> dict:
    """Snapshot of the registered warmup builders (name -> callable)."""
    return dict(_WARMUP_BUILDERS)


class QueryRejected(RuntimeError):
    """Admission control refused the query: estimate over the whole
    budget, session queue full, admission timeout, or server shutdown.

    Structured context rides on the exception so clients can react
    programmatically instead of parsing the message: ``session``,
    ``reason``, ``queue_depth`` (entries waiting in the session's queue
    at rejection), ``bytes_requested`` vs ``bytes_available`` (the
    limiter's free bytes at rejection), and ``retry_after_s`` — the
    server's backoff suggestion (``None`` means retrying can never
    succeed, e.g. an estimate larger than the whole budget).
    ``flight_record`` is the path of the flight-recorder artifact dumped
    at rejection (None when the recorder is disabled or the rejection
    happened before a span tree existed)."""

    def __init__(self, message: str, *,
                 session: str = "",
                 reason: str = "",
                 queue_depth: int = 0,
                 bytes_requested: int = 0,
                 bytes_available: int = 0,
                 retry_after_s: Optional[float] = None,
                 flight_record: Optional[str] = None):
        super().__init__(message)
        self.session = session
        self.reason = reason
        self.queue_depth = int(queue_depth)
        self.bytes_requested = int(bytes_requested)
        self.bytes_available = int(bytes_available)
        self.retry_after_s = retry_after_s
        self.flight_record = flight_record


class QueryTicket:
    """One submitted query's future. Resolves to the plan's
    ``FusedResult`` (``result()``), a raised ``QueryRejected``, the
    classified ``QueryCancelled`` (deadline expiry or ``cancel()``), or
    the classified execution error. ``status`` walks
    queued -> admitted -> served | rejected | cancelled | failed."""

    def __init__(self, session_id: str, plan: fusion.Plan, bindings: dict,
                 estimate: int, donate_inputs: bool,
                 deadline_ms: int = 0,
                 outofcore: Optional[Callable] = None,
                 server: Optional["QueryServer"] = None):
        self.session = session_id
        self.request = next(_REQUEST_IDS)
        self.plan = plan
        self.bindings = bindings
        self.estimate = int(estimate)
        self.donate_inputs = bool(donate_inputs)
        self.outofcore = outofcore
        # (signature, input fingerprint) — set by submit when the result
        # cache is on; the serve path populates the cache under it
        self.cache_key = None
        # the deadline clock starts at SUBMIT: queue wait counts against
        # it, so a query stuck behind a backlog cancels instead of running
        # pointlessly after its client gave up
        self.deadline_ms = int(deadline_ms)
        self.cancel_token = resilience.CancelToken(
            self.deadline_ms, label=f"{plan.name}/{session_id}")
        self.status = "queued"
        self.queue_wait_s: Optional[float] = None
        self.latency_s: Optional[float] = None
        # where on the degrade ladder the query FINISHED (what inspect()
        # shows while it runs); None until executed — a result-cache hit
        # never executes and keeps None
        self.tier: Optional[str] = None
        self.rung: Optional[int] = None
        self.steps: Optional[int] = None
        self._submitted_at = time.monotonic()
        # set by submit: the id of its submit.<plan> span (None with
        # telemetry off) and when the ticket entered its session's queue,
        # which is where the worker's admission.queue span starts
        self._submit_span: Optional[int] = None
        self._enqueued_at: Optional[float] = None
        # the client's end, all of it None / False with telemetry off: how
        # long the first result() took to come back once the worker had
        # resolved (0.0 for a ticket resolved before it was asked), the two
        # stamps that part it, and the request's roots, kept for the record
        # of a request that ran long
        self.wake_s: Optional[float] = None
        self._resolved_at: Optional[float] = None
        self._returned_at: Optional[float] = None
        self._submit_root: Optional[spans.Span] = None
        self._query_root: Optional[spans.Span] = None
        self._result_root: Optional[spans.Span] = None
        self._awaited = False
        self._judged = False
        self._server = server
        self._value: Any = None
        self._exc: Optional[BaseException] = None
        self._done = threading.Event()

    def cancel(self, reason: str = "client cancel") -> None:
        """Cooperatively cancel: the query stops at its next region/chunk
        boundary (or decode-pool checkpoint), releases everything it
        holds, and the ticket resolves ``cancelled``."""
        self.cancel_token.cancel(reason)

    def done(self) -> bool:
        return self._done.is_set()

    def result(self, timeout: Optional[float] = None):
        if not self._first_await():
            self._wait(timeout)
        else:
            # the request's third root, on the CALLER's thread and in no
            # tree of its own: query.result.<plan>, joined to the other
            # two like the worker's. Its name starts with ``query.`` and
            # its children carry no profiler annotation, so a reader that
            # counts idle time under program spans counts none here
            with spans.span(f"query.result.{self.plan.name}",
                            parent=spans.NULL_SPAN, session=self.session,
                            plan=self.plan.name, **self._joined()) as rsp:
                self._result_root = rsp
                try:
                    self._wait(timeout)
                finally:
                    self._returned(rsp)
            if self._server is not None:
                self._server._judge(self, from_worker=False)
        if self._exc is not None:
            raise self._exc
        return self._value

    def _joined(self) -> dict:
        """What joins a later root of the request to its first: the
        request's id and the id of its ``submit.<plan>`` span."""
        joined = {"request": self.request}
        if self._submit_span is not None:
            joined["caused_by"] = self._submit_span
        return joined

    def _wait(self, timeout: Optional[float]) -> None:
        if not self._done.wait(timeout):
            raise TimeoutError(
                f"query {self.plan.name!r} (session {self.session}) not "
                f"done within {timeout}s")

    def _first_await(self) -> bool:
        """True for the first ``result()`` of a ticket with telemetry on:
        the one call that records the client's end."""
        if not _telemetry_enabled():
            return False
        with _TICKET_LOCK:
            first, self._awaited = not self._awaited, True
        return first

    def _returned(self, rsp) -> None:
        """The two halves of the client's wait, recorded after the fact
        under ``rsp``: ``ticket.wait`` up to the worker's stamp and
        ``ticket.wake`` from it to now. A ticket resolved before it was
        asked has neither, one that timed out has no resolve to part at."""
        now = time.monotonic()
        resolved = self._resolved_at
        if resolved is not None and resolved > rsp.start:
            spans.record_child("ticket.wait", rsp.start, resolved,
                               session=self.session)
            spans.record_child("ticket.wake", resolved, now,
                               session=self.session)
            self.wake_s = now - resolved
        elif resolved is not None:
            self.wake_s = 0.0
        self._returned_at = now

    def _resolve(self, status: str, value: Any = None,
                 exc: Optional[BaseException] = None) -> None:
        self.status = status
        self._value = value
        self._exc = exc
        if _telemetry_enabled():
            self._resolved_at = time.monotonic()
        self._done.set()


class Session:
    """A client handle: submits against one session id on the server."""

    def __init__(self, server: "QueryServer", session_id: str):
        self._server = server
        self.session_id = session_id

    def submit(self, plan: fusion.Plan, bindings: dict, *,
               estimate_bytes: Optional[int] = None,
               donate_inputs: bool = False,
               deadline_ms: Optional[int] = None,
               outofcore: Optional[Callable] = None,
               cache_fingerprint: Optional[str] = None) -> QueryTicket:
        return self._server.submit(
            self.session_id, plan, bindings,
            estimate_bytes=estimate_bytes, donate_inputs=donate_inputs,
            deadline_ms=deadline_ms, outofcore=outofcore,
            cache_fingerprint=cache_fingerprint)

    def stats(self) -> dict:
        return self._server.session_stats(self.session_id)


class QueryServer:
    """The serving runtime. Construct, ``session(sid).submit(...)``,
    ``ticket.result()``; ``close()`` (or the context manager) drains the
    workers and rejects whatever is still queued."""

    def __init__(self, *,
                 limiter: Optional[MemoryLimiter] = None,
                 budget_bytes: Optional[int] = None,
                 max_inflight: Optional[int] = None,
                 admission_timeout_s: Optional[float] = None,
                 queue_depth: Optional[int] = None,
                 estimate_headroom: Optional[float] = None):
        if limiter is not None and budget_bytes is not None:
            raise ValueError("pass limiter OR budget_bytes, not both")
        self.limiter = limiter if limiter is not None else MemoryLimiter(
            int(budget_bytes if budget_bytes is not None
                else get_option("server.hbm_budget_bytes")))
        self.max_inflight = max(1, int(
            max_inflight if max_inflight is not None
            else get_option("server.max_inflight")))
        self.admission_timeout_s = float(
            admission_timeout_s if admission_timeout_s is not None
            else get_option("server.admission_timeout_s"))
        self.queue_depth = max(1, int(
            queue_depth if queue_depth is not None
            else get_option("server.queue_depth")))
        self.estimate_headroom = float(
            estimate_headroom if estimate_headroom is not None
            else get_option("server.estimate_headroom"))
        # every concurrent query shares ONE host decode/staging pool
        # (runtime/pipeline.py) instead of spinning a private executor
        self.decode_pool = pipeline.shared_decode_pool()
        # the server-owned spill store backs degraded queries' partials
        # AND is the limiter's proactive-spill target when the high
        # watermark trips (memory.py)
        self.spill_store = SpillStore(self.limiter.budget)
        self.limiter.attach_spill_store(self.spill_store)
        self.degrader = degrade.DegradationController(self.limiter)
        # plan-signature result & subplan cache (runtime/resultcache.py):
        # entries ride the server's spill store under the integrity.cache
        # seam and are byte-charged against the shared limiter; attaching
        # makes them the FIRST thing high-watermark pressure evicts (and
        # discounts them from parked queries' drain thresholds). All hot-
        # path probes gate on ``cache.enabled`` — off is byte-for-byte
        # today's serving path
        self.result_cache = resultcache.ResultCache(
            self.spill_store, self.limiter)
        self.limiter.attach_result_cache(self.result_cache)
        # learned admission: plan signature -> EMA of measured working-set
        # bytes, loaded from (and written through to) the crash-safe state
        # file beside the compile cache
        self._learned_lock = threading.Lock()
        self._learned: dict[str, float] = {}
        self._learned_dirty = False
        self._last_save: Optional[float] = None  # None = never saved
        self._estimate_path = self._resolve_estimate_path()
        self._load_learned()
        self._cond = threading.Condition()
        self._queues: dict[str, collections.deque] = {}
        # round-robin ring over session ids, registration order
        self._ring: collections.deque = collections.deque()
        # live introspection: ticket id -> {ticket, span, tier, rung, ...}
        # maintained by _serve (register/deregister in its try/finally)
        # and updated by the degrade observer; inspect() snapshots it
        self._inflight: dict[int, dict] = {}
        self._inflight_lock = threading.Lock()
        # resident registered tables (the mesh's shard store): name ->
        # (table, fingerprint). Shard-step submits bind these by name so
        # the query ships to the data, not the data to the query.
        self._registered: dict[str, tuple] = {}
        self._registered_lock = threading.Lock()
        self._stop = threading.Event()
        self._closed = False
        self._draining = False
        # plan signature -> latencies of its last served requests, what a
        # slow one is told from (under _TICKET_LOCK); with telemetry on the
        # first server of a process puts the collector's pauses on record
        # and the slow count exists, so a window without one reads 0
        self._latencies: dict[str, collections.deque] = {}
        self._gc_watched = _telemetry_enabled()
        if self._gc_watched:
            gcwatch.acquire()
            REGISTRY.counter("server.slow_requests")
        _LIVE_SERVERS.add(self)
        self._workers = [
            threading.Thread(target=self._worker, daemon=True,
                             name=f"tpu-server-worker-{i}")
            for i in range(self.max_inflight)
        ]
        for w in self._workers:
            w.start()

    # -- client surface ------------------------------------------------------

    def session(self, session_id: str) -> Session:
        if not session_id or not str(session_id).strip():
            raise ValueError("session_id must be non-empty")
        sid = str(session_id)
        with self._cond:
            if sid not in self._queues:
                self._queues[sid] = collections.deque()
                self._ring.append(sid)
        return Session(self, sid)

    def register_table(self, name: str, table) -> str:
        """Install a resident table for shard-step submits (the mesh's
        "ship the query to the shard" surface): subsequent queries bind
        it by name via :meth:`registered_table` so only the plan — not
        the shard's bytes — rides each submit. Returns the table's
        content fingerprint, the input half of the idempotency pair a
        supervisor verifies across hosts and failovers. Re-registering
        a name replaces it (re-homed shards after a host death)."""
        if not name or not str(name).strip():
            raise ValueError("registered table name must be non-empty")
        fp = resultcache.table_fingerprint(table)
        with self._registered_lock:
            self._registered[str(name)] = (table, fp)
        record_server("server", "registered", session="_cluster",
                      table=str(name), rows=int(table.num_rows),
                      fingerprint=fp)
        return fp

    def registered_table(self, name: str):
        """The resident table registered under ``name`` (KeyError when
        absent — the caller classifies)."""
        with self._registered_lock:
            return self._registered[str(name)][0]

    def registered_fingerprint(self, name: str) -> str:
        with self._registered_lock:
            return self._registered[str(name)][1]

    def submit(self, session_id: str, plan: fusion.Plan, bindings: dict, *,
               estimate_bytes: Optional[int] = None,
               donate_inputs: bool = False,
               deadline_ms: Optional[int] = None,
               outofcore: Optional[Callable] = None,
               cache_fingerprint: Optional[str] = None) -> QueryTicket:
        """Queue one query. Never blocks: over-the-whole-budget estimates
        and full session queues come back as immediately-rejected tickets
        (backpressure belongs to the client, not to unbounded memory).

        ``deadline_ms`` (default ``server.deadline_ms``; 0 = none) arms the
        ticket's :class:`~.resilience.CancelToken` from SUBMIT time.
        ``outofcore`` optionally supplies the degradation ladder's rung-2
        runner factory, ``(bindings, limiter) -> (chunk_rows, token) ->
        Table`` (see ``degrade.row_chunked_tier``); without it the ladder
        for this query is fused -> staged -> parked.

        With ``cache.enabled``, a submission whose ``(plan signature,
        input fingerprint)`` matches a cached result resolves served
        IMMEDIATELY — no admission, no compile, no execution; the hit is
        visible as ``query.<plan>`` / ``cache.hit`` under the request's
        ``submit.<plan>`` span.
        ``cache_fingerprint`` overrides the content digest of the
        bindings (e.g. a :func:`resultcache.source_fingerprint` the
        client maintains for file-backed scans) — changing it is the
        invalidation handle."""
        sid = str(session_id)
        self.session(sid)  # idempotent registration
        # a Parquet split is costed from its footer, read below inside the
        # request's span; everything else is costed here, as it always was
        splits = any(isinstance(v, ParquetSplit) for v in bindings.values())
        estimate = int(estimate_bytes) if estimate_bytes is not None \
            else 0 if splits else self._default_estimate(plan, bindings)
        ddl = int(deadline_ms if deadline_ms is not None
                  else get_option("server.deadline_ms"))
        ticket = QueryTicket(sid, plan, bindings, estimate, donate_inputs,
                             deadline_ms=ddl, outofcore=outofcore,
                             server=self)
        # the request's first root, on the CLIENT's thread: fingerprint,
        # cache lookup and enqueue are its children; the worker's root
        # query.<plan> carries the same request id and names this span
        # in caused_by (a cache hit's query.<plan> nests right here)
        with spans.span(f"submit.{plan.name}", session=sid, plan=plan.name,
                        request=ticket.request) as sspan:
            ticket._submit_span = sspan.id
            ticket._submit_root = sspan or None
            if not splits or self._resolve_splits(
                    ticket, sspan, costed=estimate_bytes is not None):
                self._submit(ticket, cache_fingerprint)
        return ticket

    def _resolve_splits(self, ticket: QueryTicket, sspan,
                        costed: bool) -> bool:
        """Read the footer of every Parquet split the request binds (span
        ``scan.footer``, one a split) and bind what it resolves to: the
        row groups and columns to decode, and the rows and decoded bytes
        the cache key's plan half and the estimate need. No page is read.
        False: a footer could not be read or lacks a column, and the
        ticket has resolved ``failed``, classified."""
        resolved = dict(ticket.bindings)
        try:
            for name, value in ticket.bindings.items():
                if isinstance(value, ParquetSplit):
                    with spans.child("scan.footer", session=ticket.session,
                                     path=value.path):
                        resolved[name] = value.resolve()
        except (resilience.MalformedInputError, OSError,
                NotImplementedError) as exc:
            # a file that fails validation or lacks a column, one that
            # cannot be opened, a column type a split does not stage
            self._failed(ticket, exc, sspan)
            return False
        ticket.bindings = resolved
        if not costed:
            ticket.estimate = self._default_estimate(ticket.plan, resolved)
        return True

    def _submit(self, ticket: QueryTicket,
                cache_fingerprint: Optional[str]) -> None:
        """The body of :meth:`submit`, inside its ``submit.<plan>`` span:
        resolve the ticket from the cache, reject it, or queue it."""
        sid, plan, estimate = ticket.session, ticket.plan, ticket.estimate
        self._count("submitted", sid)
        record_server(plan.name, "submitted", session=sid,
                      estimate_bytes=estimate)
        if resultcache.enabled():
            try:
                with spans.child("cache.fingerprint", session=sid) as fsp:
                    ticket.cache_key = resultcache.cache_key(
                        plan, ticket.bindings, fingerprint=cache_fingerprint)
                    if fsp:
                        fsp.annotate(nbytes=sum(
                            c.attrs["nbytes"] for c in fsp.children
                            if c.name == "cache.fingerprint.hash"))
            except (ValueError, KeyError, TypeError):
                # unfingerprintable plan/bindings (local callables,
                # non-table bindings): serve normally, never cache
                ticket.cache_key = None
            if ticket.cache_key is not None:
                with spans.child("cache.lookup", session=sid):
                    hit = self.result_cache.get(ticket.cache_key)
                if hit is not None:
                    self._serve_hit(ticket, hit)
                    return
        if estimate > self.limiter.budget:
            self._reject(ticket,
                         f"estimate {estimate} exceeds the whole HBM "
                         f"budget ({self.limiter.budget}): can never fit",
                         retry_after_s=None)
            return
        with spans.child("admission.enqueue", session=sid), self._cond:
            if self._closed:
                reject_why = "server closed"
                retry_after: Optional[float] = None
            elif self._draining:
                reject_why = "server draining"
                retry_after = None
            elif len(self._queues[sid]) >= self.queue_depth:
                reject_why = (f"session queue full "
                              f"({self.queue_depth} deep)")
                # the queue drains roughly one p50 latency per entry; a
                # zero histogram (cold server) suggests a short poll
                p50 = REGISTRY.histogram("server.latency_ms").percentile(50)
                retry_after = max(0.05, float(p50 or 0.0) / 1e3)
            else:
                reject_why = None
                retry_after = None
                ticket._enqueued_at = time.monotonic()
                self._queues[sid].append(ticket)
                self._cond.notify()
        if reject_why is not None:
            self._reject(ticket, reject_why, retry_after_s=retry_after)
            return
        self._count("queued", sid)
        record_server(plan.name, "queued", session=sid,
                      estimate_bytes=estimate)

    def close(self, timeout: Optional[float] = 30.0) -> None:
        """Stop accepting work, drain the workers, reject the backlog."""
        with self._cond:
            if self._closed:
                return
            self._closed = True
            self._stop.set()
            self._cond.notify_all()
        for w in self._workers:
            w.join(timeout)
        # whatever the workers never picked up resolves as rejected
        with self._cond:
            backlog = [t for q in self._queues.values() for t in q]
            for q in self._queues.values():
                q.clear()
        for t in backlog:
            self._reject(t, "server shutdown")
        # drop cached entries and release their limiter charges before
        # anyone inspects the limiter for leaks
        self.result_cache.close()
        self._save_learned()
        if self._gc_watched:
            gcwatch.release()

    def drain(self, timeout: Optional[float] = 30.0) -> dict:
        """Graceful drain: stop admitting (new submits reject with
        "server draining"), let every queued and in-flight query finish,
        then flush learned estimates to the shared state file. The
        server object stays alive — the fleet supervisor drains a
        replica before recycling it so a warm restart (shared JAX
        persistent compile cache + merged learned estimates) loses no
        state. Returns ``{"drained": bool, "inflight": n, "queued": n}``
        — ``drained=False`` means the timeout expired with work still
        running (the caller decides whether to wait more or kill)."""
        with self._cond:
            self._draining = True
        deadline = (None if timeout is None
                    else time.monotonic() + float(timeout))
        while True:
            with self._cond:
                queued = sum(len(q) for q in self._queues.values())
            with self._inflight_lock:
                inflight = len(self._inflight)
            if queued == 0 and inflight == 0:
                break
            if deadline is not None and time.monotonic() >= deadline:
                break
            time.sleep(0.01)
        self.flush_learned()
        record_server("server", "drained", session="_fleet",
                      inflight=inflight, queued=queued)
        return {"drained": queued == 0 and inflight == 0,
                "inflight": inflight, "queued": queued}

    def flush_learned(self) -> None:
        """Force-persist learned estimates now, ignoring the debounce
        interval (drain/recycle hook: the successor replica warm-starts
        off this file)."""
        self._save_learned()

    def warmup(self, top_n: Optional[int] = None) -> dict:
        """AOT-precompile the ``top_n`` costliest learned plan signatures
        (by estimated working set, descending) before serving traffic.

        Each signature ``<plan>@<bucket>`` replays through its registered
        warmup builder (:func:`register_warmup_builder`) at exactly the
        signature's bucket rows, so the executables a first query would
        stall compiling are already in the dispatch cache — the fleet
        replica boot hook (runtime/fleet.py) runs this before ``boot_ok``
        when ``server.warmup_top_n`` > 0. Warmup NEVER fails boot: a
        signature with no registered builder is skipped (counted under
        ``server.warmup_skipped``), a builder that raises is counted
        under ``server.warmup_failed`` and logged, and the summary dict
        reports attempted/compiled/skipped/failed either way."""
        if top_n is None:
            top_n = int(get_option("server.warmup_top_n"))
        summary = {"attempted": 0, "compiled": 0, "skipped": 0, "failed": 0}
        if top_n <= 0:
            return summary
        with self._learned_lock:
            ranked = sorted(self._learned.items(), key=lambda kv: -kv[1])
        for sig, _est in ranked[:int(top_n)]:
            name, _, bucket = sig.rpartition("@")
            builder = _WARMUP_BUILDERS.get(name)
            if builder is None or not bucket.isdigit() or int(bucket) <= 0:
                summary["skipped"] += 1
                REGISTRY.counter("server.warmup_skipped").inc()
                continue
            summary["attempted"] += 1
            try:
                with spans.span(f"warmup.{name}", rows=int(bucket)):
                    builder(int(bucket))
            except Exception as exc:
                # a warmup miss costs the first real query a compile,
                # never the boot — same posture as learned-state I/O
                summary["failed"] += 1
                REGISTRY.counter("server.warmup_failed").inc()
                _log.warning("warmup of %s failed: %s", sig, exc)
            else:
                summary["compiled"] += 1
                REGISTRY.counter("server.warmup_compiled").inc()
        return summary

    def __enter__(self) -> "QueryServer":
        return self

    def __exit__(self, *exc) -> bool:
        self.close()
        return False

    # -- introspection -------------------------------------------------------

    def stats(self) -> dict:
        c = REGISTRY.counters("server.")
        lat = REGISTRY.histogram("server.latency_ms")
        wait = REGISTRY.histogram("server.queue_wait_ms")
        return {
            "submitted": c.get("server.submitted", 0),
            "queued": c.get("server.queued", 0),
            "admitted": c.get("server.admitted", 0),
            "served": c.get("server.served", 0),
            "rejected": c.get("server.rejected", 0),
            "cancelled": c.get("server.cancelled", 0),
            "failed": c.get("server.failed", 0),
            "latency_ms_p50": lat.percentile(50),
            "latency_ms_p95": lat.percentile(95),
            "queue_wait_ms_p50": wait.percentile(50),
            "queue_wait_ms_p95": wait.percentile(95),
            "reserved_bytes": self.limiter.used,
            "budget_bytes": self.limiter.budget,
            "pressure_crossings": self.limiter.pressure_crossings,
            "degrade_steps": REGISTRY.counters("degrade.").get(
                "degrade.step", 0),
            "learned_signatures": len(self._learned),
            "sessions": sorted(self._queues),
            "cache": self.result_cache.stats(),
        }

    def inspect(self) -> dict:
        """Live serving introspection: every in-flight query with its
        current span (the deepest open node of its tree), degradation
        tier/rung, held bytes, deadline remaining and age, plus queue
        depths and the limiter's watermark state. Pure host-side reads —
        safe to call from any thread at any time; rendered by
        ``python -m spark_rapids_jni_tpu.telemetry top``."""
        with self._cond:
            queues = {sid: len(q) for sid, q in self._queues.items()}
        with self._inflight_lock:
            infos = [dict(i) for i in self._inflight.values()]
        now = time.monotonic()
        inflight = []
        for info in infos:
            ticket = info["ticket"]
            sp = info.get("span")
            current = None
            if isinstance(sp, spans.Span):
                deepest = sp.deepest_open()
                current = deepest.name if deepest is not None else None
            inflight.append({
                "session": info["session"],
                "plan": info["plan"],
                "status": ticket.status,
                "tier": info["tier"],
                "rung": info["rung"],
                "steps": info["steps"],
                "chunk_rows": info["chunk_rows"],
                "held_bytes": info["held_bytes"],
                "age_s": round(now - ticket._submitted_at, 3),
                "deadline_remaining_s": ticket.cancel_token.remaining_s(),
                "current_span": current,
            })
        return {
            "inflight": sorted(inflight,
                               key=lambda q: (q["session"], -q["age_s"])),
            "queues": dict(sorted(queues.items())),
            "queued": sum(queues.values()),
            "max_inflight": self.max_inflight,
            "limiter": self.limiter.watermarks(),
            "spill": self.spill_store.stats(),
            "cache": self.result_cache.stats(),
            "closed": self._closed,
        }

    def session_stats(self, session_id: str) -> dict:
        """Per-session attribution: counters, latency/queue-wait
        percentiles, and fallback/spill accounting from the telemetry
        ring (events stamped by ``session_scope`` during execution)."""
        sid = str(session_id)
        c = REGISTRY.counters("server.")
        lat = REGISTRY.histogram(f"server.latency_ms.{sid}")
        wait = REGISTRY.histogram(f"server.queue_wait_ms.{sid}")
        fallbacks = 0
        spills = 0
        resilience_events = 0
        degrades = 0
        for rec in _ring_events():
            if rec.get("session") != sid:
                continue
            kind = rec.get("kind")
            if kind == "fallback":
                fallbacks += 1
            elif kind == "spill":
                spills += 1
            elif kind == "resilience":
                resilience_events += 1
            elif kind == "degrade" and rec.get("event") == "step":
                degrades += 1
        return {
            "session": sid,
            "submitted": c.get(f"server.submitted.{sid}", 0),
            "queued": c.get(f"server.queued.{sid}", 0),
            "admitted": c.get(f"server.admitted.{sid}", 0),
            "served": c.get(f"server.served.{sid}", 0),
            "rejected": c.get(f"server.rejected.{sid}", 0),
            "cancelled": c.get(f"server.cancelled.{sid}", 0),
            "failed": c.get(f"server.failed.{sid}", 0),
            "latency_ms_p50": lat.percentile(50),
            "latency_ms_p95": lat.percentile(95),
            "queue_wait_ms_p50": wait.percentile(50),
            "queue_wait_ms_p95": wait.percentile(95),
            "fallbacks": fallbacks,
            "spills": spills,
            "resilience_events": resilience_events,
            "degrade_steps": degrades,
        }

    # -- internals -----------------------------------------------------------

    def _count(self, event: str, sid: str) -> None:
        # unconditional (not gated on telemetry.enabled): admission
        # accounting must hold whether or not anyone is watching
        REGISTRY.counter(f"server.{event}").inc()
        REGISTRY.counter(f"server.{event}.{sid}").inc()

    # -- adaptive admission --------------------------------------------------

    @staticmethod
    def _resolve_estimate_path() -> str:
        """Where learned estimates persist: ``server.estimate_path`` if
        set, else ``learned_estimates.json`` in ``cache_dir()`` beside the
        compile cache; empty (in-memory only) when that is switched off."""
        explicit = str(get_option("server.estimate_path") or "")
        if explicit:
            return explicit
        base = cache_dir()
        return os.path.join(base, "learned_estimates.json") if base else ""

    def _read_learned_file(self) -> Optional[dict]:
        """Read + sanitize the shared estimate file. ``None`` = nothing
        usable (absent, or corrupt — counted and discarded)."""
        state, corrupt = load_json(self._estimate_path)
        if corrupt is not None:
            # a crash mid-write can't produce this (atomic replace), but
            # disk rot / manual edits can: discard, count, keep serving
            REGISTRY.counter("server.estimate_state_discarded").inc()
            record_degrade("server.learned_estimates", "state_discarded",
                           tier="persistent", trigger="corrupt", rung=0,
                           path=self._estimate_path, reason=corrupt)
            return None
        if not isinstance(state, dict):
            return None
        return {
            str(k): float(v) for k, v in state.items()
            if isinstance(v, (int, float)) and float(v) > 0
        }

    @staticmethod
    def _merge_learned(mine: dict, disk: dict) -> dict:
        """Per-signature EMA-combine of two estimate maps: a signature
        known to only one side transfers verbatim; one known to both
        blends 50/50 (each side's value is already an EMA of its own
        measurements, so the blend is a fair co-estimate, and repeated
        merge cycles converge instead of oscillating)."""
        merged = dict(disk)
        for sig, mine_v in mine.items():
            disk_v = merged.get(sig)
            merged[sig] = float(mine_v) if disk_v is None \
                else 0.5 * float(mine_v) + 0.5 * float(disk_v)
        return merged

    def _load_learned(self) -> None:
        if not self._estimate_path:
            return
        disk = self._read_learned_file()
        if disk is None:
            return
        with self._learned_lock:
            # merge, don't replace: N replicas share one state file, and
            # a reload must never discard what this process has measured
            self._learned = self._merge_learned(self._learned, disk)

    def _save_learned(self) -> None:
        if not self._estimate_path:
            return
        with self._learned_lock:
            if not self._learned_dirty:
                return
            snapshot = dict(self._learned)
            self._learned_dirty = False
        self._last_save = time.monotonic()
        # N replica processes debounce-write this file concurrently; a
        # bare tmp+replace is last-writer-wins and clobbers every other
        # replica's learning. Serialize writers with an fcntl lock on a
        # sidecar (the data file itself is replaced, so locking it would
        # lock a dead inode) and merge-on-load inside the critical
        # section: read what the last writer left, EMA-combine per
        # signature, then atomically replace.
        lock_fh = None
        try:
            if fcntl is not None:
                lock_fh = open(self._estimate_path + ".lock", "a")
                fcntl.flock(lock_fh.fileno(), fcntl.LOCK_EX)
            disk = self._read_learned_file()
            merged = self._merge_learned(snapshot, disk or {})
            atomic_write_json(self._estimate_path, merged)
        except OSError as exc:
            # warm-start state is an optimization; losing a write only
            # costs the next process a cold estimate, never a query —
            # but stay dirty so close() (or the next interval) retries
            with self._learned_lock:
                self._learned_dirty = True
            REGISTRY.counter("server.estimate_state_write_error").inc()
            _log.warning("could not persist learned estimates to %s: %s",
                         self._estimate_path, exc)
        else:
            with self._learned_lock:
                # adopt signatures sibling replicas learned (disk-only
                # keys) so this replica's admission warms too; our own
                # EMAs keep their in-memory values
                for sig, v in merged.items():
                    self._learned.setdefault(sig, float(v))
        finally:
            if lock_fh is not None:
                try:
                    fcntl.flock(lock_fh.fileno(), fcntl.LOCK_UN)
                finally:
                    lock_fh.close()

    @staticmethod
    def _plan_signature(plan: fusion.Plan, bindings: dict) -> str:
        """Plan name + pow2 bucket of total input rows: the granularity at
        which measured working sets transfer between queries (matches the
        dispatch bucketing, so same-signature queries share executables
        AND footprints)."""
        rows = 0
        for v in bindings.values():
            rows += int(getattr(v, "num_rows", 0) or 0)
        bucket = 1 << max(rows - 1, 0).bit_length() if rows else 0
        return f"{plan.name}@{bucket}"

    def _record_actual(self, ticket: QueryTicket, bindings: dict,
                       result) -> None:
        """Blend this query's measured working set (input + result device
        bytes — the floor on its true peak; headroom covers
        intermediates) into the signature's EMA. Persistence is
        debounced: at most one fsynced write per
        ``server.estimate_save_interval_s`` on the serving path (the
        first learn saves immediately; ``close()`` flushes the rest) —
        two synchronous fsyncs per served query is tail latency the hot
        path does not owe a warm-start optimization."""
        try:
            actual = table_chip_nbytes(result.table)
            for v in bindings.values():
                actual += v.nbytes if isinstance(v, HostTableChunk) \
                    else table_chip_nbytes(v)
        except (TypeError, AttributeError):
            return  # non-table result (nothing measurable to learn from)
        sig = self._plan_signature(ticket.plan, ticket.bindings)
        alpha = min(max(float(get_option("server.estimate_alpha")), 0.0), 1.0)
        with self._learned_lock:
            prev = self._learned.get(sig)
            self._learned[sig] = float(actual) if prev is None \
                else (1.0 - alpha) * prev + alpha * float(actual)
            self._learned_dirty = True
        interval = float(get_option("server.estimate_save_interval_s"))
        if (interval <= 0 or self._last_save is None
                or time.monotonic() - self._last_save >= interval):
            self._save_learned()

    def _account_meta(self, plan: fusion.Plan, meta: dict) -> None:
        """Count, once a request, what the plan's filters, joins and
        groupbys report in the result's meta (its host copy;
        ``fusion.meta_facts``: counters ``filter.rows_in``,
        ``filter.rows_kept``, ``strings.like_bytes``,
        ``join.probe_rows``, ``join.matched_rows``, ``join.build_rows``,
        ``join.key_narrowed``, ``join.probe_compacted``,
        ``join.capacity_rows``, ``join.overflowed``,
        ``join.overflow_rows``,
        ``groupby.groups``, ``groupby.in_place``, ``groupby.key_sorted``,
        ``groupby.key_one_word``, ``groupby.key_narrowed``, ``groupby.rows_in``,
        ``groupby.read_bytes``, ``groupby.capacity_groups``,
        ``sort.prefix_sorted``,
        ``join.pk_violation``, ``groupby.overflowed``,
        ``groupby.key_out_of_range``, and of a groupby or a join lowered
        over a mesh ``shuffle.exchanges``, ``shuffle.rows``,
        ``shuffle.bytes``, ``shuffle.capacity_rows``, ``shuffle.read_bytes``
        and ``shuffle.overflowed``: the served path's shuffle telemetry), and
        refuse a result that broke what its plan declares, or whose join's
        exchange found more rows for a chip than its buffer has slots:
        rows were dropped or merged, so it must not resolve as a success."""
        if not meta:
            return
        facts = fusion.meta_facts(plan, meta)
        for name, value in facts.items():
            REGISTRY.counter(name).inc(value)
        if facts["join.overflowed"]:
            raise resilience.CapacityOverflow(
                f"plan {plan.name!r}: a join found more rows than its "
                f"out_rows has room for; the result is not the query's "
                f"answer", rows=facts["join.overflow_rows"])
        if facts["shuffle.overflowed"]:
            raise resilience.CapacityOverflow(
                f"plan {plan.name!r}: a join's exchange over the mesh found "
                f"more rows for a chip than its receive buffer has slots a "
                f"sender (skewed keys); rows were dropped, the result is not "
                f"the query's answer", rows=facts["shuffle.rows"],
                capacity=facts["shuffle.capacity_rows"])
        if facts["groupby.overflowed"]:
            raise resilience.CapacityOverflow(
                f"plan {plan.name!r}: a groupby found more groups than its "
                f"bound; the result is not the query's answer",
                groups=facts["groupby.groups"])
        for fact, broke in (
                ("join.pk_violation", "a declared dense primary key is not "
                 "one (pk_violation)"),
                ("groupby.key_out_of_range", "a groupby key lies outside "
                 "its declared range (key_out_of_range)")):
            if facts[fact]:
                raise resilience.FatalExecutionError(
                    f"plan {plan.name!r}: {broke}; the result is not the "
                    f"query's answer")

    def _default_estimate(self, plan: fusion.Plan, bindings: dict) -> int:
        """Headroom x the measured-truth EMA for this plan signature when
        one exists, else headroom x the static plan-aware input+output
        estimate; host-staged chunk bindings are costed at their exact
        device footprint, a resolved Parquet split at the decoded bytes its
        footer states."""
        with self._learned_lock:
            learned = self._learned.get(self._plan_signature(plan, bindings))
        if learned is not None:
            return int(self.estimate_headroom * learned)
        if any(isinstance(v, _HOST_SIDE) for v in bindings.values()):
            base = sum(
                v.nbytes if isinstance(v, _HOST_SIDE)
                else table_chip_nbytes(v)
                for v in bindings.values())
        else:
            base = fusion.estimate_hbm_bytes(plan, bindings)
        return int(self.estimate_headroom * base)

    def _serve_hit(self, ticket: QueryTicket, result) -> None:
        """Resolve a submit-time cache hit: the cached result is returned
        bit-identically with zero admission wait, zero compiles and zero
        execution spans — ``query.<plan>`` carrying a single ``cache.hit``
        child, nested under submit's span, is all the request executes."""
        sid = ticket.session
        with spans.span(f"query.{ticket.plan.name}", session=sid,
                        plan=ticket.plan.name,
                        estimate_bytes=ticket.estimate) as qspan:
            qspan.annotate(cache_hit=True)
            with spans.child("cache.hit", session=sid,
                             key=ticket.cache_key.short):
                pass
        ticket.queue_wait_s = 0.0
        ticket.latency_s = time.monotonic() - ticket._submitted_at
        lat_ms = ticket.latency_s * 1e3
        REGISTRY.histogram("server.latency_ms").observe(lat_ms)
        REGISTRY.histogram(f"server.latency_ms.{sid}").observe(lat_ms)
        REGISTRY.histogram("server.queue_wait_ms").observe(0.0)
        REGISTRY.histogram(f"server.queue_wait_ms.{sid}").observe(0.0)
        self._count("served", sid)
        record_server(ticket.plan.name, "served", session=sid,
                      wall_ms=lat_ms, wait_ms=0.0, cache_hit=True)
        ticket._resolve("served", value=result)

    def _reject(self, ticket: QueryTicket, reason: str,
                retry_after_s: Optional[float] = None,
                flight_record: Optional[str] = None) -> None:
        sid = ticket.session
        with self._cond:
            depth = len(self._queues.get(sid, ()))
        available = max(self.limiter.budget - self.limiter.used, 0)
        self._count("rejected", sid)
        extra = {"flight_record": flight_record} if flight_record else {}
        record_server(ticket.plan.name, "rejected", session=sid,
                      reason=reason, estimate_bytes=ticket.estimate,
                      queue_depth=depth, bytes_available=available,
                      **extra)
        _log.warning("rejected %s (session %s): %s",
                     ticket.plan.name, sid, reason)
        ticket._resolve("rejected", exc=QueryRejected(
            f"{ticket.plan.name} (session {sid}): {reason}",
            session=sid, reason=reason, queue_depth=depth,
            bytes_requested=ticket.estimate, bytes_available=available,
            retry_after_s=retry_after_s, flight_record=flight_record))

    def _next_ticket(self) -> Optional[QueryTicket]:
        """Round-robin pop: the next session (in ring order after the
        previously scheduled one) that has queued work gives up its
        OLDEST query. Blocks until work arrives or the server stops."""
        with self._cond:
            while True:
                for _ in range(len(self._ring)):
                    sid = self._ring[0]
                    self._ring.rotate(-1)
                    q = self._queues.get(sid)
                    if q:
                        return q.popleft()
                if self._stop.is_set():
                    return None
                self._cond.wait(0.1)

    def _worker(self) -> None:
        while True:
            ticket = self._next_ticket()
            if ticket is None:
                return
            self._serve(ticket)
            # an idle worker must not pin its last query's bound tables
            # (at SF10 that is 2.3 GB of HBM per waiting worker)
            ticket = None

    def _stage_bindings(self, bindings: dict, cancel_token=None) -> dict:
        """Stage host-decoded chunk bindings to device tables on the
        SHARED decode pool, concurrently across tables, and decode and
        stage every resolved Parquet split (``ParquetScan.stage``: its row
        groups decode on the same pool while this thread copies the ones
        that are ready). Runs after admission: the reservation already
        covers these bytes."""
        futs = {
            name: self.decode_pool.submit(val.stage)
            for name, val in bindings.items()
            if isinstance(val, HostTableChunk)
        }
        scans = {name: val for name, val in bindings.items()
                 if isinstance(val, ParquetScan)}
        if not futs and not scans:
            return bindings
        staged = dict(bindings)
        for name, scan in scans.items():
            table = scan.stage(self.decode_pool, cancel_token)
            # the table is keyed by its source, as the request was: whoever
            # fingerprints it later (a subplan prefix) digests nothing
            table._resultcache_fp = resultcache.split_fingerprint(scan.split)
            staged[name] = table
        for name, fut in futs.items():
            staged[name] = fut.result()
        return staged

    def _cancelled(self, ticket: QueryTicket,
                   exc: resilience.QueryCancelled,
                   flight_record: Optional[str] = None) -> None:
        sid = ticket.session
        reason = str(exc.context.get("reason") or "cancelled")
        where = str(exc.context.get("where") or "checkpoint")
        ticket.latency_s = time.monotonic() - ticket._submitted_at
        self._count("cancelled", sid)
        extra = {"flight_record": flight_record} if flight_record else {}
        record_server(ticket.plan.name, "cancelled", session=sid,
                      reason=reason, where=where,
                      wall_ms=ticket.latency_s * 1e3, **extra)
        record_degrade(f"degrade.{ticket.plan.name}", "cancelled",
                       tier="cancelled", trigger=reason, rung=0,
                       session=sid)
        _log.info("query %s (session %s) cancelled: %s",
                  ticket.plan.name, sid, reason)
        ticket._resolve("cancelled", exc=exc)

    def _failed(self, ticket: QueryTicket, exc: BaseException,
                root) -> None:
        """Resolve ``ticket`` ``failed`` with ``exc``, classified and
        recorded under ``root`` (the request's open root span: the
        worker's ``query.<plan>``, or ``submit.<plan>`` for a split whose
        footer could not be read)."""
        sid = ticket.session
        kind = resilience.classify(exc, seam="server.execute").__name__
        if isinstance(exc, resilience.MalformedInputError):
            # untrusted-input rejection: this one query dies clean (no
            # retry, no degradation); count it so operators can tell
            # hostile inputs from bugs
            REGISTRY.counter("integrity.malformed_rejects").inc()
            record_integrity(ticket.plan.name, "malformed",
                             seam="integrity.ingest", session=sid)
        root.set_status("failed")
        root.annotate(error_kind=kind)
        flight = spans.dump_flight_record(
            "failed", root=root, state=self._state_snapshot())
        ticket.latency_s = time.monotonic() - ticket._submitted_at
        self._count("failed", sid)
        extra = {"flight_record": flight} if flight else {}
        record_server(ticket.plan.name, "failed", session=sid,
                      error_kind=kind,
                      reason=str(exc) or type(exc).__name__, **extra)
        _log.warning("query %s (session %s) failed classified as %s",
                     ticket.plan.name, sid, kind)
        ticket._resolve("failed", exc=exc)

    def _judge(self, ticket: QueryTicket, *, from_worker: bool) -> None:
        """Once a served request has closed its trees: note its latency
        under its plan signature and, where it ran long, keep its record.
        Both ends call it and the one that is last judges: the worker after
        its root has closed, unless a client is inside ``result()`` (whose
        return is the latency's end), the client after its own, unless the
        worker's root is still open. A ticket nobody awaits is judged by
        the worker, to its resolve. A hit never ran and is not judged."""
        qroot = ticket._query_root
        if ticket.status != "served" or qroot is None:
            return
        with _TICKET_LOCK:
            if ticket._judged:
                return
            if from_worker:
                rroot = ticket._result_root
                if ticket._awaited and (rroot is None or rroot.end is None):
                    return
            elif qroot.end is None:
                return
            ticket._judged = True
            latency = max(ticket._resolved_at, ticket._returned_at or 0.0) \
                - ticket._submitted_at
            known = self._latencies.setdefault(
                self._plan_signature(ticket.plan, ticket.bindings),
                collections.deque(maxlen=_SLOW_KNOWN))
            median = statistics.median(known) \
                if len(known) >= _SLOW_MIN_KNOWN else None
            known.append(latency)
        if (median is not None and latency > 2.0 * median
                and latency - median >= _SLOW_OVER_S):
            self._slow(ticket, latency, median)

    def _slow(self, ticket: QueryTicket, latency: float,
              median: float) -> None:
        """Count a slow request, keep its three trees with the collector's
        records that overlap it and the server's state, and say in one line
        where it spent the time."""
        REGISTRY.counter("server.slow_requests").inc()
        gcwatch.flush()
        t0 = ticket._submitted_at
        t1 = t0 + latency
        pauses = [r for r in _ring_events() if r.get("kind") == "gc"
                  and r["t1"] > t0 and r["t0"] < t1]
        paused = sum(min(r["t1"], t1) - max(r["t0"], t0) for r in pauses)
        roots = [r for r in (ticket._submit_root, ticket._query_root,
                             ticket._result_root) if r is not None]
        state = self._state_snapshot()
        state.update(request=ticket.request, latency_s=latency,
                     median_s=median, gc=pauses)
        spans.dump_flight_record("slow", roots=roots, state=state)
        own: dict = {}
        for root in roots:
            for name, seconds in spans.self_times(root).items():
                own[name] = own.get(name, 0.0) + seconds
        own.pop("ticket.wait", None)   # the worker's tree is what explains it
        largest = sorted(own.items(), key=lambda kv: -kv[1])[:3]
        _log.warning(
            "slow request: %s (session %s, request %d) took %.6fs against "
            "a median of %.6fs; largest self times %s; gc pause inside "
            "%.6fs",
            ticket.plan.name, ticket.session, ticket.request, latency,
            median, ", ".join(f"{n} {v:.6f}s" for n, v in largest), paused)

    def _state_snapshot(self) -> dict:
        """Runtime state stamped into flight-recorder dumps: limiter
        watermarks, queue depths, in-flight count, spill-store totals."""
        with self._cond:
            queues = {sid: len(q) for sid, q in self._queues.items()}
        with self._inflight_lock:
            inflight = len(self._inflight)
        return {
            "limiter": self.limiter.watermarks(),
            "queues": queues,
            "inflight": inflight,
            "spill": self.spill_store.stats(),
        }

    def _serve(self, ticket: QueryTicket) -> None:
        sid = ticket.session
        token = ticket.cancel_token
        stop = self._stop

        class _admission_cancel:
            # wake a BLOCKED admission on shutdown OR query cancellation
            # (the limiter polls this inside reserve_blocking)
            @staticmethod
            def is_set() -> bool:
                return stop.is_set() or token.cancelled()

        held = 0
        info = {
            "ticket": ticket, "session": sid, "plan": ticket.plan.name,
            "tier": "fused", "rung": 0, "steps": 0, "chunk_rows": None,
            "held_bytes": 0, "span": None,
        }
        with self._inflight_lock:
            self._inflight[id(ticket)] = info
        try:
            # ONE root span per query on this thread: every instrumented
            # seam below (admission, degrade rungs, regions, pipeline
            # chunks, spills) attaches to this tree via the thread-local
            # stack; request/caused_by join it to submit's tree
            with spans.span(f"query.{ticket.plan.name}", session=sid,
                            plan=ticket.plan.name,
                            estimate_bytes=ticket.estimate,
                            **ticket._joined()) as qspan:
                info["span"] = qspan
                ticket._query_root = qspan or None
                try:
                    # the true queue wait: from the client's enqueue (after
                    # its fingerprint) to this pickup
                    spans.record_child("admission.queue",
                                       ticket._enqueued_at, session=sid)
                    faults.fire("server.admit", 0, session=sid,
                                plan=ticket.plan.name)
                    if token.cancelled():
                        # expired (or explicitly cancelled) while queued:
                        # resolve without ever reserving — the budget
                        # goes to live queries
                        token.check("server.admit")
                    # cached results must never make a live query wait:
                    # if this admission does not currently fit, shed
                    # resident cache entries FIRST so the reserve below
                    # parks only for bytes live queries actually hold
                    if resultcache.enabled():
                        self.result_cache.make_room(ticket.estimate)
                    # admission=True: NEW work parks while the limiter is
                    # above its high watermark; in-flight queries keep
                    # draining
                    # admission runs BEFORE the execution session_scope, so
                    # the session stamp must be explicit here
                    with spans.child("admission.wait", session=sid,
                                     estimate_bytes=ticket.estimate) as asp:
                        ok = self.limiter.reserve_blocking(
                            ticket.estimate, cancel=_admission_cancel,
                            timeout=self.admission_timeout_s,
                            admission=True)
                        if not ok:
                            asp.set_status("failed")
                    if not ok:
                        if token.cancelled():
                            token.check("server.admit")
                        qspan.set_status("failed")
                        why = ("server shutdown" if self._stop.is_set()
                               else f"admission timeout "
                                    f"({self.admission_timeout_s}s) "
                                    f"waiting for {ticket.estimate} bytes")
                        qspan.annotate(reason=why)
                        self._reject(
                            ticket, why,
                            retry_after_s=None if self._stop.is_set()
                            else self.admission_timeout_s,
                            flight_record=spans.dump_flight_record(
                                "rejected", root=qspan,
                                state=self._state_snapshot()))
                        return
                    held = ticket.estimate
                    info["held_bytes"] = held
                    ticket.status = "admitted"
                    ticket.queue_wait_s = (
                        time.monotonic() - ticket._submitted_at)
                    wait_ms = ticket.queue_wait_s * 1e3
                    REGISTRY.histogram(
                        "server.queue_wait_ms").observe(wait_ms)
                    REGISTRY.histogram(
                        f"server.queue_wait_ms.{sid}").observe(wait_ms)
                    self._count("admitted", sid)
                    record_server(ticket.plan.name, "admitted", session=sid,
                                  wait_ms=wait_ms, reserved_bytes=held)

                    def _observe(tier: str, rung: int, steps: int,
                                 chunk_rows: Optional[int]) -> None:
                        # degrade-ladder progress -> inspect(); runs with
                        # telemetry on OR off (it carries no records)
                        info["tier"] = tier
                        info["rung"] = rung
                        info["steps"] = steps
                        info["chunk_rows"] = chunk_rows
                        if steps and qspan.status == "ok":
                            qspan.set_status("degraded")

                    with session_scope(sid):
                        faults.fire("server.execute", 0, session=sid,
                                    plan=ticket.plan.name)
                        token.check("server.execute")
                        with spans.child("server.stage_bindings"):
                            bindings = self._stage_bindings(
                                ticket.bindings, token)
                        runner = None if ticket.outofcore is None \
                            else ticket.outofcore(bindings, self.limiter)
                        # subplan-prefix reuse: shared scan+filter+project
                        # prefixes collapse to cached intermediates — or
                        # materialize them once for the next plan that
                        # shares them. A rewritten plan must not donate:
                        # the injected binding is cache-owned
                        run_plan, run_bindings, rewrote = \
                            resultcache.apply_subplans(
                                self.result_cache, ticket.plan, bindings,
                                cancel_token=token)
                        # held_bytes: the parked rung must discount this
                        # query's own admission reservation from the
                        # drain threshold, or a query bigger than the low
                        # watermark parks forever
                        result = self.degrader.execute(
                            degrade.DegradableQuery(
                                run_plan, run_bindings,
                                donate_inputs=(ticket.donate_inputs
                                               and not rewrote),
                                outofcore=runner),
                            cancel_token=token, held_bytes=held,
                            observer=_observe)
                    ticket.tier = info["tier"]
                    ticket.rung = info["rung"]
                    ticket.steps = info["steps"]
                    ticket.latency_s = (
                        time.monotonic() - ticket._submitted_at)
                    lat_ms = ticket.latency_s * 1e3
                    REGISTRY.histogram("server.latency_ms").observe(lat_ms)
                    REGISTRY.histogram(
                        f"server.latency_ms.{sid}").observe(lat_ms)
                    with spans.child("server.record_actual", session=sid):
                        self._record_actual(ticket, bindings, result)
                    # what the plan's nodes report is read where the meta
                    # comes to the host anyway: in cache.put, before the
                    # entry can be looked up; a result that broke its
                    # plan's declaration raises there and is not kept
                    accounted = []

                    def _account(meta: dict) -> None:
                        accounted.append(None)
                        try:
                            self._account_meta(run_plan, meta)
                        except resilience.ResilienceError as refused:
                            accounted.append(refused)
                            raise

                    if ticket.cache_key is not None:
                        try:
                            with spans.child("cache.put", session=sid):
                                self.result_cache.put(
                                    ticket.cache_key, result,
                                    accept=_account)
                        except Exception as exc:
                            if accounted[1:]:
                                raise
                            # a cache-population failure must never fail
                            # a query that already served
                            REGISTRY.counter("cache.put_error").inc()
                            _log.warning(
                                "result-cache put failed for %s: %s",
                                ticket.plan.name, exc)
                    if not accounted:   # nothing was stored: read it here
                        with spans.child("server.account_meta",
                                         session=sid):
                            _account(resultcache._snap_meta(
                                getattr(result, "meta", None)))
                    self._count("served", sid)
                    record_server(ticket.plan.name, "served", session=sid,
                                  wall_ms=lat_ms,
                                  wait_ms=ticket.queue_wait_s * 1e3)
                    # the moment of the resolve, on the profiler's clock
                    # too: where the client's wait ends and its wake-up
                    # starts
                    with spans.child("ticket.resolve", session=sid):
                        ticket._resolve("served", value=result)
                except resilience.QueryCancelled as exc:
                    # a deliberate stop, not a failure: the reservation
                    # and the in-flight slot release in the SAME finally
                    # as every exit
                    qspan.set_status("cancelled")
                    self._cancelled(
                        ticket, exc,
                        flight_record=spans.dump_flight_record(
                            "cancelled", root=qspan,
                            state=self._state_snapshot()))
                except BaseException as exc:
                    # a dying query releases everything it holds (the
                    # finally below) and resolves CLASSIFIED — never a
                    # silent wedge
                    self._failed(ticket, exc, qspan)
                    if not isinstance(exc, Exception):
                        # KeyboardInterrupt etc: not the server's to absorb
                        raise
        finally:
            with self._inflight_lock:
                self._inflight.pop(id(ticket), None)
            if held:
                self.limiter.release(held)
            if ticket._resolved_at is not None:   # telemetry is on
                gcwatch.flush()
                self._judge(ticket, from_worker=True)
