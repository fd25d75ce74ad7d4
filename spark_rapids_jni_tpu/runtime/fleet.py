"""Fault-tolerant serving fleet: supervised QueryServer replicas with
health-checked routing and bit-identical query failover.

The serving substrate is hardened *inside* one process (classified
retries, the degradation ladder, sealed spill/wire paths) but one process
is still the whole blast radius: a wedged or SIGKILLed replica takes
every session with it. This module turns "a server" into "a service":

- :class:`QueryFleet` (the supervisor + router) boots N
  :class:`~.server.QueryServer` replicas as worker subprocesses
  (``python -m spark_rapids_jni_tpu.runtime.fleet --worker``), each on
  its own end of a local socketpair carrying length-prefixed,
  integrity-sealed pickle frames — the same seal/verify discipline as
  ``parallel/dcn.py``'s wire path (table payloads inside a frame are
  codec-framed by ``dcn.serialize_table`` under the ``compress.wire``
  seam, so the trailer is the outermost wrapper over already-compressed
  bytes).
- The **router** places each submit on the healthy replica with the
  lowest outstanding cost: a supervisor-side EMA of measured per-plan-
  signature wall time over that replica's in-flight set, tie-broken by
  the live queue depth each liveness pong reports.
- The **supervisor** pings every replica each
  ``fleet.heartbeat_interval_s``; a replica silent past
  ``fleet.heartbeat_timeout_s``, exiting nonzero, or dying by signal is
  a *classified* event — :func:`~.resilience.classify_worker_exit` maps
  the exit shape into :class:`~.resilience.ReplicaDeadError` (transient
  at the ``fleet.dispatch`` seam ONLY, where re-placement is the
  structural recovery).
- **Failover**: the dead replica's in-flight queries re-dispatch to a
  healthy replica under the bounded ``fleet.failover_budget``.
  Determinism + the result-cache idempotency pair (plan signature,
  input fingerprint) make this safe: a failed-over query must come back
  bit-identical (fingerprints compared against the supervisor's result
  memo), and a late duplicate result from a kill-raced replica is
  fingerprint-checked then dropped — never silently served twice.
- **Circuit breaker**: a replica that crashes
  ``fleet.quarantine_after`` times in a row (no successfully served
  query in between) is quarantined — no restarts, no placements — and
  every death before that restarts with exponential backoff
  (``fleet.restart_backoff_s`` × ``fleet.restart_backoff_multiplier``).
- **Drain/recycle** (:meth:`QueryFleet.recycle`): stop admitting on one
  replica, let its in-flight queries finish, flush its learned
  estimates (merged into the shared ``server.estimate_path`` state
  file), then restart it warm off the shared JAX persistent compile
  cache — a planned exit, not a classified death.

Process model: one process per chip. The supervisor stays off the
accelerator (it only frames, fingerprints and routes, on the CPU backend)
and ASSIGNS each worker its platform and device: the platform is
``JAX_PLATFORMS`` of the worker environment (``worker_env``, else the
supervisor's own), and on ``tpu`` replica *i* is pinned to chip *i*
through libtpu's own process-bounds variables. A worker's ``boot_ok``
names the platform, device kind and device it actually came up on; a
worker on a platform or device it was not given is a classified failed
boot (``fleet.boot_refused``), never ``live``.

Every supervision decision is observable: unconditional ``fleet.*``
counters, ``record_fleet`` events, replica-tagged telemetry (workers
stamp ``replica=`` on every record and span via ``telemetry.replica``),
and a flight-record artifact dumped on every replica death.
"""

from __future__ import annotations

import collections
import itertools
import os
import pickle
import socket
import struct
import subprocess
import sys
import threading
import time
import weakref
from typing import Any, Dict, List, Optional

from spark_rapids_jni_tpu.runtime import compress, faults, fusion, resilience
from spark_rapids_jni_tpu.runtime import resultcache
from spark_rapids_jni_tpu.telemetry import spans
from spark_rapids_jni_tpu.telemetry.events import record_fleet
from spark_rapids_jni_tpu.telemetry.registry import REGISTRY
from spark_rapids_jni_tpu.utils.config import get_option
from spark_rapids_jni_tpu.utils.log import get_logger

__all__ = ["QueryFleet", "FleetTicket", "live_fleets", "main"]

_log = get_logger("fleet")

# test hooks (environment of ONE replica, set via per_replica_env):
# crash immediately at boot (crash-loop drills), and a fixed pre-serve
# delay that keeps a query deterministically in flight for kill-mid-query
# chaos tests
_ENV_BOOT_CRASH = "SPARK_RAPIDS_TPU_FLEET_TEST_BOOT_CRASH"
_ENV_SERVE_DELAY = "SPARK_RAPIDS_TPU_FLEET_TEST_SERVE_DELAY_MS"

_LIVE_FLEETS: "weakref.WeakSet[QueryFleet]" = weakref.WeakSet()


def live_fleets() -> List["QueryFleet"]:
    """Every open fleet in this process (telemetry ``top`` fleet view)."""
    return [f for f in list(_LIVE_FLEETS) if not f._closed]


# ---------------------------------------------------------------------------
# framing: length-prefixed, integrity-sealed pickle frames on a socketpair
# ---------------------------------------------------------------------------


class _FrameChannel:
    """One control channel: 8-byte little-endian length prefix + an
    integrity-sealed pickle payload per frame (``integrity.enabled()``
    gates the seal/verify pair exactly like the DCN wire path; off is
    byte-for-byte raw pickle frames). Table payloads inside a message
    travel as ``dcn.serialize_table`` blobs, which the columnar codec
    already framed under ``compress.wire`` — compress -> seal ordering.

    Sends are serialized by a lock (worker query threads and the
    worker's control loop share one socket); a corrupt frame raises the
    classified :class:`~.resilience.CorruptDataError` out of ``recv``
    and the caller treats the channel — and therefore the replica — as
    dead."""

    def __init__(self, sock: socket.socket):
        self._sock = sock
        self._send_lock = threading.Lock()
        self._recv_lock = threading.Lock()

    def send(self, msg: Dict[str, Any]) -> None:
        from spark_rapids_jni_tpu.runtime import integrity

        blob = pickle.dumps(msg, protocol=pickle.HIGHEST_PROTOCOL)
        if integrity.enabled():
            blob = integrity.seal(blob)
        REGISTRY.counter("fleet.link_bytes").inc(8 + len(blob))
        with self._send_lock:
            self._sock.sendall(struct.pack("<Q", len(blob)) + blob)

    def recv(self) -> Dict[str, Any]:
        from spark_rapids_jni_tpu.runtime import integrity

        with self._recv_lock:
            # _recv_lock exists ONLY to serialize whole-frame reads on
            # this one socket: it guards no other state, so blocking in
            # recv wedges nothing but the channel's other readers, who
            # must wait for the frame boundary anyway.
            # tpulint: disable=blocking-call-under-lock
            hdr = self._recv_exact(8)
            (length,) = struct.unpack("<Q", hdr)
            # same deliberate frame read  # tpulint: disable=blocking-call-under-lock
            framed = self._recv_exact(length)
        REGISTRY.counter("fleet.link_bytes").inc(8 + length)
        if integrity.enabled():
            framed = integrity.verify(framed, seam="integrity.wire",
                                      op="fleet.recv")
        return pickle.loads(framed)

    def _recv_exact(self, n: int) -> bytes:
        chunks = []
        got = 0
        while got < n:
            # runs under _recv_lock by design: the lock serializes frame
            # reads on this socket and guards nothing else (see recv()).
            # tpulint: disable=blocking-call-under-lock
            chunk = self._sock.recv(min(n - got, 1 << 20))
            if not chunk:
                raise ConnectionError("fleet peer closed the control socket")
            chunks.append(chunk)
            got += len(chunk)
        return b"".join(chunks)

    def close(self) -> None:
        try:
            self._sock.close()
        except OSError:
            pass


def _encode_table(table) -> bytes:
    from spark_rapids_jni_tpu.parallel import dcn

    if compress.seam_enabled("integrity.wire"):
        # payload rides the columnar codec inside serialize_table; count
        # it so the fleet's share of wire codec work is attributable
        REGISTRY.counter("fleet.codec_framed_tables").inc()
    return dcn.serialize_table(table)


def _decode_table(blob: bytes):
    from spark_rapids_jni_tpu.parallel import dcn

    return dcn.deserialize_table(blob)


# ---------------------------------------------------------------------------
# client surface
# ---------------------------------------------------------------------------


class FleetTicket:
    """One fleet-submitted query's future. Resolves to the plan's
    ``FusedResult`` (:meth:`result`), or raises the classified failure
    (:class:`~.resilience.ReplicaDeadError` when every failover died,
    the replica-reported classified error otherwise). ``status`` walks
    queued -> dispatched -> served | failed; ``dispatches`` counts
    placements (> 1 means the query failed over)."""

    def __init__(self, qid: int, session: str, plan_name: str):
        self.qid = qid
        self.session = session
        self.plan_name = plan_name
        self.status = "queued"
        self.replica: Optional[str] = None
        self.dispatches = 0
        self.wall_ms: Optional[float] = None
        self.fingerprint: Optional[str] = None
        self._value: Any = None
        self._exc: Optional[BaseException] = None
        self._done = threading.Event()

    def done(self) -> bool:
        return self._done.is_set()

    def result(self, timeout: Optional[float] = None):
        if not self._done.wait(timeout):
            raise TimeoutError(
                f"fleet query {self.plan_name!r} (session {self.session}) "
                f"not done within {timeout}s")
        if self._exc is not None:
            raise self._exc
        return self._value

    def _resolve(self, status: str, value: Any = None,
                 exc: Optional[BaseException] = None) -> None:
        if self._done.is_set():
            return
        self.status = status
        self._value = value
        self._exc = exc
        self._done.set()


class _Query:
    """Supervisor-side record of one submitted query: the serialized
    submit payload (built once, reused verbatim on failover) plus the
    idempotency key that makes re-dispatch safe. ``shard`` pins a
    partitioned query to its owning (table, part) — the mesh router
    (runtime/cluster.py) routes those to the shard's host instead of
    the cheapest replica."""

    __slots__ = ("qid", "session", "signature", "cost_sig", "key",
                 "payload", "ticket", "deadline_ms", "shard")

    def __init__(self, qid: int, session: str, signature: str,
                 cost_sig: str, key, payload: Dict[str, Any],
                 ticket: FleetTicket, deadline_ms: int,
                 shard=None):
        self.qid = qid
        self.session = session
        self.signature = signature
        self.cost_sig = cost_sig
        self.key = key  # resultcache.CacheKey or None (unfingerprintable)
        self.payload = payload
        self.ticket = ticket
        self.deadline_ms = deadline_ms
        self.shard = shard  # (table name, part index) or None


def _tpu_pin(index: int) -> Dict[str, str]:
    """libtpu's variables that bound one process to ONE chip of the host
    (chip ``index``), each process its own mesh controller port — the
    documented recipe for several single-chip processes on one TPU host."""
    port = str(8476 + int(index))
    return {
        "TPU_VISIBLE_CHIPS": str(int(index)),
        "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_BOUNDS": "1,1,1",
        "TPU_MESH_CONTROLLER_ADDRESS": "localhost:" + port,
        "TPU_MESH_CONTROLLER_PORT": port,
    }


def _device_report() -> Dict[str, Any]:
    """What this process actually runs on, as JAX reports it (initializes
    the backend — a worker that cannot reach its device dies here, at
    boot, not at its first query)."""
    import jax

    devs = jax.devices()
    return {
        "platform": str(devs[0].platform),
        "device_kind": str(devs[0].device_kind),
        "device_id": int(devs[0].id),
        "device_count": len(devs),
        # the chip libtpu was bounded to (None: unbounded / not a TPU)
        "chip": os.environ.get("TPU_VISIBLE_CHIPS"),
    }


class _Replica:
    """One supervised worker subprocess and its control-channel state."""

    def __init__(self, rid: str, index: int = 0):
        self.rid = rid
        self.index = index  # position in the fleet: the chip it is given
        # what the worker's boot_ok said it runs on (_device_report)
        self.device: Dict[str, Any] = {}
        self.state = "booting"  # booting|live|draining|dead|quarantined
        self.generation = 0
        self.proc: Optional[subprocess.Popen] = None
        self.chan: Optional[_FrameChannel] = None
        self.inflight: Dict[int, _Query] = {}
        self.consecutive_crashes = 0
        self.crashes_total = 0
        self.served_total = 0
        self.restart_at: Optional[float] = None
        self.boot_deadline: Optional[float] = None
        self.last_pong: Optional[float] = None
        self.load: Dict[str, Any] = {}
        self.hb_seq = 0
        self.expected_exit = False
        self.live_evt = threading.Event()
        self.drained_evt = threading.Event()
        self.env_extra: Dict[str, str] = {}


class QueryFleet:
    """Supervisor + router over N QueryServer replica subprocesses.

    ``replicas`` overrides ``fleet.replicas``; ``worker_env`` adds
    environment variables to every worker; ``per_replica_env`` maps a
    replica id (``"r0"``, ``"r1"``, ...) to extra env for that replica
    only (chaos tests: boot-crash one replica, slow another).

    Construction returns immediately (workers boot in the background,
    ~seconds each under JAX); :meth:`wait_live` blocks until a quorum is
    serving. Use as a context manager — :meth:`close` shuts every
    worker down and fails any unresolved tickets classified.

    The supervision core (heartbeat, classified deaths, bounded
    failover, quarantine, memo/duplicate discipline) is transport- and
    identity-agnostic: subclasses override :meth:`_launch_worker` (how
    a worker process and its control channel come up), :meth:`_route`
    (which replica a query lands on) and :meth:`_extra` (identity
    context stamped into supervision events and classified errors) —
    the cross-host mesh (runtime/cluster.py) reuses everything else."""

    _ID_PREFIX = "r"  # replica id prefix ("h" for mesh host workers)
    is_cluster = False

    def __init__(self, replicas: Optional[int] = None, *,
                 worker_env: Optional[Dict[str, str]] = None,
                 per_replica_env: Optional[Dict[str, Dict[str, str]]] = None):
        self.n_replicas = max(1, int(replicas if replicas is not None
                                     else get_option("fleet.replicas")))
        self._worker_env = dict(worker_env or {})
        self.platform = self._assigned_platform()
        self._lock = threading.RLock()
        self._cond = threading.Condition(self._lock)
        self._closed = False
        self._qid = itertools.count(1)
        self._queries: Dict[int, _Query] = {}
        # resolved queries kept (bounded) for late-duplicate fingerprint
        # checks after the payload is released
        self._done_fp: "collections.OrderedDict[int, Optional[str]]" = \
            collections.OrderedDict()
        # (signature, fingerprint) -> (table, meta, table_fingerprint):
        # failover dedup / bit-identity verification, and the fleet-level
        # warm path a recycled replica serves cached signatures from
        self._memo: "collections.OrderedDict[Any, tuple]" = \
            collections.OrderedDict()
        # supervisor-side learned cost: plan signature -> EMA wall ms
        self._cost: Dict[str, float] = {}
        self._replicas: List[_Replica] = []
        for i in range(self.n_replicas):
            r = _Replica(f"{self._ID_PREFIX}{i}", i)
            r.env_extra = dict((per_replica_env or {}).get(r.rid, {}))
            self._replicas.append(r)
        _LIVE_FLEETS.add(self)
        for r in self._replicas:
            self._spawn(r)
        self._hb_stop = threading.Event()
        self._hb_thread = threading.Thread(
            target=self._heartbeat_loop, daemon=True, name="fleet-heartbeat")
        self._hb_thread.start()

    # -- worker lifecycle ----------------------------------------------------

    def _assigned_platform(self) -> str:
        """The platform every worker of this fleet is given: the first
        entry of ``JAX_PLATFORMS`` in ``worker_env``, else in the
        supervisor's environment, else the supervisor's own backend. An
        accelerator belongs to one process, so a fleet of accelerator
        workers needs a supervisor pinned to the CPU
        (``utils.platform.force_cpu_platform()`` or ``JAX_PLATFORMS=cpu``)
        — refused here, at once, instead of as N boot timeouts."""
        import jax

        spec = (self._worker_env.get("JAX_PLATFORMS")
                or os.environ.get("JAX_PLATFORMS") or jax.default_backend())
        platform = str(spec).split(",")[0].strip().lower()
        own = str(jax.config.jax_platforms or "").split(",")[0].strip()
        if platform != "cpu" and own != "cpu":
            raise ValueError(
                f"fleet workers are assigned platform {platform!r} but the "
                f"supervisor is not pinned to the CPU (jax_platforms="
                f"{jax.config.jax_platforms!r}): a chip belongs to one "
                f"process — call force_cpu_platform() in the supervisor")
        return platform

    def _worker_environment(self, r: _Replica) -> Dict[str, str]:
        from spark_rapids_jni_tpu.runtime import integrity

        env = dict(os.environ)
        # propagate option state that lives in this process's overrides
        # (env-set options are already inherited)
        env["SPARK_RAPIDS_TPU_TELEMETRY_REPLICA"] = r.rid
        env["SPARK_RAPIDS_TPU_INTEGRITY"] = "1" if integrity.enabled() else "0"
        for opt, var in (
            ("telemetry.enabled", "SPARK_RAPIDS_TPU_TELEMETRY_ENABLED"),
            ("telemetry.path", "SPARK_RAPIDS_TPU_TELEMETRY_PATH"),
            ("server.estimate_path", "SPARK_RAPIDS_TPU_SERVER_ESTIMATE_PATH"),
        ):
            val = get_option(opt)
            if val:
                env[var] = "1" if val is True else str(val)
        env.update(self._worker_env)
        # the assignment: this platform, and on tpu one chip per process
        env["JAX_PLATFORMS"] = self.platform
        if self.platform == "tpu":
            env.update(_tpu_pin(r.index))
        # per-replica chaos overrides last (they may break the assignment;
        # the boot_ok check is what notices)
        env.update(r.env_extra)
        return env

    def _boot_refusal(self, r: _Replica, report: Dict[str, Any]) -> str:
        """Why this worker's boot_ok is NOT what it was given ("" = it
        is): the assigned platform, and on tpu exactly its own chip."""
        got = str(report.get("platform", "?"))
        if got != self.platform:
            return (f"booted on platform {got!r}, was assigned "
                    f"{self.platform!r}")
        if self.platform == "tpu":
            want = _tpu_pin(r.index)["TPU_VISIBLE_CHIPS"]
            if (report.get("chip") != want
                    or int(report.get("device_count", 0)) != 1):
                return (f"booted on chip {report.get('chip')!r} with "
                        f"{report.get('device_count')} device(s), was "
                        f"assigned chip {want} alone")
        return ""

    def _extra(self, r: _Replica) -> Dict[str, Any]:
        """Identity context merged into supervision events and
        classified errors (the mesh stamps ``host=`` here)."""
        return {}

    def _launch_worker(self, r: _Replica):
        """Transport hook: create the worker process and its control
        channel. Returns ``(proc, chan)``; ``chan`` may be None when the
        channel attaches asynchronously (the mesh's TCP dial-back calls
        :meth:`_attach_channel` from its accept loop instead)."""
        parent_sock, child_sock = socket.socketpair()
        child_fd = child_sock.fileno()
        os.set_inheritable(child_fd, True)
        cmd = [sys.executable, "-m", "spark_rapids_jni_tpu.runtime.fleet",
               "--worker", "--fd", str(child_fd), "--replica", r.rid]
        proc = subprocess.Popen(cmd, pass_fds=(child_fd,),
                                env=self._worker_environment(r))
        child_sock.close()
        return proc, _FrameChannel(parent_sock)

    def _attach_channel(self, r: _Replica, chan: _FrameChannel,
                        gen: int) -> None:
        """Bind a live control channel to a replica generation and start
        its receive loop (called from _spawn, or from the mesh accept
        loop once the remote worker dials back)."""
        r.chan = chan
        threading.Thread(
            target=self._recv_loop, args=(r, chan, gen), daemon=True,
            name=f"fleet-recv-{r.rid}-g{gen}").start()

    def _spawn(self, r: _Replica) -> None:
        """Boot (or re-boot) one worker subprocess on a fresh channel."""
        r.generation += 1
        gen = r.generation
        r.state = "booting"
        r.expected_exit = False
        r.live_evt.clear()
        r.drained_evt.clear()
        r.last_pong = None
        r.load = {}
        r.chan = None
        r.boot_deadline = (time.monotonic()
                           + float(get_option("fleet.worker_boot_timeout_s")))
        r.proc, chan = self._launch_worker(r)
        REGISTRY.counter("fleet.boots").inc()
        record_fleet("fleet.spawn", "boot", replica=r.rid, pid=r.proc.pid,
                     generation=gen, **self._extra(r))
        if chan is not None:
            self._attach_channel(r, chan, gen)

    def _restart(self, r: _Replica) -> None:
        REGISTRY.counter("fleet.restarts").inc()
        record_fleet("fleet.restart", "restart", replica=r.rid,
                     crashes=r.consecutive_crashes, **self._extra(r))
        self._spawn(r)

    # -- receive path --------------------------------------------------------

    def _recv_loop(self, r: _Replica, chan: _FrameChannel, gen: int) -> None:
        while True:
            try:
                msg = chan.recv()
            except BaseException as exc:
                self._reap(r, gen, exc)
                return
            t = msg.get("t")
            if t == "boot_ok":
                report = {k: msg.get(k) for k in (
                    "platform", "device_kind", "device_id", "device_count",
                    "chip")}
                refusal = self._boot_refusal(r, report)
                if refusal:
                    # a worker on a platform or device it was not given
                    # must never serve: a classified failed boot, counted
                    # toward the crash-loop breaker like any other
                    REGISTRY.counter("fleet.boot_refused").inc()
                    record_fleet("fleet.spawn", "boot_refused",
                                 replica=r.rid, pid=msg.get("pid", 0),
                                 reason=refusal, **report, **self._extra(r))
                    self._declare_dead(r, gen, resilience.ReplicaDeadError(
                        f"fleet: replica {r.rid} {refusal}",
                        replica=r.rid, seam="fleet.boot", **self._extra(r)))
                    continue
                with self._cond:
                    if r.generation == gen and r.state == "booting":
                        r.device = report
                        r.state = "live"
                        r.last_pong = time.monotonic()
                        r.live_evt.set()
                        self._cond.notify_all()
                record_fleet("fleet.spawn", "live", replica=r.rid,
                             pid=msg.get("pid", 0), **report,
                             **self._extra(r))
            elif t == "pong":
                with self._lock:
                    r.last_pong = time.monotonic()
                    r.load = dict(msg.get("load") or {})
            elif t == "result":
                self._on_result(r, gen, msg)
            elif t == "drained":
                r.drained_evt.set()
            elif t == "bye":
                pass  # shutdown ack needs no action: the exit is expected
            else:
                # subclass protocol extension point (the mesh handles
                # shard-registration acks here)
                self._on_worker_msg(r, gen, msg)

    def _on_worker_msg(self, r: _Replica, gen: int,
                       msg: Dict[str, Any]) -> None:
        """Hook for control messages beyond the base protocol."""

    def _reap(self, r: _Replica, gen: int, exc: BaseException) -> None:
        """Control channel closed: reap the worker's exit status and
        route it through the resilience taxonomy (tpulint rule 18: a
        reaped exit must classify or visibly account — this is the
        classify)."""
        with self._lock:
            if r.generation != gen:
                return  # a stale receiver from before a restart
            expected = r.expected_exit
        rc: Optional[int] = None
        if r.proc is not None:
            try:
                rc = r.proc.wait(timeout=2.0)
            except subprocess.TimeoutExpired:
                rc = None
        if expected and (rc is None or rc == 0):
            return  # planned recycle/shutdown, not a death
        try:
            faults.fire("fleet.worker_exit", gen, replica=r.rid,
                        returncode=-1 if rc is None else rc)
        except BaseException as injected:
            exc = injected
        classified = (exc if isinstance(exc, resilience.ResilienceError)
                      else resilience.classify_worker_exit(
                          rc, replica=r.rid, **self._extra(r)))
        if classified is not exc and classified.__cause__ is None:
            classified.__cause__ = exc
        self._on_replica_death(r, gen, classified)

    def _on_result(self, r: _Replica, gen: int, msg: Dict[str, Any]) -> None:
        qid = int(msg.get("qid", 0))
        with self._lock:
            q = r.inflight.pop(qid, None) if r.generation == gen else None
            if q is None:
                q = self._queries.get(qid)
        if q is None:
            # the query resolved while this replica raced its kill: a
            # LATE DUPLICATE. Verify bit-identity against the recorded
            # fingerprint, then drop — never silently serve twice.
            self._drop_duplicate(r, qid, msg)
            return
        status = str(msg.get("status", "failed"))
        if status == "served":
            try:
                table = _decode_table(msg["table"])
                fp = resultcache.table_fingerprint(table)
            except BaseException as exc:
                self._fail_query(q, resilience.classify(
                    exc, seam="fleet.dispatch")(
                        f"fleet: result decode failed for query "
                        f"{q.ticket.plan_name}: {exc}", qid=qid,
                        replica=r.rid))
                return
            result = fusion.FusedResult(table, dict(msg.get("meta") or {}))
            wall_ms = float(msg.get("wall_ms") or 0.0)
            mismatch = False
            with self._lock:
                r.served_total += 1
                r.consecutive_crashes = 0
                self._learn_cost(q.cost_sig, wall_ms)
                if q.key is not None:
                    prev = self._memo.get(q.key)
                    if prev is not None and prev[2] != fp:
                        mismatch = True
                    else:
                        self._memo_put(q.key, (table, result.meta, fp))
            if mismatch:
                REGISTRY.counter("fleet.identity_mismatch").inc()
                record_fleet("fleet.result", "identity_mismatch",
                             replica=r.rid, qid=qid,
                             signature=q.key.signature)
                self._fail_query(q, resilience.CorruptDataError(
                    f"fleet: replica {r.rid} returned a result whose "
                    f"fingerprint differs from the recorded one for the "
                    f"same (signature, input fingerprint) key — "
                    f"determinism violated", qid=qid, replica=r.rid,
                    signature=q.key.signature))
                return
            q.ticket.replica = r.rid
            q.ticket.wall_ms = wall_ms
            q.ticket.fingerprint = fp
            REGISTRY.counter("fleet.served").inc()
            REGISTRY.counter(f"fleet.served.{r.rid}").inc()
            record_fleet("fleet.result", "served", replica=r.rid, qid=qid,
                         wall_ms=wall_ms, compiles=msg.get("compiles", 0))
            self._finish_query(q, "served", value=result, fp=fp)
        else:
            # a replica-reported QUERY failure (rejected / cancelled /
            # classified execution error): deterministic, so never failed
            # over — reconstruct the classified error and resolve
            exc = self._rebuild_error(msg, r.rid)
            REGISTRY.counter("fleet.failed").inc()
            record_fleet("fleet.result", "failed", replica=r.rid, qid=qid,
                         error_kind=str(msg.get("error_kind", "?")))
            self._finish_query(q, status, exc=exc)

    def _drop_duplicate(self, r: _Replica, qid: int,
                        msg: Dict[str, Any]) -> None:
        REGISTRY.counter("fleet.duplicate_drops").inc()
        record_fleet("fleet.result", "duplicate_drop", replica=r.rid,
                     qid=qid)
        if str(msg.get("status")) != "served":
            return
        with self._lock:
            want = self._done_fp.get(qid)
        if want is None:
            return
        try:
            fp = resultcache.table_fingerprint(_decode_table(msg["table"]))
        except BaseException:
            return  # a torn duplicate from a dying replica proves nothing
        if fp != want:
            REGISTRY.counter("fleet.identity_mismatch").inc()
            record_fleet("fleet.result", "identity_mismatch",
                         replica=r.rid, qid=qid)

    @staticmethod
    def _rebuild_error(msg: Dict[str, Any], rid: str) -> BaseException:
        kind = str(msg.get("error_kind", "FatalExecutionError"))
        message = str(msg.get("message", "replica reported failure"))
        if kind == "QueryRejected":
            from spark_rapids_jni_tpu.runtime.server import QueryRejected

            return QueryRejected(message,
                                 reason=str(msg.get("reason", "")),
                                 retry_after_s=msg.get("retry_after_s"))
        cls = getattr(resilience, kind, None)
        if not (isinstance(cls, type)
                and issubclass(cls, resilience.ResilienceError)):
            cls = resilience.FatalExecutionError
        return cls(message, replica=rid)

    def _finish_query(self, q: _Query, status: str, *, value: Any = None,
                      exc: Optional[BaseException] = None,
                      fp: Optional[str] = None) -> None:
        with self._lock:
            self._queries.pop(q.qid, None)
            self._done_fp[q.qid] = fp
            while len(self._done_fp) > 4096:
                self._done_fp.popitem(last=False)
            q.payload = None  # free the serialized bindings
        q.ticket._resolve(status, value=value, exc=exc)

    def _fail_query(self, q: _Query, exc: BaseException) -> None:
        REGISTRY.counter("fleet.failed").inc()
        self._finish_query(q, "failed", exc=exc)

    # -- death, failover, quarantine ----------------------------------------

    def _on_replica_death(self, r: _Replica, gen: int,
                          classified: BaseException) -> None:
        with self._lock:
            if r.generation != gen or r.state in ("dead", "quarantined"):
                return
            if r.expected_exit:
                return  # planned recycle/shutdown racing the supervisor
            r.state = "dead"
            r.live_evt.clear()
            r.consecutive_crashes += 1
            r.crashes_total += 1
            crashes = r.consecutive_crashes
            orphans = list(r.inflight.values())
            r.inflight.clear()
        REGISTRY.counter("fleet.replica_deaths").inc()
        REGISTRY.counter(f"fleet.replica_deaths.{r.rid}").inc()
        flight = spans.dump_flight_record(
            "replica_death",
            state={"replica": r.rid, "cause": str(classified),
                   "error_kind": type(classified).__name__,
                   "consecutive_crashes": crashes,
                   "inflight_qids": [q.qid for q in orphans],
                   **self._extra(r)})
        record_fleet("fleet.supervise", "replica_death", replica=r.rid,
                     error_kind=type(classified).__name__,
                     cause=str(classified), inflight=len(orphans),
                     **self._extra(r),
                     **({"flight_record": flight} if flight else {}))
        _log.warning("fleet: replica %s died (%s); %d in-flight to fail "
                     "over", r.rid, classified, len(orphans))
        if r.chan is not None:
            r.chan.close()
        if r.proc is not None and r.proc.poll() is None:
            r.proc.kill()
        quarantine_after = max(1, int(get_option("fleet.quarantine_after")))
        with self._lock:
            if crashes >= quarantine_after:
                r.state = "quarantined"
                r.restart_at = None
            else:
                backoff = (float(get_option("fleet.restart_backoff_s"))
                           * float(get_option(
                               "fleet.restart_backoff_multiplier"))
                           ** (crashes - 1))
                r.restart_at = time.monotonic() + backoff
        if r.state == "quarantined":
            REGISTRY.counter("fleet.quarantines").inc()
            record_fleet("fleet.supervise", "quarantine", replica=r.rid,
                         crashes=crashes, **self._extra(r))
            _log.warning("fleet: replica %s quarantined after %d "
                         "consecutive crashes", r.rid, crashes)
        if orphans:
            # failover off the supervision thread: re-dispatch can block
            # on a booting replacement, and the heartbeat loop must not
            threading.Thread(
                target=self._failover_batch, args=(r.rid, orphans, classified),
                daemon=True, name=f"fleet-failover-{r.rid}").start()

    def _failover_batch(self, dead_rid: str, orphans: List[_Query],
                        cause: BaseException) -> None:
        budget = max(0, int(get_option("fleet.failover_budget")))
        for q in orphans:
            if q.ticket.done():
                continue
            if q.ticket.dispatches > budget:
                self._fail_query(q, resilience.ReplicaDeadError(
                    f"fleet: query {q.ticket.plan_name} lost its replica "
                    f"{q.ticket.dispatches} times — failover budget "
                    f"({budget}) exhausted", qid=q.qid,
                    dispatches=q.ticket.dispatches))
                continue
            REGISTRY.counter("fleet.failovers").inc()
            record_fleet("fleet.supervise", "failover", replica=dead_rid,
                         qid=q.qid, attempt=q.ticket.dispatches)
            try:
                self._dispatch(q)
            except BaseException as exc:
                self._fail_query(q, exc if isinstance(
                    exc, resilience.ResilienceError)
                    else resilience.classify(exc, seam="fleet.dispatch")(
                        f"fleet: failover dispatch failed: {exc}",
                        qid=q.qid))

    # -- heartbeat / supervision loop ---------------------------------------

    def _heartbeat_loop(self) -> None:
        interval = max(0.05, float(get_option("fleet.heartbeat_interval_s")))
        while not self._hb_stop.wait(interval):
            timeout = float(get_option("fleet.heartbeat_timeout_s"))
            now = time.monotonic()
            for r in list(self._replicas):
                with self._lock:
                    state, gen = r.state, r.generation
                # draining replicas are exempt from liveness: the worker
                # main loop is blocked inside srv.drain() and legitimately
                # not answering pings; recycle() owns its fate
                if state == "live":
                    r.hb_seq += 1
                    try:
                        faults.fire("fleet.heartbeat", r.hb_seq,
                                    replica=r.rid)
                        r.chan.send({"t": "ping", "seq": r.hb_seq})
                    except BaseException as exc:
                        self._declare_dead(r, gen, exc)
                        continue
                    last = r.last_pong
                    if last is not None and now - last > timeout:
                        REGISTRY.counter("fleet.heartbeats_missed").inc()
                        self._declare_dead(r, gen, None)
                elif state == "booting":
                    if (r.boot_deadline is not None
                            and now > r.boot_deadline):
                        self._declare_dead(r, gen, None)
                elif state == "dead":
                    with self._lock:
                        due = (r.restart_at is not None
                               and now >= r.restart_at)
                        if due:
                            r.restart_at = None
                    if due:
                        self._restart(r)

    def _declare_dead(self, r: _Replica, gen: int,
                      exc: Optional[BaseException]) -> None:
        """A liveness verdict from the supervisor's side (missed pongs,
        failed ping send, boot timeout): classify, then kill the process
        so its receiver thread reaps deterministically."""
        if exc is None or not isinstance(exc, resilience.ResilienceError):
            rc = r.proc.poll() if r.proc is not None else None
            classified = resilience.classify_worker_exit(
                rc, replica=r.rid, seam="fleet.heartbeat", **self._extra(r))
            if exc is not None and classified.__cause__ is None:
                classified.__cause__ = exc
        else:
            classified = exc
        self._on_replica_death(r, gen, classified)

    # -- routing -------------------------------------------------------------

    def _learn_cost(self, sig: str, wall_ms: float) -> None:
        if wall_ms <= 0:
            return
        prev = self._cost.get(sig)
        self._cost[sig] = wall_ms if prev is None \
            else 0.6 * prev + 0.4 * wall_ms

    def _placement_cost(self, r: _Replica) -> float:
        default = (sum(self._cost.values()) / len(self._cost)
                   if self._cost else 50.0)
        cost = sum(self._cost.get(q.cost_sig, default)
                   for q in r.inflight.values())
        # the replica's own view of its backlog (from its last pong)
        # covers work the supervisor did not place (direct sessions)
        cost += default * float(r.load.get("queued", 0) or 0)
        return cost

    def _pick_replica(self, deadline: float) -> Optional[_Replica]:
        while True:
            with self._cond:
                live = [r for r in self._replicas if r.state == "live"]
                if live:
                    picked = min(live, key=lambda r: (
                        self._placement_cost(r), r.rid))
                    # every routing decision is counted (tpulint rule 23:
                    # a placement choice must be visible in telemetry)
                    REGISTRY.counter("fleet.placements").inc()
                    REGISTRY.counter(
                        f"fleet.placements.{picked.rid}").inc()
                    return picked
                if self._closed or time.monotonic() >= deadline:
                    return None
                self._cond.wait(timeout=min(
                    0.05, max(0.0, deadline - time.monotonic())) or 0.01)

    def _route(self, q: _Query, deadline: float) -> Optional[_Replica]:
        """Routing hook: which replica this placement round lands on.
        The base fleet load-balances; the mesh router overrides with
        partition-map locality for shard-pinned queries."""
        return self._pick_replica(deadline)

    def _dispatch(self, q: _Query) -> None:
        """Place one query on the routed healthy replica and send its
        frame; raises classified when no replica can take it in time."""
        deadline = time.monotonic() + float(
            get_option("fleet.dispatch_timeout_s"))
        while True:
            r = self._route(q, deadline)
            if r is None:
                raise resilience.ReplicaDeadError(
                    "fleet: no healthy replica to dispatch to within "
                    f"{get_option('fleet.dispatch_timeout_s')}s",
                    qid=q.qid, seam="fleet.dispatch")
            with self._lock:
                gen = r.generation
                if r.state != "live":
                    continue
                r.inflight[q.qid] = q
                q.ticket.dispatches += 1
                q.ticket.replica = r.rid
                q.ticket.status = "dispatched"
            try:
                with spans.span("fleet.dispatch", replica=r.rid,
                                plan=q.ticket.plan_name, qid=q.qid):
                    faults.fire("fleet.dispatch", q.ticket.dispatches,
                                replica=r.rid, qid=q.qid)
                    r.chan.send(q.payload)
            except BaseException as exc:
                with self._lock:
                    r.inflight.pop(q.qid, None)
                classified = (exc if isinstance(
                    exc, resilience.ResilienceError)
                    else resilience.classify(exc, seam="fleet.dispatch")(
                        f"fleet: dispatch to {r.rid} failed: {exc}",
                        qid=q.qid, replica=r.rid))
                # a failed send means the replica is gone: declare it so
                # its other in-flight queries fail over too
                self._declare_dead(r, gen, classified)
                if not resilience.is_transient(classified,
                                               seam="fleet.dispatch"):
                    raise classified
                budget = max(0, int(get_option("fleet.failover_budget")))
                if q.ticket.dispatches > budget:
                    raise resilience.ReplicaDeadError(
                        f"fleet: query {q.ticket.plan_name} lost "
                        f"{q.ticket.dispatches} replicas at dispatch — "
                        f"failover budget ({budget}) exhausted",
                        qid=q.qid) from classified
                continue
            REGISTRY.counter("fleet.dispatched").inc()
            REGISTRY.counter(f"fleet.dispatched.{r.rid}").inc()
            return

    # -- client surface ------------------------------------------------------

    def wait_live(self, n: Optional[int] = None,
                  timeout: float = 120.0) -> int:
        """Block until ``n`` (default: all) replicas are serving; returns
        the live count (may be short on timeout or quarantine)."""
        want = self.n_replicas if n is None else int(n)
        deadline = time.monotonic() + timeout
        with self._cond:
            while True:
                live = sum(1 for r in self._replicas if r.state == "live")
                dead_forever = sum(1 for r in self._replicas
                                   if r.state == "quarantined")
                if live >= want or live >= self.n_replicas - dead_forever:
                    if live >= want or time.monotonic() >= deadline:
                        return live
                if time.monotonic() >= deadline:
                    return live
                self._cond.wait(timeout=0.1)

    def submit(self, session_id: str, plan: fusion.Plan, bindings: dict, *,
               deadline_ms: Optional[int] = None,
               cache_fingerprint: Optional[str] = None) -> FleetTicket:
        """Route one query to a replica. Returns immediately with a
        :class:`FleetTicket`; placement failures, replica deaths past
        the failover budget, and replica-reported failures all resolve
        the ticket classified."""
        return self._submit(session_id, plan, bindings,
                            deadline_ms=deadline_ms,
                            cache_fingerprint=cache_fingerprint)

    def _submit(self, session_id: str, plan: fusion.Plan, bindings: dict, *,
                binding_refs: Optional[Dict[str, str]] = None,
                shard=None,
                sig_bindings: Optional[Dict[str, Any]] = None,
                deadline_ms: Optional[int] = None,
                cache_fingerprint: Optional[str] = None) -> FleetTicket:
        """Shared submit core. ``binding_refs`` maps plan binding names
        to worker-resident registered tables (the mesh's ship-the-query
        path: the shard's bytes never ride the submit frame); ``shard``
        pins the query to its owning (table, part) for locality routing
        and re-homing failover; ``sig_bindings`` supplies stand-ins for
        ref-bound tables when deriving the memo key and cost signature
        (both read only ``num_rows``), so the idempotency pair survives
        without the shard's bytes ever being supervisor-resident."""
        if self._closed:
            raise RuntimeError("fleet is closed")
        qid = next(self._qid)
        sid = str(session_id)
        ticket = FleetTicket(qid, sid, plan.name)
        REGISTRY.counter("fleet.submitted").inc()
        key_bindings = (bindings if not sig_bindings
                        else {**bindings, **sig_bindings})
        key = None
        if int(get_option("fleet.result_memo_entries")) > 0:
            try:
                key = resultcache.cache_key(
                    plan, key_bindings, fingerprint=cache_fingerprint)
            except (ValueError, KeyError, TypeError):
                key = None  # unfingerprintable: serve, never memoize
        if key is not None:
            with self._lock:
                hit = self._memo.get(key)
                if hit is not None:
                    self._memo.move_to_end(key)
            if hit is not None:
                table, meta, fp = hit
                REGISTRY.counter("fleet.memo_hits").inc()
                record_fleet("fleet.submit", "memo_hit",
                             replica="supervisor", qid=qid,
                             signature=key.signature)
                ticket.fingerprint = fp
                ticket.replica = "supervisor"
                ticket._resolve("served",
                                value=fusion.FusedResult(table, dict(meta)))
                return ticket
        try:
            payload = {
                "t": "submit", "qid": qid, "session": sid,
                "plan": pickle.dumps(plan,
                                     protocol=pickle.HIGHEST_PROTOCOL),
                "bindings": {k: _encode_table(v)
                             for k, v in bindings.items()},
                "binding_refs": dict(binding_refs or {}),
                "deadline_ms": deadline_ms,
                "cache_fingerprint": cache_fingerprint,
            }
        except BaseException as exc:
            ticket._resolve("failed", exc=resilience.MalformedInputError(
                f"fleet: query {plan.name} is not shippable to a replica "
                f"(plan or bindings failed to serialize): {exc}", qid=qid))
            return ticket
        from spark_rapids_jni_tpu.runtime.server import QueryServer

        q = _Query(qid, sid, key.signature if key is not None else "",
                   QueryServer._plan_signature(plan, key_bindings), key,
                   payload, ticket,
                   int(deadline_ms or 0), shard=shard)
        with self._lock:
            self._queries[qid] = q
        try:
            self._dispatch(q)
        except BaseException as exc:
            self._fail_query(q, exc if isinstance(
                exc, resilience.ResilienceError)
                else resilience.classify(exc, seam="fleet.dispatch")(
                    f"fleet: dispatch failed: {exc}", qid=qid))
        return ticket

    def recycle(self, rid: str, timeout: float = 60.0) -> bool:
        """Graceful drain + warm restart of one replica: stop admitting,
        finish in-flight, flush learned estimates (merged into the
        shared state file), exit cleanly, boot a successor off the
        shared JAX persistent compile cache. A planned exit — no crash
        counted, no backoff. Returns True when the successor is live."""
        r = self._find(rid)
        with self._lock:
            if r.state != "live":
                return False
            r.state = "draining"
            gen = r.generation
        record_fleet("fleet.supervise", "drain", replica=rid)
        REGISTRY.counter("fleet.drains").inc()
        try:
            r.chan.send({"t": "drain", "timeout": timeout})
            if not r.drained_evt.wait(timeout):
                self._declare_dead(r, gen, None)
                return False
            with self._lock:
                r.expected_exit = True
            r.chan.send({"t": "shutdown"})
        except BaseException as exc:
            self._declare_dead(r, gen, exc)
            return False
        try:
            r.proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            r.proc.kill()
        with self._lock:
            r.state = "dead"  # planned; not a crash (counter untouched)
        self._restart(r)
        return bool(r.live_evt.wait(
            float(get_option("fleet.worker_boot_timeout_s"))))

    def _find(self, rid: str) -> _Replica:
        for r in self._replicas:
            if r.rid == rid:
                return r
        raise KeyError(f"no replica {rid!r}")

    def inspect(self) -> dict:
        """Live fleet introspection (telemetry ``top`` fleet view): every
        replica's state, load and supervision history, plus router and
        memo state. Pure host-side reads."""
        with self._lock:
            replicas = []
            for r in self._replicas:
                age = (None if r.last_pong is None
                       else time.monotonic() - r.last_pong)
                replicas.append({
                    "replica": r.rid, "state": r.state,
                    "pid": r.proc.pid if r.proc is not None else None,
                    "generation": r.generation,
                    "device": dict(r.device),
                    "inflight": len(r.inflight),
                    "served": r.served_total,
                    "crashes": r.crashes_total,
                    "consecutive_crashes": r.consecutive_crashes,
                    "last_pong_age_s": age,
                    "restart_in_s": (
                        None if r.restart_at is None
                        else max(0.0, r.restart_at - time.monotonic())),
                    "load": dict(r.load),
                })
            c = REGISTRY.counters("fleet.")
            return {
                "fleet": True,
                "replicas": replicas,
                "pending_queries": len(self._queries),
                "memo_entries": len(self._memo),
                "learned_signatures": len(self._cost),
                "counters": {k: v for k, v in sorted(c.items())
                             if k.count(".") == 1},
            }

    def leaked_bytes(self) -> int:
        """Sum of the live replicas' last-reported leaked reservation
        bytes (limiter usage beyond the result cache's resident charge)
        — zero once every query has resolved and released (chaos/CI
        leak check). Reads each replica's latest liveness pong; wait at
        least one ``fleet.heartbeat_interval_s`` after the final result
        for a fresh report."""
        with self._lock:
            return sum(int(r.load.get("leaked", 0) or 0)
                       for r in self._replicas if r.state == "live")

    def _memo_put(self, key, entry: tuple) -> None:
        cap = int(get_option("fleet.result_memo_entries"))
        if cap <= 0:
            return
        self._memo[key] = entry
        self._memo.move_to_end(key)
        while len(self._memo) > cap:
            self._memo.popitem(last=False)

    def close(self, timeout: float = 30.0) -> None:
        """Shut every worker down; unresolved tickets fail classified."""
        with self._cond:
            if self._closed:
                return
            self._closed = True
            self._cond.notify_all()
        self._hb_stop.set()
        self._hb_thread.join(timeout=5.0)
        for r in self._replicas:
            with self._lock:
                r.expected_exit = True
            if r.chan is not None and r.state in ("live", "draining"):
                try:
                    r.chan.send({"t": "shutdown"})
                except OSError:
                    pass
        for r in self._replicas:
            if r.proc is not None:
                try:
                    r.proc.wait(timeout=timeout)
                except subprocess.TimeoutExpired:
                    r.proc.kill()
                    r.proc.wait(timeout=5.0)
            if r.chan is not None:
                r.chan.close()
            with self._lock:
                r.state = "dead"
        with self._lock:
            pending = list(self._queries.values())
        for q in pending:
            self._finish_query(q, "failed", exc=resilience.ReplicaDeadError(
                "fleet closed before the query completed", qid=q.qid))

    def __enter__(self) -> "QueryFleet":
        return self

    def __exit__(self, *exc) -> bool:
        self.close()
        return False


# ---------------------------------------------------------------------------
# worker side
# ---------------------------------------------------------------------------


def _worker_load(srv) -> Dict[str, Any]:
    with srv._inflight_lock:
        inflight = len(srv._inflight)
    with srv._cond:
        queued = sum(len(dq) for dq in srv._queues.values())
    used = srv.limiter.used
    # the server-level leak invariant: at idle, limiter.used must equal
    # exactly the result cache's resident (evictable) charge — anything
    # beyond that is a reservation some query failed to release
    return {"inflight": inflight, "queued": queued, "used": used,
            "leaked": max(0, used - srv.result_cache.evictable_bytes)}


def _serve_one(chan: _FrameChannel, srv, msg: Dict[str, Any],
               replica: str) -> None:
    qid = msg["qid"]
    out: Dict[str, Any] = {"t": "result", "qid": qid}
    try:
        delay_ms = float(os.environ.get(_ENV_SERVE_DELAY, "0") or 0.0)
        if delay_ms:
            # chaos hook: hold the query in flight long enough for the
            # test to SIGKILL this worker mid-query deterministically
            time.sleep(delay_ms / 1e3)
        plan = pickle.loads(msg["plan"])
        bindings = {k: _decode_table(v)
                    for k, v in (msg.get("bindings") or {}).items()}
        # the mesh's ship-the-query path: bindings resolved from tables
        # registered on THIS worker (the shard lives here; only the
        # plan crossed the wire)
        for name, reg in (msg.get("binding_refs") or {}).items():
            try:
                bindings[name] = srv.registered_table(reg)
            except KeyError:
                raise resilience.MalformedInputError(
                    f"fleet: submit references registered table {reg!r} "
                    f"which is not resident on replica {replica}",
                    replica=replica, binding=name) from None
        compiles_before = REGISTRY.counters("dispatch.").get(
            "dispatch.compile", 0)
        t0 = time.monotonic()
        ticket = srv.submit(
            msg["session"], plan, bindings,
            deadline_ms=msg.get("deadline_ms"),
            cache_fingerprint=msg.get("cache_fingerprint"))
        result = ticket.result()
        wall_ms = (time.monotonic() - t0) * 1e3
        out.update({
            "status": "served",
            "table": _encode_table(result.table),
            "meta": resultcache._snap_meta(result.meta),
            "wall_ms": wall_ms,
            "compiles": REGISTRY.counters("dispatch.").get(
                "dispatch.compile", 0) - compiles_before,
        })
    except BaseException as exc:
        kind = type(exc).__name__
        if not isinstance(exc, resilience.ResilienceError) \
                and kind != "QueryRejected":
            kind = resilience.classify(exc).__name__
        out.update({
            "status": {"QueryRejected": "rejected",
                       "QueryCancelled": "cancelled"}.get(kind, "failed"),
            "error_kind": kind,
            "message": str(exc),
            "reason": str(getattr(exc, "reason", "") or ""),
            "retry_after_s": getattr(exc, "retry_after_s", None),
        })
    try:
        chan.send(out)
    except OSError:
        pass  # supervisor gone; this worker is about to be reaped anyway


def _register_one(chan: _FrameChannel, srv, msg: Dict[str, Any],
                  replica: str) -> None:
    """Install one shipped shard into this worker's registered-table
    store and acknowledge with its fingerprint (the supervisor verifies
    it against the fingerprint taken before the shard crossed the wire
    — the cross-host half of the idempotency pair)."""
    name = str(msg.get("name", ""))
    out: Dict[str, Any] = {"t": "registered", "name": name}
    try:
        table = _decode_table(msg["table"])
        out["fingerprint"] = srv.register_table(name, table)
        out["rows"] = int(table.num_rows)
    except BaseException as exc:
        kind = type(exc).__name__
        if not isinstance(exc, resilience.ResilienceError):
            kind = resilience.classify(exc).__name__
        out.update({"error_kind": kind, "message": str(exc)})
    try:
        chan.send(out)
    except OSError:
        pass  # supervisor gone; this worker is about to be reaped anyway


def _worker_main(fd: int, replica: str) -> int:
    """Replica entrypoint: one in-process QueryServer behind the frame
    channel."""
    if os.environ.get(_ENV_BOOT_CRASH):
        return 3  # chaos hook: crash-loop at boot
    sock = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM, fileno=fd)
    return _worker_loop(_FrameChannel(sock), replica)


def _worker_loop(chan: _FrameChannel, replica: str,
                 extensions=None) -> int:
    """The worker control loop behind any connected frame channel (a
    socketpair fd for the local fleet, a dialed-back TCP socket for the
    mesh's remote hosts). The main thread stays in the control loop
    (pings answered inline, so liveness tracks control-plane
    responsiveness); each submit serves on its own thread.

    ``extensions`` maps extra frame types to handlers
    ``fn(chan, srv, msg, replica)``; each runs on its own daemon thread
    (extension frames — e.g. the cluster's direct-exchange pack/merge —
    block on compute and peer flights, and must not stall the ping
    loop). Unknown frame types without a handler are dropped, as
    before."""
    from spark_rapids_jni_tpu.runtime.server import QueryServer

    srv = QueryServer()
    # AOT warmup BEFORE boot_ok (gated by server.warmup_top_n, default
    # off): the supervisor routes no traffic here until the costliest
    # learned plan signatures are precompiled, so a recycled replica
    # rejoins without first-query compile stalls. warmup() never raises.
    if int(get_option("server.warmup_top_n")) > 0:
        from spark_rapids_jni_tpu.models import tpch  # noqa: F401  (registers warmup builders)
        srv.warmup()
    chan.send({"t": "boot_ok", "pid": os.getpid(), **_device_report()})
    frozen = False
    try:
        while True:
            try:
                msg = chan.recv()
            except (ConnectionError, EOFError):
                return 0  # supervisor went away: exit quietly
            t = msg.get("t")
            if t == "ping":
                if not frozen:
                    chan.send({"t": "pong", "seq": msg.get("seq", 0),
                               "load": _worker_load(srv)})
            elif t == "submit":
                threading.Thread(
                    target=_serve_one, args=(chan, srv, msg, replica),
                    daemon=True,
                    name=f"fleet-serve-{msg.get('qid')}").start()
            elif t == "register":
                # inline, not threaded: registration must complete (and
                # ack) before any submit that references the shard, and
                # the control loop's ordering guarantees exactly that
                _register_one(chan, srv, msg, replica)
            elif t == "drain":
                state = srv.drain(timeout=msg.get("timeout"))
                chan.send({"t": "drained", **state})
            elif t == "freeze":
                # chaos hook: stop answering pings (simulates a wedged
                # control plane) while query threads keep running
                frozen = True
            elif t == "shutdown":
                srv.close()
                chan.send({"t": "bye"})
                return 0
            elif extensions is not None and t in extensions:
                threading.Thread(
                    target=extensions[t], args=(chan, srv, msg, replica),
                    daemon=True, name=f"fleet-ext-{t}").start()
    finally:
        srv.close()  # idempotent: a no-op after the shutdown path ran


def main(argv: Optional[List[str]] = None) -> int:
    args = list(sys.argv[1:] if argv is None else argv)
    if "--worker" not in args:
        print("usage: python -m spark_rapids_jni_tpu.runtime.fleet "
              "--worker --fd <fd> --replica <rid>", file=sys.stderr)
        return 2
    fd = replica = None
    for i, a in enumerate(args):
        if a == "--fd" and i + 1 < len(args):
            fd = int(args[i + 1])
        elif a == "--replica" and i + 1 < len(args):
            replica = args[i + 1]
    if fd is None or replica is None:
        print("fleet worker: --fd and --replica are required",
              file=sys.stderr)
        return 2
    return _worker_main(fd, replica)


if __name__ == "__main__":
    sys.exit(main())
