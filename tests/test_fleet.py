"""Fault-tolerant serving fleet (runtime/fleet, ISSUE 14).

Chaos invariant families over the supervised-replica serving fleet:

1. **Bit-identity through the fleet** — queries routed over replica
   subprocesses return byte-for-byte what serial ``fusion.execute``
   produces, including after a supervisor memo hit.

2. **Kill-mid-query failover** — SIGKILLing the serving replica while
   its query is in flight re-dispatches to a survivor and completes
   bit-identical; the death is a classified ``ReplicaDeadError``
   (signal shape, replica tagged) and zero reservations leak.

3. **Heartbeat liveness** — a replica whose control plane stops
   answering pings (frozen, not dead) is declared dead within the
   liveness deadline, classified ``unresponsive``, and restarted.

4. **Crash-loop quarantine** — a replica that dies at boot repeatedly
   trips its circuit breaker within ``fleet.quarantine_after`` boots
   and stops consuming restarts; the rest of the fleet keeps serving.

5. **Bounded failover / no healthy replica** — a query whose replicas
   keep dying resolves as a classified failure once the failover budget
   is spent, never a hang and never a silent duplicate (late duplicate
   results are fingerprint-checked then dropped).

6. **Drain/recycle warm restart** — a drained replica exits cleanly
   (no crash counted), flushes its learned estimates to the shared
   state file, and the first post-restart query of a cached signature
   is served with ZERO compiles (the supervisor memo holds the
   idempotency pair).

Replica boots cost ~1-2 s each (subprocess + jax import), so every
test keeps its fleet small and the seeded multi-round sweep is
slow-tier.
"""

import json
import os
import signal
import time

import numpy as np
import pytest

from spark_rapids_jni_tpu.models import tpch
from spark_rapids_jni_tpu.runtime import (
    dispatch,
    faults,
    fleet,
    fusion,
    resilience,
    resultcache,
)
from spark_rapids_jni_tpu.telemetry import REGISTRY
from spark_rapids_jni_tpu.telemetry import top as tele_top
from spark_rapids_jni_tpu.telemetry.events import drain as drain_events
from spark_rapids_jni_tpu.telemetry.events import events as ring_events
from spark_rapids_jni_tpu.utils.config import reset_option, set_option

SERVE_DELAY = fleet._ENV_SERVE_DELAY
BOOT_CRASH = fleet._ENV_BOOT_CRASH


@pytest.fixture(autouse=True)
def _isolated_fleet():
    """Fresh counters/events, chaos-friendly supervision cadence, and
    config back at defaults afterwards."""
    dispatch.clear()
    REGISTRY.reset()
    drain_events()
    set_option("fleet.heartbeat_interval_s", 0.1)
    set_option("fleet.restart_backoff_s", 0.1)
    set_option("telemetry.enabled", True)  # record_fleet events -> ring
    yield
    for k in ("fleet.replicas", "fleet.heartbeat_interval_s",
              "fleet.heartbeat_timeout_s", "fleet.failover_budget",
              "fleet.restart_backoff_s", "fleet.restart_backoff_multiplier",
              "fleet.quarantine_after", "fleet.result_memo_entries",
              "fleet.dispatch_timeout_s", "server.estimate_path",
              "telemetry.enabled", "telemetry.path", "telemetry.replica"):
        reset_option(k)
    dispatch.clear()


def _q1():
    plan = tpch._q1_plan()
    bindings = {"lineitem": tpch.lineitem_table(600, seed=11)}
    return plan, bindings


def _fp(table):
    return resultcache.table_fingerprint(table)


def _wait(predicate, timeout=30.0, interval=0.02):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if predicate():
            return True
        time.sleep(interval)
    return predicate()


def _fleet_events(event):
    return [r for r in ring_events()
            if r.get("kind") == "fleet" and r.get("event") == event]


# ---------------------------------------------------------------------------
# 1. bit-identity through the fleet
# ---------------------------------------------------------------------------


def test_fleet_serves_bit_identical_and_memo_hits():
    plan, bindings = _q1()
    ref = fusion.execute(plan, bindings)
    with fleet.QueryFleet(2) as f:
        assert f.wait_live(timeout=120) == 2
        first = f.submit("s0", plan, bindings)
        res = first.result(timeout=120)
        assert first.status == "served"
        assert _fp(res.table) == _fp(ref.table)
        # identical resubmission: the supervisor memo serves it without
        # touching a replica, same bytes
        again = f.submit("s1", plan, bindings)
        res2 = again.result(timeout=120)
        assert again.replica == "supervisor"
        assert _fp(res2.table) == _fp(ref.table)
        assert REGISTRY.counter("fleet.memo_hits").value == 1
        assert REGISTRY.counter("fleet.served").value == 1
        # distinct bindings really execute (no false memo hit)
        other = {"lineitem": tpch.lineitem_table(700, seed=12)}
        oref = fusion.execute(plan, other)
        got = f.submit("s0", plan, other).result(timeout=120)
        assert _fp(got.table) == _fp(oref.table)
        assert REGISTRY.counter("fleet.served").value == 2
        time.sleep(0.3)  # a fresh liveness pong carries the leak report
        assert f.leaked_bytes() == 0


# ---------------------------------------------------------------------------
# 2. kill-mid-query failover
# ---------------------------------------------------------------------------


def test_sigkill_mid_query_fails_over_bit_identical():
    plan, bindings = _q1()
    ref_fp = _fp(fusion.execute(plan, bindings).table)
    with fleet.QueryFleet(2, per_replica_env={
            "r0": {SERVE_DELAY: "3000"}}) as f:
        assert f.wait_live(timeout=120) == 2
        ticket = f.submit("chaos", plan, bindings)
        assert _wait(lambda: ticket.replica == "r0", 15), ticket.replica
        time.sleep(0.2)  # inside r0's serve hold: genuinely mid-query
        os.kill(f._find("r0").proc.pid, signal.SIGKILL)
        res = ticket.result(timeout=120)
        assert ticket.status == "served"
        assert ticket.dispatches == 2 and ticket.replica == "r1"
        assert _fp(res.table) == ref_fp, "failed-over result diverged"
        assert REGISTRY.counter("fleet.replica_deaths.r0").value == 1
        assert REGISTRY.counter("fleet.failovers").value == 1
        # the death is observable: a classified replica_death event and
        # a flight record naming the replica
        deaths = _fleet_events("replica_death")
        assert deaths and deaths[0]["replica"] == "r0"
        assert deaths[0]["error_kind"] == "ReplicaDeadError"
        assert "SIGKILL" in deaths[0]["cause"]
        # the victim restarts with backoff; nothing leaks anywhere
        assert _wait(lambda: f._find("r0").state == "live", 60)
        time.sleep(0.3)
        assert f.leaked_bytes() == 0


@pytest.mark.slow
def test_injected_dispatch_fault_fails_over():
    """An injected failure at the fleet.dispatch seam (a failed submit
    send) is transient AT THAT SEAM ONLY: the target replica is treated
    as dead and the query re-places on a survivor."""
    plan, bindings = _q1()
    ref_fp = _fp(fusion.execute(plan, bindings).table)
    script = faults.FaultScript([
        faults.FaultSpec("fleet.dispatch",
                         resilience.ReplicaDeadError("injected send death"),
                         seq=1)])
    with fleet.QueryFleet(2) as f:
        assert f.wait_live(timeout=120) == 2
        with faults.inject(script):
            ticket = f.submit("s0", plan, bindings)
            res = ticket.result(timeout=120)
        assert script.fired, "fault never reached the dispatch seam"
        assert ticket.status == "served" and ticket.dispatches == 2
        assert _fp(res.table) == ref_fp
        assert REGISTRY.counter("fleet.replica_deaths").value == 1


# ---------------------------------------------------------------------------
# 3. heartbeat liveness
# ---------------------------------------------------------------------------


def test_dropped_heartbeats_classify_unresponsive_and_restart():
    set_option("fleet.heartbeat_timeout_s", 0.6)
    with fleet.QueryFleet(2) as f:
        assert f.wait_live(timeout=120) == 2
        r0 = f._find("r0")
        gen = r0.generation
        r0.chan.send({"t": "freeze"})  # control plane wedged, not dead
        assert _wait(lambda: r0.state != "live" or r0.generation != gen, 30)
        assert REGISTRY.counter("fleet.heartbeats_missed").value >= 1
        deaths = _fleet_events("replica_death")
        assert deaths and deaths[0]["replica"] == "r0"
        assert "unresponsive" in deaths[0]["cause"]
        # a fresh process answers pings again
        assert _wait(lambda: r0.state == "live", 60)
        assert r0.generation == gen + 1


# ---------------------------------------------------------------------------
# 4. crash-loop quarantine
# ---------------------------------------------------------------------------


def test_boot_crash_loop_quarantines_within_bound():
    set_option("fleet.quarantine_after", 2)
    plan, bindings = _q1()
    with fleet.QueryFleet(2, per_replica_env={
            "r1": {BOOT_CRASH: "1"}}) as f:
        r1 = f._find("r1")
        assert _wait(lambda: r1.state == "quarantined", 60), r1.state
        assert r1.consecutive_crashes == 2, "breaker opened off-bound"
        assert REGISTRY.counter("fleet.quarantines").value == 1
        boots_at_quarantine = REGISTRY.counter("fleet.boots").value
        # quarantined means QUIET: no further restarts burn cycles
        time.sleep(0.8)
        assert REGISTRY.counter("fleet.boots").value == boots_at_quarantine
        # the healthy half of the fleet still serves
        assert f.wait_live(1, timeout=120) >= 1
        got = f.submit("s0", plan, bindings).result(timeout=120)
        assert _fp(got.table) == _fp(fusion.execute(plan, bindings).table)


def test_worker_on_unassigned_platform_is_refused_never_live():
    """The fleet assigns its workers a platform (here "tpu", through the
    worker environment); a worker that comes up elsewhere (r0's chaos
    override lands it on the CPU, the quiet way off the chip) is a
    classified failed boot with a ring event, counted toward the breaker
    — never live."""
    set_option("fleet.quarantine_after", 2)
    with fleet.QueryFleet(
            1, worker_env={"JAX_PLATFORMS": "tpu"},
            per_replica_env={"r0": {"JAX_PLATFORMS": "cpu"}}) as f:
        assert f.platform == "tpu"
        r0 = f._find("r0")
        assert _wait(lambda: r0.state == "quarantined", 60), r0.state
        assert f.wait_live(timeout=0.1) == 0
    assert _fleet_events("live") == []
    refused = _fleet_events("boot_refused")
    assert len(refused) == 2
    assert refused[0]["platform"] == "cpu"
    assert "assigned 'tpu'" in refused[0]["reason"]
    assert REGISTRY.counter("fleet.boot_refused").value == 2
    deaths = _fleet_events("replica_death")
    assert deaths and all(
        d["error_kind"] == "ReplicaDeadError" for d in deaths)
    assert "booted on platform 'cpu'" in deaths[0]["cause"]


def test_accelerator_fleet_needs_a_cpu_supervisor():
    """A chip belongs to one process: assigning workers an accelerator
    from a supervisor that is not pinned to the CPU is refused at once,
    not discovered as N boot timeouts."""
    import jax

    pinned = jax.config.jax_platforms
    jax.config.update("jax_platforms", "")  # value only: backend is up
    try:
        with pytest.raises(ValueError, match="pinned to the CPU"):
            fleet.QueryFleet(1, worker_env={"JAX_PLATFORMS": "tpu"})
    finally:
        jax.config.update("jax_platforms", pinned)
    # tpu workers are bounded to one chip each, replica i -> chip i
    pin = fleet._tpu_pin(2)
    assert pin["TPU_VISIBLE_CHIPS"] == "2"
    assert pin["TPU_PROCESS_BOUNDS"] == "1,1,1"


# ---------------------------------------------------------------------------
# 5. bounded failover, no-replica classification, duplicate drop
# ---------------------------------------------------------------------------


def test_failover_budget_exhausted_resolves_classified():
    set_option("fleet.failover_budget", 0)
    set_option("fleet.quarantine_after", 1)
    plan, bindings = _q1()
    with fleet.QueryFleet(1, per_replica_env={
            "r0": {SERVE_DELAY: "3000"}}) as f:
        assert f.wait_live(timeout=120) == 1
        ticket = f.submit("doomed", plan, bindings)
        assert _wait(lambda: ticket.replica == "r0", 15)
        time.sleep(0.2)
        os.kill(f._find("r0").proc.pid, signal.SIGKILL)
        with pytest.raises(resilience.ReplicaDeadError,
                           match="failover budget"):
            ticket.result(timeout=120)
        assert ticket.status == "failed"


def test_no_healthy_replica_times_out_classified():
    set_option("fleet.quarantine_after", 1)
    set_option("fleet.dispatch_timeout_s", 0.5)
    plan, bindings = _q1()
    with fleet.QueryFleet(1, per_replica_env={
            "r0": {BOOT_CRASH: "1"}}) as f:
        assert _wait(lambda: f._find("r0").state == "quarantined", 60)
        ticket = f.submit("nowhere", plan, bindings)
        with pytest.raises(resilience.ReplicaDeadError,
                           match="no healthy replica"):
            ticket.result(timeout=60)


def test_late_duplicate_result_is_fingerprint_checked_and_dropped():
    """A kill-raced replica may flush its result AFTER the query failed
    over and resolved: the duplicate must be dropped, never re-served,
    and its fingerprint compared against the recorded one."""
    plan, bindings = _q1()
    with fleet.QueryFleet(1) as f:
        assert f.wait_live(timeout=120) == 1
        ticket = f.submit("s0", plan, bindings)
        res = ticket.result(timeout=120)
        r0 = f._find("r0")
        table_blob = fleet._encode_table(res.table)
        # replay the replica's own result frame for the resolved qid
        dup = {"t": "result", "qid": ticket.qid, "status": "served",
               "table": table_blob, "meta": {}, "wall_ms": 1.0}
        f._on_result(r0, r0.generation, dup)
        assert REGISTRY.counter("fleet.duplicate_drops").value == 1
        assert REGISTRY.counter("fleet.identity_mismatch").value == 0
        # a duplicate with DIFFERENT bytes for the same qid is flagged
        other = fusion.execute(
            plan, {"lineitem": tpch.lineitem_table(600, seed=99)})
        dup2 = dict(dup, table=fleet._encode_table(other.table))
        f._on_result(r0, r0.generation, dup2)
        assert REGISTRY.counter("fleet.duplicate_drops").value == 2
        assert REGISTRY.counter("fleet.identity_mismatch").value == 1


# ---------------------------------------------------------------------------
# 6. drain / recycle warm restart
# ---------------------------------------------------------------------------


def test_recycle_drains_flushes_estimates_and_restarts_warm(tmp_path):
    est = tmp_path / "learned_estimates.json"
    set_option("server.estimate_path", str(est))
    plan, bindings = _q1()
    ref_fp = _fp(fusion.execute(plan, bindings).table)
    with fleet.QueryFleet(1) as f:
        assert f.wait_live(timeout=120) == 1
        first = f.submit("s0", plan, bindings)
        first.result(timeout=120)
        assert f.recycle("r0", timeout=60), "recycle failed"
        r0 = f._find("r0")
        assert r0.generation == 2 and r0.consecutive_crashes == 0
        # planned exit: drained+restarted, never a classified death
        assert REGISTRY.counter("fleet.replica_deaths").value == 0
        assert REGISTRY.counter("fleet.drains").value == 1
        assert REGISTRY.counter("fleet.restarts").value == 1
        # the drain flushed the replica's learned estimates into the
        # shared state file before exit
        learned = json.loads(est.read_text())
        sig = f"{plan.name}@1024"
        assert sig in learned and learned[sig] > 0, learned
        # first post-restart query of the cached signature: ZERO
        # compiles (served off the supervisor memo), bit-identical
        compiles0 = sum(REGISTRY.counters("dispatch.compile").values())
        warm = f.submit("s0", plan, bindings)
        res = warm.result(timeout=120)
        assert warm.replica == "supervisor"
        assert _fp(res.table) == ref_fp
        assert sum(REGISTRY.counters(
            "dispatch.compile").values()) == compiles0, \
            "post-restart cached-signature query paid a compile"


# ---------------------------------------------------------------------------
# observability
# ---------------------------------------------------------------------------


def test_inspect_and_top_fleet_view():
    with fleet.QueryFleet(2) as f:
        assert f.wait_live(timeout=120) == 2
        time.sleep(0.3)  # at least one pong per replica
        snap = f.inspect()
        assert snap["fleet"] is True
        states = {r["replica"]: r["state"] for r in snap["replicas"]}
        assert states == {"r0": "live", "r1": "live"}
        # boot_ok names what each worker actually runs on; the supervisor
        # keeps it and stamps it on the "live" ring event
        assert f.platform == "cpu"
        for r in snap["replicas"]:
            assert r["device"]["platform"] == "cpu"
            assert r["device"]["device_kind"]
            assert isinstance(r["device"]["device_id"], int)
            assert r["device"]["device_count"] >= 1
        for ev in _fleet_events("live"):
            assert ev["platform"] == "cpu" and "device_kind" in ev
            assert "device_id" in ev
        assert all(r["last_pong_age_s"] is not None
                   for r in snap["replicas"])
        snaps = tele_top.collect_fleet()
        assert len(snaps) == 1
        text = tele_top.render_fleet(snaps)
        assert "r0" in text and "r1" in text and "live" in text
    assert tele_top.collect_fleet() == []  # closed fleets drop out


@pytest.mark.slow
def test_worker_telemetry_stamped_with_replica(tmp_path):
    path = tmp_path / "run.jsonl"
    set_option("telemetry.enabled", True)
    set_option("telemetry.path", str(path))
    plan, bindings = _q1()
    with fleet.QueryFleet(2) as f:
        assert f.wait_live(timeout=120) == 2
        for i in range(2):
            f.submit(f"s{i}", plan, {
                "lineitem": tpch.lineitem_table(600 + i, seed=i)},
            ).result(timeout=120)
        time.sleep(0.2)
    recs = [json.loads(line) for line in
            path.read_text().strip().splitlines()]
    assert recs, "workers wrote no telemetry"
    replicas = {r.get("replica") for r in recs if r.get("replica")}
    assert replicas & {"r0", "r1"}, replicas
    # every worker-side record is attributable to its replica
    worker_kinds = {r["kind"] for r in recs if r.get("replica")}
    assert worker_kinds, "no replica-stamped records in the shared sink"


# ---------------------------------------------------------------------------
# seeded chaos sweep (slow tier)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_seeded_chaos_sweep_every_query_accounted():
    """Seeded rounds of mixed chaos — SIGKILL mid-query, dropped
    heartbeats, boot crash-loop on a restart — against a stream of
    queries: every ticket either serves BIT-IDENTICAL bytes or fails
    with a classified ReplicaDeadError; nothing hangs, nothing leaks,
    nothing is silently served twice."""
    rng = np.random.default_rng(1234)
    set_option("fleet.heartbeat_timeout_s", 0.6)
    set_option("fleet.result_memo_entries", 0)  # every query executes
    plan, _ = _q1()
    cases = []
    for i in range(4):
        b = {"lineitem": tpch.lineitem_table(560 + 20 * i, seed=40 + i)}
        cases.append((b, _fp(fusion.execute(plan, b).table)))
    with fleet.QueryFleet(2, per_replica_env={
            "r0": {SERVE_DELAY: "600"}}) as f:
        assert f.wait_live(timeout=120) == 2
        served = failed = 0
        for round_no in range(3):
            tickets = [(f.submit(f"s{i}", plan, b), want)
                       for i, (b, want) in enumerate(cases)]
            chaos = rng.integers(0, 3)
            time.sleep(float(rng.uniform(0.05, 0.3)))
            victim = f._find("r0")
            if chaos == 0 and victim.state == "live":
                os.kill(victim.proc.pid, signal.SIGKILL)
            elif chaos == 1 and victim.state == "live":
                try:
                    victim.chan.send({"t": "freeze"})
                except OSError:
                    pass
            for t, want in tickets:
                try:
                    res = t.result(timeout=180)
                    assert _fp(res.table) == want, "served bytes diverged"
                    served += 1
                except resilience.ReplicaDeadError:
                    failed += 1
            # between rounds, let supervision settle
            _wait(lambda: any(r.state == "live" for r in f._replicas), 60)
        assert served + failed == 3 * len(cases)
        assert served > 0, "chaos killed every single query"
        assert REGISTRY.counter("fleet.identity_mismatch").value == 0
        _wait(lambda: f.leaked_bytes() == 0, 10)
        assert f.leaked_bytes() == 0
