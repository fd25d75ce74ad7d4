"""Multi-query serving runtime (runtime/server, ISSUE 7).

Five invariant families:

1. **Bit-identity under concurrency** — N sessions submitting q1/q3/q6
   at ragged row counts through one shared server get byte-for-byte the
   results serial ``fusion.execute`` produces for the same plan and
   bindings, with zero leaked ``MemoryLimiter`` reservations afterwards.

2. **Warm-cache sharing** — sessions at ragged row counts inside one
   bucket trigger exactly ONE compile per fused region (the single-flight
   executable cache), every other query a hit.

3. **Admission control** — an estimate over the whole budget (or a full
   session queue, or an admission timeout) rejects instead of
   overcommitting; work that merely does not fit *right now* queues and
   the limiter peak never exceeds the budget.

4. **Fairness** — round-robin across sessions: a light session's query
   is served ahead of a heavy session's backlog, never starved behind it.
   Plus the ``MemoryLimiter`` FIFO regression: a later smaller
   reservation must NOT barge past an earlier blocked one (the old
   behavior granted it instantly).

5. **Fault isolation & attribution** — an injected fault in one session
   fails that query classified, leaks nothing, and never perturbs another
   session's results; telemetry events emitted during a served query
   carry its ``session`` id.
"""

import threading
import time

import numpy as np
import pytest

from spark_rapids_jni_tpu.models import tpch
from spark_rapids_jni_tpu.runtime import dispatch, faults, fusion, server
from spark_rapids_jni_tpu.runtime.memory import MemoryLimiter
from spark_rapids_jni_tpu.telemetry import REGISTRY
from spark_rapids_jni_tpu.telemetry.events import drain as drain_events
from spark_rapids_jni_tpu.telemetry.events import events as ring_events
from spark_rapids_jni_tpu.utils.config import (
    get_option,
    reset_option,
    set_option,
)

# ragged row counts inside ONE bucket of the default schedule
# (512 < n <= 1024 -> bucket 1024)
RAGGED_IN_BUCKET = (600, 700, 801, 1000)


@pytest.fixture(autouse=True)
def _isolated_server():
    """Each test sees a fresh executable cache, counter namespace, and
    event ring, and leaves the server config at its defaults."""
    dispatch.clear()
    REGISTRY.reset()
    drain_events()
    yield
    for k in ("server.max_inflight", "server.hbm_budget_bytes",
              "server.admission_timeout_s", "server.queue_depth",
              "server.estimate_headroom", "telemetry.enabled",
              "telemetry.path", "telemetry.flight_recorder_path",
              "degrade.chunk_rows"):
        reset_option(k)
    dispatch.clear()


def _q1_bindings(n, seed=0):
    return tpch._q1_plan(), {"lineitem": tpch.lineitem_table(n, seed=seed)}


def _q6_plan():
    return fusion.Plan("tpch_q6", fusion.Project(
        fusion.Scan("lineitem"), tpch._q6_reduce, rowwise=False))


def _q3_bindings(n, seed=0):
    n_ord = max(n // 8, 4)
    n_cust = max(n // 64, 2)
    plan = tpch._q3_plan(0, tpch._Q3_CUTOFF_DAYS, 2)
    bindings = {
        "customer": tpch.customer_table(n_cust, seed=seed),
        "orders": tpch.orders_table(n_ord, n_cust, seed=seed + 1),
        "lineitem": tpch.lineitem_q3_table(n, n_ord, seed=seed + 2),
    }
    return plan, bindings


def _assert_tables_identical(a, b, label=""):
    assert a.num_columns == b.num_columns, f"{label}: column count"
    assert a.num_rows == b.num_rows, f"{label}: row count"
    for i in range(a.num_columns):
        ca, cb = a.column(i), b.column(i)
        av, bv = np.asarray(ca.valid_mask()), np.asarray(cb.valid_mask())
        assert np.array_equal(av, bv), f"{label} col {i}: validity"
        ad = np.where(av, np.asarray(ca.data), 0)
        bd = np.where(bv, np.asarray(cb.data), 0)
        assert np.array_equal(ad, bd), f"{label} col {i}: data"


# ---------------------------------------------------------------------------
# 1. bit-identity under concurrency
# ---------------------------------------------------------------------------


def test_concurrent_sessions_bit_identical_to_serial():
    """4 sessions x {q1, q3, q6} at ragged row counts, 16 in-flight
    slots: every result equals its serial fusion.execute reference and
    no reservation survives the run."""
    jobs = []  # (session, plan, bindings, reference)
    for i, n in enumerate(RAGGED_IN_BUCKET):
        q1p, q1b = _q1_bindings(n, seed=i)
        q3p, q3b = _q3_bindings(max(n // 2, 64), seed=i)
        q6p, q6b = _q6_plan(), {
            "lineitem": tpch.lineitem_table(n + 7, seed=i + 10)}
        for plan, bindings in ((q1p, q1b), (q3p, q3b), (q6p, q6b)):
            ref = fusion.execute(plan, bindings)
            jobs.append((f"sess{i}", plan, bindings, ref))

    with server.QueryServer(budget_bytes=1 << 28, max_inflight=16) as srv:
        tickets = [
            (srv.session(sid).submit(plan, bindings), plan, ref)
            for sid, plan, bindings, ref in jobs
        ]
        for ticket, plan, ref in tickets:
            res = ticket.result(timeout=120)
            assert ticket.status == "served"
            _assert_tables_identical(res.table, ref.table, plan.name)
        # resident cached results hold a legitimate charge while the
        # server lives; anything beyond that is a leaked reservation
        assert srv.limiter.used == srv.result_cache.evictable_bytes, \
            "leaked reservations"
        assert srv.stats()["served"] == len(jobs)
    assert srv.limiter.used == 0, "close() left reservations behind"


# ---------------------------------------------------------------------------
# 2. warm-cache sharing across sessions (single-flight compile)
# ---------------------------------------------------------------------------


def test_sessions_in_one_bucket_share_one_executable():
    """N sessions at ragged row counts inside one bucket: exactly ONE
    compile per fused region, even though the first compiles race."""
    with server.QueryServer(budget_bytes=1 << 28, max_inflight=8) as srv:
        tickets = []
        for i, n in enumerate(RAGGED_IN_BUCKET):
            plan, bindings = _q1_bindings(n, seed=i)
            tickets.append(srv.session(f"s{i}").submit(plan, bindings))
            q6b = {"lineitem": tpch.lineitem_table(n - 3, seed=i + 20)}
            tickets.append(srv.session(f"s{i}").submit(_q6_plan(), q6b))
        for ticket in tickets:
            ticket.result(timeout=120)
    c = REGISTRY.counters("dispatch.")
    assert c.get("dispatch.compile.fusion.tpch_q1", 0) == 1
    assert c.get("dispatch.compile.fusion.tpch_q6", 0) == 1
    n_queries = len(RAGGED_IN_BUCKET)
    assert c.get("dispatch.hit.fusion.tpch_q1", 0) == n_queries - 1
    assert c.get("dispatch.hit.fusion.tpch_q6", 0) == n_queries - 1


# ---------------------------------------------------------------------------
# 3. admission control
# ---------------------------------------------------------------------------


def test_over_budget_estimate_rejected_not_overcommitted():
    plan, bindings = _q1_bindings(1000)
    with server.QueryServer(budget_bytes=10_000, max_inflight=2) as srv:
        ticket = srv.session("big").submit(plan, bindings)
        assert ticket.status == "rejected"
        with pytest.raises(server.QueryRejected, match="whole HBM budget"):
            ticket.result(timeout=5)
        assert srv.limiter.used == 0
        assert srv.stats()["rejected"] == 1


def test_tight_budget_queues_and_never_exceeds():
    """Three queries against a budget that fits only one estimate at a
    time: all serve (serialized through the limiter), and the limiter
    peak stays inside the budget — no overcommit, ever."""
    plan, bindings = _q1_bindings(700)
    est = int(get_option("server.estimate_headroom")
              * fusion.estimate_hbm_bytes(plan, bindings))
    budget = int(est * 1.5)  # one fits, two would overcommit
    with server.QueryServer(budget_bytes=budget, max_inflight=4) as srv:
        tickets = [srv.session(f"s{i}").submit(plan, bindings)
                   for i in range(3)]
        for ticket in tickets:
            ticket.result(timeout=120)
            assert ticket.status == "served"
        assert srv.limiter.peak <= budget
        assert srv.limiter.used == srv.result_cache.evictable_bytes
    assert srv.limiter.used == 0


def test_admission_timeout_rejects_and_releases_slot():
    lim = MemoryLimiter(1000)
    lim.reserve(900)  # external pressure the server cannot see past
    plan, bindings = _q1_bindings(600)
    with server.QueryServer(limiter=lim, max_inflight=2,
                            admission_timeout_s=0.3) as srv:
        ticket = srv.session("slow").submit(
            plan, bindings, estimate_bytes=500)
        with pytest.raises(server.QueryRejected, match="admission timeout") \
                as ei:
            ticket.result(timeout=30)
        assert ticket.status == "rejected"
        # a timed-out admission IS retryable: the hint is the window the
        # client just waited, not "never"
        assert ei.value.retry_after_s == pytest.approx(0.3)
        # the slot freed: a fitting query still serves afterwards
        ok = srv.session("slow").submit(plan, bindings, estimate_bytes=50)
        ok.result(timeout=60)
        assert ok.status == "served"
    assert lim.used == 900  # external reservation untouched, nothing leaked
    lim.release(900)


def test_full_session_queue_rejects_at_submit():
    plan, bindings = _q1_bindings(600)
    lim = MemoryLimiter(1 << 28)
    lim.reserve((1 << 28) - 1)  # wedge admission so the queue backs up
    picked = threading.Event()

    def probe(seam, seq, ctx):
        if seam == "server.admit":
            picked.set()

    with faults.inject(probe), \
            server.QueryServer(limiter=lim, max_inflight=1, queue_depth=2,
                               admission_timeout_s=10.0) as srv:
        sess = srv.session("burst")
        tickets = [sess.submit(plan, bindings, estimate_bytes=100)]
        assert picked.wait(10)  # the worker holds ticket 0 at admission
        tickets += [sess.submit(plan, bindings, estimate_bytes=100)
                    for _ in range(4)]
        # 1 in flight (blocked at admission) + 2 queued; the rest bounce
        rejected = [t for t in tickets if t.status == "rejected"]
        assert len(rejected) == 2
        for t in rejected:
            with pytest.raises(server.QueryRejected, match="queue full"):
                t.result(timeout=5)
        lim.release((1 << 28) - 1)
        for t in tickets:
            if t not in rejected:
                t.result(timeout=60)
                assert t.status == "served"
    assert lim.used == 0


def test_rejection_is_structured_for_client_backoff():
    """A QueryRejected carries everything a client needs to back off
    sensibly: who, why, how deep the queue was, bytes requested vs
    available, and a retry-after hint (None = retrying can NEVER
    succeed) — and the rejected telemetry event carries the same."""
    set_option("telemetry.enabled", True)
    plan, bindings = _q1_bindings(600)
    # shape 1 — never fits: estimate over the whole budget
    with server.QueryServer(budget_bytes=10_000, max_inflight=1) as srv:
        big = srv.session("big").submit(
            plan, bindings, estimate_bytes=20_000)
        with pytest.raises(server.QueryRejected) as ei:
            big.result(timeout=5)
        exc = ei.value
        assert exc.session == "big"
        assert "never fit" in exc.reason
        assert exc.bytes_requested == 20_000
        assert exc.bytes_available == 10_000
        assert exc.retry_after_s is None  # structural: do not retry
        assert exc.queue_depth == 0
    # shape 2 — queue full: transient, retry-after is a real hint
    lim = MemoryLimiter(1 << 20)
    lim.reserve((1 << 20) - 1)  # wedge admission so the queue backs up
    picked = threading.Event()

    def probe(seam, seq, ctx):
        if seam == "server.admit":
            picked.set()

    with faults.inject(probe), \
            server.QueryServer(limiter=lim, max_inflight=1, queue_depth=1,
                               admission_timeout_s=10.0) as srv:
        sess = srv.session("burst")
        first = sess.submit(plan, bindings, estimate_bytes=100)
        assert picked.wait(10)  # the worker holds ticket 0 at admission
        sess.submit(plan, bindings, estimate_bytes=100)  # fills the queue
        bounced = sess.submit(plan, bindings, estimate_bytes=100)
        assert bounced.status == "rejected"
        with pytest.raises(server.QueryRejected) as ei:
            bounced.result(timeout=5)
        exc = ei.value
        assert exc.session == "burst"
        assert "queue full" in exc.reason
        assert exc.queue_depth == 1
        assert exc.bytes_requested == 100
        assert exc.bytes_available == 1  # budget minus the wedge
        assert exc.retry_after_s is not None and exc.retry_after_s >= 0.05
        rej = [r for r in ring_events()
               if r.get("kind") == "server" and r.get("event") == "rejected"]
        assert rej and rej[-1]["queue_depth"] == 1
        assert rej[-1]["bytes_available"] == 1
        lim.release((1 << 20) - 1)
        first.result(timeout=60)
    assert lim.used == 0


# ---------------------------------------------------------------------------
# 4. fairness
# ---------------------------------------------------------------------------


def test_round_robin_light_session_not_starved():
    """A heavy session with a 4-deep backlog and a light session with one
    query: execution order must interleave — the light query runs right
    after the heavy query already in flight, not after the backlog."""
    plan, bindings = _q1_bindings(600)
    lim = MemoryLimiter(1000)
    lim.reserve(990)  # park the first pick at admission
    order = []
    picked = threading.Event()

    def probe(seam, seq, ctx):
        if seam == "server.admit":
            picked.set()
        elif seam == "server.execute":
            order.append(ctx["session"])

    with faults.inject(probe):
        with server.QueryServer(limiter=lim, max_inflight=1,
                                admission_timeout_s=30.0) as srv:
            heavy = srv.session("heavy")
            light = srv.session("light")
            first = heavy.submit(plan, bindings, estimate_bytes=100)
            assert picked.wait(10)  # the worker holds it at admission
            backlog = [heavy.submit(plan, bindings, estimate_bytes=100)
                       for _ in range(3)]
            lone = light.submit(plan, bindings, estimate_bytes=100)
            lim.release(990)
            for t in [first, lone] + backlog:
                t.result(timeout=60)
    assert order[0] == "heavy"
    assert order[1] == "light", f"light starved: {order}"
    assert order.count("heavy") == 4 and order.count("light") == 1
    assert lim.used == 0


def test_limiter_fifo_no_barge():
    """Regression (old behavior): budget 100, 80 held, thread A blocks
    wanting 60; thread B then asks for 20 — which FITS (80+20=100), so
    the old poll loop granted B instantly, barging past A. FIFO ordering
    must hold B behind A until A is served."""
    lim = MemoryLimiter(100)
    lim.reserve(80)
    order = []

    def want(tag, n):
        assert lim.reserve_blocking(n, timeout=10)
        order.append(tag)

    a = threading.Thread(target=want, args=("A", 60))
    a.start()
    time.sleep(0.2)  # A is parked before B arrives
    b = threading.Thread(target=want, args=("B", 20))
    b.start()
    time.sleep(0.3)
    # the barge window: B fits right now, but A was first — nobody may
    # have been granted yet (old code had order == ["B"] here)
    assert order == [], f"barge: {order}"
    lim.release(80)
    a.join(10)
    b.join(10)
    assert order == ["A", "B"]
    assert lim.used == 80  # A's 60 + B's 20
    lim.release(80)


def test_limiter_fifo_timeout_unblocks_queue():
    """A timed-out head-of-line waiter must not wedge the queue."""
    lim = MemoryLimiter(100)
    lim.reserve(80)
    assert lim.reserve_blocking(60, timeout=0.2) is False
    # the dead ticket is gone: a fitting request proceeds immediately
    assert lim.reserve_blocking(20, timeout=5)
    lim.release(100)


# ---------------------------------------------------------------------------
# 5. fault isolation & session attribution
# ---------------------------------------------------------------------------


def test_fault_in_one_session_leaks_nothing_and_isolates():
    plan, bindings = _q1_bindings(700)
    ref = fusion.execute(plan, bindings)

    def victim_only(seam, seq, ctx):
        if seam == "server.execute" and ctx.get("session") == "victim":
            raise RuntimeError("injected query death")

    with server.QueryServer(budget_bytes=1 << 28, max_inflight=4) as srv:
        with faults.inject(victim_only):
            doomed = srv.session("victim").submit(plan, bindings)
            fine = srv.session("bystander").submit(plan, bindings)
            with pytest.raises(RuntimeError, match="injected query death"):
                doomed.result(timeout=60)
            assert doomed.status == "failed"
            res = fine.result(timeout=60)
            assert fine.status == "served"
        _assert_tables_identical(res.table, ref.table, "bystander")
        assert srv.limiter.used == srv.result_cache.evictable_bytes, \
            "fault leaked a reservation"
        assert srv.stats()["failed"] == 1
        assert srv.session_stats("victim")["failed"] == 1
        assert srv.session_stats("bystander")["failed"] == 0
    assert srv.limiter.used == 0, "close() left reservations behind"


def test_served_query_events_carry_session_id():
    """Telemetry on: a fused-region fault falls back to the staged
    evaluator INSIDE the served query — the resulting fallback event (and
    every server event) must carry the session id via session_scope."""
    set_option("telemetry.enabled", True)
    plan, bindings = _q1_bindings(600)
    script = faults.FaultScript(
        [faults.FaultSpec("fusion.region", RuntimeError("region boom"))])
    with server.QueryServer(budget_bytes=1 << 28, max_inflight=2) as srv:
        with faults.inject(script):
            ticket = srv.session("s9").submit(plan, bindings)
            ticket.result(timeout=60)  # staged fallback still serves
        assert ticket.status == "served"
        assert script.fired == [("fusion.region", 0)]
        fallbacks = [r for r in ring_events() if r.get("kind") == "fallback"]
        assert fallbacks and all(
            r.get("session") == "s9" for r in fallbacks)
        server_events = [r for r in ring_events()
                         if r.get("kind") == "server"]
        assert server_events and all(
            r.get("session") == "s9" for r in server_events)
        st = srv.session_stats("s9")
        assert st["fallbacks"] >= 1
        assert st["served"] == 1
        assert st["latency_ms_p95"] >= 0.0


def test_live_servers_registry():
    with server.QueryServer(budget_bytes=1 << 26) as srv:
        assert srv in server.live_servers()
    assert srv not in server.live_servers()


def test_inspect_reflects_parked_admission():
    """A query blocked at admission is visible in inspect(): its session,
    held bytes (0 — not granted yet), and the admission.wait span as the
    deepest open frame."""
    set_option("telemetry.enabled", True)
    lim = MemoryLimiter(1000)
    lim.reserve(900)  # external pressure wedges admission
    plan, bindings = _q1_bindings(600)
    picked = threading.Event()

    def probe(seam, seq, ctx):
        if seam == "server.admit":
            picked.set()

    with faults.inject(probe), \
            server.QueryServer(limiter=lim, max_inflight=2,
                               admission_timeout_s=30.0) as srv:
        ticket = srv.session("parked").submit(
            plan, bindings, estimate_bytes=500)
        assert picked.wait(10)
        # poll briefly: the worker enters the admission span just after
        # the seam fires
        deadline = time.monotonic() + 10
        snap = None
        while time.monotonic() < deadline:
            snap = srv.inspect()
            if (snap["inflight"]
                    and snap["inflight"][0]["current_span"]
                    == "admission.wait"):
                break
            time.sleep(0.01)
        assert snap["inflight"], "parked query missing from inspect()"
        (q,) = snap["inflight"]
        assert q["session"] == "parked"
        assert q["current_span"] == "admission.wait"
        assert q["held_bytes"] == 0  # nothing granted while parked
        assert q["status"] == "queued"  # not yet "admitted"
        assert snap["limiter"]["used"] == 900
        assert snap["limiter"]["admission_waiters"] >= 1
        lim.release(900)
        ticket.result(timeout=60)
        assert ticket.status == "served"
        assert srv.inspect()["inflight"] == []
    assert lim.used == 0


def test_degrade_step_dumps_flight_record(tmp_path):
    """Injected pressure at the fused tier steps the ladder down; the
    step's degrade event must reference a flight-record artifact whose
    tree shows the failed rung."""
    import json as _json

    set_option("telemetry.enabled", True)
    set_option("telemetry.flight_recorder_path", str(tmp_path))
    plan, bindings = _q1_bindings(600)
    ref = fusion.execute(plan, dict(bindings))
    script = faults.FaultScript(
        [faults.FaultSpec(
            "fusion.region",
            server.resilience.ResourceExhausted("injected pressure"),
            seq=0)])
    with server.QueryServer(budget_bytes=1 << 28, max_inflight=2) as srv:
        with faults.inject(script):
            ticket = srv.session("s1").submit(plan, bindings)
            res = ticket.result(timeout=120)
        assert ticket.status == "served"
    _assert_tables_identical(res.table, ref.table, "degraded")
    steps = [r for r in ring_events()
             if r.get("kind") == "degrade" and r.get("event") == "step"]
    assert steps, "no degrade step recorded"
    path = steps[0].get("flight_record")
    assert path, "step event carries no flight_record reference"
    art = _json.loads(open(path).read())
    assert art["trigger"] == "degrade_step"
    assert art["session"] == "s1"
    assert art["tree"]["name"].startswith("query.")
    rungs = [c["name"] for c in art["tree"]["children"]
             if c["name"].startswith("rung.")]
    assert "rung.fused" in rungs
    assert art["state"]["limiter"]["budget"] == 1 << 28
    # the query's own span tree records the degraded outcome
    # (the client's root query.result.<plan> closes after it, status ok)
    by_op = {r["op"]: r for r in ring_events() if r.get("kind") == "span"}
    assert by_op["query.tpch_q1"]["status"] == "degraded"
    assert by_op["query.result.tpch_q1"]["status"] == "ok"


def test_rejection_carries_flight_record(tmp_path):
    set_option("telemetry.enabled", True)
    set_option("telemetry.flight_recorder_path", str(tmp_path))
    lim = MemoryLimiter(1000)
    lim.reserve(900)
    plan, bindings = _q1_bindings(600)
    with server.QueryServer(limiter=lim, max_inflight=2,
                            admission_timeout_s=0.2) as srv:
        ticket = srv.session("s").submit(
            plan, bindings, estimate_bytes=500)
        with pytest.raises(server.QueryRejected) as ei:
            ticket.result(timeout=30)
        assert ei.value.flight_record
        import json as _json
        art = _json.loads(open(ei.value.flight_record).read())
        assert art["trigger"] == "rejected"
        assert art["state"]["limiter"]["used"] == 900
    lim.release(900)


def test_server_seams_registered():
    assert "server.admit" in faults.SEAMS
    assert "server.execute" in faults.SEAMS


def test_server_config_defaults():
    assert get_option("server.max_inflight") == 4
    assert get_option("server.hbm_budget_bytes") == 1 << 30
    assert get_option("server.admission_timeout_s") == 30.0
    assert get_option("server.queue_depth") == 64
    assert get_option("server.estimate_headroom") == 1.5


# ---------------------------------------------------------------------------
# shared learned-estimate state: N replica writers, one file
# ---------------------------------------------------------------------------


def test_learned_estimates_two_writers_merge_not_clobber(tmp_path):
    """Two servers (the in-process stand-in for two fleet replica
    processes) debounce-write ONE estimate file: the flock + merge-on-
    load discipline means the second writer folds the first writer's
    signatures in instead of clobbering them (the old tmp+replace was
    last-writer-wins)."""
    import json

    est = tmp_path / "learned_estimates.json"
    set_option("server.estimate_path", str(est))
    plan1, b1 = _q1_bindings(600)
    plan6 = _q6_plan()
    b6 = {"lineitem": tpch.lineitem_table(600, seed=5)}
    sig1 = server.QueryServer._plan_signature(plan1, b1)
    sig6 = server.QueryServer._plan_signature(plan6, b6)
    assert sig1 != sig6
    with server.QueryServer() as a, server.QueryServer() as b:
        # each writer learns a DIFFERENT signature, then both flush —
        # writer b must not erase what writer a persisted
        a.session("sa").submit(plan1, b1).result(timeout=120)
        b.session("sb").submit(plan6, b6).result(timeout=120)
        a.flush_learned()
        b.flush_learned()
        state = json.loads(est.read_text())
        assert sig1 in state and state[sig1] > 0, state
        assert sig6 in state and state[sig6] > 0, state
        # flush also back-fills sibling learning into the writer: b now
        # warm-admits a's signature without ever having served it
        with b._learned_lock:
            assert sig1 in b._learned
    # a newcomer merges the whole file on load (fleet warm restart)
    with server.QueryServer() as c:
        with c._learned_lock:
            assert sig1 in c._learned and sig6 in c._learned
    reset_option("server.estimate_path")


# ---------------------------------------------------------------------------
# AOT warmup at boot (server.warmup_top_n)
# ---------------------------------------------------------------------------


def test_warmup_precompiles_top_signatures(tmp_path):
    """warmup() ranks the learned-estimate file by cost and precompiles
    the top-N signatures through their registered builders (models/tpch
    registers q1/q1_planned/q6 at import); a signature with no builder
    skips — it can never fail the boot."""
    import json

    from spark_rapids_jni_tpu.models import tpch as _tpch  # noqa: F401

    est = tmp_path / "learned_estimates.json"
    est.write_text(json.dumps({
        "tpch_q1@512": 9.0,       # costliest: registered builder
        "nosuch_plan@512": 8.0,   # no builder -> skipped, not failed
        "tpch_q6@512": 1.0,       # cheap: outside top_n=2, never touched
    }))
    set_option("server.estimate_path", str(est))
    try:
        with server.QueryServer() as srv:
            c0 = sum(REGISTRY.counters("dispatch.compile.").values())
            summary = srv.warmup(top_n=2)
            assert summary == {"attempted": 1, "compiled": 1,
                               "skipped": 1, "failed": 0}
            # the builder really traced+compiled something
            assert sum(REGISTRY.counters("dispatch.compile.").values()) > c0
        assert REGISTRY.counters("server.").get(
            "server.warmup_compiled", 0) == 1
        assert REGISTRY.counters("server.").get(
            "server.warmup_skipped", 0) == 1
    finally:
        reset_option("server.estimate_path")


def test_warmup_off_by_default_and_failure_never_raises(tmp_path):
    """top_n=0 (the default) is a no-op; a builder that blows up is
    counted failed and logged, never raised — warmup cannot fail a
    replica boot."""
    import json

    est = tmp_path / "learned_estimates.json"
    est.write_text(json.dumps({"exploding_plan@256": 5.0}))
    set_option("server.estimate_path", str(est))

    def _boom(rows):
        raise RuntimeError("kaboom")

    server.register_warmup_builder("exploding_plan", _boom)
    try:
        with server.QueryServer() as srv:
            assert srv.warmup(top_n=0) == {
                "attempted": 0, "compiled": 0, "skipped": 0, "failed": 0}
            summary = srv.warmup(top_n=1)
            assert summary["failed"] == 1 and summary["compiled"] == 0
        assert REGISTRY.counters("server.").get(
            "server.warmup_failed", 0) == 1
    finally:
        server._WARMUP_BUILDERS.pop("exploding_plan", None)
        reset_option("server.estimate_path")


def test_warmup_builder_registration_validates():
    with pytest.raises(ValueError):
        server.register_warmup_builder("", lambda rows: None)
    with pytest.raises(TypeError):
        server.register_warmup_builder("not_callable", 42)


# ---------------------------------------------------------------------------
# one request, one span tree from submit to resolve (ISSUE 25)
# ---------------------------------------------------------------------------


def _request_spans(ticket, want_roots, timeout=10.0):
    """The span records of ``ticket``'s request, once ``want_roots`` of its
    roots have closed (the worker's root closes just after the ticket
    resolves): ({op: record} of the roots, [records of their trees])."""
    deadline = time.monotonic() + timeout
    while True:
        recs = [r for r in ring_events() if r.get("kind") == "span"]
        roots = [r for r in recs if r["parent"] is None
                 and r.get("request") == ticket.request]
        if len(roots) >= want_roots or time.monotonic() > deadline:
            break
        time.sleep(0.005)
    ids = {r["span"] for r in roots}
    return ({r["op"]: r for r in roots},
            [r for r in recs if r["root"] in ids])


def _table_bytes(table):
    return sum(np.asarray(buf).nbytes for col in table.columns
               for buf in (col.data, col.validity, col.chars)
               if buf is not None)


def test_served_miss_is_three_trees_joined_by_request():
    from spark_rapids_jni_tpu.telemetry import spans

    set_option("telemetry.enabled", True)
    plan, bindings = _q1_bindings(600)
    with server.QueryServer(budget_bytes=1 << 28) as srv:
        ticket = srv.session("s1").submit(plan, bindings)
        ticket.result(timeout=60)
        roots, tree = _request_spans(ticket, 3)
    assert set(roots) == {"submit.tpch_q1", "query.tpch_q1",
                          "query.result.tpch_q1"}
    sub, qry, res = (roots["submit.tpch_q1"], roots["query.tpch_q1"],
                     roots["query.result.tpch_q1"])
    assert sub["request"] == qry["request"] == res["request"] == (
        ticket.request)
    assert qry["caused_by"] == res["caused_by"] == sub["span"] == (
        ticket._submit_span)
    assert "caused_by" not in sub
    # two threads: the client's holds the first and the third tree
    assert sub["tid"] == res["tid"] == threading.get_ident() != qry["tid"]
    assert spans.validate(tree) == []

    def names_under(root):
        return {r["op"] for r in tree if r["root"] == root["span"]}

    assert names_under(sub) == {
        "submit.tpch_q1", "cache.fingerprint", "cache.fingerprint.copy",
        "cache.fingerprint.hash", "cache.lookup", "admission.enqueue"}
    assert names_under(qry) >= {
        "query.tpch_q1", "admission.queue", "admission.wait",
        "server.stage_bindings", "rung.fused", "region.tpch_q1",
        "dispatch.pad", "dispatch.execute", "server.record_actual",
        "cache.put", "ticket.resolve"}
    assert names_under(res) == {"query.result.tpch_q1", "ticket.wait",
                                "ticket.wake"}
    # every buffer of this table is under the digest's threshold: each
    # crossed to the host whole, so the two halves carry the same bytes
    for half in ("copy", "hash"):
        assert sum(r["nbytes"] for r in tree
                   if r["op"] == f"cache.fingerprint.{half}") == (
            _table_bytes(bindings["lineitem"]))
    # what ran after the region is inside the client's latency and now
    # inside a span: it ends before the root does
    put = next(r for r in tree if r["op"] == "cache.put")
    assert qry["t0"] <= put["t0"] <= put["t1"] <= qry["t1"]
    # one request, counted once: exactly one root no other span caused
    assert [r["op"] for r in tree if r.get("parent") is None
            and "caused_by" not in r] == ["submit.tpch_q1"]
    # four records more a request than before them
    assert [sum(r["op"] == n for r in tree) for n in (
        "query.result.tpch_q1", "ticket.wait", "ticket.wake",
        "ticket.resolve")] == [1, 1, 1, 1]


def test_the_clients_wait_parts_at_the_workers_stamp():
    """``ticket.wait`` + ``ticket.wake`` cover ``query.result.<plan>``, and
    they part at the moment the worker stamped inside ``ticket.resolve``."""
    set_option("telemetry.enabled", True)
    plan, bindings = _q1_bindings(600)
    with server.QueryServer(budget_bytes=1 << 28) as srv:
        ticket = srv.session("s1").submit(plan, bindings)
        ticket.result(timeout=60)
        roots, tree = _request_spans(ticket, 3)
    by_op = {r["op"]: r for r in tree}
    res, wait, wake, resolve = (by_op[n] for n in (
        "query.result.tpch_q1", "ticket.wait", "ticket.wake",
        "ticket.resolve"))
    assert wait["parent"] == wake["parent"] == res["span"]
    assert resolve["parent"] == roots["query.tpch_q1"]["span"]
    assert wait["t0"] == res["t0"]
    assert wait["t1"] == wake["t0"] == ticket._resolved_at
    assert resolve["t0"] <= ticket._resolved_at <= resolve["t1"]
    assert wake["t1"] == ticket._returned_at <= res["t1"]
    assert ticket.wake_s == wake["t1"] - wake["t0"] > 0
    # the root closes two records after its children end: microseconds
    assert res["t1"] - wake["t1"] < 5e-3


def test_result_called_twice_opens_one_root():
    set_option("telemetry.enabled", True)
    plan, bindings = _q1_bindings(600)
    with server.QueryServer(budget_bytes=1 << 28) as srv:
        ticket = srv.session("s1").submit(plan, bindings)
        first = ticket.result(timeout=60)
        wake = ticket.wake_s
        assert ticket.result(timeout=60) is first
        roots, tree = _request_spans(ticket, 3)
    assert ticket.wake_s == wake
    assert [r["op"] for r in tree].count("query.result.tpch_q1") == 1
    assert [r["op"] for r in tree].count("ticket.wake") == 1


def test_telemetry_off_records_no_client_end_and_installs_no_gc_hook():
    from spark_rapids_jni_tpu.telemetry import gcwatch

    assert not get_option("telemetry.enabled")
    plan, bindings = _q1_bindings(600)
    with server.QueryServer(budget_bytes=1 << 28) as srv:
        assert not gcwatch.installed()
        ticket = srv.session("s1").submit(plan, bindings)
        ticket.result(timeout=60)
        assert ticket.status == "served"
        assert (ticket._resolved_at, ticket._returned_at, ticket.wake_s,
                ticket._result_root) == (None, None, None, None)
        assert not ticket._awaited and not ticket._judged
        assert srv._latencies == {}
    assert ring_events() == []
    assert "server.slow_requests" not in REGISTRY.counters()
    assert "host.gc_pauses" not in REGISTRY.counters()


def test_a_slow_request_keeps_its_trees(monkeypatch):
    """One request of 40 stalls at the seam ``server.execute``: it is
    counted once, its record holds its three trees, the warning line names
    the span that slept, and 40 later requests do not push the record out
    of the flight recorder (whose ring holds 16 trees)."""
    from spark_rapids_jni_tpu.telemetry import spans

    set_option("telemetry.enabled", True)
    spans.reset()
    said = []
    monkeypatch.setattr(server._log, "warning",
                        lambda msg, *args: said.append(msg % args))
    stall = {"at": 30, "seen": 0}

    def probe(seam, seq, ctx):
        if seam == "server.execute":
            stall["seen"] += 1
            # a floor under every request, so that a busy host's jitter is
            # small against the median; the one stall on top of it
            time.sleep(0.32 if stall["seen"] == stall["at"] else 0.02)

    plan = tpch._q1_plan()
    tickets = []
    with faults.inject(probe), \
            server.QueryServer(budget_bytes=1 << 28) as srv:
        session = srv.session("s1")
        for i in range(80):
            ticket = session.submit(
                plan, {"lineitem": tpch.lineitem_table(600, seed=i)})
            ticket.result(timeout=60)
            tickets.append(ticket)
            if i == 39:
                # the worker closes its root just after the client's return
                _request_spans(ticket, 3)
                slow_after_40 = REGISTRY.counters()["server.slow_requests"]
        _request_spans(tickets[-1], 3)
        (known,) = srv._latencies.values()
    stalled = tickets[stall["at"] - 1]
    assert all(t._judged for t in tickets)
    assert len(known) == 64    # of 80
    mine = [r for r in spans.flight_records() if r["trigger"] == "slow"
            and r["state"]["request"] == stalled.request]
    assert len(mine) == 1 and slow_after_40 >= 1
    assert REGISTRY.counters()["server.slow_requests"] == sum(
        r["trigger"] == "slow" for r in spans.flight_records())
    (record,) = mine
    assert [t["name"] for t in record["trees"]] == [
        "submit.tpch_q1", "query.tpch_q1", "query.result.tpch_q1"]
    assert record["tree"] == record["trees"][0]
    assert all(t["status"] == "ok" and t["t1"] is not None
               for t in record["trees"])
    assert [c["name"] for c in record["trees"][2]["children"]] == [
        "ticket.wait", "ticket.wake"]
    assert record["state"]["latency_s"] > 0.3 > 2 * record["state"]["median_s"]
    assert record["state"]["limiter"]["budget"] == 1 << 28
    assert record["state"]["gc"] == [
        r for r in ring_events() if r.get("kind") == "gc"
        and r["t1"] > stalled._submitted_at
        and r["t0"] < stalled._submitted_at + record["state"]["latency_s"]]
    # the seam fires under the worker's root alone: the root slept
    (line,) = [m for m in said if f"request {stalled.request})" in m]
    assert line.startswith("slow request: tpch_q1 (session s1,")
    assert "largest self times query.tpch_q1 0.3" in line
    assert "gc pause inside 0." in line


def test_a_ticket_nobody_awaits_is_judged_to_its_resolve():
    set_option("telemetry.enabled", True)
    plan, bindings = _q1_bindings(600)
    with server.QueryServer(budget_bytes=1 << 28) as srv:
        ticket = srv.session("s1").submit(plan, bindings)
        deadline = time.monotonic() + 60
        while not ticket._judged and time.monotonic() < deadline:
            time.sleep(0.005)
        (known,) = srv._latencies.values()
        assert list(known) == [ticket._resolved_at - ticket._submitted_at]
        ticket.result(timeout=60)      # too late to count: judged already
        assert len(known) == 1 and ticket.wake_s == 0.0


def test_a_collection_inside_a_request_is_on_record():
    """A forced ``gc.collect()`` (generation 2) while a request is served
    gives a ``gc`` record inside the request's interval and moves both
    counters; the hook is there while a server with telemetry is."""
    import gc

    from spark_rapids_jni_tpu.telemetry import gcwatch

    set_option("telemetry.enabled", True)

    def probe(seam, seq, ctx):
        if seam == "server.execute":
            gc.collect()

    plan, bindings = _q1_bindings(600)
    with server.QueryServer(budget_bytes=1 << 28) as srv:
        assert gcwatch.installed()
        with server.QueryServer(budget_bytes=1 << 28):
            pass
        assert gcwatch.installed()     # the first server still holds it
        before = REGISTRY.counters()
        assert before["host.gc_pauses"] >= 0 <= before["host.gc_pause_ns"]
        with faults.inject(probe):
            ticket = srv.session("s1").submit(plan, bindings)
            ticket.result(timeout=60)
        roots, _ = _request_spans(ticket, 3)
    assert not gcwatch.installed()
    after = REGISTRY.counters()
    assert after["host.gc_pauses"] > before["host.gc_pauses"]
    assert after["host.gc_pause_ns"] > before["host.gc_pause_ns"]
    qry = roots["query.tpch_q1"]
    full = [r for r in ring_events() if r.get("kind") == "gc"
            and r["generation"] == 2
            and qry["t0"] <= r["t0"] <= r["t1"] <= qry["t1"]]
    assert full and all(r["collected"] >= 0 and r["op"] == "gc"
                        for r in full)


def test_served_hit_is_one_tree():
    set_option("telemetry.enabled", True)
    plan, bindings = _q1_bindings(600)
    with server.QueryServer(budget_bytes=1 << 28) as srv:
        session = srv.session("s1")
        session.submit(plan, bindings).result(timeout=60)
        ticket = session.submit(plan, bindings)
        assert ticket.done() and ticket.status == "served"
        roots, tree = _request_spans(ticket, 1)
        # a ticket resolved before it is asked: the client's root is empty
        ticket.result(timeout=60)
        assert ticket.wake_s == 0.0
        (res,) = [r for r in ring_events() if r.get("kind") == "span"
                  and r.get("request") == ticket.request
                  and r["op"] == "query.result.tpch_q1"]
        assert not [r for r in ring_events() if r.get("root") == res["span"]
                    and r is not res]
        assert res["t1"] - res["t0"] < 5e-3
    assert set(roots) == {"submit.tpch_q1"}
    by_op = {r["op"]: r for r in tree}
    assert set(by_op) == {"submit.tpch_q1", "cache.fingerprint",
                          "cache.lookup", "query.tpch_q1", "cache.hit"}
    assert by_op["query.tpch_q1"]["parent"] == roots["submit.tpch_q1"]["span"]
    assert by_op["cache.hit"]["parent"] == by_op["query.tpch_q1"]["span"]
    # the table's fingerprint is memoized: nothing was fingerprinted
    # again, on the device or on the host, and nothing crossed
    assert by_op["cache.fingerprint"]["nbytes"] == 0


@pytest.mark.parametrize("rows, digested", [(700, 0), (150_000, 4)])
def test_fingerprint_spans_and_counter_account_for_the_tables_bytes(
        rows, digested):
    """``.hash`` carries the bytes fingerprinted, ``.copy`` the bytes that
    crossed to the host: the whole buffer under the digest's threshold, the
    digest's 16 bytes over it (at 150,000 rows the four int64 columns)."""
    set_option("telemetry.enabled", True)
    plan, bindings = _q1_bindings(rows)
    want = _table_bytes(bindings["lineitem"])
    before = REGISTRY.counters().get("cache.fingerprint_bytes", 0)
    with server.QueryServer(budget_bytes=1 << 28) as srv:
        ticket = srv.session("s1").submit(plan, bindings)
        ticket.result(timeout=60)
        _, tree = _request_spans(ticket, 2)
    halves = {half: [r["nbytes"] for r in tree
                     if r["op"] == f"cache.fingerprint.{half}"]
              for half in ("copy", "hash")}
    assert len(halves["copy"]) == len(halves["hash"]) == 7
    assert sum(halves["hash"]) == want
    assert sum(halves["copy"]) == want - digested * (8 * rows - 16)
    assert halves["copy"].count(16) == digested
    (whole,) = [r for r in tree if r["op"] == "cache.fingerprint"]
    assert whole["nbytes"] == want
    assert REGISTRY.counters()["cache.fingerprint_bytes"] - before == want


@pytest.mark.parametrize("rows, digested", [(700, 0), (150_000, 4)])
def test_fingerprint_device_bytes_counts_what_was_digested_in_place(
        rows, digested):
    """Written by every fingerprint (0 where every buffer is small), so a
    reader can tell a program without the digest from one that did not
    engage it."""
    plan, bindings = _q1_bindings(rows)
    assert "cache.fingerprint_device_bytes" not in REGISTRY.counters()
    with server.QueryServer(budget_bytes=1 << 28) as srv:
        srv.session("s1").submit(plan, bindings).result(timeout=60)
    c = REGISTRY.counters()
    assert c["cache.fingerprint_device_bytes"] == digested * 8 * rows
    assert c.get("dispatch.compile.cache_digest", 0) == (1 if digested else 0)


@pytest.mark.parametrize("rows, padded", [(600, True), (1024, False)])
def test_padded_copy_bytes_counts_the_whole_copy(rows, padded):
    """Off a bucket boundary every data leaf is copied to a bucket-sized
    buffer (bucket rows x row bytes); on one, none is."""
    plan, bindings = _q1_bindings(rows)
    row_bytes = sum(np.dtype(c.data.dtype).itemsize
                    for c in bindings["lineitem"].columns)
    with server.QueryServer(budget_bytes=1 << 28) as srv:
        srv.session("s1").submit(plan, bindings).result(timeout=60)
    c = REGISTRY.counters()
    assert c.get("dispatch.padded_copy_bytes", 0) == (
        1024 * row_bytes if padded else 0)
    assert c.get("dispatch.padded_waste_bytes", 0) == (
        (1024 - rows) * row_bytes)


def _dispatch_moved(before):
    now = REGISTRY.counters("dispatch.")
    return {k: v - before.get(k, 0) for k, v in now.items()
            if v != before.get(k, 0)}


def test_a_served_request_pads_through_one_cached_executable():
    """The first request of a row count compiles its pad (beside its
    region); every later one of that row count, over fresh rows, is one hit
    of the pad and one of the region and compiles nothing. A second row
    count in the bucket compiles a pad and no region."""
    with server.QueryServer(budget_bytes=1 << 28) as srv:
        session = srv.session("s1")

        def served(rows, seed):
            before = REGISTRY.counters("dispatch.")
            plan, bindings = _q1_bindings(rows, seed=seed)
            session.submit(plan, bindings).result(timeout=60)
            return _dispatch_moved(before)

        first = served(600, 0)
        assert first["dispatch.compile.pad"] == 1
        assert first["dispatch.compile.fusion.tpch_q1"] == 1
        assert first["dispatch.pad.jitted"] == 1
        for seed in (1, 2, 3):
            moved = served(600, seed)
            assert moved["dispatch.hit.pad"] == 1
            assert moved["dispatch.hit.fusion.tpch_q1"] == 1
            assert moved["dispatch.pad.jitted"] == 1
            assert "dispatch.compile" not in moved
            assert not any(k.startswith("dispatch.inline") for k in moved)
        other = served(700, 4)
        assert other["dispatch.compile.pad"] == 1
        assert other["dispatch.hit.fusion.tpch_q1"] == 1
        assert other["dispatch.compile"] == 1


def test_a_pad_that_fails_still_serves_the_right_answer(monkeypatch):
    """``dispatch.call`` takes the inline path (``pad_error``) and the
    region answers un-jitted over the rows as they came; the ops inside it
    then dispatch one by one, and their pads fail alike."""
    plan, bindings = _q1_bindings(600)
    want = fusion.execute(plan, bindings)
    real = dispatch.compiled

    def broken(op, fn, *args, **kw):
        if op == "pad":
            raise RuntimeError("injected pad failure")
        return real(op, fn, *args, **kw)

    monkeypatch.setattr(dispatch, "compiled", broken)
    before = REGISTRY.counters("dispatch.")
    with server.QueryServer(budget_bytes=1 << 28) as srv:
        ticket = srv.session("s1").submit(
            plan, {"lineitem": tpch.lineitem_table(600)})
        got = ticket.result(timeout=60)
    _assert_tables_identical(got.table, want.table, "q1 after a failed pad")
    moved = _dispatch_moved(before)
    assert moved["dispatch.inline.pad_error"] >= 1
    assert moved["dispatch.inline"] == moved["dispatch.inline.pad_error"]
    assert "dispatch.pad.jitted" not in moved


def test_admission_queue_starts_at_enqueue_not_at_ticket_creation(
        monkeypatch):
    """A slow fingerprint sits between the ticket's creation and its
    enqueue: ``queue_wait_s`` (the deadline clock) counts it, the
    ``admission.queue`` span does not."""
    from spark_rapids_jni_tpu.runtime import resultcache

    set_option("telemetry.enabled", True)
    real = resultcache.cache_key

    def slow_cache_key(*args, **kwargs):
        time.sleep(0.25)
        return real(*args, **kwargs)

    monkeypatch.setattr(resultcache, "cache_key", slow_cache_key)
    plan, bindings = _q1_bindings(600)
    with server.QueryServer(budget_bytes=1 << 28) as srv:
        ticket = srv.session("s1").submit(plan, bindings)
        ticket.result(timeout=60)
        roots, tree = _request_spans(ticket, 2)
    (queue,) = [r for r in tree if r["op"] == "admission.queue"]
    assert queue["parent"] == roots["query.tpch_q1"]["span"]
    assert queue["t0"] == ticket._enqueued_at
    assert queue["t0"] - ticket._submitted_at >= 0.25
    assert queue["t1"] <= roots["query.tpch_q1"]["t0"] + 1e-3
    assert ticket.queue_wait_s >= 0.25


def test_region_module_is_named_after_the_plan():
    """The compiled module's name is what a device trace shows: it has to
    tell one plan's region from another's."""
    plan, bindings = _q1_bindings(600)
    fusion.execute(plan, bindings)
    (compiled,) = [exe for k, (exe, _) in dispatch._EXEC_CACHE.items()
                   if k[0] == "fusion.tpch_q1"]
    head = compiled.as_text().split("\n", 1)[0]
    assert "jit_region_tpch_q1" in head, head
    assert "jit__region" not in head
