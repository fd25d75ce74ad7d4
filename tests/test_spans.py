"""Hierarchical query spans, flight recorder & live introspection
(spark_rapids_jni_tpu/telemetry/spans + the instrumented runtime seams).

Five layers under test:

1. **Span trees** — parentage via the thread-local stack, explicit
   cross-thread parents, status derivation from exceptions, and the
   well-formedness checker (``spans.validate``).
2. **Zero-overhead contract** — ``telemetry.enabled=false`` emits zero
   records and hands every call site the shared ``NULL_SPAN``.
3. **Flight recorder** — the bounded ring of recent trees, and the
   structured dump artifact written on degrade/cancel/failure.
4. **Exports** — Chrome-trace JSON, the Prometheus-style
   ``Registry.exposition()`` text, per-phase breakdown, and the
   ``trace`` / ``top`` / filtered-``report`` CLI.
5. **Thread safety** — 16 concurrent sessions hammering counters,
   histograms and span trees produce a consistent snapshot and
   well-formed trees.
"""

import json
import threading

import pytest

from spark_rapids_jni_tpu import telemetry
from spark_rapids_jni_tpu.telemetry import spans
from spark_rapids_jni_tpu.telemetry.__main__ import main as telemetry_cli
from spark_rapids_jni_tpu.telemetry.events import session_scope
from spark_rapids_jni_tpu.telemetry.registry import Registry
from spark_rapids_jni_tpu.telemetry.report import (
    filter_records,
    load_jsonl,
)
from spark_rapids_jni_tpu.telemetry.top import render_top
from spark_rapids_jni_tpu.utils import config
from spark_rapids_jni_tpu.utils.tracing import trace_range


@pytest.fixture(autouse=True)
def _reset():
    telemetry.drain()
    telemetry.REGISTRY.reset()
    spans.reset()
    yield
    telemetry.drain()
    telemetry.REGISTRY.reset()
    spans.reset()
    for name in list(config._overrides):
        config.reset_option(name)


@pytest.fixture
def enabled(tmp_path):
    path = tmp_path / "run.jsonl"
    config.set_option("telemetry.enabled", True)
    config.set_option("telemetry.path", str(path))
    return path


def _span_records():
    return [r for r in telemetry.events() if r.get("kind") == "span"]


# ---------------------------------------------------------------------------
# span trees
# ---------------------------------------------------------------------------


def test_span_tree_parentage(enabled):
    with spans.span("query.q") as q:
        with spans.child("admission.wait") as a:
            pass
        with spans.child("rung.fused") as r:
            with spans.child("region.q") as g:
                pass
    recs = _span_records()
    by_op = {r["op"]: r for r in recs}
    assert set(by_op) == {"query.q", "admission.wait", "rung.fused",
                          "region.q"}
    root = by_op["query.q"]
    assert root["parent"] is None
    assert by_op["admission.wait"]["parent"] == root["span"]
    assert by_op["rung.fused"]["parent"] == root["span"]
    assert by_op["region.q"]["parent"] == by_op["rung.fused"]["span"]
    assert all(r["root"] == root["span"] for r in recs)
    assert spans.validate(recs) == []
    # children close before parents; every record has end >= start
    assert all(r["t1"] >= r["t0"] for r in recs)
    assert q.id == root["span"] and a.id and r.id and g.id


def test_span_status_from_exception(enabled):
    with pytest.raises(ValueError):
        with spans.span("query.q"):
            with spans.child("rung.fused"):
                raise ValueError("boom")
    by_op = {r["op"]: r for r in _span_records()}
    assert by_op["rung.fused"]["status"] == "failed"
    assert by_op["rung.fused"]["error"] == "ValueError"
    assert by_op["query.q"]["status"] == "failed"


def test_span_status_cancelled(enabled):
    from spark_rapids_jni_tpu.runtime.resilience import QueryCancelled
    with pytest.raises(QueryCancelled):
        with spans.span("query.q"):
            raise QueryCancelled("deadline")
    (rec,) = _span_records()
    assert rec["status"] == "cancelled"


def test_explicit_status_wins(enabled):
    with spans.span("query.q") as q:
        q.set_status("degraded")
    (rec,) = _span_records()
    assert rec["status"] == "degraded"
    with pytest.raises(ValueError):
        q.set_status("bogus")


def test_cross_thread_parent(enabled):
    done = threading.Event()
    with spans.span("query.q") as q:
        def worker():
            # pool-thread idiom: empty local stack, explicit parent
            with spans.child("pipeline.chunk", parent=q, seq=0):
                with spans.child("pipeline.decode"):
                    pass
            done.set()
        t = threading.Thread(target=worker)
        t.start()
        t.join()
    assert done.is_set()
    recs = _span_records()
    assert spans.validate(recs) == []
    by_op = {r["op"]: r for r in recs}
    assert by_op["pipeline.chunk"]["parent"] == by_op["query.q"]["span"]
    assert (by_op["pipeline.decode"]["parent"]
            == by_op["pipeline.chunk"]["span"])


def test_child_without_parent_is_null(enabled):
    # a bare child() at top level must not fabricate an orphan root
    assert spans.child("pipeline.decode") is spans.NULL_SPAN
    with spans.child("pipeline.decode"):
        pass
    assert _span_records() == []


def test_record_child_with_a_given_start(enabled):
    """A wait that began on another thread: the child starts where it is
    told, ends now, and opens nothing on this thread's stack."""
    import time

    spans.record_child("admission.queue", 1.0)   # no tree: nothing
    assert _span_records() == []
    began = time.monotonic() - 0.5
    with spans.span("query.q") as q:
        spans.record_child("admission.queue", began, session="s")
        assert spans.current_span() is q
        (node,) = q.tree()["children"]
        assert node["name"] == "admission.queue" and node["status"] == "ok"
    by_op = {r["op"]: r for r in _span_records()}
    rec = by_op["admission.queue"]
    assert rec["parent"] == by_op["query.q"]["span"]
    assert rec["t0"] == began and rec["session"] == "s"
    assert 0.5 <= rec["t1"] - rec["t0"] < 5.0
    assert rec["t1"] <= by_op["query.q"]["t1"]
    assert spans.validate(_span_records()) == []


def test_record_child_with_a_given_end(enabled):
    """An interval that ended at a moment another thread stamped: the
    child ends where it is told, not now."""
    import time

    began = time.monotonic() - 0.5
    with spans.span("query.result.q") as root:
        spans.record_child("ticket.wait", began, began + 0.2, session="s")
        spans.record_child("ticket.wake", began + 0.2, began + 0.25)
        assert [c["name"] for c in root.tree()["children"]] == [
            "ticket.wait", "ticket.wake"]
    by_op = {r["op"]: r for r in _span_records()}
    wait, wake = by_op["ticket.wait"], by_op["ticket.wake"]
    assert (wait["t0"], wait["t1"]) == (began, began + 0.2)
    assert (wake["t0"], wake["t1"]) == (began + 0.2, began + 0.25)
    assert wait["dur_ms"] == pytest.approx(200.0) and wait["session"] == "s"
    assert wait["parent"] == wake["parent"] == by_op["query.result.q"]["span"]
    assert spans.validate(_span_records()) == []


def test_self_times_of_a_tree(enabled):
    """A node's duration less what its children cover of it: overlapping
    children count once, one that lies before its parent explains none."""
    with spans.span("query.q") as root:
        pass
    root.start, root.end = 10.0, 20.0
    for name, t0, t1 in (("admission.queue", 8.0, 10.0), ("scan", 11.0, 15.0),
                         ("scan", 13.0, 17.0), ("cache.put", 18.0, 19.0)):
        kid = spans.Span(name, root, {})
        kid._attach()
        kid.start, kid.end = t0, t1
    assert spans.self_times(root) == {
        "query.q": 3.0, "admission.queue": 2.0, "scan": 8.0,
        "cache.put": 1.0}


def test_spans_land_in_a_profiler_trace(enabled, tmp_path):
    """Under a profiler session every span is also a TraceAnnotation of
    its name in the host plane, carrying its id and the request's."""
    import glob

    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path / "trace"),
                             profiler_options=options)
    try:
        with spans.span("submit.q", request=41) as root:
            with spans.child("cache.fingerprint"):
                with trace_range("cache.fingerprint.copy"):
                    pass
            spans.record_child("admission.queue", 0.0)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "trace" / "plugins" / "profile"
                            / "*" / "*.xplane.pb"))
    profile = jax.profiler.ProfileData.from_file(path)
    found = {ev.name: dict(ev.stats) for plane in profile.planes
             if plane.name == "/host:CPU"
             for line in plane.lines for ev in line.events}
    assert {"submit.q", "cache.fingerprint",
            "cache.fingerprint.copy"} <= set(found)
    assert found["submit.q"] == {"span": root.id, "request": 41}
    assert found["cache.fingerprint"]["request"] == 41
    # a recorded child has no past to annotate
    assert "admission.queue" not in found


def test_span_tree_node_cap(enabled):
    config.set_option("telemetry.max_spans_per_tree", 4)
    with spans.span("query.q"):
        for i in range(8):
            with spans.child("dispatch.execute", seq=i):
                pass
    # JSONL stays unbounded: every span still emits a record ...
    assert len(_span_records()) == 9
    # ... but the in-memory tree (flight recorder, inspect()) stops at
    # the cap and accounts for the overflow
    (ring_entry,) = spans.flight_records()
    tree = ring_entry["tree"]
    assert len(tree["children"]) == 3  # root + 3 children == 4 nodes
    assert tree["dropped_spans"] == 5


# ---------------------------------------------------------------------------
# zero-overhead contract
# ---------------------------------------------------------------------------


def test_disabled_emits_nothing():
    assert not telemetry.enabled()
    sp = spans.span("query.q")
    assert sp is spans.NULL_SPAN
    with sp:
        with spans.child("rung.fused") as c:
            c.set_status("degraded")
            c.annotate(x=1)
    spans.record_child("admission.queue", 0.0)
    assert telemetry.events() == []
    assert spans.flight_records() == []
    assert not spans.dump_flight_record("failed")


def test_disabled_submit_opens_nothing():
    """The served path with telemetry off: ``submit`` opens no span on the
    client's thread, the worker none, and the ticket names none."""
    from spark_rapids_jni_tpu.models import tpch
    from spark_rapids_jni_tpu.runtime.server import QueryServer

    assert not telemetry.enabled()
    with QueryServer(budget_bytes=1 << 28) as srv:
        ticket = srv.session("s").submit(
            tpch._q1_plan(), {"lineitem": tpch.lineitem_table(600, seed=1)})
        ticket.result(timeout=60)
    assert ticket.status == "served" and ticket.request >= 1
    assert ticket._submit_span is None
    assert spans.current_span() is None
    assert telemetry.events() == []
    assert spans.flight_records() == []


def test_null_span_is_falsy_and_inert():
    assert not spans.NULL_SPAN
    assert spans.NULL_SPAN.id is None
    assert spans.NULL_SPAN.status == "ok"


# ---------------------------------------------------------------------------
# the trace_range seam (satellite 1: errors record too)
# ---------------------------------------------------------------------------


def test_trace_range_nests_under_open_span(enabled):
    with spans.span("query.q"):
        with trace_range("pipeline.decode"):
            pass
    by_op = {r["op"]: r for r in _span_records()}
    assert (by_op["pipeline.decode"]["parent"]
            == by_op["query.q"]["span"])


def test_trace_range_records_error_dispatch(enabled):
    with pytest.raises(RuntimeError):
        with trace_range("groupby_aggregate", record=True):
            raise RuntimeError("device OOM")
    disp = [r for r in telemetry.events() if r.get("kind") == "dispatch"]
    assert len(disp) == 1
    assert disp[0]["op"] == "groupby_aggregate"
    assert disp[0]["status"] == "error"
    assert disp[0]["error"] == "RuntimeError"
    assert disp[0]["wall_ms"] >= 0.0


def test_trace_range_success_has_no_status(enabled):
    with trace_range("groupby_aggregate", record=True):
        pass
    (disp,) = [r for r in telemetry.events() if r.get("kind") == "dispatch"]
    assert "status" not in disp


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------


def test_flight_ring_records_completed_roots(enabled):
    for i in range(3):
        with spans.span(f"query.q{i}"):
            pass
    ring = spans.flight_records()
    assert [r["trigger"] for r in ring] == ["completed"] * 3
    assert [r["tree"]["name"] for r in ring] == ["query.q0", "query.q1",
                                                "query.q2"]


def test_flight_ring_is_bounded(enabled):
    config.set_option("telemetry.flight_recorder_depth", 2)
    for i in range(5):
        with spans.span(f"query.q{i}"):
            pass
    ring = spans.flight_records()
    assert [r["tree"]["name"] for r in ring] == ["query.q3", "query.q4"]


def test_completed_trees_are_serialised_when_the_ring_is_read(enabled):
    """A closed root hands the recorder the tree itself; a reader gets what
    it always got."""
    with spans.span("query.q", session="s") as q:
        with spans.child("rung.fused", rows=7) as c:
            c.set_status("degraded")
    (held,) = spans._RECORDER._ring
    assert held["tree"] is q
    (entry,) = spans.flight_records()
    assert entry == {
        "trigger": "completed", "root": q.id, "seq": held["seq"],
        "tree": {"span": q.id, "name": "query.q", "status": "ok",
                 "t0": q.start, "t1": q.end, "attrs": {"session": "s"},
                 "children": [{
                     "span": c.id, "name": "rung.fused",
                     "status": "degraded", "t0": c.start, "t1": c.end,
                     "attrs": {"rows": 7}, "children": []}]}}
    assert json.loads(json.dumps(spans.flight_records())) == [entry]
    assert spans._RECORDER._ring[0]["tree"] is q   # reading keeps the tree


def test_a_tree_that_leaves_the_ring_is_freed_by_count(enabled):
    """A span tree is a reference cycle. One the recorder has held for a
    few requests is old by the time it is let go: taken apart, it needs no
    collection to be freed."""
    import gc
    import weakref

    gc.collect()
    gc.disable()
    try:
        class Tag:
            pass

        with spans.span("query.q") as q:
            with spans.child("rung.fused") as c:
                pass
        tags = [Tag(), Tag()]        # what only the two spans hold on to
        c.annotate(tag=tags[0])
        q.annotate(tag=tags[1])
        kid, root = (weakref.ref(t) for t in tags)
        del c, tags
        for i in range(16):      # the ring's depth: the first tree leaves
            with spans.span(f"query.q{i}"):
                pass
        assert kid() is None and root() is not None
        assert q.root is None and q.children == []
        assert q.tree()["name"] == "query.q" and q.tree()["children"] == []
        assert spans.self_times(q) == {"query.q": q.end - q.start}
        del q
        assert root() is None
    finally:
        gc.enable()
    assert [r["tree"]["name"] for r in spans.flight_records()] == [
        f"query.q{i}" for i in range(16)]


def test_slow_dumps_are_kept_apart_from_the_ring(enabled):
    """The trees of a request that ran long outlive the next sixteen
    completed roots; the newest 32 such dumps are kept."""
    with spans.span("submit.q", request=5) as a:
        pass
    with spans.span("query.q", request=5) as b:
        pass
    assert spans.dump_flight_record(
        "slow", roots=[a, spans.NULL_SPAN, b], state={"request": 5}) is None
    for i in range(40):
        with spans.span(f"query.q{i}"):
            pass
    ring = spans.flight_records()
    assert [r["trigger"] for r in ring] == ["slow"] + ["completed"] * 16
    slow = ring[0]
    assert slow["root"] == a.id and slow["tree"]["name"] == "submit.q"
    assert [t["name"] for t in slow["trees"]] == ["submit.q", "query.q"]
    assert slow["state"] == {"request": 5}
    assert [r["seq"] for r in ring] == sorted(r["seq"] for r in ring)
    for i in range(40):
        spans.dump_flight_record("slow", roots=[a], state={"request": i})
    kept = [r for r in spans.flight_records() if r["trigger"] == "slow"]
    assert [r["state"]["request"] for r in kept] == list(range(8, 40))
    spans.reset()
    assert spans.flight_records() == []


def test_dump_flight_record_writes_artifact(enabled, tmp_path):
    out = tmp_path / "flights"
    config.set_option("telemetry.flight_recorder_path", str(out))
    with spans.span("query.q") as q:
        with spans.child("rung.staged"):
            path = spans.dump_flight_record(
                "degrade_step", state={"limiter": {"used": 7}})
    assert path is not None
    art = json.loads(open(path).read())
    assert art["trigger"] == "degrade_step"
    assert art["root"] == q.id
    assert art["state"] == {"limiter": {"used": 7}}
    # the tree snapshot captures the OPEN spans at dump time
    assert art["tree"]["name"] == "query.q"
    kids = [c["name"] for c in art["tree"]["children"]]
    assert kids == ["rung.staged"]
    assert "degrade_step" in path and "flight-" in path


def test_dump_flight_record_never_raises_on_bad_dir(enabled):
    config.set_option("telemetry.flight_recorder_path",
                      "/proc/definitely/not/writable")
    with spans.span("query.q"):
        assert spans.dump_flight_record("failed") is None
    assert telemetry.REGISTRY.counter("dropped_writes").value >= 1


# ---------------------------------------------------------------------------
# exports: chrome trace, exposition, phases, CLI
# ---------------------------------------------------------------------------


def test_chrome_trace_shape(enabled):
    with session_scope("s1"):
        with spans.span("query.q"):
            with spans.child("admission.wait"):
                pass
    trace = spans.chrome_trace(telemetry.events())
    assert trace["displayTimeUnit"] == "ms"
    xs = [e for e in trace["traceEvents"] if e["ph"] == "X"]
    metas = [e for e in trace["traceEvents"] if e["ph"] == "M"]
    assert len(xs) == 2 and metas
    root = [e for e in xs if e["name"] == "query.q"][0]
    kid = [e for e in xs if e["name"] == "admission.wait"][0]
    assert kid["ts"] >= root["ts"]
    assert root["args"]["session"] == "s1"
    assert all(e["dur"] > 0 for e in xs)


def test_trace_cli_roundtrip(enabled, tmp_path):
    with spans.span("query.q"):
        with spans.child("rung.fused"):
            pass
    out = tmp_path / "trace.json"
    assert telemetry_cli(["trace", str(enabled), str(out)]) == 0
    doc = json.loads(out.read_text())
    names = {e["name"] for e in doc["traceEvents"] if e["ph"] == "X"}
    assert names == {"query.q", "rung.fused"}


def test_report_session_and_kind_filters(enabled, capsys):
    with session_scope("alpha"):
        telemetry.record_dispatch("op_a", wall_ms=1.0)
        telemetry.record_server("q", "admitted", session="alpha",
                                wait_ms=2.0)
    with session_scope("beta"):
        telemetry.record_dispatch("op_b", wall_ms=2.0)
    recs = load_jsonl(str(enabled))
    assert len(filter_records(recs, session="alpha")) == 2
    assert len(filter_records(recs, kind="server")) == 1
    with pytest.raises(ValueError):
        filter_records(recs, kind="bogus")
    assert telemetry_cli(
        ["report", "--session", "alpha", str(enabled)]) == 0
    out = capsys.readouterr().out
    assert "op_a" in out and "op_b" not in out
    assert "server events:" in out
    assert telemetry_cli(["report", "--kind", "bogus", str(enabled)]) == 2


def test_registry_exposition_format():
    reg = Registry()
    reg.counter("spans.total").inc(3)
    reg.gauge("pipeline.chunks_in_flight").add(2)
    h = reg.histogram("server.latency_ms", bounds=(1.0, 10.0))
    h.observe(0.5)
    h.observe(5.0)
    h.observe(100.0)
    text = reg.exposition()
    assert "# TYPE spans_total counter" in text
    assert "spans_total 3" in text
    assert "pipeline_chunks_in_flight 2" in text
    assert '_bucket{le="1.0"} 1' in text
    assert '_bucket{le="10.0"} 2' in text
    assert '_bucket{le="+Inf"} 3' in text
    assert "server_latency_ms_count 3" in text
    assert text.endswith("\n")


def test_render_top_snapshot():
    text = render_top({
        "limiter": {"used": 1 << 20, "budget": 1 << 22, "peak": 1 << 21,
                    "pressure": True, "waiters": 2, "admission_waiters": 1},
        "queues": {"a": 1}, "queued": 1,
        "inflight": [{"session": "a", "plan": "q1", "status": "admitted",
                      "tier": "outofcore", "rung": 2, "held_bytes": 4096,
                      "age_s": 0.5, "deadline_remaining_s": None,
                      "current_span": "pipeline.decode"}],
    })
    assert "PRESSURE" in text
    assert "outofcore" in text
    assert "pipeline.decode" in text
    assert render_top([]) == "no live query servers in this process"


# ---------------------------------------------------------------------------
# thread safety
# ---------------------------------------------------------------------------


def test_sixteen_sessions_hammer(enabled):
    n_threads, per_thread = 16, 20
    barrier = threading.Barrier(n_threads)
    errors = []

    def worker(i):
        try:
            barrier.wait()
            with session_scope(f"s{i}"):
                for j in range(per_thread):
                    telemetry.REGISTRY.counter("hammer.total").inc()
                    telemetry.REGISTRY.histogram("hammer.ms").observe(j)
                    with spans.span(f"query.s{i}", seq=j):
                        with spans.child("rung.fused"):
                            pass
        except BaseException as exc:  # pragma: no cover - the assertion
            errors.append(exc)

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    total = n_threads * per_thread
    assert telemetry.REGISTRY.counter("hammer.total").value == total
    snap = telemetry.REGISTRY.histogram("hammer.ms").snapshot()
    assert snap["count"] == total
    recs = _span_records()
    assert len(recs) == 2 * total
    assert spans.validate(recs) == []
    # one root per (thread, iteration); every child parents in-tree
    roots = [r for r in recs if r["parent"] is None]
    assert len(roots) == total
    sessions = {r["session"] for r in recs}
    assert sessions == {f"s{i}" for i in range(n_threads)}


def test_hammer_disabled_emits_zero():
    n_threads = 16

    def worker(i):
        for j in range(10):
            with spans.span(f"query.s{i}"):
                with spans.child("rung.fused"):
                    pass

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert telemetry.events() == []
    assert spans.flight_records() == []
