"""The readers of the program's own spans and counters (PR 25): each on the
CPU stand-in at 4,096 rows through ``run_cell``, the helper on made-up
records for the cases that must raise or give nothing, and on a recorded
trace of the chip (two traced requests of ``sf10_q1_planned_fresh`` with
the program's spans in the host plane, my chip run, PR 25)."""

import gzip
import os
from types import SimpleNamespace as NS

import pytest

from benchmark import span_reduce as sr

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                       "sf10_q1_planned_fresh.spans.xplane.pb.gz")
CELLS = ("sf10_q1_planned_fresh", "sf1_q1_general_fresh")
NEW = {   # reader -> (unit, what the CPU stand-in has to read at 4,096 rows)
    "cache.fingerprint_s": ("s", lambda v: 0 < v < 1),
    "cache.fingerprint_copy_s": ("s", lambda v: 0 < v < 1),
    "cache.fingerprint_hash_s": ("s", lambda v: 0 < v < 1),
    # seven data columns, 38 B a row, no validity masks
    "cache.fingerprint_bytes_per_query": ("bytes", lambda v: v == 4096 * 38),
    "admission.wait_s": ("s", lambda v: 0 < v < 1),
    "dispatch.host_s": ("s", lambda v: 0 < v < 1),
    # 4,096 rows sit on a bucket boundary: nothing is copied
    "dispatch.padded_copy_bytes_per_query": ("bytes", lambda v: v == 0),
    "fusion.regions_per_query": ("count", lambda v: v == 1),
    "request.untraced_share": ("%", lambda v: 0 <= v < 50),
    "region.device_s_per_query": ("s", lambda v: 0 < v < 1),
    "dispatch.pad_device_s_per_query": ("s", lambda v: 0 <= v < 1),
    "device.idle_attributed_share": ("%", lambda v: 0 < v <= 100),
}


@pytest.fixture(scope="module")
def traced():
    """One traced run a cell, shared by the cases below."""
    from benchmark import harness
    from conftest import TINY

    return {cell: harness.run_cell(
        cell, 2**31 + 17, 0.3, True, platform="cpu", sizes=TINY,
        say=lambda msg, flush=False: None) for cell in CELLS}


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("name", sorted(NEW))
def test_reader_on_the_cpu_stand_in(name, cell, bench, traced):
    declared = {m["name"]: m for m in bench["per_layer"]}[name]
    assert cell in declared["workloads"]
    unit, sound = NEW[name]
    metric = traced[cell]["metrics"][name]
    assert metric["unit"] == unit == declared["unit"]
    assert sound(metric["value"]), metric


@pytest.mark.parametrize("cell", CELLS)
def test_the_numbers_close_on_the_cpu_stand_in(cell, traced):
    m = {k: v["value"] for k, v in traced[cell]["metrics"].items()}
    # region + everything else is the device's busy time (the stand-in's
    # operations overlap across threads, so a little may count twice)
    assert m["region.device_s_per_query"] + m[
        "dispatch.pad_device_s_per_query"] == pytest.approx(
            m["device.busy_s_per_query"], rel=0.05)
    # the halves lie inside the whole, the whole inside submit
    assert (m["cache.fingerprint_copy_s"] + m["cache.fingerprint_hash_s"]
            <= m["cache.fingerprint_s"] <= m["session.submit_s"] * 1.5)
    # the true wait is not the old metric, which starts before the
    # fingerprint
    assert m["admission.wait_s"] < m["admission.queue_wait_s"]


def _run(n):
    return NS(requests=[None] * n, trace=None, counters={},
              device={"platform": "cpu"})


def _tree(request, root_id, *, t0=0.0, joined=True):
    """A request's two trees as records: submit (fingerprint 3 s of 4) and
    query (a region of 1 s of 2)."""
    tag = {"request": request} if joined else {}
    q = root_id + 10
    return [
        {"kind": "span", "op": "cache.fingerprint", "span": root_id + 1,
         "parent": root_id, "root": root_id, "t0": t0, "t1": t0 + 3.0},
        {"kind": "span", "op": "submit.q", "span": root_id, "parent": None,
         "root": root_id, "t0": t0, "t1": t0 + 4.0, **tag},
        {"kind": "span", "op": "admission.queue", "span": q + 1, "parent": q,
         "root": q, "t0": t0 + 3.5, "t1": t0 + 4.0},
        {"kind": "span", "op": "region.q", "span": q + 2, "parent": q,
         "root": q, "t0": t0 + 4.5, "t1": t0 + 5.5},
        {"kind": "span", "op": "query.q", "span": q, "parent": None,
         "root": q, "t0": t0 + 4.0, "t1": t0 + 6.0, **tag},
    ]


def test_window_requests_are_the_last_ids(monkeypatch):
    records = (_tree(1, 100) + [{"kind": "server", "op": "q"}]
               + _tree(2, 200, t0=10.0) + _tree(3, 300, t0=20.0))
    got = sr.window_requests(_run(2), records)
    assert [g["request"] for g in got] == [2, 3]
    assert all(len(g["roots"]) == 2 and len(g["spans"]) == 5 for g in got)
    from spark_rapids_jni_tpu import telemetry

    monkeypatch.setattr(telemetry, "events", lambda: records)
    assert sr.median_of_spans(_run(2), "cache.fingerprint") == 3.0
    assert sr.median_of_spans(_run(2), "region.q",
                              "admission.queue") == 1.5
    # submit: 1 s of 4 unexplained; query: 1 s of 2 (the queue span lies
    # before its parent's start and explains none of it)
    assert sr.untraced_share(_run(2)) == pytest.approx(100.0 * 2 / 6)


def test_a_wrapped_ring_raises_and_an_older_program_reads_nothing():
    records = _tree(1, 100) + _tree(2, 200) + _tree(3, 300)
    with pytest.raises(sr.SpanError, match="wrapped"):
        sr.window_requests(_run(3), records)     # no request before them
    with pytest.raises(sr.SpanError, match="no query span"):
        sr.window_requests(_run(3), [{"kind": "dispatch", "op": "x"}] * 9)
    # spans without request ids: the parent commit's program
    older = [r for i in (1, 2, 3) for r in _tree(i, 100 * i, joined=False)]
    assert sr.window_requests(_run(2), older) is None
    assert sr.window_requests(_run(0), records) is None


def test_a_reader_raises_once_the_real_ring_has_wrapped(run_tiny):
    from benchmark import resolve
    from spark_rapids_jni_tpu import telemetry

    result, _ = run_tiny(CELLS[0], trace=True)
    n = result["attempted"]
    reader = resolve.module("layer_metrics", "cache.fingerprint_s")
    assert reader.read(_run(n)) == result["metrics"][
        "cache.fingerprint_s"]["value"]
    for _ in range(4096):     # what a long window's records do to the ring
        telemetry.record_dispatch("filler")
    with pytest.raises(sr.SpanError, match="wrapped"):
        reader.read(_run(n))


def test_without_a_trace_the_device_readers_read_nothing():
    from benchmark import resolve

    for name in ("region.device_s_per_query",
                 "dispatch.pad_device_s_per_query",
                 "device.idle_attributed_share"):
        assert resolve.module("layer_metrics", name).read(_run(3)) is None


@pytest.fixture(scope="module")
def recorded():
    import jax

    with gzip.open(FIXTURE, "rb") as f:
        return jax.profiler.ProfileData.from_serialized_xspace(f.read())


def test_recorded_chip_trace_holds_the_programs_names(recorded):
    host = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
            for plane in recorded.planes if plane.name == "/host:CPU"
            for line in plane.lines for ev in line.events]
    requests = [(s, e) for s, e, n in host if n == "bench.request"]
    assert len(requests) == 2
    for name in ("submit.tpch_q1_planned", "cache.fingerprint",
                 "cache.fingerprint.copy", "cache.fingerprint.hash",
                 "cache.lookup", "admission.enqueue", "query.tpch_q1_planned",
                 "admission.wait", "region.tpch_q1_planned", "dispatch.pad",
                 "dispatch.execute", "server.record_actual", "cache.put"):
        inside = [1 for s, e, n in host if n == name and any(
            lo <= s and e <= hi for lo, hi in requests)]
        assert len(inside) >= 2, name
    modules = {ev.name.split("(")[0] for plane in recorded.planes
               if plane.name.startswith("/device:TPU:")
               for line in plane.lines if line.name == "XLA Modules"
               for ev in line.events}
    assert "jit_region_tpch_q1_planned" in modules
    assert "jit__region" not in modules


def test_recorded_chip_trace_reduces(recorded):
    from benchmark import trace_reduce

    r = sr.reduce_profile(recorded, "tpu")
    old = trace_reduce.reduce_profile(recorded, "tpu")
    assert r["requests"] == old["requests"] == 2
    assert r["region_modules"] == {"jit_region_tpch_q1_planned"}
    assert r["program_spans"] is True
    # the same idle time as the old reduction, and nearly all of it under
    # a program span: the fingerprint's copy and hash
    assert r["idle_s"] == pytest.approx(old["window_s"] - old["busy_s"],
                                        rel=1e-9)
    assert r["idle_attributed_s"] / r["idle_s"] > 0.95
    # modules run one after another on the chip: region and the rest are
    # the busy time, less the gaps inside a module
    assert r["region_s"] + r["other_s"] == pytest.approx(old["busy_s"],
                                                         rel=0.03)
    assert r["region_s"] / 2 == pytest.approx(0.094, abs=0.01)
