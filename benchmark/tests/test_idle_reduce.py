"""The nine readers of PR 36 (the six ``idle.*`` phases of
``benchmark/idle_reduce.py``, ``request.wake_s``, ``request.slow_share``,
``host.gc_pause_s_per_query``): each on the CPU stand-in at 4,096 rows
through ``run_cell``, each reading ``None`` for a program that writes none of
the new spans or counters, and the reduction on a recorded trace of the chip
(three traced requests of ``sf1_q1_planned_fresh``, my chip run, PR 36).
The phases on hand-made profiles are tier-1: ``tests/test_benchmark_idle_reduce.py``."""

import gzip
import os
from types import SimpleNamespace as NS

import pytest

from benchmark import idle_reduce as ir
from benchmark import resolve
from benchmark import span_reduce as sr

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                       "sf1_q1_planned_fresh.idle.xplane.pb.gz")
CELLS = ("sf1_q1_planned_fresh", "sf1_q1_general_fresh",
         "sf1_q1_parquet_fresh")
IDLE = tuple(f"idle.{p}_s_per_query" for p in ir.PHASES)
NEW = {   # reader -> (unit, what the CPU stand-in has to read at 4,096 rows)
    **{name: ("s", lambda v: 0 <= v < 1) for name in IDLE},
    "request.wake_s": ("s", lambda v: 0 < v < 0.1),
    "request.slow_share": ("%", lambda v: 0 <= v <= 100),
    "host.gc_pause_s_per_query": ("s", lambda v: 0 <= v < 1),
}


@pytest.fixture(scope="module")
def traced():
    from benchmark import harness
    from conftest import TINY

    out = {}
    for cell in CELLS:
        lines = []
        out[cell] = (harness.run_cell(
            cell, 2**31 + 19, 0.3, True, platform="cpu", sizes=TINY,
            say=lambda msg, flush=False: lines.append(msg)), lines)
    return out


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("name", sorted(NEW))
def test_reader_on_the_cpu_stand_in(name, cell, bench, traced):
    declared = {m["name"]: m for m in bench["per_layer"]}[name]
    assert declared["workloads"] == [w["name"] for w in bench["workloads"]]
    unit, sound = NEW[name]
    metric = traced[cell][0]["metrics"][name]
    assert metric["unit"] == unit == declared["unit"]
    assert sound(metric["value"]), metric


@pytest.mark.parametrize("cell", CELLS)
def test_the_six_phases_sum_to_the_idle_time(cell, traced):
    result, lines = traced[cell]
    m = {k: v["value"] for k, v in result["metrics"].items()}
    idle = m["device.idle_share"] / 100.0 * result["device"]["window_s"] / 3
    # the stand-in's idle share counts thunk markers as operations, this
    # reduction (with span_reduce) does not: a little apart
    assert sum(m[k] for k in IDLE) == pytest.approx(idle, rel=0.05)
    (said,) = [ln for ln in lines if "idle by span: " in ln]
    assert "over 3 traced requests" in said
    if cell == "sf1_q1_parquet_fresh":      # the scan is where it waits
        assert m["idle.stage_s_per_query"] == max(m[k] for k in IDLE)
        assert "idle by span: scan." in said


def _old_tree(request, root):
    tag = {"request": request}
    return [{"kind": "span", "op": "submit.q", "span": root, "parent": None,
             "root": root, "t0": 0.0, "t1": 1.0, **tag},
            {"kind": "span", "op": "cache.put", "span": root + 11,
             "parent": root + 10, "root": root + 10, "t0": 1.0, "t1": 2.0},
            {"kind": "span", "op": "query.q", "span": root + 10,
             "parent": None, "root": root + 10, "t0": 1.0, "t1": 3.0, **tag}]


def test_a_program_without_the_new_spans_and_counters_reads_none(monkeypatch):
    """The parent of PR 36: two roots a request, no ``ticket.wake``, neither
    counter, no ``query.result.<plan>`` in the trace. No reader raises."""
    from spark_rapids_jni_tpu import telemetry

    telemetry.REGISTRY.reset()
    records = [r for i in (1, 2, 3) for r in _old_tree(i, 100 * i)]
    monkeypatch.setattr(telemetry, "events", lambda: records)
    host = NS(name="/host:CPU", lines=[NS(name="client", events=[
        NS(name=n, start_ns=s, duration_ns=e - s, stats=st)
        for s, e, n, st in (
            (0, 1000, "bench.request", []),
            (10, 200, "submit.q", [("span", 1), ("request", 3)]),
            (300, 900, "query.q", [("span", 2), ("request", 3)]))])])
    device = NS(name="/device:TPU:0", lines=[NS(name="XLA Ops", events=[
        NS(name="%a = add()", start_ns=400, duration_ns=100, stats=[])])])
    monkeypatch.setattr(ir, "trace_path", lambda run: "a.xplane.pb")
    monkeypatch.setattr(ir, "_load", lambda path: NS(planes=[device, host]))
    run = NS(requests=[None] * 2, counters={}, trace={},
             device={"platform": "tpu"}, say=lambda msg: None)
    for name in NEW:
        assert resolve.module("layer_metrics", name).read(run) is None, name
    # with the counters there and unmoved they read 0, not nothing
    for counter in ("server.slow_requests", "host.gc_pause_ns"):
        telemetry.REGISTRY.counter(counter)
    assert resolve.module("layer_metrics", "request.slow_share").read(
        run) == 0.0
    assert resolve.module(
        "layer_metrics", "host.gc_pause_s_per_query").read(run) == 0.0
    telemetry.REGISTRY.reset()


@pytest.fixture(scope="module")
def recorded():
    import jax

    with gzip.open(FIXTURE, "rb") as f:
        return jax.profiler.ProfileData.from_serialized_xspace(f.read())


def test_recorded_chip_trace_reduces(recorded):
    got = ir.reduce_profile(recorded, "tpu")
    old = sr.reduce_profile(recorded, "tpu")
    assert got["requests"] == old["requests"] == 3
    assert got["idle_s"] * 3 == pytest.approx(old["idle_s"], rel=1e-9)
    assert sum(got["phases"].values()) == pytest.approx(got["idle_s"],
                                                        rel=1e-9)
    assert got["idle_s"] == pytest.approx(0.011021648, abs=1e-9)
    assert got["phases"] == pytest.approx({
        "submit": 0.003772418, "handoff": 0.000339097, "stage": 0.000302403,
        "dispatch": 0.003180434, "result": 0.003262908,
        "client": 0.000164387}, abs=1e-9)
    # the largest piece has a name: the worker in cache.put, the device done
    assert max(got["spans"], key=got["spans"].get) == "cache.put"
    # a planned SF1 request: the device waits for the host's digest in
    # submit and for its pad and enqueue, hardly for the scan-less staging
    assert got["phases"]["submit"] > got["phases"]["stage"]
    assert got["phases"]["dispatch"] > got["phases"]["stage"]
    top = sorted(got["spans"].items(), key=lambda kv: -kv[1])[:ir.TOP]
    assert sum(v for _, v in top) >= 0.95 * got["idle_s"]
