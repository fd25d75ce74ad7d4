"""The six readers of what ``runtime/dispatch.py`` observes of a compile
(PR 50): each on the CPU stand-in at 4,096 rows through ``run_cell``, and
the shared step ``compile_reduce`` on made-up records for the cases that
give nothing: a program without the attributes, a request served from
the cache."""

import statistics
from types import SimpleNamespace as NS

import pytest

from benchmark import compile_reduce, resolve

CELL = "sf1_q1_planned_fresh"
NEW = {   # reader -> (unit, what the CPU stand-in has to read at 4,096 rows)
    "dispatch.compile_trace_lower_s": ("s", lambda v: 0 < v < 60),
    "dispatch.compile_backend_s": ("s", lambda v: 0 < v < 60),
    "dispatch.compile_persistent_hit_share": ("%", lambda v: 0 <= v <= 100),
    # seven columns of 4,096 rows are 155,648 B of arguments alone
    "region.hbm_need_bytes": ("bytes", lambda v: v > 4096 * 38),
    "region.hbm_temp_share": ("%", lambda v: 0 <= v < 100),
    "admission.reserved_need_share": ("%", lambda v: v > 0),
}


@pytest.fixture(scope="module")
def traced():
    """One traced run of a q1 cell, its printed lines and the ring's
    records as the readers found them."""
    from benchmark import harness
    from conftest import TINY
    from spark_rapids_jni_tpu import telemetry

    lines = []
    result = harness.run_cell(
        CELL, 2**31 + 23, 0.3, True, platform="cpu", sizes=TINY,
        say=lambda msg, flush=False: lines.append(msg))
    return result, lines, telemetry.events()


@pytest.mark.parametrize("name", sorted(NEW))
def test_reader_on_the_cpu_stand_in(name, bench, traced):
    declared = {m["name"]: m for m in bench["per_layer"]}[name]
    assert declared["workloads"] == [w["name"] for w in bench["workloads"]]
    unit, sound = NEW[name]
    metric = traced[0]["metrics"][name]
    assert metric["unit"] == unit == declared["unit"]
    assert sound(metric["value"]), metric


def test_reserved_need_share_is_the_two_attributes_quotient(traced):
    """By hand from the ring: a request's ``admission.wait`` says what was
    reserved, its ``dispatch.execute`` what the region needs."""
    result, lines, records = traced
    spans = [r for r in records if r.get("kind") == "span"]
    request_of = {r["span"]: r["request"] for r in spans
                  if r.get("parent") is None and "request" in r}
    reserved, need, temp = {}, {}, {}
    for r in spans:
        request = request_of.get(r["root"])
        if r["op"] == "admission.wait":
            reserved[request] = r["estimate_bytes"]
        elif r["op"] == "dispatch.execute":
            assert r["need_bytes"] > r["temp_bytes"] >= 0
            need[request], temp[request] = r["need_bytes"], r["temp_bytes"]
    window = sorted(need)[-result["attempted"]:]
    assert len(window) == result["attempted"] >= 1
    m = {k: v["value"] for k, v in result["metrics"].items()}
    assert m["admission.reserved_need_share"] == statistics.median(
        100.0 * reserved[q] / need[q] for q in window)
    assert m["region.hbm_need_bytes"] == statistics.median(
        need[q] for q in window)
    assert m["region.hbm_temp_share"] == statistics.median(
        100.0 * temp[q] / need[q] for q in window)
    # the backend's seconds are printed with the cache's load beside them
    (line,) = [ln for ln in lines if "compile: backend" in ln]
    assert "loading from the persistent cache" in line


def test_compile_counters_are_absolute_and_outside_the_op_space(traced):
    """The readers take the process's counters, not the window's delta: the
    window compiles nothing, the warm-up did."""
    from spark_rapids_jni_tpu.telemetry import REGISTRY

    result = traced[0]
    assert result["metrics"]["dispatch.compiles_in_window"]["value"] == 0
    found = compile_reduce.xla_counters()
    assert found["trace_lower_ns"] > 0 and found["backend_ns"] > 0
    assert not any(k.startswith("dispatch.compile.xla")
                   for k in REGISTRY.counters("dispatch."))


def _run_of(requests):
    return NS(requests=[None] * len(requests), _window_requests=requests)


def _span(op, **attrs):
    return dict(attrs, op=op, t0=0.0, t1=1.0)


def test_region_needs_takes_the_largest_executable_of_a_request():
    run = _run_of([
        {"request": 1, "roots": [], "spans": [
            _span("admission.wait", estimate_bytes=300),
            _span("dispatch.execute", need_bytes=100, temp_bytes=90),
            _span("dispatch.execute", need_bytes=1000, temp_bytes=250)]},
        # served from the result cache: no executable ran
        {"request": 2, "roots": [], "spans": [_span("cache.hit")]},
        # a scan that staged with no admission span of its own
        {"request": 3, "roots": [], "spans": [
            _span("dispatch.execute", need_bytes=500, temp_bytes=0)]}])
    assert compile_reduce.region_needs(run) == [
        {"need": 1000, "temp": 250, "reserved": 300},
        {"need": 500, "temp": 0, "reserved": None}]
    read = {n: resolve.module("layer_metrics", n).read(run) for n in (
        "region.hbm_need_bytes", "region.hbm_temp_share",
        "admission.reserved_need_share")}
    assert read == {"region.hbm_need_bytes": 750,
                    "region.hbm_temp_share": 12.5,
                    "admission.reserved_need_share": 30.0}


def test_a_program_without_the_attributes_gives_nothing():
    """The parent's spans: ``dispatch.execute`` says no need, and the
    metric is left out of the line."""
    run = _run_of([{"request": 1, "roots": [], "spans": [
        _span("admission.wait", estimate_bytes=300),
        _span("dispatch.execute")]}])
    assert compile_reduce.region_needs(run) is None
    for name in ("region.hbm_need_bytes", "region.hbm_temp_share",
                 "admission.reserved_need_share"):
        assert resolve.module("layer_metrics", name).read(run) is None
    share = resolve.module(
        "layer_metrics", "dispatch.compile_persistent_hit_share").hit_share
    assert share(None) is None
    assert share({"persistent_hit": 3, "persistent_miss": 1}) == 75.0
    assert share({"persistent_miss": 2}) == 0.0
    assert share({"trace_lower_ns": 5}) == 100.0   # nothing asked
