"""``benchmark/idle_reduce.py`` on hand-made profiles (tier-1; the readers
on a real trace are ``benchmark/tests/test_idle_reduce.py``, by hand): every
idle piece of a traced request goes to one phase, the six phases sum to the
idle time, and the client's root adds nothing to what ``span_reduce`` counts
as idle time under a program span."""

import os
import sys
from types import SimpleNamespace as NS

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import idle_reduce as ir  # noqa: E402
from benchmark import span_reduce as sr  # noqa: E402

# two traced requests, ns; (start, end, name[, request]): a program span
# carries its request's id, the harness's annotations none
CLIENT = [
    (0, 1000, "bench.request"), (0, 200, "bench.submit"),
    (10, 190, "submit.q", 7), (20, 120, "cache.fingerprint", 7),
    (150, 180, "admission.enqueue", 7), (200, 1000, "bench.result"),
    (210, 900, "query.result.q", 7),
    (2000, 3000, "bench.request"), (2010, 2100, "submit.q", 8),
    (2120, 2900, "query.result.q", 8)]
WORKER = [
    # picked up before the client's submit has closed; its root closes
    # before the client has woken
    (170, 880, "query.q", 7), (180, 200, "admission.wait", 7),
    (200, 260, "server.stage_bindings", 7), (300, 600, "rung.fused", 7),
    (310, 590, "region.q", 7), (320, 400, "dispatch.pad", 7),
    (400, 500, "dispatch.execute", 7), (620, 640, "server.record_actual", 7),
    (650, 800, "cache.put", 7), (820, 840, "ticket.resolve", 7),
    # picked up late; its root outlives the client's return
    (2200, 2950, "query.q", 8), (2250, 2500, "rung.fused", 8),
    (2550, 2700, "cache.put", 8), (2800, 2850, "ticket.resolve", 8)]
BUSY = [(450, 700, "%fusion.1 = f32[] fusion()"),
        (2300, 2600, "%fusion.1 = f32[] fusion()")]
PHASES_NS = {     # request 7 + request 8, as worked out in the comments above
    "submit": 160 + 90, "handoff": 60 + 150, "stage": 130 + 50,
    "dispatch": 150 + 50, "result": 140 + 300, "client": 110 + 60}


def _profile(client=CLIENT, worker=WORKER, devices=(BUSY,)):
    def line(name, events):
        return NS(name=name, events=[
            NS(name=ev[2], start_ns=ev[0], duration_ns=ev[1] - ev[0],
               stats=[("span", i), ("request", ev[3])] if len(ev) > 3 else [])
            for i, ev in enumerate(events)])

    return NS(planes=[
        NS(name=f"/device:TPU:{d}", lines=[line("XLA Ops", busy)])
        for d, busy in enumerate(devices)] + [
        NS(name="/host:CPU", lines=[line("client", client),
                                    line("tpu-server-worker-0", worker)])])


def test_every_idle_piece_goes_to_one_phase_and_the_six_sum_to_the_idle_time():
    got = ir.reduce_profile(_profile(), "tpu")
    assert got["requests"] == 2
    assert got["phases"] == pytest.approx(
        {k: v / 2 / 1e9 for k, v in PHASES_NS.items()})
    assert sum(got["phases"].values()) == pytest.approx(got["idle_s"])
    assert got["idle_s"] == pytest.approx((750 + 700) / 2 / 1e9)
    # the same idle time as span_reduce's, which the readers' sum is held to
    assert got["idle_s"] * 2 == pytest.approx(
        sr.reduce_profile(_profile(), "tpu")["idle_s"])
    # by name: the innermost span of the thread that decides, the client's
    # root for the hand-offs, bench.request for the caller's own time
    names = {k: round(v * 2e9) for k, v in got["spans"].items()}
    assert names == {
        "bench.request": 170, "submit.q": 40 + 90, "cache.fingerprint": 100,
        "admission.enqueue": 20, "query.q": 10 + 40 + 20 + 50 + 100 + 50,
        "admission.wait": 20, "server.stage_bindings": 60,
        "rung.fused": 10 + 50, "region.q": 10, "dispatch.pad": 80,
        "dispatch.execute": 50, "cache.put": 100 + 100,
        "ticket.resolve": 20 + 50, "query.result.q": 60 + 150}
    assert sum(names.values()) == 1450


def test_several_device_planes_are_averaged():
    """A four-chip cell: a chip's mean, as span_reduce takes it."""
    quiet = [(450, 500, "%a = f32[] add()")]        # busy 50 ns of request 7
    got = ir.reduce_profile(_profile(devices=(BUSY, quiet)), "tpu")
    assert got["idle_s"] == pytest.approx((1450 + 1950) / 2 / 2 / 1e9)
    assert sum(got["phases"].values()) == pytest.approx(got["idle_s"])
    assert got["idle_s"] * 2 == pytest.approx(sr.reduce_profile(
        _profile(devices=(BUSY, quiet)), "tpu")["idle_s"])
    # the second chip waits through nearly all of both regions; the root's
    # own time after a rung goes with the rung
    assert got["phases"]["dispatch"] == pytest.approx(
        (150 + 50 + 270 + 300) / 2 / 2 / 1e9)


def test_the_clients_root_adds_nothing_to_span_reduces_attributed_idle():
    """``query.result.<plan>`` starts with ``query.`` and its children have
    no annotation: ``device.idle_attributed_share`` keeps its meaning."""
    without = [ev for ev in CLIENT if ev[2] != "query.result.q"]
    new = sr.reduce_profile(_profile(), "tpu")
    old = sr.reduce_profile(_profile(client=without), "tpu")
    assert new == old and old["program_spans"]
    assert old["idle_attributed_s"] == pytest.approx(
        (100 + 30 + 20 + 60 + 150 + 100 + 20 + 50 + 100 + 50) / 1e9)


def test_a_program_without_the_clients_root_reads_nothing():
    without = [ev for ev in CLIENT if ev[2] != "query.result.q"]
    assert ir.reduce_profile(_profile(client=without), "tpu") is None
    # nor one whose spans carry no request id (before PR 25)
    bare = [ev[:3] for ev in CLIENT]
    assert ir.reduce_profile(_profile(client=bare), "tpu") is None
    run = NS(trace=None, device={"platform": "tpu"})
    assert ir.phase(run, "submit") is None      # and no trace, no number


def test_a_hit_and_a_trace_that_cannot_be_split():
    # a request served from the cache never reaches a worker: submit, then
    # the caller's own time
    hit = [(0, 1000, "bench.request"), (10, 190, "submit.q", 7),
           (100, 150, "query.q", 7), (200, 240, "query.result.q", 7)]
    got = ir.reduce_profile(_profile(client=hit, worker=[],
                                     devices=([(500, 600, "%a = add()")],)),
                            "tpu")
    assert {k: round(v * 1e9) for k, v in got["phases"].items()} == {
        "submit": 180, "handoff": 0, "stage": 0, "dispatch": 0,
        "result": 0, "client": 10 + 310 + 400}
    with pytest.raises(sr.SpanError, match="no bench.request"):
        ir.reduce_profile(_profile(client=[], worker=[]), "tpu")
    two = CLIENT[:7] + [(300, 400, "submit.q", 9)]
    with pytest.raises(sr.SpanError, match="submit roots of 2 requests"):
        ir.reduce_profile(_profile(client=two), "tpu")


def test_flatten_names_the_span_that_started_last():
    assert ir._flatten([(0, 100, "q"), (10, 30, "a"), (12, 20, "a1"),
                        (40, 60, "b"), (60, 70, "c"), (95, 105, "late")]) == [
        (0, 10, "q", None), (10, 12, "a", "a"), (12, 20, "a1", "a"),
        (20, 30, "a", "a"), (30, 40, "q", None), (40, 60, "b", "b"),
        (60, 70, "c", "c"), (70, 95, "q", None), (95, 100, "late", "late")]
