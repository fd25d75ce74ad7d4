"""The reduction from a profiler trace to busy time, idle share, top
operations and named idle gaps: on a recorded trace of the chip (two
traced requests of ``sf10_q1_planned_fresh``, my chip run, PR 24) and on
made-up profiles for the cases that must raise."""

import gzip
import os
from types import SimpleNamespace as NS

import pytest

from benchmark import trace_reduce as tr

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                       "sf10_q1_planned_fresh.xplane.pb.gz")


def test_recorded_chip_trace():
    import jax

    with gzip.open(FIXTURE, "rb") as f:
        profile = jax.profiler.ProfileData.from_serialized_xspace(f.read())
    r = tr.reduce_profile(profile, "tpu", chips=1)
    assert r["requests"] == 2
    assert r["window_s"] == pytest.approx(25.835669599, abs=1e-9)
    assert r["busy_s"] == pytest.approx(0.257316974, abs=1e-9)
    assert r["idle_share"] == pytest.approx(99.004024, abs=1e-5)
    # the padded copy's 64-bit zip leads; names carry no HLO text
    assert r["device_ops"][0][0] == "custom-call.4 X64Combine"
    assert len(r["device_ops"]) == 10
    assert all(" = " not in name for name, _ in r["device_ops"])
    assert [s for _, s in r["device_ops"]] == sorted(
        (s for _, s in r["device_ops"]), reverse=True)
    # the device waited while the host fingerprinted the table in submit
    gaps = dict(r["idle_gaps"])
    assert r["idle_gaps"][0][0] == "bench.submit"
    assert gaps["bench.submit"] == pytest.approx(25.534, abs=1e-3)
    assert set(gaps) <= set(tr.LEAVES) | {"bench.other"}
    # gaps cover the traced span (requests and the roll between) less busy
    assert sum(gaps.values()) == pytest.approx(
        r["window_s"] + gaps["bench.roll"] + gaps["bench.readback"]
        + gaps.get("bench.other", 0) - r["busy_s"], rel=1e-3)


def _profile(device_events, host_events, device_plane="/device:TPU:0"):
    def line(name, events):
        return NS(name=name, events=[
            NS(name=n, start_ns=s, duration_ns=e - s) for s, e, n in events])

    return NS(planes=[
        NS(name=device_plane, lines=[line("XLA Ops", device_events),
                                     line("XLA Modules", [(0, 10**9, "m")])]),
        NS(name="/host:CPU", lines=[line("python3", host_events)])])


def test_busy_is_a_union_clipped_to_the_requests():
    host = [(100, 1100, "bench.request"), (100, 600, "bench.submit"),
            (600, 1100, "bench.result"), (1100, 1300, "bench.roll"),
            (1300, 2300, "bench.request"), (1300, 1400, "bench.submit"),
            (1400, 2300, "bench.result")]
    device = [(0, 200, "%a = f32[] add()"),          # half outside
              (150, 400, "%b = f32[] mul()"),        # overlaps a
              (1150, 1250, "%roll = s32[] copy()"),  # between requests
              (2000, 2300, "%a = f32[] add()")]
    r = tr.reduce_profile(_profile(device, host), "tpu")
    assert r["requests"] == 2
    assert r["window_s"] == pytest.approx(2000e-9)
    assert r["busy_s"] == pytest.approx((300 + 300) * 1e-9)
    assert r["idle_share"] == pytest.approx(70.0)
    assert dict(r["device_ops"]) == pytest.approx({"a": 400e-9, "b": 250e-9})
    assert dict(r["idle_gaps"]) == pytest.approx({
        "bench.submit": 300e-9, "bench.result": 1100e-9,
        "bench.roll": 100e-9})


def test_missing_device_plane_raises():
    host = [(0, 10, "bench.request")]
    with pytest.raises(tr.TraceError, match="no device plane"):
        tr.reduce_profile(_profile([(1, 2, "x")], host, "/device:GPU:0"),
                          "tpu")
    with pytest.raises(tr.TraceError, match="no device plane"):
        tr.reduce_profile(_profile([], host), "tpu")   # nothing ran


def test_no_request_annotation_raises():
    with pytest.raises(tr.TraceError, match="bench.request"):
        tr.reduce_profile(_profile([(1, 2, "x")], [(0, 5, "bench.roll")]),
                          "tpu")


def test_fewer_device_planes_than_chips_raises():
    with pytest.raises(tr.TraceError, match="1 device planes"):
        tr.reduce_profile(_profile([(1, 2, "x")], [(0, 5, "bench.request")]),
                          "tpu", chips=4)


def test_share_over_100_raises(monkeypatch):
    # cannot happen through clip(); the guard is for a reduction gone wrong
    monkeypatch.setattr(tr, "clip", lambda intervals, windows: intervals)
    with pytest.raises(tr.TraceError, match="over 100%"):
        tr.reduce_profile(_profile([(0, 50, "x")], [(0, 5, "bench.request")]),
                          "tpu")


def test_interval_helpers():
    assert tr.union([(5, 7), (1, 3), (2, 4), (9, 9)]) == [(1, 4), (5, 7)]
    assert tr.clip([(0, 10), (20, 30)], [(5, 25)]) == [(5, 10), (20, 25)]
    assert tr.short_name('%custom-call.4 = s64[8] custom-call(u32[8] %p), '
                         'custom_call_target="X64Combine"') == \
        "custom-call.4 X64Combine"
