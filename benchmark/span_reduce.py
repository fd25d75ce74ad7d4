"""From the program's own spans and counters to per-layer metrics: what
one request spent under each named span, and what the device did under them.

The program writes one span tree per thread a request crosses
(``spark_rapids_jni_tpu/telemetry/spans.py``): ``submit.<plan>`` on the
client's thread and ``query.<plan>`` on the worker's, both carrying the
request's id. The readers under ``layer_metrics/`` get them without an
edit to the harness:

- **Spans** are in the process: the ring of ``telemetry.events()`` (4,096
  records of every kind). The window's requests are the last
  ``len(run.requests)`` request ids in it; one older request (the
  warm-up's, at least) has to be there still, or the ring has wrapped
  inside the window and ``SpanError`` is raised: a median over the
  requests that happen to be left is never given. A program that writes
  no request ids (any commit before these spans) gives ``None``: the
  metric is left out of the line.
- **The profiler trace**: ``Run`` carries no path to the ``.xplane.pb``.
  The run's scratch directory is the directory of the option
  ``server.estimate_path``, which the harness set, and the trace lies
  under ``trace/plugins/profile/*/`` in it, still there when the readers
  run. Every span is also a ``jax.profiler.TraceAnnotation`` of its name
  with the stats ``span`` and ``request``, so the host plane holds the
  program's spans on the clock of the device plane. The fused region's
  module is named ``jit_region_<plan>``; on the TPU a module is one event
  of the line ``XLA Modules``, on the CPU stand-in of the tests every
  operation names its module in the stat ``hlo_module``.

Seconds everywhere; intervals are ``trace_reduce``'s.
"""

from __future__ import annotations

import functools
import glob
import os
import statistics

from benchmark.trace_reduce import (
    DEVICE_LINES,
    HOST_PLANE,
    REQUEST,
    _complement,
    clip,
    total,
    union,
)

REGION_MODULE = "jit_region_"          # fusion.py names the region's jit so
ROOTS = ("submit.", "query.")          # the two roots of a request
MODULE_LINE = "XLA Modules"


class SpanError(RuntimeError):
    """The spans cannot give a number; the message says why."""


# -- the program's span records ------------------------------------------------

def window_requests(run, records=None):
    """One entry per request of the window, oldest first: ``{"request": id,
    "roots": [root records], "spans": [every record of their trees]}``.
    ``None`` where there is nothing to read (no request in the window, or a
    program without request ids); raises ``SpanError`` when the ring no
    longer holds the window. ``records`` replaces the ring (tests)."""
    n = len(run.requests)
    if not n:
        return None
    if records is None:
        from spark_rapids_jni_tpu import telemetry

        records = telemetry.events()
    spans = [r for r in records if r.get("kind") == "span"]
    roots = [r for r in spans if r.get("parent") is None]
    joined = [r for r in roots if "request" in r]
    if not joined:
        if any(str(r.get("op")).startswith("query.") for r in roots):
            return None   # the program's query spans carry no request id
        raise SpanError(
            f"the window had {n} requests and the ring holds no query "
            f"span: it has wrapped, or telemetry was off")
    ids = sorted({r["request"] for r in joined
                  if str(r["op"]).startswith("submit.")})
    if len(ids) <= n:
        raise SpanError(
            f"the ring holds the submit spans of {len(ids)} requests; the "
            f"window's {n} and one before them are needed: it has wrapped")
    out = []
    for request in ids[-n:]:
        mine = [r for r in joined if r["request"] == request]
        trees = {r["span"] for r in mine}
        out.append({"request": request, "roots": mine,
                    "spans": [r for r in spans if r.get("root") in trees]})
    return out


def _seconds(rec: dict) -> float:
    return float(rec["t1"]) - float(rec["t0"])


def median_of_spans(run, *names: str):
    """Median over the window's requests of the time one request spent in
    the spans called ``names`` (their sum: the names given together never
    nest in one another)."""
    requests = window_requests(run)
    if requests is None:
        return None
    return statistics.median(
        sum(_seconds(r) for r in req["spans"] if r["op"] in names)
        for req in requests)


def untraced_share(run):
    """Median over the window's requests of the two roots' self time (a
    root's duration less what its children cover of it) over their
    duration, in percent: what the spans do not explain."""
    requests = window_requests(run)
    if requests is None:
        return None
    shares = []
    for req in requests:
        whole = own = 0.0
        for root in req["roots"]:
            inside = [(root["t0"], root["t1"])]
            covered = clip(union([(r["t0"], r["t1"]) for r in req["spans"]
                                  if r.get("parent") == root["span"]]),
                           inside)
            whole += total(inside)
            own += total(inside) - total(covered)
        if whole > 0:
            shares.append(100.0 * own / whole)
    return statistics.median(shares) if shares else None


def counter_per_request(run, name: str):
    """The window's delta of the program's counter ``name`` over its
    requests; ``None`` for a program that has never written the counter."""
    from spark_rapids_jni_tpu.telemetry import REGISTRY

    if not run.requests or name not in REGISTRY.counters():
        return None
    return run.counters.get(name, 0) / len(run.requests)


# -- the profiler trace of the run ------------------------------------------------

def trace_path(run):
    """The one ``.xplane.pb`` of this run, or ``None`` without a trace."""
    if run.trace is None:
        return None
    from spark_rapids_jni_tpu.utils.config import get_option

    scratch = os.path.dirname(str(get_option("server.estimate_path")))
    found = glob.glob(os.path.join(scratch, "trace", "plugins", "profile",
                                   "*", "*.xplane.pb"))
    if len(found) != 1:
        raise SpanError(f"expected one .xplane.pb under {scratch}, "
                        f"found {found}")
    return found[0]


@functools.lru_cache(maxsize=1)
def _load(path: str):
    import jax

    return jax.profiler.ProfileData.from_file(path)


def _intervals(line) -> list:
    return [(ev.start_ns, ev.start_ns + ev.duration_ns, ev)
            for ev in line.events if ev.duration_ns > 0]


def reduce_profile(profile, platform: str) -> dict:
    """What the readers take from a trace: the traced requests, and on
    every device plane the time under the region's module, under every
    other module, idle, and idle under a program span below the roots."""
    if platform not in DEVICE_LINES:
        raise SpanError(f"no device line is known for platform {platform!r}")
    plane_prefix, line_prefix = DEVICE_LINES[platform]
    requests, program, planes = [], [], {}
    for plane in profile.planes:
        if plane.name.startswith(HOST_PLANE):
            for line in plane.lines:
                if line.name.startswith(line_prefix):
                    continue   # the CPU stand-in's device threads
                for s, e, ev in _intervals(line):
                    if ev.name == REQUEST:
                        requests.append((s, e))
                    elif (not ev.name.startswith(ROOTS + ("bench.",))
                          and "span" in dict(ev.stats)):
                        program.append((s, e))
        if plane.name.startswith(plane_prefix):
            ops, modules = planes.setdefault(plane.name, ([], []))
            for line in plane.lines:
                if platform == "cpu" and line.name.startswith(line_prefix):
                    for s, e, ev in _intervals(line):
                        module = dict(ev.stats).get("hlo_module")
                        if module is not None:   # an operation, not a marker
                            ops.append((s, e))
                            modules.append((s, e, str(module)))
                elif line.name.startswith(line_prefix):
                    ops.extend((s, e) for s, e, _ in _intervals(line))
                elif line.name == MODULE_LINE:
                    modules.extend((s, e, ev.name)
                                   for s, e, ev in _intervals(line))
    planes = {p: v for p, v in planes.items() if v[0]}
    requests = union(requests)
    if not requests or not planes:
        raise SpanError("the trace holds no bench.request or no device "
                        "operation")
    program = union(program)
    out = {"requests": len(requests), "region_s": 0.0, "other_s": 0.0,
           "idle_s": 0.0, "idle_attributed_s": 0.0,
           "region_modules": set(), "program_spans": bool(program)}
    for ops, modules in planes.values():
        region = [m for m in modules if m[2].startswith(REGION_MODULE)]
        out["region_modules"].update(m[2].split("(")[0] for m in region)
        out["region_s"] += total(clip(union(
            [(s, e) for s, e, _ in region]), requests))
        out["other_s"] += total(clip(union(
            [(s, e) for s, e, m in modules
             if not m.startswith(REGION_MODULE)]), requests))
        busy = clip(union(ops), requests)
        idle = [gap for window in requests
                for gap in _complement(clip(busy, [window]), window)]
        out["idle_s"] += total(idle)
        out["idle_attributed_s"] += total(clip(idle, program))
    for key in ("region_s", "other_s", "idle_s", "idle_attributed_s"):
        out[key] = out[key] / len(planes) / 1e9
    return out


def device(run):
    """``reduce_profile`` of this run's trace; ``None`` without a trace. Its
    ``region_modules`` is empty for a program whose regions all compile as
    ``jit__region`` (nothing to key on), its ``program_spans`` false for one
    whose spans do not reach the profiler: the readers then give ``None``."""
    path = trace_path(run)
    if path is None:
        return None
    return reduce_profile(_load(path), run.device["platform"])
