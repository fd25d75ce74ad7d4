"""From a profiler trace to what each chip of a mesh did: device time by
chip, and under the stages a groupby lowered over a mesh runs in.

``runtime/fusion.py`` lowers such a groupby under the sub-scopes
``partial`` (a chip's own aggregate), ``exchange`` (``hash_shuffle``: the
``all_to_all`` over ICI and the packing around it), ``merge`` and
``collect`` of its node's scope: ``region.<plan>/<node>/<stage>/...`` in
the stat ``tf_op`` that ``scope_reduce`` reads. Every chip is a device
plane of its own (``/device:TPU:<i>``); a stage's time is the union of its
operations inside the traced requests on one chip, averaged over the
chips, as ``trace_reduce`` averages busy time.

A trace with fewer device planes than the cell has chips, or with no such
scope in it (any program before the lowering), gives ``None``: the reader
leaves its metric out of the line.
"""

from __future__ import annotations

import functools
import re

from benchmark import scope_reduce as sr
from benchmark import span_reduce
from benchmark.trace_reduce import DEVICE_LINES, clip, total, union

STAGES = ("partial", "exchange", "merge", "collect")
_STAGE = re.compile(
    r"(?:^|/)region\.[^/]+/[^/]+/(" + "|".join(STAGES) + r")(?:[/:]|$)")


def stage_of(scope) -> str | None:
    """The stage an operation's scope names, or None."""
    match = _STAGE.search(scope or "")
    return match.group(1) if match else None


def operations_by_plane(path: str, platform: str) -> dict:
    """``{device plane: [(start_ns, end_ns, scope or None)]}`` of every
    operation that ran on a device (``scope_reduce.device_operations`` with
    the plane kept; the CPU stand-in of the tests has one plane)."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    if platform == "cpu":
        return {"cpu": [(s, e, scope) for s, e, _, scope
                        in sr._cpu_operations(path, space)]}
    plane_prefix, line_prefix = DEVICE_LINES[platform]
    out: dict = {}
    for number, plane in sr._fields(space):
        if number != sr._SPACE_PLANES:
            continue
        fields = list(sr._fields(plane))
        name = next((sr._text(v) for n, v in fields
                     if n == sr._PLANE_NAME), "")
        if not name.startswith(plane_prefix):
            continue
        stat_names = sr._map(
            [v for n, v in fields if n == sr._PLANE_STAT_META],
            lambda v: sr._text(dict(sr._fields(v)).get(sr._META_NAME, b"")))
        wanted = {i for i, n in stat_names.items() if n == sr.SCOPE_STAT}

        def scope_of(view):
            for number, value in sr._fields(view):
                if number != sr._META_STATS:
                    continue
                stat = dict(sr._fields(value))
                if stat.get(sr._STAT_META_ID) in wanted:
                    if sr._STAT_STR in stat:
                        return sr._text(stat[sr._STAT_STR])
                    return stat_names.get(stat.get(sr._STAT_REF))
            return None

        scopes = sr._map([v for n, v in fields
                          if n == sr._PLANE_EVENT_META], scope_of)
        for number, line in fields:
            if number != sr._PLANE_LINES:
                continue
            lf = list(sr._fields(line))
            if not next((sr._text(v) for n, v in lf if n == sr._LINE_NAME),
                        "").startswith(line_prefix):
                continue
            t0 = next((v for n, v in lf if n == sr._LINE_TIMESTAMP_NS), 0)
            for number, event in lf:
                if number != sr._LINE_EVENTS:
                    continue
                ev = dict(sr._fields(event))
                dur = ev.get(sr._EVENT_DURATION_PS, 0)
                if dur > 0:
                    start = t0 + ev.get(sr._EVENT_OFFSET_PS, 0) / 1000.0
                    out.setdefault(name, []).append(
                        (start, start + dur / 1000.0,
                         scopes.get(ev.get(sr._EVENT_META_ID))))
    return out


def reduce_planes(planes: dict, requests: list) -> dict:
    """``{"busy_s": {plane: s}, "stage_s": {stage: s averaged over the
    planes}}`` inside the disjoint sorted ``requests`` intervals (ns)."""
    busy, stages = {}, dict.fromkeys(STAGES, 0.0)
    for plane, ops in planes.items():
        busy[plane] = total(clip(union(
            [(s, e) for s, e, _ in ops]), requests)) / 1e9
        for stage in STAGES:
            stages[stage] += total(clip(union(
                [(s, e) for s, e, scope in ops
                 if stage_of(scope) == stage]), requests)) / 1e9
    return {"busy_s": busy,
            "stage_s": {k: v / max(1, len(planes))
                        for k, v in stages.items()}}


@functools.lru_cache(maxsize=1)
def _reduced(path: str, platform: str) -> tuple:
    requests = union([
        (ev.start_ns, ev.start_ns + ev.duration_ns)
        for plane in span_reduce._load(path).planes
        if plane.name.startswith(span_reduce.HOST_PLANE)
        for line in plane.lines for ev in line.events
        if ev.name == span_reduce.REQUEST and ev.duration_ns > 0])
    return len(requests), reduce_planes(
        operations_by_plane(path, platform), requests)


def of(run):
    """``(requests traced, reduce_planes(...))`` of this run's trace;
    ``None`` without a trace, with fewer device planes than the cell has
    chips, or with no request in it."""
    path = span_reduce.trace_path(run)
    if path is None:
        return None
    requests, reduced = _reduced(path, run.device["platform"])
    if not requests or len(reduced["busy_s"]) < int(run.workload["chips"]):
        return None
    return requests, reduced


def stage_seconds_per_query(run, *stages: str):
    """Device seconds a request under the given stages (their sum),
    averaged over the chips; ``None`` where the trace shows no stage."""
    found = of(run)
    if found is None or not any(found[1]["stage_s"].values()):
        return None
    requests, reduced = found
    return sum(reduced["stage_s"][s] for s in stages) / requests


def chip_skew_share(run):
    """The slowest chip's busy time less the fastest's, over the
    slowest's, in percent, across the traced requests."""
    found = of(run)
    if found is None:
        return None
    busy = sorted(found[1]["busy_s"].values())
    return 100.0 * (busy[-1] - busy[0]) / busy[-1] if busy[-1] else None
