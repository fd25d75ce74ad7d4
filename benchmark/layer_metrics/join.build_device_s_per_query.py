"""Layer metric ``join.build_device_s_per_query``: device time a request under the
``build`` sub-scope of the plan's general joins (``fusion.Join``):
everything that orders or indexes the build side. For a semi or anti join
that is the one sort of both sides' keys (``ops/join.py``); for a
maps-based join the build side's sort."""

LAYER = "operators"
UNIT = "s"
SOURCE = "device_trace"
MOVES = "rows_per_s"
BETTER = "lower"
STAGE = "build"


def stage_seconds_per_query(run, stage: str):
    """Device seconds a request under ``region.<plan>/<a Join's
    label>/.../<stage>``: the union of those operations inside the traced
    requests, over their number. ``None`` without a trace, on a program
    whose joins name no such scope, or in a mix without a ``Join``."""
    import re

    from benchmark import scope_reduce, span_reduce
    from benchmark.trace_reduce import clip, total, union

    kinds = scope_reduce._node_kinds(run)
    path = span_reduce.trace_path(run)
    labels = [re.escape(scope) for scope, kind in (kinds or {}).items()
              if kind == "Join"]
    if not labels or path is None:
        return None
    under = re.compile(
        r"(?:^|/)region\.[^/]+/(?:" + "|".join(labels) + r")/(?:[^/:]+/)*"
        + re.escape(stage) + r"(?:[/:]|$)")
    requests = union([
        (ev.start_ns, ev.start_ns + ev.duration_ns)
        for plane in span_reduce._load(path).planes
        if plane.name.startswith(span_reduce.HOST_PLANE)
        for line in plane.lines for ev in line.events
        if ev.name == span_reduce.REQUEST and ev.duration_ns > 0])
    spans = [(start, end) for start, end, _, scope
             in scope_reduce.device_operations(path, run.device["platform"])
             if scope and under.search(scope)]
    if not requests or not spans:
        return None
    return total(clip(union(spans), requests)) / 1e9 / len(requests)


def read(run):
    return stage_seconds_per_query(run, STAGE)
