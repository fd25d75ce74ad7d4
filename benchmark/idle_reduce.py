"""From the device's idle time to the phase of a request it lies under: what
the program was doing, by its own annotations, while the chip waited.

``span_reduce`` says how much of the idle time lies under *some* program
span. This reducer gives every piece of it to ONE phase of the request it
belongs to, and to one span name. It reads the same trace through
``span_reduce``'s intervals: the traced requests are the harness's
``bench.request`` annotations, the device's idle time is what no operation
covers of them on a device plane, and a request's program spans are the host
plane's annotations that carry its id in the stat ``request``. A request has
three roots (``spark_rapids_jni_tpu/runtime/server.py``): ``submit.<plan>``
on the client's thread, ``query.<plan>`` on a worker's, and
``query.result.<plan>`` on the client's again, around its wait for the
result; the worker closes its annotated child ``ticket.resolve`` at the moment
that wait ends. A host-plane line is a thread, so the innermost span of a
line at an instant is the one that started last. Where both threads have a
span open the worker's tree decides. In the order in which a piece is given
away:

- under ``query.<plan>`` up to the end of ``ticket.resolve``, by the root's
  child it lies under: ``stage`` (``admission.wait``,
  ``server.stage_bindings``: the scan of a file-backed request),
  ``dispatch`` (``rung.*`` / ``region.<plan>`` with the pad, the enqueue and
  a compile under them) or ``result`` (``server.record_actual``,
  ``cache.put``, ``server.account_meta``, ``ticket.resolve``); the root's own
  time goes with the child that ended last before it (``stage`` before the
  first);
- from the end of ``ticket.resolve`` to the end of ``query.result.<plan>``:
  ``handoff``, the client's wake-up while the worker closes its root; what
  the worker's root still covers after the client has gone: ``result``;
- inside ``submit.<plan>``: ``submit`` (the footer, the digest's enqueue and
  the wait for it, the look-up, the enqueue);
- between the end of ``submit.<plan>`` and the start of ``query.<plan>``:
  ``handoff``, the worker's pickup;
- the rest of ``bench.request``: ``client``, the caller's own time, the sync.

The six sum to the idle time. Seconds a request; with several device planes
(a four-chip cell) the mean over the planes, as ``span_reduce`` takes it. A
program that writes no ``query.result.<plan>`` (any commit before it) gives
``None``: its hand-offs have no end to measure to.
"""

from __future__ import annotations

from benchmark.span_reduce import (
    DEVICE_LINES,
    HOST_PLANE,
    REQUEST,
    SpanError,
    _complement,
    _intervals,
    _load,
    clip,
    total,
    trace_path,
    union,
)

PHASES = ("submit", "handoff", "stage", "dispatch", "result", "client")
STAGE = ("admission.wait", "server.stage_bindings")
DISPATCH = ("rung.", "region.")
RESULT = ("server.record_actual", "cache.put", "server.account_meta",
          "ticket.resolve")
RESOLVE = "ticket.resolve"
OWN = REQUEST          # the name of what lies under no program span
TOP = 10               # span names in the printed line


def _subtract(intervals: list, minus: list) -> list:
    """The parts of disjoint sorted ``intervals`` outside ``minus``."""
    return [gap for w in intervals
            for gap in _complement(clip(minus, [w]), w)]


def _flatten(events: list) -> list:
    """The properly nested ``(start, end, name)`` of one thread as disjoint
    ``(start, end, innermost name, name at depth 1)`` in time order. Depth 0
    is an outermost event; its own time has ``None`` at depth 1."""
    out, stack, at = [], [], 0

    def emit(until) -> None:
        nonlocal at
        if until > at:
            out.append((at, until, stack[-1][2],
                        stack[1][2] if len(stack) > 1 else None))
            at = until

    for s, e, name in sorted(events, key=lambda ev: (ev[0], -ev[1])):
        while stack and stack[-1][1] <= s:
            emit(stack[-1][1])
            stack.pop()
        if stack:
            emit(s)
            e = min(e, stack[-1][1])   # a clock's rounding, not an overlap
        else:
            at = s
        stack.append((s, e, name))
    while stack:
        emit(stack[-1][1])
        stack.pop()
    return out


def _phase_of(child: str):
    if child in STAGE:
        return "stage"
    if child.startswith(DISPATCH):
        return "dispatch"
    if child in RESULT:
        return "result"
    return None


def _request_parts(window: tuple, spans: list):
    """``[(start, end, phase, span name)]``, disjoint and covering
    ``window``: one traced request by the program's annotations of it
    (``spans``: ``(start, end, name, line)``). ``None`` where the request has
    no ``query.result.<plan>``."""
    submit = [ev for ev in spans if ev[2].startswith("submit.")]
    if len(submit) != 1:
        raise SpanError(f"a traced request holds {len(submit)} submit roots")
    (s0, s1, name, client_line), = submit
    plan = name[len("submit."):]
    worker = [ev for ev in spans if ev[2] == "query." + plan
              and ev[3] != client_line]
    result = [ev for ev in spans if ev[2] == "query.result." + plan]
    if not result:
        return None
    c1, cname = result[0][1], result[0][2]
    free, parts = [window], []

    def give(start, end, phase: str, name: str) -> None:
        nonlocal free
        got = clip([(start, end)], free) if end > start else []
        parts.extend((s, e, phase, name) for s, e in got)
        free = _subtract(free, got)

    def thread(line, start, end) -> list:
        return _flatten([ev[:3] for ev in spans if ev[3] == line
                         and start <= ev[0] < end])

    q0 = s1           # a hit: nothing was handed to a worker
    if worker:
        q0, q1, qname, line = worker[0]
        resolved = [ev[1] for ev in spans
                    if ev[2] == RESOLVE and ev[3] == line]
        r1 = min(resolved[0], q1) if resolved else q1
        phase = "stage"
        for s, e, inner, child in thread(line, q0, q1):
            phase = (child and _phase_of(child)) or phase
            give(s, min(e, r1), phase, inner)
        give(r1, c1, "handoff", cname)
        give(r1, q1, "result", qname)
    for s, e, inner, _ in thread(client_line, s0, s1):
        give(s, e, "submit", inner)
    give(s1, q0, "handoff", cname)
    give(*window, "client", OWN)
    return parts


def reduce_profile(profile, platform: str):
    """``{"requests": n, "idle_s": s, "phases": {phase: s}, "spans": {span
    name: s}}``, seconds a request, or ``None`` for a program without the
    client's root."""
    if platform not in DEVICE_LINES:
        raise SpanError(f"no device line is known for platform {platform!r}")
    plane_prefix, line_prefix = DEVICE_LINES[platform]
    windows, annotated, planes = [], [], {}
    for p, plane in enumerate(profile.planes):
        if plane.name.startswith(HOST_PLANE):
            for ln, line in enumerate(plane.lines):
                if line.name.startswith(line_prefix):
                    continue   # the CPU stand-in's device threads
                for s, e, ev in _intervals(line):
                    if ev.name == REQUEST:
                        windows.append((s, e))
                    elif not ev.name.startswith("bench."):
                        stats = dict(ev.stats)
                        if "span" in stats and "request" in stats:
                            annotated.append((s, e, ev.name, (p, ln),
                                              stats["request"]))
        if plane.name.startswith(plane_prefix):
            ops = planes.setdefault(plane.name, [])
            for line in plane.lines:
                if not line.name.startswith(line_prefix):
                    continue
                for s, e, ev in _intervals(line):
                    # the stand-in's thunk markers are not operations
                    if platform != "cpu" or dict(ev.stats).get(
                            "hlo_module") is not None:
                        ops.append((s, e))
    planes = {p: union(ops) for p, ops in planes.items() if ops}
    windows = union(windows)
    if not windows or not planes:
        raise SpanError("the trace holds no bench.request or no device "
                        "operation")
    by_label: dict = {}
    for window in windows:
        started = {ev[4] for ev in annotated if ev[2].startswith("submit.")
                   and window[0] <= ev[0] < window[1]}
        if len(started) != 1:
            if not started:
                return None   # a program whose spans carry no request id
            raise SpanError(f"a traced request holds the submit roots of "
                            f"{len(started)} requests")
        (request,) = started
        parts = _request_parts(window, [ev[:4] for ev in annotated
                                        if ev[4] == request])
        if parts is None:
            return None
        for s, e, phase, name in parts:
            by_label.setdefault((phase, name), []).append((s, e))
    phases = dict.fromkeys(PHASES, 0.0)
    spans: dict = {}
    idle_s = 0.0
    for busy in planes.values():
        idle = [gap for window in windows
                for gap in _complement(clip(busy, [window]), window)]
        idle_s += total(idle)
        for (phase, name), intervals in by_label.items():
            inside = total(clip(idle, union(intervals)))
            phases[phase] += inside
            spans[name] = spans.get(name, 0.0) + inside
    per = 1e9 * len(planes) * len(windows)
    return {"requests": len(windows), "idle_s": idle_s / per,
            "phases": {k: v / per for k, v in phases.items()},
            "spans": {k: v / per for k, v in spans.items() if v}}


def reduced(run):
    """``reduce_profile`` of this run's trace, once a run, with the line
    ``idle by span: ...``; ``None`` without a trace or the client's root."""
    if hasattr(run, "_idle_reduce"):
        return run._idle_reduce
    path = trace_path(run)
    got = run._idle_reduce = None if path is None else reduce_profile(
        _load(path), run.device["platform"])
    if got is not None:
        top = sorted(got["spans"].items(), key=lambda kv: -kv[1])[:TOP]
        held = 100.0 * sum(v for _, v in top) / (got["idle_s"] or 1.0)
        getattr(run, "say", print)(
            "idle by span: " + ", ".join(f"{k} {v:.6f}" for k, v in top)
            + f"; s a request of {got['idle_s']:.6f} idle over "
            f"{got['requests']} traced requests, these {len(top)} names "
            f"hold {held:.1f}% of it")
    return got


def phase(run, name: str):
    """The device's idle seconds a request under the phase ``name``."""
    got = reduced(run)
    return None if got is None else got["phases"][name]
