"""From a profiler trace (``.xplane.pb``) to device busy time, idle share,
the device operations that took most time and the idle gaps named by what
the host was doing.

The harness wraps its own calls in ``jax.profiler.TraceAnnotation``:
``bench.request`` around one timed request (submit start to result
synced), and the leaves ``bench.submit``, ``bench.result``,
``bench.readback`` and ``bench.roll`` for what the host does. Those land in
the host plane on the same clock as the device plane's operations.

Busy time is the union of the intervals in which an operation ran on the
device, clipped to the traced requests' intervals; the window is the
length of those intervals. A share over 100% or a trace without a device
plane raises: a number is never made up.
"""

from __future__ import annotations

REQUEST = "bench.request"
LEAVES = ("bench.submit", "bench.result", "bench.readback", "bench.roll")
# where a platform's trace keeps the operations that ran on the device:
# (plane name prefix, line name prefix). The CPU row is a stand-in for the
# tests only: XLA:CPU runs its thunks on host threads.
DEVICE_LINES = {
    "tpu": ("/device:TPU:", "XLA Ops"),
    "cpu": ("/host:CPU", "tf_XLA"),
}
HOST_PLANE = "/host:CPU"


class TraceError(RuntimeError):
    pass


def union(intervals: list) -> list:
    """Sorted, disjoint (start, end) pairs covering the same points."""
    out: list = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def clip(intervals: list, windows: list) -> list:
    """The parts of disjoint sorted ``intervals`` inside disjoint sorted
    ``windows``."""
    out, j = [], 0
    for s, e in intervals:
        while j < len(windows) and windows[j][1] <= s:
            j += 1
        k = j
        while k < len(windows) and windows[k][0] < e:
            out.append((max(s, windows[k][0]), min(e, windows[k][1])))
            k += 1
    return [i for i in out if i[1] > i[0]]


def total(intervals: list) -> float:
    return float(sum(e - s for s, e in intervals))


def short_name(name: str) -> str:
    """A device operation's name without its HLO text: ``%fusion.163 =
    (s32[...]) fusion(...)`` is ``fusion.163``; a custom call keeps its
    target (``custom-call.4 X64Combine``)."""
    short = name.split(" = ", 1)[0].lstrip("%")
    if 'custom_call_target="' in name:
        short += " " + name.split('custom_call_target="', 1)[1].split('"')[0]
    return short


def _events(profile, plane_prefix: str, line_prefix: str) -> dict:
    """{plane name: [(start_ns, end_ns, name)]} of the matching lines."""
    out: dict = {}
    for plane in profile.planes:
        if not plane.name.startswith(plane_prefix):
            continue
        for ln in plane.lines:
            if not ln.name.startswith(line_prefix):
                continue
            for ev in ln.events:
                if ev.duration_ns > 0:
                    out.setdefault(plane.name, []).append(
                        (ev.start_ns, ev.start_ns + ev.duration_ns, ev.name))
    return out


def reduce(path: str, platform: str, chips: int = 1) -> dict:
    """Reduce the trace at ``path``; seconds everywhere."""
    import jax

    return reduce_profile(jax.profiler.ProfileData.from_file(path),
                          platform, chips)


def reduce_profile(profile, platform: str, chips: int = 1) -> dict:
    if platform not in DEVICE_LINES:
        raise TraceError(f"no device line is known for platform {platform!r}")
    host = [ev for evs in _events(profile, HOST_PLANE, "").values()
            for ev in evs if ev[2].startswith("bench.")]
    requests = union([(s, e) for s, e, n in host if n == REQUEST])
    if not requests:
        raise TraceError(f"the trace holds no {REQUEST!r} annotation")
    plane_prefix, line_prefix = DEVICE_LINES[platform]
    planes = _events(profile, plane_prefix, line_prefix)
    if platform == "cpu":   # the stand-in: thunk markers are not operations
        planes = {p: [ev for ev in evs if not ev[2].startswith(
            ("end: ", "Threadpool", "ThunkExecutor"))]
            for p, evs in planes.items()}
    planes = {p: evs for p, evs in planes.items() if evs}
    if not planes:
        raise TraceError(
            f"the trace has no device plane {plane_prefix}* with a line "
            f"{line_prefix}*: no operation ran on the device")
    window_ns = total(requests)
    busy_ns, by_op, gaps = [], {}, {}
    span = [(requests[0][0], requests[-1][1])]
    leaves = [(s, e, n) for s, e, n in host if n in LEAVES]
    for evs in planes.values():
        busy_all = union([(s, e) for s, e, _ in evs])
        busy = clip(busy_all, requests)
        busy_ns.append(total(busy))
        for s, e, name in evs:
            inside = total(clip([(s, e)], requests))
            if inside:
                name = short_name(name)
                by_op[name] = by_op.get(name, 0.0) + inside
        # idle gaps over the whole traced span, named by the leaf annotation
        # the host was in; what no leaf covers is the harness's own time
        idle = _complement(clip(busy_all, span), span[0])
        for name in LEAVES:
            inside = total(clip(idle, union(
                [(s, e) for s, e, n in leaves if n == name])))
            if inside:
                gaps[name] = gaps.get(name, 0.0) + inside
        covered = total(clip(idle, union([(s, e) for s, e, _ in leaves])))
        if total(idle) - covered > 0:
            gaps["bench.other"] = gaps.get("bench.other", 0.0) + (
                total(idle) - covered)
    n = len(planes)
    if n < chips:
        raise TraceError(f"{n} device planes in the trace, the cell uses "
                         f"{chips} chips")
    busy_s = sum(busy_ns) / n / 1e9
    window_s = window_ns / 1e9
    if busy_s > window_s * (1 + 1e-9):
        raise TraceError(f"device busy {busy_s}s exceeds the traced window "
                         f"{window_s}s: a share over 100%")

    def top(d: dict) -> list:
        return [[k, v / n / 1e9] for k, v in sorted(
            d.items(), key=lambda kv: -kv[1])[:10]]

    return {"requests": sum(nm == REQUEST for _, _, nm in host),
            "busy_s": busy_s, "window_s": window_s,
            "idle_share": 100.0 * (1.0 - busy_s / window_s),
            "device_ops": top(by_op), "idle_gaps": top(gaps)}


def _complement(intervals: list, span: tuple) -> list:
    """The gaps between disjoint sorted ``intervals`` inside ``span``."""
    out, at = [], span[0]
    for s, e in intervals:
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if at < span[1]:
        out.append((at, span[1]))
    return out
