"""From a profiler trace to device time by plan node: which operator of a
fused region a device operation belongs to.

``runtime/fusion.py`` lowers every node of a region under
``jax.named_scope`` of its own name (``fusion.node_scopes``: ``pk1``,
``groupby``, ``sort``, ``project.2``), inside ``region.<plan>``. XLA keeps
the scope in an operation's ``op_name``, and the TPU's trace carries that
as the stat ``tf_op`` of the operation's event metadata
(``jit(region_<plan>)/region.<plan>/groupby/while/body/closed_call/sort:``).
``jax.profiler.ProfileData`` shows an event's own stats and not its
metadata's, so this reads the ``.xplane.pb`` itself: a protobuf of planes,
lines, events and the metadata they refer to by id, of which the few
fields below are read with a wire-format reader of thirty lines and no
generated module (none is installed).

A program whose regions carry no node scopes (any commit before them)
gives ``None``; a reader then leaves its metric out of the line.
"""

from __future__ import annotations

import functools
import re

from benchmark import span_reduce
from benchmark.trace_reduce import DEVICE_LINES, clip, total, union

# field numbers of tsl/profiler/protobuf/xplane.proto
_SPACE_PLANES = 1
_PLANE_NAME, _PLANE_LINES, _PLANE_EVENT_META, _PLANE_STAT_META = 2, 3, 4, 5
_LINE_NAME, _LINE_TIMESTAMP_NS, _LINE_EVENTS = 2, 3, 4
_EVENT_META_ID, _EVENT_OFFSET_PS, _EVENT_DURATION_PS = 1, 2, 3
_META_NAME, _META_STATS = 2, 5
_STAT_META_ID, _STAT_STR, _STAT_BYTES, _STAT_REF = 1, 5, 6, 7
# and of xla/service/hlo.proto: HloProto.hlo_module, HloModuleProto.name /
# .computations, HloComputationProto.instructions, HloInstructionProto.name
# / .metadata, OpMetadata.op_name
_HLO_MODULE, _HLO_NAME, _HLO_COMPUTATIONS, _HLO_INSTRUCTIONS = 1, 1, 3, 2
_HLO_METADATA, _HLO_OP_NAME = 7, 2
_MAP_KEY, _MAP_VALUE = 1, 2
SCOPE_STAT = "tf_op"
_REGION = re.compile(r"(?:^|/)region\.[^/]+/([^/:]+)")


def _varint(buf: memoryview, at: int) -> tuple:
    """(value, position after it) of the varint at ``at``."""
    value, shift = 0, 0
    while True:
        b = buf[at]
        at += 1
        value |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return value, at


def _fields(buf: memoryview):
    """(field number, value) of one message: an int for a varint or fixed
    field, a memoryview for a length-delimited one."""
    at, end = 0, len(buf)
    while at < end:
        key, at = _varint(buf, at)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, at = _varint(buf, at)
            yield number, value
        elif wire == 2:
            size, at = _varint(buf, at)
            yield number, buf[at:at + size]
            at += size
        elif wire in (1, 5):
            width = 8 if wire == 1 else 4
            yield number, int.from_bytes(buf[at:at + width], "little")
            at += width
        else:
            raise ValueError(f"wire type {wire} in an xplane message")


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def _map(entries: list, parse) -> dict:
    out = {}
    for entry in entries:
        fields = dict(_fields(entry))
        out[fields.get(_MAP_KEY, 0)] = parse(fields[_MAP_VALUE])
    return out


def _hlo_op_names(space: memoryview) -> dict:
    """``{(module, instruction): op_name}`` from the HLO protos a trace
    keeps in its plane ``/host:metadata`` (one a module, the bytes stat of
    an event metadata named ``<module>(<program id>)``)."""
    out = {}
    for number, plane in _fields(space):
        if number != _SPACE_PLANES:
            continue
        fields = list(_fields(plane))
        if not any(n == _PLANE_NAME and _text(v) == "/host:metadata"
                   for n, v in fields):
            continue
        protos = [dict(_fields(stat)).get(_STAT_BYTES)
                  for n, entry in fields if n == _PLANE_EVENT_META
                  for m, stat in _fields(dict(_fields(entry))[_MAP_VALUE])
                  if m == _META_STATS]
        for proto in protos:
            if proto is None:
                continue
            module = list(_fields(dict(_fields(proto))[_HLO_MODULE]))
            name = _text(next(v for n, v in module if n == _HLO_NAME))
            for n, computation in module:
                if n != _HLO_COMPUTATIONS:
                    continue
                for m, instruction in _fields(computation):
                    if m != _HLO_INSTRUCTIONS:
                        continue
                    ins = dict(_fields(instruction))
                    op = dict(_fields(ins.get(_HLO_METADATA, b""))).get(
                        _HLO_OP_NAME)
                    if op is not None:
                        out[name, _text(ins[_HLO_NAME])] = _text(op)
    return out


def _cpu_operations(path: str, space: memoryview) -> list:
    """The tests' stand-in: XLA:CPU's thunks name their module and
    instruction (stats ``hlo_module``, ``hlo_op``), and the scope is the
    instruction's ``op_name`` in the module's HLO proto."""
    names = _hlo_op_names(space)
    plane_prefix, line_prefix = DEVICE_LINES["cpu"]
    out = []
    for plane in span_reduce._load(path).planes:
        if not plane.name.startswith(plane_prefix):
            continue
        for line in plane.lines:
            if not line.name.startswith(line_prefix):
                continue
            for ev in line.events:
                stats = dict(ev.stats)
                if ev.duration_ns > 0 and "hlo_module" in stats:
                    out.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                ev.name, names.get((
                                    str(stats["hlo_module"]),
                                    str(stats.get("hlo_op"))))))
    return out


def device_operations(path: str, platform: str) -> list:
    """``[(start_ns, end_ns, name, scope or None)]`` of every operation
    that ran on the device, from the ``.xplane.pb`` at ``path``."""
    plane_prefix, line_prefix = DEVICE_LINES[platform]
    with open(path, "rb") as f:
        space = memoryview(f.read())
    if platform == "cpu":
        return _cpu_operations(path, space)
    out = []
    for number, plane in _fields(space):
        if number != _SPACE_PLANES:
            continue
        name, lines, event_meta, stat_meta = "", [], [], []
        for number, value in _fields(plane):
            if number == _PLANE_NAME:
                name = _text(value)
            elif number == _PLANE_LINES:
                lines.append(value)
            elif number == _PLANE_EVENT_META:
                event_meta.append(value)
            elif number == _PLANE_STAT_META:
                stat_meta.append(value)
        if not name.startswith(plane_prefix):
            continue
        stat_names = _map(stat_meta, lambda v: _text(
            dict(_fields(v)).get(_META_NAME, b"")))
        wanted = {i for i, n in stat_names.items() if n == SCOPE_STAT}

        def parse_meta(view):
            label, scope = "", None
            for number, value in _fields(view):
                if number == _META_NAME:
                    label = _text(value)
                elif number == _META_STATS:
                    stat = dict(_fields(value))
                    if stat.get(_STAT_META_ID) in wanted:
                        if _STAT_STR in stat:
                            scope = _text(stat[_STAT_STR])
                        elif _STAT_REF in stat:   # a string kept once
                            scope = stat_names.get(stat[_STAT_REF])
            return label, scope

        metas = _map(event_meta, parse_meta)
        for line in lines:
            fields = list(_fields(line))
            line_name = next((_text(v) for n, v in fields
                              if n == _LINE_NAME), "")
            if not line_name.startswith(line_prefix):
                continue
            t0 = next((v for n, v in fields if n == _LINE_TIMESTAMP_NS), 0)
            for number, event in fields:
                if number != _LINE_EVENTS:
                    continue
                ev = dict(_fields(event))
                dur = ev.get(_EVENT_DURATION_PS, 0)
                if dur <= 0:
                    continue
                start = t0 + ev.get(_EVENT_OFFSET_PS, 0) / 1000.0
                label, scope = metas.get(ev.get(_EVENT_META_ID), ("", None))
                out.append((start, start + dur / 1000.0, label, scope))
    return out


def node_of(scope) -> str | None:
    """The plan node an operation's scope stat names: the path element
    after ``region.<plan>``; ``None`` for an operation under no node."""
    match = _REGION.search(scope or "")
    return match.group(1) if match else None


@functools.lru_cache(maxsize=1)
def _by_node(path: str, platform: str) -> tuple:
    """(requests traced, {node or None: seconds inside the traced
    requests}) over the operations whose scope names a region."""
    profile = span_reduce._load(path)
    requests = union([
        (ev.start_ns, ev.start_ns + ev.duration_ns)
        for plane in profile.planes
        if plane.name.startswith(span_reduce.HOST_PLANE)
        for line in plane.lines for ev in line.events
        if ev.name == span_reduce.REQUEST and ev.duration_ns > 0])
    spans: dict = {}
    for start, end, _, scope in device_operations(path, platform):
        if scope is not None and "region." in scope:
            spans.setdefault(node_of(scope), []).append((start, end))
    seconds = {node: total(clip(union(iv), requests)) / 1e9
               for node, iv in spans.items()}
    return len(requests), seconds


def seconds_by_node(run):
    """``(requests traced, {node scope: device seconds})`` of this run's
    trace; ``None`` without a trace, or with no node scope in it."""
    path = span_reduce.trace_path(run)
    if path is None:
        return None
    requests, seconds = _by_node(path, run.device["platform"])
    if not requests or not any(node is not None for node in seconds):
        return None
    return requests, seconds


JOINS, GROUPBYS, SORTS = ("Join", "DensePkJoin"), ("GroupBy",), ("Sort",)


def _node_kinds(run):
    """``{node scope: fusion class name}`` over the plans of the mix;
    ``None`` for a program that names no node scopes."""
    from spark_rapids_jni_tpu.runtime import fusion

    if not hasattr(fusion, "node_scopes"):
        return None
    if not hasattr(run, "_node_kinds"):   # every reader asks once
        run._node_kinds = {}
        for mod in run.plans.values():
            nodes = fusion._topo(mod.plan().root)
            scopes = fusion.node_scopes(nodes)
            run._node_kinds.update(
                (scopes[id(n)], type(n).__name__) for n in nodes)
    return run._node_kinds


def kind_seconds_per_query(run, kinds: tuple):
    """Device seconds a request under the scopes of the plan nodes of
    ``kinds`` (``fusion`` class names); ``None`` where the program names
    no node scopes, the mix has no such node or the trace shows none. The
    first call of a run prints how the region's time splits over the
    operators."""
    by_scope = _node_kinds(run)
    if not by_scope or not set(by_scope.values()) & set(kinds):
        return None
    found = seconds_by_node(run)
    if found is None or not set(by_scope) & set(found[1]):
        return None   # no trace, or an executable without the plan's scopes
    requests, seconds = found

    def under(wanted: tuple) -> float:
        return sum(seconds.get(scope, 0.0)
                   for scope, kind in by_scope.items() if kind in wanted)

    if not getattr(run, "_operator_split_said", False):
        run._operator_split_said = True
        part = {"join": under(JOINS), "groupby": under(GROUPBYS),
                "sort": under(SORTS)}
        scoped = sum(v for node, v in seconds.items() if node is not None)
        reduced = span_reduce.device(run)
        region = reduced["region_s"] if reduced else scoped
        unscoped = max(region - scoped, 0.0)
        run.say(
            "operators: " + ", ".join(
                f"{k} {v / requests:.6f}s" for k, v in part.items())
            + f", other nodes {(scoped - sum(part.values())) / requests:.6f}s"
            f", under no node's scope {unscoped / requests:.6f}s "
            f"({100.0 * unscoped / region if region else 0.0:.2f}% of the "
            f"region's {region / requests:.6f}s a request)")
    return under(kinds) / requests
