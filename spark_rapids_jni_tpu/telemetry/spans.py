"""Hierarchical query spans: one request, one span tree per thread it
crosses, joined by the request's id.

The reference answers "why was this query slow" with NVTX ranges
(``CUDF_FUNC_RANGE()``) that nest into a causal timeline in Nsight; our
flat counters and unordered JSONL events cannot. A span is a named,
timestamped (``time.monotonic``) node with an id, a parent id and a
status (``ok`` / ``degraded`` / ``cancelled`` / ``failed``). A served
request starts on the client's thread, where ``QueryServer.submit`` opens
the root ``submit.<plan>`` (``request=<id>``), and goes on on a worker,
whose root ``query.<plan>`` carries the same ``request`` and
``caused_by=<the submit span's id>``; every instrumented seam underneath
attaches a child, so the two trees record::

    submit.<plan> -> scan.footer (a Parquet split: parquet/split.py)
                  -> cache.fingerprint -> cache.fingerprint.{copy,hash}
                  -> cache.lookup -> admission.enqueue
                  (a cache hit: -> query.<plan> -> cache.hit)
    query.<plan>  -> admission.queue -> admission.wait
                  -> server.stage_bindings (-> scan -> scan.decode ->
                     scan.decode.chunk on the pool's threads; scan.stage)
                  -> rung.* -> region.<plan>
                     -> dispatch.{pad,compile,execute} / pipeline.chunk ->
                     pipeline.{decode,staging,transfer,compute,merge}
                     -> spill/unspill
                  -> server.record_actual -> cache.put

Contracts:
- **Zero overhead when disabled.** Every factory checks
  ``telemetry.enabled`` once and hands back a shared no-op span; nothing
  allocates, nothing locks, nothing emits.
- **Never on the device path.** Spans only read the host clock and
  append to host-side structures; opening or closing one never forces a
  device sync or transfer.
- **On the profiler's clock too.** Entering a span also enters a
  ``jax.profiler.TraceAnnotation`` of its name (stats ``span`` and, in a
  request's tree, ``request``), so a profiler trace holds the program's
  spans in its host plane beside the device plane. This module still
  never imports jax: the class is taken from ``sys.modules`` once jax is
  there, and outside a profiler session entering one costs a flag test.
- **Emission through the one JSONL sink.** Closing a span emits a
  ``kind="span"`` record via events._emit — same ring buffer, same
  file, same never-raise posture as every other telemetry record.
- **Scope discipline.** A span must be used as a context manager (tpulint
  rule span-must-scope): ``with spans.span(...) as sp:`` — a raise then
  still closes it, marking status from the exception class
  (QueryCancelled -> ``cancelled``, anything else -> ``failed``).

The **flight recorder** keeps a bounded ring of recent span trees
(``telemetry.flight_recorder_depth``); ``dump_flight_record`` snapshots
the current tree plus caller-supplied limiter/queue state into one
structured artifact, written to ``telemetry.flight_recorder_path`` when
set and referenced from the server's rejection/failure records.

Chrome-trace export (``chrome_trace`` / ``python -m
spark_rapids_jni_tpu.telemetry trace``) lays the same records out as
``chrome://tracing`` / Perfetto complete events, one display track per
(query, OS thread) pair so overlapping decode-pool chunks render side
by side while each track stays properly nested.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time
from collections import deque
from typing import Any, Iterable, Optional

import importlib

from spark_rapids_jni_tpu.telemetry.registry import REGISTRY
from spark_rapids_jni_tpu.utils.config import get_option

# The package __init__ re-exports the events() *function*, which shadows the
# submodule attribute — resolve the module itself, unambiguously.
_events = importlib.import_module("spark_rapids_jni_tpu.telemetry.events")

__all__ = [
    "Span",
    "NULL_SPAN",
    "span",
    "child",
    "record_child",
    "current_span",
    "current_root",
    "self_times",
    "validate",
    "chrome_trace",
    "write_chrome_trace",
    "flight_records",
    "dump_flight_record",
    "reset",
]

STATUSES = ("ok", "degraded", "cancelled", "failed")

# Walking __mro__ by class NAME keeps this module import-free of the
# runtime layer (resilience imports telemetry; the reverse would cycle).
_CANCEL_EXC_NAME = "QueryCancelled"

_ctx = threading.local()  # .stack: list[Span] — this thread's open spans

_id_lock = threading.Lock()
_next_id = 0


def _new_id() -> int:
    global _next_id
    with _id_lock:
        _next_id += 1
        return _next_id


def _stack() -> list:
    stack = getattr(_ctx, "stack", None)
    if stack is None:
        stack = []
        _ctx.stack = stack
    return stack


_annotation_cls = None  # jax.profiler.TraceAnnotation, once jax is imported


def _trace_annotation():
    """``jax.profiler.TraceAnnotation`` if the workload has imported jax,
    else None. Never imports it (test_import_hygiene.py)."""
    global _annotation_cls
    if _annotation_cls is None:
        profiler = getattr(sys.modules.get("jax"), "profiler", None)
        _annotation_cls = getattr(profiler, "TraceAnnotation", None)
    return _annotation_cls


class _NullSpan:
    """Shared no-op span: what the factories return when telemetry is
    disabled (or ``child`` finds no open parent). Supports the full Span
    surface so call sites never branch on enablement."""

    __slots__ = ()
    id = None
    parent_id = None
    root = None
    name = ""
    status = "ok"

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc: object) -> bool:
        return False

    def set_status(self, status: str) -> None:
        pass

    def annotate(self, **attrs: Any) -> None:
        pass

    def __bool__(self) -> bool:
        return False


NULL_SPAN = _NullSpan()


class Span:
    """One live node of a query's causal tree.

    Created via the ``span``/``child`` factories, entered immediately
    (``with``), closed by ``__exit__`` — which stamps the end timestamp,
    derives status from any in-flight exception, emits the JSONL record
    and, for a root, hands the completed tree to the flight recorder.
    Children normally attach to the thread-local current span; crossing
    a thread boundary (decode pool) passes ``parent=`` explicitly and
    the child still pushes onto *its* thread's stack so deeper spans
    nest correctly.
    """

    __slots__ = ("id", "parent", "root", "name", "status", "start", "end",
                 "attrs", "children", "tid", "_entered", "_annotation",
                 "_tree_lock", "_nodes", "_dropped", "_max_nodes")

    def __init__(self, name: str, parent: Optional["Span"],
                 attrs: dict) -> None:
        self.id = _new_id()
        self.name = str(name)
        self.parent = parent
        self.status = "ok"
        self.attrs = dict(attrs)
        self.children: list = []
        self.start: float = 0.0
        self.end: Optional[float] = None
        self.tid = threading.get_ident()
        self._entered = False
        self._annotation = None
        if parent is None:
            self.root = self
            # the in-memory tree backs the flight recorder and inspect();
            # the JSONL sink stays unbounded — past the cap, records still
            # emit but the tree stops growing.
            self._tree_lock = threading.Lock()
            self._nodes = 1
            self._dropped = 0
            self._max_nodes = int(get_option("telemetry.max_spans_per_tree"))
        else:
            self.root = parent.root
            self._tree_lock = None
            self._nodes = 0
            self._dropped = 0
            self._max_nodes = 0

    # -- context management --------------------------------------------------

    def __enter__(self) -> "Span":
        if self._entered:
            raise RuntimeError(f"span {self.name!r} entered twice")
        self._attach()
        _stack().append(self)
        self.start = time.monotonic()
        cls = _trace_annotation()
        if cls is not None:
            stats = {"span": self.id}
            request = self.root.attrs.get("request")
            if request is not None:
                stats["request"] = request
            self._annotation = cls(self.name, **stats)
            self._annotation.__enter__()
        return self

    def _attach(self) -> None:
        """Mark the span entered on this thread and hang it under its
        parent, while the tree has room."""
        self._entered = True
        self.tid = threading.get_ident()
        if self.parent is not None:
            root = self.root
            with root._tree_lock:
                if root._nodes < root._max_nodes:
                    root._nodes += 1
                    self.parent.children.append(self)
                else:
                    root._dropped += 1

    def __exit__(self, exc_type, exc, tb) -> bool:
        if self._annotation is not None:
            self._annotation.__exit__(exc_type, exc, tb)
            self._annotation = None
        self.end = time.monotonic()
        if exc_type is not None and self.status == "ok":
            names = {c.__name__ for c in getattr(exc_type, "__mro__", ())}
            self.status = ("cancelled" if _CANCEL_EXC_NAME in names
                           else "failed")
            if self.status == "failed":
                self.attrs.setdefault("error", exc_type.__name__)
        stack = _stack()
        if stack and stack[-1] is self:
            stack.pop()
        self._emit()
        return False

    def _emit(self) -> None:
        """The closed span's JSONL record; a root also goes to the flight
        recorder."""
        if _events.enabled():
            rec = dict(self.attrs)
            rec.update({
                "kind": "span",
                "op": self.name,
                "span": self.id,
                "parent": self.parent.id if self.parent is not None else None,
                "root": self.root.id,
                "t0": self.start,
                "t1": self.end,
                "dur_ms": round((self.end - self.start) * 1e3, 6),
                "status": self.status,
                "tid": self.tid,
            })
            _events._emit(rec)
            REGISTRY.counter("spans_total").inc()
            if self.parent is None:
                # the closed tree itself: serialised when someone reads
                # the ring, which is almost never
                _RECORDER.note({"trigger": "completed", "root": self.id,
                                "tree": self})

    # -- mutation ------------------------------------------------------------

    def set_status(self, status: str) -> None:
        if status not in STATUSES:
            raise ValueError(
                f"span status {status!r} not in {STATUSES}")
        self.status = status

    def annotate(self, **attrs: Any) -> None:
        self.attrs.update(attrs)

    # -- tree inspection -----------------------------------------------------

    def _node(self) -> dict:
        return {
            "span": self.id,
            "name": self.name,
            "status": self.status if self.end is not None else "open",
            "t0": self.start,
            "t1": self.end,
            "attrs": dict(self.attrs),
            "children": [c._node() for c in self.children],
        }

    def tree(self) -> dict:
        """Serialize the whole tree this span roots (or belongs to).
        Open spans appear with ``t1: null`` / status ``open``."""
        root = self.root
        if root is None:        # taken apart: what is left is the node
            return self._node()
        with root._tree_lock:
            out = root._node()
        if root._dropped:
            out["dropped_spans"] = root._dropped
        return out

    def _take_apart(self) -> None:
        """Unlink the closed tree this root holds, once the flight recorder
        has let go of it. A tree is a cycle (a root is its own ``root``,
        every child points back at its parent): kept for a few requests it
        grows old, and left whole it would wait for a collection of the
        oldest generation to be freed. Apart, its spans go by count."""
        todo = [self]
        while todo:
            node = todo.pop()
            todo.extend(node.children)
            node.children = []
            node.parent = node.root = None

    def deepest_open(self) -> Optional["Span"]:
        """The deepest not-yet-closed span in this tree — 'where is this
        query right now' for live introspection."""
        root = self.root
        with root._tree_lock:
            node = root if root.end is None else None
            cur = root
            while True:
                nxt = None
                for c in reversed(cur.children):
                    if c.end is None:
                        nxt = c
                        break
                if nxt is None:
                    return node
                node = nxt
                cur = nxt


def span(name: str, *, parent: Optional[Span] = None, **attrs: Any):
    """Open a span. With no ``parent`` and no thread-local current span
    this starts a new root (a new query tree) — seams that must never
    create trees of their own use :func:`child` instead.
    ``parent=NULL_SPAN`` starts a root whatever this thread has open (the
    client's end of a request, which belongs to no tree of its caller)."""
    if not _events.enabled():
        return NULL_SPAN
    if parent is None:
        parent = current_span()
    if isinstance(parent, _NullSpan):
        parent = None
    return Span(name, parent, attrs)


def child(name: str, *, parent: Optional[Span] = None, **attrs: Any):
    """Open a child span only when there is a tree to attach to: returns
    the no-op span when telemetry is disabled or no parent exists. The
    instrumentation seams (trace_range, pipeline stages, dispatch,
    spill) all use this, so standalone calls outside a served query
    never fabricate orphan roots."""
    if not _events.enabled():
        return NULL_SPAN
    p = parent if parent is not None else current_span()
    if p is None or isinstance(p, _NullSpan):
        return NULL_SPAN
    return Span(name, p, attrs)


def record_child(name: str, start: float, end: Optional[float] = None,
                 **attrs: Any) -> None:
    """Record a child of this thread's current span that began at ``start``
    (a ``time.monotonic`` reading, taken on whichever thread the wait
    began on) and ends at ``end`` (another such reading; now when left
    out). For an interval no ``with`` on this thread can see: a ticket's
    wait between the client's enqueue and the worker's pickup, which lies
    before its parent's own start, or the two halves of a client's wait
    for its result, which part at a moment the worker stamped. It has no
    profiler annotation (the profiler takes no past start)."""
    parent = current_span()
    if parent is None or not _events.enabled():
        return
    sp = Span(name, parent, attrs)
    sp._attach()
    sp.start = float(start)
    sp.end = time.monotonic() if end is None else float(end)
    sp._emit()


def self_times(root: Span) -> dict:
    """{span name: seconds} of self time over the tree of ``root``: a
    node's duration less what its children cover of it (a child recorded
    after the fact may lie outside its parent; children on a pool's
    threads overlap one another). A node still open ends now."""
    now = time.monotonic()
    out: dict = {}
    with root._tree_lock:
        todo = [root]
        while todo:
            node = todo.pop()
            t0 = node.start
            t1 = node.end if node.end is not None else now
            covered, at = 0.0, t0
            for s, e in sorted(
                    (max(c.start, t0),
                     min(c.end if c.end is not None else now, t1))
                    for c in node.children):
                if e > at:
                    covered += e - max(s, at)
                    at = e
            out[node.name] = out.get(node.name, 0.0) + max(
                t1 - t0 - covered, 0.0)
            todo.extend(node.children)
    return out


def current_span() -> Optional[Span]:
    stack = getattr(_ctx, "stack", None)
    return stack[-1] if stack else None


def current_root() -> Optional[Span]:
    cur = current_span()
    return cur.root if cur is not None else None


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------


_SLOW_KEPT = 32   # dumps of slow requests kept, apart from the ring


class _FlightRecorder:
    """Bounded ring of recent span trees (completed roots and explicit
    dumps). Depth re-reads ``telemetry.flight_recorder_depth`` on every
    note so tests/operators can resize without rebuilding the ring. A
    completed root is noted as the closed ``Span`` and serialised when the
    ring is read; one that leaves the ring is taken apart. Dumps of slow requests (trigger ``slow``) are kept apart,
    the newest ``_SLOW_KEPT``: the next few requests' completed trees must
    not push out the one a reader will come for."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._ring: deque = deque()
        self._slow: deque = deque(maxlen=_SLOW_KEPT)
        self._seq = 0

    def note(self, entry: dict) -> None:
        depth = max(1, int(get_option("telemetry.flight_recorder_depth")))
        gone = []
        with self._lock:
            self._seq += 1
            entry = dict(entry)
            entry["seq"] = self._seq
            if entry["trigger"] == "slow":
                self._slow.append(entry)
                return
            self._ring.append(entry)
            while len(self._ring) > depth:
                gone.append(self._ring.popleft()["tree"])
        for tree in gone:
            if isinstance(tree, Span):
                tree._take_apart()

    def records(self) -> list:
        with self._lock:
            kept = sorted([*self._slow, *self._ring],
                          key=lambda e: e["seq"])
        return [dict(e, tree=e["tree"].tree())
                if isinstance(e["tree"], Span) else e for e in kept]

    def clear(self) -> None:
        with self._lock:
            self._ring.clear()
            self._slow.clear()


_RECORDER = _FlightRecorder()


def flight_records() -> list:
    """The in-memory flight-recorder ring and the kept dumps of slow
    requests, oldest first."""
    return _RECORDER.records()


def _safe_name(text: str) -> str:
    return "".join(c if c.isalnum() or c in "-_" else "_" for c in str(text))


def dump_flight_record(trigger: str, *, root: Optional[Span] = None,
                       roots: Iterable[Span] = (),
                       state: Optional[dict] = None) -> Optional[str]:
    """Snapshot one query's span tree plus the caller-supplied runtime
    state (limiter watermarks, queue depths) into a single structured
    artifact: appended to the in-memory ring always, written as JSON
    under ``telemetry.flight_recorder_path`` when that is set. ``roots``
    are the roots of every tree of one request (client's, worker's,
    client's end): the first is the artifact's ``tree``, all of them its
    ``trees``. Returns
    the artifact path (referenced from QueryRejected / failure records)
    or None. Never raises — a failed write only bumps the
    ``dropped_writes`` counter, matching the JSONL sink's posture."""
    if not _events.enabled():
        return None
    roots = [r for r in roots if isinstance(r, Span)]
    if root is None:
        root = roots[0] if roots else current_root()
    tree = root.tree() if isinstance(root, Span) else None
    artifact = {
        "kind": "flight_record",
        "trigger": str(trigger),
        "ts": time.time(),
        "session": _events.current_session(),
        "root": root.id if isinstance(root, Span) else None,
        "tree": tree,
        "state": dict(state) if state else {},
    }
    if roots:
        artifact["trees"] = [r.tree() for r in roots]
    _RECORDER.note(artifact)
    out_dir = str(get_option("telemetry.flight_recorder_path") or "")
    if not out_dir:
        return None
    with _RECORDER._lock:
        seq = _RECORDER._seq
    fname = os.path.join(
        out_dir,
        f"flight-{seq:06d}-{_safe_name(trigger)}-"
        f"{artifact['root'] or 'noroot'}.json")
    try:
        os.makedirs(out_dir, exist_ok=True)
        with open(fname, "w", encoding="utf-8") as fh:
            json.dump(artifact, fh, sort_keys=True, default=str)
    except OSError:
        REGISTRY.counter("dropped_writes").inc()
        return None
    REGISTRY.counter("flight_records").inc()
    return fname


def reset() -> None:
    """Clear the flight-recorder ring (tests)."""
    _RECORDER.clear()


# ---------------------------------------------------------------------------
# record-stream analysis: wellformedness, Chrome trace, phase attribution
# ---------------------------------------------------------------------------


def _span_records(records: Iterable[dict]) -> list:
    return [r for r in records
            if isinstance(r, dict) and r.get("kind") == "span"
            and "t0" in r and "t1" in r]


def validate(records: Iterable[dict]) -> list:
    """Wellformedness of the span records in a telemetry stream: every
    tree has exactly one root, every parent id resolves inside the same
    tree, and end >= start. Returns a list of problem strings (empty =
    well-formed) — used by tests and the CI trace smoke."""
    recs = _span_records(records)
    problems = []
    by_id = {}
    for r in recs:
        sid = r.get("span")
        if sid in by_id:
            problems.append(f"duplicate span id {sid}")
        by_id[sid] = r
    roots_of: dict = {}
    for r in recs:
        roots_of.setdefault(r.get("root"), []).append(r)
    for root_id, members in sorted(roots_of.items(), key=lambda kv: str(kv[0])):
        roots = [r for r in members if r.get("parent") is None]
        if len(roots) != 1:
            problems.append(
                f"tree {root_id}: {len(roots)} parentless spans (want 1)")
        elif roots[0].get("span") != root_id:
            problems.append(
                f"tree {root_id}: root record has span id "
                f"{roots[0].get('span')}")
        for r in members:
            pid = r.get("parent")
            if pid is not None:
                parent = by_id.get(pid)
                if parent is None:
                    problems.append(
                        f"span {r.get('span')} ({r.get('op')}): orphan "
                        f"parent id {pid}")
                elif parent.get("root") != r.get("root"):
                    problems.append(
                        f"span {r.get('span')}: parent {pid} belongs to "
                        f"tree {parent.get('root')}, not {r.get('root')}")
            if float(r.get("t1", 0.0)) < float(r.get("t0", 0.0)):
                problems.append(
                    f"span {r.get('span')} ({r.get('op')}): end < start")
            if r.get("status") not in STATUSES:
                problems.append(
                    f"span {r.get('span')} ({r.get('op')}): bad status "
                    f"{r.get('status')!r}")
    return problems


_ARG_KEYS = ("span", "parent", "root", "status", "session")


def chrome_trace(records: Iterable[dict]) -> dict:
    """Lay the span records out as Chrome-trace / Perfetto 'complete'
    (ph: X) events. Chrome nests events per (pid, tid) by time
    containment, and our stack discipline only holds per OS thread
    within one query, so each (query root, OS thread) pair gets its own
    display track — overlapping decode-pool chunks land side by side
    instead of corrupting one track's nesting."""
    recs = sorted(_span_records(records),
                  key=lambda r: float(r.get("t0", 0.0)))
    lanes: dict = {}
    root_labels: dict = {}
    events = []
    for r in recs:
        root = r.get("root", r.get("span"))
        key = (root, r.get("tid", 0))
        tid = lanes.setdefault(key, len(lanes) + 1)
        if r.get("parent") is None:
            sess = r.get("session", "")
            root_labels[root] = (f"{r.get('op', '?')}"
                                 + (f" [{sess}]" if sess else ""))
        args = {k: r[k] for k in _ARG_KEYS if k in r}
        for k, v in r.items():
            if k not in args and k not in ("kind", "op", "t0", "t1",
                                           "dur_ms", "tid", "ts",
                                           "platform"):
                args[k] = v
        events.append({
            "name": r.get("op", "?"),
            "cat": "span",
            "ph": "X",
            "ts": round(float(r["t0"]) * 1e6, 3),
            "dur": max(round((float(r["t1"]) - float(r["t0"])) * 1e6, 3),
                       0.001),
            "pid": 1,
            "tid": tid,
            "args": args,
        })
    meta = [{"name": "process_name", "ph": "M", "pid": 1,
             "args": {"name": "spark_rapids_jni_tpu"}}]
    for (root, os_tid), tid in sorted(lanes.items(),
                                      key=lambda kv: kv[1]):
        label = root_labels.get(root, f"tree {root}")
        meta.append({"name": "thread_name", "ph": "M", "pid": 1,
                     "tid": tid, "args": {"name": label}})
        meta.append({"name": "thread_sort_index", "ph": "M", "pid": 1,
                     "tid": tid, "args": {"sort_index": tid}})
    return {"traceEvents": meta + events, "displayTimeUnit": "ms"}


def write_chrome_trace(records: Iterable[dict], out_path: str) -> int:
    """Export ``records`` as Chrome-trace JSON; returns the number of
    span events written."""
    doc = chrome_trace(records)
    with open(out_path, "w", encoding="utf-8") as fh:
        json.dump(doc, fh, sort_keys=True)
    return sum(1 for e in doc["traceEvents"] if e.get("ph") == "X")
