"""The host's garbage-collector pauses, on record.

A collection stops every Python thread of the process: the worker that
enqueues a region, the client that waits for it. ``acquire`` installs one
``gc.callbacks`` hook for the process (the first ``QueryServer`` that
starts with telemetry on does; the last one to close removes it through
``release``). The hook itself only stamps ``time.monotonic`` and appends a
tuple: a collection starts wherever an allocation lands, also inside a
``with`` on the registry's or the ring's lock, so taking either from the
hook could deadlock the thread against itself. ``flush`` (the server calls
it once a request, from a place that holds no lock) turns what the hook
left into the counters ``host.gc_pauses`` and ``host.gc_pause_ns``, every
collection, and for a collection of generation 2 or a pause over 1 ms one
ring record ``kind="gc"`` (``t0``, ``t1`` on the spans' clock,
``generation``, ``collected``). No profiler annotation: the ring and the
counters are what the slow-request record and the benchmark read.
"""

from __future__ import annotations

import collections
import gc
import importlib
import threading
import time

from spark_rapids_jni_tpu.telemetry.registry import REGISTRY

_events = importlib.import_module("spark_rapids_jni_tpu.telemetry.events")

__all__ = ["acquire", "release", "flush", "installed"]

_RECORD_OVER_S = 1e-3        # a shorter young collection is only counted

_lock = threading.Lock()     # the holders' count; never taken by the hook
_holders = 0
_began = 0.0                 # the collection in progress (they never nest)
_done: collections.deque = collections.deque()   # (t0, t1, generation, collected)


def _hook(phase: str, info: dict) -> None:
    global _began
    if phase == "start":
        _began = time.monotonic()
    elif _began:
        _done.append((_began, time.monotonic(), info.get("generation"),
                      info.get("collected")))
        _began = 0.0


def installed() -> bool:
    return _hook in gc.callbacks


def acquire() -> None:
    """One more holder; the first installs the hook and makes the two
    counters exist, so that a window without a collection reads 0."""
    global _holders
    with _lock:
        _holders += 1
        if _holders == 1:
            REGISTRY.counter("host.gc_pauses")
            REGISTRY.counter("host.gc_pause_ns")
            gc.callbacks.append(_hook)


def release() -> None:
    """One holder fewer; the last removes the hook and flushes."""
    global _holders
    with _lock:
        _holders = max(_holders - 1, 0)
        if _holders == 0 and _hook in gc.callbacks:
            gc.callbacks.remove(_hook)
    flush()


def flush() -> None:
    """Count the collections the hook has seen since the last flush and
    record the long ones. Called holding no lock of the telemetry."""
    pauses = ns = 0
    while True:
        try:
            t0, t1, generation, collected = _done.popleft()
        except IndexError:
            break
        pauses += 1
        ns += int((t1 - t0) * 1e9)
        if generation == 2 or t1 - t0 > _RECORD_OVER_S:
            _events._emit({"kind": "gc", "op": "gc", "t0": t0, "t1": t1,
                           "generation": generation, "collected": collected})
    if pauses:
        REGISTRY.counter("host.gc_pauses").inc(pauses)
        REGISTRY.counter("host.gc_pause_ns").inc(ns)
