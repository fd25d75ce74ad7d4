"""Named ranges around instrumented ops — the NVTX-range equivalent.

The reference opens an NVTX range (``CUDF_FUNC_RANGE()``) at the top of every
nontrivial native function (e.g. NativeParquetJni.cpp:191,400,455,508) behind
the ``ai.rapids.cudf.nvtx.enabled`` toggle (pom.xml:85,437). Here a range is a
thin wrapper over ``spans.child`` (telemetry/spans.py) behind the one switch
``telemetry.enabled``: when a query span is open on this thread the range
attaches a child span, so every ``trace_range``-wrapped stage lands in the
served query's causal tree and, through the span's own
profiler annotation, in the host plane of a profiler trace.
Outside a served query a range opens nothing.

``record=True`` additionally times the range and records a ``dispatch``
telemetry event carrying ``wall_ms`` (``telemetry report`` reads it); a body
that raises still records, with ``status="error"`` and the exception class,
so failed dispatches are visible in the per-op report instead of silently
dropping their timing.
"""

from __future__ import annotations

import contextlib
import functools
import time
from typing import Callable, TypeVar

from spark_rapids_jni_tpu import telemetry
from spark_rapids_jni_tpu.telemetry import spans

F = TypeVar("F", bound=Callable)


@contextlib.contextmanager
def trace_range(name: str, record: bool = False):
    """Context manager attaching a child span named ``name`` to the query
    span open on this thread (none open, or telemetry off: nothing).

    With ``record=True`` (and telemetry enabled), also times the body and
    records a ``dispatch`` telemetry event carrying ``wall_ms`` — with
    ``status="error"`` / ``error=<exception class>`` when the body raises.
    """
    if record:
        record = telemetry.enabled()
    t0 = time.perf_counter() if record else 0.0
    try:
        with spans.child(name):
            yield
    except BaseException as exc:
        if record:
            telemetry.record_dispatch(
                name,
                wall_ms=(time.perf_counter() - t0) * 1e3,
                status="error",
                error=type(exc).__name__,
            )
        raise
    if record:
        telemetry.record_dispatch(
            name, wall_ms=(time.perf_counter() - t0) * 1e3
        )


def func_range(name: str, record: bool = False) -> Callable[[F], F]:
    """Decorator form — CUDF_FUNC_RANGE() parity."""

    def deco(fn: F) -> F:
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            with trace_range(name, record=record):
                return fn(*args, **kwargs)

        return wrapper  # type: ignore[return-value]

    return deco
