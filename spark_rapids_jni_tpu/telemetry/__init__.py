"""Execution telemetry & fallback accounting.

The reference answers "where did GPU time go" with NVTX ranges
(``ai.rapids.cudf.nvtx.enabled``) plus RMM counters; this package is the TPU
port's equivalent *and* closes the gap NVTX never covered: counting where
execution actually landed. Every device→host fallback (regex NUL byteset,
unsupported regex atom, cast-strings host assembly, out-of-core spill,
shuffle overflow reroute) records an event with a mandatory ``reason``;
``benchmark/run.py --trace 1`` reports the counters per layer; and
``python -m spark_rapids_jni_tpu.telemetry report run.jsonl`` renders the
per-op device/host split with p50/p95 wall times and bytes moved.

On top of the flat stream sit hierarchical per-query span trees
(``spans`` — one causal tree per served query), a bounded flight
recorder with structured dump artifacts, Chrome-trace/Perfetto export
(``python -m spark_rapids_jni_tpu.telemetry trace``), live serving
introspection (``QueryServer.inspect()`` rendered by ``... telemetry
top``) and Prometheus-style text exposition (``REGISTRY.exposition()``).

Toggles (utils/config.py): ``telemetry.enabled``
(``SPARK_RAPIDS_TPU_TELEMETRY_ENABLED=1``) turns recording on;
``telemetry.path`` (``SPARK_RAPIDS_TPU_TELEMETRY_PATH=run.jsonl``) adds a
JSONL file sink on top of the in-process ring. Zero third-party deps, no jax
import, near-zero cost when disabled (one config lookup per instrumented
call).
"""

from spark_rapids_jni_tpu.telemetry.events import (
    current_session,
    drain,
    enabled,
    events,
    record_compile_cache,
    record_degrade,
    record_dispatch,
    record_exchange,
    record_fallback,
    record_fleet,
    record_integrity,
    record_resilience,
    record_rtfilter,
    record_server,
    record_spill,
    session_scope,
    summary,
)
from spark_rapids_jni_tpu.telemetry.registry import REGISTRY, Registry
from spark_rapids_jni_tpu.telemetry import spans
from spark_rapids_jni_tpu.telemetry.spans import (
    chrome_trace,
    current_span,
    dump_flight_record,
    flight_records,
    span,
)

__all__ = [
    "REGISTRY",
    "Registry",
    "chrome_trace",
    "current_session",
    "current_span",
    "drain",
    "dump_flight_record",
    "enabled",
    "events",
    "flight_records",
    "record_compile_cache",
    "record_degrade",
    "record_dispatch",
    "record_exchange",
    "record_fallback",
    "record_fleet",
    "record_integrity",
    "record_resilience",
    "record_rtfilter",
    "record_server",
    "record_spill",
    "session_scope",
    "span",
    "spans",
    "summary",
]
