"""Runtime bloom-join filters: learned-selectivity gating and state.

The reference family ships xxhash64 + Spark-compatible bloom filters
precisely so selective joins can prune probe-side scans before they
stage ("Accelerating Presto with GPUs", PAPERS.md, shows multi-join
analytics queries go scan-bound without this). This module is the
DECISION half of that subsystem: the planner pass itself lives in
``runtime/fusion.inject_runtime_filters`` (it owns the plan IR), and
calls back here for every on/off/sizing choice.

Contract: every decision is recorded with a mandatory reason
(``record_rtfilter`` + the ``rtfilter.decision.*`` counters — tpulint
rule 24 ``rtfilter-decision-must-record`` enforces the static half), and
results are bit-identical whatever this module decides: a bloom filter
only drops rows the join was about to drop, so the gate trades probe
overhead against pruning payoff, never correctness.

Learned gating: each ``(plan, join label)`` signature keeps an EMA of
its observed pass fraction (``rows_pass / rows_in`` harvested from the
``BloomProbe`` side outputs after every region). A signature whose EMA
rises above ``rtfilter.gate_pass_frac`` is judged non-selective and the
filter switches off for it; signatures with no history run
optimistically. The EMAs persist in ``learned_selectivity.json`` beside
the learned admission estimates with the SAME crash-safe discipline
(``runtime/server.py``): sidecar ``fcntl`` lock, read-merge-replace via
``atomic_write_json``, corrupt files discarded and counted — N replica
processes share one state file without clobbering each other.

Chunked/out-of-core paths can't prune inside a region (static shapes —
masking never drops a row); they prune on the HOST side instead, where
chunk boundaries make dynamic shapes free: ``prune_chunk`` compacts a
decoded chunk down to its possibly-matching rows before the per-chunk
region stages it, which is where the rows-scanned (and bytes reserved /
spilled) reduction actually lands. ``packed_table`` wraps a filter's
``to_packed`` wire form as a one-column table so a cluster fan-out ships
it inline over the sealed DCN transport and every shard prunes locally.

Config (utils/config.py): ``rtfilter.enabled`` / ``max_build_rows`` /
``fpp`` / ``gate_pass_frac`` / ``alpha`` / ``path`` /
``save_interval_s`` (env ``SPARK_RAPIDS_TPU_RTFILTER_*``).
"""

from __future__ import annotations

import os
import threading
import time
from typing import NamedTuple, Optional

import jax.numpy as jnp
import numpy as np

from spark_rapids_jni_tpu import types as t
from spark_rapids_jni_tpu.columnar import Column, Table
from spark_rapids_jni_tpu.ops.bloom_filter import (
    BloomFilter,
    bloom_might_contain_spark,
    bloom_put_spark,
    optimal_params,
)
from spark_rapids_jni_tpu.telemetry import spans
from spark_rapids_jni_tpu.telemetry.events import record_rtfilter
from spark_rapids_jni_tpu.telemetry.registry import REGISTRY
from spark_rapids_jni_tpu.utils.atomic_io import atomic_write_json, load_json
from spark_rapids_jni_tpu.utils.config import cache_dir, get_option

try:
    import fcntl
except ImportError:  # pragma: no cover - non-POSIX
    fcntl = None  # type: ignore[assignment]

__all__ = [
    "Decision",
    "decide",
    "observe",
    "build_filter",
    "prune_chunk",
    "pruned_chunks",
    "packed_table",
    "learned_pass_frac",
    "flush",
    "reset",
    "stats",
]


class Decision(NamedTuple):
    """One recorded planner choice for one join of one plan."""

    apply: bool
    reason: str
    num_bits: int
    num_hashes: int


# ---------------------------------------------------------------------------
# learned selectivity state (the admission-estimate persistence twin)
# ---------------------------------------------------------------------------


class _SelectivityStore:
    """Per-signature pass-fraction EMAs with the flock-merge write
    discipline of ``QueryServer._save_learned`` (one file, N writers)."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._ema: dict[str, float] = {}
        self._dirty = False
        self._last_save: Optional[float] = None
        self._loaded_from = ""

    # -- path / file ----------------------------------------------------

    @staticmethod
    def _resolve_path() -> str:
        explicit = str(get_option("rtfilter.path") or "")
        if explicit:
            return explicit
        base = cache_dir()
        return os.path.join(base, "learned_selectivity.json") if base else ""

    def _read_file(self, path: str) -> Optional[dict]:
        state, corrupt = load_json(path)
        if corrupt is not None:
            # atomic replace means a crash can't produce this; disk rot
            # or a manual edit can — discard, count, keep deciding
            REGISTRY.counter("rtfilter.state_discarded").inc()
            record_rtfilter("rtfilter.state", "state_discarded",
                            reason="corrupt", path=path, detail=corrupt)
            return None
        if not isinstance(state, dict):
            return None
        return {
            str(k): float(v) for k, v in state.items()
            if isinstance(v, (int, float)) and 0.0 <= float(v) <= 1.0
        }

    @staticmethod
    def _merge(mine: dict, disk: dict) -> dict:
        # 50/50 blend of two EMAs is a fair co-estimate and converges
        # under repeated merge cycles (same rationale as the admission
        # estimates' _merge_learned)
        merged = dict(disk)
        for sig, v in mine.items():
            dv = merged.get(sig)
            merged[sig] = float(v) if dv is None \
                else 0.5 * float(v) + 0.5 * float(dv)
        return merged

    def _maybe_load(self) -> None:
        path = self._resolve_path()
        with self._lock:
            if path == self._loaded_from:
                return
            self._loaded_from = path
        if not path:
            return
        disk = self._read_file(path)
        if disk is None:
            return
        with self._lock:
            self._ema = self._merge(self._ema, disk)

    # -- reads ----------------------------------------------------------

    def get(self, sig: str) -> Optional[float]:
        self._maybe_load()
        with self._lock:
            return self._ema.get(sig)

    def snapshot(self) -> dict:
        with self._lock:
            return dict(self._ema)

    # -- writes ---------------------------------------------------------

    def update(self, sig: str, pass_frac: float) -> float:
        self._maybe_load()
        alpha = float(get_option("rtfilter.alpha"))
        with self._lock:
            old = self._ema.get(sig)
            new = float(pass_frac) if old is None \
                else (1.0 - alpha) * old + alpha * float(pass_frac)
            self._ema[sig] = new
            self._dirty = True
            last = self._last_save
        interval = float(get_option("rtfilter.save_interval_s"))
        if last is None or time.monotonic() - last >= interval:
            self.save()
        return new

    def save(self) -> None:
        path = self._resolve_path()
        if not path:
            return
        with self._lock:
            if not self._dirty:
                return
            snapshot = dict(self._ema)
            self._dirty = False
            self._last_save = time.monotonic()
        lock_fh = None
        try:
            if fcntl is not None:
                lock_fh = open(path + ".lock", "a")
                fcntl.flock(lock_fh.fileno(), fcntl.LOCK_EX)
            disk = self._read_file(path)
            atomic_write_json(path, self._merge(snapshot, disk or {}))
        except OSError:
            # selectivity history is an optimization: losing a write
            # costs the next process one optimistic run, never a result
            with self._lock:
                self._dirty = True
            REGISTRY.counter("rtfilter.state_write_error").inc()
        finally:
            if lock_fh is not None:
                try:
                    fcntl.flock(lock_fh.fileno(), fcntl.LOCK_UN)
                finally:
                    lock_fh.close()

    def reset(self) -> None:
        with self._lock:
            self._ema = {}
            self._dirty = False
            self._last_save = None
            self._loaded_from = ""


_STORE = _SelectivityStore()


def _signature(plan_name: str, label: str) -> str:
    return f"{plan_name}/{label}"


def learned_pass_frac(plan_name: str, label: str) -> Optional[float]:
    """The signature's current EMA (None = no history)."""
    return _STORE.get(_signature(plan_name, label))


def flush() -> None:
    """Force-persist dirty selectivity state now (close/atexit twin)."""
    _STORE.save()


def reset() -> None:
    """Drop in-memory selectivity state (tests; disk is untouched)."""
    _STORE.reset()


# ---------------------------------------------------------------------------
# decisions
# ---------------------------------------------------------------------------


def decide(plan_name: str, label: str, build_rows: int) -> Decision:
    """Gate one join: filter on/off plus bits sizing. EVERY path records
    its reason (counter + ``record_rtfilter``) — an unexplained decision
    is a bug (tpulint rule 24)."""
    sig = _signature(plan_name, label)

    def _skip(reason: str) -> Decision:
        REGISTRY.counter("rtfilter.decision.skip").inc()
        record_rtfilter(sig, "skip", reason=reason, build_rows=build_rows)
        return Decision(False, reason, 0, 0)

    if not get_option("rtfilter.enabled"):
        return _skip("disabled")
    if build_rows > int(get_option("rtfilter.max_build_rows")):
        return _skip("build_too_large")
    ema = _STORE.get(sig)
    gate = float(get_option("rtfilter.gate_pass_frac"))
    if ema is not None and ema > gate:
        return _skip("learned_nonselective")
    reason = "no_history_optimistic" if ema is None else "selective"
    num_bits, num_hashes = optimal_params(
        build_rows, float(get_option("rtfilter.fpp")))
    REGISTRY.counter("rtfilter.decision.apply").inc()
    record_rtfilter(sig, "apply", reason=reason, build_rows=build_rows,
                    num_bits=num_bits, num_hashes=num_hashes,
                    pass_frac_ema=ema)
    return Decision(True, reason, num_bits, num_hashes)


def observe(plan_name: str, probe_label: str, rows_in, rows_pass) -> None:
    """Harvest one probe's measured pass fraction into the learned EMA
    (and the ``rtfilter.rows_pruned`` ledger). Accepts the raw
    ``<label>.rows_in`` / ``<label>.rows_pass`` side outputs; silently a
    no-op under tracers (a fused region evaluated inside another trace
    has nothing concrete to learn from yet)."""
    if rows_in is None or rows_pass is None:
        return
    try:
        n_in, n_pass = int(rows_in), int(rows_pass)
    except TypeError:  # tracer values: nothing concrete to learn from
        return
    if n_in <= 0:
        # an empty probe side carries no selectivity information
        return
    label = probe_label[4:] if probe_label.startswith("rtf_") \
        else probe_label
    sig = _signature(plan_name, label)
    pass_frac = n_pass / n_in
    REGISTRY.counter("rtfilter.rows_in").inc(n_in)
    REGISTRY.counter("rtfilter.rows_pruned").inc(n_in - n_pass)
    REGISTRY.counter("rtfilter.observations").inc()
    ema = _STORE.update(sig, pass_frac)
    record_rtfilter(sig, "observed", reason="measured", rows_in=n_in,
                    rows_pass=n_pass, pass_frac=pass_frac,
                    pass_frac_ema=ema)


# ---------------------------------------------------------------------------
# host-side helpers (chunked and cluster paths)
# ---------------------------------------------------------------------------


def build_filter(values: jnp.ndarray, valid=None, *,
                 expected_items: int,
                 fpp: Optional[float] = None) -> BloomFilter:
    """Materialize build keys into a filter (dispatch-routed
    ``bloom_put_spark``), timing the build into
    ``rtfilter.build_us``."""
    num_bits, num_hashes = optimal_params(
        expected_items,
        float(get_option("rtfilter.fpp")) if fpp is None else float(fpp))
    start = time.monotonic()
    with spans.child("rtfilter.build", num_bits=num_bits,
                     num_hashes=num_hashes):
        bf = bloom_put_spark(BloomFilter.empty(num_bits, num_hashes),
                             values, valid)
        jnp.asarray(bf.bits).block_until_ready()
    build_us = (time.monotonic() - start) * 1e6
    REGISTRY.counter("rtfilter.builds").inc()
    REGISTRY.histogram("rtfilter.build_us").observe(build_us)
    return bf


def prune_chunk(chunk: Table, bf: BloomFilter, key: int, *,
                plan_name: str = "", label: str = "",
                min_rows: int = 1) -> Table:
    """Compact a decoded chunk down to its possibly-matching rows before
    the per-chunk region stages it — the HOST half of the pushdown,
    where chunk boundaries make dynamic shapes free. Null-keyed rows are
    KEPT (their fate belongs to the plan's own masking, not to us); at
    least ``min_rows`` rows survive so the downstream plan never sees an
    empty table. Bit-identity: every dropped row is provably unmatched
    (no false negatives) and the survivors keep their relative order.
    With ``plan_name``/``label`` the measured pass fraction also feeds
    the learned gate via :func:`observe`."""
    from spark_rapids_jni_tpu.ops.sort import gather

    col = chunk.columns[key]
    kv = np.asarray(col.valid_mask())
    hit = np.asarray(bloom_might_contain_spark(bf, col.data))
    keep = hit | ~kv
    n_pass = int(keep.sum())
    if plan_name and label:
        observe(plan_name, label, int(chunk.num_rows), n_pass)
    else:
        REGISTRY.counter("rtfilter.rows_in").inc(int(chunk.num_rows))
        REGISTRY.counter("rtfilter.rows_pruned").inc(
            int(chunk.num_rows) - n_pass)
    idx = np.flatnonzero(keep)
    if idx.size < min_rows:
        idx = np.arange(min(min_rows, chunk.num_rows))
    record_rtfilter("rtfilter.chunk", "prune", reason="measured",
                    rows_in=int(chunk.num_rows), rows_out=int(idx.size))
    if idx.size == chunk.num_rows:
        return chunk
    with spans.child("rtfilter.prune", rows_in=int(chunk.num_rows),
                     rows_out=int(idx.size)):
        return gather(chunk, jnp.asarray(idx, dtype=jnp.int32))


class _PrunedReader:
    """Chunked-reader wrapper that ALSO forwards ``chunk_sources()`` so
    the pipelined out-of-core executor keeps its decode-thunk overlap:
    each thunk decodes, then prunes, still on the host side of the
    staging boundary."""

    def __init__(self, inner, prune) -> None:
        self._inner = inner
        self._prune = prune

    def __iter__(self):
        return (self._prune(c) for c in self._inner)

    def chunk_sources(self):
        return [
            (lambda s=s: self._prune(s()))
            for s in self._inner.chunk_sources()
        ]


def pruned_chunks(chunks, bf: BloomFilter, key: int, *,
                  plan_name: str = "", label: str = ""):
    """Wrap a chunk iterable (or a ``chunk_sources()`` reader) so every
    chunk is bloom-pruned BEFORE the out-of-core runner reserves or
    stages it — fewer bytes reserved, spilled, and shipped, same
    bytes out."""
    def _prune(chunk: Table) -> Table:
        return prune_chunk(chunk, bf, key, plan_name=plan_name,
                           label=label)

    if hasattr(chunks, "chunk_sources"):
        return _PrunedReader(chunks, _prune)
    return (_prune(c) for c in chunks)


def packed_table(bf: BloomFilter) -> Table:
    """The filter's ``to_packed`` wire form as a one-column uint8 table —
    what a cluster fan-out ships inline (sealed DCN transport) so each
    shard probes locally via ``BloomProbe(packed=True)`` over an
    unbucketed Scan bound to this table."""
    return Table([Column(t.UINT8, bf.to_packed())])


def stats() -> dict:
    """Aggregate runtime-filter counters for the bench ``rtfilter``
    block."""
    c = REGISTRY.counters("rtfilter.")
    rows_in = c.get("rtfilter.rows_in", 0)
    pruned = c.get("rtfilter.rows_pruned", 0)
    return {
        "decisions_apply": c.get("rtfilter.decision.apply", 0),
        "decisions_skip": c.get("rtfilter.decision.skip", 0),
        "observations": c.get("rtfilter.observations", 0),
        "builds": c.get("rtfilter.builds", 0),
        "build_us_p50": REGISTRY.histogram(
            "rtfilter.build_us").percentile(50),
        "rows_in": rows_in,
        "rows_pruned": pruned,
        "pass_frac": (rows_in - pruned) / rows_in if rows_in else None,
        "state_discarded": c.get("rtfilter.state_discarded", 0),
        "learned_signatures": len(_STORE.snapshot()),
    }
