"""Runtime configuration.

The reference configures at three tiers — Maven -D properties -> CMake cache
vars -> Java system properties (SURVEY.md section 5, "Config / flag system");
at runtime only system properties matter (e.g. ``ai.rapids.cudf.nvtx.enabled``,
reference pom.xml:85,437). The TPU equivalent: env vars
(``SPARK_RAPIDS_TPU_<OPTION>``) overridden by programmatic set_option, with
documented defaults. No config files.
"""

from __future__ import annotations

import os
from typing import Any

_ENV_PREFIX = "SPARK_RAPIDS_TPU_"

# option name -> (default, parser)
_OPTIONS: dict[str, tuple[Any, type]] = {
    # Lift the reference's 1.5KB row-size contract check.
    "row_conversion.enforce_row_limit": (True, bool),
    # Log level for the thin runtime logger (slf4j-equivalent).
    "log.level": ("WARNING", str),
    # Memory-layer allocation logging: 0 = off (RMM_LOGGING_LEVEL default
    # OFF parity, reference pom.xml:82), 1 = staging allocs, 2 = +reserves.
    "memory.log_level": (0, int),
    # regexp engine pin: "" = auto (device when compilable, else host),
    # "device" = require the DFA engine, "host" = force java.util.regex
    # emulation (testing / behavior comparison).
    "regex.force_engine": ("", str),
    # Execution telemetry (telemetry/): record op dispatches, device->host
    # fallbacks (with reasons), compile-cache hits, spills, bench staleness.
    # Also the NVTX-equivalent switch (ai.rapids.cudf.nvtx.enabled parity,
    # default false like pom.xml:85): spans write profiler annotations.
    "telemetry.enabled": (False, bool),
    # JSONL sink for telemetry events; "" = in-process ring buffer only.
    "telemetry.path": ("", str),
    # Flight recorder (telemetry/spans.py): how many recent query span
    # trees (completed roots + explicit dumps) the in-process ring keeps
    # for post-mortem inspection.
    "telemetry.flight_recorder_depth": (16, int),
    # Directory flight-recorder artifacts (full span tree + limiter /
    # queue state, dumped on a classified death, degrade-rung step or
    # cancellation) are written to; "" = in-memory ring only.
    "telemetry.flight_recorder_path": ("", str),
    # Cap on span nodes kept per in-memory query tree (the JSONL sink is
    # unbounded; the tree backs the flight recorder and inspect()).
    # Past the cap, spans still emit records but stop growing the tree.
    "telemetry.max_spans_per_tree": (2048, int),
    # Shape-bucketed dispatch (runtime/dispatch.py): pad the leading row
    # dimension of device-op inputs up to a bucket so one compiled
    # executable serves every batch size inside the bucket (the reference
    # launches per-shape CUDA kernels; XLA instead recompiles per shape,
    # which this layer amortizes).
    "dispatch.enabled": (True, bool),
    # Smallest bucket and bucket granularity (rows). Every bucket is a
    # multiple of this.
    "dispatch.bucket_base": (16, int),
    # Upper bound on padding waste per bucket step: buckets grow
    # geometrically by min(1 + max_waste_frac, 2). 1.0 = power-of-two
    # buckets (<= 50% padded rows); 0.0 = linear base-multiple buckets.
    "dispatch.max_waste_frac": (1.0, float),
    # Pipelined out-of-core execution (runtime/pipeline.py): overlap host
    # read/decode with device transfer+compute through a bounded-queue
    # multi-stage executor. Off by default — the serial path stays the
    # reference implementation; results are bit-identical either way.
    "pipeline.enabled": (False, bool),
    # How many chunks the producer stages may run ahead of the consumer.
    # Also honored via the short env var SPARK_RAPIDS_TPU_PIPELINE_PREFETCH
    # (checked first by runtime/pipeline.py).
    "pipeline.prefetch_depth": (2, int),
    # Worker threads for the host read/decode stage. Decode is mostly
    # C-extension (numpy / native codec) work that releases the GIL, so a
    # small pool overlaps IO with decode without oversubscribing the host.
    # Twelve since PR 34 (two before): on the TPU VM's 13 cores the decode
    # of a served Parquet scan's 49 column chunks scales with the threads
    # (0.45 s on two, 0.24 on four, 0.16 on six, 0.125 on eight), and the
    # fewer the threads the more one process's requests differ from the
    # next's: a served scan's median latency spread over six runs by 5-7%
    # on two, 4.5% on four, 2.4-3.9% on eight, 1.5% on twelve (my chip
    # runs; more runnable threads than free cores, so every process is
    # slowed alike where on eight one in four drew a busy core).
    "pipeline.decode_threads": (12, int),
    # Whole-stage fusion (runtime/fusion.py): compile each fusible plan
    # region into ONE executable through dispatch.call instead of one
    # executable per op. Off -> the same plan runs op-by-op (the staged
    # reference path); results are bit-identical either way.
    "fusion.enabled": (True, bool),
    # Donate region-input buffers the caller declared dead (intermediate
    # tables between regions, out-of-core chunk tables) into the fused
    # executable so XLA reuses them for outputs instead of
    # double-buffering HBM. Donation never applies to caller-owned scans.
    "fusion.donate": (True, bool),
    # Resilient execution (runtime/resilience.py): the single retry /
    # degradation policy every runtime seam routes transient failure
    # through. Off -> each call site reproduces its pre-resilience
    # behavior exactly (one-shot shuffle retry, unbounded grow loops,
    # raw error propagation).
    "resilience.enabled": (True, bool),
    # Bounded attempts for transient-classified failures at one seam
    # (TransientDeviceError / TransportError). Exhaustion raises a
    # classified FatalExecutionError — never a hang, never a silent
    # wrong result.
    "resilience.max_attempts": (4, int),
    # Geometric factor for capacity escalation (groupby cardinality
    # bound, join output capacity, shuffle slot count) when the failed
    # attempt reports no exact requirement.
    "resilience.growth": (4, int),
    # Base backoff between transient retries, in milliseconds; each
    # further retry multiplies by resilience.backoff_multiplier. 0 (the
    # default) retries immediately — device-local faults clear on
    # replay, not on wall time.
    "resilience.backoff_ms": (0, int),
    "resilience.backoff_multiplier": (2.0, float),
    # Multi-query serving runtime (runtime/server.py): maximum queries
    # executing concurrently across ALL sessions; queued work beyond this
    # waits its round-robin turn.
    "server.max_inflight": (4, int),
    # Default HBM budget (bytes) for a QueryServer built without an
    # explicit MemoryLimiter — every admitted query reserves its estimate
    # against this before it starts.
    "server.hbm_budget_bytes": (1 << 30, int),
    # How long (seconds) an admitted-for-execution query may wait for its
    # HBM reservation before it is rejected instead of held forever.
    "server.admission_timeout_s": (30.0, float),
    # Per-session queue depth: submissions beyond this are rejected at
    # submit time (backpressure to the client, not unbounded memory).
    "server.queue_depth": (64, int),
    # Safety multiplier applied to the input-bytes HBM estimate when the
    # caller does not supply one (intermediates cost more than inputs).
    "server.estimate_headroom": (1.5, float),
    # Per-query wall-clock deadline in milliseconds; 0 = no deadline. A
    # query past its deadline is cancelled cooperatively (region/chunk
    # boundaries, decode pool) and dies classified as QueryCancelled with
    # every reservation and queue slot released.
    "server.deadline_ms": (0, int),
    # Graceful degradation (runtime/degrade.py): when a classified
    # ResourceExhausted / CapacityOverflow escapes the retry/escalate
    # budget, re-execute one rung down the bit-identical tier ladder
    # (fused -> staged -> out-of-core halved chunks -> park-and-retry).
    # Off -> the serving runtime is byte-for-byte the pre-degradation
    # path: the first classified failure propagates.
    "degrade.enabled": (True, bool),
    # Maximum rungs a single query may step down before its original
    # classified failure is re-raised (4 covers the whole ladder).
    "degrade.max_steps": (4, int),
    # Park-and-retry rung: how long (seconds) a parked query waits for
    # the limiter to drain below the low watermark before giving up and
    # re-raising the classified failure.
    "degrade.park_timeout_s": (30.0, float),
    # Out-of-core rung: rows per chunk for the first out-of-core attempt;
    # each further pressure failure on this rung halves it (floor 1).
    "degrade.chunk_rows": (65536, int),
    # Memory-pressure watermarks as fractions of the limiter budget.
    # Crossing high proactively spills the coldest SpillStore entries and
    # pauses admission; admission resumes once usage drains below low.
    "memory.high_watermark": (0.85, float),
    "memory.low_watermark": (0.6, float),
    # Adaptive admission: blend factor for folding the measured peak
    # reservation of a plan signature into future estimates
    # (new = (1-alpha)*old + alpha*measured). 0 disables learning.
    "server.estimate_alpha": (0.4, float),
    # Where learned per-signature estimates persist ("" = in cache_dir()
    # beside the compile cache; unpersisted when that is switched off).
    # Writes are crash-safe: tmp file + os.replace + fsync.
    "server.estimate_path": ("", str),
    # Minimum seconds between learned-estimate persistence writes on the
    # serving path (the fsync pair is tail latency, not serving work);
    # the first learn saves immediately and close() always flushes.
    # <= 0 writes through on every served query.
    "server.estimate_save_interval_s": (5.0, float),
    # End-to-end data integrity (runtime/integrity.py): length+checksum
    # trailers sealed onto spill payloads, DCN wire frames and
    # out-of-core checkpoints, verified before any read-back byte is
    # decoded, plus structural validation of untrusted Parquet/ORC
    # input. Also honored via the short env var SPARK_RAPIDS_TPU_INTEGRITY
    # (checked first by integrity.enabled()). Off restores today's
    # byte-for-byte behavior at every seam: no trailers, no wire acks,
    # no envelope preflight.
    "integrity.enabled": (True, bool),
    # Directory for disk-tier spill files (SpillStore). "" keeps spilled
    # entries in host memory (today's behavior); a path moves spilled
    # payloads to checksummed files written crash-safe (tmp + os.replace
    # + fsync + read-back verify) so a crash mid-spill can never leave a
    # torn entry a later unspill trusts.
    "memory.spill_dir": ("", str),
    # Plan-signature result & subplan cache (runtime/resultcache.py):
    # memoize final query results and fused-region intermediates keyed by
    # (plan signature, input fingerprint), stored through the SpillStore's
    # integrity-sealed tiers. A hit in QueryServer.submit short-circuits
    # admission, compile and execution. Off restores today's serving path
    # byte-for-byte: no fingerprinting, no cache probes, no extra spans.
    "cache.enabled": (True, bool),
    # LRU capacity of the result cache in logical payload bytes (across
    # all tiers). Resident entries are charged against the MemoryLimiter
    # so cached results can never starve live queries; under pressure the
    # high-watermark spiller sheds cache entries first. 0 sizes it from
    # the limiter's budget: an eighth of it, and no less than 256 MiB
    # (one padded general-q3 result at TPC-H SF1 is 336 MB).
    "cache.max_bytes": (0, int),
    # Subplan-prefix reuse: hash canonicalized scan+filter+project prefixes
    # of submitted plans so two distinct plans sharing a prefix execute the
    # shared region once and reuse the materialized intermediate. Gated
    # separately because it rewrites plans before execution.
    "cache.subplan_enabled": (True, bool),
    # Columnar compression (runtime/compress.py): dictionary/RLE re-encode
    # + bit-packed validity + optional zstd UNDER the integrity seal on
    # every managed byte path. Off restores byte-for-byte legacy framing
    # at every seam: raw snapshots, flag-0/1 wire buffers, no codec frames.
    "compress.enabled": (True, bool),
    # Per-seam gates (all under compress.enabled): SpillStore host/disk
    # tiers, DCN wire frames, out-of-core checkpoints, result-cache
    # entries. Any one off restores that seam's legacy framing alone.
    "compress.spill": (True, bool),
    "compress.wire": (True, bool),
    "compress.checkpoint": (True, bool),
    "compress.cache": (True, bool),
    # zstd final-stage level over the winning scheme payload; used only
    # when the optional zstandard package is importable. <= 0 disables
    # the final stage (dict/RLE/bitpack still run).
    "compress.zstd_level": (3, int),
    # Serving fleet (runtime/fleet.py): number of QueryServer replica
    # subprocesses the supervisor boots and routes over.
    "fleet.replicas": (2, int),
    # Supervisor -> replica liveness ping cadence, and how long a replica
    # may go without answering before it is declared dead (classified
    # ReplicaDeadError via the fleet.heartbeat seam).
    "fleet.heartbeat_interval_s": (0.5, float),
    "fleet.heartbeat_timeout_s": (5.0, float),
    # How many times one query may be re-dispatched after replica deaths
    # before its in-flight failure is surfaced classified to the caller.
    "fleet.failover_budget": (2, int),
    # Exponential restart backoff for dead replicas: first restart waits
    # backoff_s, each consecutive crash multiplies the wait.
    "fleet.restart_backoff_s": (0.25, float),
    "fleet.restart_backoff_multiplier": (2.0, float),
    # Consecutive crashes (no successfully served query in between) after
    # which a replica's circuit breaker opens: it is quarantined and no
    # longer restarted or routed to.
    "fleet.quarantine_after": (3, int),
    # Supervisor-side result memo keyed by the result-cache idempotency
    # pair (plan signature, input fingerprint): bounds entries kept for
    # failover dedup / bit-identity verification. 0 disables the memo.
    "fleet.result_memo_entries": (64, int),
    # How long a worker subprocess may take to report boot_ok before its
    # boot counts as a crash (feeds the crash-loop circuit breaker).
    "fleet.worker_boot_timeout_s": (60.0, float),
    # How long a submit waits for a healthy replica (all dead/quarantined
    # or still booting) before failing classified.
    "fleet.dispatch_timeout_s": (30.0, float),
    # AOT warmup (QueryServer.warmup): how many of the costliest plan
    # signatures from the learned-estimate file a fresh replica
    # precompiles at boot (fleet _worker_main calls this before
    # reporting boot_ok). 0 = off — boot stays byte-for-byte the
    # pre-warmup path.
    "server.warmup_top_n": (0, int),
    # Replica identity stamped onto every telemetry record/span emitted by
    # this process ("" = unstamped). The fleet supervisor sets this in
    # each worker's environment so a shared JSONL sink attributes every
    # line, and `telemetry report`/`trace` can group by replica.
    "telemetry.replica": ("", str),
    # Host identity stamped next to the replica stamp ("" = unstamped).
    # The cluster supervisor sets this in each remote worker's
    # environment so cross-host records/spans aggregate per host.
    "telemetry.host": ("", str),
    # Default interface for DCN listeners and dials (runtime/cluster
    # gateway, SliceLink.listen/connect when no host is passed).
    # Loopback keeps CI single-machine; a mesh deploy sets the NIC.
    "dcn.bind_host": ("127.0.0.1", str),
    # Cross-host serving mesh (runtime/cluster.py): number of host
    # workers the mesh supervisor boots (localhost-simulated in CI).
    "cluster.hosts": (2, int),
    # How long one shard registration (ship + decode + fingerprint ack)
    # may take before it fails classified.
    "cluster.register_timeout_s": (60.0, float),
    # Distributed exchange (runtime/exchange.py): hard ceiling on the
    # per-destination send-buffer capacity the escalation ladder may
    # grow to before the pack demotes to multi-flight chunking (the
    # spill-aware rung). Quantized through the dispatch bucket schedule.
    "exchange.max_capacity_rows": (1 << 16, int),
    # Device-byte budget for the receive-side chunked merge of exchange
    # flights (MemoryLimiter budget handed to run_chunked_aggregate);
    # partial results beyond it LRU-spill to compressed host memory.
    "exchange.merge_budget_bytes": (64 << 20, int),
    # Direct host-to-host exchange flights: when on, the cluster ships
    # only the routing manifest and sources dial destination peers
    # directly (sealed TPCZ flights, HMAC-signed grants); the
    # router-mediated path stays as the classified fallback rung. Off
    # forces every flight through the supervisor (the PR-19 topology).
    "exchange.direct_enabled": (True, bool),
    # Bounded connect retry for one peer dial (a dead peer must fail
    # fast into the routed fallback, not hang the exchange): attempts x
    # delay ~= the dial budget before TransportError surfaces.
    "exchange.peer_dial_retries": (8, int),
    "exchange.peer_dial_delay_s": (0.05, float),
    # How long a direct exchange may sit on the WIRE before it fails
    # classified and the supervisor falls back to the routed path: a
    # destination's wait for its manifest-listed peer flights, and the
    # supervisor's wait on a worker that is flying, not computing. The
    # pack and merge plans themselves (minutes of cold compile on a
    # chip) are not timed by it; the caller's deadline bounds those.
    "exchange.direct_timeout_s": (30.0, float),
    # Planner-placed exchanges: when an interior Exchange node carries
    # parts=0 ("auto"), the partition count comes from the learned-
    # selectivity store (rows in x learned pass fraction / target rows
    # per partition, clamped to max_parts); no history falls back to 1.
    "exchange.target_rows_per_part": (4096, int),
    "exchange.max_parts": (64, int),
    # Runtime bloom-join filters (runtime/rtfilter.py): master switch for
    # the planner pass that builds a bloom filter from a selective join's
    # build side and prunes the probe side before it stages. Off by
    # default — results are bit-identical either way (a bloom filter only
    # drops rows the join would drop); on buys fewer rows scanned on
    # chunked/fan-out paths at the cost of the build.
    "rtfilter.enabled": (False, bool),
    # Build sides above this many rows never get a filter (the bloom
    # bits would be large and the join is unlikely to be selective).
    "rtfilter.max_build_rows": (1 << 16, int),
    # Target false-positive probability handed to BloomFilter.optimal
    # when sizing a filter's bits for the observed build cardinality.
    "rtfilter.fpp": (0.03, float),
    # Learned gate: once a (plan, join) signature's observed pass
    # fraction EMA rises above this, the filter is judged non-selective
    # and switched off for that signature (probe overhead with no
    # pruning payoff). Signatures with no history run optimistically.
    "rtfilter.gate_pass_frac": (0.8, float),
    # EMA blend weight for newly observed pass fractions (same role as
    # server.estimate_alpha for admission estimates).
    "rtfilter.alpha": (0.4, float),
    # Where the selectivity EMAs persist ("" = learned_selectivity.json
    # in cache_dir(), beside the learned admission estimates; in-memory
    # only when that is switched off). Shares the flock+merge write
    # discipline with the estimate file.
    "rtfilter.path": ("", str),
    # Debounce for selectivity-state writes, seconds.
    "rtfilter.save_interval_s": (5.0, float),
}

_overrides: dict[str, Any] = {}

# Where the persistent compile cache lives when JAX_COMPILATION_CACHE_DIR
# does not place it: one fixed, git-ignored directory at the root of the
# checkout. The path is part of JAX's cache key, so it is a constant, never
# built from a temporary name, a pid or the time.
FIXED_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def cache_dir() -> str:
    """The one directory this program persists to across processes: JAX's
    compile cache, and beside it the learned admission estimates
    (runtime/server.py) and runtime-filter selectivities
    (runtime/rtfilter.py). ``JAX_COMPILATION_CACHE_DIR`` where it is set
    (JAX reads it itself; no code sets another directory), else
    ``FIXED_CACHE_DIR``. Empty when JAX's own switch
    (``jax_enable_compilation_cache``) is off: then nothing persists."""
    import jax

    if not jax.config.jax_enable_compilation_cache:
        return ""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or FIXED_CACHE_DIR


def _parse(raw: str, typ: type) -> Any:
    if typ is bool:
        return raw.strip().lower() in ("1", "true", "yes", "on")
    return typ(raw)


def get_option(name: str) -> Any:
    if name not in _OPTIONS:
        raise KeyError(f"unknown option {name!r}")
    if name in _overrides:
        return _overrides[name]
    default, typ = _OPTIONS[name]
    env = os.environ.get(_ENV_PREFIX + name.upper().replace(".", "_"))
    if env is not None:
        return _parse(env, typ)
    return default


def set_option(name: str, value: Any) -> None:
    if name not in _OPTIONS:
        raise KeyError(f"unknown option {name!r}")
    _, typ = _OPTIONS[name]
    # coerce through the same parser env values get, so
    # set_option("telemetry.enabled", "off") == env ..._ENABLED=off
    _overrides[name] = _parse(value, typ) if isinstance(value, str) else typ(value)


def reset_option(name: str) -> None:
    _overrides.pop(name, None)
