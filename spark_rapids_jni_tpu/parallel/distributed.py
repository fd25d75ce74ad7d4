"""Distributed operators: shard tables over the executor mesh and run
shuffle-backed relational ops across it.

The execution model mirrors the Spark plugin's (SURVEY.md section 2.3): each
executor owns a partition of rows and runs the same operator pipeline; the
only inter-executor step is the repartition-by-key exchange, which here is
the ICI all_to_all in parallel.shuffle instead of the UCX shuffle manager.

Phantom rows (unoccupied shuffle slots) carry null keys and null values, so
aggregates skip them by construction; their only observable artifact is a
possible all-null key group in the padded output, which callers discard the
same way they discard local groupby padding.
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from spark_rapids_jni_tpu import telemetry
from spark_rapids_jni_tpu.columnar import Column, Table
from spark_rapids_jni_tpu.ops.groupby import groupby_aggregate
from spark_rapids_jni_tpu.parallel.mesh import EXEC_AXIS
from spark_rapids_jni_tpu.parallel.shuffle import ShuffleResult, hash_shuffle
from spark_rapids_jni_tpu.runtime.dispatch import (
    mesh_fingerprint as _mesh_fingerprint,
)
from spark_rapids_jni_tpu.utils.tracing import func_range




def head_table(table: Table, k: int) -> Table:
    """First k rows (static slice) — groupby outputs put real groups first."""
    cols = []
    for c in table.columns:
        if c.dtype.is_string and not c.is_padded_string:
            raise NotImplementedError(
                "head_table needs string columns in the padded device layout "
                "(ops.strings.pad_strings); Arrow offsets cannot be sliced "
                "like row data"
            )
        validity = None if c.validity is None else c.validity[:k]
        chars = c.chars[:k] if c.is_padded_string else None
        cols.append(Column(c.dtype, c.data[:k], validity, chars=chars))
    return Table(cols)


def shard_table(
    table: Table,
    mesh: Mesh,
    axis: str = EXEC_AXIS,
    return_row_valid: bool = False,
):
    """Distribute a host-built table row-wise across the mesh axis.

    Rows are padded to a multiple of the axis size with null rows (null
    rows fall out of every aggregate, the framework-wide masking idiom).
    With ``return_row_valid=True`` also returns the sharded bool[n] mask
    marking real rows — needed by operators where a padding row is not
    equivalent to a null-key row (left joins emit unmatched null-key rows
    but must not emit padding)."""
    d = mesh.shape[axis]
    n = table.num_rows
    pad = (-n) % d
    sharding = NamedSharding(mesh, P(axis))
    out = []
    for c in table.columns:
        if c.dtype.is_string:
            # strings shard in the padded device layout: int32 lengths ride
            # the fixed-width path, the (n, W) char matrix shards by rows
            from spark_rapids_jni_tpu.ops.strings import pad_strings

            p = pad_strings(c)
            lengths, mat = p.data, p.chars
            valid = p.valid_mask()
            if pad:
                lengths = jnp.concatenate([lengths, jnp.zeros((pad,), jnp.int32)])
                mat = jnp.concatenate(
                    [mat, jnp.zeros((pad, mat.shape[1]), jnp.uint8)]
                )
                valid = jnp.concatenate([valid, jnp.zeros((pad,), jnp.bool_)])
            out.append(Column(
                c.dtype,
                jax.device_put(lengths, sharding),
                jax.device_put(valid, sharding),
                chars=jax.device_put(mat, sharding),
            ))
            continue
        if not (c.dtype.is_fixed_width or c.dtype.is_decimal128):
            raise NotImplementedError(
                "shard_table: fixed-width and string columns only"
            )
        if pad:
            data = jnp.concatenate(
                [c.data, jnp.zeros((pad,) + c.data.shape[1:], c.data.dtype)]
            )
        else:
            data = c.data
        valid = c.valid_mask()
        valid = jnp.concatenate([valid, jnp.zeros((pad,), jnp.bool_)]) if pad else valid
        out.append(
            Column(
                c.dtype,
                jax.device_put(data, sharding),
                jax.device_put(valid, sharding),
            )
        )
    sharded = Table(out)
    if not return_row_valid:
        return sharded
    row_valid = jnp.concatenate(
        [jnp.ones((n,), jnp.bool_), jnp.zeros((pad,), jnp.bool_)]
    )
    return sharded, jax.device_put(row_valid, sharding)


def shard_table_multiprocess(
    local: Table,
    mesh: Mesh,
    axis: str = EXEC_AXIS,
) -> Table:
    """Multi-process variant of ``shard_table``: every participating
    process contributes its own local row chunk and gets back a GLOBAL
    sharded Table spanning all processes' devices (the
    one-PJRT-client-per-executor-JVM model, SURVEY.md section 7's
    riskiest piece).

    Requires ``jax.distributed.initialize`` to have run; ``mesh`` must
    span the global device list. Every process must call this
    collectively with the SAME number of local rows, a multiple of its
    local device count (pad with null rows first if needed — static
    shapes make uniform partitions a hard requirement, the same
    bucketed-padding discipline as everywhere else; verified here with
    an allgather so a mismatch fails loudly instead of hanging in the
    next collective). String columns are padded to the GLOBAL max char
    width (also allgathered) so every process builds the same program.

    What changes for Spark executor JVMs: each executor's embedded
    runtime calls ``jax.distributed.initialize(coordinator, n_execs,
    exec_id)`` once at startup (the coordinator address comes from the
    driver, like the UCX shuffle manager's handshake), builds the same
    global mesh from ``jax.devices()``, and builds global arrays from
    its local partitions exactly like this function. The jitted shuffle
    step is then identical to the single-process path — XLA's CPU/TPU
    collectives carry cross-process traffic (ICI on a slice, DCN across
    slices) without any operator-level change."""
    from jax.experimental import multihost_utils

    sharding = NamedSharding(mesh, P(axis))
    n_procs = jax.process_count()
    counts = np.asarray(multihost_utils.process_allgather(
        jnp.asarray([local.num_rows], jnp.int64), tiled=True))
    if not (counts == local.num_rows).all():
        raise ValueError(
            f"shard_table_multiprocess needs the SAME row count in every "
            f"process (static shapes); got per-process counts "
            f"{counts.tolist()} — pad with null rows to a common size "
            f"first")
    global_rows = local.num_rows * n_procs

    def make_global(arr):
        np_arr = np.asarray(arr)
        return jax.make_array_from_process_local_data(
            sharding, np_arr, (global_rows,) + np_arr.shape[1:])

    out = []
    for c in local.columns:
        if c.dtype.is_string:
            from spark_rapids_jni_tpu.ops.strings import pad_strings

            # pad to the GLOBAL max width: a process-local width would
            # compile a different program per process and wedge the
            # collectives on a shape mismatch
            if not c.is_padded_string:
                c = pad_strings(c)
            local_w = int(c.chars.shape[1])
            widths = np.asarray(multihost_utils.process_allgather(
                jnp.asarray([local_w], jnp.int64), tiled=True))
            target_w = int(widths.max())
            if local_w < target_w:  # pad_strings no-ops on padded input
                c = Column(c.dtype, c.data, c.validity, chars=jnp.pad(
                    c.chars, ((0, 0), (0, target_w - local_w))))
        chars = make_global(c.chars) if c.is_padded_string else None
        out.append(Column(
            c.dtype, make_global(c.data), make_global(c.valid_mask()),
            chars=chars,
        ))
    return Table(out)


class DistributedGroupBy(NamedTuple):
    table: Table             # per-device padded results, sharded over EXEC_AXIS
    num_groups: jnp.ndarray  # int32[D] groups owned by each device
    overflowed: jnp.ndarray  # bool[D] shuffle capacity overflow per device
    # bool[D] per-device DECIMAL128 SUM 128-bit overflow (the group is
    # nulled locally; this flag is how the caller tells an overflowed
    # group from an all-null-input group — Spark ANSI posture)
    sum_overflow: jnp.ndarray | bool = False


@func_range("distributed_groupby_aggregate")
def distributed_groupby_aggregate(
    table: Table,
    keys: Sequence[int],
    aggs: Sequence[tuple[int, str]],
    mesh: Mesh,
    capacity: Optional[int] = None,
) -> DistributedGroupBy:
    """Global groupby: shuffle rows by key hash, then one local groupby per
    device. After the exchange each device owns a disjoint key range, so the
    per-device results ARE the global answer, partitioned.

    ``table`` must already be sharded row-wise over ``mesh`` (shard_table).
    """
    aggs = list(aggs)
    aggs_fp = tuple(
        (int(c), tuple(op) if isinstance(op, tuple) else op)
        for c, op in aggs)
    return _distributed_groupby(
        table, list(keys), mesh, capacity,
        lambda sh_tbl, ks: groupby_aggregate(sh_tbl, ks, aggs),
        cache_key=("aggregate", aggs_fp))


class DistributedBoundedGroupBy(NamedTuple):
    """Replicated global result of the shuffle-free bounded plan: the
    same m-slot table on every device."""

    table: Table
    present: jnp.ndarray      # bool[m] — some row anywhere hit the slot
    domain_miss: jnp.ndarray  # scalar bool — any device saw an OOD key


def merge_bounded_slots(res, aggs: Sequence[tuple[int, str]], nk: int,
                        axis: str):
    """``(table, present, domain_miss)`` of one chip's bounded-plan result
    ``res`` (``plan_groupby``, lowered ``bounded``) merged over the mesh
    axis, inside ``shard_map``: a slot's sum/count/min/max aggregates are
    associative, so the merge is one ``psum`` / ``pmin`` / ``pmax`` over
    the slot table and no row crosses. The same on every chip. Shared by
    ``distributed_groupby_bounded`` and the served path's lowering of a
    declared-domain ``GroupBy`` over sharded rows (``runtime/fusion.py``)."""
    from spark_rapids_jni_tpu.ops.groupby import minmax_sentinel

    present_g = jax.lax.psum(res.present.astype(jnp.int32), axis) > 0
    miss_g = jax.lax.psum(res.domain_miss.astype(jnp.int32), axis) > 0
    out_cols: list[Column] = []
    for pos, c in enumerate(res.table.columns):
        valid_g = jax.lax.psum(c.valid_mask().astype(jnp.int32), axis) > 0
        if pos < nk:
            # key data is a trace-time constant, identical on every
            # device — only the validity needs combining
            out_cols.append(Column(c.dtype, c.data, valid_g, chars=c.chars))
            continue
        if c.dtype.is_decimal128:
            raise NotImplementedError(
                "DECIMAL128 aggregates need carry-aware merges — use "
                "distributed_groupby_aggregate")
        op = aggs[pos - nk][1]
        if op in ("sum", "count"):
            # absent slots hold the 0 neutral already
            data = jax.lax.psum(c.data, axis)
        else:
            guarded = jnp.where(
                c.valid_mask(), c.data,
                jnp.asarray(minmax_sentinel(c.dtype, op), c.data.dtype))
            data = (jax.lax.pmin(guarded, axis) if op == "min"
                    else jax.lax.pmax(guarded, axis))
        out_cols.append(Column(c.dtype, data, valid_g))
    return Table(out_cols), present_g, miss_g


@func_range("distributed_groupby_bounded")
def distributed_groupby_bounded(
    table: Table,
    keys: Sequence[int],
    aggs: Sequence[tuple[int, str]],
    domains: Sequence,
    mesh: Mesh,
    budget: int = 4096,
    row_valid: Optional[jnp.ndarray] = None,
) -> DistributedBoundedGroupBy:
    """SHUFFLE-FREE distributed groupby for planner-bounded keys.

    The bounded plan's output is a STATIC slot table (one row per domain
    combination) whose sum/count/min/max aggregates are associative per
    slot — so the cross-device merge is one collective over the m-row
    partials (psum / pmin / pmax), never a row shuffle. Where
    ``distributed_groupby_aggregate`` pays hash_shuffle (all_to_all of
    whole rows over ICI) plus per-device sort machinery, this path pays
    a per-device streaming masked-reduction pass plus an m-row
    collective: the single-chip gain of the bounded plan (PERF.md:
    planned over general q1 at SF1, 6.4 times in rows/s) composes with
    m rows on the wire instead of n. The served path takes the same
    merge for a declared-domain ``GroupBy`` over sharded rows
    (``merge_bounded_slots``); no cell measures it yet.

    ``table`` must already be sharded row-wise over ``mesh``. Output is
    REPLICATED (every device holds the global m-slot answer) — m is
    small by construction, and replication is what lets the next
    pipeline stage consume it without a broadcast.

    Scope: sum/count/min/max (mean decomposes to sum+count — the q1
    partial-aggregate convention); no DECIMAL128 aggregate columns
    (limb-pair psum has no carry propagation — use the shuffle path).
    String KEYS are fine (on-device dictionary encode, static decode).
    """
    from spark_rapids_jni_tpu.ops.planner import plan_groupby

    aggs = list(aggs)
    for _, op in aggs:
        if op not in ("sum", "count", "min", "max"):
            raise ValueError(
                f"distributed bounded groupby supports sum/count/min/max "
                f"(decompose mean to sum+count), not {op!r}")
    for col_idx, _ in aggs:
        if table.column(col_idx).dtype.is_decimal128:
            raise NotImplementedError(
                "DECIMAL128 aggregates need carry-aware merges — use "
                "distributed_groupby_aggregate")
    # eager lowering validation (NOT an assert: an un-bounded plan
    # psummed across devices would sum rows of DIFFERENT keys —
    # silently wrong, so it must raise even under python -O)
    domains = list(domains)
    if any(d is None for d in domains):
        raise ValueError(
            "every key needs a declared Domain for the shuffle-free "
            "bounded plan; use distributed_groupby_aggregate otherwise")
    slots = int(np.prod([len(d.values) + 1 for d in domains]))
    if slots > budget:
        raise ValueError(
            f"domain cross product ({slots} slots) exceeds the bounded "
            f"budget ({budget}); use distributed_groupby_aggregate")
    nk = len(keys)

    def step(local: Table, rv):
        res = plan_groupby(local, list(keys), aggs, domains,
                           budget=budget, row_valid=rv)
        assert res.lowered == "bounded"  # guaranteed by the checks above
        return merge_bounded_slots(res, aggs, nk, EXEC_AXIS)

    if row_valid is None:
        row_valid = jax.device_put(
            jnp.ones((table.num_rows,), jnp.bool_),
            NamedSharding(mesh, P(EXEC_AXIS)))
    from spark_rapids_jni_tpu.runtime import dispatch

    out_tbl, present, miss = dispatch.sharded_call(
        "distributed_groupby_bounded",
        lambda: jax.shard_map(
            step,
            mesh=mesh,
            in_specs=(P(EXEC_AXIS), P(EXEC_AXIS)),
            out_specs=(P(), P(), P()),
        ),
        (table, row_valid),
        statics=(tuple(keys),
                 tuple((int(c), op) for c, op in aggs),
                 tuple((tuple(d.values), d.kind) for d in domains),
                 int(budget), _mesh_fingerprint(mesh)),
    )
    return DistributedBoundedGroupBy(out_tbl, present, miss)


def _shuffle_retry_capacity(table: Table, mesh: Mesh,
                            capacity: Optional[int]) -> int:
    """Capacity for the one-shot overflow retry: double the EFFECTIVE
    per-device slot count (the shuffle's derived default when the caller
    passed None — mirror of shuffle_by_partition's in-trace formula) and
    re-quantize through the dispatch bucket schedule so the retry shape
    still shares executables with other batches that land in its bucket."""
    import math

    from spark_rapids_jni_tpu.runtime import dispatch

    if capacity is None:
        D = int(mesh.shape[EXEC_AXIS])
        n_local = max(1, math.ceil(table.num_rows / D))
        capacity = dispatch.quantize_capacity(
            max(1, math.ceil(n_local / D) * 2))
    return dispatch.quantize_capacity(max(int(capacity), 1) * 2)


def _distributed_groupby(table, keys, mesh, capacity, local_groupby,
                         cache_key=None):
    """Shared shuffle-then-local-groupby scaffold: hash-exchange rows so
    each device owns whole key groups, run ``local_groupby(shuffled_table,
    keys)`` per device, and pack the sharded GroupByResult.

    ``cache_key`` is a hashable fingerprint of everything ``local_groupby``
    closes over (agg list, percentile qs, ...) — the dispatch executable
    cache keys on it, NOT on the closure's identity. ``None`` means the
    closure is opaque: fall back to an uncached shard_map call rather than
    risk serving a stale executable for different closure contents.

    Shuffle capacity overflow recovers HERE, instead of at every caller:
    ``overflowed`` is a device flag (the in-trace shuffle cannot grow its
    static send-buffer shape), so the host boundary after the call is the
    first place a bigger capacity can be chosen. Escalation is bounded
    geometric through the shared resilience policy — each step doubles
    and re-quantizes through the dispatch bucket schedule, and the final
    allowed attempt jumps to the quantized row count (a per-device
    capacity of n rows always fits, so a recoverable skew never exhausts
    the bound). Still overflowing there — or past
    ``resilience.max_attempts`` — raises a classified
    ``FatalExecutionError`` carrying rows/capacity context. With
    ``resilience.enabled=false`` the historical behavior runs verbatim:
    one retry at doubled quantized capacity, then the flag is returned
    set (fail loud at the caller)."""
    from spark_rapids_jni_tpu.runtime import faults, resilience

    def run(cap):
        def step(local: Table):
            sh = hash_shuffle(local, keys, EXEC_AXIS, capacity=cap)
            res = local_groupby(sh.table, keys)
            return (res.table, res.num_groups.reshape(1),
                    sh.overflowed.reshape(1),
                    jnp.asarray(res.sum_overflow).reshape(1))

        def build():
            return jax.shard_map(
                step,
                mesh=mesh,
                in_specs=(P(EXEC_AXIS),),
                out_specs=(P(EXEC_AXIS), P(EXEC_AXIS), P(EXEC_AXIS),
                           P(EXEC_AXIS)),
            )

        if cache_key is None:
            return build()(table)
        from spark_rapids_jni_tpu.runtime import dispatch

        return dispatch.sharded_call(
            "distributed_groupby", build, (table,),
            statics=(tuple(keys), cap, cache_key,
                     _mesh_fingerprint(mesh)),
        )

    pol = resilience.policy()
    if not pol.enabled:
        out_tbl, num_groups, overflowed, sum_overflow = run(capacity)
        if bool(np.asarray(overflowed).any()):
            retry_cap = _shuffle_retry_capacity(table, mesh, capacity)
            telemetry.record_fallback(
                "distributed_groupby",
                "shuffle capacity overflow: a device received more rows "
                "than its send-buffer slots; retrying once at doubled "
                "quantized capacity",
                rows=table.num_rows, retry_capacity=retry_cap)
            out_tbl, num_groups, overflowed, sum_overflow = run(retry_cap)
        return DistributedGroupBy(out_tbl, num_groups, overflowed,
                                  sum_overflow)

    from spark_rapids_jni_tpu.runtime import dispatch

    max_cap = dispatch.quantize_capacity(max(table.num_rows, 1))
    cap = capacity  # None on attempt 1: hash_shuffle derives it in-trace

    def _run(c):
        faults.fire("shuffle.transport", 0, rows=table.num_rows)
        return run(c)

    attempt = 1
    while True:
        out_tbl, num_groups, overflowed, sum_overflow = resilience.retrying(
            "distributed_groupby", lambda: _run(cap),
            seam="shuffle.transport", pol=pol, rows=table.num_rows)
        if not bool(np.asarray(overflowed).any()):
            if attempt > 1:
                telemetry.record_resilience(
                    "distributed_groupby", "recovered",
                    seam="shuffle.transport", attempt=attempt,
                    rung="grow_capacity", rows=table.num_rows)
            return DistributedGroupBy(out_tbl, num_groups, overflowed,
                                      sum_overflow)
        at_max = cap is not None and int(cap) >= max_cap
        if attempt >= pol.max_attempts or at_max:
            telemetry.record_resilience(
                "distributed_groupby", "fatal", seam="shuffle.transport",
                attempt=attempt, rung="grow_capacity", rows=table.num_rows)
            raise resilience.FatalExecutionError(
                "distributed_groupby: shuffle capacity escalation "
                "exhausted with the overflow flag still set",
                rows=table.num_rows,
                capacity=int(cap) if cap is not None else "derived",
                max_capacity=max_cap, attempts=attempt)
        # final allowed attempt jumps straight to the quantized row count
        # (always sufficient); earlier steps double-and-quantize
        if attempt + 1 >= pol.max_attempts:
            retry_cap = max_cap
        else:
            retry_cap = min(_shuffle_retry_capacity(table, mesh, cap),
                            max_cap)
        telemetry.record_fallback(
            "distributed_groupby",
            "shuffle capacity overflow: a device received more rows than "
            "its send-buffer slots; escalating quantized capacity",
            rows=table.num_rows, retry_capacity=retry_cap)
        telemetry.record_resilience(
            "distributed_groupby", "escalate", seam="shuffle.transport",
            attempt=attempt, rung="grow_capacity", rows=table.num_rows,
            capacity=retry_cap)
        cap = retry_cap
        attempt += 1


def distributed_groupby_percentile(
    table: Table,
    keys: Sequence[int],
    value_col: int,
    qs: Sequence[float],
    mesh: Mesh,
    capacity: Optional[int] = None,
) -> DistributedGroupBy:
    """Global exact percentiles: shuffle rows by key hash (whole groups
    co-locate), then one local sort-based groupby_percentile per device —
    order statistics are group-local, so co-location makes the per-device
    answers globally exact (no sketch merging, unlike t-digest designs)."""
    from spark_rapids_jni_tpu.ops.groupby import groupby_percentile

    qs = [float(q) for q in qs]
    return _distributed_groupby(
        table, list(keys), mesh, capacity,
        lambda sh_tbl, ks: groupby_percentile(sh_tbl, ks, value_col, qs),
        cache_key=("percentile", int(value_col), tuple(qs)))


@jax.jit
def _compact_to_front(table: Table, counts: jnp.ndarray) -> Table:
    """Device-side compaction of a per-device-padded sharded result: gather
    every device's first counts[i] rows into a contiguous prefix. One
    searchsorted-driven gather (the framework's scatter-free routing idiom);
    XLA/GSPMD inserts the cross-shard collective. Rows past the real total
    are clamped repeats of row 0 — the caller slices them off."""
    d = counts.shape[0]
    n = table.num_rows
    per_dev = n // d
    off = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(counts).astype(jnp.int32)]
    )  # (d+1,) exclusive prefix
    j = jnp.arange(n, dtype=jnp.int32)
    dev = jnp.clip(
        jnp.searchsorted(off[1:], j, side="right").astype(jnp.int32), 0, d - 1
    )
    src = dev * per_dev + (j - off[dev])
    src = jnp.where(j < off[-1], src, 0)
    from spark_rapids_jni_tpu.ops.sort import gather

    return gather(table, src)


def collect(table: Table, num_rows_per_device: jnp.ndarray, mesh: Mesh) -> Table:
    """Driver-side collect of a sharded, per-device-padded result into one
    compact host table. The compaction runs on-device in one jitted gather
    (not a per-device host loop), so exactly ``total`` rows cross to the
    host — one bounded transfer per buffer, O(result), not O(padded)."""
    counts = jnp.asarray(num_rows_per_device).reshape(-1).astype(jnp.int32)
    d = int(np.prod(list(mesh.shape.values())))
    if counts.shape[0] != d:
        raise ValueError(
            f"collect: {counts.shape[0]} per-device counts for a "
            f"{d}-device mesh"
        )
    compacted = _compact_to_front(table, counts)
    total = int(np.asarray(counts).astype(np.int64).sum())
    out = []
    for c in compacted.columns:
        valid = np.asarray(c.valid_mask()[:total])
        if c.is_padded_string:
            # back to the Arrow at-rest layout on host: lengths ride the
            # data buffer; flatten the fetched (total, W) char matrix
            lens = np.asarray(c.data[:total])
            mat = np.asarray(c.chars[:total])
            blob = (
                mat.reshape(-1)[
                    (np.arange(mat.shape[1])[None, :] < lens[:, None]).reshape(-1)
                ]
                if lens.size else np.zeros((0,), np.uint8)
            )
            nbytes = int(lens.astype(np.int64).sum())
            if nbytes > np.iinfo(np.int32).max:
                raise ValueError(
                    f"collected string column holds {nbytes} bytes, over the "
                    "int32 Arrow offset bound (2^31-1); collect in batches"
                )
            offsets = np.zeros(lens.size + 1, dtype=np.int32)
            np.cumsum(lens, out=offsets[1:])
            out.append(Column(
                c.dtype, jnp.asarray(offsets), jnp.asarray(valid),
                chars=jnp.asarray(blob),
            ))
            continue
        out.append(Column(c.dtype, jnp.asarray(c.data[:total]), jnp.asarray(valid)))
    return Table(out)


class DistributedWindow(NamedTuple):
    table: Table             # shuffled input rows (padded), sharded
    results: Table           # one column per requested window spec,
                             # aligned row-for-row with ``table``
    row_valid: jnp.ndarray   # bool[D*capacity]: slot holds a real row
    overflowed: jnp.ndarray  # bool[D] shuffle capacity overflow


@func_range("distributed_window")
def distributed_window(
    table: Table,
    partition_by: Sequence[int],
    order_by: Sequence[int],
    specs: Sequence,
    mesh: Mesh,
    row_valid: jnp.ndarray,
    capacity: Optional[int] = None,
) -> DistributedWindow:
    """Global window functions: shuffle rows by partition-key hash so each
    device owns whole partitions, then evaluate partition-local windows —
    window functions are partition-local once partitions are co-located,
    exactly the distributed groupby argument.

    ``specs``: window requests as static tuples —
    ``("row_number",)``, ``("rank",)``, ``("dense_rank",)``,
    ``("lag", col_idx, k)``, ``("lead", col_idx, k)``,
    ``("running_sum", col_idx)``, ``("running_min", col_idx)``,
    ``("running_max", col_idx)``, ``("ntile", buckets)``,
    ``("percent_rank",)``, ``("cume_dist",)``,
    ``("first_value", col_idx)``, ``("last_value", col_idx)``,
    ``("nth_value", col_idx, k)``, and
    ``("rolling_<sum|count|mean|min|max>", col_idx, preceding,
    following)``, and ``("rolling_<var|std>", col_idx, preceding,
    following[, ddof])``, and value-based RANGE frames as
    ``("rolling_<sum|count|mean|min|max>_range", col_idx, preceding,
    following)``. Results come back sharded, aligned to
    the shuffled rows; filter output by the returned ``row_valid``.

    ``row_valid`` is REQUIRED (use ``shard_table(...,
    return_row_valid=True)``): unlike aggregates, window functions give
    null-key rows real results, so a padding row mistaken for a real row
    would pollute the genuine null-key partition — an all-ones default
    would hide exactly that hazard. Phantom shuffle slots are kept out of
    every real partition by an occupancy pseudo-key."""
    from spark_rapids_jni_tpu.ops.window import Window

    pkeys = list(partition_by)
    okeys = list(order_by)
    specs = [tuple(s) for s in specs]

    def step(local: Table, lrv):
        sh = hash_shuffle(local, pkeys, EXEC_AXIS, capacity=capacity,
                          row_valid=lrv)
        from spark_rapids_jni_tpu import types as t_

        # phantom slots must not join the (real) null-key partition:
        # a leading occupancy pseudo-key banishes them to their own
        # trailing partition
        occ = Column(t_.INT8,
                     jnp.where(sh.row_valid, jnp.int8(0), jnp.int8(1)),
                     None)
        wtbl = Table([occ] + list(sh.table.columns))
        w = Window(wtbl, partition_by=[0] + [k + 1 for k in pkeys],
                   order_by=[k + 1 for k in okeys])
        out_cols = []
        for spec in specs:
            kind = spec[0]
            if kind in ("row_number", "rank", "dense_rank",
                        "percent_rank", "cume_dist"):
                out_cols.append(getattr(w, kind)())
            elif kind in ("lag", "lead"):
                out_cols.append(getattr(w, kind)(spec[1] + 1, spec[2]))
            elif kind in ("running_sum", "running_min", "running_max",
                          "first_value", "last_value"):
                out_cols.append(getattr(w, kind)(spec[1] + 1))
            elif kind == "nth_value":
                out_cols.append(w.nth_value(spec[1] + 1, spec[2]))
            elif kind == "ntile":
                out_cols.append(w.ntile(spec[1]))
            elif kind in ("rolling_sum", "rolling_count", "rolling_mean",
                          "rolling_min", "rolling_max"):
                out_cols.append(getattr(w, kind)(
                    spec[1] + 1, spec[2], spec[3]))
            elif kind in ("rolling_sum_range", "rolling_count_range",
                          "rolling_mean_range", "rolling_min_range",
                          "rolling_max_range"):
                out_cols.append(getattr(w, kind[:-6])(
                    spec[1] + 1, spec[2], spec[3], frame="range"))
            elif kind in ("rolling_var", "rolling_std"):
                # optional trailing ddof (default 1 = sample)
                out_cols.append(getattr(w, kind)(
                    spec[1] + 1, spec[2], spec[3],
                    spec[4] if len(spec) > 4 else 1))
            else:
                raise ValueError(f"unknown window spec {spec!r}")
        return (sh.table, Table(out_cols), sh.row_valid,
                sh.overflowed.reshape(1))

    from spark_rapids_jni_tpu.runtime import dispatch

    out_tbl, results, rv, ovf = dispatch.sharded_call(
        "distributed_window",
        lambda: jax.shard_map(
            step,
            mesh=mesh,
            in_specs=(P(EXEC_AXIS), P(EXEC_AXIS)),
            out_specs=(P(EXEC_AXIS),) * 4,
        ),
        (table, row_valid),
        statics=(tuple(pkeys), tuple(okeys), tuple(specs), capacity,
                 _mesh_fingerprint(mesh)),
    )
    return DistributedWindow(out_tbl, results, rv, ovf)


class ShuffledJoin(NamedTuple):
    """One chip's part of an exchange-then-join (:func:`shuffled_join`)."""

    left: ShuffleResult      # the left rows this chip owns after the exchange
    right: ShuffleResult     # the right rows it owns
    # the join of the two: ``JoinMaps`` into ``out_size`` rows, or with
    # ``out_size=None`` a ``SemiJoinMask`` over the left rows where they landed
    joined: object


def shuffled_join(left: Table, right: Table, left_keys: Sequence[int],
                  right_keys: Sequence[int], axis: str, how: str,
                  out_size: Optional[int], *,
                  left_row_valid=None, right_row_valid=None,
                  left_capacity: Optional[int] = None,
                  right_capacity: Optional[int] = None) -> ShuffledJoin:
    """The exchange-then-join step as ONE chip of ``axis`` runs it (inside
    ``shard_map``): both sides exchanged by the hash of their join keys
    (``hash_shuffle``: ``partition_hash`` depends on the key's value and
    storage type alone, so equal keys land on one chip whichever side they
    come from), then the one-chip join of what landed, the shuffles'
    occupied slots as its row masks (an empty slot emits nothing, not even
    under an outer join). The one such step in the package:
    ``distributed_join`` and the served path's lowering of a ``Join`` over
    sharded rows (``runtime/fusion.py``) both call it.

    ``out_size=None`` takes ``left_semi`` / ``left_anti`` as a mask over the
    landed left rows (``ops/join.semi_join_mask``); otherwise ``join`` lays
    its maps out into ``out_size`` rows a chip. Rows that are False in a
    ``*_row_valid`` are packed out before the exchange and never travel.
    Whether a shuffle found more rows for a chip than ``*_capacity`` slots
    (default ``hash_shuffle``'s) is ``.left.overflowed`` /
    ``.right.overflowed``: rows were dropped, the caller must not answer.

    A device trace splits the step by its sub-scopes: ``exchange/left``,
    ``exchange/right``, and the join's own ``build`` / ``probe``."""
    from spark_rapids_jni_tpu.ops.join import join, semi_join_mask

    lkeys, rkeys = list(left_keys), list(right_keys)
    with jax.named_scope("exchange"):
        with jax.named_scope("left"):
            ls = hash_shuffle(left, lkeys, axis, capacity=left_capacity,
                              row_valid=left_row_valid)
        with jax.named_scope("right"):
            rs = hash_shuffle(right, rkeys, axis, capacity=right_capacity,
                              row_valid=right_row_valid)
    if out_size is None:
        joined = semi_join_mask(
            ls.table, rs.table, lkeys, rkeys, how,
            left_row_valid=ls.row_valid, right_row_valid=rs.row_valid)
    else:
        joined = join(ls.table, rs.table, lkeys, rkeys, out_size, how=how,
                      left_row_valid=ls.row_valid,
                      right_row_valid=rs.row_valid)
    return ShuffledJoin(ls, rs, joined)


class DistributedJoin(NamedTuple):
    table: Table             # per-device joined rows (padded), sharded
    total: jnp.ndarray       # int64[D] true match count per device
    overflowed: jnp.ndarray  # bool[D] shuffle capacity overflow per device


@func_range("distributed_join")
def distributed_join(
    left: Table,
    right: Table,
    left_on: int | Sequence[int],
    right_on: int | Sequence[int],
    mesh: Mesh,
    out_size_per_device: int,
    how: str = "inner",
    left_capacity: Optional[int] = None,
    right_capacity: Optional[int] = None,
    left_row_valid: Optional[jnp.ndarray] = None,
    right_row_valid: Optional[jnp.ndarray] = None,
) -> DistributedJoin:
    """Repartitioned equi-join — the RapidsShuffleManager join pattern: both
    sides exchange rows by key hash over ICI, after which equal keys live on
    the same device and a device-local sort-merge join finishes the work
    (:func:`shuffled_join`, the step the served path lowers a ``Join`` of
    sharded rows to as well).

    Both inputs must already be sharded row-wise over ``mesh``. Pass the
    ``row_valid`` masks from ``shard_table(..., return_row_valid=True)`` so
    padding rows are dropped before the exchange — under a left join a
    padding row would otherwise be indistinguishable from a genuine
    NULL-key row and emit output.
    """
    from spark_rapids_jni_tpu.ops.join import apply_join_maps

    left_keys = [left_on] if isinstance(left_on, int) else list(left_on)
    right_keys = [right_on] if isinstance(right_on, int) else list(right_on)

    def step(l: Table, r: Table, lrv, rrv):
        sj = shuffled_join(
            l, r, left_keys, right_keys, EXEC_AXIS, how,
            out_size_per_device, left_row_valid=lrv, right_row_valid=rrv,
            left_capacity=left_capacity, right_capacity=right_capacity)
        joined = apply_join_maps(sj.left.table, sj.right.table, sj.joined)
        overflow = sj.left.overflowed | sj.right.overflowed
        return joined, sj.joined.total.reshape(1), overflow.reshape(1)

    if left_row_valid is None:
        left_row_valid = jnp.ones((left.num_rows,), jnp.bool_)
    if right_row_valid is None:
        right_row_valid = jnp.ones((right.num_rows,), jnp.bool_)
    from spark_rapids_jni_tpu.runtime import dispatch, faults, resilience

    def _exchange():
        # the exchange is the ICI-transport boundary: a transient
        # transport fault here replays the whole (idempotent) step
        faults.fire("shuffle.transport", 0,
                    rows=left.num_rows + right.num_rows)
        return dispatch.sharded_call(
            "distributed_join",
            lambda: jax.shard_map(
                step,
                mesh=mesh,
                in_specs=(P(EXEC_AXIS), P(EXEC_AXIS), P(EXEC_AXIS),
                          P(EXEC_AXIS)),
                out_specs=(P(EXEC_AXIS), P(EXEC_AXIS), P(EXEC_AXIS)),
            ),
            (left, right, left_row_valid, right_row_valid),
            statics=(tuple(left_keys), tuple(right_keys),
                     int(out_size_per_device), how, left_capacity,
                     right_capacity, _mesh_fingerprint(mesh)),
        )

    if resilience.enabled():
        out, total, overflowed = resilience.retrying(
            "distributed_join", _exchange, seam="shuffle.transport",
            rows=left.num_rows + right.num_rows)
    else:
        out, total, overflowed = _exchange()
    return DistributedJoin(out, total, overflowed)


class DistributedCollectList(NamedTuple):
    table: Table             # keys then one LIST column, host-assembled
    overflowed: jnp.ndarray  # bool[D] shuffle capacity overflow


@func_range("distributed_groupby_collect")
def distributed_groupby_collect(
    table: Table,
    keys: Sequence[int],
    value_col: int,
    mesh: Mesh,
    capacity: int,
    distinct: bool = False,
) -> DistributedCollectList:
    """Global collect_list/collect_set: hash-shuffle rows so whole key
    groups co-locate (the shared ``_distributed_groupby`` scaffold), run
    one local ``groupby_collect`` per device, then assemble the
    per-device LIST results on the driver (trim + LIST-aware
    concatenate — the nested-offset analogue of ``collect``). Row order
    across devices is unspecified (sort on the keys afterwards if
    needed).

    Shard padding rows follow the module's phantom-row posture: they
    surface as one all-null-key group (with an empty list) that callers
    discard like local groupby padding."""
    from spark_rapids_jni_tpu.ops.lists import groupby_collect
    from spark_rapids_jni_tpu.ops.groupby import GroupByResult
    from spark_rapids_jni_tpu.ops.table_ops import concatenate, trim_table

    ks = list(keys)

    def local_collect(sh_tbl: Table, kss):
        res = groupby_collect(sh_tbl, kss, value_col, distinct=distinct)
        # adapt to the scaffold's GroupByResult packing (the default
        # overflow flags are static False — collect has no max_groups)
        return GroupByResult(res.table, res.num_groups)

    dist = _distributed_groupby(
        table, ks, mesh, capacity, local_collect,
        cache_key=("collect", int(value_col), bool(distinct)))
    out_tbl, ngs, ovf = dist.table, dist.num_groups, dist.overflowed
    d = int(np.prod(list(mesh.shape.values())))
    counts = np.asarray(ngs).reshape(-1)

    def _host_chunks(c: Column) -> list[Column]:
        """ONE device->host fetch per buffer, then numpy slicing — no
        per-device sync loop (each leaf is evenly divided across the
        mesh by shard_map)."""
        bufs = {}
        for name in ("data", "validity", "chars"):
            arr = getattr(c, name)
            bufs[name] = None if arr is None else np.asarray(arr)
        kid_chunks = (None if c.children is None
                      else [_host_chunks(k) for k in c.children])
        out = []
        for di in range(d):
            def seg(arr):
                if arr is None:
                    return None
                chunk = arr.shape[0] // d
                return jnp.asarray(arr[di * chunk:(di + 1) * chunk])

            kids = (None if kid_chunks is None
                    else [kc[di] for kc in kid_chunks])
            out.append(Column(c.dtype, seg(bufs["data"]),
                              seg(bufs["validity"]),
                              chars=seg(bufs["chars"]), children=kids))
        return out

    col_chunks = [_host_chunks(c) for c in out_tbl.columns]
    per_dev = []
    for di in range(d):
        tbl_d = Table([cc[di] for cc in col_chunks])
        per_dev.append(trim_table(tbl_d, int(counts[di])))
    return DistributedCollectList(concatenate(per_dev), ovf)
