"""General-cardinality distributed exchange — hash-partitioned all-to-all
repartitioning over the cluster mesh.

The ICI shuffle (parallel/shuffle.py) moves rows between devices of ONE
host's mesh program with an XLA ``all_to_all``; the serving mesh
(runtime/cluster.py) moves whole tables between HOSTS but only along a
static partition-for-slices layout. This module is the missing middle —
the Spark exchange operator: repartition a device-resident table by
arbitrary key columns so that every key lands on exactly one destination,
with no static slot table anywhere.

Three halves, each reusing an existing discipline:

* **Device half** — ``partition_hash`` -> destination-sorted pack into a
  contiguous ``(parts, capacity)`` send buffer, via the SAME send plan the
  ICI shuffle uses (``_plan_send`` / ``_rows_by_destination`` /
  ``_pack_send``: a stable sort by destination and one slice a
  destination; imported, not copied).
  Capacities are quantized
  through the dispatch bucket schedule so ragged partition sizes share
  executables; destination p's rows are exactly the first ``counts[p]``
  slots of its capacity run, so the host trims real rows with plain
  slices, never a compaction pass.

* **Wire half** — per-destination buffers ship as TPCZ codec frames under
  the integrity seal via ``dcn.send_framed`` / ``dcn.recv_framed`` (the
  one shared seal-ordering helper): verify-then-decode with NAK-driven
  ARQ refetch comes for free, and injected corruption is scoped to the
  ``exchange.wire`` seam so chaos scripts can target shuffle traffic
  without touching registration frames. Inside the cluster the wire form
  is ONE concatenated table per source (flight-major, part-major slices)
  whose ``row_counts`` ride as plain meta — it survives the fleet's
  result frames unchanged.

* **Overflow half** — the one-shot doubled-capacity retry is replaced by
  a spill-aware ladder: overflowing packs escalate geometrically through
  ``resilience.escalate`` (rung ``grow_capacity``) up to
  ``exchange.max_capacity_rows``, then demote to multi-flight chunking
  (each chunk packed at a capacity that provably cannot overflow), and
  the receive side merges flights through ``outofcore.
  run_chunked_aggregate`` with a SpillStore so skewed keys degrade into
  host spill instead of dying. Every overflow that escapes the ladder is
  classified (``shuffle.classify_overflow`` -> ``CapacityOverflow`` with
  partition/capacity context) — never a bare boolean.

On top sit the general plan steps: ``partitioned_groupby`` /
``partitioned_join`` (hash co-partition, per-partition op, concat —
output keys are disjoint across partitions so the concat IS the result)
and the ``Exchange`` plan-root node (runtime/fusion.py) the cluster's
``submit_exchange`` drives end-to-end.
"""

from __future__ import annotations

import math
from typing import Callable, NamedTuple, Optional, Sequence

import jax.numpy as jnp
import numpy as np

from spark_rapids_jni_tpu import telemetry
from spark_rapids_jni_tpu.columnar import Column, Table
from spark_rapids_jni_tpu.ops.hash import partition_hash
from spark_rapids_jni_tpu.ops.table_ops import _slice_rows, concatenate
from spark_rapids_jni_tpu.parallel.shuffle import (
    _pack_send,
    _plan_send,
    _rows_by_destination,
    classify_overflow,
)
from spark_rapids_jni_tpu.runtime import dispatch, resilience
from spark_rapids_jni_tpu.runtime.memory import (
    MemoryLimiter,
    SpillStore,
    _table_nbytes,
)
from spark_rapids_jni_tpu.telemetry import spans
from spark_rapids_jni_tpu.telemetry.registry import REGISTRY
from spark_rapids_jni_tpu.types import TypeId
from spark_rapids_jni_tpu.utils.config import get_option
from spark_rapids_jni_tpu.utils.log import get_logger
from spark_rapids_jni_tpu.utils.tracing import func_range

_log = get_logger(__name__)


class PackResult(NamedTuple):
    """One packed flight: ``parts * capacity`` destination-sorted rows.

    ``counts[p]`` is destination p's TRUE row count; in a returned (non-
    overflowed) flight ``counts[p] <= capacity`` and p's rows are exactly
    slots ``[p * capacity, p * capacity + counts[p])`` — contiguous, so
    per-destination send buffers are plain slices."""

    table: Table
    counts: np.ndarray
    capacity: int


def _make_pack_fn(keys: tuple, parts: int, capacity: int) -> Callable:
    """The dispatchable pack: ``shuffle_by_partition``'s send plan with the
    mesh axis replaced by a host-level destination dim (no ``all_to_all``
    — the wire half moves the buffers). The closure's variation is fully
    captured by the caller's ``statics``."""

    def pack(row_args, aux_args, row_valids):
        (table,) = row_args
        rv = None if row_valids is None else row_valids[0]
        part = partition_hash(table, list(keys), parts)
        plan = _plan_send(part, rv, parts, capacity)
        occupied = plan.occupied
        # full real count per destination (including overflow past the
        # capacity) — the escalation ladder's exact `required`
        counts = plan.counts
        overflowed = jnp.any(counts > capacity)

        out_cols = []
        for col in _rows_by_destination(table, plan).columns:
            if col.dtype.is_string:
                if not col.is_padded_string:
                    raise NotImplementedError(
                        "exchange pack needs string columns in the padded "
                        "device layout (ops.strings.pad_strings)")
                lens = _pack_send(col.data, plan)
                chars = _pack_send(col.chars, plan)
                valid = _pack_send(col.valid_mask(), plan) & occupied
                out_cols.append(Column(col.dtype, lens, valid, chars=chars))
                continue
            if col.dtype.type_id == TypeId.LIST:
                if not col.is_padded_list:
                    raise NotImplementedError(
                        "exchange pack needs LIST columns in the padded "
                        "wire layout (ops.lists.pad_lists)")
                elem = col.children[0]
                lens = _pack_send(col.data, plan)
                emat = _pack_send(elem.data, plan)
                ev = _pack_send(elem.valid_mask(), plan)
                valid = _pack_send(col.valid_mask(), plan) & occupied
                # unoccupied slots must read as EMPTY lists
                lens = jnp.where(occupied, lens, 0)
                ev = ev & occupied[:, None]
                out_cols.append(Column(
                    col.dtype, lens, valid,
                    children=[Column(elem.dtype, emat, ev)]))
                continue
            if not (col.dtype.is_fixed_width or col.dtype.is_decimal128):
                raise NotImplementedError(
                    "exchange pack supports fixed-width columns only "
                    "(the ICI shuffle shares this restriction)")
            data = _pack_send(col.data, plan)
            valid = _pack_send(col.valid_mask(), plan) & occupied
            out_cols.append(Column(col.dtype, data, valid))
        return Table(out_cols), counts, overflowed

    return pack


def _pack_once(table: Table, keys: Sequence[int], parts: int,
               capacity: int) -> tuple[PackResult, bool]:
    keys = tuple(int(k) for k in keys)
    parts = int(parts)
    capacity = int(capacity)
    fn = _make_pack_fn(keys, parts, capacity)
    packed, counts, overflowed = dispatch.call(
        "exchange.pack", fn, (table,),
        statics=(keys, parts, capacity), slice_rows=False)
    res = PackResult(packed, np.asarray(counts).astype(np.int64), capacity)
    return res, bool(np.asarray(overflowed))


@func_range("exchange_pack")
def pack_flights(table: Table, keys: Sequence[int], parts: int, *,
                 capacity: Optional[int] = None, op: str = "exchange",
                 cancel_token=None) -> list[PackResult]:
    """Pack ``table`` into per-destination send buffers — the spill-aware
    overflow ladder.

    Rung 1: geometric capacity escalation through ``resilience.escalate``
    (start ``ceil(n/parts) * 2`` quantized, or the caller's planned
    capacity), each overflow naming its exact requirement so the schedule
    jumps there. Rung 2: at ``exchange.max_capacity_rows`` the pack
    demotes to MULTI-FLIGHT chunking — the source is host-sliced into
    chunks no larger than the cap and each chunk packs at a capacity that
    cannot overflow (a chunk's hottest destination holds at most the
    chunk's rows), so arbitrarily skewed keys always ship; the receive
    side absorbs the extra flights through the SpillStore merge
    (:func:`merge_flights`). Exhaustion inside a rung raises classified
    (``CapacityOverflow`` with partition/capacity context), never a bare
    boolean."""
    if cancel_token is not None:
        cancel_token.check(op)
    n = table.num_rows
    parts = int(parts)
    if parts < 1:
        raise ValueError(f"{op}: parts must be >= 1, got {parts}")
    max_cap = max(1, dispatch.quantize_capacity(
        int(get_option("exchange.max_capacity_rows"))))
    if capacity is None:
        initial = dispatch.quantize_capacity(
            max(1, math.ceil(max(n, 1) / parts) * 2))
    else:
        initial = max(1, int(capacity))
    initial = min(initial, max_cap)

    def attempt(cap: int):
        res, overflowed = _pack_once(table, keys, parts, cap)
        if overflowed:
            REGISTRY.counter("exchange.overflow_escalations").inc()
            telemetry.record_exchange(
                op, "overflow_escalate", rows=n, capacity=cap,
                partition=int(res.counts.argmax()),
                required=int(res.counts.max()))
            return None, True, int(res.counts.max())
        return res, False, None

    try:
        return [resilience.escalate(
            f"{op}.pack", attempt, seam="exchange.pack",
            initial=initial, max_capacity=max_cap,
            quantize=dispatch.quantize_capacity,
            exhaust=lambda cap, steps: classify_overflow(
                op=f"{op}.pack", capacity=cap, rows=n,
                seam="exchange.pack", steps=steps),
            rows=n)]
    except resilience.CapacityOverflow:
        # rung 2: chunked flights. Each chunk's hottest destination can
        # receive at most the chunk's row count, and the chunk is at most
        # max_cap rows packed at capacity >= chunk rows — overflow is
        # structurally impossible, so this rung always terminates.
        if cancel_token is not None:
            cancel_token.check(op)
        flights: list[PackResult] = []
        for lo in range(0, n, max_cap):
            chunk = _slice_rows(table, lo, min(lo + max_cap, n))
            cap = max(chunk.num_rows,
                      dispatch.quantize_capacity(chunk.num_rows))
            res, overflowed = _pack_once(chunk, keys, parts, cap)
            if overflowed:  # pragma: no cover - see invariant above
                raise classify_overflow(
                    op=f"{op}.pack", capacity=cap, rows=chunk.num_rows,
                    seam="exchange.pack")
            flights.append(res)
        REGISTRY.counter("exchange.chunked_flights").inc()
        telemetry.record_exchange(
            op, "chunked_flights", rows=n, flights=len(flights),
            capacity=max_cap)
        _log.info("%s: demoted to %d chunked flights (max capacity %d)",
                  op, len(flights), max_cap)
        return flights


def flight_slices(res: PackResult) -> list[Table]:
    """Per-destination trim of one packed flight: destination p's real
    rows are exactly the first ``counts[p]`` slots of its capacity run
    (contiguous by construction — plain slices, no compaction)."""
    return [
        _slice_rows(res.table, p * res.capacity,
                    p * res.capacity + int(c))
        for p, c in enumerate(res.counts)
    ]


def build_wire(flights: Sequence[PackResult]) -> tuple[Table, list]:
    """Flatten flights into the cluster wire form: ONE table — the
    per-destination slices concatenated flight-major then part-major —
    plus the flat ``row_counts`` list (length ``flights * parts``) that
    inverts it. ``row_counts`` is plain Python, so it rides result-frame
    meta through the fleet codec unchanged."""
    slices: list[Table] = []
    row_counts: list[int] = []
    for res in flights:
        for s in flight_slices(res):
            row_counts.append(int(s.num_rows))
            slices.append(s)
    nonempty = [s for s in slices if s.num_rows]
    if nonempty:
        wire = nonempty[0] if len(nonempty) == 1 else concatenate(nonempty)
    else:
        wire = _slice_rows(flights[0].table, 0, 0)
    return wire, row_counts


def split_wire(wire: Table, row_counts: Sequence[int],
               parts: int) -> list[list[Table]]:
    """Supervisor-side inverse of :func:`build_wire`: slice a source's
    wire table back into per-destination flight tables. Returns
    ``parts`` lists (destination-indexed), each holding that
    destination's non-empty flights in flight order."""
    parts = int(parts)
    if len(row_counts) % parts:
        raise resilience.MalformedInputError(
            f"exchange wire row_counts length {len(row_counts)} is not a "
            f"multiple of parts={parts}", seam="exchange.wire")
    per_dest: list[list[Table]] = [[] for _ in range(parts)]
    lo = 0
    for i, c in enumerate(row_counts):
        hi = lo + int(c)
        if hi > lo:
            per_dest[i % parts].append(_slice_rows(wire, lo, hi))
        lo = hi
    if lo != wire.num_rows:
        raise resilience.MalformedInputError(
            f"exchange wire table has {wire.num_rows} rows but row_counts "
            f"sum to {lo}", seam="exchange.wire")
    return per_dest


def choose_parts(plan_name: str, label: str, rows: int, *,
                 fallback: int = 1) -> int:
    """Pick a partition count for an auto-parts (``parts=0``) Exchange
    from the learned-selectivity store: the store's EMA for this
    (plan, exchange label) signature is the observed fraction of the
    region's input rows that actually enter the exchange (a partial
    groupby's group density), so ``rows x ema / target_rows_per_part``
    estimates how many destinations the packed output warrants. No
    history falls back to ``fallback``. Every choice is recorded with
    its reason (an unexplained partition count is an unexplainable plan
    change, same contract as the rtfilter gate)."""
    from spark_rapids_jni_tpu.runtime import rtfilter

    rows = int(rows)
    ema = rtfilter.learned_pass_frac(plan_name, f"xparts.{label}")
    if ema is None:
        parts, reason = int(fallback), "no_history"
    else:
        target = max(1, int(get_option("exchange.target_rows_per_part")))
        est = max(1, int(rows * float(ema)))
        parts = max(1, min(int(get_option("exchange.max_parts")),
                           -(-est // target)))
        reason = "learned_density"
    REGISTRY.counter("exchange.parts_chosen").inc()
    telemetry.record_exchange(
        f"exchange.{label}", "parts_decision", parts=parts, rows=rows,
        reason=reason, pass_frac_ema=ema)
    return parts


def resolve_auto_parts(plan_name: str, node, bindings: dict):
    """Resolve an Exchange node's ``parts=0`` auto sentinel into a
    concrete partition count (:func:`choose_parts` over the bound input
    rows). Returns the node unchanged when parts is already concrete —
    fingerprints and plan signatures only ever see resolved counts."""
    if int(node.parts) != 0:
        return node
    rows = sum(int(t.num_rows) for t in bindings.values())
    return node._replace(parts=choose_parts(plan_name, node.label, rows))


def execute_exchange_root(plan, bindings: dict, *,
                          donate_inputs: bool = False,
                          force_staged: bool = False,
                          surface_pressure: bool = False,
                          cancel_token=None):
    """Run a Plan whose root is an ``Exchange`` node: execute the child
    region normally (fused or staged — ``fusion.execute`` decides), trim
    budget-padding phantoms via ``valid_meta``, pack through the overflow
    ladder, and return the wire form with routing meta
    (``<label>.parts/.capacity/.flights/.row_counts/.rows``) merged over
    the child's. Called by ``fusion.execute`` itself — an Exchange root
    is the one node that is a genuine host boundary."""
    from spark_rapids_jni_tpu.runtime import fusion, rtfilter

    root = resolve_auto_parts(plan.name, plan.root, bindings)
    inner = fusion.execute(
        fusion.Plan(plan.name, root.child), bindings,
        donate_inputs=donate_inputs, force_staged=force_staged,
        surface_pressure=surface_pressure, cancel_token=cancel_token)
    tbl = inner.table
    if root.valid_meta is not None:
        if root.valid_meta not in inner.meta:
            raise KeyError(
                f"exchange {root.label!r}: valid_meta {root.valid_meta!r} "
                f"is not a child meta key (have {sorted(inner.meta)})")
        tbl = _slice_rows(
            tbl, 0, int(np.asarray(inner.meta[root.valid_meta])))
    rows = tbl.num_rows
    # harvest the region's group density into the learned store: the
    # signal choose_parts() sizes future auto-parts exchanges from
    rtfilter.observe(plan.name, f"xparts.{root.label}",
                     sum(int(t.num_rows) for t in bindings.values()), rows)
    cap = fusion._resolve(
        root.capacity, {k: v.num_rows for k, v in bindings.items()})
    op = f"exchange.{root.label}"
    with spans.span(op, parts=int(root.parts), rows=rows):
        flights = pack_flights(
            tbl, root.keys, root.parts, capacity=cap, op=op,
            cancel_token=cancel_token)
        wire, row_counts = build_wire(flights)
    REGISTRY.counter("exchange.rows_routed").inc(int(sum(row_counts)))
    telemetry.record_exchange(
        op, "pack", rows=rows, parts=int(root.parts),
        flights=len(flights), capacity=int(flights[0].capacity))
    meta = dict(inner.meta)
    meta[f"{root.label}.parts"] = int(root.parts)
    meta[f"{root.label}.capacity"] = int(flights[0].capacity)
    meta[f"{root.label}.flights"] = len(flights)
    meta[f"{root.label}.row_counts"] = [int(c) for c in row_counts]
    meta[f"{root.label}.rows"] = int(rows)
    return fusion.FusedResult(wire, meta)


@func_range("exchange_local")
def exchange_local(table: Table, keys: Sequence[int], parts: int, *,
                   capacity: Optional[int] = None,
                   op: str = "exchange.local") -> list[Table]:
    """Single-host exchange — the bit-identity oracle for the
    distributed path and the building block of the local partitioned plan
    steps. Returns ``parts`` tables: destination p holds exactly the rows
    whose key hash lands on p, in stable (flight, input) order — the same
    rows, in the same order, the distributed exchange delivers."""
    flights = pack_flights(table, keys, parts, capacity=capacity, op=op)
    per_dest: list[list[Table]] = [[] for _ in range(int(parts))]
    for res in flights:
        for p, s in enumerate(flight_slices(res)):
            if s.num_rows:
                per_dest[p].append(s)
    empty = _slice_rows(flights[0].table, 0, 0)
    return [
        ds[0] if len(ds) == 1 else (concatenate(ds) if ds else empty)
        for ds in per_dest
    ]


def merge_flights(flights: Sequence[Table],
                  partial_fn: Callable[[Table], Table],
                  merge_fn: Callable[[Table], Table], *,
                  budget_bytes: Optional[int] = None,
                  limiter: Optional[MemoryLimiter] = None,
                  spill: Optional[SpillStore] = None,
                  op: str = "exchange.merge", cancel_token=None):
    """Receive-side spill-aware merge: stream a destination's flights
    through the out-of-core chunked aggregator under a device budget
    (``exchange.merge_budget_bytes``), demoting partials into the
    SpillStore when they exceed it — how a skewed destination absorbs a
    multi-flight exchange without holding every flight in HBM at once.
    Zero-leak contract inherited from ``run_chunked_aggregate``. Returns
    its ``OutOfCoreResult``."""
    from spark_rapids_jni_tpu.runtime import outofcore

    flights = list(flights)
    if not flights:
        raise ValueError(f"{op}: no flights to merge")
    budget = int(budget_bytes if budget_bytes is not None
                 else get_option("exchange.merge_budget_bytes"))
    if limiter is None:
        limiter = MemoryLimiter(budget)
    if spill is None:
        spill = SpillStore(budget)
    res = outofcore.run_chunked_aggregate(
        flights, partial_fn, merge_fn,
        limiter=limiter, spill=spill, cancel_token=cancel_token)
    spilled = int(res.spill_stats.get("spills", 0))
    if spilled:
        REGISTRY.counter("exchange.spill_demotions").inc(spilled)
        telemetry.record_exchange(
            op, "spill_demote", spilled=spilled, chunks=res.chunks,
            peak_bytes=res.peak_bytes)
    telemetry.record_exchange(
        op, "merge", rows=res.table.num_rows, chunks=res.chunks,
        peak_bytes=res.peak_bytes)
    return res


def serialize_flight(table: Table, *,
                     op: str = "exchange.serialize_flight", **ctx) -> bytes:
    """Serialize one flight (TPCZ codec via ``dcn.serialize_table``) and
    account for it ONCE, at first seal: ``exchange.flights`` /
    ``bytes_raw`` / ``bytes_wire`` count unique flight payloads, so ARQ
    refetch resends, a direct attempt that falls back to the routed
    rung, or any other re-send of the same pristine blob never double
    counts the wire ledger. Per-attempt transport bytes are the lane
    counters' job (:func:`send_flight_blob`)."""
    from spark_rapids_jni_tpu.parallel import dcn

    blob = dcn.serialize_table(table)
    REGISTRY.counter("exchange.flights").inc()
    REGISTRY.counter("exchange.bytes_raw").inc(int(_table_nbytes(table)))
    REGISTRY.counter("exchange.bytes_wire").inc(len(blob))
    telemetry.record_exchange(
        op, "flight", rows=table.num_rows, wire_bytes=len(blob),
        raw_bytes=int(_table_nbytes(table)), **ctx)
    return blob


def send_flight_blob(sock, blob: bytes, seq: int, *,
                     lane: str = "direct",
                     op: str = "exchange.send_flight", **ctx) -> int:
    """Ship one already-serialized flight blob through the ONE shared
    seal-ordering helper (``dcn.send_framed``) with corruption faults
    scoped to the ``exchange.wire`` seam. ``lane`` names the topology
    the bytes actually took — ``"direct"`` (host-to-host peer dial) or
    ``"routed"`` (via the supervisor) — splitting the transport ledger
    (``exchange.bytes_direct`` / ``exchange.bytes_routed``) so the
    direct path's supervisor-link win is measurable from telemetry
    alone; ``bytes_wire`` was already counted at first seal."""
    from spark_rapids_jni_tpu.parallel import dcn

    lane = str(lane)
    if lane not in ("direct", "routed"):
        raise ValueError(f"exchange flight lane must be 'direct' or "
                         f"'routed', got {lane!r}")
    REGISTRY.counter(f"exchange.bytes_{lane}").inc(len(blob))
    return dcn.send_framed(sock, blob, seq, op=op,
                           corrupt_seam="exchange.wire", lane=lane, **ctx)


def send_flight(sock, table: Table, seq: int, *,
                lane: str = "direct",
                op: str = "exchange.send_flight", **ctx) -> int:
    """Serialize-and-ship convenience: :func:`serialize_flight` (counts
    the wire ledger once) then :func:`send_flight_blob` (counts the
    lane). Callers that may send the same flight on more than one lane
    (direct attempt, routed fallback) call the two halves themselves so
    ``bytes_wire`` stays a unique-payload ledger."""
    blob = serialize_flight(table, op=op, **ctx)
    return send_flight_blob(sock, blob, seq, lane=lane, op=op,
                            rows=table.num_rows, **ctx)


def recv_flight(sock, seq: int, *, op: str = "exchange.recv_flight") -> Table:
    """Receive one flight under verify-then-decode: the trailer is
    checked (NAK-driven refetch on corruption) BEFORE the codec decode
    ever sees the bytes."""
    from spark_rapids_jni_tpu.parallel import dcn

    return dcn.deserialize_table(dcn.recv_framed(sock, seq, op=op))


@func_range("partitioned_groupby")
def partitioned_groupby(table: Table, keys: Sequence[int],
                        aggs: Sequence[tuple], *, parts: int,
                        capacity: Optional[int] = None) -> Table:
    """General hash-partitioned groupby — NO static slot table: exchange
    rows by key hash so every key lives on exactly one partition, then
    run the unbounded per-partition groupby (``max_groups=None`` pads to
    the partition's row count, which can never overflow). Output keys are
    disjoint across partitions, so the concatenation IS the global
    result (order: partition-major, then key-sorted within)."""
    from spark_rapids_jni_tpu.ops.groupby import groupby_aggregate

    out: list[Table] = []
    for dest in exchange_local(table, keys, parts, capacity=capacity):
        if not dest.num_rows:
            continue
        g = groupby_aggregate(dest, list(keys), list(aggs), max_groups=None)
        out.append(_slice_rows(g.table, 0, int(np.asarray(g.num_groups))))
    if not out:
        g = groupby_aggregate(table, list(keys), list(aggs), max_groups=None)
        return _slice_rows(g.table, 0, 0)
    return out[0] if len(out) == 1 else concatenate(out)


@func_range("partitioned_join")
def partitioned_join(left: Table, right: Table,
                     left_on, right_on, *, parts: int,
                     how: str = "inner") -> Table:
    """General hash-partitioned equi-join — co-partition both sides with
    the SAME key hash (matching keys land on the same partition by
    construction), join per partition with the grow-and-retry output
    bound, and concatenate: the per-partition results are disjoint over
    the key space, so the concat is the global join."""
    from spark_rapids_jni_tpu.ops.join import join_auto

    lks = [left_on] if isinstance(left_on, int) else list(left_on)
    rks = [right_on] if isinstance(right_on, int) else list(right_on)
    ldests = exchange_local(left, lks, parts, op="exchange.join_left")
    rdests = exchange_local(right, rks, parts, op="exchange.join_right")
    out: list[Table] = []
    for ld, rd in zip(ldests, rdests):
        if not ld.num_rows:
            continue
        if not rd.num_rows and how == "inner":
            continue
        maps, joined = join_auto(ld, rd, left_on, right_on, how=how)
        # join_auto materializes at the escalated CAPACITY; the real
        # matches are the first maps.total rows
        joined = _slice_rows(joined, 0, int(np.asarray(maps.total)))
        if joined.num_rows:
            out.append(joined)
    if not out:
        maps, joined = join_auto(left, right, left_on, right_on, how=how)
        return _slice_rows(joined, 0, 0)
    return out[0] if len(out) == 1 else concatenate(out)


def stats() -> dict:
    """Snapshot of the ``exchange.*`` transport counters (bench + CI
    smoke): rows routed, flights, raw vs wire bytes, overflow
    escalations, chunked-flight demotions, spill demotions."""
    counters = REGISTRY.counters("exchange.")
    return {
        "rows_routed": counters.get("exchange.rows_routed", 0),
        "flights": counters.get("exchange.flights", 0),
        "bytes_raw": counters.get("exchange.bytes_raw", 0),
        "bytes_wire": counters.get("exchange.bytes_wire", 0),
        "bytes_direct": counters.get("exchange.bytes_direct", 0),
        "bytes_routed": counters.get("exchange.bytes_routed", 0),
        "overflow_escalations":
            counters.get("exchange.overflow_escalations", 0),
        "chunked_flights": counters.get("exchange.chunked_flights", 0),
        "spill_demotions": counters.get("exchange.spill_demotions", 0),
    }
