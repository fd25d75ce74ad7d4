"""ICI all-to-all shuffle transport — repartition a device-resident table by
key hash across the executor mesh axis.

This is the RapidsShuffleManager replacement (BASELINE.json north_star;
SURVEY.md section 2.3 "distributed comm backend — must be built"): where the
GPU stack serializes partition blocks and moves them over UCX between
executor processes, here every executor's batch stays in HBM and one XLA
``all_to_all`` collective performs the full D x D partition exchange over
ICI in a single fused step.

TPU-first shape discipline: ``all_to_all`` needs a static per-destination
capacity, so each device packs its rows into a ``(D, capacity)`` send
buffer (rows sorted by destination, then one contiguous slice a
destination: ``_plan_send`` / ``_pack_send``) with an occupancy mask;
unoccupied receive slots surface as null rows, which every downstream
operator already skips (the masked-row trick the local operators use for
static-shape filtering). The capacity default ``ceil(n/D) * 2`` covers 2x
skew; overflow is detected and reported per-call
(`ShuffleResult.overflowed`) rather than silently dropped — the moral equal
of the reference's 2^31-byte batch bound (row_conversion.cu:476-479).

String columns travel in the padded device layout (ops.strings): their
int32 lengths ride the fixed-width path and the (n, W) char matrix is
exchanged as W parallel byte lanes of the same all_to_all — variable-length
data over a static-shape collective. Arrow-layout string columns must be
padded before entering the mesh program (shard_table does this).
"""

from __future__ import annotations

import math
from typing import NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from spark_rapids_jni_tpu.columnar import Column, Table
from spark_rapids_jni_tpu.types import TypeId
from spark_rapids_jni_tpu.ops.hash import partition_hash
from spark_rapids_jni_tpu.parallel.wire import BitPack, pack_bits, unpack_bits
from spark_rapids_jni_tpu.utils.tracing import func_range


class ShuffleResult(NamedTuple):
    table: Table            # D*capacity local rows, null-masked where empty
    row_valid: jnp.ndarray  # bool[D*capacity]: slot holds a real row
    overflowed: jnp.ndarray  # bool scalar: this device dropped rows
    # bool scalar: a wire-narrowed value did not survive the round trip
    # (planner declared a too-narrow wire type) — data arrived truncated
    narrowing_overflow: jnp.ndarray


class _SendPlan(NamedTuple):
    """Where every local row goes in the ``(D, capacity)`` send buffer.
    Computed ONCE per shuffle and reused by every column."""

    order: jnp.ndarray     # int32[n]: the rows by destination, non-rows last
    starts: jnp.ndarray    # int32[D]: where a destination's rows begin in it
    counts: jnp.ndarray    # int32[D]: a destination's real rows, all of them
    occupied: jnp.ndarray  # bool[D * capacity]: the slot holds a real row
    capacity: int


def _plan_send(part: jnp.ndarray, row_valid: Optional[jnp.ndarray],
               parts: int, capacity: int) -> _SendPlan:
    """The send plan of rows bound for ``part`` (int32[n] in [0, parts)).

    A stable sort by destination, with the rows that are none sent past
    every destination, leaves destination p's real rows in their input
    order at ``[starts[p], starts[p] + counts[p])``: its send slots are the
    first ``capacity`` of them, so a buffer is packed by ``parts``
    contiguous slices of the sorted rows (``_rows_by_destination``, then
    ``_pack_send``): no scatter, no search a slot, no gather by slot. Rows
    past the capacity are dropped (``counts > capacity`` says so)."""
    dest = part.astype(jnp.int32)
    if row_valid is not None:
        dest = jnp.where(row_valid, dest, jnp.int32(parts))
    order = jnp.argsort(dest, stable=True).astype(jnp.int32)
    counts = jnp.sum(
        dest[None, :] == jnp.arange(parts, dtype=jnp.int32)[:, None],
        axis=1, dtype=jnp.int32)
    slot = jnp.arange(capacity, dtype=jnp.int32)
    occupied = (slot[None, :] < jnp.minimum(counts, capacity)[:, None])
    return _SendPlan(order, jnp.cumsum(counts) - counts, counts,
                     occupied.reshape(-1), int(capacity))


def _rows_by_destination(table: Table, plan: _SendPlan) -> Table:
    """``table``'s rows in the plan's order, the rows a destination gets
    side by side. ``ops/sort.permute`` moves them: the fixed-width data and
    every validity bit as packed 32-bit words carried through two-operand
    sorts, where a gather by a permutation costs this chip 0.58 s for
    16,777,216 int64s (``PERF.md`` section 6, PR 52); a padded string's and
    a padded LIST's buffers keep their gathers. A column the shuffle does
    not take is left as it is, for the caller to refuse."""
    from spark_rapids_jni_tpu.ops.sort import permute

    def movable(c: Column) -> bool:
        return c.children is None and (
            c.is_padded_string if c.dtype.is_string
            else c.dtype.is_fixed_width or c.dtype.is_decimal128)

    moved = iter(permute([c for c in table.columns if movable(c)],
                         plan.order)[0])
    order = plan.order

    def taken(buf):
        return None if buf is None else buf[order]

    out = []
    for c in table.columns:
        if movable(c):
            out.append(next(moved))
        elif c.dtype.type_id == TypeId.LIST and c.is_padded_list:
            out.append(Column(
                c.dtype, taken(c.data), taken(c.validity),
                children=[Column(k.dtype, taken(k.data), taken(k.validity))
                          for k in c.children]))
        else:
            out.append(c)
    return Table(out)


def _pack_send(rows: jnp.ndarray, plan: _SendPlan) -> jnp.ndarray:
    """A buffer of ``_rows_by_destination`` laid out in send-buffer order:
    one slice of ``capacity`` rows a destination from where its rows begin,
    empty slots zeroed. Works for 1-D columns and 2-D row matrices (padded
    string chars)."""
    tail = (plan.capacity,) + rows.shape[1:]
    rows = jnp.concatenate([rows, jnp.zeros(tail, rows.dtype)])
    g = jnp.concatenate([
        jax.lax.dynamic_slice_in_dim(rows, plan.starts[p], plan.capacity)
        for p in range(plan.starts.shape[0])])
    keep = plan.occupied.reshape((-1,) + (1,) * (g.ndim - 1))
    return jnp.where(keep, g, jnp.zeros((), dtype=rows.dtype))


@func_range("hash_shuffle")
def hash_shuffle(
    table: Table,
    keys: Sequence[int],
    axis_name: str,
    capacity: Optional[int] = None,
    row_valid: Optional[jnp.ndarray] = None,
    wire_dtypes: Optional[Sequence] = None,
) -> ShuffleResult:
    """Exchange rows so row r lands on device ``hash(keys(r)) % D``.

    Must run inside ``shard_map`` over a mesh with ``axis_name``; ``table``
    is the caller's local batch. Returns the rows this device owns after
    the exchange (padded to ``D * capacity`` with null rows).

    ``row_valid`` marks which local rows exist at all (False = padding from
    shard_table etc.); non-rows are dropped before the exchange rather than
    shipped, and never count as overflow. Distinct from column validity — a
    real row with NULL key still shuffles (to the null-hash partition).

    On the served path (``runtime/fusion.py``, "lowering over a mesh") two
    kinds of plan node run it inside a region's ``shard_map``: a ``GroupBy``
    of sharded rows, for its partial groups (capacity the group budget, which
    cannot overflow), and a ``Join`` (``left_semi`` / ``left_anti`` /
    ``inner``) of two sharded children, once a side, for the rows themselves
    (``parallel.distributed.shuffled_join``, the default capacity: a chip's
    receive buffer has twice its rows in slots). A join's exchange that
    overflows is a refused request: ``overflowed`` reaches the result's meta
    as ``<label>.shuffle_overflowed`` and ``QueryServer`` raises
    ``CapacityOverflow`` in place of an answer.
    """
    part = partition_hash(table, list(keys), jax.lax.axis_size(axis_name))
    return shuffle_by_partition(table, part, axis_name, capacity=capacity,
                                row_valid=row_valid, wire_dtypes=wire_dtypes)


@func_range("shuffle_by_partition")
def shuffle_by_partition(
    table: Table,
    part: jnp.ndarray,
    axis_name: str,
    capacity: Optional[int] = None,
    row_valid: Optional[jnp.ndarray] = None,
    wire_dtypes: Optional[Sequence] = None,
) -> ShuffleResult:
    """Exchange rows by a caller-computed partition id (int32[n] in [0, D)).
    ``hash_shuffle`` routes by key hash; range shuffles (distributed sort)
    route by splitter bucket — same transport, different ``part``."""
    D = jax.lax.axis_size(axis_name)
    n = table.num_rows
    if capacity is None:
        # Bucket-quantize the derived capacity so nearby batch sizes trace
        # to the same (D, capacity) exchange shapes and share executables
        # (extra slots are row_valid=False padding downstream already
        # skips). Caller-specified capacities are honored exactly — they
        # are part of the caller's planned output contract.
        from spark_rapids_jni_tpu.runtime import dispatch

        capacity = dispatch.quantize_capacity(max(1, math.ceil(n / D) * 2))

    plan = _plan_send(part, row_valid, D, capacity)
    overflowed = jnp.any(plan.counts > capacity)
    size = D * capacity
    occupied = plan.occupied

    def exchange(flat: jnp.ndarray) -> jnp.ndarray:
        """(D*C, ...) send layout -> (D*C, ...) receive layout over ICI."""
        return jax.lax.all_to_all(
            flat.reshape((D, capacity) + flat.shape[1:]),
            axis_name, 0, 0, tiled=True,
        ).reshape((size,) + flat.shape[1:])

    recv_occupied = exchange(occupied)

    if wire_dtypes is not None and len(wire_dtypes) != table.num_columns:
        raise ValueError("wire_dtypes must match the column count")

    out_cols = []
    narrowing_overflow = jnp.zeros((), jnp.bool_)
    for i, col in enumerate(_rows_by_destination(table, plan).columns):
        if col.dtype.is_string:
            if not col.is_padded_string:
                raise NotImplementedError(
                    "hash_shuffle needs string columns in the padded device "
                    "layout (ops.strings.pad_strings / shard_table do this)"
                )
            if wire_dtypes is not None and wire_dtypes[i] is not None:
                raise ValueError(
                    "wire narrowing does not apply to string columns "
                    f"(column {i}); pass None for its wire dtype"
                )
            recv_len = exchange(_pack_send(col.data, plan))
            recv_mat = exchange(_pack_send(col.chars, plan))
            valid_flat = _pack_send(col.valid_mask(), plan)
            recv_valid = exchange(valid_flat) & recv_occupied
            out_cols.append(
                Column(col.dtype, recv_len, recv_valid, chars=recv_mat)
            )
            continue
        if col.dtype.type_id == TypeId.LIST:
            if not col.is_padded_list:
                raise NotImplementedError(
                    "hash_shuffle needs LIST columns in the padded wire "
                    "layout (ops.lists.pad_lists before the shuffle)")
            if wire_dtypes is not None and wire_dtypes[i] is not None:
                raise ValueError(
                    "wire narrowing does not apply to LIST columns "
                    f"(column {i}); pass None for its wire dtype")
            elem = col.children[0]
            recv_len = exchange(_pack_send(col.data, plan))
            recv_mat = exchange(_pack_send(elem.data, plan))
            recv_ev = exchange(_pack_send(elem.valid_mask(), plan))
            recv_valid = exchange(
                _pack_send(col.valid_mask(), plan)) & recv_occupied
            # unoccupied slots must read as EMPTY lists, not stale rows
            recv_len = jnp.where(recv_occupied, recv_len, 0)
            recv_ev = recv_ev & recv_occupied[:, None]
            out_cols.append(Column(
                col.dtype, recv_len, recv_valid,
                children=[Column(elem.dtype, recv_mat, recv_ev)]))
            continue
        if not (col.dtype.is_fixed_width or col.dtype.is_decimal128):
            raise NotImplementedError(
                "hash_shuffle supports fixed-width columns only (reference "
                "row_conversion.cu:515 has the same restriction)"
            )
        wire = None if wire_dtypes is None else wire_dtypes[i]
        if wire is not None and col.dtype.is_decimal128:
            raise ValueError(
                f"wire narrowing does not apply to DECIMAL128 (column {i}); "
                "pass None for its wire dtype"
            )
        if isinstance(wire, BitPack):
            # nvcomp-equivalent transport compression, stage 2: frame-of-
            # reference + bit-packing (parallel.wire). Null slots and
            # unoccupied send slots are cleaned to the reference value so
            # they always pack; out-of-range real values set
            # narrowing_overflow — detection, not silent truncation.
            if col.dtype.storage_dtype.kind not in ("i", "u"):
                raise TypeError(
                    f"BitPack wire spec needs integral storage (column {i})"
                )
            ref = jnp.asarray(wire.reference, col.data.dtype)
            clean = jnp.where(col.valid_mask(), col.data, ref)
            sent = _pack_send(clean, plan)
            sent = jnp.where(occupied, sent, ref)
            packed, ovf = pack_bits(sent.reshape(D, capacity), wire)
            narrowing_overflow = narrowing_overflow | ovf
            recv_words = jax.lax.all_to_all(packed, axis_name, 0, 0,
                                            tiled=True)
            recv = unpack_bits(
                recv_words, capacity, wire, col.data.dtype
            ).reshape(size)
        elif wire is not None:
            # Null slots hold unspecified data (Column contract) — zero them
            # so garbage payloads can't trip the narrowing check (and the
            # wire bytes become deterministic).
            clean = jnp.where(
                col.valid_mask(), col.data, jnp.zeros_like(col.data)
            )
            sent = _pack_send(clean, plan)
            # nvcomp-equivalent transport compression, stage 1: the planner
            # declares a narrower integral wire type (dates in int32,
            # quantities in int16, ...) and the exchange moves 2-4x fewer
            # bytes over ICI. A value that does not survive the down/up
            # cast sets narrowing_overflow.
            narrow = sent.astype(wire.jnp_dtype)
            widened = narrow.astype(col.data.dtype)
            # unoccupied slots hold zeros, which always survive narrowing
            narrowing_overflow = narrowing_overflow | jnp.any(widened != sent)
            recv = exchange(narrow).astype(col.data.dtype)
        else:
            recv = exchange(_pack_send(col.data, plan))
        valid_flat = _pack_send(col.valid_mask(), plan)
        recv_valid = exchange(valid_flat) & recv_occupied
        out_cols.append(Column(col.dtype, recv, recv_valid))

    return ShuffleResult(
        Table(out_cols), recv_occupied, overflowed, narrowing_overflow
    )


def classify_overflow(*, op: str = "hash_shuffle",
                      capacity: int | None = None,
                      rows: int | None = None,
                      partition: int | None = None,
                      required: int | None = None,
                      seam: str = "shuffle.transport",
                      **context):
    """Build the classified taxonomy error for a tripped shuffle/exchange
    capacity-overflow flag: a :class:`~.resilience.CapacityOverflow`
    carrying partition/capacity context, so the host boundary that syncs
    the device flag raises something ``resilience.escalate`` (and every
    classified handler above it) can act on — never a bare boolean."""
    from spark_rapids_jni_tpu.runtime import resilience

    where = "" if partition is None else f" (hot partition {partition})"
    need = "" if required is None else f"; {required} slots required"
    return resilience.CapacityOverflow(
        f"{op}: partition capacity overflow{where}: a destination "
        f"received more rows than its "
        f"{capacity if capacity is not None else 'derived'} send-buffer "
        f"slots{need}",
        seam=seam,
        **{k: v for k, v in dict(
            capacity=capacity, rows=rows, partition=partition,
            required=required, **context).items() if v is not None})


def report_shuffle_telemetry(result: ShuffleResult | None = None,
                             op: str = "hash_shuffle",
                             rows: int | None = None, *,
                             overflowed=None,
                             narrowing_overflow=None,
                             capacity: int | None = None,
                             partition: int | None = None,
                             raise_on_overflow: bool = False) -> None:
    """Host-side overflow accounting for a CONCRETE shuffle result.

    The shuffle itself runs inside shard_map/jit where telemetry calls are
    forbidden (they would be host transfers in a traced region — the tpulint
    no-host-transfer rule); callers that have the materialized result invoke
    this at the jit boundary — either a full ``ShuffleResult`` or just the
    two flag arrays for callers whose jitted step returns flags alone (the
    shuffle_wire bench).

    A tripped capacity flag is classified through the resilience taxonomy
    (:func:`classify_overflow` -> ``CapacityOverflow`` with
    partition/capacity context): recorded as a fallback event stamped with
    the classified kind, and RAISED when ``raise_on_overflow`` so callers
    without their own escalation ladder fail classified instead of
    carrying a bare boolean upward. A tripped narrowing flag classifies
    ``MalformedInputError`` (the planner declared a too-narrow wire type —
    a contract breach, not a capacity problem). Telemetry-off only mutes
    the event records; classification still raises when asked."""
    from spark_rapids_jni_tpu import telemetry
    from spark_rapids_jni_tpu.runtime import resilience

    if result is not None:
        overflowed = result.overflowed
        narrowing_overflow = result.narrowing_overflow
    ovf = overflowed is not None and bool(np.asarray(overflowed).any())
    nvf = (narrowing_overflow is not None
           and bool(np.asarray(narrowing_overflow).any()))
    if telemetry.enabled():
        if ovf:
            telemetry.record_fallback(
                op, "partition capacity overflow: a device dropped rows "
                "(re-plan with larger capacity)", rows=rows,
                error_kind="CapacityOverflow",
                **({} if capacity is None else {"capacity": capacity}))
        if nvf:
            telemetry.record_fallback(
                op, "wire narrowing overflow: a narrowed value did not "
                "survive the round trip (planner declared too-narrow wire "
                "type)", rows=rows, error_kind="MalformedInputError")
        if not (ovf or nvf):
            telemetry.record_dispatch(op, rows=rows)
    if raise_on_overflow:
        if ovf:
            raise classify_overflow(op=op, capacity=capacity, rows=rows,
                                    partition=partition)
        if nvf:
            raise resilience.MalformedInputError(
                f"{op}: wire narrowing overflow: a narrowed value did not "
                "survive the round trip (planner declared a too-narrow "
                "wire type)", seam="shuffle.transport",
                **({} if rows is None else {"rows": rows}))
