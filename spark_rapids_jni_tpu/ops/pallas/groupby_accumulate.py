"""Bounded-groupby accumulate kernel: the masked per-group reduction
loop of ``groupby_aggregate_bounded`` as ONE streaming Pallas pass.

Generalizes ops/pallas/q1.py's sub-block int32-limb scheme to arbitrary
bounded domains (any ``m`` slots) and arbitrary aggregate lane sets:

- the caller (ops/groupby.py) turns each aggregate into int32 LANES —
  a row-count lane, a valid-count lane per column, 16-bit limb lanes
  for integer sums (a 64-bit value splits into four limbs, each exact:
  ``v = sum_k limb_k << 16k`` with the top limb arithmetic-shifted),
  and a sentinel-masked value lane per min/max;
- each 2048-row grid block reduces in 256-row sub-blocks so every int32
  partial provably fits (|limb| < 2^16, x256 < 2^24 << 2^31);
- the tiny (blocks*subs, m*L) partial tensor is combined OUTSIDE the
  kernel by XLA in int64 — limb recombination is exact mod 2^64, which
  is exactly the oracle's wrapping int64 sum, so integer aggregates are
  bit-identical to ``per_group`` under any row count. Float aggregates
  are never kernelized (summation-order sensitivity would break the
  bit-identity contract): the call site falls back with reason
  ``float_agg``.

Mosaic-conformance posture inherited from q1's round-5 rewrite: every
intermediate keeps (sublane, lane) structure, blocks are pre-shaped on
the XLA side to (SUBS, SUB) = (8, 256), reductions keep dims, and the
output tile assembles by broadcasted_iota where-selects — no rank
changes, no 1-D vectors, no lane concatenation.
"""

from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp

from spark_rapids_jni_tpu.ops.pallas import block_index, register_kernel

_BLOCK = 2048      # rows per grid step (16 x 128 int32 tile)
_SUB = 256         # rows per int32-safe partial (2^16 * 256 < 2^31)
_SUBS = _BLOCK // _SUB
_LIMB = 16         # limb width: |limb| < 2^16 keeps sub-block sums exact
_MAX_COLS = 2048   # cap on m*L lanes (16 KiB int32 output tile per sub)

register_kernel(
    "groupby.bounded_accumulate",
    oracle="spark_rapids_jni_tpu.ops.groupby.groupby_aggregate_bounded "
           "(tier=xla per_group masked reductions)",
    doc="per-group partial sums / counts / min / max over planner-"
        "declared bounded key domains, int32 limbs in-kernel, int64 "
        "recombination outside",
)


def unsupported_reason(
    n: int, m: int, lane_count: int
) -> str | None:
    """Static (trace-time) eligibility of one accumulate launch; a
    non-None reason routes the op to the XLA oracle, recorded."""
    if n == 0:
        return "empty_input"
    if m * lane_count > _MAX_COLS:
        return "too_many_lanes"
    return None


def limb_count(itemsize: int) -> int:
    """How many 16-bit limb lanes an integer column of ``itemsize``
    bytes needs. 1- and 2-byte values ride as a single int32 lane
    (|v| <= 2^15 keeps the 256-row partial exact without splitting)."""
    return max(1, (int(itemsize) * 8) // _LIMB)


def split_limbs(values: jnp.ndarray, itemsize: int) -> list[jnp.ndarray]:
    """Exact 16-bit limb decomposition of an integer column (XLA side).

    ``v = sum_k limbs[k] << 16k``: low limbs are masked (in [0, 2^16)),
    the top limb is arithmetic-shifted (signed), so recombination in
    wrapping int64 reproduces the oracle's int64 sum bit-for-bit."""
    k = limb_count(itemsize)
    if k == 1:
        return [values.astype(jnp.int32)]
    limbs = []
    for i in range(k - 1):
        limbs.append(
            ((values >> (_LIMB * i)) & ((1 << _LIMB) - 1)).astype(jnp.int32))
    limbs.append((values >> (_LIMB * (k - 1))).astype(jnp.int32))
    return limbs


def combine_limbs(limb_totals: Sequence[jnp.ndarray]) -> jnp.ndarray:
    """int64 recombination of per-limb totals — exact mod 2^64."""
    total = limb_totals[0].astype(jnp.int64)
    for i, t in enumerate(limb_totals[1:], start=1):
        total = total + (t.astype(jnp.int64) << (_LIMB * i))
    return total


def _round_up(x: int, mult: int) -> int:
    return -(-x // mult) * mult


def _make_kernel(m: int, lane_meta: tuple[tuple[str, int], ...], total: int):
    """Kernel closure over the static layout: one grid step turns
    (1, SUBS, SUB) gid + lane slices into a (1, SUBS, total) int32
    partial tile, column g*L+li = group g's partial of lane li."""
    lane_n = len(lane_meta)

    def kernel(gid_ref, *refs):
        out_ref = refs[-1]
        lane_refs = refs[:-1]
        gid = gid_ref[0]                       # (SUBS, SUB)
        col_ids = jax.lax.broadcasted_iota(
            jnp.int32, (_SUBS, total), 1)
        acc = jnp.zeros((_SUBS, total), jnp.int32)
        for g in range(m):
            mask = gid == g
            for li, (op, neutral) in enumerate(lane_meta):
                lane = lane_refs[li][0]        # (SUBS, SUB)
                masked = jnp.where(mask, lane, jnp.int32(neutral))
                if op == "sum":
                    p = jnp.sum(masked, axis=1, keepdims=True,
                                dtype=jnp.int32)
                elif op == "min":
                    p = jnp.min(masked, axis=1, keepdims=True)
                else:  # max
                    p = jnp.max(masked, axis=1, keepdims=True)
                # each (group, lane) column is written exactly once, so a
                # where-select needs no accumulation read-modify-write
                acc = jnp.where(col_ids == g * lane_n + li, p, acc)
        out_ref[0] = acc

    return kernel


def accumulate(
    gid: jnp.ndarray,
    lanes: Sequence[jnp.ndarray],
    lane_meta: tuple[tuple[str, int], ...],
    m: int,
    *,
    interpret: bool,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One kernel launch over all lanes.

    ``gid``: int32[n] dense group ids in [0, m]; m = "no group" (shard
    padding / domain-missed rows — matches no in-kernel mask, exactly
    like the oracle's phantom-row contract). ``lanes``: int32[n] arrays,
    one per ``lane_meta`` entry ``(op, neutral)`` with op in
    sum|min|max and a static int32 neutral (0 for sums, the oracle's
    minmax_sentinel for min/max, so empty groups reproduce the oracle's
    sentinel fill).

    Returns ``(sums, mins, maxs)``, each (m, L): int64 totals for sum
    lanes, int32 reductions for min/max lanes (read only the columns
    whose op matches).
    """
    from jax.experimental import pallas as pl

    lane_n = len(lane_meta)
    total = _round_up(max(m * lane_n, 1), 128)
    n = gid.shape[0]
    pad = (-n) % _BLOCK
    if pad:
        # padding rows join NO group (gid = m); lane fill is the lane's
        # neutral so even an unmasked bug could not bend a reduction
        gid = jnp.concatenate([gid, jnp.full((pad,), m, jnp.int32)])
        lanes = [
            jnp.concatenate(
                [lane, jnp.full((pad,), jnp.int32(neutral))])
            for lane, (_, neutral) in zip(lanes, lane_meta)
        ]
    nb = (n + pad) // _BLOCK
    # blocks pre-shaped on the XLA side to the kernel's (SUBS, SUB)
    # layout — in-kernel rank-changing reshapes are what Mosaic rejects
    gid3 = gid.reshape(nb, _SUBS, _SUB)
    lanes3 = [lane.reshape(nb, _SUBS, _SUB) for lane in lanes]
    spec = pl.BlockSpec((1, _SUBS, _SUB), block_index)
    out = pl.pallas_call(
        _make_kernel(m, tuple(lane_meta), total),
        out_shape=jax.ShapeDtypeStruct((nb, _SUBS, total), jnp.int32),
        grid=(nb,),
        in_specs=[spec] * (1 + lane_n),
        out_specs=pl.BlockSpec((1, _SUBS, total), block_index),
        interpret=interpret,
    )(gid3, *lanes3)
    # tiny combine outside the kernel: (nb*SUBS, m*L) partials -> (m, L)
    flat = out.reshape(nb * _SUBS, total)[:, : m * lane_n]
    sums = jnp.sum(flat.astype(jnp.int64), axis=0).reshape(m, lane_n)
    mins = jnp.min(flat, axis=0).reshape(m, lane_n)
    maxs = jnp.max(flat, axis=0).reshape(m, lane_n)
    return sums, mins, maxs
