"""Hash-probe kernel: the join/groupby probe loop over a bucketed build
table as a streaming comparison-count pass.

The XLA probe (ops/join.py ``_join_maps_impl``) is a pair of binary
searches over the sorted build keys::

    lo = searchsorted(sorted_key, probe, side="left")   # #(build <  p)
    hi = searchsorted(sorted_key, probe, side="right")  # #(build <= p)

Counting comparisons over the build MULTISET is the same function —
including the sentinel tail ``_sorted_valid_keys`` parks past the valid
prefix (dtype max never compares below a probe, and the downstream
``min(hi, n_valid)`` clamp is shared) — so the kernel streams the build
keys from SMEM (scalar prefetch, the Ragged Paged Attention idiom for
small per-block tables) past each 2048-row probe tile and accumulates
the two counts per probe element. Bit-identity with searchsorted holds
for every probe value by construction, not by tolerance.

The brute-force stream is O(build) per probe tile, so the tier caps the
build side (``MAX_BUILD``); larger builds fall back to the oracle with
reason ``build_too_large`` — the planner's bucketed-table sweet spot
(dimension-side joins) is exactly the small-build case.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from spark_rapids_jni_tpu.ops.pallas import block_index, register_kernel

_BLOCK = 2048      # probe rows per grid step
_SUB = 256
_SUBS = _BLOCK // _SUB
MAX_BUILD = 2048   # build keys held in SMEM per grid step (8 KiB int32)

register_kernel(
    "join.hash_probe",
    oracle="spark_rapids_jni_tpu.ops.join._join_maps_impl "
           "(tier=xla jnp.searchsorted left/right pair)",
    doc="per-probe-row match-run bounds [lo, hi) counted by streaming "
        "the SMEM-resident build keys past each probe tile",
)

# int32-representable key dtypes: the cast to the kernel's int32 lanes
# must preserve order and value (rank-encoded keys are int32 already)
_OK_KINDS = ("i",)
_OK_ITEMSIZE = 4


def unsupported_reason(build_rows: int, key_dtype) -> str | None:
    """Static (trace-time) eligibility; non-None routes to the oracle."""
    dt = jnp.dtype(key_dtype)
    if dt.kind not in _OK_KINDS or dt.itemsize > _OK_ITEMSIZE:
        return "key_width"
    if build_rows > MAX_BUILD:
        return "build_too_large"
    return None


def _probe_kernel(build_ref, probe_ref, lt_ref, le_ref):
    """One grid step: stream every build key (SMEM scalar) past the
    (SUBS, SUB) probe tile, counting strictly-less and less-or-equal
    matches per probe element. Static loop bound (the padded build
    length); sentinel-tail elements count exactly like searchsorted's."""
    p = probe_ref[0]                           # (SUBS, SUB) int32
    zero = jnp.zeros((_SUBS, _SUB), jnp.int32)

    def body(j, carry):
        lt, le = carry
        b = build_ref[j]                       # scalar from SMEM
        # bool -> int32 directly: under x64 a where() over Python ints is
        # int64, which Mosaic cannot convert
        lt = lt + (b < p).astype(jnp.int32)
        le = le + (b <= p).astype(jnp.int32)
        return lt, le

    # int32 bounds: Python ints would make the loop counter int64 under x64
    lt, le = jax.lax.fori_loop(
        jnp.int32(0), jnp.int32(build_ref.shape[0]), body, (zero, zero))
    lt_ref[0] = lt
    le_ref[0] = le


def probe_lo_hi(
    sorted_key: jnp.ndarray,
    probe_key: jnp.ndarray,
    *,
    interpret: bool,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Drop-in twin of the searchsorted left/right pair over the
    sentinel-padded sorted build keys. Returns (lo, hi) with the same
    values AND dtype searchsorted would produce."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    # searchsorted's result dtype is backend/x64 policy, not ours to
    # guess: read it off a degenerate call (dead code once traced)
    out_dt = jnp.searchsorted(sorted_key[:1], probe_key[:1]).dtype

    n = probe_key.shape[0]
    pad = (-n) % _BLOCK
    probe = probe_key.astype(jnp.int32)
    if pad:
        probe = jnp.concatenate([probe, jnp.zeros((pad,), jnp.int32)])
    nb = (n + pad) // _BLOCK
    probe3 = probe.reshape(nb, _SUBS, _SUB)
    build = sorted_key.astype(jnp.int32)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nb,),
        in_specs=[pl.BlockSpec((1, _SUBS, _SUB), block_index)],
        out_specs=[
            pl.BlockSpec((1, _SUBS, _SUB), block_index),
        ] * 2,
    )
    lt, le = pl.pallas_call(
        _probe_kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((nb, _SUBS, _SUB), jnp.int32),
        ] * 2,
        interpret=interpret,
    )(build, probe3)
    lo = lt.reshape(-1)[:n].astype(out_dt)
    hi = le.reshape(-1)[:n].astype(out_dt)
    return lo, hi
