"""Maintained Pallas kernel tier for the hot inner loops.

``ops/pallas_q1.py`` proved the headroom for q1 empirically (one fused
streaming pass, no int64 in the hot loop) but was a one-off outside the
dispatch/fusion machinery. This package promotes it to a pattern: each
kernel here is a drop-in per-op device function that an XLA call site
swaps in at TRACE time, so a Pallas kernel inherits shape bucketing, the
executable cache and donation exactly like its XLA twin (the tier
decision rides every dispatch cache key via ``kernels_digest``, so a
tier flip can never reuse a stale executable).

Contract, enforced by tpulint rule 19 (``pallas-kernel-must-have-oracle``)
and tests/test_pallas.py:

- every kernel registers here with its XLA **bit-identity oracle** twin
  declared; forcing ``kernels.tier=xla`` must reproduce the pre-tier
  path byte-for-byte at every bucket size;
- on backends without Mosaic support (CPU tier-1) kernels run in the
  Pallas interpreter or fall back to XLA with a recorded reason —
  never a silent behavior change (``record_kernel_tier``);
- unsupported shapes/dtypes/aggregates fall back to the oracle with a
  recorded reason via :func:`fall_back`.

Tier selection: ``kernels.tier`` config (``xla`` | ``pallas`` | ``auto``,
short env var SPARK_RAPIDS_TPU_KERNEL_TIER checked first) with per-op
``kernels.tier_overrides`` ("op=tier,op=tier").
"""

from __future__ import annotations

import os
from typing import NamedTuple

from spark_rapids_jni_tpu.telemetry.events import record_kernel_tier
from spark_rapids_jni_tpu.utils.config import get_option

__all__ = [
    "KernelSpec",
    "TierDecision",
    "register_kernel",
    "registered",
    "block_index",
    "decide",
    "fall_back",
    "resolved_tier",
    "kernels_digest",
]

_TIERS = ("xla", "pallas", "auto")


class KernelSpec(NamedTuple):
    """One registered kernel: the op name its call site decides under,
    the dotted path of its XLA bit-identity oracle (kept reachable by
    forcing ``kernels.tier=xla``), and a one-line description."""

    name: str
    oracle: str
    doc: str


class TierDecision(NamedTuple):
    """A trace-time tier pick for one op. ``tier`` is what actually
    traces ("pallas" | "xla"); ``mode`` is how ("native" | "interpret"
    | "oracle"); ``reason`` says why (recorded in telemetry)."""

    tier: str
    mode: str
    reason: str

    @property
    def use_pallas(self) -> bool:
        return self.tier == "pallas"

    @property
    def interpret(self) -> bool:
        return self.mode == "interpret"


_registry: dict[str, KernelSpec] = {}


def register_kernel(name: str, *, oracle: str, doc: str = "") -> KernelSpec:
    """Register a Pallas kernel with its declared XLA oracle twin.

    ``oracle`` is the dotted path of the XLA implementation that
    ``kernels.tier=xla`` routes to — non-empty by contract (tpulint
    rule 19 lints the call site; this validates at import)."""
    if not oracle or not str(oracle).strip():
        raise ValueError(
            f"register_kernel({name!r}): every pallas kernel must declare "
            f"its XLA bit-identity oracle twin"
        )
    spec = KernelSpec(str(name), str(oracle), str(doc))
    _registry[spec.name] = spec
    return spec


def registered() -> dict[str, KernelSpec]:
    """Snapshot of registered kernels (name -> spec)."""
    return dict(_registry)


def _backend() -> str:
    import jax

    try:
        return str(jax.default_backend())
    except Exception:
        return "unknown"


def resolved_tier(op: str) -> str:
    """The configured tier for ``op``: per-op override, else the global
    ``kernels.tier`` (short env var SPARK_RAPIDS_TPU_KERNEL_TIER first)."""
    raw = os.environ.get("SPARK_RAPIDS_TPU_KERNEL_TIER")
    tier = (raw or get_option("kernels.tier") or "xla").strip().lower()
    for entry in str(get_option("kernels.tier_overrides")).split(","):
        entry = entry.strip()
        if not entry:
            continue
        key, _, value = entry.partition("=")
        if key.strip() == op:
            tier = value.strip().lower()
    if tier not in _TIERS:
        raise ValueError(
            f"kernels.tier for {op!r} must be one of {_TIERS}, got {tier!r}"
        )
    return tier


def decide(op: str) -> TierDecision:
    """Pick the tier for one op at trace time and record the decision.

    ``xla`` always wins when configured (the oracle stays reachable at
    every bucket size); ``pallas`` off-TPU runs the interpreter (tier-1
    CPU testing); ``auto`` is pallas on TPU and a recorded xla fallback
    elsewhere."""
    tier = resolved_tier(op)
    if tier == "xla":
        decision = TierDecision("xla", "oracle", "config")
    elif tier == "pallas":
        if _backend() == "tpu":
            decision = TierDecision("pallas", "native", "config")
        else:
            decision = TierDecision("pallas", "interpret", "no_pallas_backend")
    else:  # auto
        if _backend() == "tpu":
            decision = TierDecision("pallas", "native", "auto")
        else:
            decision = TierDecision("xla", "oracle", "no_pallas_backend")
    record_kernel_tier(
        op, tier=decision.tier, mode=decision.mode, reason=decision.reason)
    return decision


def fall_back(op: str, reason: str) -> TierDecision:
    """A pallas-decided op cannot run this trace (unsupported dtype /
    shape / aggregate...): hand it to the XLA oracle, recorded."""
    decision = TierDecision("xla", "oracle", reason)
    record_kernel_tier(op, tier="xla", mode="oracle", reason=reason)
    return decision


def block_index(i, *_scalar_prefetch) -> tuple:
    """The index_map every kernel here uses: grid step ``i`` -> block
    ``(i, 0, 0)``. The zeros are explicit int32 — under x64 a Python int
    traces as int64, and Mosaic cannot legalize an int64 in an index map
    ("failed to legalize operation 'func.return' ... (i32, i64, i64)")."""
    import jax.numpy as jnp

    zero = jnp.int32(0)
    return (i, zero, zero)


def kernels_digest() -> tuple:
    """The tier configuration as a hashable cache-key component.

    runtime/dispatch.py folds this into every executable-cache key (and
    fusion fingerprints inherit it through dispatch), so flipping
    ``kernels.tier`` or an override can never replay an executable
    traced under the other tier."""
    raw = os.environ.get("SPARK_RAPIDS_TPU_KERNEL_TIER")
    return (
        (raw or str(get_option("kernels.tier"))).strip().lower(),
        str(get_option("kernels.tier_overrides")).strip(),
    )


# kernel modules self-register on import; q1 (which pulls in the TPC-H
# model constants) registers when ops.pallas.q1 / ops.pallas_q1 loads
from spark_rapids_jni_tpu.ops.pallas import (  # noqa: E402  (registration)
    groupby_accumulate as groupby_accumulate,
    hash_probe as hash_probe,
    row_transpose as row_transpose,
)
