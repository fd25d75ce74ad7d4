"""Platform selection helper.

``JAX_PLATFORMS=cpu`` in the environment pins a process to the CPU, and the
tier-1 test command uses it. ``force_cpu_platform`` is the same pin made
from code, for a process that must stay off the accelerator whatever its
environment says: tests/conftest.py (with a virtual device pool),
__graft_entry__.dryrun_multichip, and a fleet or cluster supervisor on a
machine whose chips belong to its workers (runtime/fleet.py: one process
per chip).
"""

from __future__ import annotations

import os


def force_cpu_platform(n_virtual_devices: int | None = None) -> None:
    """Pin this process to the CPU backend, optionally with a virtual pool.

    Must be called before jax initializes any backend (first
    ``jax.devices()`` / ``device_put`` / ``jit``); the pin is process-wide
    and sticky (backend init is one-shot in jax), so callers that need the
    chip afterwards must use a fresh process.
    """
    if n_virtual_devices is not None:
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + f" --xla_force_host_platform_device_count={n_virtual_devices}"
            ).strip()

    import jax

    jax.config.update("jax_platforms", "cpu")
