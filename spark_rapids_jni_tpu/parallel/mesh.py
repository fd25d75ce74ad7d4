"""Device-mesh construction for the executor model.

The reference's inter-device story is Spark data parallelism: one executor
task per partition, each issuing independent device work (SURVEY.md
section 2.3, PER_THREAD_DEFAULT_STREAM at reference pom.xml:80). On TPU the
executors become positions along one mesh axis; partition exchange between
them is an XLA collective over ICI instead of UCX peer-to-peer blocks.

One axis is enough for the shuffle transport (all-to-all is a full
exchange); wider meshes (e.g. a second axis for within-executor model/row
sharding of a single giant partition) stack on top by reshaping the same
device list.
"""

from __future__ import annotations

from typing import Any, Optional, Sequence

import jax
from jax.sharding import Mesh, NamedSharding

# Axis name for the executor/data-parallel dimension of every mesh this
# package builds. Collectives in the shuffle bind to this name.
EXEC_AXIS = "exec"


def executor_mesh(
    num_executors: Optional[int] = None,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """A 1-D mesh of ``num_executors`` devices along ``EXEC_AXIS``.

    Defaults to every visible device — one executor per chip, the same
    1 task : 1 device contract Spark's plugin enforces on GPUs.
    """
    if devices is None:
        devices = jax.devices()
    if num_executors is None:
        num_executors = len(devices)
    if num_executors > len(devices):
        raise ValueError(
            f"requested {num_executors} executors but only "
            f"{len(devices)} devices are visible"
        )
    import numpy as np

    return Mesh(np.asarray(devices[:num_executors]), (EXEC_AXIS,))


def row_mesh(buf: Any) -> Optional[tuple]:
    """``(mesh, axis)`` when ``buf`` is a ``jax.Array`` whose rows (its
    leading dimension, and no other) are split over one named axis of more
    than one device of a mesh; else None. This is the one signal by which
    the runtime knows a bound table lives on several chips: the served path
    (``runtime/fusion.py``), the content fingerprint
    (``runtime/resultcache.py``) and admission (``runtime/memory.py``) all
    read it from the buffers and take no option."""
    sharding = getattr(buf, "sharding", None)
    if not isinstance(sharding, NamedSharding) or buf.ndim < 1:
        return None
    spec = tuple(sharding.spec)
    if not spec or any(s is not None for s in spec[1:]):
        return None
    axis = spec[0][0] if isinstance(spec[0], tuple) and len(spec[0]) == 1 \
        else spec[0]
    if not isinstance(axis, str) or sharding.mesh.shape[axis] < 2:
        return None
    return sharding.mesh, axis


def table_row_mesh(table: Any) -> Optional[tuple]:
    """``(mesh, axis)`` when every buffer of ``table`` is row-sharded over
    the same axis of the same mesh (``row_mesh``); else None."""
    found = None
    for col in table.columns:
        if col.children:
            return None
        for buf in (col.data, col.validity, col.chars):
            if buf is None:
                continue
            here = row_mesh(buf)
            if here is None or (found is not None and here != found):
                return None
            found = here
    return found
