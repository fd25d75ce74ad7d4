"""Freshener ``roll_sharded``: ``roll``'s roll (the same seeded odd stride,
every column, on the device, outside the clock) for a table whose rows are
sharded over a mesh, keeping every column sharded as it was.

Why not ``roll`` itself: it passes the stride to its jit as a traced
argument, and the partitioner answers a roll by a traced shift of a
row-sharded column with a replicated result (checked on the CPU's virtual
devices: ``PartitionSpec()``): every chip would hold the whole table, the
server would see no sharded binding, and the cell would measure one chip
four times. The stride is fixed for a run, so here it is a constant of the
jit and the output's sharding is pinned to the input's: a chip's new rows
are a slice of its own and of one neighbour's.
"""

from __future__ import annotations

from benchmark import resolve

_ROLL = resolve.module("fresh", "roll")


class Freshener(_ROLL.Freshener):
    def __init__(self, arrays: dict, seed: int):
        import jax
        import jax.numpy as jnp

        super().__init__(arrays, seed)   # draws the stride as ``roll`` does
        stride = self.stride
        rolled = jax.jit(
            lambda cols: {n: jnp.roll(a, stride) for n, a in cols.items()},
            out_shardings={n: a.sharding for n, a in arrays.items()})
        self._roll = lambda cols, _: rolled(cols)
