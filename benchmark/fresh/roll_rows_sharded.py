"""Freshener ``roll_rows_sharded``: ``roll_rows``'s roll (the same seeded
odd stride, every array along its rows, axis 0, on the device, outside the
clock) for a table whose rows are sharded over a mesh, keeping every array
sharded as it was: a padded string's bytes, ``uint8[rows, width]``, by rows
like the rest.

Why not ``roll_rows`` itself: what ``roll_sharded`` says of ``roll``. A
roll by a traced shift of a row-sharded array comes back replicated, the
server would see no sharded binding and one chip would do the work four
times. The stride is fixed for a run, so here it is a constant of the jit
and the output's sharding is pinned to the input's.
"""

from __future__ import annotations

from benchmark import resolve

_ROLL = resolve.module("fresh", "roll_rows")


class Freshener(_ROLL.Freshener):
    def __init__(self, arrays: dict, seed: int):
        import jax
        import jax.numpy as jnp

        super().__init__(arrays, seed)  # draws the stride as ``roll_rows``
        stride = self.stride
        rolled = jax.jit(
            lambda cols: {n: jnp.roll(a, stride, axis=0)
                          for n, a in cols.items()},
            out_shardings={n: a.sharding for n, a in arrays.items()})
        self._roll = lambda cols, _: rolled(cols)
