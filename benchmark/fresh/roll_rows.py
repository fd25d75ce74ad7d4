"""Freshener ``roll_rows``: ``roll`` for a table with a column of more than
one dimension (a padded string's bytes, uint8[rows, width]): every array is
rolled along its rows, axis 0, by the same seeded stride, so a row's key,
its comment's length and its comment's bytes move together and stay whole.
``roll`` rolls with no axis, which for a 2-D array rolls the flattened
bytes and would shear every comment.

As there: new bytes to the server every request, the multiset of rows the
base table's, the stride odd, coprime to the rows and drawn from the seed,
at most two tables alive at a time.
"""

from __future__ import annotations

import math
import random


class Freshener:
    def __init__(self, arrays: dict, seed: int):
        import jax
        import jax.numpy as jnp

        self._arrays = arrays
        rows = int(next(iter(arrays.values())).shape[0])
        rng = random.Random(int(seed))
        lo, hi = max(1, rows // 4), max(2, rows // 2)
        stride = rng.randrange(lo, hi) | 1
        while math.gcd(stride, rows) != 1:
            stride += 2
        self.stride = stride % rows or 1
        self._roll = jax.jit(lambda cols, k: {
            n: jnp.roll(a, k, axis=0) for n, a in cols.items()})
        self._jax = jax

    def next(self) -> dict:
        """The next table's arrays, ready on the device; the previous
        table's are dropped."""
        rolled = self._roll(self._arrays, self.stride)
        self._jax.block_until_ready(rolled)
        self._arrays = rolled
        return rolled
