"""Freshener ``roll``: the next request's table is the previous one with
every column rolled by the same number of rows, made on the device.

The bytes are new to the server (a new content fingerprint, so a result
cache miss, a full pad and a full execute), the multiset of rows is the
base table's, so one reference answer a run checks every request. The
stride is odd, coprime to the row count and drawn from the seed: no
offset repeats within a run. At most two tables are alive at a time, the
previous one only while the next is made.
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
        self._roll = jax.jit(
            lambda cols, k: {n: jnp.roll(a, k) for n, a in cols.items()})
        self._jax = jax

    def next(self) -> dict:
        """The next table's arrays, ready on the device; the previous
        table's are dropped."""
        rolled = self._roll(self._arrays, self.stride)
        self._jax.block_until_ready(rolled)
        self._arrays = rolled
        return rolled
