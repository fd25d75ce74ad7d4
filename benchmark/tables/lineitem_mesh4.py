"""Table maker ``lineitem_mesh4``: the columns of ``tables/lineitem.py``
from the same seed, value for value, made with their rows sharded over the
four chips of one host: chip i of ``executor_mesh(4)`` holds rows
``[i * rows / 4, (i + 1) * rows / 4)`` of every column, one Spark
executor's partition. The generator is ``lineitem``'s own function, jitted
with that sharding as its output's (JAX's random bits do not depend on how
an array is partitioned), so no chip ever holds the whole table.

How a four-chip cell's files differ from a one-chip cell's: the maker
places the rows (this file), and the plan file's ``min_bytes`` is a chip's
share. Everything else is found by name as for any cell.
"""

from __future__ import annotations

import functools

from benchmark import resolve

_BASE = resolve.module("tables", "lineitem")
COLUMNS, ROW_BYTES = _BASE.COLUMNS, _BASE.ROW_BYTES
CHIPS = 4
# read back and typed as ``lineitem``'s: neither asks where a row lives
host_copy, to_table = _BASE.host_copy, _BASE.to_table


def sharding():
    """Rows over ``EXEC_AXIS`` of the first ``CHIPS`` devices."""
    from jax.sharding import NamedSharding, PartitionSpec

    from spark_rapids_jni_tpu.parallel.mesh import EXEC_AXIS, executor_mesh

    return NamedSharding(executor_mesh(CHIPS), PartitionSpec(EXEC_AXIS))


@functools.lru_cache(maxsize=None)
def _generator(rows: int):
    import jax

    return jax.jit(_BASE._generator(rows).__wrapped__,
                   out_shardings=sharding())


def make(rows: int, seed: int) -> dict:
    """{column name: device array of ``rows`` values, row-sharded over the
    four chips}, from the seed alone; ``rows`` divides by four."""
    rows, seed = int(rows), int(seed)
    if rows % CHIPS:
        raise ValueError(f"{rows} rows do not split over {CHIPS} chips")
    return _generator(rows)(seed & 0x7FFFFFFF, seed >> 31)
