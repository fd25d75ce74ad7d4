"""Table maker ``orders_q4_mesh4``: the columns of ``tables/orders_q4.py``
from the same seed, value for value, made with their rows sharded over the
four chips of one host: chip i of ``executor_mesh(4)`` holds rows
``[i * rows / 4, (i + 1) * rows / 4)`` of every column (the priority's
bytes, ``uint8[rows, 15]``, by rows too), one Spark executor's partition of
ORDERS as a scan leaves it. The generator is ``orders_q4``'s own function,
jitted with that sharding as its output's, as ``lineitem_mesh4`` wraps
``lineitem``: no chip ever holds the whole table.
"""

from __future__ import annotations

import functools

from benchmark import resolve

_BASE = resolve.module("tables", "orders_q4")
COLUMNS, ROW_BYTES, SEED_PLACE = _BASE.COLUMNS, _BASE.ROW_BYTES, _BASE.SEED_PLACE
CHIPS = resolve.module("tables", "lineitem_mesh4").CHIPS
# read back and typed as ``orders_q4``'s: neither asks where a row lives
host_copy, to_table = _BASE.host_copy, _BASE.to_table


@functools.lru_cache(maxsize=None)
def _generator(rows: int):
    import jax

    return jax.jit(
        _BASE._generator(rows).__wrapped__,
        out_shardings=resolve.module("tables", "lineitem_mesh4").sharding())


def make(rows: int, seed: int) -> dict:
    """{column name: device array of ``rows`` values, row-sharded over the
    four chips}, from the seed alone; ``rows`` divides by four."""
    rows, seed = int(rows), int(seed)
    if rows % CHIPS:
        raise ValueError(f"{rows} rows do not split over {CHIPS} chips")
    return _generator(rows)(*_BASE.seed_words(seed),
                            *_BASE.seed_words(seed - SEED_PLACE))
