"""Table maker ``lineitem_q4_mesh4``: the columns of
``tables/lineitem_q4.py`` from the same seed and the same orders, value for
value, made with their rows sharded over the four chips of one host: chip
i of ``executor_mesh(4)`` holds rows ``[i * rows / 4, (i + 1) * rows / 4)``
of every column, one Spark executor's partition of LINEITEM as a scan
leaves it. The rows stay clustered by order, so a chip's lineitems belong
to about a quarter of the orders, and not to the quarter of ORDERS the same
chip holds once either table is rolled: the join's exchange is what brings
an order and its lineitems together. The generator is ``lineitem_q4``'s
own function, jitted with the row sharding as its output's, as
``lineitem_mesh4`` wraps ``lineitem``.
"""

from __future__ import annotations

import functools

from benchmark import resolve

_BASE = resolve.module("tables", "lineitem_q4")
COLUMNS, ROW_BYTES = _BASE.COLUMNS, _BASE.ROW_BYTES
NEEDS, SEED_PLACE = _BASE.NEEDS, _BASE.SEED_PLACE
CHIPS = resolve.module("tables", "lineitem_mesh4").CHIPS
# read back and typed as ``lineitem_q4``'s: neither asks where a row lives
host_copy, to_table = _BASE.host_copy, _BASE.to_table


@functools.lru_cache(maxsize=None)
def _generator(rows: int, orders: int):
    import jax

    return jax.jit(
        _BASE._generator(rows, orders).__wrapped__,
        out_shardings=resolve.module("tables", "lineitem_mesh4").sharding())


def make(rows: int, seed: int, *, rows_of: dict) -> dict:
    """{column name: device array of ``rows`` values, row-sharded over the
    four chips}, from the seed and the orders table's row count; ``rows``
    divides by four."""
    rows, orders, seed = int(rows), int(rows_of["orders"]), int(seed)
    if rows % CHIPS:
        raise ValueError(f"{rows} rows do not split over {CHIPS} chips")
    if not orders <= rows <= _BASE.MOST * orders:
        raise ValueError(
            f"lineitem_q4_mesh4: {rows} rows over {orders} orders is not 1 "
            f"to {_BASE.MOST} lineitems an order")
    words = resolve.module("tables", "orders_q4").seed_words
    return _generator(rows, orders)(*words(seed), *words(seed - SEED_PLACE))
