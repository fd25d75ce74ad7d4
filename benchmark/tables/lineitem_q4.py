"""Table maker ``lineitem_q4``: the three lineitem columns q4 reads, under
dbgen's rules for them (TPC-H clause 4.2.3).

Every order holds 1 to 7 lineitems, clustered by order in load order, so
``l_orderkey`` repeats ``orders_q4``'s sparse keys. The counts are uniform
over 1..7 from the seed; their sum then differs from the row count the
configuration states (clause 4.2.5: 6,001,215 at SF1) by a few thousand,
which the LAST orders take up: from the last order backwards each count is
raised to 7 (or lowered to 1) until the sum is the row count, so every
count stays inside 1..7 and ``|orders| <= rows <= 7 |orders|`` is all the
maker asks. ``l_commitdate`` = the order's date + [30, 90] and
``l_receiptdate`` = ``l_shipdate`` + [1, 30] with ``l_shipdate`` = the
order's date + [1, 121]; the order's date is ``orders_q4.order_dates`` of
the configuration's seed (see there: ``SEED_PLACE``), so it is the date
the orders table holds. About 63% of the rows are late (``l_commitdate <
l_receiptdate``), and about nine orders in ten hold one.
"""

from __future__ import annotations

import functools

NEEDS = ("orders",)        # tables whose row counts ``make`` is given
SEED_PLACE = 0             # "lineitem" sorts before "orders"
MOST = 7                   # lineitems an order, at most
COLUMNS = (("l_orderkey", "int64", 8), ("l_commitdate", "int32", 4),
           ("l_receiptdate", "int32", 4))
ROW_BYTES = sum(c[2] for c in COLUMNS)                # 16


def order_counts(key, orders: int, rows: int):
    """int32[orders]: lineitems an order, uniform over 1..7 but for the
    last orders, which make the sum ``rows`` (traceable)."""
    import jax
    import jax.numpy as jnp

    counts = jax.random.randint(key, (orders,), 1, MOST + 1, dtype=jnp.int32)
    short = rows - jnp.sum(counts, dtype=jnp.int32)

    def taken_up(room, want):
        # what each order takes of ``want``, the last order first
        after = jnp.cumsum(room[::-1])[::-1] - room   # the orders behind it
        return jnp.clip(want - after, 0, room)

    return (counts + taken_up(MOST - counts, jnp.maximum(short, 0))
            - taken_up(counts - 1, jnp.maximum(-short, 0)))


@functools.lru_cache(maxsize=None)
def _generator(rows: int, orders: int):
    import jax
    import jax.numpy as jnp

    from benchmark import resolve

    orders_q4 = resolve.module("tables", "orders_q4")

    def generate(seed_lo, seed_hi, config_lo, config_hi):
        key = jax.random.fold_in(jax.random.key(seed_lo), seed_hi)
        k_count, k_ship, k_commit, k_receipt = jax.random.split(key, 4)
        owner = jnp.repeat(
            jnp.arange(orders, dtype=jnp.int32),
            order_counts(k_count, orders, rows), total_repeat_length=rows)
        ordered = orders_q4.order_dates(config_lo, config_hi, orders)[owner]

        def days(k, lo, hi):
            return jax.random.randint(k, (rows,), lo, hi + 1, dtype=jnp.int32)

        return {
            "l_orderkey": orders_q4.sparse_key(owner).astype(jnp.int64),
            "l_commitdate": ordered + days(k_commit, 30, 90),
            "l_receiptdate": (ordered + days(k_ship, 1, 121)
                              + days(k_receipt, 1, 30))}

    return jax.jit(generate)


def make(rows: int, seed: int, *, rows_of: dict) -> dict:
    """{column name: device array of ``rows`` values}, from the seed and
    the orders table's row count."""
    from benchmark import resolve

    rows, orders, seed = int(rows), int(rows_of["orders"]), int(seed)
    if not orders <= rows <= MOST * orders:
        raise ValueError(
            f"lineitem_q4: {rows} rows over {orders} orders is not 1 to "
            f"{MOST} lineitems an order")
    words = resolve.module("tables", "orders_q4").seed_words
    return _generator(rows, orders)(*words(seed), *words(seed - SEED_PLACE))


def host_copy(arrays: dict) -> dict:
    from benchmark import resolve

    return resolve.module("tables", "lineitem").host_copy(arrays)


def to_table(arrays: dict):
    from spark_rapids_jni_tpu import types as t
    from spark_rapids_jni_tpu.columnar import Column, Table

    return Table([Column(t.INT64, arrays["l_orderkey"]),
                  Column(t.TIMESTAMP_DAYS, arrays["l_commitdate"]),
                  Column(t.TIMESTAMP_DAYS, arrays["l_receiptdate"])])
