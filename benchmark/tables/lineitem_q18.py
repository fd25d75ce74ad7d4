"""Table maker ``lineitem_q18``: the two lineitem columns q18 reads, under
dbgen's rules for them (TPC-H clause 4.2.3).

Every order holds 1 to 7 lineitems, clustered by order in load order, so
``l_orderkey`` repeats the orders' sparse keys (``orders_q4.sparse_key``:
row ``i`` of orders holds ``(i // 8) * 32 + i % 8 + 1``); the counts are
``lineitem_q4.order_counts``: uniform over 1..7 from the seed, the LAST
orders' counts raised to 7 (or lowered to 1) until the sum is the row
count the configuration states (clause 4.2.5). ``l_quantity`` DECIMAL(15,2)
is the integers 1..50, uniform, as unscaled int64 of scale -2 (100 to
5,000). No NULL, as dbgen. An order's quantities sum past 300 only where
it holds seven lineitems (one order in seven) and they average over 42.9:
0.0297% of those and the last orders the adjustment raised to seven,
60 to 70 orders at SF1 and 673 at SF10 (dbgen's Q18 answer holds 57 at
SF1). The random streams are the seed's, not dbgen's.
"""

from __future__ import annotations

import functools

NEEDS = ("orders",)        # tables whose row counts ``make`` is given
QUANTITY_LO, QUANTITY_HI = 1, 50
COLUMNS = (("l_orderkey", "int64", 8), ("l_quantity", "int64", 8))
ROW_BYTES = sum(c[2] for c in COLUMNS)                # 16


@functools.lru_cache(maxsize=None)
def _generator(rows: int, orders: int):
    import jax
    import jax.numpy as jnp

    from benchmark import resolve

    sparse_key = resolve.module("tables", "orders_q4").sparse_key
    q4 = resolve.module("tables", "lineitem_q4")

    def generate(seed_lo, seed_hi):
        key = jax.random.fold_in(jax.random.key(seed_lo), seed_hi)
        k_count, k_quantity = jax.random.split(key)
        owner = jnp.repeat(
            jnp.arange(orders, dtype=jnp.int32),
            q4.order_counts(k_count, orders, rows), total_repeat_length=rows)
        # every range fits 32 bits: drawn there and widened
        return {
            "l_orderkey": sparse_key(owner).astype(jnp.int64),
            "l_quantity": (jax.random.randint(
                k_quantity, (rows,), QUANTITY_LO, QUANTITY_HI + 1,
                dtype=jnp.int32) * 100).astype(jnp.int64)}

    return jax.jit(generate)


def make(rows: int, seed: int, *, rows_of: dict) -> dict:
    """{column name: device array of ``rows`` values}, from the seed and
    the orders table's row count."""
    from benchmark import resolve

    rows, orders = int(rows), int(rows_of["orders"])
    q4 = resolve.module("tables", "lineitem_q4")
    if not orders <= rows <= q4.MOST * orders:
        raise ValueError(
            f"lineitem_q18: {rows} rows over {orders} orders is not 1 to "
            f"{q4.MOST} lineitems an order")
    words = resolve.module("tables", "orders_q4").seed_words
    return _generator(rows, orders)(*words(seed))


def host_copy(arrays: dict) -> dict:
    from benchmark import resolve

    return resolve.module("tables", "lineitem").host_copy(arrays)


def to_table(arrays: dict):
    from spark_rapids_jni_tpu import types as t
    from spark_rapids_jni_tpu.columnar import Column, Table

    return Table([Column(t.INT64, arrays["l_orderkey"]),
                  Column(t.decimal64(-2), arrays["l_quantity"])])
