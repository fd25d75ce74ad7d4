"""Table maker ``orders_q18``: the four orders columns q18 reads, under
dbgen's rules for them (TPC-H clause 4.2.3).

``o_orderkey`` is SPARSE, as dbgen's is (``orders_q4.sparse_key``: row
``i`` in load order holds ``(i // 8) * 32 + i % 8 + 1``). ``o_custkey``
is uniform over the customer keys 1..|customer| that are no multiple of
3 (a third of the customers have no order). ``o_orderdate`` is uniform
over [1992-01-01, 1998-12-31 less 151 days], days 8035..10440.
``o_totalprice`` DECIMAL(15,2) is seeded uniformly over dbgen's range,
850.00 to 560,000.00, as unscaled int64 of scale -2: dbgen derives it
from the order's lineitems' prices, tax and discount, which q18 does not
read (the configuration's ``assumed``). No NULL. The random streams are
the seed's, not dbgen's.
"""

from __future__ import annotations

import functools

NEEDS = ("customer",)      # tables whose row counts ``make`` is given
DATE_LO, DATE_HI = 8035, 10440            # inclusive, days since the epoch
PRICE_LO, PRICE_HI = 85_000, 56_000_000   # inclusive, unscaled cents
COLUMNS = (("o_orderkey", "int64", 8), ("o_custkey", "int64", 8),
           ("o_orderdate", "int32", 4), ("o_totalprice", "int64", 8))
ROW_BYTES = sum(c[2] for c in COLUMNS)                # 28


def custkey_of(pick):
    """The ``pick``-th (from 0) customer key that is no multiple of 3:
    1, 2, 4, 5, 7, 8, ... (any integer array)."""
    return pick + pick // 2 + 1


def custkeys(customers: int) -> int:
    """How many of the keys 1..``customers`` are no multiple of 3."""
    return int(customers) - int(customers) // 3


@functools.lru_cache(maxsize=None)
def _generator(rows: int, customers: int):
    import jax
    import jax.numpy as jnp

    from benchmark import resolve

    sparse_key = resolve.module("tables", "orders_q4").sparse_key

    def generate(seed_lo, seed_hi):
        key = jax.random.fold_in(jax.random.key(seed_lo), seed_hi)
        k_cust, k_date, k_price = jax.random.split(key, 3)

        def draw(k, lo, hi):
            return jax.random.randint(k, (rows,), lo, hi + 1,
                                      dtype=jnp.int32)

        # every range fits 32 bits: drawn there and widened
        return {
            "o_orderkey": sparse_key(
                jnp.arange(rows, dtype=jnp.int32)).astype(jnp.int64),
            "o_custkey": custkey_of(
                draw(k_cust, 0, custkeys(customers) - 1)).astype(jnp.int64),
            "o_orderdate": draw(k_date, DATE_LO, DATE_HI),
            "o_totalprice": draw(k_price, PRICE_LO,
                                 PRICE_HI).astype(jnp.int64)}

    return jax.jit(generate)


def make(rows: int, seed: int, *, rows_of: dict) -> dict:
    """{column name: device array of ``rows`` values}, from the seed and
    the customer table's row count."""
    from benchmark import resolve

    customers = int(rows_of["customer"])
    if custkeys(customers) < 1:
        raise ValueError(
            f"orders_q18: {customers} customers hold no key that is no "
            f"multiple of 3")
    words = resolve.module("tables", "orders_q4").seed_words
    return _generator(int(rows), customers)(*words(seed))


def host_copy(arrays: dict) -> dict:
    from benchmark import resolve

    return resolve.module("tables", "lineitem").host_copy(arrays)


def to_table(arrays: dict):
    from spark_rapids_jni_tpu import types as t
    from spark_rapids_jni_tpu.columnar import Column, Table

    return Table([Column(t.INT64, arrays["o_orderkey"]),
                  Column(t.INT64, arrays["o_custkey"]),
                  Column(t.TIMESTAMP_DAYS, arrays["o_orderdate"]),
                  Column(t.decimal64(-2), arrays["o_totalprice"])])
