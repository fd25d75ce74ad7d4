"""Table maker ``lineitem_q14``: the four lineitem columns q14 reads, under
dbgen's rules for them (TPC-H clause 4.2.3).

``l_partkey`` INT64 is uniform over 1..|part| (which is why the maker asks
for the part table's row count, ``NEEDS``): the first foreign key of the
benchmark that is NOT clustered, so every look-up by it lands anywhere in
``part``. ``l_extendedprice`` and ``l_discount`` DECIMAL(15,2) are INT64
at scale -2 (the price over ``tables/lineitem.py``'s range, the discount
0.00..0.10). ``l_shipdate`` DATE is INT32 days: the order's date, uniform
over [1992-01-01, 1998-12-31 less 151 days] = 8035..10440, + [1, 121], so
8036..10561; a month of the plateau holds 30 / 2,406 of the rows, about
1.25%. No NULL, as dbgen. The random streams are the seed's, not dbgen's.
"""

from __future__ import annotations

import functools

NEEDS = ("part",)          # tables whose row counts ``make`` is given
ORDER_DATE_LO, ORDER_DATE_HI = 8035, 10440   # inclusive, days since the epoch
COLUMNS = (("l_partkey", "int64", 8), ("l_extendedprice", "int64", 8),
           ("l_discount", "int64", 8), ("l_shipdate", "int32", 4))
ROW_BYTES = sum(c[2] for c in COLUMNS)                # 28


@functools.lru_cache(maxsize=None)
def _generator(rows: int, parts: int):
    import jax
    import jax.numpy as jnp

    def generate(seed_lo, seed_hi):
        key = jax.random.fold_in(jax.random.key(seed_lo), seed_hi)
        k_part, k_price, k_disc, k_order, k_ship = jax.random.split(key, 5)

        def draw(k, lo, hi):   # inclusive; every range fits 32 bits
            return jax.random.randint(k, (rows,), lo, hi + 1, dtype=jnp.int32)

        return {
            "l_partkey": draw(k_part, 1, parts).astype(jnp.int64),
            "l_extendedprice": draw(k_price, 90_000, 10_499_999).astype(
                jnp.int64),
            "l_discount": draw(k_disc, 0, 10).astype(jnp.int64),
            "l_shipdate": (draw(k_order, ORDER_DATE_LO, ORDER_DATE_HI)
                           + draw(k_ship, 1, 121))}

    return jax.jit(generate)


def make(rows: int, seed: int, *, rows_of: dict) -> dict:
    """{column name: device array of ``rows`` values}, from the seed and
    the part table's row count."""
    seed = int(seed)
    return _generator(int(rows), int(rows_of["part"]))(
        seed & 0x7FFFFFFF, seed >> 31)


def host_copy(arrays: dict) -> dict:
    from benchmark import resolve

    return resolve.module("tables", "lineitem").host_copy(arrays)


def to_table(arrays: dict):
    from spark_rapids_jni_tpu import types as t
    from spark_rapids_jni_tpu.columnar import Column, Table

    return Table([Column(t.INT64, arrays["l_partkey"]),
                  Column(t.decimal64(-2), arrays["l_extendedprice"]),
                  Column(t.decimal64(-2), arrays["l_discount"]),
                  Column(t.TIMESTAMP_DAYS, arrays["l_shipdate"])])
