"""Table maker ``lineitem_q3``: the four lineitem columns q3 reads.

Values as ``spark_rapids_jni_tpu/models/tpch.py`` ``lineitem_q3_table``:
``l_orderkey`` uniform over 1..|orders| (the maker asks for the orders
table's row count in force, ``NEEDS``), price, discount and ship date in
the ranges of the q1 maker. Made on the device in one jitted call.
"""

from __future__ import annotations

import functools

NEEDS = ("orders",)        # tables whose row counts ``make`` is given
COLUMNS = (("l_orderkey", "int64", 8), ("l_extendedprice", "int64", 8),
           ("l_discount", "int64", 8), ("l_shipdate", "int32", 4))
ROW_BYTES = sum(c[2] for c in COLUMNS)                # 28


@functools.lru_cache(maxsize=None)
def _generator(rows: int, orders: int):
    import jax
    import jax.numpy as jnp

    def generate(seed_lo, seed_hi):
        key = jax.random.fold_in(jax.random.key(seed_lo), seed_hi)
        ranges = {"l_orderkey": (1, orders + 1),
                  "l_extendedprice": (90_000, 10_500_000),
                  "l_discount": (0, 11), "l_shipdate": (8400, 10957)}
        # every range fits 32 bits: drawn there and widened
        return {name: jax.random.randint(
                    k, (rows,), *ranges[name], dtype=jnp.int32).astype(dtype)
                for k, (name, dtype, _) in zip(
                    jax.random.split(key, len(COLUMNS)), COLUMNS)}

    return jax.jit(generate)


def make(rows: int, seed: int, *, rows_of: dict) -> dict:
    """{column name: device array of ``rows`` values}, from the seed and
    the orders table's row count."""
    seed = int(seed)
    return _generator(int(rows), int(rows_of["orders"]))(
        seed & 0x7FFFFFFF, seed >> 31)


def host_copy(arrays: dict) -> dict:
    from benchmark import resolve

    return resolve.module("tables", "lineitem").host_copy(arrays)


def to_table(arrays: dict):
    from spark_rapids_jni_tpu import types as t
    from spark_rapids_jni_tpu.columnar import Column, Table

    return Table([Column(t.INT64, arrays["l_orderkey"]),
                  Column(t.decimal64(-2), arrays["l_extendedprice"]),
                  Column(t.decimal64(-2), arrays["l_discount"]),
                  Column(t.TIMESTAMP_DAYS, arrays["l_shipdate"])])
