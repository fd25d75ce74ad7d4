"""Table maker ``orders``: the four orders columns q3 reads.

Values as ``spark_rapids_jni_tpu/models/tpch.py`` ``orders_table``:
``o_orderkey`` 1..rows in load order (dense and clustered), ``o_custkey``
uniform over 1..|customer|, which is why the maker asks for the customer
table's row count in force (``NEEDS``). Made on the device in one jitted
call from the seed.
"""

from __future__ import annotations

import functools

NEEDS = ("customer",)      # tables whose row counts ``make`` is given
COLUMNS = (("o_orderkey", "int64", 8), ("o_custkey", "int64", 8),
           ("o_orderdate", "int32", 4), ("o_shippriority", "int32", 4))
ROW_BYTES = sum(c[2] for c in COLUMNS)                # 24


@functools.lru_cache(maxsize=None)
def _generator(rows: int, customers: int):
    import jax
    import jax.numpy as jnp

    def generate(seed_lo, seed_hi):
        key = jax.random.fold_in(jax.random.key(seed_lo), seed_hi)
        k_cust, k_date, k_prio = jax.random.split(key, 3)
        return {
            "o_orderkey": jnp.arange(1, rows + 1, dtype=jnp.int32).astype(
                jnp.int64),
            "o_custkey": jax.random.randint(
                k_cust, (rows,), 1, customers + 1,
                dtype=jnp.int32).astype(jnp.int64),
            "o_orderdate": jax.random.randint(
                k_date, (rows,), 8400, 10957, dtype=jnp.int32),
            "o_shippriority": jax.random.randint(
                k_prio, (rows,), 0, 2, dtype=jnp.int32)}

    return jax.jit(generate)


def make(rows: int, seed: int, *, rows_of: dict) -> dict:
    """{column name: device array of ``rows`` values}, from the seed and
    the customer table's row count."""
    seed = int(seed)
    return _generator(int(rows), int(rows_of["customer"]))(
        seed & 0x7FFFFFFF, seed >> 31)


def host_copy(arrays: dict) -> dict:
    from benchmark import resolve

    return resolve.module("tables", "lineitem").host_copy(arrays)


def to_table(arrays: dict):
    from spark_rapids_jni_tpu import types as t
    from spark_rapids_jni_tpu.columnar import Column, Table

    return Table([Column(t.INT64, arrays["o_orderkey"]),
                  Column(t.INT64, arrays["o_custkey"]),
                  Column(t.TIMESTAMP_DAYS, arrays["o_orderdate"]),
                  Column(t.INT32, arrays["o_shippriority"])])
