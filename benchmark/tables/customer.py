"""Table maker ``customer``: the two customer columns q3 reads.

Values as ``spark_rapids_jni_tpu/models/tpch.py`` ``customer_table``
(``c_custkey`` 1..rows in load order, so the key is dense and clustered;
the market segment uniform over five), made on the device in one jitted
call from the seed.
"""

from __future__ import annotations

import functools

COLUMNS = (("c_custkey", "int64", 8), ("c_mktsegment", "int8", 1))
ROW_BYTES = sum(c[2] for c in COLUMNS)                # 9
N_SEGMENTS = 5


@functools.lru_cache(maxsize=None)
def _generator(rows: int):
    import jax
    import jax.numpy as jnp

    def generate(seed_lo, seed_hi):
        key = jax.random.fold_in(jax.random.key(seed_lo), seed_hi)
        return {
            "c_custkey": jnp.arange(1, rows + 1, dtype=jnp.int32).astype(
                jnp.int64),
            "c_mktsegment": jax.random.randint(
                key, (rows,), 0, N_SEGMENTS, dtype=jnp.int32).astype(jnp.int8)}

    return jax.jit(generate)


def make(rows: int, seed: int) -> dict:
    """{column name: device array of ``rows`` values}, from the seed alone.
    ``seed`` is any whole number up to a little over 2**31."""
    seed = int(seed)
    return _generator(int(rows))(seed & 0x7FFFFFFF, seed >> 31)


def host_copy(arrays: dict) -> dict:
    """{column name: numpy array} of the same values, for the reference."""
    from benchmark import resolve

    return resolve.module("tables", "lineitem").host_copy(arrays)


def to_table(arrays: dict):
    from spark_rapids_jni_tpu import types as t
    from spark_rapids_jni_tpu.columnar import Column, Table

    return Table([Column(t.INT64, arrays["c_custkey"]),
                  Column(t.INT8, arrays["c_mktsegment"])])
