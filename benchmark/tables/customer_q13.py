"""Table maker ``customer_q13``: the one customer column q13 reads.

``c_custkey`` 1..rows in load order (TPC-H clause 4.2.3: the key is dense,
and a loaded table is clustered by it), made on the device from nothing but
the row count: the seed changes nothing here, the orders drawn against
these keys change with it.
"""

from __future__ import annotations

import functools

COLUMNS = (("c_custkey", "int64", 8),)
ROW_BYTES = sum(c[2] for c in COLUMNS)                # 8


@functools.lru_cache(maxsize=None)
def _generator(rows: int):
    import jax
    import jax.numpy as jnp

    return jax.jit(lambda: {"c_custkey": jnp.arange(
        1, rows + 1, dtype=jnp.int32).astype(jnp.int64)})


def make(rows: int, seed: int) -> dict:
    """{column name: device array of ``rows`` values}."""
    return _generator(int(rows))()


def host_copy(arrays: dict) -> dict:
    """{column name: numpy array} of the same values, for the reference."""
    from benchmark import resolve

    return resolve.module("tables", "lineitem").host_copy(arrays)


def to_table(arrays: dict):
    from spark_rapids_jni_tpu import types as t
    from spark_rapids_jni_tpu.columnar import Column, Table

    return Table([Column(t.INT64, arrays["c_custkey"])])
