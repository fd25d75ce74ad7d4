"""Table maker ``lineitem``: the seven lineitem columns q1 and q6 read.

Value ranges are those of ``spark_rapids_jni_tpu/models/tpch.py``
``lineitem_table`` (uniform, not dbgen: six q1 groups, not four), but the
rows are made on the device in one jitted call from the seed, so a run
pays no host generation and one copy back for the reference.
"""

from __future__ import annotations

import functools

# name, storage dtype, low, high (exclusive), bytes a row
COLUMNS = (
    ("l_quantity", "int64", 100, 5100, 8),            # DECIMAL(12,2) 1..50
    ("l_extendedprice", "int64", 90_000, 10_500_000, 8),
    ("l_discount", "int64", 0, 11, 8),                # 0.00..0.10
    ("l_tax", "int64", 0, 9, 8),                      # 0.00..0.08
    ("l_returnflag", "int8", None, None, 1),          # 'A' 'N' 'R'
    ("l_linestatus", "int8", None, None, 1),          # 'F' 'O'
    ("l_shipdate", "int32", 8400, 10957, 4),          # days since the epoch
)
ROW_BYTES = sum(c[4] for c in COLUMNS)                # 38
_FLAGS = {"l_returnflag": b"ANR", "l_linestatus": b"FO"}


@functools.lru_cache(maxsize=None)
def _generator(rows: int):
    import jax
    import jax.numpy as jnp
    import numpy as np

    def generate(seed_lo, seed_hi):
        key = jax.random.fold_in(jax.random.key(seed_lo), seed_hi)
        out = {}
        for k, (name, dtype, lo, hi, _) in zip(
                jax.random.split(key, len(COLUMNS)), COLUMNS):
            if name in _FLAGS:
                codes = jnp.asarray(np.frombuffer(_FLAGS[name], dtype=np.int8))
                pick = jax.random.randint(k, (rows,), 0, codes.shape[0],
                                          dtype=jnp.int32)
                out[name] = codes[pick]
            else:
                # every range fits 32 bits: drawn there and widened, which
                # the chip (it emulates int64) compiles and runs quicker
                out[name] = jax.random.randint(
                    k, (rows,), lo, hi, dtype=jnp.int32).astype(dtype)
        return out

    return jax.jit(generate)


def make(rows: int, seed: int) -> dict:
    """{column name: device array of ``rows`` values}, from the seed alone.
    ``seed`` is any whole number up to a little over 2**31."""
    seed = int(seed)
    return _generator(int(rows))(seed & 0x7FFFFFFF, seed >> 31)


@functools.lru_cache(maxsize=None)
def _narrow():
    import jax
    import jax.numpy as jnp

    return jax.jit(lambda arrays: {
        n: a.astype(jnp.int32) if a.dtype == jnp.int64 else a
        for n, a in arrays.items()})


def host_copy(arrays: dict) -> dict:
    """{column name: numpy array} of the same values, for the reference.
    The 64-bit columns cross as 32 bits (every range fits) and are widened
    on the host: the chip hands int64 back as two halves the host has to
    zip, at four times the cost a byte."""
    import jax
    import numpy as np

    host = jax.device_get(_narrow()(arrays))
    return {n: np.asarray(host[n]).astype(np.dtype(str(a.dtype)), copy=False)
            for n, a in arrays.items()}


def to_table(arrays: dict):
    """The program's ``Table`` over those arrays, at the generator's types
    (four ``decimal64(-2)``, two ``INT8``, one ``TIMESTAMP_DAYS``)."""
    from spark_rapids_jni_tpu import types as t
    from spark_rapids_jni_tpu.columnar import Column, Table

    dtypes = [t.decimal64(-2)] * 4 + [t.INT8, t.INT8, t.TIMESTAMP_DAYS]
    return Table([Column(d, arrays[c[0]]) for d, c in zip(dtypes, COLUMNS)])
