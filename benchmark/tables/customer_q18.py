"""Table maker ``customer_q18``: the two customer columns q18 reads, the
name a string column in the padded layout, the rows in a seeded
permutation.

``c_custkey`` holds the values 1..rows each once (TPC-H clause 4.2.3: the
key is dense), but NOT in load order: a broadcast relation arrives in
whatever order its partitions were collected, so row ``i`` holds
``permutation(seed)[i] + 1`` and nothing about where a key lies can be
declared (as ``part_q14``). ``c_name`` VARCHAR(25) is clause 4.2.3's
``Customer#`` and the key as nine zero-padded digits, 18 bytes, as the
program's padded layout holds a string: lengths int32[rows] and bytes
uint8[rows, 25], zero after the length, no NULL.
"""

from __future__ import annotations

import functools

WIDTH = 25                 # VARCHAR(25): the padded layout's row width
PREFIX = b"Customer#"
DIGITS = 9
COLUMNS = (("c_custkey", "int64", 8), ("c_name_len", "int32", 4),
           ("c_name", "uint8", WIDTH))
# as the specification stores them: an identifier and VARCHAR(25) with the
# 4 bytes of length the padded layout adds
ROW_BYTES = sum(c[2] for c in COLUMNS)                # 37


def name_of(key: int) -> bytes:
    """The name clause 4.2.3 gives customer ``key``."""
    return PREFIX + b"%0*d" % (DIGITS, int(key))


@functools.lru_cache(maxsize=None)
def _generator(rows: int):
    import jax
    import jax.numpy as jnp
    import numpy as np

    head = np.frombuffer(PREFIX, dtype=np.uint8)
    tens = np.array([10 ** (DIGITS - 1 - d) for d in range(DIGITS)],
                    dtype=np.int32)

    def generate(seed_lo, seed_hi):
        key = jax.random.fold_in(jax.random.key(seed_lo), seed_hi)
        # the keys fit 32 bits far past SF100: made there and widened
        keys = jax.random.permutation(key, rows).astype(jnp.int32) + 1
        digits = (keys[:, None] // jnp.asarray(tens)) % 10 + ord("0")
        return {
            "c_custkey": keys.astype(jnp.int64),
            "c_name_len": jnp.full((rows,), len(PREFIX) + DIGITS, jnp.int32),
            "c_name": jnp.concatenate([
                jnp.broadcast_to(jnp.asarray(head), (rows, len(PREFIX))),
                digits.astype(jnp.uint8),
                jnp.zeros((rows, WIDTH - len(PREFIX) - DIGITS), jnp.uint8)],
                axis=1)}

    return jax.jit(generate)


def make(rows: int, seed: int) -> dict:
    """{column name: device array of ``rows`` values}, from the seed.
    ``seed`` is any whole number up to a little over 2**31."""
    seed = int(seed)
    return _generator(int(rows))(seed & 0x7FFFFFFF, seed >> 31)


def host_copy(arrays: dict) -> dict:
    """{column name: numpy array} of the same values, for the reference:
    the key as the lineitem maker copies it (as 32 bits, widened on the
    host), the name's bytes and lengths as they are."""
    import jax
    import numpy as np

    from benchmark import resolve

    keys = resolve.module("tables", "lineitem").host_copy(
        {"c_custkey": arrays["c_custkey"]})
    rest = jax.device_get({n: a for n, a in arrays.items()
                           if n != "c_custkey"})
    return {**keys, **{n: np.asarray(a) for n, a in rest.items()}}


def to_table(arrays: dict):
    from spark_rapids_jni_tpu import types as t
    from spark_rapids_jni_tpu.columnar import Column, Table

    return Table([Column(t.INT64, arrays["c_custkey"]),
                  Column(t.STRING, arrays["c_name_len"],
                         chars=arrays["c_name"])])
