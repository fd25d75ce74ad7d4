"""Table maker ``part_q14``: the two ``part`` columns q14 reads, the type a
string column in the padded layout, the rows in a seeded permutation.

``p_partkey`` holds the values 1..rows each once (TPC-H clause 4.2.3: the
key is dense), but NOT in load order: a broadcast relation arrives in
whatever order its partitions were collected, so row ``i`` holds
``permutation(seed)[i] + 1`` and nothing about where a key lies can be
declared. ``p_type`` VARCHAR(25) is clause 4.2.3's three syllables, each
drawn uniformly (6 x 5 x 5 = 150 values, the longest ``STANDARD BURNISHED
NICKEL``, 25 bytes; ``PROMO`` is one of the six first syllables), as the
program's padded layout holds a string: lengths int32[rows] and bytes
uint8[rows, 25], zero after the length, no NULL. The random streams are
the seed's, not dbgen's.
"""

from __future__ import annotations

import functools

WIDTH = 25                 # VARCHAR(25): the padded layout's row width
SYLLABLES = (
    (b"STANDARD", b"SMALL", b"MEDIUM", b"LARGE", b"ECONOMY", b"PROMO"),
    (b"ANODIZED", b"BURNISHED", b"PLATED", b"POLISHED", b"BRUSHED"),
    (b"TIN", b"NICKEL", b"BRASS", b"STEEL", b"COPPER"))
TYPES = tuple(b" ".join((a, b, c)) for a in SYLLABLES[0]
              for b in SYLLABLES[1] for c in SYLLABLES[2])
assert len(TYPES) == 150 and max(map(len, TYPES)) == WIDTH
COLUMNS = (("p_partkey", "int64", 8), ("p_type_len", "int32", 4),
           ("p_type", "uint8", WIDTH))
# as the specification stores them: an identifier and VARCHAR(25) with the
# 4 bytes of length the padded layout adds
ROW_BYTES = sum(c[2] for c in COLUMNS)                # 37


@functools.lru_cache(maxsize=None)
def _generator(rows: int):
    import jax
    import jax.numpy as jnp
    import numpy as np

    text = np.zeros((len(TYPES), WIDTH), dtype=np.uint8)
    for i, word in enumerate(TYPES):
        text[i, :len(word)] = np.frombuffer(word, dtype=np.uint8)
    lengths = np.array([len(w) for w in TYPES], dtype=np.int32)

    def generate(seed_lo, seed_hi):
        key = jax.random.fold_in(jax.random.key(seed_lo), seed_hi)
        k_place, k_type = jax.random.split(key)
        pick = jax.random.randint(k_type, (rows,), 0, len(TYPES),
                                  dtype=jnp.int32)
        # the keys fit 32 bits far past SF100: made there and widened
        return {
            "p_partkey": (jax.random.permutation(k_place, rows).astype(
                jnp.int32) + 1).astype(jnp.int64),
            "p_type_len": jnp.asarray(lengths)[pick],
            "p_type": jnp.asarray(text)[pick]}

    return jax.jit(generate)


def make(rows: int, seed: int) -> dict:
    """{column name: device array of ``rows`` values}, from the seed.
    ``seed`` is any whole number up to a little over 2**31."""
    seed = int(seed)
    return _generator(int(rows))(seed & 0x7FFFFFFF, seed >> 31)


def host_copy(arrays: dict) -> dict:
    """{column name: numpy array} of the same values, for the reference:
    the key as the lineitem maker copies it (as 32 bits, widened on the
    host), the type's bytes and lengths as they are."""
    import jax
    import numpy as np

    from benchmark import resolve

    keys = resolve.module("tables", "lineitem").host_copy(
        {"p_partkey": arrays["p_partkey"]})
    rest = jax.device_get({n: a for n, a in arrays.items()
                           if n != "p_partkey"})
    return {**keys, **{n: np.asarray(a) for n, a in rest.items()}}


def to_table(arrays: dict):
    from spark_rapids_jni_tpu import types as t
    from spark_rapids_jni_tpu.columnar import Column, Table

    return Table([Column(t.INT64, arrays["p_partkey"]),
                  Column(t.STRING, arrays["p_type_len"],
                         chars=arrays["p_type"])])
