"""Table maker ``orders_q4``: the three orders columns q4 reads, under
dbgen's rules for them (TPC-H clause 4.2.3), the priority a string column
in the padded layout.

``o_orderkey`` is SPARSE, as dbgen's is: only the first 8 of every 32
values are used, so row ``i`` in load order holds ``(i // 8) * 32 + i % 8
+ 1`` (1..8, 33..40, ...), and nothing about it can be declared dense.
``o_orderdate`` is uniform over [1992-01-01, 1998-12-31 less 151 days],
days 8035..10440. ``o_orderpriority`` CHAR(15) is uniform over the five
values of clause 4.2.2.13 as the program's padded layout holds a string:
lengths int32[rows] and bytes uint8[rows, 15], zero after the length, no
NULL. The random streams are the seed's, not dbgen's.

An order's date is wanted twice: here, and by ``lineitem_q4``, whose commit
and receipt dates are drawn from their order's. Both take it from
``order_dates(configuration's seed, |orders|)``, one function of the
order's index. The harness hands the table at place ``i`` of the
configuration's sorted names the seed ``--seed + i``; each of the two
makers knows its place (``SEED_PLACE``: ``lineitem`` 0, ``orders`` 1) and
takes it off again.
"""

from __future__ import annotations

import functools

SEED_PLACE = 1             # "orders" sorts after "lineitem"
WIDTH = 15                 # CHAR(15): the padded layout's row width
PRIORITIES = (b"1-URGENT", b"2-HIGH", b"3-MEDIUM", b"4-NOT SPECIFIED",
              b"5-LOW")
DATE_LO, DATE_HI = 8035, 10440      # inclusive, days since the epoch
COLUMNS = (("o_orderkey", "int64", 8), ("o_orderdate", "int32", 4),
           ("o_orderpriority_len", "int32", 4),
           ("o_orderpriority", "uint8", WIDTH))
# as the specification stores them: an identifier, a date, CHAR(15) with
# the 4 bytes of length the padded layout adds
ROW_BYTES = sum(c[2] for c in COLUMNS)                # 31


def sparse_key(i):
    """dbgen's order key of load-order row ``i`` (any integer array)."""
    return (i // 8) * 32 + i % 8 + 1


def seed_words(seed: int) -> tuple:
    """A seed (up to a little over 2**31) as the two 31-bit-safe integers
    a jitted generator takes, so one executable serves every seed."""
    seed = int(seed)
    return seed & 0x7FFFFFFF, seed >> 31


def order_dates(config_lo, config_hi, orders: int):
    """int32[orders]: every order's date, from the configuration's seed
    (``seed_words`` of it, traced) and the order's index alone: both
    makers call it inside their jit."""
    import jax
    import jax.numpy as jnp

    key = jax.random.fold_in(jax.random.fold_in(
        jax.random.key(config_lo), config_hi), 4)
    return jax.random.randint(key, (int(orders),), DATE_LO, DATE_HI + 1,
                              dtype=jnp.int32)


@functools.lru_cache(maxsize=None)
def _generator(rows: int):
    import jax
    import jax.numpy as jnp
    import numpy as np

    text = np.zeros((len(PRIORITIES), WIDTH), dtype=np.uint8)
    for i, word in enumerate(PRIORITIES):
        text[i, :len(word)] = np.frombuffer(word, dtype=np.uint8)
    lengths = np.array([len(w) for w in PRIORITIES], dtype=np.int32)

    def generate(seed_lo, seed_hi, config_lo, config_hi):
        key = jax.random.fold_in(jax.random.key(seed_lo), seed_hi)
        pick = jax.random.randint(key, (rows,), 0, len(PRIORITIES),
                                  dtype=jnp.int32)
        # the keys fit 32 bits far past SF100: made there and widened
        return {
            "o_orderkey": sparse_key(
                jnp.arange(rows, dtype=jnp.int32)).astype(jnp.int64),
            "o_orderdate": order_dates(config_lo, config_hi, rows),
            "o_orderpriority_len": jnp.asarray(lengths)[pick],
            "o_orderpriority": jnp.asarray(text)[pick]}

    return jax.jit(generate)


def make(rows: int, seed: int) -> dict:
    """{column name: device array of ``rows`` values}, from the seed."""
    return _generator(int(rows))(
        *seed_words(seed), *seed_words(int(seed) - SEED_PLACE))


def host_copy(arrays: dict) -> dict:
    """{column name: numpy array} of the same values, for the reference:
    the key as the lineitem maker copies it (as 32 bits, widened on the
    host), the date, the priority's bytes and lengths as they are."""
    import jax
    import numpy as np

    from benchmark import resolve

    keys = resolve.module("tables", "lineitem").host_copy(
        {"o_orderkey": arrays["o_orderkey"]})
    rest = jax.device_get({n: a for n, a in arrays.items()
                           if n != "o_orderkey"})
    return {**keys, **{n: np.asarray(a) for n, a in rest.items()}}


def to_table(arrays: dict):
    from spark_rapids_jni_tpu import types as t
    from spark_rapids_jni_tpu.columnar import Column, Table

    return Table([Column(t.INT64, arrays["o_orderkey"]),
                  Column(t.TIMESTAMP_DAYS, arrays["o_orderdate"]),
                  Column(t.STRING, arrays["o_orderpriority_len"],
                         chars=arrays["o_orderpriority"])])
