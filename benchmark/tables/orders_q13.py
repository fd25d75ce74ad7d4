"""Table maker ``orders_q13``: the three orders columns q13 reads, the
comment a string column in the padded layout.

``o_orderkey`` 1..rows in load order; ``o_custkey`` uniform over the keys
of 1..|customer| that are no multiple of 3 (TPC-H clause 4.2.3: a third of
the customers hold no order, which is what q13's outer join is for), which
is why the maker asks for the customer table's row count (``NEEDS``);
``o_comment`` VARCHAR(79) as the program's padded layout holds it: lengths
int32[rows], uniform in 19..78, and bytes uint8[rows, 79]: words drawn by
the seed from ``WORDS``, joined by single spaces, cut at the length, zero
bytes after it, no NULL. The text is not dbgen's grammar: ``WORDS`` holds
64 common English words of dbgen's comments, its eight most frequent nouns
and adjectives (``special``, ``requests`` and six more) drawn three times
as often as the rest, so that about one comment in sixty holds ``special``
and, after it, ``requests`` (dbgen's share is near one in a hundred).

Made on the device in one jitted call, in row blocks, with no gather: a
word's bytes and length come off the vocabulary by a one-hot product (exact
in bfloat16: every byte is under 256), the word a byte position falls into
by comparing the position with the words' ends, the byte by a second
product over the row's words.
"""

from __future__ import annotations

import functools

NEEDS = ("customer",)      # tables whose row counts ``make`` is given
WIDTH = 79                 # VARCHAR(79): the padded layout's row width
MIN_LEN, MAX_LEN = 19, 78  # clause 4.2.3: text of 19 to 78 characters
COLUMNS = (("o_orderkey", "int64", 8), ("o_custkey", "int64", 8),
           ("o_comment_len", "int32", 4), ("o_comment", "uint8", WIDTH))
ROW_BYTES = sum(c[2] for c in COLUMNS)                # 99
FREQUENT = ("special", "pending", "unusual", "express", "packages",
            "requests", "accounts", "deposits")
WORDS = FREQUENT + (
    "furiously", "carefully", "quickly", "slyly", "blithely", "regular",
    "final", "ironic", "even", "bold", "silent", "fluffy", "ruthless",
    "idle", "busy", "careful", "daring", "dogged", "enticing", "stealthy",
    "thin", "close", "permanent", "foxes", "ideas", "theodolites",
    "pinto", "beans", "instructions", "dependencies", "excuses",
    "platelets", "asymptotes", "courts", "dolphins", "multipliers",
    "sauternes", "warthogs", "frets", "dinos", "attainments", "somas",
    "braids", "hockey", "players", "frays", "warhorses", "dugouts",
    "notornis", "epitaphs", "pearls", "tithes", "waters", "orbits",
    "gifts", "sheaves")
assert len(WORDS) == len(set(WORDS)) == 64
# the slots a word is drawn from: the frequent eight three times each
SLOTS = FREQUENT * 3 + WORDS[len(FREQUENT):]
SLOT_BYTES = max(len(w) for w in WORDS) + 1       # a word and its space
# words a row draws: enough that the shortest of them fill 79 bytes
WORDS_A_ROW = -(-WIDTH // (min(len(w) for w in WORDS) + 1))
BLOCK_ROWS = 1 << 14     # rows a step: its (rows, 79, 13) product is 67 MB


@functools.lru_cache(maxsize=None)
def _generator(rows: int, customers: int):
    import jax
    import jax.numpy as jnp
    import numpy as np

    vocab = np.zeros((len(SLOTS), SLOT_BYTES + 1), dtype=np.float32)
    for i, word in enumerate(SLOTS):
        text = word.encode() + b" "
        vocab[i, :len(text)] = np.frombuffer(text, dtype=np.uint8)
        vocab[i, SLOT_BYTES] = len(text)
    blocks = -(-rows // BLOCK_ROWS)
    keys_with_orders = customers - customers // 3

    def comments(key):
        """(lengths int32[B], bytes uint8[B, 79]) of one block of rows."""
        k_len, k_word = jax.random.split(key)
        lengths = jax.random.randint(
            k_len, (BLOCK_ROWS,), MIN_LEN, MAX_LEN + 1, dtype=jnp.int32)
        ids = jax.random.randint(
            k_word, (BLOCK_ROWS, WORDS_A_ROW), 0, len(SLOTS), dtype=jnp.int32)
        drawn = jnp.einsum(
            "bks,sv->bkv",
            (ids[:, :, None] == jnp.arange(len(SLOTS))).astype(jnp.bfloat16),
            jnp.asarray(vocab, jnp.bfloat16),
            preferred_element_type=jnp.float32)
        ends = jnp.cumsum(drawn[:, :, SLOT_BYTES].astype(jnp.int32), axis=1)
        starts = ends - drawn[:, :, SLOT_BYTES].astype(jnp.int32)
        at = jnp.arange(WIDTH, dtype=jnp.int32)
        # the word a byte position lies in: the words that end at or
        # before it are the words before its own
        word_of = jnp.sum(ends[:, :, None] <= at[None, None, :], axis=1,
                          dtype=jnp.int32)                      # (B, 79)
        in_word = (word_of[:, :, None]
                   == jnp.arange(WORDS_A_ROW)).astype(jnp.bfloat16)
        offset = at[None, :] - jnp.sum(
            in_word.astype(jnp.int32) * starts[:, None, :], axis=2)
        letters = jnp.einsum(
            "bjk,bkv->bjv", in_word,
            drawn[:, :, :SLOT_BYTES].astype(jnp.bfloat16),
            preferred_element_type=jnp.float32)                 # (B, 79, 12)
        byte = jnp.sum(jnp.where(
            offset[:, :, None] == jnp.arange(SLOT_BYTES), letters, 0.0),
            axis=2)
        keep = at[None, :] < lengths[:, None]
        return lengths, jnp.where(keep, byte, 0.0).astype(jnp.uint8)

    def generate(seed_lo, seed_hi):
        key = jax.random.fold_in(jax.random.key(seed_lo), seed_hi)
        k_cust, k_text = jax.random.split(key)
        drawn = jax.random.randint(
            k_cust, (rows,), 0, keys_with_orders, dtype=jnp.int32)
        lengths, chars = jax.lax.map(
            comments, jax.random.split(k_text, blocks))
        return {
            "o_orderkey": jnp.arange(1, rows + 1, dtype=jnp.int32).astype(
                jnp.int64),
            # the r-th key that is no multiple of 3: 1 2 4 5 7 8 ...
            "o_custkey": (3 * (drawn // 2) + drawn % 2 + 1).astype(jnp.int64),
            "o_comment_len": lengths.reshape(-1)[:rows],
            "o_comment": chars.reshape(-1, WIDTH)[:rows]}

    return jax.jit(generate)


def make(rows: int, seed: int, *, rows_of: dict) -> dict:
    """{column name: device array of ``rows`` values}, from the seed and
    the customer table's row count."""
    seed = int(seed)
    return _generator(int(rows), int(rows_of["customer"]))(
        seed & 0x7FFFFFFF, seed >> 31)


def host_copy(arrays: dict) -> dict:
    """{column name: numpy array} of the same values, for the reference:
    the keys as the lineitem maker copies them (as 32 bits, widened on the
    host), the comment's bytes and lengths as they are."""
    import jax
    import numpy as np

    from benchmark import resolve

    keys = resolve.module("tables", "lineitem").host_copy(
        {n: arrays[n] for n in ("o_orderkey", "o_custkey")})
    text = jax.device_get({n: arrays[n]
                           for n in ("o_comment_len", "o_comment")})
    return {**keys, **{n: np.asarray(a) for n, a in text.items()}}


def to_table(arrays: dict):
    from spark_rapids_jni_tpu import types as t
    from spark_rapids_jni_tpu.columnar import Column, Table

    return Table([Column(t.INT64, arrays["o_orderkey"]),
                  Column(t.INT64, arrays["o_custkey"]),
                  Column(t.STRING, arrays["o_comment_len"],
                         chars=arrays["o_comment"])])
