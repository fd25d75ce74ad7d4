"""TPC-H q13 in plain numpy: the reference every q13 answer is compared
with.

Imports nothing of the program and takes nothing the program made: its
input is the host copy of the two tables the benchmark's own makers
generated from the seed.

    SELECT c_count, count(*) AS custdist
    FROM (SELECT c_custkey, count(o_orderkey)
          FROM customer LEFT OUTER JOIN orders
               ON c_custkey = o_custkey
              AND o_comment NOT LIKE '%special%requests%'
          GROUP BY c_custkey) AS c_orders (c_custkey, c_count)
    GROUP BY c_count
    ORDER BY custdist DESC, c_count DESC

(clause 2.4.13 with its validation parameters WORD1 = special, WORD2 =
requests). ``q13(tables)`` is the reference: the comments viewed as
fixed-width byte strings with their lengths, the pattern found by
``numpy.char.find`` called twice (``requests`` from where ``special`` ended,
neither past the comment's length), the orders counted by ``np.bincount``
over the customers' keys so that a customer with no order counts 0, the
distribution by a second ``bincount``, the order by ``np.lexsort``.
``q13(tables, words=("special",))`` is the control of "How correct is
decided": the same query with the predicate weakened to ``'%special%'``. It
has to come out as not correct, so a cheaper predicate cannot pass.

A host copy may say which comments and order keys are NULL
(``o_comment_valid``, ``o_orderkey_valid``: bool arrays; the makers' tables
hold no NULL and leave them out): a NULL comment does not join, a NULL
order key joins and is not counted.
"""

from __future__ import annotations

import numpy as np

from benchmark import resolve

WORDS = (b"special", b"requests")
# the guarantees of the configuration file: every (c_count, custdist) pair
# of the reference and no other, and the rows in the order the query asks
LIMITS = {"q13.group_mismatches": 0, "q13.out_of_order": 0}


def matches(orders: dict, words=WORDS) -> np.ndarray:
    """bool[rows]: the comment holds ``words`` one after the other, each
    whole and inside the comment's length (SQL ``LIKE '%w1%w2%'``)."""
    chars = np.ascontiguousarray(orders["o_comment"])
    text = chars.view(f"S{chars.shape[1]}")[:, 0]
    lengths = orders["o_comment_len"].astype(np.int64)
    found = np.ones(len(text), dtype=bool)
    start = np.zeros(len(text), dtype=np.int64)
    for word in words:
        at = np.char.find(text, word, start, lengths)
        found &= at >= 0
        start = np.where(found, at + len(word), 0)
    return found


def q13(tables: dict, words=WORDS) -> dict:
    """``{"groups": {c_count: custdist}, "rows": [(c_count, custdist)] in
    the query's order}`` over host copies ``{table name: {column:
    array}}``."""
    cust, orders = tables["customer"], tables["orders"]
    joins = ~matches(orders, words)
    if "o_comment_valid" in orders:
        joins &= orders["o_comment_valid"]
    counted = joins & orders.get("o_orderkey_valid", True)
    keys = cust["c_custkey"]
    top = int(keys.max()) + 1
    custkey = orders["o_custkey"][counted]
    if custkey.size and (custkey.min() < 0 or custkey.max() >= top):
        raise ValueError("an o_custkey that is no customer's key")
    c_count = np.bincount(custkey, minlength=top)[keys]
    custdist = np.bincount(c_count)
    present = np.flatnonzero(custdist)
    order = np.lexsort((-present, -custdist[present]))
    rows = [(int(present[i]), int(custdist[present[i]])) for i in order]
    return {"groups": dict(rows), "rows": rows}


oracle = q13


def control(tables: dict) -> dict:
    """The reference with the predicate weakened to ``'%special%'``: it has
    to come out as not correct."""
    return q13(tables, words=WORDS[:1])


def min_bytes(rows: dict) -> int:
    """The least a chip must move for one answer: one pass over the
    columns q13 reads of each table it binds (``{table name: rows}``)."""
    makers = {"customer": "customer_q13", "orders": "orders_q13"}
    return sum(resolve.module("tables", makers[t]).ROW_BYTES * int(n)
               for t, n in rows.items())


def compare(got: dict, want: dict) -> dict:
    """The two numbers a q13 answer is held to (names as in ``LIMITS``):
    how many ``(c_count, custdist)`` pairs are missing, extra or differ,
    and how many neighbouring rows of the answer stand in the wrong
    order."""
    mine, ref = got["groups"], want["groups"]
    rows = got["rows"]
    wrong = sum((b[1], b[0]) > (a[1], a[0])
                for a, b in zip(rows, rows[1:]))
    return {"q13.group_mismatches": len(rows) - len(mine) + sum(
                mine.get(k) != ref.get(k) for k in set(mine) | set(ref)),
            "q13.out_of_order": wrong}


def read_answer(table) -> dict:
    """A served q13 result table (``c_count``, ``custdist``; padded: a real
    group has a valid ``custdist``) read back to the host as ``q13``
    returns it, the rows in the order they were served."""
    real = np.flatnonzero(np.asarray(table.column(1).valid_mask()))
    c_count, custdist = (np.asarray(table.column(i).data)[real]
                         for i in range(2))
    rows = [(int(c), int(d)) for c, d in zip(c_count, custdist)]
    return {"groups": dict(rows), "rows": rows}
