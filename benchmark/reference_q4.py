"""TPC-H q4 in plain numpy: the reference every q4 answer is compared
with.

Imports nothing of the program and takes nothing the program made: its
input is the host copy of the two tables the benchmark's own makers
generated from the seed.

    SELECT o_orderpriority, count(*) AS order_count
    FROM orders
    WHERE o_orderdate >= date '1993-07-01'
      AND o_orderdate < date '1993-07-01' + interval '3' month
      AND EXISTS (SELECT * FROM lineitem
                  WHERE l_orderkey = o_orderkey
                    AND l_commitdate < l_receiptdate)
    GROUP BY o_orderpriority ORDER BY o_orderpriority

(clause 2.4.4 with its validation parameter DATE = 1993-07-01).
``q4(tables)`` is the reference: the keys of the late lineitems by
``np.unique``, the orders of the quarter that hold one by ``np.isin``, the
priorities viewed as fixed-width byte strings and compared with the five
literals of clause 4.2.2.13, counted by ``np.bincount``, ordered by their
bytes. ``q4(tables, once=False)`` is the control of "How correct is
decided": the ``EXISTS`` taken as an inner join, an order counted once for
each of its late lineitems. It has to come out as not correct, so a join
that forgets what ``EXISTS`` means cannot pass.

A host copy may say which values are NULL (``<column>_valid``: bool
arrays; the makers' tables hold no NULL and leave them out): a NULL key on
either side matches nothing, a NULL date fails its ``WHERE``.
"""

from __future__ import annotations

import numpy as np

from benchmark import resolve

PRIORITIES = (b"1-URGENT", b"2-HIGH", b"3-MEDIUM", b"4-NOT SPECIFIED",
              b"5-LOW")
QUARTER = (8582, 8674)      # [1993-07-01, 1993-10-01) in days
# the guarantees of the configuration file: every priority of the
# reference with its count and no other, in the order the query asks
LIMITS = {"q4.count_mismatches": 0, "q4.out_of_order": 0}


def _valid(table: dict, *columns) -> np.ndarray | bool:
    out = True
    for c in columns:
        out = out & table.get(c + "_valid", True)
    return out


def priority_codes(orders: dict) -> np.ndarray:
    """int[rows]: which of ``PRIORITIES`` a row's priority is, by its
    bytes and its length; ``len(PRIORITIES)`` for any other text."""
    chars = np.ascontiguousarray(orders["o_orderpriority"])
    text = chars.view(f"S{chars.shape[1]}")[:, 0]
    lengths = orders["o_orderpriority_len"]
    codes = np.full(len(text), len(PRIORITIES), dtype=np.int64)
    for i, word in enumerate(PRIORITIES):
        codes[(text == word) & (lengths == len(word))] = i
    return codes


def q4(tables: dict, quarter=QUARTER, once: bool = True) -> dict:
    """``{"groups": {priority bytes: order_count}, "rows": [(priority,
    order_count)] in the query's order}`` over host copies ``{table name:
    {column: array}}``."""
    orders, items = tables["orders"], tables["lineitem"]
    late = (items["l_commitdate"] < items["l_receiptdate"]) & _valid(
        items, "l_orderkey", "l_commitdate", "l_receiptdate")
    keys, lines = np.unique(items["l_orderkey"][late], return_counts=True)
    wanted = np.flatnonzero(
        (orders["o_orderdate"] >= quarter[0])
        & (orders["o_orderdate"] < quarter[1])
        & _valid(orders, "o_orderkey", "o_orderdate"))
    okey = orders["o_orderkey"][wanted]
    hit = np.isin(okey, keys)
    counted = wanted[hit]
    codes = priority_codes(orders)[counted]
    other = codes == len(PRIORITIES)
    if "o_orderpriority_valid" in orders:
        other = other | ~orders["o_orderpriority_valid"][counted]
    if other.any():
        raise ValueError("a counted order's priority is none of the five")
    weights = None if once else lines[np.searchsorted(keys, okey[hit])]
    counts = np.bincount(codes, weights=weights, minlength=len(PRIORITIES))
    rows = [(word, int(counts[i]))
            for i, word in sorted(enumerate(PRIORITIES), key=lambda p: p[1])
            if counts[i]]
    return {"groups": dict(rows), "rows": rows}


oracle = q4


def control(tables: dict) -> dict:
    """The reference with the semi join taken as an inner join: it has to
    come out as not correct."""
    return q4(tables, once=False)


def min_bytes(rows: dict) -> int:
    """The least a chip must move for one answer: one pass over the
    columns q4 reads of each table it binds (``{table name: rows}``)."""
    makers = {"orders": "orders_q4", "lineitem": "lineitem_q4"}
    return sum(resolve.module("tables", makers[t]).ROW_BYTES * int(n)
               for t, n in rows.items())


def compare(got: dict, want: dict) -> dict:
    """The two numbers a q4 answer is held to (names as in ``LIMITS``):
    how many priorities are missing, extra, twice there or with another
    count, and how many neighbouring rows of the answer stand in the wrong
    order."""
    mine, ref = got["groups"], want["groups"]
    rows = got["rows"]
    return {"q4.count_mismatches": len(rows) - len(mine) + sum(
                mine.get(k) != ref.get(k) for k in set(mine) | set(ref)),
            "q4.out_of_order": sum(b[0] < a[0]
                                   for a, b in zip(rows, rows[1:]))}


def read_answer(table) -> dict:
    """A served q4 result table (``o_orderpriority`` as a padded string,
    ``order_count``; six slots: a real group has a valid priority) read
    back to the host as ``q4`` returns it, the rows in the order they were
    served."""
    key, count = table.column(0), table.column(1)
    real = np.flatnonzero(np.asarray(key.valid_mask()))
    lengths = np.asarray(key.data)[real]
    chars = np.asarray(key.chars)[real]
    counts = np.asarray(count.data)[real]
    rows = [(bytes(c[:n]), int(k)) for c, n, k in zip(chars, lengths, counts)]
    return {"groups": dict(rows), "rows": rows}
