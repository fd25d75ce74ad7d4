"""TPC-H q14 in plain numpy and Python integers: the reference every q14
answer is compared with.

Imports nothing of the program and takes nothing the program made: its
input is the host copy of the two tables the benchmark's own makers
generated from the seed.

    SELECT 100.00 * sum(CASE WHEN p_type LIKE 'PROMO%'
                             THEN l_extendedprice * (1 - l_discount)
                             ELSE 0 END)
           / sum(l_extendedprice * (1 - l_discount)) AS promo_revenue
    FROM lineitem, part
    WHERE l_partkey = p_partkey
      AND l_shipdate >= date '1995-09-01'
      AND l_shipdate < date '1995-09-01' + interval '1' month

(clause 2.4.14 with its validation parameter DATE = 1995-09-01). The
served answer is the two sums, unscaled at scale -4; the ratio is the
caller's. ``q14(tables)`` is the reference: an ``argsort`` of ``part``'s
keys, the month's lineitem keys found among them by two ``searchsorted``
(left and right: a key ``part`` holds twice counts twice, an absent one
not at all, equality being what lies between the two), the first five
bytes of each part's type against ``PROMO`` under its length, and the
revenue ``l_extendedprice * (100 - l_discount)`` weighted by the matches
and by the matches that are promotional, summed in blocks as Python
integers. ``control(tables)`` is the control of "How correct is decided":
the reference over a ``part`` in which ONE row a lineitem of the month
joins has its type's first syllable flipped (to ``PROMO``, or away from
it). It has to come out as not correct, so a join that brings one wrong
string back cannot pass.

A host copy may say which values are NULL (``<column>_valid``: bool
arrays; the makers' tables hold no NULL and leave them out): a NULL key on
either side matches nothing, a NULL date fails the ``WHERE``, a NULL price
or discount makes the row's revenue NULL (in neither sum), a NULL type is
not ``LIKE`` anything (in the total alone).
"""

from __future__ import annotations

import numpy as np

from benchmark import resolve

MONTH = (9374, 9404)        # [1995-09-01, 1995-10-01) in days
PROMO = b"PROMO"
# the guarantee of the configuration file: both sums equal to the
# reference exactly
LIMITS = {"q14.sum_mismatches": 0}
_BLOCK = 1 << 20            # rows a block: its int64 sum cannot overflow


def _valid(table: dict, *columns) -> np.ndarray:
    """bool[rows]: none of ``columns`` is NULL in the row."""
    rows = len(next(iter(table.values())))
    out = np.ones(rows, dtype=bool)
    for c in columns:
        out &= table.get(c + "_valid", True)
    return out


def is_promo(part: dict) -> np.ndarray:
    """bool[rows]: the type starts with ``PROMO`` (SQL ``LIKE 'PROMO%'``:
    by its bytes, inside its length, and not NULL)."""
    chars = np.ascontiguousarray(part["p_type"][:, :len(PROMO)])
    head = chars.view(f"S{len(PROMO)}")[:, 0]
    return ((head == PROMO) & (part["p_type_len"] >= len(PROMO))
            & _valid(part, "p_type"))


def month_rows(lineitem: dict, month=MONTH) -> np.ndarray:
    """Row numbers of the lineitems the ``WHERE`` keeps that hold a key."""
    ship = lineitem["l_shipdate"]
    return np.flatnonzero((ship >= month[0]) & (ship < month[1])
                          & _valid(lineitem, "l_shipdate", "l_partkey"))


def _exact_sum(values: np.ndarray) -> int:
    return sum(int(values[lo:lo + _BLOCK].sum())
               for lo in range(0, values.size, _BLOCK))


def q14(tables: dict, month=MONTH) -> dict:
    """``{"promo_revenue": int or None, "total_revenue": int or None}``
    (unscaled, scale -4; None where no row joined) over host copies
    ``{table name: {column: array}}``."""
    items, part = tables["lineitem"], tables["part"]
    keyed = np.flatnonzero(_valid(part, "p_partkey"))
    order = keyed[np.argsort(part["p_partkey"][keyed], kind="stable")]
    keys = part["p_partkey"][order]
    # promotional parts at or before each place of the key order
    promos = np.concatenate([[0], np.cumsum(is_promo(part)[order])])
    rows = month_rows(items, month)
    wanted = items["l_partkey"][rows]
    lo = np.searchsorted(keys, wanted, side="left")
    hi = np.searchsorted(keys, wanted, side="right")
    priced = _valid(items, "l_extendedprice", "l_discount")[rows]
    revenue = np.where(priced, items["l_extendedprice"][rows].astype(
        np.int64) * (100 - items["l_discount"][rows].astype(np.int64)), 0)
    joined = int(((hi - lo) * priced).sum())
    return {"promo_revenue": _exact_sum(revenue * (promos[hi] - promos[lo]))
            if joined else None,
            "total_revenue": _exact_sum(revenue * (hi - lo))
            if joined else None}


oracle = q14


def control(tables: dict) -> dict:
    """The reference over a ``part`` with one joined row's type flipped:
    it has to come out as not correct."""
    items, part = tables["lineitem"], tables["part"]
    rows = month_rows(items)
    rows = rows[_valid(items, "l_extendedprice", "l_discount")[rows]]
    hit = np.flatnonzero(np.isin(
        part["p_partkey"], items["l_partkey"][rows])
        & _valid(part, "p_partkey", "p_type"))
    if not hit.size:
        raise ValueError("no lineitem of the month joins a part: the "
                         "control has nothing to break")
    row = int(hit[0])
    chars = np.array(part["p_type"])
    lengths = np.array(part["p_type_len"])
    word = b"LARGE" if is_promo(part)[row] else PROMO
    chars[row, :len(word)] = np.frombuffer(word, dtype=np.uint8)
    lengths[row] = max(int(lengths[row]), len(word))
    broken = dict(part, p_type=chars, p_type_len=lengths)
    return q14({"lineitem": items, "part": broken})


def min_bytes(rows: dict) -> int:
    """The least a chip must move for one answer: one pass over the
    columns q14 reads of each table it binds (``{table name: rows}``):
    28 B a lineitem row, 37 B a part row."""
    makers = {"lineitem": "lineitem_q14", "part": "part_q14"}
    return sum(resolve.module("tables", makers[t]).ROW_BYTES * int(n)
               for t, n in rows.items())


def compare(got: dict, want: dict) -> dict:
    """The one number a q14 answer is held to (as in ``LIMITS``): how many
    of the two sums differ from the reference's, a NULL beside a number
    differing too."""
    return {"q14.sum_mismatches": sum(
        got[k] != want[k] for k in ("promo_revenue", "total_revenue"))}


def read_answer(table) -> dict:
    """A served q14 result (one row: ``promo_revenue``, ``total_revenue``,
    NULL where no row joined) read back to the host as ``q14`` returns
    it."""
    out = {}
    for name, col in zip(("promo_revenue", "total_revenue"), table.columns):
        valid = bool(np.asarray(col.valid_mask())[0])
        out[name] = int(np.asarray(col.data)[0]) if valid else None
    return out
