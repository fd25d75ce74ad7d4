"""TPC-H q6 in plain numpy and Python integers: the reference every q6
answer is compared with.

Imports nothing of the program and takes nothing the program made: its
input is the host copy of the lineitem table the benchmark's own maker
generated from the seed.

    SELECT sum(l_extendedprice * l_discount) AS revenue
    FROM lineitem
    WHERE l_shipdate >= DATE '1994-01-01'
      AND l_shipdate < DATE '1994-01-01' + INTERVAL '1' YEAR
      AND l_discount BETWEEN 0.06 - 0.01 AND 0.06 + 0.01
      AND l_quantity < 24

with the constants of clause 2.4.6's validation run (DATE 1994-01-01,
DISCOUNT 0.06, QUANTITY 24), written out here at the columns' storage
scale and not taken from the program. ``q6(lineitem)`` is the reference:
the products summed as Python integers (unscaled decimal, scale -4), exact
whatever the rows. ``q6(lineitem, acc=np.float32)`` is the control of "How
correct is decided": the same query with the sum taken in the precision
below. It has to come out as not correct.
"""

from __future__ import annotations

import numpy as np

from benchmark import resolve

TABLE = "lineitem"            # the configuration's table this plan reads
BINDING = "lineitem"          # the plan's scan name it is bound to
DATE_LO = 8766                # 1994-01-01 in days since the epoch
DATE_HI = 9131                # 1995-01-01
DISCOUNT_LO, DISCOUNT_HI = 5, 7     # 0.05 .. 0.07 at scale -2
QUANTITY_BELOW = 2400         # 24 at scale -2
# the four columns q6 reads, of the maker's seven
READ = ("l_quantity", "l_extendedprice", "l_discount", "l_shipdate")
# the guarantees of the configuration file: the decimal sum equal to the
# reference exactly, and null exactly where no row passed
LIMITS = {"q6.sum_mismatch": 0, "q6.null_mismatch": 0}


def q6(lineitem: dict, acc=None) -> dict:
    """``{"revenue": the sum, None where no row passed}`` over the host
    copy ``{column: array}``."""
    ship, disc, qty = (lineitem[c] for c in (
        "l_shipdate", "l_discount", "l_quantity"))
    sel = ((ship >= DATE_LO) & (ship < DATE_HI)
           & (disc >= DISCOUNT_LO) & (disc <= DISCOUNT_HI)
           & (qty < QUANTITY_BELOW))
    price, disc = lineitem["l_extendedprice"][sel], disc[sel]
    if acc is None:
        # a product is under 2**27, so a block of 2**20 of them sums
        # exactly in int64; the blocks' sums are added as Python integers
        products = price.astype(np.int64) * disc.astype(np.int64)
        revenue = sum(int(products[lo:lo + (1 << 20)].sum())
                      for lo in range(0, products.size, 1 << 20))
    else:
        revenue = int((price.astype(acc) * disc.astype(acc)).sum(dtype=acc))
    return {"revenue": revenue if sel.any() else None}


oracle = q6


def control(lineitem: dict) -> dict:
    """The reference with the sum taken in float32: it has to come out as
    not correct."""
    return q6(lineitem, acc=np.float32)


def min_bytes(rows: int) -> int:
    """The least a chip must move for one answer: one pass over the four
    columns q6 reads (28 B a row)."""
    widths = {c[0]: c[4] for c in resolve.module("tables", TABLE).COLUMNS}
    return int(rows) * sum(widths[c] for c in READ)


def compare(got: dict, want: dict) -> dict:
    """The two numbers a q6 answer is held to (names as in ``LIMITS``):
    whether the sum differs, and whether one is null where the other is
    not."""
    mine, ref = got["revenue"], want["revenue"]
    return {"q6.null_mismatch": int((mine is None) != (ref is None)),
            "q6.sum_mismatch": int(mine is not None and ref is not None
                                   and mine != ref)}


def read_answer(table) -> dict:
    """A served q6 result (one row: the decimal sum, null where no row
    passed) read back to the host as ``q6`` returns it."""
    col = table.column(0)
    valid = bool(np.asarray(col.valid_mask())[0])
    return {"revenue": int(np.asarray(col.data)[0]) if valid else None}
