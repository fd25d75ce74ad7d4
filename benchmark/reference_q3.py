"""TPC-H q3 in plain numpy: the reference every q3 answer is compared with.

Imports nothing of the program (``resolve`` is the benchmark's own) and
takes nothing the program made: its input is the host copy of the three
tables the benchmark's own makers generated from the seed.

    SELECT l_orderkey, o_orderdate, o_shippriority,
           sum(l_extendedprice * (1 - l_discount)) AS revenue
    FROM customer, orders, lineitem
    WHERE c_mktsegment = SEGMENT AND c_custkey = o_custkey
      AND l_orderkey = o_orderkey
      AND o_orderdate < DATE AND l_shipdate > DATE
    GROUP BY l_orderkey, o_orderdate, o_shippriority
    ORDER BY revenue DESC, o_orderdate

with every group, not the first ten: the served plan leaves the LIMIT to
its caller. ``q3(tables)`` is the reference: revenue summed in int64
(unscaled decimal, scale -4). ``q3(tables, acc=np.float32)`` is the control
of "How correct is decided": the same query with the revenue accumulated
in the precision below. It has to come out as not correct.
"""

from __future__ import annotations

import numpy as np

from benchmark import resolve

SEGMENT = 0                   # c_mktsegment = 'BUILDING', the first of five
CUTOFF_DAYS = 9204            # 1995-03-15 in days since the epoch
# the guarantees of the configuration file: every group with its date, its
# priority and its decimal revenue equal to the reference exactly, and the
# rows in the order the query asks for
LIMITS = {"q3.mismatches": 0, "q3.out_of_order": 0}


def q3(tables: dict, acc=None) -> dict:
    """``{"groups": {orderkey: (revenue, orderdate, shippriority)},
    "out_of_order": 0}`` over host copies ``{table name: {column: array}}``.
    Keys are looked up, not assumed dense."""
    cust, orders, li = (tables[k] for k in ("customer", "orders", "lineitem"))
    in_segment = np.zeros(int(cust["c_custkey"].max()) + 1, dtype=bool)
    in_segment[cust["c_custkey"][cust["c_mktsegment"] == SEGMENT]] = True
    wanted = (orders["o_orderdate"] < CUTOFF_DAYS) & in_segment[
        orders["o_custkey"]]
    row_of = np.full(int(orders["o_orderkey"].max()) + 1, -1, dtype=np.int64)
    row_of[orders["o_orderkey"]] = np.arange(len(wanted))

    shipped = np.flatnonzero(li["l_shipdate"] > CUTOFF_DAYS)
    order = row_of[li["l_orderkey"][shipped]]
    joined = (order >= 0) & wanted[order]
    shipped, order = shipped[joined], order[joined]
    revenue = li["l_extendedprice"][shipped] * (
        100 - li["l_discount"][shipped])             # int64, scale -4
    by_key = np.argsort(li["l_orderkey"][shipped], kind="stable")
    order, revenue = order[by_key], revenue[by_key]
    starts = np.flatnonzero(np.r_[True, order[1:] != order[:-1]])
    if order.size == 0:
        return {"groups": {}, "out_of_order": 0}
    sums = np.add.reduceat(
        revenue if acc is None else revenue.astype(acc), starts)
    first = order[starts]
    return {"groups": {
        int(k): (r.item(), int(d), int(p)) for k, r, d, p in zip(
            orders["o_orderkey"][first], sums,
            orders["o_orderdate"][first], orders["o_shippriority"][first])},
        "out_of_order": 0}


oracle = q3


def control(tables: dict) -> dict:
    """The reference with float32 revenue sums: it has to come out as not
    correct."""
    return q3(tables, acc=np.float32)


def min_bytes(rows: dict) -> int:
    """The least a chip must move for one answer: one pass over the
    columns q3 reads of each table it binds (``{table name: rows}``)."""
    makers = {"customer": "customer", "orders": "orders",
              "lineitem": "lineitem_q3"}
    return sum(resolve.module("tables", makers[t]).ROW_BYTES * int(n)
               for t, n in rows.items())


def compare(got: dict, want: dict) -> dict:
    """The two numbers a q3 answer is held to (names as in ``LIMITS``):
    how many groups are missing, extra or differ in revenue, date or
    priority, and how many neighbouring rows of the served result stand in
    the wrong order."""
    mine, ref = got["groups"], want["groups"]
    return {"q3.mismatches": got.get("repeated", 0) + sum(
                mine.get(k) != ref.get(k) for k in set(mine) | set(ref)),
            "q3.out_of_order": got["out_of_order"]}


def read_answer(table) -> dict:
    """A served q3 result table (padded; a real group has a valid
    orderkey) read back to the host as ``q3`` returns it, with the number
    of neighbouring real rows that break ``revenue DESC, o_orderdate`` and
    the number of rows that repeat a group's key."""
    real = np.flatnonzero(np.asarray(table.column(0).valid_mask()))
    # nulls sort last, so the real rows lead: only they cross to the host
    # (88,511 of 8,388,608 padded rows at SF1)
    lead = int(real[-1]) + 1 if real.size else 0
    key, date, prio, rev = (np.asarray(table.column(i).data[:lead])[real]
                            for i in range(4))
    wrong = (rev[:-1] < rev[1:]) | ((rev[:-1] == rev[1:])
                                    & (date[:-1] > date[1:]))
    return {"groups": {int(k): (int(r), int(d), int(p))
                       for k, r, d, p in zip(key, rev, date, prio)},
            "out_of_order": int(wrong.sum()),
            "repeated": len(key) - len(np.unique(key))}
