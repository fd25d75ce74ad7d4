"""TPC-H q18 in plain numpy and Python integers: the reference every q18
answer is compared with.

Imports nothing of the program and takes nothing the program made: its
input is the host copy of the three tables the benchmark's own makers
generated from the seed.

    SELECT c_name, c_custkey, o_orderkey, o_orderdate, o_totalprice,
           sum(l_quantity)
    FROM customer, orders, lineitem
    WHERE o_orderkey IN (SELECT l_orderkey FROM lineitem
                         GROUP BY l_orderkey HAVING sum(l_quantity) > 300)
      AND c_custkey = o_custkey AND o_orderkey = l_orderkey
    GROUP BY c_name, c_custkey, o_orderkey, o_orderdate, o_totalprice
    ORDER BY o_totalprice DESC, o_orderdate
    LIMIT 100

(clause 2.4.18 with its validation parameter QUANTITY = 300). ``q18(tables)``
is the reference: a stable ``argsort`` of the lineitem keys and
``np.add.reduceat`` over the int64 quantities for the inner sums (exact: 7
x 5,000 is far under 2**63), the keys whose sum passes the threshold,
``np.isin`` over the orders, the customers by an ``argsort`` and two
``searchsorted`` (left and right: a key customer holds twice counts twice,
an absent one not at all), the outer sum taken AGAIN from the lineitem
rows of the selected orders (``np.isin`` over the lineitems, not the inner
sums copied), the ordering by ``np.lexsort``, the first hundred.
``control(tables)`` is the control of "How correct is decided": the
reference over tables in which ONE value is wrong, the price of the
answer's first order raised by a cent (or, where no order is heavy, one
quantity raised until its order is). It has to come out as not correct.

A host copy may say which values are NULL (``<column>_valid``: bool
arrays; the makers' tables hold no NULL and leave them out): a NULL
quantity is skipped by both sums (a sum over nothing is NULL and passes no
HAVING), a NULL key on any side matches nothing, a NULL date or price is
one more value of its grouping key and sorts last.

The ORDER BY leaves rows that tie on both keys in any order, so where
such a tie straddles the hundredth place the served rows of that tie may
be any of the tied rows of the full answer; everywhere else the rows are
held to the reference's one by one.
"""

from __future__ import annotations

import collections

import numpy as np

from benchmark import resolve

QUANTITY = 300              # clause 2.4.18's validation parameter
LIMIT = 100
# the guarantees of the configuration file: every row of the reference's
# first hundred (or all, if fewer), value for value, in the ORDER BY's order
LIMITS = {"q18.row_mismatches": 0, "q18.order_breaks": 0}
COLUMNS = ("c_name", "c_custkey", "o_orderkey", "o_orderdate",
           "o_totalprice", "sum_quantity")
_NAME, _CUSTKEY, _ORDERKEY, _DATE, _PRICE, _SUM = range(6)
_LAST = float("inf")        # where a NULL sorts: after every value


def _valid(table: dict, *columns) -> np.ndarray:
    """bool[rows]: none of ``columns`` is NULL in the row."""
    rows = len(next(iter(table.values())))
    out = np.ones(rows, dtype=bool)
    for c in columns:
        out &= table.get(c + "_valid", True)
    return out


def _cell(table: dict, column: str, row: int):
    """One value as the answer holds it: a Python int, or None if NULL."""
    ok = table.get(column + "_valid")
    return None if ok is not None and not ok[row] else int(table[column][row])


def heavy_orders(lineitem: dict, quantity: int = QUANTITY) -> np.ndarray:
    """The order keys whose lineitems' quantities sum past ``quantity``
    (unscaled: times 100), ascending."""
    rows = np.flatnonzero(_valid(lineitem, "l_orderkey"))
    if not rows.size:
        return np.zeros(0, dtype=np.int64)
    order = rows[np.argsort(lineitem["l_orderkey"][rows], kind="stable")]
    keys = lineitem["l_orderkey"][order]
    starts = np.flatnonzero(np.r_[True, keys[1:] != keys[:-1]])
    counted = _valid(lineitem, "l_quantity")[order]
    sums = np.add.reduceat(np.where(
        counted, lineitem["l_quantity"][order].astype(np.int64), 0), starts)
    some = np.add.reduceat(counted.astype(np.int64), starts) > 0
    return keys[starts][some & (sums > int(quantity) * 100)].astype(np.int64)


def sort_key(row: tuple) -> tuple:
    """What the ORDER BY compares: ``o_totalprice`` descending, then
    ``o_orderdate`` ascending, NULLs last in both."""
    return (_LAST if row[_PRICE] is None else -row[_PRICE],
            _LAST if row[_DATE] is None else row[_DATE])


def q18(tables: dict, quantity: int = QUANTITY, limit: int = LIMIT) -> dict:
    """``{"rows": the first ``limit`` rows of the answer in order, each
    (c_name bytes, c_custkey, o_orderkey, o_orderdate, o_totalprice,
    sum(l_quantity)) with None for NULL, "cut_ties": every row of the FULL
    answer that ties with the last of them on both ORDER BY keys (empty
    where the answer is no longer than the limit), "answer_rows": the full
    answer's length, "heavy_orders", "joined_rows": what the IN kept and
    the joins laid out}`` over host copies ``{table name: {column:
    array}}``."""
    items, orders, cust = (tables[t] for t in
                           ("lineitem", "orders", "customer"))
    heavy = heavy_orders(items, quantity)
    picked = np.flatnonzero(np.isin(orders["o_orderkey"], heavy)
                            & _valid(orders, "o_orderkey"))
    # the customers, by key: a key two rows hold joins both
    keyed = np.flatnonzero(_valid(cust, "c_custkey"))
    by_key = keyed[np.argsort(cust["c_custkey"][keyed], kind="stable")]
    ckeys = cust["c_custkey"][by_key]
    wanted = orders["o_custkey"][picked]
    lo = np.searchsorted(ckeys, wanted, side="left")
    hi = np.where(_valid(orders, "o_custkey")[picked],
                  np.searchsorted(ckeys, wanted, side="right"), lo)
    # the lineitems of the picked orders, read again from the table
    again = np.flatnonzero(
        np.isin(items["l_orderkey"], orders["o_orderkey"][picked])
        & _valid(items, "l_orderkey"))
    of_order = collections.defaultdict(list)
    for row in again.tolist():
        of_order[int(items["l_orderkey"][row])].append(
            _cell(items, "l_quantity", row))
    groups: dict = {}
    joined = 0
    named = _valid(cust, "c_name")
    for o_row, c_lo, c_hi in zip(picked.tolist(), lo.tolist(), hi.tolist()):
        okey = int(orders["o_orderkey"][o_row])
        for c_row in by_key[c_lo:c_hi].tolist():
            name = None
            if named[c_row]:
                name = bytes(cust["c_name"][c_row][
                    :int(cust["c_name_len"][c_row])])
            key = (name, int(cust["c_custkey"][c_row]), okey,
                   _cell(orders, "o_orderdate", o_row),
                   _cell(orders, "o_totalprice", o_row))
            for q in of_order[okey]:
                joined += 1
                had = groups.get(key)
                groups[key] = had if q is None else (had or 0) + q
    rows = [key + (total,) for key, total in groups.items()]
    if rows:
        by = np.lexsort((
            [r[_ORDERKEY] for r in rows],
            [sort_key(r)[1] for r in rows], [sort_key(r)[0] for r in rows]))
        rows = [rows[i] for i in by.tolist()]
    head = rows[:int(limit)]
    ties = [r for r in rows if sort_key(r) == sort_key(head[-1])] \
        if len(rows) > len(head) else []
    return {"rows": head, "cut_ties": ties, "answer_rows": len(rows),
            "heavy_orders": int(heavy.size), "joined_rows": joined}


oracle = q18


def control(tables: dict) -> dict:
    """The reference over tables with one value wrong: it has to come out
    as not correct."""
    answer = q18(tables)
    orders, items = tables["orders"], tables["lineitem"]
    if answer["rows"]:
        at = int(np.flatnonzero(
            orders["o_orderkey"] == answer["rows"][0][_ORDERKEY])[0])
        price = np.array(orders["o_totalprice"])
        price[at] += 1
        return q18(dict(tables, orders=dict(orders, o_totalprice=price)))
    keyed = np.flatnonzero(_valid(items, "l_orderkey", "l_quantity"))
    if not keyed.size:
        raise ValueError("no lineitem holds a key and a quantity: the "
                         "control has nothing to break")
    quantity = np.array(items["l_quantity"])
    quantity[keyed[0]] = (QUANTITY + 1) * 100
    return q18(dict(tables, lineitem=dict(items, l_quantity=quantity)))


def min_bytes(rows: dict) -> int:
    """The least a chip must move for one answer: one pass over the
    columns q18 reads of each table it binds (``{table name: rows}``):
    16 B a lineitem row, 28 B an orders row, 37 B a customer row."""
    makers = {"lineitem": "lineitem_q18", "orders": "orders_q18",
              "customer": "customer_q18"}
    return sum(resolve.module("tables", makers[t]).ROW_BYTES * int(n)
               for t, n in rows.items())


def compare(got: dict, want: dict) -> dict:
    """The two numbers a q18 answer is held to (as in ``LIMITS``): rows
    that are not the reference's (a served row too many, a reference row
    missing, a row one of whose six values differs; among the rows that tie
    with the reference's last on both ORDER BY keys, where its answer is
    longer than the limit, a served row that is none of the full answer's
    tied rows, or another number of them), and neighbouring served rows
    out of the ORDER BY's order."""
    cut = sort_key(want["rows"][-1]) if want["cut_ties"] else None
    served = collections.Counter(
        r for r in got["rows"] if sort_key(r) != cut)
    asked = collections.Counter(
        r for r in want["rows"] if sort_key(r) != cut)
    wrong = sum(((served - asked) + (asked - served)).values())
    tied = collections.Counter(r for r in got["rows"] if sort_key(r) == cut)
    wrong += sum((tied - collections.Counter(want["cut_ties"])).values())
    wrong += abs(sum(tied.values())
                 - sum(sort_key(r) == cut for r in want["rows"]))
    keys = [sort_key(r) for r in got["rows"]]
    return {"q18.row_mismatches": wrong,
            "q18.order_breaks": sum(a > b for a, b in zip(keys, keys[1:]))}


def read_answer(table) -> dict:
    """A served q18 result (at most ``LIMIT`` rows of ``COLUMNS``, the
    name in the padded layout; a row whose ``o_orderkey`` reads NULL is
    one the joins did not fill, and no row of the answer) read back to
    the host as ``q18`` returns its rows."""
    name = table.columns[_NAME]
    lengths, chars = np.asarray(name.data), np.asarray(name.chars)
    valid = [np.asarray(c.valid_mask()) for c in table.columns]
    data = [np.asarray(c.data) for c in table.columns]
    rows = []
    for i in np.flatnonzero(valid[_ORDERKEY]).tolist():
        rows.append((
            bytes(chars[i][:int(lengths[i])]) if valid[_NAME][i] else None,
            *(int(data[c][i]) if valid[c][i] else None
              for c in range(1, len(COLUMNS)))))
    return {"rows": rows}
