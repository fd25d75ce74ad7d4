"""TPC-H q1 in plain numpy: the reference every q1 answer is compared with.

Imports nothing of the program (``resolve`` is the benchmark's own) and
takes nothing the program made: its
input is the host copy of the columns the benchmark's own table maker
generated from the seed. Copied in meaning from
``spark_rapids_jni_tpu/models/tpch.py`` ``tpch_q1_numpy`` (PERF.md, Open
questions, lists the original for a later PR to delete).

``q1(cols)`` is the reference: decimal sums in int64, averages in float64.
``q1(cols, acc=np.float32)`` is the control of "How correct is decided":
the same query with every aggregate accumulated in the precision below,
the step that would tempt a later PR on a chip that emulates int64 and
float64. It has to come out as not correct.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

from benchmark import resolve

# what every q1 plan file under ``plans/`` shares
TABLE = "lineitem"            # the configuration's table a q1 plan binds
BINDING = "lineitem"          # under this name

# 1998-12-01 minus 90 days, in days since the epoch (TPC-H q1, DELTA = 90)
CUTOFF_DAYS = 10560
INT_AGGS = ("sum_qty", "sum_base_price", "sum_disc_price", "sum_charge",
            "count")
AVG_AGGS = ("avg_qty", "avg_price", "avg_disc")
# the guarantees of the configuration files: decimals and counts equal to
# the reference exactly, float64 averages to a relative 1e-9
LIMITS = {"q1.int_mismatches": 0, "q1.avg_max_rel_err": 1e-9}


def q1(cols: dict, acc=None, blocks: int = 8) -> dict:
    """{(returnflag, linestatus): {aggregate: value}} over the rows shipped
    on or before the cutoff. ``acc`` None: exact (int64 sums merged as
    Python integers, averages their float64 quotient). Otherwise every sum
    is accumulated in that dtype. The rows are taken in ``blocks`` blocks
    on a few threads (numpy releases the interpreter lock in its passes),
    which keeps the reference shorter than the warm-up it runs beside."""
    n = len(cols["l_shipdate"])
    edges = [n * i // blocks for i in range(blocks + 1)]
    with ThreadPoolExecutor(4) as pool:
        parts = list(pool.map(
            lambda i: _q1_block({k: v[edges[i]:edges[i + 1]]
                                 for k, v in cols.items()}, acc),
            range(blocks)))
    merged: dict = {}
    for part in parts:
        for key, sums in part.items():
            have = merged.get(key)
            merged[key] = sums if have is None else [
                a + b for a, b in zip(have, sums)]
    out = {}
    for key in sorted(merged):
        qty, price, disc_price, charge, disc, count = merged[key]
        # true values: unscaled decimal(scale -2) means x 10^-2
        out[key] = {"sum_qty": int(qty), "sum_base_price": int(price),
                    "sum_disc_price": int(disc_price),
                    "sum_charge": int(charge),
                    "avg_qty": float(qty / count) * 1e-2,
                    "avg_price": float(price / count) * 1e-2,
                    "avg_disc": float(disc / count) * 1e-2,
                    "count": int(count)}
    return out


oracle = q1


def control(cols: dict) -> dict:
    """The reference in the precision below (float32 sums): it has to come
    out as not correct."""
    return q1(cols, acc=np.float32)


def min_bytes(rows: int) -> int:
    """The least a chip must move for one answer: one pass over the seven
    columns q1 reads (38 bytes a row); the answer itself is six rows."""
    return resolve.module("tables", TABLE).ROW_BYTES * int(rows)


def _q1_block(cols: dict, acc) -> dict:
    """{group: [sum_qty, sum_price, sum_disc_price, sum_charge, sum_disc,
    count]} of one block of rows."""
    qty, price, disc, tax = (cols[k] for k in (
        "l_quantity", "l_extendedprice", "l_discount", "l_tax"))
    rf, ls, ship = cols["l_returnflag"], cols["l_linestatus"], cols["l_shipdate"]
    keep = ship <= CUTOFF_DAYS
    out = {}
    for f in _distinct(rf[keep]):
        for s in _distinct(ls[keep]):
            rows = np.flatnonzero(keep & (rf == f) & (ls == s))
            if rows.size == 0:
                continue
            q, p, d, t = (a[rows] if acc is None else a[rows].astype(acc)
                          for a in (qty, price, disc, tax))
            hundred = 100 if acc is None else acc(100)
            disc_price = p * (hundred - d)           # decimal scale -4
            charge = disc_price * (hundred + t)      # decimal scale -6
            sums = [a.sum(dtype=acc) for a in (q, p, disc_price, charge, d)]
            if acc is None:                          # exact from here on
                out[(f, s)] = [int(v) for v in sums] + [int(rows.size)]
            else:
                out[(f, s)] = sums + [acc(rows.size)]
    return out


def _distinct(flags: np.ndarray) -> list:
    """The distinct values of an int8 column, ascending (a count of each
    byte value: ``np.unique`` would sort 60 million rows)."""
    seen = np.flatnonzero(np.bincount(flags.view(np.uint8), minlength=256))
    return sorted(int(v) for v in seen.astype(np.uint8).view(np.int8))


def compare(got: dict, want: dict) -> dict:
    """The two numbers a q1 answer is held to (names as in ``LIMITS``):
    how many groups or integer aggregates differ from the reference, and
    the largest relative error of a float64 average."""
    wrong = len(set(got) ^ set(want))
    worst = float("inf") if wrong else 0.0
    for key in set(got) & set(want):
        for name in INT_AGGS:
            wrong += int(got[key][name]) != want[key][name]
        for name in AVG_AGGS:
            w = want[key][name]
            worst = max(worst, abs(float(got[key][name]) - w) / abs(w))
    return {"q1.int_mismatches": wrong, "q1.avg_max_rel_err": worst}


def read_answer(table) -> dict:
    """A served q1 result table (padded; real groups have both key columns
    valid) read back to the host as ``q1`` returns it."""
    cols = [np.asarray(c.data) for c in table.columns]
    valid = (np.asarray(table.column(0).valid_mask())
             & np.asarray(table.column(1).valid_mask()))
    names = ("sum_qty", "sum_base_price", "sum_disc_price", "sum_charge",
             "avg_qty", "avg_price", "avg_disc", "count")
    return {(int(cols[0][i]), int(cols[1][i])):
            {n: cols[2 + k][i].item() for k, n in enumerate(names)}
            for i in np.nonzero(valid)[0]}
