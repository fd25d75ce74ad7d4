"""The bounded groupby, the join's maps and the row image at the edges of
``dispatch.call``'s row buckets (1, 2^k-1, 2^k, 2^k+1 rows, null tails
included), each against an answer computed in numpy.

Every case goes through ``dispatch.call``: the rows are padded to their
bucket and the padding rides the ops' ``row_valid`` contract, so a row
count on, under and over a bucket boundary is where a phantom row would
show. The last case of each test is a shape somebody once thought
special: an extremum over a 64-bit lane, a build side of 2,049 rows,
64-bit join keys, a row of 264 data bytes.
"""

import numpy as np
import pytest

from spark_rapids_jni_tpu import types as t
from spark_rapids_jni_tpu.columnar import Column, Table
from spark_rapids_jni_tpu.ops.join import join
from spark_rapids_jni_tpu.ops.planner import scalar_domain
from spark_rapids_jni_tpu.ops.row_conversion import (
    convert_from_rows,
    convert_to_rows,
)
from spark_rapids_jni_tpu.runtime import fusion

EDGE_ROWS = [1, 255, 256, 257, 2047, 2048, 2049]


# ---------------------------------------------------------------------------
# bounded groupby
# ---------------------------------------------------------------------------

_DOMAIN = (0, 5, 10)
_EDGE_AGGS = ((1, "sum"), (1, "count"), (1, "mean"),
              (2, "min"), (2, "max"), (2, "sum"))


def _groupby_input(n, seed=0):
    rng = np.random.default_rng(seed)
    keys = rng.integers(0, 3, n).astype(np.int32) * 5        # domain {0,5,10}
    kvalid = np.ones(n, bool)
    kvalid[-max(1, n // 4):] = False                         # null tail
    v64 = rng.integers(-(2 ** 40), 2 ** 40, n).astype(np.int64)
    v64_valid = np.ones(n, bool)
    v64_valid[-max(1, n // 8):] = False
    v8 = rng.integers(-128, 128, n).astype(np.int8)
    return keys, kvalid, [None, (v64, v64_valid), (v8, np.ones(n, bool))]


def _numpy_groupby(keys, kvalid, cols, aggs):
    """One row a domain value, the null-key group last; None where a
    group holds no row (or, for an aggregate, no valid value)."""
    out = []
    for k in _DOMAIN + (None,):
        rows = ~kvalid if k is None else kvalid & (keys == k)
        cells = [k if rows.any() else None]
        for col, op in aggs:
            vals, valid = cols[col]
            v = vals[rows & valid].astype(object)  # Python ints: exact
            if not rows.any() or (op != "count" and not len(v)):
                cells.append(None)
            elif op == "count":
                cells.append(len(v))
            elif op == "mean":
                cells.append(float(np.float64(sum(v)) / np.float64(len(v))))
            else:
                cells.append(int({"sum": sum, "min": min, "max": max}[op](v)))
        out.append(cells)
    return out


@pytest.mark.parametrize("n, seed, aggs", [
    *[pytest.param(n, n, _EDGE_AGGS, id=str(n)) for n in EDGE_ROWS],
    pytest.param(300, 3, ((1, "sum"), (1, "max")), id="max_over_int64"),
])
def test_bounded_groupby_at_bucket_edges(n, seed, aggs):
    keys, kvalid, cols = _groupby_input(n, seed)
    table = Table([Column.from_numpy(keys, validity=kvalid)] + [
        Column.from_numpy(vals, validity=None if valid.all() else valid)
        for vals, valid in cols[1:]])
    plan = fusion.Plan("bucket_edges_groupby", fusion.GroupBy(
        fusion.Scan("t"), (0,), aggs, domains=(scalar_domain(_DOMAIN),),
        label="g"))
    res = fusion.execute(plan, {"t": table})
    assert res.meta["g.lowered"] == "bounded"
    assert not bool(res.meta["g.domain_miss"])
    got = [list(row) for row in zip(
        *(c.to_pylist() for c in res.table.columns))]
    assert got == _numpy_groupby(keys, kvalid, cols, aggs)


# ---------------------------------------------------------------------------
# the join's maps
# ---------------------------------------------------------------------------

def _join_input(n_left, n_right, seed=0, key_dtype=np.int32):
    rng = np.random.default_rng(seed)
    lk = rng.integers(0, max(2, n_left // 2 + 1), n_left).astype(key_dtype)
    rk = rng.integers(0, max(2, n_left // 2 + 1), n_right).astype(key_dtype)
    lvalid = np.ones(n_left, bool)
    lvalid[-max(1, n_left // 4):] = False                    # null tail
    return lk, lvalid, rk


def _numpy_join(lk, lvalid, rk, how):
    """The output rows in the order the maps promise: the probe's rows
    in order, a row's matches by build row; then, under ``full``, the
    build rows nothing matched. -1 stands for the side that is null."""
    rows = []
    for i in range(len(lk)):
        hits = np.flatnonzero(rk == lk[i]) if lvalid[i] else []
        rows += [(i, int(r)) for r in hits]
        if not len(hits) and how in ("left", "full"):
            rows.append((i, -1))
    if how == "full":
        probed = set(lk[lvalid].tolist())
        rows += [(-1, r) for r in range(len(rk)) if int(rk[r]) not in probed]
    return rows


def _check_join(n_left, n_right, seed, how, key_dtype=np.int32):
    lk, lvalid, rk = _join_input(n_left, n_right, seed, key_dtype)
    left = Table([Column.from_numpy(lk, validity=lvalid)])
    right = Table([Column.from_numpy(rk)])
    out_size = min((n_left + 1) * (n_right + 1), 1 << 20)
    maps = join(left, right, 0, 0, out_size, how=how)
    want = _numpy_join(lk, lvalid, rk, how)
    total = int(maps.total)
    assert total == len(want) <= out_size
    assert np.array_equal(np.asarray(maps.row_valid),
                          np.arange(out_size) < total)
    li, ri = np.asarray(maps.left_index), np.asarray(maps.right_index)
    lv, rv = np.asarray(maps.left_valid), np.asarray(maps.right_valid)
    got = [(int(li[j]) if lv[j] else -1, int(ri[j]) if rv[j] else -1)
           for j in range(total)]
    assert got == want
    assert not lv[total:].any() and not rv[total:].any()


@pytest.mark.parametrize("n_right", EDGE_ROWS[:-1])
@pytest.mark.parametrize("how", ["inner", "left", "full"])
def test_join_maps_at_build_side_bucket_edges(how, n_right):
    _check_join(257, n_right, n_right, how)


@pytest.mark.parametrize("n_left, n_right, seed, key_dtype", [
    *[pytest.param(n, 256, n, np.int32, id=str(n)) for n in EDGE_ROWS],
    pytest.param(64, 2049, 9, np.int32, id="build_of_2049"),
    pytest.param(48, 96, 13, np.int64, id="int64_keys"),
])
def test_join_maps_at_probe_side_bucket_edges(n_left, n_right, seed,
                                              key_dtype):
    _check_join(n_left, n_right, seed, "inner", key_dtype)


# ---------------------------------------------------------------------------
# the row image
# ---------------------------------------------------------------------------

def _rows_input(n, seed=0):
    rng = np.random.default_rng(seed)
    every = np.ones(n, bool)
    valid = every.copy()
    valid[-max(1, n // 4):] = False                          # null tail
    return [
        (rng.integers(-(2 ** 60), 2 ** 60, n).astype(np.int64), None, valid),
        (rng.integers(-100, 100, n).astype(np.int8), None, every),
        (rng.random(n).astype(np.float64), None, every),
        ((rng.random(n) > 0.5).astype(np.uint8), t.BOOL8, valid),
        (rng.integers(-1000, 1000, n).astype(np.int16), None, valid),
    ]


def _wide_rows_input(n, seed=0):
    # 33 int64 columns: 264 data bytes a row
    rng = np.random.default_rng(seed)
    return [(rng.integers(-100, 100, n).astype(np.int64), None,
             np.ones(n, bool)) for _ in range(33)]


def _numpy_row_image(cols):
    """The reference's fixed-width row (row_conversion.cu): a column at
    the next multiple of its own size, then a validity bit a column
    (bit ``c % 8`` of byte ``c // 8``), the row padded to 8 bytes."""
    n = len(cols[0][0])
    cursor, starts = 0, []
    for vals, _, _ in cols:
        size = vals.dtype.itemsize
        cursor = -(-cursor // size) * size
        starts.append(cursor)
        cursor += size
    validity_at = cursor
    row_size = -(-(cursor + -(-len(cols) // 8)) // 8) * 8
    image = np.zeros((n, row_size), np.uint8)
    for c, ((vals, _, valid), start) in enumerate(zip(cols, starts)):
        size = vals.dtype.itemsize
        image[:, start:start + size] = np.ascontiguousarray(vals).view(
            np.uint8).reshape(n, size)
        image[:, validity_at + c // 8] |= valid.astype(np.uint8) << (c % 8)
    return row_size, image


@pytest.mark.parametrize("n, seed, make", [
    *[pytest.param(n, n, _rows_input, id=str(n)) for n in EDGE_ROWS[:4]],
    pytest.param(16, 11, _wide_rows_input, id="row_of_264_bytes"),
])
def test_to_rows_at_bucket_edges(n, seed, make):
    cols = make(n, seed)
    table = Table([
        Column.from_numpy(vals, dtype=dt,
                          validity=None if valid.all() else valid)
        for vals, dt, valid in cols])
    (batch,) = convert_to_rows(table)
    row_size, image = _numpy_row_image(cols)
    assert (batch.num_rows, batch.row_size) == (n, row_size)
    assert np.asarray(batch.data).tobytes() == image.tobytes()
    back = convert_from_rows(batch, table.schema())
    for got, (vals, _, valid) in zip(back.columns, cols):
        assert np.array_equal(np.asarray(got.valid_mask()), valid)
        assert np.asarray(got.data)[valid].tobytes() == vals[valid].tobytes()
