"""LIST-column operators: explode/posexplode and collect_list/collect_set.

cuDF ships ``explode``/``explode_position`` and the ``collect_list``/
``collect_set`` groupby aggregations as part of the vendored capability
surface (SURVEY.md section 2.2 — libcudf columnar engine; Spark lowers
``explode()``, ``posexplode()``, ``collect_list()``, ``collect_set()``
straight onto them). The TPU designs here are scatter-free:

- ``explode``: each output slot finds its parent row with ONE searchsorted
  against the per-row start positions, then gathers. Inner and outer
  explode share the mechanism — outer adds one slot for every empty/null
  list (start = offsets + running empty count), which reproduces Spark's
  exact interleaved row order with static shapes (output padded to the
  worst case, ``row_valid`` reports the live slots).
- ``groupby_collect``: stable key sort + one boolean argsort compacts each
  group's kept values into a dense child in input order; list offsets are
  a cumsum of per-group keep counts. ``distinct=True`` re-sorts by
  (keys, value) and keeps first occurrences — set semantics with
  value-ordered output (Spark's collect_set leaves order unspecified).

Null semantics are Spark's: collect_list/collect_set SKIP null values and
return EMPTY lists (never null) for groups with no kept values; explode
drops null/empty lists, explode_outer emits one all-null row for them.
"""

from __future__ import annotations

from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from spark_rapids_jni_tpu.columnar import Column, Table
from spark_rapids_jni_tpu.ops.groupby import (
    _group_bounds,
    _gather_group_keys,
    _rows_equal_prev,
    _col_values_equal_prev,
)
from spark_rapids_jni_tpu.ops.sort import gather, sort_order
from spark_rapids_jni_tpu.types import DType, TypeId
from spark_rapids_jni_tpu.utils.tracing import func_range


def make_list_column(values: Sequence, element_dtype: DType) -> Column:
    """Host-side LIST builder from ``[[...], None, [...]]`` pylists (the
    test/ingest convenience mirroring ``Column.from_pylist``)."""
    import numpy as np

    offsets = np.zeros(len(values) + 1, dtype=np.int32)
    flat: list = []
    valid = np.ones(len(values), dtype=bool)
    for i, v in enumerate(values):
        if v is None:
            valid[i] = False
            offsets[i + 1] = offsets[i]
        else:
            flat.extend(v)
            offsets[i + 1] = offsets[i] + len(v)
    child = Column.from_pylist(flat, element_dtype)
    return Column(
        DType(TypeId.LIST), jnp.asarray(offsets),
        None if valid.all() else jnp.asarray(valid),
        children=[child],
    )


class ExplodeResult(NamedTuple):
    table: Table              # exploded rows, padded to the static bound
    row_valid: jnp.ndarray    # bool[out_n]: live output slots
    num_rows: jnp.ndarray     # scalar int64 true output row count


def _gather_any(c: Column, idx: jnp.ndarray, extra_valid) -> Column:
    """Gather a non-LIST column at ``idx`` with extra invalidation."""
    valid = c.valid_mask()[idx] & extra_valid
    if c.dtype.is_string:
        from spark_rapids_jni_tpu.ops import strings as s

        g = s.gather_strings(c, idx)
        return Column(c.dtype, g.data, valid, chars=g.chars)
    return Column(c.dtype, c.data[idx], valid)


@func_range("explode")
def explode(table: Table, col_idx: int, *, outer: bool = False,
            position: bool = False) -> ExplodeResult:
    """Explode the LIST column ``col_idx``: one output row per element,
    the other columns repeated, in Spark's exact interleaved order.

    ``outer=True`` (Spark ``explode_outer``) keeps rows whose list is
    empty or null as a single row with a null element. ``position=True``
    (Spark ``posexplode``) inserts an INT32 0-based position column just
    before the element column. Output is padded to the static worst case
    (child length, + row count when outer); ``row_valid`` marks live
    slots and ``num_rows`` is the true count.
    """
    lc = table.column(col_idx)
    if lc.dtype.type_id != TypeId.LIST:
        raise TypeError(f"explode needs a LIST column, got {lc.dtype}")
    child = lc.children[0]
    if child.dtype.type_id == TypeId.LIST:
        raise NotImplementedError("explode of nested LIST-of-LIST")
    n = lc.size
    offsets = lc.data.astype(jnp.int64)
    list_valid = lc.valid_mask()
    # treat null lists as length 0 (they contribute rows only under outer)
    lens = jnp.where(list_valid, offsets[1:] - offsets[:-1], 0)
    starts_inner = jnp.concatenate(
        [jnp.zeros((1,), jnp.int64), jnp.cumsum(lens)])
    if outer:
        empty = (lens == 0).astype(jnp.int64)
        starts = starts_inner + jnp.concatenate(
            [jnp.zeros((1,), jnp.int64), jnp.cumsum(empty)])
    else:
        starts = starts_inner
    total = starts[-1]
    out_n = int(child.size) + (n if outer else 0)
    k = jnp.arange(out_n, dtype=jnp.int64)
    parent = jnp.clip(
        jnp.searchsorted(starts, k, side="right") - 1, 0, max(n - 1, 0)
    ).astype(jnp.int32)
    j = k - starts[parent]
    live = k < total
    has_elem = live & (j < lens[parent])
    # element index into the ORIGINAL child buffer (null lists have
    # lens == 0, so has_elem is False and the clipped index is unused)
    eidx = jnp.clip(offsets[parent] + j, 0,
                    max(int(child.size) - 1, 0)).astype(jnp.int32)
    out_cols: list[Column] = []
    for ci in range(table.num_columns):
        if ci == col_idx:
            if position:
                out_cols.append(Column(
                    DType(TypeId.INT32), j.astype(jnp.int32), has_elem))
            out_cols.append(_gather_any(child, eidx, has_elem))
        else:
            c = table.column(ci)
            if c.dtype.type_id in (TypeId.LIST, TypeId.STRUCT):
                raise NotImplementedError(
                    "explode alongside other nested columns")
            out_cols.append(_gather_any(c, parent, live))
    return ExplodeResult(Table(out_cols), live, total)


class CollectResult(NamedTuple):
    table: Table              # keys then ONE LIST column, padded to m rows
    num_groups: jnp.ndarray   # scalar int32


@func_range("groupby_collect")
def groupby_collect(table: Table, keys: Sequence[int], value_col: int,
                    *, distinct: bool = False) -> CollectResult:
    """collect_list (``distinct=False``) / collect_set (``distinct=True``)
    of ``value_col`` grouped by ``keys``.

    The LIST child holds every kept value, groups concatenated in key
    order; offsets are the cumsum of per-group keep counts. Groups with
    no kept values get EMPTY lists (Spark returns [] here, not null).
    Output is padded to n rows like groupby_aggregate; callers trim with
    ``num_groups`` (the child is likewise padded — ``to_pylist`` only
    reads below each list's offsets).
    """
    c_check = table.column(value_col)
    if c_check.dtype.type_id in (TypeId.LIST, TypeId.STRUCT):
        raise NotImplementedError("collect of nested columns")
    n = table.num_rows
    m = n
    sub = Table([table.column(k) for k in keys] + [table.column(value_col)])
    kix = list(range(len(keys)))
    vix = len(keys)
    if distinct:
        order = sort_order(sub, kix + [vix],
                           nulls_first=[True] * len(keys) + [False])
    else:
        order = sort_order(sub, kix)
    ssub = gather(sub, order)
    same = _rows_equal_prev(ssub, kix)
    num_groups, g_lo, g_hi = _group_bounds(same, m)
    first_idx = jnp.where(g_hi > g_lo, g_lo, n)
    out_cols = _gather_group_keys(ssub, kix, first_idx, m, n)

    vc = ssub.column(vix)
    keep = vc.valid_mask()
    if distinct and n:
        # drop repeats of the same value within a group (values are
        # adjacent after the secondary sort — the nunique flag idiom)
        eqv = _col_values_equal_prev(vc)
        prev_same_valid = jnp.concatenate(
            [jnp.zeros((1,), jnp.bool_), eqv & keep[:-1]])
        keep = keep & (~same | ~prev_same_valid)
    if n:
        pref = jnp.cumsum(keep.astype(jnp.int64))
        pref0 = jnp.concatenate([jnp.zeros((1,), jnp.int64), pref])
        counts = pref0[g_hi] - pref0[g_lo]
        # kept rows first (stable) — their sorted order IS group order,
        # so the compacted prefix is exactly the dense child
        comp = jnp.argsort(~keep, stable=True).astype(jnp.int32)
        child = _gather_any(vc, comp, jnp.bool_(True))
    else:
        counts = jnp.zeros((m,), jnp.int64)
        child = vc
    offsets = jnp.concatenate(
        [jnp.zeros((1,), jnp.int64), jnp.cumsum(counts)]
    ).astype(jnp.int32)
    garange = jnp.arange(m, dtype=jnp.int32)
    out_cols.append(Column(
        DType(TypeId.LIST), offsets, garange < num_groups,
        children=[child],
    ))
    return CollectResult(Table(out_cols), num_groups)


@func_range("array_size")
def array_size(col: Column) -> Column:
    """Spark ``size``/``cardinality``: element count per list; null
    lists give null (ANSI) — the caller can map null->-1 for legacy."""
    if col.dtype.type_id != TypeId.LIST:
        raise TypeError(f"array_size needs a LIST column, got {col.dtype}")
    lens = (col.data[1:] - col.data[:-1]).astype(jnp.int32)
    return Column(DType(TypeId.INT32), lens,
                  col.valid_mask() if col.validity is not None else None)


@func_range("array_contains")
def array_contains(col: Column, value) -> Column:
    """Spark ``array_contains(list, value)``: per-row ANY over the
    child — a prefix-difference count over the flat child matches, no
    per-row loops. Three-valued logic matches Spark's ArrayContains:
    TRUE when found; NULL when not found but the list has a null
    element (the null might have been the value); FALSE otherwise; a
    null list is null."""
    if col.dtype.type_id != TypeId.LIST:
        raise TypeError(
            f"array_contains needs a LIST column, got {col.dtype}")
    child = col.children[0]
    if child.dtype.is_decimal128:
        hit = _scalar_d128_hit(child, value)
    elif child.dtype.is_string:
        hit = _scalar_string_hit(child, value)
    else:
        hit = (child.data == value) & child.valid_mask()

    found = _range_any(hit, col.data)
    has_null_elem = _range_any(~child.valid_mask(), col.data)
    from spark_rapids_jni_tpu.types import BOOL8

    validity = col.valid_mask() & (found | ~has_null_elem)
    return Column(BOOL8, found.astype(jnp.uint8), validity)


@func_range("element_at")
def element_at(col: Column, k: int) -> Column:
    """Spark ``element_at(list, k)``: 1-based; negative k counts from
    the end; out-of-bounds gives null (non-ANSI posture)."""
    if col.dtype.type_id != TypeId.LIST:
        raise TypeError(f"element_at needs a LIST column, got {col.dtype}")
    if k == 0:
        raise ValueError("element_at index is 1-based (k != 0)")
    child = col.children[0]
    off = col.data.astype(jnp.int32)
    lens = off[1:] - off[:-1]
    if k > 0:
        pos = off[:-1] + (k - 1)
        in_b = k <= lens
    else:
        pos = off[1:] + k
        in_b = -k <= lens
    valid = in_b & col.valid_mask()
    src = jnp.clip(pos, 0, max(int(child.size) - 1, 0))
    return _gather_any(child, src, valid)


@func_range("array_join")
def array_join(col: Column, sep: str,
               null_replacement: str | None = None) -> Column:
    """Spark ``array_join``: concatenate STRING list elements with
    ``sep``; null elements are skipped unless ``null_replacement``."""
    if col.dtype.type_id != TypeId.LIST:
        raise TypeError(f"array_join needs a LIST column, got {col.dtype}")
    child = col.children[0]
    if not child.dtype.is_string:
        raise TypeError("array_join needs LIST<STRING>")
    # host-assembled (ragged concatenation has no fixed-width form that
    # beats the explode->concat_ws chain; columns needing device joins
    # should explode + groupby_collect instead)
    vals = col.to_pylist()
    out = []
    for lst in vals:
        if lst is None:
            out.append(None)
            continue
        parts = []
        for v in lst:
            if v is None:
                if null_replacement is not None:
                    parts.append(null_replacement)
            else:
                parts.append(v)
        out.append(sep.join(parts))
    from spark_rapids_jni_tpu import types as t

    return Column.from_pylist(out, t.STRING)


def _scalar_string_hit(child: Column, value) -> jnp.ndarray:
    """bool[child_n]: child string elements equal to the scalar value
    (padded compare; absent when longer than the padded width)."""
    from spark_rapids_jni_tpu.ops import strings as s

    p = s.pad_strings(child)
    vb = str(value).encode()
    w = p.chars.shape[1]
    if len(vb) > w:
        return jnp.zeros((int(child.size),), jnp.bool_)
    target = jnp.zeros((w,), jnp.uint8).at[:len(vb)].set(
        jnp.asarray(bytearray(vb), dtype=jnp.uint8))
    return ((p.data == len(vb))
            & jnp.all(p.chars == target[None, :], axis=1)
            & p.valid_mask())


def _scalar_d128_hit(child: Column, value) -> jnp.ndarray:
    """bool[child_n]: DECIMAL128 elements equal to the Python-int
    unscaled ``value`` (two's-complement limb split)."""
    v = int(value)
    lo = jnp.int64(np.int64(np.uint64(v & 0xFFFFFFFFFFFFFFFF)))
    hi = jnp.int64(v >> 64)
    return ((child.data[:, 0] == lo) & (child.data[:, 1] == hi)
            & child.valid_mask())


def _range_any(flags: jnp.ndarray, offsets: jnp.ndarray) -> jnp.ndarray:
    """bool[n]: ANY of ``flags`` within each [offsets[i], offsets[i+1])
    — one cumsum + prefix difference, the shared list-predicate idiom."""
    pref = jnp.concatenate(
        [jnp.zeros((1,), jnp.int64),
         jnp.cumsum(flags.astype(jnp.int64))])
    off = offsets.astype(jnp.int32)
    return (pref[off[1:]] - pref[off[:-1]]) > 0


def _parent_ids(col: Column) -> jnp.ndarray:
    """int32 parent row per child element (searchsorted over offsets —
    the explode idiom). Child slots BEYOND offsets[-1] (the padded tail
    array_distinct/groupby_collect leave behind) get the sentinel parent
    ``n`` so they sort after every real row and match no range query —
    clipping them into the last row would corrupt it."""
    child_n = int(col.children[0].size)
    n = col.size
    off = col.data.astype(jnp.int64)
    k = jnp.arange(child_n, dtype=jnp.int64)
    real = jnp.clip(
        jnp.searchsorted(off, k, side="right") - 1, 0,
        max(n - 1, 0)).astype(jnp.int32)
    return jnp.where(k < off[-1], real, jnp.int32(n))


@func_range("sort_array")
def sort_array(col: Column, ascending: bool = True) -> Column:
    """Spark ``sort_array``: elements sorted within each list (offsets
    unchanged — one segmented sort of (parent, value)). Null elements
    first when ascending, last when descending (Spark's rule)."""
    if col.dtype.type_id != TypeId.LIST:
        raise TypeError(f"sort_array needs a LIST column, got {col.dtype}")
    child = col.children[0]
    parent = _parent_ids(col)
    from spark_rapids_jni_tpu.types import DType as _D, TypeId as _T

    ptbl = Table([
        Column(_D(_T.INT32), parent, None),
        child,
    ])
    order = sort_order(ptbl, [0, 1], ascending=[True, ascending],
                       nulls_first=[True, ascending])
    schild = gather(Table([child]), order).column(0)
    return Column(col.dtype, col.data, col.validity, children=[schild])


@func_range("array_position")
def array_position(col: Column, value) -> Column:
    """Spark ``array_position``: 1-based index of the first element equal
    to ``value``, 0 when absent, null for null lists. Null elements never
    match (no 3VL here — Spark's ArrayPosition returns a position, and
    absent-with-nulls is still 0... matching Spark's non-ANSI behavior:
    it returns null only for null inputs)."""
    if col.dtype.type_id != TypeId.LIST:
        raise TypeError(
            f"array_position needs a LIST column, got {col.dtype}")
    child = col.children[0]
    if child.dtype.is_decimal128:
        hit = _scalar_d128_hit(child, value)
    elif child.dtype.is_string:
        hit = _scalar_string_hit(child, value)
    else:
        hit = (child.data == value) & child.valid_mask()
    child_n = int(child.size)
    k = jnp.arange(child_n, dtype=jnp.int64)
    first_global = jnp.where(hit, k, child_n)
    # per-list min of the hit positions via a cummin prefix difference:
    # positions are globally increasing, so the first hit in [lo, hi) is
    # the min over that range — use a suffix-min then gather at lo
    if child_n:
        suffix_min = jax.lax.cummin(first_global[::-1])[::-1]
        off = col.data.astype(jnp.int32)
        lo = jnp.clip(off[:-1], 0, child_n - 1)
        first_in = jnp.minimum(
            suffix_min[lo],
            jnp.int64(child_n))
        # clamp to the row's own range: a hit belonging to a LATER row
        # must not leak backwards
        in_range = first_in < off[1:]
        pos = jnp.where(in_range & (first_in >= off[:-1]),
                        first_in - off[:-1] + 1, 0)
    else:
        pos = jnp.zeros((col.size,), jnp.int64)
    return Column(DType(TypeId.INT64), pos.astype(jnp.int64),
                  col.valid_mask() if col.validity is not None else None)


@func_range("array_distinct")
def array_distinct(col: Column) -> Column:
    """Spark ``array_distinct``: duplicates removed, FIRST occurrences
    kept in order. Two sorts: (parent, value) marks first occurrences,
    (parent, position) restores order; the kept elements compact into a
    dense child with prefix-sum offsets."""
    if col.dtype.type_id != TypeId.LIST:
        raise TypeError(
            f"array_distinct needs a LIST column, got {col.dtype}")
    child = col.children[0]
    n = col.size
    child_n = int(child.size)
    if child_n == 0:
        return col
    parent = _parent_ids(col)
    from spark_rapids_jni_tpu.types import DType as _D, TypeId as _T

    pcol = Column(_D(_T.INT32), parent, None)
    ptbl = Table([pcol, child])
    order = sort_order(ptbl, [0, 1], nulls_first=[True, True])
    svals = gather(ptbl, order)
    same_parent = svals.column(0).data[1:] == svals.column(0).data[:-1]
    sc = svals.column(1)
    eqv = _col_values_equal_prev(sc)
    v1 = sc.valid_mask()
    both_null = ~v1[1:] & ~v1[:-1]
    same_val = (eqv & v1[1:] & v1[:-1]) | both_null
    dup = jnp.concatenate(
        [jnp.zeros((1,), jnp.bool_), same_parent & same_val])
    # keep flag back in ORIGINAL child positions: keep[order[i]] = ~dup[i]
    # (a gather-free formulation: sort (order) is a permutation, use
    # argsort to invert — one more sort, no scatter)
    inv = jnp.argsort(order).astype(jnp.int32)
    keep = (~dup)[inv]
    counts_pref = jnp.concatenate(
        [jnp.zeros((1,), jnp.int64), jnp.cumsum(keep.astype(jnp.int64))])
    off = col.data.astype(jnp.int32)
    new_off = (counts_pref[off] ).astype(jnp.int32)
    comp = jnp.argsort(~keep, stable=True).astype(jnp.int32)
    new_child = _gather_any(child, comp, jnp.bool_(True))
    return Column(col.dtype, new_off, col.validity, children=[new_child])


@func_range("arrays_overlap")
def arrays_overlap(a: Column, b: Column) -> Column:
    """Spark ``arrays_overlap``: TRUE when the rows' lists share a
    non-null element; NULL when they don't but either side has a null
    element (3VL); FALSE otherwise; null lists give null."""
    for c in (a, b):
        if c.dtype.type_id != TypeId.LIST:
            raise TypeError(
                f"arrays_overlap needs LIST columns, got {c.dtype}")
    ca, cb = a.children[0], b.children[0]
    if ca.dtype != cb.dtype:
        raise TypeError("arrays_overlap needs matching element dtypes")
    if a.size != b.size:
        raise ValueError(
            f"arrays_overlap needs equal row counts, got {a.size} vs "
            f"{b.size}")
    # DECIMAL128 children work unchanged: limb-pair sort keys and the
    # limb-wise equal-prev compare are the same machinery sort/groupby use
    n = a.size
    pa, pb = _parent_ids(a), _parent_ids(b)
    from spark_rapids_jni_tpu.ops.table_ops import concatenate
    from spark_rapids_jni_tpu.types import DType as _D, TypeId as _T

    side_a = Column(_D(_T.INT8),
                    jnp.zeros((int(ca.size),), jnp.int8), None)
    side_b = Column(_D(_T.INT8),
                    jnp.ones((int(cb.size),), jnp.int8), None)
    ta = Table([Column(_D(_T.INT32), pa, None), ca, side_a])
    tb = Table([Column(_D(_T.INT32), pb, None), cb, side_b])
    allt = concatenate([ta, tb])
    order = sort_order(allt, [0, 1, 2], nulls_first=[True, False, True])
    sv = gather(allt, order)
    same_parent = sv.column(0).data[1:] == sv.column(0).data[:-1]
    sc = sv.column(1)
    v1 = sc.valid_mask()
    eqv = _col_values_equal_prev(sc)
    same_valid_val = eqv & v1[1:] & v1[:-1]
    diff_side = sv.column(2).data[1:] != sv.column(2).data[:-1]
    pairhit = same_parent & same_valid_val & diff_side
    # per-parent ANY over adjacent pair hits (prefix-difference count
    # indexed by the sorted parent runs)
    hit_parent = sv.column(0).data[1:]
    total = int(ca.size) + int(cb.size)
    cnt = jnp.zeros((n,), jnp.int64)
    if total > 1:
        pref = jnp.concatenate(
            [jnp.zeros((1,), jnp.int64),
             jnp.cumsum(pairhit.astype(jnp.int64))])
        pr = jnp.arange(n, dtype=jnp.int32)
        lo = jnp.searchsorted(hit_parent, pr, side="left")
        hi = jnp.searchsorted(hit_parent, pr, side="right")
        cnt = pref[hi] - pref[lo]
    overlap = cnt > 0

    # 3VL per Spark's ArraysOverlap: NULL only when there is no common
    # element, BOTH arrays are non-empty, and either contains a null
    def _range_any_nulls(col_l):
        c = col_l.children[0]
        if c.validity is None:
            return jnp.zeros((n,), jnp.bool_)
        return _range_any(~c.valid_mask(), col_l.data)

    def _nonempty(col_l):
        off_ = col_l.data.astype(jnp.int32)
        return off_[1:] > off_[:-1]

    has_null = ((_range_any_nulls(a) | _range_any_nulls(b))
                & _nonempty(a) & _nonempty(b))
    from spark_rapids_jni_tpu.types import BOOL8

    validity = a.valid_mask() & b.valid_mask() & (overlap | ~has_null)
    return Column(BOOL8, overlap.astype(jnp.uint8), validity)


@func_range("sequence")
def sequence(start: Column, stop: Column, step: Column | int = 1,
             max_length: int = 1024) -> Column:
    """Spark ``sequence(start, stop, step)``: one inclusive arithmetic
    range per row as LIST<INT64>.

    HOST-LEVEL generator (not jit-composable: the static child budget
    and Spark's error semantics both need host checks). A row whose
    range exceeds ``max_length`` raises; a step moving AWAY from stop
    raises like Spark's ILLEGAL_SEQUENCE_BOUNDARIES; step 0 is rejected
    up front; null operands give a null row (Spark null propagation)."""
    if isinstance(step, int):
        step_data = jnp.full((start.size,), step, jnp.int64)
        step_valid = jnp.ones((start.size,), jnp.bool_)
    else:
        step_data = step.data.astype(jnp.int64)
        step_valid = step.valid_mask()
    a = start.data.astype(jnp.int64)
    b = stop.data.astype(jnp.int64)
    ok = start.valid_mask() & stop.valid_mask() & step_valid
    # Spark's rule: a zero step is legal ONLY when start == stop (the
    # single-element sequence); otherwise, and for steps moving away
    # from stop, ILLEGAL_SEQUENCE_BOUNDARIES
    zero_ok = (step_data == 0) & (a == b)
    right_dir = jnp.where(step_data > 0, b >= a,
                          jnp.where(step_data < 0, b <= a, a == b))
    if bool(jnp.any(ok & ~right_dir)):
        raise ValueError(
            "sequence step moves away from stop (or is zero with "
            "start != stop) — Spark ILLEGAL_SEQUENCE_BOUNDARIES")
    safe_step = jnp.where(step_data == 0, jnp.int64(1), step_data)
    lens = jnp.where(
        ok & right_dir,
        jnp.where(zero_ok, jnp.int64(1),
                  jnp.floor_divide(b - a, safe_step) + 1),
        jnp.int64(0))
    too_long = bool(jnp.any(lens > max_length))
    if too_long:
        raise ValueError(
            f"sequence row exceeds max_length={max_length} elements; "
            "raise max_length (static child budget)")
    n = start.size
    offsets = jnp.concatenate(
        [jnp.zeros((1,), jnp.int64), jnp.cumsum(lens)]).astype(jnp.int32)
    child_n = n * max_length
    k = jnp.arange(child_n, dtype=jnp.int64)
    parent = jnp.clip(
        jnp.searchsorted(offsets.astype(jnp.int64), k, side="right") - 1,
        0, max(n - 1, 0)).astype(jnp.int32)
    j = k - offsets[parent]
    live = k < offsets[-1]
    vals = a[parent] + j * step_data[parent]
    child = Column(DType(TypeId.INT64),
                   jnp.where(live, vals, 0).astype(jnp.int64),
                   live)
    validity = None if (start.validity is None
                        and stop.validity is None
                        and not isinstance(step, Column)) else ok
    return Column(DType(TypeId.LIST), offsets, validity, children=[child])


def _list_ranges(col: Column):
    off = col.data.astype(jnp.int32)
    return off[:-1], off[1:]


@func_range("array_sum")
def array_sum(col: Column) -> Column:
    """Per-list SUM of numeric elements (nulls skipped; empty/all-null
    lists null — the aggregate posture)."""
    if col.dtype.type_id != TypeId.LIST:
        raise TypeError(f"array_sum needs a LIST column, got {col.dtype}")
    child = col.children[0]
    if child.dtype.is_string or child.dtype.is_decimal128:
        raise TypeError("array_sum needs numeric elements")
    valid = child.valid_mask()
    vv = jnp.where(valid, child.data, jnp.zeros_like(child.data))
    from spark_rapids_jni_tpu.ops.groupby import _sum_dtype

    acc_dt = _sum_dtype(child.dtype)
    acc = vv.astype(jnp.int64) if acc_dt.storage_dtype.kind in ("i", "u") \
        else vv.astype(jnp.float64)
    pref = jnp.concatenate(
        [jnp.zeros((1,), acc.dtype), jnp.cumsum(acc)])
    cpref = jnp.concatenate(
        [jnp.zeros((1,), jnp.int64),
         jnp.cumsum(valid.astype(jnp.int64))])
    lo, hi = _list_ranges(col)
    total = (pref[hi] - pref[lo]).astype(acc_dt.jnp_dtype)
    cnt = cpref[hi] - cpref[lo]
    return Column(acc_dt, total, col.valid_mask() & (cnt > 0))


def _array_extremum(col: Column, op: str) -> Column:
    if col.dtype.type_id != TypeId.LIST:
        raise TypeError(f"array_{op} needs a LIST column, got {col.dtype}")
    child = col.children[0]
    if child.dtype.is_string or child.dtype.is_decimal128:
        raise NotImplementedError(f"array_{op} on non-fixed-width elements")
    child_n = int(child.size)
    n = col.size
    lo, hi = _list_ranges(col)
    if child_n == 0:
        return Column(child.dtype,
                      jnp.zeros((n,), child.dtype.jnp_dtype),
                      jnp.zeros((n,), jnp.bool_))
    import numpy as _np

    dt = child.dtype.storage_dtype
    if dt.kind == "f":
        sentinel = jnp.inf if op == "min" else -jnp.inf
    else:
        info = _np.iinfo(dt)
        sentinel = info.max if op == "min" else info.min
    vv = jnp.where(child.valid_mask(), child.data,
                   jnp.asarray(sentinel, child.data.dtype))
    if dt.kind == "f":
        # Spark orders NaN greatest: array_max with any NaN is NaN,
        # array_min skips NaNs (unless every element is NaN). Map NaN
        # to +inf for the scan, then restore NaN where +inf won
        # (documented ambiguity with a genuine +inf element).
        vv = jnp.where(jnp.isnan(vv), jnp.inf, vv)
    pick = jnp.minimum if op == "min" else jnp.maximum
    # suffix-scan sparse table over the flat child (the rolling-extremum
    # idiom at list granularity): levels cover the longest list
    max_len = int(jnp.max(hi - lo)) if n else 1
    nlev = max(1, max(max_len, 1).bit_length())
    idx = jnp.arange(child_n, dtype=jnp.int32)
    levels = [vv]
    for lev in range(nlev - 1):
        off = 1 << lev
        levels.append(pick(
            levels[-1],
            levels[-1][jnp.clip(idx + off, 0, child_n - 1)]))
    stacked = jnp.stack(levels)
    length = jnp.maximum(hi - lo, 1)
    k = jnp.zeros((n,), jnp.int32)
    for lev in range(1, nlev):
        k = k + (length >= (1 << lev)).astype(jnp.int32)
    span = jnp.left_shift(jnp.int32(1), k)
    c32 = lambda i: jnp.clip(i, 0, child_n - 1).astype(jnp.int32)
    at_lo = stacked[:, c32(lo)]
    at_hi = stacked[:, c32(hi - span)]
    a = jnp.take_along_axis(at_lo, k[None, :], axis=0)[0]
    b = jnp.take_along_axis(at_hi, k[None, :], axis=0)[0]
    out = pick(a, b)
    if dt.kind == "f":
        out = jnp.where(jnp.isinf(out) & (out > 0), jnp.nan, out)
    cnt = _range_any(child.valid_mask(), col.data)
    return Column(child.dtype, out, col.valid_mask() & cnt)


@func_range("array_min")
def array_min(col: Column) -> Column:
    """Per-list MIN (nulls skipped; empty/all-null lists null)."""
    return _array_extremum(col, "min")


@func_range("array_max")
def array_max(col: Column) -> Column:
    return _array_extremum(col, "max")


@func_range("array_slice")
def array_slice(col: Column, start: int, length: int) -> Column:
    """Spark ``slice(arr, start, length)``: 1-based start (negative
    counts from the end — a start beyond the head gives an EMPTY list),
    ``length`` elements. Builds a dense compacted child via the
    explode-style parent mapping (new offsets + one gather)."""
    if col.dtype.type_id != TypeId.LIST:
        raise TypeError(f"array_slice needs a LIST column, got {col.dtype}")
    if start == 0:
        raise ValueError("slice start is 1-based (non-zero)")
    if length < 0:
        raise ValueError("slice length must be >= 0")
    lo, hi = _list_ranges(col)
    lens = hi - lo
    if start > 0:
        s0 = lo + (start - 1)
    else:
        # Spark: a negative start beyond the list head yields an EMPTY
        # slice, not a clamped one
        cand = hi + start
        s0 = jnp.where(cand >= lo, cand, hi)
    s0 = jnp.minimum(s0, hi)
    e0 = jnp.minimum(s0 + length, hi)
    new_lens = jnp.maximum(e0 - s0, 0)
    # rebuild offsets for a COMPACT child: gather kept elements densely
    # (explode-style parent mapping over the kept ranges)
    n = col.size
    new_off = jnp.concatenate(
        [jnp.zeros((1,), jnp.int64),
         jnp.cumsum(new_lens.astype(jnp.int64))])
    child = col.children[0]
    child_n = int(child.size)
    out_n = child_n  # static bound
    k = jnp.arange(out_n, dtype=jnp.int64)
    parent = jnp.clip(
        jnp.searchsorted(new_off, k, side="right") - 1, 0,
        max(n - 1, 0)).astype(jnp.int32)
    j = k - new_off[parent]
    live = k < new_off[-1]
    src = jnp.clip(s0[parent] + j.astype(jnp.int32), 0,
                   max(child_n - 1, 0))
    new_child = _gather_any(child, src, live)
    return Column(col.dtype, new_off.astype(jnp.int32), col.validity,
                  children=[new_child])


# ---------------------------------------------------------------------------
# Padded wire layout for LIST columns (the padded-strings trick
# generalized): data = int32 per-row lengths, children[0] = an element
# column whose data is an (n, L) matrix with (n, L) element validity.
# This is the layout the ICI shuffle ships (every lane is a dense
# row-aligned buffer); offsets-layout lists convert at the boundary.
# ---------------------------------------------------------------------------


def is_padded_list(col: Column) -> bool:
    """Delegates to the Column property (single source of truth: the
    mandatory 2-D element validity is the layout marker)."""
    return col.is_padded_list


def max_list_length(col: Column) -> int:
    """Host-side max list length (0-safe). Only valid outside jit."""
    import numpy as np

    off = np.asarray(col.data)
    if off.shape[0] <= 1:
        return 0
    return int(np.max(off[1:] - off[:-1]))


@func_range("pad_lists")
def pad_lists(col: Column, max_len: int | None = None) -> Column:
    """Offsets layout -> padded wire layout. ``max_len`` must bound every
    row's length (host-computed by default; pass it statically inside
    jit). Plain fixed-width elements only (DECIMAL128 limb pairs would
    need a rank-3 matrix the Column invariants reject; strings-in-lists
    are not wire-supported — explode them instead).

    The (n, L) element validity is MANDATORY in this layout — it is the
    layout marker (see Column.is_padded_list) and carries the element
    null mask; for null-free children it costs one bool lane on the
    wire that could in principle be derived from the lengths, a
    documented trade-off for unambiguous layout detection."""
    if col.dtype.type_id != TypeId.LIST:
        raise TypeError(f"pad_lists needs a LIST column, got {col.dtype}")
    if is_padded_list(col):
        return col
    child = col.children[0]
    if not child.dtype.is_fixed_width or child.dtype.is_string:
        raise NotImplementedError(
            "pad_lists supports plain fixed-width elements only")
    if max_len is None:
        max_len = max_list_length(col)
    L = max(int(max_len), 1)
    off = col.data.astype(jnp.int32)
    lens = off[1:] - off[:-1]
    n = col.size
    child_n = int(child.size)
    j = jnp.arange(L, dtype=jnp.int32)[None, :]
    src = jnp.clip(off[:-1][:, None] + j, 0, max(child_n - 1, 0))
    in_row = j < lens[:, None]
    if child_n:
        mat = child.data[src]
        evalid = child.valid_mask()[src] & in_row
    else:
        shape = (n, L) + child.data.shape[1:]
        mat = jnp.zeros(shape, child.data.dtype)
        evalid = jnp.zeros((n, L), jnp.bool_)
    mat = jnp.where(in_row, mat, jnp.zeros_like(mat))
    elem = Column(child.dtype, mat, evalid)
    return Column(col.dtype, lens.astype(jnp.int32), col.validity,
                  children=[elem])


@func_range("unpad_lists")
def unpad_lists(col: Column) -> Column:
    """Padded wire layout -> offsets layout (dense compacted child via
    the explode-style parent mapping)."""
    if not is_padded_list(col):
        return col
    lens = col.data.astype(jnp.int64)
    elem = col.children[0]
    n, L = int(elem.data.shape[0]), int(elem.data.shape[1])
    offsets = jnp.concatenate(
        [jnp.zeros((1,), jnp.int64), jnp.cumsum(lens)])
    cap = max(n * L, 1)
    k = jnp.arange(cap, dtype=jnp.int64)
    parent = jnp.clip(
        jnp.searchsorted(offsets, k, side="right") - 1, 0,
        max(n - 1, 0)).astype(jnp.int32)
    j = jnp.clip(k - offsets[parent], 0, L - 1).astype(jnp.int32)
    live = k < offsets[-1]
    flatv = elem.data[parent, j]
    flat_valid = elem.valid_mask()[parent, j] & live
    flatv = jnp.where(live, flatv, jnp.zeros_like(flatv))
    child = Column(elem.dtype, flatv, flat_valid)
    return Column(col.dtype, offsets.astype(jnp.int32), col.validity,
                  children=[child])
