"""Bounded-domain groupby planning — the facility behind planned q1.

When every key column's candidate values are known at plan time, grouping
lowers to ``groupby_aggregate_bounded`` — zero sort, zero gather, zero scan,
zero scatter; one streaming masked-reduction pass the TPU backend fuses —
where the general groupby sorts every row. What that is worth is measured by
the cells ``sf1_q1_planned_fresh`` and ``sf1_q1_general_fresh`` (PERF.md:
planned over general q1 at SF1 on one chip, 6.4 times in rows/s). It was
hand-wired into q1 (``_Q1_RF_DOMAIN``); this module makes it a planner
facility any query can use (VERDICT r4 item 3).

Domain sources mirror what a production Spark planner sees:

* ``scalar_domain`` / ``string_domain`` — DDL facts (CHAR(1) check
  constraints, enum-like dictionaries: TPC-H fixes l_returnflag to A/N/R,
  l_shipmode to 7 values, o_orderpriority to 5).
* ``observed_domain`` — planning-time column statistics (host-side
  distinct scan; the role the Parquet dictionary page / ORC column
  statistics play in production — the readers under
  ``spark_rapids_jni_tpu/parquet`` decode those pages).
* ``month_domain`` + ``month_bucket`` — date columns bucketed by calendar
  month: the bucket cardinality is tiny even when the date cardinality is
  not, so date-bucketed rollups ride the sort-free path.

``plan_groupby`` lowers to the bounded plan when every key carries a
domain and the slot count fits the budget, else falls back to the general
``groupby_aggregate`` — with ``domain_miss`` as the runtime escape hatch
(out-of-domain data re-plans, it never silently drops; the
``narrowing_overflow`` posture).

Reference analogue: cuDF's groupby dispatches hash vs. sort strategies on
key properties (vendored capability, /root/reference/build-libcudf.xml:
34-60); this is the TPU-shaped version of that dispatch, with the planner
supplying the cardinality facts Spark's optimizer carries.
"""

from __future__ import annotations

from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from spark_rapids_jni_tpu import types as t
from spark_rapids_jni_tpu.columnar import Column, Table
from spark_rapids_jni_tpu.ops.groupby import (
    bounded_group_layout,
    groupby_aggregate,
    groupby_aggregate_bounded,
)
from spark_rapids_jni_tpu.ops.sort import sort_table
from spark_rapids_jni_tpu.runtime.resilience import FatalExecutionError
from spark_rapids_jni_tpu.utils.tracing import func_range


class Domain(NamedTuple):
    """Planner-declared candidate values for one groupby key column.

    ``values`` are raw storage scalars for fixed-width keys, or ``str``
    for string keys; always kept sorted so group output order is the
    deterministic ORDER BY ... NULLS LAST. ``source`` is provenance
    ("ddl", "dictionary", "observed", "derived") — recorded for plan
    explainability, never branched on.
    """

    values: tuple
    kind: str  # "scalar" | "string"
    source: str


def scalar_domain(values: Sequence, source: str = "ddl") -> Domain:
    vals = tuple(sorted(set(int(v) for v in values)))
    if not vals:
        raise ValueError("empty domain")
    return Domain(vals, "scalar", source)


def string_domain(values: Sequence[str], source: str = "ddl") -> Domain:
    # byte-wise sort: the same collation packed_sort_keys uses, so the
    # bounded output order matches what sort_table would have produced
    vals = tuple(sorted(set(values), key=lambda s: s.encode()))
    if not vals:
        raise ValueError("empty domain")
    return Domain(vals, "string", source)


_OBSERVED_DEFAULT_CAP = 1024


def observed_domain(col: Column, max_size: int = _OBSERVED_DEFAULT_CAP,
                    source: str = "observed") -> Domain | None:
    """Planning-time statistics: the column's distinct values, gathered
    host-side (this runs at PLAN time over a sample/stats source, not in
    the jitted query — production gets the same facts from Parquet
    dictionary pages or ORC statistics without touching row data).
    Returns None when cardinality exceeds ``max_size`` — the key is not
    boundable and the caller stays on the general plan."""
    if col.dtype.is_string:
        vals = sorted({v for v in col.to_pylist() if v is not None},
                      key=lambda s: s.encode())
        if len(vals) > max_size:
            return None
        return Domain(tuple(vals), "string", source) if vals else None
    if col.dtype.is_decimal128 or col.children is not None:
        return None
    data = np.asarray(col.data)
    if col.validity is not None:
        data = data[np.asarray(col.validity)]
    vals = np.unique(data)
    if vals.size > max_size or vals.size == 0:
        return None
    return Domain(tuple(int(v) for v in vals), "scalar", source)


def domain_from_parquet(path, column: int,
                        max_size: int = _OBSERVED_DEFAULT_CAP,
                        sample_row_groups: int = 1) -> Domain | None:
    """Planner-time domain derivation from a Parquet file: decode the
    first ``sample_row_groups`` row groups of one column through the
    native reader and take the observed distinct values.

    This is the practical stand-in for reading the dictionary PAGE
    directly (the native reader decodes dictionary pages internally but
    does not yet expose their value arrays through the C ABI): a
    planning-time sample, so the derived domain is declared with
    ``source="observed"`` and the runtime ``domain_miss`` check remains
    the correctness backstop — exactly the posture that makes an
    inaccurate sample a re-plan, never a wrong answer.
    """
    from spark_rapids_jni_tpu.parquet.reader import (
        read_table,
        row_group_info,
    )

    n_groups = len(row_group_info(path))
    groups = list(range(min(sample_row_groups, n_groups)))
    tbl = read_table(path, columns=[column], row_groups=groups)
    return observed_domain(tbl.column(0), max_size=max_size)


def month_code(year: int, month: int) -> int:
    """Static month-bucket code: year*12 + (month-1)."""
    return year * 12 + (month - 1)


def month_bucket(col: Column) -> Column:
    """Derived key column: the calendar-month bucket of a date column
    (int32 ``year*12 + month-1``), jit-traceable. Date cardinality is
    unbounded; month-bucket cardinality over any query's date range is
    tiny, which is what puts date-bucketed rollups on the sort-free
    plan."""
    from spark_rapids_jni_tpu.ops import datetime as dt

    y = dt.year(col)
    mth = dt.month(col)
    code = y.data.astype(jnp.int32) * 12 + (mth.data.astype(jnp.int32) - 1)
    return Column(t.INT32, code, col.validity)


def month_domain(year_lo: int, month_lo: int, year_hi: int, month_hi: int,
                 source: str = "ddl") -> Domain:
    """All month-bucket codes in [year_lo-month_lo, year_hi-month_hi]
    inclusive — the domain a planner derives from a date-range predicate
    or min/max column statistics."""
    lo = month_code(year_lo, month_lo)
    hi = month_code(year_hi, month_hi)
    if hi < lo:
        raise ValueError("month range is empty")
    return Domain(tuple(range(lo, hi + 1)), "scalar", source)


def encode_string_key(col: Column, domain: Domain) -> Column:
    """Dictionary-encode a string key against its declared domain, fully
    on device: one padded-bytes equality compare per domain value (XLA
    fuses the d compares into a single pass over the char matrix — no
    sort, no hash table). Code = index in the sorted domain; rows whose
    value is outside the domain get code ``len(domain)`` which
    ``groupby_aggregate_bounded`` flags as ``domain_miss``; null rows
    stay null (the null slot)."""
    from spark_rapids_jni_tpu.ops.strings import pad_strings

    if domain.kind != "string":
        raise ValueError("encode_string_key needs a string domain")
    col = pad_strings(col)
    w = col.chars.shape[1] if col.chars is not None else 0
    n = col.chars.shape[0]
    k = len(domain.values)
    code = jnp.full((n,), k, jnp.int32)
    for idx, v in enumerate(domain.values):
        b = v.encode()
        if len(b) > w:
            continue  # longer than every row: cannot match
        target = np.zeros((w,), np.uint8)
        target[: len(b)] = np.frombuffer(b, np.uint8)
        hit = jnp.all(col.chars == jnp.asarray(target)[None, :], axis=1) \
            if w else jnp.full((n,), len(b) == 0)
        code = jnp.where(hit, jnp.int32(idx), code)
    return Column(t.INT32, code, col.validity)


class DensePkJoinResult(NamedTuple):
    """LEFT PK-join result: one output row per probe row (PK fanout is
    exactly <= 1, so there is no join-maps machinery, no capacity
    estimate, no overflow). Probe columns first, then build columns
    (the apply_join_maps convention); unmatched probe rows carry null
    build columns."""

    table: Table
    matched: jnp.ndarray       # bool[n] probe rows with a build match
    total: jnp.ndarray         # scalar match count
    # True when the declared layout lied: a clustered slot held a
    # DIFFERENT valid key (clustered mode), or the build side held
    # duplicate keys (sorted mode). The caller re-plans on the general
    # join — the domain_miss posture, never a silent wrong answer.
    pk_violation: jnp.ndarray


@func_range("dense_pk_join")
def dense_pk_join(
    probe: Table,
    build: Table,
    probe_key: int,
    build_key: int,
    key_lo: int,
    key_hi: int,
    clustered: bool = False,
    probe_clustered: bool = False,
) -> DensePkJoinResult:
    """LEFT join against a DECLARED dense primary-key build side.

    The planner fact: ``build``'s key column holds unique keys from the
    contiguous range [key_lo, key_hi] (a TPC-H DDL fact — orderkey /
    custkey / partkey are dense 1..N — and what a real planner reads
    from PK constraints + min/max statistics).

    * ``clustered=True``: build row i holds key ``key_lo + i`` (the
      layout of a loaded dimension or generated key column). The join
      is then pure arithmetic and row gathers — ZERO sorts anywhere, and
      the general join's build-side lexsort + probe searchsorted vanish.
      The declaration is VERIFIED, not trusted, and where the build side
      lives: one pass over its ``nb`` rows holds every non-null build key
      to ``key_lo + i``, and any other raises ``pk_violation``. That is
      STRICTER than comparing the gathered build key with the probe key
      at every probe row, as this join did before PR 40: a misplaced key
      that no probe row happens to hit is caught too, and nothing that
      check stopped gets through. Once it holds, the build key at a
      matched probe row IS the probe key, so the probe's rows gather one
      bit, the build key's validity (did my key survive the build side's
      filters), and the build key column of the output is the probe key's
      data under ``matched``. What is left is the gathers, the bit's and
      one of each other build column's data and mask; inside a jitted
      region a column that nothing downstream reads costs none (a
      ``Project`` that drops it leaves its gather without a user). Planned
      q3 at SF1 on a v5e (PERF.md section 5, traced runs of PR 40) takes
      0.068 s for 6,001,215 probe rows (a bucket of 8,388,608) against
      1,500,000 build rows, all of it the one gather of the bit, where the
      gathered key, its mask and the order's date and priority took 0.40 s
      in five gathers.
    * ``clustered=False``: one lexsort of the (small) build side; the
      probe side is searchsorted + gather. Duplicate build keys raise
      ``pk_violation`` (PK uniqueness is part of the declaration).

    * ``probe_clustered=True`` (with ``clustered=False``): it is the
      PROBE side that is laid out by the key, probe row i holding key
      ``key_lo + i`` (a loaded dimension on the preserved side of a LEFT
      OUTER join: q13's customer), and the build side holds unique keys
      of the range in any order (the groups of a groupby keyed by the
      foreign key). Every build row then writes its row number into the
      slot of its key, one scatter of the build's rows with no two alike,
      and every probe row gathers the build row its own slot names: no
      sort and no binary search (1,500,001 needles took 0.5 s on a v5e,
      ``ops/groupby.py``). A probe row that holds another key than its
      position says, or two build rows with one key (the slot then names
      one of them, and the other does not find itself there), raise
      ``pk_violation``.

    Build rows with NULL keys are filtered rows (the _null_where WHERE
    idiom): probes pointing at them are unmatched, not violations.
    """
    from spark_rapids_jni_tpu.ops.sort import gather

    if clustered and probe_clustered:
        raise ValueError("a dense-PK join is laid out by its key on the "
                         "build side or on the probe side, not on both")
    n = probe.num_rows
    nb = build.num_rows
    pk = probe.column(probe_key)
    bk = build.column(build_key)
    if pk.dtype.is_string or bk.dtype.is_string:
        raise NotImplementedError(
            "dense PK keys are integers (dictionary-encode first)")
    in_range = (pk.valid_mask()
                & (pk.data >= pk.data.dtype.type(key_lo))
                & (pk.data <= pk.data.dtype.type(key_hi)))
    if clustered:
        if key_hi - key_lo + 1 != nb:
            raise ValueError(
                f"clustered dense PK needs build rows == key range "
                f"({nb} != {key_hi - key_lo + 1})")
        pos = jnp.clip(pk.data - key_lo, 0, nb - 1).astype(jnp.int32)
        bvalid = bk.valid_mask()
        # a slot holding a DIFFERENT valid key means the layout is not
        # clustered after all: checked over the build's rows, no gather
        at_home = bk.data == (jnp.arange(nb, dtype=bk.data.dtype)
                              + bk.data.dtype.type(key_lo))
        pk_violation = jnp.any(bvalid & ~at_home)
        matched = in_range & bvalid[pos]
    elif probe_clustered:
        if key_hi - key_lo + 1 != n:
            raise ValueError(
                f"a probe side clustered by the dense key needs probe rows "
                f"== key range ({n} != {key_hi - key_lo + 1})")
        bvalid = bk.valid_mask()
        b_in = (bvalid & (bk.data >= bk.data.dtype.type(key_lo))
                & (bk.data <= bk.data.dtype.type(key_hi)))
        rows = jnp.arange(nb, dtype=jnp.int32)
        # a build row outside the range writes nowhere (slot n is dropped)
        slot = jnp.where(b_in, bk.data - bk.data.dtype.type(key_lo),
                         n).astype(jnp.int32)
        row_at = jnp.full((n,), -1, jnp.int32).at[slot].set(
            rows, mode="drop", unique_indices=True)
        at_home = pk.data == (jnp.arange(n, dtype=pk.data.dtype)
                              + pk.data.dtype.type(key_lo))
        matched = in_range & at_home & (row_at >= 0)
        pos = jnp.clip(row_at, 0, max(nb - 1, 0))
        found = row_at[jnp.clip(slot, 0, n - 1)]
        pk_violation = (jnp.any(pk.valid_mask() & ~at_home)
                        | jnp.any(bvalid & ~b_in)
                        | jnp.any(b_in & (found != rows)))
    else:
        # null keys (filtered rows) overwritten with the dtype max so
        # the sorted array is GLOBALLY monotone — sorting raw data with
        # a null rank leaves the tail unsorted and breaks the binary
        # search for large valid keys (silently dropped matches)
        bvalid = bk.valid_mask()
        dt_max = np.iinfo(np.dtype(bk.data.dtype)).max
        if key_hi >= dt_max:
            # the declared key range touches the null sentinel: a
            # legitimate key equal to dtype max would be overwritten
            # into the null slot and silently drop its matches
            raise ValueError(
                f"dense PK range [{key_lo}, {key_hi}] reaches "
                f"iinfo({np.dtype(bk.data.dtype).name}).max, the null "
                f"sentinel; widen the key dtype or shrink the range")
        key_clean = jnp.where(bvalid, bk.data,
                              jnp.asarray(dt_max, bk.data.dtype))
        perm = jnp.argsort(key_clean).astype(jnp.int32)
        skey = key_clean[perm]
        n_valid = jnp.sum(bvalid.astype(jnp.int32))
        pos0 = jnp.searchsorted(skey, pk.data).astype(jnp.int32)
        within = pos0 < n_valid
        hit = within & (skey[jnp.clip(pos0, 0, nb - 1)] == pk.data)
        pos = perm[jnp.clip(pos0, 0, nb - 1)]
        matched = in_range & hit
        dup = jnp.any((skey[1:] == skey[:-1])
                      & (jnp.arange(1, nb) < n_valid)) if nb > 1 \
            else jnp.bool_(False)
        # the declaration also claims build keys live in [lo, hi]: an
        # out-of-range valid build key is a lie, not an unmatched row
        oor = jnp.any(bvalid & ((bk.data < bk.data.dtype.type(key_lo))
                                | (bk.data > bk.data.dtype.type(key_hi))))
        pk_violation = dup | oor

    cols = list(build.columns)
    if clustered:
        # the layout holds, so the build key at a matched row is the probe
        # key itself: no gather reads it
        del cols[build_key]
    cols = list(gather(Table(cols), pos).columns)
    if clustered:
        cols.insert(build_key,
                    Column(bk.dtype, pk.data.astype(bk.data.dtype)))
    out_cols = list(probe.columns) + [
        Column(c.dtype, c.data, c.valid_mask() & matched, chars=c.chars)
        for c in cols]
    return DensePkJoinResult(
        Table(out_cols), matched,
        jnp.sum(matched.astype(jnp.int64)), pk_violation)


class NarrowedKeys(NamedTuple):
    """``narrow_group_keys``' result: the table to group, the check of the
    declaration, and what ``widen_group_keys`` needs on the way out."""

    table: Table
    # True when a real row's non-null key lies outside its declared range:
    # the rebased key wrapped, rows of different keys may share a group.
    # The caller refuses the result, as it does on ``pk_violation``.
    out_of_range: jnp.ndarray
    # (position among the keys, the key's own dtype, lo) of each rebased key
    narrowed: tuple


def _range_dtype(span: int):
    """The narrowest unsigned storage type that holds ``0..span``; None
    past 32 bits."""
    for dt in (t.UINT8, t.UINT16, t.UINT32):
        if span < 1 << (8 * dt.storage_dtype.itemsize):
            return dt
    return None


def narrow_group_keys(table: Table, keys: Sequence[int], ranges: Sequence,
                      row_valid: jnp.ndarray | None = None) -> NarrowedKeys:
    """Rebase every groupby key with a DECLARED range to the width the
    range takes. ``ranges`` has one entry a key: None, or ``(lo, hi)``,
    the planner's fact that every non-null key of a real row lies in
    ``[lo, hi]`` (a PK constraint, a Parquet footer's min/max, a dense key
    a join two nodes earlier verified row by row). Such a key becomes
    ``data - lo`` in the narrowest unsigned type that holds ``hi - lo``,
    its validity kept: the same groups in the same order, and a sort key
    of 8, 16 or 32 bits where the schema's ``bigint`` was two words and,
    with its null rank and the row-valid bit, a third (``ops/sort.py``: 80
    bits sort word by word in a loop, 48 in one variadic sort). A range
    that narrows nothing (past 32 bits, or as wide as the key's own type)
    leaves its key alone and is not checked: nothing rests on it.

    Rowwise, so it is the same on one chip and on a chip's share of the
    rows. The declaration is VERIFIED, not trusted: one pass over the rows
    in the key's own type, before the narrowing cast."""
    if len(ranges) != len(keys):
        raise ValueError(
            f"key_ranges has {len(ranges)} entries for {len(keys)} keys")
    cols = list(table.columns)
    out_of_range = jnp.bool_(False)
    narrowed = []
    for at, (k, rng) in enumerate(zip(keys, ranges)):
        if rng is None:
            continue
        lo, hi = int(rng[0]), int(rng[1])
        c = cols[k]
        if (c.dtype.is_string or c.dtype.is_decimal128
                or c.dtype.storage_dtype.kind not in "iu"):
            raise ValueError(
                f"a key range needs an integer key; key {k} is {c.dtype}")
        info = np.iinfo(c.dtype.storage_dtype)
        if not info.min <= lo <= hi <= info.max:
            raise ValueError(
                f"key range [{lo}, {hi}] is empty or leaves key {k}'s "
                f"{c.dtype.storage_dtype.name}")
        narrow = _range_dtype(hi - lo)
        if (narrow is None or narrow.storage_dtype.itemsize
                >= c.dtype.storage_dtype.itemsize):
            continue
        own = c.data.dtype.type
        inside = (c.data >= own(lo)) & (c.data <= own(hi))
        real = c.valid_mask() if row_valid is None \
            else c.valid_mask() & row_valid
        out_of_range = out_of_range | jnp.any(real & ~inside)
        cols[k] = Column(narrow, (c.data - own(lo)).astype(narrow.jnp_dtype),
                         c.validity)
        narrowed.append((at, c.dtype, lo))
    return NarrowedKeys(Table(cols), out_of_range, tuple(narrowed))


def widen_group_keys(grouped: Table, narrowed: tuple) -> Table:
    """A groupby's result (its keys first) with the keys
    ``narrow_group_keys`` rebased given back their own type and values:
    one pass over the m group rows. Validity as the groupby left it; the
    bytes under a null key are what the rebase of the stored bytes gives
    back (the stored bytes themselves where they lie in the range)."""
    cols = list(grouped.columns)
    for at, dtype, lo in narrowed:
        c = cols[at]
        data = c.data.astype(dtype.jnp_dtype) + dtype.jnp_dtype.type(lo)
        cols[at] = Column(dtype, data, c.validity)
    return Table(cols)


def _dense_prologue(gid: jnp.ndarray, m: int, block: int,
                    values: jnp.ndarray | None):
    """Shared scaffolding of the dense-id reductions: range-check in
    the INPUT dtype before narrowing (an int64 gid beyond 2^31 must not
    wrap into [0, m)), clamp the block, pad to a block multiple with
    the discard sentinel m, and reshape for the scan. Returns
    (gid_blocks int32[(nb, block)], value_blocks int64 | None)."""
    n = gid.shape[0]
    block = min(block, n)
    pad = (-n) % block
    safe = jnp.where((gid >= 0) & (gid < m), gid,
                     jnp.asarray(m, gid.dtype)).astype(jnp.int32)
    if pad:
        safe = jnp.concatenate([safe, jnp.full((pad,), jnp.int32(m))])
    vb = None
    if values is not None:
        v64 = values.astype(jnp.int64)
        if pad:
            v64 = jnp.concatenate([v64, jnp.zeros((pad,), jnp.int64)])
        vb = v64.reshape(-1, block)
    return safe.reshape(-1, block), vb


class PlanBudgetExceeded(FatalExecutionError, ValueError):
    """A groupby's distinct-group count exceeded ``max_budget``.

    Classified fatal in the resilience taxonomy (the budget is a caller
    contract, not a transient condition) while remaining the ValueError
    this API historically raised, so existing ``except ValueError`` /
    message-matching callers are unaffected."""


def plan_groupby_auto(
    table: Table,
    keys: Sequence[int],
    aggs: Sequence[tuple[int, str]],
    domains: Sequence["Domain | None"],
    budget: int = 4096,
    max_budget: int | None = None,
    row_valid: jnp.ndarray | None = None,
) -> "PlannedGroupBy":
    """Host wrapper completing the overflow posture: when the general
    fallback drops groups (``overflowed``), double the budget and
    retry until the result is complete (the groupby_aggregate_auto
    pattern). The bounded plan never overflows (slot count checked at
    plan time), so retries only occur on the general path. Growth runs
    through the shared resilience ladder — budget schedule min(b·2^k,
    cap) preserved exactly — and exhaustion raises
    :class:`PlanBudgetExceeded` (a ``FatalExecutionError`` that is still
    the ValueError callers match on)."""
    from spark_rapids_jni_tpu.runtime import resilience

    cap = max_budget if max_budget is not None else max(table.num_rows, 1)
    # clamp both ways: a sub-positive budget would loop forever (0*2 == 0)
    # and a starting budget above the cap would silently ignore it
    b = min(max(budget, 1), cap)
    if not resilience.enabled():
        while True:
            res = plan_groupby(table, keys, aggs, domains, budget=b,
                               row_valid=row_valid)
            if not bool(res.overflowed) or b >= cap:
                if bool(res.overflowed):
                    raise PlanBudgetExceeded(
                        f"groupby exceeded max_budget={cap} distinct groups")
                return res
            b = min(b * 2, cap)

    def _attempt(budget_):
        res = plan_groupby(table, keys, aggs, domains, budget=budget_,
                           row_valid=row_valid)
        return res, bool(res.overflowed), None

    return resilience.escalate(
        "plan_groupby_auto", _attempt, seam="dispatch.execute",
        initial=b, growth=2, max_capacity=cap,
        exhaust=lambda c, steps: PlanBudgetExceeded(
            f"groupby exceeded max_budget={cap} distinct groups"))


@func_range("dense_id_counts")
def dense_id_counts(gid: jnp.ndarray, m: int,
                    block: int = 8192) -> jnp.ndarray:
    """COUNT(*) per dense group id WITHOUT sort or scatter: a
    ``lax.scan`` over row blocks, each step materializing one
    (block, m) one-hot compare and reducing it — total traffic n*m
    bools, streamed block-by-block so VMEM holds one tile at a time.

    This is the groupby for mid-cardinality DENSE keys (m in the
    hundreds-to-thousands): too many groups for the bounded
    masked-reduction unroll (m Python-level mask terms), no sort needed
    because the key IS the group id. ``gid`` entries outside [0, m)
    (invalid/filtered/padding rows) count nowhere. Exact: int32
    accumulation, counts <= n < 2^31."""
    n = gid.shape[0]
    if n == 0:
        return jnp.zeros((m,), jnp.int64)
    gb, _ = _dense_prologue(gid, m, block, None)
    slots = jnp.arange(m, dtype=jnp.int32)[None, :]

    def step(acc, blk):
        oh = blk[:, None] == slots
        return acc + jnp.sum(oh, axis=0, dtype=jnp.int32), None

    # init derives from the input so its varying-manner annotation
    # matches the carry under shard_map (a plain zeros constant is
    # 'replicated' and the scan rejects the carry type mismatch)
    init = jnp.zeros((m,), jnp.int32) + gb[0, 0] * 0
    acc, _ = jax.lax.scan(step, init, gb)
    return acc.astype(jnp.int64)


@func_range("dense_id_sums")
def dense_id_sums(gid: jnp.ndarray, values: jnp.ndarray, m: int,
                  block: int = 1024) -> jnp.ndarray:
    """SUM(values) per dense group id, exact int64, without sort or
    scatter — the ``dense_id_counts`` scheme with a masked value
    broadcast per block: each scan step materializes one
    (block, m) int64 select and column-reduces it. ``gid`` entries
    outside [0, m) contribute nowhere; ``values`` rows whose slot they
    feed must already be zeroed for SQL null semantics (callers mask
    with validity before the call)."""
    n = gid.shape[0]
    if n == 0:
        return jnp.zeros((m,), jnp.int64)
    gb, vb = _dense_prologue(gid, m, block, values)
    slots = jnp.arange(m, dtype=jnp.int32)[None, :]

    def step(acc, xs):
        blk_gid, blk_val = xs
        sel = jnp.where(blk_gid[:, None] == slots,
                        blk_val[:, None], jnp.int64(0))
        return acc + jnp.sum(sel, axis=0), None

    init = jnp.zeros((m,), jnp.int64) + vb[0, 0] * 0  # vma-matching init
    acc, _ = jax.lax.scan(step, init, (gb, vb))
    return acc


class PlannedGroupBy(NamedTuple):
    """Uniform result of ``plan_groupby`` over both lowerings.

    ``table`` rows are in key order with null-key groups last. On the
    bounded plan the shape is the static slot count m and ``present``
    marks live groups; on the general plan the shape is the padded
    ``max_groups`` budget and ``present`` marks the first
    ``num_groups`` rows. ``domain_miss`` is False on the general plan
    (nothing to miss). ``overflowed`` is the general plan's escape
    hatch: True when the data held more groups than the budget (the
    excess was dropped — grow the budget and retry, the
    groupby_aggregate_auto posture); always False on the bounded plan,
    whose slot count is checked at plan time."""

    table: Table
    present: jnp.ndarray
    domain_miss: jnp.ndarray
    lowered: str  # "bounded" | "general" — static plan fact
    # bool or jnp scalar; a plain False default keeps module import free
    # of backend initialization (import-hygiene contract)
    overflowed: object = False


@func_range("plan_groupby")
def plan_groupby(
    table: Table,
    keys: Sequence[int],
    aggs: Sequence[tuple[int, str]],
    domains: Sequence[Domain | None],
    budget: int = 4096,
    row_valid: jnp.ndarray | None = None,
) -> PlannedGroupBy:
    """Lower a groupby to the sort-free bounded plan when the planner can
    bound every key, else to the general sort-based plan.

    Bounded eligibility: every key has a declared ``Domain``, the slot
    count ``prod(len(d)+1)`` fits ``budget``, and every agg is in the
    associative single-pass set (sum/count/mean/min/max). String keys are
    dictionary-encoded on device (``encode_string_key``) and decoded back
    to static string columns at the output — the decode costs nothing at
    runtime (trace-time constants from ``bounded_group_layout``).

    ``row_valid``: bool[n] marking rows that EXIST (shard_table padding
    contract). On the bounded plan non-rows join no slot; on the
    general fallback their keys and values are nulled, so they fold
    into the null-key pseudo-group every consumer already discards.
    """
    if len(domains) != len(keys):
        raise ValueError("one Domain (or None) per key required")
    # NOTE: no row-count condition — lowering is a static plan fact
    # (empty tables take the bounded plan too; groupby_aggregate_bounded
    # handles n == 0 with its static slot table)
    bounded_ok = (
        all(d is not None for d in domains)
        and all(op in ("sum", "count", "mean", "min", "max")
                for _, op in aggs)
        and int(np.prod([len(d.values) + 1 for d in domains])) <= budget
    )
    if not bounded_ok:
        if row_valid is not None:
            table = Table([
                Column(c.dtype, c.data, c.valid_mask() & row_valid,
                       chars=c.chars, children=c.children)
                for c in table.columns
            ])
        g = groupby_aggregate(table, keys=list(keys), aggs=list(aggs),
                              max_groups=min(budget, table.num_rows) or 1)
        srt = sort_table(g.table, list(range(len(keys))),
                         nulls_first=[False] * len(keys))
        present = (jnp.arange(srt.num_rows, dtype=jnp.int32)
                   < g.num_groups)
        # overflowed surfaces budget-dropped groups — the caller's signal
        # to grow and retry; never silently swallowed
        return PlannedGroupBy(srt, present, jnp.bool_(False), "general",
                              g.overflowed)

    # bounded plan: encode string keys to dense codes, run the static
    # masked-reduction groupby, decode codes back to strings
    work_cols = list(table.columns)
    key_domains: list[Sequence[int]] = []
    string_positions: dict[int, Domain] = {}
    for pos, (k, dom) in enumerate(zip(keys, domains)):
        if dom.kind == "string":
            work_cols[k] = encode_string_key(table.column(k), dom)
            key_domains.append(tuple(range(len(dom.values))))
            string_positions[pos] = dom
        else:
            key_domains.append(dom.values)
    res = groupby_aggregate_bounded(
        Table(work_cols), keys=list(keys), aggs=list(aggs),
        key_domains=key_domains, row_valid=row_valid)

    if string_positions:
        _, m, slot_codes, order = bounded_group_layout(
            [len(d) for d in key_domains])
        out_cols = list(res.table.columns)
        for pos, dom in string_positions.items():
            # static decode, built in numpy (trace-time constants): group
            # slot i's string is fully determined by the layout
            w = max((len(v.encode()) for v in dom.values), default=1) or 1
            mat = np.zeros((m, w), np.uint8)
            lens = np.zeros((m,), np.int32)
            valid_np = np.zeros((m,), bool)
            for i in range(m):
                code = slot_codes[order[i], pos]
                if code < len(dom.values):
                    b = dom.values[code].encode()
                    mat[i, : len(b)] = np.frombuffer(b, np.uint8)
                    lens[i] = len(b)
                    valid_np[i] = True
            out_cols[pos] = Column(
                t.STRING, jnp.asarray(lens),
                jnp.asarray(valid_np) & res.present,
                chars=jnp.asarray(mat))
        return PlannedGroupBy(Table(out_cols), res.present,
                              res.domain_miss, "bounded")
    return PlannedGroupBy(res.table, res.present, res.domain_miss,
                          "bounded")
