"""Whole-stage fusion: compile query plans into single donated executables.

The reference ships ONE fat native library so a Spark stage runs as few
device launches as possible; Flare (PAPERS.md) shows whole-stage native
compilation is the dominant win for Spark-shaped plans. Our models were
still executing op-by-op: every filter/project/groupby/join/sort went
through ``dispatch.call`` as its OWN executable, materializing each
intermediate Table in HBM and paying per-op dispatch overhead. This module
closes that gap: a small logical-plan IR (scan / filter / project /
groupby / join / sort / limit nodes over ``Table``) plus a fuser that
composes a fusible region's per-op device functions into ONE traced
callable and dispatches it once through ``dispatch.call`` — so a fused
region inherits shape bucketing and the executable cache, and a whole
query compiles to one executable per bucket instead of one per op per
bucket.

Region discipline
-----------------
``execute`` runs ONE fusible region. Genuine host boundaries — out-of-core
partial compaction (``trim_table`` between chunk and merge), the planner
``domain_miss`` / ``pk_violation`` re-plan check — stay in the model's
host wrapper, which composes one plan per region (see
``models/tpch.tpch_q1_outofcore`` for the two-region shape). The shuffle
between a distributed partial and its merge is NOT one: a region whose
scans are row-sharded over a mesh axis is one ``shard_map`` across the
chips, the ``all_to_all`` inside it (see "lowering over a mesh"). Inside a
region every op is inlined into the single trace: the per-op
``dispatch.call`` sites detect the tracer inputs and take their inline
path, so the op implementations themselves are byte-for-byte the staged
ones.

Bit-identity
------------
The region's inputs are bucket-padded ONCE at the region boundary; the
per-group ``row_valid`` masks thread through the same user-level
``row_valid`` parameters the staged ops already expose (``join``'s
``left_row_valid``, ``groupby_aggregate``'s and ``plan_groupby``'s
``row_valid``, ``sort_order``'s phantom-last ranking), so a fused region
computes exactly what the staged path computes at the same bucket — every
fused query is bit-identical to its op-by-op reference at any row count
(tests/test_fusion.py pins this at 1, 2^k-1, 2^k, 2^k+1 rows with null
tails).

Donation
--------
``execute(..., donate_inputs=True)`` is the caller's declaration that the
bound input tables are DEAD after the call (an intermediate table the plan
runner owns, an out-of-core chunk nothing else reads): the fused
executable then compiles with ``donate_argnums`` on its row param so XLA
reuses those buffers for outputs instead of double-buffering HBM
(``fusion.donate`` config gates this; bytes are accounted under
``dispatch.donated_bytes``).

Telemetry: ``fusion.regions`` / ``fusion.nodes_fused`` /
``fusion.staged_regions`` counters; executables per query are the
``dispatch.compile.fusion.<plan>`` counters (one region op name per
plan); ``fusion.stats()`` aggregates all of it for the bench block.

Config knobs (utils/config.py): ``fusion.enabled`` (off = the same plan
runs op-by-op, the staged reference path), ``fusion.donate``.
"""

from __future__ import annotations

import re
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from spark_rapids_jni_tpu.columnar import Column, Table
from spark_rapids_jni_tpu.runtime import faults, resilience
from spark_rapids_jni_tpu.telemetry import spans
from spark_rapids_jni_tpu.telemetry.events import record_fallback
from spark_rapids_jni_tpu.telemetry.registry import REGISTRY
from spark_rapids_jni_tpu.utils.config import get_option

__all__ = [
    "Scan",
    "Filter",
    "Project",
    "GroupBy",
    "Join",
    "DensePkJoin",
    "BloomBuild",
    "BloomProbe",
    "Sort",
    "Limit",
    "Exchange",
    "Plan",
    "FusedResult",
    "rows_of",
    "min_rows_of",
    "groups_of",
    "node_scopes",
    "meta_facts",
    "execute",
    "inject_runtime_filters",
    "estimate_hbm_bytes",
    "plan_fingerprint",
    "scan_prefix_chains",
    "replace_node",
    "stats",
]


# ---------------------------------------------------------------------------
# resolvable row specs — statics that depend on TRUE input row counts
# ---------------------------------------------------------------------------
#
# Capacities like a join's out_size or a partial groupby's budget are
# STATIC plan parameters derived from the true (pre-padding) row count of
# an input — never from the bucket, or the fused output shape would drift
# from the staged reference. They resolve at execute() time and ride the
# dispatch key, exactly like the statics the staged op calls carry.


def rows_of(name: str, factor: int = 1):
    """out_rows spec: ``factor *`` the bound table's true row count."""
    return ("rows_of", name, int(factor))


def min_rows_of(name: str, cap: int):
    """max_groups spec: ``min(cap, true row count)`` — the out-of-core
    partial's ``min(_Q1_GROUP_BUDGET, work.num_rows)`` shape."""
    return ("min_rows_of", name, int(cap))


def groups_of(name: str):
    """max_groups spec for a groupby whose key is a foreign key into the
    bound table ``name`` (or columns gathered by it): its true row count,
    and one for the null group of the rows that found no match. A bound
    the data cannot pass while the declaration holds; ``overflowed`` stays
    the guard for when it does not."""
    return ("groups_of", name, 1)


def _resolve(spec, true_rows: dict) -> Optional[int]:
    if spec is None or isinstance(spec, int):
        return spec
    if isinstance(spec, tuple) and len(spec) == 3:
        kind, name, arg = spec
        if kind == "rows_of":
            return int(true_rows[name]) * arg
        if kind == "min_rows_of":
            return min(arg, int(true_rows[name]))
        if kind == "groups_of":
            return int(true_rows[name]) + arg
    raise ValueError(f"unresolvable row spec {spec!r}")


# ---------------------------------------------------------------------------
# logical-plan IR
# ---------------------------------------------------------------------------
#
# Nodes are plain NamedTuples forming a DAG (shared subplans are shared by
# object identity). Node callables (Filter predicates, Project fns) must
# be module-level functions — they are fingerprinted by qualified name for
# the executable-cache key, with all per-query variation carried in the
# ``params`` tuple (the same discipline dispatch ``statics`` impose).


class Scan(NamedTuple):
    """A named input table. ``bucket=False`` keeps the table at its exact
    shape (an aux arg — broadcast build sides whose row count is a planner
    fact, e.g. a clustered dense-PK build whose rows MUST equal the
    declared key range)."""

    name: str
    bucket: bool = True


class Filter(NamedTuple):
    """WHERE via the masking idiom: ``pred(table, *params) -> bool[n]``;
    rows where the predicate is False get their validity nulled in every
    column (never compacted — static shapes). ``like_columns`` names the
    padded string columns of the child that the predicate matches against
    a pattern (``ops/strings.py like``): what it examines of them is their
    ``chars``, every real row at the column's full width.
    Meta: ``<label>.rows_in`` (real rows the predicate saw: not a
    bucket's padding, and over a bounded ``GroupBy`` (a HAVING) its
    groups, not the rows of its bound) / ``<label>.rows_kept`` /
    ``<label>.like_bytes``."""

    child: Any
    pred: Callable
    params: tuple = ()
    label: str = "filter"
    like_columns: tuple = ()


class Project(NamedTuple):
    """``fn(table, *params) -> Table``. ``rowwise=True`` (the default)
    promises the output rows align 1:1 with the input rows (derived
    columns, key masking). ``rowwise=False`` marks a shape-changing
    compute (a full-table reduction like q6's multiply-accumulate); the fn
    then receives the region row_valid as ``fn(table, row_valid, *params)``
    and its output is its own row space."""

    child: Any
    fn: Callable
    params: tuple = ()
    rowwise: bool = True


class GroupBy(NamedTuple):
    """``groupby_aggregate`` (or ``plan_groupby`` when ``domains`` is
    given). ``max_groups`` may be an int, None, or a ``min_rows_of`` /
    ``groups_of`` spec. ``key_ranges`` is a planner's fact like it: one
    entry a key, None or ``(lo, hi)`` with ``hi`` an int or a ``rows_of``
    spec, declaring that every non-null key of a real row lies in ``[lo,
    hi]``. An integer key with a range is grouped as ``key - lo`` in the
    narrowest unsigned type that holds ``hi - lo``
    (``ops/planner.narrow_group_keys``) and the group keys are widened back
    on the way out: the same table, from a sort key of 32 bits or fewer
    where the schema's 64 were sorted word by word (planned q3's key sort:
    one sort of two words and an iota, not three passes that each gather a
    word). Not with ``domains`` (the bounded lowering has its own
    dictionary).
    Side outputs land in the result meta under ``<label>.*``
    (num_groups/overflowed/sum_overflow/in_place/key_sorted/key_one_word,
    the last whether a lone 64-bit key with no declared range was ordered
    as one word, a fact of the data; with a range that
    narrowed a key also key_narrowed and key_out_of_range, which the
    served path refuses as it does ``pk_violation``; or
    present/domain_miss/lowered on the planned lowering). A sort-path
    node also says what entered it and what it had room for:
    ``<label>.rows_in`` (the real rows of its input: a scan's true rows
    where its input holds a scan's rows one for one, not a bucket's
    padding), ``<label>.read_bytes`` (those rows times the bytes of its
    key columns and of the columns it aggregates, each with one byte of
    validity) and, with a bound, ``<label>.capacity`` (the resolved
    ``max_groups``)."""

    child: Any
    keys: tuple
    aggs: tuple
    max_groups: Any = None
    domains: Any = None
    budget: int = 4096
    label: str = "groupby"
    key_ranges: Any = None


class Join(NamedTuple):
    """General equi-join on keys nobody declared anything about
    (``ops/join.py``). What each ``how`` hands to the node above:

    * ``inner`` / ``left`` / ``right`` / ``full``: ``apply_join_maps``'
      materialization, left columns then right columns, ``out_rows`` output
      rows (an int or a ``rows_of`` spec — resolved from TRUE row counts,
      never buckets), a row space of its own; the side a row did not find
      reads NULL.
    * ``left_semi`` / ``left_anti``: the LEFT columns alone, every row where
      it lay (``semi_join_mask``: one sort of both sides' keys as the
      uint32 words they need and a place word, a running maximum, a sort
      back; a 64-bit key whose rows hold one high word between them, and
      low words under 2**31 apart, sorts as one word with the place word
      its payload, chosen inside the region from the data): a
      row the join drops keeps its place and loses its validity in every
      column, as a ``Filter``'s does, so the output is the left child's
      row space and ``out_rows`` is not read (give None). An anti join
      keeps a row with a NULL key, and cannot tell one from a row a
      ``Filter`` below it dropped: both read NULL in every column above it.

    ``out_rows`` is a capacity and a guarantee: a join with nothing
    declared has no static bound on its output, so one that lays rows out
    reports ``<label>.capacity`` (the resolved ``out_rows``) and
    ``<label>.overflowed`` (``total`` passed it: rows were dropped), and
    the served path refuses such a result (``QueryServer._account_meta``:
    ``CapacityOverflow`` with the true total).

    With both children row-sharded over a mesh, ``inner``, ``left_semi``
    and ``left_anti`` lower as a shuffled join (``_mesh_join``: the extra
    sub-scope ``exchange``, the ``<label>.shuffle_*`` facts, ``out_rows``
    then a chip's room); the other kinds have no lowering there.

    Lowers under its label's scope with the sub-scopes ``build`` and
    ``probe`` (``ops/join.py`` says which stage lies under which) and,
    where it lays rows out, ``gather_rows`` (``apply_join_maps``: the
    columns of both sides fetched by the maps; not ``gather``, the
    primitive's own name, which ends the op name of every gather under
    ``probe``: a reader by scope would count those too).
    Meta: ``<label>.total`` (output rows; of a semi or anti join the left
    rows kept), ``<label>.build_rows`` (real right rows with a non-null
    key: what entered the join of the build side), of a semi or anti join
    ``<label>.key_narrowed`` (its merged sort took the narrow form: a fact
    of the data), of the others ``<label>.capacity``,
    ``<label>.overflowed`` and ``<label>.probe_compacted`` (the join ran on
    the probe rows that can emit alone: a capacity far under the probe's
    rows and no more such rows than it has slots, ``ops/join.py``; a fact
    of the data too), and where the left side holds a scan's rows
    ``<label>.probe_rows`` (a static: that scan's)."""

    left: Any
    right: Any
    left_on: tuple
    right_on: tuple
    out_rows: Any
    how: str = "inner"
    label: str = "join"


class DensePkJoin(NamedTuple):
    """Planner-declared dense-PK lookup join (``ops/planner.dense_pk_join``):
    probe-aligned output, no capacity estimate. ``key_hi`` may be a
    ``rows_of`` spec. The build child should hang off an unbucketed Scan
    when ``clustered=True`` (build rows must equal the declared range), and
    the probe child when ``probe_clustered=True`` (the preserved side of a
    LEFT OUTER join is the table laid out by the key: q13's customer).
    Meta: ``<label>.total`` / ``<label>.pk_violation``."""

    probe: Any
    build: Any
    probe_key: int
    build_key: int
    key_lo: int
    key_hi: Any
    clustered: bool = False
    label: str = "pk_join"
    probe_clustered: bool = False


class BloomBuild(NamedTuple):
    """Runtime-filter build side: materialize the child's key column into
    a Spark-compatible bloom filter (``bloom_put_spark`` — null keys and
    phantom rows skipped), emitted as a one-column uint8 bits table.
    Inserted by :func:`inject_runtime_filters`, never written by hand;
    geometry (num_bits, num_hashes) is a static chosen by the gate and
    fingerprinted, so on/off — and differently-sized — plans never alias
    an executable."""

    child: Any
    key: int
    num_bits: int
    num_hashes: int
    label: str = "rtf"


class BloomProbe(NamedTuple):
    """Runtime-filter probe side: rows whose key is definitely absent
    from the ``build`` filter get that KEY's validity nulled — exactly
    the WHERE-before-join masking idiom, so the join downstream treats
    them as the non-matches they are provably about to be. No row is
    compacted and no data byte changes: results are bit-identical with
    the probe present or absent, for probe-aligned and compacting joins
    alike (a bloom filter has no false negatives). ``build`` is either a
    :class:`BloomBuild` or an unbucketed Scan bound to a bits table
    (``packed=True`` when those bits are the ``to_packed`` wire form a
    cluster shard received). Meta: ``<label>.rows_in`` /
    ``<label>.rows_pass`` — the observed selectivity the learned gate
    feeds on."""

    child: Any
    build: Any
    key: int
    num_bits: int
    num_hashes: int
    packed: bool = False
    label: str = "rtf"


class Sort(NamedTuple):
    """``sort_table``; when the input still carries a region row_valid the
    phantom rows rank strictly last (``sort_order``'s row_valid contract),
    so the real prefix is exactly the staged sort.

    An input with no row_valid whose keys all sort their nulls last and
    have validity masks, of enough rows (``ops/sort.py padding_rung``: a
    bounded groupby's result, the groups and then null rows up to the
    bound), is sorted at a sixteenth of its rows where the data says that
    every row past them is null in every key, the rest left where it
    lies: the same table, value for value (``sort_before_padding``). Meta
    of such a node, under its scope's name (``node_scopes``):
    ``sort.prefix_sorted``, whether that ran; any other ``Sort`` lowers
    as it always has and reports nothing."""

    child: Any
    keys: tuple
    ascending: Any = None
    nulls_first: Any = None


class Limit(NamedTuple):
    """Positional head: first ``min(count, true rows)`` rows."""

    child: Any
    count: int


class Exchange(NamedTuple):
    """General-cardinality hash repartition of the child's output — the
    distributed-exchange boundary BETWEEN PROCESSES (runtime/exchange.py).
    Such a shuffle is a genuine host boundary, so an Exchange never evaluates
    INSIDE a fused/staged region; the planner instead breaks the plan at it.
    (The exchange between the chips of ONE process's mesh is no node: a
    ``Join`` or ``GroupBy`` of rows sharded over a mesh lowers to it inside
    the region, "lowering over a mesh" below.) As
    a plan ROOT, the child region fuses and executes normally and the
    exchange pack runs as its own dispatch op on the result (the wire
    form the cluster ships). Placed MID-PLAN, ``execute`` splits the
    DAG at the (deepest-first) interior Exchange into region ->
    exchange -> region: the pack half runs as an Exchange root, the
    remainder re-runs per destination with the Exchange swapped for a
    Scan bound to that destination's regrouped rows, and the
    part-ordered concatenation is the plan's result — bit-identical to
    the hand-split (pack plan, merge plan) pair it replaces.

    ``keys`` are column indices hashed with the Spark-compatible
    ``partition_hash``; ``parts`` is the destination count (cluster
    hosts), or 0 for "auto" — resolved at execute time from the
    learned-selectivity store (``exchange.choose_parts``; fingerprints
    only ever see the resolved count). ``capacity`` is the
    per-destination slot count (an int, a ``rows_of`` spec, or None for
    the escalation ladder's derived start). ``valid_meta`` optionally
    names a child meta key holding the TRUE row count of the child's
    padded output (e.g. a partial groupby's ``partial.num_groups``) so
    budget-padding phantom rows never ride the wire. Meta:
    ``<label>.parts`` / ``<label>.capacity`` / ``<label>.flights`` /
    ``<label>.row_counts`` / ``<label>.rows`` (plain Python — they
    survive the fleet's result frames)."""

    child: Any
    keys: tuple
    parts: int
    capacity: Any = None
    valid_meta: Optional[str] = None
    label: str = "exchange"


class Plan(NamedTuple):
    """A named fusible region: one root node, one fused executable. The
    name becomes the dispatch op (``fusion.<name>``), so executables per
    query are countable (``dispatch.compile.fusion.<name>``)."""

    name: str
    root: Any


_MASK_JOINS = ("left_semi", "left_anti")   # ``Join.how`` that keep row space

_NODE_TYPES = (Scan, Filter, Project, GroupBy, Join, DensePkJoin,
               BloomBuild, BloomProbe, Sort, Limit, Exchange)


class FusedResult(NamedTuple):
    table: Table
    # side outputs of labeled nodes: "<label>.<field>" -> scalar/array
    # (plus static plan facts like "<label>.lowered")
    meta: dict


# ---------------------------------------------------------------------------
# static plan analysis
# ---------------------------------------------------------------------------


def _children(node) -> tuple:
    if isinstance(node, Scan):
        return ()
    if isinstance(node, (Filter, Project, GroupBy, Sort, Limit, BloomBuild,
                         Exchange)):
        return (node.child,)
    if isinstance(node, Join):
        return (node.left, node.right)
    if isinstance(node, DensePkJoin):
        return (node.probe, node.build)
    if isinstance(node, BloomProbe):
        return (node.child, node.build)
    raise TypeError(f"not a plan node: {type(node).__name__}")


def _topo(root) -> list:
    """Children-first topological order over the node DAG."""
    order: list = []
    seen: set = set()

    def visit(node):
        if id(node) in seen:
            return
        seen.add(id(node))
        for c in _children(node):
            visit(c)
        order.append(node)

    visit(root)
    return order


def node_scopes(nodes) -> dict:
    """``{id(node): scope}`` of a plan's nodes (as ``_topo`` orders them):
    the ``jax.named_scope`` every node lowers under, inside the region's
    ``region.<plan>``, so a device trace can say which operator an
    operation belongs to. A node's label if it has one (``pk1``,
    ``groupby``), else its kind (``sort``); a name several nodes share
    gets each node's position in the order (``project.2``)."""
    names = [getattr(n, "label", None) or type(n).__name__.lower()
             for n in nodes]
    return {id(n): name if names.count(name) == 1 else f"{name}.{i}"
            for i, (n, name) in enumerate(zip(nodes, names))}


def _scan_names(nodes) -> tuple[list, list]:
    """(bucketed, exact) scan names in first-appearance order. A name
    must be scanned consistently (one bucket flag per table)."""
    bucketed: list = []
    exact: list = []
    flags: dict = {}
    for node in nodes:
        if not isinstance(node, Scan):
            continue
        if node.name in flags:
            if flags[node.name] != node.bucket:
                raise ValueError(
                    f"scan {node.name!r} used both bucketed and exact")
            continue
        flags[node.name] = node.bucket
        (bucketed if node.bucket else exact).append(node.name)
    return bucketed, exact


def _fn_key(fn) -> tuple:
    mod = getattr(fn, "__module__", None)
    qual = getattr(fn, "__qualname__", None)
    if mod is None or qual is None or "<locals>" in (qual or ""):
        raise ValueError(
            "plan callables must be module-level functions (their "
            "qualified name keys the executable cache); got "
            f"{fn!r} — carry per-query variation in params instead")
    return (mod, qual)


def _declares_range(node: GroupBy) -> bool:
    """Whether a groupby declares a range for any of its keys
    (``key_ranges`` None, or None for every key, declares none and lowers
    as a node without the field)."""
    if node.key_ranges is None:
        return False
    if node.domains is not None:
        raise ValueError(
            f"groupby {node.label!r}: key_ranges with domains (the bounded "
            f"lowering has its own dictionary)")
    if len(node.key_ranges) != len(node.keys):
        raise ValueError(
            f"groupby {node.label!r}: {len(node.key_ranges)} key_ranges "
            f"for {len(node.keys)} keys")
    return any(r is not None for r in node.key_ranges)


def _fingerprint(nodes, resolved: dict) -> tuple:
    """Structural digest of the plan DAG: node kinds, static params,
    resolved row specs, and child indices — the fused region's dispatch
    ``statics``. Two plans collide only if they trace identically."""
    index = {id(n): i for i, n in enumerate(nodes)}
    out = []
    for node in nodes:
        kids = tuple(index[id(c)] for c in _children(node))
        if isinstance(node, Scan):
            entry = ("scan", node.name, node.bucket)
        elif isinstance(node, Filter):
            entry = ("filter", _fn_key(node.pred), node.params,
                     node.like_columns)
        elif isinstance(node, Project):
            entry = ("project", _fn_key(node.fn), node.params, node.rowwise)
        elif isinstance(node, GroupBy):
            doms = None
            if node.domains is not None:
                doms = tuple(
                    (None if d is None else (tuple(d.values), d.kind))
                    for d in node.domains)
            entry = ("groupby", node.keys, node.aggs,
                     resolved[id(node)], doms, node.budget,
                     resolved.get((id(node), "key_ranges")))
        elif isinstance(node, Join):
            entry = ("join", node.left_on, node.right_on,
                     resolved[id(node)], node.how)
        elif isinstance(node, DensePkJoin):
            entry = ("pk_join", node.probe_key, node.build_key, node.key_lo,
                     resolved[id(node)], node.clustered,
                     node.probe_clustered)
        elif isinstance(node, BloomBuild):
            entry = ("bloom_build", node.key, node.num_bits, node.num_hashes)
        elif isinstance(node, BloomProbe):
            entry = ("bloom_probe", node.key, node.num_bits,
                     node.num_hashes, node.packed)
        elif isinstance(node, Sort):
            entry = ("sort", node.keys,
                     None if node.ascending is None else tuple(node.ascending),
                     None if node.nulls_first is None
                     else tuple(node.nulls_first))
        elif isinstance(node, Limit):
            entry = ("limit", resolved[id(node)])
        elif isinstance(node, Exchange):
            entry = ("exchange", node.keys, node.parts,
                     resolved[id(node)], node.valid_meta)
        else:  # pragma: no cover - _children already rejects
            raise TypeError(type(node).__name__)
        out.append(entry + (kids,))
    return tuple(out)


def _resolve_statics(nodes, true_rows: dict) -> dict:
    """Evaluate every row-count-derived static against TRUE row counts."""
    resolved: dict = {}
    for node in nodes:
        if isinstance(node, GroupBy):
            resolved[id(node)] = _resolve(node.max_groups, true_rows)
            if _declares_range(node):
                resolved[id(node), "key_ranges"] = tuple(
                    None if r is None
                    else (int(r[0]), _resolve(r[1], true_rows))
                    for r in node.key_ranges)
        elif isinstance(node, Join):
            resolved[id(node)] = _resolve(node.out_rows, true_rows)
        elif isinstance(node, DensePkJoin):
            resolved[id(node)] = _resolve(node.key_hi, true_rows)
        elif isinstance(node, Limit):
            resolved[id(node)] = int(node.count)
        elif isinstance(node, Exchange):
            resolved[id(node)] = _resolve(node.capacity, true_rows)
    return resolved


def _spaces(nodes) -> dict:
    """Static row-space analysis: node id -> scan name whose POSITIONAL
    row space the node's output lives in (sliceable back to the true row
    count after a padded fused run), or None for fixed/derived shapes
    (groupby budgets, join out_size, bounded-plan slot tables)."""
    spaces: dict = {}
    for node in nodes:
        if isinstance(node, Scan):
            spaces[id(node)] = node.name if node.bucket else None
        elif isinstance(node, Filter):
            spaces[id(node)] = spaces[id(node.child)]
        elif isinstance(node, Project):
            spaces[id(node)] = (
                spaces[id(node.child)] if node.rowwise else None)
        elif isinstance(node, GroupBy):
            # max_groups=None pads the output to the input row count, so
            # it stays positionally sliceable; an explicit budget (or the
            # bounded plan's slot count) is its own fixed shape
            if node.max_groups is None and node.domains is None:
                spaces[id(node)] = spaces[id(node.child)]
            else:
                spaces[id(node)] = None
        elif isinstance(node, DensePkJoin):
            spaces[id(node)] = spaces[id(node.probe)]  # probe-aligned
        elif isinstance(node, BloomBuild):
            spaces[id(node)] = None  # fixed shape: num_bits bytes
        elif isinstance(node, BloomProbe):
            # only a key's validity changes — strictly row-preserving
            spaces[id(node)] = spaces[id(node.child)]
        elif isinstance(node, Sort):
            spaces[id(node)] = spaces[id(node.child)]
        elif isinstance(node, Join) and node.how in _MASK_JOINS:
            spaces[id(node)] = spaces[id(node.left)]   # rows stay in place
        elif isinstance(node, (Join, Limit, Exchange)):
            spaces[id(node)] = None
    return spaces


def _side_keys(nodes, placement: Optional[dict] = None) -> list:
    """Deterministic (label, field) order of traced side outputs;
    ``placement`` (``_mesh_placement``) adds what a groupby or a join
    lowered over a mesh reports of its shuffles."""
    keys: list = []
    scopes = node_scopes(nodes)    # a Sort has no label: its scope's name
    for node in nodes:
        if isinstance(node, Filter):
            keys += [f"{node.label}.rows_in", f"{node.label}.rows_kept",
                     f"{node.label}.like_bytes"]
        elif isinstance(node, GroupBy):
            if node.domains is not None:
                keys += [f"{node.label}.present",
                         f"{node.label}.domain_miss",
                         f"{node.label}.overflowed"]
            else:
                keys += [f"{node.label}.num_groups",
                         f"{node.label}.overflowed",
                         f"{node.label}.sum_overflow",
                         f"{node.label}.in_place",
                         f"{node.label}.key_sorted",
                         f"{node.label}.key_one_word"]
                if placement and placement[id(node.child)] == SHARDED:
                    keys += [f"{node.label}.shuffle_rows",
                             f"{node.label}.shuffle_bytes"]
                keys += [f"{node.label}.rows_in",
                         f"{node.label}.read_bytes"]
                if _declares_range(node):
                    keys += [f"{node.label}.key_narrowed",
                             f"{node.label}.key_out_of_range"]
        elif isinstance(node, Join):
            keys += [f"{node.label}.total", f"{node.label}.build_rows"]
            if node.how in _MASK_JOINS:
                keys += [f"{node.label}.key_narrowed"]
            else:
                keys += [f"{node.label}.overflowed",
                         f"{node.label}.probe_compacted"]
            if placement and placement[id(node)] == SHARDED:
                keys += [f"{node.label}.{fact}" for fact in _SHUFFLE_FACTS]
        elif isinstance(node, DensePkJoin):
            keys += [f"{node.label}.total", f"{node.label}.pk_violation"]
        elif isinstance(node, BloomProbe):
            keys += [f"{node.label}.rows_in", f"{node.label}.rows_pass"]
        elif isinstance(node, Sort):
            keys += [f"{scopes[id(node)]}.prefix_sorted"]
    return keys


def _side_meta(side) -> dict:
    """The meta of ``(key, value)`` side outputs. A key that ``_side_keys``
    lists for every node of a kind, while whether a node has the fact is
    known only once its input is traced (a ``Sort``'s ``prefix_sorted``),
    comes with None from a node that has none: it is left out."""
    return {key: value for key, value in side if value is not None}


# ---------------------------------------------------------------------------
# evaluation — one shared walker for the fused trace AND the staged path
# ---------------------------------------------------------------------------


def _scanned_rows(node, true_rows: dict) -> Optional[int]:
    """The true row count of ``node``'s output where it is a scan's rows,
    one for one (filters null rows, they do not drop them); else None."""
    while not isinstance(node, Scan):
        if isinstance(node, (Filter, BloomProbe, Sort)) or (
                isinstance(node, Project) and node.rowwise):
            node = node.child
        elif isinstance(node, DensePkJoin):
            node = node.probe
        elif isinstance(node, Join) and node.how in _MASK_JOINS:
            node = node.left
        else:
            return None
    return int(true_rows[node.name])


def _column_row_bytes(c: Column) -> int:
    """Bytes a row of ``c``'s buffers but its validity: the data's, and a
    padded string's width."""
    return sum(int(np.prod(buf.shape[1:])) * buf.dtype.itemsize
               for buf in (c.data, c.chars) if buf is not None)


def _null_all(table: Table, keep: jnp.ndarray) -> Table:
    return Table([
        Column(c.dtype, c.data, c.valid_mask() & keep,
               chars=c.chars, children=c.children)
        for c in table.columns
    ])


def _head(table: Table, k: int) -> Table:
    return Table([
        Column(c.dtype, c.data[:k],
               None if c.validity is None else c.validity[:k],
               chars=None if c.chars is None else c.chars[:k])
        for c in table.columns
    ])


def _eval_plan(root, tables: dict, rvs: dict, resolved: dict,
               true_rows: dict, mesh_axis: Optional[str] = None,
               placement: Optional[dict] = None):
    """Evaluate the DAG. ``tables``/``rvs`` hold the (possibly padded)
    input tables and their region row_valid masks. Returns
    (root table, [(side key, traced value), ...]). Called with tracer
    tables inside the fused region fn and with concrete tables on the
    staged path — the SAME per-op calls either way. With ``mesh_axis``
    the caller is one chip of a ``shard_map`` over that axis holding its
    rows of every scan, and a join or a groupby whose input is ``SHARDED``
    in ``placement`` (``_mesh_placement``) lowers across the chips."""
    from spark_rapids_jni_tpu import types as _t
    from spark_rapids_jni_tpu.ops import bloom_filter as _bloom
    from spark_rapids_jni_tpu.ops.groupby import groupby_aggregate
    from spark_rapids_jni_tpu.ops.join import (
        apply_join_maps,
        join,
        key_valid,
        semi_join_mask,
    )
    from spark_rapids_jni_tpu.ops.planner import (
        dense_pk_join, narrow_group_keys, plan_groupby, widen_group_keys)
    from spark_rapids_jni_tpu.ops.sort import (
        gather, padding_rung, sort_before_padding, sort_order)

    env: dict = {}
    side: list = []
    # over a mesh, a sharded node's id -> bool[rows]: the rows no Filter
    # below it dropped. A dropped row stays where it lay, null in every
    # column; an exchange must not carry it (``_mesh_join``)
    live: dict = {}
    # a bounded groupby's node id -> the groups its output holds (int32):
    # the rows past them are padding, which no row mask says
    groups_of_node: dict = {}
    # children first, each node under its own scope (not its parents')
    nodes = _topo(root)
    scopes = node_scopes(nodes)

    def ev(node):
        if id(node) in env:
            return env[id(node)]
        if isinstance(node, Scan):
            out = (tables[node.name], rvs.get(node.name))
        elif isinstance(node, Filter):
            tbl, rv = ev(node.child)
            keep = node.pred(tbl, *node.params)
            seen = rv
            if rv is None and id(node.child) in groups_of_node:
                # a HAVING: the bound's rows past the groups are no rows
                seen = (jax.lax.iota(jnp.int32, tbl.num_rows)
                        < groups_of_node[id(node.child)])
            real = jnp.asarray(tbl.num_rows if seen is None else jnp.sum(
                seen, dtype=jnp.int64), jnp.int64)
            kept = jnp.sum(keep if seen is None else keep & seen,
                           dtype=jnp.int64)
            if placement is not None and placement[id(node)] == SHARDED:
                # a chip saw its share of the rows: the counts of them all
                real, kept = (jax.lax.psum(v, mesh_axis)
                              for v in (real, kept))
                live[id(node)] = keep & live.get(id(node.child), True)
            width = sum(int(tbl.column(i).chars.shape[1])
                        for i in node.like_columns)
            side.extend([
                (f"{node.label}.rows_in", real),
                (f"{node.label}.rows_kept", kept),
                (f"{node.label}.like_bytes", real * width),
            ])
            out = (_null_all(tbl, keep), rv)
        elif isinstance(node, Project):
            tbl, rv = ev(node.child)
            if node.rowwise:
                out = (node.fn(tbl, *node.params), rv)
                if id(node.child) in live:
                    live[id(node)] = live[id(node.child)]
            else:
                out = (node.fn(tbl, rv, *node.params), None)
        elif isinstance(node, GroupBy):
            tbl, rv = ev(node.child)
            # a declared key range: the key is grouped at the width the
            # range takes and widened back on the way out, all under this
            # node's scope (rowwise, so the same on a chip's share)
            ranges = resolved.get((id(node), "key_ranges"))
            if ranges is not None:
                keyed = narrow_group_keys(tbl, node.keys, ranges, rv)
                tbl = keyed.table
            over_mesh = (placement is not None
                         and placement[id(node.child)] == SHARDED)
            rv_out = None
            if over_mesh:
                gtbl, gside = _mesh_groupby(
                    node, tbl, rv, resolved[id(node)], mesh_axis)
            elif node.domains is not None:
                res = plan_groupby(
                    tbl, list(node.keys), list(node.aggs),
                    list(node.domains), budget=node.budget, row_valid=rv)
                gtbl, gside = res.table, [
                    (f"{node.label}.present", res.present),
                    (f"{node.label}.domain_miss", res.domain_miss),
                    (f"{node.label}.overflowed",
                     jnp.asarray(res.overflowed)),
                ]
            else:
                g = groupby_aggregate(
                    tbl, list(node.keys), list(node.aggs),
                    max_groups=resolved[id(node)], row_valid=rv)
                gtbl, gside = g.table, [
                    (f"{node.label}.num_groups", g.num_groups),
                    (f"{node.label}.overflowed", jnp.asarray(g.overflowed)),
                    (f"{node.label}.sum_overflow",
                     jnp.asarray(g.sum_overflow)),
                    (f"{node.label}.in_place", jnp.asarray(g.in_place)),
                    (f"{node.label}.key_sorted", jnp.asarray(g.key_sorted)),
                    (f"{node.label}.key_one_word",
                     jnp.asarray(g.key_one_word)),
                ]
                if resolved[id(node)] is None:
                    rv_out = rv   # padded to the input rows: still positional
                else:
                    groups_of_node[id(node)] = jnp.minimum(
                        g.num_groups, resolved[id(node)])
            side.extend(gside)
            if node.domains is None:
                # what entered it: a scan's true rows where the input holds
                # them one for one (known while tracing), else its rows
                rows_in = _scanned_rows(node.child, true_rows)
                if rows_in is None:
                    rows_in = tbl.num_rows if rv is None else jnp.sum(
                        rv, dtype=jnp.int64)
                read = dict.fromkeys(
                    list(node.keys) + [c for c, _ in node.aggs]
                    + [op[1] for _, op in node.aggs if isinstance(op, tuple)])
                width = sum(_column_row_bytes(tbl.column(i)) + 1
                            for i in read)
                side.extend([
                    (f"{node.label}.rows_in",
                     jnp.asarray(rows_in, jnp.int64)),
                    (f"{node.label}.read_bytes",
                     jnp.asarray(rows_in, jnp.int64) * width)])
            if ranges is not None:
                gtbl = widen_group_keys(gtbl, keyed.narrowed)
                broke = keyed.out_of_range
                if over_mesh:     # on any chip
                    broke = jax.lax.psum(
                        broke.astype(jnp.int32), mesh_axis) > 0
                side.extend([
                    # whether a key was narrowed is a fact of the lowering
                    # (the key's type, the range's width), as in_place is
                    (f"{node.label}.key_narrowed",
                     jnp.asarray(bool(keyed.narrowed))),
                    (f"{node.label}.key_out_of_range", broke),
                ])
            out = (gtbl, rv_out)
        elif isinstance(node, Join):
            ltbl, lrv = ev(node.left)
            rtbl, rrv = ev(node.right)
            if placement is not None and placement[id(node)] == SHARDED:
                out, facts, kept = _mesh_join(
                    node, ltbl, lrv, live.get(id(node.left)), rtbl, rrv,
                    live.get(id(node.right)), resolved[id(node)], mesh_axis)
                if kept is not None:    # the rows it dropped stay, nulled
                    live[id(node)] = kept
            elif node.how in _MASK_JOINS:
                # one bit a left row: no maps, nothing moves
                semi = semi_join_mask(
                    ltbl, rtbl, list(node.left_on), list(node.right_on),
                    node.how, left_row_valid=lrv, right_row_valid=rrv)
                facts = [("total", semi.total),
                         ("build_rows", semi.build_rows),
                         ("key_narrowed", semi.key_narrowed)]
                out = (_null_all(ltbl, semi.keep), lrv)
            else:
                capacity = resolved[id(node)]
                maps = join(
                    ltbl, rtbl, list(node.left_on), list(node.right_on),
                    out_size=capacity, how=node.how,
                    left_row_valid=lrv, right_row_valid=rrv)
                with jax.named_scope("probe"):
                    build_rows = jnp.sum(
                        key_valid(rtbl, node.right_on, rrv), dtype=jnp.int64)
                with jax.named_scope("gather_rows"):
                    out = (apply_join_maps(ltbl, rtbl, maps), None)
                facts = [("total", maps.total), ("build_rows", build_rows),
                         ("overflowed", maps.total > capacity),
                         ("probe_compacted", maps.probe_compacted)]
            side.extend((f"{node.label}.{fact}", value)
                        for fact, value in facts)
        elif isinstance(node, DensePkJoin):
            ptbl, prv = ev(node.probe)
            btbl, brv = ev(node.build)
            if brv is not None:
                # a padded build side would break the declared layout;
                # phantom build rows are nulled out of the lookup instead
                btbl = _null_all(btbl, brv)
            r = dense_pk_join(ptbl, btbl, node.probe_key, node.build_key,
                              node.key_lo, resolved[id(node)],
                              clustered=node.clustered,
                              probe_clustered=node.probe_clustered)
            side.extend([
                (f"{node.label}.total", r.total),
                (f"{node.label}.pk_violation", r.pk_violation),
            ])
            out = (r.table, prv)
        elif isinstance(node, BloomBuild):
            tbl, rv = ev(node.child)
            col = tbl.columns[node.key]
            kv = col.valid_mask()
            if rv is not None:
                kv = kv & rv
            bf = _bloom.BloomFilter(
                jnp.zeros((node.num_bits,), dtype=jnp.uint8),
                node.num_hashes)
            bf = _bloom.bloom_put_spark(bf, col.data, kv)
            out = (Table([Column(_t.UINT8, bf.bits)]), None)
        elif isinstance(node, BloomProbe):
            tbl, rv = ev(node.child)
            btbl, _ = ev(node.build)
            bits = btbl.columns[0].data
            if node.packed:
                bf = _bloom.BloomFilter.from_packed(
                    bits, node.num_bits, node.num_hashes)
            else:
                bf = _bloom.BloomFilter(bits, node.num_hashes)
            col = tbl.columns[node.key]
            kv = col.valid_mask()
            if rv is not None:
                kv = kv & rv
            hit = _bloom.bloom_might_contain_spark(bf, col.data)
            side.extend([
                (f"{node.label}.rows_in",
                 jnp.sum(kv.astype(jnp.int32))),
                (f"{node.label}.rows_pass",
                 jnp.sum((kv & hit).astype(jnp.int32))),
            ])
            # null ONLY the key's validity where the filter proves the
            # key absent from the build — data bytes and every other
            # column untouched, so this is indistinguishable from the
            # key having been nulled by a WHERE upstream
            cols = list(tbl.columns)
            cols[node.key] = Column(
                col.dtype, col.data, col.valid_mask() & (hit | ~kv),
                chars=col.chars, children=col.children)
            out = (Table(cols), rv)
        elif isinstance(node, Sort):
            tbl, rv = ev(node.child)
            asc = None if node.ascending is None else list(node.ascending)
            nf = None if node.nulls_first is None else list(node.nulls_first)
            rung = padding_rung(tbl, node.keys, nf) if rv is None else None
            if rung is None:
                srt = gather(tbl, sort_order(tbl, list(node.keys), asc, nf,
                                             row_valid=rv))
                took = None     # no fact of this node (``_side_meta``)
            else:
                srt, took = sort_before_padding(
                    tbl, list(node.keys), asc, nf, rung)
            side.append((f"{scopes[id(node)]}.prefix_sorted", took))
            if rv is None:
                out = (srt, None)
            else:
                # phantoms ranked strictly last: the real prefix is the
                # staged sort, and the mask becomes positional again
                n = jnp.sum(rv.astype(jnp.int32))
                out = (srt,
                       jnp.arange(tbl.num_rows, dtype=jnp.int32) < n)
        elif isinstance(node, Limit):
            tbl, rv = ev(node.child)
            out = (_head(tbl, resolved[id(node)]), None)
        elif isinstance(node, Exchange):
            raise TypeError(
                "Exchange is a host boundary: it is only valid as a plan "
                "root (execute() routes it to runtime.exchange), never "
                "inside a fused/staged region")
        else:
            raise TypeError(f"not a plan node: {type(node).__name__}")
        env[id(node)] = out
        return out

    for node in nodes:
        with jax.named_scope(scopes[id(node)]):
            value, _ = ev(node)
    return value, side


# ---------------------------------------------------------------------------
# lowering over a mesh — a region whose scans are row-sharded over one axis
# ---------------------------------------------------------------------------
#
# The sharding of the bound buffers is the only signal (``parallel/mesh.py``
# ``table_row_mesh``): no option, no second entry point. Each chip of the
# axis is one Spark executor holding its partition of every scan. Filters
# and row-wise projections run on a chip's own rows. Two kinds of node are
# where the chips meet:
#
# * a ``Join`` (``left_semi``, ``left_anti``, ``inner``) of two sharded
#   children is a shuffled join (``_mesh_join``): both sides exchanged by
#   the hash of the join key (``hash_shuffle``, an ``all_to_all`` of ROWS
#   over ICI), the one-chip join of what landed on a chip; its output is
#   still a chip's share of the rows. A shuffle that finds more rows for a
#   chip than its receive buffer has slots drops them and says so
#   (``<label>.shuffle_overflowed``): the served path refuses that request
#   (``QueryServer._account_meta``: ``CapacityOverflow``), it never answers.
# * a groupby: a partial aggregate a chip bounded at the plan's group
#   budget, ``hash_shuffle`` of the real partial rows over the axis, the
#   merge of what a chip then owns, and the collect of every chip's groups
#   into one table that every chip holds (declared domains: one collective
#   over the slots, no row crosses). Whatever stands above the groupby (q1's
#   finalize and ORDER BY) then runs on that small table as on one chip.
#
# ``left`` / ``right`` / ``full`` joins (a NULL-keyed row has to come out,
# and every one of them hashes to one chip), a join with one whole child (a
# broadcast side), a ``Sort`` and a ``Limit`` of sharded rows have no
# lowering: ``_mesh_placement`` gives None and the plan runs as it always has.

SHARDED, WHOLE = "sharded", "whole"
# what merges a partial aggregate of each kind across the shuffle
_MERGE_OF = {"sum": "sum", "count": "sum", "min": "min", "max": "max"}
# ``Join.how`` with a lowering over a mesh
_MESH_JOINS = _MASK_JOINS + ("inner",)
# what a join lowered over a mesh reports of its two shuffles
# (``<label>.<fact>``, ``_mesh_join``)
_SHUFFLE_FACTS = ("shuffle_rows", "shuffle_bytes", "shuffle_capacity",
                  "shuffle_read_bytes", "shuffle_overflowed")


def _mesh_placement(nodes, resolved: dict) -> Optional[dict]:
    """``{id(node): SHARDED | WHOLE}``: whether a node's output is a
    chip's share of the rows or the one table every chip holds, when every
    bucketed scan is row-sharded over one mesh axis; None where the plan
    has no lowering over a mesh (an exact scan, an outer join, a join with
    one whole child, a sort or limit of sharded rows, an aggregate with no
    associative merge, a groupby with neither a group bound nor declared
    domains, a root that is still sharded): ``execute`` then runs it as it
    always has."""
    place: dict = {}
    for node in nodes:
        kids = [place[id(c)] for c in _children(node)]
        if isinstance(node, Scan):
            if not node.bucket:
                return None
            here = SHARDED
        elif isinstance(node, Filter) or (
                isinstance(node, Project) and node.rowwise):
            here = kids[0]
        elif isinstance(node, GroupBy) and kids[0] == SHARDED:
            if not all(op in _MERGE_OF for _, op in node.aggs):
                return None
            if node.domains is None:
                if not isinstance(resolved[id(node)], int):
                    return None
            elif _planned_lowering(node) != "bounded":
                return None
            here = WHOLE
        elif (isinstance(node, Join) and node.how in _MESH_JOINS
                and kids == [SHARDED, SHARDED]):
            here = SHARDED
        elif SHARDED in kids:
            return None
        else:
            here = WHOLE
        place[id(node)] = here
    return place if here == WHOLE else None


def _gather_rows(col: Column, axis: str) -> Column:
    """Every chip's rows of ``col``, chip after chip, on every chip."""
    def every(buf):
        return None if buf is None else jax.lax.all_gather(
            buf, axis, tiled=True)

    return Column(col.dtype, every(col.data), every(col.valid_mask()),
                  chars=every(col.chars))


def _mesh_groupby(node: GroupBy, tbl: Table, rv, bound, axis: str):
    """One chip's part of a groupby over rows sharded across ``axis``
    (inside ``shard_map``): ``(the whole result table, side outputs)``,
    the same on every chip. The sub-scopes ``partial`` / ``exchange`` /
    ``merge`` / ``collect`` are what a device trace splits its time by."""
    from spark_rapids_jni_tpu.ops.groupby import groupby_aggregate
    from spark_rapids_jni_tpu.ops.planner import plan_groupby
    from spark_rapids_jni_tpu.parallel.distributed import merge_bounded_slots
    from spark_rapids_jni_tpu.parallel.shuffle import hash_shuffle
    from spark_rapids_jni_tpu.parallel.wire import shuffle_wire_bytes

    def anywhere(flag):
        return jax.lax.psum(jnp.asarray(flag).astype(jnp.int32), axis) > 0

    label, nk = node.label, len(node.keys)
    if node.domains is not None:
        # declared domains: the slot table is static and its aggregates
        # associative a slot, so the merge is one collective over the
        # slots and no row crosses
        with jax.named_scope("partial"):
            res = plan_groupby(tbl, list(node.keys), list(node.aggs),
                               list(node.domains), budget=node.budget,
                               row_valid=rv)
        with jax.named_scope("merge"):
            out, present, miss = merge_bounded_slots(
                res, list(node.aggs), nk, axis)
        return out, [(f"{label}.present", present),
                     (f"{label}.domain_miss", miss),
                     (f"{label}.overflowed", anywhere(res.overflowed))]
    chips = jax.lax.axis_size(axis)
    budget = min(int(bound), tbl.num_rows)
    with jax.named_scope("partial"):
        part = groupby_aggregate(tbl, list(node.keys), list(node.aggs),
                                 max_groups=budget, row_valid=rv)
    with jax.named_scope("exchange"):
        # only the real groups cross the wire: the budget's padding rows
        # would all hash to one chip
        sent = jnp.minimum(part.num_groups, budget)
        sh = hash_shuffle(
            part.table, list(range(nk)), axis, capacity=budget,
            row_valid=jnp.arange(budget, dtype=jnp.int32) < sent)
    with jax.named_scope("merge"):
        # max_groups None: a chip may own up to chips * budget partial
        # groups, the shuffle buffer's size, which cannot overflow
        merged = groupby_aggregate(
            sh.table, list(range(nk)),
            [(nk + i, _MERGE_OF[op]) for i, (_, op) in enumerate(node.aggs)],
            row_valid=sh.row_valid)
    with jax.named_scope("collect"):
        # the driver-side collect: every chip's groups, chip after chip,
        # real rows first, bounded at the plan's group budget
        each = merged.table.num_rows
        counts = jax.lax.all_gather(
            merged.num_groups.reshape(1).astype(jnp.int32), axis, tiled=True)
        ends = jnp.cumsum(counts)
        row = jnp.arange(int(bound), dtype=jnp.int32)
        chip = jnp.clip(jnp.searchsorted(ends, row, side="right"),
                        0, chips - 1).astype(jnp.int32)
        real = row < ends[-1]
        src = jnp.where(real, chip * each + row - (ends - counts)[chip], 0)
        cols = []
        for col in merged.table.columns:
            g = _gather_rows(col, axis)
            # rows past the last group: null, their bytes zero as a
            # groupby's own padding is
            def taken(buf):
                keep = real.reshape((-1,) + (1,) * (buf.ndim - 1))
                return jnp.where(keep, buf[src], jnp.zeros((), buf.dtype))

            cols.append(Column(
                g.dtype, taken(g.data), g.validity[src] & real,
                chars=None if g.chars is None else taken(g.chars)))
        flags = (anywhere(part.overflowed) | anywhere(sh.overflowed)
                 | anywhere(merged.overflowed) | (ends[-1] > int(bound)))
        side = [(f"{label}.num_groups", ends[-1]),
                (f"{label}.overflowed", flags),
                (f"{label}.sum_overflow",
                 anywhere(part.sum_overflow) | anywhere(merged.sum_overflow)),
                # how the partial was lowered (ops/groupby.py): a fact of
                # the trace, the same on every chip
                (f"{label}.in_place", jnp.asarray(part.in_place)),
                # whether a chip's partial sorted its key words to count
                # the groups past its bound: a fact of the data
                (f"{label}.key_sorted", anywhere(part.key_sorted)),
                # whether a chip's partial ordered a lone 64-bit key as one
                # word (``ops/sort.py``): a fact of the data as well
                (f"{label}.key_one_word", anywhere(part.key_one_word)),
                (f"{label}.shuffle_rows", jax.lax.psum(sent, axis)),
                # what the all_to_all carries between chips (a chip keeps
                # its own share): a fact of the partial's schema and the
                # bound, known when the region is traced
                (f"{label}.shuffle_bytes", jnp.asarray(
                    (chips - 1) * shuffle_wire_bytes(
                        part.table, None, budget, chips)["wire_bytes"],
                    jnp.int64))]
    return Table(cols), side


def _mesh_join(node: Join, ltbl: Table, lrv, llive, rtbl: Table, rrv,
               rlive, capacity, axis: str):
    """One chip's part of a join of rows sharded across ``axis`` (inside
    ``shard_map``): ``((its share of the output, row mask), facts, the left
    rows a mask join kept)``. The step is ``distributed.shuffled_join``:
    under the sub-scope ``exchange`` (``exchange/left``, ``exchange/right``)
    both sides go through ``hash_shuffle`` by the join key, then the join the
    node runs on one chip joins what landed (``build`` / ``probe`` / ...).

    What rides: every column of the left side (the node's output holds
    them), and of the right side every column where the join lays rows out
    but the KEY alone for a mask join, whose right side nobody above reads.
    Which rows: a bucket's padding (``*rv``) and the rows a ``Filter`` below
    dropped (``*live``) are no rows and are packed out before the exchange,
    and so is a row with a NULL key wherever it can match nothing and
    appear nowhere (either side of a semi or inner join, an anti join's
    right side); an anti join's NULL-keyed left rows ride, to the chip NULL
    hashes to, and come out. ``<label>.total`` therefore counts, for an
    anti join over a mesh, rows that a ``Filter`` kept; on one chip it
    cannot tell them from the dropped ones (``Join``).

    A mask join's output is the landed left rows under the occupied slots
    as their row mask; an inner join's is ``capacity`` (the resolved
    ``out_rows``) rows A CHIP, ``<label>.overflowed`` where any chip found
    more. ``total``, ``build_rows`` and the shuffles' rows are the whole
    request's, summed over the chips."""
    from spark_rapids_jni_tpu.ops.join import apply_join_maps, key_valid
    from spark_rapids_jni_tpu.parallel.distributed import shuffled_join
    from spark_rapids_jni_tpu.parallel.wire import shuffle_wire_bytes

    def anywhere(flag):
        return jax.lax.psum(jnp.asarray(flag).astype(jnp.int32), axis) > 0

    def of_all(count):
        return jax.lax.psum(jnp.asarray(count, jnp.int64), axis)

    chips = jax.lax.axis_size(axis)
    mask = node.how in _MASK_JOINS
    lkeys, rkeys = list(node.left_on), list(node.right_on)
    if mask:
        rtbl = Table([rtbl.column(k) for k in rkeys])
        rkeys = list(range(len(rkeys)))

    def riders(tbl, keys, rv, alive, keyed):
        send = key_valid(tbl, keys, rv) if keyed else (
            jnp.ones((tbl.num_rows,), jnp.bool_) if rv is None else rv)
        return send if alive is None else send & alive

    lsend = riders(ltbl, lkeys, lrv, llive, node.how != "left_anti")
    rsend = riders(rtbl, rkeys, rrv, rlive, True)
    sj = shuffled_join(ltbl, rtbl, lkeys, rkeys, axis, node.how,
                       None if mask else capacity,
                       left_row_valid=lsend, right_row_valid=rsend)
    ls, rs, joined = sj
    if mask:
        out = (_null_all(ls.table, joined.keep), ls.row_valid)
        facts = [("total", of_all(joined.total)),
                 ("build_rows", of_all(joined.build_rows)),
                 ("key_narrowed", anywhere(joined.key_narrowed))]
    else:
        with jax.named_scope("gather_rows"):
            out = (apply_join_maps(ls.table, rs.table, joined), None)
        facts = [("total", of_all(joined.total)),
                 ("build_rows", of_all(jnp.sum(rsend, dtype=jnp.int64))),
                 ("overflowed", anywhere(joined.total > capacity)),
                 ("probe_compacted", anywhere(joined.probe_compacted))]
    with jax.named_scope("exchange"):
        sent = [(ltbl, jnp.sum(lsend, dtype=jnp.int64), ls),
                (rtbl, jnp.sum(rsend, dtype=jnp.int64), rs)]
        facts += [
            ("shuffle_rows", of_all(sent[0][1] + sent[1][1])),
            # what the two all_to_alls carry between chips (a chip keeps
            # its own share): a fact of the schemas and the capacities,
            # known when the region is traced, as the next is
            ("shuffle_bytes", jnp.asarray(sum(
                (chips - 1) * shuffle_wire_bytes(
                    tbl, None, sh.row_valid.shape[0] // chips,
                    chips)["wire_bytes"] for tbl, _, sh in sent), jnp.int64)),
            # the slots of the receive buffers, both sides, every chip
            ("shuffle_capacity", jnp.asarray(
                chips * sum(sh.row_valid.shape[0] for _, _, sh in sent),
                jnp.int64)),
            # the bytes of the columns that rode, and a validity byte each,
            # of every row that entered a shuffle
            ("shuffle_read_bytes", of_all(sum(
                rows * sum(_column_row_bytes(c) + 1 for c in tbl.columns)
                for tbl, rows, _ in sent))),
            ("shuffle_overflowed", anywhere(ls.overflowed | rs.overflowed)),
        ]
    return out, facts, (joined.keep if mask else None)


def _bindings_mesh(bindings: dict, names: list) -> Optional[tuple]:
    """``(mesh, axis)`` when every table bound under ``names`` is
    row-sharded over the same axis of the same mesh; else None."""
    from spark_rapids_jni_tpu.parallel.mesh import table_row_mesh

    found = None
    for name in names:
        here = table_row_mesh(bindings[name])
        if here is None or (found is not None and here != found):
            return None
        found = here
    return found


def mesh_step(plan: Plan, local: dict, axis: str) -> FusedResult:
    """A plan's lowering over a mesh as seen by ONE chip, for a caller
    already inside ``shard_map`` over ``axis`` (a mesh that spans
    processes, which builds its own program): ``local`` binds every scan
    to this chip's rows, the result is the whole answer on every chip.
    ``execute`` builds the same step around row-sharded bindings."""
    nodes = _topo(plan.root)
    chips = jax.lax.axis_size(axis)
    true_rows = {name: tbl.num_rows * chips for name, tbl in local.items()}
    resolved = _resolve_statics(nodes, true_rows)
    placement = _mesh_placement(nodes, resolved)
    if placement is None:
        raise NotImplementedError(
            f"plan {plan.name!r} has no lowering over a mesh")
    with jax.named_scope(f"region.{plan.name}"):
        value, side = _eval_plan(plan.root, local, {}, resolved, true_rows,
                                 mesh_axis=axis, placement=placement)
    return FusedResult(value, _side_meta(side))


def _limit_bound(nodes, resolved: dict, spaces: dict,
                 true_rows: dict) -> None:
    """Clamp Limit counts to the true row count of their space so the
    fused (padded) head matches the staged (exact) head shape."""
    for node in nodes:
        if isinstance(node, Limit):
            space = spaces[id(node.child)]
            if space is not None:
                resolved[id(node)] = min(resolved[id(node)],
                                         int(true_rows[space]))


def _slice_to(out, n: int):
    """Trim a padded leading dimension back to the true row count."""
    from spark_rapids_jni_tpu.runtime.dispatch import _slice_tree

    if isinstance(out, Table):
        rows = out.num_rows
    elif isinstance(out, Column):
        rows = out.size
    else:
        return out
    if rows == n:
        return out
    return _slice_tree(out, n, rows)


# ---------------------------------------------------------------------------
# runtime-filter planner pass
# ---------------------------------------------------------------------------


def _subtree_rows_estimate(node, bindings: dict) -> int:
    """Static upper-ish bound on the distinct keys a subtree can feed a
    bloom build: bound scan rows summed, and any interior join's resolved
    out_rows taken as a floor (a join can expand past its scans). Used
    only for gating and bits sizing — an overestimate just buys a larger,
    lower-FPP filter, never a wrong result."""
    rows = 0
    for n in _topo(node):
        if isinstance(n, Scan) and n.name in bindings:
            rows += int(bindings[n.name].num_rows)
    for n in _topo(node):
        if isinstance(n, Join):
            spec = n.out_rows
            if isinstance(spec, int):
                rows = max(rows, spec)
            elif (isinstance(spec, tuple) and len(spec) == 3
                    and spec[0] == "rows_of" and spec[1] in bindings):
                rows = max(rows,
                           int(bindings[spec[1]].num_rows) * int(spec[2]))
    return rows


def inject_runtime_filters(plan: Plan, bindings: dict) -> Plan:
    """The RuntimeFilter planner pass: for each single-key inner Join
    (either direction — the smaller side builds) and each DensePkJoin
    (build side fixed by the layout), ask the learned gate
    (``runtime/rtfilter.decide`` — every decision recorded with a
    reason) whether a bloom filter pays, and when it does, insert a
    :class:`BloomBuild` over the build child and a :class:`BloomProbe`
    over the probe child. The probe sits INSIDE the region — below the
    fusion boundary — so the pruned scan fuses with everything above it;
    chunked paths prune per chunk on the host side instead, where compaction
    is free, and ``execute`` their regions ``runtime_filters=False``. Results
    are bit-identical with the pass on or off (see :class:`BloomProbe`);
    what changes is the dispatch fingerprint, so filtered and unfiltered
    plans never alias an executable."""
    from spark_rapids_jni_tpu.runtime import rtfilter

    root = plan.root
    done: set = set()
    while True:
        target = None
        for node in _topo(root):
            if isinstance(node, Join):
                if (node.how != "inner" or len(node.left_on) != 1
                        or len(node.right_on) != 1):
                    continue
                if node.label in done:
                    continue
                if isinstance(node.left, BloomProbe) \
                        or isinstance(node.right, BloomProbe):
                    done.add(node.label)
                    continue
                left_rows = _subtree_rows_estimate(node.left, bindings)
                right_rows = _subtree_rows_estimate(node.right, bindings)
                if right_rows <= left_rows:
                    sides = ("left", node.left, node.left_on[0],
                             node.right, node.right_on[0], right_rows)
                else:
                    sides = ("right", node.right, node.right_on[0],
                             node.left, node.left_on[0], left_rows)
                target = (node,) + sides
                break
            if isinstance(node, DensePkJoin):
                if node.label in done:
                    continue
                if isinstance(node.probe, BloomProbe):
                    done.add(node.label)
                    continue
                build_rows = _subtree_rows_estimate(node.build, bindings)
                target = (node, "probe", node.probe, node.probe_key,
                          node.build, node.build_key, build_rows)
                break
        if target is None:
            break
        node, side, probe_child, probe_key, build_child, build_key, \
            build_rows = target
        done.add(node.label)
        decision = rtfilter.decide(plan.name, node.label, build_rows)
        if not decision.apply:
            continue
        rtf_label = f"rtf_{node.label}"
        bb = BloomBuild(build_child, build_key, decision.num_bits,
                        decision.num_hashes, label=rtf_label)
        bp = BloomProbe(probe_child, bb, probe_key, decision.num_bits,
                        decision.num_hashes, label=rtf_label)
        if isinstance(node, DensePkJoin):
            new_node = node._replace(probe=bp)
        elif side == "left":
            new_node = node._replace(left=bp)
        else:
            new_node = node._replace(right=bp)
        root = replace_node(root, node, new_node)
    if root is plan.root:
        return plan
    return plan._replace(root=root)


def _harvest_rtfilter(plan: Plan, nodes, meta: dict) -> None:
    """Feed each probe's observed pass fraction back to the learned
    gate (no-op when the region produced tracers)."""
    probes = [n for n in nodes if isinstance(n, BloomProbe)]
    if not probes:
        return
    from spark_rapids_jni_tpu.runtime import rtfilter

    for n in probes:
        rtfilter.observe(plan.name, n.label,
                         meta.get(f"{n.label}.rows_in"),
                         meta.get(f"{n.label}.rows_pass"))


# ---------------------------------------------------------------------------
# the fuser
# ---------------------------------------------------------------------------


def split_at_exchange(plan: Plan):
    """Break a plan at its deepest INTERIOR ``Exchange`` node — the
    planner-placed exchange: regions already break at genuine host
    boundaries, and a mid-plan shuffle is one. Returns ``None`` when the
    plan has no interior Exchange (a root Exchange is the classic pack
    plan, handled by ``execute`` directly); otherwise
    ``(pack_plan, merge_plan, binding, exchange_node)`` where the pack
    plan roots the Exchange subtree and the merge plan is the remainder
    with the Exchange swapped for a ``Scan(binding)`` — exactly the
    hand-split plan pair shape ``QueryCluster.submit_exchange`` has
    always driven, derived instead of hand-written. Multi-exchange
    plans split one boundary at a time (deepest first); the remainder's
    own interior exchanges split recursively at execute time."""
    nodes = _topo(plan.root)
    xs = [n for n in nodes
          if isinstance(n, Exchange) and n is not plan.root]
    if not xs:
        return None
    x = xs[0]  # _topo is children-first: the deepest boundary splits first
    binding = f"__exchange__{x.label}"
    pack = Plan(f"{plan.name}.pack_{x.label}", x)
    merge = Plan(f"{plan.name}.merge_{x.label}",
                 replace_node(plan.root, x, Scan(binding)))
    return pack, merge, binding, x


def _trim_region_result(res: FusedResult, root) -> Table:
    """True-row slice of one per-destination merge-region result: an
    unbounded groupby root pads to its input row count, and only its
    ``<label>.num_groups`` rows are real."""
    from spark_rapids_jni_tpu.ops.table_ops import _slice_rows

    if isinstance(root, GroupBy) and root.max_groups is None:
        # region boundary: ``res`` is an already-executed region's
        # output, so reading its meta here cannot split a trace
        n = int(np.asarray(  # tpulint: disable=fusion-region-host-sync
            res.meta[f"{root.label}.num_groups"]))
        return _slice_rows(res.table, 0, n)
    return res.table


def _execute_midplan_exchange(plan: Plan, bindings: dict, *,
                              donate_inputs: bool,
                              force_staged: bool,
                              surface_pressure: bool,
                              cancel_token) -> FusedResult:
    """Execute a plan with an interior Exchange as region -> exchange ->
    region: run the pack half (an Exchange-rooted plan — the overflow
    ladder, valid_meta trim and wire form all apply unchanged), regroup
    the wire table per destination, run the remainder once per non-empty
    destination with the exchange output bound as its scan, and
    concatenate part-ordered. Destination key spaces are disjoint by
    construction, so the concatenation IS the plan's result —
    bit-identical to the hand-split (pack, merge) plan pair and to the
    ``exchange_local`` oracle over the same child output."""
    from spark_rapids_jni_tpu.ops.table_ops import _slice_rows, concatenate
    from spark_rapids_jni_tpu.runtime import exchange as _exchange

    pack_plan, merge_plan, binding, x = split_at_exchange(plan)
    pb, pe = _scan_names(_topo(x))
    pack_bindings = {n: bindings[n] for n in pb + pe if n in bindings}
    x = _exchange.resolve_auto_parts(pack_plan.name, x, pack_bindings)
    pack_plan = Plan(pack_plan.name, x)
    mb, me = _scan_names(_topo(merge_plan.root))
    merge_scans = (set(mb) | set(me)) - {binding}
    # the pack may only donate bindings the remainder never rereads
    donate_pack = (bool(donate_inputs)
                   and not (merge_scans & set(pack_bindings)))
    REGISTRY.counter("fusion.midplan_exchanges").inc()
    label, parts = x.label, int(x.parts)
    with spans.child(f"midplan.{plan.name}", label=label, parts=parts):
        fused = execute(pack_plan, pack_bindings,
                        donate_inputs=donate_pack,
                        force_staged=force_staged,
                        surface_pressure=surface_pressure,
                        cancel_token=cancel_token)
        rc = fused.meta[f"{label}.row_counts"]
        per_dest = _exchange.split_wire(fused.table, rc, parts)
        empty = _slice_rows(fused.table, 0, 0)
        merge_base = {n: bindings[n] for n in merge_scans
                      if n in bindings}
        outs: list = []
        for flights in per_dest:
            if not flights:
                continue
            dest_in = (flights[0] if len(flights) == 1
                       else concatenate(flights))
            res = execute(merge_plan, {**merge_base, binding: dest_in},
                          force_staged=force_staged,
                          surface_pressure=surface_pressure,
                          cancel_token=cancel_token)
            outs.append(_trim_region_result(res, merge_plan.root))
        if outs:
            tbl = outs[0] if len(outs) == 1 else concatenate(outs)
        else:
            res = execute(merge_plan, {**merge_base, binding: empty},
                          force_staged=force_staged,
                          surface_pressure=surface_pressure,
                          cancel_token=cancel_token)
            tbl = _slice_rows(res.table, 0, 0)
    meta = {
        f"{label}.parts": parts,
        f"{label}.rows": int(fused.meta[f"{label}.rows"]),
        f"{label}.dests": len(outs),
    }
    root = merge_plan.root
    if isinstance(root, GroupBy) and root.max_groups is None:
        # the concatenation is already trimmed: every row is real
        meta[f"{root.label}.num_groups"] = int(tbl.num_rows)
    return FusedResult(tbl, meta)


def execute(plan: Plan, bindings: dict, *,
            donate_inputs: bool = False,
            force_staged: bool = False,
            surface_pressure: bool = False,
            cancel_token=None, runtime_filters: bool = True) -> FusedResult:
    """Run one fusible region.

    ``bindings`` maps every Scan name to a Table. With ``fusion.enabled``
    the whole region dispatches as ONE callable through ``dispatch.call``
    (op name ``fusion.<plan.name>``): bucketed scans are the row groups,
    exact scans ride as aux args, and each per-op implementation inlines
    into the single trace. With fusion disabled — or when the bindings are
    tracers, dispatch is disabled, or compilation fails — the exact same
    node walk runs op-by-op (each op dispatching itself), which IS the
    staged reference path; results are bit-identical either way.

    ``donate_inputs=True`` declares every bound table dead after the call
    (intermediates the caller owns — never user-visible inputs); see the
    module docstring.

    ``force_staged=True`` takes the staged reference path for THIS call
    regardless of the global ``fusion.enabled`` option — the per-query
    knob the degradation ladder (runtime/degrade.py) steps a live query
    down on without flipping global state under concurrent sessions.
    ``surface_pressure=True`` lets PRESSURE-classified failures
    (``ResourceExhausted`` / ``CapacityOverflow``) that exhaust the retry
    budget propagate instead of silently taking the implicit staged
    fallback, so the degradation controller can take — and account for —
    the fused->staged step itself. Non-pressure failures keep the
    fallback either way.

    ``cancel_token`` (a ``resilience.CancelToken``) is checked at the
    region boundary before any compute or donation happens; cancellation
    raises ``QueryCancelled`` with the bound inputs untouched.
    """
    if cancel_token is not None:
        cancel_token.check(f"fusion.{plan.name}")
    if isinstance(plan.root, Exchange):
        # host boundary: partition-hash pack + wire framing happen outside
        # any fused region — runtime.exchange runs the child plan, then
        # packs per-destination flights on the host side of the seam
        from spark_rapids_jni_tpu.runtime import exchange as _exchange
        return _exchange.execute_exchange_root(
            plan, bindings,
            donate_inputs=donate_inputs,
            force_staged=force_staged,
            surface_pressure=surface_pressure,
            cancel_token=cancel_token)
    if split_at_exchange(plan) is not None:
        # planner-placed mid-plan exchange: break the region at the
        # interior Exchange and run region -> exchange -> region
        return _execute_midplan_exchange(
            plan, bindings,
            donate_inputs=donate_inputs,
            force_staged=force_staged,
            surface_pressure=surface_pressure,
            cancel_token=cancel_token)
    if runtime_filters and get_option("rtfilter.enabled"):  # see the pass
        plan = inject_runtime_filters(plan, bindings)
    nodes = _topo(plan.root)
    bucketed, exact = _scan_names(nodes)
    for name in bucketed + exact:
        if name not in bindings:
            raise KeyError(f"plan {plan.name!r} scans unbound table "
                           f"{name!r}")
    true_rows = {name: bindings[name].num_rows for name in bucketed + exact}
    resolved = _resolve_statics(nodes, true_rows)
    spaces = _spaces(nodes)
    _limit_bound(nodes, resolved, spaces, true_rows)
    static_meta = {
        f"{n.label}.lowered": _planned_lowering(n)
        for n in nodes
        if isinstance(n, GroupBy) and n.domains is not None
    }
    # a join whose probe side holds a scan's rows says how many probed it:
    # the denominator of its ``<label>.total``; one that lays rows out
    # says how many it had room for
    for n in nodes:
        if isinstance(n, (Join, DensePkJoin)):
            rows = _scanned_rows(
                n.left if isinstance(n, Join) else n.probe, true_rows)
            if rows is not None:
                static_meta[f"{n.label}.probe_rows"] = rows
        if isinstance(n, Join) and n.how not in _MASK_JOINS:
            static_meta[f"{n.label}.capacity"] = resolved[id(n)]
        if (isinstance(n, GroupBy) and n.domains is None
                and resolved[id(n)] is not None):
            static_meta[f"{n.label}.capacity"] = resolved[id(n)]
    # rows sharded over a mesh axis are the signal, and the only one, that
    # the region runs across chips (see "lowering over a mesh" above)
    over = _bindings_mesh(bindings, bucketed)
    placement = _mesh_placement(nodes, resolved) if over else None
    side_keys = _side_keys(nodes, placement)
    # of the region run over the mesh: a join that lays rows out there has
    # room for ``out_rows`` on every chip
    mesh_meta = {
        f"{n.label}.capacity": resolved[id(n)] * int(over[0].shape[over[1]])
        for n in nodes if placement is not None and isinstance(n, Join)
        and n.how not in _MASK_JOINS and placement[id(n)] == SHARDED}

    def _staged_eval() -> FusedResult:
        # the staged reference path (the bit-identity oracle): the same
        # node walk op-by-op, each op dispatching itself. The region seam
        # fires here too (seq=1; the fused attempt is seq=0) so chaos
        # scripts can kill each tier independently — per-op dispatch
        # failures below never propagate (dispatch falls back to the
        # host inline path), so this is the staged tier's one seam
        faults.fire("fusion.region", 1, plan=plan.name, staged=True)
        REGISTRY.counter("fusion.staged_regions").inc()
        with spans.child(f"region.{plan.name}", mode="staged"):
            tables = {name: bindings[name] for name in bucketed + exact}
            rvs = {name: None for name in tables}
            value, side = _eval_plan(plan.root, tables, rvs, resolved,
                                     true_rows)
        meta = _side_meta(side)
        meta.update(static_meta)
        res = FusedResult(value, meta)
        _harvest_rtfilter(plan, nodes, res.meta)
        return res

    if force_staged or not get_option("fusion.enabled"):
        return _staged_eval()

    from spark_rapids_jni_tpu.runtime import dispatch

    REGISTRY.counter("fusion.regions").inc()
    REGISTRY.counter("fusion.nodes_fused").inc(len(nodes))

    row_args = tuple(bindings[name] for name in bucketed)
    aux_args = tuple(bindings[name] for name in exact)
    fingerprint = _fingerprint(nodes, resolved)

    def _region(row_args_, aux_args_, row_valids):
        rvs_ = row_valids if row_valids is not None \
            else (None,) * len(bucketed)
        tables = dict(zip(bucketed, row_args_))
        tables.update(zip(exact, aux_args_))
        rvmap = dict(zip(bucketed, rvs_))
        with jax.named_scope(f"region.{plan.name}"):
            value, side = _eval_plan(plan.root, tables, rvmap, resolved,
                                     true_rows)
        return value, tuple(v for _, v in side)

    # jit names the compiled module after the function: a profiler trace
    # then shows jit_region_<plan>, which a reader can key on, and not the
    # same jit__region for every plan
    _region.__name__ = _region.__qualname__ = "region_" + re.sub(
        r"\W", "_", plan.name)

    donate = (bool(donate_inputs) and bool(get_option("fusion.donate"))
              and bool(bucketed))

    def _mesh_region():
        """The region as one ``shard_map`` over the bindings' mesh: every
        chip pads its own rows to a chip's bucket (``dispatch.pad_sharded``)
        and runs the plan on them; the result is whole on every chip."""
        from jax.sharding import PartitionSpec as P

        mesh, axis = over
        padded, row_valids = dispatch.pad_sharded(
            f"fusion.{plan.name}", row_args, mesh, axis)

        def build():
            def step(groups, rvs):
                with jax.named_scope(f"region.{plan.name}"):
                    value, side = _eval_plan(
                        plan.root, dict(zip(bucketed, groups)),
                        dict(zip(bucketed, rvs)), resolved, true_rows,
                        mesh_axis=axis, placement=placement)
                return value, tuple(v for _, v in side)

            # all_gather gives every chip the same rows, which the
            # replication check cannot see: it is off
            region = jax.shard_map(
                step, mesh=mesh, in_specs=(P(axis), P(axis)),
                out_specs=P(), check_vma=False)
            region.__name__ = region.__qualname__ = _region.__name__
            return region

        return dispatch.sharded_call(
            f"fusion.{plan.name}", build, (padded, row_valids),
            statics=("fusion", fingerprint, axis,
                     dispatch.mesh_fingerprint(mesh)))

    def _dispatch_region():
        # the seam fires BEFORE dispatch.call touches (and possibly
        # donates) the bound buffers, so both the retry and the staged
        # fallback below replay against intact inputs
        faults.fire("fusion.region", 0, plan=plan.name)
        with spans.child(f"region.{plan.name}", mode="fused"):
            if placement is not None:
                return _mesh_region()
            return dispatch.call(
                f"fusion.{plan.name}", _region, row_args, aux_args,
                statics=("fusion", fingerprint), slice_rows=False,
                donate_rows=donate)

    if resilience.enabled():
        out, exc = resilience.retry_or_none(
            f"fusion.{plan.name}", _dispatch_region,
            seam="fusion.region", rung="staged_fallback")
        if exc is not None:
            if not isinstance(exc, Exception):
                raise exc
            if surface_pressure:
                # the degradation controller owns tier transitions under
                # memory pressure: let the classified failure surface so
                # the step is taken — and accounted — at the ladder, not
                # silently here; anything else still falls back below
                kind = resilience.classify(exc)
                if kind is resilience.ResourceExhausted or issubclass(
                        kind, resilience.CapacityOverflow):
                    raise exc
            # final ladder rung: run the region through the staged
            # evaluator (bit-identical) and account for it
            record_fallback(
                f"fusion.{plan.name}",
                f"fused region dispatch failed "
                f"({type(exc).__name__}): staged evaluator fallback")
            return _staged_eval()
        value, side_vals = out
    else:
        value, side_vals = _dispatch_region()

    root_space = spaces[id(plan.root)]
    if root_space is not None:
        value = _slice_to(value, int(true_rows[root_space]))
    meta = _side_meta(zip(side_keys, side_vals))
    meta.update(static_meta)
    meta.update(mesh_meta)
    _harvest_rtfilter(plan, nodes, meta)
    return FusedResult(value, meta)


def meta_facts(plan: Plan, meta: dict) -> dict:
    """What the filters, joins and groupbys of ``plan`` report in a result's
    ``meta``, summed over its nodes: real rows a predicate saw and rows it
    kept, and the bytes of string ``chars`` it matched against a pattern
    (``filter.rows_in``, ``filter.rows_kept``, ``strings.like_bytes``), rows
    that probed and rows that matched
    (joins that say both), semi and anti joins whose merged sort carried a
    64-bit key as one word (``join.key_narrowed``: a fact of the data,
    ``ops/join.py``), the rows the joins that lay rows out had room for
    (``join.capacity_rows``), how many of them ran on the probe rows that
    can emit alone (``join.probe_compacted``: a fact of the data as
    well), how many outgrew their room
    (``join.overflowed``) and the true totals of those
    (``join.overflow_rows``), groups, what the groupbys and joins lowered
    over a mesh shuffled (``shuffle.exchanges``: one a groupby, two a join;
    the rows sent and the bytes the ``all_to_all``s put between chips; of a
    join's exchanges also the slots of the receive buffers summed over the
    chips, ``shuffle.capacity_rows``, the bytes of the columns that rode and
    a validity byte each of every row sent, ``shuffle.read_bytes``, and how
    many joins had a shuffle drop rows, ``shuffle.overflowed``), and how
    many nodes broke what the plan
    declares: a dense primary key that is not one (``pk_violation``), a
    group bound or a join's capacity that was too small (``overflowed``),
    a key outside its declared range (``key_out_of_range``); the real rows
    that entered the sort-path groupbys, the bytes of the key and
    aggregated columns those rows hold, and the groups their bounds have
    room for (``groupby.rows_in``, ``groupby.read_bytes``,
    ``groupby.capacity_groups``); and how many
    groupbys took
    their aggregates over the rows where they lie, no value word brought
    into key order (``groupby.in_place``: a fact of the lowering,
    ``ops/groupby.py``), how many of those sorted their key words to count
    the groups past a broken bound (``groupby.key_sorted``: a fact of the
    data; a bound of 64 or fewer that holds finds its groups with no
    sort), how many ordered a lone 64-bit key nobody declared a range for
    as ONE word (``groupby.key_one_word``: a fact of the data,
    ``ops/sort.py _lone_key_order``), and how many grouped a key at the width of its
    declared range (``groupby.key_narrowed``: a fact of the lowering too,
    ``ops/planner.narrow_group_keys``), and how many sorts ordered the rows
    before their input's padding alone (``sort.prefix_sorted``: a fact of
    the data, ``ops/sort.py sort_before_padding``). A result with a broken
    declaration is a wrong answer; the served path refuses it
    (``QueryServer._account_meta``). Converting a meta value waits for the
    device, so call it where the meta is wanted on the host anyway."""
    facts = {"join.probe_rows": 0, "join.matched_rows": 0,
             "join.build_rows": 0, "join.key_narrowed": 0,
             "join.probe_compacted": 0,
             "join.capacity_rows": 0, "join.overflowed": 0,
             "join.overflow_rows": 0,
             "join.pk_violation": 0, "groupby.groups": 0,
             "groupby.overflowed": 0, "groupby.in_place": 0,
             "groupby.key_sorted": 0, "groupby.key_one_word": 0,
             "groupby.key_narrowed": 0, "groupby.key_out_of_range": 0,
             "groupby.rows_in": 0, "groupby.read_bytes": 0,
             "groupby.capacity_groups": 0,
             "shuffle.exchanges": 0, "shuffle.rows": 0, "shuffle.bytes": 0,
             "shuffle.capacity_rows": 0, "shuffle.read_bytes": 0,
             "shuffle.overflowed": 0,
             "filter.rows_in": 0, "filter.rows_kept": 0,
             "strings.like_bytes": 0, "sort.prefix_sorted": 0}
    nodes = _topo(plan.root)
    scopes = node_scopes(nodes)
    for node in nodes:
        if isinstance(node, Filter):
            for fact, field in (("filter.rows_in", "rows_in"),
                                ("filter.rows_kept", "rows_kept"),
                                ("strings.like_bytes", "like_bytes")):
                facts[fact] += int(meta.get(f"{node.label}.{field}", 0))
        elif isinstance(node, (Join, DensePkJoin)):
            total = meta.get(f"{node.label}.total")
            rows = meta.get(f"{node.label}.probe_rows")
            if total is not None and rows is not None:
                facts["join.probe_rows"] += int(rows)
                facts["join.matched_rows"] += int(total)
            facts["join.build_rows"] += int(
                meta.get(f"{node.label}.build_rows", 0))
            for fact in ("key_narrowed", "probe_compacted"):
                facts[f"join.{fact}"] += bool(
                    meta.get(f"{node.label}.{fact}", False))
            facts["join.capacity_rows"] += int(
                meta.get(f"{node.label}.capacity", 0))
            if bool(meta.get(f"{node.label}.overflowed", False)):
                facts["join.overflowed"] += 1
                facts["join.overflow_rows"] += int(total)
            facts["join.pk_violation"] += bool(
                meta.get(f"{node.label}.pk_violation", False))
            if f"{node.label}.shuffle_rows" in meta:
                # lowered over a mesh: an all_to_all a side
                facts["shuffle.exchanges"] += 2
                for fact, field in (("rows", "shuffle_rows"),
                                    ("bytes", "shuffle_bytes"),
                                    ("capacity_rows", "shuffle_capacity"),
                                    ("read_bytes", "shuffle_read_bytes")):
                    facts[f"shuffle.{fact}"] += int(
                        meta[f"{node.label}.{field}"])
                facts["shuffle.overflowed"] += bool(
                    meta[f"{node.label}.shuffle_overflowed"])
        elif isinstance(node, GroupBy):
            groups = meta.get(f"{node.label}.num_groups")
            if groups is not None:
                facts["groupby.groups"] += int(groups)
            facts["groupby.overflowed"] += bool(
                meta.get(f"{node.label}.overflowed", False))
            for fact in ("in_place", "key_sorted", "key_one_word",
                         "key_narrowed", "key_out_of_range"):
                facts[f"groupby.{fact}"] += bool(
                    meta.get(f"{node.label}.{fact}", False))
            for fact, field in (("rows_in", "rows_in"),
                                ("read_bytes", "read_bytes"),
                                ("capacity_groups", "capacity")):
                facts[f"groupby.{fact}"] += int(
                    meta.get(f"{node.label}.{field}", 0))
            sent = meta.get(f"{node.label}.shuffle_rows")
            if sent is not None:   # lowered over a mesh: one all_to_all
                facts["shuffle.exchanges"] += 1
                facts["shuffle.rows"] += int(sent)
                facts["shuffle.bytes"] += int(
                    meta[f"{node.label}.shuffle_bytes"])
        elif isinstance(node, Sort):
            facts["sort.prefix_sorted"] += bool(
                meta.get(f"{scopes[id(node)]}.prefix_sorted", False))
    return facts


def plan_fingerprint(plan: Plan, bindings: dict) -> tuple:
    """Canonical structural digest of a whole plan against its bound row
    counts — the plan-signature half of the result-cache key
    (runtime/resultcache.py). Deliberately excludes ``plan.name``: two
    plans that trace identically produce identical results, whatever they
    are called. Row-count-derived statics resolve (and Limit counts clamp)
    exactly as :func:`execute` resolves them, so a cached entry can never
    be replayed against a binding set the executable would have shaped
    differently — everything else row-dependent is covered by the input
    fingerprint half of the key."""
    nodes = _topo(plan.root)
    bucketed, exact = _scan_names(nodes)
    for name in bucketed + exact:
        if name not in bindings:
            raise KeyError(f"plan {plan.name!r} scans unbound table "
                           f"{name!r}")
    true_rows = {name: bindings[name].num_rows for name in bucketed + exact}
    resolved = _resolve_statics(nodes, true_rows)
    _limit_bound(nodes, resolved, _spaces(nodes), true_rows)
    return _fingerprint(nodes, resolved)


def scan_prefix_chains(root) -> list:
    """Maximal single-consumer chains of Filter / rowwise-Project nodes
    sitting directly on a bucketed Scan — the shareable scan+filter+project
    prefixes subplan caching keys on. Returns ``(scan, top, length)``
    tuples where ``top`` is the highest chain node and ``length`` counts
    the non-Scan nodes in it; ``top`` is never ``root`` itself (a whole-
    plan prefix is the final-result cache's job). Only mask-preserving
    nodes qualify: Filter nulls validity in place and a rowwise Project
    stays in the scan's row space, so the materialized chain output is a
    drop-in replacement table for any consumer."""
    nodes = _topo(root)
    consumers: dict = {}
    for node in nodes:
        for c in _children(node):
            consumers.setdefault(id(c), []).append(node)
    chains = []
    for node in nodes:
        if not (isinstance(node, Scan) and node.bucket):
            continue
        top, length = node, 0
        while True:
            nexts = consumers.get(id(top), [])
            if len(nexts) != 1 or nexts[0] is root:
                break
            nxt = nexts[0]
            if isinstance(nxt, Filter):
                pass
            elif isinstance(nxt, Project) and nxt.rowwise:
                pass
            else:
                break
            top, length = nxt, length + 1
        if length > 0:
            chains.append((node, top, length))
    return chains


def replace_node(root, target, replacement):
    """Rebuild the plan DAG with ``target`` (matched by object identity)
    swapped for ``replacement`` — the subplan-cache rewrite: a cached
    prefix's subtree becomes a Scan bound to the materialized
    intermediate. Shared nodes stay shared; untouched subtrees are reused
    as-is."""
    memo: dict = {id(target): replacement}

    def rebuild(node):
        if id(node) in memo:
            return memo[id(node)]
        kids = _children(node)
        new_kids = tuple(rebuild(c) for c in kids)
        if all(nk is k for nk, k in zip(new_kids, kids)):
            out = node
        elif isinstance(node, (Filter, Project, GroupBy, Sort, Limit,
                               BloomBuild, Exchange)):
            out = node._replace(child=new_kids[0])
        elif isinstance(node, Join):
            out = node._replace(left=new_kids[0], right=new_kids[1])
        elif isinstance(node, DensePkJoin):
            out = node._replace(probe=new_kids[0], build=new_kids[1])
        elif isinstance(node, BloomProbe):
            out = node._replace(child=new_kids[0], build=new_kids[1])
        else:  # pragma: no cover - Scan has no children to rebuild
            out = node
        memo[id(node)] = out
        return out

    return rebuild(root)


def estimate_hbm_bytes(plan: Plan, bindings: dict) -> int:
    """Plan-aware HBM footprint estimate for serving admission control.

    The inputs' exact device bytes plus the materialized output of every
    capacity-bearing node — joins at their resolved ``out_rows``, groupbys
    at their group budget — each costed at the inputs' mean row width. An
    estimate the admission gate reserves through the ``MemoryLimiter``,
    not a hard bound: ``runtime/server.py`` applies the configured
    ``server.estimate_headroom`` multiplier on top for intermediates this
    static walk cannot see.

    The bytes are ONE chip's, as the limiter's budget is: a binding whose
    buffers are sharded over a mesh costs a chip its shard
    (``memory.table_chip_nbytes``), so a table that would not fit one chip
    is admitted when its shard does, and rejected when even that does not.
    """
    from spark_rapids_jni_tpu.runtime.memory import (
        _table_nbytes,
        table_chip_nbytes,
    )

    nodes = _topo(plan.root)
    bucketed, exact = _scan_names(nodes)
    for name in bucketed + exact:
        if name not in bindings:
            raise KeyError(f"plan {plan.name!r} scans unbound table "
                           f"{name!r}")
    true_rows = {name: bindings[name].num_rows for name in bucketed + exact}
    resolved = _resolve_statics(nodes, true_rows)
    input_bytes = sum(
        table_chip_nbytes(bindings[name]) for name in bucketed + exact)
    total_rows = max(1, sum(true_rows.values()))
    row_width = max(1, sum(
        _table_nbytes(bindings[name])
        for name in bucketed + exact) // total_rows)
    out_rows = 0
    extra_bytes = 0
    for node in nodes:
        if isinstance(node, (Join, DensePkJoin)):
            out_rows += int(resolved[id(node)] or 0)
        elif isinstance(node, GroupBy):
            cap = resolved.get(id(node))
            out_rows += int(cap if cap is not None else node.budget)
        elif isinstance(node, BloomBuild):
            # byte-per-bit filter plus the (n, k) position scratch
            extra_bytes += int(node.num_bits)
        elif isinstance(node, Exchange):
            # destination-sorted pack materializes parts * capacity rows
            cap = resolved.get(id(node))
            if cap is not None:
                out_rows += int(node.parts) * int(cap)
    return int(input_bytes + out_rows * row_width + extra_bytes)


def _planned_lowering(node: GroupBy) -> str:
    """The static ``lowered`` plan fact, mirroring ``plan_groupby``'s
    eligibility check (it never depends on data)."""
    bounded_ok = (
        all(d is not None for d in node.domains)
        and all(op in ("sum", "count", "mean", "min", "max")
                for _, op in node.aggs)
        and int(np.prod([len(d.values) + 1 for d in node.domains]))
        <= node.budget
    )
    return "bounded" if bounded_ok else "general"


# ---------------------------------------------------------------------------
# introspection
# ---------------------------------------------------------------------------


def stats() -> dict:
    """Aggregate fusion counters for the bench ``fusion`` block:
    regions/nodes fused, executables per query (the
    ``dispatch.compile.fusion.<plan>`` counters), and donated bytes."""
    c = REGISTRY.counters("fusion.")
    d = REGISTRY.counters("dispatch.compile.fusion.")
    per_query = {
        name[len("dispatch.compile.fusion."):]: count
        for name, count in sorted(d.items())
    }
    return {
        "regions": c.get("fusion.regions", 0),
        "staged_regions": c.get("fusion.staged_regions", 0),
        "nodes_fused": c.get("fusion.nodes_fused", 0),
        "executables": sum(per_query.values()),
        "executables_per_query": per_query,
        "donated_bytes": REGISTRY.counters("dispatch.").get(
            "dispatch.donated_bytes", 0),
    }
