"""TPC-H q14, whole, as a served Plan (``tpch._q14_plan``): the join as
``fusion.Join(how="inner")`` on part keys nobody declared anything about,
``part`` in a seeded permutation, ``p_type`` carried through the join and
the ``LIKE`` above it; held to the benchmark's plain-numpy reference
(``benchmark/reference_q14.py``) and to ``tpch_q14_numpy`` case by case
through ``QueryServer`` and ``fusion.execute``; the capacity a join states
as a guarantee of the served path; and the merged-sort ``join()``'s maps
against a numpy oracle of the pairs for every ``how`` and key width."""

import os
import sys
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from spark_rapids_jni_tpu import types as t
from spark_rapids_jni_tpu.columnar import Column, Table
from spark_rapids_jni_tpu.models import tpch
from spark_rapids_jni_tpu.ops import join as join_ops
from spark_rapids_jni_tpu.ops.join import join
from spark_rapids_jni_tpu.runtime import fusion, resilience
from spark_rapids_jni_tpu.telemetry import REGISTRY

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness, reference_q14  # noqa: E402

PARTS, ITEMS = 500, 12100
MONTH = reference_q14.MONTH


def _host(seed: int, parts: int = PARTS, items: int = ITEMS) -> dict:
    """``{table: host copy}`` of seeded tables by the benchmark's own
    makers, through the harness (which hands each maker its seed)."""
    config = {"tables": {"part": {"maker": "part_q14", "rows": parts},
                         "lineitem": {"maker": "lineitem_q14",
                                      "rows": items}}}
    return {name: {c: np.array(a) for c, a in maker.host_copy(arrays).items()}
            for name, (maker, _, arrays)
            in harness.make_tables(config, seed, {}).items()}


def _valid(table: dict, name: str):
    mask = table.get(name + "_valid")
    return None if mask is None else jnp.asarray(mask)


def _device(host: dict) -> dict:
    li, p = host["lineitem"], host["part"]
    return {
        "lineitem": Table([
            Column(t.INT64, jnp.asarray(li["l_partkey"]),
                   _valid(li, "l_partkey")),
            Column(t.decimal64(-2), jnp.asarray(li["l_extendedprice"])),
            Column(t.decimal64(-2), jnp.asarray(li["l_discount"])),
            Column(t.TIMESTAMP_DAYS, jnp.asarray(li["l_shipdate"]))]),
        "part": Table([
            Column(t.INT64, jnp.asarray(p["p_partkey"]),
                   _valid(p, "p_partkey")),
            Column(t.STRING, jnp.asarray(p["p_type_len"]),
                   chars=jnp.asarray(p["p_type"]))])}


def _arrow_part(part: Table) -> Table:
    """The part table ``tpch_q14_numpy`` reads: the type as Python
    strings."""
    lengths = np.asarray(part.column(1).data)
    chars = np.asarray(part.column(1).chars)
    text = [bytes(c[:n]).decode() for c, n in zip(chars, lengths)]
    return Table([part.column(0), Column.from_pylist(text, t.STRING)])


@pytest.fixture(scope="module")
def server():
    from spark_rapids_jni_tpu.runtime.server import QueryServer

    with QueryServer(budget_bytes=4 << 30) as srv:
        yield srv


def _serve(server, plan, bindings):
    ticket = server.session("q14").submit(plan, bindings)
    result = ticket.result()
    assert (ticket.tier, ticket.rung, ticket.steps) == ("fused", 0, 0)
    return result


def _month_keys(host) -> np.ndarray:
    li = host["lineitem"]
    return li["l_partkey"][reference_q14.month_rows(li)]


def _duplicated_part_key(host):
    """Two part rows hold a key the month's lineitems ask for: both
    count."""
    p = host["part"]
    key = _month_keys(host)[0]
    at = int(np.flatnonzero(p["p_partkey"] == key)[0])
    p["p_partkey"][(at + 1) % PARTS] = key


def _absent_part_key(host):
    """A key the month's lineitems ask for is in no part row."""
    p = host["part"]
    p["p_partkey"][p["p_partkey"] == _month_keys(host)[0]] = PARTS + 7


def _null_keys(host):
    rng = np.random.default_rng(5)
    host["lineitem"]["l_partkey_valid"] = rng.random(ITEMS) > 0.2
    host["part"]["p_partkey_valid"] = rng.random(PARTS) > 0.2


# case -> (what it does to the seeded tables, the month asked for)
CASES = {
    "part_permuted": (None, MONTH),
    "a_duplicated_part_key_counts_twice": (_duplicated_part_key, MONTH),
    "a_key_absent_from_part": (_absent_part_key, MONTH),
    "null_keys_on_either_side": (_null_keys, MONTH),
    "an_empty_month": (None, (20000, 20030)),
    "every_lineitem_in_the_month": (None, (0, 30000)),
}


@pytest.mark.parametrize("case", list(CASES))
def test_served_q14_equals_both_references(server, case):
    change, month = CASES[case]
    host = _host(4500 + list(CASES).index(case))
    assert not np.array_equal(host["part"]["p_partkey"],
                              np.arange(1, PARTS + 1))    # permuted
    want_before = reference_q14.q14(host, month)
    if change is not None:
        change(host)
    want = reference_q14.q14(host, month)
    plan = tpch._q14_plan(*month)
    bindings = _device(host)
    before = REGISTRY.counters()
    served = _serve(server, plan, bindings)
    moved = {k: v - before.get(k, 0) for k, v in REGISTRY.counters().items()}
    got = reference_q14.read_answer(served.table)
    assert reference_q14.compare(got, want) == {"q14.sum_mismatches": 0}
    assert got == want
    # the program's own oracle, a Python loop over the same rows (it knows
    # no NULL key and no duplicate: only where the case has none)
    if change in (None, _absent_part_key):
        promo, total = tpch.tpch_q14_numpy(
            _arrow_part(bindings["part"]), bindings["lineitem"], *month)
        assert (got["promo_revenue"] or 0, got["total_revenue"] or 0) == (
            promo, total)
    # the same through fusion.execute, fused and staged
    for staged in (False, True):
        direct = fusion.execute(plan, bindings, force_staged=staged)
        assert reference_q14.read_answer(direct.table) == want
    # one region, and what the join reports, once a request
    li, p = host["lineitem"], host["part"]
    in_month = (li["l_shipdate"] >= month[0]) & (li["l_shipdate"] < month[1])
    assert moved["fusion.regions"] == 1
    assert moved.get("fusion.staged_regions", 0) == 0
    assert moved["filter.rows_in"] == ITEMS
    assert moved["filter.rows_kept"] == int(in_month.sum())
    meta = {k: int(v) for k, v in served.meta.items()
            if k.startswith("part_join.")}
    assert set(meta) == {"part_join.total", "part_join.build_rows",
                         "part_join.probe_rows", "part_join.capacity",
                         "part_join.overflowed",
                         "part_join.probe_compacted"}
    assert meta["part_join.build_rows"] == int(
        np.sum(p.get("p_partkey_valid", np.ones(PARTS, bool))))
    assert meta["part_join.probe_rows"] == ITEMS
    assert meta["part_join.capacity"] == ITEMS     # the plan's default
    assert meta["part_join.overflowed"] == 0
    # (a capacity at the probe's rows: the join runs on all of them)
    assert meta["part_join.probe_compacted"] == 0
    assert moved.get("join.probe_compacted", 0) == 0
    assert moved["join.capacity_rows"] == ITEMS
    assert moved.get("join.overflowed", 0) == 0
    assert moved.get("join.matched_rows", 0) == meta["part_join.total"]
    assert moved["join.probe_rows"] == ITEMS
    # every lineitem of the month once for every part row holding its key
    keyed = in_month & li.get("l_partkey_valid", True)
    held = p["p_partkey"][p.get("p_partkey_valid", np.ones(PARTS, bool))]
    values, holders = np.unique(held, return_counts=True)
    asked = li["l_partkey"][keyed]
    at = np.clip(np.searchsorted(values, asked), 0, len(values) - 1)
    matches = np.where(values[at] == asked, holders[at], 0)
    assert meta["part_join.total"] == int(matches.sum())
    if case == "part_permuted":
        assert meta["part_join.total"] == int(keyed.sum()) > 100
        assert 0 < want["promo_revenue"] < want["total_revenue"]
    if case == "a_duplicated_part_key_counts_twice":
        assert matches.max() == 2
    if case == "a_key_absent_from_part":
        assert matches.min() == 0
        assert want["total_revenue"] < want_before["total_revenue"]
    if case == "an_empty_month":
        assert want == {"promo_revenue": None, "total_revenue": None}
        assert meta["part_join.total"] == 0


def test_the_plan_declares_nothing_about_either_key():
    plan = tpch._q14_plan()
    nodes = fusion._topo(plan.root)
    assert not any(isinstance(n, fusion.DensePkJoin) for n in nodes)
    assert not any(isinstance(n, fusion.GroupBy) for n in nodes)
    (node,) = [n for n in nodes if isinstance(n, fusion.Join)]
    assert (node.how, node.label) == ("inner", "part_join")
    assert all(isinstance(n, fusion.Scan) and n.bucket for n in nodes
               if isinstance(n, fusion.Scan))


@pytest.mark.parametrize("parts, items", [(512, 8192), (513, 8193),
                                          (300, 7000)])
def test_probe_and_build_padding_match_nothing(parts, items):
    """On a bucket's edge and one past it (511 and 8,191 phantom rows):
    a phantom row of either side holds key bytes (zeros) and no key."""
    host = _host(77, parts, items)
    # a real lineitem of the month and a real part with the phantoms' key
    host["lineitem"]["l_partkey"][3] = 0
    host["lineitem"]["l_shipdate"][3] = MONTH[0]
    got = fusion.execute(tpch._q14_plan(), _device(host))
    assert reference_q14.read_answer(got.table) == reference_q14.q14(host)
    host["part"]["p_partkey"][7] = 0
    got = fusion.execute(tpch._q14_plan(), _device(host))
    assert reference_q14.read_answer(got.table) == reference_q14.q14(host)


def test_control_one_flipped_type_is_not_correct():
    host = _host(91)
    numbers = reference_q14.compare(reference_q14.control(host),
                                    reference_q14.q14(host))
    assert numbers["q14.sum_mismatches"] > 0


def test_one_executable_serves_every_batch(server):
    """A second batch of the same rows compiles nothing."""
    plan = tpch._q14_plan()
    _serve(server, plan, _device(_host(300)))
    before = REGISTRY.counters()
    _serve(server, plan, _device(_host(301)))
    moved = {k: v - before.get(k, 0) for k, v in REGISTRY.counters().items()
             if v != before.get(k, 0)}
    assert not any(k.startswith("dispatch.compile") for k in moved), moved
    assert moved["fusion.regions"] == 1


def _left_plan(out_rows) -> fusion.Plan:
    return fusion.Plan("left_capacity", fusion.Join(
        fusion.Scan("l"), fusion.Scan("r"), (0,), (0,), out_rows,
        how="left", label="j"))


@pytest.mark.parametrize("how", ["inner", "left"])
def test_a_capacity_one_row_too_small_fails_the_request(server, how):
    """The served path refuses a join that outgrew its ``out_rows``, with
    the true total; at the total it serves."""
    host = _host(640)
    if how == "inner":
        bindings = _device(host)
        make = lambda rows: tpch._q14_plan(out_rows=rows)  # noqa: E731
        label = "part_join"
    else:
        rng = np.random.default_rng(3)
        bindings = {
            "l": Table([Column(t.INT64, jnp.asarray(
                rng.integers(0, 50, 300).astype(np.int64)))]),
            "r": Table([Column(t.INT64, jnp.asarray(
                rng.integers(0, 80, 200).astype(np.int64)))])}
        make, label = _left_plan, "j"
    total = int(fusion.execute(make(1 << 16), bindings).meta[
        f"{label}.total"])
    assert total > 100
    before = REGISTRY.counters()
    ok = _serve(server, make(total), bindings)
    assert int(ok.meta[f"{label}.total"]) == total
    assert int(ok.meta[f"{label}.capacity"]) == total
    assert not bool(ok.meta[f"{label}.overflowed"])
    # q14's capacity lies far under the batch's 16,384 padded rows and
    # every keyed row of the month finds its part, so the join ran on
    # those rows alone; the left join's capacity is over its probe's rows
    compacted = how == "inner"
    assert total * join_ops._COMPACT_FACTOR <= 16384 or not compacted
    assert bool(ok.meta[f"{label}.probe_compacted"]) == compacted
    assert REGISTRY.counters().get("join.probe_compacted", 0) - before.get(
        "join.probe_compacted", 0) == compacted
    before = REGISTRY.counters()
    ticket = server.session("q14").submit(make(total - 1), bindings)
    with pytest.raises(resilience.CapacityOverflow) as refused:
        ticket.result()
    assert refused.value.context == {"rows": total}
    moved = {k: v - before.get(k, 0) for k, v in REGISTRY.counters().items()}
    assert moved["join.overflowed"] == 1
    assert moved["join.capacity_rows"] == total - 1
    # one keyed row more than slots: today's path, whole
    assert moved.get("join.probe_compacted", 0) == 0
    # and fusion.execute, which raises nothing, says so in the meta
    direct = fusion.execute(make(total - 1), bindings)
    assert bool(direct.meta[f"{label}.overflowed"])
    assert int(direct.meta[f"{label}.total"]) == total


def test_general_q1_under_a_bound_over_the_in_place_gate():
    """``_q1_plan(max_groups=4096)`` is q1 through the groupby that sorts
    its rows and moves the value words: the same answer, not in place."""
    lineitem = tpch.lineitem_table(3000, seed=4)
    want = fusion.execute(tpch._q1_plan(), {"lineitem": lineitem})
    got = fusion.execute(tpch._q1_plan(max_groups=4096),
                         {"lineitem": lineitem})
    assert bool(want.meta["groupby.in_place"])
    assert not bool(got.meta["groupby.in_place"])
    groups = int(want.meta["groupby.num_groups"])
    assert int(got.meta["groupby.num_groups"]) == groups
    for a, b in zip(want.table.columns, got.table.columns):
        assert np.array_equal(np.asarray(a.valid_mask())[:groups],
                              np.asarray(b.valid_mask())[:groups])
        assert np.array_equal(np.asarray(a.data)[:groups],
                              np.asarray(b.data)[:groups])


# ---------------------------------------------------------------------------
# join()'s maps against a numpy oracle of the pairs
# ---------------------------------------------------------------------------

_HOWS = ("inner", "left", "left_semi", "left_anti", "right", "full")


def _oracle_pairs(lkeys, lkeyed, lreal, rkeys, rkeyed, rreal, how):
    """The output rows in the order the maps promise: the probe's rows in
    order, a row's matches by build row; then, under ``right`` / ``full``,
    the real build rows nothing matched. -1 stands for the null side.
    Keys are tuples; ``*keyed`` says the row holds one, ``*real`` that it
    exists."""
    rows = []
    for i in range(len(lkeys)):
        if not lreal[i]:
            continue
        hits = [r for r in range(len(rkeys))
                if lkeyed[i] and rreal[r] and rkeyed[r]
                and rkeys[r] == lkeys[i]]
        if how in ("inner", "right"):
            rows += [(i, r) for r in hits]
        elif how in ("left", "full"):
            rows += [(i, r) for r in hits] or [(i, -1)]
        elif how == "left_semi" and hits:
            rows.append((i, hits[0]))
        elif how == "left_anti" and not hits:
            rows.append((i, -1))
    if how in ("right", "full"):
        probed = {lkeys[i] for i in range(len(lkeys))
                  if lreal[i] and lkeyed[i]}
        rows += [(-1, r) for r in range(len(rkeys)) if rreal[r]
                 and not (rkeyed[r] and rkeys[r] in probed)]
    return rows


def _key_tables(kind: str, rng, nl: int, nr: int):
    """``(left table, right table, key columns, left keys, right keys)``:
    few distinct values, so both sides hold duplicates."""
    def draw(n):
        return rng.integers(-3, 12, n)

    if kind == "int32":
        lk, rk = draw(nl).astype(np.int32), draw(nr).astype(np.int32)
        cols = lambda k: [Column(t.INT32, jnp.asarray(k))]  # noqa: E731
        tup = lambda k: [(int(v),) for v in k]  # noqa: E731
    elif kind in ("int64_narrow", "int64_wide"):
        # one high word and low words close together; or keys 2**33 apart
        scale, base = (1, 5 * 2 ** 32) if kind == "int64_narrow" \
            else (2 ** 33, 0)
        lk = draw(nl).astype(np.int64) * scale + base
        rk = draw(nr).astype(np.int64) * scale + base
        cols = lambda k: [Column(t.INT64, jnp.asarray(k))]  # noqa: E731
        tup = lambda k: [(int(v),) for v in k]  # noqa: E731
    else:   # composite: an int64 and a string, rank encoded over both sides
        words = ["a", "ab", "b", ""]
        lk = list(zip(draw(nl).tolist(), rng.integers(0, 4, nl).tolist()))
        rk = list(zip(draw(nr).tolist(), rng.integers(0, 4, nr).tolist()))
        cols = lambda k: [  # noqa: E731
            Column(t.INT64, jnp.asarray(np.array([a for a, _ in k],
                                                 dtype=np.int64))),
            Column.from_pylist([words[b] for _, b in k], t.STRING)]
        tup = lambda k: [(int(a), int(b)) for a, b in k]  # noqa: E731
    return cols(lk), cols(rk), tup(lk), tup(rk)


@pytest.mark.parametrize("how", _HOWS)
@pytest.mark.parametrize("kind", ["int32", "int64_narrow", "int64_wide",
                                  "composite"])
def test_join_maps_against_the_pairs(how, kind):
    """``JoinMaps`` of the merged-sort ``join()``, row for row, over keys
    of every width it takes, duplicates on both sides, NULL keys and
    phantom rows, with and without row masks."""
    rng = np.random.default_rng(_HOWS.index(how) * 7 + len(kind))
    for nl, nr, masks in ((1, 1, False), (9, 140, True), (140, 9, False),
                          (65, 63, True), (1, 40, True)):
        lcols, rcols, lkeys, rkeys = _key_tables(kind, rng, nl, nr)
        lkeyed, rkeyed = rng.random(nl) > 0.15, rng.random(nr) > 0.15
        lcols[0] = Column(lcols[0].dtype, lcols[0].data,
                          jnp.asarray(lkeyed), chars=lcols[0].chars)
        rcols[0] = Column(rcols[0].dtype, rcols[0].data,
                          jnp.asarray(rkeyed), chars=rcols[0].chars)
        lreal = rng.random(nl) > 0.2 if masks else np.ones(nl, bool)
        rreal = rng.random(nr) > 0.2 if masks else np.ones(nr, bool)
        on = list(range(len(lcols)))
        want = _oracle_pairs(lkeys, lkeyed, lreal, rkeys, rkeyed, rreal, how)
        out_size = len(want) + 5
        maps = join(Table(lcols), Table(rcols), on, on, out_size, how=how,
                    left_row_valid=jnp.asarray(lreal) if masks else None,
                    right_row_valid=jnp.asarray(rreal) if masks else None)
        total = int(maps.total)
        assert total == len(want)
        assert np.array_equal(np.asarray(maps.row_valid),
                              np.arange(out_size) < total)
        li, ri = np.asarray(maps.left_index), np.asarray(maps.right_index)
        lv, rv = np.asarray(maps.left_valid), np.asarray(maps.right_valid)
        got = [(int(li[j]) if lv[j] else -1, int(ri[j]) if rv[j] else -1)
               for j in range(total)]
        if how == "left_semi":   # the right side is a match, the first
            assert [g[0] for g in got] == [w[0] for w in want]
            assert all(rkeys[g[1]] == lkeys[g[0]] for g in got)
        else:
            assert got == want, (nl, nr, masks)
        assert not lv[total:].any() and not rv[total:].any()
        # a capacity too small still reports the true total
        if total > 1:
            short = join(Table(lcols), Table(rcols), on, on, total - 1,
                         how=how,
                         left_row_valid=jnp.asarray(lreal) if masks else None,
                         right_row_valid=jnp.asarray(rreal) if masks
                         else None)
            assert int(short.total) == total
            assert np.asarray(short.row_valid).all()


# ---------------------------------------------------------------------------
# the join over the probe rows that can emit alone (a capacity far under the
# probe's rows) against today's path and the oracle
# ---------------------------------------------------------------------------

_SLOTS = 48
_PROBE_ROWS = 2 * _SLOTS * join_ops._COMPACT_FACTOR
# build rows: duplicates, a NULL key and a phantom row among them
_BUILD = {"key": [1, 1, 2, 3, 3, 3, 4, 5, 6, 7, 8, 9],
          "keyed": [1, 1, 1, 1, 1, 1, 1, 1, 0, 1, 1, 1],
          "real": [1, 1, 1, 1, 1, 1, 1, 1, 1, 0, 1, 1]}


def _emitting_sides(how: str, emitting: int, rng):
    """``(probe keys, keyed, real)`` with exactly ``emitting`` rows that
    can emit under ``how``; the others are phantom rows or, where only a
    key lets a row emit, also real rows with a NULL key. Most keys miss
    the build side, some repeat, some hit a key two or three build rows
    hold (never under ``left`` / ``full`` / ``left_anti``, whose every
    real row emits: a second match would pass the slots)."""
    by_key = how in join_ops._EMITS_BY_KEY
    chosen = np.zeros(_PROBE_ROWS, bool)
    chosen[rng.choice(_PROBE_ROWS, emitting, replace=False)] = True
    hits = [1, 3, 4, 5, 8] if by_key else [2, 4, 5, 8, 9]
    keys = np.where(rng.random(_PROBE_ROWS) < 0.3,
                    rng.choice(hits, _PROBE_ROWS),
                    rng.integers(100, 110, _PROBE_ROWS)).astype(np.int64)
    if by_key:
        keyed = chosen | (rng.random(_PROBE_ROWS) < 0.1)
        real = chosen | (~keyed & (rng.random(_PROBE_ROWS) < 0.5))
    else:
        keyed = rng.random(_PROBE_ROWS) < 0.8
        real = chosen
    return keys, keyed, real


def _maps_at(sides, out_size: int, how: str):
    lkeys, lkeyed, lreal = sides
    return jax.jit(partial(join_ops._join_maps_impl, out_size=out_size,
                           how=how))(
        jnp.asarray(lkeys), jnp.asarray(lkeyed),
        jnp.asarray(np.array(_BUILD["key"], np.int64)),
        jnp.asarray(np.array(_BUILD["keyed"], bool)),
        left_row_valid=jnp.asarray(lreal),
        right_row_valid=jnp.asarray(np.array(_BUILD["real"], bool)))


@pytest.mark.parametrize("emitting", [_SLOTS // 2, _SLOTS - 1, _SLOTS,
                                      _SLOTS + 1])
@pytest.mark.parametrize("how", _HOWS)
def test_join_maps_at_the_emitting_rows(how, emitting, monkeypatch):
    """A capacity ``_COMPACT_FACTOR`` times under the probe's rows: with
    no more rows that can emit than slots the join runs on those alone
    (``probe_compacted``), with one more on today's path, and either way
    its maps are today's (the same call with the gate shut), row for row;
    where the total fits the slots they are the oracle's pairs."""
    sides = _emitting_sides(how, emitting, np.random.default_rng(
        _HOWS.index(how) * 100 + emitting))
    got = _maps_at(sides, _SLOTS, how)
    assert bool(got.probe_compacted) == (emitting <= _SLOTS)
    monkeypatch.setattr(join_ops, "_COMPACT_FACTOR", 1 << 40)
    today = _maps_at(sides, _SLOTS, how)
    assert not bool(today.probe_compacted)
    total = int(got.total)
    for name, a, b in zip(got._fields[:-1], got, today):
        a, b = np.asarray(a), np.asarray(b)
        if name.endswith("_index"):   # read only where its side is valid
            valid = np.asarray(getattr(today, name[:-5] + "valid"))
            a, b = a[valid], b[valid]
        assert np.array_equal(a, b), name
    want = _oracle_pairs(
        [(int(k),) for k in sides[0]], sides[1], sides[2],
        [(k,) for k in _BUILD["key"]], _BUILD["keyed"], _BUILD["real"], how)
    assert total == len(want)
    if emitting == _SLOTS // 2:
        assert total <= _SLOTS
        if how in ("inner", "right"):   # a key that two or three rows hold
            assert total > emitting / 4
    if total <= _SLOTS:
        li, ri = np.asarray(got.left_index), np.asarray(got.right_index)
        lv, rv = np.asarray(got.left_valid), np.asarray(got.right_valid)
        pairs = [(int(li[j]) if lv[j] else -1, int(ri[j]) if rv[j] else -1)
                 for j in range(total)]
        if how == "left_semi":
            assert [g[0] for g in pairs] == [w[0] for w in want]
        else:
            assert pairs == want


def test_a_capacity_at_half_the_probe_rows_lowers_as_before():
    """The gate is static: a join whose ``out_size`` is half its probe
    side's rows or more (every caller that sizes it by the probe's true
    rows) holds no conditional and packs no mask."""
    def lowered(out_size):
        shapes = [jax.ShapeDtypeStruct((n,), dt) for n, dt in (
            (1024, jnp.int64), (1024, jnp.bool_), (64, jnp.int64),
            (64, jnp.bool_), (1024, jnp.bool_))]
        return str(jax.make_jaxpr(
            lambda lk, lv, rk, rv, lrv: join_ops._join_maps_impl(
                lk, lv, rk, rv, out_size, "inner", lrv))(*shapes))

    near = 1024 // join_ops._COMPACT_FACTOR
    assert join_ops._COMPACT_FACTOR > 2
    for out_size in (512, 1024, near + 1):
        # (a 64-bit key's merged sort is a conditional of its own)
        assert lowered(out_size).count("cond[") == 1
        assert "population_count" not in lowered(out_size)
    assert lowered(near).count("cond[") >= 2
    assert "population_count" in lowered(near)
