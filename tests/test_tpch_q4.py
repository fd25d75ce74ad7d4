"""TPC-H q4, whole, as a served Plan (``tpch._q4_plan``): the ``EXISTS`` as
``fusion.Join(how="left_semi")`` on dbgen's sparse order keys with nothing
declared about them, held to the benchmark's plain-numpy reference
(``benchmark/reference_q4.py``) and to ``tpch_q4_numpy`` case by case
through ``fusion.execute`` and ``QueryServer``; the lowering it brought
(``ops/join.semi_join_mask``) against the maps-based join; the counters,
the scopes and the makers' rules."""

import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from spark_rapids_jni_tpu import types as t
from spark_rapids_jni_tpu.columnar import Column, Table
from spark_rapids_jni_tpu.models import tpch
from spark_rapids_jni_tpu.ops.join import (
    _probe_matches,
    apply_join_maps,
    join,
    semi_join_mask,
)
from spark_rapids_jni_tpu.runtime import fusion
from spark_rapids_jni_tpu.telemetry import REGISTRY

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness, reference_q4, resolve  # noqa: E402

ORDERS, ITEMS = 3000, 12100
QUARTER = reference_q4.QUARTER


def _host(seed: int, orders: int = ORDERS, items: int = ITEMS) -> dict:
    """``{table: host copy}`` of seeded dbgen-rule tables by the benchmark's
    own makers, through the harness (which hands each maker its seed)."""
    config = {"tables": {"orders": {"maker": "orders_q4", "rows": orders},
                         "lineitem": {"maker": "lineitem_q4", "rows": items}}}
    return {name: {c: np.array(a) for c, a in maker.host_copy(arrays).items()}
            for name, (maker, _, arrays)
            in harness.make_tables(config, seed, {}).items()}


def _valid(table: dict, name: str):
    mask = table.get(name + "_valid")
    return None if mask is None else jnp.asarray(mask)


def _device(host: dict) -> dict:
    o, li = host["orders"], host["lineitem"]
    return {
        "orders": Table([
            Column(t.INT64, jnp.asarray(o["o_orderkey"]),
                   _valid(o, "o_orderkey")),
            Column(t.TIMESTAMP_DAYS, jnp.asarray(o["o_orderdate"])),
            Column(t.STRING, jnp.asarray(o["o_orderpriority_len"]),
                   chars=jnp.asarray(o["o_orderpriority"]))]),
        "lineitem": Table([
            Column(t.INT64, jnp.asarray(li["l_orderkey"]),
                   _valid(li, "l_orderkey")),
            Column(t.TIMESTAMP_DAYS, jnp.asarray(li["l_commitdate"])),
            Column(t.TIMESTAMP_DAYS, jnp.asarray(li["l_receiptdate"]))])}


def _arrow(bindings: dict) -> tuple:
    """The tables ``tpch_q4_numpy`` reads: the priority as Python strings,
    the lineitem columns at the q12 table's positions."""
    o, li = bindings["orders"], bindings["lineitem"]
    lengths = np.asarray(o.column(2).data)
    chars = np.asarray(o.column(2).chars)
    text = [bytes(c[:n]).decode() for c, n in zip(chars, lengths)]
    width = max(tpch.L12_ORDERKEY, tpch.L12_COMMITDATE,
                tpch.L12_RECEIPTDATE) + 1
    cols = [li.column(0)] * width
    cols[tpch.L12_ORDERKEY] = li.column(0)
    cols[tpch.L12_COMMITDATE] = li.column(1)
    cols[tpch.L12_RECEIPTDATE] = li.column(2)
    return (Table([o.column(0), o.column(1),
                   Column.from_pylist(text, t.STRING)]), Table(cols))


@pytest.fixture(scope="module")
def server():
    from spark_rapids_jni_tpu.runtime.server import QueryServer

    with QueryServer(budget_bytes=4 << 30) as srv:
        yield srv


def _serve(server, plan, bindings):
    ticket = server.session("q4").submit(plan, bindings)
    result = ticket.result()
    assert (ticket.tier, ticket.rung, ticket.steps) == ("fused", 0, 0)
    return result


def _several_late(host):
    """Order 0 lies in the quarter and all of its lineitems are late."""
    host["orders"]["o_orderdate"][0] = QUARTER[0]
    key = host["orders"]["o_orderkey"][0]
    mine = host["lineitem"]["l_orderkey"] == key
    host["lineitem"]["l_commitdate"][mine] = 9000
    host["lineitem"]["l_receiptdate"][mine] = 9001
    host["lineitem"]["l_orderkey"][:5] = key     # five of them at least
    host["lineitem"]["l_commitdate"][:5] = 9000
    host["lineitem"]["l_receiptdate"][:5] = 9001


def _null_keys(host):
    rng = np.random.default_rng(5)
    host["orders"]["o_orderkey_valid"] = rng.random(ORDERS) > 0.2
    host["lineitem"]["l_orderkey_valid"] = rng.random(ITEMS) > 0.2


def _no_late_row(host):
    host["lineitem"]["l_receiptdate"][:] = host["lineitem"]["l_commitdate"]


# case -> (what it does to the seeded tables, the quarter asked for)
CASES = {
    "plain": (None, QUARTER),
    "several_late_lineitems_count_once": (_several_late, QUARTER),
    "null_keys_on_either_side": (_null_keys, QUARTER),
    "a_quarter_with_no_order": (None, (20000, 20092)),
    "a_build_side_with_no_late_row": (_no_late_row, QUARTER),
    "every_order_in_the_quarter": (None, (0, 30000)),
}


@pytest.mark.parametrize("case", list(CASES))
def test_served_q4_equals_both_references(server, case):
    change, quarter = CASES[case]
    host = _host(4100 + list(CASES).index(case))
    if change is not None:
        change(host)
    plan = tpch._q4_plan(*quarter)
    bindings = _device(host)
    want = reference_q4.q4(host, quarter)
    before = REGISTRY.counters()
    served = _serve(server, plan, bindings)
    moved = {k: v - before.get(k, 0) for k, v in REGISTRY.counters().items()}
    got = reference_q4.read_answer(served.table)
    assert reference_q4.compare(got, want) == {
        "q4.count_mismatches": 0, "q4.out_of_order": 0}
    assert got["rows"] == want["rows"]
    # the program's own oracle, a Python loop over the same rows (it knows
    # no NULL key: only where the case has none)
    if "o_orderkey_valid" not in host["orders"]:
        assert {k.decode(): v for k, v in got["groups"].items()} == \
            tpch.tpch_q4_numpy(*_arrow(bindings), *quarter)
    # the same through fusion.execute, fused and staged
    for staged in (False, True):
        direct = fusion.execute(plan, bindings, force_staged=staged)
        assert reference_q4.read_answer(direct.table)["rows"] == want["rows"]
    # the counters, once a request
    o, li = host["orders"], host["lineitem"]
    late = (li["l_commitdate"] < li["l_receiptdate"])
    entered = late & li.get("l_orderkey_valid", True)
    in_quarter = (o["o_orderdate"] >= quarter[0]) & (
        o["o_orderdate"] < quarter[1])
    assert moved["filter.rows_in"] == ORDERS + ITEMS
    assert moved["filter.rows_kept"] == int(in_quarter.sum() + late.sum())
    assert moved.get("join.build_rows", 0) == int(entered.sum())
    # dbgen's keys lie under 2**31: the merged sort carried one key word
    assert moved.get("join.key_narrowed", 0) == 1
    assert bool(served.meta["exists.key_narrowed"])
    assert moved["join.probe_rows"] == ORDERS
    assert moved.get("join.matched_rows", 0) == sum(want["groups"].values())
    assert moved.get("fusion.staged_regions", 0) == 0
    assert int(served.meta["exists.total"]) == sum(want["groups"].values())
    assert not bool(served.meta["groupby.domain_miss"])
    if case == "several_late_lineitems_count_once":
        inner = reference_q4.q4(host, quarter, once=False)
        assert sum(inner["groups"].values()) > sum(want["groups"].values())
    if case in ("a_quarter_with_no_order", "a_build_side_with_no_late_row"):
        assert want["rows"] == [] and got["rows"] == []
    if case == "plain":
        assert len(want["rows"]) == 5    # the seeded quarter selects orders


@pytest.mark.parametrize("orders, items", [(2048, 8192), (2049, 8193),
                                           (1000, 7000)])
def test_probe_and_build_padding_match_nothing(orders, items):
    """On a bucket's edge and one past it (2,047 and 8,191 phantom rows):
    a phantom row of either side holds key bytes (zeros) and no key."""
    host = _host(77, orders, items)
    # a real order and a real lineitem with the phantoms' key bytes
    host["orders"]["o_orderkey"][3] = 0
    host["orders"]["o_orderdate"][3] = QUARTER[0]
    got = fusion.execute(tpch._q4_plan(), _device(host))
    assert reference_q4.read_answer(got.table)["rows"] == \
        reference_q4.q4(host)["rows"]
    host["lineitem"]["l_orderkey"][7] = 0
    host["lineitem"]["l_commitdate"][7] = 1
    host["lineitem"]["l_receiptdate"][7] = 2
    got = fusion.execute(tpch._q4_plan(), _device(host))
    assert reference_q4.read_answer(got.table)["rows"] == \
        reference_q4.q4(host)["rows"]


def test_control_inner_join_is_not_correct():
    host = _host(91)
    numbers = reference_q4.compare(reference_q4.control(host),
                                   reference_q4.q4(host))
    assert numbers["q4.count_mismatches"] > 0


def _anti_plan() -> fusion.Plan:
    """q4 with ``NOT EXISTS``: the plan's semi join turned into its
    mirror."""
    plan = tpch._q4_plan()
    exists = next(n for n in fusion._topo(plan.root)
                  if isinstance(n, fusion.Join))
    return fusion.Plan("tpch_q4_not_exists", fusion.replace_node(
        plan.root, exists, exists._replace(how="left_anti")))


def test_semi_and_anti_partition_the_filtered_orders():
    host = _host(123)
    _null_keys(host)
    bindings = _device(host)
    semi = reference_q4.read_answer(
        fusion.execute(tpch._q4_plan(), bindings).table)["groups"]
    anti = reference_q4.read_answer(
        fusion.execute(_anti_plan(), bindings).table)["groups"]
    o = host["orders"]
    # every order of the quarter with a priority, NULL key or not (a NULL
    # key matches nothing: NOT EXISTS holds); the orders the WHERE dropped
    # read NULL in every column and count under no priority
    in_quarter = (o["o_orderdate"] >= QUARTER[0]) & (
        o["o_orderdate"] < QUARTER[1])
    codes = reference_q4.priority_codes(o)[in_quarter]
    keyed = o["o_orderkey_valid"][in_quarter]
    for i, word in enumerate(reference_q4.PRIORITIES):
        # count(o_orderkey): the NULL keys of an anti join's rows count 0
        assert semi.get(word, 0) + anti.get(word, 0) == int(
            np.sum((codes == i) & keyed)), word
    assert sum(anti.values()) > 0 and sum(semi.values()) > 0


def _random_tables(rng, nl, nr, wide):
    scale = 2 ** 33 if wide else 1
    lk = rng.integers(-6, 40, nl).astype(np.int64) * scale
    rk = rng.integers(-6, 40, nr).astype(np.int64) * scale
    left = Table([
        Column(t.INT64, jnp.asarray(lk), jnp.asarray(rng.random(nl) > 0.1)),
        Column(t.INT32, jnp.arange(nl, dtype=jnp.int32))])
    right = Table([
        Column(t.INT64, jnp.asarray(rk), jnp.asarray(rng.random(nr) > 0.1))])
    return left, right


@pytest.mark.parametrize("how", ["left_semi", "left_anti"])
@pytest.mark.parametrize("wide", [False, True], ids=["narrow", "wide"])
@pytest.mark.parametrize("masks", [False, True], ids=["whole", "phantoms"])
def test_mask_is_the_maps_on_duplicate_laden_keys(how, wide, masks):
    """``semi_join_mask`` keeps exactly the probe rows ``join(how=...)``
    emits, and the table under the mask, compacted, is
    ``apply_join_maps``' left columns bit for bit."""
    rng = np.random.default_rng(17 + wide + 2 * masks)
    for nl, nr in ((1, 1), (7, 300), (300, 7), (257, 255), (64, 64)):
        left, right = _random_tables(rng, nl, nr, wide)
        lrv = jnp.asarray(rng.random(nl) > 0.15) if masks else None
        rrv = jnp.asarray(rng.random(nr) > 0.15) if masks else None
        maps = join(left, right, 0, 0, out_size=nl, how=how,
                    left_row_valid=lrv, right_row_valid=rrv)
        semi = semi_join_mask(left, right, 0, 0, how, lrv, rrv)
        total = int(maps.total)
        assert int(semi.total) == total
        kept = np.flatnonzero(np.asarray(semi.keep))
        assert np.array_equal(np.asarray(maps.left_index)[:total], kept)
        joined = apply_join_maps(left, right, maps)
        for i, col in enumerate(left.columns):
            assert np.array_equal(np.asarray(joined.column(i).data)[:total],
                                  np.asarray(col.data)[kept])
            assert np.array_equal(
                np.asarray(joined.column(i).valid_mask())[:total],
                np.asarray(col.valid_mask())[kept])
        real = np.asarray(right.column(0).valid_mask())
        if masks:
            real = real & np.asarray(rrv)
        assert int(semi.build_rows) == int(real.sum())


def _high_word_cases(rng) -> dict:
    """case -> (left keys, left key valid, left row valid, right keys,
    right key valid, right row valid, dtype, whether the merged sort
    carries one key word for a 64-bit key): a few hundred rows standing for
    the millions, duplicates on both sides in every case."""
    nl, nr = 300, 700
    # from 2**30 to 3.2e9: under 2**32, over 2**31 among them, less than
    # 2**31 apart
    low_l = rng.integers(0, 60, nl).astype(np.int64) * 35_791_394 + 2**30
    low_r = rng.integers(0, 45, nr).astype(np.int64) * 35_791_394 + 2**30
    some = lambda n, p: rng.random(n) > p        # noqa: E731
    cases = {}
    # (a) every key under 2**32: high word 0
    cases["under_2_32"] = (low_l, some(nl, .1), None,
                           low_r, some(nr, .1), None, t.INT64, True)
    # and the same further apart than 2**31: the place's top bit has no
    # room under the low word
    cases["under_2_32_spanning_2_31"] = (
        (low_l - 2**30) * 2, some(nl, .1), None,
        (low_r - 2**30) * 2, some(nr, .1), None, t.INT64, False)
    # (b) every key negative, over -2**32: high word 0xFFFFFFFF
    cases["all_negative"] = (low_l - 2**32, some(nl, .1), None,
                             low_r - 2**32, some(nr, .1), None, t.INT64,
                             True)
    # (c) equal low words under different high words must not match: the
    # right side holds every left key once more, 2**32 and 2**33 higher
    straddle_r = np.concatenate([low_r[:300], low_l[:200] + 2**32,
                                 low_l[:200] + 2**33])
    cases["straddling_2_32"] = (low_l, some(nl, .1), None,
                                straddle_r, some(nr, .1), None, t.INT64,
                                False)
    # (d) ONE valid row with another high word among equal ones, and its
    # low word is a key the other side holds
    one_r = low_r.copy()
    one_r[123] = low_l[5] + 2**32
    valid_r = some(nr, .1)
    valid_r[123] = True
    cases["one_row_with_another_high_word"] = (
        low_l, some(nl, .1), None, one_r, valid_r, None, t.INT64, False)
    # (e) NULL keys and phantom rows under another high word than every
    # row with a key; their low words are keys the other side holds, or
    # (the phantoms of the right) 2**31 off one: the same rebased word
    lkv, lrv = some(nl, .2), some(nl, .2)
    rkv, rrv = some(nr, .2), some(nr, .2)
    odd_l = np.where(lkv & lrv, low_l, low_l + 3 * 2**32)
    odd_r = np.where(rkv & rrv, low_r,
                     low_r + np.where(rkv, 5 * 2**32 + 2**31, -2**32))
    cases["keyless_rows_hold_another_high_word"] = (
        odd_l, lkv, lrv, odd_r, rkv, rrv, t.INT64, True)
    # (f) a 4-byte key is one word as it comes
    cases["int32_keys"] = ((low_l - 2**30).astype(np.int32), some(nl, .1),
                           some(nl, .1), (low_r - 2**30).astype(np.int32),
                           some(nr, .1), None, t.INT32, False)
    return cases


HIGH_WORD_CASES = list(_high_word_cases(np.random.default_rng(0)))


@pytest.mark.parametrize("how", ["left_semi", "left_anti"])
@pytest.mark.parametrize("case", HIGH_WORD_CASES)
def test_mask_by_the_key_words_the_keys_need(case, how):
    """``semi_join_mask`` against ``join(how=...)``'s ``left_index`` set
    where the merged sort carries one key word and where it carries all,
    and the branch it took."""
    lk, lkv, lrv, rk, rkv, rrv, dtype, narrowed = _high_word_cases(
        np.random.default_rng(43 + HIGH_WORD_CASES.index(case)))[case]
    mask = lambda m: None if m is None else jnp.asarray(m)   # noqa: E731
    left = Table([Column(dtype, jnp.asarray(lk), jnp.asarray(lkv))])
    right = Table([Column(dtype, jnp.asarray(rk), jnp.asarray(rkv))])
    maps = join(left, right, 0, 0, out_size=len(lk), how=how,
                left_row_valid=mask(lrv), right_row_valid=mask(rrv))
    semi = semi_join_mask(left, right, 0, 0, how, mask(lrv), mask(rrv))
    total = int(maps.total)
    assert int(semi.total) == total and 0 < total < len(lk)
    assert np.array_equal(np.asarray(maps.left_index)[:total],
                          np.flatnonzero(np.asarray(semi.keep)))
    assert bool(semi.key_narrowed) == narrowed
    if how == "left_semi":     # numpy's word on it
        real_r = rkv if rrv is None else rkv & rrv
        real_l = lkv if lrv is None else lkv & lrv
        assert np.array_equal(np.asarray(semi.keep),
                              real_l & np.isin(lk, rk[real_r]))
    # one word as it comes takes neither the reductions nor the conditional
    jaxpr = str(jax.make_jaxpr(_probe_matches)(
        jnp.asarray(lk), jnp.asarray(lkv), jnp.asarray(rk),
        jnp.asarray(rkv)))
    assert ("cond" in jaxpr) == (dtype == t.INT64)
    assert ("reduce_min" in jaxpr) == (dtype == t.INT64)


@pytest.mark.parametrize("shift, narrowed", [(0, 1), (2**32 - 1500, 0)],
                         ids=["dbgen_keys", "keys_straddling_2_32"])
def test_key_narrowed_is_a_fact_of_the_keys_and_counted_once(
        server, shift, narrowed):
    """``exists.key_narrowed`` in the result's meta and the server's
    ``join.key_narrowed``: one a request over dbgen's keys, none over the
    same keys moved to straddle 2**32, the answer the same."""
    host = _host(4300)
    want = reference_q4.q4(host)
    host["orders"]["o_orderkey"] = host["orders"]["o_orderkey"] + shift
    host["lineitem"]["l_orderkey"] = host["lineitem"]["l_orderkey"] + shift
    keys = host["orders"]["o_orderkey"]
    assert (keys.min() < 2**32 <= keys.max()) == (not narrowed)
    before = REGISTRY.counters()
    served = _serve(server, tpch._q4_plan(), _device(host))
    moved = {k: v - before.get(k, 0) for k, v in REGISTRY.counters().items()}
    assert reference_q4.read_answer(served.table)["rows"] == want["rows"]
    assert len(want["rows"]) == 5
    assert bool(served.meta["exists.key_narrowed"]) == bool(narrowed)
    assert moved.get("join.key_narrowed", 0) == narrowed
    assert moved["join.probe_rows"] == ORDERS
    # the staged walk reports it as the region does
    staged = fusion.execute(tpch._q4_plan(), _device(host), force_staged=True)
    assert bool(staged.meta["exists.key_narrowed"]) == bool(narrowed)


def test_mask_on_string_and_composite_keys():
    """Any key ``join`` takes: the dense ranks over both sides."""
    rng = np.random.default_rng(3)
    words = ["a", "bb", "ccc", "dd", "e", ""]
    lw = [words[i] for i in rng.integers(0, 6, 90)]
    rw = [words[i] for i in rng.integers(0, 4, 70)]
    left = Table([Column.from_pylist(lw, t.STRING), Column.from_numpy(
        rng.integers(0, 3, 90).astype(np.int32))])
    right = Table([Column.from_pylist(rw, t.STRING), Column.from_numpy(
        rng.integers(0, 3, 70).astype(np.int32))])
    for how in ("left_semi", "left_anti"):
        maps = join(left, right, [0, 1], [0, 1], out_size=90, how=how)
        semi = semi_join_mask(left, right, [0, 1], [0, 1], how)
        total = int(maps.total)
        assert int(semi.total) == total and 0 < total < 90
        assert np.array_equal(np.asarray(maps.left_index)[:total],
                              np.flatnonzero(np.asarray(semi.keep)))
        assert not bool(semi.key_narrowed)    # dense ranks: one word
    with pytest.raises(ValueError, match="no semi or anti join"):
        semi_join_mask(left, right, 0, 0, "inner")


def test_q4_plan_is_one_region_with_its_scopes():
    plan = tpch._q4_plan()
    nodes = fusion._topo(plan.root)
    scopes = set(fusion.node_scopes(nodes).values())
    assert {"quarter", "late", "exists", "groupby", "sort"} <= scopes
    assert fusion.split_at_exchange(plan) is None
    # nothing is declared about either key: the one join is the general
    # one, and the group key is the string column itself
    joins = [n for n in nodes if isinstance(n, (fusion.Join,
                                                fusion.DensePkJoin))]
    assert len(joins) == 1 and isinstance(joins[0], fusion.Join)
    assert joins[0].how == "left_semi" and joins[0].out_rows is None
    group = next(n for n in nodes if isinstance(n, fusion.GroupBy))
    assert group.keys == (tpch.O4_ORDERPRIORITY,) and group.key_ranges is None
    assert group.domains[0].kind == "string"
    bindings = _device(_host(1, 64, 200))
    assert bindings["orders"].column(2).is_padded_string
    assert fusion.plan_fingerprint(plan, bindings) != fusion.plan_fingerprint(
        tpch._q4_plan(8674, 8766), bindings)


def test_build_and_probe_scopes_are_in_the_regions_hlo():
    plan = tpch._q4_plan()
    nodes = fusion._topo(plan.root)
    bindings = _device(_host(2, 256, 900))
    true_rows = {k: v.num_rows for k, v in bindings.items()}
    resolved = fusion._resolve_statics(nodes, true_rows)

    def region(tables):
        with jax.named_scope("region.tpch_q4"):
            return fusion._eval_plan(plan.root, tables, {}, resolved,
                                     true_rows)[0]

    hlo = jax.jit(region).lower(bindings).as_text(debug_info=True)
    under = set(re.findall(r'"jit\(region\)/region\.tpch_q4/exists/([^"]*)"',
                           hlo))
    # the merged sort twice, a form a branch of one conditional: three key
    # words (branch 0) or one with the place word as its payload (branch 1)
    assert {"build/cond/branch_0_fun/sort", "build/cond/branch_1_fun/sort",
            "build/concatenate", "probe/sort", "probe/slice"} <= under
    # every operation of the join lies under one of the two but the fold
    # of the row masks into the keys' validity
    assert {n.split("/")[0] for n in under} <= {"build", "probe", "and"}
    # a semi join lays nothing out: no search, no offsets, no gather
    assert not any(word in name for name in under
                   for word in ("gather", "while", "cumsum", "scatter"))


def test_the_makers_keep_dbgens_rules():
    host = _host(2**31 + 7, 5000, 20007)
    o, li = host["orders"], host["lineitem"]
    at = np.arange(5000)
    assert np.array_equal(o["o_orderkey"], (at // 8) * 32 + at % 8 + 1)
    assert ((o["o_orderkey"] - 1) % 32 < 8).all()
    assert o["o_orderdate"].min() >= 8035 and o["o_orderdate"].max() <= 10440
    codes = reference_q4.priority_codes(o)
    assert set(np.unique(codes)) == set(range(5))
    assert o["o_orderpriority"].shape == (5000, 15)
    keys, counts = np.unique(li["l_orderkey"], return_counts=True)
    assert np.array_equal(keys, o["o_orderkey"])        # every order has one
    assert counts.min() >= 1 and counts.max() <= 7 and counts.sum() == 20007
    assert (np.diff(li["l_orderkey"]) >= 0).all()       # clustered by order
    dates = o["o_orderdate"][np.searchsorted(keys, li["l_orderkey"])]
    commit = li["l_commitdate"] - dates
    receipt = li["l_receiptdate"] - dates
    assert commit.min() >= 30 and commit.max() <= 90
    assert receipt.min() >= 2 and receipt.max() <= 151
    late = (li["l_commitdate"] < li["l_receiptdate"]).mean()
    assert 0.55 < late < 0.70, late
    # the same seed gives the same tables, another seed others
    again = _host(2**31 + 7, 5000, 20007)
    assert all(np.array_equal(again[t_][c], host[t_][c])
               for t_ in host for c in host[t_])
    other = _host(2**31 + 8, 5000, 20007)
    assert not np.array_equal(other["orders"]["o_orderdate"], o["o_orderdate"])
    with pytest.raises(ValueError, match="1 to 7 lineitems"):
        _host(1, 100, 701)


@pytest.mark.parametrize("items", [5000, 35000, 20000])
def test_the_last_orders_take_up_the_row_count(items):
    """One lineitem an order, seven an order, and the mean: the counts'
    sum is the row count asked for and every count stays in 1..7."""
    import jax.random as jr

    maker = resolve.module("tables", "lineitem_q4")
    counts = np.asarray(maker.order_counts(jr.key(5), 5000, items))
    assert counts.sum() == items and counts.min() >= 1 and counts.max() <= 7
