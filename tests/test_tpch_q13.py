"""TPC-H q13, whole, as a served Plan (``tpch._q13_plan``): the ``NOT LIKE``
over the padded comment column, the outer join's zero-count customers and
the two chained groupbys, held to the benchmark's plain-numpy reference
(``benchmark/reference_q13.py``) case by case through ``QueryServer``; the
pieces it brought (``strings.like`` in row blocks, ``dense_pk_join``'s
``probe_clustered`` lowering, a ``Filter``'s counters, q6's WHERE as a
``Filter``) each on their own."""

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
from spark_rapids_jni_tpu.ops import strings as s
from spark_rapids_jni_tpu.ops.planner import dense_pk_join
from spark_rapids_jni_tpu.runtime import fusion, resilience
from spark_rapids_jni_tpu.telemetry import REGISTRY

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import reference_q13  # noqa: E402

WIDTH = 79
CUSTOMERS, ORDERS = 450, 3000
_VOCAB = ("pending", "unusual", "express", "packages", "accounts",
          "deposits", "carefully", "final", "ironic", "quickly", "special",
          "requests")


def _base(seed: int, orders: int = ORDERS, customers: int = CUSTOMERS):
    """Seeded host tables as the benchmark's makers shape them: dense
    customer keys, ``o_custkey`` on no multiple of 3, comments of 19..78
    bytes of words, some holding the pattern."""
    rng = np.random.default_rng(seed)
    keys = np.arange(1, customers + 1, dtype=np.int64)
    texts = []
    for _ in range(orders):
        text = " ".join(rng.choice(_VOCAB, 14))
        texts.append(text[:int(rng.integers(19, 79))].encode())
    return ({"c_custkey": keys},
            {"o_orderkey": np.arange(1, orders + 1, dtype=np.int64),
             "o_custkey": rng.choice(keys[keys % 3 != 0], orders),
             "texts": texts})


def _with_text(orders: dict) -> dict:
    """The host copy's comment columns from the rows' byte strings."""
    texts = orders.pop("texts")
    chars = np.zeros((len(texts), WIDTH), dtype=np.uint8)
    for i, text in enumerate(texts):
        chars[i, :len(text)] = np.frombuffer(text, dtype=np.uint8)
    orders["o_comment"] = chars
    orders["o_comment_len"] = np.array([len(x) for x in texts], np.int32)
    return orders


def _device(customer: dict, orders: dict):
    def valid(name):
        mask = orders.get(name)
        return None if mask is None else jnp.asarray(mask)

    return {
        "customer": Table([Column(t.INT64, jnp.asarray(customer["c_custkey"]))]),
        "orders": Table([
            Column(t.INT64, jnp.asarray(orders["o_orderkey"]),
                   valid("o_orderkey_valid")),
            Column(t.INT64, jnp.asarray(orders["o_custkey"])),
            Column(t.STRING, jnp.asarray(orders["o_comment_len"]),
                   valid("o_comment_valid"),
                   chars=jnp.asarray(orders["o_comment"]))])}


def _pad(text: bytes, to: int, filler: bytes = b"x") -> bytes:
    return text + filler * (to - len(text))


# case -> (words, rows written over the base's first rows as (text, does
# the pattern match it), what else the case changes)
_SPECIAL = (b"special", b"requests")
CASES = {
    "plain": (_SPECIAL, [], {}),
    "requests_before_special": (_SPECIAL, [
        (b"requests come before special ones here", False),
        (b"pending requests special", False)], {}),
    "touching_and_overlapping": ((b"abab", b"abc"), [
        (b"the words touch: ababc is no match", False),    # abc inside abab
        (b"the words touch: abababc matches it", True),    # abab then abc
        (b"one after the other: abab and abc", True)], {}),
    "words_touch": (_SPECIAL, [
        (b"no gap at all: specialrequests", True),
        (b"specialrequest and no final letter", False)], {}),
    "byte_0_and_last_byte": (_SPECIAL, [
        (b"special packages and final requests", True),    # both ends
        (_pad(b"special ", WIDTH - 8) + b"requests", True),  # the full width
        (_pad(b"special ", WIDTH - 9) + b"requests", True),
        (b"requests" + _pad(b" ", 30, b" ") + b"special", False)], {}),
    "match_needs_bytes_past_the_length": (_SPECIAL, [
        # cut inside the second word; the next row opens with its rest
        (b"a special order with its reque", False),
        (b"sts are what the row above lacks", False),
        (b"quickly special reques", False)], {}),
    "null_comment": (_SPECIAL, [], {"null_comments": True}),
    "null_orderkey": (_SPECIAL, [], {"null_orderkeys": True}),
    "customers_with_no_order": (_SPECIAL, [], {"check_zero": True}),
    "bucket_padding": (_SPECIAL, [], {"orders": 5000}),
    "rows_no_multiple_of_the_block": (_SPECIAL, [], {"block": 1000,
                                                      "orders": 4500}),
    "order_of_ties": (_SPECIAL, [], {"ties": True}),
    "custkey_out_of_range": (_SPECIAL, [], {"out_of_range": True}),
}


@pytest.fixture(scope="module")
def server():
    from spark_rapids_jni_tpu.runtime.server import QueryServer

    with QueryServer(budget_bytes=4 << 30) as srv:
        yield srv


def _serve(server, plan, bindings):
    ticket = server.session("q13").submit(plan, bindings)
    result = ticket.result()
    assert (ticket.tier, ticket.rung, ticket.steps) == ("fused", 0, 0)
    return result


@pytest.mark.parametrize("case", list(CASES))
def test_served_q13_equals_reference(server, case, monkeypatch):
    words, written, change = CASES[case]
    customer, orders = _base(1300 + list(CASES).index(case),
                             orders=change.get("orders", ORDERS))
    for i, (text, _) in enumerate(written):
        orders["texts"][i] = text
    if change.get("ties"):
        # twelve customers: four with one order, four with two, four (the
        # multiples of 3) with none: every custdist is 4, c_count decides
        keep = np.array([1, 2, 4, 5, 7, 7, 8, 8, 10, 10, 11, 11], np.int64)
        orders = {"o_orderkey": np.arange(1, len(keep) + 1, dtype=np.int64),
                  "o_custkey": keep,
                  "texts": [b"carefully final packages"] * len(keep)}
        customer = {"c_custkey": np.arange(1, 13, dtype=np.int64)}
    orders = _with_text(orders)
    n = len(orders["o_orderkey"])
    rng = np.random.default_rng(7)
    if change.get("null_comments"):
        orders["o_comment_valid"] = rng.random(n) > 0.2
    if change.get("null_orderkeys"):
        orders["o_orderkey_valid"] = rng.random(n) > 0.2
    if "block" in change:
        monkeypatch.setattr(s, "_LIKE_BLOCK_ROWS", change["block"])
    plan = tpch._q13_plan(*(w.decode() for w in words))
    tables = {"customer": customer, "orders": orders}

    if change.get("out_of_range"):
        broken = dict(orders, o_custkey=orders["o_custkey"].copy())
        broken["o_custkey"][17] = CUSTOMERS + 1
        ticket = server.session("q13").submit(
            plan, _device(customer, broken))
        seen = REGISTRY.counters().get("groupby.key_out_of_range", 0)
        # the wrapped key also leaves the outer join's build side outside
        # its range: the server names the first broken declaration it finds
        with pytest.raises(resilience.FatalExecutionError,
                           match="(key_out_of_range|pk_violation).*"
                                 "not the query's"):
            ticket.result()
        assert ticket.status == "failed"   # and the server keeps serving
        assert REGISTRY.counters()["groupby.key_out_of_range"] == seen + 1

    # the rows the case wrote are the case: the reference reads them so
    hit = reference_q13.matches(orders, words)
    assert hit[:len(written)].tolist() == [m for _, m in written]
    before = REGISTRY.counters()
    result = _serve(server, plan, _device(customer, orders))
    got = reference_q13.read_answer(result.table)
    want = reference_q13.q13(tables, words)
    assert reference_q13.compare(got, want) == {
        "q13.group_mismatches": 0, "q13.out_of_order": 0}
    assert got["rows"] == want["rows"]
    moved = {k: v - before.get(k, 0) for k, v in REGISTRY.counters().items()}
    assert moved["filter.rows_in"] == n
    kept = ~hit & orders.get("o_comment_valid", True)
    assert moved["filter.rows_kept"] == int(np.sum(kept))
    assert moved["strings.like_bytes"] == n * WIDTH
    assert moved.get("fusion.staged_regions", 0) == 0
    if change.get("check_zero"):
        # a third of the customers hold no order: c_count 0 is there, by
        # the plan's join
        absent = len(customer["c_custkey"]) - len(
            np.unique(orders["o_custkey"][kept]))
        assert absent >= CUSTOMERS // 3 and got["groups"][0] == absent
    if change.get("ties"):
        assert got["rows"] == [(2, 4), (1, 4), (0, 4)]
    if "block" in change:
        # four full blocks of 1,000 rows and a tail of 500 (the region pads
        # them to 8,192: eight blocks and a tail of 192), equal to the
        # reference, which knows no block
        assert n % change["block"] and n > change["block"]
    if case == "plain":
        assert bool(result.meta["c_orders.key_narrowed"])
        # a key with a declared range is never ordered as one word
        # (``ops/sort.py _lone_key_order``). ``custdist`` is at these 451
        # rows: under 2 * 1,024 * 32 they are no in-place groupby's, and
        # the word-moving path orders the counts as one word (at the
        # cell's 1,500,001 rows it is in place and reports 0:
        # ``test_groupby_in_place.py``)
        assert not bool(result.meta["c_orders.key_one_word"])
        assert not bool(result.meta["custdist.in_place"])
        assert bool(result.meta["custdist.key_one_word"])
        assert moved["groupby.key_one_word"] == 1
        assert not bool(result.meta["outer.pk_violation"])
        assert int(result.meta["outer.total"]) == len(
            np.unique(orders["o_custkey"][kept]))


def test_control_predicate_is_not_correct():
    """``'%special%'`` alone removes more orders: the weakened reference
    differs from the reference, so it cannot pass for it."""
    customer, orders = _base(77)
    tables = {"customer": customer, "orders": _with_text(orders)}
    numbers = reference_q13.compare(reference_q13.control(tables),
                                    reference_q13.q13(tables))
    assert numbers["q13.group_mismatches"] > 0


_LIKE_PATTERNS = {
    "%special%requests%": "special.*requests",
    "special%": "^special",
    "%requests": "requests$",
    "%special_requests%": "special.requests",
    "%l r%": "l r",
    "%pending%pending%pending%": "pending.*pending.*pending",
    "%": "",
    "%final%ironic": "final.*ironic$",
}


@pytest.mark.parametrize("pattern", list(_LIKE_PATTERNS))
@pytest.mark.parametrize("block", [1 << 16, 700], ids=["whole", "blocked"])
def test_like_against_re_on_seeded_comments(pattern, block, monkeypatch):
    """``ops/strings.like`` alone against Python's ``re`` on the same
    seeded comments, whole and in row blocks with a tail."""
    monkeypatch.setattr(s, "_LIKE_BLOCK_ROWS", block)
    _, orders = _base(4242, orders=2000)
    texts = orders["texts"]
    orders = _with_text(orders)
    col = Column(t.STRING, jnp.asarray(orders["o_comment_len"]),
                 chars=jnp.asarray(orders["o_comment"]))
    got = np.asarray(jax.jit(
        lambda c: s.like(c, pattern).data)(col)).astype(bool)
    rx = re.compile(_LIKE_PATTERNS[pattern].encode(), re.S)
    want = np.array([bool(rx.search(x)) for x in texts])
    assert np.array_equal(got, want)
    assert 0 < want.sum() <= len(texts)


def test_host_oracle_agrees_with_the_reference():
    """``tpch_q13_numpy`` (``re`` and ``Counter``) and the benchmark's
    reference (``numpy.char.find`` and ``bincount``) are two hands."""
    customer, orders = _base(99, orders=1500)
    orders = _with_text(orders)
    bindings = _device(customer, orders)
    want = reference_q13.q13({"customer": customer, "orders": orders})
    assert tpch.tpch_q13_numpy(
        bindings["customer"], bindings["orders"]) == want["rows"]


def test_q13_plan_is_one_region_with_its_scopes():
    plan = tpch._q13_plan()
    nodes = fusion._topo(plan.root)
    scopes = set(fusion.node_scopes(nodes).values())
    assert {"where", "c_orders", "outer", "custdist", "sort"} <= scopes
    assert fusion.split_at_exchange(plan) is None
    # the orders prefix is the Filter alone: no subplan is materialized
    assert all(length < 2 for _, _, length
               in fusion.scan_prefix_chains(plan.root))
    customer, orders = _base(1, orders=64)
    bindings = _device(customer, _with_text(orders))
    assert fusion.plan_fingerprint(plan, bindings) != fusion.plan_fingerprint(
        tpch._q13_plan("special", "deposits"), bindings)
    # custdist's 1,024 slots are under ``ops/sort.py``'s floor: the ORDER BY
    # lowers as it always has and says nothing of a sorted head
    assert "sort.prefix_sorted" not in fusion.execute(plan, bindings).meta


def test_a_customer_out_of_place_is_a_pk_violation(server):
    """The outer join's declaration is verified: a customer table that is
    not clustered by its key fails the request."""
    customer, orders = _base(11, orders=600)
    customer["c_custkey"] = customer["c_custkey"].copy()
    customer["c_custkey"][[3, 4]] = customer["c_custkey"][[4, 3]]
    ticket = server.session("q13").submit(
        tpch._q13_plan(), _device(customer, _with_text(orders)))
    with pytest.raises(resilience.FatalExecutionError, match="pk_violation"):
        ticket.result()


# -- dense_pk_join, probe_clustered -------------------------------------------

def _fill_tables(build_keys, build_valid=None, probe_rows=8):
    probe = Table([Column(t.INT64, jnp.arange(1, probe_rows + 1,
                                               dtype=jnp.int64))])
    keys = np.asarray(build_keys, dtype=np.int64)
    build = Table([
        Column(t.INT64, jnp.asarray(keys),
               None if build_valid is None else jnp.asarray(build_valid)),
        Column(t.INT64, jnp.asarray(keys * 10))])
    return probe, build


def test_probe_clustered_join_fills_the_slots():
    probe, build = _fill_tables([5, 2, 8, 1], [True, True, True, False])
    r = dense_pk_join(probe, build, 0, 0, 1, 8, probe_clustered=True)
    assert not bool(r.pk_violation) and int(r.total) == 3
    assert np.asarray(r.matched).tolist() == [
        False, True, False, False, True, False, False, True]
    val = r.table.column(2)
    assert np.asarray(val.data)[np.asarray(val.valid_mask())].tolist() == [
        20, 50, 80]


@pytest.mark.parametrize("keys, valid, why", [
    ([5, 2, 5], None, "two build rows with one key"),
    ([5, 9], None, "a build key outside the range"),
    ([5, 0], None, "a build key under the range"),
], ids=["duplicate", "above", "below"])
def test_probe_clustered_join_reports_a_broken_build(keys, valid, why):
    probe, build = _fill_tables(keys, valid)
    r = dense_pk_join(probe, build, 0, 0, 1, 8, probe_clustered=True)
    assert bool(r.pk_violation), why


def test_probe_clustered_join_needs_the_probe_at_its_place():
    probe, build = _fill_tables([5, 2])
    moved = Table([Column(t.INT64, jnp.asarray(
        np.array([1, 2, 4, 3, 5, 6, 7, 8], np.int64)))])
    r = dense_pk_join(moved, build, 0, 0, 1, 8, probe_clustered=True)
    assert bool(r.pk_violation)
    with pytest.raises(ValueError, match="not on both"):
        dense_pk_join(probe, build, 0, 0, 1, 8, clustered=True,
                      probe_clustered=True)
    with pytest.raises(ValueError, match="probe rows == key range"):
        dense_pk_join(probe, build, 0, 0, 1, 9, probe_clustered=True)


# -- a Filter's counters --------------------------------------------------------

def _keep_small(table, bound):
    return table.column(0).data < bound


@pytest.mark.parametrize("n", [4096, 5000], ids=["on_bucket", "phantom_rows"])
def test_filter_reports_rows_in_and_kept(n):
    table = Table([Column(t.INT64, jnp.arange(n, dtype=jnp.int64))])
    plan = fusion.Plan("filter_counts", fusion.Project(
        fusion.Filter(fusion.Scan("t"), _keep_small, (100,), label="few"),
        _first_column))
    for res in (fusion.execute(plan, {"t": table}),
                fusion.execute(plan, {"t": table}, force_staged=True)):
        assert int(res.meta["few.rows_in"]) == n
        assert int(res.meta["few.rows_kept"]) == 100
        assert int(res.meta["few.like_bytes"]) == 0
        facts = fusion.meta_facts(plan, res.meta)
        assert (facts["filter.rows_in"], facts["filter.rows_kept"],
                facts["strings.like_bytes"]) == (n, 100, 0)
    scopes = fusion.node_scopes(fusion._topo(plan.root))
    assert "few" in scopes.values()


def _first_column(table):
    return Table([table.column(0)])


def test_filter_like_columns_ride_the_fingerprint():
    _, orders = _base(3, orders=64)
    bindings = {"orders": _device(_base(3)[0], _with_text(orders))["orders"]}
    plain = fusion.Plan("p", fusion.Project(fusion.Filter(
        fusion.Scan("orders"), tpch._q13_where, ("%a%",)), _first_column))
    counted = fusion.Plan("p", fusion.Project(fusion.Filter(
        fusion.Scan("orders"), tpch._q13_where, ("%a%",),
        like_columns=(tpch.O13_COMMENT,)), _first_column))
    assert fusion.plan_fingerprint(plain, bindings) != fusion.plan_fingerprint(
        counted, bindings)
    res = fusion.execute(counted, bindings)
    assert int(res.meta["filter.like_bytes"]) == 64 * WIDTH


# -- q6's WHERE as a Filter -----------------------------------------------------

@pytest.mark.parametrize("n", [1, 4096, 5001])
def test_q6_filter_plan_matches_numpy(n):
    li = tpch.lineitem_table(n, seed=n)
    res = fusion.execute(tpch._q6_plan(), {"lineitem": li})
    want = tpch.tpch_q6_numpy(li)
    col = res.table.column(0)
    assert col.dtype == t.decimal64(-4)
    if bool(np.asarray(col.valid_mask())[0]):
        assert int(np.asarray(col.data)[0]) == want
    else:
        assert want == 0
    assert int(res.meta["where.rows_in"]) == n
    ship = np.asarray(li.column(tpch.L_SHIPDATE).data)
    disc = np.asarray(li.column(tpch.L_DISCOUNT).data)
    qty = np.asarray(li.column(tpch.L_QUANTITY).data)
    kept = ((ship >= 8766) & (ship < 9131) & (disc >= 5) & (disc <= 7)
            & (qty < 2400))
    assert int(res.meta["where.rows_kept"]) == int(kept.sum())
