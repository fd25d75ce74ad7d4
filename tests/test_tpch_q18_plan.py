"""TPC-H q18, whole, as a served Plan (``tpch._q18_plan``): the inner
groupby on an ``l_orderkey`` nobody declared a range for, the HAVING as a
``Filter`` over its groups, the IN as a semi join whose build side is
that filter, two joins that lay rows out, the outer groupby on five keys
one of them a string, ORDER BY, LIMIT; held to the benchmark's plain-numpy
reference (``benchmark/reference_q18.py``) and to ``tpch_q18_numpy`` case
by case through ``QueryServer``, with sparse order keys, rolled fact
tables and a permuted customer; and what the plan states as guarantees of
the served path (the joins' capacity, the foreign key's group bound)."""

import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from spark_rapids_jni_tpu import types as t
from spark_rapids_jni_tpu.columnar import Column, Table
from spark_rapids_jni_tpu.models import tpch
from spark_rapids_jni_tpu.runtime import fusion, resilience
from spark_rapids_jni_tpu.telemetry import REGISTRY

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness, reference_q18  # noqa: E402

CUSTOMERS, ORDERS, ITEMS = 90, 600, 2400
OUT_ROWS = 2048
EVERYTHING = 10 ** 9        # a limit no answer reaches


def _host(seed: int) -> dict:
    """``{table: host copy}`` of seeded tables by the benchmark's own
    makers, through the harness (which hands each maker its seed), the
    two fact tables rolled as the cell's freshener rolls them."""
    config = {"tables": {
        "customer": {"maker": "customer_q18", "rows": CUSTOMERS},
        "lineitem": {"maker": "lineitem_q18", "rows": ITEMS},
        "orders": {"maker": "orders_q18", "rows": ORDERS}}}
    host = {name: {c: np.array(a) for c, a in maker.host_copy(arrays).items()}
            for name, (maker, _, arrays)
            in harness.make_tables(config, seed, {}).items()}
    for name, stride in (("lineitem", 977), ("orders", 211)):
        host[name] = {c: np.roll(a, stride, axis=0)
                      for c, a in host[name].items()}
    return host


def _heavy(host: dict, orders: int, least_items: int = 4) -> np.ndarray:
    """Make ``orders`` orders heavy: every lineitem of an order that holds
    ``least_items`` or more gets the quantity 100.00 (400.00 and more an
    order; nothing else of the seeded table passes 300.00 at this size
    but an order of seven, which stays what it is). The order keys."""
    li = host["lineitem"]
    keys, counts = np.unique(li["l_orderkey"], return_counts=True)
    chosen = keys[counts >= least_items][:orders]
    assert chosen.size == orders
    li["l_quantity"][np.isin(li["l_orderkey"], chosen)] = 100_00
    return chosen


def _column(dtype, table: dict, name: str) -> Column:
    mask = table.get(name + "_valid")
    return Column(dtype, jnp.asarray(table[name]),
                  None if mask is None else jnp.asarray(mask))


def _device(host: dict) -> dict:
    li, o, c = host["lineitem"], host["orders"], host["customer"]
    return {
        "lineitem": Table([_column(t.INT64, li, "l_orderkey"),
                           _column(t.decimal64(-2), li, "l_quantity")]),
        "orders": Table([_column(t.INT64, o, "o_orderkey"),
                         _column(t.INT64, o, "o_custkey"),
                         _column(t.TIMESTAMP_DAYS, o, "o_orderdate"),
                         _column(t.decimal64(-2), o, "o_totalprice")]),
        "customer": Table([
            _column(t.INT64, c, "c_custkey"),
            Column(t.STRING, jnp.asarray(c["c_name_len"]),
                   chars=jnp.asarray(c["c_name"]))])}


def _arrow_customer(customer: Table) -> Table:
    """The customer table ``tpch_q18_numpy`` reads: the name as Python
    strings."""
    lengths = np.asarray(customer.column(1).data)
    chars = np.asarray(customer.column(1).chars)
    text = [bytes(c[:n]).decode() for c, n in zip(chars, lengths)]
    return Table([customer.column(0), Column.from_pylist(text, t.STRING)])


@pytest.fixture(scope="module")
def server():
    from spark_rapids_jni_tpu.runtime.server import QueryServer

    with QueryServer(budget_bytes=4 << 30) as srv:
        yield srv


def _serve(server, plan, bindings):
    ticket = server.session("q18").submit(plan, bindings)
    result = ticket.result()
    assert (ticket.tier, ticket.rung, ticket.steps) == ("fused", 0, 0)
    return result


def _exactly_300(host):
    """One order's quantities sum to exactly 300.00: not over it, out."""
    li = host["lineitem"]
    key = _heavy(host, 12)[3]
    rows = np.flatnonzero(li["l_orderkey"] == key)
    li["l_quantity"][rows] = 0
    li["l_quantity"][rows[:3]] = 100_00
    return {"out": int(key)}


def _null_quantities_and_keys(host):
    """NULL quantities are skipped by both sums (an order they keep under
    300.00 is out; one all of whose quantities are NULL has no sum); a
    NULL ``l_orderkey``'s rows form the null group, which no order
    matches however much it holds."""
    li = host["lineitem"]
    keys = _heavy(host, 12, least_items=5)
    li["l_quantity_valid"] = np.ones(ITEMS, bool)
    li["l_orderkey_valid"] = np.ones(ITEMS, bool)
    still, out, none = keys[2], keys[5], keys[7]
    li["l_quantity_valid"][np.flatnonzero(li["l_orderkey"] == still)[:1]] = 0
    rows = np.flatnonzero(li["l_orderkey"] == out)
    li["l_quantity_valid"][rows[: len(rows) - 2]] = False
    li["l_quantity_valid"][li["l_orderkey"] == none] = False
    li["l_orderkey_valid"][li["l_orderkey"] == keys[9]] = False
    li["l_orderkey_valid"][:40] = False      # 40 rows more in the null group
    return {"in": int(still), "out": (int(out), int(none), int(keys[9]))}


def _a_key_no_order_holds(host):
    """A heavy lineitem key is in no orders row."""
    keys = _heavy(host, 12)
    o = host["orders"]
    o["o_orderkey"][o["o_orderkey"] == keys[4]] = 10 ** 7 + 5
    return {"out": int(keys[4])}


def _a_duplicated_custkey(host):
    """Two customer rows hold the custkey of a heavy order: both count."""
    keys = _heavy(host, 12)
    o, c = host["orders"], host["customer"]
    wanted = o["o_custkey"][o["o_orderkey"] == keys[1]][0]
    at = int(np.flatnonzero(c["c_custkey"] == wanted)[0])
    c["c_custkey"][(at + 1) % CUSTOMERS] = wanted
    return {"twice": int(keys[1])}


def _a_tie_at_the_cut(host):
    """Over a hundred heavy orders, and the 99th to the 102nd of the
    answer share price and date: the tie straddles the hundredth place."""
    _heavy(host, 130)
    full = reference_q18.q18(host, limit=EVERYTHING)["rows"]
    o = host["orders"]
    price, date = full[99][4], full[99][3]
    for row in full[98:102]:
        at = o["o_orderkey"] == row[2]
        o["o_totalprice"][at] = price
        o["o_orderdate"][at] = date
    return {"rows": reference_q18.LIMIT}


def _a_key_past_a_high_word(host):
    """One heavy order's key lies past 2**32, in the lineitem batch and in
    its orders row: the keys straddle a high word, so ``order_qty`` sorts
    them word by word (``ops/sort.py _lone_key_order``'s other branch) and
    the semi join's merged sort is the wide one; the same answer."""
    key, far = _heavy(host, 12)[6], 2 ** 32 + 17
    for table, column in (("lineitem", "l_orderkey"),
                          ("orders", "o_orderkey")):
        host[table][column][host[table][column] == key] = far
    return {"in": far, "wide": True}


# case -> what it does to the seeded tables (and what it says to check)
CASES = {
    "sparse_keys_rolled_tables_permuted_customer": lambda host: (
        _heavy(host, 12), {})[1],
    "a_sum_of_exactly_300_is_out": _exactly_300,
    "null_quantities_and_null_keys": _null_quantities_and_keys,
    "a_lineitem_key_no_order_holds": _a_key_no_order_holds,
    "a_duplicated_custkey_counts_twice": _a_duplicated_custkey,
    "no_heavy_order_but_the_seeds": lambda host: {},
    "over_a_hundred_with_a_tie_at_the_cut": _a_tie_at_the_cut,
    "an_order_key_past_a_high_word": _a_key_past_a_high_word,
}


@pytest.mark.parametrize("case", list(CASES))
def test_served_q18_equals_both_references(server, case):
    host = _host(4800 + list(CASES).index(case))
    assert not np.array_equal(host["customer"]["c_custkey"],
                              np.arange(1, CUSTOMERS + 1))    # permuted
    assert host["orders"]["o_orderkey"].max() > 2 * ORDERS    # sparse
    said = CASES[case](host)
    want = reference_q18.q18(host)
    plan = tpch._q18_plan(out_rows=OUT_ROWS)
    bindings = _device(host)
    before = REGISTRY.counters()
    served = _serve(server, plan, bindings)
    moved = {k: v - before.get(k, 0) for k, v in REGISTRY.counters().items()}
    got = reference_q18.read_answer(served.table)
    assert reference_q18.compare(got, want) == {
        "q18.row_mismatches": 0, "q18.order_breaks": 0}
    # the program's own oracle, the query evaluated row by row: it breaks
    # ties as the plan does, so the rows are the served rows one by one
    mine = tpch.tpch_q18_numpy(_arrow_customer(bindings["customer"]),
                               bindings["orders"], bindings["lineitem"])
    assert [(r[0].decode(),) + r[1:] for r in got["rows"]] == mine
    assert len(got["rows"]) == min(want["answer_rows"], reference_q18.LIMIT)
    # one region, every node's facts once a request
    assert moved["fusion.regions"] == 1
    assert moved.get("fusion.staged_regions", 0) == 0
    li = host["lineitem"]
    keyed = li.get("l_orderkey_valid", np.ones(ITEMS, bool))
    groups = len(np.unique(li["l_orderkey"][keyed])) + (not keyed.all())
    meta = {k: int(v) for k, v in served.meta.items()}
    assert meta["order_qty.num_groups"] == groups
    assert meta["order_qty.capacity"] == ORDERS + 1
    assert meta["order_qty.rows_in"] == ITEMS
    assert meta["order_qty.read_bytes"] == 18 * ITEMS
    assert not meta["order_qty.overflowed"] and not meta["order_qty.in_place"]
    # dbgen's keys, one high word and under 2**30 apart, are ordered as
    # ONE word; the five-key outer groupby never is (a fact of the data,
    # counted once a request)
    one_word = not said.get("wide", False)
    assert meta["order_qty.key_one_word"] == one_word
    assert meta["groupby.key_one_word"] == 0
    assert moved.get("groupby.key_one_word", 0) == one_word
    assert moved.get("join.key_narrowed", 0) == one_word
    # a HAVING sees the groups, not the bound's rows
    assert meta["having.rows_in"] == groups
    # (the null group is a group: heavy, it passes the HAVING, and as a
    # NULL of the IN's list it matches no order)
    null_group = li["l_quantity"][~keyed & li.get("l_quantity_valid", True)]
    assert meta["having.rows_kept"] == want["heavy_orders"] + (
        int(null_group.sum()) > 300_00)
    assert meta["in_heavy.build_rows"] == want["heavy_orders"]
    assert meta["item_join.total"] == want["joined_rows"]
    assert meta["cust_join.capacity"] == meta["item_join.capacity"] == OUT_ROWS
    assert meta["groupby.num_groups"] == want["answer_rows"] + (
        want["joined_rows"] < OUT_ROWS)          # and the null group
    assert moved["filter.rows_in"] == groups
    assert moved["groupby.groups"] == groups + meta["groupby.num_groups"]
    assert moved["groupby.rows_in"] == ITEMS + OUT_ROWS
    assert moved["groupby.capacity_groups"] == ORDERS + 1 + OUT_ROWS
    assert moved.get("join.overflowed", 0) == 0
    orders_served = {r[2] for r in got["rows"]}
    if "out" in said:
        outs = said["out"] if isinstance(said["out"], tuple) else (
            said["out"],)
        assert not orders_served & set(outs)
    if "in" in said:
        assert said["in"] in orders_served
    if "twice" in said:
        assert sum(r[2] == said["twice"] for r in got["rows"]) == 2
    if "rows" in said:
        assert len(got["rows"]) == said["rows"] < want["answer_rows"]
        assert len(want["cut_ties"]) == 4
    if case == "no_heavy_order_but_the_seeds":
        assert want["answer_rows"] < 5


def test_the_plan_declares_only_the_foreign_keys_bound():
    plan = tpch._q18_plan()
    nodes = fusion._topo(plan.root)
    assert not any(isinstance(n, fusion.DensePkJoin) for n in nodes)
    inner, outer = [n for n in nodes if isinstance(n, fusion.GroupBy)]
    assert inner.label == "order_qty" and inner.key_ranges is None
    assert inner.max_groups == fusion.groups_of("orders")
    assert inner.domains is None and outer.domains is None
    assert outer.key_ranges is None and len(outer.keys) == 5
    assert [(n.label, n.how) for n in nodes if isinstance(n, fusion.Join)] \
        == [("in_heavy", "left_semi"), ("cust_join", "inner"),
            ("item_join", "inner")]
    scans = [n for n in nodes if isinstance(n, fusion.Scan)]
    assert sorted(n.name for n in scans) == ["customer", "lineitem",
                                             "orders"]     # one scan a table
    assert all(n.bucket for n in scans)
    (having,) = [n for n in nodes if isinstance(n, fusion.Filter)]
    assert isinstance(having.child, fusion.GroupBy)       # a HAVING
    (semi,) = [n for n in nodes if isinstance(n, fusion.Join)
               and n.how == "left_semi"]
    assert semi.right is having                           # a computed build


def test_out_rows_too_small_fails_the_request(server):
    """The served path refuses a join that outgrew its ``out_rows``, with
    the true total."""
    host = _host(4900)
    _heavy(host, 12)
    want = reference_q18.q18(host)
    assert want["joined_rows"] > 32
    with pytest.raises(resilience.CapacityOverflow) as caught:
        _serve(server, tpch._q18_plan(out_rows=32), _device(host))
    assert caught.value.context == {"rows": want["joined_rows"]}


def test_more_groups_than_the_foreign_keys_bound_fails_the_request(server):
    """A lineitem batch with more order keys than |orders| + 1 breaks the
    one thing the plan declares: ``order_qty.overflowed``, refused."""
    host = _host(4901)
    _heavy(host, 12)
    few = {c: a[:50] for c, a in host["orders"].items()}
    before = REGISTRY.counters().get("groupby.overflowed", 0)
    with pytest.raises(resilience.CapacityOverflow) as caught:
        _serve(server, tpch._q18_plan(out_rows=OUT_ROWS),
               _device(dict(host, orders=few)))
    assert caught.value.context["groups"] > 51
    assert REGISTRY.counters()["groupby.overflowed"] == before + 1


def test_control_one_wrong_value_is_not_correct():
    host = _host(4902)
    want = reference_q18.q18(host)
    numbers = reference_q18.compare(reference_q18.control(host), want)
    assert numbers["q18.row_mismatches"] > 0
    _heavy(host, 12)       # and with an answer to break: a price
    numbers = reference_q18.compare(reference_q18.control(host),
                                    reference_q18.q18(host))
    assert numbers["q18.row_mismatches"] > 0
