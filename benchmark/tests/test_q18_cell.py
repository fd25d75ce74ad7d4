"""The cell ``q18_high_card_groupby_fresh`` as the chip runs it, at small
sizes on the CPU: correct, customer resident and both fact tables rolled,
the control not correct, traced with the four readers of PR 48 and the
``groupby.*`` / ``join.*`` / ``sort.*`` / ``filter.*`` readers it joined
returning a value, and the makers' rules."""

import numpy as np
import pytest

from conftest import ROOT  # noqa: F401  (puts the root on sys.path)

CELL = "q18_high_card_groupby_fresh"
# one order in seven holds seven lineitems and 0.03% of those pass 300:
# 200,000 orders hold about eight
SIZES = {"lineitem": 800_000, "orders": 200_000, "customer": 3_000}
NEW = ("groupby.key_sort_device_s_per_query",
       "groupby.move_device_s_per_query", "groupby.group_fill_share")
ROOFLINES = {"plan.hbm_roofline_share", "join.hbm_roofline_share",
             "groupby.hbm_roofline_share"}


@pytest.fixture(scope="module")
def traced():
    from benchmark import harness

    lines = []
    result = harness.run_cell(
        CELL, 2**31 + 48, 0.5, True, platform="cpu", sizes=SIZES,
        say=lambda msg, flush=False: lines.append(msg))
    return result, lines


def test_q18_cell_untraced():
    from benchmark import harness

    result = harness.run_cell(CELL, 2**31 + 49, 0.5, False, platform="cpu",
                              sizes=SIZES, say=lambda *a, **k: None)
    assert result["correct"] and result["failed"] == 0
    assert result["checks"] == {
        "q18.order_breaks": {"value": 0, "limit": 0},
        "q18.row_mismatches": {"value": 0, "limit": 0},
        "failed_requests": {"value": 0, "limit": 0}}
    assert set(result["metrics"]) == {"query_p50_s", "query_p95_s",
                                      "rows_per_s", "setup_s"}


def test_q18_cell_traced_reads_every_metric(traced, bench):
    result, lines = traced
    assert result["correct"] and result["failed"] == 0
    declared = {m["name"] for m in bench["per_layer"]
                if CELL in m.get("workloads", [CELL])}
    assert set(NEW) | ROOFLINES <= declared
    # the roofline shares need the chip's peaks; everything else reads
    assert set(result["metrics"]) == declared - ROOFLINES
    m = {k: v["value"] for k, v in result["metrics"].items()}
    for name in NEW:
        assert m[name] > 0, name
    # the stages lie inside the groupbys' scopes, and leave room for the
    # third (``reduce``)
    assert m["groupby.key_sort_device_s_per_query"] + m[
        "groupby.move_device_s_per_query"] < m["groupby.device_s_per_query"]
    # every order holds a lineitem: the inner groupby's bound is full but
    # for the null group's row, the outer one's (65,536) nearly empty
    groups = SIZES["orders"]
    assert groups < m["groupby.groups_per_query"] < groups + 100
    assert m["groupby.group_fill_share"] == pytest.approx(
        100.0 * m["groupby.groups_per_query"] / (groups + 1 + 65_536))
    # the HAVING saw the groups and kept a handful
    assert 0 < m["filter.kept_share"] < 0.05
    assert 0 < m["join.output_fill_share"] < 1
    assert m["fusion.regions_per_query"] == 1 and m["cache.hit_share"] == 0
    assert m["sort.device_s_per_query"] > 0 and m["join.device_s_per_query"] > 0


def test_roofline_reads_with_the_chips_peaks(traced, monkeypatch):
    """``groupby.hbm_roofline_share`` finds its counters and its device
    time in this cell: given the chip's peak to divide by, it returns the
    share its function reckons."""
    import types

    from benchmark import harness, resolve

    result, _ = traced
    mod = resolve.module("layer_metrics", "groupby.hbm_roofline_share")
    requests = result["attempted"]
    rows, groups = SIZES["lineitem"], SIZES["orders"]
    counters = {"groupby.rows_in": rows * requests,
                "groupby.read_bytes": 18 * rows * requests,
                "groupby.groups": groups * requests}
    assert mod.groupby_bytes(counters, requests) == 18 * (rows + groups)
    assert mod.groupby_bytes({"groupby.groups": 5}, requests) == 0.0
    seconds = result["metrics"]["groupby.device_s_per_query"]["value"]
    monkeypatch.setattr(
        resolve.module("layer_metrics", "groupby.device_s_per_query"),
        "read", lambda run: seconds)
    run = types.SimpleNamespace(
        counters=counters, requests=[None] * requests,
        peaks=harness._peaks("TPU v5 lite", "tpu"))
    assert mod.read(run) == pytest.approx(
        100.0 * 18 * (rows + groups) / 819e9 / seconds)
    run.counters = {}          # a program that does not count: nothing
    assert mod.read(run) is None


def test_fill_share_is_the_counters():
    from benchmark import resolve

    mod = resolve.module("layer_metrics", "groupby.group_fill_share")
    assert mod.fill_share({"groupby.groups": 3 * 87_776,
                           "groupby.capacity_groups": 3 * 1_500_001}) == \
        100.0 * 87_776 / 1_500_001
    assert mod.fill_share({"groupby.groups": 5}) is None


def test_control_is_not_correct():
    from benchmark import control, resolve

    numbers = control.control_numbers(CELL, 2**31 + 5, platform="cpu",
                                      sizes=SIZES)["q18"]
    limits = resolve.module("plans", "q18").LIMITS
    assert any(not v <= limits[n] for n, v in numbers.items()), numbers


def test_customer_is_resident_and_the_makers_keep_dbgens_rules():
    from benchmark import harness, resolve

    bound = harness.PlanTables.of(resolve.module("plans", "q18"))
    assert bound.fresh == ("lineitem", "orders")
    assert bound.tables == ["customer", "lineitem", "orders"]
    config = {"tables": {
        "customer": {"maker": "customer_q18", "rows": 3000},
        "lineitem": {"maker": "lineitem_q18", "rows": 40_000},
        "orders": {"maker": "orders_q18", "rows": 10_000}}}
    made = harness.make_tables(config, 2**31 + 9, {})
    host = {name: maker.host_copy(arrays)
            for name, (maker, _, arrays) in made.items()}
    li, o, c = host["lineitem"], host["orders"], host["customer"]
    # 1 to 7 lineitems an order, clustered, on the orders' sparse keys
    keys, counts = np.unique(li["l_orderkey"], return_counts=True)
    assert np.array_equal(keys, np.sort(o["o_orderkey"]))
    assert counts.min() >= 1 and counts.max() == 7
    assert (np.diff(li["l_orderkey"]) >= 0).all()
    index = np.arange(10_000)
    assert np.array_equal(o["o_orderkey"], (index // 8) * 32 + index % 8 + 1)
    # quantities 1..50, as cents
    assert set(np.unique(li["l_quantity"]).tolist()) == {
        100 * q for q in range(1, 51)}
    # no order for a customer whose key 3 divides; every other key drawn
    assert (o["o_custkey"] % 3 != 0).all()
    assert o["o_custkey"].min() == 1 and o["o_custkey"].max() == 2999
    assert len(np.unique(o["o_custkey"])) > 1900
    assert o["o_orderdate"].min() >= 8035 and o["o_orderdate"].max() <= 10440
    assert o["o_totalprice"].min() >= 85_000
    assert o["o_totalprice"].max() <= 56_000_000
    # the customers: every key once, permuted, named by their key
    maker = resolve.module("tables", "customer_q18")
    assert sorted(c["c_custkey"].tolist()) == list(range(1, 3001))
    assert (c["c_custkey"] != range(1, 3001)).sum() > 2900
    for key, n, chars in zip(c["c_custkey"][:200], c["c_name_len"][:200],
                             c["c_name"][:200]):
        assert bytes(chars[:n]) == maker.name_of(key) == \
            b"Customer#%09d" % key
        assert n == 18 and not chars[n:].any()
    table = maker.to_table(made["customer"][2])
    assert table.column(1).is_padded_string and table.num_rows == 3000
    assert resolve.module("plans", "q18").min_bytes(
        {"lineitem": 10, "orders": 10, "customer": 10}) == 10 * (16 + 28 + 37)
