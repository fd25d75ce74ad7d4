"""The cell ``q4_semi_join_fresh`` as the chip runs it, at tiny sizes on
the CPU: correct, two tables bound and both rolled, the control not
correct, and traced with the three ``join.*`` readers of PR 41 and the
operators' readers returning a value."""

import pytest

from conftest import ROOT  # noqa: F401  (puts the root on sys.path)

CELL = "q4_semi_join_fresh"
SIZES = {"orders": 3000, "lineitem": 12100}
NEW = ("join.build_device_s_per_query", "join.probe_device_s_per_query")


@pytest.fixture(scope="module")
def traced():
    from benchmark import harness

    lines = []
    result = harness.run_cell(
        CELL, 2**31 + 41, 0.5, True, platform="cpu", sizes=SIZES,
        say=lambda msg, flush=False: lines.append(msg))
    return result, lines


def test_q4_cell_untraced():
    from benchmark import harness

    result = harness.run_cell(CELL, 2**31 + 43, 0.5, False, platform="cpu",
                              sizes=SIZES, say=lambda *a, **k: None)
    assert result["correct"] and result["failed"] == 0
    assert result["checks"] == {
        "q4.count_mismatches": {"value": 0, "limit": 0},
        "q4.out_of_order": {"value": 0, "limit": 0},
        "failed_requests": {"value": 0, "limit": 0}}
    assert set(result["metrics"]) == {"query_p50_s", "query_p95_s",
                                      "rows_per_s", "setup_s"}


def test_q4_cell_traced_reads_every_metric(traced, bench):
    result, lines = traced
    assert result["correct"] and result["failed"] == 0
    declared = {m["name"] for m in bench["per_layer"]
                if CELL in m.get("workloads", [CELL])}
    # the roofline shares need the chip's peaks; everything else reads
    assert set(result["metrics"]) == declared - {
        "plan.hbm_roofline_share", "join.hbm_roofline_share"}
    m = {k: v["value"] for k, v in result["metrics"].items()}
    for name in NEW:
        assert m[name] > 0, name
    stages = sum(m[name] for name in NEW)
    # the CPU stand-in runs operations side by side: the stages may overlap
    assert 0.9 * m["join.device_s_per_query"] <= stages \
        <= m["join.device_s_per_query"] * 1.25
    assert m["join.device_s_per_query"] > 0.5 * m["region.device_s_per_query"]
    assert 40 < m["filter.kept_share"] < 60     # the quarter, and 63% late
    assert 0 < m["join.matched_share"] < 10     # the quarter's orders
    assert m["groupby.groups_per_query"] == 0   # bounded: no sort-path group
    assert m["fusion.regions_per_query"] == 1 and m["cache.hit_share"] == 0


def test_roofline_bytes_are_the_counters():
    from benchmark import resolve

    mod = resolve.module("layer_metrics", "join.hbm_roofline_share")
    counters = {"join.build_rows": 2 * 7000, "join.probe_rows": 2 * 3000}
    assert mod.join_bytes(counters, 2) == 9 * 10000
    assert mod.join_bytes(counters, 0) == 0.0


def test_control_is_not_correct():
    from benchmark import control, resolve

    numbers = control.control_numbers(CELL, 2**31 + 5, platform="cpu",
                                      sizes=SIZES)["q4"]
    limits = resolve.module("plans", "q4").LIMITS
    assert any(not v <= limits[n] for n, v in numbers.items()), numbers


def test_both_tables_are_rolled_whole():
    """Every array of a table moves by one stride: a lineitem keeps its
    dates, an order its priority, and the multiset of rows is the base's."""
    from benchmark import resolve

    maker = resolve.module("tables", "orders_q4")
    arrays = maker.make(4096, 2**31 + 9)
    base = maker.host_copy(arrays)

    def rows(host):
        return sorted(zip(host["o_orderkey"].tolist(),
                          host["o_orderdate"].tolist(),
                          host["o_orderpriority_len"].tolist(),
                          (r.tobytes() for r in host["o_orderpriority"])))

    fresh = resolve.module("fresh", "roll_rows").Freshener(arrays, 2**31 + 9)
    first = maker.host_copy(fresh.next())
    assert rows(first) == rows(base)
    assert not (first["o_orderkey"] == base["o_orderkey"]).any()
    table = maker.to_table(fresh.next())
    assert table.column(2).is_padded_string and table.num_rows == 4096
