"""The cell ``q14_broadcast_join_fresh`` as the chip runs it, at tiny sizes
on the CPU: correct, ``part`` resident and lineitem rolled, the control not
correct, and traced with the two readers of PR 45 and the ``join.*``
readers it joined returning a value. (The second cell ISSUE 45 asked for,
``sf10_q1_general_sorted_fresh``, was left out by its fit rule: the plan it
would have run is held to q1's answer in
``tests/test_tpch_q14_plan.py``.)"""

import pytest

from conftest import ROOT  # noqa: F401  (puts the root on sys.path)

CELL = "q14_broadcast_join_fresh"
SIZES = {"lineitem": 12100, "part": 500}
NEW = ("join.output_fill_share", "join.gather_device_s_per_query")
STAGES = ("join.build_device_s_per_query", "join.probe_device_s_per_query",
          "join.gather_device_s_per_query")


@pytest.fixture(scope="module")
def traced():
    from benchmark import harness

    lines = []
    result = harness.run_cell(
        CELL, 2**31 + 45, 0.5, True, platform="cpu", sizes=SIZES,
        say=lambda msg, flush=False: lines.append(msg))
    return result, lines


def test_q14_cell_untraced():
    from benchmark import harness

    result = harness.run_cell(CELL, 2**31 + 47, 0.5, False, platform="cpu",
                              sizes=SIZES, say=lambda *a, **k: None)
    assert result["correct"] and result["failed"] == 0
    assert result["checks"] == {
        "q14.sum_mismatches": {"value": 0, "limit": 0},
        "failed_requests": {"value": 0, "limit": 0}}
    assert set(result["metrics"]) == {"query_p50_s", "query_p95_s",
                                      "rows_per_s", "setup_s"}


def test_q14_cell_traced_reads_every_metric(traced, bench):
    result, lines = traced
    assert result["correct"] and result["failed"] == 0
    declared = {m["name"] for m in bench["per_layer"]
                if CELL in m.get("workloads", [CELL])}
    assert set(NEW) | set(STAGES) | {"join.hbm_roofline_share"} <= declared
    # the roofline shares need the chip's peaks; everything else reads
    assert set(result["metrics"]) == declared - {
        "plan.hbm_roofline_share", "join.hbm_roofline_share"}
    m = {k: v["value"] for k, v in result["metrics"].items()}
    for name in NEW + STAGES:
        assert m[name] > 0, name
    # the CPU stand-in runs operations side by side: the stages may overlap
    assert 0.9 * m["join.device_s_per_query"] <= sum(
        m[name] for name in STAGES) <= m["join.device_s_per_query"] * 1.25
    assert m["join.device_s_per_query"] > 0.5 * m["region.device_s_per_query"]
    assert 0.5 < m["filter.kept_share"] < 2.5      # one month of 2,406 days
    assert m["join.matched_share"] == m["filter.kept_share"]  # every key held
    # about 150 rows in a capacity of 2,097,152
    assert 0 < m["join.output_fill_share"] < 0.02
    assert m["fusion.regions_per_query"] == 1 and m["cache.hit_share"] == 0


def test_roofline_reads_with_the_chips_peaks(traced, monkeypatch):
    """``join.hbm_roofline_share`` finds its counters and its device time
    in this cell (an inner join whose probe side holds a scan's rows says
    ``probe_rows``): given the chip's peak to divide by, it returns a
    share."""
    import types

    from benchmark import harness, resolve

    result, _ = traced
    mod = resolve.module("layer_metrics", "join.hbm_roofline_share")
    requests = result["attempted"]
    counters = {"join.build_rows": 500 * requests,
                "join.probe_rows": 12100 * requests}
    assert mod.join_bytes(counters, requests) == 9 * 12600
    seconds = result["metrics"]["join.device_s_per_query"]["value"]
    monkeypatch.setattr(
        resolve.module("layer_metrics", "join.device_s_per_query"), "read",
        lambda run: seconds)
    run = types.SimpleNamespace(
        counters=counters, requests=[None] * requests,
        peaks=harness._peaks("TPU v5 lite", "tpu"))
    assert mod.read(run) == pytest.approx(
        100.0 * 9 * 12600 / 819e9 / seconds)


def test_fill_share_is_the_counters():
    from benchmark import resolve

    mod = resolve.module("layer_metrics", "join.output_fill_share")
    assert mod.fill_share({"join.matched_rows": 3 * 749_000,
                           "join.capacity_rows": 3 * 2_097_152}) == \
        100.0 * 749_000 / 2_097_152
    assert mod.fill_share({"join.matched_rows": 5}) is None


def test_control_is_not_correct():
    from benchmark import control, resolve

    numbers = control.control_numbers(CELL, 2**31 + 5, platform="cpu",
                                      sizes=SIZES)["q14"]
    limits = resolve.module("plans", "q14").LIMITS
    assert any(not v <= limits[n] for n, v in numbers.items()), numbers


def test_part_is_resident_and_lineitem_rolled():
    from benchmark import harness, resolve

    bound = harness.PlanTables.of(resolve.module("plans", "q14"))
    assert bound.fresh == ("lineitem",)
    assert bound.tables == ["lineitem", "part"]
    maker = resolve.module("tables", "part_q14")
    host = maker.host_copy(maker.make(4096, 2**31 + 9))
    keys = host["p_partkey"]
    assert sorted(keys.tolist()) == list(range(1, 4097))      # each once
    assert (keys != range(1, 4097)).sum() > 4000               # permuted
    text = [bytes(r[:n]) for r, n in zip(host["p_type"], host["p_type_len"])]
    assert set(text) <= set(maker.TYPES) and len(set(text)) > 140
    assert not any(r[n:].any() for r, n in zip(host["p_type"],
                                               host["p_type_len"]))
    table = maker.to_table(maker.make(4096, 2**31 + 9))
    assert table.column(1).is_padded_string and table.num_rows == 4096
