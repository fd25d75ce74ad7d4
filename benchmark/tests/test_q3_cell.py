"""The cell ``sf1_q3_planned_fresh`` as the chip runs it, at tiny sizes on
the CPU: correct, three tables bound, and traced with every one of the six
per-layer readers PR 28 brought returning a value (on the CPU stand-in an
operation's scope comes from the module's HLO proto in the trace, on the
TPU from the stat ``tf_op``: ``scope_reduce``)."""

import gzip
import os

import pytest

from conftest import ROOT

CELL = "sf1_q3_planned_fresh"
SIZES = {"customer": 512, "orders": 1024, "lineitem": 4096}
NEW = ("join.device_s_per_query", "groupby.device_s_per_query",
       "sort.device_s_per_query", "join.matched_share",
       "groupby.groups_per_query", "dispatch.compile_s")


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    from benchmark import harness

    lines = []
    keep = str(tmp_path_factory.mktemp("trace"))
    result = harness.run_cell(
        CELL, 2**31 + 29, 0.5, True, platform="cpu", sizes=SIZES,
        keep_trace=keep, say=lambda msg, flush=False: lines.append(msg))
    return result, lines, os.path.join(keep, "trace.xplane.pb")


def test_q3_cell_untraced():
    from benchmark import harness

    result = harness.run_cell(CELL, 2**31 + 31, 0.5, False, platform="cpu",
                              sizes=SIZES, say=lambda *a, **k: None)
    assert result["correct"] and result["failed"] == 0
    assert result["checks"] == {
        "q3.mismatches": {"value": 0, "limit": 0},
        "q3.out_of_order": {"value": 0, "limit": 0},
        "failed_requests": {"value": 0, "limit": 0}}
    assert set(result["metrics"]) == {"query_p50_s", "query_p95_s",
                                      "rows_per_s", "setup_s"}


def test_q3_cell_traced_reads_every_new_metric(traced, bench):
    result, lines, _ = traced
    assert result["correct"] and result["failed"] == 0
    declared = {m["name"] for m in bench["per_layer"]
                if CELL in m.get("workloads", [CELL])}
    # the roofline share needs the chip's peaks; everything else reads
    assert set(result["metrics"]) == declared - {"plan.hbm_roofline_share"}
    for name in NEW:
        assert result["metrics"][name]["value"] > 0, name
    m = {k: v["value"] for k, v in result["metrics"].items()}
    # the operators' parts lie inside the region's time
    parts = (m["join.device_s_per_query"] + m["groupby.device_s_per_query"]
             + m["sort.device_s_per_query"])
    assert 0 < parts <= m["region.device_s_per_query"] * 1.001
    assert 0 < m["join.matched_share"] < 100
    assert m["groupby.groups_per_query"] <= SIZES["orders"] + 1
    said = [ln for ln in lines if "] operators: " in ln]
    assert len(said) == 1 and "under no node's scope" in said[0]


def test_scopes_of_the_kept_trace(traced):
    """Every operation of the region's module names a plan node."""
    from benchmark import scope_reduce

    _, _, path = traced
    ops = scope_reduce.device_operations(path, "cpu")
    nodes = {scope_reduce.node_of(scope) for _, _, _, scope in ops
             if scope and "region.tpch_q3_planned" in scope}
    assert {"pk1", "pk2", "groupby", "sort"} <= nodes
    assert scope_reduce.node_of(
        "jit(region_x)/region.x/groupby/while/body/closed_call/sort:"
    ) == "groupby"
    assert scope_reduce.node_of("row_args_[0][1][0]:") is None


def test_tpu_trace_scopes_are_read_from_tf_op(tmp_path):
    """The kept chip trace of PR 25 (planned q1 at SF10): operations of the
    region carry ``region.tpch_q1_planned`` in the stat ``tf_op`` of their
    event metadata, which the wire-format reader finds."""
    from benchmark import scope_reduce

    src = os.path.join(ROOT, "benchmark", "tests", "data",
                       "sf10_q1_planned_fresh.spans.xplane.pb.gz")
    path = str(tmp_path / "trace.xplane.pb")
    with gzip.open(src) as f, open(path, "wb") as out:
        out.write(f.read())
    ops = scope_reduce.device_operations(path, "tpu")
    assert len(ops) > 1000
    region = [o for o in ops if o[3] and "region.tpch_q1_planned" in o[3]]
    assert region and all(e > s for s, e, _, _ in region)
    assert any(name.startswith("%fusion") for _, _, name, _ in region)
