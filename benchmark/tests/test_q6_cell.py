"""The cell ``sf10_q6_fresh`` as the chip runs it, at 4,096 rows on the CPU:
correct against the Python-integer reference, the float32 control not
correct, and traced with the ``filter.*`` reader that lists it."""

import numpy as np

from conftest import ROOT, TINY  # noqa: F401

CELL = "sf10_q6_fresh"


def test_q6_cell_untraced(run_tiny):
    result, _ = run_tiny(CELL)
    assert result["correct"] and result["failed"] == 0
    assert result["checks"] == {
        "q6.null_mismatch": {"value": 0, "limit": 0},
        "q6.sum_mismatch": {"value": 0, "limit": 0},
        "failed_requests": {"value": 0, "limit": 0}}
    assert set(result["metrics"]) == {"query_p50_s", "query_p95_s",
                                      "rows_per_s", "setup_s"}


def test_q6_cell_traced_reads_every_metric(run_tiny, bench):
    result, _ = run_tiny(CELL, trace=True)
    assert result["correct"] and result["failed"] == 0
    declared = {m["name"] for m in bench["per_layer"]
                if CELL in m.get("workloads", [CELL])}
    assert set(result["metrics"]) == declared - {"plan.hbm_roofline_share"}
    m = {k: v["value"] for k, v in result["metrics"].items()}
    assert 0 < m["filter.kept_share"] < 10
    assert "filter.device_s_per_query" not in m    # its docstring says why
    assert m["fusion.regions_per_query"] == 1 and m["cache.hit_share"] == 0


def test_control_is_not_correct():
    """At the cell's own size the float32 sum is off by far more than one;
    at 4,096 rows it still differs (a product has up to 27 bits)."""
    from benchmark import control, resolve

    numbers = control.control_numbers(CELL, 2**31 + 5, platform="cpu",
                                      sizes={"lineitem": 1 << 16})["q6"]
    limits = resolve.module("plans", "q6").LIMITS
    assert any(not v <= limits[n] for n, v in numbers.items()), numbers


def test_reference_is_exact_and_says_null():
    from benchmark import reference_q6 as ref

    li = {"l_shipdate": np.array([8766, 9130, 9131, 8765], np.int32),
          "l_discount": np.array([5, 7, 6, 6], np.int64),
          "l_quantity": np.array([2399, 100, 100, 100], np.int64),
          "l_extendedprice": np.array([10_499_999, 90_000, 1, 1], np.int64)}
    assert ref.q6(li)["revenue"] == 10_499_999 * 5 + 90_000 * 7
    li["l_quantity"][:] = 2400
    assert ref.q6(li)["revenue"] is None
    assert ref.compare({"revenue": None}, {"revenue": None}) == {
        "q6.null_mismatch": 0, "q6.sum_mismatch": 0}
    assert ref.compare({"revenue": 1}, {"revenue": None})[
        "q6.null_mismatch"] == 1
    assert ref.min_bytes(10) == 280
