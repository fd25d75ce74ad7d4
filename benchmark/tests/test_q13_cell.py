"""The cell ``q13_like_planned_fresh`` as the chip runs it, at tiny sizes on
the CPU: correct, two tables bound (customer resident, orders rolled row by
row), the control not correct, and traced with the three ``filter.*``
readers of PR 38 and the operators' readers returning a value."""

import numpy as np
import pytest

from conftest import ROOT  # noqa: F401  (puts the root on sys.path)

CELL = "q13_like_planned_fresh"
SIZES = {"customer": 600, "orders": 4096}
NEW = ("filter.device_s_per_query", "filter.kept_share")


@pytest.fixture(scope="module")
def traced():
    from benchmark import harness

    lines = []
    result = harness.run_cell(
        CELL, 2**31 + 29, 0.5, True, platform="cpu", sizes=SIZES,
        say=lambda msg, flush=False: lines.append(msg))
    return result, lines


def test_q13_cell_untraced():
    from benchmark import harness

    result = harness.run_cell(CELL, 2**31 + 31, 0.5, False, platform="cpu",
                              sizes=SIZES, say=lambda *a, **k: None)
    assert result["correct"] and result["failed"] == 0
    assert result["checks"] == {
        "q13.group_mismatches": {"value": 0, "limit": 0},
        "q13.out_of_order": {"value": 0, "limit": 0},
        "failed_requests": {"value": 0, "limit": 0}}
    assert set(result["metrics"]) == {"query_p50_s", "query_p95_s",
                                      "rows_per_s", "setup_s"}


def test_q13_cell_traced_reads_every_metric(traced, bench):
    result, lines = traced
    assert result["correct"] and result["failed"] == 0
    declared = {m["name"] for m in bench["per_layer"]
                if CELL in m.get("workloads", [CELL])}
    # the roofline shares need the chip's peaks; everything else reads
    assert set(result["metrics"]) == declared - {
        "plan.hbm_roofline_share", "filter.hbm_roofline_share"}
    m = {k: v["value"] for k, v in result["metrics"].items()}
    for name in NEW:
        assert m[name] > 0, name
    assert 90 < m["filter.kept_share"] < 100
    assert 0 < m["join.matched_share"] < 100      # a third hold no order
    parts = (m["filter.device_s_per_query"] + m["join.device_s_per_query"]
             + m["groupby.device_s_per_query"] + m["sort.device_s_per_query"])
    assert 0 < parts <= m["region.device_s_per_query"] * 1.001
    assert m["fusion.regions_per_query"] == 1 and m["cache.hit_share"] == 0


def test_roofline_bytes_are_the_counters():
    from benchmark import resolve

    mod = resolve.module("layer_metrics", "filter.hbm_roofline_share")
    counters = {"strings.like_bytes": 2 * 4096 * 79,
                "filter.rows_in": 2 * 4096}
    assert mod.like_bytes(counters, 2) == 4096 * (79 + 4)
    assert mod.like_bytes(counters, 0) == 0.0


def test_control_is_not_correct():
    from benchmark import control, resolve

    numbers = control.control_numbers(CELL, 2**31 + 5, platform="cpu",
                                      sizes=SIZES)["q13_planned"]
    limits = resolve.module("plans", "q13_planned").LIMITS
    assert any(not v <= limits[n] for n, v in numbers.items()), numbers


def test_the_text_is_the_configurations():
    """Lengths 19..78, zero bytes after them, words of the list joined by
    single spaces, a third of the customers without an order, and the
    pattern removing between 0.5% and 5% of the orders."""
    from benchmark import reference_q13, resolve

    maker = resolve.module("tables", "orders_q13")
    host = maker.host_copy(maker.make(8192, 2**31 + 7,
                                      rows_of={"customer": 600}))
    lengths, chars = host["o_comment_len"], host["o_comment"]
    assert chars.shape == (8192, 79) and chars.dtype == np.uint8
    assert lengths.min() >= 19 and lengths.max() <= 78
    at = np.arange(79)
    assert not chars[at[None, :] >= lengths[:, None]].any()
    assert chars[at[None, :] < lengths[:, None]].all()
    words = set(maker.WORDS)
    for row, n in zip(chars[:256], lengths[:256]):
        text = row[:n].tobytes().decode().split(" ")
        assert all(w in words for w in text[:-1])
        assert any(w.startswith(text[-1]) for w in words)   # the cut one
    keys = host["o_custkey"]
    assert keys.min() >= 1 and keys.max() <= 600 and (keys % 3 != 0).all()
    assert (host["o_orderkey"] == np.arange(1, 8193)).all()
    share = reference_q13.matches(host).mean()
    assert 0.005 < share < 0.05, share


def test_roll_rows_keeps_every_row_whole():
    """The multiset of (key, custkey, comment) rows is the base's, and no
    offset repeats."""
    from benchmark import resolve

    maker = resolve.module("tables", "orders_q13")
    arrays = maker.make(4096, 2**31 + 9, rows_of={"customer": 600})
    base = maker.host_copy(arrays)

    def rows(host):
        return sorted(zip(host["o_orderkey"].tolist(),
                          host["o_custkey"].tolist(),
                          host["o_comment_len"].tolist(),
                          (r.tobytes() for r in host["o_comment"])))

    fresh = resolve.module("fresh", "roll_rows").Freshener(arrays, 2**31 + 9)
    first = maker.host_copy(fresh.next())
    second = maker.host_copy(fresh.next())
    assert rows(first) == rows(base) == rows(second)
    assert not (first["o_orderkey"] == base["o_orderkey"]).any()
    assert not (second["o_orderkey"] == first["o_orderkey"]).any()
    # the table it hands the server is the program's, comment and all
    table = maker.to_table(fresh.next())
    assert table.column(2).is_padded_string and table.num_rows == 4096
