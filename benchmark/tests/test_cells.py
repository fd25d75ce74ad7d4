"""Each cell end to end at 4,096 rows through the functions a chip run
uses, and the result line held to the contract's keys."""

import json
import os
import subprocess
import sys

import pytest

from conftest import ROOT


def _cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return [w["name"] for w in json.load(f)["workloads"]]


def _declared(bench, kind, cell):
    return {m["name"]: m for m in bench[kind]
            if cell in m.get("workloads", [cell])}


@pytest.mark.parametrize("cell", _cells())
def test_cell_end_to_end(cell, bench, run_tiny):
    result, lines = run_tiny(cell)
    assert json.loads(json.dumps(result)) == result
    assert set(result) == {"correct", "attempted", "failed", "metrics",
                           "device"}
    assert result["correct"] is True
    assert result["attempted"] >= 1 and result["failed"] == 0
    want = _declared(bench, "end_to_end", cell)
    assert set(result["metrics"]) == set(want)
    for name, m in result["metrics"].items():
        assert m["unit"] == want[name]["unit"] and m["value"] > 0
    assert set(result["device"]) == {"platform", "kind", "count",
                                     "memory_peak_bytes"}
    # every line names platform, device kind and device count
    assert lines and all(ln.startswith("[cpu cpu x") for ln in lines)
    # each number compared is printed beside its limit
    assert any("check q1.int_mismatches" in ln and "limit 0" in ln
               for ln in lines)
    assert any("check q1.avg_max_rel_err" in ln and "limit 1e-09" in ln
               for ln in lines)


@pytest.mark.parametrize("cell", _cells())
def test_cell_traced(cell, bench, run_tiny):
    result, _ = run_tiny(cell, trace=True)
    assert result["correct"] is True
    want = _declared(bench, "per_layer", cell)
    assert set(result["metrics"]) <= set(want)
    # everything but the roofline share (no peak is claimed for a CPU)
    assert set(want) - set(result["metrics"]) == {"plan.hbm_roofline_share"}
    for name, m in result["metrics"].items():
        assert m["unit"] == want[name]["unit"]
    assert result["metrics"]["cache.hit_share"]["value"] == 0
    assert result["metrics"]["dispatch.compiles_in_window"]["value"] == 0
    dev = result["device"]
    assert 0 < dev["busy_s"] <= dev["window_s"]
    for key in ("device_ops", "idle_gaps"):
        rows = result["breakdown"][key]
        assert 1 <= len(rows) <= 10
        assert all(isinstance(n, str) and s >= 0 for n, s in rows)


def test_no_chip_exits_nonzero_and_prints_no_result():
    """``run.py`` asks for the TPU; this machine has none."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", _cells()[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "no chip found" in proc.stderr
    assert not any(ln.startswith("{") for ln in proc.stdout.splitlines())


def test_percentile_is_nearest_rank():
    from benchmark.harness import percentile_nearest_rank

    assert percentile_nearest_rank([3, 1, 2, 4], 95) == 4
    assert percentile_nearest_rank(list(range(1, 101)), 95) == 95
    assert percentile_nearest_rank([7], 95) == 7


def test_plan_cycle_sends_every_seed_the_same_work():
    from itertools import islice

    from benchmark.harness import _plan_cycle

    mix = {"plans": [{"plan": "a", "weight": 4}, {"plan": "b", "weight": 1}]}
    for seed in (1, 2, 2**31 + 5):
        turn = list(islice(_plan_cycle(mix, seed), 10))
        assert sorted(turn[:5]) == sorted(turn[5:]) == ["a"] * 4 + ["b"]
