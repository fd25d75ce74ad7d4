"""The yardstick itself, guarded by tier-1: every cell of ``BENCHMARK.json``
once through ``benchmark.harness.run_cell`` on the CPU at tiny sizes (the
functions are the chip's, the sizes are not), the q3 control, and the
served planned q3 against the benchmark's own numpy reference."""

import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

TINY = {"lineitem": 4096, "customer": 512, "orders": 1024}
END_TO_END = {"query_p50_s", "query_p95_s", "rows_per_s", "setup_s"}


def _cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return [w["name"] for w in json.load(f)["workloads"]]


@pytest.fixture(autouse=True)
def _harness_options():
    """The harness sets three options of the program and leaves them set
    (a run is a process of its own); a test puts them back."""
    from spark_rapids_jni_tpu.utils.config import reset_option

    yield
    for name in ("telemetry.enabled", "server.estimate_path",
                 "rtfilter.path"):
        reset_option(name)


def test_benchmark_has_the_q3_cell():
    assert _cells()[:4] == ["sf10_q1_planned_fresh", "sf1_q1_general_fresh",
                            "sf1_q1_planned_fresh", "sf1_q3_planned_fresh"]


@pytest.mark.parametrize("cell", _cells())
def test_cell_runs_correct_on_the_cpu(cell):
    from benchmark import harness

    lines = []
    result = harness.run_cell(
        cell, 2**31 + 17, 0.5, False, platform="cpu", sizes=TINY,
        say=lambda msg, flush=False: lines.append(msg))
    assert result["correct"] is True, lines[-8:]
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert result["checks"]["failed_requests"] == {"value": 0, "limit": 0}
    assert set(result["metrics"]) == END_TO_END
    assert all(m["value"] > 0 for m in result["metrics"].values())
    assert json.loads(json.dumps(result)) == result


def test_q3_control_is_not_correct():
    """The reference with float32 revenue sums, at a size where a group's
    sum passes 2**24: it must differ from the reference."""
    from benchmark import control, resolve

    numbers = control.control_numbers(
        "sf1_q3_planned_fresh", 2**31 + 5, platform="cpu", sizes=TINY)
    limits = resolve.module("plans", "q3_planned").LIMITS
    assert numbers["q3_planned"]["q3.mismatches"] > limits[
        "q3.mismatches"] == 0


@pytest.mark.parametrize("seed", [3, 2**31 + 5, 2**32 + 77])
def test_served_planned_q3_equals_the_benchmarks_reference(seed):
    """The program's planned q3 through ``QueryServer`` against
    ``benchmark/reference_q3.py`` over the benchmark's own tables: every
    group, its date, priority and revenue, and the order of the rows."""
    import jax

    from benchmark import harness, resolve
    from spark_rapids_jni_tpu.runtime.server import QueryServer

    mod = resolve.module("plans", "q3_planned")
    _, config, _ = resolve.cell("sf1_q3_planned_fresh", resolve.spec())
    made = harness.make_tables(config, seed, TINY)
    hosts = {name: maker.host_copy(arrays)
             for name, (maker, _, arrays) in made.items()}
    bind = {scan: made[table][0].to_table(made[table][2])
            for scan, table in mod.BINDINGS.items()}
    with QueryServer(budget_bytes=4 << 30) as srv:
        ticket = srv.session("t").submit(mod.plan(), bind)
        result = ticket.result()
        jax.block_until_ready(result.table.column(0).data)
        assert (ticket.tier, ticket.rung, ticket.steps) == ("fused", 0, 0)
    want = mod.oracle(hosts)
    assert len(want["groups"]) > 10
    got = mod.read_answer(result.table)
    assert mod.compare(got, want) == {"q3.mismatches": 0,
                                      "q3.out_of_order": 0}
    assert got["groups"] == want["groups"]
    # the group bound the plan states is the result's row count
    assert result.table.num_rows == TINY["orders"] + 1
    assert int(np.asarray(result.meta["groupby.num_groups"])) == len(
        want["groups"]) + 1   # and the null group of the unmatched rows
