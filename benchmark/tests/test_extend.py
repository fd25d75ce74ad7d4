"""A later PR adds a cell, a mix, a plan, a loop and a layer metric as new
files and new entries, and edits no file that is there:
shown on a temporary copy of the tree."""

import json
import os
import shutil
import subprocess
import sys

from conftest import ROOT

PLAN = '''"""Plan ``q1_planned_twice``: a later PR's plan, one more file."""
from benchmark.reference_q1 import (  # noqa: F401
    BINDING, LIMITS, TABLE, compare, control, min_bytes, oracle, read_answer)


def plan():
    from spark_rapids_jni_tpu.models import tpch

    return tpch._q1_planned_plan()
'''
LOOP = '''"""Loop ``closed_counted``: a later PR's loop, one more file."""
import time


def run(mix, seconds, request, stop_trace=None):
    t0 = time.perf_counter()
    for i in range(int(mix["requests"])):
        request(i)
        if stop_trace is not None:
            stop_trace(i + 1, time.perf_counter() - t0)
'''
METRIC = '''"""Layer metric ``session.requests``: a later PR's, one more file."""
LAYER = "client / session"
UNIT = "count"
SOURCE = "program_counter"
MOVES = "rows_per_s"
BETTER = "higher"


def read(run):
    return len(run.requests)
'''
DRIVE = '''
import json, sys
sys.path[:0] = [{tmp!r}, {root!r}]
from benchmark import harness, resolve
assert resolve.ROOT == {tmp!r}, resolve.ROOT
for trace in (False, True):
    r = harness.run_cell("sf1_q1_mixed_counted", 2**31 + 3, 1.0, trace,
                         platform="cpu", sizes={{"lineitem": 4096}})
    print(json.dumps(r))
'''


def test_a_cell_from_added_files_alone(tmp_path):
    tmp = str(tmp_path / "tree")
    os.makedirs(tmp)
    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    os.path.join(tmp, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    before = {}
    for d, _, files in os.walk(os.path.join(tmp, "benchmark")):
        for name in files:
            with open(os.path.join(d, name), "rb") as f:
                before[os.path.join(d, name)] = f.read()

    # what the later PR brings: entries, and files that were not there
    bench["workloads"].append({
        "name": "sf1_q1_mixed_counted", "config": "tpch_sf1_lineitem",
        "traffic": "q1_mixed_counted", "chips": 1, "why": "test"})
    bench["per_layer"].append({
        "name": "session.requests", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "client / session",
        "moves": "rows_per_s", "workloads": ["sf1_q1_mixed_counted"]})
    new = {
        "mixes/q1_mixed_counted.json": json.dumps({
            "loop": "closed_counted", "clients": 1, "requests": 5,
            "fresh": "roll", "plans": [
                {"plan": "q1_planned_twice", "weight": 4},
                {"plan": "q1_general", "weight": 1}]}),
        "plans/q1_planned_twice.py": PLAN,
        "loops/closed_counted.py": LOOP,
        "layer_metrics/session.requests.py": METRIC,
    }
    for rel, text in new.items():
        path = os.path.join(tmp, "benchmark", rel)
        assert not os.path.exists(path)
        with open(path, "w") as f:
            f.write(text)
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)

    proc = subprocess.run(
        [sys.executable, "-c", DRIVE.format(tmp=tmp, root=ROOT)],
        cwd=tmp, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    plain, traced = (json.loads(ln) for ln in proc.stdout.splitlines()
                     if ln.startswith("{"))
    assert plain["correct"] and plain["attempted"] == 5 and not plain["failed"]
    assert set(plain["metrics"]) == {"query_p50_s", "query_p95_s",
                                     "rows_per_s", "setup_s"}
    assert traced["correct"] and traced["attempted"] == 5
    assert traced["metrics"]["session.requests"] == {"value": 5,
                                                     "unit": "count"}
    # the old cells do not report the new cell's metric
    assert "device.idle_share" in traced["metrics"]
    # both plans were warmed up and served: four to one
    lines = [ln for ln in proc.stdout.splitlines() if "] warm-up " in ln]
    assert len(lines) == 4 and "q1_planned_twice" in lines[0]
    # and no file that was there changed
    for path, content in before.items():
        with open(path, "rb") as f:
            assert f.read() == content, path
