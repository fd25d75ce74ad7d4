"""``BENCHMARK.json`` against the contract's limits and against the files
it names: what a cell needs is found by name and says the same thing in
both places."""

import json
import os
import re

from conftest import ROOT

from benchmark import resolve

NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}\Z")
UNIT = re.compile(r"[A-Za-z0-9_/%.\-]{1,16}\Z")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_keys_and_limits(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 << 10
    assert bench["paths"] == ["benchmark"]
    assert bench["command"] == ["python3", "benchmark/run.py"]
    assert isinstance(bench["run_seconds"], int)
    assert 1 <= bench["run_seconds"] <= 51
    cells = 24   # the check has to fit with every cell a later PR may add
    assert ((2 + 14 * cells) * (bench["run_seconds"] + 60) + cells * 2 * 90
            + 1200) <= 43200
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in bench[k]]
    assert all(NAME.match(n) for n in names)
    for kind in ("configs", "workloads"):
        ns = [x["name"] for x in bench[kind]]
        assert len(ns) == len(set(ns))
    metrics = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(metrics) == len(set(metrics))


def test_configs(bench):
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("benchmark/")
        with open(os.path.join(ROOT, c["file"])) as f:
            held = json.load(f)
        assert held["name"] == c["name"]
        assert held["reduced"] == c["reduced"] and len(c["reduced"]) <= 16
        assert set(held["reduced_why"]) == set(c["reduced"])
        assert all(NAME.match(k) for k in c["reduced"])
        for text in (c["source"], c["why"]):
            assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text
        for spec in held["tables"].values():
            maker = resolve.module("tables", spec["maker"])
            assert spec["row_bytes"] == maker.ROW_BYTES
            assert spec["bytes"] == spec["rows"] * maker.ROW_BYTES
        assert held["guarantees"]["options_set_by_the_benchmark"] == [
            "telemetry.enabled", "server.estimate_path", "rtfilter.path"]
    files = [c["file"] for c in bench["configs"]]
    assert len(files) == len(set(files))
    used = {w["config"] for w in bench["workloads"]}
    assert used == {c["name"] for c in bench["configs"]}


def test_workloads_resolve(bench):
    pairs = set()
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
        assert NAME.match(w["traffic"]) and NAME.match(w["config"])
        pairs.add((w["config"], w["traffic"]))
        cell, config, mix = resolve.cell(w["name"], bench)
        assert cell is w
        resolve.module("loops", mix["loop"])
        resolve.module("fresh", mix["fresh"])
        for p in mix["plans"]:
            plan = resolve.module("plans", p["plan"])
            assert plan.TABLE in config["tables"]
            assert plan.min_bytes(10) == 380
            assert set(plan.LIMITS) == {"q1.int_mismatches",
                                        "q1.avg_max_rel_err"}
    assert len(pairs) == len(bench["workloads"])
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four <= max(1, len(bench["workloads"]) // 2)


def test_metrics_say_what_their_readers_say(bench):
    ends = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in ends and ends["setup_s"]["bound"] <= 0.25
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in {"host_clock", "device_trace"}
        assert UNIT.match(m["unit"]) and m["better"] in {"lower", "higher"}
    layers = set()
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        reader = resolve.module("layer_metrics", m["name"])
        assert (m["unit"], m["better"], m["source"], m["layer"],
                m["moves"]) == (reader.UNIT, reader.BETTER, reader.SOURCE,
                                reader.LAYER, reader.MOVES)
        assert m["source"] in SOURCES and m["moves"] in ends
        assert UNIT.match(m["unit"]) and 1 <= len(m["layer"]) <= 200
        layers.add(m["layer"])
    # every reader under layer_metrics/ is declared
    on_disk = {f[:-3] for f in os.listdir(
        os.path.join(resolve.HERE, "layer_metrics")) if f.endswith(".py")}
    assert on_disk == {m["name"] for m in bench["per_layer"]}
    # PERF.md's list of layers has each layer under the same name
    with open(os.path.join(ROOT, "PERF.md")) as f:
        perf = f.read()
    for layer in layers:
        assert f"| {layer} |" in perf, layer


def test_peaks_have_sources():
    with open(os.path.join(resolve.HERE, "peaks.json")) as f:
        peaks = json.load(f)
    assert peaks["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9
    assert all(row["source"] for row in peaks.values())


def test_unknown_names_are_errors(bench):
    import pytest

    with pytest.raises(LookupError, match="no workload"):
        resolve.cell("no_such_cell", bench)
    with pytest.raises(LookupError, match="no plans named"):
        resolve.module("plans", "q99")
    with pytest.raises(LookupError, match="not a benchmark name"):
        resolve.module("plans", "../harness")


def test_unknown_device_kind_is_an_error():
    import pytest

    from benchmark import harness

    assert harness._peaks("TPU v5 lite", "tpu")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(harness.BenchFailure, match="not in benchmark/peaks"):
        harness._peaks("TPU v9 imaginary", "tpu")
