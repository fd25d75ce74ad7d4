"""What decides ``correct``: the reference against itself passes, the
control (the reference in the precision below) does not, and a run whose
timed path is broken underneath comes out as not correct."""

import numpy as np
import pytest

from conftest import TINY


def _cols(seed, rows=4096):
    import jax

    from benchmark import resolve

    maker = resolve.module("tables", "lineitem")
    arrays = maker.make(rows, seed)
    host = maker.host_copy(arrays)
    assert {k: v.dtype for k, v in host.items()} == {
        k: np.dtype(str(v.dtype)) for k, v in arrays.items()}
    for k in arrays:   # the narrowed copy holds the same values
        assert np.array_equal(host[k], jax.device_get(arrays[k]))
    return host


@pytest.mark.parametrize("seed", [3, 2**31 + 9, 77])
def test_reference_passes_and_control_does_not(seed):
    from benchmark import reference_q1 as ref

    cols = _cols(seed)
    want = ref.q1(cols)
    assert len(want) == 6
    assert ref.compare(ref.q1(cols, blocks=1), want) == {
        "q1.int_mismatches": 0, "q1.avg_max_rel_err": 0.0}
    control = ref.compare(ref.q1(cols, acc=np.float32), want)
    assert any(not control[n] <= ref.LIMITS[n] for n in ref.LIMITS)
    # the limit sits between sound runs (about 1e-14 on the chip) and this
    assert control["q1.avg_max_rel_err"] > 10 * ref.LIMITS["q1.avg_max_rel_err"]
    assert control["q1.int_mismatches"] > 0


@pytest.mark.parametrize("seed", [5, 6, 2**31 + 1])
def test_control_fails_every_cell(seed, bench):
    from benchmark import control, resolve

    for cell in bench["workloads"]:
        for plan, numbers in control.control_numbers(
                cell["name"], seed, platform="cpu", sizes=TINY).items():
            limits = resolve.module("plans", plan).LIMITS
            assert any(not numbers[n] <= limits[n] for n in limits), numbers


def test_reference_matches_the_programs_oracle():
    """The copy under ``benchmark/`` says what the original says."""
    from spark_rapids_jni_tpu.models import tpch

    from benchmark import reference_q1 as ref
    from benchmark import resolve

    maker = resolve.module("tables", "lineitem")
    arrays = maker.make(4096, 12)
    theirs = tpch.tpch_q1_numpy(maker.to_table(arrays))
    assert ref.compare(theirs, ref.q1(maker.host_copy(arrays))) == {
        "q1.int_mismatches": 0, "q1.avg_max_rel_err": 0.0}


def test_compare_counts_missing_groups():
    from benchmark import reference_q1 as ref

    want = ref.q1(_cols(1))
    got = dict(want)
    got.pop(next(iter(got)))
    numbers = ref.compare(got, want)
    assert numbers["q1.int_mismatches"] == 1
    assert numbers["q1.avg_max_rel_err"] == float("inf")


def _break_result(monkeypatch, how):
    """Alter what the served path hands back, where it is produced."""
    from spark_rapids_jni_tpu.columnar import Column, Table
    from spark_rapids_jni_tpu.runtime.server import QueryTicket
    from spark_rapids_jni_tpu.telemetry import REGISTRY

    served = QueryTicket.result

    def result(self, timeout=None):
        res = served(self, timeout)
        if how == "off_by_one":      # sum_qty of the first group, plus one
            cols = list(res.table.columns)
            c = cols[2]
            cols[2] = Column(c.dtype, c.data.at[0].add(1), c.validity)
            return res._replace(table=Table(cols))
        if how == "off_rung":
            self.rung = 1
        if how == "fallback_counter":
            REGISTRY.counter("degrade.step").inc()
        return res

    monkeypatch.setattr(QueryTicket, "result", result)


@pytest.mark.parametrize("how,why", [
    ("off_by_one", "q1.int_mismatches 1 over its limit 0"),
    ("off_rung", "finished at (tier, rung, steps) = ('fused', 1, 0)"),
    ("fallback_counter", "fallback counters moved: degrade.step +1"),
])
def test_broken_timed_path_is_not_correct(how, why, bench, run_tiny,
                                          monkeypatch):
    _break_result(monkeypatch, how)
    result, lines = run_tiny(bench["workloads"][0]["name"])
    assert result["correct"] is False
    assert result["attempted"] >= 1
    assert result["failed"] == result["attempted"]
    assert any("FAILED request" in ln and why in ln for ln in lines), lines


def test_request_that_raises_is_failed(bench, run_tiny, monkeypatch):
    from spark_rapids_jni_tpu.runtime.server import QueryRejected, QueryTicket

    def result(self, timeout=None):
        raise QueryRejected("no room")

    monkeypatch.setattr(QueryTicket, "result", result)
    result_, lines = run_tiny(bench["workloads"][0]["name"])
    assert result_["correct"] is False
    assert result_["failed"] == result_["attempted"] >= 1
    assert set(result_["metrics"]) == {"setup_s"}   # no latency is made up
    assert any("raised QueryRejected" in ln for ln in lines)
