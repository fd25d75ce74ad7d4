"""Execution telemetry & fallback accounting (spark_rapids_jni_tpu/telemetry).

Four layers under test:

1. **Registry semantics** — counters/gauges/bounded histograms are pure
   stdlib and always usable (no option flip needed).
2. **JSONL event schema** — with ``telemetry.enabled`` + ``telemetry.path``
   set, every record parses, carries kind/ts/platform, and fallback/spill
   records carry a non-empty ``reason`` (mandatory even when disabled).
3. **Instrumented seams** — the regex NUL byteset, unsupported-atom,
   force_engine pin, cast-strings host assembly, compile caches and the
   SpillStore all emit events with the reasons the ISSUE requires.
4. **Report CLI** — ``python -m spark_rapids_jni_tpu.telemetry report``
   renders the per-op device/host table from a golden ledger.
"""

import json
import os

import pytest

from spark_rapids_jni_tpu import telemetry
from spark_rapids_jni_tpu import types as t
from spark_rapids_jni_tpu.columnar import Column
from spark_rapids_jni_tpu.telemetry.registry import (
    Counter,
    Gauge,
    Histogram,
    Registry,
)
from spark_rapids_jni_tpu.utils import config


@pytest.fixture(autouse=True)
def _reset():
    telemetry.drain()
    telemetry.REGISTRY.reset()
    yield
    telemetry.drain()
    telemetry.REGISTRY.reset()
    for name in list(config._overrides):
        config.reset_option(name)


@pytest.fixture
def enabled(tmp_path):
    path = tmp_path / "run.jsonl"
    config.set_option("telemetry.enabled", True)
    config.set_option("telemetry.path", str(path))
    return path


# ---------------------------------------------------------------------------
# registry semantics
# ---------------------------------------------------------------------------


def test_counter_monotonic_and_negative_rejected():
    c = Counter("x")
    c.inc()
    c.inc(4)
    assert c.value == 5
    with pytest.raises(ValueError):
        c.inc(-1)
    assert c.value == 5


def test_gauge_set_add():
    g = Gauge("staged_bytes")
    g.set(10)
    g.add(-4)
    assert g.value == 6.0


def test_histogram_buckets_and_percentiles():
    h = Histogram("wall", bounds=(1.0, 10.0, 100.0))
    for v in (0.5, 0.5, 5.0, 50.0, 500.0):  # last lands in overflow
        h.observe(v)
    assert h.count == 5
    assert h.sum == pytest.approx(556.0)
    snap = h.snapshot()
    assert snap["counts"] == [2, 1, 1, 1]
    assert snap["max"] == 500.0
    # percentiles are bucket-interpolated estimates: monotone, bounded
    p50, p95 = h.percentile(50.0), h.percentile(95.0)
    assert 0.0 < p50 <= 10.0
    assert p50 <= p95 <= 500.0
    with pytest.raises(ValueError):
        h.percentile(101.0)


def test_histogram_rejects_unsorted_bounds():
    with pytest.raises(ValueError):
        Histogram("bad", bounds=(10.0, 1.0))


def test_registry_create_on_first_use_and_prefix():
    r = Registry()
    r.counter("fallback.regexp_contains").inc()
    r.counter("fallback.regexp_contains").inc()
    r.counter("dispatch.sort").inc()
    assert r.counter("fallback.regexp_contains").value == 2
    assert r.counters("fallback.") == {"fallback.regexp_contains": 2}
    snap = r.snapshot()
    assert snap["counters"]["dispatch.sort"] == 1
    r.reset()
    assert r.counters() == {}


# ---------------------------------------------------------------------------
# event schema + config round trip
# ---------------------------------------------------------------------------


def test_disabled_records_nothing_by_default():
    assert config.get_option("telemetry.enabled") is False
    assert telemetry.record_dispatch("op") is False
    assert telemetry.events() == []


def test_fallback_reason_mandatory_even_when_disabled():
    assert config.get_option("telemetry.enabled") is False
    with pytest.raises(ValueError):
        telemetry.record_fallback("op", "")
    with pytest.raises(ValueError):
        telemetry.record_fallback("op", "   ")
    with pytest.raises(ValueError):
        telemetry.record_spill("op", "", bytes_moved=1)


def test_jsonl_schema(enabled):
    telemetry.record_dispatch(
        "sort", rows=128, dtype_widths=[8, 4], wall_ms=1.5)
    telemetry.record_fallback("regexp_contains", "unsupported atom", rows=3)
    telemetry.record_compile_cache("regex_dfa", hit=False)
    telemetry.record_spill(
        "spill_store", "budget exceeded", bytes_moved=4096, rows=10)
    lines = enabled.read_text().splitlines()
    assert len(lines) == 4
    recs = [json.loads(ln) for ln in lines]  # every line parses
    for rec in recs:
        assert rec["kind"] in (
            "dispatch", "fallback", "compile_cache", "spill")
        assert rec["op"]
        assert isinstance(rec["ts"], float)
        assert isinstance(rec["platform"], str)
        if rec["kind"] in ("fallback", "spill"):
            assert rec["reason"].strip()
    by_kind = {r["kind"]: r for r in recs}
    assert by_kind["dispatch"]["rows"] == 128
    assert by_kind["dispatch"]["dtype_widths"] == [8, 4]
    assert by_kind["dispatch"]["wall_ms"] == 1.5
    assert by_kind["fallback"]["engine"] == "host"
    assert by_kind["spill"]["bytes_moved"] == 4096
    # the ring mirrors the file
    assert [r["kind"] for r in telemetry.events()] == [r["kind"] for r in recs]
    # registry counters track the event stream
    assert telemetry.REGISTRY.counter("fallbacks_total").value == 1
    assert telemetry.REGISTRY.counter("events_total").value == 4
    # the kind a bench.py that is gone wrote has gone with it
    assert not hasattr(telemetry, "record_bench_stale")
    assert "stale_reads" not in telemetry.summary()


def test_env_round_trip(monkeypatch, tmp_path):
    """Satellite: SPARK_RAPIDS_TPU_TELEMETRY_* env vars drive the options."""
    p = tmp_path / "env.jsonl"
    monkeypatch.setenv("SPARK_RAPIDS_TPU_TELEMETRY_ENABLED", "1")
    monkeypatch.setenv("SPARK_RAPIDS_TPU_TELEMETRY_PATH", str(p))
    assert config.get_option("telemetry.enabled") is True
    assert config.get_option("telemetry.path") == str(p)
    assert telemetry.enabled() is True
    telemetry.record_dispatch("env_op", rows=1)
    assert json.loads(p.read_text())["op"] == "env_op"
    monkeypatch.setenv("SPARK_RAPIDS_TPU_TELEMETRY_ENABLED", "off")
    assert telemetry.enabled() is False


def test_sink_io_failure_never_raises(tmp_path):
    config.set_option("telemetry.enabled", True)
    config.set_option("telemetry.path", str(tmp_path))  # a directory: open fails
    assert telemetry.record_dispatch("op") is True
    assert telemetry.REGISTRY.counter("dropped_writes").value == 1


def test_summary_counts(enabled):
    telemetry.record_dispatch("sort", wall_ms=2.0)
    telemetry.record_fallback("regexp_contains", "r1")
    telemetry.record_fallback("regexp_contains", "r2")
    telemetry.record_spill("spill_store", "lru", bytes_moved=100)
    telemetry.record_compile_cache("regex_dfa", hit=True)
    s = telemetry.summary()
    assert s["events"] == 5
    assert s["dispatches"] == 1
    assert s["fallbacks"] == {"regexp_contains": 2}
    assert s["fallbacks_total"] == 2
    assert s["spill_bytes_total"] == 100
    assert s["compile_cache"] == {"hit": 1, "miss": 0}


# ---------------------------------------------------------------------------
# instrumented seams: every fallback path emits a non-empty reason
# ---------------------------------------------------------------------------


def _fallbacks(op=None):
    recs = [r for r in telemetry.events() if r["kind"] == "fallback"]
    return [r for r in recs if op is None or r["op"] == op]


def test_regex_nul_byteset_fallback(enabled):
    from spark_rapids_jni_tpu.ops import strings as s

    col = Column.from_pylist(["a\x00b", "plain"], t.STRING)
    got = s.regexp_contains(col, r"a").to_pylist()
    assert got == [True, True]
    fbs = _fallbacks("regexp_contains")
    assert len(fbs) == 1
    assert "NUL" in fbs[0]["reason"]
    assert fbs[0]["rows"] == 2


def test_regex_unsupported_atom_fallback(enabled):
    from spark_rapids_jni_tpu.ops import strings as s

    col = Column.from_pylist(["abab", "xy"], t.STRING)
    got = s.regexp_contains(col, r"(ab)\1").to_pylist()  # backref: host only
    assert got == [True, False]
    fbs = _fallbacks("regexp_contains")
    assert len(fbs) == 1
    assert "unsupported regex atom" in fbs[0]["reason"]


def test_regex_force_host_pin_fallback(enabled):
    from spark_rapids_jni_tpu.ops import strings as s

    config.set_option("regex.force_engine", "host")
    col = Column.from_pylist(["a1"], t.STRING)
    assert s.regexp_contains(col, r"\d").to_pylist() == [True]
    fbs = _fallbacks("regexp_contains")
    assert len(fbs) == 1
    assert "force_engine=host" in fbs[0]["reason"]


def test_cast_strings_host_assembly_fallback(enabled):
    from spark_rapids_jni_tpu.ops.cast_strings import integer_to_string

    col = Column.from_pylist([1, -22, None], t.INT64)
    assert integer_to_string(col).to_pylist() == ["1", "-22", None]
    fbs = _fallbacks("integer_to_string")
    assert len(fbs) == 1
    assert "host-side Arrow string assembly" in fbs[0]["reason"]


def test_compile_cache_hit_miss_events(enabled):
    from spark_rapids_jni_tpu.ops import regex_device as rd

    rd._compile_pattern_cached.cache_clear()
    rd.compile_pattern(r"zq[0-9]+x")   # miss
    rd.compile_pattern(r"zq[0-9]+x")   # hit
    recs = [r for r in telemetry.events()
            if r["kind"] == "compile_cache" and r["op"] == "regex_dfa"]
    assert [r["hit"] for r in recs] == [False, True]


def test_spill_store_emits_spill_events(enabled):
    from spark_rapids_jni_tpu.columnar import Table
    from spark_rapids_jni_tpu.runtime.memory import SpillStore, _table_nbytes

    tbl = Table([Column.from_pylist(list(range(256)), t.INT64)])
    store = SpillStore(_table_nbytes(tbl) + 8)  # room for exactly one
    h1 = store.put(tbl)
    store.put(Table([Column.from_pylist(list(range(256)), t.INT64)]))
    spills = [r for r in telemetry.events() if r["kind"] == "spill"]
    assert len(spills) == 1
    assert spills[0]["direction"] == "device_to_host"
    assert spills[0]["bytes_moved"] == _table_nbytes(tbl)
    assert spills[0]["reason"].strip()
    store.get(h1)  # staging back emits the mirror event
    spills = [r for r in telemetry.events() if r["kind"] == "spill"]
    assert [s["direction"] for s in spills] == [
        "device_to_host", "device_to_host", "host_to_device"]


def test_outofcore_spill_fallback(enabled):
    from spark_rapids_jni_tpu.columnar import Table
    from spark_rapids_jni_tpu.runtime.memory import MemoryLimiter, _table_nbytes
    from spark_rapids_jni_tpu.runtime.outofcore import run_chunked_aggregate

    chunks = [Table([Column.from_pylist(list(range(128)), t.INT64)])
              for _ in range(2)]
    nb = _table_nbytes(chunks[0])
    out = run_chunked_aggregate(
        chunks, lambda tb: tb, lambda tb: tb,
        limiter=MemoryLimiter(10 * nb),
        spill_budget_bytes=nb + 8,  # room for one partial: second one spills
    )
    assert out.spill_stats["spills"] >= 1
    fbs = _fallbacks("run_chunked_aggregate")
    assert len(fbs) == 1
    assert "spill budget" in fbs[0]["reason"]
    # SpillStore's own per-table byte accounting rides alongside
    spills = [r for r in telemetry.events() if r["kind"] == "spill"]
    assert spills and all(r["reason"].strip() for r in spills)


def test_shuffle_flag_accounting_at_jit_boundary(enabled):
    import numpy as np

    from spark_rapids_jni_tpu.parallel.shuffle import report_shuffle_telemetry

    report_shuffle_telemetry(
        overflowed=np.array(False), narrowing_overflow=np.array(False),
        rows=8)
    report_shuffle_telemetry(
        overflowed=np.array(True), narrowing_overflow=np.array(True),
        rows=8)
    kinds = [r["kind"] for r in telemetry.events()]
    assert kinds == ["dispatch", "fallback", "fallback"]
    fbs = _fallbacks("hash_shuffle")
    assert any("capacity overflow" in r["reason"] for r in fbs)
    assert any("narrowing overflow" in r["reason"] for r in fbs)


def test_trace_range_record_emits_timed_dispatch(enabled):
    from spark_rapids_jni_tpu.utils.tracing import trace_range

    with trace_range("unit_op", record=True):
        pass
    recs = [r for r in telemetry.events() if r["kind"] == "dispatch"]
    assert len(recs) == 1
    assert recs[0]["op"] == "unit_op"
    assert recs[0]["wall_ms"] >= 0.0


# ---------------------------------------------------------------------------
# report CLI on a golden ledger
# ---------------------------------------------------------------------------

_GOLDEN = [
    {"kind": "dispatch", "op": "regexp_contains", "wall_ms": 2.0},
    {"kind": "dispatch", "op": "regexp_contains", "wall_ms": 4.0},
    {"kind": "dispatch", "op": "regexp_contains", "wall_ms": 6.0},
    {"kind": "fallback", "op": "regexp_contains",
     "reason": "embedded NUL bytes alias the 0x00 padding sentinel"},
    {"kind": "spill", "op": "spill_store",
     "reason": "device spill budget exceeded: LRU eviction to host",
     "bytes_moved": 2048},
    {"kind": "compile_cache", "op": "regex_dfa", "hit": True},
]


def _write_golden(tmp_path):
    p = tmp_path / "golden.jsonl"
    lines = [json.dumps(r) for r in _GOLDEN]
    lines.insert(2, "{torn line that never finished writ")  # must be skipped
    p.write_text("\n".join(lines) + "\n")
    return p


def test_report_aggregate_golden(tmp_path):
    from spark_rapids_jni_tpu.telemetry.report import aggregate, load_jsonl

    per_op = aggregate(load_jsonl(str(_write_golden(tmp_path))))
    rc = per_op["regexp_contains"]
    # 3 calls, 1 of which fell back: 2 device / 1 host
    assert (rc["calls"], rc["device"], rc["host"]) == (3, 2, 1)
    assert rc["p50_ms"] == 4.0
    assert rc["p95_ms"] == 6.0
    assert per_op["spill_store"]["bytes_moved"] == 2048


def test_report_cli_renders_table(tmp_path, capsys):
    from spark_rapids_jni_tpu.telemetry.__main__ import main

    rc = main(["report", str(_write_golden(tmp_path))])
    out = capsys.readouterr().out
    assert rc == 0
    assert "regexp_contains" in out
    assert "device" in out and "host" in out
    assert "TOTAL" in out
    assert "embedded NUL bytes" in out  # reasons section
    assert "2.0KiB" in out


def test_report_cli_errors(tmp_path, capsys):
    from spark_rapids_jni_tpu.telemetry.__main__ import main

    assert main(["report", str(tmp_path / "missing.jsonl")]) == 1
    assert main(["not-a-command"]) == 2
    assert main([]) == 2


# ---------------------------------------------------------------------------
# session attribution (multi-query serving)
# ---------------------------------------------------------------------------


def test_session_scope_stamps_events(enabled):
    with telemetry.session_scope("tenant-a"):
        assert telemetry.current_session() == "tenant-a"
        telemetry.record_fallback("regexp", "scoped probe")
        with telemetry.session_scope("tenant-b"):  # shadow-nests
            telemetry.record_fallback("regexp", "inner probe")
        telemetry.record_fallback("regexp", "outer again")
    assert telemetry.current_session() is None
    telemetry.record_fallback("regexp", "unscoped")
    sids = [r.get("session") for r in telemetry.events()
            if r["kind"] == "fallback"]
    assert sids == ["tenant-a", "tenant-b", "tenant-a", None]


def test_session_scope_rejects_empty_id():
    with pytest.raises(ValueError):
        with telemetry.session_scope(""):
            pass


def test_record_server_event_schema(enabled):
    telemetry.record_server("tpch_q1", "served", session="s1",
                            rows=100, wall_ms=1.5)
    (rec,) = [r for r in telemetry.events() if r["kind"] == "server"]
    assert rec["event"] == "served"
    assert rec["session"] == "s1"
    assert rec["rows"] == 100
    # record_server does NOT touch counters: the serving runtime owns
    # server.* accounting unconditionally (admission must hold with
    # telemetry off), so a counter here would double-count
    assert telemetry.REGISTRY.counters("server.") == {}
    summary = telemetry.summary()
    assert summary["server"] == {"served": 1}


def test_record_server_session_mandatory_even_when_disabled():
    # disabled-path validation, same contract as record_fallback's reason
    with pytest.raises(ValueError):
        telemetry.record_server("tpch_q1", "served", session="")


# ---------------------------------------------------------------------------
# fleet events & replica attribution (runtime/fleet.py's contract)
# ---------------------------------------------------------------------------


def test_record_fleet_event_schema(enabled):
    telemetry.record_fleet("fleet.supervise", "replica_death",
                           replica="r0", error_kind="ReplicaDeadError")
    (rec,) = [r for r in telemetry.events() if r["kind"] == "fleet"]
    assert rec["event"] == "replica_death"
    assert rec["replica"] == "r0"
    assert rec["error_kind"] == "ReplicaDeadError"
    # the supervisor owns fleet.* counters unconditionally; the recorder
    # must not double-count (same contract as record_server)
    assert telemetry.REGISTRY.counters("fleet.") == {}
    assert telemetry.summary()["fleet"] == {"replica_death": 1}


def test_record_fleet_replica_mandatory_even_when_disabled():
    with pytest.raises(ValueError):
        telemetry.record_fleet("fleet.supervise", "boot", replica="")
    with pytest.raises(ValueError):
        telemetry.record_fleet("fleet.supervise", "boot", replica="r0",
                               kind="smuggled")


def test_replica_option_stamps_every_record(enabled):
    config.set_option("telemetry.replica", "r7")
    telemetry.record_server("tpch_q1", "served", session="s1")
    telemetry.record_spill("spill", nbytes=10, tier="host", reason="x")
    for rec in telemetry.events():
        assert rec["replica"] == "r7", rec


def test_two_process_shared_sink_no_torn_lines(tmp_path):
    """N replica processes appending to ONE JSONL path concurrently: every
    record lands as a single O_APPEND write(2), so a reader must see
    exactly writers x records parseable lines, each stamped with its
    writer's replica id — never two lines torn into each other."""
    import subprocess
    import sys

    path = tmp_path / "shared.jsonl"
    per_writer = 400
    code = (
        "import sys\n"
        "from spark_rapids_jni_tpu import telemetry\n"
        "for i in range(%d):\n"
        "    telemetry.record_server('tpch_q1', 'served',\n"
        "                            session='s%%d' %% i, rows=i)\n"
        % per_writer)
    procs = []
    for rid in ("r0", "r1"):
        env = dict(os.environ)
        env.update({
            "SPARK_RAPIDS_TPU_TELEMETRY_ENABLED": "1",
            "SPARK_RAPIDS_TPU_TELEMETRY_PATH": str(path),
            "SPARK_RAPIDS_TPU_TELEMETRY_REPLICA": rid,
        })
        procs.append(subprocess.Popen([sys.executable, "-c", code],
                                      env=env))
    for p in procs:
        assert p.wait(timeout=120) == 0
    lines = path.read_text().splitlines()
    assert len(lines) == 2 * per_writer
    by_replica = {}
    for line in lines:
        rec = json.loads(line)  # a torn line would fail to parse
        by_replica[rec["replica"]] = by_replica.get(rec["replica"], 0) + 1
    assert by_replica == {"r0": per_writer, "r1": per_writer}
