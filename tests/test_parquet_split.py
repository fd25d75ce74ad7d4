"""A table that arrives as a file: ``parquet.split.ParquetSplit`` bound to a
``Plan`` and served by ``QueryServer`` (ISSUE 34).

The file is written by pyarrow (snappy, at least three row groups, columns
in an order that is not the plan's, two columns the plan never reads); the
reader, the footer prune and filter, the admission, the staging and the
cache key under test are the program's.
"""

import os
import sys

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from spark_rapids_jni_tpu import telemetry
from spark_rapids_jni_tpu import types as t
from spark_rapids_jni_tpu.columnar import Column, Table
from spark_rapids_jni_tpu.models import tpch
from spark_rapids_jni_tpu.parquet import ParquetFooter, ParquetSplit
from spark_rapids_jni_tpu.parquet.footer import MalformedFileError
from spark_rapids_jni_tpu.parquet.split import _footer_bytes
from spark_rapids_jni_tpu.runtime import dispatch, pipeline, resultcache
from spark_rapids_jni_tpu.runtime.resilience import MalformedInputError
from spark_rapids_jni_tpu.runtime.server import QueryRejected, QueryServer
from spark_rapids_jni_tpu.telemetry import REGISTRY
from spark_rapids_jni_tpu.telemetry.events import drain as drain_events
from spark_rapids_jni_tpu.utils.config import reset_option, set_option

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

# the order the plan's scan expects; the file holds them in another
READ = ("l_quantity", "l_extendedprice", "l_discount", "l_tax",
        "l_returnflag", "l_linestatus", "l_shipdate")
FILE_ORDER = ("l_shipdate", "l_orderkey", "l_linestatus", "l_tax",
              "l_quantity", "l_comment", "l_returnflag", "l_extendedprice",
              "l_discount")
DTYPES = [t.decimal64(-2)] * 4 + [t.INT8, t.INT8, t.TIMESTAMP_DAYS]


@pytest.fixture(autouse=True)
def _isolated():
    dispatch.clear()
    REGISTRY.reset()
    drain_events()
    set_option("telemetry.enabled", True)
    yield
    reset_option("telemetry.enabled")
    dispatch.clear()


def _columns(rows: int, seed: int, nulls: bool):
    """{name: (values, mask of nulls or None)} of the seven q1 columns."""
    li = tpch.lineitem_table(rows, seed=seed)
    rng = np.random.default_rng(seed)
    out = {}
    for i, name in enumerate(READ):
        mask = None
        # the value columns and the filter's: q1's model takes its flag
        # columns as NOT NULL (``tpch._q1_work_table`` reads a key's bytes
        # whatever its validity, and the decoder zero-fills a null)
        if nulls and name in ("l_quantity", "l_discount", "l_shipdate"):
            mask = rng.random(rows) < 0.07
        out[name] = (np.asarray(li.column(i).data), mask)
    return out


def _write(path, cols: dict, rows: int, groups: int = 3) -> None:
    def money(v, mask):   # DECIMAL(15,2) stored as INT64, as Spark writes it
        arr = pa.Array.from_buffers(
            pa.decimal128(15, 2), rows,
            [None, pa.py_buffer(np.stack([v, v >> 63], axis=1))])
        if mask is None:
            return arr
        return pa.array(arr.to_pylist(), type=pa.decimal128(15, 2),
                        mask=mask)

    arrays = {}
    for name, (v, mask) in cols.items():
        if name == "l_shipdate":
            arrays[name] = pa.array(v, mask=mask).cast(pa.date32())
        elif v.dtype == np.int64:
            arrays[name] = money(v, mask)
        else:
            arrays[name] = pa.array(v, mask=mask)
    arrays["l_orderkey"] = pa.array(np.arange(rows, dtype=np.int64))
    arrays["l_comment"] = pa.array([f"row {i}" for i in range(rows)])
    pq.write_table(pa.table({n: arrays[n] for n in FILE_ORDER}), str(path),
                   row_group_size=-(-rows // groups), compression="snappy",
                   store_decimal_as_integer=True)


def _table(cols: dict) -> Table:
    import jax.numpy as jnp

    return Table([
        Column(d, jnp.asarray(cols[n][0]),
               None if cols[n][1] is None else jnp.asarray(~cols[n][1]))
        for d, n in zip(DTYPES, READ)])


def _buffers(table) -> list:
    return [None if b is None else np.asarray(b)
            for c in table.columns for b in (c.data, c.validity)]


def _moved(before: dict, prefix: str) -> dict:
    after = REGISTRY.counters()
    return {k: v - before.get(k, 0) for k, v in after.items()
            if k.startswith(prefix) and v != before.get(k, 0)}


@pytest.fixture
def lineitem(tmp_path):
    cols = _columns(6000, seed=5, nulls=False)
    path = tmp_path / "lineitem.parquet"
    _write(path, cols, 6000)
    return path, cols


# -- the served path ---------------------------------------------------------


@pytest.mark.parametrize("rows,nulls", [(4096, False), (4096, True),
                                        (6000, False), (6000, True)])
def test_served_q1_from_a_split_equals_the_table_bound_result(
        tmp_path, rows, nulls):
    from benchmark import reference_q1

    cols = _columns(rows, seed=rows + nulls, nulls=nulls)
    path = tmp_path / "li.parquet"
    _write(path, cols, rows)
    assert pq.ParquetFile(str(path)).metadata.num_row_groups >= 3
    plan = tpch._q1_planned_plan()
    with QueryServer(budget_bytes=1 << 30) as srv:
        session = srv.session("scan")
        before = REGISTRY.counters()
        ticket = session.submit(plan, {"lineitem": ParquetSplit(path, READ)})
        from_file = ticket.result(timeout=120)
        assert (ticket.status, ticket.tier, ticket.rung, ticket.steps) == (
            "served", "fused", 0, 0)
        moved = _moved(before, "")
        assert moved["scan.row_groups"] == 3
        assert moved["scan.columns_read"] == 7
        assert moved["scan.columns_pruned"] == 2
        assert moved["scan.decoded_bytes"] == 38 * rows + 3 * rows * nulls
        assert moved["cache.miss"] == 1 and "cache.fingerprint_bytes" not in moved
        assert not [k for k in moved if k.startswith(
            ("fallback.", "degrade.", "resilience.rung."))]
        bound = session.submit(plan, {"lineitem": _table(cols)})
        from_table = bound.result(timeout=120)
    for got, want in zip(_buffers(from_file.table), _buffers(from_table.table)):
        assert (got is None) == (want is None)
        if got is not None:
            assert got.dtype == want.dtype and np.array_equal(got, want)
    if not nulls:
        numbers = reference_q1.compare(
            reference_q1.read_answer(from_file.table),
            reference_q1.oracle({n: v for n, (v, _) in cols.items()}))
        assert numbers["q1.int_mismatches"] == 0
        assert numbers["q1.avg_max_rel_err"] <= 1e-9
    assert srv.limiter.used == 0


def test_projection_is_by_name_in_the_plans_order(lineitem):
    path, cols = lineitem
    scan = ParquetSplit(path, READ).resolve()
    # the file's leaf indices of the read schema, in the REQUEST's order
    assert scan.leaves == tuple(FILE_ORDER.index(n) for n in READ)
    assert scan.leaves != tuple(sorted(scan.leaves))
    assert scan.dtypes == tuple(DTYPES)
    assert scan.num_rows == 6000 and scan.columns_pruned == 2
    # every read column is optional in the file: a validity byte a row is
    # admitted beside the 38 bytes of data
    assert scan.nbytes == 6000 * (38 + 7)
    table = scan.stage(pipeline.shared_decode_pool())
    for i, name in enumerate(READ):
        assert np.array_equal(np.asarray(table.column(i).data), cols[name][0])
        assert table.column(i).validity is None


def test_a_narrower_file_type_is_read_as_the_plans(tmp_path):
    rows = 3000
    values = np.arange(rows, dtype=np.int32)
    pq.write_table(pa.table({"v": pa.array(values)}), str(tmp_path / "f"),
                   row_group_size=1000)
    split = ParquetSplit(tmp_path / "f", ("v",), dtypes=(t.decimal64(-2),))
    scan = split.resolve()
    assert scan.dtypes == (t.decimal64(-2),) and len(scan.row_groups) == 3
    col = scan.stage(pipeline.shared_decode_pool()).column(0)
    assert col.dtype == t.decimal64(-2) and col.data.dtype == np.int64
    assert np.array_equal(np.asarray(col.data), values.astype(np.int64))


def test_a_string_column_in_the_read_schema_fails_the_request(lineitem):
    path, _ = lineitem
    with pytest.raises(NotImplementedError, match="l_comment"):
        ParquetSplit(path, ("l_comment", "l_orderkey")).resolve()
    with QueryServer(budget_bytes=1 << 30) as srv:
        ticket = srv.session("scan").submit(
            tpch._q1_planned_plan(),
            {"lineitem": ParquetSplit(path, READ[:6] + ("l_comment",))})
        with pytest.raises(NotImplementedError):
            ticket.result(timeout=60)
        assert ticket.status == "failed"
    assert srv.limiter.used == 0


def test_a_missing_name_fails_classified_and_the_server_keeps_serving(
        lineitem):
    path, _ = lineitem
    plan = tpch._q1_planned_plan()
    with QueryServer(budget_bytes=1 << 30) as srv:
        session = srv.session("scan")
        names = READ[:3] + ("l_nope",) + READ[4:]
        ticket = session.submit(plan, {"lineitem": ParquetSplit(path, names)})
        with pytest.raises(MalformedInputError, match="l_nope"):
            ticket.result(timeout=60)
        assert ticket.status == "failed"
        assert not isinstance(ticket._exc, KeyError)
        assert REGISTRY.counters()["integrity.malformed_rejects"] == 1
        assert "scan.row_groups" not in REGISTRY.counters()
        ok = session.submit(plan, {"lineitem": ParquetSplit(path, READ)})
        assert ok.result(timeout=120) is not None and ok.status == "served"
    assert srv.limiter.used == 0


def test_two_half_file_splits_select_disjoint_row_groups(lineitem):
    path, _ = lineitem
    size = os.path.getsize(path)
    whole = ParquetSplit(path, READ, 0, size).resolve()
    assert whole.row_groups == ParquetSplit(path, READ).resolve().row_groups
    assert [g for g, _ in whole.row_groups] == [0, 1, 2]
    cut = size // 2
    first = ParquetSplit(path, READ, 0, cut).resolve()
    second = ParquetSplit(path, READ, cut, size - cut).resolve()
    assert not set(first.row_groups) & set(second.row_groups)
    assert first.row_groups + second.row_groups == whole.row_groups
    assert first.row_groups and second.row_groups
    assert first.num_rows + second.num_rows == 6000
    # read_and_filter's own rule, asked directly
    for split, scan in ((ParquetSplit(path, READ, 0, cut), first),
                        (ParquetSplit(path, READ, cut, size - cut), second)):
        with ParquetFooter.read_and_filter(
                _footer_bytes(str(path)), split.part_offset,
                split.part_length, list(READ), [0] * 7, 7) as footer:
            assert footer.num_rows == scan.num_rows
            assert footer.num_columns == 7
            assert tuple(footer.row_groups()) == scan.row_groups
    # the second half's rows are the file's last rows
    table = second.stage(pipeline.shared_decode_pool())
    assert table.num_rows == second.num_rows


# -- the cache key -----------------------------------------------------------


def test_the_same_source_twice_is_a_hit_that_decodes_nothing(lineitem):
    path, cols = lineitem
    plan = tpch._q1_planned_plan()
    with QueryServer(budget_bytes=1 << 30) as srv:
        session = srv.session("scan")
        first = session.submit(plan, {"lineitem": ParquetSplit(path, READ)})
        result = first.result(timeout=120)
        before = REGISTRY.counters()
        again = session.submit(plan, {"lineitem": ParquetSplit(path, READ)})
        for got, want in zip(_buffers(again.result(timeout=60).table),
                             _buffers(result.table)):
            assert np.array_equal(got, want)
        assert _moved(before, "cache.") == {"cache.hit": 1}
        assert _moved(before, "scan.") == {}
        assert again.tier is None   # a hit never executes

        # another projection of the same file: a miss
        before = REGISTRY.counters()
        other = ParquetSplit(path, READ[:4] + ("l_linestatus", "l_returnflag",
                                               "l_shipdate"))
        session.submit(plan, {"lineitem": other}).result(timeout=120)
        assert _moved(before, "cache.")["cache.miss"] == 1
        assert _moved(before, "scan.")["scan.row_groups"] == 3

        # another byte range: a miss
        before = REGISTRY.counters()
        size = os.path.getsize(path)
        session.submit(plan, {"lineitem": ParquetSplit(
            path, READ, 0, size // 2)}).result(timeout=120)
        assert _moved(before, "cache.")["cache.miss"] == 1

        # the file rewritten under the same path: a miss, and no byte of
        # any file was ever digested
        stat = os.stat(path)
        _write(path, cols, 6000)
        os.utime(path, ns=(stat.st_atime_ns, stat.st_mtime_ns + 10**9))
        before = REGISTRY.counters()
        session.submit(plan, {"lineitem": ParquetSplit(path, READ)}).result(
            timeout=120)
        assert _moved(before, "cache.")["cache.miss"] == 1
        assert _moved(before, "scan.")["scan.row_groups"] == 3
    assert "cache.fingerprint_bytes" not in REGISTRY.counters()


def test_the_key_is_the_sources_not_the_contents(lineitem, tmp_path):
    path, _ = lineitem
    split = ParquetSplit(path, READ)
    key = resultcache.input_fingerprint({"lineitem": split})
    assert key == resultcache.input_fingerprint({"lineitem": split.resolve()})
    link = tmp_path / "another-name.parquet"
    os.link(path, link)
    assert key != resultcache.input_fingerprint(
        {"lineitem": ParquetSplit(link, READ)})
    assert key != resultcache.input_fingerprint(
        {"lineitem": ParquetSplit(path, READ, 0, 100)})
    assert key != resultcache.input_fingerprint(
        {"lineitem": ParquetSplit(path, READ[::-1])})


# -- admission ---------------------------------------------------------------


def test_admission_is_on_the_footers_bytes(lineitem):
    path, _ = lineitem
    plan = tpch._q1_planned_plan()
    scan = ParquetSplit(path, READ).resolve()
    with QueryServer(budget_bytes=1 << 30, estimate_headroom=1.5) as srv:
        ticket = srv.session("scan").submit(
            plan, {"lineitem": ParquetSplit(path, READ)})
        ticket.result(timeout=120)
        assert ticket.estimate == int(1.5 * scan.nbytes)
        assert isinstance(ticket.bindings["lineitem"], type(scan))
    assert srv.limiter.used == 0 and srv.limiter.peak >= ticket.estimate


def test_an_oversize_split_is_rejected_with_nothing_decoded(lineitem):
    path, _ = lineitem
    plan = tpch._q1_planned_plan()
    scan = ParquetSplit(path, READ).resolve()
    with QueryServer(budget_bytes=scan.nbytes // 2) as srv:
        ticket = srv.session("scan").submit(
            plan, {"lineitem": ParquetSplit(path, READ)})
        with pytest.raises(QueryRejected, match="exceeds the whole HBM"):
            ticket.result(timeout=60)
        assert ticket.status == "rejected"
        assert ticket._exc.bytes_requested >= scan.nbytes
    assert not [k for k in REGISTRY.counters() if k.startswith("scan.")]
    assert srv.limiter.used == 0 and srv.limiter.peak == 0


# -- failure -----------------------------------------------------------------


def test_a_truncated_file_fails_the_request_and_the_next_is_served(
        lineitem, tmp_path):
    path, _ = lineitem
    data = open(path, "rb").read()
    truncated = tmp_path / "truncated.parquet"
    truncated.write_bytes(data[:len(data) - 1000])
    # a page of the first row group clobbered: the footer still parses, so
    # the request is admitted and fails in the decode
    corrupt = tmp_path / "corrupt.parquet"
    corrupt.write_bytes(data[:64] + bytes(200) + data[264:])
    plan = tpch._q1_planned_plan()
    with QueryServer(budget_bytes=1 << 30) as srv:
        session = srv.session("scan")
        for bad in (truncated, corrupt):
            ticket = session.submit(plan, {"lineitem": ParquetSplit(bad, READ)})
            with pytest.raises(MalformedFileError):
                ticket.result(timeout=60)
            assert ticket.status == "failed"
            assert srv.limiter.used == 0
        assert REGISTRY.counters()["server.admitted"] == 1   # the corrupt one
        assert "scan.row_groups" not in REGISTRY.counters()  # no partial table
        ok = session.submit(plan, {"lineitem": ParquetSplit(path, READ)})
        ok.result(timeout=120)
        assert ok.status == "served"
        assert REGISTRY.counters()["scan.row_groups"] == 3
    assert srv.limiter.used == 0


# -- spans -------------------------------------------------------------------


def test_the_span_trees_of_a_request_hold_the_scan(lineitem):
    path, _ = lineitem
    with QueryServer(budget_bytes=1 << 30) as srv:
        ticket = srv.session("scan").submit(
            tpch._q1_planned_plan(), {"lineitem": ParquetSplit(path, READ)})
        ticket.result(timeout=120)
    spans = [r for r in telemetry.events() if r.get("kind") == "span"]
    roots = {r["span"]: r for r in spans
             if r.get("parent") is None and r.get("request") == ticket.request}
    assert sorted(r["op"] for r in roots.values()) == [
        "query.result.tpch_q1_planned", "query.tpch_q1_planned",
        "submit.tpch_q1_planned"]
    mine = [r for r in spans if r["root"] in roots]
    by_id = {r["span"]: r for r in mine}

    def named(op):
        return [r for r in mine if r["op"] == op]

    def parent(r):
        return by_id[r["parent"]]["op"]

    (footer,), (scan,), (decode,) = (
        named("scan.footer"), named("scan"), named("scan.decode"))
    assert parent(footer) == "submit.tpch_q1_planned"
    assert parent(scan) == "server.stage_bindings"
    assert parent(decode) == "scan"
    chunks = named("scan.decode.chunk")
    assert sorted((r["row_group"], r["column"]) for r in chunks) == [
        (g, k) for g in range(3) for k in range(7)]
    assert {parent(r) for r in chunks} == {"scan.decode"}
    # decoded on the pool's threads, not on the worker's
    assert not {r["tid"] for r in chunks} & {scan["tid"]}
    stages = named("scan.stage")
    assert len(stages) == 4 and {parent(r) for r in stages} == {"scan"}
    assert sum(bool(r.get("ready")) for r in stages) == 1
    # the footer precedes admission, the scan follows it
    (wait,) = named("admission.wait")
    assert footer["t1"] <= wait["t0"] and wait["t1"] <= scan["t0"]
    assert scan["t0"] <= decode["t0"] and decode["t1"] <= scan["t1"]
    assert all(decode["t0"] <= r["t0"] and r["t1"] <= decode["t1"]
               for r in chunks)
    assert not telemetry.spans.validate(spans)


# -- the cell's control --------------------------------------------------------


def test_the_parquet_cells_control_is_not_correct():
    from benchmark import control, resolve

    from spark_rapids_jni_tpu.utils.config import reset_option

    try:
        numbers = control.control_numbers(
            "sf1_q1_parquet_fresh", 2**31 + 5, platform="cpu",
            sizes={"lineitem": 4096})
    finally:
        for name in ("telemetry.enabled", "server.estimate_path",
                     "rtfilter.path"):
            reset_option(name)
    limits = resolve.module("plans", "q1_planned_parquet").LIMITS
    got = numbers["q1_planned_parquet"]
    assert [n for n, v in got.items() if not v <= limits[n]]
