"""Runtime bloom-join filters (ISSUE 18): primitive edge cases, the
bit-identity acceptance gate (q3/q64/q72 byte-identical with the filter
on vs off — monolithic, out-of-core, and through a 2-host cluster
fan-out), and the learned-selectivity state machine.

The subsystem's whole correctness claim is that a bloom filter only
drops rows the join was about to drop (no false negatives), so every
on/off pair here compares raw bytes — data AND validity — not just
aggregates.
"""

import json
import time

import jax.numpy as jnp
import numpy as np
import pytest

from spark_rapids_jni_tpu import types as t
from spark_rapids_jni_tpu.columnar import Column, Table
from spark_rapids_jni_tpu.models import tpcds, tpch
from spark_rapids_jni_tpu.ops.bloom_filter import (
    BloomFilter,
    bloom_merge,
    bloom_might_contain,
    bloom_put,
    optimal_params,
)
from spark_rapids_jni_tpu.ops.table_ops import trim_table
from spark_rapids_jni_tpu.runtime import dispatch, fusion, rtfilter
from spark_rapids_jni_tpu.runtime.resilience import MalformedInputError
from spark_rapids_jni_tpu.telemetry import REGISTRY
from spark_rapids_jni_tpu.utils.config import reset_option, set_option


@pytest.fixture(autouse=True)
def _clean_rtfilter_state():
    """Fresh learned state and counters; config back at defaults after."""
    rtfilter.reset()
    REGISTRY.reset()
    yield
    rtfilter.reset()
    for k in ("rtfilter.enabled", "rtfilter.path", "rtfilter.fpp",
              "rtfilter.max_build_rows", "rtfilter.gate_pass_frac",
              "rtfilter.alpha", "rtfilter.save_interval_s"):
        reset_option(k)


def _assert_tables_identical(a: Table, b: Table):
    assert a.num_rows == b.num_rows
    assert len(a.columns) == len(b.columns)
    for ca, cb in zip(a.columns, b.columns):
        assert ca.dtype == cb.dtype
        np.testing.assert_array_equal(np.asarray(ca.data),
                                      np.asarray(cb.data))
        np.testing.assert_array_equal(np.asarray(ca.valid_mask()),
                                      np.asarray(cb.valid_mask()))


# ---------------------------------------------------------------------------
# bloom primitive edge cases
# ---------------------------------------------------------------------------


def test_empty_build_side_sizes_to_floor_and_rejects_everything():
    # optimal(0) clamps to the 64-bit floor instead of a zero-size filter
    m, k = optimal_params(0, 0.03)
    assert m == 64 and k >= 1
    bf = bloom_put(BloomFilter.empty(m, k),
                   jnp.zeros((0,), dtype=jnp.int64))
    hit = np.asarray(bloom_might_contain(
        bf, jnp.arange(100, dtype=jnp.int64)))
    assert not hit.any()  # nothing inserted -> nothing might match


def test_null_build_keys_are_not_inserted():
    vals = jnp.arange(64, dtype=jnp.int64)
    valid = jnp.asarray(np.arange(64) % 2 == 0)
    # large filter so false positives can't blur the assertion
    bf = bloom_put(BloomFilter.optimal(64, fpp=1e-4), vals, valid)
    hit = np.asarray(bloom_might_contain(bf, vals))
    assert hit[np.asarray(valid)].all()  # no false negatives
    assert not hit[~np.asarray(valid)].any()  # nulls never inserted


@pytest.mark.parametrize("n", [127, 128, 129])
def test_bucket_edge_row_counts_no_false_negatives(n):
    # 2^k-1 / 2^k / 2^k+1 rows: the dispatch bucket edges, where padded
    # tail rows must neither insert bits nor fake probe hits
    vals = jnp.asarray(np.arange(n, dtype=np.int64) * 7 + 1)
    bf = bloom_put(BloomFilter.optimal(n, fpp=1e-3), vals)
    assert np.asarray(bloom_might_contain(bf, vals)).all()
    others = jnp.asarray(-(np.arange(n, dtype=np.int64) + 1))
    fp = np.asarray(bloom_might_contain(bf, others)).mean()
    assert fp <= 0.05


def test_fpp_bound_sanity():
    n = 1000
    vals = jnp.asarray(np.arange(n, dtype=np.int64))
    bf = bloom_put(BloomFilter.optimal(n, fpp=0.03), vals)
    probes = jnp.asarray(np.arange(n, n + 20_000, dtype=np.int64))
    fp = np.asarray(bloom_might_contain(bf, probes)).mean()
    assert fp <= 0.06  # 2x headroom over the target fpp


def test_bloom_merge_geometry_mismatch_classified():
    a = BloomFilter.empty(128, 3)
    b = BloomFilter.empty(128, 4)
    with pytest.raises(MalformedInputError, match="geometry mismatch"):
        bloom_merge(a, b)
    assert REGISTRY.counter("rtfilter.merge_mismatch").value == 1
    c = bloom_merge(a, BloomFilter.empty(128, 3))  # agreeing pair still ORs
    assert c.num_bits == 128


# ---------------------------------------------------------------------------
# on == off bit-identity (the acceptance gate)
# ---------------------------------------------------------------------------


def _q72_data(n_cs=1200, n_items=60, n_days=730):
    return (
        tpcds.catalog_sales_table(n_cs, num_items=n_items, num_days=n_days),
        tpcds.date_dim_table(n_days),
        tpcds.item_table(n_items),
        tpcds.inventory_table(num_items=n_items, num_weeks=105),
    )


def test_q72_bit_identical_on_vs_off():
    cs, dd, it, inv = _q72_data()
    off = tpcds.tpcds_q72(cs, dd, it, inv, year=2000)
    set_option("rtfilter.enabled", True)
    on = tpcds.tpcds_q72(cs, dd, it, inv, year=2000)
    _assert_tables_identical(off.table, on.table)
    assert int(np.asarray(off.num_groups)) == int(np.asarray(on.num_groups))
    s = rtfilter.stats()
    assert s["decisions_apply"] >= 1  # the filter actually injected
    assert s["rows_in"] > 0 and s["observations"] >= 1


def test_q72_disabled_parity_run_to_run():
    # two disabled runs stay byte-for-byte: the off path is untouched by
    # the subsystem existing (decide records "disabled" and bows out)
    cs, dd, it, inv = _q72_data(n_cs=600, n_items=40)
    a = tpcds.tpcds_q72(cs, dd, it, inv, year=2000)
    b = tpcds.tpcds_q72(cs, dd, it, inv, year=2000)
    _assert_tables_identical(a.table, b.table)
    assert rtfilter.stats()["decisions_apply"] == 0


def test_q64_bit_identical_on_vs_off():
    ss = tpcds.store_sales_table(2000, num_items=60, num_customers=300)
    off = tpcds.tpcds_q64(ss)
    set_option("rtfilter.enabled", True)
    on = tpcds.tpcds_q64(ss)
    _assert_tables_identical(off.result.table, on.result.table)
    assert int(np.asarray(off.join_total)) == int(np.asarray(on.join_total))


def test_q3_bit_identical_on_vs_off():
    c = tpch.customer_table(40)
    o = tpch.orders_table(150, 40)
    li = tpch.lineitem_q3_table(4000, 150)
    off = tpch.tpch_q3(c, o, li)
    set_option("rtfilter.enabled", True)
    on = tpch.tpch_q3(c, o, li)
    _assert_tables_identical(off.result.table, on.result.table)
    assert int(np.asarray(off.join_total)) == int(np.asarray(on.join_total))


def _native_reader_available() -> bool:
    try:
        from spark_rapids_jni_tpu.runtime.native import load_native

        load_native()
        return True
    except OSError:
        return False


def test_pruned_chunks_reduce_reserved_bytes_bit_identical():
    """The generic chunked path (no parquet needed): bloom-pruning the
    chunk stream compacts rows BEFORE the per-chunk reserve, so peak
    bytes drop while the merged aggregate stays byte-for-byte."""
    from spark_rapids_jni_tpu.ops.groupby import groupby_aggregate
    from spark_rapids_jni_tpu.runtime.memory import (
        MemoryLimiter,
        _table_nbytes,
    )
    from spark_rapids_jni_tpu.runtime.outofcore import run_chunked_aggregate

    rng = np.random.default_rng(3)
    build_keys = jnp.asarray(np.arange(0, 40, dtype=np.int64))

    def chunks():
        for i in range(6):
            keys = rng.integers(0, 400, size=4096).astype(np.int64)
            vals = np.full(4096, i + 1, dtype=np.int64)
            yield Table([Column(t.INT64, jnp.asarray(keys)),
                         Column(t.INT64, jnp.asarray(vals))])

    def partial(chunk):
        # count rows per key, keys outside the build set nulled (the
        # downstream join's own masking — pruning must commute with it)
        keep = np.isin(np.asarray(chunk.column(0).data),
                       np.asarray(build_keys))
        keyed = Table([
            Column(t.INT64, chunk.column(0).data,
                   chunk.column(0).valid_mask() & jnp.asarray(keep)),
            chunk.column(1),
        ])
        g = groupby_aggregate(keyed, keys=[0], aggs=[(1, "sum")])
        return trim_table(g.table, int(np.asarray(g.num_groups)))

    def merge(merged_in):
        # merged_in is the concatenation of all partials
        g = groupby_aggregate(merged_in, keys=[0], aggs=[(1, "sum")])
        out = trim_table(g.table, int(np.asarray(g.num_groups)))
        return tpcds._compact_valid_keys(out, 1, [0], [True])

    limiter = MemoryLimiter(64 << 20)
    off = run_chunked_aggregate(chunks(), partial, merge, limiter=limiter)
    bf = rtfilter.build_filter(build_keys, expected_items=40, fpp=0.01)
    rng = np.random.default_rng(3)  # same chunk stream
    on = run_chunked_aggregate(
        rtfilter.pruned_chunks(chunks(), bf, 0, plan_name="toy",
                               label="join1"),
        partial, merge, limiter=MemoryLimiter(64 << 20))
    _assert_tables_identical(off.table, on.table)
    # 40-of-400 key selectivity: ~90% of every chunk pruned pre-reserve
    assert on.peak_bytes < off.peak_bytes
    s = rtfilter.stats()
    assert s["rows_in"] == 6 * 4096
    assert s["pass_frac"] < 0.3
    # the measured pass fraction fed the learned gate for this signature
    assert rtfilter.learned_pass_frac("toy", "join1") < 0.3


def test_q3_outofcore_pruned_chunks_bit_identical(tmp_path):
    pa = pytest.importorskip("pyarrow")
    pq = pytest.importorskip("pyarrow.parquet")
    if not _native_reader_available():
        pytest.skip("native parquet reader (libtpudf.so) unavailable")
    n_cust, n_ord, n = 32, 120, 20_000
    c = tpch.customer_table(n_cust)
    o = tpch.orders_table(n_ord, n_cust)
    li = tpch.lineitem_q3_table(n, n_ord)
    pa_table = pa.table({
        "l_orderkey": pa.array(np.asarray(li.column(0).data),
                               type=pa.int64()),
        "l_extendedprice": pa.array(np.asarray(li.column(1).data),
                                    type=pa.int64()),
        "l_discount": pa.array(np.asarray(li.column(2).data),
                               type=pa.int64()),
        "l_shipdate": pa.array(np.asarray(li.column(3).data))
                        .cast(pa.date32()),
    })
    path = str(tmp_path / "li_q3.parquet")
    pq.write_table(pa_table, path, row_group_size=5_000)  # 4 chunks
    budget = 64 << 20
    off = tpch.tpch_q3_outofcore(path, c, o, budget_bytes=budget,
                                 chunk_read_limit=1)
    set_option("rtfilter.enabled", True)
    on = tpch.tpch_q3_outofcore(path, c, o, budget_bytes=budget,
                                chunk_read_limit=1)
    _assert_tables_identical(off.table, on.table)
    s = rtfilter.stats()
    # one join (pk2), decided once a run, by the driver, for the host side
    # ("disabled" in the first run, apply in the second): the per-chunk
    # region does not decide, build and probe for it again
    assert (s["decisions_apply"], s["decisions_skip"]) == (1, 1)
    assert s["builds"] == 1
    # orders from one of five segments match -> most chunk rows prune
    # BEFORE staging, which is where the rows-scanned reduction lands
    assert s["rows_in"] == n
    assert s["rows_pruned"] > n // 2
    assert s["pass_frac"] < 0.5


# ---------------------------------------------------------------------------
# learned-selectivity gating & persistence
# ---------------------------------------------------------------------------


def test_decide_gates_and_records_reasons():
    # disabled (the default) records its reason and declines
    d = rtfilter.decide("plan", "join1", 100)
    assert (d.apply, d.reason) == (False, "disabled")
    set_option("rtfilter.enabled", True)
    d = rtfilter.decide("plan", "join1", 100)
    assert d.apply and d.reason == "no_history_optimistic"
    assert (d.num_bits, d.num_hashes) == optimal_params(100, 0.03)
    # oversized build side
    d = rtfilter.decide("plan", "join1", 10**9)
    assert (d.apply, d.reason) == (False, "build_too_large")
    s = rtfilter.stats()
    assert s["decisions_apply"] == 1 and s["decisions_skip"] == 2


def test_learned_nonselective_gate_switches_off():
    set_option("rtfilter.enabled", True)
    # a measured 95% pass fraction: the filter buys nothing on this join
    rtfilter.observe("plan", "join1", 1000, 950)
    d = rtfilter.decide("plan", "join1", 100)
    assert (d.apply, d.reason) == (False, "learned_nonselective")
    # the harvested label arrives prefixed rtf_<label>; same signature
    rtfilter.observe("plan2", "rtf_join1", 1000, 10)
    d2 = rtfilter.decide("plan2", "join1", 100)
    assert d2.apply and d2.reason == "selective"


def test_ema_blends_and_ignores_empty_probes():
    set_option("rtfilter.enabled", True)
    set_option("rtfilter.alpha", 0.5)
    rtfilter.observe("p", "j", 100, 100)
    rtfilter.observe("p", "j", 100, 0)
    assert rtfilter.learned_pass_frac("p", "j") == pytest.approx(0.5)
    rtfilter.observe("p", "j", 0, 0)  # no rows -> no information
    assert rtfilter.learned_pass_frac("p", "j") == pytest.approx(0.5)


def test_selectivity_persists_and_reloads(tmp_path):
    path = str(tmp_path / "learned_selectivity.json")
    set_option("rtfilter.path", path)
    set_option("rtfilter.enabled", True)
    rtfilter.observe("plan", "join1", 1000, 900)
    rtfilter.flush()
    with open(path) as fh:
        on_disk = json.load(fh)
    assert on_disk["plan/join1"] == pytest.approx(0.9)
    # a fresh process (reset drops memory, disk survives) re-learns the
    # gate from the file: first decide already skips as non-selective
    rtfilter.reset()
    assert rtfilter.learned_pass_frac("plan", "join1") == pytest.approx(0.9)
    d = rtfilter.decide("plan", "join1", 100)
    assert (d.apply, d.reason) == (False, "learned_nonselective")


def test_corrupt_state_file_discarded_and_counted(tmp_path):
    path = str(tmp_path / "learned_selectivity.json")
    with open(path, "w") as fh:
        fh.write("{not json")
    set_option("rtfilter.path", path)
    set_option("rtfilter.enabled", True)
    # corrupt history never fails a query: discarded, counted, and the
    # planner runs optimistically as if no history existed
    assert rtfilter.learned_pass_frac("plan", "join1") is None
    assert rtfilter.stats()["state_discarded"] >= 1
    d = rtfilter.decide("plan", "join1", 100)
    assert d.apply and d.reason == "no_history_optimistic"
    # the next save atomically replaces the rot with good state
    rtfilter.observe("plan", "join1", 1000, 100)
    rtfilter.flush()
    with open(path) as fh:
        assert json.load(fh)["plan/join1"] == pytest.approx(0.1)


def test_prune_chunk_keeps_null_keys_and_order():
    vals = np.arange(32, dtype=np.int64)
    valid = np.ones(32, dtype=bool)
    valid[5] = False  # null key: its fate belongs to the plan's masking
    chunk = Table([Column(t.INT64, jnp.asarray(vals), jnp.asarray(valid))])
    bf = rtfilter.build_filter(jnp.asarray(np.array([4, 8, 12], np.int64)),
                               expected_items=3, fpp=1e-4)
    out = rtfilter.prune_chunk(chunk, bf, 0)
    kept = np.asarray(out.column(0).data)
    kept_valid = np.asarray(out.column(0).valid_mask())
    assert 5 in kept and not kept_valid[list(kept).index(5)]
    for v in (4, 8, 12):
        assert v in kept
    assert list(kept) == sorted(kept, key=list(kept).index)  # order kept
    assert out.num_rows < chunk.num_rows


# ---------------------------------------------------------------------------
# 2-host cluster fan-out: the filter crosses the DCN wire packed
# ---------------------------------------------------------------------------


def _single_host_q72_reference(cs, dd, it, inv, year):
    res = tpcds.tpcds_q72(cs, dd, it, inv, year=year)
    out = trim_table(res.table, int(np.asarray(res.num_groups)))
    return tpcds._compact_valid_keys(out, 2, [2, 0], [False, True])


def test_q72_cluster_fanout_bit_identical_on_vs_off():
    from spark_rapids_jni_tpu.runtime import cluster, resultcache

    cs, dd, it, inv = _q72_data(n_cs=800, n_items=40)
    ref = _single_host_q72_reference(cs, dd, it, inv, 2000)
    ref_fp = resultcache.table_fingerprint(ref)
    set_option("fleet.heartbeat_interval_s", 0.1)
    try:
        with cluster.QueryCluster(2) as c:
            assert c.wait_live(timeout=120) == 2
            info = c.register_table("catalog_sales", cs, keys=(0,))
            assert info["parts"] == 2
            off = tpcds.tpcds_q72_cluster(c, "s0", dd, it, inv, year=2000,
                                          merge_timeout_s=120)
            assert resultcache.table_fingerprint(off) == ref_fp
            assert rtfilter.stats()["decisions_apply"] == 0
            # filters on: the router builds ONE filter from date_dim's
            # in-year keys, ships it packed inline with each per-shard
            # submit, and every host prunes its shard locally — merged
            # bytes unchanged
            set_option("rtfilter.enabled", True)
            on = tpcds.tpcds_q72_cluster(c, "s1", dd, it, inv, year=2000,
                                         merge_timeout_s=120)
            assert resultcache.table_fingerprint(on) == ref_fp
            s = rtfilter.stats()
            assert s["decisions_apply"] == 1 and s["builds"] == 1
            time.sleep(0.3)  # a fresh liveness pong carries the leak report
            assert c.leaked_bytes() == 0
    finally:
        reset_option("fleet.heartbeat_interval_s")
        dispatch.clear()
