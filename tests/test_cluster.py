"""Cross-host serving mesh (runtime/cluster, ISSUE 17).

Chaos invariant families over the partitioned query mesh — remote
replicas ("hosts") dial back into the supervisor over the sealed DCN
transport, registered tables are hash-sharded across them, and queries
ship to the shard rather than the shard to the query:

1. **Bit-identity through the mesh** — a partitioned q1 fan-out over
   two hosts merges to byte-for-byte what the single-host
   partial->merge algebra produces in-process, and a repeated fan-out
   is served entirely from the supervisor memo.

2. **Kill-the-host-mid-query failover** — SIGKILLing the remote host
   that owns the hot shard while its query is in flight re-homes the
   shard (re-registered from the supervisor's retained blob,
   fingerprint-verified) and completes bit-identical on the survivor;
   the death is classified as a *host* death and zero bytes leak.

3. **Partition-map routing** — single-shard queries land on the owning
   host (``cluster.route_local``), ``shard_for_key`` agrees with the
   partition map, and mis-keyed lookups are classified, not routed
   randomly.

4. **Cross-host late-duplicate drop** — a kill-raced host flushing its
   result after failover resolved the query is fingerprint-checked and
   dropped, never re-served (the (plan signature, input fingerprint)
   idempotency pair holds across hosts).

5. **Host-stamped telemetry** — worker-side records carry ``host=``,
   cluster supervision events aggregate into their own summary
   section, and the top/report cluster views render the partition map.

Host boots cost ~1-2 s each (subprocess + jax import + dial-back), so
every test keeps its mesh at two hosts.
"""

import signal
import time

import numpy as np
import pytest

from spark_rapids_jni_tpu.models import tpch
from spark_rapids_jni_tpu.columnar.table import Table
from spark_rapids_jni_tpu.ops.table_ops import concatenate, trim_table
from spark_rapids_jni_tpu.parallel import dcn
from spark_rapids_jni_tpu.runtime import cluster, dispatch, fleet, fusion, resultcache
from spark_rapids_jni_tpu.telemetry import REGISTRY
from spark_rapids_jni_tpu.telemetry import top as tele_top
from spark_rapids_jni_tpu.telemetry.events import drain as drain_events
from spark_rapids_jni_tpu.telemetry.events import events as ring_events
from spark_rapids_jni_tpu.telemetry.events import summary
from spark_rapids_jni_tpu.utils.config import reset_option, set_option

SERVE_DELAY = fleet._ENV_SERVE_DELAY


@pytest.fixture(autouse=True)
def _isolated_cluster():
    """Fresh counters/events, chaos-friendly supervision cadence, and
    config back at defaults afterwards."""
    dispatch.clear()
    REGISTRY.reset()
    drain_events()
    set_option("fleet.heartbeat_interval_s", 0.1)
    set_option("fleet.restart_backoff_s", 0.1)
    set_option("telemetry.enabled", True)
    yield
    for k in ("fleet.heartbeat_interval_s", "fleet.heartbeat_timeout_s",
              "fleet.restart_backoff_s", "fleet.failover_budget",
              "fleet.quarantine_after", "fleet.result_memo_entries",
              "fleet.dispatch_timeout_s", "telemetry.enabled",
              "telemetry.host", "telemetry.replica",
              "cluster.hosts", "cluster.register_timeout_s",
              "dcn.bind_host"):
        reset_option(k)
    dispatch.clear()


LI_KEYS = (4, 5)  # l_returnflag, l_linestatus — the q1 group keys


def _li(rows=300, seed=7):
    return tpch.lineitem_table(rows, seed=seed)


def _fp(table):
    return resultcache.table_fingerprint(table)


def _merge_partials(results):
    """The router-side q1 merge: trim each padded partial, concatenate,
    re-aggregate, trim the padded merge output."""
    parts = [trim_table(r.table, int(np.asarray(r.meta["partial.num_groups"])))
             for r in results]
    res = fusion.execute(tpch._q1_merge_plan(), {"partials": concatenate(parts)})
    return trim_table(res.table, int(np.asarray(res.meta["merge.num_groups"])))


def _single_host_q1(li):
    """Reference: the same partial -> merge algebra over one chunk."""
    pres = fusion.execute(tpch._q1_partial_plan(), {"chunk": li})
    return _merge_partials([pres])


def _cluster_events(event):
    return [r for r in ring_events()
            if str(r.get("op", "")).startswith("cluster.")
            and r.get("event") == event]


# ---------------------------------------------------------------------------
# 1. bit-identity through the mesh
# ---------------------------------------------------------------------------


def test_partitioned_q1_bit_identical_to_single_host_and_memo_hits():
    li = _li()
    ref_fp = _fp(_single_host_q1(li))
    with cluster.QueryCluster(2) as c:
        assert c.wait_live(timeout=120) == 2
        info = c.register_table("lineitem", li, keys=LI_KEYS)
        assert info["parts"] == 2
        assert info["rows"] == li.num_rows
        mt = c.submit_merge("s0", tpch._q1_partial_plan(), _merge_partials,
                            table="lineitem", binding="chunk")
        assert _fp(mt.result(timeout=120)) == ref_fp
        assert REGISTRY.counter("cluster.route_local").value == 2
        assert REGISTRY.counter("cluster.merges").value == 1
        served = REGISTRY.counter("fleet.served").value
        # identical re-fan-out: every shard query and the merge resolve
        # from the supervisor memos without touching a host, same bytes
        mt2 = c.submit_merge("s1", tpch._q1_partial_plan(), _merge_partials,
                             table="lineitem", binding="chunk")
        assert _fp(mt2.result(timeout=120)) == ref_fp
        assert REGISTRY.counter("fleet.served").value == served
        assert REGISTRY.counter("fleet.memo_hits").value >= 2
        time.sleep(0.3)  # a fresh liveness pong carries the leak report
        assert c.leaked_bytes() == 0


# ---------------------------------------------------------------------------
# 2. kill the host owning the hot shard mid-query
# ---------------------------------------------------------------------------


def test_sigkill_hot_shard_host_fails_over_bit_identical():
    li = _li()
    shard0 = dcn.partition_for_slices(li, list(LI_KEYS), 2)[0]
    # workers return the raw padded partial table — the ticket
    # fingerprint is over those bytes, so the reference stays untrimmed
    ref_fp = _fp(fusion.execute(tpch._q1_partial_plan(), {"chunk": shard0}).table)
    with cluster.QueryCluster(2, per_replica_env={
            "h0": {SERVE_DELAY: "1500"}}) as c:
        assert c.wait_live(timeout=120) == 2
        info = c.register_table("lineitem", li, keys=LI_KEYS)
        assert info["owners"][0] == "h0"
        t = c.submit_to_shard("s0", tpch._q1_partial_plan(),
                              table="lineitem", binding="chunk", part=0)
        deadline = time.monotonic() + 15
        while time.monotonic() < deadline and t.replica != "h0":
            time.sleep(0.01)
        assert t.replica == "h0"
        time.sleep(0.2)  # inside h0's serve hold
        c._host("h0").proc.send_signal(signal.SIGKILL)
        res = t.result(timeout=120)
        assert t.status == "served"
        assert t.dispatches == 2
        assert t.replica == "h1"
        assert _fp(res.table) == ref_fp
        # the shard re-homed: partition map now points at the survivor
        assert c._tables["lineitem"].owners[0] == "h1"
        assert REGISTRY.counter("cluster.host_deaths").value == 1
        assert REGISTRY.counter("cluster.route_rehomed").value == 1
        deaths = _cluster_events("host_death")
        assert deaths and deaths[0]["host"] == "h0"
        assert deaths[0]["error_kind"] == "ReplicaDeadError"
        rehomes = _cluster_events("rehomed")
        assert rehomes and rehomes[0]["host"] == "h1"
        assert rehomes[0]["from_host"] == "h0"
        time.sleep(0.3)
        assert c.leaked_bytes() == 0


# ---------------------------------------------------------------------------
# 3. partition-map routing
# ---------------------------------------------------------------------------


def test_partition_map_routes_to_owner():
    li = _li()
    with cluster.QueryCluster(2) as c:
        assert c.wait_live(timeout=120) == 2
        c.register_table("lineitem", li, keys=LI_KEYS)
        # every shard query lands on the owning host: 100% local hits
        for part in range(2):
            t = c.submit_to_shard(f"s{part}", tpch._q1_partial_plan(),
                                  table="lineitem", binding="chunk",
                                  part=part)
            t.result(timeout=120)
            assert t.replica == c._tables["lineitem"].owners[part]
        assert REGISTRY.counter("cluster.route_local").value == 2
        assert REGISTRY.counter("cluster.route_rehomed").value == 0
        # each host worker's boot_ok named its platform and device
        for r in c.inspect()["replicas"]:
            assert r["device"]["platform"] == c.platform == "cpu"
            assert r["device"]["device_kind"]
            assert isinstance(r["device"]["device_id"], int)
        # shard_for_key agrees with the sharding: a single-row key table
        # built from row 0's key columns hashes to a valid partition and
        # routing by key_table reaches the same owner
        key = Table([
            type(li.columns[k])(li.columns[k].dtype, li.columns[k].data[:1])
            for k in LI_KEYS])
        part = c.shard_for_key("lineitem", key)
        assert part in (0, 1)
        t = c.submit_to_shard("sk", tpch._q1_partial_plan(),
                              table="lineitem", binding="chunk",
                              key_table=key)
        t.result(timeout=120)
        # same shard already served above -> the idempotent memo answers
        # (proving key-routing resolved to the identical memo pair)
        assert t.replica in ("supervisor", c._tables["lineitem"].owners[part])
        # mis-keyed lookups are classified, never routed
        with pytest.raises(ValueError, match="key column"):
            c.shard_for_key("lineitem", Table([li.columns[4]]))


def test_host_on_unassigned_platform_is_refused_never_live():
    """The mesh shares the fleet's boot check: a host worker that dials
    back from a platform it was not assigned is a classified failed boot,
    host-stamped, never live."""
    set_option("fleet.quarantine_after", 1)
    with cluster.QueryCluster(
            1, worker_env={"JAX_PLATFORMS": "tpu"},
            per_replica_env={"h0": {"JAX_PLATFORMS": "cpu"}}) as c:
        h0 = c._find("h0")
        deadline = time.monotonic() + 60
        while h0.state != "quarantined" and time.monotonic() < deadline:
            time.sleep(0.02)
        assert h0.state == "quarantined"
        assert c.wait_live(timeout=0.1) == 0
    refused = [r for r in ring_events() if r.get("event") == "boot_refused"]
    assert len(refused) == 1 and refused[0]["host"] == "h0"
    assert refused[0]["platform"] == "cpu"
    assert not [r for r in ring_events() if r.get("event") == "live"]
    assert REGISTRY.counter("fleet.boot_refused").value == 1


def test_unregistered_table_is_classified():
    with cluster.QueryCluster(1) as c:
        assert c.wait_live(timeout=120) == 1
        with pytest.raises(KeyError, match="not registered"):
            c.submit_to_shard("s0", tpch._q1_partial_plan(),
                              table="nope", binding="chunk", part=0)


# ---------------------------------------------------------------------------
# 4. cross-host late-duplicate drop
# ---------------------------------------------------------------------------


def test_late_duplicate_across_hosts_is_fingerprint_checked_and_dropped():
    li = _li()
    with cluster.QueryCluster(2) as c:
        assert c.wait_live(timeout=120) == 2
        c.register_table("lineitem", li, keys=LI_KEYS)
        t = c.submit_to_shard("s0", tpch._q1_partial_plan(),
                              table="lineitem", binding="chunk", part=0)
        res = t.result(timeout=120)
        # replay the owner's own result frame for the resolved qid, as a
        # kill-raced host flushing after failover would: dropped, bytes
        # verified against the recorded fingerprint
        owner = c._host(t.replica)
        blob = fleet._encode_table(res.table)
        dup = {"t": "result", "qid": t.qid, "status": "served",
               "table": blob, "meta": {}, "wall_ms": 1.0}
        c._on_result(owner, owner.generation, dup)
        assert REGISTRY.counter("fleet.duplicate_drops").value == 1
        assert REGISTRY.counter("fleet.identity_mismatch").value == 0
        # the same qid surfacing from the OTHER host with different
        # bytes is a cross-host identity violation and is flagged
        other = c._host("h1" if t.replica == "h0" else "h0")
        shard1 = dcn.partition_for_slices(li, list(LI_KEYS), 2)[1]
        wrong = fusion.execute(tpch._q1_partial_plan(), {"chunk": shard1})
        dup2 = dict(dup, table=fleet._encode_table(wrong.table))
        c._on_result(other, other.generation, dup2)
        assert REGISTRY.counter("fleet.duplicate_drops").value == 2
        assert REGISTRY.counter("fleet.identity_mismatch").value == 1


# ---------------------------------------------------------------------------
# 5. host-stamped telemetry + cluster views
# ---------------------------------------------------------------------------


def test_cluster_events_host_stamped_and_views_render():
    li = _li()
    with cluster.QueryCluster(2) as c:
        assert c.wait_live(timeout=120) == 2
        c.register_table("lineitem", li, keys=LI_KEYS)
        mt = c.submit_merge("s0", tpch._q1_partial_plan(), _merge_partials,
                            table="lineitem", binding="chunk")
        mt.result(timeout=120)
        # supervisor-side cluster events are host-stamped
        dialed = _cluster_events("host_dialed_in")
        assert len(dialed) == 2
        assert {r["host"] for r in dialed} == {"h0", "h1"}
        for r in _cluster_events("local"):
            assert r["host"] in ("h0", "h1")
        # events summary grows a cluster section keyed by event name
        s = summary()
        assert s["cluster"].get("local") == 2
        assert s["cluster"].get("merged") == 1
        assert s["cluster"].get("host_dialed_in") == 2
        # inspect + top render the partition map and routing counters
        snap = c.inspect()
        assert snap["cluster"] is True
        assert snap["tables"]["lineitem"]["owners"] == ["h0", "h1"]
        assert snap["counters"]["cluster.route_local"] == 2
        text = tele_top.render_cluster(tele_top.collect_cluster())
        assert "lineitem" in text
        assert "routing:" in text
    assert tele_top.collect_cluster() == []  # closed mesh leaves the view


def test_worker_records_host_stamped(tmp_path):
    li = _li(rows=200)
    path = tmp_path / "tele.jsonl"
    set_option("telemetry.path", str(path))
    try:
        with cluster.QueryCluster(1) as c:
            assert c.wait_live(timeout=120) == 1
            c.register_table("lineitem", li, keys=LI_KEYS)
            c.submit_to_shard("s0", tpch._q1_partial_plan(),
                              table="lineitem", binding="chunk",
                              part=0).result(timeout=120)
    finally:
        reset_option("telemetry.path")
    import json

    stamped = [json.loads(line) for line in
               path.read_text().splitlines() if "host" in line]
    worker = [r for r in stamped if r.get("host") == "h0"]
    assert worker, "no worker-side record carried host=h0"
