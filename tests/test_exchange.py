"""General-cardinality distributed exchange (runtime/exchange, ISSUES 19+20).

Invariant families over the hash-partitioned all-to-all:

1. **Pack correctness at bucket edges** — ``exchange_local`` at 1,
   2^k-1, 2^k, 2^k+1 rows (the dispatch bucket seams) with null tails
   and padded string payloads is a pure repartition: the destinations
   concatenate back to the input multiset, every row lands on its key
   hash's destination, and ``partitioned_groupby`` matches the global
   single-host reference. The ``Exchange`` plan root carries the wire
   meta (``row_counts`` as plain Python) and ``split_wire`` rejects
   malformed counts classified at the ``exchange.wire`` seam.

2. **Skew sweep** — one hot key owning 90% of the rows rides the full
   overflow ladder: geometric capacity escalation, demotion to chunked
   flights at ``exchange.max_capacity_rows``, and a receive-side
   chunked merge whose partials demote into the SpillStore — correct
   result, ``exchange.*`` counters tell the story, and the caller's
   MemoryLimiter ends at zero (no leaked reservations).

3. **Wire corruption** — an injected ``exchange.wire`` corruption on a
   sealed flight frame is NAK'd and refetched to a bit-identical
   delivery (verify-then-decode: the codec never sees corrupt bytes).

4. **Cluster bit-identity + chaos** — a 2-host distributed exchange
   (TPC-H q13-shaped high-cardinality aggregation) returns
   byte-for-byte the single-host oracle, including with a host
   SIGKILLed mid-exchange (failover re-packs on the survivor) and with
   skewed keys under a tight merge budget (router-side spill-aware
   merge) — zero leaked bytes in every case.

5. **Direct flights + planner placement (ISSUE 20)** — a plan with an
   INTERIOR ``Exchange`` executes as region → exchange → region
   byte-for-byte the hand-split pair (bucket edges, null tails, padded
   strings; ``parts=0`` sized from the learned-selectivity store); the
   direct host-to-host rung is HMAC-grant-gated, moves strictly fewer
   supervisor-link bytes than routed, and degrades rung-by-rung
   (unreachable peer → per-flight reroute; no gateway / SIGKILL
   mid-flight → whole-exchange routed fallback) — always bit-identical,
   always zero leaked reservations, ``bytes_wire`` counted once per
   sealed flight with the ``bytes_direct``/``bytes_routed`` lane split.

Host boots cost ~1-2 s each, so every cluster test keeps its mesh at
two hosts (same discipline as test_cluster.py), the non-chaos tests
share one module-scoped mesh, and the dispatch cache is cleared per
MODULE, not per test — repeated signatures (the q13 oracle, the skew
merges) compile once.
"""

import signal
import socket
import threading
import time

import numpy as np
import pytest

from spark_rapids_jni_tpu import telemetry, types as t
from spark_rapids_jni_tpu.columnar import Column, Table
from spark_rapids_jni_tpu.models import tpch
from spark_rapids_jni_tpu.ops.groupby import groupby_aggregate
from spark_rapids_jni_tpu.ops.hash import partition_hash
from spark_rapids_jni_tpu.ops.strings import pad_strings
from spark_rapids_jni_tpu.ops.table_ops import concatenate, trim_table
from spark_rapids_jni_tpu.runtime import (
    cluster,
    dispatch,
    faults,
    fleet,
    fusion,
    resilience,
    resultcache,
)
from spark_rapids_jni_tpu.runtime import exchange as xch
from spark_rapids_jni_tpu.runtime.memory import (
    MemoryLimiter,
    SpillStore,
    _table_nbytes,
)
from spark_rapids_jni_tpu.telemetry import REGISTRY
from spark_rapids_jni_tpu.telemetry.events import drain as drain_events
from spark_rapids_jni_tpu.telemetry.events import events as ring_events
from spark_rapids_jni_tpu.utils.config import reset_option, set_option

SERVE_DELAY = fleet._ENV_SERVE_DELAY


@pytest.fixture(autouse=True, scope="module")
def _module_dispatch():
    """One dispatch cache for the whole module: the q13 oracle, the
    skew merges, and the pack/groupby signatures repeat across tests,
    and recompiling them per test puts this file over the premerge
    wall-clock budget.  Cleared at both edges so neighbouring test
    files keep their compile-count determinism."""
    dispatch.clear()
    yield
    dispatch.clear()


@pytest.fixture(autouse=True)
def _isolated():
    REGISTRY.reset()
    drain_events()
    set_option("fleet.heartbeat_interval_s", 0.1)
    set_option("fleet.restart_backoff_s", 0.1)
    set_option("telemetry.enabled", True)
    yield
    for k in ("fleet.heartbeat_interval_s", "fleet.restart_backoff_s",
              "telemetry.enabled", "exchange.max_capacity_rows",
              "exchange.merge_budget_bytes", "resilience.max_attempts",
              "cluster.hosts", "dcn.bind_host"):
        reset_option(k)


def _fp(table):
    return resultcache.table_fingerprint(table)


def _rows(tbl):
    """Logical row multiset (sorted): decodes padded strings and maps
    invalid cells to None so null tails compare by meaning, not bits."""
    if tbl.num_rows == 0:
        return []
    cols = []
    for c in tbl.columns:
        valid = np.asarray(c.valid_mask()).tolist()
        if c.dtype.is_string:
            lens = np.asarray(c.data)
            chars = np.asarray(c.chars)
            vals = [bytes(chars[i, :int(lens[i])]).decode()
                    for i in range(tbl.num_rows)]
        else:
            vals = np.asarray(c.data).tolist()
        cols.append([v if ok else None for v, ok in zip(vals, valid)])
    return sorted(zip(*cols), key=repr)


def _mixed_table(n, seed=11, nkeys=37):
    """Key + int payload with a null tail + padded string payload."""
    rng = np.random.default_rng(seed)
    key = rng.integers(0, nkeys, n).astype(np.int64)
    val = rng.integers(-50, 50, n).astype(np.int64)
    valid = np.ones(n, dtype=bool)
    valid[-max(1, n // 8):] = False  # the null tail
    strs = [f"s{int(k)}-{i % 5}" for i, k in enumerate(key)]
    return Table([
        Column.from_numpy(key),
        Column.from_numpy(val, validity=valid),
        pad_strings(Column.from_pylist(strs, t.STRING)),
    ])


def _exchange_events(event):
    return [r for r in ring_events()
            if r.get("kind") == "exchange" and r.get("event") == event]


# ---------------------------------------------------------------------------
# 1. pack correctness at bucket edges
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("rows", [1, 255, 256, 257])
def test_exchange_local_is_a_pure_repartition_at_bucket_edges(rows):
    tbl = _mixed_table(rows)
    dests = xch.exchange_local(tbl, [0], 3)
    assert len(dests) == 3
    # every row landed on its key hash's destination
    for p, d in enumerate(dests):
        if d.num_rows:
            got = np.asarray(partition_hash(d, [0], 3))
            assert got.tolist() == [p] * d.num_rows
    # and nothing was lost, duplicated, or bit-mangled (nulls + strings)
    nonempty = [d for d in dests if d.num_rows]
    assert sum(d.num_rows for d in dests) == rows
    assert _rows(concatenate(nonempty)) == _rows(tbl)
    assert REGISTRY.counter("exchange.overflow_escalations").value == 0


@pytest.mark.parametrize("rows", [256, 257])
def test_partitioned_groupby_matches_single_host_reference(rows):
    tbl = _mixed_table(rows)
    got = xch.partitioned_groupby(tbl, [0], [(1, "count"), (1, "sum")],
                                  parts=3)
    ref = groupby_aggregate(tbl, [0], [(1, "count"), (1, "sum")],
                            max_groups=None)
    want = trim_table(ref.table, int(np.asarray(ref.num_groups)))
    assert _rows(got) == _rows(want)


def test_partitioned_join_matches_global_join():
    rng = np.random.default_rng(5)
    lkey = rng.integers(0, 20, 300).astype(np.int64)
    lval = np.arange(300, dtype=np.int64)
    rkey = rng.integers(0, 20, 80).astype(np.int64)
    rval = np.arange(80, dtype=np.int64) * 10
    left = Table([Column.from_numpy(lkey), Column.from_numpy(lval)])
    right = Table([Column.from_numpy(rkey), Column.from_numpy(rval)])

    got = xch.partitioned_join(left, right, 0, 0, parts=2)
    # independent python inner-join oracle (not join_auto: the check
    # must not share code with the thing under test)
    want = sorted((int(k), int(v), int(k), int(w))
                  for k, v in zip(lkey, lval)
                  for k2, w in zip(rkey, rval) if k == k2)
    rows = [tuple(int(x) for x in r) for r in _rows(got)]
    assert sorted(rows) == want


def test_exchange_plan_root_carries_wire_meta_and_split_inverts():
    tbl = _mixed_table(500)
    plan = fusion.Plan("xroot", fusion.Exchange(
        fusion.Scan("rows"), keys=(0,), parts=3, label="ex"))
    fused = fusion.execute(plan, {"rows": tbl})
    assert fused.meta["ex.parts"] == 3
    assert fused.meta["ex.rows"] == 500
    assert fused.meta["ex.flights"] == 1
    rc = fused.meta["ex.row_counts"]
    assert isinstance(rc, list) and all(isinstance(c, int) for c in rc)
    assert sum(rc) == 500
    per_dest = xch.split_wire(fused.table, rc, 3)
    whole = concatenate([f for fls in per_dest for f in fls])
    assert _rows(whole) == _rows(tbl)
    # malformed counts are classified at the exchange.wire seam
    with pytest.raises(resilience.MalformedInputError, match="row_counts"):
        xch.split_wire(fused.table, rc[:-1], 3)
    with pytest.raises(resilience.MalformedInputError, match="sum"):
        xch.split_wire(fused.table, [c + 1 for c in rc[:1]] + rc[1:], 3)


def _midplan(name, parts, label="ex"):
    """ONE plan with a planner-placed interior Exchange: partial
    groupby -> exchange by key -> sum merge (the q13 shape)."""
    return fusion.Plan(name, fusion.GroupBy(
        fusion.Exchange(
            fusion.GroupBy(fusion.Scan("rows"), (0,), ((1, "sum"),),
                           max_groups=None, label="partial"),
            keys=(0,), parts=parts, valid_meta="partial.num_groups",
            label=label),
        (0,), ((1, "sum"),), max_groups=None, label="merge"))


def _slice(tbl, n):
    from spark_rapids_jni_tpu.ops.table_ops import _slice_rows

    return _slice_rows(tbl, 0, n)


@pytest.mark.parametrize("rows", [1, 255, 256, 257])
def test_midplan_exchange_bit_identical_to_hand_split_pair(rows):
    """An interior Exchange executes as region -> exchange -> region and
    is byte-for-byte the hand-split (pack, merge) plan pair it
    replaces — at every dispatch bucket seam, with null tails and
    padded strings riding along."""
    tbl = _mixed_table(rows)
    parts = 3
    got = fusion.execute(_midplan("edge_mid", parts), {"rows": tbl})
    assert got.meta["ex.parts"] == parts
    assert REGISTRY.counter("fusion.midplan_exchanges").value == 1
    # the hand-split pair over the same input
    pack = fusion.Plan("edge_pack", fusion.Exchange(
        fusion.GroupBy(fusion.Scan("rows"), (0,), ((1, "sum"),),
                       max_groups=None, label="partial"),
        keys=(0,), parts=parts, valid_meta="partial.num_groups",
        label="ex"))
    merge = fusion.Plan("edge_merge", fusion.GroupBy(
        fusion.Scan("partials"), (0,), ((1, "sum"),),
        max_groups=None, label="merge"))
    fused = fusion.execute(pack, {"rows": tbl})
    outs = []
    for fls in xch.split_wire(fused.table, fused.meta["ex.row_counts"],
                              parts):
        if not fls:
            continue
        dest_in = fls[0] if len(fls) == 1 else concatenate(fls)
        r = fusion.execute(merge, {"partials": dest_in})
        outs.append(_slice(r.table,
                           int(np.asarray(r.meta["merge.num_groups"]))))
    hand = outs[0] if len(outs) == 1 else concatenate(outs)
    assert _fp(got.table) == _fp(hand)
    assert got.meta["merge.num_groups"] == hand.num_rows
    # value-level: same groups and sums as the naive global groupby
    ref = groupby_aggregate(tbl, [0], [(1, "sum")], max_groups=None)
    want = trim_table(ref.table, int(np.asarray(ref.num_groups)))
    assert _rows(got.table) == _rows(want)


@pytest.mark.parametrize("rows", [1, 255, 256, 257])
def test_midplan_exchange_bit_identical_to_exchange_local(rows):
    """A raw-row interior Exchange (the pack child is a Scan) merges to
    exactly what the ``exchange_local`` oracle delivers per
    destination."""
    tbl = _mixed_table(rows)
    parts = 3
    mid = fusion.Plan("edge_raw_mid", fusion.GroupBy(
        fusion.Exchange(fusion.Scan("rows"), keys=(0,), parts=parts,
                        label="ex"),
        (0,), ((1, "sum"),), max_groups=None, label="merge"))
    got = fusion.execute(mid, {"rows": tbl})
    merge = fusion.Plan("edge_raw_merge", fusion.GroupBy(
        fusion.Scan("partials"), (0,), ((1, "sum"),),
        max_groups=None, label="merge"))
    outs = []
    for d in xch.exchange_local(tbl, [0], parts):
        if not d.num_rows:
            continue
        r = fusion.execute(merge, {"partials": d})
        outs.append(_slice(r.table,
                           int(np.asarray(r.meta["merge.num_groups"]))))
    want = outs[0] if len(outs) == 1 else concatenate(outs)
    assert _fp(got.table) == _fp(want)


def test_midplan_exchange_auto_parts_from_learned_density():
    """``parts=0`` defers the fan-out width to the learned-selectivity
    store: no history falls back to 1 part; after one run the observed
    group density sizes the next fan-out."""
    from spark_rapids_jni_tpu.runtime import rtfilter

    rtfilter.reset()
    set_option("exchange.target_rows_per_part", 64)
    try:
        tbl = _mixed_table(600, nkeys=300)
        r1 = fusion.execute(_midplan("auto_mid", 0), {"rows": tbl})
        assert r1.meta["ex.parts"] == 1  # no history: fallback
        r2 = fusion.execute(_midplan("auto_mid", 0), {"rows": tbl})
        assert r2.meta["ex.parts"] > 1  # learned density sized it
        assert _rows(r2.table) == _rows(r1.table)
        decisions = [r for r in ring_events()
                     if r.get("event") == "parts_decision"]
        assert any(d.get("reason") == "no_history" for d in decisions)
        assert any(d.get("reason") == "learned_density"
                   for d in decisions)
    finally:
        reset_option("exchange.target_rows_per_part")
        rtfilter.reset()


# ---------------------------------------------------------------------------
# 2. skew sweep: overflow ladder -> chunked flights -> spill merge
# ---------------------------------------------------------------------------


def _skewed_table(n=2000, hot_frac=0.9, seed=3):
    """One hot key owning ``hot_frac`` of the rows + a ones column, so
    ``sum(col1) per key`` is a re-applicable count (sum of sums)."""
    rng = np.random.default_rng(seed)
    key = rng.integers(1, 16, n).astype(np.int64)
    key[rng.random(n) < hot_frac] = 0
    return Table([
        Column.from_numpy(key),
        Column.from_numpy(np.ones(n, dtype=np.int64)),
    ])


def test_skewed_hot_key_rides_the_full_spill_ladder_zero_leaks():
    set_option("exchange.max_capacity_rows", 256)
    tbl = _skewed_table(1200)
    parts = 4
    flights = xch.pack_flights(tbl, [0], parts)
    # rung 1 escalated, then rung 2 demoted to chunked flights
    assert len(flights) > 1
    assert all(f.capacity <= 256 for f in flights)
    assert REGISTRY.counter("exchange.overflow_escalations").value >= 1
    assert REGISTRY.counter("exchange.chunked_flights").value == 1
    assert _exchange_events("overflow_escalate")
    assert _exchange_events("chunked_flights")

    # regroup by destination; the hot key's destination holds ~90%
    per_dest = [[] for _ in range(parts)]
    for res in flights:
        for p, s in enumerate(xch.flight_slices(res)):
            if s.num_rows:
                per_dest[p].append(s)
    hot = max(range(parts), key=lambda p: sum(s.num_rows
                                              for s in per_dest[p]))
    hot_flights = per_dest[hot]
    assert len(hot_flights) > 1
    assert sum(s.num_rows for s in hot_flights) >= int(0.9 * 1200)

    # receive side: chunked merge under a caller limiter, partials
    # forced through a tiny SpillStore — the spill demotion path
    def merge_step(chunk):
        g = groupby_aggregate(chunk, [0], [(1, "sum")], max_groups=None)
        return trim_table(g.table, int(np.asarray(g.num_groups)))

    budget = sum(_table_nbytes(f) for f in hot_flights) * 4
    limiter = MemoryLimiter(budget)
    # a store that holds ONE checkpointed partial: every subsequent put
    # LRU-demotes its predecessor to host
    spill = SpillStore(max(_table_nbytes(merge_step(f))
                           for f in hot_flights) + 1)
    res = xch.merge_flights(hot_flights, merge_step, merge_step,
                            budget_bytes=budget, limiter=limiter,
                            spill=spill)
    assert res.spill_stats["spills"] > 0
    assert REGISTRY.counter("exchange.spill_demotions").value > 0
    assert _exchange_events("spill_demote")
    assert limiter.used == 0, "leaked reservations"
    want = merge_step(concatenate(hot_flights))
    assert _rows(res.table) == _rows(want)


def test_rung1_escalation_resolves_moderate_skew_in_one_flight():
    """Skew the schedule can absorb stays a SINGLE flight: rung 1 grows
    capacity geometrically (each overflow naming its exact requirement)
    and never demotes to chunking."""
    tbl = _skewed_table(1000, hot_frac=0.6)
    # start the ladder far below the hot destination's true need
    flights = xch.pack_flights(tbl, [0], 4, capacity=64)
    assert len(flights) == 1
    assert int(flights[0].counts.max()) <= flights[0].capacity
    assert int(flights[0].counts.sum()) == 1000
    assert REGISTRY.counter("exchange.overflow_escalations").value >= 1
    assert REGISTRY.counter("exchange.chunked_flights").value == 0


def test_total_skew_exhausts_into_chunked_flights_classified():
    """100% of rows on one key: rung 1 provably exhausts (required >
    max capacity) and the demotion is the classified CapacityOverflow
    path, not a bare boolean anywhere."""
    set_option("exchange.max_capacity_rows", 8)
    tbl = _skewed_table(64, hot_frac=1.0)
    flights = xch.pack_flights(tbl, [0], 2)
    # the ladder tops out at quantize(8) and chunks the 64 rows
    assert len(flights) >= 2
    assert sum(int(f.counts.sum()) for f in flights) == 64
    assert all(int(f.counts.max()) <= f.capacity for f in flights)
    assert REGISTRY.counter("exchange.chunked_flights").value == 1


def test_classify_overflow_context():
    from spark_rapids_jni_tpu.parallel.shuffle import classify_overflow

    err = classify_overflow(op="exchange.pack", capacity=8, rows=64,
                            partition=3, required=60,
                            seam="exchange.pack")
    assert isinstance(err, resilience.CapacityOverflow)
    assert "exchange.pack" in str(err)


# ---------------------------------------------------------------------------
# 3. wire corruption: sealed flights refetch bit-identical
# ---------------------------------------------------------------------------


def _flight_roundtrip(tbl, script=None):
    a, b = socket.socketpair()
    a.settimeout(60)
    b.settimeout(60)
    out, err = {}, {}

    def _rx():
        try:
            out["tbl"] = xch.recv_flight(b, 7)
        except BaseException as exc:  # noqa: BLE001 - surfaced below
            err["rx"] = exc

    th = threading.Thread(target=_rx)
    try:
        ctx = faults.inject(script) if script is not None else None
        if ctx is not None:
            ctx.__enter__()
        try:
            th.start()
            try:
                xch.send_flight(a, tbl, 7, dest=1)
            except BaseException as exc:  # noqa: BLE001 - surfaced below
                err["tx"] = exc
            th.join(60)
            assert not th.is_alive(), "receiver hung"
        finally:
            if ctx is not None:
                ctx.__exit__(None, None, None)
    finally:
        a.close()
        b.close()
    return out.get("tbl"), err


def test_clean_flight_roundtrip_counts_wire_bytes():
    tbl = _skewed_table(300)
    got, err = _flight_roundtrip(tbl)
    assert not err
    assert _fp(got) == _fp(tbl)
    assert REGISTRY.counter("exchange.flights").value == 1
    assert REGISTRY.counter("exchange.bytes_raw").value > 0
    assert REGISTRY.counter("exchange.bytes_wire").value > 0
    assert REGISTRY.counter("integrity.refetch").value == 0
    evs = _exchange_events("flight")
    assert evs and evs[0]["wire_bytes"] > 0


@pytest.mark.parametrize("mode", ["flip", "truncate"])
def test_exchange_wire_corruption_refetches_bit_identical(mode):
    tbl = _skewed_table(300)
    script = faults.FaultScript(corruptions=[
        faults.CorruptionSpec("exchange.wire", mode=mode, seed=19)])
    got, err = _flight_roundtrip(tbl, script)
    assert not err, f"refetch should have recovered: {err}"
    assert script.fired == [("exchange.wire", 7)]
    assert _fp(got) == _fp(tbl)
    assert REGISTRY.counter("integrity.refetch").value == 1


def test_exchange_wire_exhaustion_dies_classified():
    set_option("resilience.max_attempts", 2)
    tbl = _skewed_table(100)
    script = faults.FaultScript(corruptions=[
        faults.CorruptionSpec("exchange.wire", mode="flip", times=10,
                              seed=5)])
    got, err = _flight_roundtrip(tbl, script)
    assert got is None
    assert isinstance(err.get("tx"), resilience.FatalExecutionError)
    assert isinstance(err.get("rx"), resilience.FatalExecutionError)
    assert REGISTRY.counter("integrity.refetch").value == 2


def test_bytes_wire_ledger_counts_each_flight_once():
    """``exchange.bytes_wire`` is a unique-payload ledger, counted at
    first seal: an ARQ refetch re-sends the same sealed blob without
    re-counting it, and a routed re-send of the SAME payload moves only
    the lane counters (``bytes_direct`` / ``bytes_routed`` split)."""
    from spark_rapids_jni_tpu.parallel import dcn

    tbl = _skewed_table(300)
    script = faults.FaultScript(corruptions=[
        faults.CorruptionSpec("exchange.wire", mode="flip", seed=23)])
    got, err = _flight_roundtrip(tbl, script)  # direct lane + 1 refetch
    assert not err
    assert _fp(got) == _fp(tbl)
    assert REGISTRY.counter("integrity.refetch").value == 1
    wire = REGISTRY.counter("exchange.bytes_wire").value
    assert REGISTRY.counter("exchange.flights").value == 1
    assert REGISTRY.counter("exchange.bytes_direct").value == wire
    assert REGISTRY.counter("exchange.bytes_routed").value == 0
    # routed fallback rung: the same pristine blob rides the other lane
    blob = dcn.serialize_table(tbl)  # codec only — not a new seal
    a, b = socket.socketpair()
    a.settimeout(60)
    b.settimeout(60)
    out = {}
    th = threading.Thread(
        target=lambda: out.update(tbl=xch.recv_flight(b, 9)))
    try:
        th.start()
        xch.send_flight_blob(a, blob, 9, lane="routed")
        th.join(60)
        assert not th.is_alive()
    finally:
        a.close()
        b.close()
    assert _fp(out["tbl"]) == _fp(tbl)
    assert REGISTRY.counter("exchange.bytes_wire").value == wire
    assert REGISTRY.counter("exchange.flights").value == 1
    assert REGISTRY.counter("exchange.bytes_routed").value == len(blob)
    with pytest.raises(ValueError, match="lane"):
        xch.send_flight_blob(None, b"", 0, lane="sideways")


# ---------------------------------------------------------------------------
# 4. cluster: distributed exchange == single-host oracle (+ chaos)
# ---------------------------------------------------------------------------


def _orders(rows=900, customers=120, seed=5):
    return tpch.orders_table(rows, customers, seed=seed)


@pytest.fixture(scope="module")
def mesh():
    """One healthy 2-host mesh shared by the non-chaos cluster tests
    (the SIGKILL test boots its own: it leaves a corpse).  Boots are
    ~1.5 s each; the shared mesh keeps this module under the premerge
    wall-clock budget."""
    set_option("fleet.heartbeat_interval_s", 0.1)
    set_option("fleet.restart_backoff_s", 0.1)
    with cluster.QueryCluster(2) as c:
        assert c.wait_live(timeout=120) == 2
        yield c


def test_distributed_q13_exchange_bit_identical_to_oracle(mesh):
    orders = _orders()
    oracle = tpch.tpch_q13_local(orders, 2)
    # the oracle itself is value-identical to the naive global groupby
    assert _rows(oracle) == _rows(tpch.tpch_q13_reference(orders))
    ref_fp = _fp(oracle)
    pack, merge = tpch.q13_exchange_plans(2)
    c = mesh
    c.register_table("orders", orders, keys=(tpch.O_ORDERKEY,))
    xt = c.submit_exchange(
        "s0", pack, merge, table="orders", binding="orders",
        merge_binding="partials", merge_valid_meta="merge.num_groups")
    assert _fp(xt.result(timeout=120)) == ref_fp
    assert xt.fingerprint == ref_fp
    assert REGISTRY.counter("cluster.exchanges").value == 1
    assert REGISTRY.counter("cluster.exchange_merges").value == 1
    # direct is the default rung: the flight payloads went host-to-host
    assert REGISTRY.counter("cluster.exchanges_direct").value == 1
    assert REGISTRY.counter("cluster.exchange_direct_fallbacks").value == 0
    assert REGISTRY.counter("exchange.bytes_direct").value > 0
    assert REGISTRY.counter("exchange.bytes_routed").value == 0
    # a repeated exchange must come back bit-identical (memo-checked)
    xt2 = c.submit_exchange(
        "s1", pack, merge, table="orders", binding="orders",
        merge_binding="partials", merge_valid_meta="merge.num_groups")
    assert _fp(xt2.result(timeout=120)) == ref_fp
    assert REGISTRY.counter("fleet.identity_mismatch").value == 0
    time.sleep(0.3)  # a fresh liveness pong carries the leak report
    assert c.leaked_bytes() == 0


def test_sigkill_host_mid_exchange_fails_over_bit_identical():
    orders = _orders()
    ref_fp = _fp(tpch.tpch_q13_local(orders, 2))
    pack, merge = tpch.q13_exchange_plans(2)
    with cluster.QueryCluster(2, per_replica_env={
            "h0": {SERVE_DELAY: "1500"}}) as c:
        assert c.wait_live(timeout=120) == 2
        info = c.register_table("orders", orders, keys=(tpch.O_ORDERKEY,))
        assert info["owners"][0] == "h0"
        xt = c.submit_exchange(
            "s0", pack, merge, table="orders", binding="orders",
            merge_binding="partials", merge_valid_meta="merge.num_groups",
            direct=False)  # pin the routed rung: this test is its chaos
        t0 = xt.tickets[0]
        deadline = time.monotonic() + 15
        while time.monotonic() < deadline and t0.replica != "h0":
            time.sleep(0.01)
        assert t0.replica == "h0"
        time.sleep(0.2)  # inside h0's serve hold: the pack is in flight
        c._host("h0").proc.send_signal(signal.SIGKILL)
        res = xt.result(timeout=120)
        assert _fp(res) == ref_fp
        assert t0.dispatches == 2  # failed over to the survivor
        assert REGISTRY.counter("cluster.host_deaths").value == 1
        time.sleep(0.3)
        assert c.leaked_bytes() == 0


def test_skewed_exchange_under_tight_budget_takes_spill_merge(mesh):
    """Raw-row exchange (the pack child is a Scan) concentrates ~90% of
    the rows on one destination; a merge budget below that destination's
    flight total forces the router-side spill-aware chunked merge —
    still value-identical to the local partitioned groupby, zero leaked
    bytes."""
    tbl = _skewed_table(2400)
    rowid = Column.from_numpy(np.arange(2400, dtype=np.int64))
    tbl = Table(list(tbl.columns) + [rowid])
    oracle = xch.partitioned_groupby(tbl, [0], [(1, "sum")], parts=2)
    pack = fusion.Plan("skew_pack", fusion.Exchange(
        fusion.Scan("rows"), keys=(0,), parts=2, label="exchange"))
    merge = fusion.Plan("skew_merge", fusion.GroupBy(
        fusion.Scan("partials"), (0,), ((1, "sum"),),
        max_groups=None, label="merge"))
    # budget: above any single flight (the chunked merge reserves each
    # chunk fail-loud) but below the hot destination's two-flight total
    from spark_rapids_jni_tpu.parallel import dcn

    flight = max(_table_nbytes(d)
                 for shard in dcn.partition_for_slices(tbl, [2], 2)
                 for d in xch.exchange_local(shard, [0], 2) if d.num_rows)
    budget = int(flight * 1.5)
    c = mesh
    # shard by the unique rowid so BOTH hosts hold hot-key rows and
    # the hot destination receives two large flights
    c.register_table("rows", tbl, keys=(2,))
    xt = c.submit_exchange(
        "s2", pack, merge, table="rows", binding="rows",
        merge_binding="partials", merge_valid_meta="merge.num_groups",
        merge_budget_bytes=budget,
        direct=False)  # the ROUTER-side spill merge is under test here
    res = xt.result(timeout=120)
    assert _rows(res) == _rows(oracle)
    assert REGISTRY.counter("cluster.exchange_spill_merges").value >= 1
    spills = [r for r in ring_events()
              if r.get("op") == "cluster.exchange"
              and r.get("event") == "spill_merge"]
    assert spills
    time.sleep(0.3)
    assert c.leaked_bytes() == 0


# ---------------------------------------------------------------------------
# 5. direct host-to-host flights: grants, manifests, fallback ladder
# ---------------------------------------------------------------------------


def test_peer_flight_server_rejects_unsigned_dials():
    """The peer gateway refuses a dial whose grant was not HMAC-signed
    by THIS boot's supervisor — before a single flight byte is read —
    and a grant for one (xid, src, dest, part) does not authorize any
    other. The properly signed dial lands in the mailbox."""
    from spark_rapids_jni_tpu.parallel import dcn

    key = dcn.grant_key("boot-secret")
    srv = dcn.PeerFlightServer(key, dest="h1")
    try:
        tbl = _mixed_table(64)
        blob = xch.serialize_flight(tbl, op="test.peer")
        fp = dcn.flight_fingerprint(blob)

        def _dial(grant):
            dcn.send_peer_flight(
                (srv.host, srv.port),
                {"xid": "x1", "src": "p0", "part": 0, "grant": grant,
                 "fp": fp}, blob, retries=2, delay_s=0.01)

        # forged grant (wrong boot secret): refused, counted, recorded
        forged = dcn.sign_grant(dcn.grant_key("wrong-secret"),
                                xid="x1", src="p0", dest="h1", part=0)
        with pytest.raises((resilience.ResilienceError, OSError)):
            _dial(forged)
        assert REGISTRY.counter("cluster.rejected_dials").value == 1
        rej = [r for r in ring_events()
               if r.get("op") == "cluster.peer_gateway"
               and r.get("event") == "rejected_dial"]
        assert rej and rej[0]["xid"] == "x1"
        # a real grant for a DIFFERENT destination part: also refused
        wrong = dcn.sign_grant(key, xid="x1", src="p0", dest="h1",
                               part=5)
        with pytest.raises((resilience.ResilienceError, OSError)):
            _dial(wrong)
        assert REGISTRY.counter("cluster.rejected_dials").value == 2
        assert srv._mail == {}  # nothing was accepted
        # the supervisor-signed grant delivers
        good = dcn.sign_grant(key, xid="x1", src="p0", dest="h1", part=0)
        _dial(good)
        flights = srv.wait_flights("x1", 0, ["p0"], timeout=30)
        assert dcn.flight_fingerprint(flights["p0"]) == fp
        assert REGISTRY.counter("exchange.peer_flights_recv").value == 1
        srv.discard("x1")
        assert srv._mail == {}
    finally:
        srv.close()


def test_direct_exchange_beats_routed_on_supervisor_link_bytes(mesh):
    """The heart of the PR: a warmed direct exchange moves strictly
    fewer bytes over the supervisor link than the same exchange routed
    — the flight payloads go host-to-host and the supervisor sees only
    manifests and acks. Both modes are bit-identical to each other."""
    orders = _orders(seed=7)
    ref_fp = _fp(tpch.tpch_q13_local(orders, 2))
    pack, merge = tpch.q13_exchange_plans(2)
    c = mesh
    c.register_table("xorders", orders, keys=(tpch.O_ORDERKEY,))
    set_option("fleet.result_memo_entries", 0)
    try:
        def run(sid, direct):
            xt = c.submit_exchange(
                sid, pack, merge, table="xorders", binding="orders",
                merge_binding="partials",
                merge_valid_meta="merge.num_groups", direct=direct)
            return _fp(xt.result(timeout=120))

        # warm both modes first: first-run compiles stretch the rounds
        # and the ping/pong chatter under them would swamp the
        # steady-state link measurement
        assert run("w0", True) == ref_fp
        assert run("w1", False) == ref_fp
        link = REGISTRY.counter("fleet.link_bytes")
        base = link.value
        assert run("m0", True) == ref_fp
        direct_bytes = link.value - base
        base = link.value
        assert run("m1", False) == ref_fp
        routed_bytes = link.value - base
        assert direct_bytes < routed_bytes, (direct_bytes, routed_bytes)
        assert REGISTRY.counter("exchange.bytes_direct").value > 0
    finally:
        reset_option("fleet.result_memo_entries")


def test_midplan_single_plan_form_over_the_mesh(mesh):
    """Planner-placed form end-to-end: ONE q13 plan with an interior
    Exchange submits without a hand-split pair — the supervisor splits
    it, resolves ``parts=0`` to the mesh width, and the result is
    byte-for-byte the single-host oracle."""
    c = mesh
    orders = _orders(seed=13)
    ref_fp = _fp(tpch.tpch_q13_local(orders, 2))
    c.register_table("morders", orders, keys=(tpch.O_ORDERKEY,))
    xt = c.submit_exchange("m2", tpch.q13_midplan_plan(0),
                           table="morders", binding="orders")
    assert _fp(xt.result(timeout=120)) == ref_fp
    time.sleep(0.3)
    assert c.leaked_bytes() == 0


def test_peer_dial_failure_falls_back_rung_by_rung(mesh):
    """The classified fallback ladder, bit-identical at every rung.
    Rung 1: peers unreachable — each cross-host flight re-routes via
    the supervisor INSIDE the direct protocol (the manifest marks it
    routed). Rung 2: no peer gateway at all — the direct attempt
    classifies and the WHOLE exchange drops to the routed path."""
    c = mesh
    orders = _orders(seed=11)
    ref_fp = _fp(tpch.tpch_q13_local(orders, 2))
    pack, merge = tpch.q13_exchange_plans(2)
    c.register_table("forders", orders, keys=(tpch.O_ORDERKEY,))
    saved = dict(c._peer_addrs)
    assert len(saved) == 2

    def run(sid):
        xt = c.submit_exchange(
            sid, pack, merge, table="forders", binding="orders",
            merge_binding="partials", merge_valid_meta="merge.num_groups")
        return _fp(xt.result(timeout=120))

    try:
        # rung 1: nothing listens at the peer addresses
        dead = socket.socket()
        dead.bind(("127.0.0.1", 0))
        port = dead.getsockname()[1]
        dead.close()
        c._peer_addrs.clear()
        c._peer_addrs.update({k: ("127.0.0.1", port) for k in saved})
        assert run("f0") == ref_fp
        assert REGISTRY.counter("exchange.bytes_routed").value > 0
        assert REGISTRY.counter("exchange.bytes_direct").value > 0
        assert (REGISTRY.counter("cluster.exchange_direct_fallbacks")
                .value) == 0  # the direct protocol itself completed
        # rung 2: no peer gateways known at all
        c._peer_addrs.clear()
        assert run("f1") == ref_fp
        assert (REGISTRY.counter("cluster.exchange_direct_fallbacks")
                .value) == 1
        fb = [r for r in ring_events()
              if r.get("event") == "direct_fallback"]
        assert fb
        time.sleep(0.3)
        assert c.leaked_bytes() == 0
    finally:
        c._peer_addrs.clear()
        c._peer_addrs.update(saved)


def test_sigkill_host_mid_direct_flight_falls_back_bit_identical():
    """Chaos on the direct rung: h0 is SIGKILLed while holding a direct
    pack inside its serve-delay window. The supervisor's collect fails
    classified, the exchange drops to the routed rung on the survivor,
    and the result is byte-for-byte the oracle — zero leaked
    reservations."""
    orders = _orders()
    ref_fp = _fp(tpch.tpch_q13_local(orders, 2))
    pack, merge = tpch.q13_exchange_plans(2)
    with cluster.QueryCluster(2, per_replica_env={
            "h0": {SERVE_DELAY: "1500"}}) as c:
        assert c.wait_live(timeout=120) == 2
        info = c.register_table("orders", orders, keys=(tpch.O_ORDERKEY,))
        assert info["owners"][0] == "h0"
        out = {}
        done = threading.Event()

        def _run():
            try:
                xt = c.submit_exchange(
                    "s0", pack, merge, table="orders", binding="orders",
                    merge_binding="partials",
                    merge_valid_meta="merge.num_groups")
                out["fp"] = _fp(xt.result(timeout=120))
            except BaseException as exc:  # noqa: BLE001 - surfaced below
                out["err"] = exc
            finally:
                done.set()

        th = threading.Thread(target=_run)
        th.start()
        time.sleep(0.5)  # inside h0's xpack hold: the flight is pending
        c._host("h0").proc.send_signal(signal.SIGKILL)
        assert done.wait(120)
        th.join(10)
        assert out.get("err") is None, repr(out.get("err"))
        assert out["fp"] == ref_fp
        assert (REGISTRY.counter("cluster.exchange_direct_fallbacks")
                .value) >= 1
        assert REGISTRY.counter("cluster.host_deaths").value == 1
        time.sleep(0.3)
        assert c.leaked_bytes() == 0


def _wait_slot(slot):
    """One pending direct-exchange wait and the minimal supervisor state
    ``QueryCluster._x_collect`` touches."""
    import types

    key = ("x", "xpack_done", 0)
    sup = types.SimpleNamespace(_lock=threading.Lock(),
                                _x_waits={key: None})
    return sup, (key, threading.Event(), slot, "h0")


def test_direct_timeout_times_the_wire_not_the_plan():
    """``exchange.direct_timeout_s`` must not count a worker's plan (a
    cold pack plan compiles for minutes on a chip, which sent a cold
    four-chip cluster down the routed lane): while the worker reports
    ``busy`` only the caller's deadline bounds the wait; on the wire the
    cap trips the classified fallback."""
    sup, wait = _wait_slot({"busy": True})
    t0 = time.monotonic()
    with pytest.raises(TimeoutError, match="caller deadline"):
        cluster.QueryCluster._x_collect(sup, wait, t0 + 0.4, 0.1, "xpack")
    assert time.monotonic() - t0 >= 0.4  # four caps long, never tripped
    assert not sup._x_waits
    sup, wait = _wait_slot({})  # not busy: the worker is flying
    with pytest.raises(resilience.TransportError, match="stalled on the "
                       "wire"):
        cluster.QueryCluster._x_collect(sup, wait, None, 0.1, "xpack")
    sup, wait = _wait_slot({"busy": True, "status": "ok", "fps": {}})
    wait[1].set()
    assert cluster.QueryCluster._x_collect(
        sup, wait, None, 0.1, "xpack")["fps"] == {}


@pytest.mark.slow
def test_slow_pack_plan_stays_on_the_direct_lane():
    """End to end: h0 holds its pack for 2.5 s under a 0.5 s
    ``exchange.direct_timeout_s``. The hold is the worker's compute
    window (``xbusy``), so the exchange completes direct, bit-identical,
    with no fallback."""
    orders = _orders()
    ref_fp = _fp(tpch.tpch_q13_local(orders, 2))
    pack, merge = tpch.q13_exchange_plans(2)
    set_option("exchange.direct_timeout_s", 0.5)
    try:
        with cluster.QueryCluster(2, per_replica_env={
                "h0": {SERVE_DELAY: "2500"}}) as c:
            assert c.wait_live(timeout=120) == 2
            c.register_table("orders", orders, keys=(tpch.O_ORDERKEY,))
            xt = c.submit_exchange(
                "s0", pack, merge, table="orders", binding="orders",
                merge_binding="partials",
                merge_valid_meta="merge.num_groups")
            assert _fp(xt.result(timeout=120)) == ref_fp
    finally:
        reset_option("exchange.direct_timeout_s")
    assert REGISTRY.counter("cluster.exchanges_direct").value == 1
    assert REGISTRY.counter("cluster.exchange_direct_fallbacks").value == 0
