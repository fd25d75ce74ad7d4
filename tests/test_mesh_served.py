"""A request served across the chips of a mesh: a ``Plan`` bound to tables
whose rows are sharded over a mesh axis goes through ``QueryServer`` /
``Session.submit`` like any other and finishes at ``("fused", 0, 0)``. The
sharding of the bound buffers is the only signal. On the CPU's eight
virtual devices, four of them the mesh."""

import hashlib
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from spark_rapids_jni_tpu.columnar import Column, Table  # noqa: E402
from spark_rapids_jni_tpu.models import tpch  # noqa: E402
from spark_rapids_jni_tpu.parallel.mesh import (  # noqa: E402
    EXEC_AXIS,
    executor_mesh,
    row_mesh,
    table_row_mesh,
)
from spark_rapids_jni_tpu.runtime import (  # noqa: E402
    fusion,
    memory,
    resultcache,
    server,
)
from spark_rapids_jni_tpu.telemetry import REGISTRY  # noqa: E402

CHIPS = 4
@pytest.fixture(scope="module")
def mesh():
    return executor_mesh(CHIPS)


@pytest.fixture(autouse=True)
def _telemetry_on():
    from spark_rapids_jni_tpu.utils.config import reset_option, set_option

    set_option("telemetry.enabled", True)
    yield
    reset_option("telemetry.enabled")


def _sharded(mesh):
    return NamedSharding(mesh, P(EXEC_AXIS))


def _lineitem(rows, seed, mesh=None, null_every=0):
    """(host columns, Table): the benchmark's own lineitem from ``seed``;
    with ``mesh`` its rows sharded over it; with ``null_every`` every
    such row's ship date null (the row then falls out of every group)."""
    from benchmark import resolve

    maker = resolve.module("tables", "lineitem")
    arrays = maker.make(rows, seed)
    cols = maker.host_copy(arrays)
    if mesh is not None:
        arrays = jax.device_put(arrays, _sharded(mesh))
    table = maker.to_table(arrays)
    if null_every:
        alive = np.arange(rows) % null_every != 0
        valid = jnp.asarray(alive)
        if mesh is not None:
            valid = jax.device_put(valid, _sharded(mesh))
        ship = table.columns[tpch.L_SHIPDATE]
        table = Table(list(table.columns[:tpch.L_SHIPDATE]) + [
            Column(ship.dtype, ship.data, valid)])
        cols = {k: v[alive] for k, v in cols.items()}
    return cols, table


def _serve(plan, table, budget=4 << 30):
    before = REGISTRY.counters()
    with server.QueryServer(budget_bytes=budget) as srv:
        ticket = srv.session("t").submit(plan, {"lineitem": table})
        result = ticket.result()
        jax.block_until_ready(result.table.column(0).data)
    moved = {k: v - before.get(k, 0) for k, v in REGISTRY.counters().items()
             if v != before.get(k, 0)}
    return ticket, result, moved


@pytest.mark.parametrize("null_every", [0, 7], ids=["no_nulls", "nulls"])
@pytest.mark.parametrize("rows", [4096, 6000])
def test_served_distributed_q1(mesh, rows, null_every):
    """Against the benchmark's numpy reference, and against the general q1
    one chip serves over the same rows, buffer for buffer."""
    from benchmark import harness
    from benchmark import reference_q1 as ref

    seed = 2**31 + rows + null_every
    cols, sharded = _lineitem(rows, seed, mesh, null_every)
    assert table_row_mesh(sharded) == (mesh, EXEC_AXIS)
    ticket, got, moved = _serve(tpch._q1_distributed_plan(), sharded)
    assert (ticket.tier, ticket.rung, ticket.steps) == ("fused", 0, 0)
    assert not harness._moved_fallbacks(moved, native=False), moved
    numbers = ref.compare(ref.read_answer(got.table), ref.q1(cols))
    assert numbers["q1.int_mismatches"] == 0
    assert numbers["q1.avg_max_rel_err"] <= 1e-12
    # one region, one per-shard pad, one shuffle of at most 4 x 64 rows
    assert moved["fusion.regions"] == 1
    assert moved.get("dispatch.hit.pad_sharded", 0) + moved.get(
        "dispatch.compile.pad_sharded", 0) == 1
    assert moved["shuffle.exchanges"] == 1
    assert 6 <= moved["shuffle.rows"] <= CHIPS * 7
    assert moved["shuffle.bytes"] > 0
    assert moved["groupby.groups"] == 7   # six, and the filtered rows' null
    # every chip holds the whole answer; no chip held the whole table
    assert all(len(c.data.sharding.device_set) == CHIPS
               and c.data.sharding.is_fully_replicated
               for c in got.table.columns)
    # the one-chip general q1 over the same rows: the same table. (Key
    # bytes under a null validity are unspecified: the Column contract.)
    _, one = _lineitem(rows, seed, None, null_every)
    ticket1, want, _ = _serve(tpch._q1_plan(), one)
    assert (ticket1.tier, ticket1.rung, ticket1.steps) == ("fused", 0, 0)
    assert got.table.num_rows == want.table.num_rows == 64
    for i, (g, w) in enumerate(zip(got.table.columns, want.table.columns)):
        assert g.dtype == w.dtype
        gv, wv = np.asarray(g.valid_mask()), np.asarray(w.valid_mask())
        assert np.array_equal(gv, wv), i
        gd, wd = np.asarray(g.data), np.asarray(w.data)
        assert gd.dtype == wd.dtype
        assert np.array_equal(gd, wd) if i >= 2 else np.array_equal(
            gd[gv], wd[wv]), i


def test_second_submit_hits_every_executable_and_then_the_cache(mesh):
    """The same shapes compile nothing; the same bytes are a cache hit."""
    _, a = _lineitem(4096, 11, mesh)
    _, b = _lineitem(4096, 12, mesh)
    plan = tpch._q1_distributed_plan()
    with server.QueryServer(budget_bytes=4 << 30) as srv:
        s = srv.session("t")
        s.submit(plan, {"lineitem": a}).result()
        before = REGISTRY.counters()
        s.submit(plan, {"lineitem": b}).result()
        s.submit(plan, {"lineitem": a}).result()
        after = REGISTRY.counters()
    assert after.get("dispatch.compile", 0) == before.get(
        "dispatch.compile", 0)
    assert after["cache.hit"] == before.get("cache.hit", 0) + 1


def test_unsharded_binding_of_the_same_plan_runs_on_one_chip():
    """Bound to a table on one device the plan is one more general q1: no
    shuffle is counted, the result is the sharded run's."""
    cols, one = _lineitem(4096, 5)
    _, got, moved = _serve(tpch._q1_distributed_plan(), one)
    from benchmark import reference_q1 as ref

    assert ref.compare(ref.read_answer(got.table), ref.q1(cols))[
        "q1.int_mismatches"] == 0
    assert "shuffle.exchanges" not in moved
    assert "dispatch.compile.pad_sharded" not in moved


@pytest.mark.parametrize("dtype", [np.int8, np.int32, np.int64, np.bool_,
                                   np.float32])
@pytest.mark.parametrize("size", [1 << 20, (1 << 20) + 4, 3 * (1 << 19) + 8,
                                  (1 << 20) + 3])
def test_fingerprint_is_the_same_wherever_the_bytes_live(mesh, dtype, size):
    """A buffer's fingerprint on four chips, on one, and on the host: the
    same bytes as ``_digest_numpy`` gives, at sizes that split over the
    chips (digested shard by shard, where they live) and one that does not
    (such a buffer cannot be row-sharded: it is placed whole)."""
    rng = np.random.default_rng(size)
    host = rng.integers(-100, 100, size).astype(dtype)
    if host.nbytes < resultcache._DIGEST_MIN_BYTES:   # the 1-byte dtypes
        host = np.concatenate([host] * 2)
    want = hashlib.sha256()
    want.update(str(host.dtype).encode() + repr(host.shape).encode())
    want.update(resultcache._digest_tag(
        host.nbytes, resultcache._digest_numpy(host)))

    def fingerprint(buf):
        h = hashlib.sha256()
        resultcache._stage_buffer(buf)(h)
        return h.hexdigest()

    splits = host.shape[0] % CHIPS == 0
    placed = jax.device_put(host, _sharded(mesh) if splits else
                            NamedSharding(mesh, P()))
    assert (row_mesh(placed) is not None) == splits
    before = REGISTRY.counters()
    assert fingerprint(placed) == want.hexdigest()
    after = REGISTRY.counters()
    # a sharded buffer is digested on its chips: no byte of it crosses
    device = after["cache.fingerprint_device_bytes"] - before.get(
        "cache.fingerprint_device_bytes", 0)
    assert device == (host.nbytes if splits else 0)
    assert fingerprint(host) == fingerprint(jnp.asarray(host)) \
        == want.hexdigest()


def test_rolled_sharded_table_is_a_new_fingerprint_and_stays_sharded(mesh):
    """The four-chip cell's maker and freshener: the maker gives
    ``lineitem``'s values with no chip holding more than its quarter;
    ``roll`` (a traced shift) would hand back replicated columns, so the
    cell's mix names ``roll_sharded``, which keeps every column sharded and
    rolls by the same stride; the rolled table's fingerprint is the host's
    of the rolled bytes."""
    from benchmark import resolve

    maker = resolve.module("tables", "lineitem_mesh4")
    base = resolve.module("tables", "lineitem")
    over = (executor_mesh(CHIPS), EXEC_AXIS)
    rows, seed = 1 << 18, 2**31 + 99
    arrays = maker.make(rows, seed)
    for name, whole in base.make(rows, seed).items():
        assert np.array_equal(np.asarray(arrays[name]), np.asarray(whole))
        assert row_mesh(arrays[name]) == over
        assert {s.data.shape for s in arrays[name].addressable_shards} == {
            (rows // CHIPS,)}
    plain = resolve.module("fresh", "roll").Freshener(arrays, 3)
    assert all(row_mesh(a) is None for a in plain.next().values())
    assert resolve.data("mixes", "q1_distributed_fresh")["fresh"] \
        == "roll_sharded"
    fresh = resolve.module("fresh", "roll_sharded").Freshener(arrays, 3)
    assert fresh.stride == plain.stride
    rolled = fresh.next()
    for name, a in rolled.items():
        assert row_mesh(a) == over
        assert np.array_equal(np.asarray(a), np.roll(
            np.asarray(arrays[name]), fresh.stride))
    again = fresh.next()
    assert np.array_equal(np.asarray(again["l_tax"]), np.roll(
        np.asarray(arrays["l_tax"]), 2 * fresh.stride))
    on_host = maker.to_table({k: np.asarray(v) for k, v in rolled.items()})
    assert resultcache.table_fingerprint(maker.to_table(rolled)) \
        == resultcache.table_fingerprint(on_host) \
        != resultcache.table_fingerprint(maker.to_table(arrays))


def test_the_cell_runs_across_the_mesh_on_the_cpu():
    """``tests/test_benchmark_harness.py`` holds every cell to ``correct``;
    this holds the four-chip cell to having crossed chips: every request
    shuffled, and was digested where it lives."""
    from benchmark import harness
    from spark_rapids_jni_tpu.utils.config import reset_option

    before = REGISTRY.counters()
    try:
        result = harness.run_cell(
            "sf10_q1_distributed_4chip", 2**31 + 23, 0.5, False,
            platform="cpu", sizes={"lineitem": 1 << 20},
            say=lambda msg, flush=False: None)
    finally:
        for name in ("server.estimate_path", "rtfilter.path"):
            reset_option(name)
    after = REGISTRY.counters()
    assert result["correct"] is True and result["failed"] == 0
    requests = result["attempted"] + 1   # and the warm-up's

    def moved(name):
        return after.get(name, 0) - before.get(name, 0)

    assert moved("shuffle.exchanges") == requests
    assert moved("fusion.regions") == requests
    assert moved("cache.hit") == 0
    assert moved("cache.fingerprint_device_bytes") == moved(
        "cache.fingerprint_bytes") > 0
    assert moved("shuffle.bytes") == requests * 3 * 4 * 64 * (
        2 + 8 * 8 + 1 + 10)   # 3 of 4 slices of 4 x 64 slots, a row + masks


def test_the_q4_cell_exchanges_rows_on_the_cpu():
    """The shuffled-join cell crossed chips: every request exchanged both
    sides of its join, the bytes the schema and the capacity give."""
    from benchmark import harness
    from spark_rapids_jni_tpu.utils.config import reset_option

    before = REGISTRY.counters()
    try:
        result = harness.run_cell(
            "q4_shuffled_join_4chip", 2**31 + 29, 0.5, False,
            platform="cpu", sizes={"orders": 4096, "lineitem": 16384},
            say=lambda msg, flush=False: None)
    finally:
        for name in ("server.estimate_path", "rtfilter.path"):
            reset_option(name)
    after = REGISTRY.counters()
    assert result["correct"] is True and result["failed"] == 0
    requests = result["attempted"] + 1   # and the warm-up's

    def moved(name):
        return after.get(name, 0) - before.get(name, 0)

    assert moved("shuffle.exchanges") == 2 * requests
    assert moved("fusion.regions") == requests
    assert moved("cache.hit") == 0 == moved("shuffle.overflowed")
    # a chip holds 1,024 orders and 4,096 lineitems: 512 and 2,048 slots a
    # destination, 4 destinations; 3 of 4 slices leave the chip, from 4
    # chips; an orders slot is its 31 bytes, 3 validity bytes and the
    # occupied byte, a lineitem slot the key, its validity, the occupied
    assert moved("shuffle.bytes") == requests * 3 * 4 * (
        512 * (31 + 3 + 1) + 2048 * (8 + 1 + 1))
    assert moved("shuffle.capacity_rows") == requests * 4 * 4 * (512 + 2048)
    assert 0 < moved("shuffle.rows") < moved("shuffle.capacity_rows")


def test_admission_is_per_chip(mesh):
    """The budget is one chip's: a sharded table whose shard fits it is
    admitted though the whole would not be; one whose shard does not fit
    is rejected as any oversize request is."""
    _, sharded = _lineitem(8192, 21, mesh)
    _, one = _lineitem(8192, 21)
    whole, chip = memory._table_nbytes(sharded), memory.table_chip_nbytes(
        sharded)
    assert whole == 8192 * 38 and chip == whole // CHIPS
    assert memory.table_chip_nbytes(one) == whole
    plan = tpch._q1_distributed_plan()
    est_chip = fusion.estimate_hbm_bytes(plan, {"lineitem": sharded})
    est_one = fusion.estimate_hbm_bytes(plan, {"lineitem": one})
    assert est_one - est_chip == whole - chip
    budget = int(1.25 * whole)   # under the whole table's estimate
    with server.QueryServer(budget_bytes=budget,
                            estimate_headroom=2.0) as srv:
        s = srv.session("t")
        assert 2.0 * est_chip <= budget < 2.0 * est_one
        with pytest.raises(server.QueryRejected, match="whole HBM budget"):
            s.submit(plan, {"lineitem": one}).result()
        ticket = s.submit(plan, {"lineitem": sharded})
        assert ticket.result().table.num_rows == 64
        assert ticket.estimate == int(2.0 * est_chip)
    with server.QueryServer(budget_bytes=chip,   # a shard alone fills it
                            estimate_headroom=2.0) as srv:
        with pytest.raises(server.QueryRejected, match="whole HBM budget"):
            srv.session("t").submit(plan, {"lineitem": sharded}).result()


def test_float32_control_of_the_cell_is_not_correct():
    from benchmark import control, resolve

    numbers = control.control_numbers(
        "sf10_q1_distributed_4chip", 2**31 + 5, platform="cpu",
        sizes={"lineitem": 4096})["q1_distributed"]
    limits = resolve.module("plans", "q1_distributed").LIMITS
    assert numbers["q1.int_mismatches"] > limits["q1.int_mismatches"] == 0
    assert numbers["q1.avg_max_rel_err"] > limits["q1.avg_max_rel_err"]


def test_plans_without_a_lowering_run_as_before(mesh):
    """A plan the mesh has no lowering for (a sort of sharded rows, an
    aggregate that does not merge) keeps the path it had."""
    nodes = fusion._topo(tpch._q1_plan().root)   # its groupby takes means
    assert fusion._mesh_placement(
        nodes, fusion._resolve_statics(nodes, {"lineitem": 4096})) is None
    nodes = fusion._topo(tpch._q1_distributed_plan().root)
    place = fusion._mesh_placement(
        nodes, fusion._resolve_statics(nodes, {"lineitem": 4096}))
    assert [place[id(n)] for n in nodes] == [
        fusion.SHARDED, fusion.SHARDED, fusion.WHOLE, fusion.WHOLE,
        fusion.WHOLE]
    sort = fusion.Plan("s", fusion.Sort(fusion.Scan("lineitem"), (6,)))
    nodes = fusion._topo(sort.root)
    assert fusion._mesh_placement(nodes, {}) is None
    # q4: sharded up to and including the join, whole from the groupby
    nodes = fusion._topo(tpch._q4_plan().root)
    place = fusion._mesh_placement(nodes, fusion._resolve_statics(
        nodes, {"orders": 4096, "lineitem": 16384}))
    assert [(type(n).__name__, place[id(n)]) for n in nodes] == [
        ("Scan", fusion.SHARDED), ("Filter", fusion.SHARDED),
        ("Scan", fusion.SHARDED), ("Filter", fusion.SHARDED),
        ("Join", fusion.SHARDED), ("GroupBy", fusion.WHOLE),
        ("Sort", fusion.WHOLE)]
    # an outer join (a NULL-keyed row has to come out), a join with one
    # whole side and a limit of sharded rows are still without a lowering
    count = ((0, "count"),)

    def counted(child):
        nodes = fusion._topo(fusion.GroupBy(child, (0,), count,
                                            max_groups=64, label="g"))
        return fusion._mesh_placement(nodes, fusion._resolve_statics(
            nodes, {"lineitem": 4096, "orders": 1024}))

    scans = fusion.Scan("orders"), fusion.Scan("lineitem")
    for how in ("inner", "left_semi", "left_anti"):
        assert counted(fusion.Join(*scans, (0,), (0,), 64, how=how,
                                   label="j")) is not None
    for how in ("left", "right", "full"):
        assert counted(fusion.Join(*scans, (0,), (0,), 64, how=how,
                                   label="j")) is None
    whole = fusion.GroupBy(scans[1], (0,), count, max_groups=64, label="w")
    assert counted(fusion.Join(scans[0], whole, (0,), (0,), 64,
                               label="j")) is None
    assert counted(fusion.Limit(scans[0], 10)) is None


def test_bounded_domain_groupby_lowers_as_partial_and_psum(mesh):
    """Declared domains over sharded rows: a chip's slot table, one
    ``psum`` of it, no shuffle; equal to the one-chip planned result."""
    from spark_rapids_jni_tpu.ops.planner import scalar_domain

    plan = fusion.Plan("q1_bounded_mesh", fusion.GroupBy(
        fusion.Project(fusion.Scan("lineitem"), tpch._q1_work_table),
        (0, 1), tuple(tpch._Q1_PARTIAL_AGGS),
        domains=(scalar_domain(tpch._Q1_RF_DOMAIN),
                 scalar_domain(tpch._Q1_LS_DOMAIN)), label="plan"))
    _, sharded = _lineitem(6000, 31, mesh)
    _, one = _lineitem(6000, 31)
    ticket, got, moved = _serve(plan, sharded)
    assert (ticket.tier, ticket.rung, ticket.steps) == ("fused", 0, 0)
    assert "shuffle.exchanges" not in moved
    assert moved.get("dispatch.compile.pad_sharded", 0) + moved.get(
        "dispatch.hit.pad_sharded", 0) == 1
    _, want, _ = _serve(plan, one)
    assert got.meta["plan.lowered"] == want.meta["plan.lowered"] == "bounded"
    assert not bool(got.meta["plan.domain_miss"])
    assert np.array_equal(np.asarray(got.meta["plan.present"]),
                          np.asarray(want.meta["plan.present"]))
    for g, w in zip(got.table.columns, want.table.columns):
        assert np.array_equal(np.asarray(g.valid_mask()),
                              np.asarray(w.valid_mask()))
        assert np.array_equal(np.asarray(g.data), np.asarray(w.data))


def _price_as_key(tbl, far):
    """(the price, every seventh value ``far`` higher; the quantity)."""
    price = tbl.column(tpch.L_EXTENDEDPRICE)
    key = price.data + jnp.where(price.data % 7 == 0, far, 0)
    return Table([Column(price.dtype, key), tbl.column(tpch.L_QUANTITY)])


@pytest.mark.parametrize("far", [0, 2 ** 32], ids=["one_word", "wide"])
def test_a_lone_int64_key_over_the_mesh_says_how_it_was_ordered(mesh, far):
    """A key nobody declared a range for, grouped over sharded rows: a
    chip's partial orders its rows' keys as ONE word where ITS rows allow
    (``ops/sort.py _lone_key_order`` under ``shard_map``: the reductions
    and the conditional are a chip's own) and the node reports whether any
    chip did; with keys that straddle a high word none does. The groups
    are the one-chip result's either way."""
    plan = fusion.Plan(f"by_price_{far}", fusion.GroupBy(
        fusion.Project(fusion.Scan("lineitem"), _price_as_key,
                       params=(far,)), (0,),
        ((1, "sum"), (1, "count")), max_groups=8192, label="by_price"))
    _, sharded = _lineitem(6000, 49, mesh)
    _, one = _lineitem(6000, 49)
    ticket, got, moved = _serve(plan, sharded)
    assert (ticket.tier, ticket.rung, ticket.steps) == ("fused", 0, 0)
    assert moved["shuffle.exchanges"] == 1
    assert bool(got.meta["by_price.key_one_word"]) == (far == 0)
    assert moved.get("groupby.key_one_word", 0) == (far == 0)
    _, want, one_moved = _serve(plan, one)
    assert one_moved.get("groupby.key_one_word", 0) == (far == 0)
    groups = int(want.meta["by_price.num_groups"])
    assert int(got.meta["by_price.num_groups"]) == groups > 1024

    def rows(res):
        cols = [np.asarray(c.data)[:groups] for c in res.table.columns]
        return sorted(zip(*(c.tolist() for c in cols)))

    assert rows(got) == rows(want)


def test_step_inside_a_callers_shard_map(mesh):
    """``q1_distributed_step`` is the plan seen by one chip, for a caller
    that builds its own program over a mesh (one that spans processes)."""
    li = tpch.lineitem_table(2048, seed=7)
    from spark_rapids_jni_tpu.parallel.distributed import shard_table

    out = jax.jit(jax.shard_map(
        tpch.q1_distributed_step, mesh=mesh, in_specs=(P(EXEC_AXIS),),
        out_specs=P(), check_vma=False))(shard_table(li, mesh))
    served = tpch.tpch_q1_distributed(li, mesh)
    for g, w in zip(out.columns, served.columns):
        assert np.array_equal(np.asarray(g.data), np.asarray(w.data))
    oracle = tpch.tpch_q1_numpy(li)
    rf, ls = out.column(0).to_pylist(), out.column(1).to_pylist()
    got = {(rf[i], ls[i]): out.column(9).to_pylist()[i]
           for i in range(out.num_rows) if rf[i] is not None}
    assert got == {k: v["count"] for k, v in oracle.items()}


def test_mesh_reduce_by_chip_and_stage():
    """The benchmark's reducer of a four-chip trace, on made-up planes:
    a stage's time is its union inside the requests on a chip, averaged
    over the chips; the skew is the slowest chip against the fastest."""
    from benchmark import mesh_reduce

    scope = "jit(region_q)/shard_map/region.q/groupby/{}/sort:"
    assert mesh_reduce.stage_of(scope.format("partial")) == "partial"
    assert mesh_reduce.stage_of("jit(region_q)/region.q/groupby/sort") is None
    assert mesh_reduce.stage_of(None) is None
    requests = [(0, 1000), (2000, 3000)]
    planes = {
        "/device:TPU:0": [(0, 400, scope.format("partial")),
                          (300, 500, scope.format("partial")),
                          (500, 600, scope.format("exchange")),
                          (900, 1100, scope.format("merge")),
                          (1500, 1600, None)],
        "/device:TPU:1": [(0, 300, scope.format("partial")),
                          (2000, 2100, scope.format("collect"))],
    }
    reduced = mesh_reduce.reduce_planes(planes, requests)
    assert reduced["busy_s"] == {"/device:TPU:0": 700e-9,
                                 "/device:TPU:1": 400e-9}
    assert reduced["stage_s"] == pytest.approx({
        "partial": 400e-9, "exchange": 50e-9, "merge": 50e-9,
        "collect": 50e-9})
