"""A join whose rows cross chips on the served path: a ``Plan`` whose
``Join`` has two children row-sharded over a mesh lowers, inside the one
fused region, as a shuffled join (``parallel.distributed.shuffled_join``:
both sides through ``hash_shuffle`` by the join key, the one-chip join of
what landed). TPC-H q4, unchanged, is the plan; the sharding of the bound
buffers is the only signal. On four of the CPU's eight virtual devices."""

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

from benchmark import harness, reference_q4, resolve  # noqa: E402
from spark_rapids_jni_tpu import types as t  # noqa: E402
from spark_rapids_jni_tpu.columnar import Column, Table  # noqa: E402
from spark_rapids_jni_tpu.models import tpch  # noqa: E402
from spark_rapids_jni_tpu.parallel import distributed  # noqa: E402
from spark_rapids_jni_tpu.parallel.mesh import (  # noqa: E402
    EXEC_AXIS,
    executor_mesh,
    row_mesh,
    table_row_mesh,
)
from spark_rapids_jni_tpu.runtime import fusion, resilience, server  # noqa: E402
from spark_rapids_jni_tpu.telemetry import REGISTRY  # noqa: E402

CHIPS = 4
# 750 and 3,025 rows a chip: buckets of 1,024 and 4,096, both padded
ORDERS, ITEMS = 3000, 12100
QUARTER = reference_q4.QUARTER


@pytest.fixture(scope="module")
def mesh():
    return executor_mesh(CHIPS)


@pytest.fixture(autouse=True)
def _telemetry_on():
    from spark_rapids_jni_tpu.utils.config import reset_option, set_option

    set_option("telemetry.enabled", True)
    yield
    reset_option("telemetry.enabled")


def _host(seed: int, orders: int = ORDERS, items: int = ITEMS) -> dict:
    """``{table: host copy}`` of seeded dbgen-rule tables by the
    benchmark's own makers, with NULL keys on both sides and an order all
    of whose (at least five) lineitems are late."""
    config = {"tables": {"orders": {"maker": "orders_q4", "rows": orders},
                         "lineitem": {"maker": "lineitem_q4", "rows": items}}}
    host = {name: {c: np.array(a) for c, a in maker.host_copy(arrays).items()}
            for name, (maker, _, arrays)
            in harness.make_tables(config, seed, {}).items()}
    rng = np.random.default_rng(seed)
    host["orders"]["o_orderkey_valid"] = rng.random(orders) > 0.1
    host["lineitem"]["l_orderkey_valid"] = rng.random(items) > 0.1
    host["orders"]["o_orderkey_valid"][0] = True
    host["orders"]["o_orderdate"][0] = QUARTER[0]
    host["lineitem"]["l_orderkey"][:5] = host["orders"]["o_orderkey"][0]
    host["lineitem"]["l_commitdate"][:5] = 9000
    host["lineitem"]["l_receiptdate"][:5] = 9001
    return host


def _device(host: dict, mesh=None) -> dict:
    """The two tables on one device, or with ``mesh`` every buffer (key
    validity and the priority's bytes too) row-sharded over it."""
    def put(a):
        a = jnp.asarray(a)
        return a if mesh is None else jax.device_put(
            a, NamedSharding(mesh, P(EXEC_AXIS)))

    o, li = host["orders"], host["lineitem"]
    return {
        "orders": Table([
            Column(t.INT64, put(o["o_orderkey"]),
                   put(o["o_orderkey_valid"])),
            Column(t.TIMESTAMP_DAYS, put(o["o_orderdate"])),
            Column(t.STRING, put(o["o_orderpriority_len"]),
                   chars=put(o["o_orderpriority"]))]),
        "lineitem": Table([
            Column(t.INT64, put(li["l_orderkey"]),
                   put(li["l_orderkey_valid"])),
            Column(t.TIMESTAMP_DAYS, put(li["l_commitdate"])),
            Column(t.TIMESTAMP_DAYS, put(li["l_receiptdate"]))])}


def _arrow(host: dict) -> tuple:
    """The tables ``tpch_q4_numpy`` reads (it knows no NULL key: the rows
    with one, which match nothing and count nowhere, are left out)."""
    o, li = host["orders"], host["lineitem"]
    ok, lk = o["o_orderkey_valid"], li["l_orderkey_valid"]
    text = [bytes(c[:n]).decode() for c, n in zip(
        o["o_orderpriority"][ok], o["o_orderpriority_len"][ok])]
    width = max(tpch.L12_ORDERKEY, tpch.L12_COMMITDATE,
                tpch.L12_RECEIPTDATE) + 1
    key = Column(t.INT64, jnp.asarray(li["l_orderkey"][lk]))
    cols = [key] * width
    cols[tpch.L12_COMMITDATE] = Column(
        t.TIMESTAMP_DAYS, jnp.asarray(li["l_commitdate"][lk]))
    cols[tpch.L12_RECEIPTDATE] = Column(
        t.TIMESTAMP_DAYS, jnp.asarray(li["l_receiptdate"][lk]))
    return (Table([Column(t.INT64, jnp.asarray(o["o_orderkey"][ok])),
                   Column(t.TIMESTAMP_DAYS, jnp.asarray(o["o_orderdate"][ok])),
                   Column.from_pylist(text, t.STRING)]), Table(cols))


def _serve(plan, bindings):
    """``(ticket, result or the error it ended in, counters moved)``."""
    before = REGISTRY.counters()
    with server.QueryServer(budget_bytes=4 << 30) as srv:
        ticket = srv.session("t").submit(plan, bindings)
        try:
            result = ticket.result()
            jax.block_until_ready(result.table.column(0).data)
        except resilience.ResilienceError as refused:
            result = refused
    moved = {k: v - before.get(k, 0) for k, v in REGISTRY.counters().items()
             if v != before.get(k, 0)}
    return ticket, result, moved


def _q4_with(how: str) -> fusion.Plan:
    """``tpch._q4_plan`` with its ``exists`` another kind of join."""
    sort = tpch._q4_plan(*QUARTER).root
    groupby = sort.child
    return fusion.Plan(f"tpch_q4_{how}", sort._replace(
        child=groupby._replace(child=groupby.child._replace(how=how))))


def _same_tables(got: Table, want: Table) -> None:
    assert got.num_rows == want.num_rows
    for g, w in zip(got.columns, want.columns):
        gv, wv = np.asarray(g.valid_mask()), np.asarray(w.valid_mask())
        assert g.dtype == w.dtype and np.array_equal(gv, wv)
        assert np.array_equal(np.asarray(g.data)[gv], np.asarray(w.data)[wv])
        if g.chars is not None:
            assert np.array_equal(np.asarray(g.chars)[gv],
                                  np.asarray(w.chars)[wv])


@pytest.mark.parametrize("seed", [5200, 2**31 + 5201, 2**32 + 5202])
def test_served_q4_over_the_mesh(mesh, seed):
    """The SAME plan bound to row-sharded tables: one fused region over
    the mesh, both sides exchanged, equal to both references and to the
    one-chip answer; the join's counters are the whole request's."""
    host = _host(seed)
    plan = tpch._q4_plan(*QUARTER)
    bindings = _device(host, mesh)
    assert all(table_row_mesh(b) == (mesh, EXEC_AXIS)
               for b in bindings.values())
    ticket, got, moved = _serve(plan, bindings)
    assert (ticket.tier, ticket.rung, ticket.steps) == ("fused", 0, 0)
    assert not harness._moved_fallbacks(moved, native=False), moved
    want = reference_q4.q4(host)
    answer = reference_q4.read_answer(got.table)
    assert reference_q4.compare(answer, want) == {
        "q4.count_mismatches": 0, "q4.out_of_order": 0}
    assert answer["rows"] == want["rows"] and len(want["rows"]) == 5
    assert {k.decode(): v for k, v in answer["groups"].items()} == \
        tpch.tpch_q4_numpy(*_arrow(host), *QUARTER)
    # one region, one pad of both tables (two row counts, two buckets),
    # two exchanges; nothing was gathered to one chip on the way
    assert moved["fusion.regions"] == 1
    assert moved.get("dispatch.hit.pad_sharded", 0) + moved.get(
        "dispatch.compile.pad_sharded", 0) == 1
    assert moved["shuffle.exchanges"] == 2
    assert "shuffle.overflowed" not in moved
    assert all(table_row_mesh(b) == (mesh, EXEC_AXIS)
               for b in bindings.values())
    assert all(c.data.sharding.is_fully_replicated
               and len(c.data.sharding.device_set) == CHIPS
               for c in got.table.columns)
    # what rode: the rows with a key that their WHERE kept, no other; every
    # orders column (31 B and 3 validity bytes), of lineitem the key alone
    o, li = host["orders"], host["lineitem"]
    sent_o = int(((o["o_orderdate"] >= QUARTER[0])
                  & (o["o_orderdate"] < QUARTER[1])
                  & o["o_orderkey_valid"]).sum())
    sent_l = int(((li["l_commitdate"] < li["l_receiptdate"])
                  & li["l_orderkey_valid"]).sum())
    assert moved["shuffle.rows"] == sent_o + sent_l
    assert moved["shuffle.read_bytes"] == sent_o * (31 + 3) + sent_l * (8 + 1)
    # a chip's bucket b has 2 * b / 4 slots a destination: b / 2
    slots_o, slots_l = CHIPS * 512, CHIPS * 2048
    assert moved["shuffle.capacity_rows"] == CHIPS * (slots_o + slots_l)
    assert int(got.meta["exists.shuffle_capacity"]) == CHIPS * (
        slots_o + slots_l)
    assert moved["shuffle.bytes"] == (CHIPS - 1) * (
        slots_o * (31 + 3 + 1) + slots_l * (8 + 1 + 1))
    # the one-chip answer of the same plan, and the same counters
    ticket1, one, moved1 = _serve(plan, _device(host))
    assert (ticket1.tier, ticket1.rung, ticket1.steps) == ("fused", 0, 0)
    assert "shuffle.exchanges" not in moved1
    _same_tables(got.table, one.table)
    for name in ("join.probe_rows", "join.matched_rows", "join.build_rows",
                 "join.key_narrowed", "filter.rows_in", "filter.rows_kept"):
        assert moved[name] == moved1[name], name
    assert moved["join.probe_rows"] == ORDERS
    assert moved["join.matched_rows"] == sum(want["groups"].values()) \
        == int(got.meta["exists.total"])
    assert moved["join.build_rows"] == sent_l


def test_left_anti_over_the_mesh(mesh):
    """NOT EXISTS on the same tables: an order of the quarter counts where
    no late lineitem holds its key (``count(o_orderkey)``: an order with a
    NULL key qualifies and counts nothing). Rows a ``Filter`` dropped do not
    ride, so ``total`` is the orders of the quarter that qualify."""
    host = _host(5210)
    plan = _q4_with("left_anti")
    ticket, got, moved = _serve(plan, _device(host, mesh))
    assert (ticket.tier, ticket.rung, ticket.steps) == ("fused", 0, 0)
    assert moved["shuffle.exchanges"] == 2
    o, li = host["orders"], host["lineitem"]
    late = li["l_orderkey"][(li["l_commitdate"] < li["l_receiptdate"])
                            & li["l_orderkey_valid"]]
    asked = (o["o_orderdate"] >= QUARTER[0]) & (o["o_orderdate"] < QUARTER[1])
    alone = asked & ~(np.isin(o["o_orderkey"], late) & o["o_orderkey_valid"])
    counted = alone & o["o_orderkey_valid"]
    codes = reference_q4.priority_codes(o)[counted]
    want = {reference_q4.PRIORITIES[i]: int(n)
            for i, n in enumerate(np.bincount(codes, minlength=5)) if n}
    assert reference_q4.read_answer(got.table)["groups"] == want
    assert int(got.meta["exists.total"]) == int(alone.sum())
    assert moved["shuffle.rows"] == int(asked.sum()) + len(late)
    _, one, _ = _serve(plan, _device(host))
    _same_tables(got.table, one.table)


def _pairs_plan(out_rows: int, groups: int) -> fusion.Plan:
    """(k, a) INNER JOIN (k, b) ON k, its rows counted by (k, a, b)."""
    return fusion.Plan("pairs", fusion.GroupBy(
        fusion.Join(fusion.Scan("left"), fusion.Scan("right"), (0,), (0,),
                    out_rows, how="inner", label="pairs"),
        (0, 1, 3), ((2, "count"),), max_groups=groups, label="rows"))


def _pairs_tables(mesh=None) -> tuple:
    """Keys with no, one and several matches, NULL keys on both sides;
    ``a`` and ``b`` number the rows, so a joined row is there once. 300
    and 180 rows: a chip's 75 and 45 are padded to 128 and 64."""
    rng = np.random.default_rng(52)
    lk, rk = rng.integers(0, 90, 300), rng.integers(40, 130, 180)
    lv, rv = rng.random(300) > 0.1, rng.random(180) > 0.1

    def put(a):
        a = jnp.asarray(a)
        return a if mesh is None else jax.device_put(
            a, NamedSharding(mesh, P(EXEC_AXIS)))

    bindings = {
        "left": Table([Column(t.INT64, put(lk), put(lv)),
                       Column(t.INT64, put(np.arange(300)))]),
        "right": Table([Column(t.INT64, put(rk), put(rv)),
                        Column(t.INT64, put(1000 + np.arange(180)))])}
    rows = sorted((int(k), a, 1000 + b)
                  for a, k in enumerate(lk) if lv[a]
                  for b in np.flatnonzero((rk == k) & rv))
    return bindings, rows


def _counted_rows(result) -> list:
    groups = int(result.meta["rows.num_groups"])
    cols = [np.asarray(c.data)[:groups] for c in result.table.columns]
    keyed = np.asarray(result.table.column(0).valid_mask())[:groups]
    return sorted((int(k), int(a), int(b)) for k, a, b, n, ok
                  in zip(*cols, keyed) if ok for _ in range(int(n)))


def test_inner_join_over_the_mesh_lays_rows_out(mesh):
    """The multiset of joined rows against a numpy join; ``out_rows`` is a
    chip's room and ``capacity`` says the whole's; one that is too small
    on any chip refuses the request as it does on one chip."""
    bindings, want = _pairs_tables(mesh)
    assert 200 < len(want) < 300
    plan = _pairs_plan(128, 4096)
    ticket, got, moved = _serve(plan, bindings)
    assert (ticket.tier, ticket.rung, ticket.steps) == ("fused", 0, 0)
    assert moved["shuffle.exchanges"] == 3      # the join's two, the groupby
    assert _counted_rows(got) == want
    assert int(got.meta["pairs.total"]) == len(want)
    assert int(got.meta["pairs.capacity"]) == CHIPS * 128
    assert moved["join.capacity_rows"] == CHIPS * 128
    _, one, moved1 = _serve(_pairs_plan(CHIPS * 128, 4096),
                            _pairs_tables()[0])
    assert _counted_rows(one) == want
    assert moved["join.build_rows"] == moved1["join.build_rows"]
    _, refused, moved = _serve(_pairs_plan(16, 4096), bindings)
    assert isinstance(refused, resilience.CapacityOverflow)
    assert moved["join.overflowed"] == 1


def test_a_shuffle_that_overflows_refuses_the_request(mesh):
    """Every order's key the same: each chip sends all its rows to one
    chip, 750 for 512 slots. The request ends in ``CapacityOverflow``,
    never in an answer. With a third of them in the quarter the rows that
    ride (a ``Filter``'s dropped rows do not) fit, and the answer is
    right."""
    host = _host(5220)
    o, li = host["orders"], host["lineitem"]
    o["o_orderkey"][:] = li["l_orderkey"][7]
    o["o_orderkey_valid"][:] = True
    o["o_orderdate"][:] = QUARTER[0]
    plan = tpch._q4_plan(*QUARTER)
    ticket, refused, moved = _serve(plan, _device(host, mesh))
    assert isinstance(refused, resilience.CapacityOverflow)
    assert "exchange" in str(refused)
    assert moved["shuffle.overflowed"] == 1
    assert moved["shuffle.exchanges"] == 2
    o["o_orderdate"][np.arange(ORDERS) % 3 != 0] = QUARTER[1]
    ticket, got, moved = _serve(plan, _device(host, mesh))
    assert (ticket.tier, ticket.rung, ticket.steps) == ("fused", 0, 0)
    assert "shuffle.overflowed" not in moved
    assert reference_q4.read_answer(got.table)["rows"] \
        == reference_q4.q4(host)["rows"]


def test_one_exchange_then_join_step_in_the_package(mesh, monkeypatch):
    """``distributed_join`` and the served lowering run the same
    ``shuffled_join``."""
    calls = []
    real = distributed.shuffled_join

    def counted(*args, **kwargs):
        calls.append(args[5])
        return real(*args, **kwargs)

    monkeypatch.setattr(distributed, "shuffled_join", counted)
    bindings, want = _pairs_tables()
    left, lrv = distributed.shard_table(bindings["left"], mesh,
                                        return_row_valid=True)
    right, rrv = distributed.shard_table(bindings["right"], mesh,
                                         return_row_valid=True)
    joined = distributed.distributed_join(
        left, right, 0, 0, mesh, 600, how="inner", left_capacity=75,
        left_row_valid=lrv, right_row_valid=rrv)
    assert int(np.asarray(joined.total).sum()) == len(want)
    assert not np.asarray(joined.overflowed).any()
    _serve(_pairs_plan(256, 4096), _pairs_tables(mesh)[0])
    assert calls == ["inner", "inner"]


def test_q4_mesh_makers_and_freshener_keep_values_and_sharding():
    """The cell's makers give ``orders_q4`` / ``lineitem_q4`` value for
    value with no chip holding more than its quarter; its freshener rolls
    every array along its rows by ``roll_rows``'s stride and keeps them
    sharded (``roll_rows`` itself hands back replicated arrays)."""
    over = (executor_mesh(CHIPS), EXEC_AXIS)
    orders, items, seed = 1 << 12, 1 << 14, 2**31 + 52
    made = {}
    for name, base, extra in (
            ("orders_q4", "orders_q4", {}),
            ("lineitem_q4", "lineitem_q4", {"rows_of": {"orders": orders}})):
        rows = orders if name == "orders_q4" else items
        maker = resolve.module("tables", name + "_mesh4")
        arrays = maker.make(rows, seed, **extra)
        whole = resolve.module("tables", base).make(rows, seed, **extra)
        for column, want in whole.items():
            assert np.array_equal(np.asarray(arrays[column]),
                                  np.asarray(want))
            assert row_mesh(arrays[column]) == over
            assert {s.data.shape[0]
                    for s in arrays[column].addressable_shards} == {
                        rows // CHIPS}
        assert table_row_mesh(maker.to_table(arrays)) == over
        made[name] = arrays
    with pytest.raises(ValueError, match="do not split"):
        resolve.module("tables", "orders_q4_mesh4").make(1001, seed)
    assert resolve.data("mixes", "q4_shuffled_fresh")["fresh"] \
        == "roll_rows_sharded"
    arrays = made["orders_q4"]
    plain = resolve.module("fresh", "roll_rows").Freshener(arrays, 3)
    assert all(row_mesh(a) is None for a in plain.next().values())
    fresh = resolve.module("fresh", "roll_rows_sharded").Freshener(arrays, 3)
    assert fresh.stride == plain.stride
    rolled = fresh.next()
    for column, a in rolled.items():
        assert row_mesh(a) == over
        assert np.array_equal(np.asarray(a), np.roll(
            np.asarray(arrays[column]), fresh.stride, axis=0))


def test_the_new_layer_metrics_from_counters():
    fill = resolve.module("layer_metrics", "mesh.shuffle_fill_share")
    assert fill.fill_share({}) is None
    assert fill.fill_share({"shuffle.rows": 30,
                            "shuffle.capacity_rows": 120}) == 25.0
    roof = resolve.module("layer_metrics", "mesh.exchange_hbm_roofline_share")
    assert roof.exchange_bytes({"shuffle.read_bytes": 800}, 2, 4) == 200.0
    assert roof.exchange_bytes({}, 0, 4) == 0.0


def test_inner_join_control_of_the_cell_is_not_correct():
    """The cell's control (the ``EXISTS`` taken as an inner join) must not
    pass the comparison that decides ``correct``."""
    from benchmark import control

    numbers = control.control_numbers(
        "q4_shuffled_join_4chip", 2**31 + 5, platform="cpu",
        sizes={"orders": 4096, "lineitem": 16384})["q4_shuffled"]
    limits = resolve.module("plans", "q4_shuffled").LIMITS
    assert numbers["q4.count_mismatches"] > limits["q4.count_mismatches"] == 0
