"""Plan-signature result & subplan cache (runtime/resultcache, ISSUE 11).

Six invariant families:

1. **Bit-identity** — a repeat submission of an identical plan against
   identical bindings is served from cache with ZERO new dispatch
   compiles, and the table (data, validity, meta side-outputs) is
   byte-for-byte what the first execution produced, across ragged row
   counts and null tails.

2. **Invalidation** — any change to the bound input content (or to an
   explicit ``cache_fingerprint`` the caller maintains) misses; the
   ``source_fingerprint`` helper changes whenever a backing file is
   rewritten.

3. **Subplan-prefix reuse** — two distinct plans sharing a
   scan+filter+project prefix execute the shared region exactly once
   between them; the second plan's result is bit-identical to its
   un-rewritten staged execution.

4. **Capacity & accounting** — the LRU bound in logical bytes holds
   under the shared ``MemoryLimiter``; every resident entry's charge is
   released on eviction/clear, zero leaked reservations.

5. **Corruption** — a cached payload corrupted at the
   ``integrity.cache`` seam is a classified discard at read; the caller
   recomputes bit-identically with zero leaked reservations.

6. **Eviction ordering & parity** — pressure sheds cache entries BEFORE
   any live working set spills; a parked query's drain threshold does
   not count evictable cache bytes as held; ``cache.enabled=false``
   reproduces the uncached serving path (no cache state, no counters).
"""

import os

import numpy as np
import pytest

import jax.numpy as jnp

from spark_rapids_jni_tpu.columnar.column import Column
from spark_rapids_jni_tpu.columnar.table import Table
from spark_rapids_jni_tpu.models import tpch
from spark_rapids_jni_tpu.runtime import (
    dispatch,
    faults,
    fusion,
    resultcache,
    server,
)
from spark_rapids_jni_tpu.runtime.memory import (
    MemoryLimiter,
    SpillStore,
    _table_nbytes,
)
from spark_rapids_jni_tpu.telemetry import REGISTRY
from spark_rapids_jni_tpu.telemetry.events import drain as drain_events
from spark_rapids_jni_tpu.utils.config import reset_option, set_option


@pytest.fixture(autouse=True)
def _isolated():
    """Fresh executable cache, counters, event ring; default config."""
    dispatch.clear()
    REGISTRY.reset()
    drain_events()
    yield
    for k in ("cache.enabled", "cache.max_bytes", "cache.subplan_enabled",
              "server.hbm_budget_bytes", "degrade.enabled",
              "memory.high_watermark", "memory.low_watermark",
              "telemetry.enabled"):
        reset_option(k)
    dispatch.clear()


# ---------------------------------------------------------------------------
# plan / table builders (module-level callables: fusion requires
# canonically-nameable fns, and the cache key inherits that)
# ---------------------------------------------------------------------------


def _table(n, seed=0, null_tail=0):
    rng = np.random.default_rng(seed)
    validity = None
    if null_tail:
        validity = np.ones(n, dtype=bool)
        validity[n - null_tail:] = False
    return Table([
        Column.from_numpy(rng.integers(0, 100, n).astype(np.int32)),
        Column.from_numpy(rng.random(n).astype(np.float32),
                          validity=validity),
    ])


def _pred(t, cut):
    return t.columns[0].data < cut


def _derive(t):
    c = t.columns[1]
    return Table(list(t.columns) + [Column(c.dtype, c.data * 2.0,
                                           c.validity)])


def _valid(t, row_valid):
    m = t.columns[2].valid_mask()
    return m if row_valid is None else (row_valid & m)


def _sum_agg(t, row_valid):
    v = jnp.where(_valid(t, row_valid), t.columns[2].data, 0.0)
    return Table([Column(t.columns[2].dtype, jnp.sum(v)[None])])


def _max_agg(t, row_valid):
    v = jnp.where(_valid(t, row_valid), t.columns[2].data, 0.0)
    return Table([Column(t.columns[2].dtype, jnp.max(v)[None])])


def _prefix():
    return fusion.Project(
        fusion.Filter(fusion.Scan("t"), _pred, (50,)), _derive)


def _plan_sum():
    return fusion.Plan("rc_sum", fusion.Project(_prefix(), _sum_agg,
                                                rowwise=False))


def _plan_max():
    return fusion.Plan("rc_max", fusion.Project(_prefix(), _max_agg,
                                                rowwise=False))


def _mask_plan():
    # root IS the masking filter: results carry nulled validity tails
    return fusion.Plan("rc_mask", fusion.Project(
        fusion.Filter(fusion.Scan("t"), _pred, (50,)), _derive))


def _tables_bit_identical(a, b):
    assert a.num_columns == b.num_columns and a.num_rows == b.num_rows
    for ca, cb in zip(a.columns, b.columns):
        assert np.array_equal(np.asarray(ca.data), np.asarray(cb.data))
        va = None if ca.validity is None else np.asarray(ca.validity)
        vb = None if cb.validity is None else np.asarray(cb.validity)
        if va is None or vb is None:
            assert (va is None) == (vb is None)
        else:
            assert np.array_equal(va, vb)
    return True


def _compiles():
    return sum(REGISTRY.counters("dispatch.compile.").values())


# ---------------------------------------------------------------------------
# 1. bit-identity on hit
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n,null_tail", [(600, 0), (801, 7), (1000, 33)])
def test_hit_bit_identical_across_ragged_and_null_tails(n, null_tail):
    plan = _mask_plan()
    bindings = {"t": _table(n, seed=n, null_tail=null_tail)}
    with server.QueryServer(budget_bytes=1 << 28) as srv:
        r1 = srv.session("a").submit(plan, bindings).result(timeout=120)
        before = _compiles()
        t2 = srv.session("a").submit(plan, bindings)
        r2 = t2.result(timeout=120)
        assert t2.status == "served"
        assert t2.queue_wait_s == 0.0  # short-circuited admission
        assert _compiles() == before, "cache hit must not compile"
        assert REGISTRY.counter("cache.hit").value == 1
        _tables_bit_identical(r1.table, r2.table)
        # meta side-outputs survive the round trip
        assert set(r2.meta) == set(r1.meta)
    assert srv.limiter.used == 0


def test_hit_skips_execution_spans():
    plan, bindings = tpch._q1_plan(), {
        "lineitem": tpch.lineitem_table(1024, seed=5)}
    set_option("telemetry.enabled", True)
    with server.QueryServer(budget_bytes=1 << 28) as srv:
        srv.session("a").submit(plan, bindings).result(timeout=120)
        drain_events()
        srv.session("a").submit(plan, bindings).result(timeout=120)
        ops = [r["op"] for r in drain_events() if r.get("kind") == "span"]
    assert "cache.hit" in ops
    assert not any(o.startswith("rung.") or o.startswith("region.")
                   or o.startswith("admission") for o in ops), ops


def test_plan_name_excluded_from_signature():
    # identically-traced plans share a cache slot whatever they are called
    b = {"t": _table(500, seed=2)}
    s1 = resultcache.plan_signature(_plan_sum(), b)
    renamed = fusion.Plan("other_name", _plan_sum().root)
    assert resultcache.plan_signature(renamed, b) == s1


# ---------------------------------------------------------------------------
# 2. invalidation
# ---------------------------------------------------------------------------


def test_content_change_misses():
    plan = _mask_plan()
    with server.QueryServer(budget_bytes=1 << 28) as srv:
        srv.session("a").submit(
            plan, {"t": _table(700, seed=1)}).result(timeout=120)
        srv.session("a").submit(
            plan, {"t": _table(700, seed=2)}).result(timeout=120)
        assert REGISTRY.counter("cache.hit").value == 0
        assert REGISTRY.counter("cache.miss").value == 2


def test_explicit_fingerprint_overrides_and_invalidates():
    plan = _mask_plan()
    bindings = {"t": _table(700, seed=1)}
    with server.QueryServer(budget_bytes=1 << 28) as srv:
        sess = srv.session("a")
        sess.submit(plan, bindings,
                    cache_fingerprint="v1").result(timeout=120)
        sess.submit(plan, bindings,
                    cache_fingerprint="v1").result(timeout=120)
        assert REGISTRY.counter("cache.hit").value == 1
        # the caller's fingerprint changed (source rewritten): miss
        sess.submit(plan, bindings,
                    cache_fingerprint="v2").result(timeout=120)
        assert REGISTRY.counter("cache.hit").value == 1


def test_source_fingerprint_tracks_file_rewrites(tmp_path):
    p = tmp_path / "scan.bin"
    p.write_bytes(b"generation one")
    fp1 = resultcache.source_fingerprint(str(p))
    assert fp1 == resultcache.source_fingerprint(str(p))
    p.write_bytes(b"generation TWO")
    os.utime(p, ns=(1, 1))  # force an mtime step even on coarse clocks
    assert resultcache.source_fingerprint(str(p)) != fp1


def test_cache_key_requires_both_halves():
    cache = _bare_cache(1 << 20)[2]
    with pytest.raises(ValueError, match="fingerprint"):
        cache.get(resultcache.CacheKey("sig", ""))
    with pytest.raises(ValueError, match="CacheKey"):
        cache.get("sig-only-string")
    with pytest.raises(ValueError, match="signature"):
        cache.get(resultcache.CacheKey("", "fp"))


# ---------------------------------------------------------------------------
# 2b. the buffer digest: one definition, evaluated where the buffer lives
# ---------------------------------------------------------------------------

_BIG = 140_001   # int64 elements: over the digest's threshold, odd on purpose


def _values(dtype, n, seed=0):
    rng = np.random.default_rng(seed)
    if np.dtype(dtype) == np.bool_:
        return rng.integers(0, 2, n).astype(bool)
    if np.dtype(dtype).kind == "f":
        return rng.standard_normal(n).astype(dtype)
    info = np.iinfo(dtype)
    return rng.integers(info.min, info.max, n, dtype=dtype, endpoint=True)


def _column(arr):
    from spark_rapids_jni_tpu import types as t

    kinds = {"int8": t.INT8, "int32": t.INT32, "int64": t.INT64,
             "float32": t.FLOAT32, "float64": t.FLOAT64}
    return Column(kinds[str(arr.dtype)], arr)


def _fp(*arrays):
    """The fingerprint of a new Table over the arrays as they are given."""
    return resultcache.table_fingerprint(Table([_column(a) for a in arrays]))


def _sha_of_bytes(table):
    """What the fingerprint was before there was a digest: sha256 over
    every buffer's dtype, shape and bytes."""
    import hashlib

    h = hashlib.sha256()
    for col in table.columns:
        h.update(repr(col.dtype).encode())
        for buf in (col.data, col.validity, col.chars):
            if buf is None:
                h.update(b"\xff")
                continue
            arr = np.ascontiguousarray(np.asarray(buf))
            h.update(str(arr.dtype).encode())
            h.update(repr(arr.shape).encode())
            h.update(arr.tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("n", [1, 4099, 70_003])
@pytest.mark.parametrize("dtype", ["int8", "int16", "int32", "int64",
                                   "uint8", "bool", "float32"])
def test_device_digest_equals_the_numpy_twin(dtype, n):
    """Lengths that are no multiple of a word, a lane count or the numpy
    evaluation's block; every dtype the digest takes."""
    import jax

    arr = _values(dtype, n, seed=n)
    on_device = np.asarray(jax.jit(resultcache.cache_digest)(
        jnp.asarray(arr)))
    twin = resultcache._digest_numpy(arr)
    assert on_device.dtype == twin.dtype == np.uint32
    assert on_device.shape == twin.shape == (4,)
    assert resultcache.DIGEST_BITS == 128
    assert on_device.tolist() == twin.tolist()


def _flip_first_word(a):
    a.view(np.uint8)[0] ^= 1


def _flip_last_byte(a):
    a.view(np.uint8)[-1] ^= 0x80


def _flip_high_half(a):
    a.view(np.uint32)[2 * (_BIG // 2) + 1] ^= 1 << 31


def _swap_two(a):
    i, j = 17, _BIG - 5
    assert a[i] != a[j]
    a[i], a[j] = a[j], a[i]


@pytest.mark.parametrize("change", [_flip_first_word, _flip_last_byte,
                                    _flip_high_half, _swap_two])
def test_digest_sees_one_bit_and_one_swap(change):
    base = _values("int64", _BIG)
    other = base.copy()
    change(other)
    lanes, moved = (resultcache._digest_numpy(x) for x in (base, other))
    # every lane moves, not one of four
    assert all(a != b for a, b in zip(lanes.tolist(), moved.tolist()))
    assert _fp(jnp.asarray(base)) != _fp(jnp.asarray(other))


@pytest.mark.parametrize("stride", [1, 46_667, _BIG - 2])
def test_a_rolled_buffer_is_a_new_buffer(stride):
    """The benchmark's freshener: the same multiset of words, each at
    another index. Low-entropy columns, like lineitem's flags."""
    flags = np.frombuffer(b"ANR", dtype=np.int8)[
        np.random.default_rng(3).integers(0, 3, 8 * _BIG)]
    qty = np.random.default_rng(4).integers(100, 5100, _BIG)
    for column in (flags, qty):
        fps = {_fp(jnp.roll(jnp.asarray(column), k))
               for k in (0, stride, 2 * stride)}
        assert len(fps) == 3
        lanes = [resultcache._digest_numpy(np.roll(column, k)).tolist()
                 for k in (0, stride)]
        assert all(a != b for a, b in zip(*lanes))


def test_same_bytes_under_another_type_or_length_differ():
    as64 = _values("int64", _BIG)
    as32 = as64.view(np.int32)
    assert as32.tobytes() == as64.tobytes()
    padded = np.concatenate([as64, np.zeros(3, np.int64)])
    fps = {_fp(jnp.asarray(x)) for x in (as64, as32, padded)}
    assert len(fps) == 3
    # the type is the sha256's to tell apart; trailing zeros the digest's
    assert (resultcache._digest_numpy(as64).tolist()
            == resultcache._digest_numpy(as32).tolist())
    assert (resultcache._digest_numpy(as64).tolist()
            != resultcache._digest_numpy(padded).tolist())


def test_fingerprint_is_the_contents_not_the_placements():
    """A single-device jax.Array, one sharded over the eight devices (each
    digested where it lives), a numpy array and a HostTableChunk snapshot
    of one content."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from spark_rapids_jni_tpu.runtime import memory

    big = _values("int64", 8 * 20_000)           # digested
    small = _values("int8", 8 * 20_000, seed=1)  # bytes to the sha256
    on_device = Table([_column(jnp.asarray(big)), _column(jnp.asarray(small))])
    spread = NamedSharding(Mesh(np.array(jax.devices()), ("x",)),
                           PartitionSpec("x"))
    sharded = Table([_column(jax.device_put(big, spread)),
                     _column(jax.device_put(small, spread))])
    assert len(sharded.columns[0].data.sharding.device_set) == 8
    on_host = Table([_column(big), _column(small)])
    chunk = memory.host_table_chunk(
        [memory._col_to_host(c) for c in on_device.columns], big.size)
    assert all(isinstance(snap[1], np.ndarray) for snap in chunk.cols)
    fps = {resultcache.input_fingerprint({"t": v})
           for v in (on_device, sharded, on_host, chunk)}
    assert len(fps) == 1
    c = REGISTRY.counters()
    assert c["cache.fingerprint_device_bytes"] == 2 * big.nbytes  # of four
    assert c["cache.fingerprint_bytes"] == 4 * (big.nbytes + small.nbytes)


def test_small_buffers_and_float64_keep_the_sha256_of_their_bytes():
    """Under the threshold nothing changed, to the hex; float64 is never
    digested, whatever its size."""
    small = _table(700, seed=3, null_tail=9)
    assert resultcache.table_fingerprint(small) == _sha_of_bytes(small)
    wide = Table([_column(jnp.asarray(_values("float64", _BIG)))])
    assert wide.columns[0].data.nbytes > resultcache._DIGEST_MIN_BYTES
    assert resultcache.table_fingerprint(wide) == _sha_of_bytes(wide)
    assert REGISTRY.counters()["cache.fingerprint_device_bytes"] == 0
    assert "dispatch.compile.cache_digest" not in REGISTRY.counters()
    # the same size as float32 is
    narrow = Table([_column(jnp.asarray(_values("float32", 2 * _BIG)))])
    assert resultcache.table_fingerprint(narrow) != _sha_of_bytes(narrow)
    assert REGISTRY.counters()["cache.fingerprint_device_bytes"] == (
        narrow.columns[0].data.nbytes)


def test_a_digest_that_fails_on_the_device_is_taken_on_the_host(monkeypatch):
    """The same fingerprint, and a counter every chip check holds at 0."""
    arr = jnp.asarray(_values("int64", _BIG))
    want = _fp(arr)
    assert REGISTRY.counters()["cache.fingerprint_device_bytes"] == arr.nbytes

    def no_room(op, fn, *args):
        raise RuntimeError("RESOURCE_EXHAUSTED: no room for the temporaries")

    monkeypatch.setattr(dispatch, "compiled", no_room)
    assert _fp(arr) == want
    c = REGISTRY.counters()
    assert c["dispatch.exec_error"] == 1
    assert c["cache.fingerprint_device_bytes"] == arr.nbytes   # not twice
    assert c["cache.fingerprint_bytes"] == 2 * arr.nbytes


def test_two_submits_of_one_table_digest_once():
    plan = _mask_plan()
    table = _table(300_000, seed=1)
    want = _table_nbytes(table)
    with server.QueryServer(budget_bytes=1 << 28) as srv:
        sess = srv.session("a")
        sess.submit(plan, {"t": table}).result(timeout=120)
        assert sess.submit(plan, {"t": table}).status == "served"
    c = REGISTRY.counters()
    assert c["cache.hit"] == 1
    assert c["cache.fingerprint_bytes"] == want
    assert c["cache.fingerprint_device_bytes"] == want
    # one executable a dtype and shape, then none
    assert c["dispatch.compile.cache_digest"] == 2
    other = _table(300_000, seed=2)
    resultcache.table_fingerprint(other)
    assert REGISTRY.counters()["dispatch.compile.cache_digest"] == 2


# ---------------------------------------------------------------------------
# 3. subplan-prefix reuse
# ---------------------------------------------------------------------------


def test_subplan_prefix_executes_once_across_two_plans():
    tbl = _table(3000, seed=11)
    with server.QueryServer(budget_bytes=1 << 28) as srv:
        srv.session("s").submit(_plan_sum(), {"t": tbl}).result(timeout=120)
        assert REGISTRY.counter("cache.subplan_materialize").value == 1
        rb = srv.session("s").submit(
            _plan_max(), {"t": tbl}).result(timeout=120)
        assert REGISTRY.counter("cache.subplan_materialize").value == 1
        assert REGISTRY.counter("cache.subplan_hit").value == 1
        # bit-identical to the un-rewritten staged execution
        ref = fusion.execute(_plan_max(), {"t": _table(3000, seed=11)},
                             force_staged=True)
        _tables_bit_identical(rb.table, ref.table)
    assert srv.limiter.used == 0


def test_short_prefix_not_rewritten():
    # q1's chain is Scan->Project (length 1): below _MIN_PREFIX_NODES
    plan, bindings = tpch._q1_plan(), {
        "lineitem": tpch.lineitem_table(1024, seed=5)}
    with server.QueryServer(budget_bytes=1 << 28) as srv:
        srv.session("a").submit(plan, bindings).result(timeout=120)
        assert REGISTRY.counter("cache.subplan_materialize").value == 0


def test_scan_prefix_chains_shapes():
    chains = fusion.scan_prefix_chains(_plan_sum().root)
    assert [(s.name, type(t).__name__, n) for s, t, n in chains] == [
        ("t", "Project", 2)]
    # top never reaches root, unbucketed scans excluded
    lone = fusion.Plan("lone", fusion.Filter(
        fusion.Scan("t", bucket=False), _pred, (50,)))
    assert fusion.scan_prefix_chains(lone.root) == []


# ---------------------------------------------------------------------------
# 4. capacity & accounting
# ---------------------------------------------------------------------------


def _bare_cache(max_bytes, budget=1 << 26, **lim_kw):
    limiter = MemoryLimiter(budget, **lim_kw)
    store = SpillStore(budget_bytes=budget)
    cache = resultcache.ResultCache(store, limiter, max_bytes=max_bytes)
    limiter.attach_spill_store(store)
    limiter.attach_result_cache(cache)
    return limiter, store, cache


def _result(n, seed):
    return fusion.FusedResult(_table(n, seed=seed), {})


def _key(i):
    return resultcache.CacheKey(f"sig-{i:04d}", f"fp-{i:04d}")


def test_default_capacity_follows_the_limiter_budget():
    # cache.max_bytes 0 (the default): an eighth of the budget entries are
    # charged to, at least 256 MiB — a server given a v5e's 16.9 GB can
    # cache one padded general-q3 result at SF1 (336 MB); set, it is exact
    assert _bare_cache(None)[2].stats()["max_bytes"] == 256 << 20
    assert _bare_cache(None, budget=16 << 30)[2].stats()["max_bytes"] \
        == 2 << 30
    set_option("cache.max_bytes", 1 << 20)
    assert _bare_cache(None, budget=16 << 30)[2].stats()["max_bytes"] \
        == 1 << 20


def test_lru_bound_and_charge_release():
    res = _result(512, 1)
    per = _table_nbytes(res.table)
    limiter, store, cache = _bare_cache(max_bytes=per * 3)
    for i in range(5):
        assert cache.put(_key(i), _result(512, i))
    st = cache.stats()
    assert st["entries"] == 3 and st["bytes"] <= per * 3
    assert REGISTRY.counter("cache.eviction").value == 2
    # evicted keys miss; survivors hit; LRU order: oldest went first
    assert cache.get(_key(0)) is None and cache.get(_key(1)) is None
    for i in (2, 3, 4):
        assert cache.get(_key(i)) is not None
    assert limiter.used == cache.evictable_bytes == st["bytes"]
    cache.clear()
    assert limiter.used == 0 and cache.evictable_bytes == 0


def test_get_refreshes_lru_order():
    per = _table_nbytes(_result(512, 0).table)
    limiter, store, cache = _bare_cache(max_bytes=per * 2)
    cache.put(_key(0), _result(512, 0))
    cache.put(_key(1), _result(512, 1))
    assert cache.get(_key(0)) is not None  # 0 is now the hottest
    cache.put(_key(2), _result(512, 2))    # displaces 1, not 0
    assert cache.get(_key(1)) is None
    assert cache.get(_key(0)) is not None
    cache.clear()
    assert limiter.used == 0


def test_oversized_entry_skipped():
    res = _result(2048, 3)
    limiter, store, cache = _bare_cache(
        max_bytes=_table_nbytes(res.table) - 1)
    assert not cache.put(_key(0), res)
    assert cache.stats()["entries"] == 0 and limiter.used == 0


def test_shed_demotes_but_entry_survives():
    limiter, store, cache = _bare_cache(max_bytes=1 << 24)
    cache.put(_key(0), _result(1024, 4))
    handle = next(iter(cache._entries.values()))["handle"]
    nbytes = limiter.used
    assert nbytes > 0
    assert cache.shed(1 << 30) == nbytes
    assert limiter.used == 0 and store.state(handle) == "host"
    # a later hit stages the entry back, verified, and re-charges it
    got = cache.get(_key(0))
    assert got is not None and limiter.used == nbytes
    _tables_bit_identical(got.table, _result(1024, 4).table)
    cache.clear()
    assert limiter.used == 0


# ---------------------------------------------------------------------------
# 5. corruption: classified discard + bit-identical recompute
# ---------------------------------------------------------------------------


def test_corrupt_cached_entry_discarded_and_recomputed():
    plan, bindings = tpch._q1_plan(), {
        "lineitem": tpch.lineitem_table(2048, seed=3)}
    with server.QueryServer(budget_bytes=1 << 28) as srv:
        r1 = srv.session("x").submit(plan, bindings).result(timeout=120)
        script = faults.FaultScript(corruptions=[
            faults.CorruptionSpec("integrity.cache", mode="flip")])
        with faults.inject(script):
            srv.result_cache.shed(1 << 30)  # demote -> corrupt host snap
        assert script.fired, "corruption window never fired"
        r2 = srv.session("x").submit(plan, bindings).result(timeout=120)
        assert REGISTRY.counter("cache.corrupt_discard").value == 1
        assert REGISTRY.counter(
            "integrity.mismatch.integrity.cache").value == 1
        assert REGISTRY.counter("cache.hit").value == 0
        _tables_bit_identical(r1.table, r2.table)
        # the recompute repopulated the cache: third submission hits
        r3 = srv.session("x").submit(plan, bindings).result(timeout=120)
        assert REGISTRY.counter("cache.hit").value == 1
        _tables_bit_identical(r1.table, r3.table)
    assert srv.limiter.used == 0, "corrupt discard leaked a reservation"


@pytest.mark.parametrize("mode", faults.CorruptionSpec.MODES)
def test_corrupt_disk_tier_every_mode(tmp_path, mode):
    limiter, store, cache = _bare_cache(1 << 24)
    store._spill_dir = str(tmp_path)
    store._spill_prefix = "t"
    res = _result(1024, 9)
    cache.put(_key(0), res)
    script = faults.FaultScript(corruptions=[
        faults.CorruptionSpec("integrity.cache", mode=mode, seed=7)])
    with faults.inject(script):
        h = next(iter(cache._entries.values()))["handle"]
        store.spill(h)      # -> corrupt sealed file on disk
        cache._entries[next(iter(cache._entries))]["charged"] = False
        cache.evictable_bytes = 0
        limiter.release(limiter.used)
    assert script.fired
    assert cache.get(_key(0)) is None
    assert REGISTRY.counter("cache.corrupt_discard").value == 1
    assert cache.stats()["entries"] == 0 and limiter.used == 0


# ---------------------------------------------------------------------------
# 6. eviction ordering, drain parity, kill switch
# ---------------------------------------------------------------------------


def test_pressure_sheds_cache_before_live_working_set():
    set_option("degrade.enabled", True)
    budget = 1 << 20
    limiter, store, cache = _bare_cache(
        max_bytes=1 << 24, budget=budget,
        high_watermark=0.6, low_watermark=0.55)
    live = store.put(_table(2048, seed=1))  # a live query's working set
    # a cached result large enough to absorb the whole pressure target
    cache.put(_key(0), _result(20000, 2))
    cache_handle = next(iter(cache._entries.values()))["handle"]
    cached_bytes = limiter.used
    assert cached_bytes >= int(budget * 0.1)
    # a live reservation crosses the high watermark
    limiter.reserve(budget // 2)
    assert limiter.pressure_crossings == 1
    # ordering: the CACHE entry was demoted; the live table stayed on
    # device untouched because shedding the cache absorbed the target
    assert store.state(cache_handle) == "host"
    assert store.state(live) == "device"
    assert REGISTRY.counter("cache.shed_bytes").value == cached_bytes
    assert cache.evictable_bytes == 0


def test_parked_drain_discounts_evictable_cache_bytes():
    set_option("degrade.enabled", True)
    budget = 1 << 20
    limiter, store, cache = _bare_cache(
        max_bytes=1 << 24, budget=budget,
        high_watermark=0.9, low_watermark=0.5)
    cache.put(_key(0), _result(4096, 3))
    cache.put(_key(1), _result(4096, 4))
    evictable = cache.evictable_bytes
    assert evictable > 0
    live = int(budget * 0.5) - evictable // 2
    limiter.reserve(live)
    assert limiter.used > int(budget * 0.5)  # nominally above low
    # ...but the excess is ALL evictable cache: the drain wait must not
    # park on it (the old behavior waited the full timeout here)
    assert limiter.wait_below_low(timeout=0.05)
    # reclaim_cache makes the discount real: shed down to the low mark
    freed = limiter.reclaim_cache()
    assert freed > 0
    assert limiter.used <= int(budget * 0.5)
    limiter.release(live)
    cache.clear()
    assert limiter.used == 0


def test_drain_does_not_discount_spilled_uncharged_entries():
    set_option("degrade.enabled", True)
    budget = 1 << 20
    limiter, store, cache = _bare_cache(
        max_bytes=1 << 24, budget=budget,
        high_watermark=0.9, low_watermark=0.5)
    cache.put(_key(0), _result(4096, 3))
    cache.shed(1 << 30)  # entry demoted: no longer evictable residency
    assert cache.evictable_bytes == 0
    limiter.reserve(int(budget * 0.6))
    assert not limiter.wait_below_low(timeout=0.05)
    limiter.release(limiter.used)


def test_disabled_reproduces_uncached_serving():
    set_option("cache.enabled", False)
    plan, bindings = tpch._q1_plan(), {
        "lineitem": tpch.lineitem_table(1024, seed=5)}
    with server.QueryServer(budget_bytes=1 << 28) as srv:
        r1 = srv.session("a").submit(plan, bindings).result(timeout=120)
        r2 = srv.session("a").submit(plan, bindings).result(timeout=120)
        _tables_bit_identical(r1.table, r2.table)
        assert srv.result_cache.stats()["entries"] == 0
        assert dict(REGISTRY.counters("cache.")) == {}
        assert srv.result_cache.put(
            resultcache.CacheKey("s", "f"), r1) is False
        assert srv.result_cache.get(
            resultcache.CacheKey("s", "f")) is None
    assert srv.limiter.used == 0
