#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the served query path still
starts, and stays, on the chip.

Drives the main path once through its normal entry points at TPC-H scale
(``Session.submit`` -> admission -> ``fusion.execute`` -> ``dispatch.call``
-> device, result back through ``ticket.result()``), checks every result
against the numpy oracle beside its plan, and fails unless the recovery
rungs between a query and the chip (inline dispatch, the staged evaluator,
the degrade ladder) all stayed unused.

Process model: this parent never imports JAX. Each phase is a child
process, one after another, each gone before the next starts; all share
JAX's persistent compile cache (``JAX_COMPILATION_CACHE_DIR`` where set,
else the fixed ``.jax_cache`` of the checkout). Children report to the
parent through one JSON file each under ``.chip_smoke/``.

Contract: exits 0 and prints, as the last line of stdout,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": n}}``
only when every phase passed on an accelerator. With no chip it exits
non-zero at once and runs no query. No ``except`` here turns a failure
into a printed line and a 0.

The phase functions take their sizes and the expected platform as
arguments so tests/test_chip_smoke.py can run them tiny on the CPU.
"""

from __future__ import annotations

import concurrent.futures
import json
import math
import os
import signal
import subprocess
import sys
import threading
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
SCRATCH = os.path.join(ROOT, ".chip_smoke")
DEADLINE_S = 1185.0          # one chip: the contract's 1200 s, compile included
# Four chips: mesh + cluster compile for ~950 s more when cold, so a cold
# four-chip smoke does NOT fit the 1200 s of the one-chip contract (~1750 s:
# give the chip tool --timeout 2400); these two phases get this much on top.
FOUR_CHIP_S = 1200.0
F64_RTOL = 1e-9              # float64 averages; integers and decimals exact

# TPC-H table cardinalities (specification clause 4.2.5): SF10 lineitem for
# the scans, SF1 for the joins and the fleet. Nothing of the
# schema is cut, only the scale, to what one chip holds.
FULL = {
    "sf10_rows": 59_986_052,
    "sf1_rows": 6_001_215,
    "customers": 150_000,
    "orders": 1_500_000,
    # four-chip cluster exchange: SF1 orders
    "cluster_orders": 1_500_000,
    "cluster_customers": 150_000,
}


class SmokeFailure(AssertionError):
    """A smoke check that did not hold; the message names what."""


def _tag(device: dict) -> str:
    """The prefix of every line: platform, device kind, device count."""
    return "[{platform} {kind} x{count}]".format(**device)


# ---------------------------------------------------------------------------
# shared helpers (children only: everything below imports JAX lazily)
# ---------------------------------------------------------------------------


class _Ctx:
    """One child's view of the device, and the prefix of every line."""

    def __init__(self, platform: str):
        # telemetry first: record_fallback / record_resilience count only
        # while it is on, and the counter check reads those counters
        from spark_rapids_jni_tpu.utils.config import set_option

        set_option("telemetry.enabled", True)
        import jax

        self.jax = jax
        devs = jax.devices()
        self.device = devs[0]
        self.info = {"platform": str(devs[0].platform),
                     "kind": str(devs[0].device_kind), "count": len(devs)}
        if self.info["platform"] != platform:
            raise SmokeFailure(
                f"no chip found: jax.devices()[0].platform is "
                f"{self.info['platform']!r}, expected {platform!r}")
        self.tag = _tag(self.info)

    def say(self, msg: str) -> None:
        print(f"{self.tag} {msg}", flush=True)

    def sync(self, table) -> None:
        self.jax.block_until_ready(
            [a for c in table.columns
             for a in (c.data, c.validity) if a is not None])

    def bytes_in_use(self, device=None) -> int:
        stats = (device or self.device).memory_stats() or {}
        return int(stats.get("bytes_in_use", 0))


class _PeakSampler:
    """Peak ``bytes_in_use`` over one plan's run, polled from a thread
    (the allocator's own ``peak_bytes_in_use`` never resets)."""

    def __init__(self, ctx: _Ctx):
        self._ctx = ctx
        self._stop = threading.Event()
        self.peak = ctx.bytes_in_use()
        self._thread = threading.Thread(target=self._run, daemon=True)

    def _run(self) -> None:
        while not self._stop.wait(0.005):
            self.peak = max(self.peak, self._ctx.bytes_in_use())

    def __enter__(self) -> "_PeakSampler":
        self._thread.start()
        return self

    def __exit__(self, *exc) -> bool:
        self._stop.set()
        self._thread.join()
        self.peak = max(self.peak, self._ctx.bytes_in_use())
        return False


def _counters(prefix: str = "") -> dict:
    from spark_rapids_jni_tpu.telemetry import REGISTRY

    return REGISTRY.counters(prefix)


def _compile_s(plan=None) -> float:
    """Seconds spent lowering and compiling so far: every op, or one
    plan's fused region (other plans may be compiling beside it)."""
    from spark_rapids_jni_tpu.telemetry import REGISTRY

    name = "dispatch.compile_ms" + (
        "" if plan is None else f".fusion.{plan.name}")
    return float(REGISTRY.histogram(name).sum) / 1e3


def check_counters(*, tickets=()) -> None:
    """Section 2 of the issue: no way off the chip that the smoke cannot
    see. Raises with the counter's name unless every recovery rung between
    a query and the device stayed unused."""
    c = _counters()
    for name in ("dispatch.compile_error", "dispatch.exec_error",
                 "dispatch.pad_error", "dispatch.inline.compile_error",
                 "dispatch.inline.exec_error", "dispatch.inline.pad_error",
                 "fusion.staged_regions",
                 "resilience.rung.host_fallback",
                 "resilience.rung.staged_fallback", "degrade.step"):
        if c.get(name, 0) != 0:
            raise SmokeFailure(f"{name} = {c[name]}, required 0")
    for name, value in c.items():
        if value and name.startswith(
                ("fallback.fusion.", "degrade.tier.")):
            raise SmokeFailure(f"{name} = {value}, required absent")
    for label, where in tickets:
        if where != ("fused", 0, 0):
            raise SmokeFailure(
                f"ticket {label}: finished at (tier, rung, steps) = "
                f"{where}, required ('fused', 0, 0)")


# -- result comparison against the numpy oracles ----------------------------


def _q1_groups(table) -> dict:
    import numpy as np

    cols = [np.asarray(c.data) for c in table.columns]
    valid = (np.asarray(table.column(0).valid_mask())
             & np.asarray(table.column(1).valid_mask()))
    names = ("sum_qty", "sum_base_price", "sum_disc_price", "sum_charge",
             "avg_qty", "avg_price", "avg_disc", "count")
    return {
        (int(cols[0][i]), int(cols[1][i])):
            {n: cols[2 + k][i] for k, n in enumerate(names)}
        for i in np.nonzero(valid)[0]
    }


def check_q1(table, oracle: dict, what: str) -> None:
    got = _q1_groups(table)
    if set(got) != set(oracle):
        raise SmokeFailure(f"{what}: groups {sorted(got)} != {sorted(oracle)}")
    for key, want in oracle.items():
        for name, w in want.items():
            g = got[key][name]
            if name.startswith("avg_"):
                if abs(float(g) - w) > F64_RTOL * abs(w):
                    raise SmokeFailure(
                        f"{what}: {key} {name} = {float(g)!r}, oracle {w!r} "
                        f"(rtol {F64_RTOL})")
            elif int(g) != w:
                raise SmokeFailure(
                    f"{what}: {key} {name} = {int(g)}, oracle {w} (exact)")


def check_q6(table, oracle: int, what: str) -> None:
    import numpy as np

    got = int(np.asarray(table.column(0).data)[0])
    if got != oracle:
        raise SmokeFailure(f"{what}: {got}, oracle {oracle} (exact)")


def check_q3(table, oracle: dict, what: str) -> None:
    import numpy as np

    kv = np.nonzero(np.asarray(table.column(0).valid_mask()))[0]
    key, date, prio, rev = (np.asarray(table.column(i).data)[kv]
                            for i in range(4))
    got = {int(k): (int(r), int(d), int(p))
           for k, r, d, p in zip(key, rev, date, prio)}
    if got != oracle:
        diff = set(got.items()) ^ set(oracle.items())
        raise SmokeFailure(
            f"{what}: {len(got)} groups vs oracle {len(oracle)}, "
            f"{len(diff)} differing entries (exact)")


def _table_bytes(table) -> list:
    import numpy as np

    return [(np.asarray(c.data).tobytes(),
             np.asarray(c.valid_mask()).tobytes()) for c in table.columns]


def _every_digested_dtype(rows: int, seed: int):
    """A table with a column of every width and kind the buffer digest
    takes, over the whole range of each (negative values, high halves,
    signed zeros and infinities), half of them with a validity mask."""
    import numpy as np

    from spark_rapids_jni_tpu import types as t
    from spark_rapids_jni_tpu.columnar import Column, Table

    rng = np.random.default_rng(seed)
    cols = []
    for i, dtype in enumerate((t.INT64, t.UINT64, t.INT32, t.UINT32,
                               t.INT16, t.UINT16, t.INT8, t.UINT8)):
        info = np.iinfo(dtype.jnp_dtype)
        cols.append(Column.from_numpy(
            rng.integers(info.min, info.max, rows, dtype=dtype.jnp_dtype,
                         endpoint=True), dtype,
            validity=rng.random(rows) < 0.9 if i % 2 else None))
    floats = rng.standard_normal(rows).astype(np.float32)
    floats[::1001] = -0.0
    floats[1::1001] = np.inf
    cols.append(Column.from_numpy(floats, t.FLOAT32))
    return Table(cols)


def check_fingerprint(ctx: _Ctx, table, what: str) -> dict:
    """The content fingerprint computed where the table lives has to be
    the one numpy computes over its host copy: a fleet's supervisor, on
    the CPU, compares its own with the one a replica took on its chip."""
    from spark_rapids_jni_tpu.columnar import Table
    from spark_rapids_jni_tpu.runtime import memory, resultcache

    def timed(value):
        before = _counters("cache.fingerprint")
        t0 = time.perf_counter()
        fp = resultcache.input_fingerprint({"t": value})
        took = time.perf_counter() - t0
        moved = {k: v - before.get(k, 0)
                 for k, v in _counters("cache.fingerprint").items()}
        return fp, took, moved

    # the buffers under new Table objects: the memo is the object's, and
    # the caller's table stays without one
    first, first_s, _ = timed(Table(list(table.columns)))
    again, again_s, moved = timed(Table(list(table.columns)))
    chunk = memory.host_table_chunk(
        [memory._col_to_host(c) for c in table.columns], table.num_rows)
    host, host_s, _ = timed(chunk)
    ctx.say(f"fingerprint {what}: {moved.get('cache.fingerprint_bytes', 0)} "
            f"bytes, {moved.get('cache.fingerprint_device_bytes', 0)} of "
            f"them digested on the device, in {again_s:.4f}s "
            f"({first_s:.3f}s the first time, compiles included); numpy "
            f"over the host copy {host_s:.3f}s; {first[:16]}")
    if not first == again == host:
        raise SmokeFailure(
            f"fingerprint {what}: the device gives {first} then {again}, "
            f"numpy over the host copy {host}: the fingerprint depends on "
            f"where the bytes live")
    return {"first_s": first_s, "warm_s": again_s, "host_s": host_s,
            "moved": moved}


# ---------------------------------------------------------------------------
# phase: probe — is there a chip, and what is it
# ---------------------------------------------------------------------------


def probe_phase(platform: str) -> dict:
    ctx = _Ctx(platform)
    import jax
    import jaxlib

    from spark_rapids_jni_tpu.runtime.memory import device_memory_stats

    limit = device_memory_stats(ctx.device).bytes_limit
    try:
        import libtpu
        libtpu_version = getattr(libtpu, "__version__", "unknown")
    except ImportError:
        libtpu_version = "not installed"
    ctx.say(f"probe: jax {jax.__version__} jaxlib {jaxlib.__version__} "
            f"libtpu {libtpu_version} python {sys.version.split()[0]} "
            f"bytes_limit {limit}")
    return {"device": ctx.info, "bytes_limit": limit}


# ---------------------------------------------------------------------------
# phase: serve — the served path at TPC-H scale
# ---------------------------------------------------------------------------


class _Served:
    """One plan through the server, three submits: cold on table A (its
    compile printed as set-up), table B of the same shape (must compile
    nothing), table A again (must be a result-cache hit). Construction
    only submits the cold query, so several plans can compile side by side
    on the server's workers; ``finish`` waits for it and runs the rest."""

    def __init__(self, ctx, session, name, plan, bind_a, bind_b, check):
        self.ctx, self.session, self.name, self.plan = ctx, session, name, plan
        self.bind_a, self.bind_b, self.check = bind_a, bind_b, check
        self._compile0 = _compile_s(plan)
        self._t0 = time.perf_counter()
        self._cold = session.submit(plan, bind_a)
        self._submit_s = time.perf_counter() - self._t0

    def _timed(self, bindings):
        before = _counters()
        with _PeakSampler(self.ctx) as mem:
            t0 = time.perf_counter()
            ticket = self.session.submit(self.plan, bindings)
            t1 = time.perf_counter()
            result = ticket.result()
            self.ctx.sync(result.table)
            t2 = time.perf_counter()
        after = _counters()
        # this plan's own region op: other plans may still be compiling on
        # the server's other workers, so the global counters would mix
        op = f"fusion.{self.plan.name}"
        delta = {short: after.get(k, 0) - before.get(k, 0)
                 for short, k in (("compile", f"dispatch.compile.{op}"),
                                  ("hit", f"dispatch.hit.{op}"),
                                  ("cache.hit", "cache.hit"),
                                  ("cache.too_big", "cache.too_big"))}
        return ticket, result, delta, t1 - t0, t2 - t1, mem.peak

    def finish(self, tickets: list, report: dict) -> None:
        ctx, name = self.ctx, self.name
        cold = self._cold.result()
        ctx.sync(cold.table)
        cold_s = time.perf_counter() - self._t0
        compile_s = _compile_s(self.plan) - self._compile0
        ctx.say(f"serve {name} cold: submit {self._submit_s:.3f}s, result "
                f"after {cold_s:.3f}s (compile {compile_s:.3f}s, apart "
                f"{cold_s - compile_s:.3f}s)")
        self.check(cold.table, "a", f"{name} cold")
        t_second, second, d, submit_s, serve_s, peak = self._timed(self.bind_b)
        warm_s = submit_s + serve_s
        ctx.say(f"serve {name} second table: submit {submit_s:.3f}s serve "
                f"{serve_s:.3f}s, compiles {d['compile']} hits {d['hit']}, "
                f"peak_bytes_in_use {peak}")
        self.check(second.table, "b", f"{name} second")
        if d["compile"] != 0 or d["hit"] < 1:
            raise SmokeFailure(
                f"{name}: second same-shape submit compiled {d['compile']} "
                f"executables with {d['hit']} dispatch.hit; required 0 and "
                f">= 1")
        t_repeat, repeat, d, submit_s, serve_s, _ = self._timed(self.bind_a)
        ctx.say(f"serve {name} repeat of the first: submit {submit_s:.3f}s "
                f"serve {serve_s:.3f}s, cache.hit {d['cache.hit']} "
                f"queue_wait_s {t_repeat.queue_wait_s}")
        if d["cache.hit"] != 1 or t_repeat.queue_wait_s != 0:
            raise SmokeFailure(
                f"{name}: repeated submit was not a result-cache hit "
                f"(cache.hit +{d['cache.hit']}, cache.too_big "
                f"+{d['cache.too_big']}, queue_wait_s "
                f"{t_repeat.queue_wait_s})")
        if _table_bytes(repeat.table) != _table_bytes(cold.table):
            raise SmokeFailure(f"{name}: cached result differs from the first")
        # (tier, rung, steps) only: a kept ticket would keep its bound tables
        tickets += [(f"{name} {label}", (t.tier, t.rung, t.steps))
                    for label, t in (("cold", self._cold),
                                     ("second", t_second))]
        report[name] = {"cold_s": cold_s, "cold_compile_s": compile_s,
                        "warm_s": warm_s, "peak_bytes_in_use": peak}


def _write_parquet(li, path: str) -> None:
    """The SF1 q1 columns written with pyarrow (snappy): data generation
    only — the reader under test is ours (bench.py's parquet_q1 layout)."""
    import numpy as np
    import pyarrow as pa
    import pyarrow.parquet as pq

    def col(i):
        return np.asarray(li.column(i).data)

    pq.write_table(pa.table({
        "l_quantity": pa.array(col(0), type=pa.int64()),
        "l_extendedprice": pa.array(col(1), type=pa.int64()),
        "l_discount": pa.array(col(2), type=pa.int64()),
        "l_tax": pa.array(col(3), type=pa.int64()),
        "l_returnflag": pa.array(col(4), type=pa.int8()),
        "l_linestatus": pa.array(col(5), type=pa.int8()),
        "l_shipdate": pa.array(col(6)).cast(pa.date32()),
    }), path, compression="snappy")


def _parquet_split(path: str):
    """The file as one scan task's split: all of it, the seven columns by
    name, the unscaled int64 read as the money decimals q1 wants. The
    server reads the footer, admits, decodes and stages it itself."""
    from spark_rapids_jni_tpu import types as t
    from spark_rapids_jni_tpu.parquet import ParquetSplit

    return ParquetSplit(
        path, ("l_quantity", "l_extendedprice", "l_discount", "l_tax",
               "l_returnflag", "l_linestatus", "l_shipdate"),
        dtypes=(t.decimal64(-2),) * 4 + (None,) * 3)


def serve_phase(sizes: dict, platform: str, seed: int = 0,
                scratch: str = SCRATCH) -> dict:
    ctx = _Ctx(platform)
    from spark_rapids_jni_tpu.models import tpch
    from spark_rapids_jni_tpu.ops.row_conversion import (
        convert_from_rows,
        convert_to_rows,
    )
    from spark_rapids_jni_tpu.runtime import fusion, native
    from spark_rapids_jni_tpu.runtime.memory import device_memory_stats
    from spark_rapids_jni_tpu.runtime.server import QueryServer

    os.makedirs(scratch, exist_ok=True)
    report: dict = {}
    tickets: list = []

    t0 = time.perf_counter()
    lib = native.load_native()  # configures and builds src/native (no-op when current)
    ctx.say(f"native: {lib.path} built from src/native in "
            f"{time.perf_counter() - t0:.1f}s")

    budget = device_memory_stats(ctx.device).bytes_limit
    if platform != "cpu" and budget <= 0:
        raise SmokeFailure(
            "device_memory_stats().bytes_limit is 0: the backend reports "
            "no HBM limit, so the server has no budget to admit against")
    budget = budget or (4 << 30)  # CPU backends report none (tests only)

    def load(make, *args):
        t0 = time.perf_counter()
        tables = [make(*args, seed=seed + i) for i in (0, 1)]
        for tb in tables:
            ctx.sync(tb)
        return tables, time.perf_counter() - t0

    # the numpy oracles are host work: they run on this pool while the
    # device compiles and serves, and are collected where a result is
    # checked (one entry per seeded table, "a" and "b")
    pool = concurrent.futures.ThreadPoolExecutor(4)

    def oracle_pair(fn, *pairs):
        futs = {k: pool.submit(fn, *tabs) for k, tabs in zip("ab", pairs)}
        return lambda k: futs[k].result()

    with QueryServer(budget_bytes=budget) as srv:
        session = srv.session("smoke")
        # the result cache sizes itself from that budget (cache.max_bytes
        # 0): one padded general-q3 result at SF1 is 12,002,430 rows, 336 MB
        ctx.say(f"serve: QueryServer budget_bytes {budget} (bytes_limit), "
                f"result cache max_bytes "
                f"{srv.result_cache.stats()['max_bytes']}")

        def served(name, plan, bind_a, bind_b, check):
            return _Served(ctx, session, name, plan, bind_a, bind_b, check)

        # -- SF10 lineitem resident: the scan-bound plans, one at a time ---
        n10 = sizes["sf10_rows"]
        (li_a, li_b), load_s = load(tpch.lineitem_table, n10)
        ctx.say(f"load: 2 x lineitem {n10} rows in {load_s:.1f}s, "
                f"bytes_in_use {ctx.bytes_in_use()}")
        q1_ref = oracle_pair(tpch.tpch_q1_numpy, (li_a,), (li_b,))
        q6_ref = oracle_pair(tpch.tpch_q6_numpy, (li_a,), (li_b,))
        ctx.say("oracle: numpy q1 and q6 run on host threads beside the "
                f"device (integers exact, float64 averages rtol {F64_RTOL})")
        # general q1 is not served at SF10: beside two resident SF10
        # tables its sort does not fit the 16 GB of a v5e (CHANGES.md)
        for name, plan, check in (
                ("q1_planned", tpch._q1_planned_plan(),
                 lambda tb, k, w: check_q1(tb, q1_ref(k), w)),
                ("q6", tpch._q6_plan(),
                 lambda tb, k, w: check_q6(tb, q6_ref(k), w))):
            served(f"{name}@{n10}", plan, {"lineitem": li_a},
                   {"lineitem": li_b}, check).finish(tickets, report)
        del li_a, li_b

        # -- SF1: general q1, q1 from Parquet, both q3 plans. Their cold
        # submits go in together: XLA:TPU takes minutes over the general
        # join, and the server's workers compile side by side ------------
        n1 = sizes["sf1_rows"]
        ncust, nord = sizes["customers"], sizes["orders"]
        t0 = time.perf_counter()
        li_a, li_b, pq_a, pq_b = (tpch.lineitem_table(n1, seed + i)
                                  for i in range(4))
        q3_tabs = [(tpch.customer_table(ncust, seed=seed + i),
                    tpch.orders_table(nord, ncust, seed=seed + i + 10),
                    tpch.lineitem_q3_table(n1, nord, seed=seed + i + 20))
                   for i in (0, 1)]
        q1_sf1 = oracle_pair(tpch.tpch_q1_numpy, (li_a,), (li_b,))
        pq_ref = oracle_pair(tpch.tpch_q1_numpy, (pq_a,), (pq_b,))
        q3_ref = oracle_pair(tpch.tpch_q3_numpy, *q3_tabs)
        ctx.say(f"load: 4 x lineitem {n1} rows, 2 x (customer {ncust}, "
                f"orders {nord}, lineitem {n1}) in "
                f"{time.perf_counter() - t0:.1f}s")
        report["fingerprint"] = check_fingerprint(
            ctx, li_b, f"lineitem@{n1}")
        check_fingerprint(ctx, _every_digested_dtype(n1, seed),
                          f"every digested dtype@{n1}")
        # q1 on tables that arrive as Parquet files: the server decodes
        # them itself through parquet/split.py (their own seeds, so the
        # oracle of each is its own)
        t0 = time.perf_counter()
        parquet = []
        for k, src in (("a", pq_a), ("b", pq_b)):
            path = os.path.join(scratch, f"lineitem_{k}.parquet")
            _write_parquet(src, path)
            parquet.append(_parquet_split(path))
        del pq_a, pq_b
        ctx.say(f"parquet: 2 x {n1} rows written (pyarrow, snappy) in "
                f"{time.perf_counter() - t0:.1f}s, bound as splits")
        binds = [dict(zip(("customer", "orders", "lineitem"), tabs))
                 for tabs in q3_tabs]
        cutoff = tpch._Q3_CUTOFF_DAYS
        runs = [
            served(f"q3_general@{n1}", tpch._q3_plan(0, cutoff, 2), *binds,
                   lambda tb, k, w: check_q3(tb, q3_ref(k), w)),
            served(f"q3_planned@{n1}", tpch._q3_planned_plan(0, cutoff),
                   *binds, lambda tb, k, w: check_q3(tb, q3_ref(k), w)),
            served(f"q1_general@{n1}", tpch._q1_plan(), {"lineitem": li_a},
                   {"lineitem": li_b},
                   lambda tb, k, w: check_q1(tb, q1_sf1(k), w)),
            served(f"q1_parquet@{n1}", tpch._q1_plan(),
                   {"lineitem": parquet[0]}, {"lineitem": parquet[1]},
                   lambda tb, k, w: check_q1(tb, pq_ref(k), w)),
        ]

        # meanwhile, here: one bit-exact row-conversion round trip (the
        # reference's own job, and ops/bytecast.py's branch for this backend)
        t0 = time.perf_counter()
        back = [convert_from_rows(rows, li_a.schema())
                for rows in convert_to_rows(li_a)]
        if len(back) != 1 or [b[0] for b in _table_bytes(back[0])] != [
                b[0] for b in _table_bytes(li_a)]:
            raise SmokeFailure("row conversion round trip is not bit-exact")
        ctx.say(f"rows: convert_to_rows/convert_from_rows of {n1} rows "
                f"bit-exact in {time.perf_counter() - t0:.1f}s (compiled "
                f"beside the cold submits)")
        del back

        for run in reversed(runs):  # the quick compiles first
            run.finish(tickets, report)
        for split in parquet:
            os.unlink(split.path)
        ctx.say(f"oracle: numpy q3 has {len(q3_ref('a'))} groups")

        # timed honestly: if four enqueued runs cost less than twice one
        # run, block_until_ready does not wait for the device. Judged on
        # the chip only: on a CPU shared with other work the ratio of two
        # wall-clock readings says nothing. One run is the quickest of three
        plan = tpch._q1_plan()
        one = math.inf
        for _ in range(3):
            t0 = time.perf_counter()
            ctx.sync(fusion.execute(plan, {"lineitem": li_a}).table)
            one = min(one, time.perf_counter() - t0)
        t0 = time.perf_counter()
        for _ in range(4):
            last = fusion.execute(plan, {"lineitem": li_a})
        ctx.sync(last.table)
        four = time.perf_counter() - t0
        ctx.say(f"sync: general q1 at {n1} rows warm, one run {one:.4f}s, "
                f"four enqueued runs {four:.4f}s (ratio {four / one:.2f})")
        if platform == "tpu" and four < 2 * one:
            raise SmokeFailure(
                f"block_until_ready is not a sync: four runs took "
                f"{four:.4f}s, one took {one:.4f}s")
        report["sync"] = {"one_s": one, "four_s": four}
        del runs, parquet, q3_tabs, binds, li_a, li_b, last
    pool.shutdown()

    leaked = srv.limiter.used - srv.result_cache.evictable_bytes
    if leaked != 0:
        raise SmokeFailure(
            f"limiter.used did not drain to the result cache's resident "
            f"charge after close(): {leaked} bytes still reserved")
    check_counters(tickets=tickets)
    ctx.say(f"serve: {len(tickets)} executed tickets at tier fused rung 0, "
            f"no fallback counter set, limiter drained; allocator peak "
            f"{(ctx.device.memory_stats() or {}).get('peak_bytes_in_use')}")
    return report


# ---------------------------------------------------------------------------
# phase: recompile — a second process must find the first one's executables
# ---------------------------------------------------------------------------


def recompile_phase(sizes: dict, platform: str, seed: int = 0) -> dict:
    """General q1 at SF1 again in a fresh process: JAX itself must report
    persistent-cache hits (its ``jax.monitoring`` events, which
    ``runtime/dispatch.py`` counts for the executables it compiles:
    ``dispatch.xla.persistent_hit`` / ``_miss``), and the compile time is
    printed beside the serve phase's cold one."""
    ctx = _Ctx(platform)
    from spark_rapids_jni_tpu.models import tpch
    from spark_rapids_jni_tpu.runtime import fusion
    from spark_rapids_jni_tpu.utils.config import cache_dir

    li = tpch.lineitem_table(sizes["sf1_rows"], seed)
    t0 = time.perf_counter()
    ctx.sync(fusion.execute(tpch._q1_plan(), {"lineitem": li}).table)
    wall = time.perf_counter() - t0
    xla = {k.rsplit(".", 1)[1]: v
           for k, v in _counters("dispatch.xla.").items()}
    hits = xla.get("persistent_hit", 0)
    misses = xla.get("persistent_miss", 0)
    ctx.say(f"recompile: general q1 at {sizes['sf1_rows']} rows in a second "
            f"process, compile {_compile_s():.3f}s of {wall:.3f}s (tracing "
            f"and lowering {xla.get('trace_lower_ns', 0) / 1e9:.3f}s, the "
            f"backend {xla.get('backend_ns', 0) / 1e9:.3f}s, of it the "
            f"cache's load {xla.get('cache_load_ns', 0) / 1e9:.3f}s); "
            f"persistent cache at {cache_dir()}: {hits} hits {misses} misses")
    if hits < 1:
        raise SmokeFailure(
            "the second process found nothing in the persistent compile "
            f"cache at {cache_dir()!r} (cache_hits 0)")
    check_counters()
    return {"compile_s": _compile_s(), "cache_hits": hits,
            "cache_misses": misses}


# ---------------------------------------------------------------------------
# phases with a supervisor that stays off the chip: fleet, cluster
# ---------------------------------------------------------------------------


def _cpu_supervisor(info: dict):
    """Pin THIS process to the CPU (it only frames, fingerprints and
    routes) and return say(), tagged with the device the probe found."""
    from spark_rapids_jni_tpu.utils.config import set_option
    from spark_rapids_jni_tpu.utils.platform import force_cpu_platform

    force_cpu_platform()
    set_option("telemetry.enabled", True)
    tag = _tag(info)

    def say(msg: str) -> None:
        print(f"{tag} {msg}", flush=True)

    return say


def _check_replicas(inspect: dict, n: int, platform: str, what: str) -> list:
    devices = [r["device"] for r in inspect["replicas"]
               if r["state"] == "live"]
    if len(devices) != n:
        raise SmokeFailure(f"{what}: {len(devices)} of {n} workers live")
    for d in devices:
        if d.get("platform") != platform:
            raise SmokeFailure(
                f"{what}: a worker reports platform {d.get('platform')!r}, "
                f"expected {platform!r}")
    chips = [d.get("chip") for d in devices]
    if platform == "tpu" and len(set(chips)) != n:
        raise SmokeFailure(f"{what}: workers share chips: {chips}")
    return devices


def fleet_phase(sizes: dict, platform: str, info: dict,
                seed: int = 0) -> dict:
    say = _cpu_supervisor(info)
    from spark_rapids_jni_tpu.models import tpch
    from spark_rapids_jni_tpu.runtime.fleet import QueryFleet

    n, rows = info["count"], sizes["sf1_rows"]
    plan = tpch._q1_planned_plan()
    t0 = time.perf_counter()
    with QueryFleet(n, worker_env={"JAX_PLATFORMS": platform}) as fleet:
        live = fleet.wait_live(timeout=240)
        devices = _check_replicas(fleet.inspect(), n, platform, "fleet")
        say(f"fleet: {live}/{n} workers live in "
            f"{time.perf_counter() - t0:.1f}s, supervisor on cpu: {devices}")
        served: set = set()
        for attempt in range(3):
            # n distinct tables in flight together: the router places each
            # on the replica with the least outstanding work
            tables = [tpch.lineitem_table(rows, seed + 10 * attempt + i)
                      for i in range(n)]
            results: list = [None] * n

            def one(i: int) -> None:
                t = fleet.submit("smoke", plan, {"lineitem": tables[i]})
                results[i] = (t, t.result(timeout=600))

            threads = [threading.Thread(target=one, args=(i,))
                       for i in range(n)]
            t0 = time.perf_counter()
            for th in threads:
                th.start()
            for th in threads:
                th.join()
            for i, item in enumerate(results):
                if item is None:
                    raise SmokeFailure(f"fleet: query {i} did not resolve")
                ticket, result = item
                check_q1(result.table, tpch.tpch_q1_numpy(tables[i]),
                         f"fleet planned q1 on {ticket.replica}")
                served.add(ticket.replica)
            say(f"fleet: {n} planned q1 at {rows} rows served by "
                f"{sorted(served)} in {time.perf_counter() - t0:.1f}s, "
                f"equal to the oracle")
            if len(served) == n:
                break
        if len(served) != n:
            raise SmokeFailure(
                f"fleet: only {sorted(served)} of {n} replicas served")
        c = _counters("fleet.")
        for name in ("fleet.replica_deaths", "fleet.boot_refused",
                     "fleet.failovers"):
            if c.get(name, 0):
                raise SmokeFailure(f"{name} = {c[name]}, required 0")
    return {"replicas": n, "devices": devices}


def cluster_phase(sizes: dict, platform: str, info: dict,
                  seed: int = 0, hosts: int = 4) -> dict:
    say = _cpu_supervisor(info)
    from spark_rapids_jni_tpu.models import tpch
    from spark_rapids_jni_tpu.runtime.cluster import QueryCluster

    orders = tpch.orders_table(sizes["cluster_orders"],
                               sizes["cluster_customers"], seed=seed)
    t0 = time.perf_counter()
    want = _table_bytes(tpch.tpch_q13_local(orders, hosts))
    say(f"cluster: tpch_q13_local reference on the cpu supervisor in "
        f"{time.perf_counter() - t0:.1f}s")
    t0 = time.perf_counter()
    with QueryCluster(hosts,
                      worker_env={"JAX_PLATFORMS": platform}) as cluster:
        cluster.wait_live(timeout=240)
        devices = _check_replicas(cluster.inspect(), hosts, platform,
                                  "cluster")
        say(f"cluster: {hosts} host workers live in "
            f"{time.perf_counter() - t0:.1f}s: {devices}")
        t0 = time.perf_counter()
        cluster.register_table("orders", orders, keys=(tpch.O_ORDERKEY,))
        ticket = cluster.submit_exchange(
            "smoke", tpch.q13_midplan_plan(0), table="orders",
            binding="orders")
        got = ticket.result(timeout=600)
        same = _table_bytes(got) == want
        say(f"cluster: q13 mid-plan exchange over {hosts} hosts, "
            f"{orders.num_rows} orders, {got.num_rows} groups in "
            f"{time.perf_counter() - t0:.1f}s, equal to tpch_q13_local: "
            f"{same}")
        if not same:
            raise SmokeFailure("cluster q13 exchange differs from "
                               "tpch_q13_local")
        c = _counters()
        for name in ("fleet.replica_deaths", "fleet.boot_refused",
                     "cluster.exchange_direct_fallbacks"):
            if c.get(name, 0):
                raise SmokeFailure(f"{name} = {c[name]}, required 0")
        if c.get("cluster.exchanges_direct", 0) != 1:
            raise SmokeFailure(
                "cluster.exchanges_direct = "
                f"{c.get('cluster.exchanges_direct', 0)}, required 1")
        say(f"cluster: the exchange stayed on the direct host-to-host lane "
            f"(exchange.direct_timeout_s at its default, "
            f"{c.get('exchange.bytes_direct', 0)} bytes direct, "
            f"{c.get('exchange.bytes_routed', 0)} routed)")
    return {"hosts": hosts, "devices": devices}


# ---------------------------------------------------------------------------
# phase: mesh — one process drives four chips
# ---------------------------------------------------------------------------


def mesh_phase(sizes: dict, platform: str, seed: int = 0,
               chips: int = 4) -> dict:
    ctx = _Ctx(platform)
    import jax
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from spark_rapids_jni_tpu.columnar import Column, Table
    from spark_rapids_jni_tpu.models import tpch
    from spark_rapids_jni_tpu.parallel import (
        EXEC_AXIS,
        executor_mesh,
        hash_shuffle,
        shard_table,
    )

    devices = jax.devices()[:chips]
    mesh = executor_mesh(chips, devices=devices)
    in_use0 = [ctx.bytes_in_use(d) for d in devices]

    def spans_all(array, what: str) -> None:
        got = set(array.sharding.device_set)
        if got != set(devices):
            raise SmokeFailure(
                f"{what} lives on {sorted(d.id for d in got)}, expected "
                f"all of {[d.id for d in devices]}")

    n10 = sizes["sf10_rows"]
    li = tpch.lineitem_table(n10, seed)
    sharded = shard_table(li, mesh)
    spans_all(sharded.column(0).data, "sharded lineitem")
    in_use = [ctx.bytes_in_use(d) for d in devices]
    ctx.say(f"mesh: lineitem {n10} rows sharded {chips} ways "
            f"({-(-n10 // chips)} rows a chip); bytes_in_use per device "
            f"{in_use}")
    if platform != "cpu" and not all(
            b > a for a, b in zip(in_use0, in_use)):
        raise SmokeFailure(
            f"bytes_in_use did not rise on every device: {in_use0} -> "
            f"{in_use}")
    del sharded

    # the Plan a client submits (tpch._q1_distributed_plan through
    # fusion.execute), bound to the table sharded over the mesh
    t0 = time.perf_counter()
    out = tpch.tpch_q1_distributed(li, mesh)
    ctx.sync(out)
    cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    ctx.sync(tpch.tpch_q1_distributed(li, mesh))
    warm = time.perf_counter() - t0
    check_q1(out, tpch.tpch_q1_numpy(li), "distributed q1")
    ctx.say(f"mesh: tpch_q1_distributed at {n10} rows over "
            f"executor_mesh({chips}) equal to the oracle; cold {cold:.1f}s "
            f"warm {warm:.1f}s")
    del li

    # one hash_shuffle with its overflow flag read
    n1 = sizes["sf1_rows"]
    rng = np.random.default_rng(seed)
    keys = rng.integers(0, 1 << 20, n1).astype(np.int64)
    vals = rng.integers(0, 100, n1).astype(np.int64)
    tbl, rv_in = shard_table(
        Table([Column.from_numpy(keys), Column.from_numpy(vals)]), mesh,
        return_row_valid=True)
    per_dev = -(-n1 // chips)

    def step(local, rv):
        sh = hash_shuffle(local, [0], EXEC_AXIS, capacity=2 * per_dev,
                          row_valid=rv)
        return sh.table, sh.row_valid, sh.overflowed.reshape(1)

    t0 = time.perf_counter()
    sh_tbl, row_valid, overflowed = jax.jit(jax.shard_map(
        step, mesh=mesh, in_specs=(P(EXEC_AXIS), P(EXEC_AXIS)),
        out_specs=(P(EXEC_AXIS),) * 3))(tbl, rv_in)
    spans_all(sh_tbl.column(0).data, "shuffle output")
    rv = np.asarray(row_valid)
    if np.asarray(overflowed).any():
        raise SmokeFailure("hash_shuffle overflowed its capacity")
    got_keys = np.sort(np.asarray(sh_tbl.column(0).data)[rv])
    if not np.array_equal(got_keys, np.sort(keys)):
        raise SmokeFailure("hash_shuffle lost or invented rows")
    ctx.say(f"mesh: hash_shuffle of {n1} rows over {chips} chips, overflow "
            f"flag False, key multiset preserved "
            f"({time.perf_counter() - t0:.1f}s)")
    del tbl, sh_tbl

    ncust, nord = sizes["customers"], sizes["orders"]
    q3 = (tpch.customer_table(ncust, seed=seed),
          tpch.orders_table(nord, ncust, seed=seed + 10),
          tpch.lineitem_q3_table(n1, nord, seed=seed + 20))
    t0 = time.perf_counter()
    out = tpch.tpch_q3_planned_distributed(*q3, mesh)
    ctx.sync(out)
    wall = time.perf_counter() - t0
    check_q3(out, tpch.tpch_q3_numpy(*q3), "distributed planned q3")
    ctx.say(f"mesh: tpch_q3_planned_distributed (customer {ncust}, orders "
            f"{nord}, lineitem {n1}) equal to the oracle ({out.num_rows} "
            f"groups, {wall:.1f}s cold)")
    check_counters()
    return {"chips": chips, "q1_cold_s": cold, "q1_warm_s": warm}


# ---------------------------------------------------------------------------
# the parent: runs the phases as children, never imports JAX
# ---------------------------------------------------------------------------


def _child(phase: str, deadline: float, scratch: str) -> dict:
    """Run one phase as a child process (its own session, so a timeout
    stops everything it started) and read its report file."""
    out = os.path.join(scratch, f"{phase}.json")
    if os.path.exists(out):
        os.unlink(out)
    left = deadline - time.monotonic()
    if left <= 0:
        raise SmokeFailure(f"out of time before phase {phase}")
    t0 = time.monotonic()
    proc = subprocess.Popen([sys.executable, os.path.abspath(__file__), phase],
                            cwd=ROOT, start_new_session=True)
    try:
        rc = proc.wait(timeout=left)
    except subprocess.TimeoutExpired:
        rc = None
    finally:
        # the child's whole process group: fleet and cluster workers too
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        proc.wait()
    if rc is None:
        raise SmokeFailure(f"phase {phase} exceeded the smoke's time limit")
    if rc != 0:
        raise SmokeFailure(f"phase {phase} failed with exit code {rc}")
    with open(out) as fh:
        report = json.load(fh)
    print(f"phase {phase}: ok in {time.monotonic() - t0:.1f}s", flush=True)
    return report


def _run_phase(phase: str, scratch: str = SCRATCH) -> None:
    """Child entry: run one phase, write its report for the parent."""
    os.makedirs(scratch, exist_ok=True)

    def info() -> dict:
        with open(os.path.join(scratch, "probe.json")) as fh:
            return json.load(fh)["device"]

    phases = {
        "probe": lambda: probe_phase("tpu"),
        "serve": lambda: serve_phase(FULL, "tpu"),
        "recompile": lambda: recompile_phase(FULL, "tpu"),
        "fleet": lambda: fleet_phase(FULL, "tpu", info()),
        "mesh": lambda: mesh_phase(FULL, "tpu"),
        "cluster": lambda: cluster_phase(FULL, "tpu", info()),
    }
    report = phases[phase]()
    with open(os.path.join(scratch, f"{phase}.json"), "w") as fh:
        json.dump(report, fh)


def main() -> int:
    deadline = time.monotonic() + DEADLINE_S
    os.makedirs(SCRATCH, exist_ok=True)
    t0 = time.monotonic()
    probe = _child("probe", deadline, SCRATCH)
    device = probe["device"]
    tag = _tag(device)
    serve = _child("serve", deadline, SCRATCH)
    warm = _child("recompile", deadline, SCRATCH)
    cold = serve[f"q1_general@{FULL['sf1_rows']}"]["cold_compile_s"]
    print(f"{tag} compile cache: general q1 at SF1 compiled in {cold:.3f}s "
          f"in the serve phase and in {warm['compile_s']:.3f}s in a second "
          f"process ({warm['cache_hits']} persistent-cache hits)", flush=True)
    _child("fleet", deadline, SCRATCH)
    if device["count"] >= 4:
        deadline += FOUR_CHIP_S
        _child("mesh", deadline, SCRATCH)
        _child("cluster", deadline, SCRATCH)
    else:
        print(f"{tag} four-chip phase did NOT run: jax.devices() reports "
              f"{device['count']} device(s), it needs 4 (executor_mesh(4), "
              f"QueryCluster(4)); this is not a pass of that phase",
              flush=True)
    print(f"{tag} all phases passed in {time.monotonic() - t0:.1f}s",
          flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    if len(sys.argv) > 1:
        _run_phase(sys.argv[1])
        sys.exit(0)
    sys.exit(main())
