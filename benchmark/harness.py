"""One run of one cell: set-up, the measured window, the comparison with
the reference, and the contract's result line.

The functions take the platform they must find as an argument, so the
tests under ``benchmark/tests`` drive them tiny on the CPU; ``run.py``
always asks for the TPU. From the program the harness takes the served
path (``QueryServer`` / ``Session.submit`` / ``ticket.result()``), its
counters and the ticket's fields; the clock, the traffic, the reference,
the comparison and the reduction to metrics are the benchmark's own.
"""

from __future__ import annotations

import glob
import json
import math
import os
import random
import shutil
import statistics
import tempfile
import threading
import time
from dataclasses import dataclass, field

from benchmark import resolve, trace_reduce

# counters that must not move while a request is served (the list of
# chip_smoke.check_counters): each is a recovery rung that turns a failure
# into a slower success
ZERO_COUNTERS = (
    "dispatch.compile_error", "dispatch.exec_error",
    "dispatch.inline.compile_error", "dispatch.inline.exec_error",
    "fusion.staged_regions", "resilience.rung.host_fallback",
    "resilience.rung.staged_fallback", "degrade.step")
ZERO_PREFIXES = ("fallback.fusion.", "kernels.fallback.", "degrade.tier.")
TRACED_REQUESTS = 3        # the traced part of a --trace 1 window: this
TRACED_SECONDS = 15.0      # many requests or seconds, whichever ends first


class BenchFailure(RuntimeError):
    """The run cannot give a number; the message says why."""


@dataclass
class Request:
    """One request as the client saw it."""
    plan: str
    rows: int
    submit_s: float = math.nan        # clock around Session.submit alone
    latency_s: float = math.nan       # submit start .. result synced
    queue_wait_s: float | None = None
    where: tuple = (None, None, None)  # (tier, rung, steps) it finished at
    moved: dict = field(default_factory=dict)   # counter deltas over it
    answer: object = None
    error: str | None = None          # why it failed, once known


@dataclass
class Run:
    """What the layer-metric readers read."""
    workload: dict
    config: dict
    mix: dict
    plans: dict                       # plan name -> its module
    requests: list                    # the window's requests, in order
    counters: dict                    # counter deltas across the window
    trace: dict | None                # trace_reduce.reduce(), --trace 1 only
    peaks: dict                       # this device kind's row of peaks.json
    device: dict


def percentile_nearest_rank(values: list, q: float) -> float:
    ordered = sorted(values)
    return ordered[max(0, math.ceil(q / 100.0 * len(ordered)) - 1)]


def _counters() -> dict:
    from spark_rapids_jni_tpu.telemetry import REGISTRY

    return REGISTRY.counters()


def _delta(after: dict, before: dict) -> dict:
    return {k: v - before.get(k, 0) for k, v in after.items()
            if v != before.get(k, 0)}


def _moved_fallbacks(moved: dict, native: bool) -> list:
    names = [k for k in moved if k in ZERO_COUNTERS
             or k.startswith(ZERO_PREFIXES)
             or (native and k == "kernels.interpret")]
    return sorted(names)


def find_device(platform: str, chips: int) -> dict:
    """The device as JAX reports it; raises unless it is ``platform`` with
    at least ``chips`` devices. Nothing runs before this has passed."""
    import jax

    devs = jax.devices()
    info = {"platform": str(devs[0].platform),
            "kind": str(devs[0].device_kind), "count": len(devs)}
    if info["platform"] != platform or len(devs) < chips:
        raise BenchFailure(
            f"no chip found: JAX reports {info['count']} x "
            f"{info['platform']!r} ({info['kind']}), the cell needs "
            f"{chips} x {platform!r}")
    return info


def _peaks(kind: str, platform: str) -> dict:
    with open(os.path.join(resolve.HERE, "peaks.json")) as f:
        peaks = json.load(f)
    if kind in peaks:
        return peaks[kind]
    if platform == "cpu":   # tests only: no roofline is claimed off the chip
        return {}
    raise BenchFailure(
        f"device kind {kind!r} is not in benchmark/peaks.json: add its "
        f"peaks with their source, no default is assumed")


def _plan_cycle(mix: dict, seed: int):
    """The mix's plans in weighted turns: every cycle holds each plan
    ``weight`` times, in an order drawn from the seed, so every seed sends
    the same work in another order."""
    cycle = [p["plan"] for p in mix["plans"] for _ in range(int(p["weight"]))]
    rng = random.Random(int(seed))
    while True:
        turn = list(cycle)
        rng.shuffle(turn)
        yield from turn


def make_tables(config: dict, seed: int, sizes: dict) -> dict:
    """{table name: (its maker, rows, device arrays)} of a configuration,
    made on the device from the seed. ``sizes`` replaces row counts."""
    import jax

    out = {}
    for i, (name, spec) in enumerate(sorted(config["tables"].items())):
        maker = resolve.module("tables", spec["maker"])
        rows = int(sizes.get(name, spec["rows"]))
        arrays = maker.make(rows, seed + i)
        jax.block_until_ready(arrays)
        out[name] = (maker, rows, arrays)
    return out


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             platform: str = "tpu", t_start: float | None = None,
             sizes: dict | None = None, keep_trace: str | None = None,
             say=print) -> dict:
    """Run one cell and return the contract's result object (the caller
    prints it as the last line). ``sizes`` ({table name: rows}) replaces
    the configuration's row counts: tests only. ``keep_trace`` names a
    directory that gets a copy of a traced run's ``.xplane.pb``, for looking
    at one by hand or recording the fixture of ``tests/``."""
    t_start = time.perf_counter() if t_start is None else t_start
    bench = resolve.spec()
    cell, config, mix = resolve.cell(workload, bench)

    # the program, and the three options the benchmark sets: telemetry on
    # (record_fallback counts only while it is), and the two stores of
    # learned state pointed at files of this process, so no run admits or
    # gates from what an earlier run learned
    from spark_rapids_jni_tpu.utils.config import set_option

    device = find_device(platform, int(cell["chips"]))
    tag = "[{platform} {kind} x{count}]".format(**device)

    def line(msg: str) -> None:
        say(f"{tag} {msg}", flush=True)

    scratch = tempfile.mkdtemp(prefix="benchmark-run-")
    try:
        set_option("telemetry.enabled", True)
        set_option("server.estimate_path",
                   os.path.join(scratch, "estimates.json"))
        set_option("rtfilter.path", os.path.join(scratch, "rtfilter.json"))
        return _run(cell, config, mix, bench, device, int(seed),
                    float(seconds), bool(trace), platform, t_start,
                    sizes or {}, scratch, keep_trace, line)
    finally:
        shutil.rmtree(scratch, ignore_errors=True)


def _run(cell, config, mix, bench, device, seed, seconds, trace, platform,
         t_start, sizes, scratch, keep_trace, line) -> dict:
    import jax

    from spark_rapids_jni_tpu.runtime.memory import device_memory_stats
    from spark_rapids_jni_tpu.runtime.server import QueryServer

    native = platform != "cpu"
    peaks = _peaks(device["kind"], platform)
    plans = {p["plan"]: resolve.module("plans", p["plan"])
             for p in mix["plans"]}
    loop = resolve.module("loops", mix["loop"])
    line(f"cell {cell['name']}: config {cell['config']}, mix "
         f"{cell['traffic']}, seed {seed}, window {seconds}s, trace "
         f"{int(trace)}, set-up so far {time.perf_counter() - t_start:.3f}s")

    # -- tables, on the device from the seed; the host copy feeds the
    # reference, which runs on a thread beside the warm-up ------------------
    t0 = time.perf_counter()
    tables, fresheners, base = {}, {}, {}
    for name, (maker, rows, arrays) in make_tables(config, seed,
                                                    sizes).items():
        base[name] = arrays
        fresheners[name] = resolve.module("fresh", mix["fresh"]).Freshener(
            arrays, seed)
        tables[name] = (maker, rows)
    del arrays
    line(f"tables: {', '.join(f'{n} {r} rows' for n, (_, r) in tables.items())}"
         f" made on the device in {time.perf_counter() - t0:.3f}s")

    oracles: dict = {}

    def reference() -> None:
        """Copy the base tables to the host and answer every plan of the
        mix over them, beside the warm-up."""
        t0 = time.perf_counter()
        hosts = {}
        while base:
            name, arrays = base.popitem()
            hosts[name] = tables[name][0].host_copy(arrays)
            del arrays
        oracles["_copy_s"] = time.perf_counter() - t0
        for name, mod in plans.items():
            oracles[name] = mod.oracle(hosts[mod.TABLE])
        oracles["_seconds"] = time.perf_counter() - t0

    ref_thread = threading.Thread(target=reference, name="reference")
    ref_thread.start()

    budget = device_memory_stats(jax.devices()[0]).bytes_limit
    if budget <= 0:
        if native:
            raise BenchFailure(
                "device_memory_stats().bytes_limit is 0: the server has no "
                "budget to admit against")
        budget = 4 << 30   # CPU backends report none (tests only)

    def sync(table) -> None:
        jax.block_until_ready([a for c in table.columns
                               for a in (c.data, c.validity) if a is not None])

    with QueryServer(budget_bytes=budget) as srv:
        session = srv.session("benchmark")
        compiled = {name: mod.plan() for name, mod in plans.items()}

        def one_request(plan_name: str) -> Request:
            """Make the next table fresh (outside the clock), then time
            submit .. result .. synced from the client's side."""
            mod = plans[plan_name]
            maker, rows = tables[mod.TABLE]
            with jax.profiler.TraceAnnotation("bench.roll"):
                table = maker.to_table(fresheners[mod.TABLE].next())
            req = Request(plan_name, rows)
            before = _counters()
            try:
                with jax.profiler.TraceAnnotation("bench.request"):
                    t0 = time.perf_counter()
                    with jax.profiler.TraceAnnotation("bench.submit"):
                        ticket = session.submit(
                            compiled[plan_name], {mod.BINDING: table})
                    t1 = time.perf_counter()
                    with jax.profiler.TraceAnnotation("bench.result"):
                        result = ticket.result()
                        sync(result.table)
                    t2 = time.perf_counter()
                req.submit_s, req.latency_s = t1 - t0, t2 - t0
                req.queue_wait_s = ticket.queue_wait_s
                req.where = (ticket.tier, ticket.rung, ticket.steps)
                with jax.profiler.TraceAnnotation("bench.readback"):
                    req.answer = mod.read_answer(result.table)
                del ticket, result
            except Exception as e:  # the run goes on; the request failed
                req.error = f"raised {type(e).__name__}: {e}"
            req.moved = _delta(_counters(), before)
            return req

        # -- warm-up: every plan of the mix once, on a rolled table, so the
        # window compiles nothing (checked like any other request) ---------
        warm = []
        for name in plans:
            c0 = _counters()
            t0 = time.perf_counter()
            warm.append(one_request(name))
            line(f"warm-up {name}: {time.perf_counter() - t0:.3f}s, compiled "
                 f"{_delta(_counters(), c0).get('dispatch.compile', 0)} "
                 f"executables")
        ref_thread.join()
        if "_seconds" not in oracles:
            raise BenchFailure("the reference did not finish (see above)")
        line(f"reference: {', '.join(plans)} in plain numpy on a host "
             f"thread beside the warm-up, {oracles['_seconds']:.3f}s of "
             f"which {oracles['_copy_s']:.3f}s copying the tables back")
        setup_s = time.perf_counter() - t_start

        # -- the window ------------------------------------------------------
        cycle = _plan_cycle(mix, seed)
        window: list = []
        tracing = {"on": False, "dir": os.path.join(scratch, "trace")}

        def stop_trace(done: int, elapsed: float) -> None:
            if tracing["on"] and (done >= TRACED_REQUESTS
                                  or elapsed >= TRACED_SECONDS):
                jax.profiler.stop_trace()
                tracing["on"] = False

        if trace:
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0   # the harness's annotations
            options.host_tracer_level = 2     # and the device, no Python
            jax.profiler.start_trace(tracing["dir"], profiler_options=options)
            tracing["on"] = True
        before = _counters()
        t0 = time.perf_counter()
        try:
            loop.run(mix, seconds, lambda i: window.append(
                one_request(next(cycle))), stop_trace if trace else None)
        finally:
            if tracing["on"]:
                stop_trace(len(window), math.inf)
        window_s = time.perf_counter() - t0
        moved = _delta(_counters(), before)
        stats = [d.memory_stats() or {} for d in jax.devices()]
    peak = max(int(s.get("peak_bytes_in_use", 0)) for s in stats)

    correct = _judge(warm, window, plans, oracles, native, line)
    if moved.get("dispatch.compile", 0):
        line(f"WARNING: {moved['dispatch.compile']} executables compiled "
             f"inside the window")

    # -- metrics ---------------------------------------------------------------
    good = [r for r in window if r.error is None]
    lat = [r.latency_s for r in good]
    end_to_end = {"setup_s": setup_s}
    if lat:
        end_to_end.update({
            "query_p50_s": statistics.median(lat),
            "query_p95_s": percentile_nearest_rank(lat, 95),
            "rows_per_s": sum(r.rows for r in good) / sum(lat)})
    failed = sum(r.error is not None for r in window)
    line(f"window: {len(window)} requests in {window_s:.3f}s, {failed} "
         f"failed; " + ", ".join(f"{k} {v!r}" for k, v in end_to_end.items()))
    line("latencies (submit + rest): " + " ".join(
        f"{r.submit_s:.3f}+{r.latency_s - r.submit_s:.3f}"
        for r in window[:64]) + (" ..." if len(window) > 64 else ""))
    out_device = dict(device, memory_peak_bytes=peak)
    result = {"correct": correct, "attempted": len(window), "failed": failed}

    def wanted(metric: dict) -> bool:
        return cell["name"] in metric.get("workloads", [cell["name"]])

    if not trace:
        result["metrics"] = {
            m["name"]: {"value": end_to_end[m["name"]], "unit": m["unit"]}
            for m in bench["end_to_end"]
            if wanted(m) and m["name"] in end_to_end}
    else:
        found = glob.glob(os.path.join(
            tracing["dir"], "plugins", "profile", "*", "*.xplane.pb"))
        if len(found) != 1:
            raise BenchFailure(f"expected one .xplane.pb, found {found}")
        if keep_trace:
            os.makedirs(keep_trace, exist_ok=True)
            shutil.copy(found[0], os.path.join(keep_trace, "trace.xplane.pb"))
        reduced = trace_reduce.reduce(found[0], platform, int(cell["chips"]))
        run = Run(cell, config, mix, plans, window, moved, reduced, peaks,
                  out_device)
        metrics = {}
        for m in bench["per_layer"]:
            if not wanted(m):
                continue
            value = resolve.module("layer_metrics", m["name"]).read(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        result["metrics"] = metrics
        out_device.update(busy_s=reduced["busy_s"],
                          window_s=reduced["window_s"])
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
        line(f"trace: {reduced['requests']} requests traced, device busy "
             f"{reduced['busy_s']:.6f}s of {reduced['window_s']:.6f}s")
    result["device"] = out_device
    return result


def _judge(warm: list, window: list, plans: dict, oracles: dict,
           native: bool, line) -> bool:
    """Once the window has closed: hold every request of the run to the
    reference's answer, to rung 0 and to unmoved fallback counters, print
    each number compared beside its limit, and say whether the run is
    correct. A request that fails gets its ``error``."""
    worst: dict = {}
    for req in warm + window:
        if req.error is not None:
            continue
        mod = plans[req.plan]
        numbers = mod.compare(req.answer, oracles[req.plan])
        for name, value in numbers.items():
            worst[name] = max(worst.get(name, value), value)
        over = [f"{n} {v!r} over its limit {mod.LIMITS[n]!r}"
                for n, v in numbers.items() if not v <= mod.LIMITS[n]]
        fell = _moved_fallbacks(req.moved, native)
        if over:
            req.error = "differs from the reference: " + "; ".join(over)
        elif req.where != ("fused", 0, 0):
            req.error = (f"finished at (tier, rung, steps) = {req.where}, "
                         f"required ('fused', 0, 0)")
        elif fell:
            req.error = "fallback counters moved: " + ", ".join(
                f"{k} +{req.moved[k]}" for k in fell)
    limits = {n: v for mod in plans.values() for n, v in mod.LIMITS.items()}
    for name in sorted(limits):
        line(f"check {name}: worst of {len(warm) + len(window)} requests "
             f"{worst.get(name)!r}, limit {limits[name]!r}")
    bad = [r for r in warm + window if r.error is not None]
    for r in bad[:5]:
        line(f"FAILED request ({r.plan}): {r.error}")
    return not bad and bool(window)
