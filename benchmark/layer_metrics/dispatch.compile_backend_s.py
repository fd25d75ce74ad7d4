"""Layer metric ``dispatch.compile_backend_s``: what the process spent in
the backend's compile inside its ``dispatch.compile`` spans, warm-up
included: the counter ``dispatch.xla.backend_ns``
(``backend_compile_duration``). Cold it is XLA's compile time; warm it is
the persistent cache's load, ``dispatch.xla.cache_load_ns``, which one
printed line puts beside it."""

LAYER = "dispatch"
UNIT = "s"
SOURCE = "program_counter"
MOVES = "setup_s"
BETTER = "lower"


def read(run):
    from benchmark import compile_reduce

    found = compile_reduce.xla_counters()
    if found is None or "backend_ns" not in found:
        return None
    backend, load, staged = (found.get(f"{k}_ns", 0) / 1e9 for k in (
        "backend", "cache_load", "trace_lower"))
    run.say(f"compile: backend {backend:.3f}s of which {load:.3f}s loading "
            f"from the persistent cache ({found.get('persistent_hit', 0)} "
            f"hits, {found.get('persistent_miss', 0)} misses), tracing and "
            f"lowering {staged:.3f}s")
    return backend
