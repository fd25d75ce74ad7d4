"""Layer metric ``dispatch.compile_persistent_hit_share``: of the process's
compiles that asked JAX's persistent compilation cache and were answered,
the share it held: ``dispatch.xla.persistent_hit`` over hits plus
``dispatch.xla.persistent_miss`` (a miss is counted where the executable
was then written: one that compiled under the cache's thresholds asks
nothing and counts on neither side). 100 on a warm machine, 0 on the run
that reads ``first_setup_s``; 100 too where no compile of the process
was long enough to be kept (the tests' sizes on a CPU): the cache then
had nothing to spare it, and ``dispatch.compile_backend_s`` is all
compile."""

LAYER = "dispatch"
UNIT = "%"
SOURCE = "program_counter"
MOVES = "setup_s"
BETTER = "higher"


def hit_share(found):
    """Percent of hits among hits and misses, 100 where no compile asked;
    ``None`` for a program that observes no compile."""
    if found is None:
        return None
    hits = found.get("persistent_hit", 0)
    asked = hits + found.get("persistent_miss", 0)
    return 100.0 * hits / asked if asked else 100.0


def read(run):
    from benchmark import compile_reduce

    return hit_share(compile_reduce.xla_counters())
