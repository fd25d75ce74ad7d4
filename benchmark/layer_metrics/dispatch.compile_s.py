"""Layer metric ``dispatch.compile_s``: what the run spent compiling executables or
loading them from the persistent cache: its ``dispatch.compile`` spans
summed, warm-up included (the window holds none). The part of ``setup_s``
the program's lowering decides: cold it is XLA's compile time."""

LAYER = "dispatch"
UNIT = "s"
SOURCE = "program_span"
MOVES = "setup_s"
BETTER = "lower"


def read(run):
    from spark_rapids_jni_tpu import telemetry

    spans = [r for r in telemetry.events() if r.get("kind") == "span"
             and r.get("op") == "dispatch.compile"]
    if not spans:
        return None
    return sum(float(r["t1"]) - float(r["t0"]) for r in spans)
