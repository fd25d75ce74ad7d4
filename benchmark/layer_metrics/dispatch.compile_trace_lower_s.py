"""Layer metric ``dispatch.compile_trace_lower_s``: what the process spent
tracing Python into jaxprs and lowering them to MLIR inside its
``dispatch.compile`` spans, warm-up included: the counter
``dispatch.xla.trace_lower_ns`` (``jax.monitoring``'s
``jaxpr_trace_duration`` and ``jaxpr_to_mlir_module_duration``, a nested
trace counted once, in its parent). The part of ``setup_s`` that no
persistent cache spares a restarted executor."""

LAYER = "dispatch"
UNIT = "s"
SOURCE = "program_counter"
MOVES = "setup_s"
BETTER = "lower"


def read(run):
    from benchmark import compile_reduce

    return compile_reduce.seconds("trace_lower")
