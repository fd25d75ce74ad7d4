"""Seeded violations for jit-via-dispatch: batch-shaped ops compiled with
a direct ``@jax.jit`` (one trace + compile per distinct row count) instead
of routing through the shape-bucketed executable cache in
``runtime/dispatch.py`` — the per-shape compile storm ISSUE 3 exists to
absorb. The pragma'd twin shows the blessed escape hatch for deliberate
jits (wrappers with their own shape quantization)."""

import jax
import jax.numpy as jnp


@jax.jit                                  # VIOLATION: direct jit decorator
def direct_jit_sum(col):
    return jnp.sum(col)


def bare_jit_call(col):
    fn = jax.jit(lambda c: c * 2)         # VIOLATION: bare jax.jit(...)
    return fn(col)


# deliberate jit: block-quantized kernel wrapper (reviewed)
# tpulint: disable=jit-via-dispatch
@jax.jit
def pragmaed_kernel(col):
    return col + 1


def dispatched_sum(col):
    # clean: the op rides the bucketed executable cache
    from spark_rapids_jni_tpu.runtime import dispatch

    def _impl(row_args, aux_args, row_valids):
        ((c,),) = row_args
        return jnp.sum(c)

    return dispatch.rowwise("seeded_sum", _impl, (col,), slice_rows=False)
