"""What XLA's TPU compiler makes of the main path's boundary, with no chip:
the compiler is installed here and compiles for a chip that is described
and not attached. Nothing runs, so these say nothing about results or
times; they hold the custom calls a chip trace would show.

Every compile for the described chip belongs in THIS file: the worker that
is given it loads libtpu and keeps it, so a second such file could land on
another worker and skip in silence. The topology is described inside a
fixture, never while a module is imported."""

import re

import numpy as np
import pytest

from spark_rapids_jni_tpu.columnar import Column, Table
from spark_rapids_jni_tpu.runtime import dispatch

N, B = 1_000_003, 1_048_576   # off its bucket, a bucket a chip would tile


@pytest.fixture(scope="module")
def one_chip():
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # an executable for a described chip is written to the persistent cache
    # and cannot be read back without one: keep these out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _x64(compiled) -> dict:
    """{custom call target: how many} of the 64-bit conversions."""
    found = re.findall(r'custom_call_target="(X64\w+)"', compiled.as_text())
    return {name: found.count(name) for name in set(found)}


def _shapes(tree, rows, sharding):
    import jax

    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(
            (rows,) + a.shape[1:], a.dtype, sharding=sharding), tree)


def _group():
    price = Column.from_numpy(np.arange(21, dtype=np.int64),
                              validity=np.arange(21) % 5 != 3)
    return Table([price, Column.from_numpy(np.arange(21, dtype=np.int64)),
                  Column.from_numpy(np.arange(21, dtype=np.int32))])


def _consume(rows, aux, row_valids):
    import jax.numpy as jnp

    (table,), (row_valid,) = rows, row_valids
    return [jnp.sum(jnp.where(row_valid & c.validity & (c.data > 5),
                              c.data * 3, 0)) for c in table.columns]


@pytest.mark.parametrize("words", [True, False], ids=["words", "int64"])
def test_the_pad_and_its_consumer_convert_no_int64_buffer(words, one_chip):
    """As ``_pad_groups`` pads (``words=True``): the pad splits each int64
    column once and combines nothing, and an op compiled as ``call``
    compiles it (``_on_words``) splits nothing: the assembly is folded into
    its consumers. The int64 form (a group over a mesh, the pad as it was)
    pays a combine a column in the pad and the two splits again in the op,
    which is what this test would see come back."""
    import jax

    def pad(group):
        return dispatch._pad_tree(group, N, B, dispatch._PadStats(),
                                  words=words)

    group = _shapes(_group(), N, one_chip)
    padded = jax.jit(pad).lower(group).compile()
    handed = _shapes(jax.eval_shape(pad, group), B, one_chip)
    row_valid = jax.ShapeDtypeStruct((B,), np.bool_, sharding=one_chip)
    consumer = jax.jit(dispatch._on_words(_consume)).lower(
        (handed,), (), (row_valid,)).compile()
    in_pad, in_consumer = _x64(padded), _x64(consumer)
    assert (in_pad["X64SplitLow"], in_pad["X64SplitHigh"]) == (2, 2)
    big = [line for line in consumer.as_text().splitlines()
           if "X64Split" in line and f"[{B}]" in line]
    if words:
        assert "X64Combine" not in in_pad
        assert not big and "X64SplitLow" not in in_consumer
    else:
        assert in_pad["X64Combine"] == 2
        assert len(big) == 4   # low and high of two columns, again
