import logging

import pytest

from spark_rapids_jni_tpu.utils import config
from spark_rapids_jni_tpu.utils.log import get_logger


@pytest.fixture(autouse=True)
def _reset():
    yield
    for name in list(config._overrides):
        config.reset_option(name)


def test_defaults():
    assert config.get_option("telemetry.enabled") is False
    assert config.get_option("row_conversion.enforce_row_limit") is True


def test_env_override(monkeypatch):
    monkeypatch.setenv("SPARK_RAPIDS_TPU_TELEMETRY_ENABLED", "true")
    assert config.get_option("telemetry.enabled") is True
    monkeypatch.setenv("SPARK_RAPIDS_TPU_TELEMETRY_ENABLED", "off")
    assert config.get_option("telemetry.enabled") is False


def test_set_option_coerces_like_env():
    config.set_option("telemetry.enabled", "off")
    assert config.get_option("telemetry.enabled") is False
    config.set_option("telemetry.enabled", "1")
    assert config.get_option("telemetry.enabled") is True


@pytest.mark.parametrize("name", ["no.such.option", "kernels.tier"])
def test_unknown_option_rejected(name):
    with pytest.raises(KeyError):
        config.get_option(name)
    with pytest.raises(KeyError):
        config.set_option(name, 1)


def test_row_limit_option_wired():
    from spark_rapids_jni_tpu import types as t
    from spark_rapids_jni_tpu.columnar import Table
    from spark_rapids_jni_tpu.ops import convert_to_rows

    table = Table.from_pylists([([0], t.INT64)] * 200)  # 1600B row
    with pytest.raises(ValueError):
        convert_to_rows(table)
    config.set_option("row_conversion.enforce_row_limit", False)
    assert convert_to_rows(table)[0].row_size >= 1600


def test_logger_level_from_option():
    config.set_option("log.level", "DEBUG")
    # fresh configuration path
    import spark_rapids_jni_tpu.utils.log as log_mod

    log_mod._configured = False
    logger = get_logger("spark_rapids_jni_tpu.test")
    assert logging.getLogger("spark_rapids_jni_tpu").level == logging.DEBUG


def test_zero_column_table_clear_error():
    from spark_rapids_jni_tpu.columnar import Table
    from spark_rapids_jni_tpu.ops import convert_to_rows

    with pytest.raises(ValueError, match="at least one column"):
        convert_to_rows(Table([]))


# ---- memory layer (RMM-equivalent) -----------------------------------------


def test_memory_limiter_caps_and_tracks():
    from spark_rapids_jni_tpu.runtime.memory import (
        MemoryLimiter,
        MemoryLimitExceeded,
    )

    lim = MemoryLimiter(1000)
    lim.reserve(600)
    lim.reserve(300)
    assert lim.used == 900 and lim.peak == 900
    try:
        lim.reserve(200)
        assert False, "expected MemoryLimitExceeded"
    except MemoryLimitExceeded:
        pass
    lim.release(500)
    lim.reserve(400)
    assert lim.used == 800 and lim.peak == 900


def test_host_staging_pool_recycles():
    from spark_rapids_jni_tpu.runtime.memory import HostStagingPool

    pool = HostStagingPool()
    a = pool.take(1000)
    assert a.nbytes == 1024  # rounded to size class
    pool.give(a)
    b = pool.take(900)
    assert b is a  # recycled
    assert pool.hits == 1 and pool.misses == 1


def test_device_memory_stats_shape():
    from spark_rapids_jni_tpu.runtime.memory import device_memory_stats

    s = device_memory_stats()
    assert s.bytes_in_use >= 0
    assert s.peak_bytes_in_use >= s.bytes_in_use or s.peak_bytes_in_use == 0
    assert s.bytes_free >= 0


class TestSpillStore:
    def _table(self, n, seed=0):
        import numpy as np

        from spark_rapids_jni_tpu.columnar import Column, Table

        rng = np.random.default_rng(seed)
        return Table([Column.from_numpy(
            rng.integers(0, 1000, n).astype(np.int64))])

    def test_spills_lru_and_restores_exact(self):
        import numpy as np

        from spark_rapids_jni_tpu.runtime.memory import SpillStore

        store = SpillStore(budget_bytes=3000)  # fits two 128-row int64 tables
        t1, t2, t3 = (self._table(128, s) for s in (1, 2, 3))
        want1 = np.asarray(t1.column(0).data).copy()
        h1 = store.put(t1)
        h2 = store.put(t2)
        h3 = store.put(t3)  # t1 is LRU -> spills
        assert store.spill_count == 1
        s = store.stats()
        assert s["host_bytes"] > 0 and s["device_bytes"] <= 3000
        got1 = store.get(h1)  # unspill; t2 becomes the spill victim
        np.testing.assert_array_equal(np.asarray(got1.column(0).data), want1)
        assert store.unspill_count == 1
        assert store.spill_count == 2
        # all three still retrievable and exact
        for h, t in ((h2, t2), (h3, t3)):
            got = store.get(h)
            np.testing.assert_array_equal(
                np.asarray(got.column(0).data), np.asarray(t.column(0).data))

    def test_oversized_table_raises(self):
        import pytest as _pytest

        from spark_rapids_jni_tpu.runtime.memory import (
            MemoryLimitExceeded,
            SpillStore,
        )

        store = SpillStore(budget_bytes=100)
        with _pytest.raises(MemoryLimitExceeded):
            store.put(self._table(1024))

    def test_drop_frees_budget(self):
        from spark_rapids_jni_tpu.runtime.memory import SpillStore

        store = SpillStore(budget_bytes=2100)
        h1 = store.put(self._table(128))
        store.drop(h1)
        store.put(self._table(128))  # fits again without spilling
        assert store.spill_count == 0

    def test_string_table_spills(self):
        import numpy as np

        from spark_rapids_jni_tpu import types as t
        from spark_rapids_jni_tpu.columnar import Column, Table
        from spark_rapids_jni_tpu.runtime.memory import (
            SpillStore,
            _table_nbytes,
        )

        tbl = Table([Column.from_pylist(["alpha", None, "omega"], t.STRING)])
        # budget fits exactly the string table: the next put must evict it
        store = SpillStore(budget_bytes=_table_nbytes(tbl))
        h = store.put(tbl)
        store.put(Table([Column.from_numpy(np.zeros(1, dtype=np.int8))]))
        assert store.spill_count == 1
        got = store.get(h)
        assert got.column(0).to_pylist() == ["alpha", None, "omega"]

    def test_multi_eviction_and_nested_columns(self):
        import numpy as np
        import jax.numpy as jnp

        from spark_rapids_jni_tpu.types import DType, TypeId
        from spark_rapids_jni_tpu.columnar import Column, Table
        from spark_rapids_jni_tpu.runtime.memory import (
            SpillStore,
            _table_nbytes,
        )

        small = [self._table(64, s) for s in (1, 2, 3)]
        big = self._table(160, 4)
        store = SpillStore(budget_bytes=_table_nbytes(small[0]) * 3)
        hs = [store.put(t) for t in small]
        store.put(big)  # 1280B into 1536B budget: evicts all three smalls
        assert store.spill_count == 3

        # LIST column round-trips a spill with its child intact
        child = Column.from_numpy(np.arange(5, dtype=np.int64))
        lst = Column(DType(TypeId.LIST), jnp.asarray([0, 2, 5], jnp.int32),
                     children=[child])
        ltbl = Table([lst])
        store2 = SpillStore(budget_bytes=_table_nbytes(ltbl))
        h = store2.put(ltbl)
        store2.put(self._table(4, 9))  # evicts the list table
        got = store2.get(h)
        assert got.column(0).to_pylist() == [[0, 1], [2, 3, 4]]


def test_spill_store_zstd_compression_roundtrip():
    """SpillStore's compress_spill (the nvcomp general-codec role on the
    host path): spilled tables round-trip bit-exactly and the stored
    footprint shrinks on compressible data."""
    import jax.numpy as jnp
    import numpy as np

    from spark_rapids_jni_tpu import types as t
    from spark_rapids_jni_tpu.columnar import Column, Table
    from spark_rapids_jni_tpu.runtime.memory import SpillStore

    n = 4096
    tbl1 = Table([
        Column(t.INT64, jnp.arange(n, dtype=jnp.int64) % 16, None),
        Column(t.FLOAT64, jnp.zeros(n, dtype=jnp.float64),
               jnp.asarray(np.arange(n) % 3 != 0)),
    ])
    tbl2 = Table([Column(t.INT32, jnp.arange(n, dtype=jnp.int32), None)])
    from spark_rapids_jni_tpu.runtime.memory import _table_nbytes

    store = SpillStore(budget_bytes=_table_nbytes(tbl1) + 64,
                       compress_spill=True)
    h1 = store.put(tbl1)
    h2 = store.put(tbl2)  # forces tbl1 to spill (compressed)
    st = store.stats()
    assert st["spills"] == 1
    assert 0 < st["host_stored_bytes"] < st["host_bytes"]
    back = store.get(h1)  # unspill; decompress
    assert np.array_equal(np.asarray(back.column(0).data),
                          np.arange(n) % 16)
    assert np.array_equal(np.asarray(back.column(1).valid_mask()),
                          np.arange(n) % 3 != 0)
    assert store.stats()["unspills"] == 1
    store.drop(h2)
