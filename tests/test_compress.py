"""Columnar codec (runtime/compress, ISSUE 12).

Five invariant families:

1. **Round-trip** — every dtype/shape the column layout produces
   (low-cardinality ints, sorted runs, random floats, bool validity,
   2-D char matrices, DECIMAL128 limb pairs, empty/tiny buffers)
   decodes bit-identical, and the chooser picks the expected scheme.

2. **Classification** — a mutated codec frame is a classified
   ``CorruptDataError`` from the codec's own header and per-scheme
   length checks (the corrupt-AFTER-verify case the integrity trailer
   cannot catch), with the ``compress.mismatch`` counters incremented.

3. **Disabled parity** — ``compress.enabled=false`` (and each per-seam
   toggle) restores byte-for-byte legacy framing: plain ndarray spill
   snapshots, flag-0/1 wire buffers identical to the pre-codec writer.

4. **Seam round-trips** — SpillStore host+disk tiers, DCN wire frames
   and the checkpoint path all shrink under the codec and read back
   bit-identical under the integrity seal.

5. **Result-cache accounting** — the LRU charges resident (stored)
   bytes; ``stats()`` reports logical and stored; demote shrinks the
   stored sum, restage grows it back; zero leaked reservations.
"""

import io
import pickle
import socket
import threading

import numpy as np
import pytest

from spark_rapids_jni_tpu import telemetry
from spark_rapids_jni_tpu.columnar import Column, Table
from spark_rapids_jni_tpu.runtime import compress, fusion, integrity
from spark_rapids_jni_tpu.runtime import resultcache
from spark_rapids_jni_tpu.runtime.memory import (
    MemoryLimiter,
    SpillStore,
    _col_to_host,
    _table_nbytes,
)
from spark_rapids_jni_tpu.runtime.resilience import CorruptDataError
from spark_rapids_jni_tpu.telemetry import REGISTRY
from spark_rapids_jni_tpu.utils import config


@pytest.fixture(autouse=True)
def _reset():
    telemetry.drain()
    REGISTRY.reset()
    yield
    telemetry.drain()
    REGISTRY.reset()
    for name in list(config._overrides):
        config.reset_option(name)


# ---------------------------------------------------------------------------
# family 1: round-trip + scheme choice
# ---------------------------------------------------------------------------


def _scheme_of(frame: bytes) -> int:
    assert frame[:4] == compress.FRAME_MAGIC
    return frame[5]


_CASES = [
    # (name, array factory, expected scheme or None for "don't care")
    ("lowcard_int8", lambda rng: rng.integers(0, 3, 20_000).astype(np.int8),
     compress.SCHEME_DICT),
    ("twocard_int8", lambda rng: rng.integers(0, 2, 20_000).astype(np.int8),
     compress.SCHEME_DICT),
    ("lowcard_int32", lambda rng: rng.integers(0, 9, 20_000).astype(np.int32),
     compress.SCHEME_DICT),
    ("sorted_int32", lambda rng: np.sort(
        rng.integers(0, 60, 20_000)).astype(np.int32), compress.SCHEME_RLE),
    ("const_int64", lambda rng: np.full(20_000, 7, dtype=np.int64),
     compress.SCHEME_RLE),
    ("random_f64", lambda rng: rng.random(20_000), compress.SCHEME_RAW),
    ("bool_validity", lambda rng: rng.random(20_000) > 0.1,
     compress.SCHEME_BITPACK),
    ("chars_2d", lambda rng: rng.integers(65, 70, (4096, 8)).astype(
        np.uint8), None),
    ("decimal_limbs", lambda rng: np.stack(
        [rng.integers(0, 5, 8192), np.zeros(8192, dtype=np.int64)],
        axis=1).astype(np.int64), None),
    ("string_offsets", lambda rng: np.arange(0, 8192 * 4, 4).astype(
        np.int32), None),
    ("tiny", lambda rng: np.arange(3, dtype=np.int64), compress.SCHEME_RAW),
    ("empty", lambda rng: np.empty(0, dtype=np.float32),
     compress.SCHEME_RAW),
]


@pytest.mark.parametrize("name,mk,scheme",
                         _CASES, ids=[c[0] for c in _CASES])
def test_roundtrip_bit_identical(name, mk, scheme):
    arr = mk(np.random.default_rng(11))
    frame = compress.encode_array(arr)
    got = compress.decode_array(frame)
    assert got.dtype == arr.dtype and got.shape == arr.shape
    assert np.array_equal(got, arr)
    if scheme is not None:
        assert _scheme_of(frame) == scheme, name


def test_compressible_columns_shrink_at_least_2x():
    rng = np.random.default_rng(3)
    for mk in (lambda: rng.integers(0, 3, 50_000).astype(np.int8),
               lambda: np.sort(rng.integers(0, 40, 50_000)).astype(np.int32),
               lambda: rng.random(50_000) > 0.05):
        arr = mk()
        frame = compress.encode_array(arr)
        assert arr.nbytes / len(frame) >= 2.0, arr.dtype


def test_pack_unpack_tuple_shape_matches_legacy():
    # the 4-tuple pack rides snaps_checksum/_stage_buffer unchanged: same
    # (tag, dtype_str, shape, blob) shape as the legacy ("zstd", ...) pack
    arr = np.arange(512, dtype=np.int32).reshape(2, 256)
    pack = compress.pack_array(arr, seam="integrity.spill")
    assert compress.is_codec_pack(pack)
    tag, dts, shape, blob = pack
    assert tag == compress.PACK_TAG and dts == arr.dtype.str
    assert shape == arr.shape and isinstance(blob, bytes)
    got = compress.unpack_array(pack, seam="integrity.spill")
    assert np.array_equal(got, arr)


def test_zstd_guard_is_optional_and_cached():
    # this environment ships no zstandard: the guard must say so without
    # raising, and the encoder must fall back to the stage-1 schemes
    if compress.zstd_available():
        pytest.skip("zstandard present in this environment")
    with pytest.raises(ModuleNotFoundError):
        compress.zstd_codec(3)
    config.set_option("compress.zstd_level", 19)
    arr = np.sort(np.random.default_rng(0).integers(0, 9, 10_000))
    frame = compress.encode_array(arr)
    assert np.array_equal(compress.decode_array(frame), arr)


# ---------------------------------------------------------------------------
# family 2: classification — corrupt AFTER the trailer verified
# ---------------------------------------------------------------------------


# frame-HEADER mutation positions: magic/version/scheme (0-5) and the
# dtype/ndim/shape region (7-15). Byte 6 (the zstd flag) is excluded —
# with zstandard absent a set flag is a deployment error
# (ModuleNotFoundError), deliberately NOT classified as data corruption.
# Payload VALUE bytes are also out of scope: the codec carries no inner
# checksum (the integrity seal covers the frame), so a flipped run value
# decodes to wrong-but-well-formed data — exactly why the ordering
# contract keeps the seal outermost.
_HEADER_POSITIONS = tuple(range(0, 6)) + tuple(range(7, 16))


def _mutate(frame: bytes, seed: int) -> bytes:
    rng = np.random.default_rng(seed)
    kind = seed % 3
    if kind == 0:  # flip one header bit
        pos = _HEADER_POSITIONS[int(
            rng.integers(0, len(_HEADER_POSITIONS)))]
        return frame[:pos] + bytes([frame[pos] ^ (1 << int(
            rng.integers(0, 8)))]) + frame[pos + 1:]
    if kind == 1:  # truncate
        cut = int(rng.integers(1, len(frame)))
        return frame[:cut]
    pos = _HEADER_POSITIONS[int(  # header byte clobber
        rng.integers(0, len(_HEADER_POSITIONS)))]
    return frame[:pos] + bytes([frame[pos] ^ 0xFF]) + frame[pos + 1:]


@pytest.mark.parametrize("seed", range(30))
def test_mutated_frame_classifies_or_is_bit_identical(seed):
    rng = np.random.default_rng(seed)
    arr = np.sort(rng.integers(0, 20, 4096)).astype(np.int32)
    frame = compress.encode_array(arr)
    mutated = _mutate(frame, seed)
    # the corrupt-after-verify shape: the seal covers the MUTATED bytes,
    # so the trailer verifies clean and only the codec can catch it
    sealed = integrity.seal(mutated)
    payload = integrity.verify(sealed, seam="integrity.spill")
    assert payload == mutated
    try:
        got = compress.decode_array(payload)
    except CorruptDataError:
        assert REGISTRY.counter("compress.mismatch").value >= 1
        assert REGISTRY.counter("integrity.mismatch").value >= 1
    else:
        assert np.array_equal(got, arr), \
            f"seed {seed}: undetected mutation decoded as garbage"


def test_wire_frame_header_disagreement_classifies():
    # flag-2 wire buffers re-check decoded dtype/shape against the dcn
    # buffer header: a frame swapped for a VALID frame of another array
    # still classifies (the post-decode check)
    from spark_rapids_jni_tpu.parallel import dcn

    import struct

    arr = np.arange(1024, dtype=np.int64)
    other = np.arange(100, dtype=np.int16)
    swapped = compress.encode_array(other, seam="integrity.wire")
    # hand-build a flag-2 buffer whose header describes `arr` but whose
    # payload decodes to `other` — a VALID frame of the wrong array
    dts = arr.dtype.str.encode()
    buf = b"".join([
        struct.pack("<B", len(dts)), dts,
        struct.pack("<B", arr.ndim),
        struct.pack(f"<{arr.ndim}Q", *arr.shape),
        struct.pack("<BQ", 2, len(swapped)),
        swapped,
    ])
    with pytest.raises(CorruptDataError):
        dcn._read_buffer(dcn._Reader(buf), None)
    assert REGISTRY.counter("compress.mismatch").value >= 1


# ---------------------------------------------------------------------------
# family 3: disabled parity — byte-for-byte legacy framing at every seam
# ---------------------------------------------------------------------------


def _mixed_table(n=2048, seed=0):
    rng = np.random.default_rng(seed)
    return Table([
        Column.from_numpy(rng.integers(0, 3, n).astype(np.int8)),
        Column.from_numpy(rng.random(n),
                          validity=rng.random(n) > 0.2),
    ])


def test_disabled_spill_snapshots_are_legacy_plain_arrays():
    config.set_option("compress.enabled", False)
    store = SpillStore(budget_bytes=1 << 20)
    tbl = _mixed_table()
    h = store.put(tbl)
    store.spill(h)
    try:
        e = store._entries[h]
        for snap in e["host_cols"]:
            for buf in (snap[1], snap[2]):
                assert buf is None or isinstance(buf, np.ndarray), type(buf)
        st = store.stats()
        assert st["host_stored_bytes"] == st["host_bytes"]
        assert _bit_identical(store.get(h), tbl)
    finally:
        store.close()


def test_disabled_wire_bytes_match_legacy_writer_exactly():
    import struct

    from spark_rapids_jni_tpu.parallel import dcn

    tbl = _mixed_table()
    config.set_option("compress.enabled", False)
    got = dcn.serialize_table(tbl, compress_level=0)
    # hand-rolled legacy framing: the pre-codec writer with codec=False
    out = [dcn._MAGIC, struct.pack(
        "<IIQ", dcn._VERSION, tbl.num_columns, tbl.num_rows)]
    for c in tbl.columns:
        dcn._write_column(out, c, None)
    assert got == b"".join(out)


def test_per_seam_toggle_isolates_wire_from_spill():
    from spark_rapids_jni_tpu.parallel import dcn

    tbl = _mixed_table()
    config.set_option("compress.wire", False)
    legacy_wire = dcn.serialize_table(tbl, compress_level=0)
    config.reset_option("compress.wire")
    codec_wire = dcn.serialize_table(tbl, compress_level=0)
    assert len(codec_wire) < len(legacy_wire)
    # spill stays codec-packed while the wire seam alone is off
    config.set_option("compress.wire", False)
    store = SpillStore(budget_bytes=1 << 20)
    h = store.put(tbl)
    store.spill(h)
    try:
        st = store.stats()
        assert st["host_stored_bytes"] < st["host_bytes"]
    finally:
        store.close()
    assert not compress.seam_enabled("integrity.wire")
    assert compress.seam_enabled("integrity.spill")


def test_master_toggle_disables_every_seam_and_unknown_seam_is_off():
    for seam in compress.SEAM_OPTIONS:
        assert compress.seam_enabled(seam)
    config.set_option("compress.enabled", False)
    for seam in compress.SEAM_OPTIONS:
        assert not compress.seam_enabled(seam)
    config.reset_option("compress.enabled")
    assert not compress.seam_enabled("integrity.ingest")  # no codec seam


# ---------------------------------------------------------------------------
# family 4: seam round-trips under the seal
# ---------------------------------------------------------------------------


def _bit_identical(a, b):
    if a.num_rows != b.num_rows or a.num_columns != b.num_columns:
        return False
    for ca, cb in zip(a.columns, b.columns):
        if ca.dtype != cb.dtype:
            return False
        if not np.array_equal(np.asarray(ca.data), np.asarray(cb.data)):
            return False
        if not np.array_equal(np.asarray(ca.valid_mask()),
                              np.asarray(cb.valid_mask())):
            return False
    return True


def _dict_friendly_table(n=8192, seed=0):
    rng = np.random.default_rng(seed)
    return Table([
        Column.from_numpy(rng.integers(0, 3, n).astype(np.int8)),
        Column.from_numpy(rng.integers(0, 2, n).astype(np.int8)),
        Column.from_numpy(np.sort(rng.integers(0, 50, n)).astype(np.int32),
                          validity=rng.random(n) > 0.1),
    ])


def test_spill_host_tier_shrinks_and_roundtrips():
    tbl = _dict_friendly_table()
    store = SpillStore(budget_bytes=1 << 20)
    h = store.put(tbl)
    store.spill(h)
    try:
        st = store.stats()
        assert st["host_bytes"] / st["host_stored_bytes"] > 2.0, st
        assert _bit_identical(store.get(h), tbl)
    finally:
        store.close()


def test_spill_disk_tier_shrinks_and_roundtrips(tmp_path):
    tbl = _dict_friendly_table(seed=5)
    store = SpillStore(budget_bytes=_table_nbytes(tbl),
                       spill_dir=str(tmp_path))
    h = store.put(tbl)
    store.put(_dict_friendly_table(seed=6))  # evicts h to disk
    try:
        st = store.stats()
        assert st["disk_bytes"] / st["disk_stored_bytes"] > 2.0, st
        assert _bit_identical(store.get(h), tbl)
    finally:
        store.close()


def test_wire_roundtrip_shrinks_and_survives_corruption_arq():
    from spark_rapids_jni_tpu.parallel.dcn import SliceLink, serialize_table

    tbl = _dict_friendly_table(seed=9)
    frame = serialize_table(tbl, compress_level=0)
    logical = sum(int(np.asarray(c.data).nbytes) for c in tbl.columns)
    assert logical / len(frame) > 2.0
    from spark_rapids_jni_tpu.runtime import faults
    script = faults.FaultScript(corruptions=[
        faults.CorruptionSpec("integrity.wire", mode="flip", seed=1)])
    sa, sb = socket.socketpair()
    tx, rx = SliceLink(sa), SliceLink(sb)
    out = {}
    t = threading.Thread(target=lambda: out.setdefault(
        "tbl", rx.recv_table()))
    try:
        with faults.inject(script):
            t.start()
            tx.send_table(tbl, compress_level=0)
            t.join(30)
        assert script.fired
        assert _bit_identical(out["tbl"], tbl)
        assert REGISTRY.counter("integrity.refetch").value == 1
    finally:
        tx.close()
        rx.close()


def test_checkpoint_path_rides_the_codec():
    from spark_rapids_jni_tpu.runtime.outofcore import run_chunked_aggregate

    rng = np.random.default_rng(2)
    chunks = [Table([Column.from_numpy(
        rng.integers(0, 5, 4096).astype(np.int64))]) for _ in range(3)]
    want = sum(int(np.asarray(c.columns[0].data).sum()) for c in chunks)

    def partial(chunk):
        s = int(np.asarray(chunk.columns[0].data).sum())
        return Table([Column.from_numpy(np.asarray([s], dtype=np.int64))])

    def merge(partials):
        s = int(np.asarray(partials.columns[0].data).sum())
        return Table([Column.from_numpy(np.asarray([s], dtype=np.int64))])

    limiter = MemoryLimiter(1 << 24)
    # budget fits exactly one checkpointed partial: each later put
    # demotes the previous one, so the checkpoint seam actually packs
    store = SpillStore(budget_bytes=_table_nbytes(partial(chunks[0])))
    try:
        res = run_chunked_aggregate(chunks, partial, merge,
                                    limiter=limiter, spill=store)
        assert int(np.asarray(res.table.columns[0].data)[0]) == want
        assert limiter.used == 0
        assert REGISTRY.counter("compress.bytes_in").value > 0
    finally:
        store.close()


# ---------------------------------------------------------------------------
# family 5: result-cache resident-bytes accounting
# ---------------------------------------------------------------------------


def _bare_cache(max_bytes, budget=1 << 26):
    limiter = MemoryLimiter(budget)
    store = SpillStore(budget_bytes=budget)
    cache = resultcache.ResultCache(store, limiter, max_bytes=max_bytes)
    limiter.attach_spill_store(store)
    limiter.attach_result_cache(cache)
    return limiter, store, cache


def _cached_result(seed):
    return fusion.FusedResult(_dict_friendly_table(n=4096, seed=seed), {})


def _ckey(i):
    return resultcache.CacheKey(f"sig-{i:04d}", f"fp-{i:04d}")


def test_cache_stats_report_logical_and_stored():
    per = _table_nbytes(_cached_result(0).table)
    limiter, store, cache = _bare_cache(max_bytes=per * 16)
    for i in range(4):
        assert cache.put(_ckey(i), _cached_result(i))
    st = cache.stats()
    assert st["stored_bytes"] == st["bytes"] == per * 4  # all device-resident
    cache.shed(1 << 40)
    st = cache.stats()
    assert st["bytes"] == per * 4  # logical unchanged
    assert 0 < st["stored_bytes"] < st["bytes"] // 2  # resident = compressed
    assert st["resident_bytes"] == 0
    # restage one: its stored footprint grows back to logical
    before = cache.stats()["stored_bytes"]
    assert cache.get(_ckey(0)) is not None
    assert cache.stats()["stored_bytes"] > before
    cache.clear()
    st = cache.stats()
    assert st["bytes"] == st["stored_bytes"] == st["resident_bytes"] == 0
    assert limiter.used == 0


def test_cache_lru_bound_charges_stored_bytes():
    per = _table_nbytes(_cached_result(0).table)
    limiter, store, cache = _bare_cache(max_bytes=int(per * 2.5))
    # demote each entry right after put: compressed entries must pack far
    # more than the 2 logical entries the bound used to hold
    for i in range(10):
        assert cache.put(_ckey(i), _cached_result(i))
        cache.shed(1 << 40)
    st = cache.stats()
    assert st["entries"] == 10, st
    assert st["stored_bytes"] <= st["max_bytes"]
    assert st["bytes"] > st["max_bytes"]  # logical exceeds the bound
    cache.clear()
    assert limiter.used == 0


def test_cache_disabled_compression_restores_logical_lru():
    config.set_option("compress.enabled", False)
    per = _table_nbytes(_cached_result(0).table)
    limiter, store, cache = _bare_cache(max_bytes=int(per * 2.5))
    for i in range(6):
        assert cache.put(_ckey(i), _cached_result(i))
        cache.shed(1 << 40)
    st = cache.stats()
    assert st["entries"] == 2, st  # stored == logical: the old bound
    cache.clear()
    assert limiter.used == 0
