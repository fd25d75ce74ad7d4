"""End-to-end data integrity layer (runtime/integrity.py, ISSUE 10).

Five invariant families:

1. **Trailer primitives** — ``seal``/``verify`` roundtrip; every
   corruption shape (bit flip, truncation, trailer clobber, magic
   clobber, length-field lie) raises a classified ``CorruptDataError``
   before a payload byte reaches a decoder; the masked checksum never
   equals the raw crc32 it wraps.

2. **At-rest seams** — SpillStore detects drifted host snapshots
   (in-memory crc) and corrupt disk payloads (sealed files) at unspill,
   with the entry left spilled; ``write_payload_file`` is crash-safe
   (tmp + ``os.replace``: an interrupted write leaves the old file
   intact and no tmp litter).

3. **On-wire seam** — a corrupted DCN frame is NAK'd and refetched to a
   bit-identical delivery; refetch exhaustion dies classified on BOTH
   sides; with integrity disabled the wire framing is byte-for-byte the
   legacy ``<Q length> + blob`` with no trailer and no acknowledgement.

4. **Checkpoint seam** — a corrupt out-of-core partial is discarded and
   its chunk replayed from source to a bit-identical result with zero
   leaked reservations; the serial path (no re-enterable source list)
   propagates the classified error instead.

5. **Untrusted ingestion** — malformed Parquet/ORC envelopes are
   rejected as ``MalformedFileError`` (``MalformedInputError`` for the
   serving stack, ``NativeError`` for legacy catches) by pure-Python
   preflight, no native lib needed; the server rejects that one query
   cleanly — never retried, zero leaked reservations, other sessions
   unperturbed.
"""

import os
import pickle
import socket
import struct
import threading
import zlib

import numpy as np
import pytest

from spark_rapids_jni_tpu import telemetry
from spark_rapids_jni_tpu.columnar import Column, Table
from spark_rapids_jni_tpu.parquet.footer import MalformedFileError, NativeError
from spark_rapids_jni_tpu.runtime import faults, integrity, resilience
from spark_rapids_jni_tpu.runtime.memory import (
    MemoryLimiter,
    SpillStore,
    _col_to_host,
    _table_nbytes,
)
from spark_rapids_jni_tpu.runtime.outofcore import run_chunked_aggregate
from spark_rapids_jni_tpu.runtime.resilience import (
    CorruptDataError,
    FatalExecutionError,
    MalformedInputError,
)
from spark_rapids_jni_tpu.telemetry import REGISTRY
from spark_rapids_jni_tpu.utils import config


@pytest.fixture(autouse=True)
def _reset():
    telemetry.drain()
    REGISTRY.reset()
    config.set_option("telemetry.enabled", True)
    yield
    telemetry.drain()
    REGISTRY.reset()
    for name in list(config._overrides):
        config.reset_option(name)


def _tables_bit_identical(a, b):
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


def _small_table(n=64, seed=3):
    rng = np.random.default_rng(seed)
    return Table([
        Column.from_numpy(rng.integers(0, 9, n).astype(np.int64)),
        Column.from_numpy(rng.integers(-100, 100, n).astype(np.int64),
                          validity=rng.random(n) > 0.2),
    ])


# ---------------------------------------------------------------------------
# 1. trailer primitives
# ---------------------------------------------------------------------------


def test_seal_verify_roundtrip():
    for payload in (b"", b"x", b"payload bytes under test", bytes(4096)):
        blob = integrity.seal(payload)
        assert len(blob) == len(payload) + integrity.TRAILER_SIZE
        assert integrity.verify(blob, seam="integrity.spill") == payload
    assert REGISTRY.counter("integrity.mismatch").value == 0
    assert REGISTRY.counter("integrity.bytes_verified").value > 0


@pytest.mark.parametrize("mutate, reason", [
    (lambda b: bytes([b[0] ^ 0x40]) + b[1:], "checksum mismatch"),
    (lambda b: b[:-5], "trailer"),  # truncation eats the trailer
    (lambda b: b[: len(b) // 2], "trailer"),
    (lambda b: b[:-16] + b"XXXX" + b[-12:], "magic clobbered"),
    (lambda b: b[:-12] + struct.pack("<Q", 10 ** 9) + b[-4:],
     "length disagrees"),
    (lambda b: b[:-4] + bytes([b[-4] ^ 1]) + b[-3:], "checksum mismatch"),
], ids=["payload-flip", "truncate-5", "truncate-half", "magic-clobber",
        "length-lie", "crc-flip"])
def test_verify_detects_every_corruption_shape(mutate, reason):
    blob = integrity.seal(b"the payload the trailer protects" * 8)
    with pytest.raises(CorruptDataError, match=reason):
        integrity.verify(mutate(blob), seam="integrity.wire",
                         op="test.verify")
    assert REGISTRY.counter("integrity.mismatch").value == 1
    assert REGISTRY.counter("integrity.mismatch.integrity.wire").value == 1
    evs = [e for e in telemetry.events() if e.get("kind") == "integrity"]
    assert evs and evs[-1]["event"] == "mismatch"
    assert evs[-1]["seam"] == "integrity.wire"


def test_blob_shorter_than_trailer_is_classified():
    with pytest.raises(CorruptDataError, match="shorter than"):
        integrity.verify(b"tiny", seam="integrity.spill")


def test_checksum_is_masked_crc32():
    for payload in (b"", b"abc", bytes(range(256))):
        raw = zlib.crc32(payload) & 0xFFFFFFFF
        masked = integrity.checksum(payload)
        assert masked != raw  # a blob embedding its own crc32 never verifies
        assert 0 <= masked <= 0xFFFFFFFF
    # deterministic: same bytes, same checksum
    assert integrity.checksum(b"abc") == integrity.checksum(b"abc")


def test_corrupt_data_error_transience_is_seam_specific():
    exc = CorruptDataError("bad frame", seam="integrity.wire")
    # refetchable only at transport seams (a pristine copy exists there)
    assert resilience.is_transient(exc, seam="dcn.transport")
    assert resilience.is_transient(exc, seam="shuffle.transport")
    assert not resilience.is_transient(exc, seam="spill.unspill")
    assert not resilience.is_transient(exc)
    # malformed input is never retried anywhere
    malformed = MalformedInputError("bad file")
    assert not resilience.is_transient(malformed, seam="dcn.transport")


def test_snaps_checksum_detects_drift():
    tbl = _small_table(128, seed=5)
    snaps = [_col_to_host(c) for c in tbl.columns]
    crc = integrity.snaps_checksum(snaps)
    integrity.verify_snaps(snaps, crc, seam="integrity.spill")  # no raise
    # drift one byte of one buffer: the fold must notice
    data = np.asarray(snaps[0][1]).copy()
    data.view(np.uint8)[3] ^= 0x10
    snaps[0] = (snaps[0][0], data, snaps[0][2], snaps[0][3], snaps[0][4])
    assert integrity.snaps_checksum(snaps) != crc
    with pytest.raises(CorruptDataError, match="snapshot checksum"):
        integrity.verify_snaps(snaps, crc, seam="integrity.spill")


def test_record_integrity_validates_seam_and_reserved_fields():
    with pytest.raises(ValueError, match="seam must be non-empty"):
        telemetry.record_integrity("op", "mismatch", seam="")
    with pytest.raises(ValueError, match="reserved"):
        telemetry.record_integrity("op", "mismatch",
                                   seam="integrity.spill", kind="x")


def test_enabled_env_var_overrides_option(monkeypatch):
    config.set_option("integrity.enabled", True)
    monkeypatch.setenv("SPARK_RAPIDS_TPU_INTEGRITY", "0")
    assert not integrity.enabled()
    config.set_option("integrity.enabled", False)
    monkeypatch.setenv("SPARK_RAPIDS_TPU_INTEGRITY", "on")
    assert integrity.enabled()
    monkeypatch.delenv("SPARK_RAPIDS_TPU_INTEGRITY")
    assert not integrity.enabled()
    config.reset_option("integrity.enabled")
    assert integrity.enabled()  # default is on


# ---------------------------------------------------------------------------
# 2. at-rest seams: payload files and the SpillStore tiers
# ---------------------------------------------------------------------------


def test_write_payload_file_roundtrip_and_no_tmp_litter(tmp_path):
    path = str(tmp_path / "payload.bin")
    blob = integrity.seal(b"spill bytes" * 100)
    assert integrity.write_payload_file(path, blob) == len(blob)
    assert integrity.read_payload_file(
        path, seam="integrity.spill", sealed=True) == b"spill bytes" * 100
    # crash-safety hygiene: the tmp file was consumed by os.replace
    assert [p for p in os.listdir(tmp_path)
            if p.startswith(".integrity-")] == []


def test_write_payload_file_interrupted_replace_keeps_old_file(
        tmp_path, monkeypatch):
    """A crash between tmp-write and rename must leave the previous
    payload intact and unlink the tmp — never a torn hybrid."""
    path = str(tmp_path / "payload.bin")
    integrity.write_payload_file(path, integrity.seal(b"generation one"))

    def boom(src, dst):
        raise OSError("simulated crash at rename")

    monkeypatch.setattr(os, "replace", boom)
    with pytest.raises(OSError, match="simulated crash"):
        integrity.write_payload_file(path, integrity.seal(b"generation two"))
    monkeypatch.undo()
    assert integrity.read_payload_file(
        path, seam="integrity.spill", sealed=True) == b"generation one"
    assert [p for p in os.listdir(tmp_path)
            if p.startswith(".integrity-")] == []


def test_read_payload_file_detects_on_disk_corruption(tmp_path):
    path = str(tmp_path / "payload.bin")
    integrity.write_payload_file(path, integrity.seal(b"pristine" * 64))
    raw = bytearray(open(path, "rb").read())
    raw[7] ^= 0x80  # bitrot after the write-verify passed
    with open(path, "wb") as fh:
        fh.write(raw)
    with pytest.raises(CorruptDataError):
        integrity.read_payload_file(path, seam="integrity.spill", sealed=True)


def test_read_payload_file_unsealed_returns_raw_bytes(tmp_path):
    path = str(tmp_path / "raw.bin")
    integrity.write_payload_file(path, b"no trailer here")
    assert integrity.read_payload_file(
        path, seam="integrity.spill", sealed=False) == b"no trailer here"


def _evicting_store(tbl, **kw):
    """A store whose budget fits exactly one table: the second put evicts
    the first, exercising the spill tier under test."""
    return SpillStore(budget_bytes=_table_nbytes(tbl), **kw)


def test_spill_memory_tier_clean_roundtrip_bit_identical():
    tbl = _small_table(256, seed=7)
    store = _evicting_store(tbl)
    h = store.put(tbl)
    store.put(_small_table(256, seed=8))  # evicts h to host
    assert store.stats()["host_bytes"] > 0
    got = store.get(h)
    assert _tables_bit_identical(got, tbl)
    assert REGISTRY.counter("integrity.verified.integrity.spill").value == 1
    store.close()


def test_spill_memory_tier_detects_drift_and_stays_spilled():
    tbl = _small_table(256, seed=7)
    store = _evicting_store(tbl)
    script = faults.FaultScript(
        corruptions=[faults.CorruptionSpec("integrity.spill", mode="flip")])
    with faults.inject(script):
        h = store.put(tbl)
        store.put(_small_table(256, seed=8))
    assert script.fired, "corruption window never fired"
    for _ in range(2):  # deterministic: the same bytes fail every read
        with pytest.raises(CorruptDataError, match="snapshot checksum"):
            store.get(h)
    assert REGISTRY.counter(
        "integrity.mismatch.integrity.spill").value == 2
    store.close()


@pytest.mark.parametrize("mode", faults.CorruptionSpec.MODES)
def test_spill_disk_tier_detects_every_mode(tmp_path, mode):
    tbl = _small_table(256, seed=7)
    store = _evicting_store(tbl, spill_dir=str(tmp_path))
    script = faults.FaultScript(
        corruptions=[faults.CorruptionSpec(
            "integrity.spill", mode=mode, seed=11)])
    with faults.inject(script):
        h = store.put(tbl)
        store.put(_small_table(256, seed=8))
    assert store.stats()["disk_bytes"] > 0
    assert script.fired
    with pytest.raises(CorruptDataError):
        store.get(h)
    store.close()
    assert [p for p in os.listdir(tmp_path) if p.startswith("spill-")] == []


def test_spill_disk_tier_clean_roundtrip_unlinks_file(tmp_path):
    tbl = _small_table(256, seed=7)
    store = _evicting_store(tbl, spill_dir=str(tmp_path))
    h = store.put(tbl)
    store.put(_small_table(256, seed=8))
    files = [p for p in os.listdir(tmp_path) if p.startswith("spill-")]
    assert len(files) == 1
    # the sealed file carries the trailer right at EOF
    blob = open(str(tmp_path / files[0]), "rb").read()
    assert blob[-integrity.TRAILER_SIZE:][:4] == integrity.TRAILER_MAGIC
    got = store.get(h)
    assert _tables_bit_identical(got, tbl)
    # h's file is consumed on unspill (staging h back evicted the OTHER
    # table to a fresh file); close() sweeps everything
    assert not any(p.endswith(f"-{h}.bin") for p in os.listdir(tmp_path))
    store.close()
    assert [p for p in os.listdir(tmp_path) if p.startswith("spill-")] == []


def test_spill_disabled_path_has_no_trailer_no_crc(tmp_path):
    config.set_option("integrity.enabled", False)
    tbl = _small_table(256, seed=7)
    store = _evicting_store(tbl, spill_dir=str(tmp_path))
    h = store.put(tbl)
    store.put(_small_table(256, seed=8))
    files = [p for p in os.listdir(tmp_path) if p.startswith("spill-")]
    blob = open(str(tmp_path / files[0]), "rb").read()
    # byte-for-byte legacy behavior: the file IS the pickled snapshot
    assert blob[-integrity.TRAILER_SIZE:][:4] != integrity.TRAILER_MAGIC
    pickle.loads(blob)  # decodes directly, no framing
    got = store.get(h)
    assert _tables_bit_identical(got, tbl)
    assert REGISTRY.counter("integrity.mismatch").value == 0
    assert REGISTRY.counter("integrity.bytes_verified").value == 0
    store.close()


# ---------------------------------------------------------------------------
# 3. on-wire seam: DCN loopback
# ---------------------------------------------------------------------------


def _loopback_links():
    from spark_rapids_jni_tpu.parallel.dcn import SliceLink

    a, b = socket.socketpair()
    return SliceLink(a), SliceLink(b)


def _send_recv(tbl, script=None):
    tx, rx = _loopback_links()
    out, err = {}, {}

    def _rx():
        try:
            out["tbl"] = rx.recv_table()
        except BaseException as exc:  # noqa: BLE001 - surfaced below
            err["rx"] = exc

    t = threading.Thread(target=_rx)
    try:
        ctx = faults.inject(script) if script is not None else None
        if ctx is not None:
            ctx.__enter__()
        try:
            t.start()
            try:
                tx.send_table(tbl, compress_level=0)
            except BaseException as exc:  # noqa: BLE001 - surfaced below
                err["tx"] = exc
            t.join(30)
            assert not t.is_alive(), "receiver hung"
        finally:
            if ctx is not None:
                ctx.__exit__(None, None, None)
    finally:
        tx.close()
        rx.close()
    return out.get("tbl"), err


def test_wire_clean_roundtrip_verifies_and_acks():
    tbl = _small_table()
    got, err = _send_recv(tbl)
    assert not err
    assert _tables_bit_identical(got, tbl)
    assert REGISTRY.counter("integrity.verified.integrity.wire").value == 1
    assert REGISTRY.counter("integrity.bytes_verified").value > 0
    assert REGISTRY.counter("integrity.refetch").value == 0


@pytest.mark.parametrize("mode", faults.CorruptionSpec.MODES)
def test_wire_corruption_refetches_to_bit_identical(mode):
    tbl = _small_table()
    script = faults.FaultScript(
        corruptions=[faults.CorruptionSpec(
            "integrity.wire", mode=mode, seed=23)])
    got, err = _send_recv(tbl, script)
    assert not err, f"refetch should have recovered: {err}"
    assert script.fired == [("integrity.wire", 1)]
    assert _tables_bit_identical(got, tbl)
    assert REGISTRY.counter("integrity.refetch").value == 1
    assert REGISTRY.counter("integrity.mismatch.integrity.wire").value == 1
    evs = [e for e in telemetry.events() if e.get("kind") == "integrity"]
    assert [e["event"] for e in evs] == ["mismatch", "refetch", "recovered"]


def test_wire_refetch_exhaustion_dies_classified_on_both_sides():
    config.set_option("resilience.max_attempts", 2)
    tbl = _small_table()
    script = faults.FaultScript(
        corruptions=[faults.CorruptionSpec(
            "integrity.wire", mode="flip", times=10, seed=31)])
    got, err = _send_recv(tbl, script)
    assert got is None
    assert isinstance(err.get("tx"), FatalExecutionError)
    assert isinstance(err.get("rx"), FatalExecutionError)
    assert "corrupt" in str(err["rx"])
    assert isinstance(err["rx"].__cause__, CorruptDataError)
    assert REGISTRY.counter("integrity.refetch").value == 2
    # every attempt hit the corruption window: 2 sends, both mutated
    assert len(script.fired) == 2


def test_wire_disabled_framing_is_byte_identical_legacy():
    """integrity.enabled=false: the sender writes exactly the legacy
    ``<Q length> + serialized blob`` — no trailer, no ACK wait — so a
    pre-integrity peer interoperates byte-for-byte."""
    from spark_rapids_jni_tpu.parallel.dcn import SliceLink, serialize_table

    config.set_option("integrity.enabled", False)
    tbl = _small_table()
    want = serialize_table(tbl, 0)
    sa, sb = socket.socketpair()
    tx = SliceLink(sa)
    try:
        sent = tx.send_table(tbl, compress_level=0)  # returns: no ACK wait
        assert sent == len(want)
        sb.settimeout(10)
        raw = b""
        while len(raw) < 8 + len(want):
            raw += sb.recv(1 << 20)
        assert raw == struct.pack("<Q", len(want)) + want
        assert integrity.TRAILER_MAGIC not in raw[-integrity.TRAILER_SIZE:]
    finally:
        tx.close()
        sb.close()


# ---------------------------------------------------------------------------
# 4. checkpoint seam: out-of-core replay
# ---------------------------------------------------------------------------

_CHUNK_ROWS = 96
_N_CHUNKS = 4


def _chunks():
    rng = np.random.default_rng(17)
    return [Table([
        Column.from_numpy(
            rng.integers(0, 50, _CHUNK_ROWS).astype(np.int64)),
    ]) for _ in range(_N_CHUNKS)]


def _partial_fn(chunk):
    s = int(np.asarray(chunk.columns[0].data).sum())
    return Table([Column.from_numpy(np.asarray([s], dtype=np.int64))])


def _merge_fn(partials):
    s = int(np.asarray(partials.columns[0].data).sum())
    return Table([Column.from_numpy(np.asarray([s], dtype=np.int64))])


def _checkpoint_run(chunks, limiter, store, **kw):
    return run_chunked_aggregate(
        list(chunks), _partial_fn, _merge_fn,
        limiter=limiter, spill=store, pipeline=True, **kw)


def test_corrupt_checkpoint_replays_chunk_bit_identical():
    chunks = _chunks()
    want = _merge_fn(Table([Column.from_numpy(np.concatenate(
        [np.asarray([_partial_fn(c).columns[0].data[0]])
         for c in chunks]).astype(np.int64))]))
    limiter = MemoryLimiter(1 << 24)
    # budget == one partial: every checkpoint put evicts its predecessor,
    # so the corruption window sees every partial
    store = SpillStore(budget_bytes=_table_nbytes(_partial_fn(chunks[0])))
    script = faults.FaultScript(
        corruptions=[faults.CorruptionSpec(
            "integrity.checkpoint", mode="flip", times=2, seed=41)])
    with faults.inject(script):
        res = _checkpoint_run(chunks, limiter, store)
    assert len(script.fired) == 2
    assert _tables_bit_identical(res.table, want)
    assert limiter.used == 0, "replay leaked a reservation"
    s = telemetry.summary()["integrity"]
    assert s.get("replay") == 2 and s.get("recovered") == 2
    assert REGISTRY.counter(
        "integrity.mismatch.integrity.checkpoint").value == 2
    store.close()


def test_corrupt_checkpoint_serial_path_propagates_classified():
    """A generator input stream is consumed — there is no source list to
    replay from, so the classified error is the answer."""
    chunks = _chunks()
    limiter = MemoryLimiter(1 << 24)
    store = SpillStore(budget_bytes=_table_nbytes(_partial_fn(chunks[0])))
    script = faults.FaultScript(
        corruptions=[faults.CorruptionSpec(
            "integrity.checkpoint", mode="flip", seed=43)])
    with faults.inject(script):
        with pytest.raises(CorruptDataError):
            run_chunked_aggregate(
                iter(chunks), _partial_fn, _merge_fn,
                limiter=limiter, spill=store, pipeline=False)
    assert limiter.used == 0, "classified failure leaked a reservation"
    store.close()


# ---------------------------------------------------------------------------
# 5. untrusted ingestion: parquet/orc envelopes + the serving stack
# ---------------------------------------------------------------------------


def _parquet_bytes(n=32):
    from tests.parquet_util import ColumnSpec, write_parquet

    return write_parquet([
        ColumnSpec("a", 2, list(range(n))),  # INT64
        ColumnSpec("b", 5, [float(i) / 3 for i in range(n)]),  # DOUBLE
    ])


def _orc_bytes(n=32):
    from tests.orc_util import ColumnSpec, write_orc

    return write_orc([ColumnSpec("a", 4, list(range(n)))])  # LONG


def test_parquet_envelope_malformed_variants_classified():
    from spark_rapids_jni_tpu.parquet.reader import read_table

    good = _parquet_bytes()
    variants = {
        "too-short": good[:8],
        "bad-head-magic": b"XXXX" + good[4:],
        "bad-tail-magic": good[:-4] + b"XXXX",
        "footer-length-lie": good[:-8]
        + struct.pack("<I", len(good) * 2) + good[-4:],
    }
    for name, blob in variants.items():
        with pytest.raises(MalformedFileError) as ei:
            read_table(blob)
        # dual classification: serving stack AND legacy native catches
        assert isinstance(ei.value, MalformedInputError), name
        assert isinstance(ei.value, NativeError), name
    assert REGISTRY.counter(
        "integrity.malformed.parquet.envelope").value == len(variants)
    evs = [e for e in telemetry.events() if e.get("kind") == "integrity"]
    assert all(e["seam"] == "integrity.ingest" for e in evs)


def test_orc_envelope_malformed_variants_classified():
    from spark_rapids_jni_tpu.orc.reader import read_table

    good = _orc_bytes()
    variants = {
        "too-short": good[:5],
        "bad-head-magic": b"XXX" + good[3:],
        "bad-tail-magic": good[:-4] + b"XXXA",
        "ps-length-lie": good[:-1] + bytes([251]),
    }
    for name, blob in variants.items():
        with pytest.raises(MalformedFileError) as ei:
            read_table(blob)
        assert isinstance(ei.value, MalformedInputError), name
        assert isinstance(ei.value, NativeError), name
    assert REGISTRY.counter(
        "integrity.malformed.orc.envelope").value == len(variants)


def test_valid_envelopes_pass_pure_python_preflight():
    """A well-formed file must NOT be rejected by the preflight: it goes
    on to the native parse and decodes."""
    from spark_rapids_jni_tpu.orc.reader import read_table as orc_read
    from spark_rapids_jni_tpu.parquet.reader import read_table as pq_read

    for reader, blob in ((pq_read, _parquet_bytes()),
                         (orc_read, _orc_bytes())):
        assert reader(blob).num_rows == 32
    assert REGISTRY.counter("integrity.malformed").value == 0


def test_envelope_checks_also_cover_path_inputs(tmp_path):
    from spark_rapids_jni_tpu.parquet.reader import read_table

    path = tmp_path / "broken.parquet"
    path.write_bytes(b"PAR1" + b"\x00" * 16)  # no trailing magic
    with pytest.raises(MalformedFileError):
        read_table(str(path))


def test_ingest_preflight_disabled_is_passthrough():
    """integrity.enabled=false: no preflight — malformed bytes reach the
    native parse exactly as before this layer existed, and it refuses them."""
    from spark_rapids_jni_tpu.parquet.reader import read_table

    config.set_option("integrity.enabled", False)
    # the preflight would say "bad leading magic"; off, the native parse
    # refuses the bytes itself and the reader's _check classifies that
    with pytest.raises(MalformedFileError, match="op=parquet.parquet read"):
        read_table(b"not parquet at all")
    assert REGISTRY.counter(
        "integrity.malformed.parquet.envelope").value == 0
    assert REGISTRY.counter(
        "integrity.malformed.parquet.parquet read").value == 1


def _malformed_ingest(tbl, *args):
    """Module-level plan callable (the executable cache keys on the
    qualified name): reading a malformed customer file mid-query."""
    from spark_rapids_jni_tpu.parquet.reader import read_table

    read_table(b"PAR1 this is not a parquet file")  # MalformedFileError
    return tbl


def test_server_rejects_malformed_query_cleanly():
    """The end-to-end contract: one session submits a query over a
    malformed file — that query fails classified (never retried), the
    bystander session's result is untouched, and zero reservations
    leak."""
    from spark_rapids_jni_tpu.models import tpch
    from spark_rapids_jni_tpu.runtime import dispatch, fusion, server

    dispatch.clear()
    doomed_plan = fusion.Plan("malformed_ingest", fusion.Project(
        fusion.Scan("lineitem"), _malformed_ingest, rowwise=False))
    good_plan = tpch._q1_plan()
    bindings = {"lineitem": tpch.lineitem_table(600, seed=0)}
    ref = fusion.execute(good_plan, bindings)

    with server.QueryServer(budget_bytes=1 << 28, max_inflight=4) as srv:
        doomed = srv.session("victim").submit(doomed_plan, bindings)
        fine = srv.session("bystander").submit(good_plan, bindings)
        with pytest.raises(MalformedInputError):
            doomed.result(timeout=60)
        assert doomed.status == "failed"
        res = fine.result(timeout=60)
        assert fine.status == "served"
        assert _tables_bit_identical(res.table, ref.table)
        # the bystander's cached result legitimately holds a residency
        # charge until close(); anything beyond that is a leak
        assert srv.limiter.used == srv.result_cache.evictable_bytes, \
            "malformed rejection leaked bytes"
        assert srv.session_stats("victim")["failed"] == 1
        assert srv.session_stats("bystander")["failed"] == 0
    assert srv.limiter.used == 0, "close() left reservations behind"
    assert REGISTRY.counter("integrity.malformed_rejects").value == 1
    # never retried: a malformed file is wrong forever
    retries = [e for e in telemetry.events()
               if e.get("kind") == "resilience" and e.get("event") == "retry"]
    assert retries == []
    dispatch.clear()


def test_telemetry_report_has_integrity_section(tmp_path):
    import json

    from spark_rapids_jni_tpu.telemetry.report import report

    blob = integrity.seal(b"x" * 64)
    with pytest.raises(CorruptDataError):
        integrity.verify(blob[:-3], seam="integrity.spill", op="test")
    path = tmp_path / "run.jsonl"
    path.write_text("".join(
        json.dumps(e) + "\n" for e in telemetry.events()))
    text = report(str(path))
    assert "integrity events:" in text
    assert "mismatch seams:" in text
    assert "integrity.spill=1" in text
