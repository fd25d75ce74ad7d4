"""Corruption fuzz harness for the integrity layer (ISSUE 10 satellite,
extended with compressed payloads in ISSUE 12).

260+ seeded corruption cases across every managed byte boundary — the
in-memory spill tier, the disk spill tier, the DCN wire, out-of-core
checkpoints, the result-cache seam, untrusted Parquet/ORC ingestion,
and codec frames mutated AFTER a clean seal verification. With
``compress.enabled`` defaulting on, families 1-4 already corrupt
codec-compressed payloads (flip/truncate/trailer land on the compressed
bytes under the seal); families 6-7 add the cache seam and the
corrupt-after-decompress header cases the trailer cannot catch. The
single invariant, asserted per case:

    every corruption is DETECTED AND CLASSIFIED (``CorruptDataError`` /
    ``MalformedInputError``) or the result is BIT-IDENTICAL to the
    corruption-free run — never an unclassified crash, never garbage
    decoded, never a leaked reservation.

Every mutation derives from ``CorruptionSpec(seed=...)`` — reproducible
case-by-case: a failure names its (family, mode, seed) triple and replays
standalone. Ingestion differs in one point: the files are a foreign
format without page checksums, so one outcome more is admissible there
and no other — a bit flipped inside a column's value bytes decodes to a
table of the file's schema, row count and validity that differs from
the original in that column's values alone. A mutation anywhere else
that passes the envelope preflight is refused by the native parse (built
from ``src/native`` on first touch, so it is never absent), classified.
"""

import socket
import threading

import numpy as np
import pytest

from spark_rapids_jni_tpu import telemetry
from spark_rapids_jni_tpu.columnar import Column, Table
from spark_rapids_jni_tpu.runtime import faults, integrity
from spark_rapids_jni_tpu.runtime.memory import (
    MemoryLimiter,
    SpillStore,
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

MODES = faults.CorruptionSpec.MODES  # ("flip", "truncate", "trailer")


@pytest.fixture(autouse=True)
def _reset():
    telemetry.drain()
    REGISTRY.reset()
    yield
    telemetry.drain()
    REGISTRY.reset()
    for name in list(config._overrides):
        config.reset_option(name)


def _table(n=128, seed=0):
    rng = np.random.default_rng(seed)
    return Table([
        Column.from_numpy(rng.integers(0, 1000, n).astype(np.int64)),
        Column.from_numpy(rng.integers(-50, 50, n).astype(np.int64),
                          validity=rng.random(n) > 0.15),
    ])


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


# ---------------------------------------------------------------------------
# family 1: in-memory spill tier — 60 seeded bit flips
# (live numpy snapshots cannot shrink, so flip is the only mode that
# lands there; truncation/trailer shapes are covered on the disk tier)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed", range(60))
def test_fuzz_spill_memory_flip(seed):
    tbl = _table(seed=seed)
    store = SpillStore(budget_bytes=_table_nbytes(tbl))
    script = faults.FaultScript(corruptions=[
        faults.CorruptionSpec("integrity.spill", mode="flip", seed=seed)])
    try:
        with faults.inject(script):
            h = store.put(tbl)
            store.put(_table(seed=seed + 1000))  # evict h to host
        assert script.fired, f"seed {seed}: corruption window never fired"
        try:
            got = store.get(h)
        except CorruptDataError:
            assert REGISTRY.counter("integrity.mismatch").value >= 1
        else:  # pragma: no cover - would mean a missed detection
            assert _bit_identical(got, tbl), \
                f"seed {seed}: undetected corruption decoded as garbage"
    finally:
        store.close()


# ---------------------------------------------------------------------------
# family 2: disk spill tier — 40 seeded cases over all three modes
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("case", range(40))
def test_fuzz_spill_disk(case, tmp_path):
    mode = MODES[case % len(MODES)]
    seed = 100 + case
    tbl = _table(seed=seed)
    store = SpillStore(budget_bytes=_table_nbytes(tbl),
                       spill_dir=str(tmp_path))
    script = faults.FaultScript(corruptions=[
        faults.CorruptionSpec("integrity.spill", mode=mode, seed=seed)])
    try:
        with faults.inject(script):
            h = store.put(tbl)
            store.put(_table(seed=seed + 1000))  # evict h to disk
        assert script.fired, f"{mode}/{seed}: corruption window never fired"
        try:
            got = store.get(h)
        except CorruptDataError:
            assert REGISTRY.counter("integrity.mismatch").value >= 1
        else:  # pragma: no cover - would mean a missed detection
            assert _bit_identical(got, tbl), \
                f"{mode}/{seed}: undetected corruption decoded as garbage"
    finally:
        store.close()


# ---------------------------------------------------------------------------
# family 3: DCN wire — 50 seeded frame mutations; a single corruption is
# always recovered via NAK+refetch to a bit-identical delivery
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("case", range(50))
def test_fuzz_wire_mutation_recovers_bit_identical(case):
    from spark_rapids_jni_tpu.parallel.dcn import SliceLink

    mode = MODES[case % len(MODES)]
    seed = 200 + case
    tbl = _table(n=96, seed=seed)
    script = faults.FaultScript(corruptions=[
        faults.CorruptionSpec("integrity.wire", mode=mode, seed=seed)])
    sa, sb = socket.socketpair()
    tx, rx = SliceLink(sa), SliceLink(sb)
    out, err = {}, {}

    def _rx():
        try:
            out["tbl"] = rx.recv_table()
        except BaseException as exc:  # noqa: BLE001 - surfaced below
            err["rx"] = exc

    t = threading.Thread(target=_rx)
    try:
        with faults.inject(script):
            t.start()
            tx.send_table(tbl, compress_level=0)
            t.join(30)
        assert not t.is_alive(), f"{mode}/{seed}: receiver hung"
        assert not err, f"{mode}/{seed}: refetch did not recover: {err}"
        assert script.fired, f"{mode}/{seed}: corruption window never fired"
        assert _bit_identical(out["tbl"], tbl), \
            f"{mode}/{seed}: refetched frame diverged"
        assert REGISTRY.counter("integrity.refetch").value == 1
    finally:
        tx.close()
        rx.close()


# ---------------------------------------------------------------------------
# family 4: out-of-core checkpoints — 30 seeded corruptions; the chunk is
# replayed from source to a bit-identical result, zero leaked reservations
# ---------------------------------------------------------------------------

_CK_CHUNKS = 3
_CK_ROWS = 64


def _ck_chunks(seed):
    rng = np.random.default_rng(seed)
    return [Table([
        Column.from_numpy(rng.integers(0, 99, _CK_ROWS).astype(np.int64)),
    ]) for _ in range(_CK_CHUNKS)]


def _ck_partial(chunk):
    s = int(np.asarray(chunk.columns[0].data).sum())
    return Table([Column.from_numpy(np.asarray([s], dtype=np.int64))])


def _ck_merge(partials):
    s = int(np.asarray(partials.columns[0].data).sum())
    return Table([Column.from_numpy(np.asarray([s], dtype=np.int64))])


@pytest.mark.parametrize("seed", range(300, 330))
def test_fuzz_checkpoint_corruption_replays_bit_identical(seed):
    chunks = _ck_chunks(seed)
    want = sum(int(np.asarray(c.columns[0].data).sum()) for c in chunks)
    limiter = MemoryLimiter(1 << 24)
    store = SpillStore(budget_bytes=_table_nbytes(_ck_partial(chunks[0])))
    script = faults.FaultScript(corruptions=[
        faults.CorruptionSpec("integrity.checkpoint", mode="flip",
                              seed=seed)])
    try:
        with faults.inject(script):
            res = run_chunked_aggregate(
                list(chunks), _ck_partial, _ck_merge,
                limiter=limiter, spill=store, pipeline=True)
        assert script.fired, f"seed {seed}: corruption window never fired"
        got = int(np.asarray(res.table.columns[0].data)[0])
        assert got == want, f"seed {seed}: replayed result diverged"
        assert limiter.used == 0, f"seed {seed}: leaked reservation"
        assert REGISTRY.counter(
            "integrity.mismatch.integrity.checkpoint").value == 1
    finally:
        store.close()


# ---------------------------------------------------------------------------
# family 5: untrusted ingestion — 40 seeded mutations of well-formed
# Parquet/ORC files. Every case classifies (MalformedInputError, from the
# envelope preflight or from the native parse) or, where the flipped bit
# lies inside a column's value bytes (neither file carries a checksum
# over them), decodes to the original but for that column's values.
# Never an unclassified crash, never another shape, never another column.
# ---------------------------------------------------------------------------


def _parquet_file():
    """-> (file, [(start, stop, column)] of each column's PLAIN values)."""
    from tests.parquet_util import ColumnSpec, plain_encode, write_parquet

    cols = [
        ColumnSpec("a", 2, list(range(48))),            # INT64
        ColumnSpec("b", 5, [i / 7 for i in range(48)]),  # DOUBLE
    ]
    blob = write_parquet(cols)
    spans = []
    for i, c in enumerate(cols):
        raw = plain_encode(c.physical, c.values)
        at = blob.index(raw)
        spans.append((at, at + len(raw), i))
    return blob, spans


def _orc_file():
    """-> (file, [(start, stop, column)] of the DATA stream's varints:
    one literal run of RLEv1, its control byte not among them)."""
    from tests.orc_util import ColumnSpec, rle_v1_literals, write_orc

    values = list(range(48))
    blob = write_orc([ColumnSpec("a", 4, values)])  # LONG
    run = rle_v1_literals(values)
    at = blob.index(run)
    return blob, [(at + 1, at + len(run), 0)]


def _buffers(col):
    return (col.dtype, np.asarray(col.data).tobytes(),
            np.asarray(col.valid_mask()).tobytes())


def _fuzz_ingest(read_table, blob, value_spans, mode, seed):
    want = read_table(blob)
    script = faults.FaultScript(corruptions=[
        faults.CorruptionSpec("integrity.ingest", mode=mode, seed=seed)])
    with faults.inject(script):
        try:
            got = read_table(blob)
        except MalformedInputError:
            assert REGISTRY.counter("integrity.malformed").value >= 1
            return "classified"
        except (CorruptDataError, FatalExecutionError):  # pragma: no cover
            return "classified"
    assert script.fired, f"{mode}/{seed}: corruption window never fired"
    # the file decoded: the same mutation again, to see where it fell
    mutated = faults.CorruptionSpec(
        "integrity.ingest", mode=mode, seed=seed).apply(blob, 0)
    assert len(mutated) == len(blob), \
        f"{mode}/{seed}: a file cut short decoded"
    hit = [i for i in range(len(blob)) if blob[i] != mutated[i]]
    col = next((c for a, b, c in value_spans
                if len(hit) == 1 and a <= hit[0] < b), None)
    assert col is not None, (f"{mode}/{seed}: bytes {hit} hold no column's "
                             "values and the file decoded")
    assert (got.num_rows, got.num_columns) == (
        want.num_rows, want.num_columns), f"{mode}/{seed}"
    for i, (g, w) in enumerate(zip(got.columns, want.columns)):
        (g_dtype, g_data, g_valid), (w_dtype, w_data, w_valid) = (
            _buffers(g), _buffers(w))
        assert g_dtype == w_dtype and g_valid == w_valid, \
            f"{mode}/{seed}: column {i} changed type or validity"
        assert (g_data != w_data) == (i == col), \
            f"{mode}/{seed}: byte {hit[0]} is column {col}'s, column {i}"
    return "value-flip"


@pytest.mark.parametrize("case", range(20))
def test_fuzz_ingest_parquet(case):
    from spark_rapids_jni_tpu.parquet.reader import read_table

    outcome = _fuzz_ingest(read_table, *_parquet_file(),
                           MODES[case % len(MODES)], 400 + case)
    assert outcome in ("classified", "value-flip")


# flips that fell in the file's structure and once decoded all the same:
# 503 turns the footer's one `stripes` entry into an unknown field (a
# 0-row table), 512 the root's ColumnEncoding in the stripe footer (the
# column was then read as DIRECT by default). The native parse holds the
# footer's numberOfRows against its stripes and wants an encoding a column.
_ORC_STRUCTURE_FLIPS = (503, 512)


@pytest.mark.parametrize("case", range(20))
def test_fuzz_ingest_orc(case):
    from spark_rapids_jni_tpu.orc.reader import read_table

    seed = 500 + case
    outcome = _fuzz_ingest(read_table, *_orc_file(),
                           MODES[case % len(MODES)], seed)
    assert outcome in ("classified", "value-flip")
    if seed in _ORC_STRUCTURE_FLIPS:
        assert outcome == "classified"


# ---------------------------------------------------------------------------
# family 6: result-cache seam — 20 seeded corruptions of codec-compressed
# cached snapshots; detected-and-classified or bit-identical, and the
# spill store's accounting never leaks
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("case", range(20))
def test_fuzz_cache_seam_compressed(case, tmp_path):
    from spark_rapids_jni_tpu.runtime import compress

    assert compress.seam_enabled("integrity.cache")
    mode = MODES[case % len(MODES)]
    seed = 600 + case
    tbl = _table(seed=seed)
    # disk on odd cases so all three modes land on both stored tiers
    store = SpillStore(budget_bytes=_table_nbytes(tbl),
                       spill_dir=str(tmp_path) if case % 2 else None)
    script = faults.FaultScript(corruptions=[
        faults.CorruptionSpec("integrity.cache", mode=mode, seed=seed)])
    try:
        with faults.inject(script):
            h = store.put(tbl, integrity_seam="integrity.cache")
            store.put(_table(seed=seed + 1000))  # evict h off the device
        # codec packs store BYTES in the host tier (unlike the legacy
        # live-ndarray snapshots), so all three modes land on both tiers
        assert script.fired, f"{mode}/{seed}: corruption window never fired"
        try:
            got = store.get(h)
        except CorruptDataError:
            assert REGISTRY.counter(
                "integrity.mismatch.integrity.cache").value >= 1
        else:  # pragma: no cover - would mean a missed detection
            assert _bit_identical(got, tbl), \
                f"{mode}/{seed}: undetected corruption decoded as garbage"
    finally:
        store.close()


# ---------------------------------------------------------------------------
# family 7: corrupt-after-decompress — 21 seeded codec-frame header
# mutations sealed AFTER the damage, so the trailer verifies clean and
# only the codec's own header/per-scheme length checks can classify
# ---------------------------------------------------------------------------

# header region only (magic/version/scheme + dtype/ndim/shape); byte 6
# (zstd flag) is excluded — with zstandard absent a set flag raises
# ModuleNotFoundError (deployment error), deliberately not classified
_HDR_POSITIONS = tuple(range(0, 6)) + tuple(range(7, 16))


def _mutate_frame(frame, seed):
    rng = np.random.default_rng(seed)
    kind = seed % 3
    if kind == 0:  # header bit flip
        pos = _HDR_POSITIONS[int(rng.integers(0, len(_HDR_POSITIONS)))]
        return frame[:pos] + bytes([frame[pos] ^ (1 << int(
            rng.integers(0, 8)))]) + frame[pos + 1:]
    if kind == 1:  # truncation (anywhere)
        return frame[:int(rng.integers(1, len(frame)))]
    pos = _HDR_POSITIONS[int(rng.integers(0, len(_HDR_POSITIONS)))]
    return frame[:pos] + bytes([frame[pos] ^ 0xFF]) + frame[pos + 1:]


@pytest.mark.parametrize("seed", range(700, 721))
def test_fuzz_corrupt_after_decompress_header(seed):
    from spark_rapids_jni_tpu.runtime import compress

    rng = np.random.default_rng(seed)
    arr = np.sort(rng.integers(0, 30, 2048)).astype(np.int32)
    mutated = _mutate_frame(compress.encode_array(arr), seed)
    sealed = integrity.seal(mutated)
    # the seal covers the already-mutated frame: verification is clean
    assert integrity.verify(sealed, seam="integrity.spill") == mutated
    try:
        got = compress.decode_array(mutated)
    except CorruptDataError:
        assert REGISTRY.counter("compress.mismatch").value >= 1
        assert REGISTRY.counter("integrity.mismatch").value >= 1
    else:
        assert np.array_equal(got, arr), \
            f"seed {seed}: undetected codec mutation decoded as garbage"


def test_fuzz_corpus_runs_compressed_by_default():
    """Families 1-4 corrupt codec-compressed payloads: the codec seams
    default on, so flip/truncate/trailer land on compressed bytes."""
    from spark_rapids_jni_tpu.runtime import compress

    assert compress.enabled()
    for seam in ("integrity.spill", "integrity.wire",
                 "integrity.checkpoint", "integrity.cache"):
        assert compress.seam_enabled(seam), seam


def test_fuzz_corpus_is_at_least_200_cases():
    """The harness floor pinned:
    60 + 40 + 50 + 30 + 40 + 20 + 21 seeded cases."""
    assert 60 + 40 + 50 + 30 + 40 + 20 + 21 >= 200
