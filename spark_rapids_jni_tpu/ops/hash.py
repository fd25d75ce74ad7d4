"""Spark-compatible xxhash64 (north-star component: the reference family's
``xxhash64`` kernel backs Spark's HashPartitioning/Bloom filters;
BASELINE.json north_star lists it explicitly).

Implements XXH64's short-input paths — hashInt (4-byte) and hashLong
(8-byte) — exactly as Spark's ``XXH64`` utility applies them per column
value, chained across columns with the running hash as seed and nulls
skipped (Spark HashExpression semantics). Fully vectorized uint64
arithmetic: multiplies/rotates/xors are all implemented by the TPU x64
emulation pass (no bitcasts needed for integer inputs; floats go through
ops.bytecast encodings).

Spark value widening rules: bool/byte/short/int -> hashInt of the int32
value; long/timestamp/date64 -> hashLong; float -> hashInt of its IEEE
bits (-0.0 normalized to 0.0); double -> hashLong of its bits (-0.0
normalized); decimal32/64 -> hashLong of the unscaled value.
"""

from __future__ import annotations

from functools import partial
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from spark_rapids_jni_tpu.columnar import Column, Table
from spark_rapids_jni_tpu.ops.bytecast import _f64_to_bits, _has_bitcast64
from spark_rapids_jni_tpu.types import TypeId
from spark_rapids_jni_tpu.utils.tracing import func_range

SPARK_DEFAULT_SEED = 42

_P1 = np.uint64(0x9E3779B185EBCA87)
_P2 = np.uint64(0xC2B2AE3D4F54DE4F)
_P3 = np.uint64(0x165667B19E3779F9)
_P4 = np.uint64(0x85EBCA77C2B2AE63)
_P5 = np.uint64(0x27D4EB2F165667C5)


def _rotl(x: jnp.ndarray, r: int) -> jnp.ndarray:
    return (x << np.uint64(r)) | (x >> np.uint64(64 - r))


def _avalanche(h: jnp.ndarray) -> jnp.ndarray:
    h = h ^ (h >> np.uint64(33))
    h = h * _P2
    h = h ^ (h >> np.uint64(29))
    h = h * _P3
    h = h ^ (h >> np.uint64(32))
    return h


def xxhash64_long(value: jnp.ndarray, seed: jnp.ndarray) -> jnp.ndarray:
    """XXH64 of one 8-byte little-endian value per row (Spark hashLong)."""
    value = value.astype(jnp.uint64)
    seed = seed.astype(jnp.uint64)
    h = seed + _P5 + np.uint64(8)
    k1 = _rotl(value * _P2, 31) * _P1
    h = h ^ k1
    h = _rotl(h, 27) * _P1 + _P4
    return _avalanche(h)


def xxhash64_int(value: jnp.ndarray, seed: jnp.ndarray) -> jnp.ndarray:
    """XXH64 of one 4-byte value per row (Spark hashInt)."""
    v = value.astype(jnp.uint32).astype(jnp.uint64)
    seed = seed.astype(jnp.uint64)
    h = seed + _P5 + np.uint64(4)
    h = h ^ (v * _P1)
    h = _rotl(h, 23) * _P2 + _P3
    return _avalanche(h)


def _column_hash(col: Column, seeds: jnp.ndarray) -> jnp.ndarray:
    """Hash one column's values with per-row seeds; null rows pass the seed
    through unchanged (Spark chaining semantics)."""
    tid = col.dtype.type_id
    v = col.data
    if tid == TypeId.STRING:
        from spark_rapids_jni_tpu.ops import strings as s

        # full variable-length XXH64 over the row's UTF-8 bytes — Spark's
        # hashUnsafeBytes / the reference family's string xxhash64 kernel.
        return s.hash_string_column(col, seeds)
    if tid in (TypeId.BOOL8, TypeId.INT8, TypeId.UINT8, TypeId.INT16,
               TypeId.UINT16, TypeId.INT32, TypeId.UINT32,
               TypeId.TIMESTAMP_DAYS, TypeId.DURATION_DAYS):
        # sign-extend to int32 like Spark's widening to int
        hashed = xxhash64_int(v.astype(jnp.int32), seeds)
    elif tid == TypeId.FLOAT32:
        norm = jnp.where(v == 0.0, jnp.float32(0.0), v)  # -0.0 -> 0.0
        bits = jax.lax.bitcast_convert_type(norm, jnp.uint32)
        hashed = xxhash64_int(bits, seeds)
    elif tid == TypeId.FLOAT64:
        norm = jnp.where(v == 0.0, jnp.float64(0.0), v)
        if _has_bitcast64():
            bits = jax.lax.bitcast_convert_type(norm, jnp.uint64)
        else:
            bits = _f64_to_bits(norm)
        hashed = xxhash64_long(bits, seeds)
    elif col.dtype.is_decimal128:
        # Spark hashes Decimal(precision > 18) as XXH64 over the MINIMAL
        # big-endian two's-complement byte array of the unscaled value
        # (java BigDecimal.unscaledValue().toByteArray()): build the
        # 16-byte big-endian image, strip redundant sign-filler bytes
        # (keeping one when the next byte's sign bit would flip the
        # value), left-align, and run the variable-length byte hash.
        from spark_rapids_jni_tpu.ops.strings import xxhash64_bytes

        lo = v[:, 0]
        hi = v[:, 1]
        shifts = jnp.arange(56, -1, -8, dtype=jnp.int64)
        be = jnp.concatenate(
            [((hi[:, None] >> shifts[None, :]) & 0xFF),
             ((lo[:, None] >> shifts[None, :]) & 0xFF)], axis=1
        ).astype(jnp.uint8)                         # (n, 16) big-endian
        filler = jnp.where(hi < 0, jnp.uint8(0xFF), jnp.uint8(0))
        is_filler = be == filler[:, None]
        # first non-filler byte index (16 when all filler: value 0 / -1)
        nf = jnp.argmin(is_filler.astype(jnp.int8), axis=1).astype(jnp.int32)
        all_filler = jnp.all(is_filler, axis=1)
        first = jnp.where(all_filler, 15, nf)
        # sign bit of the first kept byte must match the filler's, else
        # one filler byte stays (0x80 <-> sign flip)
        fb = jnp.take_along_axis(be, first[:, None], axis=1)[:, 0]
        sign_mismatch = (fb >= 0x80) != (hi < 0)
        start = jnp.where(all_filler, 15,
                          jnp.where(sign_mismatch, first - 1, first))
        lengths = (16 - start).astype(jnp.int32)
        src = jnp.clip(start[:, None] + jnp.arange(16, dtype=jnp.int32), 0, 15)
        shifted = jnp.take_along_axis(be, src, axis=1)
        hashed = xxhash64_bytes(shifted, lengths, seeds)
    else:
        hashed = xxhash64_long(v.astype(jnp.int64), seeds)
    if col.validity is None:
        return hashed
    return jnp.where(col.validity, hashed, seeds)


def _table_xxhash64_impl(row_args, aux, rvs, *, seed: int):
    ((table,),) = row_args
    n = table.num_rows
    h = jnp.full((n,), np.uint64(seed), dtype=jnp.uint64)
    for c in range(table.num_columns):
        h = _column_hash(table.column(c), h)
    return h.astype(jnp.int64)


@func_range("hash_table")
def table_xxhash64(
    table: Table,
    columns: Sequence[int] | None = None,
    seed: int = SPARK_DEFAULT_SEED,
) -> jnp.ndarray:
    """Row hash: per-column xxhash64 chained left-to-right with the running
    hash as seed (Spark HashExpression). Returns int64[n]. Spark-exact for
    every supported type, including DECIMAL128 (minimal two's-complement
    byte-array hash, the Decimal(precision > 18) rule)."""
    cols = tuple(range(table.num_columns) if columns is None else columns)
    # dispatch only the hashed columns: an unused Arrow-layout string
    # elsewhere in the table must not force the inline path (pad rows are
    # null -> they pass the seed through, and the tail is sliced off)
    sub = Table([table.column(c) for c in cols])
    from spark_rapids_jni_tpu.runtime import dispatch

    return dispatch.rowwise(
        "table_xxhash64", partial(_table_xxhash64_impl, seed=seed),
        (sub,), statics=(seed,))


def partition_hash(table: Table, columns: Sequence[int], num_partitions: int) -> jnp.ndarray:
    """Spark-style hash partitioning: pmod(hash, n). Returns int32[n].
    jnp's % follows Python semantics (result carries the divisor's sign),
    which IS pmod."""
    h = table_xxhash64(table, columns)
    return (h % jnp.int64(num_partitions)).astype(jnp.int32)
