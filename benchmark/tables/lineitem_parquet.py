"""Table maker ``lineitem_parquet``: lineitem as a Spark table holds it
before any query: snappy Parquet files of all sixteen columns of TPC-H
clause 1.4.1, written in set-up, of which a request reads seven by name.

How a file-backed cell's files differ from a resident cell's: ``make``
returns no device arrays but the files (a ring of ``RING`` of them under a
directory of this process in ``TMPDIR``, removed when the process ends)
and the generator's own seven q1 columns on the host; ``host_copy`` hands
the reference those columns, never what a reader decoded, so a dropped row
group, a mis-scaled decimal or a column bound out of order changes a sum;
``to_table`` makes a path the program's ``ParquetSplit`` (the whole file as
one task's split, the seven columns by name), which the server resolves,
admits, decodes and stages itself; the freshener (``fresh/next_split.py``)
hands out the ring's files under paths no request has had. The plan file
is ``q1_planned_parquet``; everything else is found by name as for any cell.

The seven columns q1 reads are ``tables/lineitem.py``'s, from the same
seed, value for value. The nine it does not read come from the cheapest
seeded generators that keep the specified type and width (keys INT64,
``l_linenumber`` INT32, DATEs, CHAR(25) / CHAR(10) dictionary-encoded as
parquet-mr would, ``l_comment`` VARCHAR(44) of 10-43 bytes). The four
scale-2 decimals are DECIMAL(15,2) stored as INT64 with the annotation,
the flags one-byte codes (INT32 / INT_8), as in every configuration here.
Columns are optional and hold no null, as Spark writes every column. Each
file after the first is the first's rows rolled, so the multiset of rows
is the base's and one reference answer checks every request.
"""

from __future__ import annotations

import concurrent.futures
import os
import shutil
import tempfile
import weakref

from benchmark import resolve

_BASE = resolve.module("tables", "lineitem")
COLUMNS, ROW_BYTES = _BASE.COLUMNS, _BASE.ROW_BYTES   # what a request reads
READ = tuple(c[0] for c in COLUMNS)
ROW_GROUP_ROWS = 1_000_000   # about 128 MB of raw rows: parquet.block.size
RING = 2
_INSTRUCT = (b"DELIVER IN PERSON", b"COLLECT COD", b"NONE",
             b"TAKE BACK RETURN")
_MODE = (b"REG AIR", b"AIR", b"RAIL", b"SHIP", b"TRUCK", b"MAIL", b"FOB")


class _Files:
    """The directory the ring lives in; it goes with this object, or at
    the latest when the process ends."""

    def __init__(self):
        self.dir = tempfile.mkdtemp(prefix="benchmark-lineitem-parquet-")
        weakref.finalize(self, shutil.rmtree, self.dir, True)


def _arrow_table(host: dict, rows: int, seed: int):
    """The sixteen columns of clause 1.4.1, in its order, as one pyarrow
    table over the seven generated columns and nine cheap ones."""
    import numpy as np
    import pyarrow as pa

    rng = np.random.default_rng(seed)
    row = np.arange(rows, dtype=np.int64)

    def fixed(kind, values):
        return pa.Array.from_buffers(kind, rows, [None, pa.py_buffer(values)])

    def money(name):   # unscaled int64 -> DECIMAL(15,2): low and high limb
        v = host[name]
        return fixed(pa.decimal128(15, 2), np.stack([v, v >> 63], axis=1))

    def chars(values, width):
        codes = rng.integers(0, len(values), rows, dtype=np.int8)
        return pa.DictionaryArray.from_arrays(
            pa.array(codes), pa.array([v.ljust(width) for v in values],
                                      type=pa.binary()).cast(pa.string()))

    ship = host["l_shipdate"]
    lengths = rng.integers(10, 44, rows, dtype=np.int32)
    offsets = np.zeros(rows + 1, np.int32)
    np.cumsum(lengths, out=offsets[1:])
    block = rng.integers(97, 123, 1 << 20, dtype=np.uint8)
    comment = pa.StringArray.from_buffers(
        rows, pa.py_buffer(offsets),
        pa.py_buffer(np.resize(block, int(offsets[-1]))))
    return pa.table({
        "l_orderkey": pa.array(row // 4 + 1),
        "l_partkey": pa.array(rng.integers(1, 200_001, rows, dtype=np.int64)),
        "l_suppkey": pa.array(rng.integers(1, 10_001, rows, dtype=np.int64)),
        "l_linenumber": pa.array((row % 4 + 1).astype(np.int32)),
        "l_quantity": money("l_quantity"),
        "l_extendedprice": money("l_extendedprice"),
        "l_discount": money("l_discount"),
        "l_tax": money("l_tax"),
        "l_returnflag": pa.array(host["l_returnflag"]),
        "l_linestatus": pa.array(host["l_linestatus"]),
        "l_shipdate": fixed(pa.date32(), ship),
        "l_commitdate": fixed(pa.date32(), ship + rng.integers(
            -30, 31, rows, dtype=np.int32)),
        "l_receiptdate": fixed(pa.date32(), ship + rng.integers(
            1, 31, rows, dtype=np.int32)),
        "l_shipinstruct": chars(_INSTRUCT, 25),
        "l_shipmode": chars(_MODE, 10),
        "l_comment": comment,
    })


def make(rows: int, seed: int) -> dict:
    """``{"host": the generator's seven columns (numpy), "paths": the
    ring's files, "files": their directory's keeper}``, from the seed
    alone. Even a tiny table is written in at least three row groups."""
    import pyarrow as pa
    import pyarrow.parquet as pq

    rows, seed = int(rows), int(seed)
    host = _BASE.host_copy(_BASE.make(rows, seed))
    table = _arrow_table(host, rows, seed)
    files = _Files()
    group = min(ROW_GROUP_ROWS, max(1, -(-rows // 3)))

    def write(k: int) -> str:
        shift = (k * (rows // RING)) % rows   # np.roll(column, shift)
        rolled = table if not shift else pa.concat_tables(
            [table.slice(rows - shift), table.slice(0, rows - shift)])
        path = os.path.join(files.dir, f"lineitem-{k}.snappy.parquet")
        pq.write_table(rolled, path, row_group_size=group,
                       compression="snappy", store_decimal_as_integer=True)
        return path

    with concurrent.futures.ThreadPoolExecutor(RING) as pool:
        paths = list(pool.map(write, range(RING)))
    return {"host": host, "paths": paths, "files": files}


def host_copy(made: dict) -> dict:
    """The generator's seven columns, for the reference: never decoded
    from the files."""
    return made["host"]


def to_table(path: str):
    """What a request binds: one task's split of the file at ``path`` (all
    of it: ``spark.sql.files.maxPartitionBytes`` is over the file's size)
    and the read schema, the seven columns q1 reads, by name."""
    from spark_rapids_jni_tpu.parquet.split import ParquetSplit

    return ParquetSplit(path, READ, 0, os.path.getsize(path))
