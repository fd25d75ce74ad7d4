from spark_rapids_jni_tpu.parquet.footer import ParquetFooter
from spark_rapids_jni_tpu.parquet.reader import (
    ParquetChunkedReader,
    read_table,
    row_group_info,
)
from spark_rapids_jni_tpu.parquet.split import ParquetScan, ParquetSplit

__all__ = [
    "ParquetChunkedReader",
    "ParquetFooter",
    "ParquetScan",
    "ParquetSplit",
    "read_table",
    "row_group_info",
]
