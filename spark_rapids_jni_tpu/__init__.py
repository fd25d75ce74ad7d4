"""spark_rapids_jni_tpu — TPU-native columnar acceleration layer for Apache Spark.

A from-scratch, TPU-first rebuild of the capability surface of
``com.nvidia:spark-rapids-jni`` (the native layer of the RAPIDS Accelerator
for Apache Spark): HBM-resident columnar tables, fully vectorized XLA
programs for the JNI-exposed operators (row<->column transpose, casts,
hashing, bloom filters, a vectorized device JSONPath engine) and the cuDF
operator substrate (sort, groupby-aggregate incl. exact DECIMAL128
variance/covariance and percentiles, exact multi-key join across all six
join types, window functions with rolling frames, LIST operators —
explode/collect/array algebra, concatenate/distinct/compaction,
EXCEPT/INTERSECT, reductions, the elementwise SQL family, string
predicates incl. a device byte-DFA regex engine with capture-tracking
regexp_extract/replace, device Unicode case mapping, string transforms
and split, datetime arithmetic — all incl. STRING and DECIMAL128
columns), pure C++ Parquet/ORC read engines, out-of-core chunked
execution under a memory budget with prefetch overlap, an ICI
all-to-all shuffle transport for multi-chip slices, and a host-staged
zstd DCN transport across slices.

Planner layer (ops/planner.py): declared knowledge is the performance
model — key Domains lower groupbys to the sort-free bounded
masked-reduction pass (125x over sort-based grouping at 16M rows on
hardware), dense clustered primary keys collapse joins to arithmetic +
gather (whole TPC-H queries compile sort-free), dense-id counts put
mid-cardinality groupbys on a blocked one-hot path, and exact rewrites
(q64's count-product join elimination) remove joins outright; every
declaration is runtime-verified (domain_miss / pk_violation) so a lie
re-plans instead of corrupting. Distributed, the bounded plans merge
with m-row collectives instead of row shuffles (zero-shuffle q72,
one-exchange broadcast q3).

Kernels: every operator has one implementation, emitted by XLA (the
measured hot spots are layout transforms, scans, sorts, and gathers the
compiler already fuses; scatter-heavy forms were redesigned scatter-free
after a v5e reading in 2026-07 put scatters 1.6-4x behind the scan
forms). A hand-written kernel replaces the XLA code for the inputs it
takes, chosen by the code, once a chip reading in a cell shows it pays.

Layer map (TPU equivalent of reference SURVEY.md section 1):
  L4' Java API parity sources  -> java/ (build-gated; no JVM in this image)
  L3' native bridge            -> src/native C API via ctypes (JNI-compatible
                                  handle model: objects cross as int64 handles)
  L2' operator layer           -> spark_rapids_jni_tpu.ops
  L1' columnar substrate       -> spark_rapids_jni_tpu.columnar
  L0' device/runtime           -> JAX/XLA on TPU (+ runtime/ arena & handles)

The whole package requires 64-bit dtypes (int64 columns, decimal64, xxhash64)
so jax x64 mode is enabled at import, before any jax array is created.
Opt out with SPARK_RAPIDS_TPU_NO_X64=1 (not recommended).
"""

import os as _os

# INVARIANT (tests/test_import_hygiene.py): importing this package must not
# initialize any jax backend — only config updates. Callers pin the platform
# (utils.platform.force_cpu_platform) AFTER importing us; a module-level
# array/device query anywhere in the import graph would break that.
import jax as _jax

if not _os.environ.get("SPARK_RAPIDS_TPU_NO_X64"):
    _jax.config.update("jax_enable_x64", True)

# The persistent compile cache: where JAX_COMPILATION_CACHE_DIR is set JAX
# has already read it and that is the whole mechanism; otherwise the cache
# goes to the one fixed path (utils/config.cache_dir()).
if not _os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    from spark_rapids_jni_tpu.utils.config import FIXED_CACHE_DIR as _FIXED

    _jax.config.update("jax_compilation_cache_dir", _FIXED)

# An executable's op names are part of what it is: every plan node lowers
# under its own scope (runtime/fusion.node_scopes) and a device trace is read
# by them. JAX's cache key leaves names and locations out by default, so a
# cached executable of a commit with other scopes would be served with its
# stale names; keyed in, a changed lowering compiles again, once.
_jax.config.update("jax_compilation_cache_include_metadata_in_key", True)

from spark_rapids_jni_tpu.types import DType, TypeId  # noqa: E402
from spark_rapids_jni_tpu.columnar import Column, Table  # noqa: E402

__version__ = "0.1.0"

__all__ = ["DType", "TypeId", "Column", "Table", "__version__"]
