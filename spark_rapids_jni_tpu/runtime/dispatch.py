"""Shape-bucketed dispatch & executable cache for the device op path.

The reference launches per-shape CUDA kernels, so a new batch size costs a
kernel *launch*; under XLA a new batch size costs a *retrace and recompile*
— orders of magnitude more. This layer closes that gap the way TPU serving
stacks do (pad ragged batches to a small set of canonical shapes): the
leading row dimension of every device-op input is padded up to a bucket
from a geometric schedule, an explicit ``row_valid`` mask (the ``n_valid``
scalar in vector form) keeps padded tail rows out of results and
reductions, and the compiled executable is memoized under
``(op, statics digest, leaf shapes/dtypes/shardings, backend)`` so every
batch size inside a bucket reuses one executable. The pad is itself one
cached executable a call (``_pad_groups``, device module ``jit_pad``: one
host call whatever the number of columns), keyed on the rows it pads and
not on the op, one an exact row count (a copy: a fraction of a second to
compile); ``dispatch.pad.jitted`` / ``.passthrough`` count the calls whose
rows went through it and those whose rows all sat on their bucket.

What crosses the boundary between the pad and the op's executable: every
leaf at its bucket's rows in its own dtype, but a 64-bit INTEGER leaf of a
group off its bucket (a ``decimal64`` / ``bigint`` / timestamp Column's
data, a bare ``int64`` / ``uint64`` array, any trailing shape) as two
``uint32`` planes, low word and high word (``_Words``). XLA's TPU compiler
computes 64-bit integers as pairs of 32-bit words and converts an int64
buffer at an executable's boundary (``X64SplitLow`` / ``X64SplitHigh`` on
the way in, each a pass over the buffer, ``X64Combine`` on the way out);
words that leave the pad as words cost it no combine, and the executable
``call`` compiles assembles them as its first operation (``(hi << 32) |
lo``, which the compiler folds into the consumers: nothing is written), so
no int64 buffer stands between the two. ``float64`` is left alone (the
chip holds it as a float32 pair and cannot bitcast it), a group ON its
bucket is handed on as the caller's own buffers, and the inline fallbacks
see the caller's arrays: the op's ``fn`` gets the same pytree either way.
``dispatch.pad.word_leaves`` counts the leaves handed on as words.

Compilation is explicit — ``jax.jit(fn).lower(args).compile()`` — rather
than delegated to jit's internal cache, so compiles and hits are exact,
countable events (telemetry counters ``dispatch.compile`` /
``dispatch.hit``; ``dispatch.padded_waste_bytes`` accounts the padding
tax and ``dispatch.padded_copy_bytes`` the bytes of the padded copy
itself; ``dispatch.compile_ms`` is the wall time spent lowering and
compiling). It happens in one place, ``_lower_and_compile``, whose span
``dispatch.compile`` says where JAX spent the seconds (tracing, lowering,
XLA's compile or the persistent cache's load: ``jax.monitoring``), what
the executable needs of the HBM (``memory_analysis()``) and why a compile
failed; the facts stay beside the executable in the cache, and every
``dispatch.execute`` span repeats ``need_bytes`` / ``temp_bytes``. JAX's
persistent compilation cache makes a second process
start warm: it lives where ``JAX_COMPILATION_CACHE_DIR`` says, else at
the fixed path ``utils/config.cache_dir()`` names (set once at package
import), with JAX's own persistence thresholds.

Fail-safe posture: anything this layer cannot bucket or compile — tracer
inputs (the op is already inside a caller's trace), Arrow-layout strings,
nested columns, zero-row batches, lowering errors, a pad that fails —
falls back to calling the op's implementation directly, with the reason
counted. Dispatch must never change what an op computes, only how often
XLA compiles it.

Config knobs (utils/config.py): ``dispatch.enabled``,
``dispatch.bucket_base``, ``dispatch.max_waste_frac``.
"""

from __future__ import annotations

import math
import threading
import time
import warnings
from functools import wraps
from typing import Any, Callable, Iterator, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from spark_rapids_jni_tpu.columnar import Column, Table
from spark_rapids_jni_tpu.runtime import faults, resilience
from spark_rapids_jni_tpu.telemetry import spans
from spark_rapids_jni_tpu.telemetry.events import enabled as _telemetry_on
from spark_rapids_jni_tpu.telemetry.registry import REGISTRY
from spark_rapids_jni_tpu.types import TypeId
from spark_rapids_jni_tpu.utils.config import get_option

__all__ = [
    "Unbucketable",
    "bucket_config",
    "bucket_for",
    "quantize_capacity",
    "call",
    "compiled",
    "rowwise",
    "sharded_call",
    "pad_sharded",
    "mesh_fingerprint",
    "clear",
]

_lock = threading.RLock()
_EXEC_CACHE: dict = {}
# key -> threading.Event: a first-compile currently in flight. Concurrent
# callers of the same key park on the event and reuse the leader's
# executable instead of compiling it N times (single-flight).
_INFLIGHT: dict = {}


class Unbucketable(Exception):
    """An input the bucketing pad cannot represent (Arrow-layout string,
    nested column, non-array leaf, mismatched leading dimension)."""


# ---------------------------------------------------------------------------
# bucket schedule
# ---------------------------------------------------------------------------


def bucket_config() -> tuple[bool, int, float]:
    """(enabled, bucket_base, max_waste_frac) — read per call, never baked
    into a trace. Callers that DO consume these at trace time (the shuffle
    capacity quantization) must carry this tuple in their dispatch key;
    ``sharded_call`` does so automatically."""
    return (
        bool(get_option("dispatch.enabled")),
        max(1, int(get_option("dispatch.bucket_base"))),
        max(0.0, float(get_option("dispatch.max_waste_frac"))),
    )


def bucket_for(n: int) -> int:
    """Smallest bucket >= n. Buckets are multiples of ``bucket_base``
    growing geometrically by ``min(1 + max_waste_frac, 2)`` — waste_frac
    1.0 gives power-of-two-style buckets (at most ~50% padded rows),
    0.0 degenerates to linear base-multiple rounding."""
    _, base, waste = bucket_config()
    n = max(int(n), 1)
    if n <= base:
        return base
    growth = min(1.0 + waste, 2.0)
    if growth <= 1.0:
        return ((n + base - 1) // base) * base
    b = base
    while b < n:
        nxt = ((int(b * growth) + base - 1) // base) * base
        b = max(nxt, b + base)
    return b


def quantize_capacity(capacity: int) -> int:
    """Bucket-quantize a derived output capacity (e.g. the shuffle's
    per-device slot count) so nearby batch sizes share one executable.
    Growing a capacity is always safe — extra slots are row_valid=False
    padding. Identity when dispatch is disabled."""
    enabled, _, _ = bucket_config()
    if not enabled:
        return int(capacity)
    return bucket_for(int(capacity))


# ---------------------------------------------------------------------------
# pytree pad / slice
# ---------------------------------------------------------------------------


def _is_array(x: Any) -> bool:
    return isinstance(x, (jax.Array, np.ndarray))


def _has_tracer(tree: Any) -> bool:
    return any(
        isinstance(leaf, jax.core.Tracer)
        for leaf in jax.tree_util.tree_leaves(tree)
    )


class _PadStats:
    __slots__ = ("padded_bytes", "total_bytes", "copied_bytes")

    def __init__(self) -> None:
        self.padded_bytes = 0
        self.total_bytes = 0
        # bytes of the leaves that really were copied into a bucket-sized
        # buffer (a leaf already on its bucket boundary is passed as it is)
        self.copied_bytes = 0


def _row_bytes(x: Any, n: int) -> int:
    """Bytes a row of one data leaf of an ``n``-row group."""
    if not _is_array(x):
        raise Unbucketable(f"non-array leaf {type(x).__name__}")
    if x.ndim < 1 or x.shape[0] != n:
        raise Unbucketable(
            f"leading dim {x.shape} != row count {n}")
    return int(np.dtype(x.dtype).itemsize) * int(
        math.prod(x.shape[1:]) if x.ndim > 1 else 1)


def _zero_tail(x: jax.Array, B: int) -> jax.Array:
    """``x`` at the head of ``B`` zeroed rows. Written into zeros rather
    than concatenated with them: alone in a jit the least device time of
    the forms measured (``PERF.md`` section 6, PR 35)."""
    return jax.lax.dynamic_update_slice(
        jnp.zeros((B,) + tuple(x.shape[1:]), x.dtype), x, (0,) * x.ndim)


class _Words:
    """A 64-bit integer array as the two ``uint32`` planes the chip computes
    in: ``lo`` the low words, ``hi`` the high words, ``dtype`` the array's
    own (``int64`` / ``uint64``). A pytree node, so a Column holds one in
    place of its ``data`` from the pad to the op's executable; ``shape`` and
    ``ndim`` are the array's, which is all a Column's constructor asks."""

    __slots__ = ("lo", "hi", "dtype")

    def __init__(self, lo: Any, hi: Any, dtype: Any) -> None:
        self.lo, self.hi, self.dtype = lo, hi, np.dtype(dtype)

    shape = property(lambda self: self.lo.shape)
    ndim = property(lambda self: self.lo.ndim)

    def join(self) -> jax.Array:
        """The array itself. XLA's TPU compiler folds this into whatever
        reads it: no pass, no buffer."""
        return ((self.hi.astype(self.dtype) << 32)
                | self.lo.astype(self.dtype))


jax.tree_util.register_pytree_node(
    _Words, lambda w: ((w.lo, w.hi), w.dtype),
    lambda dtype, planes: _Words(*planes, dtype))


def _is_words(x: Any) -> bool:
    return isinstance(x, _Words)


def _has_words(x: Any) -> bool:
    """Whether the pad hands leaf ``x`` of a group off its bucket on as
    words: the 64-bit integers (never ``float64``, which the chip holds as
    a float32 pair it cannot bitcast)."""
    dtype = np.dtype(x.dtype)
    return dtype.kind in "iu" and dtype.itemsize == 8


def _join_words(tree: Any) -> Any:
    """``tree`` with every ``_Words`` of it assembled."""
    return jax.tree_util.tree_map(
        lambda x: x.join() if _is_words(x) else x, tree, is_leaf=_is_words)


def _pad_array(x: Any, n: int, B: int, acc: _PadStats,
               words: bool = False) -> Any:
    row_bytes = _row_bytes(x, n)
    acc.padded_bytes += (B - n) * row_bytes
    acc.total_bytes += B * row_bytes
    if B == n:
        return jnp.asarray(x)
    acc.copied_bytes += B * row_bytes
    x = jnp.asarray(x)
    if words and _has_words(x):
        # an arithmetic shift for int64, a logical one for uint64: the
        # narrowing keeps the high word's bits either way
        return _Words(_zero_tail(x.astype(jnp.uint32), B),
                      _zero_tail((x >> 32).astype(jnp.uint32), B), x.dtype)
    return _zero_tail(x, B)


def _check_column(col: Column, n: int) -> None:
    if col.children is not None or col.dtype.type_id in (
            TypeId.LIST, TypeId.STRUCT):
        raise Unbucketable("nested (LIST/STRUCT) column")
    if col.dtype.is_string and not col.is_padded_string:
        raise Unbucketable("arrow-layout string column")
    if col.size != n:
        raise Unbucketable(f"column size {col.size} != row count {n}")


def _pad_column(col: Column, n: int, B: int, acc: _PadStats,
                fills: Optional[Iterator] = None,
                words: bool = False) -> Column:
    _check_column(col, n)
    data = _pad_array(col.data, n, B, acc, words)
    if fills is not None:
        # on its bucket: the validity it has, or a ready all-true mask
        validity = col.validity if col.validity is not None else next(fills)
    else:
        # padded tail rows are NULL rows: every op's null semantics already
        # neutralize them (sums add 0, min/max see sentinels, sorts rank
        # them by the row_valid key, counts skip them)
        validity = _zero_tail(col.valid_mask(), B)
    chars = None
    if col.chars is not None:
        chars = _pad_array(col.chars, n, B, acc)
    return Column(col.dtype, data, validity, chars=chars)


def _pad_tree(x: Any, n: int, B: int, acc: _PadStats,
              fills: Optional[Iterator] = None, words: bool = False) -> Any:
    """``x`` with every leaf padded from ``n`` to ``B`` rows. ``fills`` (only
    with ``B == n``, on the host): every leaf is handed on as it is and a
    Column without a validity takes the next mask of ``fills``. ``words``
    (only ``_pad_groups`` sets it, only with ``B != n``): a 64-bit integer
    leaf comes out as its two padded ``uint32`` planes, a ``_Words``."""
    if x is None:
        return None
    if isinstance(x, Column):
        return _pad_column(x, n, B, acc, fills, words)
    if isinstance(x, Table):
        return Table([_pad_column(c, n, B, acc, fills, words)
                      for c in x.columns])
    if _is_array(x):
        return _pad_array(x, n, B, acc, words)
    if isinstance(x, tuple):
        vals = [_pad_tree(v, n, B, acc, fills, words) for v in x]
        return type(x)(*vals) if hasattr(x, "_fields") else tuple(vals)
    if isinstance(x, list):
        return [_pad_tree(v, n, B, acc, fills, words) for v in x]
    if isinstance(x, dict):
        return {k: _pad_tree(v, n, B, acc, fills, words)
                for k, v in x.items()}
    raise Unbucketable(f"non-array leaf {type(x).__name__}")


def _survey(group: Any, n: int) -> tuple:
    """What the host reads off one ``n``-row group before the pad runs:
    ``(bytes a row of its data leaves, Columns without a validity, data
    leaves that are 64-bit integers)``. The data leaves are what
    ``_pad_array`` copies (a Column's data and chars, a bare array; no
    mask). Raises ``Unbucketable`` for what ``_pad_tree`` refuses, so such a
    group costs neither a trace nor a compile."""
    row_bytes = bare = wide = 0
    for x in jax.tree_util.tree_leaves(
            group, is_leaf=lambda v: isinstance(v, Column)):
        if isinstance(x, Column):
            _check_column(x, n)
            bare += x.validity is None
            if x.chars is not None:
                row_bytes += _row_bytes(x.chars, n)
            x = x.data
        row_bytes += _row_bytes(x, n)
        wide += _has_words(x)
    return row_bytes, bare, wide


def _slice_column(col: Column, n: int, B: int) -> Column:
    data = col.data
    if _is_array(data) and data.ndim >= 1 and data.shape[0] == B:
        data = data[:n]
    validity = col.validity
    if _is_array(validity) and validity.shape[0] == B:
        validity = validity[:n]
    chars = col.chars
    if _is_array(chars) and chars.ndim >= 1 and chars.shape[0] == B:
        chars = chars[:n]
    return Column(col.dtype, data, validity, chars=chars,
                  children=col.children)


def _slice_tree(x: Any, n: int, B: int) -> Any:
    if B == n or x is None:
        return x
    if isinstance(x, Column):
        return _slice_column(x, n, B)
    if isinstance(x, Table):
        return Table([_slice_column(c, n, B) for c in x.columns])
    if _is_array(x):
        if x.ndim >= 1 and x.shape[0] == B:
            return x[:n]
        return x
    if isinstance(x, tuple):
        vals = [_slice_tree(v, n, B) for v in x]
        return type(x)(*vals) if hasattr(x, "_fields") else tuple(vals)
    if isinstance(x, list):
        return [_slice_tree(v, n, B) for v in x]
    if isinstance(x, dict):
        return {k: _slice_tree(v, n, B) for k, v in x.items()}
    return x


def _group_rows(group: Any) -> int:
    """The row count of one bucketing group (a pytree whose array leaves
    all share the leading row dimension)."""
    if isinstance(group, Table):
        return group.num_rows
    if isinstance(group, Column):
        return group.size
    for leaf in jax.tree_util.tree_leaves(group):
        if isinstance(leaf, Column):
            return leaf.size
        if _is_array(leaf):
            if leaf.ndim < 1:
                raise Unbucketable("scalar leaf has no row dimension")
            return int(leaf.shape[0])
    raise Unbucketable("group has no array leaves")


def _signature(tree: Any) -> tuple:
    """Hashable aval digest: treedef (carries Column dtypes as aux data —
    the reference's (typeId, scale) JNI marshaling) + per-leaf shape,
    dtype, and sharding."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    sig = []
    for leaf in leaves:
        shard = getattr(leaf, "sharding", None)
        sig.append((
            tuple(leaf.shape) if hasattr(leaf, "shape") else (),
            str(getattr(leaf, "dtype", type(leaf).__name__)),
            repr(shard) if shard is not None else "",
        ))
    return (treedef, tuple(sig))


# ---------------------------------------------------------------------------
# executable cache
# ---------------------------------------------------------------------------


# What JAX says of a compile while it runs (``jax.monitoring``, delivered
# on the compiling thread), and what of it goes where: a duration's field
# of the open compile, or the persistent cache's answer.
_XLA_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_load",
    "/jax/compilation_cache/cache_hits": "hit",
    "/jax/compilation_cache/cache_misses": "miss",
}
_compiling = threading.local()   # .open: this thread's _OpenCompile, or None
_listening = False               # the listener pair is registered once a process


class _OpenCompile:
    """What JAX has reported of the compile this thread has open."""

    __slots__ = ("staged", "backend", "cache_load", "persistent")

    def __init__(self) -> None:
        # tracing and lowering, outermost first: [start, seconds, field].
        # jit traces nest (``jnp.sort`` inside a region reports its own
        # trace inside the region's), and a nested one is its parent's time
        self.staged: list = []
        self.backend = self.cache_load = 0.0
        self.persistent: Optional[str] = None

    def add(self, field: str, secs: float) -> None:
        if field == "backend":
            self.backend += secs
        elif field == "cache_load":
            self.cache_load += secs
        else:
            # an event arrives as its interval closes: one that began
            # before those already held contains them
            start = time.perf_counter() - secs
            while self.staged and self.staged[-1][0] >= start:
                self.staged.pop()
            self.staged.append((start, secs, field))

    def seconds(self, field: str) -> float:
        return sum((secs for _, secs, f in self.staged if f == field), 0.0)


def _on_xla_duration(event: str, secs: float, **_: Any) -> None:
    open_ = getattr(_compiling, "open", None)
    if open_ is not None and event in _XLA_EVENTS:
        open_.add(_XLA_EVENTS[event], secs)


def _on_xla_event(event: str, **_: Any) -> None:
    open_ = getattr(_compiling, "open", None)
    if open_ is not None and event in _XLA_EVENTS:
        open_.persistent = _XLA_EVENTS[event]   # "hit" or "miss"


def _listen() -> None:
    """Register the listener pair, at the first compile of the process
    (never at import). A JAX without ``jax.monitoring`` is counted once and
    every compile goes on unobserved."""
    global _listening
    with _lock:
        if _listening:
            return
        _listening = True
        try:
            from jax import monitoring

            monitoring.register_event_duration_secs_listener(_on_xla_duration)
            monitoring.register_event_listener(_on_xla_event)
        except Exception:
            REGISTRY.counter("dispatch.xla.no_listener").inc()


def _memory_facts(executable: Any) -> dict:
    """What the executable needs of one chip's HBM, by XLA's own buffer
    assignment (``memory_analysis()``; over a mesh: one chip's share):
    ``need_bytes`` is arguments + outputs + temporaries less what the
    outputs alias of the arguments. Empty, and counted, where the backend
    gives none."""
    try:
        m = executable.memory_analysis()
        facts = {
            "argument_bytes": int(m.argument_size_in_bytes),
            "output_bytes": int(m.output_size_in_bytes),
            "alias_bytes": int(m.alias_size_in_bytes),
            "temp_bytes": int(m.temp_size_in_bytes),
            "code_bytes": int(m.generated_code_size_in_bytes),
        }
    except Exception:
        REGISTRY.counter("dispatch.xla.no_memory_analysis").inc()
        return {}
    facts["need_bytes"] = (facts["argument_bytes"] + facts["output_bytes"]
                           + facts["temp_bytes"] - facts["alias_bytes"])
    peak = int(getattr(m, "peak_memory_in_bytes", 0) or 0)
    if peak > 0:
        facts["peak_bytes"] = peak
    return facts


def _lower_and_compile(op: str, jitted: Any, *args: Any) -> tuple:
    """The one place an executable is made: ``(executable, its memory
    facts)`` of ``jitted`` for exactly ``args``, under the span
    ``dispatch.compile``. Wall time goes into ``dispatch.compile_ms`` and
    ``dispatch.compile_ms.<op>`` (a persistent-cache hit shows there as a
    short compile). With telemetry on, the span also says where JAX spent
    the seconds (``trace_s``, ``lower_s``, ``backend_s``, of which
    ``cache_load_s`` reading the persistent cache, and ``persistent``:
    ``hit`` / ``miss`` / ``unasked``), what the executable needs
    (:func:`_memory_facts`) and, where lowering or compiling raised, why
    (``error``, ``error_message``); the seconds also go to the process's
    ``dispatch.xla.*`` counters, which outlive the ring. Raises what
    lowering or compiling raises."""
    _listen()
    open_ = _OpenCompile() if _telemetry_on() else None
    t0 = time.perf_counter()
    with spans.child("dispatch.compile", op=op) as sp:
        _compiling.open = open_
        try:
            executable = jitted.lower(*args).compile()
        except BaseException as e:
            sp.annotate(error=type(e).__name__, error_message=str(e)[:200])
            raise
        finally:
            _compiling.open = None
            ms = (time.perf_counter() - t0) * 1e3
            REGISTRY.histogram("dispatch.compile_ms").observe(ms)
            REGISTRY.histogram(f"dispatch.compile_ms.{op}").observe(ms)
            if open_ is not None:
                _account_compile(open_, sp)
        facts = _memory_facts(executable)
        sp.annotate(**facts)
    return executable, facts


def _account_compile(open_: _OpenCompile, sp: Any) -> None:
    """One closed compile's seconds onto its span and into the process's
    counters."""
    trace, lower = open_.seconds("trace"), open_.seconds("lower")
    if not (open_.staged or open_.backend or open_.persistent):
        REGISTRY.counter("dispatch.xla.unobserved").inc()
        return   # this JAX delivered none of _XLA_EVENTS
    sp.annotate(trace_s=trace, lower_s=lower, backend_s=open_.backend,
                cache_load_s=open_.cache_load,
                persistent=open_.persistent or "unasked")
    REGISTRY.counter("dispatch.xla.trace_lower_ns").inc(
        int((trace + lower) * 1e9))
    REGISTRY.counter("dispatch.xla.backend_ns").inc(int(open_.backend * 1e9))
    REGISTRY.counter("dispatch.xla.cache_load_ns").inc(
        int(open_.cache_load * 1e9))
    if open_.persistent:
        REGISTRY.counter(f"dispatch.xla.persistent_{open_.persistent}").inc()


def _region_need(facts: dict) -> dict:
    """What of an executable's facts its ``dispatch.execute`` span says."""
    return {k: facts[k] for k in ("need_bytes", "temp_bytes") if k in facts}


def _cache_lookup(key) -> tuple:
    """Single-flight cache lookup: ``(entry, leader_event)``.

    ``entry`` non-None is what :func:`_lower_and_compile` made for the
    key, ``(executable, its memory facts)`` (a hit — possibly
    after waiting out another thread's in-flight compile of the same
    key). ``entry`` None means THIS caller is the compile leader for
    ``key`` and holds ``leader_event``; it MUST finish with
    ``_cache_store(key, entry_or_None, leader_event)`` on every exit
    path, or waiters park forever. A leader that fails (stores None)
    wakes the waiters, and the first to re-loop becomes the new leader —
    a failed compile never wedges the key.
    """
    while True:
        with _lock:
            entry = _EXEC_CACHE.get(key)
            if entry is not None:
                return entry, None
            ev = _INFLIGHT.get(key)
            if ev is None:
                ev = threading.Event()
                _INFLIGHT[key] = ev
                return None, ev
        ev.wait()


def _cache_store(key, entry, ev: threading.Event) -> None:
    """Publish the leader's result (or its failure) and release waiters."""
    with _lock:
        if entry is not None:
            _EXEC_CACHE[key] = entry
        if _INFLIGHT.get(key) is ev:
            del _INFLIGHT[key]
    ev.set()


def _inline(op: str, reason: str, fn: Callable, row_args: tuple,
            aux_args: tuple) -> Any:
    REGISTRY.counter("dispatch.inline").inc()
    REGISTRY.counter(f"dispatch.inline.{reason}").inc()
    return fn(row_args, aux_args, None)


def _pad_groups(row_args: tuple, ns: tuple, buckets: tuple) -> tuple:
    """The bucketed pad of :func:`call`: ``(padded groups, row_valids, bytes
    a row of each group's data leaves, leaves handed on as words)``, the
    first two from ONE cached executable: one host call whatever the number
    of leaves. A group off its bucket goes through it whole (``_pad_tree``,
    traced once) and leaves it with every 64-bit integer leaf as a
    ``_Words``, two bucket-sized ``uint32`` planes and no int64 buffer (the
    executable of :func:`call` assembles them; ``_join_words`` gives the
    tree an eager pad would); every other leaf in its own dtype. A group
    on its bucket stays out of its data path: its leaves are handed on as
    they are, int64 and all (a jit would copy them, a split there would be a
    new pass, and ``donate_rows`` relies on the alias) and the executable
    builds only its masks, an all-true validity for each of its Columns
    without one among them. Keyed on what a pad depends on and nothing
    else: the groups' signature, the row counts, the buckets and the
    backend, not the op, so two ops over one column share it. One
    executable an exact row count: it is a copy and compiles in a fraction
    of a second. Counts ``dispatch.pad.word_leaves``. Raises
    ``Unbucketable`` for what cannot be padded, and what compiling or
    running it raises."""
    surveyed = tuple(_survey(g, n) for g, n in zip(row_args, ns))
    off = tuple(g if B != n else None
                for g, n, B in zip(row_args, ns, buckets))
    bare = tuple(k if B == n else 0
                 for (_, k, _), n, B in zip(surveyed, ns, buckets))

    def pad(groups):   # traced once, by the call that compiles it
        unread = _PadStats()   # call reckons the bytes from the shapes
        padded = tuple(
            None if g is None else _pad_tree(g, n, B, unread, words=True)
            for g, n, B in zip(groups, ns, buckets))
        row_valids = tuple(jnp.arange(B, dtype=jnp.int32) < jnp.int32(n)
                           for n, B in zip(ns, buckets))
        fills = tuple(tuple(jnp.ones((n,), jnp.bool_) for _ in range(k))
                      for n, k in zip(ns, bare))
        return padded, row_valids, fills

    pad.__name__ = pad.__qualname__ = "pad"   # the device module: jit_pad
    executable = compiled("pad", pad, off, statics=(
        ns, buckets, bare, jax.default_backend()))
    padded, row_valids, fills = executable(off)
    padded = list(padded)
    for i, (group, n, B, fill) in enumerate(
            zip(row_args, ns, buckets, fills)):
        if B == n:
            padded[i] = _pad_tree(group, n, n, _PadStats(), iter(fill))
    REGISTRY.counter("dispatch.pad.jitted" if any(
        g is not None for g in off) else "dispatch.pad.passthrough").inc()
    word_leaves = sum(wide for (_, _, wide), n, B in zip(
        surveyed, ns, buckets) if B != n)
    REGISTRY.counter("dispatch.pad.word_leaves").inc(word_leaves)
    return (tuple(padded), row_valids,
            tuple(row for row, _, _ in surveyed), word_leaves)


def _on_words(fn: Callable) -> Callable:
    """``fn`` as :func:`call` compiles it: behind the assembly of the words
    the pad handed on, under ``fn``'s own name (jit names the device module
    after it: ``jit_region_<plan>``). ``fn`` gets the pytree it gets
    inline."""
    @wraps(fn)
    def on_words(row_args, aux_args, row_valids):
        return fn(_join_words(row_args), aux_args, row_valids)

    return on_words


def call(
    op: str,
    fn: Callable,
    row_args: tuple,
    aux_args: tuple = (),
    *,
    statics: tuple = (),
    slice_rows: bool = True,
    bucket_rows: bool = True,
    donate_rows: bool = False,
) -> Any:
    """Dispatch ``fn`` through the bucketed executable cache.

    ``row_args`` is a tuple of bucketing GROUPS: each group is a pytree
    (Columns / Tables / arrays) whose leaves share one leading row
    dimension; each group is padded to its own bucket (a join has two
    groups). ``aux_args`` is a pytree of arrays traced but never padded
    (e.g. a DFA transition table — its shape still keys the cache).
    ``statics`` must capture every non-array value ``fn`` closes over that
    affects the trace (schemas, agg lists, config-derived flags).

    ``fn(row_args, aux_args, row_valids)`` — ``row_valids`` is one
    bool[bucket] mask per group (True = real row), or None on the inline
    path. What the compiled executable takes is not quite what ``fn``
    sees: a 64-bit integer leaf of a group off its bucket arrives as two
    ``uint32`` planes (``_pad_groups``) and is assembled in front of ``fn``
    (``_on_words``: ``(hi << 32) | lo``, folded into its consumers), so
    ``fn`` gets the same Columns, Tables and arrays on every path, and the
    cache keys itself on the planes through ``_signature``.
    ``slice_rows`` trims bucket-sized leading dimensions of the
    output back to group 0's true row count. ``bucket_rows=False`` keeps
    exact shapes (pure executable memoization, no padding) for ops whose
    semantics cannot absorb padded rows.

    ``donate_rows=True`` is the caller's declaration that every
    ``row_args`` buffer is DEAD after this call (an intermediate table it
    owns, a decoded chunk nothing else reads): the executable compiles
    with ``donate_argnums`` on the row param so XLA reuses those buffers
    for outputs instead of double-buffering. The flag keys the cache, so
    donating and non-donating call sites never share an executable; bytes
    handed over are counted under ``dispatch.donated_bytes``. Note that
    when the row count already sits on a bucket boundary the "padded"
    tree aliases the caller's arrays, so the declaration genuinely
    invalidates them — never set this for caller-visible inputs.

    Never raises on its own behalf: every failure mode falls back to
    ``fn(row_args, aux_args, None)`` with the reason counted under
    ``dispatch.inline.<reason>``.
    """
    REGISTRY.counter("dispatch.calls").inc()
    enabled, _, _ = bucket_config()
    if not enabled:
        return _inline(op, "disabled", fn, row_args, aux_args)
    if _has_tracer((row_args, aux_args)):
        return _inline(op, "tracer", fn, row_args, aux_args)
    try:
        ns = tuple(_group_rows(g) for g in row_args)
    except Unbucketable:
        return _inline(op, "unbucketable", fn, row_args, aux_args)
    if any(n == 0 for n in ns):
        return _inline(op, "empty", fn, row_args, aux_args)

    buckets = tuple(bucket_for(n) for n in ns) if bucket_rows else ns
    try:
        with spans.child("dispatch.pad", op=op) as pad_span:
            padded, row_valids, row_bytes, word_leaves = _pad_groups(
                row_args, ns, buckets)
            pad_span.annotate(word_leaves=word_leaves)
    except Unbucketable:
        return _inline(op, "unbucketable", fn, row_args, aux_args)
    except Exception:
        # a pad that does not compile or run: the op still answers
        REGISTRY.counter("dispatch.pad_error").inc()
        return _inline(op, "pad_error", fn, row_args, aux_args)

    key = (op, statics, donate_rows,
           _signature((padded, aux_args, row_valids)),
           jax.default_backend())
    entry, lead_ev = _cache_lookup(key)
    if entry is None:
        def _compile():
            faults.fire("dispatch.compile", 0, op=op)
            jitted = jax.jit(_on_words(fn),
                             donate_argnums=(0,) if donate_rows else ())
            with warnings.catch_warnings():
                # backends without donation support (CPU) warn per
                # donated buffer at lowering; the declaration is still
                # honored where the platform implements it
                warnings.filterwarnings(
                    "ignore", message="Some donated buffers were not usable")
                return _lower_and_compile(
                    op, jitted, padded, aux_args, row_valids)

        # transient device faults retry under the shared policy; genuine
        # compile errors (non-transient) give up on attempt 1 and take the
        # host_fallback ladder rung below — dispatch still never raises
        # on its own behalf
        exc = None
        try:
            entry, exc = resilience.retry_or_none(
                op, _compile, seam="dispatch.compile", rung="host_fallback")
        finally:
            # publish (or publish the failure) on EVERY leader exit path:
            # a waiter parked on this key must never hang
            _cache_store(key, entry, lead_ev)
        if entry is None:
            if exc is not None and not isinstance(exc, Exception):
                raise exc  # KeyboardInterrupt etc: not dispatch's to absorb
            REGISTRY.counter("dispatch.compile_error").inc()
            return _inline(op, "compile_error", fn, row_args, aux_args)
        REGISTRY.counter("dispatch.compile").inc()
        REGISTRY.counter(f"dispatch.compile.{op}").inc()
    else:
        REGISTRY.counter("dispatch.hit").inc()
        REGISTRY.counter(f"dispatch.hit.{op}").inc()
    compiled, facts = entry

    def _execute():
        faults.fire("dispatch.execute", 0, op=op)
        # host-side only: the span closes when the dispatch RETURNS (jax
        # is async); it never forces a device sync. It says what HBM the
        # executable needs, hit or compile (``need_bytes``, ``temp_bytes``)
        with spans.child("dispatch.execute", op=op, **_region_need(facts)):
            return compiled(padded, aux_args, row_valids)

    out, exc = resilience.retry_or_none(
        op, _execute, seam="dispatch.execute", rung="host_fallback")
    if out is None and exc is not None:
        if not isinstance(exc, Exception):
            raise exc
        # aval drift (weak types, sharding changes) — never take the op down
        REGISTRY.counter("dispatch.exec_error").inc()
        return _inline(op, "exec_error", fn, row_args, aux_args)

    # arithmetic on shapes: a leaf on its bucket is handed on, not copied
    sized = tuple(zip(ns, buckets, row_bytes))
    total_bytes = sum(B * row for _, B, row in sized)
    REGISTRY.counter("dispatch.padded_rows").inc(
        sum(B - n for n, B, _ in sized))
    REGISTRY.counter("dispatch.padded_waste_bytes").inc(
        sum((B - n) * row for n, B, row in sized))
    REGISTRY.counter("dispatch.padded_copy_bytes").inc(
        sum(B * row for n, B, row in sized if B != n))
    REGISTRY.counter("dispatch.row_bytes_total").inc(total_bytes)
    if donate_rows:
        REGISTRY.counter("dispatch.donated_bytes").inc(total_bytes)
    if slice_rows:
        out = _slice_tree(out, ns[0], buckets[0])
    return out


def compiled(op: str, fn: Callable, *args: Any,
             statics: tuple = ()) -> Callable:
    """The executable of ``jax.jit(fn)`` for exactly these arguments: no
    bucketing, no padding, no masks, for a function whose result depends
    on every element of its inputs (a content digest). Same cache as
    :func:`call` (single flight; keyed by op, ``statics`` and the
    arguments' shapes, dtypes and shardings; a function over a mesh puts
    the mesh's devices among its statics, which a sharding's ``repr``
    leaves out) and the same counters, so a compile inside a
    measured window shows as ``dispatch.compile`` like any other. Unlike
    :func:`call` it raises what lowering or compiling raises: the caller
    owns the fallback."""
    key = (op, statics, _signature(args))
    entry, lead_ev = _cache_lookup(key)
    if entry is not None:
        REGISTRY.counter("dispatch.hit").inc()
        REGISTRY.counter(f"dispatch.hit.{op}").inc()
        return entry[0]
    try:
        entry = _lower_and_compile(op, jax.jit(fn), *args)
    finally:
        _cache_store(key, entry, lead_ev)
    REGISTRY.counter("dispatch.compile").inc()
    REGISTRY.counter(f"dispatch.compile.{op}").inc()
    return entry[0]


def rowwise(
    op: str,
    fn: Callable,
    group: Any,
    aux_args: tuple = (),
    *,
    statics: tuple = (),
    slice_rows: bool = True,
) -> Any:
    """``call`` for the common single-row-group op."""
    return call(op, fn, (group,), aux_args, statics=statics,
                slice_rows=slice_rows)


def mesh_fingerprint(mesh) -> tuple:
    """Hashable mesh identity for the executable cache: axis layout plus
    the concrete device assignment; a compiled shard_map program is
    specialized to both."""
    return (tuple(mesh.shape.items()),
            tuple(str(d) for d in mesh.devices.flat))


def pad_sharded(op: str, row_args: tuple, mesh, axis: str) -> tuple:
    """The bucketed pad of :func:`call` for row groups whose buffers are
    row-sharded over ``axis`` of ``mesh``: every chip pads ITS rows to the
    bucket of a chip's row count, with its own row-valid mask, in one
    jitted ``shard_map`` that moves no row between chips (an eager
    ``concatenate`` of a sharded buffer with a global tail is a reshard).
    Returns ``(padded groups, row_valids)``, both row-sharded as the input
    is, a chip holding ``bucket`` rows of each. One executable an exact
    shape (it is a copy and compiles in a fraction of a second); the region
    that takes its output is keyed on the bucket, as on one chip. Same
    span and counters as the one-chip pad; the byte counters are summed
    over the chips, and ``dispatch.pad.word_leaves`` moves by 0: a 64-bit
    integer leaf leaves this pad as int64 (the assembly would sit in the
    caller's ``shard_map`` step). Raises ``Unbucketable`` for what cannot be
    padded."""
    from jax.sharding import PartitionSpec as P

    chips = int(mesh.shape[axis])
    ns = tuple(_group_rows(g) for g in row_args)
    if any(n == 0 or n % chips for n in ns):
        raise Unbucketable(f"row counts {ns} do not split over {chips}")
    local = tuple(n // chips for n in ns)
    buckets = tuple(bucket_for(n) for n in local)
    acc = _PadStats()

    def step(groups):   # traced once, by the call that compiles it
        padded = tuple(_pad_tree(g, n, B, acc)
                       for g, n, B in zip(groups, local, buckets))
        return padded, tuple(
            jnp.arange(B, dtype=jnp.int32) < jnp.int32(n)
            for n, B in zip(local, buckets))

    def pad(groups):
        return jax.shard_map(step, mesh=mesh, in_specs=(P(axis),),
                             out_specs=P(axis))(groups)

    pad.__name__ = pad.__qualname__ = "pad_sharded"
    with spans.child("dispatch.pad", op=op, word_leaves=0):
        executable = compiled(
            "pad_sharded", pad, row_args,
            statics=(op, local, buckets, mesh_fingerprint(mesh)))
        stats = _PAD_STATS.setdefault(executable, (
            acc.padded_bytes, acc.copied_bytes, acc.total_bytes))
        out = executable(row_args)
    REGISTRY.counter("dispatch.padded_rows").inc(
        chips * sum(B - n for n, B in zip(local, buckets)))
    REGISTRY.counter("dispatch.padded_waste_bytes").inc(chips * stats[0])
    REGISTRY.counter("dispatch.padded_copy_bytes").inc(chips * stats[1])
    REGISTRY.counter("dispatch.row_bytes_total").inc(chips * stats[2])
    REGISTRY.counter("dispatch.pad.word_leaves").inc(0)
    return out


# a pad_sharded executable -> the bytes one chip pads, copies and holds
# (counted while it was traced; a cached executable traces no more)
_PAD_STATS: dict = {}


def sharded_call(
    op: str,
    build: Callable[[], Callable],
    args: tuple,
    statics: tuple = (),
) -> Any:
    """Executable memoization (no row bucketing) for a shard_map/jit
    boundary: ``build()`` returns the per-call closure (a fresh
    ``jax.shard_map(step, ...)`` wrapper is fine — identity does not key
    the cache, ``(op, statics, signature)`` does). The bucket-schedule
    config rides the key because shuffle capacities consume it at trace
    time. Falls back to a direct call on any lower/compile failure."""
    REGISTRY.counter("dispatch.calls").inc()
    cfg = bucket_config()
    if not cfg[0]:
        REGISTRY.counter("dispatch.inline").inc()
        REGISTRY.counter("dispatch.inline.disabled").inc()
        return build()(*args)
    if _has_tracer(args):
        REGISTRY.counter("dispatch.inline").inc()
        REGISTRY.counter("dispatch.inline.tracer").inc()
        return build()(*args)
    key = (op, ("sharded", cfg) + tuple(statics),
           _signature(args), jax.default_backend())
    entry, lead_ev = _cache_lookup(key)
    if entry is None:
        def _compile():
            faults.fire("dispatch.compile", 0, op=op)
            return _lower_and_compile(op, jax.jit(build()), *args)

        exc = None
        try:
            entry, exc = resilience.retry_or_none(
                op, _compile, seam="dispatch.compile", rung="host_fallback")
        finally:
            _cache_store(key, entry, lead_ev)
        if entry is None:
            if exc is not None and not isinstance(exc, Exception):
                raise exc
            REGISTRY.counter("dispatch.compile_error").inc()
            REGISTRY.counter("dispatch.inline").inc()
            REGISTRY.counter("dispatch.inline.compile_error").inc()
            return build()(*args)
        REGISTRY.counter("dispatch.compile").inc()
        REGISTRY.counter(f"dispatch.compile.{op}").inc()
    else:
        REGISTRY.counter("dispatch.hit").inc()
        REGISTRY.counter(f"dispatch.hit.{op}").inc()
    compiled, facts = entry

    def _execute():
        faults.fire("dispatch.execute", 0, op=op)
        with spans.child("dispatch.execute", op=op, **_region_need(facts)):
            return compiled(*args)

    out, exc = resilience.retry_or_none(
        op, _execute, seam="dispatch.execute", rung="host_fallback")
    if out is None and exc is not None:
        if not isinstance(exc, Exception):
            raise exc
        REGISTRY.counter("dispatch.exec_error").inc()
        REGISTRY.counter("dispatch.inline").inc()
        REGISTRY.counter("dispatch.inline.exec_error").inc()
        return build()(*args)
    return out


# ---------------------------------------------------------------------------
# introspection
# ---------------------------------------------------------------------------


def cache_size() -> int:
    with _lock:
        return len(_EXEC_CACHE)


def clear() -> None:
    """Drop memoized executables (test isolation). Telemetry counters are
    owned by the registry and are NOT reset here."""
    with _lock:
        _EXEC_CACHE.clear()
        _PAD_STATS.clear()
