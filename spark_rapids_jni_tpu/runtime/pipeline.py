"""Pipelined out-of-core execution: overlap host IO/decode with device compute.

The serial out-of-core executor (outofcore.py) reads, decodes, transfers
and computes one chunk at a time: the device idles through every Parquet/
ORC decode and the host idles through every device step. The reference
stack hides exactly this latency by feeding the GPU from cuDF's chunked
readers asynchronously; this module is the TPU-side equivalent — a
bounded-queue multi-stage executor:

    read/decode      host staging       device transfer     merge
    (thread pool) -> (seq-ordered    -> (+compute, the   -> (consumer,
                      exact-bytes        consumer side)      outofcore
                      admission)                             merge window)

Design points, in contract order:

* **Determinism** — chunks are delivered to the consumer in source order
  regardless of decode completion order, so the partial->merge algebra
  sees exactly the serial sequence and results are bit-identical.
* **Backpressure through the MemoryLimiter** — each chunk's admission
  reserves its EXACT device bytes (decode produces a host-side
  ``HostTableChunk`` first, so the size is known) before the
  host->device copy runs. Admissions happen in sequence order through a
  turnstile: a blocked admission can only ever be waiting on releases
  from already-delivered chunks, never on a later chunk — which is what
  makes a minimum budget degrade to effectively-serial instead of
  deadlocking.
* **Prompt error propagation** — a stage failure surfaces at that
  chunk's position in the output order (the consumer is never handed a
  later chunk first); the generator's cleanup cancels the pump and
  workers, drains the queue, and releases every undelivered reservation
  (the no-phantom-usage contract ``prefetch_chunks`` established).
* **Instrumentation** — ``pipeline.*`` counters/gauges in the telemetry
  registry (chunks, decode/transfer time, producer/consumer stall time,
  queue depth, chunks in flight), ``trace_range`` spans per stage, and
  ``inject_fault`` (tests) to delay or fail any stage by name.

Config: ``pipeline.enabled`` switches the out-of-core executor onto this
path (the serial path remains the reference implementation);
``pipeline.prefetch_depth`` — also via the short env var
``SPARK_RAPIDS_TPU_PIPELINE_PREFETCH`` — bounds how far the producer
runs ahead; ``pipeline.decode_threads`` sizes the decode pool (native
decode releases the GIL, so threads genuinely overlap).
"""

from __future__ import annotations

import os
import queue
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from contextlib import contextmanager
from typing import Callable, Iterable, Iterator, Union

from spark_rapids_jni_tpu import telemetry
from spark_rapids_jni_tpu.runtime import faults
from spark_rapids_jni_tpu.telemetry import spans
from spark_rapids_jni_tpu.runtime.memory import (
    HostTableChunk,
    MemoryLimiter,
    _table_nbytes,
)
from spark_rapids_jni_tpu.utils.config import get_option
from spark_rapids_jni_tpu.utils.log import get_logger
from spark_rapids_jni_tpu.utils.tracing import trace_range

_log = get_logger(__name__)

#: Stage names, in execution order, as seen by ``inject_fault`` hooks.
STAGES = ("decode", "staging", "transfer", "compute", "merge")

#: One pipeline work item: an already-materialized device Table, or a
#: zero-arg thunk producing either a HostTableChunk (preferred: exact
#: admission before the device copy) or a device Table.
ChunkSource = Union[Callable[[], object], object]


def pipeline_enabled() -> bool:
    return bool(get_option("pipeline.enabled"))


def configured_prefetch_depth() -> int:
    """Prefetch depth: the short env var SPARK_RAPIDS_TPU_PIPELINE_PREFETCH
    wins over the ``pipeline.prefetch_depth`` option."""
    env = os.environ.get("SPARK_RAPIDS_TPU_PIPELINE_PREFETCH")
    if env is not None and env.strip():
        return max(int(env), 1)
    return max(int(get_option("pipeline.prefetch_depth")), 1)


def configured_decode_threads() -> int:
    return max(int(get_option("pipeline.decode_threads")), 1)


# ---- shared decode pool -----------------------------------------------------
#
# Concurrent pipelines (and the multi-query serving runtime) would each spin
# a private ThreadPoolExecutor, oversubscribing the host decode threads N
# ways. The shared pool is one process-wide executor every concurrent user
# can borrow; pipeline_chunks accepts it via ``pool=`` and never shuts a
# borrowed pool down.

_shared_pool: ThreadPoolExecutor | None = None
_shared_pool_lock = threading.Lock()


def shared_decode_pool() -> ThreadPoolExecutor:
    """The process-wide host decode/staging pool, created lazily at
    ``pipeline.decode_threads`` workers. Callers submit work but never
    shut it down; ``reset_shared_decode_pool`` exists for test isolation."""
    global _shared_pool
    with _shared_pool_lock:
        if _shared_pool is None:
            _shared_pool = ThreadPoolExecutor(
                max_workers=configured_decode_threads(),
                thread_name_prefix="tpu-pipeline-decode-shared")
        return _shared_pool


def reset_shared_decode_pool() -> None:
    """Shut down and drop the shared pool (test isolation / re-config)."""
    global _shared_pool
    with _shared_pool_lock:
        pool, _shared_pool = _shared_pool, None
    if pool is not None:
        pool.shutdown(wait=True)


# ---- fault injection (tests) ------------------------------------------------
#
# Pipeline stages now fire through the global runtime/faults.py registry as
# seams "pipeline.<stage>". inject_fault below is kept as a thin DEPRECATED
# alias for existing callers; new code uses faults.inject with a FaultSpec /
# FaultScript (or any injector callable) targeting the "pipeline.*" seams.


@contextmanager
def inject_fault(hook):
    """DEPRECATED alias over :func:`runtime.faults.inject`.

    ``hook(stage, seq)`` is invoked at each stage entry with the bare stage
    name (one of ``STAGES``) and the chunk sequence number; it may sleep
    (injected delay) or raise (injected failure). Only ``pipeline.*`` seam
    firings reach the hook — legacy hooks never see the registry's other
    seams. Prefer ``faults.inject`` with the ``pipeline.<stage>`` seam
    names."""

    def _adapter(seam, seq, ctx):
        if seam.startswith("pipeline."):
            hook(seam[len("pipeline."):], seq)

    with faults.inject(_adapter):
        yield


def _maybe_fault(stage: str, seq: int) -> None:
    try:
        faults.fire("pipeline." + stage, seq)
    except BaseException:
        # legacy counter: tests and the bench assert on it by name
        telemetry.REGISTRY.counter("pipeline.faults_injected").inc()
        raise


class _Cancelled(Exception):
    """Internal: a worker observed the cancel flag mid-stage."""


def _us(seconds: float) -> int:
    return max(int(seconds * 1e6), 0)


def pipeline_chunks(
    sources: Iterable[ChunkSource],
    *,
    limiter: MemoryLimiter | None = None,
    depth: int | None = None,
    decode_threads: int | None = None,
    pool: ThreadPoolExecutor | None = None,
    cancel_token=None,
) -> Iterator:
    """Run chunk sources through the async pipeline; yield device Tables
    in source order.

    ``sources`` iterates work items: zero-arg decode thunks returning a
    ``HostTableChunk`` (the chunked readers' ``chunk_sources()``) or a
    device ``Table``; already-materialized Tables are accepted directly
    for drop-in compatibility with ``prefetch_chunks`` call sites.

    Reservation contract (same as ``prefetch_chunks``): when ``limiter``
    is given the pipeline reserves each chunk before delivering it and
    the CALLER must release ``_table_nbytes(chunk)`` after use. For
    thunks that decode to ``HostTableChunk`` the reservation is exact and
    taken BEFORE the host->device copy — blocking until budget frees, so
    budgets below the overlap window serialize instead of raising. For
    sources that materialize device Tables directly the bytes are already
    resident when their size is learned, so the admission still blocks
    for budget but the residency window is ``depth + decode_threads``
    chunks (the documented ``prefetch_chunks`` posture) — size the budget
    accordingly or use host-staged thunks.

    On error or early close all undelivered reservations are released:
    no hangs, no orphaned reservations.

    ``pool`` lends an external decode executor (e.g.
    ``shared_decode_pool()``, so N concurrent pipelines share one set of
    decode threads instead of oversubscribing the host N ways); a lent
    pool is never shut down here — cleanup waits on this run's own
    futures only.

    ``cancel_token`` (a ``resilience.CancelToken``) makes the run
    cooperatively cancellable: the token is checked inside the decode
    pool before each chunk decodes and at each delivery, and a blocked
    admission wakes when the token fires. Cancellation (or deadline
    expiry) raises ``QueryCancelled`` to the consumer through the same
    cleanup path as any stage failure, so every undelivered reservation
    is released in the generator's ``finally``.
    """
    depth = configured_prefetch_depth() if depth is None \
        else max(int(depth), 1)
    workers = configured_decode_threads() if decode_threads is None \
        else max(int(decode_threads), 1)

    reg = telemetry.REGISTRY
    reg.counter("pipeline.runs").inc()
    cancel = threading.Event()
    # the consumer thread's open span (e.g. the query root or an
    # out-of-core rung): pool threads have empty span stacks, so each
    # chunk span names it as an EXPLICIT parent to stay in the tree
    span_parent = spans.current_span()

    class _either_cancel:
        """Duck-typed Event for reserve_blocking: set when the pipeline's
        internal cancel OR the caller's cancel token fired (cancelled()
        also latches deadline expiry, so a blocked admission wakes on it)."""

        @staticmethod
        def is_set() -> bool:
            return cancel.is_set() or (
                cancel_token is not None and cancel_token.cancelled())

    out_q: "queue.Queue" = queue.Queue(maxsize=depth)
    # admission turnstile: the next sequence number allowed to reserve
    admit = threading.Condition()
    admit_seq = [0]

    def _advance_turnstile(seq: int) -> None:
        with admit:
            admit_seq[0] = seq + 1
            admit.notify_all()

    def _admission(seq: int, nbytes: int) -> bool:
        """Stage 2, host staging: seq-ordered budget admission. Returns
        False when cancelled (caller raises _Cancelled)."""
        t0 = time.perf_counter()
        with admit:
            while admit_seq[0] != seq:
                if _either_cancel.is_set():
                    return False
                admit.wait(0.05)
        ok = True
        try:
            if limiter is not None:
                ok = limiter.reserve_blocking(nbytes, cancel=_either_cancel)
        finally:
            # advance even on failure/cancel so later workers see the
            # cancel flag instead of waiting on a dead turn
            _advance_turnstile(seq)
        reg.counter("pipeline.producer_stall_us").inc(
            _us(time.perf_counter() - t0))
        if ok:
            reg.gauge("pipeline.chunks_in_flight").add(1)
        return ok

    def _work(seq: int, src):
        """Stages 1-3 for one chunk, on a pool thread. Returns
        (device_table, reserved_nbytes); ownership of the reservation
        passes to whoever consumes the future."""
        if cancel.is_set():
            raise _Cancelled()
        if cancel_token is not None:
            # the decode-pool checkpoint: a cancelled/expired query stops
            # before decoding its next chunk, not after
            cancel_token.check("pipeline.decode")
        _maybe_fault("decode", seq)
        # explicit parent: this runs on a pool thread whose own span
        # stack is empty; the stage trace_ranges below nest under the
        # chunk span through this thread's stack
        with spans.child("pipeline.chunk", parent=span_parent, seq=seq):
            t0 = time.perf_counter()
            with trace_range("pipeline.decode"):
                payload = src() if callable(src) else src
            reg.counter("pipeline.decode_us").inc(
                _us(time.perf_counter() - t0))
            host_staged = isinstance(payload, HostTableChunk)
            nb = payload.nbytes if host_staged else _table_nbytes(payload)
            _maybe_fault("staging", seq)
            with trace_range("pipeline.staging"):
                if not _admission(seq, nb):
                    if cancel_token is not None and cancel_token.cancelled():
                        # surface the classified QueryCancelled, not the
                        # internal teardown marker
                        cancel_token.check("pipeline.staging")
                    raise _Cancelled()
            held = nb if limiter is not None else 0
            try:
                _maybe_fault("transfer", seq)
                if host_staged:
                    t1 = time.perf_counter()
                    with trace_range("pipeline.transfer"):
                        table = payload.stage()
                    reg.counter("pipeline.transfer_us").inc(
                        _us(time.perf_counter() - t1))
                    # true-up: the consumer releases _table_nbytes(chunk),
                    # so the held reservation must equal it exactly (it
                    # does by construction; this guards the accounting
                    # invariant)
                    actual = _table_nbytes(table)
                    if limiter is not None and actual != held:
                        if actual > held:
                            limiter.reserve(actual - held)
                        else:
                            limiter.release(held - actual)
                        held = actual
                    nb = actual
                else:
                    table = payload
                return table, nb
            except BaseException:
                if limiter is not None and held:
                    limiter.release(held)
                reg.gauge("pipeline.chunks_in_flight").add(-1)
                raise

    owns_pool = pool is None
    if pool is None:
        pool = ThreadPoolExecutor(
            max_workers=workers, thread_name_prefix="tpu-pipeline-decode")
    submitted: list = []
    pump_exc: list = []

    def _put_cancellable(item) -> bool:
        while not cancel.is_set():
            try:
                out_q.put(item, timeout=0.1)
                return True
            except queue.Full:
                continue
        return False

    def _pump():
        try:
            seq = 0
            for src in sources:
                if _either_cancel.is_set():
                    return
                fut = pool.submit(_work, seq, src)
                submitted.append(fut)
                if not _put_cancellable(("ok", fut)):
                    return
                seq += 1
        except BaseException as exc:  # noqa: BLE001 — re-raised at consumer
            pump_exc.append(exc)
            _put_cancellable(("err", exc))
            return
        _put_cancellable(("end", None))

    pump = threading.Thread(target=_pump, daemon=True,
                            name="tpu-pipeline-pump")
    pump.start()
    delivered = 0
    try:
        while True:
            t0 = time.perf_counter()
            kind, payload = out_q.get()
            if kind == "err":
                raise payload
            if kind == "end":
                break
            if cancel_token is not None:
                # delivery checkpoint: raising BEFORE result() leaves the
                # future's reservation to the finally-drain below
                cancel_token.check("pipeline.deliver")
            table, nb = payload.result()  # raises the worker's exception
            reg.counter("pipeline.consumer_stall_us").inc(
                _us(time.perf_counter() - t0))
            reg.gauge("pipeline.queue_depth").set(out_q.qsize())
            reg.gauge("pipeline.chunks_in_flight").add(-1)
            reg.counter("pipeline.chunks").inc()
            delivered += 1
            yield table
    finally:
        cancel.set()
        pump.join()
        if owns_pool:
            pool.shutdown(wait=True)
        # drain: every submitted-but-undelivered chunk that completed
        # holds a reservation nobody will ever release — release them
        # here (the no-phantom-usage contract). Failed/cancelled workers
        # released their own in _work.
        for fut in submitted[delivered:]:
            try:
                _table, nb = fut.result()
            except BaseException:  # noqa: BLE001 — already propagated
                continue
            reg.gauge("pipeline.chunks_in_flight").add(-1)
            if limiter is not None and nb:
                limiter.release(nb)
