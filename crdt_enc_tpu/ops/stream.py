"""Chunked (bounded-memory) folds and the overlapped streaming-compaction
pipeline.

The long-context story (SURVEY.md §2.3): a replica's op log is the
framework's "sequence", and because the fold is associative the log can be
folded blockwise — the same trick ring attention uses for its associative
accumulator.  A 100M-op compaction therefore never materializes the whole
batch on device: fixed-size chunks stream through one compiled fold whose
state planes are **donated** (`jax.jit(donate_argnums=...)`), so XLA reuses
the plane buffers in place and device memory stays at
``one chunk + one set of planes`` regardless of stream length.

**Overlap** (this module's second job): the host-side front end — AEAD
decrypt, native decode, columnarization, H2D staging — dominates a full
single-dispatch compaction by ~40× (BASELINE config #5), so the pipeline
here runs it CONCURRENTLY with the device fold:

* a producer pool (N threads pulling span indices from a shared cursor;
  the decrypt/decode calls are native and release the GIL, so the
  workers genuinely run in parallel) ingests chunks ahead of the fold
  while a sequencer re-emits them to the consumer in STRICT chunk-index
  order — the reduction order, and therefore the folded state bytes,
  are identical at any N (:func:`run_ingest_pipeline`,
  backpressure-bounded so at most ``depth`` chunks of host memory are
  ever live — default ``producers + 1``: one chunk per worker in flight
  plus one being reduced; :func:`stream_producer_count` auto-tunes N
  from the core count with a ``CRDT_STREAM_PRODUCERS`` override);
* the consumer issues the async ``jax.device_put`` of chunk k+1 BEFORE
  dispatching the donated fold of chunk k, so the H2D transfer rides
  under the previous fold's device execution
  (:func:`fold_chunks_overlapped`);
* column staging reuses pre-allocated fixed-shape buffers
  (:class:`ChunkPool`) instead of allocating per chunk — the host buffer
  for chunk k is recycled the moment its transfer lands.

Every stage is timed through ``utils.trace`` spans (``stream.ingest``,
``stream.columnarize``, ``stream.h2d``, ``stream.fold``,
``stream.reduce``, ``stream.d2h``, plus ``stream.producer.wait`` /
``stream.sequence`` and the ``stream_producers`` gauge for the fan-out
stage) with the chunk index as span ``meta``,
so the overlap is auditable from the event log
(``trace.enable_events()``) — tests/test_streaming_pipeline.py pins that
chunk k+1's ingest starts before chunk k's fold completes.

Exactness: chunked ≡ whole-batch under the causal-delivery contract the
core guarantees (per-actor op files apply in version order, core.py
``_read_remote_ops``) — each chunk's stale-dot filter then sees a clock
that only ever rejects true replays.  The per-op host loop is precisely
the chunk-size-1 instance of this fold, so the existing host-equality
tests pin the semantics at both extremes.
"""

from __future__ import annotations

import contextvars
import os
import queue as _queue
import threading
from functools import partial

import jax
import numpy as np

from ..obs import runtime as obs_runtime
from ..utils import trace
from .orset import orset_fold

def stream_producer_count(requested: int = 0) -> int:
    """Resolve the ingest fan-out width (the N in the N-producer
    pipeline): an explicit positive ``requested`` wins, then the
    ``CRDT_STREAM_PRODUCERS`` env override, then an auto-tune from
    ``os.cpu_count()``.

    Auto-tune policy: **one producer per core, minus one core reserved
    for the consumer** (columnarize + fold dispatch), floor 1 — an idle
    32-core host should not be throttled to a fixed handful of lanes.
    Boxes where wide fan-out genuinely thrashes (shared/throttled
    cgroups) pin ``CRDT_STREAM_PRODUCERS`` instead of everyone paying a
    global ceiling."""
    if requested > 0:
        return int(requested)
    env = os.environ.get("CRDT_STREAM_PRODUCERS", "")
    if env.strip():
        try:
            n = int(env)
        except ValueError:
            n = 0
        if n > 0:
            return n
    cpus = os.cpu_count() or 1
    return max(1, cpus - 1)


@partial(
    jax.jit,
    static_argnames=(
        "num_members", "num_replicas", "impl", "small_counters", "retire_rm",
    ),
    donate_argnums=(0, 1, 2),
)
def _fold_donated(
    clock, add, rm, kind, member, actor, counter,
    *, num_members, num_replicas, impl, small_counters, retire_rm=True,
):
    return orset_fold(
        clock, add, rm, kind, member, actor, counter,
        num_members=num_members, num_replicas=num_replicas,
        impl=impl, small_counters=small_counters, retire_rm=retire_rm,
    )


@partial(
    jax.jit,
    static_argnames=("num_members", "num_replicas", "tile_cap", "interpret",
                     "retire_rm"),
    donate_argnums=(0, 1, 2),
)
def _fold_donated_pallas(
    clock, add, rm, kind, member, actor, counter,
    *, num_members, num_replicas, tile_cap, interpret, retire_rm=True,
):
    from .pallas_fold import orset_fold_pallas

    return orset_fold_pallas(
        clock, add, rm, kind, member, actor, counter,
        num_members=num_members, num_replicas=num_replicas,
        tile_cap=tile_cap, interpret=interpret, retire_rm=retire_rm,
    )


class ChunkPool:
    """Pre-allocated fixed-shape op-column staging buffers.

    The pipeline's ONLY host staging memory: ``depth`` buffer sets of
    ``(kind int8, member/actor/counter int32) × chunk_rows``.
    ``acquire()`` blocks while every set is out — together with the
    ingest semaphore this is what bounds live host memory to ``depth``
    chunks however long the stream runs.  Release a set only after its
    H2D transfer has completed (``fold_chunks_overlapped`` does): on the
    CPU backend ``jax.device_put`` may alias the host buffer, and on any
    backend the async copy reads it after the call returns.
    """

    def __init__(self, chunk_rows: int, depth: int = 2):
        if depth < 2:
            # the overlapped consumer holds one buffer in `pending` while
            # the chunk iterator acquires the next — a single-buffer pool
            # would deadlock there (and on aliasing backends the pending
            # buffer cannot be released until its fold completes)
            raise ValueError(f"ChunkPool needs depth >= 2, got {depth}")
        self.chunk_rows = chunk_rows
        self.depth = depth
        self._free: _queue.Queue = _queue.Queue()
        for _ in range(depth):
            self._free.put((
                np.zeros(chunk_rows, np.int8),
                np.zeros(chunk_rows, np.int32),
                np.zeros(chunk_rows, np.int32),
                np.zeros(chunk_rows, np.int32),
            ))

    def acquire(self) -> tuple:
        return self._free.get()

    def release(self, bufs: tuple) -> None:
        self._free.put(bufs)


def columnarize_into(
    bufs, kind, member, actor, counter, lo: int, hi: int, num_replicas: int
):
    """Copy rows ``[lo:hi)`` of the flat columns into a pool buffer set,
    sentinel-padding the tail (``actor == num_replicas`` rows, which every
    kernel masks out).  Returns ``bufs``."""
    k, m, a, c = bufs
    n = hi - lo
    np.copyto(k[:n], kind[lo:hi], casting="unsafe")
    np.copyto(m[:n], member[lo:hi], casting="unsafe")
    np.copyto(a[:n], actor[lo:hi], casting="unsafe")
    np.copyto(c[:n], counter[lo:hi], casting="unsafe")
    if n < len(k):
        k[n:] = 0
        m[n:] = 0
        a[n:] = num_replicas
        c[n:] = 0
    return bufs


def iter_orset_chunks(
    kind, member, actor, counter, chunk_rows: int, num_replicas: int,
    pool: ChunkPool | None = None,
):
    """Slice flat op columns into fixed-shape chunks (the tail is padded
    with ``actor == num_replicas`` sentinel rows, which every kernel
    masks out) — one shape ⇒ one compilation for the whole stream.

    With a ``pool`` the chunks are columnarized into its pre-allocated
    buffers instead of fresh arrays; the consumer MUST release each
    buffer set back (``fold_chunks_overlapped(..., pool=pool)`` does)
    and ``pool.chunk_rows`` must equal ``chunk_rows``."""
    n = len(kind)
    if pool is not None:
        assert pool.chunk_rows == chunk_rows, "pool shape mismatch"
        kind = np.asarray(kind)
        member = np.asarray(member)
        actor = np.asarray(actor)
        counter = np.asarray(counter)
        for lo in range(0, n, chunk_rows):
            hi = min(lo + chunk_rows, n)
            with trace.span("stream.columnarize", meta=lo // chunk_rows):
                bufs = columnarize_into(
                    pool.acquire(), kind, member, actor, counter,
                    lo, hi, num_replicas,
                )
            yield bufs
        return
    for lo in range(0, n, chunk_rows):
        hi = min(lo + chunk_rows, n)
        pad = chunk_rows - (hi - lo)
        k = np.asarray(kind[lo:hi], np.int8)
        m = np.asarray(member[lo:hi], np.int32)
        a = np.asarray(actor[lo:hi], np.int32)
        c = np.asarray(counter[lo:hi], np.int32)
        if pad:
            k = np.concatenate([k, np.zeros(pad, np.int8)])
            m = np.concatenate([m, np.zeros(pad, np.int32)])
            a = np.concatenate([a, np.full(pad, num_replicas, np.int32)])
            c = np.concatenate([c, np.zeros(pad, np.int32)])
        yield k, m, a, c


def fold_chunks_overlapped(planes, chunks, fold_step, *, pool=None, put=None):
    """The overlapped consumer loop: fold an iterable of host column
    chunks into device ``planes`` with one-chunk H2D lookahead.

    Per cycle: the async ``jax.device_put`` of chunk k+1 is issued FIRST,
    then the donated ``fold_step(planes, dev_chunk_k)`` is dispatched
    (async), then the loop blocks on chunk k+1's transfer — which
    therefore rides under fold k's device execution — and recycles the
    host buffer to ``pool``.  ``fold_step`` must donate the planes and
    may be the jitted folds above or a test double.  ``put`` overrides
    the per-array transfer (default ``jax.device_put``) — the sharded
    streaming branch passes a ``NamedSharding``-targeted put so chunk
    k+1's rows land dp-sharded across the mesh, still under chunk k's
    fold.

    Returns the final device planes (NOT blocked: callers overlap their
    own epilogue, or block + pull under a ``stream.d2h`` span via
    :func:`planes_to_host`).

    Buffer recycling: on accelerators the H2D copy is real, so chunk k's
    staging buffer recycles as soon as its transfer lands (which happens
    under fold k-1's execution).  On the CPU backend ``jax.device_put``
    may ALIAS the host buffer zero-copy for the array's whole lifetime —
    there the buffer is held until the fold that consumes it completes
    (no overlap lost: host and "device" are the same silicon)."""
    if put is None:
        put = jax.device_put
    aliasing = pool is not None and jax.default_backend() == "cpu"
    pending = None  # device-resident chunk awaiting its fold dispatch
    pending_host = None  # its staging buffers (aliasing backends only)
    k = 0
    for host_chunk in chunks:
        with trace.span("stream.h2d", meta=k):
            trace.add(
                "h2d_bytes",
                sum(getattr(x, "nbytes", 0) for x in host_chunk),
            )
            dev_chunk = tuple(put(x) for x in host_chunk)
        if pending is not None:
            with trace.span("stream.fold", meta=k - 1):
                planes = fold_step(planes, pending)
            if aliasing:
                # fold k-1 has fully consumed its (possibly aliased)
                # staging buffers once its output is materialized
                jax.block_until_ready(planes)
                pool.release(pending_host)
        if pool is not None and not aliasing:
            # block on THIS chunk's transfer (it runs under fold k-1),
            # then the staging buffer is safely reusable
            jax.block_until_ready(dev_chunk)
            pool.release(host_chunk)
        pending = dev_chunk
        pending_host = host_chunk
        k += 1
    if pending is not None:
        with trace.span("stream.fold", meta=k - 1):
            planes = fold_step(planes, pending)
        if aliasing:
            jax.block_until_ready(planes)
            pool.release(pending_host)
    # fold boundary: the bounded-device-memory claim (one chunk + donated
    # planes), observable — a no-op on backends without allocator stats
    obs_runtime.sample_device_memory()
    return planes


def planes_to_host(planes):
    """Block on the in-flight folds and pull the planes to host, under
    the pipeline's ``stream.d2h`` span."""
    with trace.span("stream.d2h"):
        jax.block_until_ready(planes)
        return tuple(np.asarray(x) for x in planes)


def orset_fold_stream(
    clock0,
    add0,
    rm0,
    chunks,
    *,
    num_members: int,
    num_replicas: int,
    impl: str = "fused",
    small_counters: bool = False,
    tile_cap: int | None = None,
    h2d_lookahead: bool = True,
    pool: ChunkPool | None = None,
    interpret: bool = False,
):
    """Fold an iterable of fixed-shape op chunks into the state planes.

    ``chunks`` yields ``(kind, member, actor, counter)`` tuples of one
    common row count (see :func:`iter_orset_chunks`).  Returns the folded
    ``(clock, add, rm)`` device arrays.  The planes are donated between
    chunks — do not reuse the input arrays after calling.

    ``h2d_lookahead`` (default on) runs the overlapped consumer loop:
    chunk k+1's transfer is issued while chunk k's fold is in flight
    (:func:`fold_chunks_overlapped`); pass ``pool`` when the chunk
    iterator stages into a :class:`ChunkPool` so buffers recycle.

    ``impl="pallas"`` runs each chunk through the MXU fold
    (ops/pallas_fold.py); pass ``tile_cap`` computed over the WHOLE
    member column (``fold_cap``) so every chunk compiles once — a
    per-chunk cap is bounded by the global one.  ``interpret`` runs that
    kernel in the Pallas interpreter — an explicit test-only choice,
    never derived from the backend.
    """
    clock0 = np.asarray(clock0, np.int32)
    add0 = np.asarray(add0, np.int32)
    rm0 = np.asarray(rm0, np.int32)
    trace.add("h2d_bytes", clock0.nbytes + add0.nbytes + rm0.nbytes)
    clock = jax.device_put(clock0)
    add = jax.device_put(add0)
    rm = jax.device_put(rm0)
    if impl == "pallas":
        if tile_cap is None:
            # a per-chunk fold_cap here would recompile the donated fold
            # for every distinct cap — the caller computes ONE cap over
            # the whole member column (which bounds every chunk's)
            raise ValueError(
                "impl='pallas' requires tile_cap (fold_cap over the whole "
                "member column)"
            )
        def fold_step(planes, chunk):
            return _fold_donated_pallas(
                *planes, *chunk,
                num_members=num_members, num_replicas=num_replicas,
                tile_cap=tile_cap, interpret=interpret,
            )
    else:
        def fold_step(planes, chunk):
            return _fold_donated(
                *planes, *chunk,
                num_members=num_members, num_replicas=num_replicas,
                impl=impl, small_counters=small_counters,
            )

    if h2d_lookahead:
        return fold_chunks_overlapped(
            (clock, add, rm), chunks, fold_step, pool=pool
        )
    planes = (clock, add, rm)
    for chunk in chunks:
        planes = fold_step(planes, chunk)
        if pool is not None:
            jax.block_until_ready(planes)
            pool.release(chunk)
    return planes


class PipelineError(Exception):
    """A producer-stage failure, re-raised in the consumer with the
    original exception as ``__cause__``."""


def run_ingest_pipeline(
    spans, ingest_fn, reduce_fn, *, depth: int = 0, producers: int = 1,
    thread_prefix: str = "crdt-ingest-producer",
):
    """Ordered fan-out pipeline over ``spans`` (any sequence of work
    items — encrypted-blob slices for one remote's chunked ingest, or
    whole tenants for the multi-tenant serving layer's cross-tenant
    decode fan-out, crdt_enc_tpu/serve/service.py).

    ``producers`` worker threads pull span indices from a shared cursor
    and run ``ingest_fn(span, k)`` — decrypt + decode; host work whose
    native calls release the GIL — concurrently, while the calling
    thread runs ``reduce_fn(ingested, k)`` — columnarize + fold.  A
    sequencer on the calling thread re-emits completed chunks in STRICT
    chunk-index order, so the reduction order — and therefore the
    donated-fold planes and the resulting state bytes — is identical to
    the single-producer pipeline whatever the workers' finish order.

    Backpressure: a ``BoundedSemaphore(depth)`` is acquired BEFORE a
    chunk is claimed and released only after its reduce completes, so at
    most ``depth`` chunks are ever live host-side — including chunks the
    sequencer is holding back.  ``depth=0`` auto-sizes to
    ``max(2, producers + 1)``: one chunk per worker in flight plus one
    being reduced (the N-producer generalization of the double buffer).
    No deadlock is possible: indices are claimed in increasing order
    immediately after a slot acquire, so the chunk the sequencer waits
    for is always either unclaimed with a free slot on its way, or
    already being ingested by a live worker.

    Stage timing: each ingest runs under a ``stream.ingest`` span and
    each reduce under ``stream.reduce``, both with ``meta=k``; workers
    are named ``<thread_prefix>-<i>`` (default ``crdt-ingest-producer``;
    the serving layer passes ``crdt-serve-producer`` so its lanes stay
    distinguishable in a timeline export) so the timeline export gives
    each its own lane.  ``stream.producer.wait`` (meta = producer index)
    times a worker's backpressure stall, ``stream.sequence`` (meta = k)
    times the sequencer's wait for the next in-order chunk, and the
    ``stream_producers`` gauge records the pool width of the run.

    Errors: the first failing producer sets the shared stop flag — its
    peers cancel at their next claim or slot poll, never claiming new
    chunks — and the failure surfaces here as :class:`PipelineError`
    (original as ``__cause__``) once every chunk BEFORE the failed index
    has been reduced (chunks after it are discarded, releasing their
    pending sequencer slots).  A consumer exception stops all producers
    at their next poll and re-raises unchanged.  Either way the worker
    threads are joined before this function returns.
    """
    spans = list(spans)
    n_spans = len(spans)
    producers = max(1, int(producers))
    if depth <= 0:
        depth = max(2, producers + 1)
    trace.gauge("stream_producers", producers)
    if n_spans == 0:
        return
    slots = threading.BoundedSemaphore(depth)
    out_q: _queue.Queue = _queue.Queue()
    stop = threading.Event()
    cursor_lock = threading.Lock()
    next_index = [0]

    def produce(pid: int):
        k = None
        try:
            while True:
                # backpressure BEFORE claiming an index: a worker must
                # never sit on a claimed chunk while waiting for memory,
                # or the sequencer could stall behind an unstarted chunk
                # (poll so a dead consumer can't strand this thread)
                with trace.span("stream.producer.wait", meta=pid):
                    while not slots.acquire(timeout=0.1):
                        if stop.is_set():
                            return
                if stop.is_set():
                    slots.release()
                    return
                with cursor_lock:
                    k = next_index[0]
                    next_index[0] += 1
                if k >= n_spans:
                    slots.release()
                    return
                with trace.span("stream.ingest", meta=k):
                    item = ingest_fn(spans[k], k)
                out_q.put(("chunk", k, item))
                k = None
        except BaseException as e:  # noqa: BLE001 — relayed to consumer
            stop.set()  # first failure cancels the peers
            out_q.put(("error", k if k is not None else -1, e))

    # each worker runs in a copy of the caller's context, so the spans it
    # opens are parented on the span open here (and its counter taps see
    # the workers' increments), as asyncio.to_thread does for its hops
    workers = [
        threading.Thread(
            target=contextvars.copy_context().run, args=(produce, i),
            name=f"{thread_prefix}-{i}", daemon=True,
        )
        for i in range(producers)
    ]
    for w in workers:
        w.start()
    stash: dict[int, object] = {}
    failures: dict[int, BaseException] = {}
    expected = 0
    try:
        while expected < n_spans:
            if failures and expected >= min(failures):
                k = min(failures)
                raise PipelineError(
                    f"ingest producer failed at chunk {k}"
                ) from failures[k]
            if expected in stash:
                item = stash.pop(expected)
            else:
                with trace.span("stream.sequence", meta=expected):
                    while True:
                        tag, k, item = out_q.get()
                        if tag == "error":
                            failures[k] = item
                            break
                        if k == expected:
                            break
                        stash[k] = item  # holds its slot until reduced
                if tag == "error":
                    continue  # drain the pre-failure prefix, then raise
            try:
                with trace.span("stream.reduce", meta=expected):
                    reduce_fn(item, expected)
            finally:
                slots.release()
            expected += 1
    finally:
        stop.set()
        for w in workers:
            w.join(timeout=30.0)
