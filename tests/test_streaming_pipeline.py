"""The overlapped streaming-compaction pipeline (ops/stream.py).

Three contracts pinned here:

* **overlap (the seam test)**: with trace events enabled, chunk k+1's
  ingest provably STARTS before chunk k's reduce/fold COMPLETES — the
  CPU-CI stand-in for the ≥3× end-to-end TPU claim (ISSUE 1 acceptance:
  on a box without a TPU the overlap is proved structurally, from span
  timestamps, not from wall-clock).
* **backpressure**: at most ``depth`` chunks are live host-side — chunk
  k+2's ingest cannot start until chunk k's reduce released its slot.
* **exactness**: the full pipeline (encrypted blobs → decrypt → decode →
  columnarize → fold) produces a byte-identical state to the whole-batch
  fold and to the per-op host reference.
"""

from __future__ import annotations

import asyncio
import threading
import time

import numpy as np
import pytest

from crdt_enc_tpu import ops as K
from crdt_enc_tpu.utils import codec, trace


def _native_crypto_or_skip():
    from crdt_enc_tpu import native

    try:
        native.load()
    except RuntimeError as e:
        pytest.skip(f"native crypto library unavailable: {e}")


def _events_by_name(name):
    return sorted(
        (e for e in trace.events() if e["name"] == name),
        key=lambda e: e["meta"],
    )


# --------------------------------------------------------------- seam tests


def test_ingest_overlaps_reduce_seam():
    """Chunk k+1's ingest starts BEFORE chunk k's reduce completes: the
    producer/consumer overlap, proved from span timestamps with stage
    durations pinned by sleeps (deterministic on any box)."""
    trace.reset()
    trace.enable_events()
    try:
        def ingest(span, k):
            time.sleep(0.02)
            return span

        def reduce(item, k):
            time.sleep(0.05)

        K.run_ingest_pipeline(list(range(4)), ingest, reduce, depth=2)
    finally:
        trace.enable_events(False)
    ingests = _events_by_name("stream.ingest")
    reduces = _events_by_name("stream.reduce")
    assert [e["meta"] for e in ingests] == [0, 1, 2, 3]
    assert [e["meta"] for e in reduces] == [0, 1, 2, 3]
    overlapped = [
        k for k in range(3)
        if ingests[k + 1]["t0"] < reduces[k]["t1"]
    ]
    # with 20ms ingests and 50ms reduces EVERY interior chunk overlaps;
    # ≥1 required so scheduler noise can't flake the assertion
    assert overlapped, (
        "no chunk's ingest started before the previous chunk's reduce "
        f"finished: ingests={ingests} reduces={reduces}"
    )


def test_backpressure_bounds_live_chunks():
    """Chunk k+2's ingest must NOT start before chunk k's reduce has
    released its slot (BoundedSemaphore(depth=2)) — the at-most-two-
    chunks-of-host-memory guarantee."""
    trace.reset()
    trace.enable_events()
    try:
        def ingest(span, k):
            return span

        def reduce(item, k):
            time.sleep(0.03)

        K.run_ingest_pipeline(list(range(5)), ingest, reduce, depth=2)
    finally:
        trace.enable_events(False)
    ingests = _events_by_name("stream.ingest")
    reduces = _events_by_name("stream.reduce")
    for k in range(len(ingests) - 2):
        assert ingests[k + 2]["t0"] >= reduces[k]["t1"], (
            f"chunk {k + 2} ingested before chunk {k}'s slot was released"
        )


def test_h2d_issued_before_previous_fold_dispatch():
    """The consumer issues chunk k+1's device transfer BEFORE dispatching
    chunk k's donated fold (fold_chunks_overlapped's double-buffer
    discipline), so the copy rides under the in-flight fold."""
    R, E, rows = 3, 4, 8
    kind = np.zeros(24, np.int8)
    member = (np.arange(24) % E).astype(np.int32)
    actor = (np.arange(24) % R).astype(np.int32)
    counter = ((np.arange(24) // R) + 1).astype(np.int32)
    trace.reset()
    trace.enable_events()
    try:
        pool = K.ChunkPool(rows, depth=2)
        planes = K.orset_fold_stream(
            np.zeros(R, np.int32),
            np.zeros((E, R), np.int32),
            np.zeros((E, R), np.int32),
            K.iter_orset_chunks(kind, member, actor, counter, rows, R,
                                pool=pool),
            num_members=E, num_replicas=R, pool=pool,
        )
        K.planes_to_host(planes)
    finally:
        trace.enable_events(False)
    h2d = _events_by_name("stream.h2d")
    folds = _events_by_name("stream.fold")
    assert len(h2d) == 3 and len(folds) == 3
    for k in range(len(folds) - 1):
        assert h2d[k + 1]["t1"] <= folds[k]["t0"], (
            f"fold {k} dispatched before chunk {k + 1}'s transfer was issued"
        )


def test_producer_error_propagates():
    def ingest(span, k):
        if k == 1:
            raise ValueError("boom")
        return span

    with pytest.raises(K.PipelineError) as ei:
        K.run_ingest_pipeline(list(range(3)), ingest, lambda item, k: None)
    assert isinstance(ei.value.__cause__, ValueError)


def test_consumer_error_stops_producer():
    ingested = []

    def ingest(span, k):
        ingested.append(k)
        return span

    def reduce(item, k):
        raise RuntimeError("reduce failed")

    with pytest.raises(RuntimeError, match="reduce failed"):
        K.run_ingest_pipeline(list(range(50)), ingest, reduce, depth=2)
    # backpressure kept the producer from racing ahead of the failure
    assert len(ingested) <= 4
    # ... and the producer thread itself wound down (the pipeline joins
    # it on exit; poll briefly in case the runtime is slow to reap)
    _assert_no_producer_threads()


def _assert_no_producer_threads():
    deadline = time.time() + 5.0
    while time.time() < deadline and any(
        t.name.startswith("crdt-ingest-producer") and t.is_alive()
        for t in threading.enumerate()
    ):
        time.sleep(0.01)
    leaked = [
        t.name
        for t in threading.enumerate()
        if t.name.startswith("crdt-ingest-producer") and t.is_alive()
    ]
    assert not leaked, f"leaked producer threads: {leaked}"


# ----------------------------------------------------------- chunk staging


def test_pooled_chunks_equal_plain_chunks():
    """Pool-staged chunk iteration (pre-allocated buffers, sentinel
    padding) yields exactly the chunks the allocating path yields."""
    rng = np.random.default_rng(3)
    n, R, E, rows = 37, 5, 6, 8
    kind = rng.integers(0, 2, n).astype(np.int8)
    member = rng.integers(0, E, n).astype(np.int32)
    actor = rng.integers(0, R, n).astype(np.int32)
    counter = rng.integers(1, 50, n).astype(np.int32)
    plain = list(K.iter_orset_chunks(kind, member, actor, counter, rows, R))
    pool = K.ChunkPool(rows, depth=2)
    for i, bufs in enumerate(
        K.iter_orset_chunks(kind, member, actor, counter, rows, R, pool=pool)
    ):
        for got, want in zip(bufs, plain[i]):
            np.testing.assert_array_equal(got, want)
        pool.release(bufs)


def test_overlapped_stream_fold_matches_whole_batch():
    """orset_fold_stream with the overlapped loop + pool ≡ one whole-batch
    orset_fold on the same columns (plane-exact).  The op stream honors
    the causal-delivery contract the chunked fold assumes (per-actor
    counters arrive in version order — core.py _read_remote_ops): adds
    are each actor's next dot, removes carry the horizon seen so far."""
    rng = np.random.default_rng(11)
    n, R, E, rows = 301, 7, 9, 64
    kind = rng.integers(0, 2, n).astype(np.int8)
    member = rng.integers(0, E, n).astype(np.int32)
    actor = rng.integers(0, R, n).astype(np.int32)
    counter = np.zeros(n, np.int32)
    seen = np.zeros(R, np.int64)
    for i in range(n):
        a = actor[i]
        if kind[i] == 0 or seen[a] == 0:
            kind[i] = 0
            seen[a] += 1
        counter[i] = seen[a]
    z = lambda *s: np.zeros(s, np.int32)  # noqa: E731
    pool = K.ChunkPool(rows, depth=2)
    planes = K.orset_fold_stream(
        z(R), z(E, R), z(E, R),
        K.iter_orset_chunks(kind, member, actor, counter, rows, R, pool=pool),
        num_members=E, num_replicas=R, pool=pool,
    )
    clock_s, add_s, rm_s = K.planes_to_host(planes)
    clock_w, add_w, rm_w = K.orset_fold(
        z(R), z(E, R), z(E, R), kind, member, actor, counter,
        num_members=E, num_replicas=R,
    )
    np.testing.assert_array_equal(clock_s, np.asarray(clock_w))
    np.testing.assert_array_equal(add_s, np.asarray(add_w))
    np.testing.assert_array_equal(rm_s, np.asarray(rm_w))


# ------------------------------------------------- end-to-end differential
# Through the served door: Core.read_remote() on a TpuAccelerator over a
# remote whose iter_op_chunks yields several chunks (tests/_ingest_doors.py).


_run = asyncio.run


def test_streaming_pipeline_byte_identical_to_host():
    """ISSUE 1 acceptance: encrypted op files → pipelined ingest → state is
    BYTE-identical to the per-op host reference AND to the whole-batch
    bulk fold, across chunking geometries."""
    _native_crypto_or_skip()
    from _ingest_doors import orset_workload, read_pipelined, seed_remote
    from crdt_enc_tpu.models import ORSet
    from crdt_enc_tpu.parallel import TpuAccelerator

    files, actors, host = orset_workload()
    host_bytes = codec.pack(host.to_obj())

    # whole-batch bulk fold (the previously-pinned path)
    whole = ORSet()
    assert TpuAccelerator().fold_payloads(
        whole, [codec.pack(ops) for _, ops in files],
        actors_hint=sorted(actors),
    )
    assert codec.pack(whole.to_obj()) == host_bytes

    async def go():
        remote, _ = await seed_remote(files)
        for files_per_chunk in (len(files), 14, 5, 1):
            trace.reset()
            reader = await read_pipelined(
                remote, files_per_chunk, accel=TpuAccelerator()
            )
            # every file went through the fold session, none per op
            counters = trace.snapshot()["counters"]
            assert counters["op_files_bulk_folded"] == len(files)
            assert "ops_folded" not in counters
            assert reader.with_state(
                lambda s: codec.pack(s.to_obj())
            ) == host_bytes, f"divergence at {files_per_chunk} files a chunk"

    _run(go())


def test_streaming_pipeline_into_existing_state():
    """The pipeline folds INTO a non-empty replica exactly as the per-op
    path does (stale dots rejected, pre-existing entries honored)."""
    _native_crypto_or_skip()
    from _ingest_doors import (
        apply_files, chunked, make_opts, orset_workload, seed_remote,
    )
    from crdt_enc_tpu.core import Core
    from crdt_enc_tpu.models import ORSet
    from crdt_enc_tpu.parallel import TpuAccelerator

    files, actors, _ = orset_workload(seed=9)

    async def go():
        remote, _ = await seed_remote(files)
        reader = await Core.open(
            make_opts(chunked(remote, 10), accel=TpuAccelerator())
        )
        for m in (99, 5):  # entries the stream never mentions or re-adds
            await reader.update(
                lambda s, m=m: s.add_ctx(reader.actor_id, m)
            )
        # host truth in the right order: pre-ops THEN stream ops
        host = reader.with_state(lambda s: ORSet.from_obj(s.to_obj()))
        apply_files(host, files)
        await reader.read_remote()
        assert reader.with_state(
            lambda s: codec.pack(s.to_obj())
        ) == codec.pack(host.to_obj())

    _run(go())


def test_streaming_pipeline_counter_session():
    """The pipelined door is generic over session types: a PN-Counter
    ingest runs the same pipeline and equals the per-op reference."""
    _native_crypto_or_skip()
    from _ingest_doors import read_pipelined, seed_remote
    from crdt_enc_tpu.core.adapters import pncounter_adapter
    from crdt_enc_tpu.models import PNCounter
    from crdt_enc_tpu.parallel import TpuAccelerator

    actors = [bytes([a]) * 16 for a in range(1, 4)]
    host = PNCounter()
    files = []
    rng = np.random.default_rng(4)
    for f in range(24):
        a = f % 3
        ops = []
        for _ in range(5):
            sign, dot = (
                host.inc(actors[a]) if rng.random() < 0.7
                else host.dec(actors[a])
            )
            ops.append([int(sign), [dot.actor, dot.counter]])
            host.apply((sign, dot))
        files.append((actors[a], ops))

    async def go():
        remote, _ = await seed_remote(files, adapter=pncounter_adapter())
        reader = await read_pipelined(
            remote, 7, accel=TpuAccelerator(), adapter=pncounter_adapter()
        )
        assert reader.with_state(
            lambda s: codec.pack(s.to_obj())
        ) == codec.pack(host.to_obj())
        assert reader.with_state(lambda s: s.read()) == host.read()

    _run(go())


def test_streaming_pipeline_seam_on_real_path():
    """The real pipeline (native decrypt in the producer, native decode
    in a worker thread) emits the stage spans the docs promise, each
    chunk's under its own index."""
    _native_crypto_or_skip()
    from _ingest_doors import orset_workload, read_pipelined, seed_remote
    from crdt_enc_tpu.parallel import TpuAccelerator

    files, _, host = orset_workload(n_files=60, ops_per_file=8)

    async def go():
        remote, _ = await seed_remote(files)
        trace.reset()
        trace.enable_events()
        try:
            return await read_pipelined(remote, 10, accel=TpuAccelerator())
        finally:
            trace.enable_events(False)

    reader = _run(go())
    names = {e["name"] for e in trace.events()}
    for required in ("ops.chunk_load", "ops.chunk_unwrap",
                     "ops.chunk_decrypt", "ops.chunk_wait",
                     "ops.chunk_fold", "session.decode",
                     "ops.session_finish"):
        assert required in names, f"missing stage span {required}"
    for per_chunk in ("ops.chunk_unwrap", "ops.chunk_decrypt"):
        assert [e["meta"] for e in _events_by_name(per_chunk)] == list(
            range(6)
        )
    assert reader.with_state(
        lambda s: codec.pack(s.to_obj())
    ) == codec.pack(host.to_obj())


# ------------------------------------------------- multi-producer fan-out


def test_producer_count_resolution(monkeypatch):
    """stream_producer_count: explicit request > env override > the
    cpu-count auto-tune (one producer per core, one core reserved for
    the consumer, floor 1 — the stale cap of 4 is gone: an idle
    many-core host scales with its cores)."""
    monkeypatch.delenv("CRDT_STREAM_PRODUCERS", raising=False)
    assert K.stream_producer_count(3) == 3
    auto = K.stream_producer_count()
    import os

    cpus = os.cpu_count() or 1
    assert auto == max(1, cpus - 1)
    monkeypatch.setenv("CRDT_STREAM_PRODUCERS", "7")
    assert K.stream_producer_count() == 7
    assert K.stream_producer_count(2) == 2  # explicit still wins
    monkeypatch.setenv("CRDT_STREAM_PRODUCERS", "not-a-number")
    assert K.stream_producer_count() == auto


def test_multi_producer_order_deterministic():
    """The sequencer re-emits chunks in strict index order whatever the
    workers' finish order — pinned with randomized per-chunk delays at
    several fan-out widths."""
    rng = np.random.default_rng(17)
    delays = rng.random(24) * 0.01
    for producers in (1, 2, 4):
        order = []

        def ingest(span, k):
            time.sleep(delays[k])
            return span * 10

        def reduce(item, k):
            order.append((k, item))

        K.run_ingest_pipeline(
            list(range(24)), ingest, reduce, producers=producers
        )
        assert order == [(k, 10 * k) for k in range(24)], (producers, order)


def test_multi_producer_lanes_and_gauge():
    """N workers run under numbered thread lanes, the stream_producers
    gauge records the pool width, and the fan-out spans
    (stream.producer.wait, stream.sequence) are emitted."""
    trace.reset()
    trace.enable_events()
    try:
        K.run_ingest_pipeline(
            list(range(8)),
            lambda span, k: time.sleep(0.005) or span,
            lambda item, k: time.sleep(0.002),
            producers=2,
        )
    finally:
        trace.enable_events(False)
    snap = trace.snapshot()
    assert snap["gauges"]["stream_producers"] == 2
    events = trace.events()
    names = {e["name"] for e in events}
    assert {"stream.producer.wait", "stream.sequence"} <= names
    lanes = {
        e["thread"] for e in events if e["name"] == "stream.ingest"
    }
    assert lanes == {"crdt-ingest-producer-0", "crdt-ingest-producer-1"}
    trace.reset()


def test_multi_producer_overlap_seam():
    """With 2 producers and slow reduces, some chunk's ingest still
    starts before the previous chunk's reduce completes — the same
    overlap proof the single-producer seam test pins."""
    trace.reset()
    trace.enable_events()
    try:
        K.run_ingest_pipeline(
            list(range(6)),
            lambda span, k: time.sleep(0.02) or span,
            lambda item, k: time.sleep(0.05),
            producers=2,
        )
    finally:
        trace.enable_events(False)
    ingests = _events_by_name("stream.ingest")
    reduces = _events_by_name("stream.reduce")
    assert [e["meta"] for e in reduces] == list(range(6))
    assert any(
        ingests[k + 1]["t0"] < reduces[k]["t1"] for k in range(5)
    ), "no overlap with 2 producers"


def test_multi_producer_backpressure_bound():
    """At most depth chunks are ever live host-side, stashed sequencer
    chunks included: chunk k+depth's ingest cannot start before chunk
    k's reduce released its slot."""
    trace.reset()
    trace.enable_events()
    depth = 3
    try:
        K.run_ingest_pipeline(
            list(range(8)),
            lambda span, k: span,
            lambda item, k: time.sleep(0.02),
            depth=depth,
            producers=2,
        )
    finally:
        trace.enable_events(False)
    ingests = _events_by_name("stream.ingest")
    reduces = _events_by_name("stream.reduce")
    for k in range(len(ingests) - depth):
        assert ingests[k + depth]["t0"] >= reduces[k]["t1"], (
            f"chunk {k + depth} ingested before chunk {k}'s slot released"
        )


def test_multi_producer_fault_injection():
    """The first failing producer cancels its peers and the pending
    sequencer slots: every chunk BEFORE the failed index is reduced in
    order, the failure surfaces as PipelineError with the original as
    __cause__, no worker thread leaks, and the pipeline is reusable
    afterwards (no deadlocked BoundedSemaphore state escapes)."""
    rng = np.random.default_rng(3)
    delays = rng.random(30) * 0.008
    reduced = []

    def ingest(span, k):
        time.sleep(delays[k])
        if k == 7:
            raise ValueError("producer boom")
        return span

    def reduce(item, k):
        reduced.append(k)

    with pytest.raises(K.PipelineError) as ei:
        K.run_ingest_pipeline(
            list(range(30)), ingest, reduce, producers=3
        )
    assert isinstance(ei.value.__cause__, ValueError)
    # deterministic drain: exactly the pre-failure prefix, in order
    assert reduced == list(range(7)), reduced
    _assert_no_producer_threads()
    # a fresh run right after the fault completes normally (nothing
    # leaked into module or interpreter state)
    order = []
    K.run_ingest_pipeline(
        list(range(10)), lambda s, k: s, lambda i, k: order.append(k),
        producers=3,
    )
    assert order == list(range(10))


def test_multi_producer_consumer_error_cancels_pool():
    """A consumer failure stops every producer at its next poll."""
    ingested = []

    def ingest(span, k):
        ingested.append(k)
        return span

    def reduce(item, k):
        raise RuntimeError("reduce failed")

    with pytest.raises(RuntimeError, match="reduce failed"):
        K.run_ingest_pipeline(
            list(range(50)), ingest, reduce, depth=4, producers=3
        )
    # backpressure bounds how far the pool ran ahead of the failure
    assert len(ingested) <= 8
    _assert_no_producer_threads()


def test_multi_producer_byte_identical_to_single(monkeypatch):
    """ISSUE 3 acceptance (differential): the SAME encrypted op files
    ingested at ``stream_producers`` 1 and 4 — with randomized delays
    injected ahead of the real decode — produce byte-identical states,
    both equal to the per-op host reference."""
    _native_crypto_or_skip()
    from _ingest_doors import orset_workload, read_pipelined, seed_remote
    from crdt_enc_tpu.parallel import TpuAccelerator
    from crdt_enc_tpu.parallel import session as psession

    files, _, host = orset_workload(n_files=48, ops_per_file=7, seed=21)
    host_bytes = codec.pack(host.to_obj())
    delays = iter(np.random.default_rng(9).random(64) * 0.01)
    real_decode = psession.OrsetFoldSession.decode_chunk

    def jittered_decode(self, payloads):
        time.sleep(next(delays))
        return real_decode(self, payloads)

    monkeypatch.setattr(
        psession.OrsetFoldSession, "decode_chunk", jittered_decode
    )

    async def go():
        remote, _ = await seed_remote(files)
        results = {}
        for n in (1, 4):
            trace.reset()
            reader = await read_pipelined(
                remote, 6, accel=TpuAccelerator(stream_producers=n)
            )
            assert trace.snapshot()["gauges"]["stream_producers"] == n
            results[n] = reader.with_state(
                lambda s: codec.pack(s.to_obj())
            )
        return results

    for n, got in _run(go()).items():
        assert got == host_bytes, f"divergence at stream_producers={n}"


# ------------------------------------------------- mesh-sharded streaming


def _mesh_or_skip():
    import jax

    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-device virtual CPU mesh (conftest)")
    from crdt_enc_tpu.parallel import mesh as pmesh

    return pmesh.make_mesh((4, 2))


def test_sharded_stream_byte_identical_to_single_chip(monkeypatch):
    """ISSUE 3 acceptance (sharded differential): the SAME encrypted
    span set folded through the mesh-sharded streaming branch
    (session._device_feed_sharded → orset_fold_sharded, planes
    mp-sharded, chunks dp-sharded) and through the single-chip stream is
    byte-identical — both equal to the per-op host reference."""
    _native_crypto_or_skip()
    from _ingest_doors import orset_workload, read_pipelined, seed_remote
    from crdt_enc_tpu.parallel import TpuAccelerator, mesh as pmesh
    from crdt_enc_tpu.parallel import session as psession

    mesh = _mesh_or_skip()
    # tiny promotion threshold so the small workload leaves BUFFER mode
    monkeypatch.setattr(psession, "BUFFER_BYTES", 256)

    files, _, host = orset_workload(
        n_files=60, ops_per_file=8, R=5, E=24, seed=13
    )
    host_bytes = codec.pack(host.to_obj())

    assert TpuAccelerator(mesh=mesh).sharded_stream  # auto-on with a mesh

    # spy: the sharded fold step must actually run (not a silent
    # fallback to the single-chip or buffered route)
    calls = []
    real_step = pmesh.sharded_stream_fold_step

    def spy_step(*a, **kw):
        step = real_step(*a, **kw)

        def wrapped(*args):
            calls.append(1)
            return step(*args)

        return wrapped

    monkeypatch.setattr(pmesh, "sharded_stream_fold_step", spy_step)

    async def go():
        remote, _ = await seed_remote(files)
        sharded = await read_pipelined(
            remote, 10, accel=TpuAccelerator(mesh=mesh, stream_producers=2)
        )
        assert calls, "sharded streaming fold did not engage"
        single = await read_pipelined(remote, 10, accel=TpuAccelerator())
        return sharded, single

    for reader in _run(go()):
        assert reader.with_state(
            lambda s: codec.pack(s.to_obj())
        ) == host_bytes


def test_sharded_stream_into_existing_state(monkeypatch):
    """The sharded stream's finish combine uses op-APPLY semantics
    against the live state (retire_rm=False partial reduction): remove
    horizons streamed through the mesh still kill pre-existing entries,
    and stale dots are still rejected."""
    _native_crypto_or_skip()
    from _ingest_doors import (
        apply_files, chunked, make_opts, orset_workload, seed_remote,
    )
    from crdt_enc_tpu.core import Core
    from crdt_enc_tpu.models import ORSet
    from crdt_enc_tpu.parallel import TpuAccelerator
    from crdt_enc_tpu.parallel import session as psession

    mesh = _mesh_or_skip()
    monkeypatch.setattr(psession, "BUFFER_BYTES", 256)

    files, _, _ = orset_workload(
        n_files=48, ops_per_file=8, R=4, E=16, seed=29
    )

    async def go():
        remote, _ = await seed_remote(files)
        reader = await Core.open(
            make_opts(chunked(remote, 10), accel=TpuAccelerator(mesh=mesh))
        )
        for m in (3, 5):  # members the stream's removes reach
            await reader.update(
                lambda s, m=m: s.add_ctx(reader.actor_id, m)
            )
        host = reader.with_state(lambda s: ORSet.from_obj(s.to_obj()))
        apply_files(host, files)
        await reader.read_remote()
        assert reader.with_state(
            lambda s: codec.pack(s.to_obj())
        ) == codec.pack(host.to_obj())

    _run(go())


def test_sharded_stream_gated_off_multiprocess(monkeypatch):
    """On a multi-host pod (jax.process_count() > 1) the sharded stream
    must NOT engage: its growth/finish combine pulls the mp-sharded
    planes to host, which only addresses local shards — those meshes
    keep the buffered whole-batch sharded fold."""
    import jax

    from crdt_enc_tpu.models import ORSet
    from crdt_enc_tpu.parallel import TpuAccelerator
    from crdt_enc_tpu.parallel import session as psession

    mesh = _mesh_or_skip()
    monkeypatch.setattr(psession, "BUFFER_BYTES", 64)
    monkeypatch.setattr(jax, "process_count", lambda: 2)
    accel = TpuAccelerator(mesh=mesh)
    assert accel.sharded_stream  # the toggle itself stays on...
    session = accel.open_fold_session(ORSet(), actors_hint=[b"\x01" * 16])
    n = 40
    decoded = (
        np.zeros(n, np.int8),
        np.arange(n, dtype=np.int32) % 8,
        np.arange(n, dtype=np.int32) % 3,
        np.arange(n, dtype=np.int32) + 1,
        [bytes([m]) for m in range(8)],
    )
    session.reduce_chunk(decoded)
    # ...but the session refuses the promotion (local-shard host pulls)
    assert session.mode == "buffer" and not session._d_sharded


def test_sharded_stream_toggle_off_stays_buffered(monkeypatch):
    """sharded_stream=False (or CRDT_SHARDED_STREAM=0) preserves the
    historical buffered-mesh session: no promotion, finish through the
    whole-batch sharded fold."""
    from crdt_enc_tpu.models import ORSet
    from crdt_enc_tpu.parallel import TpuAccelerator
    from crdt_enc_tpu.parallel import session as psession

    mesh = _mesh_or_skip()
    monkeypatch.setattr(psession, "BUFFER_BYTES", 64)
    actors = [bytes([a]) * 16 for a in range(1, 4)]

    def feed_rows(accel):
        session = accel.open_fold_session(ORSet(), actors_hint=actors)
        # synthetic decoded chunks (kind, member_idx, actor_idx, counter,
        # member_objs) — enough rows to blow the 64-byte buffer twice
        for base in (0, 40):
            n = 40
            decoded = (
                np.zeros(n, np.int8),
                np.arange(n, dtype=np.int32) % 8,
                np.arange(n, dtype=np.int32) % 3,
                np.arange(n, dtype=np.int32) + 1 + base,
                [bytes([m]) for m in range(8)],
            )
            session.reduce_chunk(decoded)
        return session

    off = feed_rows(TpuAccelerator(mesh=mesh, sharded_stream=False))
    assert off.mode == "buffer" and not off._d_sharded

    on = feed_rows(TpuAccelerator(mesh=mesh))
    assert on.mode == "device_stream" and on._d_sharded

    monkeypatch.setenv("CRDT_SHARDED_STREAM", "0")
    env_off = TpuAccelerator(mesh=mesh)
    assert not env_off.sharded_stream


# ------------------------------------------- counters + session seams


def test_stream_counters_pinned_on_pipelined_path():
    """bytes_decrypted on the pipelined door equals EXACTLY the byte sum
    of the sealed ciphertexts (counted only after a chunk's decrypt
    succeeds), and the host/buffer regime issues zero h2d beyond the one
    dense fold — the attribution marginals' inputs stay trustworthy
    (ISSUE 13 audit)."""
    _native_crypto_or_skip()
    from _ingest_doors import orset_workload, read_pipelined, seed_remote
    from crdt_enc_tpu.backends.xchacha import AeadError, XChaChaCryptor
    from crdt_enc_tpu.core.core import IngestDecryptError
    from crdt_enc_tpu.parallel import TpuAccelerator
    from crdt_enc_tpu.utils import VersionBytes

    files, actors, host = orset_workload(seed=5)

    class DeadCryptor(XChaChaCryptor):
        async def decrypt_batch(self, key, blobs):
            raise AeadError("dead")

        async def decrypt(self, key, data):
            raise AeadError("dead")

    async def go():
        remote, writer = await seed_remote(files)
        sealed = await writer.storage.load_ops([(a, 1) for a in actors])
        ciphertext_bytes = sum(
            len(codec.unpack(VersionBytes.deserialize(raw).content)[1])
            for _, _, raw in sealed
        )
        trace.reset()
        reader = await read_pipelined(remote, 10, accel=TpuAccelerator())
        snap = trace.snapshot()
        assert snap["counters"]["bytes_decrypted"] == ciphertext_bytes
        # tiny workload stays in the BUFFER regime; its one device hop is
        # the dense fold: the state-plane upload — exactly clock (R·4) +
        # add/rm planes (2·E·R·4) for this E=12, R=5 shape — plus the 240
        # op rows' columns (13 B a row, padded to the 256-row class), and
        # the same planes pulled back.  A drift here means an unaccounted
        # (or double-counted) device hop appeared.
        planes = 5 * 4 + 2 * 12 * 5 * 4
        assert snap["counters"].get("h2d_bytes", 0) == planes + 13 * 256
        assert snap["counters"].get("d2h_bytes", 0) == planes
        assert reader.with_state(
            lambda s: codec.pack(s.to_obj())
        ) == codec.pack(host.to_obj())
        # a failed decrypt (dead cryptor) counts NOTHING
        trace.reset()
        with pytest.raises(IngestDecryptError):
            await read_pipelined(
                remote, 10, accel=TpuAccelerator(), cryptor=DeadCryptor()
            )
        assert trace.snapshot()["counters"].get("bytes_decrypted", 0) == 0

    _run(go())


def test_session_fresh_fast_init_matches_general_path():
    """The fresh-state sorted-hint fast init must agree with the general
    construction (actor table, R, clock0) and fold byte-identically when
    the hint arrives UNSORTED (general path) vs sorted (fast path)."""
    _native_crypto_or_skip()
    from _ingest_doors import (
        ChunkedMemoryStorage, orset_workload, read_pipelined, seed_remote,
    )
    from crdt_enc_tpu.models import ORSet
    from crdt_enc_tpu.parallel import TpuAccelerator
    from crdt_enc_tpu.parallel.session import OrsetFoldSession

    files, actors, host = orset_workload(seed=11)
    accel = TpuAccelerator()
    fast = OrsetFoldSession(accel, ORSet(), sorted(actors))
    slow = OrsetFoldSession(accel, ORSet(), list(reversed(sorted(actors))))
    assert fast.actors_sorted == slow.actors_sorted
    assert fast.R == slow.R
    assert (fast._clock0 == slow._clock0).all()

    # non-fresh: a state with a clock must land in _clock0 exactly
    seeded = ORSet()
    from crdt_enc_tpu.models.orset import AddOp
    from crdt_enc_tpu.models.vclock import Dot

    seeded.apply(AddOp(3, Dot(actors[1], 7)))
    sess = OrsetFoldSession(accel, seeded, sorted(actors))
    pos = sess.actors_sorted.index(actors[1])
    assert sess._clock0[pos] == 7

    # and folds byte-identically through the served door whichever way
    # the storage lists the actors (the listing IS the session's hint)
    class ReversedListing(ChunkedMemoryStorage):
        async def list_op_actors(self):
            return list(reversed(sorted(await super().list_op_actors())))

    async def go():
        remote, _ = await seed_remote(files)
        return [
            (await read_pipelined(
                remote, 10, accel=accel, base=base
            )).with_state(lambda s: codec.pack(s.to_obj()))
            for base in (ChunkedMemoryStorage, ReversedListing)
        ]

    results = _run(go())
    assert len(set(results)) == 1
    assert results[0] == codec.pack(host.to_obj())


def test_session_member_collision_declines_on_bytes_path():
    """1 == True as members: the bytes-keyed remap must decline exactly
    like the legacy object remap (the dense planes cannot represent the
    collision), and the core's per-op fallback still folds correctly."""
    _native_crypto_or_skip()
    from _ingest_doors import read_pipelined, seed_remote
    from crdt_enc_tpu.models import ORSet
    from crdt_enc_tpu.models.orset import AddOp
    from crdt_enc_tpu.models.vclock import Dot
    from crdt_enc_tpu.parallel import TpuAccelerator

    actor = b"\x01" * 16
    # enough files to promote the ingest into the session, the last two
    # colliding as Python values
    members = [1] * 17 + [True]
    files = [(actor, [[0, m, [actor, c]]]) for c, m in enumerate(members, 1)]
    host = ORSet()
    for c, m in enumerate(members, 1):
        host.apply(AddOp(m, Dot(actor, c)))

    async def go():
        remote, _ = await seed_remote(files)
        trace.reset()
        reader = await read_pipelined(remote, 6, accel=TpuAccelerator())
        counters = trace.snapshot()["counters"]
        # the chunks ahead of the collision went through the session; the
        # declined chunk (and nothing ahead of it) replayed per op
        assert counters["op_files_bulk_folded"] == 12
        assert counters["ops_folded"] == 6
        assert reader.with_state(
            lambda s: codec.pack(s.to_obj())
        ) == codec.pack(host.to_obj())
        assert reader.info().next_op_versions.get(actor) == len(members)

    _run(go())
