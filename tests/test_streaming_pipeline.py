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

import secrets
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


def _encrypted_orset_workload(n_files=40, ops_per_file=6, R=5, E=12, seed=2):
    """Per-actor op files sealed with the native AEAD + the per-op host
    truth (apply order == file order, per-actor version order)."""
    from crdt_enc_tpu.backends.xchacha import encrypt_blob
    from crdt_enc_tpu.models import ORSet
    from crdt_enc_tpu.models.orset import AddOp, RmOp
    from crdt_enc_tpu.models.vclock import Dot, VClock

    rng = np.random.default_rng(seed)
    key = secrets.token_bytes(32)
    actors = [bytes([a]) * 16 for a in range(1, R + 1)]
    counters = {a: 0 for a in range(R)}
    host = ORSet()
    blobs = []
    for f in range(n_files):
        a = f % R
        ops = []
        for _ in range(ops_per_file):
            m = int(rng.integers(0, E))
            if rng.random() < 0.75 or counters[a] == 0:
                counters[a] += 1
                ops.append([0, m, [actors[a], counters[a]]])
                host.apply(AddOp(m, Dot(actors[a], counters[a])))
            else:
                ops.append([1, m, {actors[a]: counters[a]}])
                host.apply(RmOp(m, VClock({actors[a]: counters[a]})))
        blobs.append(encrypt_blob(key, codec.pack(ops)))
    return key, blobs, actors, host


def test_streaming_pipeline_byte_identical_to_host():
    """ISSUE 1 acceptance: encrypted blobs → streaming pipeline → state is
    BYTE-identical to the per-op host reference AND to the whole-batch
    bulk fold, across chunking geometries."""
    _native_crypto_or_skip()
    from crdt_enc_tpu.backends.xchacha import decrypt_blobs
    from crdt_enc_tpu.models import ORSet
    from crdt_enc_tpu.parallel import TpuAccelerator

    key, blobs, actors, host = _encrypted_orset_workload()
    host_bytes = codec.pack(host.to_obj())
    accel = TpuAccelerator()
    hint = sorted(actors)

    # whole-batch bulk fold (the previously-pinned path)
    whole = ORSet()
    assert accel.fold_payloads(
        whole, decrypt_blobs(key, blobs), actors_hint=hint
    )
    assert codec.pack(whole.to_obj()) == host_bytes

    for n_chunks in (1, 3, 8, len(blobs)):
        streamed = ORSet()
        ok = accel.fold_encrypted_stream(
            streamed, key, blobs, actors_hint=hint, n_chunks=n_chunks,
        )
        assert ok, f"pipeline declined at n_chunks={n_chunks}"
        assert codec.pack(streamed.to_obj()) == host_bytes, (
            f"divergence at n_chunks={n_chunks}"
        )


def test_streaming_pipeline_into_existing_state():
    """The pipeline folds INTO a non-empty replica exactly as the per-op
    path does (stale dots rejected, pre-existing entries honored)."""
    _native_crypto_or_skip()
    from crdt_enc_tpu.models import ORSet
    from crdt_enc_tpu.models.orset import AddOp
    from crdt_enc_tpu.models.vclock import Dot
    from crdt_enc_tpu.parallel import TpuAccelerator

    key, blobs, actors, host = _encrypted_orset_workload(seed=9)
    pre = [(b"\x77" * 16, 1, 99), (b"\x78" * 16, 2, 5)]
    streamed = ORSet()
    for a, c, m in pre:
        host_op = AddOp(m, Dot(a, c))
        streamed.apply(host_op)
        host.apply(host_op)  # same op applied before the stream in both
    # NB: host had the stream's ops applied already in the builder, so
    # rebuild host truth in the right order: pre-ops THEN stream ops
    host2 = ORSet()
    for a, c, m in pre:
        host2.apply(AddOp(m, Dot(a, c)))
    from crdt_enc_tpu.backends.xchacha import decrypt_blobs
    from crdt_enc_tpu.models.orset import RmOp
    from crdt_enc_tpu.models.vclock import VClock

    for raw in decrypt_blobs(key, blobs):
        for o in codec.unpack(raw):
            if o[0] == 0:
                host2.apply(AddOp(o[1], Dot.from_obj(o[2])))
            else:
                host2.apply(RmOp(o[1], VClock.from_obj(o[2])))

    accel = TpuAccelerator()
    ok = accel.fold_encrypted_stream(
        streamed, key, blobs, actors_hint=sorted(actors), n_chunks=4,
    )
    assert ok
    assert codec.pack(streamed.to_obj()) == codec.pack(host2.to_obj())


def test_streaming_pipeline_counter_session():
    """fold_encrypted_stream is generic over session types: a PN-Counter
    ingest runs the same pipeline and equals the per-op reference."""
    _native_crypto_or_skip()
    from crdt_enc_tpu.backends.xchacha import encrypt_blob
    from crdt_enc_tpu.models import PNCounter
    from crdt_enc_tpu.parallel import TpuAccelerator

    key = secrets.token_bytes(32)
    actors = [bytes([a]) * 16 for a in range(1, 4)]
    host = PNCounter()
    blobs = []
    rng = np.random.default_rng(4)
    for f in range(12):
        a = f % 3
        ops = []
        for _ in range(5):
            sign, dot = (
                host.inc(actors[a]) if rng.random() < 0.7
                else host.dec(actors[a])
            )
            ops.append([int(sign), [dot.actor, dot.counter]])
            host.apply((sign, dot))
        blobs.append(encrypt_blob(key, codec.pack(ops)))
    streamed = PNCounter()
    accel = TpuAccelerator()
    ok = accel.fold_encrypted_stream(
        streamed, key, blobs, actors_hint=sorted(actors), n_chunks=3,
    )
    assert ok
    assert codec.pack(streamed.to_obj()) == codec.pack(host.to_obj())
    assert streamed.read() == host.read()


def test_streaming_pipeline_seam_on_real_path():
    """The real pipeline (native decrypt + decode in the producer) emits
    the stage spans the docs promise, and its ingest of some chunk k+1
    starts before reduce k completes once reduces are non-trivial."""
    _native_crypto_or_skip()
    from crdt_enc_tpu.models import ORSet
    from crdt_enc_tpu.parallel import TpuAccelerator

    key, blobs, actors, host = _encrypted_orset_workload(
        n_files=60, ops_per_file=8
    )
    accel = TpuAccelerator()
    streamed = ORSet()
    trace.reset()
    trace.enable_events()
    try:
        ok = accel.fold_encrypted_stream(
            streamed, key, blobs, actors_hint=sorted(actors), n_chunks=6,
        )
    finally:
        trace.enable_events(False)
    assert ok
    names = {e["name"] for e in trace.events()}
    for required in ("stream.decrypt", "stream.decode", "stream.ingest",
                     "stream.reduce", "stream.finish"):
        assert required in names, f"missing stage span {required}"
    assert codec.pack(streamed.to_obj()) == codec.pack(host.to_obj())


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


def test_multi_producer_byte_identical_to_single():
    """ISSUE 3 acceptance (differential): the SAME encrypted span set
    folded with 1, 2, and 4 producers — with randomized producer delays
    injected ahead of the real decrypt — produces byte-identical states,
    all equal to the per-op host reference."""
    _native_crypto_or_skip()
    from crdt_enc_tpu.models import ORSet
    from crdt_enc_tpu.parallel import TpuAccelerator

    key, blobs, actors, host = _encrypted_orset_workload(
        n_files=48, ops_per_file=7, seed=21
    )
    host_bytes = codec.pack(host.to_obj())
    accel = TpuAccelerator()
    hint = sorted(actors)
    rng = np.random.default_rng(9)
    delays = rng.random(12) * 0.01

    from crdt_enc_tpu.ops import stream as stream_mod

    real_pipeline = stream_mod.run_striped_ingest_pipeline

    def jittered_pipeline(spans, split_fn, stripe_fn, assemble_fn,
                          reduce_fn, **kw):
        def slow_stripe(stripe, k, s):
            time.sleep(delays[(k + s) % len(delays)])
            return stripe_fn(stripe, k, s)

        return real_pipeline(
            spans, split_fn, slow_stripe, assemble_fn, reduce_fn, **kw
        )

    results = {}
    for n_producers in (1, 2, 4):
        streamed = ORSet()
        stream_mod.run_striped_ingest_pipeline = jittered_pipeline
        try:
            ok = accel.fold_encrypted_stream(
                streamed, key, blobs, actors_hint=hint, n_chunks=8,
                n_producers=n_producers,
            )
        finally:
            stream_mod.run_striped_ingest_pipeline = real_pipeline
        assert ok, f"pipeline declined at n_producers={n_producers}"
        results[n_producers] = codec.pack(streamed.to_obj())
    for n_producers, got in results.items():
        assert got == host_bytes, f"divergence at n_producers={n_producers}"


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
    from crdt_enc_tpu.models import ORSet
    from crdt_enc_tpu.parallel import TpuAccelerator, mesh as pmesh
    from crdt_enc_tpu.parallel import session as psession

    mesh = _mesh_or_skip()
    # tiny promotion threshold so the small workload leaves BUFFER mode
    monkeypatch.setattr(psession, "BUFFER_BYTES", 256)

    key, blobs, actors, host = _encrypted_orset_workload(
        n_files=60, ops_per_file=8, R=5, E=24, seed=13
    )
    host_bytes = codec.pack(host.to_obj())
    hint = sorted(actors)

    accel = TpuAccelerator(mesh=mesh)
    assert accel.sharded_stream  # auto-on with an active mesh

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

    sharded = ORSet()
    ok = accel.fold_encrypted_stream(
        sharded, key, blobs, actors_hint=hint, n_chunks=6, n_producers=2,
    )
    assert ok and calls, "sharded streaming fold did not engage"
    assert codec.pack(sharded.to_obj()) == host_bytes

    single = ORSet()
    ok = TpuAccelerator().fold_encrypted_stream(
        single, key, blobs, actors_hint=hint, n_chunks=6,
    )
    assert ok
    assert codec.pack(single.to_obj()) == host_bytes


def test_sharded_stream_into_existing_state(monkeypatch):
    """The sharded stream's finish combine uses op-APPLY semantics
    against the live state (retire_rm=False partial reduction): remove
    horizons streamed through the mesh still kill pre-existing entries,
    and stale dots are still rejected."""
    _native_crypto_or_skip()
    from crdt_enc_tpu.backends.xchacha import decrypt_blobs
    from crdt_enc_tpu.models import ORSet
    from crdt_enc_tpu.models.orset import AddOp, RmOp
    from crdt_enc_tpu.models.vclock import Dot, VClock
    from crdt_enc_tpu.parallel import TpuAccelerator
    from crdt_enc_tpu.parallel import session as psession

    mesh = _mesh_or_skip()
    monkeypatch.setattr(psession, "BUFFER_BYTES", 256)

    key, blobs, actors, _ = _encrypted_orset_workload(
        n_files=48, ops_per_file=8, R=4, E=16, seed=29
    )
    pre = [(b"\x77" * 16, 1, 3), (b"\x78" * 16, 2, 5)]
    streamed = ORSet()
    host = ORSet()
    for a, c, m in pre:
        op = AddOp(m, Dot(a, c))
        streamed.apply(op)
        host.apply(op)
    for raw in decrypt_blobs(key, blobs):
        for o in codec.unpack(raw):
            if o[0] == 0:
                host.apply(AddOp(o[1], Dot.from_obj(o[2])))
            else:
                host.apply(RmOp(o[1], VClock.from_obj(o[2])))

    accel = TpuAccelerator(mesh=mesh)
    ok = accel.fold_encrypted_stream(
        streamed, key, blobs, actors_hint=sorted(actors), n_chunks=5,
    )
    assert ok
    assert codec.pack(streamed.to_obj()) == codec.pack(host.to_obj())


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


# ------------------------------------------- unified work queue (stripes)


def test_striped_order_deterministic_with_random_delays():
    """Stripes claimed by 1/2/4 producers with randomized stripe delays
    still reduce in strict chunk order, with each chunk's parts
    assembled in stripe order."""
    rng = np.random.default_rng(3)
    delays = rng.random(40) * 0.004

    for producers in (1, 2, 4):
        order = []

        def split(span, k):
            return [(k, s) for s in range(1 + k % 3)]

        def stripe(item, k, s):
            time.sleep(delays[(k * 3 + s) % len(delays)])
            assert item == (k, s)
            return ("part", k, s)

        def assemble(parts, span, k):
            assert parts == [("part", k, s) for s in range(1 + k % 3)]
            return ("chunk", k)

        def reduce(item, k):
            assert item == ("chunk", k)
            order.append(k)

        K.run_striped_ingest_pipeline(
            list(range(18)), split, stripe, assemble, reduce,
            producers=producers, inline=False,
        )
        assert order == list(range(18)), (producers, order)


def test_striped_giant_stripe_does_not_block_peers():
    """One slow stripe occupies one worker while a second worker keeps
    claiming OTHER stripes — the file-granular claim contract (the old
    chunk-granular pool serialized everything behind the giant)."""
    started = []
    release = threading.Event()

    def split(span, k):
        return [0, 1] if k == 0 else [0]

    def stripe(item, k, s):
        started.append((k, s))
        if (k, s) == (0, 0):
            assert release.wait(10.0)
        return (k, s)

    def assemble(parts, span, k):
        return k

    done = []

    def reduce(item, k):
        done.append(k)

    t = threading.Thread(
        target=lambda: K.run_striped_ingest_pipeline(
            list(range(4)), split, stripe, assemble, reduce,
            producers=2, inline=False,
        )
    )
    t.start()
    deadline = time.monotonic() + 10.0
    # the second worker must make progress past the stalled stripe
    while len(started) < 4 and time.monotonic() < deadline:
        time.sleep(0.005)
    assert len(started) >= 4, started
    assert not done  # chunk order: nothing reduces before chunk 0
    release.set()
    t.join(10.0)
    assert done == [0, 1, 2, 3]


def test_striped_fault_propagates_and_joins_workers():
    before = threading.active_count()

    def split(span, k):
        return [0, 1]

    def stripe(item, k, s):
        if (k, s) == (2, 1):
            raise ValueError("boom at (2,1)")
        return 0

    with pytest.raises(K.PipelineError) as ei:
        K.run_striped_ingest_pipeline(
            list(range(8)), split, stripe, lambda p, sp, k: 0,
            lambda i, k: None, producers=3, inline=False,
        )
    assert isinstance(ei.value.__cause__, ValueError)
    deadline = time.monotonic() + 5.0
    while threading.active_count() > before and time.monotonic() < deadline:
        time.sleep(0.01)
    assert threading.active_count() <= before


def test_striped_consumer_error_cancels_pool():
    before = threading.active_count()

    def reduce(item, k):
        if k == 1:
            raise RuntimeError("consumer dies")

    with pytest.raises(RuntimeError):
        K.run_striped_ingest_pipeline(
            list(range(30)), lambda sp, k: [0], lambda it, k, s: 0,
            lambda p, sp, k: 0, reduce, producers=3, inline=False,
        )
    deadline = time.monotonic() + 5.0
    while threading.active_count() > before and time.monotonic() < deadline:
        time.sleep(0.01)
    assert threading.active_count() <= before


def test_striped_empty_chunks_and_empty_split():
    """Zero spans is a no-op; a split returning [] still emits the chunk
    (assemble sees no parts) and order holds."""
    K.run_striped_ingest_pipeline(
        [], lambda sp, k: [0], lambda it, k, s: 0, lambda p, sp, k: 0,
        lambda i, k: None, producers=2, inline=False,
    )
    order = []
    K.run_striped_ingest_pipeline(
        list(range(5)),
        lambda sp, k: [] if k % 2 else [0],
        lambda it, k, s: "p",
        lambda parts, sp, k: (k, parts),
        lambda item, k: order.append(item),
        producers=2, inline=False,
    )
    assert order == [(k, ["p"] if k % 2 == 0 else []) for k in range(5)]


def test_striped_inline_auto_on_single_core(monkeypatch):
    """producers==1 on a 1-core host runs the whole pipeline inline —
    no worker threads — and still byte-identically (order + parts)."""
    import crdt_enc_tpu.ops.stream as stream_mod

    monkeypatch.setattr(stream_mod.os, "cpu_count", lambda: 1)
    spawned = []
    real_thread = threading.Thread

    class SpyThread(real_thread):
        def __init__(self, *a, **kw):
            spawned.append(kw.get("name"))
            super().__init__(*a, **kw)

    monkeypatch.setattr(stream_mod.threading, "Thread", SpyThread)
    order = []
    K.run_striped_ingest_pipeline(
        list(range(6)), lambda sp, k: [0, 1],
        lambda it, k, s: (k, s),
        lambda parts, sp, k: (k, parts),
        lambda item, k: order.append(item),
        producers=1,
    )
    assert order == [(k, [(k, 0), (k, 1)]) for k in range(6)]
    assert spawned == []  # inline: not a single worker thread
    # explicit inline=False still threads even on one core
    K.run_striped_ingest_pipeline(
        list(range(2)), lambda sp, k: [0], lambda it, k, s: 0,
        lambda p, sp, k: 0, lambda i, k: None, producers=1, inline=False,
    )
    assert spawned  # the forced path spawned its worker


def test_stream_counters_pinned_on_striped_path():
    """bytes_decrypted on the accel streaming front door equals EXACTLY
    the byte sum of the encrypted blobs (counted only after a stripe's
    decrypt succeeds), and the host/buffer regime issues zero h2d — the
    attribution marginals' inputs stay trustworthy (ISSUE 13 audit)."""
    _native_crypto_or_skip()
    from crdt_enc_tpu.models import ORSet
    from crdt_enc_tpu.parallel import TpuAccelerator

    key, blobs, actors, host = _encrypted_orset_workload(seed=5)
    accel = TpuAccelerator()
    trace.reset()
    state = ORSet()
    assert accel.fold_encrypted_stream(
        state, key, blobs, actors_hint=sorted(actors), n_chunks=4,
    )
    snap = trace.snapshot()
    assert snap["counters"].get("bytes_decrypted", 0) == sum(
        len(b) for b in blobs
    )
    # tiny workload stays in the BUFFER regime; its one device hop is
    # the dense fold: the state-plane upload — exactly clock (R·4) +
    # add/rm planes (2·E·R·4) for this E=12, R=5 shape — plus the 240
    # op rows' columns (13 B a row, padded to the 256-row class), and
    # the same planes pulled back.  A drift here means an unaccounted
    # (or double-counted) device hop appeared.
    planes = 5 * 4 + 2 * 12 * 5 * 4
    assert snap["counters"].get("h2d_bytes", 0) == planes + 13 * 256
    assert snap["counters"].get("d2h_bytes", 0) == planes
    assert codec.pack(state.to_obj()) == codec.pack(host.to_obj())
    # a failed decrypt (wrong key) counts NOTHING
    trace.reset()
    from crdt_enc_tpu.backends.xchacha import AeadError

    with pytest.raises(AeadError):
        accel.fold_encrypted_stream(
            ORSet(), secrets.token_bytes(32), blobs,
            actors_hint=sorted(actors), n_chunks=4,
        )
    assert trace.snapshot()["counters"].get("bytes_decrypted", 0) == 0


def test_session_fresh_fast_init_matches_general_path():
    """The fresh-state sorted-hint fast init must agree with the general
    construction (actor table, R, clock0) and fold byte-identically when
    the hint arrives UNSORTED (general path) vs sorted (fast path)."""
    _native_crypto_or_skip()
    from crdt_enc_tpu.models import ORSet
    from crdt_enc_tpu.parallel import TpuAccelerator
    from crdt_enc_tpu.parallel.session import OrsetFoldSession

    key, blobs, actors, host = _encrypted_orset_workload(seed=11)
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

    results = {}
    for hint in (sorted(actors), list(reversed(sorted(actors)))):
        state = ORSet()
        assert accel.fold_encrypted_stream(
            state, key, blobs, actors_hint=hint, n_chunks=4
        )
        results[tuple(hint)] = codec.pack(state.to_obj())
    assert len(set(results.values())) == 1
    assert next(iter(results.values())) == codec.pack(host.to_obj())


def test_session_member_collision_declines_on_bytes_path():
    """1 == True as members: the bytes-keyed remap must decline exactly
    like the legacy object remap (the dense planes cannot represent the
    collision), and the caller's fallback still folds correctly."""
    _native_crypto_or_skip()
    from crdt_enc_tpu.backends.xchacha import encrypt_blob
    from crdt_enc_tpu.models import ORSet
    from crdt_enc_tpu.parallel import TpuAccelerator

    key = secrets.token_bytes(32)
    actor = b"\x01" * 16
    blobs = [
        encrypt_blob(key, codec.pack([[0, 1, [actor, 1]]])),
        encrypt_blob(key, codec.pack([[0, True, [actor, 2]]])),
    ]
    accel = TpuAccelerator()
    state = ORSet()
    ok = accel.fold_encrypted_stream(
        state, key, blobs, actors_hint=[actor], n_chunks=1
    )
    assert not ok  # declined, state untouched — caller replays per-op
    assert not state.entries
