"""Chunked fold sessions (parallel/session.py) and the pipelined bulk
ingest (core._read_remote_ops_pipelined): every mode must land byte-equal
to the per-op host loop, chunk boundaries must not show, and declines /
races must degrade without losing data."""

import asyncio

import numpy as np
import pytest

from crdt_enc_tpu import ops as K
from crdt_enc_tpu.backends import (
    IdentityCryptor,
    MemoryRemote,
    MemoryStorage,
    PlainKeyCryptor,
)
from crdt_enc_tpu.core import (
    Core,
    OpenOptions,
    gcounter_adapter,
    orset_adapter,
    pncounter_adapter,
)
from crdt_enc_tpu.models import ORSet, PNCounter, canonical_bytes
from crdt_enc_tpu.models.orset import AddOp, RmOp
from crdt_enc_tpu.models.vclock import Dot, VClock
from crdt_enc_tpu.parallel import TpuAccelerator
from crdt_enc_tpu.parallel.session import (
    OrsetFoldSession,
    SessionDeclined,
    apply_batch_planes_host,
    open_fold_session,
)
from crdt_enc_tpu.utils import codec
from crdt_enc_tpu.utils.versions import DEFAULT_DATA_VERSION_1

ACTORS = [bytes([i + 1]) * 16 for i in range(5)]


def run(coro):
    return asyncio.run(coro)


# ---- session unit level ----------------------------------------------------


def _history(n_ops, n_members, seed=0, rm_every=7):
    """A well-formed multi-actor op history + the host-folded state."""
    rng = np.random.default_rng(seed)
    state = ORSet()
    ops = []
    for i in range(n_ops):
        a = ACTORS[int(rng.integers(len(ACTORS)))]
        m = int(rng.integers(n_members))
        if i % rm_every == rm_every - 1 and state.contains(m):
            op = state.rm_ctx(m)
        else:
            op = state.add_ctx(a, m)
        state.apply(op)
        ops.append(op)
    return state, ops


def _payloads(ops, per_file=10):
    """Op files exactly as the wire carries them (msgpack op arrays)."""
    out = []
    for lo in range(0, len(ops), per_file):
        out.append(codec.pack([op.to_obj() for op in ops[lo : lo + per_file]]))
    return out


def _run_session(ops, *, chunk_files, force_mode=None, state=None):
    accel = TpuAccelerator(min_device_batch=1)
    state = state if state is not None else ORSet()
    session = OrsetFoldSession(accel, state, actors_hint=ACTORS)
    if force_mode == "host_reduce":
        session._buffered_bytes = 10**9  # promote on first feed
    elif force_mode == "device_stream":
        session._buffered_bytes = 10**9
        OrsetFoldSession_promote_to_device(session)
    payloads = _payloads(ops)
    for lo in range(0, len(payloads), chunk_files):
        session.feed(payloads[lo : lo + chunk_files])
    return session.finish()


def OrsetFoldSession_promote_to_device(session):
    # force the device path regardless of plane size
    import crdt_enc_tpu.parallel.session as S

    session._orig_cells = S.HOST_PLANE_CELLS
    S.HOST_PLANE_CELLS = -1


@pytest.fixture(autouse=True)
def _restore_thresholds():
    import crdt_enc_tpu.parallel.session as S

    cells = S.HOST_PLANE_CELLS
    yield
    S.HOST_PLANE_CELLS = cells


@pytest.mark.parametrize("force_mode", [None, "host_reduce", "device_stream"])
@pytest.mark.parametrize("chunk_files", [1, 3, 50])
def test_session_modes_match_host(force_mode, chunk_files):
    host, ops = _history(400, 23, seed=3)
    folded = _run_session(ops, chunk_files=chunk_files, force_mode=force_mode)
    assert canonical_bytes(folded) == canonical_bytes(host), (
        force_mode,
        chunk_files,
    )


def test_device_stream_pallas_route_matches_host():
    """The DEVICE_STREAM fold's Pallas route (real-TPU default; interpret
    mode here) must byte-match the host fold — including the
    retire_rm=False discipline the session relies on."""
    import crdt_enc_tpu.parallel.session as S

    host, ops = _history(400, 23, seed=6)
    S.FORCE_PALLAS_STREAM = "interpret"
    try:
        folded = _run_session(ops, chunk_files=3, force_mode="device_stream")
    finally:
        S.FORCE_PALLAS_STREAM = None
    assert canonical_bytes(folded) == canonical_bytes(host)


@pytest.mark.parametrize("force_mode", ["host_reduce", "device_stream"])
def test_session_into_existing_state_matches_host(force_mode):
    """Folding a tail into a state that already holds a prefix (the
    snapshot-resume shape) — including removes whose targets live only
    in the prefix state."""
    host, ops = _history(300, 17, seed=5, rm_every=5)
    prefix = ORSet()
    for op in ops[:120]:
        prefix.apply(op)
    folded = _run_session(
        ops[120:], chunk_files=2, force_mode=force_mode,
        state=ORSet.from_obj(prefix.to_obj()),
    )
    assert canonical_bytes(folded) == canonical_bytes(host)


def test_host_and_device_combine_never_diverge():
    rng = np.random.default_rng(7)
    for _ in range(20):
        E, R = int(rng.integers(1, 12)), int(rng.integers(1, 6))
        clock0 = rng.integers(0, 9, R).astype(np.int32)
        add0 = rng.integers(0, 9, (E, R)).astype(np.int32)
        rm0 = rng.integers(0, 9, (E, R)).astype(np.int32)
        add_b = rng.integers(0, 12, (E, R)).astype(np.int32)
        rm_b = rng.integers(0, 12, (E, R)).astype(np.int32)
        h = apply_batch_planes_host(clock0, add0, rm0, add_b, rm_b)
        d = K.orset_apply_batch_planes(clock0, add0, rm0, add_b, rm_b)
        for a, b in zip(h, d):
            assert np.array_equal(a, np.asarray(b))


def test_counter_session_matches_host():
    accel = TpuAccelerator(min_device_batch=1)
    host = PNCounter()
    ops = []
    for i in range(200):
        a = ACTORS[i % 3]
        op = host.inc(a, i + 1) if i % 4 else host.dec(a, 2)
        host.apply(op)
        ops.append([op[0], op[1].to_obj()])
    payloads = [codec.pack(ops[lo : lo + 9]) for lo in range(0, len(ops), 9)]
    state = PNCounter()
    session = open_fold_session(accel, state, actors_hint=ACTORS)
    for p in payloads:
        session.feed([p])
    session.finish()
    assert canonical_bytes(state) == canonical_bytes(host)
    assert state.read() == host.read()


def test_session_decline_leaves_chunk_unconsumed():
    accel = TpuAccelerator(min_device_batch=1)
    state = ORSet()
    session = OrsetFoldSession(accel, state, actors_hint=ACTORS)
    host, ops = _history(40, 7, seed=2)
    session.feed(_payloads(ops))
    with pytest.raises(SessionDeclined):
        session.feed([b"\xc1 definitely not msgpack ops"])
    # the good chunk still lands
    folded = session.finish()
    assert canonical_bytes(folded) == canonical_bytes(host)


# ---- through the live core -------------------------------------------------


def make_opts(remote, adapter=None, accel=None):
    kw = {"accelerator": accel} if accel else {}
    return OpenOptions(
        storage=MemoryStorage(remote),
        cryptor=IdentityCryptor(),
        key_cryptor=PlainKeyCryptor(),
        adapter=adapter or orset_adapter(),
        supported_data_versions=(DEFAULT_DATA_VERSION_1,),
        current_data_version=DEFAULT_DATA_VERSION_1,
        create=True,
        **kw,
    )


from _ingest_doors import chunked as _chunked_storage  # noqa: E402


@pytest.mark.parametrize("files_per_chunk", [1, 5, 64])
def test_pipelined_ingest_matches_host_core(files_per_chunk):
    async def go():
        remote = MemoryRemote()
        producer = await Core.open(make_opts(remote))
        for w in range(40):
            await producer.update(
                lambda s, w=w: s.add_ctx(producer.actor_id, w % 19)
            )
        for m in (3, 8):
            await producer.update(lambda s, m=m: s.rm_ctx(m))

        host = await Core.open(make_opts(remote))
        await host.read_remote()

        reader_opts = make_opts(remote, accel=TpuAccelerator(min_device_batch=1))
        reader_opts.storage = _chunked_storage(remote, files_per_chunk)
        reader = await Core.open(reader_opts)
        await reader.read_remote()
        assert reader.with_state(canonical_bytes) == host.with_state(
            canonical_bytes
        )
        # and the stream is re-entrant: a second read is a no-op
        await reader.read_remote()
        assert reader.with_state(canonical_bytes) == host.with_state(
            canonical_bytes
        )

    run(go())


def test_pipelined_ingest_counters(files_per_chunk=4):
    async def go():
        remote = MemoryRemote()
        producer = await Core.open(make_opts(remote, adapter=pncounter_adapter()))
        for i in range(30):
            await producer.update(
                lambda s, i=i: s.inc(producer.actor_id, i + 1)
                if i % 3
                else s.dec(producer.actor_id, 1)
            )
        host = await Core.open(make_opts(remote, adapter=pncounter_adapter()))
        await host.read_remote()
        reader_opts = make_opts(
            remote, adapter=pncounter_adapter(),
            accel=TpuAccelerator(min_device_batch=1),
        )
        reader_opts.storage = _chunked_storage(remote, files_per_chunk)
        reader = await Core.open(reader_opts)
        await reader.read_remote()
        assert reader.with_state(canonical_bytes) == host.with_state(
            canonical_bytes
        )
        assert reader.with_state(lambda s: s.read()) == host.with_state(
            lambda s: s.read()
        )

    run(go())


def test_concurrent_apply_during_pipelined_ingest_survives():
    """A local write landing BETWEEN pipeline chunks must not be clobbered
    by the session's finish (the finish re-reads the state in its sync
    section; host-reduce re-masks against the current clock)."""

    async def go():
        remote = MemoryRemote()
        producer = await Core.open(make_opts(remote))
        for w in range(30):
            await producer.update(
                lambda s, w=w: s.add_ctx(producer.actor_id, w)
            )

        reader_opts = make_opts(remote, accel=TpuAccelerator(min_device_batch=1))
        base = _chunked_storage(remote, 5)
        reader_holder = {}

        class RacingStorage(type(base)):
            async def iter_op_chunks(self, wanted, max_bytes=1 << 30):
                n = 0
                async for chunk in super().iter_op_chunks(wanted, max_bytes):
                    yield chunk
                    n += 1
                    if n == 2 and "core" in reader_holder:
                        # a local write lands mid-ingest
                        core = reader_holder["core"]
                        await core.update(
                            lambda s: s.add_ctx(core.actor_id, b"local-mid")
                        )

        racing = RacingStorage(remote)
        reader_opts.storage = racing
        reader = await Core.open(reader_opts)
        reader_holder["core"] = reader
        await reader.read_remote()
        # both the remote history AND the mid-ingest local write survive
        assert reader.with_state(lambda s: s.contains(b"local-mid"))
        for w in range(30):
            assert reader.with_state(lambda s, w=w: s.contains(w)), w

    run(go())


def test_empty_crdt_falls_back_to_legacy():
    """No columnar session exists for EmptyCrdt-style adapters — the
    pipelined path must bow out cleanly."""
    from crdt_enc_tpu.core import empty_adapter

    async def go():
        remote = MemoryRemote()
        producer = await Core.open(make_opts(remote, adapter=empty_adapter()))
        for _ in range(20):
            await producer.apply_ops([None])
        reader = await Core.open(
            make_opts(
                remote, adapter=empty_adapter(),
                accel=TpuAccelerator(min_device_batch=1),
            )
        )
        await reader.read_remote()  # must not raise

    run(go())


def test_concurrent_new_actor_before_finish():
    """An apply from an actor unknown at session init landing before
    finish() must neither crash (the state planes then carry more replica
    columns than the batch planes) nor be clobbered by the writeback."""
    host, ops = _history(200, 11, seed=8)
    accel = TpuAccelerator(min_device_batch=1)
    state = ORSet()
    session = OrsetFoldSession(accel, state, actors_hint=ACTORS)
    session._buffered_bytes = 10**9  # promote to host_reduce on first feed
    payloads = _payloads(ops)
    for lo in range(0, len(payloads), 4):
        session.feed(payloads[lo : lo + 4])
    # a brand-new actor writes directly to the state mid-session
    newcomer = b"\xaa" * 16
    late = state.add_ctx(newcomer, b"late-member")
    state.apply(late)
    host.apply(AddOp(b"late-member", late.dot))
    folded = session.finish()
    assert folded.contains(b"late-member")
    assert canonical_bytes(folded) == canonical_bytes(host)


def test_mid_stream_decline_keeps_version_order():
    """A chunk the native decoder declines (here: an op whose dot actor
    appears in no op directory or state) flips the pipeline to per-op
    folds — chunks already validated and in flight must fold IN ORDER
    first, or the version-gap check would trip on a newer chunk."""

    async def go():
        remote = MemoryRemote()
        producer = await Core.open(make_opts(remote))
        fake = b"\xbb" * 16  # a dot actor with no op dir: decoder declines
        for w in range(30):
            if w == 12:
                await producer.apply_ops([AddOp(999, Dot(fake, 1))])
            else:
                await producer.update(
                    lambda s, w=w: s.add_ctx(producer.actor_id, w)
                )
        host = await Core.open(make_opts(remote))
        await host.read_remote()
        reader_opts = make_opts(remote, accel=TpuAccelerator(min_device_batch=1))
        reader_opts.storage = _chunked_storage(remote, 3)
        reader = await Core.open(reader_opts)
        await reader.read_remote()
        assert reader.with_state(canonical_bytes) == host.with_state(
            canonical_bytes
        )
        assert reader.with_state(lambda s: s.contains(999))

    run(go())


def test_scan_error_propagates_not_hangs(tmp_path):
    """A window's read that dies on its thread must deliver its failure
    to the chunk emitter, not leave it awaiting a result that never comes."""
    import os as _os

    import crdt_enc_tpu.backends.fs as fsmod
    from crdt_enc_tpu import native
    from crdt_enc_tpu.backends.fs import FsStorage

    async def go():
        s = FsStorage(str(tmp_path / "l"), str(tmp_path / "remote"))
        actor = b"\x07" * 16
        for v in range(1, 8):
            await s.store_ops(actor, v, bytes([v]) * 30)

        lib = native.load()
        real_rf = fsmod._read_file

        def failing_rf(path):
            if path.endswith(_os.sep + "4"):
                raise PermissionError(path)
            return real_rf(path)

        import unittest.mock as mock

        # a file that is there and unreadable: the window's one call says
        # EACCES and the per-file reader raises
        with mock.patch.object(lib, "load_op_window", lambda *a: 13), \
                mock.patch.object(fsmod, "_read_file", failing_rf):
            with pytest.raises(PermissionError):
                chunks = []
                async for c in s.iter_op_chunks([(actor, 1)]):
                    chunks.append(c)

    # a hang would block forever; wrap in a timeout to fail loudly instead
    async def with_timeout():
        await asyncio.wait_for(go(), timeout=30)

    run(with_timeout())


@pytest.mark.parametrize("force_mode", ["host_reduce", "device_stream"])
def test_session_keeps_untouched_preexisting_members(force_mode):
    """Regression (confirmed data loss): a pre-existing member whose dot
    is OLDER than the batch's dots for the same actor, and which the
    batch never mentions, must survive the session.  The zero-seeded
    device planes' per-actor add maxima cover such dots, so combining
    them with the CvRDT merge (instead of op-apply semantics) deleted
    the member; `_history`'s small cycling member pool masked it because
    every prefix member was re-added in the tail."""
    actor = ACTORS[0]
    base = ORSet()
    base.apply(base.add_ctx(actor, "old-untouched"))
    host = ORSet.from_obj(base.to_obj())
    ops = []
    for i in range(40):  # later dots by the SAME actor, other members
        op = host.add_ctx(actor, f"new-{i}")
        host.apply(op)
        ops.append(op)
    folded = _run_session(
        ops, chunk_files=2, force_mode=force_mode,
        state=ORSet.from_obj(base.to_obj()),
    )
    assert folded.contains("old-untouched"), force_mode
    assert canonical_bytes(folded) == canonical_bytes(host), force_mode


def test_encrypted_stream_device_mode_matches_host(monkeypatch):
    """ISSUE 1 differential: the full overlapped pipeline (decrypting
    producer → session consumer) forced through the DEVICE_STREAM
    donated-fold mode lands byte-identical to the per-op host loop —
    streaming ≡ whole-batch on the device path too."""
    import crdt_enc_tpu.parallel.session as S
    from _ingest_doors import read_pipelined, seed_remote
    from crdt_enc_tpu import native

    try:
        native.load()
    except RuntimeError as e:
        pytest.skip(f"native crypto library unavailable: {e}")
    monkeypatch.setattr(S, "BUFFER_BYTES", 0)  # promote on first chunk
    monkeypatch.setattr(S, "HOST_PLANE_CELLS", -1)  # ... to device planes
    host, ops = _history(300, 17, seed=6)
    # every actor writes its own adds (removes ride with the first): the
    # session's actor table is the storage listing
    by_actor = {a: [] for a in ACTORS}
    for op in ops:
        by_actor[op.dot.actor if isinstance(op, AddOp) else ACTORS[0]].append(op)
    files = [
        (a, codec.unpack(p)) for a in ACTORS for p in _payloads(by_actor[a])
    ]
    modes = []
    real_finish = OrsetFoldSession.finish

    def spy_finish(self):
        modes.append(self.mode)
        return real_finish(self)

    monkeypatch.setattr(OrsetFoldSession, "finish", spy_finish)

    async def go():
        remote, _ = await seed_remote(files)
        return await read_pipelined(remote, 6)

    reader = run(go())
    assert modes == ["device_stream"]
    assert reader.with_state(canonical_bytes) == canonical_bytes(host)
