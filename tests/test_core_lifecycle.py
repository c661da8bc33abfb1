"""End-to-end replica lifecycle: the multi-replica convergence tests the
reference's architecture enables but never shipped (SURVEY.md §4).

N cores with distinct local storage share one remote (memory dict or
tmpdir); convergence flows purely through stored files — no other channel
exists, exactly like replicas under a file-sync tool.
"""

import asyncio
import uuid

import pytest

from crdt_enc_tpu.backends import (
    FsStorage,
    IdentityCryptor,
    MemoryRemote,
    MemoryStorage,
    PlainKeyCryptor,
)
from crdt_enc_tpu.core import (
    Core,
    CoreError,
    OpenOptions,
    gcounter_adapter,
    orset_adapter,
)
from crdt_enc_tpu.models import canonical_bytes
from crdt_enc_tpu.utils.versions import DEFAULT_DATA_VERSION_1


def run(coro):
    return asyncio.run(coro)


def make_opts(storage, adapter, create=True):
    return OpenOptions(
        storage=storage,
        cryptor=IdentityCryptor(),
        key_cryptor=PlainKeyCryptor(),
        adapter=adapter,
        supported_data_versions=(DEFAULT_DATA_VERSION_1,),
        current_data_version=DEFAULT_DATA_VERSION_1,
        create=create,
    )


@pytest.fixture(params=["memory", "fs"])
def storage_factory(request, tmp_path):
    """Returns a () -> Storage factory where all instances share a remote."""
    if request.param == "memory":
        remote = MemoryRemote()
        return lambda: MemoryStorage(remote)
    remote_dir = tmp_path / "remote"
    counter = iter(range(1000))
    return lambda: FsStorage(str(tmp_path / f"local{next(counter)}"), str(remote_dir))


def test_open_requires_create(storage_factory):
    async def go():
        with pytest.raises(CoreError):
            await Core.open(make_opts(storage_factory(), gcounter_adapter(), create=False))

    run(go())


def test_open_persists_identity(storage_factory):
    async def go():
        storage = storage_factory()
        c1 = await Core.open(make_opts(storage, gcounter_adapter()))
        actor = c1.actor_id
        # reopening the same local storage must restore the same actor
        c2 = await Core.open(make_opts(storage, gcounter_adapter(), create=False))
        assert c2.actor_id == actor

    run(go())


def test_key_bootstrap_and_share(storage_factory):
    async def go():
        c1 = await Core.open(make_opts(storage_factory(), gcounter_adapter()))
        assert c1.info().has_latest_key
        # a second replica joining the same remote adopts the existing key
        c2 = await Core.open(make_opts(storage_factory(), gcounter_adapter()))
        k1 = c1._data.keys.latest_key()
        k2 = c2._data.keys.latest_key()
        assert k1 is not None and k2 is not None
        assert k1.id == k2.id and k1.material == k2.material

    run(go())


def test_two_replica_convergence(storage_factory):
    async def go():
        c1 = await Core.open(make_opts(storage_factory(), gcounter_adapter()))
        c2 = await Core.open(make_opts(storage_factory(), gcounter_adapter()))
        await c1.apply_ops([c1.with_state(lambda s: s.inc(c1.actor_id, 5))])
        await c2.apply_ops([c2.with_state(lambda s: s.inc(c2.actor_id, 7))])
        await c1.read_remote()
        await c2.read_remote()
        assert c1.with_state(lambda s: s.read()) == 12
        assert c2.with_state(lambda s: s.read()) == 12
        assert c1.with_state(canonical_bytes) == c2.with_state(canonical_bytes)

    run(go())


def test_orset_convergence_and_remove(storage_factory):
    async def go():
        c1 = await Core.open(make_opts(storage_factory(), orset_adapter()))
        c2 = await Core.open(make_opts(storage_factory(), orset_adapter()))
        await c1.apply_ops([c1.with_state(lambda s: s.add_ctx(c1.actor_id, b"x"))])
        await c2.read_remote()
        assert c2.with_state(lambda s: s.contains(b"x"))
        await c2.apply_ops([c2.with_state(lambda s: s.rm_ctx(b"x"))])
        await c1.read_remote()
        assert not c1.with_state(lambda s: s.contains(b"x"))
        assert c1.with_state(canonical_bytes) == c2.with_state(canonical_bytes)

    run(go())


def test_compact_roundtrip(storage_factory):
    """The reference's own compacted states couldn't be read back
    (SURVEY.md §3.4 defect 1).  Ours must: compact, then a fresh replica
    joins from the snapshot alone."""

    async def go():
        c1 = await Core.open(make_opts(storage_factory(), orset_adapter()))
        for m in (b"a", b"b", b"c"):
            await c1.apply_ops([c1.with_state(lambda s, m=m: s.add_ctx(c1.actor_id, m))])
        await c1.apply_ops([c1.with_state(lambda s: s.rm_ctx(b"b"))])
        await c1.compact()

        # defect-2 fix: ALL covered op files must be gone, not just the last
        storage = storage_factory()
        assert await storage.list_op_actors() == []
        assert len(await storage.list_state_names()) == 1

        c3 = await Core.open(make_opts(storage_factory(), orset_adapter()))
        await c3.read_remote()
        assert c3.with_state(lambda s: s.members()) == [b"a", b"c"]
        assert c3.with_state(canonical_bytes) == c1.with_state(canonical_bytes)

    run(go())


def test_compact_then_new_ops_resume(storage_factory):
    async def go():
        c1 = await Core.open(make_opts(storage_factory(), gcounter_adapter()))
        await c1.apply_ops([c1.with_state(lambda s: s.inc(c1.actor_id, 3))])
        await c1.compact()
        # ops continue after compaction; cursors must resume past the snapshot
        await c1.apply_ops([c1.with_state(lambda s: s.inc(c1.actor_id, 4))])
        c2 = await Core.open(make_opts(storage_factory(), gcounter_adapter()))
        await c2.read_remote()
        assert c2.with_state(lambda s: s.read()) == 7
        # second compaction folds snapshot + tail into one fresh snapshot
        await c2.compact()
        c3 = await Core.open(make_opts(storage_factory(), gcounter_adapter()))
        await c3.read_remote()
        assert c3.with_state(lambda s: s.read()) == 7

    run(go())


def test_duplicate_read_is_idempotent(storage_factory):
    async def go():
        c1 = await Core.open(make_opts(storage_factory(), gcounter_adapter()))
        await c1.apply_ops([c1.with_state(lambda s: s.inc(c1.actor_id, 2))])
        c2 = await Core.open(make_opts(storage_factory(), gcounter_adapter()))
        await c2.read_remote()
        await c2.read_remote()  # replay: version-skew skip must absorb it
        assert c2.with_state(lambda s: s.read()) == 2

    run(go())


def test_meta_files_garbage_collected(storage_factory):
    async def go():
        storage = storage_factory()
        await Core.open(make_opts(storage, gcounter_adapter()))
        await Core.open(make_opts(storage_factory(), gcounter_adapter()))
        # store-then-delete keeps the meta family compact: after both opens
        # settle, each replica folded to few (≤2 with concurrent writers) files
        names = await storage.list_remote_meta_names()
        assert 1 <= len(names) <= 2

    run(go())


def test_concurrent_writers_serialized(storage_factory):
    async def go():
        c1 = await Core.open(make_opts(storage_factory(), gcounter_adapter()))

        async def writer(amount):
            # update() derives the dot under the writer lock — concurrent
            # with_state+apply_ops would race on dot derivation
            await c1.update(lambda s: s.inc(c1.actor_id, amount))

        await asyncio.gather(*(writer(i + 1) for i in range(5)))
        c2 = await Core.open(make_opts(storage_factory(), gcounter_adapter()))
        await c2.read_remote()
        assert c2.with_state(lambda s: s.read()) == 15

    run(go())


def test_key_rotation_old_data_stays_readable(storage_factory):
    """rotate_key: new writes seal with the new key, old blobs stay
    readable via their recorded key id, and the rotation converges to
    replicas that join later (the LUKS property, README.md:19-25)."""

    async def go():
        c1 = await Core.open(make_opts(storage_factory(), gcounter_adapter()))
        await c1.update(lambda s: s.inc(c1.actor_id, 3))
        old = c1._data.keys.latest_key()

        new = await c1.rotate_key()
        assert new.id != old.id
        assert c1._data.keys.latest_key().id == new.id
        # the superseded key remains resolvable for old blobs
        assert c1._data.keys.get_key(old.id) is not None

        await c1.update(lambda s: s.inc(c1.actor_id, 4))  # sealed w/ new key

        # a replica joining after the rotation reads both generations
        c2 = await Core.open(make_opts(storage_factory(), gcounter_adapter()))
        assert c2._data.keys.latest_key().id == new.id
        await c2.read_remote()
        assert c2.with_state(lambda s: s.read()) == 7

        # compaction re-seals everything under the latest key
        await c2.compact()
        c3 = await Core.open(make_opts(storage_factory(), gcounter_adapter()))
        await c3.read_remote()
        assert c3.with_state(lambda s: s.read()) == 7

    run(go())


def test_rotation_race_min_id_tie_break(storage_factory):
    """Two replicas rotate concurrently: both keys land in the CRDT and
    every replica deterministically agrees on the same latest
    (min-id tie-break, reference key_cryptor.rs:59-70)."""

    async def go():
        c1 = await Core.open(make_opts(storage_factory(), gcounter_adapter()))
        c2 = await Core.open(make_opts(storage_factory(), gcounter_adapter()))
        # both rotate without seeing each other's rotation
        k1 = await c1.rotate_key()
        k2 = await c2.rotate_key()
        await c1.read_remote()
        await c2.read_remote()
        expect = min(k1.id, k2.id)
        assert c1._data.keys.latest_key().id == expect
        assert c2._data.keys.latest_key().id == expect
        # writes from both sides remain mutually readable
        await c1.update(lambda s: s.inc(c1.actor_id, 1))
        await c2.update(lambda s: s.inc(c2.actor_id, 2))
        await c1.read_remote()
        await c2.read_remote()
        assert c1.with_state(lambda s: s.read()) == 3
        assert c2.with_state(lambda s: s.read()) == 3

    run(go())


def test_rotation_vs_meta_ingestion_race_keeps_all_keys(storage_factory):
    """Regression: rotate_key's snapshot→register-write cycle suspends in
    the key cryptor's protect step (scrypt takes ~50ms); a remote Keys
    value merged during that window must NOT be causally superseded by
    the stale snapshot — that would permanently drop its key material and
    orphan every blob it sealed.  The _keys_lock serializes the two."""
    import asyncio as aio

    from crdt_enc_tpu.backends.plain_keys import PlainKeyCryptor

    class SlowKeyCryptor(PlainKeyCryptor):
        async def _protect(self, raw):
            await aio.sleep(0.05)  # model the scrypt window
            return raw

    async def go():
        c1 = await Core.open(make_opts(storage_factory(), gcounter_adapter()))
        # B opens BEFORE A's rotation, so B's key snapshot can't contain kA
        opts_b = make_opts(storage_factory(), gcounter_adapter())
        opts_b.key_cryptor = SlowKeyCryptor()
        c2 = await Core.open(opts_b)

        await c1.update(lambda s: s.inc(c1.actor_id, 1))
        kA = await c1.rotate_key()
        await c1.update(lambda s: s.inc(c1.actor_id, 2))  # sealed with kA

        # the race: B rotates (slow protect) while ingesting A's metadata
        await aio.gather(c2.rotate_key(), c2.read_remote())
        await c2.read_remote()
        assert c2._data.keys.get_key(kA.id) is not None, "kA material lost"
        assert c2.with_state(lambda s: s.read()) == 3  # kA blobs readable

        # and A still converges with B's rotation in the mix
        await c1.read_remote()
        assert c1._data.keys.get_key(kA.id) is not None

    run(go())


def test_a_load_over_one_calls_buffers_is_drained_natively(tmp_path, monkeypatch):
    """A load larger than ONE native call brings back is several calls,
    each going on where the last one stopped: the same files as one
    call's, and one native read however many calls it took.  A file that
    alone overflows the buffer is the per-file reader's, from there on."""
    from test_fs_native_steps import reads

    from crdt_enc_tpu.backends.fs import FsStorage
    from crdt_enc_tpu.utils import trace

    async def go():
        s = FsStorage(str(tmp_path / "l"), str(tmp_path / "remote"))
        actor = b"\x02" * 16
        blobs = [bytes([i]) * (200 + i) for i in range(9)]
        for v, b in enumerate(blobs, start=1):
            await s.store_ops(actor, v, b)
        expect = [(actor, v, blobs[v - 1]) for v in range(1, 10)]
        calls = []
        real = FsStorage._native_runs

        def counting(self, wanted, *args, **kw):
            calls.append(list(wanted))
            return real(self, wanted, *args, **kw)

        monkeypatch.setattr(FsStorage, "_native_runs", counting)
        for files, nbytes, n_calls in ((1024, 512, 5), (2, 1 << 20, 5), (9, 1 << 20, 1)):
            monkeypatch.setattr(FsStorage, "LOAD_RUNS_FILES", files)
            monkeypatch.setattr(FsStorage, "LOAD_RUNS_BYTES", nbytes)
            calls.clear()
            trace.reset()
            assert await s.load_ops([(actor, 1)]) == expect
            assert len(calls) == n_calls and reads() == (1, 0)
            assert calls[-1] == [(actor, 9 if n_calls > 1 else 1)]
        monkeypatch.setattr(FsStorage, "LOAD_RUNS_BYTES", 64)  # under every file
        calls.clear()
        trace.reset()
        assert await s.load_ops([(actor, 1)]) == expect
        assert len(calls) == 1 and reads() == (0, 1)
        trace.reset()

    run(go())


def test_a_file_removed_under_the_per_file_reader_ends_its_run(tmp_path, monkeypatch):
    """A file the sync tool takes away while the per-file reader is in
    its run ends the dense run cleanly there: the files before it are
    kept, nothing is raised, and a load that fell back reads the same."""
    import os as _os

    import crdt_enc_tpu.backends.fs as fsmod
    from crdt_enc_tpu import native
    from crdt_enc_tpu.backends.fs import FsStorage

    async def go():
        s = FsStorage(str(tmp_path / "l"), str(tmp_path / "remote"))
        actor, other = b"\x03" * 16, b"\x04" * 16
        blobs = [bytes([i]) * 50 for i in range(8)]
        for v, b in enumerate(blobs, start=1):
            await s.store_ops(actor, v, b)
        await s.store_ops(other, 1, b"whole")
        real_rf = fsmod._read_file

        def racy_rf(path):
            if path.endswith(_os.sep + "4") and _os.path.exists(path):
                _os.remove(path)  # gone between the third read and this one
            return real_rf(path)

        monkeypatch.setattr(fsmod, "_read_file", racy_rf)
        wanted = [(actor, 1), (other, 1)]
        expect = [(actor, v, blobs[v - 1]) for v in (1, 2, 3)]
        expect.append((other, 1, b"whole"))
        assert s._file_runs(wanted, 1024, 1 << 20) == (expect, [])
        monkeypatch.setattr(native.load(), "load_op_window", lambda *a: 11)
        assert await s.load_ops(wanted) == expect

    run(go())


def test_unreadable_op_file_raises_loudly(tmp_path, monkeypatch):
    """A present-but-unreadable op file is a real defect, not a race: the
    scan must raise, not silently truncate the log (reviewer finding).
    Unreadability is simulated by monkeypatching (chmod 0 would not bind
    when tests run as root): the one native call reports the errno and
    reads nothing, and the per-file reader hits the open error — the
    exact production sequence."""
    import os as _os

    import pytest

    import crdt_enc_tpu.backends.fs as fsmod
    from crdt_enc_tpu import native
    from crdt_enc_tpu.backends.fs import FsStorage

    async def go():
        s = FsStorage(str(tmp_path / "l"), str(tmp_path / "remote"))
        actor = b"\x05" * 16
        for v in range(1, 6):
            await s.store_ops(actor, v, bytes([v]) * 40)

        real_rf = fsmod._read_file

        def failing_rf(path):
            if path.endswith(_os.sep + "3"):
                raise PermissionError(path)
            return real_rf(path)

        monkeypatch.setattr(native.load(), "load_op_window", lambda *a: 13)  # EACCES
        monkeypatch.setattr(fsmod, "_read_file", failing_rf)
        with pytest.raises(PermissionError):
            await s.load_ops([(actor, 1)])

    run(go())
