"""Regressions for review findings: key-id envelope selection, durable
producer cursor, and race-free immutable op publishes."""

import asyncio
import hashlib

import pytest

from crdt_enc_tpu.backends import (
    FsStorage,
    IdentityCryptor,
    MemoryRemote,
    MemoryStorage,
    PlainKeyCryptor,
)
from crdt_enc_tpu.core import Core, Cryptor, OpenOptions, gcounter_adapter
from crdt_enc_tpu.utils import VersionBytes
from crdt_enc_tpu.utils.versions import (
    DEFAULT_DATA_VERSION_1,
    IDENTITY_DATA_VERSION_1,
    IDENTITY_KEY_VERSION_1,
)


class CheckedCryptor(IdentityCryptor):
    """Identity transport that *verifies the key*: wrong key ⇒ hard error,
    like a real AEAD tag failure."""

    async def encrypt(self, key: VersionBytes, data: bytes) -> bytes:
        key.ensure_version(IDENTITY_KEY_VERSION_1)
        tag = hashlib.sha3_256(key.content + data).digest()[:8]
        return VersionBytes(IDENTITY_DATA_VERSION_1, tag + data).serialize()

    async def decrypt(self, key: VersionBytes, data: bytes) -> bytes:
        key.ensure_version(IDENTITY_KEY_VERSION_1)
        body = (
            VersionBytes.deserialize(data)
            .ensure_version(IDENTITY_DATA_VERSION_1)
            .content
        )
        tag, payload = body[:8], body[8:]
        if hashlib.sha3_256(key.content + payload).digest()[:8] != tag:
            raise ValueError("wrong key (simulated AEAD tag mismatch)")
        return payload


def make_opts(storage, cryptor=None, create=True, **kw):
    return OpenOptions(
        storage=storage,
        cryptor=cryptor or CheckedCryptor(),
        key_cryptor=PlainKeyCryptor(),
        adapter=gcounter_adapter(),
        supported_data_versions=(DEFAULT_DATA_VERSION_1,),
        current_data_version=DEFAULT_DATA_VERSION_1,
        create=create,
        **kw,
    )


def test_concurrent_bootstrap_two_keys_both_decryptable():
    """Two replicas bootstrap disjoint keys before their remotes sync (the
    syncthing split-brain); after sync each must decrypt the other's files
    via the key id recorded in the envelope."""

    async def go():
        ra, rb = MemoryRemote(), MemoryRemote()
        ca = await Core.open(make_opts(MemoryStorage(ra)))
        cb = await Core.open(make_opts(MemoryStorage(rb)))
        await ca.update(lambda s: s.inc(ca.actor_id, 3))
        await cb.update(lambda s: s.inc(cb.actor_id, 4))
        # the sync tool merges the trees (union of immutable files)
        ra.metas.update(rb.metas)
        ra.states.update(rb.states)
        for actor, log in rb.ops.items():
            ra.ops.setdefault(actor, {}).update(log)
        await ca.read_remote()
        assert ca.with_state(lambda s: s.read()) == 7

    asyncio.run(go())


def test_unknown_key_is_loud_not_silent():
    async def go():
        ra, rb = MemoryRemote(), MemoryRemote()
        ca = await Core.open(make_opts(MemoryStorage(ra)))
        cb = await Core.open(make_opts(MemoryStorage(rb)))
        await cb.update(lambda s: s.inc(cb.actor_id, 4))
        # ops sync over but the key metadata does NOT (partial sync)
        for actor, log in rb.ops.items():
            ra.ops.setdefault(actor, {}).update(log)
        from crdt_enc_tpu.core import MissingKeyError

        with pytest.raises(MissingKeyError):
            await ca.read_remote()

    asyncio.run(go())


def test_producer_cursor_survives_restart(tmp_path):
    """Write, compact, 'restart' the process, write again WITHOUT an
    explicit read_remote: the new op file must land past the compacted
    range so consumers whose scan cursor is already beyond v1 still
    find it.  (Without the durable cursor it lands at v1 and is
    invisible to them forever — the silent-loss scenario.)

    Checkpointing is disabled for the restart, pinning the cold-open
    path.  Since the dot-reuse fix (``Core._ensure_own_history``,
    simulator-discovered: tests/data/sim/dot_reuse_crash_reopen.json),
    the first write of a reopened producer auto-ingests its own durable
    history first — deriving against an empty clock would re-mint
    pre-crash event ids — so the increment CONTINUES from the resumed
    state (15, not an absolute 10), identical to the warm-open twin
    below."""

    async def go():
        local, remote = str(tmp_path / "l1"), str(tmp_path / "r")
        c1 = await Core.open(make_opts(FsStorage(local, remote)))
        actor = c1.actor_id
        await c1.update(lambda s: s.inc(actor, 3))
        await c1.update(lambda s: s.inc(actor, 2))
        await c1.compact()
        # a consumer ingests the snapshot: its scan cursor is now v2
        c2 = await Core.open(make_opts(FsStorage(str(tmp_path / "l2"), remote)))
        await c2.read_remote()
        assert c2.with_state(lambda s: s.read()) == 5
        # restart the producer COLD; write immediately (no read_remote)
        c1b = await Core.open(
            make_opts(FsStorage(local, remote), create=False, checkpoint=False)
        )
        assert c1b.actor_id == actor
        await c1b.update(lambda s: s.inc(actor, 10))
        # the write re-learned its own history (snapshot = 5) first
        assert c1b.with_state(lambda s: s.read()) == 15
        # the op file must be at v3 — past the compacted v1..v2 range
        ops_dir = tmp_path / "r" / "ops" / actor.hex()
        assert sorted(p.name for p in ops_dir.iterdir()) == ["3"]
        await c2.read_remote()
        assert c2.with_state(lambda s: s.read()) == 15

    asyncio.run(go())


def test_producer_restart_warm_checkpoint_continues_increments(tmp_path):
    """The checkpointed restart (default): the warm open restores the
    compacted state, so an immediate write continues from it — the
    resume protocol's result without an explicit read_remote."""

    async def go():
        local, remote = str(tmp_path / "l1"), str(tmp_path / "r")
        c1 = await Core.open(make_opts(FsStorage(local, remote)))
        actor = c1.actor_id
        await c1.update(lambda s: s.inc(actor, 3))
        await c1.update(lambda s: s.inc(actor, 2))
        await c1.compact()
        c1b = await Core.open(make_opts(FsStorage(local, remote), create=False))
        assert c1b.opened_from_checkpoint
        await c1b.update(lambda s: s.inc(actor, 10))
        ops_dir = tmp_path / "r" / "ops" / actor.hex()
        assert sorted(p.name for p in ops_dir.iterdir()) == ["3"]
        c2 = await Core.open(make_opts(FsStorage(str(tmp_path / "l2"), remote)))
        await c2.read_remote()
        assert c2.with_state(lambda s: s.read()) == 15

    asyncio.run(go())


def test_restart_with_resume_protocol_increments_correctly(tmp_path):
    """The documented resume: open + read_remote, then write — increments
    continue from the folded state."""

    async def go():
        local, remote = str(tmp_path / "l1"), str(tmp_path / "r")
        c1 = await Core.open(make_opts(FsStorage(local, remote)))
        await c1.update(lambda s: s.inc(c1.actor_id, 5))
        await c1.compact()
        c1b = await Core.open(make_opts(FsStorage(local, remote), create=False))
        await c1b.read_remote()
        await c1b.update(lambda s: s.inc(c1b.actor_id, 10))
        c2 = await Core.open(make_opts(FsStorage(str(tmp_path / "l2"), remote)))
        await c2.read_remote()
        assert c2.with_state(lambda s: s.read()) == 15

    asyncio.run(go())


def test_store_ops_collision_is_detected(tmp_path):
    async def go():
        remote = str(tmp_path / "r")
        s1 = FsStorage(str(tmp_path / "l1"), remote)
        s2 = FsStorage(str(tmp_path / "l2"), remote)
        actor = b"\x01" * 16
        await s1.store_ops(actor, 1, b"first writer wins")
        with pytest.raises(FileExistsError):
            await s2.store_ops(actor, 1, b"second writer must fail")
        # identical content is an idempotent replay, not an error
        await s2.store_ops(actor, 1, b"first writer wins")
        [(a, v, data)] = await s1.load_ops([(actor, 1)])
        assert data == b"first writer wins"

    asyncio.run(go())


# ------------------------------------------------ sync twins of the ports


def test_sync_twins_are_offered_by_the_class_that_defines_them():
    """What sends a seal tail to one worker job is what the ports' classes
    define (core/twins.py): a forwarding wrapper offers nothing of its
    inner storage's, and a subclass that overrides an awaitable alone has
    left the inherited twin behind."""
    from crdt_enc_tpu.backends import FsStorage, XChaChaCryptor
    from crdt_enc_tpu.core import twins
    from crdt_enc_tpu.core.storage import SEAL_TAIL_TWINS
    from crdt_enc_tpu.sim.faults import FaultConfig, FaultyStorage
    from crdt_enc_tpu.sim.runner import DeterministicCryptor, _TapStorage

    seal = (("encrypt", "encrypt_fn"),)
    memory = MemoryStorage(MemoryRemote())
    assert twins.offers(memory, SEAL_TAIL_TWINS)
    assert twins.offers(FsStorage("/nowhere/l", "/nowhere/r"), SEAL_TAIL_TWINS)
    assert twins.offers(IdentityCryptor(), seal)
    assert twins.offers(XChaChaCryptor(), seal)
    assert twins.offers(DeterministicCryptor("k"), seal)  # gen_key only

    class Forwarding:  # tools/daemon.py's _FlakyStorage, the sim's tap
        def __init__(self, inner):
            self.inner = inner

        def __getattr__(self, name):
            return getattr(self.inner, name)

    assert Forwarding(memory).store_state_sync  # reachable on the instance
    assert not twins.offers(Forwarding(memory), SEAL_TAIL_TWINS)
    assert not twins.offers(_TapStorage(memory, {}), SEAL_TAIL_TWINS)
    faulty = FaultyStorage(memory, FaultConfig.none(), seed=1, name="r")
    assert not twins.offers(faulty, SEAL_TAIL_TWINS)
    assert not twins.offers(CheckedCryptor(), seal)  # overrides encrypt alone

    class CountedStore(MemoryStorage):
        async def store_state(self, data):
            return await super().store_state(data)

    class SlowDisk(MemoryStorage):
        def store_state_sync(self, data):
            return super().store_state_sync(data)

    assert not twins.offers(CountedStore(MemoryRemote()), SEAL_TAIL_TWINS)
    assert twins.offers(SlowDisk(MemoryRemote()), SEAL_TAIL_TWINS)


def test_checked_cryptor_compaction_stays_readable():
    """A cryptor that overrides ``encrypt`` alone seals through its own
    ``encrypt`` (stepwise), never through the base class's twin: what it
    seals, it opens."""
    from crdt_enc_tpu.utils import trace

    async def go():
        remote = MemoryRemote()
        w = await Core.open(make_opts(MemoryStorage(remote)))
        await w.update(lambda s: s.inc(w.actor_id))
        trace.reset()
        await w.compact()
        counted = trace.snapshot()["counters"]
        assert counted.get("seal_stepwise") == 1 and not counted.get("seal_jobs")
        r = await Core.open(make_opts(MemoryStorage(remote)))
        await r.read_remote()
        assert r.with_state(lambda s: s.to_obj()) == w.with_state(
            lambda s: s.to_obj()
        )

    asyncio.run(go())
    trace.reset()
