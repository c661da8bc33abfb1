"""Shared by the seal-tail tests (test_delta, test_crash_safety,
test_checkpoint, test_serve): one scripted history sealed twice, once with
the tail run as ONE worker-thread job (both ports offer sync twins) and once
with every port call awaited on the loop (a forwarding wrapper offers none),
so that the two drives can be compared byte for byte.

Entropy is pinned the way the simulator pins it: ``uuid4`` (actor and key
ids) comes from a seeded stream and key material from a seeded cryptor; the
identity cipher has no nonce, so equal payloads seal to equal files.
"""

import asyncio
import os

from crdt_enc_tpu.backends import (
    FsStorage,
    MemoryRemote,
    MemoryStorage,
    PlainKeyCryptor,
)
from crdt_enc_tpu.core import Core, OpenOptions, orset_adapter
from crdt_enc_tpu.core.storage import SEAL_TAIL_TWINS
from crdt_enc_tpu.sim.runner import DeterministicCryptor, _deterministic_uuid
from crdt_enc_tpu.utils.versions import DEFAULT_DATA_VERSION_1

DRIVES = ("job", "stepwise")


class Stepwise:
    """A storage that offers no sync twins: everything is forwarded, so the
    Core drives its seal tail call by call on the loop."""

    def __init__(self, inner):
        self.inner = inner

    def __getattr__(self, name):
        return getattr(self.inner, name)


class Injected(Exception):
    pass


def failing(base):
    """``base`` with one seal-tail call that raises :class:`Injected` while
    ``fail`` names it.  The twin is overridden, and the awaitable is written
    over the twin, so both drives meet the same failure."""

    class Failing(base):
        fail = None

    def make(twin):
        def method(self, *args):
            if self.fail == twin[: -len("_sync")]:
                raise Injected(twin)
            return getattr(super(Failing, self), twin)(*args)

        return method

    for _, twin in SEAL_TAIL_TWINS:
        setattr(Failing, twin, make(twin))
    return Failing


class FailingCryptor(DeterministicCryptor):
    """Fails its ``nth`` seal (1: the snapshot, 2: the delta, 3: the
    checkpoint of a compaction), in either drive: ``encrypt`` is written
    over ``encrypt_fn``."""

    nth = 0

    def encrypt_fn(self, key):
        seal = super().encrypt_fn(key)

        def counted(data):
            self.nth -= 1
            if self.nth == 0:
                raise Injected("encrypt")
            return seal(data)

        return counted


class Fleet:
    """Storages of one kind under one root, each replica's wrapped for the
    drive under test."""

    def __init__(self, kind: str, drive: str, root, cls=None):
        self.kind, self.drive, self.root = kind, drive, str(root)
        self.cls = cls or (MemoryStorage if kind == "memory" else FsStorage)
        self.remote = MemoryRemote() if kind == "memory" else None
        self.inner: dict = {}

    def storage(self, name: str):
        if name not in self.inner:
            if self.kind == "memory":
                self.inner[name] = self.cls(self.remote)
            else:
                self.inner[name] = self.cls(
                    os.path.join(self.root, f"local-{name}"),
                    os.path.join(self.root, "remote"),
                )
        inner = self.inner[name]
        return inner if self.drive == "job" else Stepwise(inner)

    def opts(self, name: str, **kw):
        kw.setdefault("adapter", orset_adapter())
        kw.setdefault("cryptor", DeterministicCryptor("seal-drive"))
        return OpenOptions(
            storage=self.storage(name),
            key_cryptor=PlainKeyCryptor(),
            supported_data_versions=(DEFAULT_DATA_VERSION_1,),
            current_data_version=DEFAULT_DATA_VERSION_1,
            create=True,
            **kw,
        )

    async def open(self, name: str, **kw) -> Core:
        return await Core.open(self.opts(name, **kw))


async def published(storage) -> dict:
    """Every byte ``storage`` can see: the remote's four families and the
    replica's two local files."""
    states = await storage.load_states(await storage.list_state_names())
    metas = await storage.load_remote_metas(
        await storage.list_remote_meta_names()
    )
    ops = []
    for a in await storage.list_op_actors():
        for first in range(1, 256):  # the GC'd prefix ends a dense scan
            ops += (run := await storage.load_ops([(a, first)]))
            if run:
                break
    deltas = await storage.load_deltas(
        [(a, 0) for a in await storage.list_delta_actors()]
    )
    return {
        "states": dict(states),
        "metas": dict(metas),
        "ops": {(a, v): raw for a, v, raw in ops},
        "deltas": {(a, v): raw for a, v, raw in deltas},
        "local_meta": await storage.load_local_meta(),
        "checkpoint": await storage.load_local_checkpoint(),
    }


def bookkeeping(core: Core) -> dict:
    d = core._data
    return {
        "seal_sig": core._last_seal_sig,
        "checkpoint_sig": core._checkpoint_sig,
        "delta_base": core._delta_base,
        "local_meta": core._local_meta.to_obj(),
        "read_states": sorted(d.read_states),
        "read_deltas": dict(d.read_deltas),
        "cursor": d.next_op_versions.to_obj(),
    }


def run_pinned(coro_fn, seed: int = 27):
    """Run ``coro_fn()`` with the uuid stream seeded: two runs of one script
    draw the same actor and key ids."""
    with _deterministic_uuid(seed):
        return asyncio.run(coro_fn())


async def add_members(core: Core, members) -> None:
    for m in members:
        await core.update(lambda s, m=m: s.add_ctx(core.actor_id, m))


async def remove_members(core: Core, members) -> None:
    for m in members:
        await core.update(lambda s, m=m: s.rm_ctx(m))
