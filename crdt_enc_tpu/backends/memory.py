"""In-memory storage backend — the fake the reference's trait-object design
enables but never shipped (SURVEY.md §4).  Multi-replica tests share one
``MemoryRemote`` the way real replicas share a synced directory."""

from __future__ import annotations

import base64
import hashlib
import threading
from dataclasses import dataclass, field

from ..core.storage import Storage
from ..models.vclock import Actor


def content_name(data: bytes) -> str:
    """SHA3-256 → base32-nopad, the reference's content addressing
    (crdt-enc-tokio/src/lib.rs:403-432)."""
    digest = hashlib.sha3_256(data).digest()
    return base64.b32encode(digest).decode().rstrip("=")


@dataclass
class MemoryRemote:
    """The shared 'remote' directory tree."""

    metas: dict = field(default_factory=dict)  # name -> bytes
    states: dict = field(default_factory=dict)  # name -> bytes
    ops: dict = field(default_factory=dict)  # actor -> {version: bytes}
    deltas: dict = field(default_factory=dict)  # actor -> {version: bytes}


# The seal tail's sync twins run on worker threads, several replicas of
# one remote at once: a removal that walks a log and drops it when empty
# must not interleave with a store into that log.  One lock for every
# remote (a field would end ``copy.deepcopy(remote)``, which tests use).
_LOG_LOCK = threading.Lock()


def _remove_prefixes(logs: dict, actor_last_versions) -> None:
    for actor, last in actor_last_versions:
        log = logs.get(actor)
        if not log:
            continue
        for v in [v for v in log if v <= last]:
            del log[v]
        if not log:
            del logs[actor]


class MemoryStorage(Storage):
    def __init__(self, remote: MemoryRemote | None = None):
        self.remote = remote if remote is not None else MemoryRemote()
        self._local_meta: bytes | None = None
        self._local_checkpoint: bytes | None = None

    # -- local meta --------------------------------------------------------
    async def load_local_meta(self) -> bytes | None:
        return self._local_meta

    # The seven calls of the seal tail are plain functions (``*_sync``,
    # the port's optional sync twins: core/storage.py) with the
    # awaitables written over them; nothing here ever awaited anything.
    def store_local_meta_sync(self, data: bytes) -> None:
        self._local_meta = bytes(data)

    async def store_local_meta(self, data: bytes) -> None:
        self.store_local_meta_sync(data)

    # -- local fold checkpoint ---------------------------------------------
    async def load_local_checkpoint(self) -> bytes | None:
        return self._local_checkpoint

    def store_local_checkpoint_sync(self, data: bytes) -> None:
        self._local_checkpoint = bytes(data)

    async def store_local_checkpoint(self, data: bytes) -> None:
        self.store_local_checkpoint_sync(data)

    async def remove_local_checkpoint(self) -> None:
        self._local_checkpoint = None

    # -- remote metas ------------------------------------------------------
    async def list_remote_meta_names(self) -> list[str]:
        return sorted(self.remote.metas)

    async def load_remote_metas(self, names: list[str]) -> list[tuple[str, bytes]]:
        return [(n, self.remote.metas[n]) for n in names if n in self.remote.metas]

    async def store_remote_meta(self, data: bytes) -> str:
        name = content_name(data)
        self.remote.metas.setdefault(name, bytes(data))
        return name

    async def remove_remote_metas(self, names: list[str]) -> None:
        for n in names:
            self.remote.metas.pop(n, None)

    # -- states ------------------------------------------------------------
    async def list_state_names(self) -> list[str]:
        return sorted(self.remote.states)

    async def load_states(self, names: list[str]) -> list[tuple[str, bytes]]:
        return [(n, self.remote.states[n]) for n in names if n in self.remote.states]

    def store_state_sync(self, data: bytes) -> str:
        name = content_name(data)
        self.remote.states.setdefault(name, bytes(data))
        return name

    async def store_state(self, data: bytes) -> str:
        return self.store_state_sync(data)

    def remove_states_sync(self, names: list[str]) -> None:
        for n in names:
            self.remote.states.pop(n, None)

    async def remove_states(self, names: list[str]) -> None:
        self.remove_states_sync(names)

    # -- ops ---------------------------------------------------------------
    async def list_op_actors(self) -> list[Actor]:
        return sorted(self.remote.ops)

    async def load_ops(
        self, actor_first_versions: list[tuple[Actor, int]]
    ) -> list[tuple[Actor, int, bytes]]:
        out = []
        for actor, first in actor_first_versions:
            log = self.remote.ops.get(actor, {})
            v = first
            # gap-free scan (crdt-enc-tokio lib.rs:254-269); a file a
            # worker's GC takes between the probe and the read ends it
            while (raw := log.get(v)) is not None:
                out.append((actor, v, raw))
                v += 1
        return out

    async def stat_ops(
        self, actor_first_versions: list[tuple[Actor, int]]
    ) -> list[tuple[Actor, int, int]]:
        out = []
        for actor, first in actor_first_versions:
            log = self.remote.ops.get(actor, {})
            v = first
            while (raw := log.get(v)) is not None:
                out.append((actor, v, len(raw)))
                v += 1
        return out

    async def store_ops(self, actor: Actor, version: int, data: bytes) -> None:
        with _LOG_LOCK:
            log = self.remote.ops.setdefault(actor, {})
            if version in log:
                raise FileExistsError(
                    f"op v{version} already exists for this actor"
                )
            log[version] = bytes(data)

    def remove_ops_sync(
        self, actor_last_versions: list[tuple[Actor, int]]
    ) -> None:
        with _LOG_LOCK:
            _remove_prefixes(self.remote.ops, actor_last_versions)

    async def remove_ops(self, actor_last_versions: list[tuple[Actor, int]]) -> None:
        self.remove_ops_sync(actor_last_versions)

    # -- delta snapshots ---------------------------------------------------
    has_deltas = True

    async def list_delta_actors(self) -> list[Actor]:
        return sorted(self.remote.deltas)

    async def load_deltas(
        self, actor_first_versions: list[tuple[Actor, int]]
    ) -> list[tuple[Actor, int, bytes]]:
        out = []
        for actor, first in actor_first_versions:
            # sorted, holes tolerated: density is not part of the delta
            # contract (chain validity comes from the base-name links)
            with _LOG_LOCK:
                log = dict(self.remote.deltas.get(actor, {}))
            for v in sorted(v for v in log if v >= first):
                out.append((actor, v, log[v]))
        return out

    def store_delta_sync(self, actor: Actor, version: int, data: bytes) -> None:
        with _LOG_LOCK:
            log = self.remote.deltas.setdefault(actor, {})
            if version in log:
                raise FileExistsError(
                    f"delta v{version} already exists for this actor"
                )
            log[version] = bytes(data)

    async def store_delta(self, actor: Actor, version: int, data: bytes) -> None:
        self.store_delta_sync(actor, version, data)

    def remove_deltas_sync(
        self, actor_last_versions: list[tuple[Actor, int]]
    ) -> None:
        with _LOG_LOCK:
            _remove_prefixes(self.remote.deltas, actor_last_versions)

    async def remove_deltas(
        self, actor_last_versions: list[tuple[Actor, int]]
    ) -> None:
        self.remove_deltas_sync(actor_last_versions)
