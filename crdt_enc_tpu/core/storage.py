"""Storage port: abstract persistence over four object families.

Mirrors the reference Storage trait (crdt-enc/src/storage.rs:8-43): local
meta (one mutable blob), remote metas / states (immutable content-addressed
blobs), and per-actor op logs (immutable, densely version-numbered files).

Contracts carried over:
* ``load_ops`` returns each actor's ops **ordered by version** starting at
  the requested first version, with no gaps (storage.rs:36).
* names returned by list/store are opaque strings; stores of metas/states
  are content-addressed so rewrites are idempotent.
* ``remove_ops`` removes **all versions ≤ the given last version** per actor
  — the "everything up to" semantics the reference intended but didn't
  implement (SURVEY.md §3.4 defect 2; storage.rs:42 ``actor_last_verions``).

Missing directories/objects are treated as empty/None, never as errors
(crdt-enc-tokio/src/lib.rs:376-401) — a replica may simply not have synced
yet.

Sync twins (optional).  The seal tail of a compaction makes seven calls
(:data:`SEAL_TAIL_TWINS`).  A backend whose work is a plain function
anyway may offer each as ``<name>_sync`` with the same arguments, result
and exceptions (``store_delta_sync`` raises ``FileExistsError`` too), and
write the awaitable over it, so that the two cannot drift.  When the
storage and the cryptor both offer twins (:mod:`.twins`), a tenant's whole
tail runs as ONE worker-thread job instead of one thread round-trip a
call; a twin is therefore called from a worker thread, with other
replicas' twins in flight on other threads.  A storage without them, or a
wrapper around one, has every call awaited on the loop, in the same
order.  ``FsStorage`` and ``MemoryStorage`` offer all seven.

A poll of the remote makes four reads (:data:`INGEST_TWINS`), and the
same holds of them: a storage that offers all four as plain functions has
the serving layer's poll of a tenant run as ONE worker-thread job
(``Core.poll_sealed_ops``), any other has each read awaited in turn.
``FsStorage`` offers them.
"""

from __future__ import annotations

from abc import ABC, abstractmethod

from ..models.vclock import Actor

# (awaitable, sync twin) for each storage call of Core's seal tail
SEAL_TAIL_TWINS = tuple(
    (name, name + "_sync")
    for name in (
        "store_state", "store_delta", "store_local_meta",
        "store_local_checkpoint", "remove_states", "remove_ops",
        "remove_deltas",
    )
)

# (awaitable, sync twin) for each storage read of a poll of the remote
INGEST_TWINS = tuple(
    (name, name + "_sync")
    for name in (
        "list_remote_meta_names", "list_state_names", "list_op_actors",
        "load_ops",
    )
)


class Storage(ABC):
    # -- local meta (mutable, private to this replica) ---------------------
    @abstractmethod
    async def load_local_meta(self) -> bytes | None: ...

    @abstractmethod
    async def store_local_meta(self, data: bytes) -> None: ...

    # -- local fold checkpoint (mutable, private, a pure CACHE) ------------
    # The warm-open resume point (core.py save_checkpoint): one sealed
    # blob per replica holding the materialized state + ingest cursor.
    # Contract: strictly local (never synced, never GC'd by remote
    # compaction), atomic (readers see the old blob or the new one,
    # never a torn mix — fs backends write tmp + fsync + rename), and
    # DISPOSABLE — the core verifies every load and falls back to a cold
    # refold on any mismatch, so a backend may drop the blob at any
    # time.  These defaults implement "no local cache": loads miss,
    # stores are no-ops — a storage backend without durable local
    # scratch simply always opens cold.
    async def load_local_checkpoint(self) -> bytes | None:
        return None

    async def store_local_checkpoint(self, data: bytes) -> None:
        pass

    async def remove_local_checkpoint(self) -> None:
        pass

    # -- remote metas (immutable, content-addressed) -----------------------
    @abstractmethod
    async def list_remote_meta_names(self) -> list[str]: ...

    @abstractmethod
    async def load_remote_metas(self, names: list[str]) -> list[tuple[str, bytes]]:
        """Missing names are silently skipped (concurrent compaction may
        have removed them)."""

    @abstractmethod
    async def store_remote_meta(self, data: bytes) -> str: ...

    @abstractmethod
    async def remove_remote_metas(self, names: list[str]) -> None: ...

    # -- states (immutable full-state snapshots, content-addressed) --------
    @abstractmethod
    async def list_state_names(self) -> list[str]: ...

    @abstractmethod
    async def load_states(self, names: list[str]) -> list[tuple[str, bytes]]: ...

    @abstractmethod
    async def store_state(self, data: bytes) -> str: ...

    @abstractmethod
    async def remove_states(self, names: list[str]) -> None: ...

    # -- op logs (immutable, per-actor, versioned 1,2,3,…) -----------------
    @abstractmethod
    async def list_op_actors(self) -> list[Actor]: ...

    @abstractmethod
    async def load_ops(
        self, actor_first_versions: list[tuple[Actor, int]]
    ) -> list[tuple[Actor, int, bytes]]:
        """For each (actor, first), every stored op file with
        version ≥ first, in version order per actor (scan until the first
        missing version, tolerating none at all)."""

    async def iter_op_chunks(
        self,
        actor_first_versions: list[tuple[Actor, int]],
        max_bytes: int = 64 << 20,
    ):
        """Async-iterate op files in bounded chunks — the feed for the
        core's pipelined bulk ingest (read of chunk i+1 overlaps decrypt +
        fold of chunk i, host memory bounded by ~max_bytes per stage).

        Yields lists of ``(actor, version, raw)``; concatenated, the lists
        must equal ``load_ops`` of the same request (per-actor version
        order holds ACROSS chunks; a chunk may end mid-actor).  This base
        implementation degrades to one ``load_ops`` chunk — backends with
        real IO (fs) override it with incremental scans."""
        chunk = await self.load_ops(actor_first_versions)
        if chunk:
            yield chunk

    async def stat_ops(
        self, actor_first_versions: list[tuple[Actor, int]]
    ) -> list[tuple[Actor, int, int]]:
        """Like ``load_ops`` but returns ``(actor, version, nbytes)`` —
        sizes without content, the replication-status backlog probe
        (obs/replication.py).  Same dense-scan contract as ``load_ops``.
        This base implementation degrades to loading (correct anywhere);
        backends with cheap metadata (fs: stat, memory: dict walk)
        override it so status sampling never reads op payloads."""
        return [
            (actor, version, len(raw))
            for actor, version, raw in await self.load_ops(
                actor_first_versions
            )
        ]

    @abstractmethod
    async def store_ops(self, actor: Actor, version: int, data: bytes) -> None: ...

    @abstractmethod
    async def remove_ops(self, actor_last_versions: list[tuple[Actor, int]]) -> None:
        """Remove every op file with version ≤ last for each actor."""

    # -- delta snapshots (immutable, per-sealer, versioned 1,2,3,…) --------
    # The delta-state replication family (docs/delta.md): each compacting
    # replica keeps a small versioned log of sealed delta snapshots next
    # to its op log.  Contract differences from the op family, both
    # deliberate: ``load_deltas`` returns every version ≥ first that
    # EXISTS, sorted, tolerating leading holes (prefix GC is routine and
    # chain validity is established by the payload's base-name links,
    # not by density); and the whole family is OPTIONAL — these defaults
    # implement "no delta support" (``has_deltas`` False, loads empty,
    # stores/removes no-ops), under which producers seal no deltas and
    # consumers read full snapshots, exactly the pre-delta behavior.
    has_deltas = False

    async def list_delta_actors(self) -> list[Actor]:
        return []

    async def load_deltas(
        self, actor_first_versions: list[tuple[Actor, int]]
    ) -> list[tuple[Actor, int, bytes]]:
        """Every stored delta with version ≥ first, sorted by version
        per actor (leading/interior holes skipped, not scanned-to)."""
        return []

    async def store_delta(self, actor: Actor, version: int, data: bytes) -> None:
        """Publish one immutable delta file.  Must raise
        ``FileExistsError`` on a version collision (the producer probes
        forward, the op-file discipline)."""

    async def remove_deltas(
        self, actor_last_versions: list[tuple[Actor, int]]
    ) -> None:
        """Remove every delta with version ≤ last for each actor."""

    # -- lifecycle ---------------------------------------------------------
    async def init(self, core) -> None:
        """Called once at open with the core handle (plugins may call back,
        cf. CoreSubHandle, reference lib.rs:286-290)."""

    async def set_remote_meta(self, meta) -> None:
        """This plugin's converged config blob changed (an MVReg of opaque
        VersionBytes, reference lib.rs:596-609).

        Delivery-order contract: concurrent ``read_remote`` calls may
        deliver register snapshots out of order.  The register is a CRDT —
        implementations must MERGE it into their own copy (stale snapshots
        then converge to no-ops), never replace state with it."""
