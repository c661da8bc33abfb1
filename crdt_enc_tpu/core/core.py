"""The core runtime: open / apply_ops / read_remote / compact.

Rebuilds the reference Core (crdt-enc/src/lib.rs:189-775) around the same
lifecycle and invariants:

* **three-layer wire format** on every op and state file — inner
  ``VersionBytes(data_version, msgpack payload)``, middle cipher envelope
  from the Cryptor, outer ``VersionBytes(container_version, …)`` (the ops
  path's coherent nesting, lib.rs:670-695).  The reference's compacted
  states used an inconsistent layering and could not be read back
  (SURVEY.md §3.4 defect 1); here states use the exact ops-path scheme.
* **writer serialization**: one async lock around apply_ops
  (lib.rs:196,668), and the LockBox discipline — mutable core data is only
  touched in sync sections, never across an await (utils/mod.rs:165-195).
* **ordered op ingestion** with concurrent-read tolerance: op files apply in
  version order per actor; an already-applied version is skipped, a gap is a
  hard error (lib.rs:519-531).
* **crash safety by ordering**: new content-addressed writes land (fsync'd)
  before old files are removed, in compact and metadata rewrite
  (lib.rs:362-369, 653-661).
* **complete op GC**: compaction removes every op file the snapshot covers
  (≤ last applied version per actor), fixing SURVEY.md §3.4 defect 2.

The hot fold/merge paths go through a pluggable accelerator (host loop or
TPU kernels) — see crdt_enc_tpu/core/adapters.py and parallel/accel.py.
"""

from __future__ import annotations

import asyncio
import hashlib
import logging
import os
import time
import uuid
from dataclasses import dataclass, field

from ..models import MVReg, ORSet, VClock
from ..utils.lockbox import LockBox
from ..models.vclock import Actor, Dot
from ..utils import VersionBytes, codec, trace
from ..utils.versions import (
    CURRENT_CONTAINER_VERSION,
    SUPPORTED_CONTAINER_VERSIONS,
)
from . import twins
from .adapters import CrdtAdapter, HostAccelerator
from .cryptor import Cryptor
from .key_cryptor import Key, KeyCryptor, Keys
from .storage import INGEST_TWINS, SEAL_TAIL_TWINS, Storage

IO_CONCURRENCY = 16  # bounded pipeline width (reference lib.rs:452,512)
BULK_MIN_FILES = 16  # below this the per-file asyncio path is cheaper

# local fold-checkpoint payload formats (docs/checkpointing.md)
CHECKPOINT_FMT_OBJ = 0  # adapter.state_to_obj, nested: read, no longer written
CHECKPOINT_FMT_ORSET = 1  # ops/columnar.py orset_pack_checkpoint
CHECKPOINT_FMT_BYTES = 2  # one bin: adapter.state_pack, the snapshot's bytes

logger = logging.getLogger("crdt_enc_tpu.core")


class CoreError(Exception):
    pass


class MissingKeyError(CoreError):
    """No usable data key (key management not initialized)."""


class OpOrderError(CoreError):
    """An op file arrived beyond the expected next version — the storage
    layer violated the gap-free ordering contract (lib.rs:527-531)."""


class IngestDecryptError(CoreError):
    """EVERY blob of a multi-file ingest batch failed to open — that is
    indistinguishable from a dead cryptor backend or damaged key
    material, so instead of quarantining the whole backlog (a replica
    that silently stops converging behind warnings), the read aborts
    loudly with the last underlying error as ``__cause__``.  Nothing
    was ingested and no cursor moved: retry after the repair.  A
    single damaged file still quarantines — per-file damage is exactly
    what the quarantine path exists for."""


class StaleWriterError(CoreError):
    """A reopened producer could not re-learn its own durable history
    (its op files — or a snapshot covering them — have not synced back),
    so writing now would mint event identifiers (Orswot dots) already
    used by pre-crash events.  Two different events with one identity is
    the one thing a CRDT cannot reconcile: replicas diverge permanently
    (simulator-discovered; shrunk repro
    ``tests/data/sim/dot_reuse_crash_reopen.json``).  Retry once the
    remote has synced."""


class _Quarantined:
    """Sentinel standing in a clears/payloads list for a synced file
    whose decrypt or decode failed: the file is SKIPPED (quarantined),
    never folded, and — critically — the ingest cursor is NOT advanced
    past it, so a later repaired sync retries it.  One damaged file
    must not abort a whole read (the passively synced directory tears
    files routinely); an op quarantine also ends its actor's dense run
    for this pass (nothing past the hole may fold).  Unknown sealing
    keys stay LOUD (:class:`MissingKeyError`) — that is a sync-state
    error the caller must see, not file damage."""

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return "<quarantined>"


_QUARANTINED = _Quarantined()


@dataclass
class LocalMeta:
    """Private per-replica identity + durable producer cursor.

    ``last_op_version`` is the highest op-file version this replica has ever
    written.  The reference keeps this cursor only in memory, so a write
    after reopen (before read_remote) silently lands at a version consumers'
    dense scans have already passed — the cursor is persisted here instead
    (reference LocalMeta holds just the actor id, lib.rs:734-737)."""

    local_actor_id: bytes
    last_op_version: int = 0
    # highest delta-snapshot version this replica ever sealed (the delta
    # log is version-addressed like the op log, docs/delta.md); absent
    # in pre-delta metas, so readers default to 0
    last_delta_version: int = 0
    # highest keys-ORSet dot counter this replica ever minted — the
    # durable cursor behind the key-register dot-reuse guard in
    # _install_new_key (simulator-discovered, same class as the op-log
    # dot reuse: tests/data/sim/key_dot_reuse_partial_meta.json)
    last_key_dot: int = 0

    def to_obj(self):
        return {
            b"actor": self.local_actor_id,
            b"last_op": self.last_op_version,
            b"last_delta": self.last_delta_version,
            b"last_key": self.last_key_dot,
        }

    @classmethod
    def from_obj(cls, obj) -> "LocalMeta":
        return cls(
            bytes(obj[b"actor"]),
            int(obj.get(b"last_op", 0)),
            int(obj.get(b"last_delta", 0)),
            int(obj.get(b"last_key", 0)),
        )


@dataclass
class RemoteMeta:
    """CRDT-of-CRDTs: one opaque MVReg config slot per plugin port
    (reference lib.rs:745-764) — the convergent "LUKS header"."""

    storage: MVReg = field(default_factory=MVReg)
    cryptor: MVReg = field(default_factory=MVReg)
    key_cryptor: MVReg = field(default_factory=MVReg)

    def merge(self, other: "RemoteMeta") -> None:
        self.storage.merge(other.storage)
        self.cryptor.merge(other.cryptor)
        self.key_cryptor.merge(other.key_cryptor)

    def to_obj(self):
        return {
            b"s": self.storage.to_obj(),
            b"c": self.cryptor.to_obj(),
            b"k": self.key_cryptor.to_obj(),
        }

    @classmethod
    def from_obj(cls, obj) -> "RemoteMeta":
        return cls(
            MVReg.from_obj(obj.get(b"s")),
            MVReg.from_obj(obj.get(b"c")),
            MVReg.from_obj(obj.get(b"k")),
        )

    def is_empty(self) -> bool:
        return (
            self.storage.is_empty()
            and self.cryptor.is_empty()
            and self.key_cryptor.is_empty()
        )


@dataclass
class StateWrapper:
    """A full-state snapshot: the CRDT value + the op-log cursor (VClock of
    last applied op-file versions — the resume point, lib.rs:740-743).

    On the wire a snapshot payload is ``[state, cursor]`` or (since the
    replication-observability layer) ``[state, cursor, sealer_actor]`` —
    the sealing replica's id, which lets readers attribute the cursor to
    a replica and maintain the cursor matrix behind the causal stability
    watermark (obs/replication.py).  Readers tolerate both lengths, so
    pre-existing remotes stay readable and old *core* readers (which
    index ``[0]``/``[1]``) never notice the extra element — but the
    pre-replication ``tools/fsck`` hard-checks ``len == 2`` and reports
    every 3-element snapshot as corruption, so upgrade fsck installs
    before producers start sealing the 3-form."""

    state: object
    next_op_versions: VClock


def snapshot_sealer(obj) -> bytes | None:
    """The validated sealer id from a decoded snapshot wrapper, or
    ``None`` when absent or malformed — the single encoding of the
    sealer wire rule (16-byte actor id in slot 2).  The type check
    matters: ``bytes(16)`` would coerce an integer into 16 NUL bytes —
    a phantom all-zero replica.  Core ingest silently drops what this
    rejects (observational, never a read failure); fsck reports it."""
    sealer = obj[2] if len(obj) > 2 else None
    if (
        isinstance(sealer, (bytes, bytearray, memoryview))
        and len(sealer) == 16
    ):
        return bytes(sealer)
    return None


@dataclass
class Info:
    """Observability snapshot (reference Info, lib.rs:766-775)."""

    local_actor_id: bytes
    next_op_versions: VClock
    read_states: frozenset
    has_latest_key: bool


@dataclass
class OpenOptions:
    """Configuration-as-code (reference OpenOptions, lib.rs:725-732)."""

    storage: Storage
    cryptor: Cryptor
    key_cryptor: KeyCryptor
    adapter: CrdtAdapter
    supported_data_versions: tuple
    current_data_version: bytes
    create: bool = False
    accelerator: object = field(default_factory=HostAccelerator)
    # local fold checkpoints (docs/checkpointing.md): with ``checkpoint``
    # on, compact() seals a warm-open resume point through the storage
    # port's local-checkpoint slot and open() restores it after
    # verification (falling back to the cold refold on any mismatch).
    # ``checkpoint_on_read`` additionally reseals after every
    # read_remote() — for pure-consumer replicas that never compact.
    checkpoint: bool = True
    checkpoint_on_read: bool = False
    # delta-state replication (docs/delta.md): with ``delta`` on and a
    # storage backend that has the delta family, compact() additionally
    # seals a delta snapshot since this replica's previous snapshot, and
    # read_remote() prefers folding ``known-base + delta chain`` over
    # re-reading full snapshots (automatic traced fallback on any gap,
    # GC'd link, or fingerprint doubt).  ``CRDT_DELTA=0`` force-disables.
    delta: bool = True
    # strong-read membership policy (docs/strong_reads.md): an explicit
    # crdt_enc_tpu.read.MembershipPolicy pinning the watermark
    # denominator (expected replicas, silence decay).  None = the
    # observed-replica denominator, the PR-6 watermark math unchanged.
    membership: object | None = None


async def open_sealed_blob(
    keys: Keys, cryptor: Cryptor, raw: bytes, supported_data_versions=None,
    *, count_clear: str | None = None,
):
    """Unwrap one three-layer sealed blob (the single implementation of
    the wire contract — the core and the fsck tool both go through here,
    so the two can never drift).  ``supported_data_versions=None`` skips
    the inner app-version check (diagnostic callers that do not know the
    application's version set).  ``count_clear`` names a counter that
    grows by the blob's cleartext length (what the decode below walks)."""
    outer = VersionBytes.deserialize(raw).ensure_versions(
        SUPPORTED_CONTAINER_VERSIONS
    )
    key_id, middle = codec.unpack(outer.content)
    key = keys.get_key(bytes(key_id))
    if key is None:
        raise MissingKeyError(
            f"blob sealed with unknown key {uuid.UUID(bytes=bytes(key_id))}; "
            "key metadata may not have synced yet"
        )
    clear = await cryptor.decrypt(key.material, bytes(middle))
    if count_clear is not None:
        trace.add(count_clear, len(clear))
    inner = VersionBytes.deserialize(clear)
    if supported_data_versions is not None:
        inner.ensure_versions(supported_data_versions)
    return codec.unpack(inner.content)


def unpack_checkpoint_state(adapter, fmt: int, st):
    """Decode a checkpoint's state payload — the ONE implementation of
    the format dispatch (the core's warm open and ``tools/fsck
    --verify-checkpoint`` both go through here, so a new format can
    never be readable by one and 'unknown' to the other)."""
    if fmt == CHECKPOINT_FMT_ORSET:
        from ..ops.columnar import orset_unpack_checkpoint

        return orset_unpack_checkpoint(st)
    if fmt == CHECKPOINT_FMT_BYTES:
        return adapter.state_from_obj(codec.unpack(st))
    if fmt == CHECKPOINT_FMT_OBJ:
        return adapter.state_from_obj(st)
    raise CoreError(f"unknown checkpoint format {fmt!r}")


class _MutData:
    """All mutable core state.  LockBox discipline: methods touching this
    must be synchronous (asyncio makes sync sections atomic); the only
    cross-await exclusion is the writer lock in apply_ops."""

    def __init__(self, state):
        self.state = state
        self.next_op_versions = VClock()
        self.read_states: set[str] = set()
        self.read_metas: set[str] = set()
        self.remote_meta = RemoteMeta()
        self.keys = Keys()
        # cursor matrix: other replicas' last PUBLISHED ingest cursors,
        # learned from the sealer id + cursor each compacted snapshot
        # carries (obs/replication.py).  Monotone (clocks only merge) and
        # purely observational — convergence never depends on it.
        self.cursor_matrix: dict[Actor, VClock] = {}
        # delta-chain consumption cursor: per sealer, the highest delta
        # version already scanned (applied OR skipped) — the next read
        # loads only past it, and compaction GCs the consumed prefix
        self.read_deltas: dict[Actor, int] = {}
        # delta bases that outlive their file: per foreign sealer, the
        # name of the newest snapshot of that sealer this replica has
        # merged (loaded whole, or reached through a link).  compact()
        # GCs a merged snapshot and drops its name from ``read_states``,
        # and the sealer's NEXT link is based on exactly that snapshot:
        # "has merged it" stays true after the file is gone
        # (docs/delta.md "A consumer of several sealers").  In memory
        # only: after a reopen one link a sealer falls back
        self.merged_bases: dict[Actor, str] = {}


@dataclass
class _SealPlan:
    """What one seal tail works from, cut from the live replica in one
    loop slice (``Core._plan_seal``).  The steps own everything in it
    but ``local_meta`` (see ``Core._seal_delta``)."""

    key: Key  # the latest data key: all three blobs seal under it
    snapshot: tuple  # the payload's items, packed: state, cursor, sealer
    snap_mut: int | None  # the state's mutation epoch at the cut
    delta: dict | None  # _plan_delta_seal's plan
    clock: VClock  # ingest cursor and cursor matrix, for the
    matrix: dict  # delta's stability watermark
    local_meta: LocalMeta
    last_delta_version: int
    prior_names: frozenset  # snapshots folded into the state
    states_to_remove: list
    ops_to_remove: list
    deltas_to_remove: list
    checkpoint: dict | None  # _plan_checkpoint's payload, if enabled


@dataclass
class _SealOutcome:
    """What the steps of one tail did, for ``Core._commit_seal``: a
    field is set once its step is through, so a failed tail commits
    exactly what it made durable."""

    name: str | None = None  # the snapshot, durable
    delta_version: int | None = None  # own delta published here
    # (name, bytes | None, cursor, state | None) to retain
    base: tuple | None = None
    stale_states: list | None = None  # GC done; these snapshots removed
    checkpoint_sig: tuple | None = None  # checkpoint durable
    error: BaseException | None = None  # what stopped the steps


@dataclass
class _Poll:
    """What the storage reads of one poll of the remote found
    (``Core._poll_steps``).  A listing that shows an unread name is the
    last read made: what follows it depends on the merge of what the
    name holds, which is the loop's to do."""

    metas: list  # names in meta/
    states: list | None = None  # names in states/
    # past the cursor: (actors, files, [(key id, idxs, middles)])
    ops: tuple | None = None


class _LoopPorts:
    """The seal tail's ports as the plugins give them: every call is
    the plugin's own awaitable, awaited on the event loop."""

    def __init__(self, core: "Core"):
        self._storage = core.storage
        self.encrypt = core.cryptor.encrypt

    def __getattr__(self, name):
        return getattr(self._storage, name)

    offload = staticmethod(asyncio.to_thread)

    @staticmethod
    async def both(a, b) -> None:
        await asyncio.gather(a, b)


class _JobPorts:
    """The same ports over their sync twins, for one worker-thread job:
    each call has run to its end by the time its await is reached."""

    def __init__(self, storage, encrypt_fn=None):
        self._storage = storage
        self._encrypt = encrypt_fn  # bound to the plan's key (seal tail)

    async def encrypt(self, _material, data: bytes) -> bytes:
        return self._encrypt(data)

    def __getattr__(self, name):
        twin = getattr(self._storage, name + "_sync")

        async def call(*args):
            return twin(*args)

        return call

    @staticmethod
    async def offload(fn, *args):
        return fn(*args)

    @staticmethod
    async def both(a, b) -> None:
        # gather's contract without a loop: the second runs whatever the
        # first did (there it was already started)
        try:
            await a
        finally:
            await b


def _run_to_end(coro):
    """Drive a coroutine none of whose awaits suspends (the seal tail
    over :class:`_JobPorts`) to its result, with no event loop."""
    try:
        coro.send(None)
    except StopIteration as stop:
        return stop.value
    coro.close()
    raise RuntimeError("a sync port twin awaited the event loop")


async def _job_to_end(coro, queue_us: str, return_us: str):
    """``coro`` driven to its end (:func:`_run_to_end`) as ONE
    worker-thread job, with the two hand-offs around it counted on the
    spans' clock, whether it returns or raises: ``queue_us`` takes the
    microseconds from the submit here on the loop to the job's first
    instruction (a free worker thread, and the interpreter lock),
    ``return_us`` those from its last instruction to this task running
    again (the loop's ready queue).  Counters and no spans: the job's own
    spans keep the parent they had, and a solo ``compact()`` no further
    child of its root."""
    first = last = 0.0

    def job():
        nonlocal first, last
        first = time.perf_counter()
        try:
            return _run_to_end(coro)
        finally:
            last = time.perf_counter()

    submit = time.perf_counter()
    try:
        return await asyncio.to_thread(job)
    finally:
        if last:  # else cancelled with the job still out: nothing to split
            trace.add_many({
                queue_us: int(1e6 * (first - submit)),
                return_us: int(1e6 * (time.perf_counter() - last)),
            })


class Core:
    """One replica's runtime.  Construct via ``Core.open``."""

    def __init__(self, opts: OpenOptions):
        self.storage = opts.storage
        self.cryptor = opts.cryptor
        self.key_cryptor = opts.key_cryptor
        self.adapter = opts.adapter
        self.accel = opts.accelerator
        self.supported_data_versions = tuple(sorted(opts.supported_data_versions))
        self.current_data_version = opts.current_data_version
        self._data = _MutData(opts.adapter.new())
        self._apply_lock = asyncio.Lock()
        self._meta_lock = asyncio.Lock()
        # Serializes every keys read-copy-write against remote-meta
        # ingestion: the key cryptor's register write happens AFTER its
        # (possibly slow, e.g. scrypt) protect step, so without exclusion a
        # Keys value merged during that await would be causally superseded
        # by a write built from a stale snapshot — losing key material.
        # Lock order: _keys_lock → _meta_lock (never the reverse).
        self._keys_lock = asyncio.Lock()
        self._local_meta: LocalMeta | None = None
        self._checkpoint_enabled = opts.checkpoint
        self._checkpoint_on_read = opts.checkpoint_on_read
        self._checkpoint_sig: tuple | None = None  # last sealed resume point
        # warm-open observability: did open() restore a checkpoint, and
        # if not (one existed but was rejected), why
        self.opened_from_checkpoint = False
        self.checkpoint_fallback_reason: str | None = None
        # replication-status sampling (obs/replication.py) runs on every
        # open/read_remote/compact unless opted out; the last computed
        # status is kept for callers that want the full dict
        self._repl_sample = os.environ.get("CRDT_REPL_SAMPLE", "") != "0"
        self.last_replication_status: dict | None = None
        # memoized _remote_id; dropped by every remote-meta merge site
        self._remote_id_cache: bytes | None = None
        # delta-state replication (docs/delta.md): the retained base —
        # the last snapshot THIS replica sealed, as its canonical packed
        # state bytes + name + cursor obj — is what the next compaction
        # diffs against.  Bytes, not a live object: snapshot objs may
        # alias mutable state dicts (the serve path's plane writeback).
        self._delta_enabled = (
            opts.delta and os.environ.get("CRDT_DELTA", "") != "0"
        )
        self._delta_verify = os.environ.get("CRDT_DELTA_VERIFY", "") != "0"
        self._delta_base: dict | None = None
        self.last_delta_fallback_reason: str | None = None
        # seal signature of the last _compact_seal (cursor + read sets +
        # mutation epoch at snapshot time): the serving layer's
        # no-op-cycle detector — when a quiet tenant's signature has not
        # moved, re-sealing would publish the identical snapshot, so the
        # whole seal/GC/checkpoint tail can be skipped honestly
        # (docs/multitenant.md "cycle-cost law")
        self._last_seal_sig: tuple | None = None
        # writer-side dot-reuse guard (_ensure_own_history): the first
        # write of this incarnation probes for un-refolded own history
        self._own_history_checked = False
        # strong-read tier (docs/strong_reads.md): the stable prefix is
        # created lazily on the first linearizable read (or restored
        # from the warm-open checkpoint's observational b"sp" slot), so
        # eventual-only replicas pay nothing for it
        self._membership = opts.membership
        self._stable = None

    # ------------------------------------------------------------------ open
    @classmethod
    async def open(cls, opts: OpenOptions) -> "Core":
        core = cls(opts)
        # warm the native libraries off-loop before the first codec.pack
        # below can reach them: the build-on-demand loader runs `make`
        # once per process, and that subprocess must never run on the
        # event loop (ASY001).  warm() memoizes failure too, so after
        # this every load()/load_state() probe is a cached dict hit.
        from .. import native

        await asyncio.to_thread(native.warm)
        raw = await core.storage.load_local_meta()
        if raw is None:
            if not opts.create:
                raise CoreError(
                    "no local replica metadata; open with create=True to join"
                )
            core._local_meta = LocalMeta(uuid.uuid4().bytes)
            vb = VersionBytes(
                CURRENT_CONTAINER_VERSION, codec.pack(core._local_meta.to_obj())
            )
            await core.storage.store_local_meta(vb.serialize())
        else:
            vb = VersionBytes.deserialize(raw).ensure_versions(
                SUPPORTED_CONTAINER_VERSIONS
            )
            core._local_meta = LocalMeta.from_obj(codec.unpack(vb.content))

        # plugins capture the core handle (CoreSubHandle, lib.rs:286-290)
        await asyncio.gather(
            core.storage.init(core),
            core.cryptor.init(core),
            core.key_cryptor.init(core),
        )
        # pull converged metadata; force-notify so plugins initialize even
        # from an empty remote (lib.rs:292)
        await core._read_remote_meta(force_notify=True)

        # bootstrap the first data key if key management has none yet
        if core._data.keys.latest_key() is None:
            await core._install_new_key()
            if core._data.keys.latest_key() is None:
                raise MissingKeyError(
                    "key cryptor did not install a latest key at open"
                )
        if opts.checkpoint:
            await core._open_from_checkpoint()
        # replication status at open: the backlog gauge here is the
        # answer to "how much will the first read_remote have to fold?"
        await core._sample_replication("open")
        return core

    # -------------------------------------------------------------- identity
    @property
    def actor_id(self) -> Actor:
        assert self._local_meta is not None
        return self._local_meta.local_actor_id

    def info(self) -> Info:
        d = self._data
        return Info(
            self.actor_id,
            d.next_op_versions.copy(),
            frozenset(d.read_states),
            d.keys.latest_key() is not None,
        )

    def with_state(self, fn):
        """Run ``fn(state)`` synchronously under the data-lock discipline —
        the way applications build ops against current state
        (reference lib.rs:325-330).  The LockBox mechanism
        (utils/lockbox.py) enforces the discipline at runtime: ``fn`` gets
        a revocable borrow, so a retained state reference used after the
        section (the Python shape of holding the lock across an await)
        raises instead of racing; awaitable returns are rejected."""
        return LockBox(self._data.state).with_(fn)

    # ------------------------------------------------------- replication obs
    async def replication_status(
        self, *, _backlog: list | None = None, _caller: str | None = None
    ) -> dict:
        """This replica's replication/convergence status: the causal
        stability watermark, per-actor op backlog (files + bytes past
        the local cursor, sized without reading — ``Storage.stat_ops``),
        divergence vs. everything known to exist, and checkpoint
        staleness.  Pure observation — no state is mutated, no op
        payload is read, and the math lives in
        :func:`crdt_enc_tpu.obs.replication.compute_status` (exactly
        unit-tested); this method only gathers its inputs.  The result
        is byte-stable under ``json.dumps(..., sort_keys=True)`` for a
        given replica state.

        ``_backlog`` is the post-ingest fast path: read_remote just
        folded everything its own listing found, so its sample passes
        ``[]`` instead of paying a second per-actor storage probe on
        the polling hot path (ops sealed concurrently with the fold
        surface in the next sample).  ``_caller`` (``open`` /
        ``read_remote`` / ``compact``) is the sampling entry point, kept
        as the span's ``meta``."""
        from ..obs import replication

        with trace.span("repl.status", meta=_caller):
            d = self._data
            if _backlog is None:
                with trace.span("repl.probe"):
                    actors = await self.storage.list_op_actors()
                    wanted = [
                        (a, d.next_op_versions.get(a) + 1)
                        for a in sorted(actors)
                    ]
                    backlog = (
                        await self.storage.stat_ops(wanted) if wanted else []
                    )
            else:
                backlog = _backlog
            # sync section: clocks snapshot + compute, no await between
            with trace.span("repl.compute"):
                ckpt = self._checkpoint_sig
                status = replication.compute_status(
                    self.actor_id,
                    d.next_op_versions.copy(),
                    {a: c.copy() for a, c in d.cursor_matrix.items()},
                    backlog,
                    self._remote_id(),
                    dict(ckpt[0]) if ckpt is not None else None,
                    self._checkpoint_enabled,
                )
                if self._membership is not None:
                    # the strong-read membership policy's loud surface:
                    # who the watermark denominator excludes rides with
                    # every status into /healthz and obs_report fleet
                    # (the key is absent without a configured policy, so
                    # the PR-6 byte-stability contract is unchanged for
                    # everyone else)
                    status["membership"] = self._membership.summary()
        self.last_replication_status = status
        return status

    async def _sample_replication(
        self, caller: str, *, _backlog: list | None = None
    ) -> dict | None:
        """Status → registered gauges (obs.replication.sample) on every
        open / read_remote / compact; ``CRDT_REPL_SAMPLE=0`` opts out.
        Observability must never kill the run it observes: a failed
        probe logs at debug and samples nothing."""
        if not self._repl_sample:
            return None
        from ..obs import replication

        try:
            status = await self.replication_status(
                _backlog=_backlog, _caller=caller
            )
        except Exception:
            logger.debug("replication status sampling failed", exc_info=True)
            return None
        with trace.span("repl.publish"):
            replication.sample(status)
            # freshness-SLO gauges + live /healthz publication: both are
            # no-ops-with-one-check unless opted in (CRDT_OBS_HTTP / a
            # configured server), and neither may kill the run it observes
            try:
                from ..obs import live as obs_live
                from ..obs import slo as obs_slo

                obs_slo.sample_freshness(status)
                obs_live.publish(status)
            except Exception:
                logger.debug("slo/live sampling failed", exc_info=True)
        return status

    # ------------------------------------------------------------ strong reads
    def _strong(self):
        """The lazily-created stable prefix (docs/strong_reads.md)."""
        if self._stable is None:
            from ..read.stable import StablePrefix

            self._stable = StablePrefix(self.adapter)
        return self._stable

    async def stable_prefix(self, *, refresh: bool = True):
        """Advance the stable prefix to the current (policy-adjusted)
        stability watermark and return its
        :class:`~crdt_enc_tpu.read.stable.StableView`.  With ``refresh``
        (default), ``read_remote()`` runs first so the watermark
        reflects the latest published cursors; ``refresh=False`` trusts
        current knowledge (the fold service's post-cycle reads, polling
        loops that just ingested).  Monotone: the returned frontier
        never regresses within an incarnation."""
        from ..read.stable import (
            StableView, effective_watermark, find_holdouts,
        )

        if refresh:
            await self.read_remote()
        prefix = self._strong()
        wm, union, replicas, excluded = effective_watermark(
            self, policy=self._membership
        )
        await prefix.advance(self, wm)
        # sync summary section
        lag = sum(
            c - prefix.cursor.get(a)
            for a, c in union.counters.items()
            if c > prefix.cursor.get(a)
        )
        wm_lag = sum(
            c - wm.get(a, 0) for a, c in union.counters.items()
        )
        view = StableView(
            cursor=prefix.cursor.copy(),
            watermark=dict(wm),
            lag=lag,
            watermark_lag=wm_lag,
            excluded=tuple(sorted(a.hex() for a in excluded)),
            holdouts=tuple(find_holdouts(self, wm, union, replicas)),
            wedged={a.hex(): r for a, r in sorted(prefix.wedged.items())},
        )
        trace.gauge("read_stable_lag", lag)
        return view

    async def read(
        self,
        *,
        linearizable: bool = False,
        max_lag: int | None = None,
        min_cursor: VClock | None = None,
        refresh: bool = True,
    ):
        """Read this replica's value.  ``linearizable=False`` (default)
        is the eventual tier: the live state's object form, free, no
        guarantee beyond CRDT convergence.  ``linearizable=True``
        answers from the stable prefix — a fold every denominator
        replica provably holds — refusing honestly
        (:class:`~crdt_enc_tpu.read.StalenessError`) when the caller's
        constraints cannot be met: ``max_lag`` bounds how many versions
        the union may be ahead of the served frontier
        (``lag_exceeded``), ``min_cursor`` demands coverage of a target
        clock, e.g. the caller's own last write (``uncovered_target``).
        There is no silent fallback tier: callers that can accept
        eventual values on refusal catch the error and re-read with
        ``linearizable=False`` — the two consistencies never mix
        implicitly."""
        from ..read.stable import ReadResult, StalenessError

        if not linearizable:
            if max_lag is not None or min_cursor is not None:
                # staleness constraints are strong-read-only; silently
                # dropping one would hand back an eventual value the
                # caller explicitly bounded — the implicit tier mix
                # this API promises never happens
                raise ValueError(
                    "max_lag/min_cursor require linearizable=True"
                )
            d = self._data
            return ReadResult(
                obj=self.adapter.state_to_obj(d.state),
                consistency="eventual",
                cursor=d.next_op_versions.copy(),
            )
        with trace.span("read.strong"):
            trace.add("read_strong_total", 1)
            view = await self.stable_prefix(refresh=refresh)
            status = {
                "watermark": {a.hex(): c for a, c in view.watermark.items()},
                "lag": view.lag,
                "watermark_lag": view.watermark_lag,
                "excluded": list(view.excluded),
                "holdouts": list(view.holdouts),
                "wedged": dict(view.wedged),
            }
            if min_cursor is not None and not view.covers(min_cursor):
                trace.add("read_strong_refusals", 1)
                raise StalenessError(
                    "uncovered_target",
                    "stable prefix does not cover the requested clock "
                    f"(holdouts: {', '.join(view.holdouts) or 'none'}); "
                    "await_stable() or retry later",
                    status=status,
                )
            if max_lag is not None and view.lag > max_lag:
                trace.add("read_strong_refusals", 1)
                raise StalenessError(
                    "lag_exceeded",
                    f"stable prefix lags the union by {view.lag} versions "
                    f"(> max_lag {max_lag}); holdouts: "
                    f"{', '.join(view.holdouts) or 'none'}"
                    + (
                        f"; policy excluded: {', '.join(view.excluded)}"
                        if view.excluded else ""
                    ),
                    status=status,
                )
            prefix = self._strong()
            return ReadResult(
                obj=self.adapter.state_to_obj(prefix.state),
                consistency="strong",
                cursor=view.cursor,
                view=view,
            )

    async def contains(self, member, **kw) -> bool:
        """Linearizable (or eventual) point membership lookup for
        set-shaped states.  Same keywords and refusal taxonomy as
        :meth:`read`; raises ``TypeError`` for states without a
        ``contains`` — honest refusal, not a guess."""
        state = await self._read_state(**kw)
        probe = getattr(state, "contains", None)
        if probe is None:
            raise TypeError(
                f"{type(state).__name__} has no membership lookup"
            )
        return bool(probe(member))

    async def value(self, **kw):
        """Linearizable (or eventual) point value lookup for
        value-shaped states (counters, registers).  Same keywords and
        refusal taxonomy as :meth:`read`."""
        state = await self._read_state(**kw)
        probe = getattr(state, "value", None)
        if probe is None:
            probe = getattr(state, "read", None)  # counters/registers
        if probe is None:
            raise TypeError(f"{type(state).__name__} has no value()")
        return probe() if callable(probe) else probe

    async def _read_state(self, *, linearizable: bool = False, **kw):
        """The live or stable STATE object behind the point lookups —
        read-only by contract."""
        if not linearizable:
            return self._data.state
        await self.read(linearizable=True, **kw)  # advances + enforces
        return self._strong().state

    async def await_stable(
        self,
        target: VClock,
        *,
        timeout_s: float = 30.0,
        poll_interval_s: float = 0.05,
        on_poll=None,
        clock=None,
    ):
        """The freshness-wait protocol: block until the stable prefix
        covers ``target`` (e.g. the caller's own last-write clock —
        read-your-writes made strong), re-reading the remote each poll
        so newly published cursors advance the watermark.  Returns the
        covering :class:`StableView`; raises
        :class:`~crdt_enc_tpu.read.StalenessError` (``timeout``) when
        ``timeout_s`` elapses first.  ``on_poll`` and ``clock`` are the
        determinism seams: the simulator paces with sync ticks and a
        counted clock so waits replay bit-for-bit; production uses the
        defaults (asyncio sleep, monotonic time)."""
        from ..read.stable import StalenessError

        clock = clock if clock is not None else time.monotonic
        t0 = clock()
        trace.add("read_await_total", 1)
        with trace.span("read.await"):
            refresh = False  # first pass reuses current knowledge
            while True:
                view = await self.stable_prefix(refresh=refresh)
                if view.covers(target):
                    return view
                refresh = True
                if clock() - t0 >= timeout_s:
                    trace.add("read_await_timeouts", 1)
                    raise StalenessError(
                        "timeout",
                        f"watermark did not cover the target within "
                        f"{timeout_s}s; holdouts: "
                        f"{', '.join(view.holdouts) or 'none'}",
                        status={"holdouts": list(view.holdouts),
                                "excluded": list(view.excluded)},
                    )
                if on_poll is not None:
                    await on_poll()
                else:
                    await asyncio.sleep(poll_interval_s)

    # ----------------------------------------------------------- key rotation
    async def _install_new_key(self) -> Key:
        """Generate a key, add it to the Keys CRDT as the new latest, and
        push through the key cryptor — the snapshot→write cycle runs under
        ``_keys_lock`` so concurrent meta ingestion cannot be superseded
        by a stale snapshot.

        Key-register dot-reuse guard (simulator-discovered; shrunk repro
        ``tests/data/sim/key_dot_reuse_partial_meta.json``): a reopened
        replica whose own key-register write is not visible (a partially
        synced meta listing) would mint a keys-ORSet dot its pre-crash
        incarnation already spent on a DIFFERENT key — on merge the
        Orswot kills one of the two entries, losing key material, and
        when the latest-register tie-break lands on the killed id every
        subsequent open dies with ``DanglingLatestKey``.  The durable
        ``LocalMeta.last_key_dot`` cursor refuses the mint loudly
        (:class:`MissingKeyError`, retry after sync) whenever the
        observed keys clock trails it — the op-log
        :meth:`_ensure_own_history` discipline applied to the key
        register.  The cursor is persisted BEFORE the remote write, so
        no crash window can mint a colliding dot; the cost is that a
        crash between the two writes leaves a mint the cursor records
        but the remote never saw — that replica refuses further mints
        (rotation/bootstrap) until an operator intervenes, which is the
        safe side: a refused rotation is recoverable, fleet-wide key
        loss is not."""
        for attempt in (0, 1):
            async with self._keys_lock:
                keys = Keys.from_obj(self._data.keys.to_obj())
                expected = keys.keys.clock.get(self.actor_id) + 1
                lm = self._local_meta
                stale = lm is not None and expected <= lm.last_key_dot
                if not stale:
                    material = await self.cryptor.gen_key()
                    key = Key.new(material)
                    keys.insert_latest_key(self.actor_id, key)
                    if lm is not None and expected > lm.last_key_dot:
                        lm.last_key_dot = expected
                        vb = VersionBytes(
                            CURRENT_CONTAINER_VERSION,
                            codec.pack(lm.to_obj()),
                        )
                        await self.storage.store_local_meta(vb.serialize())
                    await self.key_cryptor.set_keys(keys)
            if not stale:
                break
            if attempt == 0:
                # our own register may simply not have been read yet
                # this incarnation — one refresh before refusing
                await self._read_remote_meta()
                continue
            raise MissingKeyError(
                "own key-register history (keys dot "
                f"{self._local_meta.last_key_dot}) is not yet visible on "
                "the remote; minting now would reuse a spent key dot"
            )
        if self._data.keys.get_key(key.id) is None:
            raise MissingKeyError("key cryptor did not install the new key")
        return key

    async def rotate_key(self) -> Key:
        """Generate and install a fresh data key as the new latest.

        The LUKS property the layered design exists for (reference
        README.md:19-25): rotation never re-encrypts data.  Blobs written
        before the rotation stay readable because every blob's outer layer
        records its sealing key id (see ``_seal``) and old keys remain in
        the Keys CRDT; everything written after seals with the new key.
        Converges to other replicas through the remote metadata like any
        key change.  Returns the new key.
        """
        return await self._install_new_key()

    # ------------------------------------------------------ fold checkpoints
    def _checkpoint_fingerprint(self) -> dict:
        """The warm-open validity seal (docs/checkpointing.md): a
        checkpoint is only installable into a replica whose adapter,
        identity, data version, key generation (latest data-key id —
        rotation invalidates) and converged remote metadata all match
        the sealing replica's.  The meta hash is over the canonical
        packed RemoteMeta, so any plugin-config or key-register change
        on the remote (including a wiped-and-recreated remote) forces a
        cold refold."""
        d = self._data
        latest = d.keys.latest_key()
        return {
            b"a": self.adapter.name,
            b"id": self.actor_id,
            b"dv": self.current_data_version,
            b"key": latest.id if latest is not None else b"",
            b"meta": self._remote_id(),
        }

    def _remote_id(self) -> bytes:
        """SHA3 of the canonical converged RemoteMeta — the stable
        identity of the remote this replica is attached to.  Doubles as
        the checkpoint fingerprint's meta hash and the ``remote_id`` the
        replication status / fleet aggregator group devices by.

        Cached: the hash is read several times per compaction (the
        checkpoint fingerprint + every replication sample — at fleet
        scale that is 3+ pack+SHA3 rounds per tenant per service
        cycle) while the RemoteMeta only changes on a meta merge; every
        merge site drops the cache."""
        if self._remote_id_cache is None:
            self._remote_id_cache = hashlib.sha3_256(
                codec.pack(self._data.remote_meta.to_obj())
            ).digest()
        return self._remote_id_cache

    def _pack_checkpoint_state(self, state_bytes: bytes | None = None):
        """(fmt, obj) for the current state: the packed-columnar ORSet
        encoding when it applies losslessly, else the state's canonical
        bytes as one ``bin`` (format 2: the compacted snapshot's state
        part, byte for byte).  ``state_bytes`` is those bytes where the
        caller already holds them for this slice and epoch (a seal's
        plan, ``checkpoint_pack_shared``); without them they are made
        here by the adapter's pack (``checkpoint_pack_bytes``).

        A fresh streaming fold stashes its surviving rows on the state
        (``_ckpt_rows``, mut-epoch-guarded — ops/columnar.py
        ``_orset_fresh_fold_native``); when the state provably has not
        mutated since, the checkpoint packs straight from those rows —
        the zero-copy decode→planes tail, no dict walk (the solo twin
        of the fold service's planes-packed ``_packed`` path).  Every
        other ORSet is packed from its dicts, by one native pass or the
        Python walk (``orset_pack_checkpoint``); counters
        ``checkpoint_pack_rows`` / ``_native`` / ``_walk`` say which of
        the three producers ran."""
        state = self._data.state
        if type(state) is ORSet:
            from ..ops.columnar import (
                orset_pack_checkpoint, orset_pack_checkpoint_rows,
            )

            stash = getattr(state, "_ckpt_rows", None)
            if stash is not None:
                # consume the stash either way: a stale one (mutated
                # since the fold) is dead weight, and a used one has
                # served its purpose — without this the row arrays and
                # both vocab object lists stay pinned to the state for
                # its whole lifetime
                state._ckpt_rows = None
                if stash[0] == getattr(state, "_mut", None):
                    trace.add("checkpoint_pack_rows", 1)
                    return (
                        CHECKPOINT_FMT_ORSET,
                        orset_pack_checkpoint_rows(*stash[1]),
                    )
            obj = orset_pack_checkpoint(state)
            if obj is not None:
                return CHECKPOINT_FMT_ORSET, obj
        if state_bytes is None:
            state_bytes = self.adapter.state_pack(state)
            trace.add("checkpoint_pack_bytes", 1)
        else:
            trace.add("checkpoint_pack_shared", 1)
        return CHECKPOINT_FMT_BYTES, state_bytes

    def _unpack_checkpoint_state(self, fmt: int, st):
        return unpack_checkpoint_state(self.adapter, fmt, st)

    def _plan_checkpoint(
        self, _packed: tuple | None = None, state_bytes: bytes | None = None
    ) -> dict:
        """The checkpoint payload, every mutable input materialized in
        the calling loop slice so a concurrent apply cannot tear the
        (state, cursor) pair.  Its state part owns what it holds
        (packed row buffers, or the state's packed bytes), so the
        payload may be packed later, off the loop.

        ``state_bytes`` is the canonical packed state a seal's plan made
        in this same slice (:meth:`_pack_checkpoint_state`).

        ``_packed`` is the fold service's pre-packed state payload,
        ``(fmt, obj, mut_epoch)``: the service packs from the dense
        planes it already holds (no sparse walk), and the epoch guards
        staleness — if the state mutated since packing (a concurrent
        apply), the live state is re-packed here instead."""
        d = self._data
        if (
            _packed is not None
            and _packed[2] == getattr(d.state, "_mut", None)
        ):
            fmt, st = _packed[0], _packed[1]
        else:
            fmt, st = self._pack_checkpoint_state(state_bytes)
        payload = {
            b"fmt": fmt,
            b"state": st,
            b"cursor": d.next_op_versions.to_obj(),
            b"rs": sorted(d.read_states),
            b"fp": self._checkpoint_fingerprint(),
            # the cursor matrix rides along so a warm open keeps its
            # replication view (stability watermark continuity);
            # observational only — never part of the fingerprint
            b"cm": {
                a: c.to_obj() for a, c in sorted(d.cursor_matrix.items())
            },
            # delta-chain continuity (observational): the per-sealer
            # delta consumption cursor
            b"rd": dict(sorted(d.read_deltas.items())),
        }
        if self._stable is not None and self._stable.cursor.counters:
            # the stable prefix only grows, so it is checkpointable
            # as-is (docs/strong_reads.md): a warm reopen resumes
            # the exposed strong-read frontier instead of
            # restarting the session guarantee from bottom.
            # Observational — never fingerprinted; a malformed slot
            # costs a cold prefix rebuild, never a wrong read.
            payload[b"sp"] = self._stable.to_obj()
        return payload

    async def _store_checkpoint(self, payload: dict, key: Key, ports) -> tuple:
        """Seal and store a planned checkpoint payload; returns the
        signature that gates no-op reseals once the store is durable."""
        blob = await self._seal_packed(
            key, codec.pack(payload), ports.encrypt
        )
        await ports.store_local_checkpoint(blob)
        trace.add("checkpoint_bytes", len(blob))
        return (dict(payload[b"cursor"]), frozenset(payload[b"rs"]))

    async def save_checkpoint(self) -> bool:
        """Seal the materialized state + ingest cursor + read-states set
        as this replica's local warm-open checkpoint (sealed with the
        normal data-key cryptor, stored through the storage port's
        atomic local-checkpoint slot).  A later ``open`` restores it and
        ingests only op tails past the cursor — state-based CRDTs need
        no op log to resume (arXiv:1905.08733), so the persisted state +
        cursor is a complete, safe resume point.  Returns False when
        checkpointing is disabled on this core.

        A compaction writes its own checkpoint as the last step of its
        seal tail (:meth:`_seal_steps`), from the same two halves."""
        if not self._checkpoint_enabled:
            return False
        with trace.span("checkpoint.save"):
            payload = self._plan_checkpoint()
            # only a DURABLE seal gates skips
            self._checkpoint_sig = await self._store_checkpoint(
                payload, self._latest_key(), _LoopPorts(self)
            )
        return True

    async def _checkpoint_fallback(self, reason: str) -> bool:
        """Record WHY a present checkpoint was rejected (traced counter +
        reason attribute), drop the rejected blob (a cache that failed
        verification is dead weight every future open would re-parse —
        the next save reseals a valid one), and signal the cold path."""
        self.checkpoint_fallback_reason = reason
        trace.add("checkpoint_fallbacks", 1)
        logger.info(
            "local checkpoint rejected (%s); opening cold", reason
        )
        await self.storage.remove_local_checkpoint()
        return False

    @staticmethod
    def _fp_bytes(v) -> bytes | None:
        return bytes(v) if isinstance(v, (bytes, bytearray, memoryview)) else None

    async def _open_from_checkpoint(self) -> bool:
        """Restore the local fold checkpoint if one exists and verifies:
        decrypts under a known key, fingerprint current (adapter /
        actor / data version / key generation / remote-meta hash), and
        the cursor still traceable against the remote listing.  Any
        torn file, decrypt failure, or mismatch falls back to the cold
        refold with the reason traced — a checkpoint is a cache, never
        a source of truth."""
        raw = await self.storage.load_local_checkpoint()
        if raw is None:
            return False
        with trace.span("checkpoint.load"):
            try:
                obj = await self._open_sealed(raw)
            except Exception:
                logger.debug("checkpoint undecryptable", exc_info=True)
                return await self._checkpoint_fallback("unreadable")
            with trace.span("checkpoint.verify"):
                try:
                    fp = dict(obj[b"fp"])
                    fmt = int(obj[b"fmt"])
                    cursor = VClock.from_obj(obj[b"cursor"])
                    read_states = {str(n) for n in obj[b"rs"]}
                    cursor_matrix = {
                        bytes(a): VClock.from_obj(c)
                        for a, c in (obj.get(b"cm") or {}).items()
                    }
                    read_deltas = {
                        bytes(a): int(v)
                        for a, v in (obj.get(b"rd") or {}).items()
                    }
                except Exception:
                    logger.debug("checkpoint malformed", exc_info=True)
                    return await self._checkpoint_fallback("malformed")
                expected = self._checkpoint_fingerprint()
                for field_key, reason in (
                    (b"a", "adapter"),
                    (b"id", "actor"),
                    (b"dv", "data_version"),
                    (b"key", "key_rotation"),
                    (b"meta", "remote_meta"),
                ):
                    if self._fp_bytes(fp.get(field_key)) != expected[field_key]:
                        return await self._checkpoint_fallback(reason)
                # cursor ⊆ remote listing: every actor the checkpoint
                # claims folded must still have its op log listed, OR a
                # state snapshot must exist (compaction legitimately GCs
                # op logs into snapshots — whether it is one this
                # checkpoint folded or a superseding unread one, the
                # CvRDT merge of read_remote converges either way).  A
                # remote with neither — no cursor actors, no snapshots —
                # is not the remote this checkpoint came from.
                if cursor.counters:
                    op_actors = set(await self.storage.list_op_actors())
                    covered = set(cursor.counters) <= op_actors or bool(
                        await self.storage.list_state_names()
                    )
                    if not covered:
                        return await self._checkpoint_fallback("cursor")
                try:
                    state = self._unpack_checkpoint_state(fmt, obj[b"state"])
                except Exception:
                    logger.debug(
                        "checkpoint state undecodable", exc_info=True
                    )
                    return await self._checkpoint_fallback("malformed")
            # sync install section: the resume point becomes the live
            # replica state; read_remote ingests only past the cursor
            d = self._data
            d.state = state
            d.next_op_versions = cursor
            d.read_states = read_states
            d.cursor_matrix = cursor_matrix
            d.read_deltas = read_deltas
            # the installed resume point IS the last sealed one: a quiet
            # first poll under checkpoint_on_read must not reseal it
            self._checkpoint_sig = (
                dict(cursor.counters), frozenset(read_states)
            )
            # delta-base continuity: when the checkpoint proves it was
            # sealed WITH the snapshot (state == snapshot, name known),
            # the next compaction keeps extending the delta chain
            # instead of breaking it with a delta-less seal
            sp = obj.get(b"sp")
            if sp is not None:
                try:
                    from ..read.stable import StablePrefix

                    self._stable = StablePrefix.from_obj(self.adapter, sp)
                except Exception:
                    # observational slot: a malformed prefix rebuilds
                    # cold, it never fails the checkpoint
                    logger.debug(
                        "checkpoint stable-prefix slot undecodable; "
                        "strong reads rebuild cold", exc_info=True,
                    )
                    self._stable = None
            snap = obj.get(b"snap")
            if (
                # a base is retained only where a seal will diff against
                # one: without a codec it would pin O(state) bytes unread
                self._delta_codec() is not None
                and isinstance(snap, (bytes, bytearray, memoryview))
            ):
                snap_name = bytes(snap).decode()
                if snap_name in read_states:
                    self._set_delta_base(
                        snap_name,
                        # format 2 IS the snapshot's packed state
                        bytes(obj[b"state"])
                        if fmt == CHECKPOINT_FMT_BYTES
                        else self.adapter.state_pack(state),
                        cursor.to_obj(),
                    )
        self.opened_from_checkpoint = True
        return True

    # ------------------------------------------------------- wire (3 layers)
    def _latest_key(self) -> Key:
        key = self._data.keys.latest_key()
        if key is None:
            raise MissingKeyError("no latest data key")
        return key

    async def _seal(self, payload_obj) -> bytes:
        return await self._seal_packed(
            self._latest_key(), codec.pack(payload_obj), self.cryptor.encrypt
        )

    async def _seal_packed(self, key: Key, content: bytes, encrypt) -> bytes:
        """inner(data version) → cipher middle → outer(container), with the
        sealing key's id recorded in the outer layer so readers can select
        the right key after rotation or concurrent bootstrap (the reference
        decrypts everything with the current latest key, lib.rs:437-441,
        which loses data once two keys exist — deliberately fixed here).
        ``content`` is the canonically packed payload; ``encrypt`` is the
        cryptor port's, or the seal job's twin of it."""
        inner = VersionBytes(self.current_data_version, content)
        middle = await encrypt(key.material, inner.serialize())
        return VersionBytes(
            CURRENT_CONTAINER_VERSION, codec.pack([key.id, middle])
        ).serialize()

    async def _open_sealed(self, raw: bytes, *, count_clear: str | None = None):
        return await open_sealed_blob(
            self._data.keys, self.cryptor, raw, self.supported_data_versions,
            count_clear=count_clear,
        )

    def _note_quarantine(self, family: str, ident: str, exc: Exception) -> None:
        """Bookkeeping for one quarantined synced file (see
        :class:`_Quarantined`): counted under ``ingest_quarantined``
        and one warning naming the damaged object — the signal an
        operator greps for before reaching for ``tools/fsck``."""
        trace.add("ingest_quarantined", 1)
        logger.warning(
            "quarantining %s %s: %r (cursor held; retried on repaired sync)",
            family, ident, exc,
        )

    async def _decrypt_tolerant(self, key: Key, files: list, middles: list) -> list:
        """Batched AEAD open with per-file quarantine: the batch fast
        path first, and on failure a per-file pass that replaces each
        undecryptable blob with the :class:`_Quarantined` sentinel
        instead of aborting the whole ingest.

        Escalation rule: when EVERY file of a multi-file batch fails,
        the failure is indistinguishable from a dead cryptor or damaged
        key material — quarantining it all would silently stop
        convergence behind warnings — so :class:`IngestDecryptError`
        propagates loudly instead (nothing consumed, cursors held).  A
        single-file batch still quarantines (one torn file IS the
        per-file damage case this exists for)."""
        try:
            return await self.cryptor.decrypt_batch(key.material, middles)
        except Exception:
            logger.debug(
                "batch decrypt failed; isolating per file", exc_info=True
            )
        outs, failed = [], []
        for (actor, version, _), middle in zip(files, middles):
            try:
                outs.append(await self.cryptor.decrypt(key.material, middle))
            except Exception as e:
                outs.append(_QUARANTINED)
                failed.append((actor, version, e))
        if len(files) > 1 and len(failed) == len(files):
            raise IngestDecryptError(
                f"all {len(files)} op files in the batch failed to open"
            ) from failed[-1][2]
        for actor, version, e in failed:
            self._note_quarantine("op", f"{actor.hex()}:v{version}", e)
        return outs

    # ------------------------------------------------------------- apply_ops
    async def _ensure_own_history(self) -> None:
        """Dot-reuse guard, run under the writer lock before any op is
        BUILT: a producer whose in-memory clock trails its own durable
        history would mint event identifiers (Orswot dots) that its
        pre-crash incarnation already spent on *different* events —
        after which replicas diverge permanently, because a CRDT merge
        has no way to tell two events with one identity apart
        (simulator-discovered: a 4-step no-fault schedule
        ``add → crash → reopen → add`` reproduces it;
        ``tests/data/sim/dot_reuse_crash_reopen.json``).

        Cheap when in sync: two integer reads per write, plus ONE
        own-tail storage probe on the first write of each incarnation
        (a crash between ``store_ops`` and the local-meta update leaves
        an op file the durable cursor does not know about — only
        storage can reveal it).  The probe has a peer-GC blind spot
        (simulator-discovered under the daemon vocabulary:
        ``tests/data/sim/dot_reuse_gc_orphan.json``): a peer's
        compaction may fold the orphan op file into a snapshot and GC
        it before this incarnation's first write, destroying the tail
        evidence — the covering snapshot is then the only carrier of
        the spent dots.  So when the tail probe of a replica WITH prior
        history comes up empty, the snapshot listing is checked too:
        any unread snapshot forces a full re-read before the write, and
        a listing where EVERY snapshot this replica merged vanished
        with no unread replacement (a peer GC whose covering snapshot
        is not yet visible) refuses the write loudly.  When behind,
        the remote is re-read (own op
        tail, or the snapshot a peer compacted it into); a remote that
        STILL does not show the recorded history refuses the write
        loudly (:class:`StaleWriterError`) rather than corrupting every
        replica quietly."""
        actor = self.actor_id
        assert self._local_meta is not None
        behind = (
            self._data.next_op_versions.get(actor)
            < self._local_meta.last_op_version
        )
        probe_ok = True
        if not behind and not self._own_history_checked:
            try:
                tail = await self.storage.stat_ops(
                    [(actor, self._data.next_op_versions.get(actor) + 1)]
                )
                if not tail and self._local_meta.last_op_version > 0:
                    # peer-GC blind spot (docstring): only replicas that
                    # have EVER written can have a crash orphan, so the
                    # extra listing is skipped for fresh joiners.  Op
                    # files only vanish when a covering snapshot became
                    # durable first (write-new-then-delete-old), so a
                    # replica with durable history facing an empty op
                    # tail must see EITHER only snapshots it already
                    # merged (in sync) or an unread one (re-read first);
                    # a view where known snapshots vanished — or where
                    # nothing is visible at all — is inconsistent, and
                    # writing into it could re-mint dots a peer already
                    # folded.  (Assumes removes never become visible
                    # before the snapshot that justified them — the GC
                    # ordering the whole sync model rests on.)
                    names = set(await self.storage.list_state_names())
                    unread = names - self._data.read_states
                    if unread:
                        tail = True  # re-read the covering snapshots
                    elif self._data.read_states and not (
                        self._data.read_states & names
                    ):
                        # EVERY snapshot this replica merged vanished
                        # and nothing unread replaced it: the covering
                        # snapshot of that GC is not visible yet.  (A
                        # ghost name from a stale checkpoint next to a
                        # listed snapshot we also read is benign — the
                        # current listing's snapshots collectively
                        # carry all GC coverage once fully read.)
                        raise StaleWriterError(
                            "snapshots this replica merged were "
                            "garbage-collected but no replacement is "
                            "visible; writing now could reuse dots the "
                            "collecting peer's snapshot already folded"
                        )
                    elif not names and not await self.storage.stat_ops(
                        [(actor, 1)]
                    ):
                        # zero snapshots anywhere AND the own op log is
                        # gone below the cursor too: the history went
                        # SOMEWHERE (a not-yet-visible snapshot) — an
                        # intact own log (the never-compacted remote)
                        # passes this probe and writes normally
                        raise StaleWriterError(
                            "own durable op history vanished with no "
                            "covering snapshot visible; writing now "
                            "could reuse dots it carried"
                        )
            except StaleWriterError:
                raise
            except Exception:
                # a safety guard must not fail OPEN permanently: the
                # recorded-cursor check above still fails closed, and
                # leaving the checked flag unset re-probes for the
                # unrecorded-orphan corner on the next write
                logger.warning(
                    "own-tail probe failed; re-probing on the next write",
                    exc_info=True,
                )
                tail = []
                probe_ok = False
            behind = bool(tail)
        if behind:
            await self.read_remote(_sample=False)
            if (
                self._data.next_op_versions.get(actor)
                < self._local_meta.last_op_version
            ):
                raise StaleWriterError(
                    "own durable history (op files through "
                    f"v{self._local_meta.last_op_version}) is not yet "
                    "visible on the remote; writing now would reuse "
                    "pre-crash event ids"
                )
        if probe_ok:
            self._own_history_checked = True

    async def apply_ops(self, ops: list) -> None:
        """Persist a batch of local ops as one immutable op file, then fold
        it into memory (producer path, lib.rs:666-722).

        Ops must have been built against the *current* state (with_state).
        When multiple tasks write concurrently, use ``update`` instead — it
        derives the ops under the writer lock, so dots can't collide."""
        if not ops:
            return
        async with self._apply_lock:
            await self._ensure_own_history()
            await self._apply_ops_locked(ops)

    async def update(self, build) -> list:
        """Build-and-apply under the writer lock: ``build(state)`` (sync,
        LockBox discipline) returns one op or a list of ops derived from the
        live state; they are persisted and folded atomically with respect to
        other writers.  Returns the ops."""
        async with self._apply_lock:
            await self._ensure_own_history()
            ops = LockBox(self._data.state).with_(build)
            if ops is None:
                return []
            if not isinstance(ops, list):
                ops = [ops]
            if ops:
                await self._apply_ops_locked(ops)
            return ops

    async def _apply_ops_locked(self, ops: list) -> None:
        payload = [self.adapter.op_to_obj(op) for op in ops]
        blob = await self._seal(payload)
        actor = self.actor_id
        assert self._local_meta is not None
        # The true next version is past everything this replica has ever
        # written (durable cursor) and everything it has folded (memory
        # cursor); a collision with a file a previous crash left behind
        # probes forward rather than clobbering.
        version = (
            max(
                self._data.next_op_versions.get(actor),
                self._local_meta.last_op_version,
            )
            + 1
        )
        while True:
            try:
                await self.storage.store_ops(actor, version, blob)
                break
            except FileExistsError:
                version += 1
        self._local_meta.last_op_version = version
        vb = VersionBytes(
            CURRENT_CONTAINER_VERSION, codec.pack(self._local_meta.to_obj())
        )
        await self.storage.store_local_meta(vb.serialize())
        # sync section: fold into memory
        self.accel.fold_ops(self._data.state, ops)
        self._data.next_op_versions.apply(Dot(actor, version))

    # ----------------------------------------------------------- read_remote
    async def read_remote(self, *, _sample: bool = True) -> None:
        """Ingest everything new: snapshots first, then op tails
        (consumer path, lib.rs:390-399).  ``_sample=False`` is compact's
        internal call — it samples once itself, post-GC, so the inner
        ingest must not pay a second status probe."""
        await self._read_remote_meta()
        await self._read_remote_states()
        await self._read_remote_ops()
        if self._checkpoint_on_read and self._checkpoint_enabled:
            # pure-consumer replicas (no compaction rights) reseal their
            # resume point after every ingest — but not after a no-op
            # poll (same cursor + read-states as the last seal): a quiet
            # remote must not cost a multi-MB re-pack + fsync per poll
            d = self._data
            sig = (
                dict(d.next_op_versions.counters), frozenset(d.read_states)
            )
            if sig != self._checkpoint_sig:
                await self.save_checkpoint()
        if _sample:
            # the ingest above folded everything its own listing found,
            # so the backlog is empty as-of that listing — don't pay a
            # second per-actor storage probe on the polling hot path
            await self._sample_replication("read_remote", _backlog=[])

    async def _read_remote_states(self, names: list | None = None) -> None:
        """``names``: the listing, where the caller has just made it
        (:meth:`poll_sealed_ops`)."""
        if names is None:
            with trace.span("states.list"):
                names = await self.storage.list_state_names()
        new = [n for n in names if n not in self._data.read_states]
        if not new:
            # a quiet poll pays NO delta machinery: deltas are sealed
            # with their snapshots, so no unread snapshot ⇒ no new delta
            return
        if self._delta_enabled and getattr(self.storage, "has_deltas", False):
            # delta-first: chains that anchor at an already-merged base
            # snapshot fold without downloading the full snapshot; any
            # snapshot a chain cannot reach (gap, GC'd link, fingerprint
            # doubt, no codec) is full-loaded below — the delta layer
            # can save bytes but never lose data (docs/delta.md)
            if await self._read_remote_deltas():
                new = [n for n in new if n not in self._data.read_states]
                if not new:
                    return
        with trace.span("states.load"):
            loaded = await self.storage.load_states(new)
        sem = asyncio.Semaphore(IO_CONCURRENCY)

        state_failures: list[tuple[str, Exception]] = []

        async def decode(name: str, raw: bytes):
            async with sem:
                try:
                    obj = await self._open_sealed(
                        raw, count_clear="snapshot_bytes_opened"
                    )
                    # [state, cursor] or [state, cursor, sealer] — see
                    # StateWrapper's wire note; a malformed sealer id is
                    # ignored (observational), never a read failure
                    sealer = snapshot_sealer(obj)
                    return name, sealer, StateWrapper(
                        self.adapter.state_from_obj(obj[0]),
                        VClock.from_obj(obj[1]),
                    )
                except MissingKeyError:
                    raise  # key metadata not synced: loud, not damage
                except Exception as e:
                    # torn/tampered snapshot: quarantine it — the name
                    # stays OUT of read_states, so a repaired sync is
                    # retried on the next listing
                    state_failures.append((name, e))
                    return None

        with trace.span("states.decrypt_decode"):
            decoded = [
                d
                for d in await asyncio.gather(
                    *(decode(n, raw) for n, raw in loaded)
                )
                if d is not None
            ]
        if len(loaded) > 1 and len(state_failures) == len(loaded):
            # every snapshot failing = dead cryptor / damaged keys, not
            # file damage: escalate (the _decrypt_tolerant rule)
            raise IngestDecryptError(
                f"all {len(loaded)} state snapshots failed to open"
            ) from state_failures[-1][1]
        for name, e in state_failures:
            self._note_quarantine("state", name, e)
        if not decoded:
            return
        # sync section: CvRDT merge (HOT LOOP #1 → accelerator)
        wrappers = [sw for _, _, sw in decoded]
        with trace.span("states.merge"):
            self.accel.merge_states(
                self._data.state, [sw.state for sw in wrappers]
            )
        trace.add("states_merged", len(wrappers))
        for name, sealer, sw in decoded:
            self._data.next_op_versions.merge(sw.next_op_versions)
            if sealer is not None:
                self._note_sealer(sealer, name, sw.next_op_versions)
        self._data.read_states.update(name for name, _, _ in decoded)

    def _note_sealer(self, sealer: Actor, name: str, cursor: VClock) -> None:
        """What merging a foreign sealer's snapshot ``name`` (whole, or
        through a link) teaches about that sealer: its published ingest
        cursor — the matrix row the stability watermark mins over — and,
        where that cursor is the newest seen from it, the base its next
        link will name (``_MutData.merged_bases``)."""
        if sealer == self.actor_id:
            return
        d = self._data
        row = d.cursor_matrix.setdefault(sealer, VClock())
        if cursor.descends(row):
            d.merged_bases[sealer] = name
        row.merge(cursor)

    # ------------------------------------------------------- delta chains
    def _delta_fallback(self, actor: Actor, version: int, reason: str) -> None:
        """One unusable delta link: counted (``delta_fallbacks``) and
        attributed, never silent — the snapshot path picks the slack up
        in the same pass, so this is an efficiency signal, not an
        error.  The last reason is kept for tests/operators."""
        trace.add("delta_fallbacks", 1)
        self.last_delta_fallback_reason = reason
        logger.debug(
            "delta chain fallback at %s:v%d (%s); using the snapshot path",
            actor.hex(), version, reason,
        )

    async def _read_remote_deltas(self) -> int:
        """Walk every sealer's delta log past the consumed cursor and
        apply each link whose base snapshot this replica has already
        merged (base NAME ∈ ``read_states``, or the sealer's newest
        merged snapshot, whose name outlives its GC in ``merged_bases``
        — the content address is the fingerprint, so an unknown or
        renamed base is doubt and falls back).  Applying a link is
        byte-equal to merging its target snapshot (delta/codec.py
        contract), so the target name is marked read, its cursor
        merged, and the sealer's cursor-matrix row advanced and next
        base noted (:meth:`_note_sealer`) — exactly the full-snapshot
        bookkeeping.  Returns the number of links applied."""
        from ..delta import codec_for, wire

        d = self._data
        codec_cls = codec_for(self.adapter.name)
        with trace.span("delta.read"):
            with trace.span("delta.read.list"):
                actors = await self.storage.list_delta_actors()
            wanted = [
                (a, d.read_deltas.get(a, 0) + 1) for a in sorted(actors)
            ]
            if not wanted:
                return 0
            with trace.span("delta.read.load"):
                files = await self.storage.load_deltas(wanted)
            if not files:
                return 0
            trace.add("delta_passes", 1)
            trace.add("delta_links_read", len(files))
            trace.add("delta_bytes_read", sum(len(raw) for _, _, raw in files))
            # a log that starts past the cursor lost a link (never
            # synced, or GC'd under a consumer this far behind): its
            # target came, or comes, by the snapshot path
            first_loaded: dict[Actor, int] = {}
            for actor, version, _ in files:
                first_loaded[actor] = min(
                    version, first_loaded.get(actor, version)
                )
            for actor, version in wanted:
                if first_loaded.get(actor, version) > version:
                    self._delta_fallback(actor, version, "gap")
            applied = 0
            chain = 0  # longest contiguous applied run this pass
            run: dict[Actor, int] = {}
            for actor, version, raw in files:
                # scanned-is-consumed: whatever this link's fate, the next
                # poll starts past it (its target is reachable through the
                # snapshot listing regardless — see the caller's note)
                if version > d.read_deltas.get(actor, 0):
                    d.read_deltas[actor] = version
                link = f"{actor.hex()}:{version}"
                try:
                    with trace.span("delta.read.open", link):
                        obj = await self._open_sealed(raw)
                        rec = wire.parse_delta_obj(obj)
                except MissingKeyError:
                    # unlike op ingest this is NOT loud: the full
                    # snapshot (sealed with the same key register) will
                    # raise it if the key truly has not synced
                    self._delta_fallback(actor, version, "unknown_key")
                    continue
                except Exception:
                    logger.debug("delta undecodable", exc_info=True)
                    self._delta_fallback(actor, version, "unreadable")
                    continue
                if rec.adapter != self.adapter.name:
                    self._delta_fallback(actor, version, "adapter")
                    continue
                if rec.new_name in d.read_states:
                    continue  # already merged (idempotent re-delivery)
                if codec_cls is None:
                    self._delta_fallback(actor, version, "no_codec")
                    continue
                if not rec.base_name or (
                    rec.base_name not in d.read_states
                    and d.merged_bases.get(rec.sealer) != rec.base_name
                ):
                    self._delta_fallback(actor, version, "base_missing")
                    continue
                # sync section: fold the link + full snapshot bookkeeping
                with trace.span("delta.apply", link):
                    walked = codec_cls.apply(d.state, rec.delta_obj)
                if walked:
                    trace.add("delta_apply_slots", walked)
                d.next_op_versions.merge(rec.new_cursor)
                d.read_states.add(rec.new_name)
                self._note_sealer(rec.sealer, rec.new_name, rec.new_cursor)
                applied += 1
                run[actor] = run.get(actor, 0) + 1
                chain = max(chain, run[actor])
            if applied:
                trace.add("delta_applied", applied)
                trace.gauge("delta_chain_length", chain)
        return applied

    async def _read_remote_ops(self) -> None:
        with trace.span("ops.list"):
            actors = await self.storage.list_op_actors()
        wanted = [
            (a, self._data.next_op_versions.get(a) + 1) for a in sorted(actors)
        ]
        if not wanted:
            return
        if await self._read_remote_ops_pipelined(wanted, actors):
            return
        # legacy whole-batch flow (no fold session, or the pipeline hit a
        # structural surprise): cursors already reflect everything the
        # pipeline folded, so recompute and load only the remainder
        wanted = [
            (a, self._data.next_op_versions.get(a) + 1) for a in sorted(actors)
        ]
        with trace.span("ops.load"):
            files = await self.storage.load_ops(wanted)
        trace.add("op_files_loaded", len(files))
        if not files:
            return
        if len(files) >= BULK_MIN_FILES:
            # streaming front end: batched native decrypt + columnar decode
            # (SURVEY.md §7 step 6); falls through on structural surprises
            if await self._read_remote_ops_bulk(files, actors):
                return
        sem = asyncio.Semaphore(IO_CONCURRENCY)

        failures: list[tuple[Actor, int, Exception]] = []

        async def decode(actor: Actor, version: int, raw: bytes):
            async with sem:
                try:
                    return actor, version, await self._open_sealed(raw)
                except MissingKeyError:
                    raise  # key metadata not synced: loud, not damage
                except Exception as e:
                    failures.append((actor, version, e))
                    return actor, version, _QUARANTINED

        # concurrent decode, ORDER PRESERVED (the reference's `buffered`
        # not `buffer_unordered` — ordering is load-bearing, lib.rs:497-514)
        with trace.span("ops.decrypt_decode"):
            decoded = await asyncio.gather(
                *(decode(a, v, raw) for a, v, raw in files)
            )
        if len(files) > 1 and len(failures) == len(files):
            # the _decrypt_tolerant escalation rule, per-file-path twin
            raise IngestDecryptError(
                f"all {len(files)} op files failed to open"
            ) from failures[-1][2]
        for actor, version, e in failures:
            self._note_quarantine("op", f"{actor.hex()}:v{version}", e)

        # sync section: version bookkeeping + batched fold (HOT LOOP #2)
        batch = []
        blocked: set[Actor] = set()  # actors cut at a quarantined file
        for actor, version, payload in decoded:
            if actor in blocked:
                continue
            expected = self._data.next_op_versions.get(actor) + 1
            if version < expected:
                continue  # concurrent-read tolerance (lib.rs:521-525)
            if payload is _QUARANTINED:
                # the hole ends this actor's dense run for this pass;
                # the cursor stays put so the file is retried later
                blocked.add(actor)
                continue
            if version > expected:
                raise OpOrderError(
                    f"op file v{version} for {uuid.UUID(bytes=actor)} arrived "
                    f"beyond expected v{expected}"
                )
            batch.extend(self.adapter.op_from_obj(o) for o in payload)
            self._data.next_op_versions.apply(Dot(actor, version))
        if batch:
            with trace.span("ops.fold"):
                self.accel.fold_ops(self._data.state, batch)
            trace.add("ops_folded", len(batch))

    # ------------------------------------------------- pipelined bulk ingest
    def _validate_chunk(self, files: list, clears: list, overlay=None,
                        blocked: set | None = None):
        """Sync section: ordered version bookkeeping for one chunk WITHOUT
        advancing the global cursors (the caller advances only after the
        chunk's fold is accepted — a declined or failed chunk stays
        re-readable).  ``overlay`` carries validated-but-not-yet-advanced
        versions across chunks when several are in flight; ``blocked``
        likewise carries quarantine cuts (an actor whose run hit a
        damaged file — see :class:`_Quarantined` — folds nothing past
        the hole, and the cursor holds there).  Returns
        ``(payloads, metas)``; skew tolerance and gap errors exactly as
        lib.rs:519-531."""
        payloads, metas = [], []
        local: dict[Actor, int] = overlay if overlay is not None else {}
        cut: set = blocked if blocked is not None else set()
        for (actor, version, _), clear in zip(files, clears):
            if actor in cut:
                continue
            expected = (
                max(self._data.next_op_versions.get(actor), local.get(actor, 0))
                + 1
            )
            if version < expected:
                continue  # concurrent-read tolerance (lib.rs:521-525)
            if clear is _QUARANTINED:
                cut.add(actor)  # already counted at the decrypt site
                continue
            if version > expected:
                raise OpOrderError(
                    f"op file v{version} for {uuid.UUID(bytes=actor)} arrived "
                    f"beyond expected v{expected}"
                )
            try:
                inner = VersionBytes.deserialize(clear).ensure_versions(
                    self.supported_data_versions
                )
            except Exception as e:
                # decrypted fine but the cleartext framing is damaged
                # (or a data version this build cannot read): same
                # quarantine discipline — skip, cut the actor, hold
                self._note_quarantine("op", f"{actor.hex()}:v{version}", e)
                cut.add(actor)
                continue
            payloads.append(inner.content)
            metas.append((actor, version))
            local[actor] = version
        return payloads, metas

    def _advance_cursors(self, metas: list) -> None:
        for actor, version in metas:
            self._data.next_op_versions.apply(Dot(actor, version))

    async def _fold_chunk_python(self, files: list, clears: list,
                                 blocked: set | None = None) -> None:
        """Per-op fallback fold of one decrypted chunk (non-columnar CRDT
        or a session decline) — bounded by the chunk size."""
        payloads, metas = self._validate_chunk(files, clears, blocked=blocked)
        if not payloads:
            return
        batch = []
        for p in payloads:
            batch.extend(self.adapter.op_from_obj(o) for o in codec.unpack(p))
        if batch:
            with trace.span("ops.fold"):
                self.accel.fold_ops(self._data.state, batch)
            trace.add("ops_folded", len(batch))
        self._advance_cursors(metas)

    async def _read_remote_ops_pipelined(self, wanted, actors) -> bool:
        """Bounded-memory overlapped ingest: the reader+decryptor task
        streams chunks (storage.iter_op_chunks → outer unwrap → batched
        native decrypt) through a small queue while this task validates,
        decodes, and folds them through a fold session — read of chunk
        i+1 overlaps decrypt of chunk i and fold of chunk i-1, and host
        memory is bounded by chunk size × queue depth (SURVEY.md §7 hard
        part 3; restructures ref lib.rs:471-547).

        Returns True when the stream was fully consumed; False hands the
        remainder to the legacy path (an outer-envelope surprise there
        produces the precise per-file error)."""
        open_session = getattr(self.accel, "open_fold_session", None)
        if open_session is None:
            return False
        # cheap type gate BEFORE any pipeline machinery: a session-less
        # CRDT type must not pay the producer's storage scan (incl. the
        # per-actor tail probe) only to cancel it and re-read legacily
        can_open = getattr(self.accel, "can_open_fold_session", None)
        if can_open is not None and not can_open(self._data.state):
            return False

        q: asyncio.Queue = asyncio.Queue(maxsize=2)

        async def produce():
            ci = 0  # chunk index: span meta, so overlap is event-auditable
            cut: set = set()  # actors ended by an unwrap quarantine
            chunks = aiter(self.storage.iter_op_chunks(wanted))
            try:
                while True:
                    # the generator cannot be wrapped: time each pull of
                    # the next chunk (the file loads of this path, where
                    # ops.load never fires)
                    with trace.span("ops.chunk_load", meta=ci):
                        try:
                            files = await anext(chunks)
                        except StopAsyncIteration:
                            break
                    with trace.span("ops.chunk_unwrap", meta=ci):
                        files, groups = self._unwrap_op_files(files, cut)
                    clears: list = [None] * len(files)
                    with trace.span("ops.chunk_decrypt", meta=ci):
                        # each key resolves just before its own group
                        # opens: an unsynced key raises only after the
                        # groups ahead of it noted their quarantines
                        for kid, idxs, mids in groups:
                            outs = await self._decrypt_tolerant(
                                self._sealing_key(kid),
                                [files[i] for i in idxs],
                                mids,
                            )
                            for i, clear in zip(idxs, outs):
                                clears[i] = clear
                    trace.add(
                        "bytes_decrypted",
                        sum(len(m) for _, _, mids in groups for m in mids),
                    )
                    if files:
                        await q.put(("chunk", ci, files, clears))
                        ci += 1
                await q.put(("end",))
            except Exception as e:
                await q.put(("error", e))

        from ..parallel.session import SessionDeclined

        producer = asyncio.create_task(produce())
        # one tick steps the producer into its first storage scan (a
        # worker thread), so the session's sync state-vocabulary walk
        # below — the other big fixed cost of a tail ingest — runs
        # CONCURRENTLY with the per-actor tail probe instead of after it
        await asyncio.sleep(0)
        try:
            session = open_session(self._data.state, actors_hint=actors)
        except BaseException:
            producer.cancel()
            raise
        if session is None:
            # no chunked path for this CRDT type: the legacy flow
            # re-lists and re-loads (reads are idempotent)
            producer.cancel()
            try:
                await producer
            except (asyncio.CancelledError, Exception):
                pass
            return False
        session_done = False
        python_mode = False
        pending: list[tuple] = []  # chunks buffered below BULK_MIN_FILES
        pending_files = 0
        session_started = False
        fed_files = 0
        overlay: dict[Actor, int] = {}  # validated-but-unadvanced versions
        blocked: set[Actor] = set()  # actors cut at a quarantined file
        # decode runs in parallel threads (pure, GIL-released ctypes);
        # reduces drain strictly FIFO so per-actor cursor advancement stays
        # in version order even under a mid-stream failure.  The in-flight
        # width is the asyncio twin of the thread pipeline's producer
        # count (ops/stream.py stream_producer_count): the accelerator's
        # configured fan-out, else the cpu-count auto-tune.
        from ..ops.stream import stream_producer_count

        inflight: list[tuple] = []  # (decode_task, metas, ci, files, clears)
        n_producers = stream_producer_count(
            getattr(self.accel, "stream_producers", 0)
        )
        # the gauge records the resolved fan-out width; the in-flight
        # decode bound keeps its historical floor of 2 (one decode of
        # lookahead even at width 1 — that lookahead IS the pipeline)
        MAX_DECODES = max(2, n_producers)
        trace.gauge("stream_producers", n_producers)

        async def finish_session():
            # state mutates ONLY here; must precede any python-mode fold
            # (the session's plane capture would clobber a direct fold).
            # Deliberately SYNCHRONOUS: finish reads the state, combines,
            # and writes it back — in a worker thread an update() landing
            # between its read and writeback would be silently clobbered.
            # One event-loop stall (≈combine+writeback) buys atomicity.
            nonlocal session_done
            if not session_done:
                session_done = True
                with trace.span("ops.session_finish"):
                    session.finish()

        async def drain_one() -> None:
            """Complete the oldest in-flight chunk: await its decode,
            reduce it (serialized), advance its cursors.  A decline flips
            to per-op python folds for it and everything after."""
            nonlocal python_mode, fed_files
            task, metas, ci, files, clears = inflight.pop(0)
            try:
                decoded = await task
                if python_mode:
                    raise SessionDeclined("session already degraded")
                # the chunk index pairs this fold with the producer's
                # ops.chunk_* spans, so the overlap is event-auditable
                with trace.span("ops.chunk_fold", meta=ci):
                    await asyncio.to_thread(session.reduce_chunk, decoded)
            except SessionDeclined:
                if not python_mode:
                    await finish_session()
                    python_mode = True
                await self._fold_chunk_python(files, clears, blocked)
                # later chunks already in flight were validated ahead of
                # this one — fold them NOW, in order, or a newer chunk
                # would fold first and trip the version-gap check
                while inflight:
                    t2, _m2, _ci2, f2, c2 = inflight.pop(0)
                    t2.cancel()
                    try:
                        await t2
                    except (asyncio.CancelledError, Exception):
                        pass
                    await self._fold_chunk_python(f2, c2, blocked)
                return
            self._advance_cursors(metas)
            fed_files += len(files)

        async def dispatch(ci, files, clears) -> None:
            nonlocal python_mode
            if python_mode:
                await self._fold_chunk_python(files, clears, blocked)
                return
            payloads, metas = self._validate_chunk(
                files, clears, overlay, blocked
            )
            if not payloads:
                return
            task = asyncio.create_task(
                asyncio.to_thread(session.decode_chunk, payloads)
            )
            inflight.append((task, metas, ci, files, clears))
            if len(inflight) >= MAX_DECODES:
                await drain_one()

        try:
            while True:
                # the fold side starved by the ingest side
                with trace.span("ops.chunk_wait"):
                    item = await q.get()
                tag = item[0]
                if tag == "end":
                    break
                if tag == "error":
                    raise item[1]
                _, ci, files, clears = item
                if not session_started and not python_mode:
                    pending.append((ci, files, clears))
                    pending_files += len(files)
                    if pending_files < BULK_MIN_FILES:
                        continue
                    session_started = True
                    backlog, pending = pending, []
                    for chunk in backlog:
                        await dispatch(*chunk)
                    continue
                await dispatch(ci, files, clears)
            # stream fully consumed; a never-promoted tiny ingest folds
            # per-op, the same shape as the legacy small path (decrypt
            # already happened, batched)
            while inflight:
                await drain_one()
            await finish_session()
            for _, files, clears in pending:
                await self._fold_chunk_python(files, clears, blocked)
            pending = []
            return True
        finally:
            producer.cancel()
            for task, *_ in inflight:
                task.cancel()
            # fold whatever was fed — chunks whose cursors advanced must
            # land in the state even on an exceptional exit
            await finish_session()
            if fed_files:
                trace.add("op_files_bulk_folded", fed_files)

    async def _read_remote_ops_bulk(self, files: list, actors) -> bool:
        """Bulk ingestion: unwrap all outer envelopes, one batched decrypt
        per sealing key, then hand raw payloads to the accelerator's
        columnar decode+fold.  Damaged files quarantine per-file (see
        :class:`_Quarantined`) instead of surprising the ingest; key-auth
        and op-order violations raise exactly as the per-file path would
        (lib.rs:519-531 semantics preserved)."""
        files, groups = self._unwrap_resolved(files)
        if not files:
            return True  # every file quarantined: consumed, cursors held
        with trace.span("ops.bulk_decrypt"):
            clears: list = [None] * len(files)
            for key, idxs, mids in groups:
                outs = await self._decrypt_tolerant(
                    key, [files[i] for i in idxs], mids
                )
                for i, clear in zip(idxs, outs):
                    clears[i] = clear
            # cursors move only after the fold lands: an OpOrderError
            # mid-batch must not strand validated ops behind them
            payloads, metas = self._validate_chunk(files, clears)
        trace.add(
            "bytes_decrypted",
            sum(len(m) for _, _, mids in groups for m in mids),
        )
        if not payloads:
            return True
        with trace.span("ops.bulk_fold"):
            if self.accel.fold_payloads(
                self._data.state, payloads, actors_hint=actors
            ):
                self._advance_cursors(metas)
                trace.add("op_files_bulk_folded", len(payloads))
                return True
            # accelerator declined (non-columnar CRDT, vocab collision):
            # decode per-op in Python but still fold as one batch
            batch = []
            with trace.span("ops.bulk_decode"):
                for p in payloads:
                    batch.extend(
                        self.adapter.op_from_obj(o) for o in codec.unpack(p)
                    )
            self.accel.fold_ops(self._data.state, batch)
            self._advance_cursors(metas)
            trace.add("ops_folded", len(batch))
        return True

    # ------------------------------------- the unwrap rule (every ingest door)
    def _unwrap_op_files(self, files: list, cut: set | None = None):
        """Outer-envelope unwrap of loaded op files, grouped by sealing
        key id: ``(kept, [(kid, idxs, middles)])`` — ONE implementation
        of the unwrap → group sequence for every ingest door (solo
        pipelined, solo whole-batch, serve); with :meth:`_sealing_key`
        a wire or error-message change has one home.  A file whose
        outer framing does not parse is QUARANTINED (counter + warning,
        the actor's dense run ends there, cursor held — see
        :class:`_Quarantined`), so ``kept`` may be shorter than
        ``files``; ``idxs`` index into ``kept``.  ``cut`` is the set of
        actors already ended by a quarantine: the pipelined door hands
        in the one it carries across chunks, and it is added to."""
        if cut is None:
            cut = set()
        kept: list = []
        by_kid: dict[bytes, tuple[list, list]] = {}
        for f in files:
            actor, version, raw = f
            if actor in cut:
                continue
            try:
                outer = VersionBytes.deserialize(raw).ensure_versions(
                    SUPPORTED_CONTAINER_VERSIONS
                )
                kid, middle = codec.unpack(outer.content)
            except Exception as e:
                self._note_quarantine("op", f"{actor.hex()}:v{version}", e)
                cut.add(actor)
                continue
            idxs, mids = by_kid.setdefault(bytes(kid), ([], []))
            idxs.append(len(kept))
            mids.append(bytes(middle))
            kept.append(f)
        return kept, [(kid, *group) for kid, group in by_kid.items()]

    def _sealing_key(self, kid: bytes) -> Key:
        """The key an op file names as its sealer.  An unsynced key
        raises :class:`MissingKeyError` — loud, not damage."""
        key = self._data.keys.get_key(kid)
        if key is None:
            raise MissingKeyError(
                f"ops sealed with unknown key {uuid.UUID(bytes=kid)}; "
                "key metadata may not have synced yet"
            )
        return key

    def _unwrap_grouped(self, files: list):
        with trace.span("ops.bulk_unwrap"):
            return self._unwrap_op_files(files)

    def _resolve_groups(self, groups: list) -> list:
        return [
            (self._sealing_key(kid), idxs, mids) for kid, idxs, mids in groups
        ]

    def _unwrap_resolved(self, files: list):
        """:meth:`_unwrap_op_files` with every sealing key resolved
        before anything opens — the whole-batch doors (solo bulk,
        serve): ``(kept, [(key, idxs, middles)])``."""
        files, groups = self._unwrap_grouped(files)
        return files, self._resolve_groups(groups)

    # -------------------------------------------------- serving front end
    async def load_sealed_ops(self):
        """The multi-tenant serving layer's ingest front end
        (crdt_enc_tpu/serve/service.py): list + load + outer-unwrap
        every op file past the local cursor, grouping ciphertexts by
        sealing key WITHOUT decrypting, validating, folding, or
        advancing any cursor.  Returns ``(actors, files, groups)``
        where ``groups`` is ``[(key, idxs, middles)]`` — the fold
        service executes many tenants' decrypt plans inside one
        worker-thread hop (``Cryptor.decrypt_batch_fn``) instead of
        paying a per-tenant ``asyncio.to_thread`` round-trip, then
        validates through :meth:`_validate_chunk` and advances cursors
        only after its fold lands — the solo bulk-ingest discipline,
        factored so the two cannot drift.  No ``bytes_decrypted``
        counting here: nothing is decrypted yet — the caller counts
        after its decrypt phase actually succeeds."""
        actors, files, groups = await self._sealed_op_steps(
            self._data.next_op_versions, self.storage
        )
        return actors, files, self._resolve_groups(groups)

    async def _sealed_op_steps(self, cursor: VClock, ports):
        """The reads of :meth:`load_sealed_ops`, written once: list →
        load past ``cursor`` → outer unwrap, the groups still under
        their sealing key's id.  ``ports`` is the storage itself or its
        sync twins (:class:`_JobPorts`, inside the ingest job, where
        ``cursor`` is a copy and no key is looked up)."""
        with trace.span("ops.list"):
            actors = await ports.list_op_actors()
        wanted = [(a, cursor.get(a) + 1) for a in sorted(actors)]
        if not wanted:
            return [], [], []
        with trace.span("ops.load"):
            files = await ports.load_ops(wanted)
        trace.add("op_files_loaded", len(files))
        if not files:
            return actors, [], []
        files, groups = self._unwrap_grouped(files)
        return actors, files, groups

    async def _poll_steps(
        self, read_metas: frozenset, read_states: frozenset, cursor: VClock,
        ports,
    ) -> "_Poll":
        """The storage reads of one poll in their order, each under the
        span it has when awaited.  The body of the ingest job: ``ports``
        are the storage's sync twins, the three arguments before it
        copies cut on the loop; nothing live is read, no cursor moves,
        nothing is decrypted, validated or folded."""
        with trace.span("meta.list"):
            poll = _Poll(await ports.list_remote_meta_names())
        if not read_metas.issuperset(poll.metas):
            return poll  # may bring a key: merged before anything is read
        with trace.span("states.list"):
            poll.states = await ports.list_state_names()
        if not read_states.issuperset(poll.states):
            return poll  # a snapshot merge moves the cursor the load is planned from
        poll.ops = await self._sealed_op_steps(cursor, ports)
        return poll

    async def poll_sealed_ops(self):
        """One poll of the remote for the serving layer: remote meta,
        then snapshots, then :meth:`load_sealed_ops`, whose result it
        returns.

        Where the storage offers sync twins of the four reads
        (core/twins.py, ``INGEST_TWINS``) they leave the loop as ONE
        worker-thread job (:meth:`_poll_steps`) and not one thread
        round-trip each; key resolution (loud on an unsynced key) and
        every bookkeeping step stay here on the loop (``ingest_jobs``).
        A job that meets an unread name in ``meta/`` or ``states/``
        stops at that listing, and the poll goes on from it call by
        call, as it does from its start over a storage without twins
        (``ingest_stepwise``): same reads, same order, same spans."""
        metas = states = None
        how = "ingest_stepwise"
        try:
            if twins.offers(self.storage, INGEST_TWINS):
                how = "ingest_jobs"
                d = self._data
                poll = await _job_to_end(
                    self._poll_steps(
                        frozenset(d.read_metas), frozenset(d.read_states),
                        d.next_op_versions.copy(), _JobPorts(self.storage),
                    ),
                    "ingest_job_queue_us", "ingest_job_return_us",
                )
                if poll.ops is not None:
                    actors, files, groups = poll.ops
                    return actors, files, self._resolve_groups(groups)
                how = "ingest_stepwise"
                metas, states = poll.metas, poll.states
            if states is None:  # else the job found nothing unread in meta/
                await self._read_remote_meta(names=metas)
            await self._read_remote_states(names=states)
            return await self.load_sealed_ops()
        finally:
            trace.add(how, 1)  # one a poll, however it ended

    # --------------------------------------------------------- delta sealing
    @property
    def delta_base_name(self) -> str | None:
        """Content-addressed name of the retained diff base (the last
        snapshot this replica sealed), or None.  The serving layer
        matches it against a warm entry's ``seal_name`` to decide
        whether a device-cut delta is possible this cycle."""
        base = self._delta_base
        return base["name"] if base is not None else None

    def _seal_signature(self, _mut=None) -> tuple:
        """Everything a re-seal of the current state would depend on:
        the op cursor, the read snapshot/delta sets, and the state's
        mutation epoch.  Two equal signatures ⇒ ``_compact_seal`` would
        publish the identical snapshot + GC set, so the serving layer
        may skip it.  ``_mut`` overrides the live epoch (callers pass
        the SNAPSHOT-time epoch so a mutation landing mid-seal can
        never alias the next cycle's comparison)."""
        d = self._data
        return (
            tuple(sorted(d.next_op_versions.counters.items())),
            frozenset(d.read_states),
            tuple(sorted(d.read_deltas.items())),
            getattr(d.state, "_mut", None) if _mut is None else _mut,
        )

    def _delta_codec(self):
        """The delta codec a seal of this replica plans a link with, or
        None where it plans none: deltas off, a storage without a delta
        log, or a state type no codec is registered for.  Asked once a
        plan (:meth:`_plan_seal`), which builds the state as an object
        only where a plan will read it."""
        if not self._delta_enabled or not getattr(
            self.storage, "has_deltas", False
        ):
            return None
        from ..delta import codec_for

        return codec_for(self.adapter.name)

    def _plan_delta_seal(
        self, state_obj, cursor_obj, codec_cls, _cut=None, owned=False
    ):
        """Sync section of the delta seal (docs/delta.md): diff the
        about-to-be-sealed state against the retained base (this
        replica's previous snapshot), self-verify, and hand the await
        half (:meth:`_seal_delta`) an immutable plan.  Runs BEFORE the
        first await of the seal tail so a concurrent apply cannot tear
        the (base, new, delta) triple.  ``codec_cls`` is
        :meth:`_delta_codec`'s answer, never None here.

        ``owned`` says that ``state_obj`` is a copy nobody else holds
        (:meth:`_plan_seal` built it in this slice): a host-route plan
        that will be verified then keeps it as ``state_obj`` for the
        verify to compare its applied base against
        (:meth:`_verify_delta_plan`), which drops it.  The service's
        object aliases the live entry dicts and is never kept.

        The plan always carries ``new_bytes`` — the canonical packed
        state — which becomes the NEXT base even when no delta can be
        cut this round (first seal, divergent or oversize
        diff); ``dobj`` is None in those cases and consumers fall back
        to the full snapshot for this link only."""
        d = self._data
        with trace.span("delta.pack"):
            new_bytes = codec.pack(state_obj)
        plan = {
            "new_bytes": new_bytes,
            "cursor": cursor_obj,
            "dobj": None,
            "codec": codec_cls,
            "base_state": None,
            "base_name": "",
            "base_cursor": None,
            "state_obj": None,
        }
        base = self._delta_base
        if base is None:
            return plan
        if (
            _cut is not None
            and _cut.get("base_name") == base["name"]
            and _cut.get("mut") == getattr(d.state, "_mut", None)
        ):
            # device-cut fast path (docs/delta.md "device-cut deltas"):
            # the serving layer already compared base vs post-fold
            # planes ON DEVICE and built the wire object from just the
            # diff rows — no host dict walk, no need for host-resident
            # base bytes.  The base planes ride in the plan so the
            # seal-time self-verify can still rebuild the base and
            # refold the delta against it.
            plan["dobj"] = _cut["dobj"]
            plan["base_planes"] = _cut.get("base_planes")
            plan["base_name"] = base["name"]
            plan["base_cursor"] = base["cursor"]
            plan["device_cut"] = True
            trace.add("delta_device_cuts", 1)
            return plan
        if base["bytes"] is None:
            # the bytes were dropped by a prior device-cut seal and this
            # cycle's cut does not line up (warm-tier eviction or a
            # mut-epoch bump mid-continuation): seal one snapshot-only
            # link — it re-anchors the chain AND re-retains the bytes,
            # so the fallback is self-healing
            trace.add("delta_cut_fallbacks", 1)
            trace.add("delta_seal_skipped", 1)
            return plan
        # the base as an object is TAKEN, not borrowed: the verify
        # mutates it on a worker thread, so from this slice on it is the
        # plan's alone.  A tail that fails before its commit leaves the
        # old name with its bytes and no object, and a second plan made
        # while this one's tail runs finds none: both unpack the bytes
        base_state, base["state"] = base["state"], None
        try:
            if base_state is None:
                with trace.span("delta.base_unpack"):
                    base_state = self.adapter.state_from_obj(
                        codec.unpack(base["bytes"])
                    )
                trace.add("delta_base_unpacked", 1)
            else:
                trace.add("delta_base_reused", 1)
            with trace.span("delta.diff"):
                dobj = codec_cls.diff(base_state, d.state)
        except Exception:
            logger.warning(
                "delta diff failed; sealing snapshot only", exc_info=True
            )
            trace.add("delta_seal_skipped", 1)
            return plan
        if dobj is None:
            trace.add("delta_seal_skipped", 1)
            return plan
        # the size guard and self-verify run in _seal_delta's await half
        # (everything they read is an immutable plan-owned copy) — only
        # the diff against the LIVE state needed this sync section
        plan["dobj"] = dobj
        plan["base_state"] = base_state
        plan["base_name"] = base["name"]
        plan["base_cursor"] = base["cursor"]
        if owned and self._delta_verify:
            plan["state_obj"] = state_obj
        return plan

    def _set_delta_base(
        self, name: str, state_bytes: bytes | None, cursor_obj, state=None
    ) -> None:
        """Retain the just-sealed snapshot as the next diff base.
        ``state_bytes`` is a resident O(state) canonical copy per Core —
        deliberate (the alternative is re-decrypting the sealed snapshot
        every compact) but not free at fleet scale, so the cost is
        published (``delta_base_bytes``, last-writer-wins across cores)
        and the whole subsystem is opt-out (``OpenOptions.delta`` /
        ``CRDT_DELTA=0``).  A plane-resident tenant (one whose seal just
        rode the device-cut path) passes ``state_bytes=None``: the warm
        tier's device planes ARE the base, so no host copy is retained —
        ``delta_base_bytes`` drops to ~0 and the next cycle either cuts
        on device again or seals one snapshot-only link
        (``delta_cut_fallbacks``) that re-retains the bytes.

        ``state`` is that snapshot as an object, and only one source has
        it: a seal-time verify that returned true, whose base copy, the
        delta applied, packed to ``state_bytes`` (:meth:`_seal_delta`).
        The next host-route plan diffs against it and skips the unpack
        of the bytes; it takes the object out of here when it does
        (:meth:`_plan_delta_seal`), so what this dict names is never
        mutated.  Every other caller leaves it None.  It is the copy the
        verify used to free at its end, kept to the next plan instead: a
        host-route Core's floor between rounds is one state higher, its
        peak what it was."""
        self._delta_base = {
            "name": name, "bytes": state_bytes, "cursor": cursor_obj,
            "state": state,
        }
        trace.gauge(
            "delta_base_bytes",
            0 if state_bytes is None else len(state_bytes),
        )

    def _verify_delta_plan(self, plan) -> bool:
        """The refusal-to-publish guard (worker thread — the plan owns
        every input, so nothing races the live state): apply the delta
        to the base copy and require that it IS the sealed state.  Where
        the plan kept the object its ``new_bytes`` were packed from, the
        applied base as an object is compared with that object, both
        walked together and neither packed (``codec.canon_same``: true
        only where the two pack to the same bytes); a plan without the
        object, and every answer but true, is decided by the bytes, as
        ever.  A codec bug must surface HERE, on the sealer, not as
        divergence scattered across the fleet (``CRDT_DELTA_VERIFY=0``
        opts out)."""
        with trace.span("delta.verify"):
            try:
                base_state = plan["base_state"]
                if base_state is None:
                    # device-cut plan: the host base copy was never
                    # built — rebuild it from the plan-owned base
                    # planes (normalized by the fold kernel's output
                    # law; zero padding reconstructs to nothing)
                    with trace.span("delta.verify.rebuild"):
                        clock, add, rm, members, replicas = plan[
                            "base_planes"
                        ]
                        from ..obs.runtime import pull
                        from ..ops import orset_planes_to_state

                        base_state = orset_planes_to_state(
                            *pull(clock, add, rm), members, replicas
                        )
                with trace.span("delta.verify.apply"):
                    plan["codec"].apply(base_state, plan["dobj"])
                with trace.span("delta.verify.pack"):
                    return self._applied_is_sealed(base_state, plan)
            except Exception:
                logger.warning("delta verify crashed", exc_info=True)
                return False
            finally:
                # held from the plan to the comparison and no further:
                # the rest of the tail runs with what it always ran with
                plan["state_obj"] = None

    def _applied_is_sealed(self, base_state, plan) -> bool:
        """The comparison of :meth:`_verify_delta_plan`, a frame of its
        own so that both objects are freed inside its span."""
        applied = self.adapter.state_to_obj(base_state)
        sealed, plan["state_obj"] = plan["state_obj"], None
        if sealed is not None and codec.canon_same(applied, sealed):
            trace.add("delta_verify_structural", 1)
            return True
        trace.add("delta_verify_bytes", 1)
        return codec.pack(applied) == plan["new_bytes"]

    async def _seal_delta(self, plan, name: str, ports, out) -> None:
        """The delta steps of the seal tail (:meth:`_seal_steps`):
        wire-build, seal with the data key, publish at the next own-log
        version (FileExistsError probes forward — the op-file
        discipline), persist the bumped local-meta cursor, and name the
        new base to retain.  A delta-less round (``dobj`` None) wipes
        the own log instead: a chain that cannot extend to the new
        snapshot is dead weight every consumer would scan and fall back
        on.  Bookkeeping is reported on ``out``, never written here."""
        from ..delta import wire
        from ..obs.replication import stability_watermark

        dp = plan.delta
        if name == dp["base_name"]:
            dp["state_obj"] = None
            return  # idempotent re-seal of the identical snapshot
        verified = False
        if dp["dobj"] is not None:
            with trace.span("delta.size"):
                delta_len = len(codec.pack(dp["dobj"]))
            if delta_len >= len(dp["new_bytes"]):
                # a delta no smaller than the state saves nothing (and
                # no verify will read the plan's object)
                trace.add("delta_seal_skipped", 1)
                dp["dobj"] = dp["state_obj"] = None
            elif self._delta_verify:
                verified = await ports.offload(self._verify_delta_plan, dp)
                if not verified:
                    logger.warning(
                        "delta diff does not refold to the sealed state; "
                        "refusing to publish it (snapshot only)"
                    )
                    trace.add("delta_seal_divergence", 1)
                    dp["dobj"] = None
        if dp["dobj"] is None:
            out.base = (name, dp["new_bytes"], dp["cursor"], None)
            last = plan.last_delta_version
            if last:
                trace.add("delta_pruned", 1)
                await ports.remove_deltas([(self.actor_id, last)])
            return
        with trace.span("delta.seal"):
            union = plan.clock.copy()
            for clock in plan.matrix.values():
                union.merge(clock)
            rec = wire.DeltaRecord(
                base_name=dp["base_name"],
                new_name=name,
                base_cursor=VClock.from_obj(dp["base_cursor"]),
                new_cursor=VClock.from_obj(dp["cursor"]),
                sealer=self.actor_id,
                adapter=self.adapter.name,
                watermark=stability_watermark(
                    self.actor_id, plan.clock, plan.matrix, union
                ),
                delta_obj=dp["dobj"],
            )
            blob = await self._seal_packed(
                plan.key, codec.pack(wire.build_delta_obj(rec)), ports.encrypt
            )
            version = plan.last_delta_version + 1
            while True:
                try:
                    await ports.store_delta(self.actor_id, version, blob)
                    break
                except FileExistsError:
                    version += 1
            out.delta_version = version
            # the one live object the tail reads: the producer cursors
            # beside ``last_delta`` are taken as they stand at the write,
            # as before, so an ``apply_ops`` that persisted a newer
            # ``last_op`` while this tail ran is never written back stale
            # (three monotone counters; a torn read is a valid past)
            meta_obj = plan.local_meta.to_obj()
            meta_obj[b"last_delta"] = version
            vb = VersionBytes(CURRENT_CONTAINER_VERSION, codec.pack(meta_obj))
            await ports.store_local_meta(vb.serialize())
            trace.add("delta_files_sealed", 1)
            trace.add("delta_bytes_sealed", len(blob))
            # own-log bound: consumers further than MAX_CHAIN behind
            # re-read the full snapshot once and rejoin the chain
            from ..delta import MAX_CHAIN

            if version > MAX_CHAIN:
                trace.add("delta_pruned", 1)
                await ports.remove_deltas(
                    [(self.actor_id, version - MAX_CHAIN)]
                )
        # a published device-cut proves the warm planes ARE this
        # snapshot: drop the host base copy (the planes take over as
        # the base; _plan_delta_seal's bytes-None branch covers any
        # future cycle where they no longer line up).  A verify that
        # held left the plan's base copy equal to this snapshot: it moves
        # on to the next plan (None in a device-cut plan, whose copy the
        # verify built from the planes and dropped)
        state = None
        if verified:
            state, dp["base_state"] = dp["base_state"], None
        out.base = (
            name,
            None if dp.get("device_cut") else dp["new_bytes"],
            dp["cursor"],
            state,
        )

    # --------------------------------------------------------------- compact
    async def compact(self) -> None:
        """Fold everything, snapshot, write-new-then-delete-old
        (north-star path, lib.rs:332-380, with both WIP defects fixed).

        The ingest below runs the overlapped streaming pipeline when the
        storage/accelerator support it (_read_remote_ops_pipelined /
        _read_remote_ops_bulk): decrypt+decode of chunk k+1 proceeds
        while chunk k folds, with per-stage ``stream.*`` trace spans —
        see docs/streaming_pipeline.md for how to read them."""
        with trace.span("core.compact"):
            with trace.span("compact.ingest"):
                await self.read_remote(_sample=False)
            record = await self._compact_seal(_sink=False)
        # with core.compact closed: a sink record takes the registry's
        # snapshot, and carries only what has ended
        await self._sink_compact(*record)

    async def _compact_seal(
        self, *, _backlog: list | None = None,
        _packed_state: tuple | None = None,
        _state_obj: tuple | None = None,
        _delta_cut: dict | None = None,
        _sink: bool = True,
    ) -> tuple:
        """The seal tail of :meth:`compact`: snapshot the CURRENT state +
        cursor, write-new-then-delete-old, reseal the warm-open
        checkpoint, sample replication, and append the sink record.

        Factored out so the multi-tenant serving layer
        (crdt_enc_tpu/serve/) can install a batch-folded state and then
        run the EXACT solo sealing path — one implementation of the
        snapshot wire form, the GC ordering, and the checkpoint reseal,
        so a service-compacted remote can never drift from a solo
        ``compact()``.

        Three parts.  **Plan** (:meth:`_plan_seal`, one synchronous
        slice of the loop): everything the tail needs from the live
        replica, copied.  **Steps** (:meth:`_seal_steps`, the one
        ordered body: snapshot durable → delta → local meta → GC →
        checkpoint): every byte sealed and every file step made, live
        state untouched.  When the storage and the cryptor both offer
        sync twins (core/twins.py) the steps run to their end as ONE
        worker-thread job (``seal_jobs``); otherwise each port call is
        awaited here on the loop as the plugin gives it
        (``seal_stepwise``) — same body, same order, same crash points.
        **Commit** (:meth:`_commit_seal`, on the loop): the bookkeeping
        of the steps that completed, then a failed step's error.  A
        mutation landing while the steps run is kept apart by the
        epochs, and the files written are the plan-time triple.

        ``_backlog`` is forwarded to the replication sample: the service
        passes ``[]`` because its own ingest just folded everything its
        listing found (same contract as ``read_remote``'s post-ingest
        sample) — a batch of N tenants must not pay N per-actor storage
        probes per dispatch.  ``_packed_state`` is the service's
        planes-packed checkpoint payload (:meth:`_plan_checkpoint`);
        ``_state_obj`` is ``(obj, mut_epoch)`` — a pre-built snapshot
        state object (the service derives it from the canonical fold
        writeback instead of re-walking the state), used only when the
        state's mutation epoch still matches, else the live state is
        serialized here.  The canonical packer re-sorts maps, so an
        equivalent obj seals byte-identical payloads.  Returns the sink
        record's ``(meta, status)``; ``_sink=False`` leaves writing it
        to the caller (:meth:`compact`, after its root span has
        closed)."""
        plan = self._plan_seal(_packed_state, _state_obj, _delta_cut)
        ports = self._job_ports(plan)
        if ports is not None:
            trace.add("seal_jobs", 1)
            out = await _job_to_end(
                self._seal_tail(plan, ports),
                "seal_job_queue_us", "seal_job_return_us",
            )
        else:
            trace.add("seal_stepwise", 1)
            out = await self._seal_tail(plan, _LoopPorts(self))
        self._commit_seal(plan, out)
        if out.error is not None:
            raise out.error
        # local ops are now folded into the snapshot; reset the producer
        # cursor bookkeeping is unnecessary — versions only grow.
        # replication status AFTER the GC + checkpoint seal (backlog is
        # zero by construction, staleness zero): the post-compaction
        # fixed point is what rides into the sink record below — the
        # per-device line the fleet aggregator reads.
        status = await self._sample_replication("compact", _backlog=_backlog)
        # ops_to_remove is (actor, covered-version-cursor) pairs — the
        # GC prefix per actor, not a file count
        record = (
            {"gc_op_actors": len(plan.ops_to_remove),
             "gc_states": len(plan.states_to_remove)},
            status,
        )
        if _sink:
            await self._sink_compact(*record)
        return record

    def _plan_seal(self, _packed_state, _state_obj, _delta_cut) -> "_SealPlan":
        """Part one of the seal tail.  A plain function: the snapshot,
        cursor, delta plan, GC lists, key and checkpoint payload are cut
        from ONE loop slice — an await in here would let an ingest
        interleave and seal a torn (state, cursor, delta) triple."""
        d = self._data
        key = self._latest_key()
        cursor_obj = d.next_op_versions.to_obj()
        snap_mut = getattr(d.state, "_mut", None)
        # who built the object decides whether the delta plan may keep
        # it: the caller's aliases the live entry dicts and is good for
        # this slice alone, the one built here is a copy of its own
        owned = _state_obj is None or _state_obj[1] != snap_mut
        codec_cls = self._delta_codec()
        delta_plan = None
        if codec_cls is None and owned:
            # no plan will read the state as an object, so none is
            # built: the adapter packs the live state (one serialisation
            # a seal; the checkpoint below takes the same bytes)
            with trace.span("seal.state_obj"):
                state_bytes = self.adapter.state_pack(d.state)
            trace.add("seal_pack_inplace", 1)
        else:
            if owned:
                with trace.span("seal.state_obj"):
                    state_obj = self.adapter.state_to_obj(d.state)
            else:
                state_obj = _state_obj[0]
            trace.add("seal_pack_obj", 1)
            if codec_cls is not None:
                # delta plan (diff + self-verify) in the SAME slice: the
                # (base, new, delta) triple must be cut from one stable
                # state.  ``_delta_cut`` is the serving layer's
                # device-cut candidate — validated (base name + mut
                # epoch) inside the plan, never trusted blindly
                with trace.span("delta.plan"):
                    delta_plan = self._plan_delta_seal(
                        state_obj, cursor_obj, codec_cls,
                        _cut=_delta_cut, owned=owned,
                    )
                state_bytes = delta_plan["new_bytes"]
            else:
                with trace.span("seal.state_obj"):
                    state_bytes = codec.pack(state_obj)
        checkpoint = None
        if self._checkpoint_enabled:
            # the freshly compacted state is the ideal warm-open resume
            # point: everything folded, op logs GC'd to the cursor
            with trace.span("checkpoint.save"):
                checkpoint = self._plan_checkpoint(_packed_state, state_bytes)
        assert self._local_meta is not None
        return _SealPlan(
            key=key,
            # as bytes, packed in this slice: the service's ``state_obj``
            # aliases the live entry dicts and is valid only at this epoch
            snapshot=(
                state_bytes,
                codec.pack(cursor_obj),
                # sealer id: readers attribute the cursor to this replica
                # in their cursor matrix (StateWrapper's wire note) — old
                # readers index [0]/[1] and never see it
                codec.pack(self.actor_id),
            ),
            snap_mut=snap_mut,
            delta=delta_plan,
            clock=d.next_op_versions.copy(),
            matrix={a: c.copy() for a, c in d.cursor_matrix.items()},
            local_meta=self._local_meta,
            last_delta_version=self._local_meta.last_delta_version,
            prior_names=frozenset(d.read_states),
            states_to_remove=sorted(d.read_states),
            ops_to_remove=sorted(d.next_op_versions.counters.items()),
            # consumed-prefix GC covers FOREIGN logs only: the own log is
            # governed by _seal_delta's MAX_CHAIN bound — a stale reopen
            # that re-scanned its own chain must not wipe links steady
            # consumers are still walking
            deltas_to_remove=sorted(
                (a, v) for a, v in d.read_deltas.items()
                if a != self.actor_id
            ),
            checkpoint=checkpoint,
        )

    def _job_ports(self, plan: "_SealPlan"):
        """The two plugin ports over their sync twins, or None when
        either offers none (core/twins.py): what decides between one
        worker job and the stepwise drive, and nothing else does."""
        if not (
            twins.offers(self.storage, SEAL_TAIL_TWINS)
            and twins.offers(self.cryptor, (("encrypt", "encrypt_fn"),))
        ):
            return None
        encrypt = self.cryptor.encrypt_fn(plan.key.material)
        if encrypt is None:
            return None
        return _JobPorts(self.storage, encrypt)

    async def _seal_tail(self, plan: "_SealPlan", ports) -> "_SealOutcome":
        out = _SealOutcome()
        try:
            await self._seal_steps(plan, ports, out)
        except BaseException as e:
            # the commit still records the steps that completed (a
            # published delta's version, a durable snapshot's name), as
            # the bookkeeping between the awaits always did
            out.error = e
        return out

    async def _seal_steps(self, plan: "_SealPlan", ports, out) -> None:
        """Part two: THE order of the tail's durable steps, the only
        place it is written.  ``ports`` is either the plugins themselves
        (:class:`_LoopPorts`) or their sync twins (:class:`_JobPorts`,
        whose awaits complete without suspending, so one ``send`` runs
        this body to its end on a worker thread).  Reads the plan,
        reports on ``out``, touches nothing live."""
        with trace.span("compact.seal"):
            blob = await self._seal_packed(
                plan.key, codec.pack_array(plan.snapshot), ports.encrypt
            )
        # crash safety: the new snapshot is durable before anything vanishes
        with trace.span("compact.write"):
            name = out.name = await ports.store_state(blob)
        if plan.delta is not None:
            # the delta lands AFTER its target snapshot is durable (a
            # crash between the two leaves a snapshot consumers simply
            # full-read) and BEFORE the GC below
            await self._seal_delta(plan, name, ports, out)
        # snapshot-GC guard: foreign snapshots may only be removed when
        # the justifying snapshot ``name`` has never been published
        # before.  A re-seal of unchanged state reproduces its previous
        # content-addressed name — a name concurrent peers may already
        # have read, making it a legal target of THEIR GC; when every
        # member of a batch re-seals unchanged state, the union of
        # removes can wipe every snapshot (each remove justified by a
        # snapshot that is itself another sealer's remove target), and a
        # crashed replica reopening cold finds an empty remote it can
        # never converge from.  A never-before-published name cannot be
        # a concurrent remove target (removing requires having read it,
        # which orders the remover strictly after this store), so its
        # removes always stay covered by a durable snapshot — the GC
        # ordering _ensure_own_history's cross-check assumes.  Deferred
        # names stay in read_states and are GC'd by the next
        # genuinely-new seal.
        if name in plan.prior_names:
            stale_states: list[str] = []
            trace.add("seal_gc_deferred", 1)
        else:
            stale_states = plan.states_to_remove
        with trace.span("compact.gc"):
            if plan.deltas_to_remove and self._delta_enabled:
                # consumed delta prefixes go FIRST: the new snapshot
                # covers them, and removing them before their target
                # snapshots keeps any crash window free of dangling
                # chain heads (docs/delta.md GC ordering)
                await ports.remove_deltas(plan.deltas_to_remove)
            await ports.both(
                ports.remove_states(stale_states),
                ports.remove_ops(plan.ops_to_remove),
            )
        out.stale_states = stale_states
        if plan.checkpoint is not None:
            with trace.span("checkpoint.save"):
                payload = plan.checkpoint
                # functions of the plan and of the snapshot's name: the
                # read set once the GC above is accounted, and the name
                # itself, which the plan-time state equals by
                # construction (docs/delta.md: a warm reopen restores
                # the delta-sealing base and keeps its chain unbroken).
                # States without a mutation epoch never record it, as
                # before — a missing name only costs one full read
                payload[b"rs"] = sorted(
                    (plan.prior_names - set(stale_states)) | {name}
                )
                if plan.snap_mut is not None:
                    payload[b"snap"] = name.encode()
                out.checkpoint_sig = await self._store_checkpoint(
                    payload, plan.key, ports
                )

    def _commit_seal(self, plan: "_SealPlan", out: "_SealOutcome") -> None:
        """Part three, on the loop: the bookkeeping of every step that
        completed, in the order the steps made it."""
        d = self._data
        if out.delta_version is not None:
            self._local_meta.last_delta_version = out.delta_version
        if out.base is not None:
            self._set_delta_base(*out.base)
        if out.stale_states is not None:  # the GC ran to its end
            d.read_states.difference_update(out.stale_states)
            d.read_states.add(out.name)
            # record what this seal depended on, AT the snapshot epoch:
            # the serving layer skips the next seal iff the signature has
            # not moved (a mutation landing mid-seal keeps the epochs
            # apart, so the skip can never alias it away)
            self._last_seal_sig = self._seal_signature(_mut=plan.snap_mut)
        if out.checkpoint_sig is not None:
            self._checkpoint_sig = out.checkpoint_sig

    async def _sink_compact(self, meta: dict, status: dict | None) -> None:
        """Run-scoped metrics sink (CRDT_OBS_SINK / obs.sink.configure):
        every compaction appends its phase table + counters, so the
        streaming pipeline is auditable after the process is gone.
        Off the event loop: with events enabled the record can carry a
        full ring of timeline events, and json.dumps + the file append
        must not stall concurrent ingests (the registry is lock-backed,
        so snapshot/drain from a worker thread is safe)."""
        from ..obs import sink as obs_sink

        if obs_sink.default_sink() is not None:
            await asyncio.to_thread(
                obs_sink.maybe_write, "compact", meta, status
            )

    # ------------------------------------------------- remote meta lifecycle
    async def _read_remote_meta(
        self, force_notify: bool = False, names: list | None = None
    ) -> None:
        """``names``: as for :meth:`_read_remote_states`."""
        if names is None:
            with trace.span("meta.list"):
                names = await self.storage.list_remote_meta_names()
        new = [n for n in names if n not in self._data.read_metas]
        loaded = []
        if new:
            with trace.span("meta.load"):
                loaded = await self.storage.load_remote_metas(new)
        # The merge and the KEY-cryptor fan-out hold the keys lock: a
        # key-register merge landing inside _install_new_key's
        # snapshot→write window would be silently superseded (lock order:
        # _keys_lock → _meta_lock).  The storage/cryptor notifications
        # don't touch the keys register, so they run outside the lock —
        # rotation never waits on their (possibly fsync-heavy) callbacks.
        storage_reg = cryptor_reg = None
        async with self._keys_lock:
            for name, raw in loaded:
                vb = VersionBytes.deserialize(raw).ensure_versions(
                    SUPPORTED_CONTAINER_VERSIONS
                )
                self._data.remote_meta.merge(
                    RemoteMeta.from_obj(codec.unpack(vb.content))
                )
                self._remote_id_cache = None
                self._data.read_metas.add(name)
            if loaded or force_notify:
                rm = self._data.remote_meta
                storage_reg = MVReg.from_obj(rm.storage.to_obj())
                cryptor_reg = MVReg.from_obj(rm.cryptor.to_obj())
                await self.key_cryptor.set_remote_meta(
                    MVReg.from_obj(rm.key_cryptor.to_obj())
                )
        if storage_reg is not None:
            await asyncio.gather(
                self.storage.set_remote_meta(storage_reg),
                self.cryptor.set_remote_meta(cryptor_reg),
            )

    async def _store_remote_meta(self) -> None:
        """Persist converged metadata: content-addressed write, then remove
        superseded meta files (store-then-delete, lib.rs:647-664)."""
        vb = VersionBytes(
            CURRENT_CONTAINER_VERSION, codec.pack(self._data.remote_meta.to_obj())
        )
        old = set(self._data.read_metas)
        name = await self.storage.store_remote_meta(vb.serialize())
        await self.storage.remove_remote_metas([n for n in old if n != name])
        self._data.read_metas.difference_update(old)
        self._data.read_metas.add(name)

    # --------------------------------------- plugin callbacks (CoreSubHandle)
    def set_keys(self, keys: Keys) -> None:
        """Key cryptor installed a decoded key set (lib.rs:382-388)."""
        self._data.keys = keys

    async def set_remote_meta_storage(self, reg: MVReg) -> None:
        async with self._meta_lock:
            self._data.remote_meta.storage.merge(reg)
            self._remote_id_cache = None
            await self._store_remote_meta()

    async def set_remote_meta_cryptor(self, reg: MVReg) -> None:
        async with self._meta_lock:
            self._data.remote_meta.cryptor.merge(reg)
            self._remote_id_cache = None
            await self._store_remote_meta()

    async def set_remote_meta_key_cryptor(self, reg: MVReg) -> None:
        async with self._meta_lock:
            self._data.remote_meta.key_cryptor.merge(reg)
            self._remote_id_cache = None
            await self._store_remote_meta()
