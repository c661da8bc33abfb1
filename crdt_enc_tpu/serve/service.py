"""Multi-tenant fold service: thousands of small remotes, one dispatch.

The paper's design is one device folding one remote; the north star is
millions of *users* — millions of small encrypted remotes, where every
solo ``Core.compact()`` pays full dispatch, session, and probe overhead
per tenant (ROADMAP item 1).  :class:`FoldService` amortizes all of it
across a fleet of open cores:

1. **ingest** — per tenant, the service reads remote meta + snapshots
   through the tenant's normal paths and pulls the pending op tail
   through ``Core.load_sealed_ops`` (list → load → outer unwrap,
   ciphertexts grouped by sealing key, decrypt deferred to the
   cycle-wide phase below) — ``Core.poll_sealed_ops``: the storage
   reads of all three as ONE worker-thread job where the storage offers
   sync twins — then validates versions with the core's
   own ``_validate_chunk`` — cursors do NOT advance until the fold
   lands, exactly the solo bulk-ingest discipline.  Tenants ingest
   concurrently under a bounded semaphore.
2. **decode** — the PR-3 producer pool (``ops.stream
   .run_ingest_pipeline``) fans the native columnar decode out ACROSS
   TENANTS instead of across one tenant's chunks: worker threads decode
   different tenants' payloads in parallel (the native calls release
   the GIL) while the sequencer collects results in tenant order.
3. **plan + fold** — decoded tenants quantize into bucketed size
   classes (``serve.bucketing``) and every bucket collapses in ONE
   vmapped device dispatch (``ops.orset.orset_fold_tenants`` /
   ``ops.counters.gcounter_fold_tenants``): the tenant batch is just
   another fold axis over the existing columnar kernels.  Oversized
   tenants spill to the existing solo accelerator paths
   (``fold_payloads`` — sparse/streaming regimes); tenants the decoder
   declines fold per-op through ``Core._fold_chunk_python``.  The whole
   fold phase — plane capture, kernel, writeback, cursor advance — is
   one synchronous section, so concurrent applies can never interleave
   a torn (planes, state) pair (the same stall ``finish_session`` buys
   in the solo pipeline).
4. **scatter + seal** — per-tenant result planes write back through
   ``orset_planes_to_state`` into each tenant's live state, and each
   tenant seals through its normal encrypted snapshot path
   (``Core._compact_seal``): the same snapshot wire form, GC ordering,
   checkpoint reseal, and sink record as a solo compact — byte-identical
   states by construction, pinned end-to-end by the differential tests.

**Warm tier** (``serve.warm``): each tenant's post-fold planes are kept
under a byte-budgeted LRU keyed by state identity × mutation epoch, so
the next cycle on an un-mutated tenant skips the sparse state walk and
the full-plane re-upload — the multi-tenant generalization of the PR-4
device-resident plane cache.

**Replication probes**: a solo compact pays one per-actor ``stat_ops``
probe per tenant when it samples replication status.  The service's
ingest just folded everything its own listing found, so every tenant's
sample reuses that listing (``_compact_seal(_backlog=[])`` — the same
contract as ``read_remote``'s post-ingest sample): a batch of N tenants
pays ZERO extra storage probes per cycle, regression-pinned in
tests/test_serve.py.

Every phase emits ``serve.*`` spans and the per-tenant end-to-end
latency lands in the ``serve.tenant`` histogram (p50/p95/p99 via the
obs registry) — ``bench.py --e2e-multitenant`` publishes them.
"""

from __future__ import annotations

import asyncio
import logging
import time
from dataclasses import dataclass, field

import numpy as np

from .. import ops as K
from ..models import GCounter, ORSet
from ..models.counters import POS
from ..obs import runtime as obs_runtime
from ..utils import codec, trace
from . import bucketing
from .bucketing import TenantShape, _bucket, plan_buckets
from .warm import DEFAULT_BYTE_BUDGET, PlaneWarmTier

logger = logging.getLogger("crdt_enc_tpu.serve")


@dataclass
class ServeConfig:
    """Service knobs; the defaults serve the many-small-tenants shape."""

    rows_cap: int = bucketing.DEFAULT_ROWS_CAP
    cells_cap: int = bucketing.DEFAULT_CELLS_CAP
    tenants_cap: int = bucketing.DEFAULT_TENANTS_CAP
    # decode fan-out width: 0 = auto (ops.stream.stream_producer_count)
    producers: int = 0
    # concurrent tenant ingests/seals (bounded asyncio semaphore)
    io_width: int = 16
    # warm plane tier (serve.warm); budget in summed plane bytes
    warm: bool = True
    warm_bytes: int = DEFAULT_BYTE_BUDGET
    # seal a snapshot for tenants with no new ops (solo-compact parity);
    # off = a quiet tenant costs nothing per cycle
    seal_empty: bool = True
    # skip the whole seal/GC/checkpoint tail for a tenant whose seal
    # SIGNATURE has not moved since its last seal (cursor, read sets,
    # mutation epoch — Core._seal_signature): re-sealing would publish
    # the identical snapshot, so the cycle honestly no-ops it
    # (``serve_noop_cycles``).  Off = every cycle re-seals, the
    # O(state) steady state (the bench's comparison arm).
    noop_skip: bool = True


@dataclass
class TenantResult:
    """One tenant's outcome for one service cycle.  ``path`` is how its
    ops folded: ``batched`` (the mega-fold), ``solo`` (spilled to the
    single-tenant accelerator bulk path), ``perop`` (decoder declined —
    python per-op fold), ``empty`` (no new ops), or ``error``."""

    path: str = "empty"
    rows: int = 0
    latency_s: float = 0.0
    sealed: bool = False
    error: str | None = None


@dataclass
class _TenantWork:
    idx: int
    core: object
    actors: list = field(default_factory=list)
    files: list = field(default_factory=list)
    groups: list = field(default_factory=list)  # (key, idxs, middles)
    clears: list = field(default_factory=list)
    payloads: list = field(default_factory=list)
    metas: list = field(default_factory=list)
    actors_sorted: list = field(default_factory=list)
    kind: str | None = None  # "orset" | "gcounter" | None (solo type)
    cols: tuple | None = None  # decoded columns + vocabs
    prepared: tuple | None = None  # fold-phase planes/vocabs
    packed: tuple | None = None  # planes-packed checkpoint payload
    state_obj: tuple | None = None  # pre-built snapshot state obj
    delta_cut: dict | None = None  # device-cut delta candidate
    result: TenantResult = field(default_factory=TenantResult)

    @property
    def ok(self) -> bool:
        return self.result.error is None


async def _take_slot(sem: asyncio.Semaphore, idx: int) -> None:
    """Take one of a phase's ``io_width`` slots (its semaphore) for tenant
    ``idx``; the caller releases it.  Where every slot is taken the wait
    for one is the span ``serve.slot_wait`` (meta: the tenant), a child of
    the phase's span: the part of a tenant's latency that is queueing, not
    service.  A tenant that finds a slot free opens no span."""
    if sem.locked():
        with trace.span("serve.slot_wait", meta=idx):
            await sem.acquire()
    else:
        await sem.acquire()


def _actor_table(state, actors) -> list:
    """Sorted actor table for the native decoders: the storage listing
    plus every actor the state mentions (the serving twin of
    ``TpuAccelerator._orset_actor_table``, without the fast-path
    micro-optimizations — tenant tables are small by definition)."""
    actor_set = set(actors)
    if isinstance(state, ORSet):
        actor_set.update(state.clock.counters)
        for entry in state.entries.values():
            actor_set.update(entry)
        for dfr in state.deferred.values():
            actor_set.update(dfr)
    elif isinstance(state, GCounter):
        actor_set.update(state.clock.counters)
    return sorted(actor_set)


def _decode_orset_columns(adapter, payloads, actors_sorted):
    """One tenant's payloads → ``(kind, member, actor, counter, members,
    replicas)`` columns.  Native span decode first; the Python
    columnarizer takes over when the native decoder declines OR a
    member value collision (1 == True, 0.0 == -0.0) makes the native
    per-bytes vocab unrepresentable as dense planes — the Python path
    interns by value, which IS the host dict semantics."""
    from ..ops.native_decode import decode_orset_payload_batch

    try:
        decoded = decode_orset_payload_batch(payloads, actors_sorted)
    except RuntimeError:  # native lib unavailable on this box
        decoded = None
    if decoded is not None:
        kind, member_idx, actor_idx, counter, member_objs = decoded
        members = K.Vocab(member_objs)
        if len(members) == len(member_objs):
            replicas = K.Vocab.presorted_unique(list(actors_sorted))
            return kind, member_idx, actor_idx, counter, members, replicas
    ops = [
        adapter.op_from_obj(o) for p in payloads for o in codec.unpack(p)
    ]
    members, replicas = K.Vocab(), K.Vocab(list(actors_sorted))
    cols = K.orset_ops_to_columns(ops, members, replicas)
    return cols.kind, cols.member, cols.actor, cols.counter, members, replicas


def _decode_gcounter_columns(adapter, payloads, actors_sorted):
    """One tenant's payloads → ``(actor, counter, replicas)`` columns,
    or None when the rows are not plain G-Counter increments (the
    per-op path then decides, exactly as the solo bulk path would)."""
    from ..ops.native_decode import decode_counter_payload_batch

    try:
        decoded = decode_counter_payload_batch(payloads, actors_sorted)
    except RuntimeError:  # native lib unavailable on this box
        decoded = None
    if decoded is not None:
        sign, actor_idx, counter = decoded
        if len(sign) and bool(np.any(sign != POS)):
            return None
        return actor_idx, counter, K.Vocab.presorted_unique(
            list(actors_sorted)
        )
    ops = [
        adapter.op_from_obj(o) for p in payloads for o in codec.unpack(p)
    ]
    cols = K.counter_ops_to_columns(ops, K.Vocab(list(actors_sorted)))
    if len(cols.sign) and bool(np.any(cols.sign != POS)):
        return None
    return cols.actor, cols.counter, cols.replicas


class FoldService:
    """Batch many tenants' compactions into shared device dispatches.

    ``tenants`` are OPEN :class:`~crdt_enc_tpu.core.Core` handles, each
    attached to its own remote; the service takes over their compaction
    cadence (``run_cycle`` ≈ one ``compact()`` for every tenant).  The
    service owns the write side of its tenants while a cycle runs the
    same way a solo compact does — concurrent local ``apply_ops`` are
    honored (the fold phase is one sync section), but a second
    concurrent compactor on the same tenant is the caller's bug, as it
    always was.
    """

    def __init__(self, tenants, config: ServeConfig | None = None,
                 live_port: int | None = None, mesh=None):
        self.tenants = list(tenants)
        self.config = config if config is not None else ServeConfig()
        # device mesh (parallel.mesh.make_mesh): with more than one
        # device the bucketed mega-folds run the SPMD tenant kernels —
        # tenant lanes over dp, member planes over mp — and oversize
        # spills route through a service-owned mesh accelerator's
        # orset_fold_sharded path instead of the tenant's solo chip.
        # The planner quantizes bucket classes to the mesh axes, so the
        # zero-steady-state-recompile contract survives sharding.
        self.mesh = mesh
        self._mesh_active = mesh is not None and mesh.size > 1
        self._mesh_accel = None
        if self._mesh_active:
            from ..parallel.accel import TpuAccelerator

            self._mesh_accel = TpuAccelerator(min_device_batch=1, mesh=mesh)
            trace.gauge("serve_mesh_devices", mesh.size)
        self.warm = (
            PlaneWarmTier(
                self.config.warm_bytes,
                mesh_key=mesh if self._mesh_active else None,
            )
            if self.config.warm
            else None
        )
        # the mesh-identity guard, enforced where entries are consumed:
        # a tier built for another device layout holds plane slices this
        # service cannot address (today the service builds its own tier,
        # so this can only fire if tier injection is ever added — which
        # is exactly when it must)
        if self.warm is not None and not self.warm.compatible_with(
            mesh if self._mesh_active else None
        ):
            raise ValueError(
                "warm tier belongs to a different mesh identity"
            )
        # service-owned live telemetry endpoint (obs/live.py): /metrics,
        # /healthz (per-tenant watermarks + the last cycle summary),
        # /snapshot.  live_port=0 binds an ephemeral port (see
        # self.live.port); None = no server (the process-default
        # CRDT_OBS_HTTP server, if any, still receives publications).
        self.live = None
        if live_port is not None:
            from ..obs.live import LiveTelemetryServer

            self.live = LiveTelemetryServer(port=live_port)
            self.live.start()
        # last cycle's summary (tenant paths, wall, SLO burn) — what
        # /healthz shows and the cycle sink record carries
        self.last_cycle_summary: dict | None = None
        # lifecycle guards: a second close() is a logged no-op, a cycle
        # on a closed service (or overlapping a running one) is a loud
        # error — never a hang or an interleaved fold
        self._closed = False
        self._cycle_running = False
        # shared-owner serialization (run_cycle_shared): lazily built per
        # event loop so a service outliving one asyncio.run() can be
        # shared again under the next loop
        self._owner_lock: asyncio.Lock | None = None
        self._owner_loop = None

    @property
    def closed(self) -> bool:
        return self._closed

    def close(self) -> None:
        """Graceful shutdown of service-owned resources (the live
        telemetry listener; tenants stay open — they are the caller's).
        Idempotent: a second close is a logged no-op, never a hang."""
        if self._closed:
            logger.warning("FoldService.close(): already closed (no-op)")
            return
        self._closed = True
        if self.live is not None:
            self.live.stop()

    # ------------------------------------------------------------- cycle
    async def run_cycle(self, tenants=None) -> list[TenantResult]:
        """One service cycle: ingest → decode → bucketed mega-folds →
        per-tenant seal.  ``tenants`` overrides the fleet for THIS cycle
        (the daemon's staleness scheduler compacts subsets); default is
        ``self.tenants``.  Returns one :class:`TenantResult` per tenant
        (index-aligned with the cycled list).  Tenant failures are
        isolated: an erroring tenant reports ``path="error"`` and the
        rest of the fleet still compacts.

        NOT reentrant: the fold phase assumes exclusive ownership of the
        cycle's tenants, so an overlapping ``run_cycle`` (or one on a
        closed service) raises ``RuntimeError`` immediately instead of
        silently interleaving two fleets' folds."""
        if self._closed:
            raise RuntimeError("FoldService is closed; run_cycle refused")
        if self._cycle_running:
            raise RuntimeError(
                "FoldService.run_cycle is not reentrant: a cycle is "
                "already in flight on this service"
            )
        self._cycle_running = True
        try:
            return await self._run_cycle(
                self.tenants if tenants is None else list(tenants)
            )
        finally:
            self._cycle_running = False

    async def run_cycle_shared(self, tenants=None) -> list[TenantResult]:
        """Subset-cycle entry for MULTIPLE concurrent owners sharing one
        service (the population runner's lanes, docs/simulation.md
        "Population runs"): overlapping calls QUEUE on an internal
        asyncio lock and run one full cycle at a time, instead of
        tripping :meth:`run_cycle`'s non-reentrancy error.  Each queued
        cycle is exactly the cycle its owner would have run on a private
        service — the fold phase still has exclusive ownership of its
        tenants for the duration, the shared warm tier is keyed by
        tenant-state identity so owners never alias — which is what
        keeps a lane's results bit-identical to its serial twin while P
        lanes amortize one set of jitted programs.  Single-owner callers
        should keep using :meth:`run_cycle`: the loud overlap error
        there is a real bug-catcher, not a nuisance."""
        loop = asyncio.get_running_loop()
        if self._owner_lock is None or self._owner_loop is not loop:
            self._owner_lock = asyncio.Lock()
            self._owner_loop = loop
        async with self._owner_lock:
            return await self.run_cycle(tenants)

    async def _run_cycle(self, tenants) -> list[TenantResult]:
        t0 = time.perf_counter()
        works = [_TenantWork(i, core) for i, core in enumerate(tenants)]
        # serve.run_cycle is the whole call; serve.cycle inside it ends
        # before the telemetry (obs_report gap reads it as the cycle's
        # wall).  The seven phases run one after another and partition it:
        # each phase span is the WALL of its phase (the spans inside
        # them are summed over the tenants in flight)
        with trace.span("serve.run_cycle"):
            with trace.span("serve.cycle"):
                with trace.span("serve.phase.ingest"):
                    await self._ingest_all(works)
                with trace.span("serve.phase.decrypt"):
                    await self._decrypt_all(works)
                with trace.span("serve.phase.decode"):
                    decodable = [
                        w for w in works if w.ok and w.kind and w.payloads
                    ]
                    if decodable:
                        await asyncio.to_thread(self._decode_all, decodable)
                with trace.span("serve.phase.fold"):
                    self._fold_batched(works)
                with trace.span("serve.phase.fallback"):
                    await self._fold_fallbacks(works)
                with trace.span("serve.phase.seal"):
                    await self._seal_all(works, t0)
                with trace.span("serve.phase.continue"):
                    self._stamp_continuations(works)
            trace.add("serve_cycles", 1)
            trace.add("serve_tenants", len(works))
            results = [w.result for w in works]
            with trace.span("serve.publish"):
                summary = self._publish_cycle(
                    tenants, results, time.perf_counter() - t0
                )
        # with every span of the cycle closed: a record takes the
        # registry's snapshot, and carries only what has ended
        if summary is not None:
            await self._sink_cycle(summary)
        return results

    def _publish_cycle(self, tenants, results, wall_s: float) -> dict | None:
        """Post-cycle telemetry: the cycle summary (tenant paths, wall,
        per-tenant seal-latency SLO burn) goes to the live /healthz
        endpoint; each sealed tenant's replication status (sampled by
        its own ``_compact_seal``) feeds the live health map.  Strictly
        after the fold/seal work, never on the hot path, and never
        fatal to the cycle it describes.  Returns the summary (None if
        it could not be made) for the ``serve_cycle`` sink record."""
        from ..obs import live as obs_live
        from ..obs import slo as obs_slo

        try:
            burn = obs_slo.cycle_burn(results)
            paths: dict[str, int] = {}
            for r in results:
                paths[r.path] = paths.get(r.path, 0) + 1
            summary = {
                "tenants": len(results),
                "sealed": sum(1 for r in results if r.sealed),
                "errors": sum(1 for r in results if r.error is not None),
                "paths": paths,
                "wall_s": round(wall_s, 4),
                "slo": burn,
            }
            self.last_cycle_summary = summary
            trace.gauge("serve_slo_seal_burn", burn["burn_rate"])
            target = self.live if self.live is not None \
                else obs_live.default_server()
            if target is not None:
                target.publish_cycle("fold_service", summary)
                # only tenants that SEALED this cycle republished a
                # fresh replication sample (_compact_seal's sampler) —
                # republishing a quiet/errored tenant's old status
                # would stamp stale watermark data with a current ts,
                # hiding exactly the wedged-replica staleness /healthz
                # exists to expose
                for core, r in zip(tenants, results):
                    status = getattr(core, "last_replication_status", None)
                    if r.sealed and status is not None:
                        target.publish_health(status)
            return summary
        except Exception:  # telemetry must not fail the fleet cycle
            logger.debug("cycle telemetry publication failed",
                         exc_info=True)
            return None

    async def _sink_cycle(self, summary: dict) -> None:
        """One ``serve_cycle`` sink record, when a sink is configured.
        Written after ``serve.run_cycle`` has closed, so the k-th record
        carries k of every span of a cycle, and its events are its own
        cycle's."""
        from ..obs import sink as obs_sink

        try:
            if obs_sink.default_sink() is not None:
                await asyncio.to_thread(
                    obs_sink.maybe_write, "serve_cycle", summary
                )
        except Exception:  # telemetry must not fail the fleet cycle
            logger.debug("cycle sink record failed", exc_info=True)

    # ------------------------------------------------------- strong reads
    async def read_strong(self, core, *, max_lag=None, min_cursor=None,
                          refresh: bool = True):
        """Per-tenant strong read through the serving layer
        (docs/strong_reads.md): the same stable-prefix guarantee as
        ``Core.read(linearizable=True)`` — served tenants do not trade
        consistency for batching.  ``refresh=False`` skips the
        per-read ``read_remote`` when the caller knows the tenant just
        cycled (the daemon's post-cycle waiter resolution); the
        default refreshes, so a standalone endpoint call observes the
        latest published cursors.  Refusals raise
        :class:`~crdt_enc_tpu.read.StalenessError` unchanged."""
        if self._closed:
            raise RuntimeError("FoldService is closed; read_strong refused")
        with trace.span("serve.read_strong"):
            trace.add("serve_strong_reads", 1)
            return await core.read(
                linearizable=True, max_lag=max_lag,
                min_cursor=min_cursor, refresh=refresh,
            )

    # ------------------------------------------------------------ ingest
    async def _ingest_all(self, works) -> None:
        sem = asyncio.Semaphore(max(1, self.config.io_width))

        async def one(w: _TenantWork):
            await _take_slot(sem, w.idx)
            try:
                try:
                    with trace.span("serve.ingest", meta=w.idx):
                        # remote meta, snapshots, then the decrypt-
                        # deferred ops load (ciphertexts grouped by
                        # sealing key; the cycle-wide decrypt phase below
                        # opens every tenant's in ONE thread hop): one
                        # worker job a tenant where the storage offers
                        # sync twins of the reads, awaited in turn else
                        w.actors, w.files, w.groups = (
                            await w.core.poll_sealed_ops()
                        )
                except Exception as e:  # tenant isolation, never fleet-fatal
                    w.result.error = repr(e)
                    w.result.path = "error"
            finally:
                sem.release()

        await asyncio.gather(*(one(w) for w in works))

    # ----------------------------------------------------------- decrypt
    async def _decrypt_all(self, works) -> None:
        """Open every tenant's ciphertexts, then validate versions.

        Tenants whose cryptor exposes the sync bulk hook
        (``Cryptor.decrypt_batch_fn``) all decrypt inside ONE
        ``asyncio.to_thread`` hop — per-tenant thread round-trips
        (~1ms each) would otherwise dominate a many-small-tenant cycle;
        the rest fall back to the normal async ``decrypt_batch``.  The
        version checks (``_validate_chunk``) run back on the event
        loop: they read live cursors, which must not race a concurrent
        apply."""
        sync_plans: list[tuple[_TenantWork, list]] = []
        async_works: list[_TenantWork] = []
        for w in works:
            if not w.ok or not w.files:
                continue
            try:
                plans = []
                for key, idxs, mids in w.groups:
                    fn = w.core.cryptor.decrypt_batch_fn(key.material)
                    if fn is None:
                        plans = None
                        break
                    plans.append((fn, idxs, mids))
            except Exception as e:  # e.g. foreign key version — tenant-local
                w.result.error = repr(e)
                w.result.path = "error"
                continue
            if plans is None:
                async_works.append(w)
            else:
                sync_plans.append((w, plans))

        def run_sync_plans():
            from ..core.core import _QUARANTINED

            for w, plans in sync_plans:
                try:
                    clears: list = [None] * len(w.files)
                    for fn, idxs, mids in plans:
                        try:
                            outs = fn(mids)
                        except Exception:
                            # a damaged blob in the batch: isolate it
                            # per file — the core's quarantine
                            # discipline (skip + counter + held
                            # cursor), not a whole-tenant error.  But
                            # the WHOLE batch failing is a dead
                            # cryptor / damaged key, not file damage:
                            # re-raise into the tenant error (the
                            # core's _decrypt_tolerant escalation rule)
                            outs, failed = [], []
                            for i, m in zip(idxs, mids):
                                try:
                                    outs.append(fn([m])[0])
                                except Exception as e:
                                    outs.append(_QUARANTINED)
                                    failed.append((i, e))
                            if len(mids) > 1 and len(failed) == len(mids):
                                from ..core.core import IngestDecryptError

                                raise IngestDecryptError(
                                    f"all {len(mids)} op files in the "
                                    "tenant batch failed to open"
                                ) from failed[-1][1]
                            for i, e in failed:
                                actor, version, _ = w.files[i]
                                w.core._note_quarantine(
                                    "op",
                                    f"{actor.hex()}:v{version}", e,
                                )
                        for i, clear in zip(idxs, outs):
                            clears[i] = clear
                    w.clears = clears
                    trace.add(
                        "bytes_decrypted",
                        sum(len(m) for _, _, mids in plans for m in mids),
                    )
                except Exception as e:  # tenant-local (plan-level surprise)
                    w.result.error = repr(e)
                    w.result.path = "error"

        if sync_plans:
            with trace.span("serve.decrypt", meta=len(sync_plans)):
                await asyncio.to_thread(run_sync_plans)
        for w in async_works:
            try:
                with trace.span("serve.decrypt", meta=w.idx):
                    clears = [None] * len(w.files)
                    for key, idxs, mids in w.groups:
                        # per-file quarantine on damage, exactly the
                        # solo bulk path's discipline
                        outs = await w.core._decrypt_tolerant(
                            key, [w.files[i] for i in idxs], mids
                        )
                        for i, clear in zip(idxs, outs):
                            clears[i] = clear
                    w.clears = clears
                    trace.add(
                        "bytes_decrypted",
                        sum(len(m) for _, _, mids in w.groups for m in mids),
                    )
            except Exception as e:
                w.result.error = repr(e)
                w.result.path = "error"
        # sync section: inner version checks WITHOUT cursor advance —
        # cursors move only after the fold lands
        for w in works:
            if not w.ok or not w.files:
                continue
            try:
                w.payloads, w.metas = w.core._validate_chunk(
                    w.files, w.clears
                )
                state = w.core._data.state
                if isinstance(state, ORSet):
                    w.kind = "orset"
                elif isinstance(state, GCounter):
                    w.kind = "gcounter"
                if w.payloads:
                    w.actors_sorted = _actor_table(state, w.actors)
            except Exception as e:
                w.result.error = repr(e)
                w.result.path = "error"

    # ------------------------------------------------------------ decode
    def _decode_all(self, works) -> None:
        """Cross-tenant decode fan-out: the PR-3 producer pool with
        TENANTS as the work items.  Runs off the event loop (the native
        decode calls release the GIL, so the workers genuinely overlap);
        results land on each work item in tenant order."""
        from ..ops.stream import run_ingest_pipeline, stream_producer_count

        producers = stream_producer_count(self.config.producers)
        # a few work items per producer: per-item queue/span overhead is
        # ~1ms, so thousands of tiny tenants ride in tenant GROUPS
        group = max(1, -(-len(works) // max(producers * 4, 1)))
        chunks = [
            works[i : i + group] for i in range(0, len(works), group)
        ]

        def decode_one(w: _TenantWork):
            with trace.span("serve.decode", meta=w.idx):
                if w.kind == "orset":
                    return _decode_orset_columns(
                        w.core.adapter, w.payloads, w.actors_sorted
                    )
                return _decode_gcounter_columns(
                    w.core.adapter, w.payloads, w.actors_sorted
                )

        def ingest(chunk: list, k: int):
            out = []
            for w in chunk:
                try:
                    out.append(decode_one(w))
                except Exception as e:  # tenant isolation
                    out.append(("error", e))
            return out

        def reduce(decoded_list, k: int):
            for w, decoded in zip(chunks[k], decoded_list):
                if isinstance(decoded, tuple) and len(decoded) == 2 and \
                        decoded[0] == "error":
                    w.result.error = repr(decoded[1])
                    w.result.path = "error"
                else:
                    w.cols = decoded  # None = per-op fallback

        run_ingest_pipeline(
            chunks, ingest, reduce, producers=producers,
            thread_prefix="crdt-serve-producer",
        )

    # -------------------------------------------------------------- fold
    def _fold_batched(self, works) -> None:
        """Plan and run the bucketed mega-folds.  One synchronous
        section per cycle: plane capture, kernel dispatch, writeback and
        cursor advance never interleave with concurrent applies."""
        by_idx: dict[int, _TenantWork] = {}
        shapes: list[TenantShape] = []
        with trace.span("serve.plan"):
            for w in works:
                if not (w.ok and w.kind and w.payloads):
                    continue
                if w.cols is None:
                    w.result.path = "perop"
                    continue
                if len(w.cols[0]) == 0:
                    # validated files that decode to ZERO rows (e.g. an
                    # empty-ctx remove, or an empty op list a foreign
                    # writer sealed): the fold is a no-op but the
                    # cursors MUST advance exactly as the solo path's
                    # — or the sealed snapshot carries a stale cursor
                    # and the covered files are re-read forever
                    w.core._advance_cursors(w.metas)
                    w.result.path = "batched"
                    continue
                prepared = self._prepare_tenant(w)
                if prepared is None:
                    w.result.path = "solo"
                    continue
                shape = prepared[0]
                w.prepared = prepared[1]
                by_idx[w.idx] = w
                shapes.append(shape)
            buckets, solo = plan_buckets(
                shapes,
                rows_cap=self.config.rows_cap,
                cells_cap=self.config.cells_cap,
                tenants_cap=self.config.tenants_cap,
                dp=self.mesh.shape["dp"] if self._mesh_active else 1,
                mp=self.mesh.shape["mp"] if self._mesh_active else 1,
            )
            for key in solo:
                by_idx[key].result.path = "solo"
                trace.add("serve_solo_spills", 1)
                del by_idx[key]
        trace.gauge("serve_buckets", len(buckets))
        for bi, bucket in enumerate(buckets):
            trace.add("serve_buckets_folded", 1)
            try:
                if bucket.kind == "orset":
                    self._fold_orset_bucket(bi, bucket, by_idx)
                else:
                    self._fold_gcounter_bucket(bi, bucket, by_idx)
            except Exception as e:  # e.g. device OOM stacking a bucket
                # tenant isolation at bucket granularity: tenants whose
                # scatter already landed (path "batched", cursors
                # advanced) go on to seal; the rest of the bucket
                # reports the error and the OTHER buckets still fold
                for key in bucket.tenants:
                    w = by_idx[key]
                    if w.result.path != "batched":
                        w.result.error = repr(e)
                        w.result.path = "error"

    def _prepare_tenant(self, w: _TenantWork):
        """Fold-phase prep for one decoded tenant: resolve vocabularies
        (warm-tier remap or state scan) and pin its ragged shape.
        Returns ``(TenantShape, prepared)`` or None to route the tenant
        to the solo path (wide clocks the int32 planes cannot hold)."""
        state = w.core._data.state
        if w.kind == "orset":
            from ..parallel.accel import TpuAccelerator

            kind, member, actor, counter, members, replicas = w.cols
            entry = self.warm.lookup(state) if self.warm is not None else None
            if entry is not None:
                remapped = TpuAccelerator._remap_to_cache(
                    entry, member, actor, members, replicas
                )
                if remapped is None:
                    entry = None
                else:
                    member, actor = remapped
                    members, replicas = entry.members, entry.replicas
            if entry is None:
                # no planes for this tenant (never folded here, evicted,
                # or mutated since): the first of the rebuild's two host
                # walks, the second is the rows' in _fold_orset_bucket
                with trace.span("serve.rebuild", meta=w.idx):
                    K.orset_scan_vocab(state, members, replicas)
            shape = TenantShape(
                w.idx, "orset", len(kind), len(members), len(replicas)
            )
            return shape, (kind, member, actor, counter, members, replicas,
                           entry)
        actor_idx, counter, replicas = w.cols
        clock0 = K.vclock_to_dense(state.clock, replicas)
        if clock0.dtype != np.int32:
            return None  # >int32 counters: the solo sparse path's regime
        shape = TenantShape(
            w.idx, "gcounter", len(actor_idx), 0, len(replicas)
        )
        return shape, (actor_idx, counter, replicas, clock0)

    def _fold_orset_bucket(self, bi: int, bucket, by_idx) -> None:
        import jax

        from ..core.core import CHECKPOINT_FMT_ORSET
        from ..parallel.accel import TpuAccelerator

        cpu_backend = jax.default_backend() == "cpu"

        # re-quantize at the call site (idempotent — the planner already
        # bucketed) so the jitted statics' boundedness is provenance-
        # checkable (JIT002) right where they are passed
        N_b = _bucket(bucket.rows)
        E_b = _bucket(bucket.members)
        R_b = _bucket(bucket.replicas)
        T = bucket.slots
        # the bucket's class, on its spans: the event log then tells a
        # 512-slot bucket of 128-member tenants from one slot of 65,536
        shape = f"{bi}:{T}x{N_b}x{E_b}x{R_b}"
        kind = np.zeros((T, N_b), np.int8)
        member = np.zeros((T, N_b), np.int32)
        actor = np.full((T, N_b), R_b, np.int32)  # dummy lanes: all-pad
        counter = np.zeros((T, N_b), np.int32)
        clock_rows, add_rows, rm_rows = [], [], []
        # slots whose pre-fold planes ARE the tenant's current delta
        # base (a live warm entry stamped with the base's seal name):
        # after the fold these tenants can cut their delta on device
        # from planes already in hand — no host dict walk, no retained
        # base bytes (docs/delta.md "device-cut deltas")
        cut_slots: list[tuple[int, object]] = []
        cold_slots: list[int] = []  # no warm planes: rows built below
        tenant_cells = 0
        for slot, key in enumerate(bucket.tenants):
            w = by_idx[key]
            k, m, a, c, members, replicas, entry = w.prepared
            if (
                entry is not None
                and entry.seal_name is not None
                and entry.seal_name == w.core.delta_base_name
                and w.core._delta_enabled
                and getattr(w.core.storage, "has_deltas", False)
            ):
                cut_slots.append((slot, key))
            n = len(k)
            kind[slot, :n] = k
            member[slot, :n] = m
            actor[slot, :n] = a
            counter[slot, :n] = c
            E, R = len(members), len(replicas)
            tenant_cells += E * R
            if entry is not None:
                clock0, add0, rm0 = TpuAccelerator._cached_planes_padded(
                    entry, E_b, R_b
                )
            else:
                cold_slots.append(slot)
                clock0 = add0 = rm0 = None
            clock_rows.append(clock0)
            add_rows.append(add0)
            rm_rows.append(rm0)
        if cold_slots:
            # the tenants the warm tier holds nothing for: their planes
            # are rebuilt from the host state, a dict walk a tenant, and
            # uploaded whole with the stacks below
            rebuilt = 0
            with trace.span("serve.rebuild", meta=shape):
                for slot in cold_slots:
                    w = by_idx[bucket.tenants[slot]]
                    members, replicas = w.prepared[4], w.prepared[5]
                    E, R = len(members), len(replicas)
                    clock0, add0, rm0 = K.orset_state_to_planes(
                        w.core._data.state, members, replicas, scanned=True
                    )
                    pads = ((0, E_b - E), (0, R_b - R))
                    clock_rows[slot] = np.pad(clock0, (0, R_b - R))
                    add_rows[slot] = np.pad(add0, pads)
                    rm_rows[slot] = np.pad(rm0, pads)
                    rebuilt += (
                        clock_rows[slot].nbytes + add_rows[slot].nbytes
                        + rm_rows[slot].nbytes
                    )
            trace.add("serve_warm_rebuilds", len(cold_slots))
            trace.add("serve_warm_rebuild_bytes", rebuilt)
        # the padding share: what the stacks hold against what is a tenant's
        trace.add("serve_stack_cells", T * E_b * R_b)
        trace.add("serve_tenant_cells", tenant_cells)
        # every HOST-sourced plane row uploads here (cold scans always;
        # warm-tier rows too on the CPU backend, where the tier stores
        # host views) plus the op columns; device-resident rows re-wrap
        # for free
        trace.add(
            "h2d_bytes",
            sum(
                x.nbytes
                for rows in (clock_rows, add_rows, rm_rows)
                for x in rows
                if isinstance(x, np.ndarray)
            )
            + kind.nbytes + member.nbytes + actor.nbytes + counter.nbytes,
        )
        # stack the pre-fold planes ONCE, a program per 128 slots and
        # never one per tenant: the fold consumes the stacks and — when
        # any slot is cut-eligible — the plane diff reuses the very same
        # device stacks as its base side.  The dummy lanes past the
        # tenants are made inside the programs
        clock_s, add_s, rm_s = K.orset_stack_tenants(
            clock_rows, add_rows, rm_rows, slots=T
        )
        if self._mesh_active:
            # SPMD mega-fold: tenant lanes over dp, member planes over
            # mp (parallel.mesh.orset_fold_tenants_sharded) — slot and
            # member classes already divide the mesh by planner law
            from ..parallel import mesh as pmesh

            orset_step, _ = pmesh.tenant_fold_steps(self.mesh)
            with trace.span("serve.shard", meta=shape):
                out = orset_step(
                    clock_s, add_s, rm_s, kind, member, actor, counter,
                )
            trace.add("serve_sharded_folds", 1)
            trace.add("serve_sharded_tenants", len(bucket.tenants))
        else:
            with trace.span("serve.fold", meta=shape):
                out = K.orset_fold_tenants(
                    clock_s, add_s, rm_s, kind, member, actor, counter,
                    num_members=E_b, num_replicas=R_b,
                )
        with trace.span("serve.scatter", meta=shape):
            clock_all, add_all, rm_all = obs_runtime.pull(*out)
            if self.warm is not None and not cpu_backend:
                # the tenants' next-cycle resume planes, a program per
                # 128 slots: each an owned device buffer, so a warm
                # entry pins its tenant's planes and not the bucket's
                # stacks
                resume = K.orset_unstack_tenants(*out)
            for slot, key in enumerate(bucket.tenants):
                w = by_idx[key]
                _, _, _, _, members, replicas, entry = w.prepared
                E, R = len(members), len(replicas)
                state = w.core._data.state
                folded = K.orset_planes_to_state(
                    clock_all[slot][:R], add_all[slot][:E, :R],
                    rm_all[slot][:E, :R], members, replicas,
                )
                state.clock = folded.clock
                state.entries = folded.entries
                state.deferred = folded.deferred
                note = getattr(w.core.accel, "_note_orset_writeback", None)
                if note is not None:
                    note(state)
                else:
                    state._mut += 1
                w.core._advance_cursors(w.metas)
                # the warm-open checkpoint payload, packed VECTORIZED
                # from the planes just written back (the sparse pack
                # walk was the seal phase's biggest CPU item at fleet
                # scale); the recorded epoch lets save_checkpoint
                # reject it if a concurrent apply lands before the seal
                w.packed = (
                    CHECKPOINT_FMT_ORSET,
                    K.orset_pack_checkpoint_planes(
                        clock_all[slot], add_all[slot], rm_all[slot],
                        members, replicas,
                    ),
                    state._mut,
                )
                # snapshot payload obj without a second state walk: the
                # dicts just written back ARE plane-canonical (entries
                # non-empty, retired horizons already dropped), so
                # wrapping them is exactly ORSet.to_obj's output; the
                # epoch guard keeps the alias safe (any mutation makes
                # _compact_seal re-serialize the live state) and the
                # canonical packer re-sorts, so the sealed bytes equal
                # a solo compact's
                w.state_obj = (
                    {
                        b"c": state.clock.to_obj(),
                        b"e": state.entries,
                        b"d": state.deferred,
                    },
                    state._mut,
                )
                n_rows = len(w.prepared[0])
                w.result.path = "batched"
                w.result.rows = n_rows
                trace.add("serve_rows_folded", n_rows)
                if self.warm is not None:
                    # the tenant's next-cycle resume planes, epoch-
                    # stamped post-writeback.  On an accelerator the
                    # DEVICE slices are kept (no re-upload next cycle);
                    # the CPU backend keeps host copies — "device" and
                    # host are the same silicon there, and small owned
                    # copies beat pinning the whole bucket stack alive
                    if cpu_backend:
                        planes = (
                            clock_all[slot].copy(),
                            add_all[slot].copy(),
                            rm_all[slot].copy(),
                        )
                    else:
                        planes = tuple(rows[slot] for rows in resume)
                    self.warm.store(
                        state, members, replicas, planes,
                        canon=entry.canon if entry is not None else None,
                    )
        if cut_slots:
            # device-cut delta sealing (docs/delta.md): diff the bucket's
            # pre-fold stacks (for eligible slots, byte-identical to the
            # tenants' sealed diff bases) against the post-fold planes in
            # ONE dispatch, gather every slot's diff rows in a second
            # one, and bring the counts, the rows and the base stacks
            # home in one pull each: the device is spoken to per bucket,
            # and the loop over the eligible tenants below is host work
            # only.  Slots that are not
            # cut-eligible ride the same dispatches for free and their
            # rows are simply never read.  A separate span, deliberately
            # outside serve.scatter: attribution groups both under the
            # seal stage without double-counting.
            from ..delta.codec import orset_delta_from_rows

            with trace.span("delta.cut", meta=shape):
                if self._mesh_active:
                    from ..parallel import mesh as pmesh

                    code, counts = pmesh.tenant_diff_step(self.mesh)(
                        clock_s, add_s, rm_s, out[0], out[1], out[2]
                    )
                else:
                    code, counts = K.orset_plane_diff_tenants(
                        clock_s, add_s, rm_s, out[0], out[1], out[2]
                    )
                (counts,) = obs_runtime.pull(counts)  # one (T,) D2H per bucket
                most = max(int(counts[slot]) for slot, _ in cut_slots)
                if most:
                    # O(diff rows) a slot, not O(state): one static
                    # capacity for the bucket, the largest count that
                    # will be read, quantized and capped by the same law
                    # the per-tenant gather used
                    rows_all = obs_runtime.pull(
                        *K.orset_plane_diff_rows_tenants(
                            code, add_s, out[1], out[2],
                            size=min(_bucket(most), E_b * R_b),
                        )
                    )
                # the base sides on the host: the clocks for the wire
                # form, the planes for the seal-time self-verify (numpy
                # views of one pull, so the verify on the seal workers
                # rebuilds the base without touching the device); a
                # bucket in which no eligible tenant verifies leaves the
                # planes where they are
                (base_clocks,) = obs_runtime.pull(clock_s)
                base_adds = base_rms = None
                if any(by_idx[key].core._delta_verify for _, key in cut_slots):
                    base_adds, base_rms = obs_runtime.pull(add_s, rm_s)
                for slot, key in cut_slots:
                    w = by_idx[key]
                    _, _, _, _, members, replicas, entry = w.prepared
                    n_diff = int(counts[slot])
                    if n_diff:
                        rows = tuple(r[slot, :n_diff] for r in rows_all)
                    else:
                        empty = np.zeros(0, np.int64)
                        rows = (empty, empty, empty, empty, empty)
                    dobj = orset_delta_from_rows(
                        rows,
                        members=members.items,
                        replicas=replicas.items,
                        row_width=R_b,
                        base_clock=base_clocks[slot],
                        new_clock=clock_all[slot],
                    )
                    # epoch-guarded candidate: _plan_delta_seal only
                    # accepts it while the base name AND the mutation
                    # epoch still match at seal time
                    w.delta_cut = {
                        "dobj": dobj,
                        "base_name": entry.seal_name,
                        "mut": w.core._data.state._mut,
                        "base_planes": None if base_adds is None else (
                            base_clocks[slot], base_adds[slot],
                            base_rms[slot], members, replicas,
                        ),
                    }

    def _fold_gcounter_bucket(self, bi: int, bucket, by_idx) -> None:
        N_b = _bucket(bucket.rows)
        R_b = _bucket(bucket.replicas)
        T = bucket.slots
        shape = f"{bi}:{T}x{N_b}x0x{R_b}"
        actor = np.full((T, N_b), R_b, np.int32)
        counter = np.zeros((T, N_b), np.int32)
        clock0 = np.zeros((T, R_b), np.int32)
        for slot, key in enumerate(bucket.tenants):
            w = by_idx[key]
            a, c, replicas, dense = w.prepared
            n = len(a)
            actor[slot, :n] = a
            counter[slot, :n] = c
            clock0[slot, : len(dense)] = dense
        trace.add(
            "h2d_bytes", clock0.nbytes + actor.nbytes + counter.nbytes
        )
        if self._mesh_active:
            from ..parallel import mesh as pmesh

            _, gcounter_step = pmesh.tenant_fold_steps(self.mesh)
            with trace.span("serve.shard", meta=shape):
                out = gcounter_step(clock0, actor, counter)
            trace.add("serve_sharded_folds", 1)
            trace.add("serve_sharded_tenants", len(bucket.tenants))
        else:
            with trace.span("serve.fold", meta=shape):
                out = K.gcounter_fold_tenants(
                    clock0, actor, counter, num_replicas=R_b
                )
        with trace.span("serve.scatter", meta=shape):
            (out_all,) = obs_runtime.pull(out)
            for slot, key in enumerate(bucket.tenants):
                w = by_idx[key]
                a, _, replicas, _ = w.prepared
                state = w.core._data.state
                state.clock = K.dense_to_vclock(
                    out_all[slot][: len(replicas)], replicas
                )
                w.core._advance_cursors(w.metas)
                w.result.path = "batched"
                w.result.rows = len(a)
                trace.add("serve_rows_folded", len(a))

    @staticmethod
    def _fallback_rows(w: _TenantWork) -> int:
        """Op-ROW count for a fallback tenant, same units as the batched
        path's ``rows``: the decoded columns when the tenant was decoded
        (solo spills), else a payload unpack count (rare paths only —
        decoder declines and non-columnar types)."""
        if w.cols is not None:
            return len(w.cols[0])
        return sum(len(codec.unpack(p)) for p in w.payloads)

    # -------------------------------------------------------- fallbacks
    async def _fold_fallbacks(self, works) -> None:
        """Tenants outside the mega-fold: solo spills run the existing
        single-tenant bulk accelerator path on the already-decrypted
        payloads; decoder-declined tenants fold per-op — both the exact
        machinery a solo compact would have used."""
        for w in works:
            if not w.ok or not w.payloads:
                continue
            core = w.core
            # with an active mesh, a columnar oversize spill folds
            # through the service-owned mesh accelerator — the existing
            # solo orset_fold_sharded / gcounter_fold_sharded SPMD path
            # (one huge tenant uses the whole pod) — instead of the
            # tenant's own single-chip accelerator.  The writeback bumps
            # the state's _mut epoch, so any planes the tenant's own
            # accel cached for it expire by token, never go stale.
            spill_accel = (
                self._mesh_accel
                if self._mesh_accel is not None
                and w.kind in ("orset", "gcounter")
                else core.accel
            )
            try:
                if w.result.path == "solo":
                    # a spilled tenant folds at power-of-two vocabulary
                    # classes from here on, like every bucket: its
                    # vocabulary grows by a few members a cycle, and the
                    # solo dense fold otherwise compiles per exact
                    # (members, replicas) — a compile a cycle, for ever
                    if getattr(spill_accel, "bucket_vocab", None) is False:
                        spill_accel.bucket_vocab = True
                    with trace.span("serve.solo", meta=w.idx):
                        ok = spill_accel.fold_payloads(
                            core._data.state, list(w.payloads),
                            actors_hint=w.actors_sorted,
                        )
                    if ok:
                        core._advance_cursors(w.metas)
                    else:
                        # the spilled tenant's bulk path declined too:
                        # report the machinery that actually folded it
                        await core._fold_chunk_python(w.files, w.clears)
                        w.result.path = "perop"
                        trace.add("serve_python_fallbacks", 1)
                    w.result.rows = self._fallback_rows(w)
                elif w.kind is None or w.result.path == "perop":
                    # no columnar kind (solo type) or decoder declined
                    ok = core.accel.fold_payloads(
                        core._data.state, list(w.payloads),
                        actors_hint=w.actors_sorted,
                    ) if w.kind is None else False
                    if ok:
                        core._advance_cursors(w.metas)
                        w.result.path = "solo"
                    else:
                        await core._fold_chunk_python(w.files, w.clears)
                        w.result.path = "perop"
                        trace.add("serve_python_fallbacks", 1)
                    w.result.rows = self._fallback_rows(w)
            except Exception as e:
                w.result.error = repr(e)
                w.result.path = "error"

    def _stamp_continuations(self, works) -> None:
        """Post-seal half of the persistent fold continuation: for every
        tenant that sealed this cycle and whose warm planes still match
        its live state, stamp the entry with the sealed snapshot's name
        (= the tenant's new delta base).  Next cycle those planes serve
        double duty — fold base for the tenant's new rows AND diff base
        for the device-cut delta — so the steady-state cycle touches
        only the tail.  Any doubt (mutation since the fold, no delta
        base, fallback-path seal) just leaves the entry unstamped: the
        next seal walks the host path, byte-identically."""
        if self.warm is None:
            return
        with trace.span("serve.continue"):
            stamped = 0
            for w in works:
                if not (w.ok and w.result.sealed):
                    continue
                name = w.core.delta_base_name
                if name is None:
                    continue
                if self.warm.stamp_seal(w.core._data.state, name):
                    stamped += 1
            if stamped:
                trace.add("serve_continuations", stamped)

    # -------------------------------------------------------------- seal
    async def _seal_all(self, works, t0: float) -> None:
        sem = asyncio.Semaphore(max(1, self.config.io_width))

        async def one(w: _TenantWork):
            await _take_slot(sem, w.idx)
            try:
                if not w.ok:
                    trace.add("serve_tenant_errors", 1)
                    w.result.latency_s = time.perf_counter() - t0
                    return
                if (
                    w.result.path == "empty"
                    and self.config.noop_skip
                    and w.core._last_seal_sig is not None
                    and w.core._seal_signature() == w.core._last_seal_sig
                ):
                    # quiet tenant, nothing moved since its last seal
                    # (cursor, read sets, mutation epoch all equal):
                    # re-sealing would publish the identical snapshot.
                    # Skip the seal, GC, checkpoint AND replication
                    # sample — the honest O(tail) no-op
                    # (docs/multitenant.md "cycle-cost law")
                    trace.add("serve_noop_cycles", 1)
                    w.result.latency_s = time.perf_counter() - t0
                    return
                if w.result.path == "empty" and not self.config.seal_empty:
                    w.result.latency_s = time.perf_counter() - t0
                    return
                try:
                    with trace.span("serve.seal", meta=w.idx):
                        # _backlog=[]: the cycle's ingest folded
                        # everything its own listing found — no second
                        # per-actor storage probe per tenant (the PR-6
                        # probe-cost fix, regression-pinned)
                        await w.core._compact_seal(
                            _backlog=[], _packed_state=w.packed,
                            _state_obj=w.state_obj,
                            _delta_cut=w.delta_cut,
                        )
                    w.result.sealed = True
                except Exception as e:
                    w.result.error = repr(e)
                    w.result.path = "error"
                    trace.add("serve_tenant_errors", 1)
                dt = time.perf_counter() - t0
                w.result.latency_s = dt
                if w.result.sealed:
                    # the registry documents this histogram as seal
                    # COMPLETIONS — failed seals carry their latency on
                    # the TenantResult but stay out of the percentiles
                    trace.observe("serve.tenant", dt)
            finally:
                sem.release()

        await asyncio.gather(*(one(w) for w in works))
