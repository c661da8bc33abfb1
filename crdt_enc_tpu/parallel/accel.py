"""TPU accelerator: the drop-in replacement for the core's host fold/merge.

Plugs into ``OpenOptions.accelerator`` (crdt_enc_tpu/core/adapters.py
defines the interface + the host reference implementation).  Each call
converts sparse host state ↔ dense planes around one jitted kernel; the
conversion cost is amortized over whole op batches, which is exactly the
compaction shape (thousands of files → one fold).  Small batches fall back
to the host loop — dispatch overhead would dominate.

Shapes are bucket-padded (powers of two) so repeated compactions reuse
compiled programs (SURVEY.md §7 hard part 3).
"""

from __future__ import annotations

import os

import numpy as np

from ..core.adapters import HostAccelerator
from ..models import GCounter, LWWMap, ORSet, PNCounter
from ..models.counters import NEG, POS
from ..models.vclock import Dot, VClock
from ..obs import runtime as obs_runtime
from ..utils import trace
from .. import ops as K

MIN_DEVICE_BATCH = 256  # below this the host loop wins


def _bucket(n: int, floor: int = 8) -> int:
    b = floor
    while b < n:
        b *= 2
    return b


class _OrsetPlaneCache:
    """Device-resident ORSet state planes carried between folds.

    After a dense fold writes its result back to the sparse host state,
    the very planes it computed — already on device, already normalized,
    byte-equal to the state — are kept here so the NEXT fold on the same
    un-mutated state skips the state→planes walk and the full-state H2D
    re-upload (repeated ``read_remote``/``compact`` rounds in one
    process).  Validity is (object identity via weakref) × (the state's
    ``_mut`` mutation epoch recorded at writeback): any host mutation —
    per-op apply, CvRDT merge, another accelerator path's writeback —
    bumps the epoch and the entry silently expires.  The vocabularies
    are the fold vocabs of the caching round; later batches remap onto
    them (value-collision-guarded, exactly like the fold sessions)."""

    __slots__ = ("ref", "token", "members", "replicas", "planes", "canon")

    def __init__(self, ref, token, members, replicas, planes, canon):
        self.ref = ref
        self.token = token
        self.members = members
        self.replicas = replicas
        self.planes = planes  # (clock, add, rm) device arrays
        self.canon = canon  # member slot -> canonical packed bytes


class TpuAccelerator(HostAccelerator):
    """Accelerates ORSet / G-Counter / PN-Counter / LWW-Map folds and
    ORSet / MVReg merges; anything else (EmptyCrdt, custom types — and
    any batch too small to beat dispatch overhead) falls back to the
    host loops.

    ``mesh``: an optional ``jax.sharding.Mesh`` with ``(dp, mp)`` axes
    (``parallel.mesh.make_mesh`` / ``distributed.make_multihost_mesh``).
    With more than one device, every fold and merge routes through the
    sharded SPMD kernels — op rows over ``dp``, state planes over ``mp`` —
    so ``Core.compact`` executes multi-chip, not on device 0 of a pod."""

    def __init__(
        self,
        min_device_batch: int = MIN_DEVICE_BATCH,
        mesh=None,
        sparse_device: bool = False,
        map_fold_impl: str | None = None,
        sharded_stream: bool | None = None,
        stream_producers: int = 0,
        plane_reuse: bool | None = None,
        bucket_vocab: bool | None = None,
    ):
        self.min_device_batch = min_device_batch
        self.mesh = mesh
        # vocabulary-axis bucketing (None = env CRDT_BUCKET_VOCAB, default
        # OFF): lift the member/replica plane dims — and merge stack
        # heights — to power-of-two classes (zero padding; sliced back at
        # writeback).  Row counts are always bucketed; this extends the
        # same recompilation bound to E/R/S, so many small states with
        # churning vocabularies (the simulator's population shape) share
        # one compiled program set instead of compiling per vocab size.
        if bucket_vocab is None:
            bucket_vocab = os.environ.get(
                "CRDT_BUCKET_VOCAB", ""
            ).strip().lower() in ("1", "true", "on", "yes", "enabled")
        self.bucket_vocab = bool(bucket_vocab)
        # device-resident plane reuse across fold rounds (None = auto-on;
        # CRDT_PLANE_REUSE=0 opts out).  Single-device only: the sharded
        # fold keeps planes mp-distributed and re-builds per round.
        if plane_reuse is None:
            plane_reuse = os.environ.get(
                "CRDT_PLANE_REUSE", ""
            ).strip().lower() not in ("0", "false", "off", "no", "disabled")
        self.plane_reuse = bool(plane_reuse)
        self._plane_cache: _OrsetPlaneCache | None = None
        # mesh-sharded streaming fold (parallel/session.py
        # _device_feed_sharded): None = auto — ON whenever the mesh is
        # active, so a pod compaction streams through the SPMD kernels
        # instead of buffering the whole row batch host-side.
        # CRDT_SHARDED_STREAM=0/1 overrides the auto default; an
        # unrecognized value keeps the auto default (never a silent
        # opt-in from a typo'd opt-out).
        if sharded_stream is None:
            env = os.environ.get("CRDT_SHARDED_STREAM", "").strip().lower()
            if env in ("0", "false", "off", "no", "disabled"):
                sharded_stream = False
            elif env in ("1", "true", "on", "yes", "enabled") or not env:
                sharded_stream = True
            else:
                import warnings

                warnings.warn(
                    f"CRDT_SHARDED_STREAM={env!r} not recognized; "
                    "keeping the auto default (on with an active mesh)",
                    stacklevel=2,
                )
                sharded_stream = True
        self.sharded_stream = bool(sharded_stream) and self._mesh_active()
        # ingest fan-out width for the core's pipelined bulk ingest:
        # 0 = auto (ops.stream.stream_producer_count — env
        # CRDT_STREAM_PRODUCERS, else cpu_count-derived)
        self.stream_producers = stream_producers
        # every XLA backend compile around the jitted/Pallas folds bumps
        # the jax_compiles counter — steady-state growth is the ADVICE-r5
        # unbounded-recompile bug class, now mechanically visible
        # (default-on; an explicit operator track_recompiles(False) wins)
        obs_runtime.ensure_recompile_tracking()
        # likewise the collector's passes (gc_passes, gc_pause_us, ...):
        # a pass stops every thread inside whichever span is open
        obs_runtime.ensure_gc_tracking()
        # CrdtMap scatter phase: "host" (numpy reference), "device"
        # (ops/map_device.py jit), or None = device for batches past
        # min_device_batch
        self.map_fold_impl = map_fold_impl
        # sparse-regime folds (``_use_sparse``: planes that cannot stay
        # on the device) default to the vectorized host sort: numpy's
        # lexsort beat the TPU's bitonic sort ~25× at these shapes and no
        # planes exist to ship (see orset_fold_sparse_host).  Opt in to
        # the device COO kernel where that trade flips: columns already
        # device-resident, or hosts much slower than this one.
        self.sparse_device = sparse_device

    def _mesh_active(self) -> bool:
        return self.mesh is not None and self.mesh.size > 1

    def _dp(self) -> int:
        return self.mesh.shape["dp"] if self._mesh_active() else 1

    @staticmethod
    def _round_to(n: int, mult: int) -> int:
        return -(-n // mult) * mult

    # ------------------------------------------------------------- fold_ops
    def fold_ops(self, state, ops: list):
        if len(ops) < self.min_device_batch:
            if ops and isinstance(state, LWWMap):
                trace.add("fold_rows_host", len(ops))
            return super().fold_ops(state, ops)
        if isinstance(state, ORSet):
            return self._fold_orset(state, ops)
        if isinstance(state, PNCounter):
            return self._fold_pncounter(state, ops)
        if isinstance(state, GCounter):
            return self._fold_gcounter(state, ops)
        if isinstance(state, LWWMap):
            return self._fold_lww(state, ops)
        return super().fold_ops(state, ops)

    def _fold_orset(self, state: ORSet, ops: list) -> ORSet:
        members, replicas = K.Vocab(), K.Vocab()
        cols = K.orset_ops_to_columns(ops, members, replicas)
        return self._fold_orset_columns(
            state, cols.kind, cols.member, cols.actor, cols.counter,
            members, replicas,
        )

    # The sparse regime: planes of SPARSE_MIN_CELLS cells or more, with
    # more than SPARSE_CELLS_PER_ROW cells a batch row.  Below either
    # bound the dense planes are cheap wherever they live (a walk of a
    # small state, or a batch that names a fair share of the cells).
    # Inside it what decides is whether the planes can STAY on the device
    # between folds (``_keeps_planes``).  Where they can, a round uploads
    # its rows, folds, and pulls the cells its rows named.  Where they
    # cannot (no TPU, no plane reuse, planes too large), every dense round
    # pays the state → planes walk, the upload and the whole pull-back,
    # and the sorted host fold wins.  Measured on the attached TPU v5e
    # (2026-09-30, PERF.md §6 PR 41) at 4,096 × 10,000 cells and 48,000
    # rows, decode to written state: the host sparse fold 114–126 ms; a
    # dense round that walks, uploads and pulls the planes whole 1,126 ms
    # (walk 421, pull 194, writeback 449); over resident planes with the
    # named cells pulled 64–65 ms (fold, gather and pull 13, writeback
    # 24–26).  The two constants date from a ~20 MB/s, ~100 ms-per-
    # dispatch link that is gone; on the attached chip they only mark
    # where the question is asked at all.
    SPARSE_CELLS_PER_ROW = 64
    SPARSE_MIN_CELLS = 1 << 22
    # Resident planes and one fold's result planes side by side (the fold
    # does not donate its inputs) may take this share of the device.
    RESIDENT_MEMORY_SHARE = 0.5
    # Dense batches beyond this many rows fold blockwise (ops/stream.py) so
    # device memory stays at one chunk + planes however big the ingest.
    STREAM_CHUNK_ROWS = 1 << 22

    @classmethod
    def _fits_device(cls, cells: int) -> bool:
        """Whether three int32 planes of ``cells`` cells, twice over, are
        inside ``RESIDENT_MEMORY_SHARE`` of what a TPU's allocator reports
        (no TPU: no)."""
        import jax

        if jax.default_backend() != "tpu":
            return False
        stats = jax.local_devices()[0].memory_stats() or {}
        return 2 * 3 * 4 * cells <= (
            cls.RESIDENT_MEMORY_SHARE * stats.get("bytes_limit", 0)
        )

    def _keeps_planes(self, E: int, R: int) -> bool:
        """Whether (E, R) planes stay on the device between folds: plane
        reuse on, one device (the sharded fold re-builds its planes per
        round), and planes that are small or fit the device.  The session
        installs a finished ingest's planes under the same rule
        (parallel/session.py ``finish``)."""
        if not self.plane_reuse or self._mesh_active():
            return False
        cells = E * R
        return cells < self.SPARSE_MIN_CELLS or self._fits_device(cells)

    def _use_sparse(self, E: int, R: int, n_rows: int) -> bool:
        cells = E * R
        return (
            cells >= self.SPARSE_MIN_CELLS
            and cells > self.SPARSE_CELLS_PER_ROW * max(n_rows, 1)
            and not self._keeps_planes(E, R)
        )

    def orset_fold_route(self, E: int, R: int, n_rows: int) -> str:
        """Where a fold of ``n_rows`` op rows into an ORSet of ``E``
        members × ``R`` replicas runs, as ``_fold_orset_columns`` decides
        it from the platform, the shape and the device's memory:
        ``"mesh"`` (the sharded fold), ``"host"`` (the sorted host fold of
        the sparse regime; ``"device_coo"`` with ``sparse_device``),
        ``"resident"`` (the dense device fold over planes that stay on the
        device between rounds) or ``"dense"`` (the same fold with plane
        reuse off: the planes are rebuilt every round)."""
        if self._mesh_active():
            return "mesh"
        if self._use_sparse(E, R, n_rows):
            coo = self.sparse_device and 2 * E * R < 2**31
            return "device_coo" if coo else "host"
        return "resident" if self.plane_reuse else "dense"

    def _plane_cache_for(self, state: ORSet) -> _OrsetPlaneCache | None:
        """The live cache entry for ``state``, or None (no entry, entry
        for another object, or the state mutated since it was filled)."""
        if not self.plane_reuse or self._mesh_active():
            return None
        c = self._plane_cache
        if c is None or c.ref() is not state:
            return None
        if c.token != getattr(state, "_mut", None):
            self._drop_plane_cache()  # stale: free the device planes
            return None
        return c

    @staticmethod
    def _remap_to_cache(cache: _OrsetPlaneCache, member, actor,
                        members, replicas):
        """Remap batch columns from their batch-local vocabs onto the
        cache's vocabs (growing them), or None when a member value
        collision (1 == True, 0.0 == -0.0) makes the dense planes
        unrepresentable — the caller then takes the uncached path."""
        from ..utils import codec

        if (len(member) and int(np.max(member)) >= len(members.items)) or (
            len(actor) and int(np.max(actor)) >= len(replicas.items)
        ):
            return None  # sentinel/padded columns: not plain vocab indices
        mt = np.empty(len(members.items), np.int32)
        canon = cache.canon
        for i, obj in enumerate(members.items):
            gid = cache.members.intern(obj)
            pk = codec.pack(obj)
            prev = canon.get(gid)
            if prev is None:
                stored = cache.members.items[gid]
                prev = pk if stored is obj else codec.pack(stored)
                canon[gid] = prev
            if prev != pk:
                return None
            mt[i] = gid
        rt = np.empty(len(replicas.items), np.int32)
        for i, a in enumerate(replicas.items):
            rt[i] = cache.replicas.intern(a)
        member = mt[member] if len(member) else np.asarray(member, np.int32)
        actor = rt[actor] if len(actor) else np.asarray(actor, np.int32)
        return member, actor

    @staticmethod
    def _cached_planes_padded(cache: _OrsetPlaneCache, E: int, R: int):
        """The cached device planes grown (on device — no host transfer)
        to the post-remap vocab sizes."""
        import jax.numpy as jnp

        clock, add, rm = cache.planes
        E0, R0 = add.shape
        if R > R0:
            clock = jnp.pad(clock, (0, R - R0))
            add = jnp.pad(add, ((0, 0), (0, R - R0)))
            rm = jnp.pad(rm, ((0, 0), (0, R - R0)))
        if E > E0:
            add = jnp.pad(add, ((0, E - E0), (0, 0)))
            rm = jnp.pad(rm, ((0, E - E0), (0, 0)))
        return clock, add, rm

    def _install_plane_cache(
        self, state: ORSet, members, replicas, dev_planes, canon
    ) -> None:
        """Record the fold's device planes as the state's resume planes.
        The writeback bump happens HERE so the recorded token is the
        post-writeback epoch.  The weakref finalizer drops the entry the
        moment the state dies — plane-sized device buffers must not
        outlive the replica they cache (the accelerator itself is held
        weakly in the callback, so nothing keeps anything alive)."""
        state._mut += 1
        if not self.plane_reuse or self._mesh_active():
            return
        import weakref

        accel_ref = weakref.ref(self)

        def _drop(dead_ref):
            accel = accel_ref()
            if accel is not None:
                c = accel._plane_cache
                if c is not None and c.ref is dead_ref:
                    accel._plane_cache = None

        self._plane_cache = _OrsetPlaneCache(
            weakref.ref(state, _drop), state._mut, members, replicas,
            dev_planes, canon if canon is not None else {},
        )

    def _drop_plane_cache(self) -> None:
        """Free the device planes held for a state something else rewrote
        (or is about to), and count it."""
        self._plane_cache = None
        trace.add("plane_cache_drops", 1)

    def _note_orset_writeback(self, state: ORSet) -> None:
        """A non-caching path rewrote ``state``: bump its epoch and drop
        any device planes held for it."""
        state._mut += 1
        c = self._plane_cache
        if c is not None and c.ref() is state:
            self._drop_plane_cache()

    def install_orset_planes(
        self, state: ORSet, members, replicas, clock, add, rm, canon=None
    ) -> None:
        """A host path just wrote ``state`` from these normalized host
        planes (the session's finish): where planes of this shape stay on
        the device, upload them as the state's resident planes, so the
        next fold is a cache hit and not a walk of the state; else what
        ``_note_orset_writeback`` does."""
        if not self._keeps_planes(len(members), len(replicas)):
            self._note_orset_writeback(state)
            return
        import jax

        self._plane_cache = None  # the stale planes go before the new come
        trace.add("h2d_bytes", clock.nbytes + add.nbytes + rm.nbytes)
        self._install_plane_cache(
            state, members, replicas, jax.device_put((clock, add, rm)), canon
        )

    def _fold_orset_columns(
        self, state: ORSet, kind, member, actor, counter, members, replicas
    ) -> ORSet:
        """Shared tail: state → planes, pad, jit fold, planes → state.
        With ``plane_reuse`` on and an unmutated state the dense fold runs
        over the previous round's device-resident planes: only the row
        columns go up, and only the cells the rows named and the clock
        come back (``orset_gather_cells`` / ``orset_cells_to_state``).
        Batches in the sparse regime whose planes cannot stay on the
        device (``_use_sparse``) take the sorted host fold instead — same
        semantics, no dense plane materialization."""
        n_rows = len(kind)
        cache = self._plane_cache_for(state)
        collision = False
        if cache is not None:
            remapped = self._remap_to_cache(
                cache, member, actor, members, replicas
            )
            if remapped is None:
                # dense planes cannot hold this batch beside this state:
                # the sorted host fold keys on the objects themselves
                self._drop_plane_cache()
                cache, collision = None, True
            else:
                member, actor = remapped
                members, replicas = cache.members, cache.replicas
        if cache is None:
            with trace.span("fold.vocab"):
                K.orset_scan_vocab(state, members, replicas)
        E, R = len(members), len(replicas)
        if E == 0 or R == 0:
            return state
        # vocab-axis compile classes (bucket_vocab): fold at the padded
        # (Ep, Rp) and slice back at writeback.  Zero rows/columns are
        # inert through the whole kernel — no op references a padded
        # member, padded replica columns carry zero clocks and zero
        # cells, and the sentinel row mask keys on ``actor >= Rp``.
        bucketed = (
            self.bucket_vocab
            and not self._mesh_active()
            and n_rows <= self.STREAM_CHUNK_ROWS
        )
        Ep = _bucket(E) if bucketed else E
        Rp = _bucket(R) if bucketed else R
        if self._mesh_active():
            # SPMD fold: rows shard over dp, planes over mp.  The mp axis is
            # also what makes huge (E, R) planes tractable — each device
            # holds E/mp rows — so the single-device sparse escape hatch
            # does not apply here.
            trace.add("fold_rows_device", n_rows)
            return self._fold_orset_sharded(
                state, kind, member, actor, counter, members, replicas
            )
        # resident planes are used whatever the batch's shape: the sparse
        # regime is a question about planes that are NOT on the device
        if collision or (
            cache is None and self._use_sparse(E, R, n_rows)
        ):
            if self.sparse_device and 2 * E * R < 2**31 and not collision:
                trace.add("fold_rows_device", n_rows)
                return self._fold_orset_coo_device(
                    state, kind, member, actor, counter, members, replicas
                )
            # vectorized host fold: in the N ≪ E·R regime the work is
            # one sort, where numpy beats the TPU's bitonic sort ~25x
            # and no dense planes exist to ship (see
            # orset_fold_sparse_host docs).  No bucket padding — that
            # exists only to bound jit recompilation, and this path
            # never compiles anything.
            trace.add("fold_rows_host", n_rows)
            with trace.span("fold.host_sparse"):
                return K.orset_fold_sparse_host(
                    state, kind, member, actor, counter, members, replicas,
                )
        if self.bucket_vocab and not bucketed:
            # the streaming fold runs at true (E, R); cached planes from a
            # bucketed round may be padded past it, so rebuild from state
            cache = None
        if cache is not None:
            trace.add("plane_cache_hits", 1)
            clock0, add0, rm0 = self._cached_planes_padded(cache, Ep, Rp)
        else:
            trace.add("plane_cache_misses", 1)
            with trace.span("fold.planes"):
                clock0, add0, rm0 = K.orset_state_to_planes(
                    state, members, replicas, scanned=True
                )
            if (Ep, Rp) != (E, R):
                clock0 = np.pad(clock0, (0, Rp - R))
                add0 = np.pad(add0, ((0, Ep - E), (0, Rp - R)))
                rm0 = np.pad(rm0, ((0, Ep - E), (0, Rp - R)))
        # a fold over resident planes pulls the named cells, not the planes
        resident = cache is not None
        with trace.span("fold.device"):
            trace.add("fold_rows_device", n_rows)
            if n_rows > self.STREAM_CHUNK_ROWS:
                if cache is not None:
                    # the blockwise stream stages planes from host (its
                    # own H2D rides under the first fold) — pull once
                    clock0, add0, rm0 = obs_runtime.pull(clock0, add0, rm0)
                # blockwise fold with donated plane buffers: bounded device
                # memory for arbitrarily large ingests (ops/stream.py).
                # Chunks route through the Pallas MXU fold when eligible —
                # the streaming path must run the same flagship kernel the
                # dense path does (chunk size == MAX_ROWS, so the row
                # bound holds by construction here).
                from ..ops import pallas_fold as PF
                from ..ops.stream import ChunkPool

                stream_kw = {}
                if self._pallas_eligible(counter):
                    trace.add("pallas_routed", 1)
                    stream_kw = dict(
                        impl="pallas", tile_cap=PF.fold_cap(member, E)
                    )
                # double-buffered staging: chunk k+1 columnarizes into a
                # recycled pool buffer and its H2D transfer rides under
                # chunk k's fold (ops/stream.py fold_chunks_overlapped)
                pool = ChunkPool(self.STREAM_CHUNK_ROWS, depth=2)
                dev_planes = K.orset_fold_stream(
                    clock0, add0, rm0,
                    K.iter_orset_chunks(
                        kind, member, actor, counter,
                        self.STREAM_CHUNK_ROWS, R, pool=pool,
                    ),
                    num_members=E, num_replicas=R, pool=pool, **stream_kw,
                )
                resident = False  # the planes were staged from the host
            else:
                import jax

                if cache is None:
                    # the full-state upload the plane cache exists to
                    # elide — counted at issue, like the streaming paths
                    # (the stream branch above counts its own)
                    trace.add(
                        "h2d_bytes",
                        clock0.nbytes + add0.nbytes + rm0.nbytes,
                    )
                cols = K.OrsetColumns(kind, member, actor, counter, members, replicas)
                K.pad_orset_rows(cols, _bucket(len(cols.kind)), Rp)
                # the padded row columns upload on every round, plane
                # cache hit or miss; member and actor once for the fold
                # and the gather after it
                trace.add("h2d_bytes", cols.row_bytes)
                fold = self._pick_dense_fold(cols, Ep, Rp)
                member_d, actor_d = jax.device_put((cols.member, cols.actor))
                dev_planes = fold(
                    clock0, add0, rm0,
                    cols.kind, member_d, actor_d, cols.counter,
                )
            with trace.span("fold.pull"):
                if resident:
                    # the planes the state was written from are the ones
                    # just folded over: only the cells the rows named and
                    # the clock can differ from the host state
                    clock, *cells = obs_runtime.pull(
                        dev_planes[0],
                        *K.orset_gather_cells(
                            dev_planes[1], dev_planes[2], member_d, actor_d
                        ),
                    )
                    clock = clock[:R]
                    trace.add("fold_cells_pulled", 2 * len(cells[0]))
                else:
                    # the O(state) pull-back of a round that built planes
                    clock, add, rm = obs_runtime.pull(*dev_planes)
                    trace.add("fold_cells_pulled", add.size + rm.size)
                    if (Ep, Rp) != (E, R):
                        clock, add, rm = clock[:R], add[:E, :R], rm[:E, :R]
        obs_runtime.sample_device_memory()  # fold boundary
        with trace.span("fold.writeback"):
            if resident:
                # the clock whole, by the one function every plane
                # writeback goes through; then the cells over it
                none = np.zeros((0, R), np.int32)
                state.clock = K.orset_planes_to_state(
                    clock, none, none, members, replicas
                ).clock
                K.orset_cells_to_state(
                    state, member, actor,
                    cells[0][:n_rows], cells[1][:n_rows], members, replicas,
                )
            else:
                folded = K.orset_planes_to_state(
                    clock, add, rm, members, replicas
                )
                state.clock = folded.clock
                state.entries = folded.entries
                state.deferred = folded.deferred
        # the planes just computed ARE the new state, already on device:
        # keep them for the next round (epoch recorded post-writeback)
        self._install_plane_cache(
            state, members, replicas, dev_planes,
            cache.canon if cache is not None else None,
        )
        return state

    @staticmethod
    def _lww_pallas_eligible(num_values, ts_hi, n_rows: int) -> bool:
        """Pallas LWW winner-fold precondition: real TPU, a packed
        (actor, value) rank (num_values set — its +1 present-offset is
        the only one the kernel applies, and it cannot wrap under the
        packed-rank bound), rows inside the sort working set."""
        import jax

        from ..ops import pallas_lww as PL

        return (
            jax.default_backend() == "tpu"
            and num_values is not None
            and n_rows <= PL.MAX_ROWS
        )

    @staticmethod
    def _pallas_eligible(counter) -> bool:
        """Shared Pallas-fold precondition: real TPU hardware and every
        counter inside the kernel's 7-bit-limb bound.  Row-count limits
        are the caller's concern (the dense path checks MAX_ROWS, the
        streaming path chunks at exactly that size)."""
        import jax

        from ..ops import pallas_fold as PF

        return (
            jax.default_backend() == "tpu"
            and int(np.max(counter, initial=0)) < PF.MAX_COUNTER
        )

    def _pick_dense_fold(self, cols, E: int, R: int):
        """The dense single-device fold kernel: the Pallas MXU fold when
        eligible on real TPU hardware (counters inside the 7-bit-limb
        bound, batch inside the sort working set — the same routing the
        bench publishes), else the XLA scatter fold.  The product ingest
        and the benchmark must run the same machinery."""
        from ..ops import pallas_fold as PF

        eligible = (
            len(cols.kind) <= PF.MAX_ROWS
            and self._pallas_eligible(cols.counter)
        )
        if eligible:
            trace.add("pallas_routed", 1)
            tile_cap = PF.fold_cap(cols.member, E)
            # all-small counters skip the hi-limb matmul statically —
            # half the MXU work and no per-chunk max/branch at all
            hi_mode = (
                "skip"
                if int(np.max(cols.counter, initial=0)) < 128 else "cond"
            )

            def fold(c, a, r, kind, member, actor, counter):
                return PF.orset_fold_pallas(
                    c, a, r, kind, member, actor, counter,
                    num_members=E, num_replicas=R, tile_cap=tile_cap,
                    hi_mode=hi_mode,
                )

            return fold

        def fold(c, a, r, kind, member, actor, counter):
            return K.orset_fold(
                c, a, r, kind, member, actor, counter,
                num_members=E, num_replicas=R,
            )

        return fold

    def _fold_orset_coo_device(
        self, state: ORSet, kind, member, actor, counter, members, replicas
    ) -> ORSet:
        """Sparse-regime device fold: the sorted-COO kernel aggregates the
        batch on device without dense planes; the sparse state writeback
        shares ``orset_apply_coo`` with the host twin, so the two paths
        cannot drift."""
        # dense clock FIRST: it may intern clock actors into `replicas`,
        # and the kernel's segment keys are encoded modulo the final R
        clock0 = K.vclock_to_dense(state.clock, replicas)
        E, R = len(members), len(replicas)
        cols = K.OrsetColumns(
            np.asarray(kind, np.int8),
            np.asarray(member, np.int32),
            np.asarray(actor, np.int32),
            np.asarray(counter, np.int32),
            members,
            replicas,
        )
        K.pad_orset_rows(cols, _bucket(len(cols.kind)), R)
        trace.add("h2d_bytes", clock0.nbytes + cols.row_bytes)
        clock, skey, smax, is_max = K.orset_fold_coo(
            clock0, cols.kind, cols.member, cols.actor, cols.counter,
            num_members=E, num_replicas=R,
        )
        return K.orset_apply_coo(
            state, *obs_runtime.pull(clock, skey, smax, is_max),
            members, replicas,
        )

    def _fold_orset_sharded(
        self, state: ORSet, kind, member, actor, counter, members, replicas
    ) -> ORSet:
        """Multi-device tail: pad rows to the dp axis and the plane member
        axis to the mp axis, run the shard_map fold, write planes back."""
        from . import mesh as pmesh

        mesh = self.mesh
        dp, mp = mesh.shape["dp"], mesh.shape["mp"]
        E, R = len(members), len(replicas)
        clock0, add0, rm0 = K.orset_state_to_planes(
            state, members, replicas, scanned=True
        )
        E_pad = self._round_to(E, mp)
        if E_pad != E:
            z = np.zeros((E_pad - E, R), add0.dtype)
            add0 = np.concatenate([add0, z])
            rm0 = np.concatenate([rm0, z])
        cols = K.OrsetColumns(
            np.asarray(kind, np.int8),
            np.asarray(member, np.int32),
            np.asarray(actor, np.int32),
            np.asarray(counter, np.int32),
            members,
            replicas,
        )
        K.pad_orset_rows(
            cols, self._round_to(_bucket(len(cols.kind)), dp), R
        )
        # each shard runs the flagship Pallas scatter when eligible — a
        # mesh compaction must execute the same kernel a single chip does
        fold_kw = {}
        from ..ops import pallas_fold as PF

        # int32 segment-key bound for the per-shard ablk kernel (the
        # single-chip front door switches layouts past this; the sharded
        # route has only the ablk layout, so it must stay on XLA there)
        if (
            self._pallas_eligible(cols.counter)
            and len(cols.kind) // dp <= PF.MAX_ROWS
            and PF.ablk_key_space_fits(E_pad // mp, R)
        ):
            trace.add("pallas_routed", 1)
            fold_kw = dict(
                impl="pallas",
                tile_cap=pmesh.sharded_fold_cap(cols.member, E_pad, dp, mp),
            )
        trace.add(
            "h2d_bytes",
            clock0.nbytes + add0.nbytes + rm0.nbytes + cols.row_bytes,
        )
        clock, add, rm = pmesh.orset_fold_sharded(
            mesh, clock0, add0, rm0,
            cols.kind, cols.member, cols.actor, cols.counter, **fold_kw,
        )
        clock, add, rm = obs_runtime.pull(clock, add, rm)
        folded = K.orset_planes_to_state(
            clock, add[:E], rm[:E], members, replicas
        )
        state.clock = folded.clock
        state.entries = folded.entries
        state.deferred = folded.deferred
        self._note_orset_writeback(state)
        return state

    # ------------------------------------------------------- fold sessions
    def can_open_fold_session(self, state) -> bool:
        """Cheap predicate twin of :meth:`open_fold_session` (no session
        construction): the core checks it before spinning up pipeline
        machinery whose cost only pays off when a session exists."""
        from .session import session_supported

        return session_supported(state)

    def open_fold_session(self, state, actors_hint=()):
        """A chunked fold session for the core's pipelined bulk ingest
        (parallel/session.py), or None for CRDT types without a columnar
        chunk path — the core then uses the legacy whole-batch flow."""
        from .session import open_fold_session

        return open_fold_session(self, state, actors_hint)

    # -------------------------------------------------------- fold_payloads
    def fold_payloads(self, state, payloads: list, actors_hint=()) -> bool:
        """Bulk front end: decrypted op-file payloads → native columnar
        decode → jit fold.  Handles ORSet and the two counters; anything
        else (or any payload the native decoder declines) falls back to
        the per-op path."""
        if isinstance(state, (GCounter, PNCounter)):
            return self._fold_counter_payloads(state, payloads, actors_hint)
        from ..models.crdtmap import CrdtMap

        if isinstance(state, CrdtMap):
            return self._fold_map_payloads(state, payloads, actors_hint)
        from ..models import GSet, LWWReg, MVReg, MerkleReg, SeqList

        if isinstance(state, GSet):
            return self._fold_gset_payloads(state, payloads)
        if isinstance(state, LWWReg):
            return self._fold_lwwreg_payloads(state, payloads)
        if isinstance(state, MVReg):
            return self._fold_mvreg_payloads(state, payloads)
        if isinstance(state, SeqList):
            return self._fold_seqlist_payloads(state, payloads)
        if isinstance(state, MerkleReg):
            return self._fold_merklereg_payloads(state, payloads)
        if not isinstance(state, ORSet):
            return False
        from ..ops.native_decode import decode_orset_payload_batch

        actors_sorted = self._orset_actor_table(state, actors_hint)
        with trace.span("fold.decode"):
            decoded = decode_orset_payload_batch(payloads, actors_sorted)
        if decoded is None:
            return False
        return self._fold_orset_decoded(state, decoded, actors_sorted)

    def _orset_actor_table(self, state: ORSet, actors_hint) -> list:
        """Sorted actor table for the native decoder (it binary-searches):
        the caller's hint plus every actor the state mentions.

        Callers usually pass an already-sorted hint (storage listings
        are sorted) covering every state actor; detecting that case
        skips re-sorting a set-scrambled copy — at 100k replicas the
        n·log n byte-string sort cost more than the decrypt phase."""
        import operator
        from itertools import islice

        def strictly_sorted(seq):
            # C-level pairwise compare: ~3ms at 100k vs ~10ms for an
            # index-based genexp — this sits ahead of every bulk ingest
            return all(map(operator.lt, seq, islice(seq, 1, None)))

        if (
            not state.clock.counters
            and not state.entries
            and not state.deferred
        ):
            # fresh replica (the streaming shape): the hint IS the table —
            # no set union to build, just the sorted-unique check
            hint = list(actors_hint)
            if strictly_sorted(hint):
                return hint
            return sorted(set(hint))
        actor_set = set(actors_hint)
        n_hint = len(actor_set)
        actor_set.update(state.clock.counters)
        for entry in state.entries.values():
            actor_set.update(entry)
        for dfr in state.deferred.values():
            actor_set.update(dfr)
        if len(actor_set) == n_hint and len(actors_hint) == n_hint:
            hint = list(actors_hint)
            if strictly_sorted(hint):
                return hint
        return sorted(actor_set)

    def _fold_orset_decoded(self, state: ORSet, decoded, actors_sorted) -> bool:
        kind, member_idx, actor_idx, counter, member_objs = decoded
        if len(kind) == 0:
            return True
        # vocabs: replicas in the decoder's sorted order (strictly sorted
        # ⇒ unique — skip the 100k-key eager index build); members in the
        # decoder's intern order (state members appended by planes builder)
        members = K.Vocab(member_objs)
        replicas = K.Vocab.presorted_unique(actors_sorted)
        # Vocab interning hashes member *objects*; distinct canonical bytes
        # can still collide as Python values (1 == True, 0.0 == -0.0).  A
        # collapsed vocab would leave member_idx out of range and scatter
        # ops onto the wrong member — bail to the per-op path instead.
        if len(members) != len(member_objs):
            return False
        self._fold_orset_columns(
            state, kind, member_idx, actor_idx, counter, members, replicas
        )
        return True

    def _fold_map_payloads(self, state, payloads: list, actors_hint=()) -> bool:
        """CrdtMap<orset> bulk path: native four-family decode → the
        vectorized columnar fold (ops/map_columnar.py).  Declines (per-op
        fallback) for other child types, non-shared-dot payloads, or any
        decode surprise."""
        if state.child != b"orset":
            return False
        from ..ops.map_columnar import crdtmap_fold_host, decode_map_payload_batch

        actor_set = set(actors_hint)
        actor_set.update(state.clock.counters)
        for birth in state.births.values():
            actor_set.update(birth)
        for ctx, _rm_keys in state.deferred.values():
            actor_set.update(ctx.counters)
        for child in state.vals.values():
            actor_set.update(child.clock.counters)
            for entry in child.entries.values():
                actor_set.update(entry)
            for dfr in child.deferred.values():
                actor_set.update(dfr)
        actors_sorted = sorted(actor_set)
        with trace.span("fold.map_decode"):
            decoded = decode_map_payload_batch(payloads, actors_sorted)
        if decoded is None:
            return False
        B, A, Rm, Kk, key_objs, member_objs = decoded
        keys = K.Vocab(key_objs)
        members = K.Vocab(member_objs)
        # vocab value-collision guard (1 == True etc.), as in the ORSet path
        if len(keys) != len(key_objs) or len(members) != len(member_objs):
            return False
        replicas = K.Vocab(actors_sorted)
        impl = self.map_fold_impl
        if impl is None and self._mesh_active():
            impl = "device"  # SPMD scatter phase over the mesh
        elif impl is None:
            n_rows = (
                len(B["actor"]) + len(A["actor"]) + len(Rm["actor"])
                + len(Kk["actor"])
            )
            impl = "device" if n_rows >= self.min_device_batch else "host"
        with trace.span("fold.map"):
            crdtmap_fold_host(
                state, B, A, Rm, Kk, keys, members, replicas, fold_impl=impl,
                mesh=self.mesh
                if impl == "device" and self._mesh_active()
                else None,
            )
        return True

    # -------------------------------------------- catalogue bulk front ends
    def _fold_gset_payloads(self, state, payloads: list) -> bool:
        """G-Set bulk: one msgpack unpack per file, one set update.  No
        device path — the fold IS deduplication of opaque values, which
        is exactly what hashing them into the host set does; there is no
        arithmetic to put on the MXU/VPU (docs/PARITY.md row 14)."""
        from ..utils import codec

        frozen = state._freeze
        state.members.update(
            frozen(op) for p in payloads for op in codec.unpack(p)
        )
        return True

    def _fold_lwwreg_payloads(self, state, payloads: list) -> bool:
        """LWW-Register bulk: the LWW-map cascade at K=1 — one device
        ``lww_fold`` over all writes, winner resolved against the slot
        with the host tie-break (identical total order: the columns are
        rank-interned so integer compare ≡ bytes compare)."""
        from ..models.lwwmap import LWWOp
        from ..utils import codec

        rows = [op for p in payloads for op in codec.unpack(p)]
        if not rows:
            return True
        if len(rows) < self.min_device_batch:
            for o in rows:
                state.apply(o)
            return True
        ops = [
            LWWOp(None, int(o[0]), bytes(o[1]), o[2], False) for o in rows
        ]
        cols = K.lww_ops_to_columns(ops)
        # the packed-rank multiplier from a bucket, as in _fold_lww (the
        # rows here are not padded: a batch length is a shape of its own)
        Vp = _bucket(len(cols.values_sorted))
        num_values = Vp if len(cols.actors_sorted) * Vp < 2**31 else None
        m_hi, m_lo, m_actor, m_value, present = K.lww_fold(
            cols.key, cols.ts_hi, cols.ts_lo, cols.actor, cols.value,
            num_keys=1, num_values=num_values,
        )
        if not bool(np.asarray(present)[0]):
            return True
        ts = (int(np.asarray(m_hi)[0]) << 31) | int(np.asarray(m_lo)[0])
        actor = cols.actors_sorted[int(np.asarray(m_actor)[0])]
        value = cols.values_sorted[int(np.asarray(m_value)[0])]
        state._take(ts, actor, value)
        return True

    def _fold_mvreg_payloads(self, state, payloads: list) -> bool:
        """MVReg bulk fold: ops are (clock, value) candidates; iterated
        strict-dominance apply equals the global anti-chain (dominance is
        transitive), so one ``mvreg_dominance_keep`` call replaces the
        per-op loop — the same argument ``_merge_mvregs`` documents."""
        from ..models.vclock import VClock as VC
        from ..utils import codec

        pairs = list(state.vals)
        n_ops = 0
        for p in payloads:
            for obj in codec.unpack(p):
                pairs.append((VC.from_obj(obj[0]), obj[1]))
                n_ops += 1
        if n_ops == 0:
            return True
        if n_ops + len(state.vals) < self.min_device_batch:
            from ..models.mvreg import MVRegOp

            for c, v in pairs[len(state.vals):]:
                state.apply(MVRegOp(c, v))
            return True
        self._mvreg_antichain(state, pairs)
        return True

    def _fold_seqlist_payloads(self, state, payloads: list) -> bool:
        """SeqList bulk: whole-file unpack, vectorized-enough host apply.
        No device kernel: the state is an order-keyed tree of opaque
        idents (Logoot paths) — resolving it is pointer/compare work on
        variable-length paths with no dense tensor shape
        (docs/PARITY.md row 14)."""
        from ..models.seqlist import op_from_obj
        from ..utils import codec

        for p in payloads:
            for obj in codec.unpack(p):
                state.apply(op_from_obj(obj))
        return True

    def _fold_merklereg_payloads(self, state, payloads: list) -> bool:
        """MerkleReg bulk: whole-file unpack + apply.  No device kernel:
        the fold is hash-DAG bookkeeping (parent links, head set), not
        arithmetic (docs/PARITY.md row 14)."""
        from ..models.merkle_reg import MerkleNode
        from ..utils import codec

        for p in payloads:
            for obj in codec.unpack(p):
                state.apply(MerkleNode.from_obj(obj))
        return True

    def _fold_counter_payloads(self, state, payloads: list, actors_hint=()) -> bool:
        """Counter bulk path: native decode straight to (sign, actor,
        counter) columns, one segment-max fold.  Dots are monotone per
        actor, so max-folding whole files at once equals per-op apply."""
        from ..ops.native_decode import decode_counter_payload_batch

        clocks = (
            (state.p.clock, state.n.clock)
            if isinstance(state, PNCounter)
            else (state.clock,)
        )
        actor_set = set(actors_hint)
        for c in clocks:
            actor_set.update(c.counters)
        actors_sorted = sorted(actor_set)
        decoded = decode_counter_payload_batch(payloads, actors_sorted)
        if decoded is None:
            return False
        sign, actor_idx, counter = decoded
        if len(sign) == 0:
            return True
        if isinstance(state, GCounter) and np.any(sign != POS):
            return False  # PN-shaped rows in a G-Counter state
        self._fold_counter_dense(
            state, K.CounterColumns(sign, actor_idx, counter, K.Vocab(actors_sorted))
        )
        return True

    def _pad_counter_cols(self, cols, num_replicas: int):
        n = len(cols.sign)
        padn = self._round_to(_bucket(n), self._dp()) - n
        if padn:
            cols.sign = np.concatenate([cols.sign, np.zeros(padn, np.int8)])
            cols.actor = np.concatenate(
                [cols.actor, np.full(padn, num_replicas, np.int32)]
            )
            cols.counter = np.concatenate([cols.counter, np.zeros(padn, np.int32)])
        return cols

    def _fold_counter_dense(self, state, cols):
        """Shared tail for every counter fold: fix the replica vocab (state
        actors included), pad the columns, run the kernel, write the dense
        clocks back to the sparse state."""
        replicas = cols.replicas
        clocks = (
            (state.p.clock, state.n.clock)
            if isinstance(state, PNCounter)
            else (state.clock,)
        )
        for c in clocks:
            for a in c.counters:
                replicas.intern(a)
        R = len(replicas)
        if R == 0:
            return state
        self._pad_counter_cols(cols, R)
        sharded = self._mesh_active()
        if sharded:
            from . import mesh as pmesh
        if isinstance(state, PNCounter):
            p0 = K.vclock_to_dense(state.p.clock, replicas)
            n0 = K.vclock_to_dense(state.n.clock, replicas)
            if sharded:
                p, n, _ = pmesh.pncounter_fold_sharded(
                    self.mesh, p0, n0, cols.sign, cols.actor, cols.counter
                )
            else:
                p, n, _ = K.pncounter_fold(
                    p0, n0, cols.sign, cols.actor, cols.counter, num_replicas=R
                )
            state.p.clock = K.dense_to_vclock(np.asarray(p), replicas)
            state.n.clock = K.dense_to_vclock(np.asarray(n), replicas)
        else:
            clock0 = K.vclock_to_dense(state.clock, replicas)
            if sharded:
                clock, _ = pmesh.gcounter_fold_sharded(
                    self.mesh, clock0, cols.actor, cols.counter
                )
            else:
                clock, _ = K.gcounter_fold(
                    clock0, cols.actor, cols.counter, num_replicas=R
                )
            state.clock = K.dense_to_vclock(np.asarray(clock), replicas)
        return state

    def _fold_gcounter(self, state: GCounter, ops: list) -> GCounter:
        return self._fold_counter_dense(state, K.counter_ops_to_columns(ops))

    def _fold_pncounter(self, state: PNCounter, ops: list) -> PNCounter:
        return self._fold_counter_dense(state, K.counter_ops_to_columns(ops))

    def _fold_lww(self, state: LWWMap, ops: list) -> LWWMap:
        with trace.span("fold.lww.columns"):
            cols = K.lww_ops_to_columns(ops)
            Kn = len(cols.keys)
            if Kn == 0:
                return state
            n = len(cols.key)
            # Every static argument of the programs below comes from a
            # bucket, so a steady folder compiles nothing after its first
            # rounds: the rows and the key vocabulary are padded to
            # ``_bucket`` (a batch names another number of distinct keys
            # every round), ``num_values`` likewise (below),
            # ``tile_cap`` is a power of two and the limb counts are
            # quantized to their 1-4 range (≤ 64 tuples).  Pad rows carry
            # the sentinel key ``Kp`` (== num_keys ⇒ padding row) and the
            # tables are cut back to ``Kn`` after the pull.
            Kp = _bucket(Kn)
            padn = self._round_to(_bucket(n), self._dp()) - n
            key_col, hi, lo, actor_col, value_col = (
                cols.key,
                cols.ts_hi,
                cols.ts_lo,
                cols.actor,
                cols.value,
            )
            if padn:
                key_col = np.concatenate([key_col, np.full(padn, Kp, np.int32)])
                hi = np.concatenate([hi, np.zeros(padn, np.int32)])
                lo = np.concatenate([lo, np.zeros(padn, np.int32)])
                actor_col = np.concatenate([actor_col, np.zeros(padn, np.int32)])
                value_col = np.concatenate([value_col, np.zeros(padn, np.int32)])
            # pack (actor, value) into one cascade when the rank product
            # fits; the multiplier is the bucket of the batch's count of
            # distinct values (any number above every value rank keeps the
            # lexicographic order, and the count itself differs by round)
            Vp = _bucket(len(cols.values_sorted))
            num_values = Vp if len(cols.actors_sorted) * Vp < 2**31 else None
            pallas = not self._mesh_active() and self._lww_pallas_eligible(
                num_values, hi, len(key_col)
            )
            if pallas:
                from ..ops.pallas_lww import (
                    lww_column_maxima, lww_fold_pallas, lww_limbs,
                    lww_tile_cap,
                )

                # maxima on the UNPADDED columns, computed once (the pad
                # rows are zeros and cannot raise them)
                maxima = lww_column_maxima(
                    cols.ts_hi, cols.ts_lo, cols.actor, num_values
                )
                tile_cap = lww_tile_cap(key_col, Kp)
                # static limb counts from the batch's host-side maxima:
                # the in-kernel per-chunk limb conds measured 4x slower
                limbs = lww_limbs(hi, lo, actor_col, num_values, maxima=maxima)
        trace.add_many({
            "lww_folds": 1, "lww_fold_rows": n, "lww_fold_keys": Kn,
            "fold_rows_device": n,
        })
        with trace.span("fold.lww.device"):
            # the five padded columns upload with the dispatch
            columns = (key_col, hi, lo, actor_col, value_col)
            trace.add("h2d_bytes", 5 * key_col.nbytes)  # five int32 columns
            if self._mesh_active():
                from . import mesh as pmesh

                tables = pmesh.lww_fold_sharded(
                    self.mesh, *columns, num_keys=Kp
                )
            elif pallas:
                trace.add_many({"pallas_routed": 1, "lww_folds_pallas": 1})
                tables = lww_fold_pallas(
                    *columns, num_keys=Kp, num_values=num_values,
                    tile_cap=tile_cap, limbs=limbs,
                )
            else:
                tables = K.lww_fold(
                    *columns, num_keys=Kp, num_values=num_values
                )
            m_hi, m_lo, m_actor, m_value, present = (
                t[:Kn] for t in obs_runtime.pull(*tables)
            )
        with trace.span("fold.lww.writeback"):
            # winner rows → tombstone lookup (vectorized over the batch)
            ki = cols.key
            win = (
                (cols.ts_hi == m_hi[ki])
                & (cols.ts_lo == m_lo[ki])
                & (cols.actor == m_actor[ki])
                & (cols.value == m_value[ki])
            )
            tomb_by_key = np.zeros(Kn, bool)
            np.maximum.at(tomb_by_key, ki[win], cols.tombstone[win])

            # vectorized writeback: materialize all winner entries in bulk
            # (batched .tolist() conversions, no per-key state.apply /
            # LWWOp), then resolve against existing entries — the host
            # tie-break runs only on actual key collisions
            from ..models.lwwmap import _wins

            idx = np.flatnonzero(present)
            ts64 = (m_hi[idx].astype(np.int64) << 31) | m_lo[idx]
            items = cols.keys.items
            actors, values = cols.actors_sorted, cols.values_sorted
            tombs = tomb_by_key[idx].tolist()
            new_entries = {
                items[k]: [
                    t,
                    actors[a],
                    None if tomb else values[v],
                    tomb,
                ]
                for k, t, a, v, tomb in zip(
                    idx.tolist(),
                    ts64.tolist(),
                    m_actor[idx].tolist(),
                    m_value[idx].tolist(),
                    tombs,
                )
            }
            entries = state.entries
            if not entries:
                state.entries = new_entries
                written = len(new_entries)
            else:
                written = 0
                for key_obj, new in new_entries.items():
                    cur = entries.get(key_obj)
                    if cur is None or _wins(*new, *cur):
                        entries[key_obj] = new
                        written += 1
            trace.add("lww_keys_written", written)
        return state

    # --------------------------------------------------------- merge_states
    def merge_states(self, state, others: list):
        if not others:
            return state
        if isinstance(state, ORSet):
            if self._mesh_active():
                return self._merge_orsets_sharded(state, others)
            if len(others) + 1 >= 3:
                return self._merge_orsets(state, others)
        from ..models import MVReg

        if isinstance(state, MVReg):
            total = len(state.vals) + sum(len(o.vals) for o in others)
            if total >= self.min_device_batch:
                return self._merge_mvregs(state, others)
        return super().merge_states(state, others)

    def _merge_mvregs(self, state, others: list):
        """Batched MVReg snapshot merge: the global anti-chain of every
        candidate (clock, value) pair via ONE dominance-filter kernel
        call, instead of S sequential pairwise merges.  Equivalent
        because each input register is already an anti-chain and
        domination is transitive, so iterated pairwise merging and the
        global filter both keep exactly the pairs no other pair strictly
        dominates; identical duplicates never dominate each other
        (strict filter) and collapse in canonicalization."""
        pairs = list(state.vals)
        for o in others:
            pairs.extend(o.vals)
        return self._mvreg_antichain(state, pairs)

    def _mvreg_antichain(self, state, pairs: list):
        """Write the global strict-dominance anti-chain of ``pairs`` into
        ``state`` via one ``mvreg_dominance_keep`` kernel call."""
        replicas = K.Vocab()
        for c, _ in pairs:
            for a in c.counters:
                replicas.intern(a)
        R, V = len(replicas), len(pairs)
        if R == 0 or V <= 1:  # empty clocks: dedup is all there is
            state.vals = pairs
            state._canonicalize()
            return state
        # bucket-pad both axes so repeated merges reuse the compiled
        # program: zero rows are masked out via `valid`, zero columns are
        # inert (elementwise comparisons on equal zeros)
        Vp = self._round_to(_bucket(V), self._dp())
        clocks = np.zeros((Vp, _bucket(R)), np.int32)
        for i, (c, _) in enumerate(pairs):
            for a, n in c.counters.items():
                clocks[i, replicas.intern(a)] = n
        valid = np.zeros(len(clocks), bool)
        valid[:V] = True
        if self._mesh_active():
            from . import mesh as pmesh

            keep = np.asarray(
                pmesh.mvreg_keep_sharded(self.mesh, clocks, valid)
            )
        else:
            keep = np.asarray(K.mvreg_dominance_keep(clocks, valid))
        state.vals = [pairs[i] for i in np.flatnonzero(keep[:V])]
        state._canonicalize()
        return state

    def _merge_orsets_sharded(self, state: ORSet, others: list) -> ORSet:
        """Pairwise SPMD merges with planes sharded over mp — elementwise
        work only, so each pair is one shard_map with no collectives."""
        from . import mesh as pmesh

        mesh = self.mesh
        mp = mesh.shape["mp"]
        members, replicas = K.Vocab(), K.Vocab()
        all_states = [state] + list(others)
        for s in all_states:
            K.orset_scan_vocab(s, members, replicas)
        E, R = len(members), len(replicas)
        if E == 0 or R == 0:
            return super().merge_states(state, others)
        E_pad = self._round_to(E, mp)

        def planes(s):
            clock, add, rm = K.orset_state_to_planes(
                s, members, replicas, scanned=True
            )
            if E_pad != E:
                z = np.zeros((E_pad - E, R), add.dtype)
                add = np.concatenate([add, z])
                rm = np.concatenate([rm, z])
            return clock, add, rm

        acc = planes(state)
        for other in others:
            acc = pmesh.orset_merge_sharded(mesh, *acc, *planes(other))
        clock, add, rm = (np.asarray(x) for x in acc)
        merged = K.orset_planes_to_state(
            clock, add[:E], rm[:E], members, replicas
        )
        state.clock = merged.clock
        state.entries = merged.entries
        state.deferred = merged.deferred
        self._note_orset_writeback(state)
        return state

    def _merge_orsets(self, state: ORSet, others: list) -> ORSet:
        members, replicas = K.Vocab(), K.Vocab()
        all_states = [state] + list(others)
        with trace.span("states.merge.scan"):
            for s in all_states:
                K.orset_scan_vocab(s, members, replicas)  # vocab-only pass
        if len(members) == 0 or len(replicas) == 0:
            return state
        planes = []
        for i, s in enumerate(all_states):
            with trace.span("states.merge.to_planes", i):
                planes.append(
                    K.orset_state_to_planes(s, members, replicas, scanned=True)
                )
        S, E, R = len(all_states), len(members), len(replicas)
        with trace.span("states.merge.stack"):
            clocks = np.stack([p[0] for p in planes])
            adds = np.stack([p[1] for p in planes])
            rms = np.stack([p[2] for p in planes])
            if self.bucket_vocab:
                # merge at power-of-two (S, E, R) classes: all-zero states
                # are the merge identity and zero vocab lanes are inert, so
                # the padded tree merge is byte-equal after the slice back —
                # and a population of small states shares one compiled
                # merge set
                Sp, Ep, Rp = _bucket(S, 2), _bucket(E), _bucket(R)
                if (Sp, Ep, Rp) != (S, E, R):
                    pad = ((0, Sp - S), (0, Ep - E), (0, Rp - R))
                    clocks = np.pad(clocks, (pad[0], pad[2]))
                    adds = np.pad(adds, pad)
                    rms = np.pad(rms, pad)
        trace.add("snapshot_merges", 1)
        trace.add("merge_state_cells", S * E * R)
        trace.add("merge_out_cells", E * R)
        trace.add("merge_clock_cells", (S + 1) * R)
        with trace.span("states.merge.device"):  # dispatch to the last byte
            clock, add, rm = obs_runtime.pull(
                *K.orset_merge_many(clocks, adds, rms)
            )
        with trace.span("states.merge.writeback"):
            merged = K.orset_planes_to_state(
                clock[:R], add[:E, :R], rm[:E, :R], members, replicas
            )
            state.clock = merged.clock
            state.entries = merged.entries
            state.deferred = merged.deferred
            self._note_orset_writeback(state)
        return state
