"""Chunked fold sessions: bounded-memory, transfer-aware bulk ingestion.

A session consumes decrypted op-file payloads chunk by chunk (fed by the
core's pipelined reader, core.py ``_read_remote_ops_pipelined``) and folds
them into one CRDT state with memory bounded by the chunk size — the
restructuring of the reference's consumer path (crdt-enc/src/lib.rs:471-547)
that SURVEY.md §7 hard part 3 calls for.

Three execution modes, chosen adaptively because the dominant cost changes
with regime.  The regime boundaries date from a v5e behind a ~20 MB/s,
~100 ms-per-dispatch host↔device link (BASELINE.md).  Measured on the
directly attached chip on 2026-09-30 (PERF.md §6, PR 41) at the one size a
benchmark cell reaches them, a 960k-op head over 4,096 × 10,000 cells:
HOST_REDUCE takes it in 2.5 s (leaf fold 0.35, combine 1.5–1.7, writeback
0.4–0.6) and DEVICE_STREAM in 36.0 s, 23.6 of them compiles and 9.9 the
1.31 GB pull-back of the batch planes at finish, byte-equal; so the
boundary between them stands, and a round of 48,000 rows stays in BUFFER:

* **BUFFER** — small ingests accumulate columns and fold once at finish
  through the accelerator's existing regime-picking tail (dense device
  over resident planes / sparse host / mesh).  Promotion out of BUFFER happens the moment the
  accumulated column bytes exceed ``BUFFER_BYTES``, so memory stays small.
* **HOST_REDUCE** — when the dense state planes are small relative to the
  row stream (``3·E·R·4 ≪ N·13``), shipping every row to the device is
  pure transfer cost (the fold itself is a segment-max the host can run at
  memory bandwidth).  Each chunk reduces into persistent host planes with
  ``np.maximum.at``; ONE tiny device pass applies the batch planes to the
  state planes at finish.  This is a hierarchical fold: host does the leaf
  level on data it necessarily already holds (it just decrypted it),
  device does the combine — bytes over the interconnect drop from
  ``N·13`` to ``6·E·R·4``.
* **DEVICE_STREAM** — when the planes themselves are large (E·R beyond
  ``HOST_PLANE_CELLS``), host reduction thrashes caches and the planes,
  not the rows, dominate transfer; the planes live on device (donated
  between chunks, ops/stream.py) and fixed-shape row chunks stream
  through the compiled fold — device memory stays at one chunk + planes.
  Under an ACTIVE MESH (and the accelerator's ``sharded_stream`` toggle,
  auto-on) this mode goes SPMD (``_device_feed_sharded``): chunks
  dp-sharded, donated planes mp-sharded, per-chunk ``orset_fold_sharded``
  in partial-reduction mode — a pod compaction streams through the same
  kernels the whole-batch sharded fold runs, instead of buffering the
  entire row batch host-side.

Exactness: every mode reproduces the one-big-``orset_fold`` semantics.
HOST_REDUCE masks stale adds against the state clock captured at session
start (exactly the kernel's ``seen`` mask); DEVICE_STREAM's carried clock
only ever rejects true replays under the core's per-actor version ordering
(ops/stream.py module docs).  Byte equality vs the host loop is pinned in
tests/test_fold_session.py across all modes.
"""

from __future__ import annotations

import numpy as np

from .. import ops as K
from ..models import GCounter, ORSet, PNCounter
from ..models.counters import POS
from ..obs import runtime as obs_runtime
from ..ops.columnar import KIND_ADD, KIND_RM
from ..utils import trace

BUFFER_BYTES = 4 << 20  # promote out of BUFFER beyond this many column bytes
# host-reduce planes up to E·R = 128M cells (~1.5GB for 3 int32 planes):
# np.maximum.at runs at memory bandwidth and the combine is elementwise, so
# host reduction wins until the planes threaten host RAM — only beyond that
# is the donated-buffer device stream (bounded device memory) the answer.
# (On the attached chip, 2026-09-30, at 41M cells and 960k rows: 2.5 s here
# against 9.9 s for the device stream's finish alone, which pulls its
# E-overshot batch planes back whole; nothing above 41M cells is measured.)
HOST_PLANE_CELLS = 1 << 27
DEVICE_CHUNK_ROWS = 1 << 20  # device-stream row bucket (one compile)

# Tests only: pin the DEVICE_STREAM fold's kernel choice (None = the
# product routing — the Pallas route engages on real TPU; False = XLA;
# True = the compiled Pallas kernel; "interpret" = the Pallas kernel in
# the interpreter, which is how a host-backend test reaches the branch).
FORCE_PALLAS_STREAM: bool | str | None = None


def _bucket(n: int, floor: int = 8) -> int:
    b = floor
    while b < n:
        b *= 2
    return b


class SessionDeclined(Exception):
    """The native decoder cannot represent this chunk (non-canonical
    encoding, vocab collision); the caller must fold it another way."""


def apply_batch_planes_host(clock0, add0, rm0, add_b, rm_b):
    """numpy mirror of :func:`crdt_enc_tpu.ops.orset.orset_apply_batch_planes`
    for small planes, where a device round-trip is pure latency.  The two
    must never diverge — tests/test_fold_session.py pins them equal on
    randomized inputs."""
    add_b = np.where(add_b > clock0[None, :], add_b, 0)
    clock = np.maximum(clock0, add_b.max(axis=0, initial=0))
    add = np.maximum(add0, add_b)
    rm = np.maximum(rm0, rm_b)
    add = np.where(add > rm, add, 0)
    rm = np.where(rm > clock[None, :], rm, 0)
    return clock, add, rm


class OrsetFoldSession:
    """Fold ORSet op-file payloads chunk by chunk into ``state``.

    Protocol: ``feed(payloads)`` per chunk (raises :class:`SessionDeclined`
    with the chunk unconsumed if the native decoder declines), then
    ``finish()`` exactly once — only finish mutates ``state``.
    """

    def __init__(self, accel, state: ORSet, actors_hint=()):
        from ..ops.columnar import strictly_sorted

        self.accel = accel
        self.state = state
        clock_counters = state.clock.counters
        fresh = (
            not clock_counters and not state.entries and not state.deferred
        )
        if fresh and strictly_sorted(actors_hint):
            # the streaming shape — a FRESH replica whose actor hint is
            # already the sorted table (storage listings are sorted):
            # the hint IS actors_sorted, the clock is all zeros, and the
            # Vocab index builds lazily.  The general path below cost
            # ~77ms of a ~150ms e2e streaming wall at the config-5
            # shape (100k-actor set union + sort + a 100k-iteration
            # Python clock loop + two eager index builds) — all of it
            # provably no-ops on an empty state.
            self.actors_sorted = list(actors_hint)
            self.replicas = K.Vocab.presorted_unique(self.actors_sorted)
            member_list: list = []
        else:
            # one pass over the state builds BOTH vocabularies: actors
            # via C-level set.update per entry dict, members in
            # first-appearance order (entries, then deferred) — a
            # per-dot intern walk here cost ~0.5s of every warm-open
            # tail ingest at 1M-dot states
            actor_set = set(actors_hint)
            actor_set.update(clock_counters)
            member_list = []
            for m, entry in state.entries.items():
                member_list.append(m)
                actor_set.update(entry)
            for m, dfr in state.deferred.items():
                member_list.append(m)
                actor_set.update(dfr)
            self.actors_sorted = sorted(actor_set)
            # sorted set ⇒ unique: skip the eager index build too
            self.replicas = K.Vocab.presorted_unique(self.actors_sorted)
        self.members = K.Vocab()
        for m in member_list:
            self.members.intern(m)
        self._state_members = len(self.members)
        self.R = len(self.replicas)
        # the kernel's stale-add mask is evaluated against the clock as of
        # session start for EVERY chunk — one-big-batch semantics.  Only
        # actors the clock actually mentions are visited (zeros
        # elsewhere), and none are on the fresh fast path.
        self._clock0 = np.zeros(max(self.R, 1), np.int32)
        if clock_counters:
            index = self.replicas.index
            for a, c in clock_counters.items():
                self._clock0[index[a]] = c
        self.mode = "buffer"
        self._buffered: list[tuple] = []
        self._buffered_bytes = 0
        self._member_canon: dict[int, bytes] = {}
        self._member_ids: dict[bytes, int] = {}  # wire bytes → member gid
        # actor-table flattening + native hash index, built once per
        # session and reused across chunk decodes (rebuilding per chunk
        # at 100k actors costs more than the decode itself); entries are
        # immutable, so concurrent decode_chunk threads can share it —
        # a racing double-build just writes the same value twice
        self._decode_cache: dict = {}
        self.rows_fed = 0
        # HOST_REDUCE accumulators (allocated at promotion)
        self._h_add = self._h_rm = None
        # DEVICE_STREAM carry (allocated at promotion); _d_sharded marks
        # the mesh route (planes mp-sharded, chunks dp-sharded)
        self._d_planes = None
        self._d_E = 0
        self._d_sharded = False
        self._finished = False

    # ------------------------------------------------------------------ feed
    def decode_chunk(self, payloads: list):
        """Stage 1, thread-safe (no session mutation): native columnar
        decode of one chunk's payloads.  The ctypes call releases the GIL,
        so the core decodes chunk i+1 while chunk i reduces."""
        from ..ops.native_decode import (
            decode_orset_payload_spans, intern_orset_spans,
        )

        with trace.span("session.decode"):
            part = decode_orset_payload_spans(
                payloads, self.actors_sorted, cache=self._decode_cache
            )
            if part is None:
                raise SessionDeclined("native decoder declined the chunk")
            return intern_orset_spans(part, with_bytes=True)

    def reduce_chunk(self, decoded) -> None:
        """Stage 2, serialized by the caller (mutates vocab + planes)."""
        assert not self._finished, "session already finished"
        member_bytes = None
        if len(decoded) == 6:
            kind, member_idx, actor_idx, counter, member_objs, \
                member_bytes = decoded
        else:
            kind, member_idx, actor_idx, counter, member_objs = decoded
        if len(kind) == 0:
            return
        with trace.span("session.remap"):
            member_global = self._remap_members(
                member_idx, member_objs, member_bytes
            )
        self.rows_fed += len(kind)
        cols = (kind, member_global, actor_idx, counter)
        if self.mode == "buffer":
            self._buffered.append(cols)
            self._buffered_bytes += len(kind) * 13
            if self._buffered_bytes > BUFFER_BYTES:
                self._promote()
        elif self.mode == "host_reduce":
            self._host_reduce(*cols)
        else:
            self._device_feed(*cols)

    def feed(self, payloads: list) -> None:
        """decode + reduce in one call (single-threaded convenience)."""
        self.reduce_chunk(self.decode_chunk(payloads))

    def _remap_members(self, member_idx, member_objs, member_bytes=None):
        """Chunk-local member interning → the session-global vocabulary.

        With ``member_bytes`` (the decoder's unique wire spans) a seen
        span is ONE bytes-dict hit — no object hashing, no re-pack: the
        per-chunk Python work drops from one intern + canonical pack per
        distinct member (measured ~30ms across the config-5 chunks) to
        effectively zero after the first chunk.  A new span pays one
        intern + pack exactly like the legacy path.

        Collision guard (both paths): distinct canonical bytes can still
        collide as Python values (1 == True, 0.0 == -0.0) — including
        ACROSS chunks or against members already in the state.  The
        dense planes cannot represent that, so each vocab slot remembers
        the canonical bytes it was first interned under and any mismatch
        declines the chunk (the per-op path then matches the host dict
        semantics exactly).  A NON-canonical wire alias of the same
        value (e.g. uint8-encoded 5) is accepted and cached per wire
        span, exactly as the legacy re-pack accepted it."""
        from ..utils import codec

        canon = self._member_canon
        if member_bytes is not None:
            # member_objs may be None (lazy mode): a new span decodes
            # HERE, once per distinct member per stream
            table = np.empty(len(member_bytes), np.int32)
            ids = self._member_ids
            for i, pk in enumerate(member_bytes):
                gid = ids.get(pk)
                if gid is None:
                    obj = (
                        codec.unpack(pk) if member_objs is None
                        else member_objs[i]
                    )
                    gid = self.members.intern(obj)
                    prev = canon.get(gid)
                    if prev is None:
                        stored = self.members.items[gid]
                        prev = codec.pack(stored)
                        canon[gid] = prev
                    if prev != pk and codec.pack(obj) != prev:
                        raise SessionDeclined("member vocab collision")
                    ids[pk] = gid
                table[i] = gid
            return table[member_idx]
        table = np.empty(len(member_objs), np.int32)
        for i, obj in enumerate(member_objs):
            gid = self.members.intern(obj)
            table[i] = gid
            pk = codec.pack(obj)
            prev = canon.get(gid)
            if prev is None:
                stored = self.members.items[gid]
                prev = pk if stored is obj else codec.pack(stored)
                canon[gid] = prev
            if prev != pk:
                raise SessionDeclined("member vocab collision")
        return table[member_idx]

    # ------------------------------------------------------------- promotion
    def _promote(self) -> None:
        """Leave BUFFER mode: pick the cheap representation for this regime
        and replay the buffered chunks through it."""
        mesh_on = getattr(self.accel, "_mesh_active", lambda: False)()
        sharded_ok = mesh_on and getattr(self.accel, "sharded_stream", False)
        if sharded_ok:
            import jax

            if jax.process_count() > 1:
                # the stream's growth and finish combine pull the
                # mp-sharded planes to host (np.asarray), which only
                # addresses LOCAL shards — on a multi-host pod that
                # raises, so those meshes keep the buffered whole-batch
                # sharded fold until a process_allgather combine lands
                sharded_ok = False
        if mesh_on and not sharded_ok:
            # mesh ingests without the sharded streaming route finish
            # through the whole-batch sharded fold — stay buffered
            # (multi-chip compaction trades host memory for SPMD
            # execution; the sharded_stream toggle removes the trade)
            return
        E_est = _bucket(max(len(self.members), 1))
        if not mesh_on and E_est * self.R <= HOST_PLANE_CELLS:
            self.mode = "host_reduce"
            self._h_add = np.zeros((E_est, self.R), np.int32)
            self._h_rm = np.zeros((E_est, self.R), np.int32)
            for cols in self._buffered:
                self._host_reduce(*cols)
        else:
            self.mode = "device_stream"
            self._d_sharded = mesh_on
            # overshoot the member capacity: every growth step recompiles
            # the donated fold for the new static shape, so fewer, larger
            # steps (the compile cache then amortizes across runs)
            self._d_E = _bucket(max(len(self.members), 1) * 4)
            # the device planes seed from ZERO, not from the state: the
            # streamed fold is a pure reduction of the op batch, combined
            # into the live state at finish with op-APPLY semantics
            # (apply_batch_planes_host — NOT the CvRDT merge, whose
            # survivor rule would misread the batch clock as state
            # history), and never reading the state here keeps this
            # thread-safe against concurrent applies — this code runs off
            # the event loop (core drain_one → to_thread)
            import jax

            if mesh_on:
                # mp-sharded planes: each device owns E_pad/mp member rows
                from . import mesh as pmesh

                mp = self.accel.mesh.shape["mp"]
                self._d_E = -(-self._d_E // mp) * mp
                # h2d_bytes counted inside sharded_stream_planes, at issue
                self._d_planes = pmesh.sharded_stream_planes(
                    self.accel.mesh, self._d_E, self.R
                )
            else:
                # the zero accumulator planes materialize ON device (an
                # XLA fill — no host buffer exists, so there is no
                # full-plane device_put to issue or count): repeated
                # read_remote rounds in one process stop re-uploading
                # plane-sized zero buffers (ISSUE-4 plane reuse)
                import jax.numpy as jnp

                self._d_planes = (
                    jnp.zeros(max(self.R, 1), jnp.int32),
                    jnp.zeros((self._d_E, self.R), jnp.int32),
                    jnp.zeros((self._d_E, self.R), jnp.int32),
                )
            for cols in self._buffered:
                self._device_feed(*cols)
        self._buffered = []
        self._buffered_bytes = 0

    def _state_planes(self, E_pad: int):
        clock0, add0, rm0 = K.orset_state_to_planes(
            self.state, self.members, self.replicas, scanned=True
        )
        E = add0.shape[0]
        if E_pad > E:
            # column count follows the CURRENT replica vocab — it may have
            # grown past self.R if a concurrent apply introduced an actor
            z = np.zeros((E_pad - E, len(self.replicas)), np.int32)
            add0 = np.concatenate([add0, z])
            rm0 = np.concatenate([rm0, z])
        return clock0, add0, rm0

    # ------------------------------------------------- host-reduce internals
    def _grow_host_planes(self) -> None:
        E_new = _bucket(len(self.members))
        if E_new * self.R > 2 * HOST_PLANE_CELLS:
            # a member-skewed stream outgrew the promotion-time estimate;
            # declining (before any mutation) keeps the bounded-memory
            # contract — the core folds the rest per-op, chunk by chunk
            raise SessionDeclined(
                "member vocabulary outgrew the host reduction planes"
            )
        grow = E_new - self._h_add.shape[0]
        if grow > 0:
            z = np.zeros((grow, self.R), np.int32)
            self._h_add = np.concatenate([self._h_add, z])
            self._h_rm = np.concatenate([self._h_rm, z])

    def _host_reduce(self, kind, member, actor, counter) -> None:
        """The leaf-level fold on host: exactly orset_fold's masked
        scatter-max (ops/orset.py:84-131).  One native linear pass
        (np.maximum.at is a buffered ufunc, ~10× slower at these scales);
        the numpy form remains as fallback."""
        if len(self.members) > self._h_add.shape[0]:
            self._grow_host_planes()
        trace.add("fold_rows_host", len(kind))
        with trace.span("session.host_reduce"):
            try:
                from .. import native

                lib = native.load()
                import ctypes

                i32p = ctypes.POINTER(ctypes.c_int32)
                i8p = ctypes.POINTER(ctypes.c_int8)
                kind_c = np.ascontiguousarray(kind, np.int8)
                member_c = np.ascontiguousarray(member, np.int32)
                actor_c = np.ascontiguousarray(actor, np.int32)
                counter_c = np.ascontiguousarray(counter, np.int32)
                clock_c = np.ascontiguousarray(self._clock0, np.int32)
                oob = lib.orset_host_reduce(
                    kind_c.ctypes.data_as(i8p),
                    member_c.ctypes.data_as(i32p),
                    actor_c.ctypes.data_as(i32p),
                    counter_c.ctypes.data_as(i32p),
                    len(kind_c),
                    clock_c.ctypes.data_as(i32p),
                    self.R,
                    self._h_add.shape[0],
                    self._h_add.ctypes.data_as(i32p),
                    self._h_rm.ctypes.data_as(i32p),
                )
                if oob:
                    raise AssertionError(
                        f"{oob} rows outside the host planes (sizing bug)"
                    )
                return
            except RuntimeError:  # native lib unavailable: numpy fallback
                pass
            valid = actor < self.R
            seen = counter <= self._clock0[np.minimum(actor, self.R - 1)]
            live_add = (kind == KIND_ADD) & valid & ~seen
            is_rm = (kind == KIND_RM) & valid
            np.maximum.at(
                self._h_add,
                (member[live_add], actor[live_add]),
                counter[live_add],
            )
            np.maximum.at(
                self._h_rm, (member[is_rm], actor[is_rm]), counter[is_rm]
            )

    # ------------------------------------------------ device-stream internals
    def _grow_device_planes(self) -> None:
        E_new = _bucket(len(self.members) * 4)  # overshoot (see _promote)
        if self._d_sharded:
            from . import mesh as pmesh

            mp = self.accel.mesh.shape["mp"]
            E_new = -(-E_new // mp) * mp
            if E_new <= self._d_E:
                return
            # growth is rare (4× overshoot): a host round-trip keeps the
            # mp re-shard trivial instead of a resharding pad program
            _, clock_s, plane_s = pmesh.stream_sharding(self.accel.mesh)
            import jax

            clock, add, rm = obs_runtime.pull(*self._d_planes)
            z = np.zeros((E_new - self._d_E, add.shape[1]), np.int32)
            # the growth re-upload is a real transfer the plane gauges
            # would otherwise miss (OBS001)
            trace.add(
                "h2d_bytes", clock.nbytes + 2 * (add.nbytes + z.nbytes)
            )
            self._d_planes = (
                jax.device_put(clock, clock_s),
                jax.device_put(np.concatenate([add, z]), plane_s),
                jax.device_put(np.concatenate([rm, z]), plane_s),
            )
            self._d_E = E_new
            return
        if E_new > self._d_E:
            import jax.numpy as jnp

            clock, add, rm = self._d_planes
            pad = E_new - self._d_E
            add = jnp.pad(add, ((0, pad), (0, 0)))
            rm = jnp.pad(rm, ((0, pad), (0, 0)))
            self._d_planes = (clock, add, rm)
            self._d_E = E_new

    def _device_feed_sharded(self, kind, member, actor, counter) -> None:
        """DEVICE_STREAM over the accelerator's mesh: the SPMD twin of
        :meth:`_device_feed`.  Rows pad to the dp axis
        (``pad_rows_for_mesh``) and stream as dp-sharded fixed-shape
        chunks through the donated ``orset_fold_sharded`` step
        (``retire_rm=False`` — partial-reduction mode, identical combine
        discipline to the single-chip stream); the accumulator planes
        stay mp-sharded on device between chunks, and chunk k+1's
        sharded ``device_put`` is still issued under chunk k's in-flight
        fold (``fold_chunks_overlapped`` with a sharded ``put``).  The
        per-shard scatter runs the XLA segment-max kernel — the
        per-shard Pallas route needs a shard-local tile cap per chunk,
        which would recompile per chunk; the whole-batch sharded fold
        keeps that kernel."""
        import jax

        from ..ops.stream import fold_chunks_overlapped, iter_orset_chunks
        from . import mesh as pmesh

        mesh = self.accel.mesh
        dp = mesh.shape["dp"]
        if len(self.members) > self._d_E:
            self._grow_device_planes()
        cols = K.OrsetColumns(
            np.asarray(kind, np.int8),
            np.asarray(member, np.int32),
            np.asarray(actor, np.int32),
            np.asarray(counter, np.int32),
            self.members,
            self.replicas,
        )
        pmesh.pad_rows_for_mesh(cols, dp, self.R)
        rows = min(DEVICE_CHUNK_ROWS, _bucket(len(cols.kind)))
        rows = -(-rows // dp) * dp  # the fixed chunk shape must divide dp
        step = pmesh.sharded_stream_fold_step(mesh)
        row_s, _, _ = pmesh.stream_sharding(mesh)

        def put(x):
            # h2d_bytes counted by fold_chunks_overlapped at chunk issue
            return jax.device_put(x, row_s)  # lint: disable=OBS001

        def fold_step(planes, chunk):
            return step(*planes, *chunk)

        with trace.span("session.device_fold"):
            self._d_planes = fold_chunks_overlapped(
                self._d_planes,
                iter_orset_chunks(
                    cols.kind, cols.member, cols.actor, cols.counter,
                    rows, self.R,
                ),
                fold_step,
                put=put,
            )

    def _device_feed(self, kind, member, actor, counter) -> None:
        trace.add("fold_rows_device", len(kind))
        if self._d_sharded:
            return self._device_feed_sharded(kind, member, actor, counter)
        from ..ops import pallas_fold as PF
        from ..ops.stream import (
            _fold_donated, _fold_donated_pallas, fold_chunks_overlapped,
            iter_orset_chunks,
        )

        if len(self.members) > self._d_E:
            self._grow_device_planes()
        # the flagship Pallas scatter serves the streaming-plane regime
        # too when eligible — the SAME predicate as the dense/sharded
        # routes (accel._pallas_eligible) plus the ablk key-space bound
        # (this route has no wide-layout fallback)
        use_pallas = bool(
            len(counter)
            and self.accel._pallas_eligible(counter)
            and PF.ablk_key_space_fits(self._d_E, self.R)
        )
        interpret = False
        if FORCE_PALLAS_STREAM is not None:  # tests pin the branch
            use_pallas = bool(FORCE_PALLAS_STREAM)
            interpret = FORCE_PALLAS_STREAM == "interpret"
        tile_cap = 0
        if use_pallas:
            trace.add("pallas_routed", 1)
            tile_cap = PF.fold_cap(member, self._d_E)

        # retire_rm=False: a horizon retired against the batch-local
        # clock would lose its kill-effect on pre-existing state
        # entries; finish() retires once against the true merged clock
        def fold_step(planes, chunk):
            if use_pallas:
                return _fold_donated_pallas(
                    *planes, *chunk,
                    num_members=self._d_E, num_replicas=self.R,
                    tile_cap=tile_cap, retire_rm=False,
                    interpret=interpret,
                )
            return _fold_donated(
                *planes, *chunk,
                num_members=self._d_E, num_replicas=self.R,
                impl="fused", small_counters=False, retire_rm=False,
            )

        with trace.span("session.device_fold"):
            rows = min(DEVICE_CHUNK_ROWS, _bucket(len(kind)))
            # overlapped consumer loop: chunk k+1's H2D transfer is
            # issued while chunk k's donated fold is in flight; the
            # final fold stays un-blocked — jax dispatch is async, so
            # the next chunk's decrypt and decode overlap the device
            # work (ops/stream.py fold_chunks_overlapped)
            self._d_planes = fold_chunks_overlapped(
                self._d_planes,
                iter_orset_chunks(kind, member, actor, counter, rows, self.R),
                fold_step,
            )

    # ---------------------------------------------------------------- finish
    def finish(self) -> ORSet:
        """Fold everything fed into ``state`` (the only state mutation).

        Concurrency-correct by construction: the state is re-read HERE, in
        one sync section, so applies or state merges that landed while
        chunks were in flight are honored — both modes re-evaluate the
        stale mask against the current clock inside the op-apply combine
        (``apply_batch_planes_host``; batch planes are reductions of OPS,
        never CvRDT states — see the device_finish comment)."""
        assert not self._finished, "session already finished"
        self._finished = True
        state = self.state
        if self.mode == "buffer":
            if not self._buffered:
                return state
            kind = np.concatenate([c[0] for c in self._buffered])
            member = np.concatenate([c[1] for c in self._buffered])
            actor = np.concatenate([c[2] for c in self._buffered])
            counter = np.concatenate([c[3] for c in self._buffered])
            self._buffered = []
            if len(self.members) == 0 or self.R == 0:
                return state
            return self.accel._fold_orset_columns(
                state, kind, member, actor, counter, self.members, self.replicas
            )
        # concurrent applies may have introduced members (never actors —
        # feeds only ever index the fixed actors_sorted columns, and new
        # actors' dots live in the state planes, re-read below)
        K.orset_scan_vocab(state, self.members, self.replicas)
        E = len(self.members)
        R_final = len(self.replicas)
        if self.mode == "host_reduce":
            with trace.span("session.combine"):
                E_pad = max(self._h_add.shape[0], _bucket(max(E, 1)))
                clock0, add0, rm0 = self._state_planes(E_pad)
                add_b = self._pad_batch(self._h_add, E_pad, R_final)
                rm_b = self._pad_batch(self._h_rm, E_pad, R_final)
                # the combine is one elementwise pass — the host runs it at
                # memory bandwidth on planes it already holds, so shipping
                # them to an accelerator is pure interconnect cost at ANY
                # size (the jit twin orset_apply_batch_planes exists for
                # callers whose planes are already device-resident, and
                # tests pin the two equal)
                clock, add, rm = apply_batch_planes_host(
                    clock0, add0, rm0, add_b, rm_b
                )
        else:
            obs_runtime.sample_device_memory()  # planes still resident
            with trace.span("session.device_finish"):
                # op-APPLY semantics, exactly as HOST_REDUCE: the streamed
                # planes are a fold of OPS from a zero clock, NOT a valid
                # CvRDT state — their clock (per-actor add maxima) covers
                # every older dot of those actors, so the CvRDT merge's
                # survivor rule would delete pre-existing entries the
                # batch never touched (confirmed data loss; regression in
                # tests/test_fold_session.py)
                d_add, d_rm = obs_runtime.pull(*self._d_planes[1:])
                E_pad = max(self._d_E, _bucket(max(E, 1)))
                clock0, add0, rm0 = self._state_planes(E_pad)
                d_add = self._pad_batch(d_add, E_pad, R_final)
                d_rm = self._pad_batch(d_rm, E_pad, R_final)
                clock, add, rm = apply_batch_planes_host(
                    clock0, add0, rm0, d_add, d_rm
                )
        with trace.span("session.writeback"):
            folded = K.orset_planes_to_state(
                clock, add[:E], rm[:E], self.members, self.replicas
            )
        state.clock = folded.clock
        state.entries = folded.entries
        state.deferred = folded.deferred
        # the combine ran on the host, and these planes ARE the state it
        # wrote: where planes of this shape stay on the device the
        # accelerator takes them up now, once, so that the first round
        # after an open's ingest folds over resident planes instead of
        # walking the state back into them; else it bumps the mutation
        # epoch and drops what it held for this state
        self._d_planes = None  # the batch planes go before the state's come
        install = getattr(self.accel, "install_orset_planes", None)
        if install is not None:
            install(
                state, self.members, self.replicas, clock, add[:E], rm[:E],
                self._member_canon,
            )
        else:
            state._mut += 1
        return state

    @staticmethod
    def _pad_batch(plane, E_pad: int, R_final: int):
        e, r = plane.shape
        if e == E_pad and r == R_final:
            return plane
        out = np.zeros((E_pad, R_final), np.int32)
        out[:e, :r] = plane
        return out

    @staticmethod
    def _pad_clock(clock, R_final: int):
        if len(clock) == R_final:
            return clock
        out = np.zeros(R_final, np.int32)
        out[: len(clock)] = clock
        return out


class CounterFoldSession:
    """Chunked G/PN-Counter ingestion: per-actor maxima reduce on host per
    chunk (the planes are O(R) — transfer and scatter are both trivial),
    one device combine at finish."""

    def __init__(self, accel, state, actors_hint=()):
        self.accel = accel
        self.state = state
        self.is_pn = isinstance(state, PNCounter)
        clocks = (
            (state.p.clock, state.n.clock) if self.is_pn else (state.clock,)
        )
        actor_set = set(actors_hint)
        for c in clocks:
            actor_set.update(c.counters)
        self.actors_sorted = sorted(actor_set)
        self.replicas = K.Vocab(self.actors_sorted)
        self.R = len(self.replicas)
        self._p = np.zeros(max(self.R, 1), np.int32)
        self._n = np.zeros(max(self.R, 1), np.int32)
        self.rows_fed = 0
        self._finished = False

    def decode_chunk(self, payloads: list):
        from ..ops.native_decode import decode_counter_payload_batch

        decoded = decode_counter_payload_batch(payloads, self.actors_sorted)
        if decoded is None:
            raise SessionDeclined("native decoder declined the chunk")
        sign = decoded[0]
        if len(sign) and isinstance(self.state, GCounter) and np.any(sign != POS):
            raise SessionDeclined("PN-shaped rows in a G-Counter state")
        return decoded

    def reduce_chunk(self, decoded) -> None:
        assert not self._finished, "session already finished"
        sign, actor_idx, counter = decoded
        if len(sign) == 0:
            return
        self.rows_fed += len(sign)
        pos = sign == POS
        np.maximum.at(self._p, actor_idx[pos], counter[pos])
        np.maximum.at(self._n, actor_idx[~pos], counter[~pos])

    def feed(self, payloads: list) -> None:
        self.reduce_chunk(self.decode_chunk(payloads))

    def finish(self):
        assert not self._finished, "session already finished"
        self._finished = True
        state = self.state
        if self.R == 0 or self.rows_fed == 0:
            return state
        # concurrent applies may have introduced actors since init: rescan
        # the state clocks (fed rows only ever index the original columns)
        clocks = (
            (state.p.clock, state.n.clock) if self.is_pn else (state.clock,)
        )
        for c in clocks:
            for a in c.counters:
                self.replicas.intern(a)
        R_final = len(self.replicas)
        p = self._pad(self._p, R_final)
        n = self._pad(self._n, R_final)
        if self.is_pn:
            p0 = K.vclock_to_dense(state.p.clock, self.replicas)
            n0 = K.vclock_to_dense(state.n.clock, self.replicas)
            state.p.clock = K.dense_to_vclock(np.maximum(p0, p), self.replicas)
            state.n.clock = K.dense_to_vclock(np.maximum(n0, n), self.replicas)
        else:
            c0 = K.vclock_to_dense(state.clock, self.replicas)
            state.clock = K.dense_to_vclock(np.maximum(c0, p), self.replicas)
        return state

    @staticmethod
    def _pad(arr, R_final: int):
        if len(arr) == R_final:
            return arr
        out = np.zeros(R_final, np.int32)
        out[: len(arr)] = arr
        return out


class MapFoldSession:
    """Chunked CrdtMap<orset> ingestion: each chunk decodes to the four
    row families natively (validation up front — ``SessionDeclined``
    fires at reduce time, never at finish) and interns its key/member
    spans into running vocabularies; finish concatenates the remapped
    families and runs the columnar map fold once against the state read
    AT FINISH (``crdtmap_fold_host``), so applies that landed while
    chunks were in flight are honored exactly like the whole-batch
    path."""

    def __init__(self, accel, state, actors_hint=()):
        from ..ops.columnar import Vocab

        self.accel = accel
        self.state = state
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
        self.actors_sorted = sorted(actor_set)
        self.keys = Vocab()
        self.members = Vocab()
        self._fams: list = []  # (B, A, Rm, K) with vocab-global indices
        self._n_groups = 0
        self.rows_fed = 0
        self._finished = False

    def decode_chunk(self, payloads: list):
        from ..ops.map_columnar import decode_map_payload_batch

        decoded = decode_map_payload_batch(payloads, self.actors_sorted)
        if decoded is None:
            raise SessionDeclined("native map decoder declined the chunk")
        return decoded

    def _remap(self, vocab, objs):
        """Chunk-local object table → running-vocab indices; declines on
        a value collision (1 == True etc. — distinct canonical spans
        interning to one slot would scatter rows onto the wrong row)."""
        idx = np.fromiter(
            (vocab.intern(o) for o in objs), np.int32, count=len(objs)
        )
        if len(objs) and len(np.unique(idx)) != len(objs):
            raise SessionDeclined("vocab value collision in map chunk")
        return idx

    def reduce_chunk(self, decoded) -> None:
        assert not self._finished, "session already finished"
        B, A, Rm, Kk, key_objs, member_objs = decoded
        kmap = self._remap(self.keys, key_objs)
        mmap = self._remap(self.members, member_objs)

        def rekey(fam, with_member):
            out = dict(fam)
            if len(fam["key"]):
                out["key"] = kmap[fam["key"]]
            if with_member and len(fam.get("member", ())):
                out["member"] = mmap[fam["member"]]
            return out

        B2, A2, Rm2 = rekey(B, False), rekey(A, True), rekey(Rm, True)
        K2 = rekey(Kk, False)
        if len(K2["group"]):
            K2["group"] = K2["group"] + self._n_groups
            self._n_groups += int(Kk["group"].max()) + 1
        self._fams.append((B2, A2, Rm2, K2))
        self.rows_fed += (
            len(B2["actor"]) + len(A2["actor"]) + len(Rm2["actor"])
            + len(K2["actor"])
        )

    def feed(self, payloads: list) -> None:
        self.reduce_chunk(self.decode_chunk(payloads))

    def finish(self):
        from ..ops.columnar import Vocab
        from ..ops.map_columnar import crdtmap_fold_host

        assert not self._finished, "session already finished"
        self._finished = True
        state = self.state
        if not self._fams:
            return state

        def cat(ix, names):
            return {
                n: np.concatenate([f[ix].get(n, np.zeros(0, np.int32))
                                   for f in self._fams])
                for n in names
            }

        B = cat(0, ("key", "actor", "ctr"))
        A = cat(1, ("key", "member", "actor", "ctr"))
        Rm = cat(2, ("key", "member", "actor", "ctr", "mactor", "mctr"))
        Kk = cat(3, ("key", "actor", "ctr", "group"))
        self._fams = []
        # concurrent applies may have introduced actors since open: the
        # fed rows only ever index the original sorted prefix, so new
        # actors intern AFTER it and the row indices stay valid
        replicas = Vocab(self.actors_sorted)
        state_actors = set(state.clock.counters)
        for birth in state.births.values():
            state_actors.update(birth)
        for ctx, _rm_keys in state.deferred.values():
            state_actors.update(ctx.counters)
        for child in state.vals.values():
            state_actors.update(child.clock.counters)
            for entry in child.entries.values():
                state_actors.update(entry)
            for dfr in child.deferred.values():
                state_actors.update(dfr)
        for a in sorted(state_actors):
            replicas.intern(a)
        impl = self.accel.map_fold_impl
        mesh_on = getattr(self.accel, "_mesh_active", lambda: False)()
        if impl is None and mesh_on:
            impl = "device"
        elif impl is None:
            impl = (
                "device"
                if self.rows_fed >= self.accel.min_device_batch
                else "host"
            )
        crdtmap_fold_host(
            state, B, A, Rm, Kk, self.keys, self.members,
            replicas, fold_impl=impl,
            mesh=self.accel.mesh if impl == "device" and mesh_on else None,
        )
        return state


def session_supported(state) -> bool:
    """Cheap type predicate for :func:`open_fold_session` — True iff a
    chunked columnar session exists for ``state``'s type.  Costs one
    isinstance chain, no session construction (whose state scans are the
    expensive part) — callers use it to decide whether to spin up
    pipeline machinery at all."""
    from ..models.crdtmap import CrdtMap

    if isinstance(state, (ORSet, GCounter, PNCounter)):
        return True
    return isinstance(state, CrdtMap) and state.child == b"orset"


def open_fold_session(accel, state, actors_hint=()):
    """A fold session for ``state``, or None when no chunked columnar path
    exists for its type (the caller folds chunks through the per-op path)."""
    if not session_supported(state):
        return None
    if isinstance(state, ORSet):
        return OrsetFoldSession(accel, state, actors_hint)
    if isinstance(state, (GCounter, PNCounter)):
        return CounterFoldSession(accel, state, actors_hint)
    return MapFoldSession(accel, state, actors_hint)
