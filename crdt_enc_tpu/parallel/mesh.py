"""Distributed fold/merge: SPMD over a device mesh.

The scale-out story (SURVEY.md §2.3): op batches shard across the ``dp``
axis (each device folds its slice of the flattened op rows) and the state
planes shard across the ``mp`` axis (each device owns a contiguous member
range of the (E, R) matrices — the "tensor parallel" analogue).  Because the
fold is an elementwise-max semigroup, cross-device combination is a single
``jax.lax.pmax`` over ``dp`` riding ICI — no parameter servers, no NCCL,
exactly XLA collectives (the reference has no distributed backend at all;
its transport is the synced filesystem, which this keeps untouched).

Works on any mesh JAX can build: the one real TPU chip (1×1), a virtual
8-CPU-device mesh in tests, or a multi-host TPU slice (devices spanning
hosts — ``jax.distributed`` handles DCN bootstrap; the collectives here are
oblivious to the host boundary).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import ops as K
from ..ops.columnar import KIND_ADD, KIND_RM
from ..ops.counters import sum_wide
from ..utils import trace

def parse_mesh_spec(spec: str) -> tuple[int, int]:
    """``"dp=N[,mp=M]"`` → ``(dp, mp)``.  The ONE parser behind every
    ``--mesh`` CLI flag (bench.py, tools/daemon) — raises ``ValueError``
    on malformed specs, non-positive axes, or a single-device mesh
    (``dp·mp < 2``: a size-1 "mesh" silently degrades to the unsharded
    path, which a flag asking for sharding must never do)."""
    dp, mp = 1, 1
    for part in spec.split(","):
        k, _, v = part.partition("=")
        if k == "dp":
            dp = int(v)
        elif k == "mp":
            mp = int(v)
        else:
            raise ValueError(f"unknown mesh axis {k!r} (want dp=N[,mp=M])")
    if dp < 1 or mp < 1 or dp * mp < 2:
        raise ValueError(
            f"mesh wants positive axes and at least 2 devices, got "
            f"dp={dp},mp={mp}"
        )
    return dp, mp


def make_mesh(shape: tuple[int, int] = None, devices=None) -> Mesh:
    """A (dp, mp) mesh over the available devices; defaults to all devices
    on the dp axis."""
    devices = devices if devices is not None else jax.devices()
    if shape is None:
        shape = (len(devices), 1)
    dp, mp = shape
    arr = np.asarray(devices[: dp * mp]).reshape(dp, mp)
    return Mesh(arr, axis_names=("dp", "mp"))


def mesh_for_population(n_lanes: int, devices=None) -> Mesh | None:
    """The population runner's mesh (sim/population.py): schedule×tenant
    lanes ride the ``dp`` axis — a lane's tenants are just more rows in
    the PR-14 tenant mega-fold, since schedules never interact — and the
    replica planes ride ``mp``.  dp gets the device majority (lanes
    outnumber the per-tenant replica-plane width in every population
    shape), mp takes what cleanly remains: dp = min(n_lanes, D) and
    mp = D // dp when that divides, else a flat (D, 1).  Returns None on
    a single-device host — the unsharded path IS the single-chip layout,
    and a size-1 mesh must not pretend otherwise (parse_mesh_spec
    enforces the same rule for explicit specs)."""
    devices = devices if devices is not None else jax.devices()
    n_dev = len(devices)
    if n_dev < 2:
        return None
    dp = max(1, min(int(n_lanes), n_dev))
    mp = n_dev // dp if n_dev % dp == 0 else 1
    return make_mesh((dp, mp), devices=devices[: dp * mp])


def _local_fold(clock0, add0, rm0, kind, member, actor, counter, member_lo, R,
                impl="xla", tile_cap=0, interpret=False, retire_rm=True):
    """Per-device body: fold this device's op rows into its member slice.

    ``member_lo`` is the first global member index of this device's slice;
    rows outside the slice are masked (they belong to a different mp shard).
    ``add0``/``rm0`` arrive as this device's (E_local, R) slice.

    ``impl="pallas"`` runs the scatter phase through the flagship ablk
    kernel (ops/pallas_fold.py orset_scatter_pallas) — a mesh compaction
    then executes the same kernel a single chip does; the dp-pmax
    combine and normalize tail are identical either way.

    ``retire_rm=False`` keeps remove horizons un-retired, exactly as in
    ``ops.orset.orset_fold``: required when the planes are a PARTIAL
    reduction (the sharded streaming fold) combined with a pre-existing
    state later — a horizon retired against the batch-local clock would
    lose its kill-effect on state entries it never met.
    """
    E_local = add0.shape[0]
    pad = actor >= R
    local_member = member - member_lo
    in_slice = (local_member >= 0) & (local_member < E_local)
    is_add = (kind == KIND_ADD) & ~pad & in_slice
    is_rm = (kind == KIND_RM) & ~pad & in_slice
    actor_ix = jnp.minimum(actor, R - 1)
    member_ix = jnp.clip(local_member, 0, E_local - 1)

    if impl == "pallas":
        from ..ops.pallas_fold import orset_scatter_pallas

        # out-of-slice rows become padding for this shard's kernel
        shard_actor = jnp.where(in_slice & ~pad, actor, R)
        add_new, rm_new = orset_scatter_pallas(
            kind, member_ix, shard_actor, counter,
            num_members=E_local, num_replicas=R, tile_cap=tile_cap,
            interpret=interpret,
        )
    else:
        seg = member_ix * R + actor_ix
        add_new = jax.ops.segment_max(
            jnp.where(is_add, counter, 0), seg, num_segments=E_local * R
        )
        rm_new = jax.ops.segment_max(
            jnp.where(is_rm, counter, 0), seg, num_segments=E_local * R
        )
        add_new = jnp.maximum(add_new, 0).reshape(E_local, R)
        rm_new = jnp.maximum(rm_new, 0).reshape(E_local, R)
    # cell-level replay gate (≡ row gating by per-actor dot monotonicity;
    # see ops/orset.py) — avoids a per-row clock gather on every shard
    add_new = jnp.where(add_new > clock0[None, :], add_new, 0)
    clock_new = jnp.maximum(
        jax.ops.segment_max(
            jnp.where((kind == KIND_ADD) & ~pad, counter, 0),
            actor_ix,
            num_segments=R,
        ),
        0,
    )

    # combine partials across the dp axis: max is the whole merge
    add_new = jax.lax.pmax(add_new, "dp")
    rm_new = jax.lax.pmax(rm_new, "dp")
    clock_new = jax.lax.pmax(clock_new, "dp")

    clock = jnp.maximum(clock0, clock_new)
    add = jnp.maximum(add0, add_new)
    rm = jnp.maximum(rm0, rm_new)
    add = jnp.where(add > rm, add, 0)
    if retire_rm:
        rm = jnp.where(rm > clock[None, :], rm, 0)
    return clock, add, rm


def orset_fold_sharded(
    mesh: Mesh,
    clock0,
    add0,
    rm0,
    kind,
    member,
    actor,
    counter,
    impl: str = "xla",
    tile_cap: int = 0,
    interpret: bool = False,
    retire_rm: bool = True,
):
    """Sharded ORSet fold.

    Layout: op rows sharded over ``dp`` (row count must divide by dp —
    bucket-pad first); state planes sharded over ``mp`` on the member axis
    (E must divide by mp); the clock is replicated (it is O(R) and every
    shard updates it).  Returns (clock, add, rm) with the same shardings.

    ``impl="pallas"``: each shard's scatter phase runs the flagship ablk
    kernel (pass ``tile_cap`` from ``fold_cap`` over the WHOLE member
    column — it bounds every shard's tiles).

    ``retire_rm=False``: partial-reduction mode for the sharded
    streaming fold (see :func:`_local_fold`).
    """
    dp = mesh.shape["dp"]
    mp = mesh.shape["mp"]
    E, R = add0.shape
    if len(kind) % dp or E % mp:
        raise ValueError(
            f"pad first: rows {len(kind)} % dp {dp} or members {E} % mp {mp}"
        )
    if impl == "pallas" and not tile_cap:
        raise ValueError(
            "impl='pallas' requires tile_cap (fold_cap over the whole "
            "member column)"
        )
    E_local = E // mp

    def body(clock0, add0, rm0, kind, member, actor, counter, member_lo):
        return _local_fold(
            clock0, add0, rm0, kind, member, actor, counter, member_lo[0], R,
            impl=impl, tile_cap=tile_cap, interpret=interpret,
            retire_rm=retire_rm,
        )

    # each mp shard needs its global member offset
    member_lo = np.arange(mp, dtype=np.int32) * E_local

    # op rows sharded over dp; plane member-axis sharded over mp
    fold = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(
            P(),
            P("mp", None),
            P("mp", None),
            P("dp"),
            P("dp"),
            P("dp"),
            P("dp"),
            P("mp"),
        ),
        out_specs=(P(), P("mp", None), P("mp", None)),
        check_vma=False,
    )
    return fold(clock0, add0, rm0, kind, member, actor, counter, member_lo)


def orset_merge_sharded(mesh: Mesh, clock_a, add_a, rm_a, clock_b, add_b, rm_b):
    """Pairwise state merge with planes sharded over mp — pure elementwise,
    so the spec is trivial; exists to keep compaction fully SPMD."""

    merge = jax.shard_map(
        K.orset_merge,
        mesh=mesh,
        in_specs=(P(), P("mp", None), P("mp", None), P(), P("mp", None), P("mp", None)),
        out_specs=(P(), P("mp", None), P("mp", None)),
        check_vma=False,
    )
    return merge(clock_a, add_a, rm_a, clock_b, add_b, rm_b)


def sharded_fold_cap(member, E_pad: int, dp: int, mp: int) -> int:
    """``tile_cap`` for the pallas-sharded fold: the max op-row count over
    any (dp shard, mp slice)-local 8-member tile, bucketed to a power of
    two.  A global ``fold_cap`` does NOT bound this when ``E_pad/mp`` is
    not a multiple of 8 (shard-local tiles straddle global ones), so the
    count runs over the actual shard decomposition — dp row blocks are
    contiguous, mp slices are contiguous member ranges."""
    m = np.asarray(member, np.int64)
    if len(m) % dp:
        # padding AFTER computing the cap would shift the contiguous dp
        # block boundaries and silently undercount a shard's tiles
        raise ValueError(
            f"pad rows to a dp={dp} multiple BEFORE computing the cap "
            f"(got {len(m)})"
        )
    rows_per = max(len(m) // dp, 1)
    E_local = E_pad // mp
    T = max(-(-E_local // 8), 1)
    # one pass: composite (dp block, mp slice, local tile) key per row
    s = np.minimum(m // E_local, mp - 1)
    tile = np.minimum((m - s * E_local) // 8, T - 1)
    d = np.arange(len(m)) // rows_per
    key = (d * mp + s) * T + tile
    need = int(np.bincount(key).max(initial=0)) if len(m) else 0
    cap = 256
    while cap < need:
        cap *= 2
    return cap


def pad_rows_for_mesh(cols, dp: int, num_replicas: int):
    """Pad flattened op columns so the row count divides the dp axis."""
    n = len(cols.kind)
    target = ((n + dp - 1) // dp) * dp
    return K.pad_orset_rows(cols, target, num_replicas)


# ---- sharded streaming fold ------------------------------------------------


def stream_sharding(mesh: Mesh):
    """The (rows, clock, planes) shardings of the streaming fold: op-row
    chunks over ``dp``, the clock replicated, the (E, R) planes over
    ``mp`` on the member axis."""
    return (
        NamedSharding(mesh, P("dp")),
        NamedSharding(mesh, P()),
        NamedSharding(mesh, P("mp", None)),
    )


def sharded_stream_planes(mesh: Mesh, E_pad: int, R: int):
    """Zero-seeded accumulator planes for the sharded streaming fold,
    placed with :func:`stream_sharding` (clock replicated, planes
    mp-sharded).  ``E_pad`` must divide the mp axis."""
    _, clock_s, plane_s = stream_sharding(mesh)
    clock0 = np.zeros(max(R, 1), np.int32)
    add0 = np.zeros((E_pad, R), np.int32)
    rm0 = np.zeros((E_pad, R), np.int32)
    # counted HERE, at issue (OBS001) — callers must not count again
    trace.add("h2d_bytes", clock0.nbytes + add0.nbytes + rm0.nbytes)
    clock = jax.device_put(clock0, clock_s)
    add = jax.device_put(add0, plane_s)
    rm = jax.device_put(rm0, plane_s)
    return clock, add, rm


# One compiled step per (mesh, kernel route): the streaming session calls
# this per promotion/growth, and repeated compactions over the same mesh
# must reuse the compiled program (the jax_compiles invariant) — jit
# caches per function object, so the function object itself is cached.
# BOUNDED LRU, not a weak dict: the step closure must capture the mesh
# (shard_map needs it at trace time), so a weak key would be pinned by
# its own value; eviction caps what a mesh-churning process can retain.
_STREAM_STEP_CACHE: dict = {}
_STREAM_STEP_CACHE_MAX = 8


def sharded_stream_fold_step(
    mesh: Mesh, impl: str = "xla", tile_cap: int = 0, interpret: bool = False
):
    """A donated ``(clock, add, rm), chunk → (clock, add, rm)`` step for
    the sharded streaming fold: one jitted :func:`orset_fold_sharded`
    with ``retire_rm=False`` (partial-reduction mode — the session's
    finish retires once against the true merged clock, exactly like the
    single-chip stream).  The planes are donated, so device memory stays
    at one dp-sharded chunk + one mp-sharded set of planes however long
    the stream runs."""
    key = (mesh, impl, tile_cap, interpret)
    step = _STREAM_STEP_CACHE.pop(key, None)
    if step is None:

        @partial(jax.jit, donate_argnums=(0, 1, 2))
        def step(clock, add, rm, kind, member, actor, counter):
            return orset_fold_sharded(
                mesh, clock, add, rm, kind, member, actor, counter,
                impl=impl, tile_cap=tile_cap, interpret=interpret,
                retire_rm=False,
            )

    _STREAM_STEP_CACHE[key] = step  # re-insert = mark most-recently-used
    while len(_STREAM_STEP_CACHE) > _STREAM_STEP_CACHE_MAX:
        _STREAM_STEP_CACHE.pop(next(iter(_STREAM_STEP_CACHE)))
    return step


# ---- sharded multi-tenant mega-folds --------------------------------------
#
# The serving layer's tenant batch (ops/orset.orset_fold_tenants — the
# vmapped mega-fold) as a MESH axis: tenant lanes partition over ``dp``
# (each device folds its slice of the fleet, tenants never interact so
# no cross-dp collective exists at all) and each tenant's member planes
# partition over ``mp`` (rows replicate across mp and mask to the local
# member slice — the one cross-device value, the per-tenant clock, is a
# single ``pmax`` over mp).  One multi-chip pod then serves the
# many-small-tenants shape the solo ``orset_fold_sharded`` was never
# built for: a whole bucket of tenants per dispatch, every chip busy.


def _tenant_local_fold(clock0, add0, rm0, kind, member, actor, counter,
                       member_lo, E_local, R):
    """One tenant's fold against this device's member slice.

    ``add0``/``rm0`` arrive as the tenant's (E_local, R) mp-slice; the
    tenant's op rows arrive WHOLE (replicated over mp — the tenant lives
    on one dp shard), so rows outside the slice mask out of the scatter
    but still feed the clock, exactly as in ``ops.orset.orset_fold``
    where the clock is the column max over every live add."""
    pad = actor >= R
    local_member = member - member_lo
    in_slice = (local_member >= 0) & (local_member < E_local)
    is_add = (kind == KIND_ADD) & ~pad
    is_rm = (kind == KIND_RM) & ~pad & in_slice
    actor_ix = jnp.minimum(actor, R - 1)
    member_ix = jnp.clip(local_member, 0, E_local - 1)
    seg = member_ix * R + actor_ix
    add_new = jax.ops.segment_max(
        jnp.where(is_add & in_slice, counter, 0), seg,
        num_segments=E_local * R,
    )
    rm_new = jax.ops.segment_max(
        jnp.where(is_rm, counter, 0), seg, num_segments=E_local * R
    )
    add_new = jnp.maximum(add_new, 0).reshape(E_local, R)
    rm_new = jnp.maximum(rm_new, 0).reshape(E_local, R)
    # cell-level stale-add gate (≡ ops.orset.orset_fold's)
    add_new = jnp.where(add_new > clock0[None, :], add_new, 0)
    # the clock sees EVERY live add, in-slice or not (each mp shard has
    # all the tenant's rows) — but gated against clock0 exactly as the
    # solo kernel's post-gate column max is
    clock_new = jnp.maximum(
        jax.ops.segment_max(
            jnp.where(is_add, counter, 0), actor_ix, num_segments=R
        ),
        0,
    )
    clock_new = jnp.where(clock_new > clock0, clock_new, 0)
    # combine the per-shard clocks over mp: each shard computed the full
    # clock already (rows replicate over mp), so this pmax is a no-op at
    # mp=1 and pure agreement insurance otherwise
    clock_new = jax.lax.pmax(clock_new, "mp")
    clock = jnp.maximum(clock0, clock_new)
    add = jnp.maximum(add0, add_new)
    rm = jnp.maximum(rm0, rm_new)
    add = jnp.where(add > rm, add, 0)
    rm = jnp.where(rm > clock[None, :], rm, 0)
    return clock, add, rm


def orset_fold_tenants_sharded(
    mesh: Mesh,
    clock0,  # (T, R) int32 — per-tenant state clocks
    add0,  # (T, E, R) int32 — per-tenant state planes
    rm0,  # (T, E, R) int32
    kind,  # (T, N) int8 — per-tenant op rows
    member,  # (T, N) int32
    actor,  # (T, N) int32  (== num_replicas ⇒ padding row)
    counter,  # (T, N) int32
):
    """Mesh-sharded twin of ``ops.orset.orset_fold_tenants``.

    Layout: the tenant axis shards over ``dp`` (T must divide dp — the
    serve planner quantizes bucket slots to dp multiples), each tenant's
    member axis over ``mp`` (E must divide mp — the planner lifts E
    classes to mp multiples), op rows replicated across mp.  Per-tenant
    results are byte-identical to the vmapped single-device mega-fold —
    pinned by the differential tests on the virtual 8-device mesh."""
    dp = mesh.shape["dp"]
    mp = mesh.shape["mp"]
    T, E, R = add0.shape
    if T % dp or E % mp:
        raise ValueError(
            f"pad first: tenants {T} % dp {dp} or members {E} % mp {mp}"
        )
    E_local = E // mp

    def body(c0, a0, r0, k, m, ac, ct, lo):
        def one(c, a, r, kk, mm, aa, cc):
            return _tenant_local_fold(
                c, a, r, kk, mm, aa, cc, lo[0], E_local, R
            )

        return jax.vmap(one)(c0, a0, r0, k, m, ac, ct)

    member_lo = np.arange(mp, dtype=np.int32) * E_local
    fold = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(
            P("dp", None),
            P("dp", "mp", None),
            P("dp", "mp", None),
            P("dp", None),
            P("dp", None),
            P("dp", None),
            P("dp", None),
            P("mp"),
        ),
        out_specs=(P("dp", None), P("dp", "mp", None), P("dp", "mp", None)),
        check_vma=False,
    )
    return fold(clock0, add0, rm0, kind, member, actor, counter, member_lo)


def gcounter_fold_tenants_sharded(
    mesh: Mesh,
    clock0,  # (T, R) int32 — per-tenant clocks
    actor,  # (T, N) int32  (== num_replicas ⇒ padding row)
    counter,  # (T, N) int32
):
    """Mesh-sharded twin of ``ops.counters.gcounter_fold_tenants``:
    tenant lanes over ``dp``, the tiny (R,) planes shard-local (they
    replicate over mp — counter tenants are plane-light by definition).
    T must divide dp."""
    from ..ops.counters import gcounter_fold

    dp = mesh.shape["dp"]
    T, R = clock0.shape
    if T % dp:
        raise ValueError(f"pad first: tenants {T} % dp {dp}")

    def body(c0, a, ct):
        def one(c, aa, cc):
            clock, _value = gcounter_fold(c, aa, cc, num_replicas=R)
            return clock

        return jax.vmap(one)(c0, a, ct)

    fold = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P("dp", None), P("dp", None), P("dp", None)),
        out_specs=P("dp", None),
        check_vma=False,
    )
    return fold(clock0, actor, counter)


def tenant_plane_diff_sharded(
    mesh: Mesh,
    clock_b,  # (T, R) int32 — per-tenant BASE clocks (last sealed)
    add_b,  # (T, E, R) int32 — per-tenant BASE planes
    rm_b,  # (T, E, R) int32
    clock_n,  # (T, R) int32 — per-tenant post-fold clocks
    add_n,  # (T, E, R) int32 — per-tenant post-fold planes
    rm_n,  # (T, E, R) int32
):
    """Mesh-sharded twin of ``ops.orset.orset_plane_diff_tenants`` for
    the device-cut delta seal (docs/delta.md): tenant lanes over ``dp``,
    member slices over ``mp`` — the SAME layout the fold twin just left
    the planes in, so the diff dispatch reads them where they already
    live.  The per-cell code is embarrassingly shard-local (every bit
    condition reads one cell plus the replicated clock rows); only the
    per-tenant count crosses shards, as one ``psum`` over mp.  Same
    bucket-class law as the fold: shapes are planner-quantized, so churn
    never recompiles."""
    dp = mesh.shape["dp"]
    mp = mesh.shape["mp"]
    T, E, R = add_n.shape
    if T % dp or E % mp:
        raise ValueError(
            f"pad first: tenants {T} % dp {dp} or members {E} % mp {mp}"
        )

    def body(cb, ab, rb, cn, an, rn):
        code, count = jax.vmap(K.orset_plane_diff)(cb, ab, rb, cn, an, rn)
        return code, jax.lax.psum(count, "mp")

    diff = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(
            P("dp", None),
            P("dp", "mp", None),
            P("dp", "mp", None),
            P("dp", None),
            P("dp", "mp", None),
            P("dp", "mp", None),
        ),
        out_specs=(P("dp", "mp", None), P("dp")),
        check_vma=False,
    )
    return diff(clock_b, add_b, rm_b, clock_n, add_n, rm_n)


# One compiled step pair per mesh, same bounded-LRU discipline (and the
# same pinning rationale) as _STREAM_STEP_CACHE below: the serve layer
# calls these per bucket, and shape variation is already quantized by
# the planner, so jit's own shape cache stays bounded per step.
_TENANT_STEP_CACHE: dict = {}
_TENANT_STEP_CACHE_MAX = 8


def tenant_fold_steps(mesh: Mesh):
    """The jitted ``(orset_step, gcounter_step)`` pair for one mesh —
    shapes are the only statics (derived inside the trace), so a fixed
    bucket-class set compiles a fixed program set."""
    steps = _TENANT_STEP_CACHE.pop(mesh, None)
    if steps is None:

        @jax.jit
        def orset_step(clock0, add0, rm0, kind, member, actor, counter):
            return orset_fold_tenants_sharded(
                mesh, clock0, add0, rm0, kind, member, actor, counter
            )

        @jax.jit
        def gcounter_step(clock0, actor, counter):
            return gcounter_fold_tenants_sharded(mesh, clock0, actor, counter)

        steps = (orset_step, gcounter_step)
    _TENANT_STEP_CACHE[mesh] = steps  # re-insert = mark most-recently-used
    while len(_TENANT_STEP_CACHE) > _TENANT_STEP_CACHE_MAX:
        _TENANT_STEP_CACHE.pop(next(iter(_TENANT_STEP_CACHE)))
    return steps


def tenant_diff_step(mesh: Mesh):
    """The jitted plane-diff step for one mesh — same bounded-LRU cache
    and bucket-class pinning as :func:`tenant_fold_steps` (the two share
    the dict; diff entries key on ``(mesh, "diff")``)."""
    key = (mesh, "diff")
    step = _TENANT_STEP_CACHE.pop(key, None)
    if step is None:

        @jax.jit
        def diff_step(clock_b, add_b, rm_b, clock_n, add_n, rm_n):
            return tenant_plane_diff_sharded(
                mesh, clock_b, add_b, rm_b, clock_n, add_n, rm_n
            )

        step = diff_step
    _TENANT_STEP_CACHE[key] = step
    while len(_TENANT_STEP_CACHE) > _TENANT_STEP_CACHE_MAX:
        _TENANT_STEP_CACHE.pop(next(iter(_TENANT_STEP_CACHE)))
    return step


# ---- counters -------------------------------------------------------------


def pncounter_fold_sharded(mesh: Mesh, p0, n0, sign, actor, counter):
    """PN-Counter fold with op rows sharded over ``dp`` (pad row count to
    a dp multiple with ``actor == R`` sentinels first).  The (R,) planes
    are replicated — they are tiny next to the batch — and the cross-
    device combine is one ``pmax``, the same shape as the ORSet fold's."""
    R = len(p0)
    dp = mesh.shape["dp"]
    if len(sign) % dp:
        raise ValueError(f"pad first: rows {len(sign)} % dp {dp}")

    def body(p0, n0, sign, actor, counter):
        p, n, _ = K.pncounter_fold(
            jnp.zeros_like(p0), jnp.zeros_like(n0), sign, actor, counter,
            num_replicas=R,
        )
        p = jnp.maximum(p0, jax.lax.pmax(p, "dp"))
        n = jnp.maximum(n0, jax.lax.pmax(n, "dp"))
        return p, n, sum_wide(p) - sum_wide(n)

    fold = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(), P(), P("dp"), P("dp"), P("dp")),
        out_specs=(P(), P(), P()),
        check_vma=False,
    )
    return fold(p0, n0, sign, actor, counter)


def gcounter_fold_sharded(mesh: Mesh, clock0, actor, counter):
    """G-Counter fold sharded over ``dp`` (see pncounter_fold_sharded)."""
    sign = np.zeros(len(actor), np.int8)
    p, _, total = pncounter_fold_sharded(
        mesh, clock0, jnp.zeros_like(clock0), sign, actor, counter
    )
    return p, total  # n-plane is zero, so the pn value IS the sum


# ---- LWW ------------------------------------------------------------------


def lww_fold_sharded(mesh: Mesh, key, ts_hi, ts_lo, actor, value, *, num_keys: int):
    """LWW-map fold with write rows sharded over ``dp``.

    Each device selects its shard's per-key winners (``lww_fold``), then
    the winner tables combine across ``dp`` with the same lexicographic
    order evaluated **elementwise** on an ``all_gather`` of the (K,)-sized
    tables (``lww_table_merge``) — dense per-key state moves once, rows
    never do, and the cross-shard combine never touches the scatter path.
    Row count must divide dp (pad with ``key == num_keys`` sentinel
    rows)."""
    Kk = num_keys
    dp = mesh.shape["dp"]
    if len(key) % dp:
        raise ValueError(f"pad first: rows {len(key)} % dp {dp}")

    def body(key, ts_hi, ts_lo, actor, value):
        local = K.lww_fold(key, ts_hi, ts_lo, actor, value, num_keys=Kk)
        # gather every shard's winner table ((dp, K) per column) and
        # lex-reduce across the dp axis — pure VPU work, no re-scatter
        g = tuple(jax.lax.all_gather(x, "dp") for x in local)
        acc = tuple(x[0] for x in g)
        for i in range(1, dp):
            acc = K.lww_table_merge(tuple(x[i] for x in g), acc)
        return acc

    fold = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P("dp"),) * 5,
        out_specs=(P(),) * 5,
        check_vma=False,
    )
    return fold(key, ts_hi, ts_lo, actor, value)


# ---- CrdtMap --------------------------------------------------------------


def crdtmap_scatter_sharded(
    mesh: Mesh,
    clock0, births0, cclk0, cadd0, crm0, key_of_pair,
    b_rows, k_rows, a_rows, r_rows,
    *, num_groups: int,
):
    """Sharded CrdtMap scatter phase: the four row families shard over
    ``dp`` (each padded to a dp multiple with ``actor == R`` sentinels);
    the key/pair planes are replicated — map workloads are row-heavy and
    plane-light (NK·R and NP·R are bounded by the touched vocabulary,
    not the batch), the opposite regime from the ORSet fold's mp axis.
    Each scatter combines across dp with one ``pmax`` (``pmin`` for the
    remove-group gate) inside ops/map_device.crdtmap_scatter_phase."""
    from ..ops.map_device import crdtmap_scatter_phase

    dp = mesh.shape["dp"]
    for fam in (b_rows, k_rows, a_rows, r_rows):
        if len(fam[0]) % dp:
            raise ValueError(f"pad row families to dp={dp} multiples first")
    NK, R = births0.shape
    NP = cadd0.shape[0]

    def body(c0, b0, cc0, ca0, cr0, kop, *rows):
        b = rows[0:3]
        k = rows[3:7]
        a = rows[7:11]
        r = rows[11:16]
        return crdtmap_scatter_phase(
            c0, b0, cc0, ca0, cr0, kop, *b, *k, *a, *r,
            num_keys=NK, num_pairs=NP, num_replicas=R,
            num_groups=num_groups, axis_name="dp",
        )

    n_rows = 3 + 4 + 4 + 5
    fold = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(), P(), P(), P(), P(), P()) + (P("dp"),) * n_rows,
        out_specs=(P(),) * 6,
        check_vma=False,
    )
    return fold(
        clock0, births0, cclk0, cadd0, crm0, key_of_pair,
        *b_rows, *k_rows, *a_rows, *r_rows,
    )


# ---- MVReg ----------------------------------------------------------------


def mvreg_keep_sharded(mesh: Mesh, clocks, valid):
    """Sharded MVReg dominance filter: candidate rows shard over ``dp``
    (pad V to a dp multiple with invalid rows); each device all_gathers
    the full candidate set (V·R is small — clocks, not payloads) and
    filters its slice, so the O(V²R) compare matrix is split V/dp ways.
    Same contract as ops/mvreg.mvreg_dominance_keep."""
    dp = mesh.shape["dp"]
    V, R = clocks.shape
    if V % dp:
        raise ValueError(f"pad candidates {V} to a dp={dp} multiple first")

    def body(c_slice, v_slice):
        full_c = jax.lax.all_gather(c_slice, "dp", tiled=True)  # (V, R)
        full_v = jax.lax.all_gather(v_slice, "dp", tiled=True)  # (V,)
        ge = jnp.all(full_c[:, None, :] >= c_slice[None, :, :], axis=-1)
        gt = jnp.any(full_c[:, None, :] > c_slice[None, :, :], axis=-1)
        dominated = jnp.any((ge & gt) & full_v[:, None], axis=0)
        return v_slice & ~dominated

    keep = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P("dp", None), P("dp")),
        out_specs=P("dp"),
        check_vma=False,
    )
    return keep(clocks, valid)
