"""ORSet fold and merge as jitted tensor programs — the north-star kernels.

These replace the reference's per-op/per-state host loops (HOT LOOP #2
``state.apply(op)`` at crdt-enc/src/lib.rs:533-539 and HOT LOOP #1
``state.merge`` at lib.rs:458-466) with batched XLA reductions:

* **fold**: a whole op batch (adds as dots, removes flattened to per-replica
  horizon rows) collapses into the state planes via two ``segment_max``
  scatters and elementwise masks.  Order-independence of the dense formulas
  (max over monotone per-replica counters) is exactly why this is legal — the
  property tests in tests/test_crdt_laws.py pin the host semantics and
  tests/test_ops_kernels.py pins host≡TPU byte-equality.
* **merge**: the Orswot clock-filter merge as pure elementwise arithmetic
  over ``(E, R)`` planes.

All shapes are static under jit; ragged op batches are padded with no-op rows
(``actor = R`` sentinel column, masked out) so recompilation is bounded by
shape buckets, not batch contents.  Counters are int32 and always ≥ 1 for
real dots, so 0 is the universal "absent" value and empty ``segment_max``
segments (dtype-min) clamp back to 0.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..utils import trace
from .columnar import KIND_ADD, KIND_RM


@partial(
    jax.jit,
    static_argnames=(
        "num_members", "num_replicas", "sort_segments", "impl",
        "small_counters", "retire_rm",
    ),
)
def orset_fold(
    clock0: jax.Array,  # (R,) int32
    add0: jax.Array,  # (E, R) int32
    rm0: jax.Array,  # (E, R) int32
    kind: jax.Array,  # (N,) int8
    member: jax.Array,  # (N,) int32
    actor: jax.Array,  # (N,) int32  (== num_replicas ⇒ padding row)
    counter: jax.Array,  # (N,) int32
    *,
    num_members: int,
    num_replicas: int,
    sort_segments: bool = False,
    impl: str = "fused",
    small_counters: bool = False,
    retire_rm: bool = True,
):
    """Fold an op batch into normalized ORSet planes.

    ``retire_rm=False`` keeps remove horizons un-retired (no
    ``rm > clock`` zeroing): required when the planes are a PARTIAL
    reduction to be combined with a pre-existing state later — a horizon
    retired against the batch-local clock would lose its kill-effect on
    state entries it never met (the streaming session's combine retires
    once, against the true merged clock).

    Returns ``(clock, add, rm)`` in canonical/normalized form: entries
    zeroed where ``add ≤ rm``, horizons zeroed where ``rm ≤ clock``.

    ``impl`` selects the scatter strategy (hardware-benchmarked on v5e,
    see bench.py):

    * ``"fused"`` (default) — ONE combined scatter-max: removes land at a
      ``E*R`` offset in a ``(2, E, R)`` target, so XLA initializes and
      sweeps the 2·E·R scatter target once instead of twice
      (31ms → 23ms on the 1M-op / 10k-replica north-star config).
      With ``small_counters=True`` (caller asserts all counters
      < 2**15) the scatter runs on int16 values, halving the scatter
      target's HBM footprint (→ 21ms).
    * ``"two_pass"`` — the original pair of ``segment_max`` calls;
      ``sort_segments=True`` additionally sorts the batch by segment id
      and tells XLA the indices are sorted (workload-dependent; loses on
      the north-star config).

    ``small_counters`` only affects ``"fused"`` and ``sort_segments``
    only affects ``"two_pass"``; a flag passed to the other impl raises.
    """
    if small_counters and impl != "fused":
        raise ValueError("small_counters requires impl='fused'")
    if sort_segments and impl != "two_pass":
        raise ValueError("sort_segments requires impl='two_pass'")
    E, R = num_members, num_replicas
    pad = actor >= R  # sentinel rows from bucket padding
    is_add = (kind == KIND_ADD) & ~pad
    is_rm = (kind == KIND_RM) & ~pad
    actor_ix = jnp.minimum(actor, R - 1)

    seg = member * R + actor_ix
    if impl == "fused":
        # Removes scatter into the second (E, R) plane of one flat target.
        seg2 = jnp.where(is_rm, seg + E * R, seg)
        vals = jnp.where(is_add | is_rm, counter, 0)
        if small_counters:
            z = jnp.zeros((2 * E * R,), jnp.int16)
            both = z.at[seg2].max(vals.astype(jnp.int16), mode="drop")
            both = both.astype(jnp.int32).reshape(2, E, R)
        else:
            z = jnp.zeros((2 * E * R,), jnp.int32)
            both = z.at[seg2].max(vals, mode="drop").reshape(2, E, R)
        add_new, rm_new = both[0], both[1]
    elif impl == "two_pass":
        vals_add = jnp.where(is_add, counter, 0)
        vals_rm = jnp.where(is_rm, counter, 0)
        if sort_segments:
            order = jnp.argsort(seg)
            seg_s = seg[order]
            add_new = jax.ops.segment_max(
                vals_add[order], seg_s, num_segments=E * R,
                indices_are_sorted=True,
            )
            rm_new = jax.ops.segment_max(
                vals_rm[order], seg_s, num_segments=E * R,
                indices_are_sorted=True,
            )
        else:
            add_new = jax.ops.segment_max(vals_add, seg, num_segments=E * R)
            rm_new = jax.ops.segment_max(vals_rm, seg, num_segments=E * R)
        # clamp empty segments (dtype-min fill) back to "absent"
        add_new = jnp.maximum(add_new, 0).reshape(E, R)
        rm_new = jnp.maximum(rm_new, 0).reshape(E, R)
    else:
        raise ValueError(f"unknown fold impl {impl!r}; use 'fused' or 'two_pass'")

    # Stale-add replay gate, lifted from row level to CELL level: dots
    # are monotone per actor, so a cell whose scattered max is ≤ the
    # incoming clock held ONLY stale adds — zeroing it equals excluding
    # each stale row from the scatter (the round-2 kernels gated per row,
    # which cost a 1M-element clock gather per fold; measured ~6ms of the
    # old 19.6ms marginal).
    add_new = jnp.where(add_new > clock0[None, :], add_new, 0)

    # Adds advance the global clock; removes never do.  The batch's max
    # live-add counter per actor is already in add_new — a dense column
    # reduction instead of a third scatter.
    clock = jnp.maximum(clock0, jnp.max(add_new, axis=0, initial=0))

    add = jnp.maximum(add0, add_new)
    rm = jnp.maximum(rm0, rm_new)

    # Normalize: a horizon kills every dot it covers; a horizon the clock
    # caught up with has fully applied.
    add = jnp.where(add > rm, add, 0)
    if retire_rm:
        rm = jnp.where(rm > clock[None, :], rm, 0)
    return clock, add, rm


@partial(jax.jit, static_argnames=("num_members", "num_replicas"))
def orset_fold_coo(
    clock0: jax.Array,  # (R,) int32
    kind: jax.Array,  # (N,) int8
    member: jax.Array,  # (N,) int32
    actor: jax.Array,  # (N,) int32  (== num_replicas ⇒ padding row)
    counter: jax.Array,  # (N,) int32
    *,
    num_members: int,
    num_replicas: int,
):
    """Sparse fold: aggregate an op batch WITHOUT materializing the dense
    ``(E, R)`` planes.

    The dense ``orset_fold`` initializes and sweeps a ``2·E·R`` scatter
    target per call — at the 100k-replica streaming scale that is ~800MB
    of HBM traffic for a few hundred thousand updates (measured 46s/fold,
    N ≪ E·R).  Here the batch is sorted by segment key and per-segment
    maxima fall out of run boundaries: O(N log N) work, independent of
    E·R.  Returns ``(clock, seg_keys, seg_max, is_seg_max)`` where rows
    with ``is_seg_max`` hold each touched segment's aggregated value
    (key < E·R: live-add dot max; key ≥ E·R: remove-horizon max — same
    aggregation the dense kernel's two scatter planes perform).  Feed to
    ``ops.columnar.orset_apply_coo`` to fold into sparse host state with
    the dense kernel's exact normalization semantics.

    Requires ``2·E·R < 2^31`` (int32 keys; same bound the dense kernel's
    flat scatter target imposes).
    """
    E, R = num_members, num_replicas
    if 2 * E * R >= 2 ** 31:
        raise ValueError("segment key space exceeds int32; shard members first")
    pad = actor >= R
    actor_ix = jnp.minimum(actor, R - 1)
    is_add = (kind == KIND_ADD) & ~pad
    is_rm = (kind == KIND_RM) & ~pad
    seen = counter <= clock0[actor_ix]
    live_add = is_add & ~seen
    valid = live_add | is_rm
    seg = member * R + actor_ix
    key = jnp.where(valid, jnp.where(is_rm, seg + E * R, seg), 2 * E * R)
    skey, scounter = jax.lax.sort((key, counter), num_keys=2)
    # lexicographic sort ⇒ the last row of every key-run is that segment's max
    nxt = jnp.concatenate([skey[1:], jnp.full((1,), -1, skey.dtype)])
    is_seg_max = (skey != nxt) & (skey < 2 * E * R)
    clock_new = jax.ops.segment_max(
        jnp.where(live_add, counter, 0), actor_ix, num_segments=R
    )
    clock = jnp.maximum(clock0, jnp.maximum(clock_new, 0))
    return clock, skey, scounter, is_seg_max


@jax.jit
def orset_gather_cells(
    add: jax.Array,  # (E, R) int32 — post-fold planes, resident
    rm: jax.Array,
    member: jax.Array,  # (N,) int32 — the fold's own (padded) row columns
    actor: jax.Array,  # (N,) int32  (>= R ⇒ padding row)
):
    """The add and remove words of the cell each op row names: what a
    fold over resident planes brings home instead of the planes.  A fold
    changes the planes only at cells its rows name (and retires horizons
    the advanced clock caught up with, which the host can do from the
    clock alone: ``ops.columnar.orset_cells_to_state``), so these two
    ``(N,)`` arrays and the clock are the whole difference between the
    host state before and after.  One value per ROW, duplicates and all:
    the rows are already on the device and already bucket-padded, so the
    compile classes are the fold's own and nothing more is uploaded.
    Padding rows clamp to a real cell; the caller reads only the first
    real-row-count values."""
    a = jnp.minimum(actor, add.shape[1] - 1)
    return add[member, a], rm[member, a]


@jax.jit
def orset_apply_batch_planes(
    clock0: jax.Array,  # (R,) int32 — CURRENT state clock
    add0: jax.Array,  # (E, R) int32 — current state planes
    rm0: jax.Array,
    add_b: jax.Array,  # (E, R) int32 — batch-reduced planes (leaf fold)
    rm_b: jax.Array,
):
    """Apply pre-reduced op-batch planes to the state planes: the tail of
    :func:`orset_fold` after the scatter phase, with the stale-add mask
    lifted to cell level — ``add_b`` cells not beyond the CURRENT clock
    are replays (per-actor dot counters are monotone, so a stale cell max
    means every dot in the cell was stale) and drop, exactly as the
    kernel's row-level ``seen`` mask would have dropped them.  Evaluating
    the mask against the clock *now* (not at session start) keeps the
    combine correct when concurrent applies or state merges advanced the
    state while chunks were being reduced.  NOT the CvRDT state merge
    (``orset_merge``) — batch rows are ops, so no clock-filter survivor
    rule applies to them."""
    add_b = jnp.where(add_b > clock0[None, :], add_b, 0)
    clock = jnp.maximum(clock0, jnp.max(add_b, axis=0, initial=0))
    add = jnp.maximum(add0, add_b)
    rm = jnp.maximum(rm0, rm_b)
    add = jnp.where(add > rm, add, 0)
    rm = jnp.where(rm > clock[None, :], rm, 0)
    return clock, add, rm


@partial(jax.jit, static_argnames=("num_members", "num_replicas"))
def orset_fold_tenants(
    clock0: jax.Array,  # (T, R) int32 — per-tenant state clocks
    add0: jax.Array,  # (T, E, R) int32 — per-tenant state planes
    rm0: jax.Array,  # (T, E, R) int32
    kind: jax.Array,  # (T, N) int8 — per-tenant op rows
    member: jax.Array,  # (T, N) int32
    actor: jax.Array,  # (T, N) int32  (== num_replicas ⇒ padding row)
    counter: jax.Array,  # (T, N) int32
    *,
    num_members: int,
    num_replicas: int,
):
    """The multi-tenant mega-fold: :func:`orset_fold` with the tenant
    batch as one more fold axis (``vmap`` over the leading dim), so a
    whole bucket of small tenants collapses in ONE device dispatch
    instead of T dispatch+compile-amortization rounds (ROADMAP item 1,
    the serving shape — millions of *small* remotes, not one huge one).

    Tenants never interact: every scatter segment id is tenant-local by
    construction of the vmap, so the result planes are exactly what T
    independent ``orset_fold`` calls would produce — the serving layer's
    byte-identity differential (tests/test_serve.py) pins it against the
    solo ``Core.compact`` path end to end.  Shapes are quantized by the
    serving layer's bucket planner (crdt_enc_tpu/serve/bucketing.py), so
    compilation count is bounded by size classes, not tenant mixes.
    Padding rows use the same ``actor == num_replicas`` sentinel as every
    other fold; dummy tenant slots are all-sentinel rows over zero
    planes."""

    def one(c, a, r, k, m, ac, ct):
        return orset_fold(
            c, a, r, k, m, ac, ct,
            num_members=num_members, num_replicas=num_replicas,
        )

    return jax.vmap(one)(clock0, add0, rm0, kind, member, actor, counter)


# Diff-row code bits (orset_plane_diff): which wire-form map a diff cell
# feeds — the Orswot window delta's ``e`` / ``x`` / ``t`` keys
# (delta/codec.orset_delta_diff).  A cell can set the add and horizon
# bits together in principle (they read different planes); add and
# removed are mutually exclusive by construction (``add_n > clock_b``
# needs ``add_n > 0``, removed needs ``add_n == 0``).
DIFF_ADD = 1  # surviving window dot: add_n > base clock (new adds AND
#               confirmations that keep a window dot alive)
DIFF_REMOVED = 2  # dot-exact removal: base slot absent from new
DIFF_HORIZON = 4  # remove horizon raised past the base's


@jax.jit
def orset_plane_diff(clock_b, add_b, rm_b, clock_n, add_n, rm_n):
    """Device cut of the Orswot window delta (docs/delta.md): compare a
    sealed BASE state's planes against the post-fold NEW planes and mark
    every cell the host dict-walk ``delta.codec.orset_delta_diff`` would
    emit.  Returns ``(code, count)`` — an int8 code plane (DIFF_* bits)
    and the number of nonzero cells — so the caller can size the
    O(diff-rows) gather (:func:`orset_plane_diff_rows`) and D2H only the
    rows that feed the wire form, never the full planes.

    Both plane sets must be canonical (the fold/merge kernels' output
    law: entries killed where add ≤ rm, rm zeroed where rm ≤ clock) and
    indexed by ONE shared vocabulary; zero-padded cells are absent in
    both states and can never mark.  The bit conditions are exactly the
    host walk's comprehensions:

    * add: ``add_n > clock_b[r]`` — slots in ``new.entries`` whose dot
      lies past the base clock (``c > bc.get(r)``), including unchanged
      survivors (the confirmations);
    * removed: ``add_b > 0 and add_n == 0`` — base slots with no slot in
      ``new`` (``not new_slots.get(r, 0)``), dot-exact with the base
      counter as the value;
    * horizon: ``rm_n > rm_b and rm_n > clock_n[r]`` — deferred removes
      raised past the base's (``h > base_hs.get(r, 0)``) and still ahead
      of the new clock (canonical planes imply the second clause; it is
      kept so the kernel never depends on the caller normalizing).
    """
    add_bit = (add_n > clock_b[None, :]).astype(jnp.int8) * DIFF_ADD
    rm_bit = (
        (add_b > 0) & (add_n == 0)
    ).astype(jnp.int8) * DIFF_REMOVED
    hz_bit = (
        (rm_n > rm_b) & (rm_n > clock_n[None, :])
    ).astype(jnp.int8) * DIFF_HORIZON
    code = add_bit | rm_bit | hz_bit
    return code, jnp.sum(code != 0, dtype=jnp.int32)


@jax.jit
def orset_plane_diff_tenants(clock_b, add_b, rm_b, clock_n, add_n, rm_n):
    """The serving layer's batched twin of :func:`orset_plane_diff`:
    one dispatch marks a whole bucket's diff cells (``vmap`` over the
    tenant axis, the mega-fold discipline), and the per-tenant counts
    come home in one (T,) D2H instead of T scalar syncs."""
    return jax.vmap(orset_plane_diff)(
        clock_b, add_b, rm_b, clock_n, add_n, rm_n
    )


@partial(jax.jit, static_argnames=("size",))
def orset_plane_diff_rows(code, add_b, add_n, rm_n, *, size):
    """Gather ONE tenant's diff rows from its code plane: the flat cell
    indices (row-major, so ``divmod(idx, R)`` recovers ``(e, r)``) plus
    the code and the three counter values the wire builder needs
    (``delta.codec.orset_delta_from_rows``).  ``size`` is the static
    row capacity — the caller quantizes the phase-1 count through the
    repo's ``_bucket`` law, so compile classes stay bounded by
    log(E·R), not by diff contents.  Slots past the real count carry
    ``idx == code.size`` (out of range) and zero values."""
    flat = code.ravel()
    n = flat.shape[0]
    (idx,) = jnp.nonzero(flat, size=size, fill_value=n)
    safe = jnp.minimum(idx, n - 1)
    live = idx < n

    def take(plane):
        return jnp.where(live, plane.ravel()[safe], 0)

    return (
        idx,
        take(flat),
        take(add_b),
        take(add_n),
        take(rm_n),
    )


@partial(jax.jit, static_argnames=("size",))
def orset_plane_diff_rows_tenants(code, add_b, add_n, rm_n, *, size):
    """The serving layer's batched twin of :func:`orset_plane_diff_rows`:
    one dispatch gathers a whole bucket's diff rows (``vmap`` of the same
    gather over the tenant axis) and the five ``(T, size)`` arrays come
    home in one pull instead of five per tenant.  ``size`` is one static
    capacity for the bucket — the caller quantizes the LARGEST phase-1
    count it will read through ``_bucket`` — so a slot with fewer diffs
    is padded past its count exactly as the solo gather pads (``idx ==
    cells``, zero values), and a slot with more (one the caller never
    reads) is truncated."""
    return jax.vmap(partial(orset_plane_diff_rows, size=size))(
        code, add_b, add_n, rm_n
    )


# Slots per stack / unstack program.  One program over a whole 1,024-slot
# bucket takes 3,072 operands (or results): the chip's compiler needs 20 s
# and 12 s for the pair and leaves 40 MB of code on the device per bucket
# class; at 128 slots the pair compiles in 3 s to 5 MB, and a bucket of
# the largest class costs sixteen more launches (PERF.md, PR 25).
TENANT_CHUNK = 128


@jax.jit
def _stack_chunk(clock_rows, add_rows, rm_rows, live):
    keep = jnp.arange(len(clock_rows)) < live

    def stack(rows):
        s = jnp.stack(rows)
        return jnp.where(jnp.expand_dims(keep, range(1, s.ndim)), s, 0)

    return stack(clock_rows), stack(add_rows), stack(rm_rows)


@jax.jit
def _concat_chunks(clock, add, rm):
    return jnp.concatenate(clock), jnp.concatenate(add), jnp.concatenate(rm)


def orset_stack_tenants(clock_rows, add_rows, rm_rows, slots: int):
    """A bucket's pre-fold plane stacks from its tenants' rows: three
    lists of equally shaped rows (device arrays and host arrays mixed
    freely), one entry a tenant, become the ``(slots, R)`` /
    ``(slots, E, R)`` stacks the mega-fold consumes.  One program per
    ``TENANT_CHUNK`` slots and one concatenate, never one per row; the
    dummy slots past the tenants are zeroed inside the program (its
    lists fill up with a row already in hand, and the count of live
    rows is a traced scalar), so the compile class is the bucket class
    alone, whatever the number of tenants."""
    live = len(clock_rows)
    parts = []
    for lo in range(0, slots, TENANT_CHUNK):
        n = min(TENANT_CHUNK, slots - lo)

        def fill(rows):
            return (rows[lo : lo + n] + rows[:1] * n)[:n]

        parts.append(
            _stack_chunk(
                fill(clock_rows), fill(add_rows), fill(rm_rows),
                np.int32(min(max(live - lo, 0), n)),
            )
        )
    if len(parts) == 1:
        return parts[0]
    return _concat_chunks(*zip(*parts))


@partial(jax.jit, static_argnames=("n",))
def _unstack_chunk(clock, add, rm, lo, *, n):
    def rows(x):
        return jnp.unstack(jax.lax.dynamic_slice_in_dim(x, lo, n))

    return rows(clock), rows(add), rows(rm)


def orset_unstack_tenants(clock, add, rm):
    """The inverse of :func:`orset_stack_tenants` for the post-fold
    stacks: three lists of per-slot arrays, ``TENANT_CHUNK`` slots a
    program, each array an owned buffer (a warm-tier entry pins its
    tenant's planes, never the bucket's stack)."""
    slots = clock.shape[0]
    out: tuple[list, list, list] = ([], [], [])
    for lo in range(0, slots, TENANT_CHUNK):
        n = min(TENANT_CHUNK, slots - lo)
        part = _unstack_chunk(clock, add, rm, np.int32(lo), n=n)
        for rows, more in zip(out, part):
            rows.extend(more)
    return out


def merge_rule(clock_a, add_a, rm_a, clock_b, add_b, rm_b, clock_merged):
    """The clock-filter merge on raw arrays (clocks already row-broadcast
    ready, ``clock_merged = max(clock_a, clock_b)`` supplied by the
    caller).  Single source of truth for the Orswot merge semantics —
    used by ``orset_merge`` AND the Pallas streaming kernel
    (ops/pallas_merge.py), which must never diverge."""
    same = add_a == add_b
    surv_a = jnp.where(same | (add_a > clock_b), add_a, 0)
    surv_b = jnp.where(same | (add_b > clock_a), add_b, 0)
    add = jnp.maximum(surv_a, surv_b)
    rm = jnp.maximum(rm_a, rm_b)
    add = jnp.where(add > rm, add, 0)
    rm = jnp.where(rm > clock_merged, rm, 0)
    return add, rm


@jax.jit
def orset_merge(
    clock_a: jax.Array,
    add_a: jax.Array,
    rm_a: jax.Array,
    clock_b: jax.Array,
    add_b: jax.Array,
    rm_b: jax.Array,
):
    """CvRDT merge of two dense ORSet states over the same (members,
    replicas) vocabularies.  Pure elementwise — the tombstone-free
    clock-filter rule (see crdt_enc_tpu/models/orset.py module docs)."""
    clock = jnp.maximum(clock_a, clock_b)
    add, rm = merge_rule(
        clock_a[None, :], add_a, rm_a, clock_b[None, :], add_b, rm_b,
        clock[None, :],
    )
    return clock, add, rm


@jax.jit
def _merge_halves(c1, a1, r1, c2, a2, r2):
    return jax.vmap(orset_merge)(c1, a1, r1, c2, a2, r2)


def orset_merge_many(
    clocks: jax.Array, adds: jax.Array, rms: jax.Array,
    impl: str | None = None, interpret: bool = False,
):
    """Merge a stacked batch of S states ``(S,R) / (S,E,R)`` into one.

    ``impl``: ``"tree"`` = ⌈log2 S⌉ rounds of the pairwise merge (XLA);
    ``"pallas"`` = single-HBM-pass streaming kernel (ops/pallas_merge.py);
    None = pallas on TPU for batches worth streaming, tree elsewhere.
    ``interpret`` runs the Pallas kernel in the interpreter — an explicit
    test-only choice, never derived from the backend.
    Merge associativity (tests/test_crdt_laws.py) makes any order legal.
    """
    # host-resident stacks upload here; device inputs re-wrap for free
    trace.add("h2d_bytes", sum(
        x.nbytes for x in (clocks, adds, rms) if isinstance(x, np.ndarray)
    ))
    c, a, r = jnp.asarray(clocks), jnp.asarray(adds), jnp.asarray(rms)
    if impl is None:
        on_tpu = jax.default_backend() == "tpu"
        impl = "pallas" if on_tpu and c.shape[0] >= 4 else "tree"
    if impl == "pallas":
        from .pallas_merge import orset_merge_many_pallas

        trace.add("pallas_routed", 1)
        return orset_merge_many_pallas(c, a, r, interpret=interpret)
    if impl != "tree":
        raise ValueError(f"unknown merge impl {impl!r}; use 'tree' or 'pallas'")
    while c.shape[0] > 1:
        s = c.shape[0]
        half = s // 2
        cm, am, rmm = _merge_halves(
            c[:half], a[:half], r[:half], c[half : 2 * half], a[half : 2 * half], r[half : 2 * half]
        )
        if s % 2:
            cm = jnp.concatenate([cm, c[-1:]])
            am = jnp.concatenate([am, a[-1:]])
            rmm = jnp.concatenate([rmm, r[-1:]])
        c, a, r = cm, am, rmm
    return c[0], a[0], r[0]
