"""Columnar (structure-of-arrays) encodings bridging host CRDTs and kernels.

The TPU consumes dense tensors; CRDT states and op logs are sparse,
dict-shaped host objects.  This module owns the conversion:

* **interning**: replica UUIDs and set members become dense indices via a
  ``Vocab`` (order of first appearance; canonical output never depends on
  intern order because serialization re-sorts),
* **op columns**: a batch of CRDT ops flattens to parallel int arrays — one
  row per add-dot or per (remove × context-actor),
* **state planes**: an ORSet becomes ``(clock[R], add[E,R], rm[E,R])`` int32
  matrices and back, losslessly.

The batched-tensor fold these feed is the rebuild's replacement for the
reference's per-op host loops (HOT LOOPS #1/#2, reference
crdt-enc/src/lib.rs:458-466 and :533-539).
"""

from __future__ import annotations

import logging
from dataclasses import dataclass, field

import numpy as np

from ..models import AddOp, ORSet, RmOp, VClock
from ..models.counters import NEG, POS
from ..models.vclock import Dot
from ..utils import codec, trace

logger = logging.getLogger("crdt_enc_tpu.columnar")

_warned_no_native_state = False


def _warn_no_native_state(exc: Exception) -> None:
    """Log the state-assembly native fallback ONCE per process: losing
    statebuild.cpp silently costs ~4x on fresh folds and checkpoint
    unpacks (EXC001 — the bytes_lens_join regression class), but a box
    that cannot build the C-API library must not warn per call."""
    global _warned_no_native_state
    if not _warned_no_native_state:
        _warned_no_native_state = True
        logger.warning(
            "native state assembly unavailable (%r); using the "
            "numpy/Python fallback for fresh folds and checkpoint "
            "unpacks", exc
        )

KIND_ADD = 0
KIND_RM = 1


def pad_orset_rows(cols: "OrsetColumns", target: int, num_replicas: int):
    """Pad flattened op columns to ``target`` rows with sentinel no-ops
    (``actor == num_replicas`` marks padding — the single invariant every
    fold kernel keys on).  Shared by bucket padding (recompilation bound)
    and mesh padding (dp divisibility)."""
    n = len(cols.kind)
    padn = target - n
    if padn > 0:
        cols.kind = np.concatenate([cols.kind, np.zeros(padn, np.int8)])
        cols.member = np.concatenate([cols.member, np.zeros(padn, np.int32)])
        cols.actor = np.concatenate(
            [cols.actor, np.full(padn, num_replicas, np.int32)]
        )
        cols.counter = np.concatenate([cols.counter, np.zeros(padn, np.int32)])
    return cols


def strictly_sorted(seq) -> bool:
    """True iff ``seq`` is strictly ascending (⇒ unique).  C-level
    pairwise compare — ~3ms at 100k byte-string actors vs ~10ms for an
    index-based genexp; this sits ahead of every bulk ingest, where a
    storage listing that is already the sorted actor table lets callers
    skip a set union + re-sort of 100k keys."""
    import operator
    from itertools import islice

    return all(map(operator.lt, seq, islice(seq, 1, None)))


class Vocab:
    """Interning table: object → dense index (first-appearance order)."""

    __slots__ = ("items", "_index")

    def __init__(self, items=()):
        items = list(items)
        index = dict(zip(items, range(len(items))))
        if len(index) == len(items):  # no duplicates: one bulk dict build
            self._index: dict | None = index
            self.items: list = items
        else:
            self._index = {}
            self.items = []
            for it in items:
                self.intern(it)

    @classmethod
    def presorted_unique(cls, items) -> "Vocab":
        """Vocab over items the CALLER guarantees unique (e.g. a
        strictly-sorted actor table).  Skips the eager index build —
        hashing 100k byte-string keys costs ~10ms and the bulk fold
        paths only read ``items`` positionally; the index still builds
        lazily on first ``intern``/lookup."""
        v = cls.__new__(cls)
        v.items = list(items)
        v._index = None
        return v

    @property
    def index(self) -> dict:
        if self._index is None:
            self._index = dict(zip(self.items, range(len(self.items))))
        return self._index

    def intern(self, item) -> int:
        index = self.index
        idx = index.get(item)
        if idx is None:
            idx = len(self.items)
            index[item] = idx
            self.items.append(item)
        return idx

    def __len__(self) -> int:
        return len(self.items)


@dataclass
class OrsetColumns:
    """Flattened ORSet op batch (one row per dot / per rm-context entry)."""

    kind: np.ndarray  # int8  — KIND_ADD | KIND_RM
    member: np.ndarray  # int32 — index into members vocab
    actor: np.ndarray  # int32 — index into replicas vocab
    counter: np.ndarray  # int32 — dot counter / remove horizon
    members: Vocab = field(default_factory=Vocab)
    replicas: Vocab = field(default_factory=Vocab)

    @property
    def row_bytes(self) -> int:
        """Bytes of the four row columns: what handing them to a jitted
        fold uploads (``h2d_bytes``)."""
        return (self.kind.nbytes + self.member.nbytes
                + self.actor.nbytes + self.counter.nbytes)


def orset_ops_to_columns(
    ops, members: Vocab | None = None, replicas: Vocab | None = None
) -> OrsetColumns:
    members = members if members is not None else Vocab()
    replicas = replicas if replicas is not None else Vocab()
    kind, member, actor, counter = [], [], [], []
    for op in ops:
        if isinstance(op, (list, tuple)):
            from ..models.orset import op_from_obj

            op = op_from_obj(op)
        if isinstance(op, AddOp):
            kind.append(KIND_ADD)
            member.append(members.intern(op.member))
            actor.append(replicas.intern(op.dot.actor))
            counter.append(op.dot.counter)
        elif isinstance(op, RmOp):
            m = members.intern(op.member)
            # sorted-actor order matches the canonical packed form the
            # native decoder walks, so both flattenings are positionally equal
            for r, c in sorted(op.ctx.counters.items()):
                kind.append(KIND_RM)
                member.append(m)
                actor.append(replicas.intern(r))
                counter.append(c)
        else:
            raise TypeError(f"bad ORSet op {op!r}")
    return OrsetColumns(
        np.asarray(kind, np.int8),
        np.asarray(member, np.int32),
        np.asarray(actor, np.int32),
        np.asarray(counter, np.int32),
        members,
        replicas,
    )


def orset_scan_vocab(state: ORSet, members: Vocab, replicas: Vocab) -> None:
    """Grow the vocabularies with everything the state mentions, without
    building planes — the cheap first pass when densifying many states to a
    shared vocabulary.

    Actors collect through C-level ``set.update`` per entry dict and new
    ones append in sorted order (deterministic), instead of one ``intern``
    call per dot — at ~1M dots the per-dot Python calls cost ~0.5s of
    every warm-open tail ingest and every fold's vocab pass."""
    if not state.entries and not state.deferred and not state.clock.counters:
        # an empty state mentions nothing — in particular do NOT touch
        # ``replicas.index``, whose lazy build over a 100k-actor table
        # costs ~10ms and is pure waste on the fresh streaming shape
        return
    actor_set: set = set()
    for m, entry in state.entries.items():
        members.intern(m)
        actor_set.update(entry)
    for m, dfr in state.deferred.items():
        members.intern(m)
        actor_set.update(dfr)
    actor_set.update(state.clock.counters)
    index = replicas.index
    new = [r for r in actor_set if r not in index]
    try:
        new.sort()
    except TypeError:  # mixed-type actor ids: sort by canonical bytes
        new.sort(key=codec.pack)
    for r in new:
        replicas.intern(r)


def orset_state_to_planes(
    state: ORSet, members: Vocab, replicas: Vocab, *, scanned: bool = False
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Dense ``(clock[R], add[E,R], rm[E,R])`` planes (int32).

    The vocabs are extended in place with anything the state mentions;
    pass ``scanned=True`` when ``orset_scan_vocab`` already ran for this
    state (skips a redundant sparse pass).
    """
    if not scanned:
        orset_scan_vocab(state, members, replicas)
    E, R = len(members), len(replicas)
    clock = np.zeros(R, np.int32)
    add = np.zeros((E, R), np.int32)
    rm = np.zeros((E, R), np.int32)
    for r, c in state.clock.counters.items():
        clock[replicas.index[r]] = c
    for m, entry in state.entries.items():
        e = members.index[m]
        for r, c in entry.items():
            add[e, replicas.index[r]] = c
    for m, dfr in state.deferred.items():
        e = members.index[m]
        for r, c in dfr.items():
            rm[e, replicas.index[r]] = c
    return clock, add, rm


def _grouped_rows_dicts_native(
    m_idx: np.ndarray, a_idx: np.ndarray, ctr: np.ndarray,
    members: list, actors: list, target: dict,
) -> bool:
    """ONE home for the native ``grouped_rows_dicts`` invocation
    (statebuild.cpp): member-contiguous int32/int32/int64 rows → nested
    ``{member: {actor: counter}}`` dicts in one C pass.  Returns False
    — with ``target`` left EMPTY (a partial fill is cleared) — when the
    native library is unavailable or declines; callers then run their
    own Python fallback.  Shared by the checkpoint unpack and the plane
    writeback, so the ABI and the partial-fill recovery can never
    drift between them."""
    try:
        import ctypes

        from .. import native

        lib = native.load_state()
        i32p = ctypes.POINTER(ctypes.c_int32)
        i64p = ctypes.POINTER(ctypes.c_int64)
        rc = lib.grouped_rows_dicts(
            np.ascontiguousarray(m_idx, np.int32).ctypes.data_as(i32p),
            np.ascontiguousarray(a_idx, np.int32).ctypes.data_as(i32p),
            np.ascontiguousarray(ctr, np.int64).ctypes.data_as(i64p),
            len(m_idx), members, actors, target,
        )
        if rc == 0:
            return True
        target.clear()  # partial native fill: rebuild from scratch
    except Exception as e:
        _warn_no_native_state(e)
    return False


def _dicts_grouped_rows_native(table: dict, members: Vocab, actors: Vocab):
    """ONE home for the native ``dicts_grouped_rows`` invocation
    (statebuild.cpp), the way back from ``grouped_rows_dicts``: one
    ``{member: {actor: counter}}`` table → its ``(member index, actor
    index, counter)`` row buffers as three ``bytes`` (int32, int32,
    int64), in the order of a walk of the dicts, ``members`` and
    ``actors`` interned as that walk meets them.  ``None`` where the
    native library is unavailable or declines (a slot map that is not a
    dict, a counter that is not an int or is outside int64), with the
    two tables then in an UNKNOWN state: the caller starts over on fresh
    ones."""
    try:
        from .. import native

        return native.load_state().dicts_grouped_rows(
            table, members.items, members.index, actors.items, actors.index
        )
    except Exception as e:
        _warn_no_native_state(e)
    return None


def _fill_dicts_from_plane(plane: np.ndarray, members: Vocab,
                           replicas: Vocab, target: dict) -> None:
    """Nonzero plane cells → nested ``{member: {actor: counter}}`` dicts.

    ``np.nonzero`` yields rows in row-major order, i.e. grouped by
    member — exactly the contiguous-groups contract of the native
    ``grouped_rows_dicts`` pass, so the dict assembly that dominated
    the plane writeback at fleet scale (~0.6ms per small tenant, ×
    every tenant × every service cycle — and every solo session
    finish) runs as one C call.  The Python loop remains as the
    no-native fallback, byte-identical."""
    es, rs = np.nonzero(plane)
    if not len(es):
        return
    if _grouped_rows_dicts_native(
        es, rs, plane[es, rs], members.items, replicas.items, target
    ):
        return
    for e, r in zip(es.tolist(), rs.tolist()):
        target.setdefault(members.items[e], {})[replicas.items[r]] = int(
            plane[e, r]
        )


def orset_planes_to_state(
    clock: np.ndarray, add: np.ndarray, rm: np.ndarray, members: Vocab, replicas: Vocab
) -> ORSet:
    """Inverse of ``orset_state_to_planes`` (planes must be normalized:
    entries killed where add ≤ rm, rm zeroed where rm ≤ clock)."""
    clock = np.asarray(clock)
    add = np.asarray(add)
    rm = np.asarray(rm)
    state = ORSet()
    state.clock = VClock(
        {replicas.items[r]: int(clock[r]) for r in np.nonzero(clock)[0]}
    )
    _fill_dicts_from_plane(add, members, replicas, state.entries)
    _fill_dicts_from_plane(rm, members, replicas, state.deferred)
    return state


def _set_cells(target: dict, m_idx, a_objs: list, vals: list, mobj: list) -> None:
    """Member-contiguous cells into ``target``'s nested dicts: a nonzero
    value is set, a zero removes the slot, a member left with no slot
    goes."""
    starts = np.flatnonzero(np.r_[True, np.diff(m_idx) != 0])
    ends = np.r_[starts[1:], len(m_idx)]
    for s, e in zip(starts.tolist(), ends.tolist()):
        mo = mobj[int(m_idx[s])]
        slot = target.get(mo)
        if slot is None:
            slot = {a: v for a, v in zip(a_objs[s:e], vals[s:e]) if v}
            if slot:
                target[mo] = slot
            continue
        for a, v in zip(a_objs[s:e], vals[s:e]):
            if v:
                slot[a] = v
            else:
                slot.pop(a, None)
        if not slot:
            del target[mo]


def orset_cells_to_state(
    state: ORSet,
    member: np.ndarray,  # (N,) the batch's rows: indices into ``members``
    actor: np.ndarray,  # (N,) ... and into ``replicas``
    add_c: np.ndarray,  # (N,) the post-fold add word of each row's cell
    rm_c: np.ndarray,  # (N,) ... and its remove word
    members: Vocab,
    replicas: Vocab,
) -> ORSet:
    """The partial form of :func:`orset_planes_to_state`: write into
    ``state``, whose entries and horizons equal the planes as they stood
    BEFORE a fold and whose clock is already the post-fold one, what the
    fold changed, given the post-fold words of the cells its rows named
    (``ops.orset.orset_gather_cells``).  A fold touches no other add word;
    the only other remove words it changes are horizons the advanced
    clock caught up with, retired here from the clock.  The result is the
    state ``orset_planes_to_state`` builds from the whole post-fold planes
    (tests/test_resident_fold.py)."""
    R = len(replicas)
    key = np.asarray(member, np.int64) * R + np.asarray(actor, np.int64)
    key, first = np.unique(key, return_index=True)  # member-major order
    if len(key):
        m_idx = key // R
        a_objs = np.asarray(replicas.items, dtype=object)[key % R].tolist()
        mobj = members.items
        _set_cells(state.entries, m_idx, a_objs,
                   np.asarray(add_c)[first].tolist(), mobj)
        _set_cells(state.deferred, m_idx, a_objs,
                   np.asarray(rm_c)[first].tolist(), mobj)
    for mo in list(state.deferred):
        state._normalize_member(mo)  # the one host home of the retire rule
    return state


def orset_fold_sparse_host(
    state: ORSet,
    kind: np.ndarray,
    member: np.ndarray,
    actor: np.ndarray,
    counter: np.ndarray,
    members: Vocab,
    replicas: Vocab,
) -> ORSet:
    """Vectorized-numpy sparse fold: the host twin of ``orset_fold_coo``.

    Same aggregation (per-segment max of live-add dots and remove
    horizons, stale-filter against the state clock) via ``np.lexsort``
    run-boundaries instead of a device sort.  Exists because TPU sorts
    are bitonic and slow for this shape (measured 0.7s for 256k rows vs
    29ms in numpy — sorting is not MXU work), and the sparse regime is
    N ≪ E·R where the device has nothing else to offer; the jitted
    ``orset_fold_coo`` remains for compositions that are already
    device-resident.  int64 keys — no ``2·E·R < 2^31`` bound.
    """
    state._mut += 1  # invalidate any device-resident plane cache
    # dense clock FIRST: it may intern clock actors into `replicas`, and
    # the segment keys below must be encoded with the final R or
    # orset_apply_coo would decode them against a different modulus
    clock0 = vclock_to_dense(state.clock, replicas).astype(np.int64)
    E, R = len(members), len(replicas)
    if not state.entries and not state.deferred and E and R:
        # the streaming shape (one combined fold into an empty state):
        # native sort + dict assembly (statebuild.cpp) replaces the numpy
        # lexsort and the Python writeback — measured ~5x on the config-5
        # wall.  Falls through on any native unavailability or a shape
        # past the packed-sort bound.
        folded = _orset_fresh_fold_native(
            state, kind, member, actor, counter, members, replicas, clock0
        )
        if folded is not None:
            return folded
    kind = np.asarray(kind)
    member = np.asarray(member, np.int64)
    actor = np.asarray(actor, np.int64)
    counter = np.asarray(counter, np.int64)
    pad = actor >= R
    a_ix = np.minimum(actor, R - 1)
    is_add = (kind == KIND_ADD) & ~pad
    is_rm = (kind == KIND_RM) & ~pad
    live = is_add & (counter > clock0[a_ix])
    valid = live | is_rm
    seg = member * R + a_ix
    key = np.where(is_rm, seg + E * R, seg)[valid]
    c = counter[valid]
    order = np.lexsort((c, key))
    sk = key[order]
    sc = c[order]
    is_last = np.ones(len(sk), bool)
    if len(sk) > 1:
        is_last[:-1] = sk[:-1] != sk[1:]
    clock = clock0.copy()
    np.maximum.at(clock, a_ix[live], counter[live])
    # int64 throughout: narrowing here would silently wrap a > 2^31
    # clock (apply_coo and dense_to_vclock are dtype-agnostic)
    return orset_apply_coo(
        state, clock, sk, sc, is_last, members, replicas
    )


#: rows below this skip the checkpoint-stash bookkeeping — repacking a
#: tiny state from its dicts costs less than carrying the row arrays
CKPT_STASH_MIN_ROWS = 4096


def _orset_fresh_fold_native(
    state, kind, member, actor, counter, members, replicas, clock0
):
    """Attempt the native fresh-state sparse fold (statebuild.cpp),
    byte-identical to the numpy/Python path below.  Returns the folded
    state, or None when the native library is unavailable or the shape
    overflows the packed sort (caller falls through to the Python path).

    Split protocol (``orset_fold_rows`` → ``grouped_rows_dicts``): the
    pure-C FOLD — gate + packed-u64 radix sort + dedup + survivor
    filter — runs under its own ``session.sparse_fold`` span, and the
    CPython dict WRITEBACK under ``session.writeback``, so the gap
    report's fold marginal stops absorbing dict-assembly time.  The
    surviving rows come out member-contiguous in the
    ``orset_pack_checkpoint`` layout and are stashed on the state
    (mut-epoch-guarded) so the compaction's warm-open checkpoint seals
    straight from them — zero dict re-walk (core.py
    ``_pack_checkpoint_state``).  Falls back to the fused
    ``orset_fresh_fold`` (one call, dicts built inside) when the split
    entry points are missing (older .so)."""
    import ctypes

    from .. import native

    try:
        lib = native.load_state()
    except Exception as e:
        _warn_no_native_state(e)
        return None
    # self-protecting epoch bump (MUT001): the caller bumps too, but the
    # native writeback below mutates entries/deferred/clock directly and
    # must not depend on every future caller remembering to
    state._mut += 1
    E, R = len(members), len(replicas)
    kind = np.ascontiguousarray(kind, np.int8)
    member32 = np.ascontiguousarray(member, np.int32)
    actor32 = np.ascontiguousarray(np.minimum(actor, R), np.int32)
    counter32 = np.ascontiguousarray(counter, np.int32)
    if len(member32) and (
        int(counter32.max(initial=0)) != int(np.asarray(counter).max(initial=0))
        or int(member32.max(initial=0)) >= E
    ):
        return None  # int32 narrowing lost information — Python path
    if len(clock0) and int(np.asarray(clock0).max(initial=0)) > 2 ** 31 - 1:
        return None  # an int64 clock would wrap through the int32 gate
    clock = np.ascontiguousarray(clock0, np.int32)
    i8p = ctypes.POINTER(ctypes.c_int8)
    i32p = ctypes.POINTER(ctypes.c_int32)
    i64p = ctypes.POINTER(ctypes.c_int64)
    if not hasattr(lib, "orset_fold_rows"):
        # stale .so without the split protocol: fused fold+writeback
        rc = lib.orset_fresh_fold(
            kind.ctypes.data_as(i8p),
            member32.ctypes.data_as(i32p),
            actor32.ctypes.data_as(i32p),
            counter32.ctypes.data_as(i32p),
            len(kind), E, R,
            clock.ctypes.data_as(i32p),
            members.items, replicas.items,
            state.entries, state.deferred,
        )
        if rc == -2:
            raise RuntimeError("native orset_fresh_fold failed")
        if rc != 0:
            return None
        clock_dict = lib.dense_clock_dict(
            clock.ctypes.data_as(i32p), R, replicas.items
        )
        state.clock = VClock(clock_dict)
        return state
    with trace.span("session.sparse_fold"):
        counts = np.zeros(2, np.int64)
        handle = lib.orset_fold_rows(
            kind.ctypes.data_as(i8p),
            member32.ctypes.data_as(i32p),
            actor32.ctypes.data_as(i32p),
            counter32.ctypes.data_as(i32p),
            len(kind), E, R,
            clock.ctypes.data_as(i32p),
            counts.ctypes.data_as(i64p),
        )
        if not handle:
            return None  # packed-sort overflow / alloc failure
        n_a, n_d = int(counts[0]), int(counts[1])
        taken = False
        try:
            am = np.zeros(n_a, np.int32)
            aa = np.zeros(n_a, np.int32)
            ac = np.zeros(n_a, np.int64)
            dm = np.zeros(n_d, np.int32)
            da = np.zeros(n_d, np.int32)
            dc = np.zeros(n_d, np.int64)
            taken = True  # take() frees even if a later copy would fail
            rc = lib.orset_fold_rows_take(
                handle,
                am.ctypes.data_as(i32p), aa.ctypes.data_as(i32p),
                ac.ctypes.data_as(i64p), n_a,
                dm.ctypes.data_as(i32p), da.ctypes.data_as(i32p),
                dc.ctypes.data_as(i64p), n_d,
            )
            if rc != 0:
                raise RuntimeError(
                    "orset_fold_rows_take capacity mismatch"
                )
        finally:
            if not taken:  # e.g. MemoryError sizing the output arrays
                lib.orset_fold_rows_drop(handle)
    with trace.span("session.writeback"):
        if n_a and not _grouped_rows_dicts_native(
            am, aa, ac, members.items, replicas.items, state.entries
        ):
            _fill_dicts_from_rows(
                am, aa, ac, members, replicas, state.entries
            )
        if n_d and not _grouped_rows_dicts_native(
            dm, da, dc, members.items, replicas.items, state.deferred
        ):
            _fill_dicts_from_rows(
                dm, da, dc, members, replicas, state.deferred
            )
        clock_dict = lib.dense_clock_dict(
            clock.ctypes.data_as(i32p), R, replicas.items
        )
        state.clock = VClock(clock_dict)
    if n_a + n_d >= CKPT_STASH_MIN_ROWS:
        state._ckpt_rows = (
            getattr(state, "_mut", None),
            (clock.copy(), am, aa, ac, dm, da, dc, members, replicas),
        )
    return state


def _fill_dicts_from_rows(m_idx, a_idx, ctr, members: Vocab,
                          replicas: Vocab, target: dict) -> None:
    """Python fallback for the member-contiguous rows → nested-dicts
    writeback (the ``grouped_rows_dicts`` contract) — byte-identical."""
    a_l = a_idx.tolist()
    c_l = ctr.tolist()
    starts = np.flatnonzero(np.r_[True, np.diff(m_idx) != 0])
    ends = np.r_[starts[1:], len(m_idx)]
    for s, e in zip(starts.tolist(), ends.tolist()):
        target[members.items[int(m_idx[s])]] = {
            replicas.items[a_l[t]]: c_l[t] for t in range(s, e)
        }


def orset_pack_checkpoint_rows(
    clock: np.ndarray, am, aa, ac, dm, da, dc,
    members: Vocab, replicas: Vocab,
) -> dict:
    """:func:`orset_pack_checkpoint` computed from the fresh fold's
    surviving ROW columns (``_orset_fresh_fold_native``'s stash) — the
    zero-copy decode→planes tail: the checkpoint payload falls out of
    vectorized index remaps over arrays the fold already produced, with
    no walk of the dicts the state also materialized.  Same wire keys
    and invariants as the sparse pack (clock actors first and aligned
    with ``cc``, member groups contiguous, only referenced objects
    listed); table/row ORDER may differ from the dict walk — legal, the
    checkpoint is a local cache and ``orset_unpack_checkpoint`` is
    order-agnostic beyond group contiguity (the
    ``orset_pack_checkpoint_planes`` precedent)."""
    clock = np.asarray(clock)
    cnz = np.nonzero(clock)[0]
    used = np.union1d(np.union1d(cnz, aa), da)
    a_order = np.concatenate([cnz, np.setdiff1d(used, cnz)])
    a_perm = np.zeros((int(a_order.max()) + 1) if len(a_order) else 1,
                      np.int32)
    a_perm[a_order] = np.arange(len(a_order), dtype=np.int32)
    em = np.unique(am)
    m_order = np.concatenate([em, np.setdiff1d(np.unique(dm), em)])
    m_perm = np.zeros((int(m_order.max()) + 1) if len(m_order) else 1,
                      np.int32)
    m_perm[m_order] = np.arange(len(m_order), dtype=np.int32)
    aobj, mobj = replicas.items, members.items
    return {
        b"actors": [aobj[int(i)] for i in a_order],
        b"members": [mobj[int(i)] for i in m_order],
        b"nc": len(cnz),
        b"cc": clock[cnz].astype(np.int64).tobytes(),
        b"em": m_perm[am].tobytes(),
        b"ea": a_perm[aa].tobytes(),
        b"ec": np.asarray(ac, np.int64).tobytes(),
        b"dm": m_perm[dm].tobytes(),
        b"da": a_perm[da].tobytes(),
        b"dc": np.asarray(dc, np.int64).tobytes(),
    }


def orset_apply_coo(
    state: ORSet,
    clock_dense: np.ndarray,
    seg_keys: np.ndarray,
    seg_max: np.ndarray,
    is_seg_max: np.ndarray,
    members: Vocab,
    replicas: Vocab,
) -> ORSet:
    """Fold ``orset_fold_coo`` results into sparse host state.

    Applies exactly the dense kernel's semantics without planes: per
    touched segment, entry ``= max(entry, add-dot)``, remove horizon
    ``= max(horizon, batch horizon)``, then the normalization rules —
    entries killed where ``entry ≤ horizon``, horizons dropped where
    ``≤ clock`` — via the state's own ``_normalize_member`` (the single
    host implementation of those rules).  Touched members plus every
    member holding deferred horizons are normalized: the batch may have
    advanced clocks that retire horizons the batch never mentioned.
    """
    state._mut += 1  # invalidate any device-resident plane cache
    E, R = len(members), len(replicas)
    sel = np.asarray(is_seg_max)
    k = np.asarray(seg_keys)[sel].astype(np.int64)
    c = np.asarray(seg_max)[sel]
    mobj = members.items
    aobj_arr = np.asarray(replicas.items, dtype=object)

    # keys are sorted: adds (key < E·R) form the prefix, removes the
    # suffix, and within each side rows are member-major — so members are
    # contiguous groups and fresh entries build as one dict(zip(...))
    split = int(np.searchsorted(k, E * R))
    ak, ac = k[:split], c[:split]
    rk, rc = k[split:] - E * R, c[split:]
    a_m, a_a = ak // R, ak % R
    r_m, r_a = rk // R, rk % R

    # Members absent from BOTH state.entries and state.deferred take a
    # fully vectorized path: for them the post-merge dicts are exactly the
    # batch segments with the normalization rules applied column-wise —
    # adds killed where ≤ the batch horizon on the same (member, actor)
    # segment, horizons dropped where ≤ the merged clock — so no per-member
    # Python normalize is needed.  On a fresh ingest that is every member.
    clock_arr = np.asarray(clock_dense, np.int64)
    if not state.entries and not state.deferred:
        fresh = None  # all members fresh
        a_fresh = np.ones(len(ak), bool)
        r_fresh = np.ones(len(rk), bool)
        pre_deferred: list = []
    else:
        existing = set(state.entries)
        existing.update(state.deferred)
        # pre-existing horizons re-normalize below even when the batch
        # never mentions them: the batch may have advanced clocks that
        # retire them
        pre_deferred = list(state.deferred)
        fresh = np.fromiter(
            (mo not in existing for mo in mobj), bool, count=E
        )
        a_fresh = fresh[a_m]
        r_fresh = fresh[r_m]

    def build_fresh(m_idx, a_idx, vals, target: dict):
        if not len(m_idx):
            return
        starts = np.flatnonzero(np.r_[True, np.diff(m_idx) != 0])
        ends = np.r_[starts[1:], len(m_idx)]
        a_objs = aobj_arr[a_idx].tolist()
        vv = vals.tolist()
        for s, e in zip(starts.tolist(), ends.tolist()):
            target[mobj[int(m_idx[s])]] = dict(zip(a_objs[s:e], vv[s:e]))

    # fresh adds: survive the batch horizon for their own (m, a) segment
    # (strict >: an equal horizon observed the dot — it dies)
    if len(rk):
        pos = np.minimum(np.searchsorted(rk, ak), len(rk) - 1)
        horizon = np.where(rk[pos] == ak, rc[pos], 0)
        keep_add = a_fresh & (ac > horizon)
    else:
        keep_add = a_fresh
    build_fresh(a_m[keep_add], a_a[keep_add], ac[keep_add], state.entries)
    # fresh horizons: only those the merged clock has not caught up with
    keep_rm = r_fresh & (rc > clock_arr[r_a])
    build_fresh(r_m[keep_rm], r_a[keep_rm], rc[keep_rm], state.deferred)

    # members with pre-existing state merge by max, then normalize
    touched: set = set()

    aobj = replicas.items

    def fold_groups(m_idx, a_idx, vals, target: dict):
        a_idx = a_idx.tolist()
        vals = vals.tolist()
        starts = np.flatnonzero(np.r_[True, np.diff(m_idx) != 0])
        ends = np.r_[starts[1:], len(m_idx)]
        for s, e in zip(starts.tolist(), ends.tolist()):
            mo = mobj[int(m_idx[s])]
            touched.add(mo)
            slot = target.setdefault(mo, {})
            for x, cc in zip(a_idx[s:e], vals[s:e]):
                ao = aobj[x]
                if cc > slot.get(ao, 0):
                    slot[ao] = cc

    if fresh is not None:
        stale_a = ~a_fresh
        if stale_a.any():
            fold_groups(a_m[stale_a], a_a[stale_a], ac[stale_a], state.entries)
        stale_r = ~r_fresh
        if stale_r.any():
            fold_groups(r_m[stale_r], r_a[stale_r], rc[stale_r], state.deferred)

    state.clock = dense_to_vclock(clock_dense, replicas)
    touched.update(pre_deferred)
    for mo in touched:
        state._normalize_member(mo)
    return state


# ---- checkpoint pack/unpack ----------------------------------------------


def orset_pack_checkpoint(state: ORSet) -> dict | None:
    """Columnar encoding of one ORSet for the local fold checkpoint
    (core.py ``save_checkpoint``): the three sparse tables flatten to raw
    int row buffers over interned actor/member tables, so a 100k-replica
    clock packs and loads as ``np.frombuffer`` + one zip instead of a
    per-key msgpack map walk.  Lossless by value; byte-identity of the
    canonical serialization follows because ``codec.pack`` re-sorts maps.

    The row buffers come from one native pass a table
    (:func:`_dicts_grouped_rows_native`) wherever the library loads and
    the state is dicts of ints, else from the Python walk
    (:func:`_dicts_grouped_rows_walk`) started over on fresh tables: the
    two give equal payloads, key for key and byte for byte.  Counters
    ``checkpoint_pack_native`` / ``checkpoint_pack_walk`` say which made
    the payload.

    Returns None when any counter falls outside int64 (precision must
    never be lost — the caller then uses the generic ``state_to_obj``
    encoding instead).
    """
    try:
        clock_ctr = np.asarray(
            list(state.clock.counters.values()), np.int64
        )
        how = "checkpoint_pack_native"
        got = _checkpoint_rows(state, _dicts_grouped_rows_native)
        if got is None:
            how = "checkpoint_pack_walk"
            got = _checkpoint_rows(state, _dicts_grouped_rows_walk)
    except OverflowError:
        return None
    trace.add(how, 1)
    actors, members, (em, ea, ec), (dm, da, dc) = got
    return {
        b"actors": list(actors.items),
        b"members": list(members.items),
        b"nc": len(state.clock.counters),
        b"cc": clock_ctr.tobytes(),
        b"em": em, b"ea": ea, b"ec": ec,
        b"dm": dm, b"da": da, b"dc": dc,
    }


def _checkpoint_rows(state: ORSet, table_rows):
    """Fresh interning tables, the clock's actors first (so they stay
    aligned with ``cc``), and the row bytes of ``entries`` then
    ``deferred`` by ``table_rows(table, members, actors)``:
    ``(actors, members, entry rows, deferred rows)``, or ``None`` where
    ``table_rows`` declined a table."""
    actors = Vocab(state.clock.counters)
    members = Vocab()
    rows = []
    for table in (state.entries, state.deferred):
        got = table_rows(table, members, actors)
        if got is None:
            return None
        rows.append(got)
    return actors, members, rows[0], rows[1]


def _dicts_grouped_rows_walk(table: dict, members: Vocab, actors: Vocab):
    """:func:`_dicts_grouped_rows_native` as a Python loop over every
    slot: the fallback where the native pass is unavailable or declines,
    and the tests' oracle for it.  ``OverflowError`` for a counter
    outside int64."""
    m_idx, a_idx, ctr = [], [], []
    for m, slots in table.items():
        e = members.intern(m)
        for r, c in slots.items():
            m_idx.append(e)
            a_idx.append(actors.intern(r))
            ctr.append(c)
    return (
        np.asarray(m_idx, np.int32).tobytes(),
        np.asarray(a_idx, np.int32).tobytes(),
        np.asarray(ctr, np.int64).tobytes(),
    )


def orset_unpack_checkpoint(obj) -> ORSet:
    """Inverse of :func:`orset_pack_checkpoint`."""
    state = ORSet()
    actors = list(obj[b"actors"])
    members = list(obj[b"members"])
    nc = int(obj[b"nc"])
    cc = np.frombuffer(bytes(obj[b"cc"]), np.int64)
    state.clock = VClock(dict(zip(actors[:nc], cc.tolist())))

    def build(mi, ai, ci, target: dict):
        m_idx = np.frombuffer(bytes(obj[mi]), np.int32)
        if not len(m_idx):
            return
        a_idx = np.frombuffer(bytes(obj[ai]), np.int32)
        ctr = np.frombuffer(bytes(obj[ci]), np.int64)
        # rows were emitted in one walk of the source dict, so each
        # member's rows are contiguous.  Native fast path: one C pass
        # builds all the nested dicts (statebuild.cpp) — the Python
        # grouping below cost ~0.5s of every 1M-dot warm open.
        if _grouped_rows_dicts_native(
            m_idx, a_idx, ctr, members, actors, target
        ):
            return
        a_l = a_idx.tolist()
        c_l = ctr.tolist()
        starts = np.flatnonzero(np.r_[True, np.diff(m_idx) != 0])
        ends = np.r_[starts[1:], len(m_idx)]
        for s, e in zip(starts.tolist(), ends.tolist()):
            target[members[int(m_idx[s])]] = {
                actors[a_l[t]]: c_l[t] for t in range(s, e)
            }

    build(b"em", b"ea", b"ec", state.entries)
    build(b"dm", b"da", b"dc", state.deferred)
    return state


def orset_pack_checkpoint_planes(
    clock: np.ndarray, add: np.ndarray, rm: np.ndarray,
    members: Vocab, replicas: Vocab,
) -> dict:
    """:func:`orset_pack_checkpoint` computed from dense planes instead
    of the sparse state — all row buffers fall out of ``np.nonzero``
    with no per-dot Python (the fold service already HOLDS each
    tenant's folded planes, and the sparse pack walk was its single
    biggest seal-phase CPU item at fleet scale).  Same wire keys and
    invariants as the sparse pack: ``actors[:nc]`` are exactly the
    clock's actors (aligned with ``cc``), row groups are
    member-contiguous (the unpack contract — here by ``np.nonzero``'s
    row-major order), tables list only referenced actors/members.  The
    encodings differ in table/row ORDER (plane order vs dict walk) —
    legal, the checkpoint is a local cache and ``orset_unpack_
    checkpoint`` is order-agnostic beyond group contiguity; equality is
    pinned semantically in tests.  Planes may be bucket-padded: padded
    cells are zero, so no index past the vocabularies can appear.
    Counters are int32 by plane construction, so the sparse pack's
    int64-overflow decline cannot arise.

    Implementation: ``np.nonzero`` flattens the planes to the entry /
    deferred row columns (row-major ⇒ member-contiguous), then the ONE
    row-layout packer (:func:`orset_pack_checkpoint_rows`) builds the
    payload — the two plane/row entry points cannot drift."""
    clock = np.asarray(clock)
    add = np.asarray(add)
    rm = np.asarray(rm)
    es, rs = np.nonzero(add)
    ds, qs = np.nonzero(rm)
    return orset_pack_checkpoint_rows(
        clock, es, rs, add[es, rs], ds, qs, rm[ds, qs], members, replicas
    )


# ---- counters ------------------------------------------------------------


@dataclass
class CounterColumns:
    sign: np.ndarray  # int8 — POS | NEG (always POS for G-Counter)
    actor: np.ndarray  # int32
    counter: np.ndarray  # int32
    replicas: Vocab = field(default_factory=Vocab)


def counter_ops_to_columns(ops, replicas: Vocab | None = None) -> CounterColumns:
    """Flatten G-Counter (Dot) or PN-Counter ((dir, Dot)) op batches."""
    replicas = replicas if replicas is not None else Vocab()
    sign, actor, counter = [], [], []
    for op in ops:
        if isinstance(op, Dot):
            direction, dot = POS, op
        else:
            direction, dot = op
            if not isinstance(dot, Dot):
                dot = Dot.from_obj(dot)
        if direction not in (POS, NEG):
            raise ValueError(f"bad counter op direction {direction!r}")
        sign.append(direction)
        actor.append(replicas.intern(dot.actor))
        counter.append(dot.counter)
    return CounterColumns(
        np.asarray(sign, np.int8),
        np.asarray(actor, np.int32),
        np.asarray(counter, np.int32),
        replicas,
    )


def vclock_to_dense(clock: VClock, replicas: Vocab) -> np.ndarray:
    for r in clock.counters:
        replicas.intern(r)
    # int64 when any counter needs it: the sparse host path supports the
    # full counter range (device paths bound counters to int32 upstream)
    wide = any(c > 2 ** 31 - 1 for c in clock.counters.values())
    out = np.zeros(len(replicas), np.int64 if wide else np.int32)
    for r, c in clock.counters.items():
        out[replicas.index[r]] = c
    return out


def dense_to_vclock(arr: np.ndarray, replicas: Vocab) -> VClock:
    arr = np.asarray(arr)
    nz = np.nonzero(arr)[0]
    robj = np.asarray(replicas.items, dtype=object)[nz].tolist()
    return VClock(dict(zip(robj, arr[nz].tolist())))


# ---- LWW -----------------------------------------------------------------


@dataclass
class LwwColumns:
    key: np.ndarray  # int32 — index into keys vocab
    ts_hi: np.ndarray  # int32 — timestamp high 31 bits
    ts_lo: np.ndarray  # int32 — timestamp low 31 bits
    actor: np.ndarray  # int32 — index into actor-rank vocab (see below)
    value: np.ndarray  # int32 — index into values list (rank-ordered)
    tombstone: np.ndarray  # bool
    keys: Vocab = field(default_factory=Vocab)
    actors_sorted: list = field(default_factory=list)  # rank → actor bytes
    values_sorted: list = field(default_factory=list)  # rank → value object


def lww_ops_to_columns(ops, keys: Vocab | None = None) -> LwwColumns:
    """Flatten LWW ops.  Actors and values are *rank*-interned (sorted by
    bytes) so integer comparison on the device reproduces the host's
    lexicographic tie-breaks exactly."""
    from ..models.lwwmap import LWWOp

    ops = [LWWOp.from_obj(o) if isinstance(o, (list, tuple)) else o for o in ops]
    keys = keys if keys is not None else Vocab()
    actors = sorted({op.actor for op in ops})
    actor_rank = {a: i for i, a in enumerate(actors)}
    packed_vals = {}
    for op in ops:
        v = None if op.tombstone else op.value
        packed_vals[codec.pack(v)] = v
    values_sorted = [packed_vals[k] for k in sorted(packed_vals)]
    value_rank = {k: i for i, k in enumerate(sorted(packed_vals))}
    key_col, ts_col, actor_col, value_col, tomb_col = [], [], [], [], []
    for op in ops:
        key_col.append(keys.intern(op.key))
        ts_col.append(op.ts)
        actor_col.append(actor_rank[op.actor])
        v = None if op.tombstone else op.value
        value_col.append(value_rank[codec.pack(v)])
        tomb_col.append(op.tombstone)
    from .lww import ts_split

    ts_hi, ts_lo = ts_split(np.asarray(ts_col, np.int64).reshape(-1))
    return LwwColumns(
        np.asarray(key_col, np.int32),
        ts_hi,
        ts_lo,
        np.asarray(actor_col, np.int32),
        np.asarray(value_col, np.int32),
        np.asarray(tomb_col, bool),
        keys,
        actors,
        values_sorted,
    )
