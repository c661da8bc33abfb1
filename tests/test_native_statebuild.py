"""The native fresh-state sparse fold (native/statebuild.cpp) must be
byte-identical to the numpy/Python sparse fold it replaces on the
streaming path (ops/columnar.py orset_fold_sparse_host).

The native path engages only for empty-entries states (the streaming
shape — one combined fold into a fresh replica, BASELINE config 5);
differential coverage here forces both paths over the same inputs,
including pre-existing clocks (fresh entries, non-empty history) and
the int32/packed-sort fallback edges.
"""

from __future__ import annotations

import numpy as np
import pytest

from crdt_enc_tpu import native
from crdt_enc_tpu.models import ORSet
from crdt_enc_tpu.models.vclock import VClock
from crdt_enc_tpu.ops import columnar as C
from crdt_enc_tpu.utils import codec


def _gen(N, E, R, seed, rm=0.3, pad=0.05, maxc=500):
    rng = np.random.default_rng(seed)
    kind = (rng.random(N) < rm).astype(np.int8)
    member = rng.integers(0, E, N, dtype=np.int32)
    actor = rng.integers(0, R, N, dtype=np.int32)
    actor = np.where(rng.random(N) < pad, R, actor)
    counter = rng.integers(1, maxc, N, dtype=np.int32)
    return kind, member, actor, counter


def _fold_both(state_fn, kind, member, actor, counter, E, R, actors):
    outs = []
    for force_python in (False, True):
        st = state_fn()
        mem_v, rep_v = C.Vocab(range(E)), C.Vocab(actors)
        if force_python:
            orig = C._orset_fresh_fold_native
            C._orset_fresh_fold_native = lambda *a, **k: None
            try:
                r = C.orset_fold_sparse_host(
                    st, kind, member, actor, counter, mem_v, rep_v
                )
            finally:
                C._orset_fresh_fold_native = orig
        else:
            r = C.orset_fold_sparse_host(
                st, kind, member, actor, counter, mem_v, rep_v
            )
        outs.append(codec.pack(r.to_obj()))
    assert outs[0] == outs[1]


@pytest.mark.parametrize("seed", range(12))
def test_differential_random(seed):
    rng = np.random.default_rng(seed)
    N = int(rng.integers(1, 3000))
    E = int(rng.integers(1, 200))
    R = int(rng.integers(1, 500))
    actors = [b"a%06d" % i for i in range(R)]
    kind, member, actor, counter = _gen(N, E, R, seed)

    # fresh entries but a pre-existing clock: the replay gate and the
    # deferred-horizon filter must use it identically.  Drawn ONCE so
    # both paths fold from the same state.
    cl = {}
    if seed % 3 == 0:
        cl = {
            actors[int(i)]: int(c)
            for i, c in zip(rng.integers(0, R, 20), rng.integers(1, 100, 20))
        }

    def fresh():
        s = ORSet()
        s.clock = VClock(dict(cl))
        return s

    _fold_both(fresh, kind, member, actor, counter, E, R, actors)


def test_all_padding_and_empty():
    E, R = 8, 8
    actors = [b"a%d" % i for i in range(R)]
    kind = np.zeros(64, np.int8)
    member = np.zeros(64, np.int32)
    actor = np.full(64, R, np.int32)  # every row padding
    counter = np.ones(64, np.int32)
    _fold_both(ORSet, kind, member, actor, counter, E, R, actors)


def test_equal_horizon_kills_add():
    # strict >: an add whose counter equals the remove horizon dies
    E, R = 2, 2
    actors = [b"x", b"y"]
    kind = np.array([0, 1], np.int8)
    member = np.array([0, 0], np.int32)
    actor = np.array([0, 0], np.int32)
    counter = np.array([5, 5], np.int32)
    _fold_both(ORSet, kind, member, actor, counter, E, R, actors)
    st = ORSet()
    mem_v, rep_v = C.Vocab(range(E)), C.Vocab(actors)
    r = C.orset_fold_sparse_host(st, kind, member, actor, counter, mem_v, rep_v)
    assert not r.entries  # the add died on its own horizon


def test_int64_clock_falls_back():
    # a pre-existing clock past int32 must route to the Python path —
    # narrowing it would re-open the replay gate for stale ops
    E, R = 2, 2
    actors = [b"x", b"y"]
    st = ORSet()
    st.clock = VClock({b"x": 2 ** 40})
    kind = np.array([0], np.int8)
    member = np.array([0], np.int32)
    actor = np.array([0], np.int32)
    counter = np.array([7], np.int32)  # stale: 7 <= 2**40
    mem_v, rep_v = C.Vocab(range(E)), C.Vocab(actors)
    r = C.orset_fold_sparse_host(st, kind, member, actor, counter, mem_v, rep_v)
    assert not r.entries  # the stale add must NOT replay
    assert r.clock.get(b"x") == 2 ** 40


def test_int64_counter_falls_back():
    # counters past int32 must take the Python path, not corrupt
    E, R = 4, 4
    actors = [b"a%d" % i for i in range(R)]
    kind = np.array([0, 0], np.int8)
    member = np.array([1, 2], np.int32)
    actor = np.array([0, 1], np.int32)
    counter = np.array([2 ** 40, 7], np.int64)
    st = ORSet()
    mem_v, rep_v = C.Vocab(range(E)), C.Vocab(actors)
    r = C.orset_fold_sparse_host(st, kind, member, actor, counter, mem_v, rep_v)
    assert r.entries[1][b"a0"] == 2 ** 40
    assert r.entries[2][b"a1"] == 7
    # the merged clock must not wrap through an int32 narrowing (this
    # silently corrupted before round 4 — clock.astype(np.int32))
    assert r.clock.get(b"a0") == 2 ** 40


def test_bytes_lens_join_capacity_bound():
    """ADVICE r5 (medium) regression: the join pass is bounded by
    ``out_capacity`` — a blobs list that grew between the lengths pass
    and the join pass (pure Python runs between the two ctypes calls)
    returns -1 BEFORE writing past the buffer, and a clean join returns
    exactly the expected total so callers can detect staleness."""
    from crdt_enc_tpu import native

    try:
        slib = native.load_state()
    except RuntimeError as e:
        pytest.skip(f"native state library unavailable: {e}")
    blobs = [b"abc", b"defg", b"hi"]
    n = len(blobs)
    lens = np.zeros(n, np.uint64)
    total = int(slib.bytes_lens_join(
        blobs, lens.ctypes.data_as(native.u64p), None, 0, n
    ))
    assert total == 9 and lens.tolist() == [3, 4, 2]
    # join with exactly-sized capacity succeeds and fills the buffer
    out = np.zeros(total, np.uint8)
    assert int(slib.bytes_lens_join(
        blobs, lens.ctypes.data_as(native.u64p),
        out.ctypes.data_as(native.u8p), total, n,
    )) == total
    assert out.tobytes() == b"abcdefghi"
    # a list that GREW after sizing: rejected by the element-count bound
    # BEFORE any lens[] write (the lens array was sized for n) — and even
    # with the count unchecked (expected_n=-1) the join stops at the
    # capacity and reports -1, leaving the canary past the buffer's
    # logical end untouched
    blobs.append(b"overflow-blob")
    lens2 = np.zeros(len(blobs), np.uint64)
    guard = np.full(total + 1, 0xAB, np.uint8)
    assert int(slib.bytes_lens_join(
        blobs, lens2.ctypes.data_as(native.u64p),
        guard.ctypes.data_as(native.u8p), total, n,
    )) == -1
    assert int(slib.bytes_lens_join(
        blobs, lens2.ctypes.data_as(native.u64p),
        guard.ctypes.data_as(native.u8p), total, -1,
    )) == -1
    assert guard[total] == 0xAB
    # non-bytes element: -1 without touching the output
    assert int(slib.bytes_lens_join(
        [b"x", 7], lens2.ctypes.data_as(native.u64p), None, 0, 2
    )) == -1


def test_decrypt_blobs_packed_survives_blob_list_mutation():
    """End-to-end pin of the hardened join path: the bulk decrypt's
    lengths-pass → capacity-bounded join → verified-total sequence
    roundtrips correctly (the mutation fallback itself is pinned at the
    native layer in test_bytes_lens_join_capacity_bound — list mutation
    between the two passes cannot be scripted deterministically from
    here, but the -1/short-return it produces is)."""
    import secrets

    from crdt_enc_tpu.backends import xchacha

    try:
        native.load()
    except RuntimeError as e:
        pytest.skip(f"native crypto library unavailable: {e}")
    key = secrets.token_bytes(32)
    blobs = [xchacha.encrypt_blob(key, b"v%d" % i) for i in range(24)]
    out = xchacha.decrypt_blobs(key, blobs)
    assert [bytes(v) for v in out] == [b"v%d" % i for i in range(24)]


class _Libc:
    def __init__(self, calls):
        self.mallopt = lambda param, value: calls.append((param, value)) or 1


@pytest.mark.parametrize("libc", ["glibc", "no mallopt", "no libc"])
def test_warm_sets_the_thread_arenas_growth_once_and_only_where_glibc_is(monkeypatch, libc):
    """warm() has glibc open a thread's arena heap whole (M_TOP_PAD = the
    heap's 64 MB) and pins the two thresholds that setting one freezes;
    a second warm() sets nothing again; without mallopt it does nothing."""
    calls = []

    def cdll(name):
        assert name is None
        if libc == "no libc":
            raise OSError("no such library")
        return _Libc(calls) if libc == "glibc" else object()

    monkeypatch.setattr(native, "_arenas_set", False)
    monkeypatch.setattr(native.ctypes, "CDLL", cdll)
    native._grow_thread_arenas_whole()
    native._grow_thread_arenas_whole()
    want = [(-3, 32 << 20), (-2, 64 << 20), (-1, 128 << 20)]
    assert calls == (want if libc == "glibc" else [])
    assert native._arenas_set is True


def test_warm_reaches_the_arena_setting(monkeypatch):
    seen = []
    monkeypatch.setattr(native, "_grow_thread_arenas_whole", lambda: seen.append(1))
    native.warm()
    assert seen == [1]
