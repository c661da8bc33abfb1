"""Kernel ≡ host byte-equality: the framework's central correctness contract.

Every jitted fold/merge must produce exactly the canonical serialized state
the host-reference engine produces (SURVEY.md §7: "byte-identical resulting
state").  Runs on the virtual CPU mesh in CI; the same code path runs on TPU
in bench.py.
"""

import uuid

import numpy as np
from _hyp import given, settings, st  # hypothesis, or skip-stubs

from crdt_enc_tpu.models import (
    GCounter,
    LWWMap,
    MVReg,
    ORSet,
    PNCounter,
    canonical_bytes,
)
from crdt_enc_tpu import ops as K

ACTORS = [uuid.UUID(int=i + 1).bytes for i in range(5)]
MEMBERS = [b"a", b"b", b"c", b"d"]

orset_script = st.lists(
    st.tuples(
        st.integers(0, len(ACTORS) - 1),
        st.sampled_from(["add", "rm"]),
        st.integers(0, len(MEMBERS) - 1),
    ),
    max_size=30,
)


def run_script(script, state=None):
    state = state if state is not None else ORSet()
    ops = []
    for actor_i, kind, member_i in script:
        actor, member = ACTORS[actor_i], MEMBERS[member_i]
        if kind == "add":
            op = state.add_ctx(actor, member)
        else:
            op = state.rm_ctx(member)
            if op.ctx.is_empty():
                continue
        state.apply(op)
        ops.append(op)
    return state, ops


def _bucket(n: int) -> int:
    """Next power-of-two padding bucket — bounds jit recompilation."""
    b = 8
    while b < n:
        b *= 2
    return b


def fixed_vocabs():
    """Full fixed vocabularies so kernel shapes are identical across
    hypothesis examples (one compilation, hundreds of examples)."""
    return K.Vocab(MEMBERS), K.Vocab(ACTORS)


def fold_on_device(initial: ORSet, ops, pad_to=None, **fold_kw):
    """Host initial state + op batch → kernel fold → host state."""
    members, replicas = fixed_vocabs()
    clock0, add0, rm0 = K.orset_state_to_planes(initial, members, replicas)
    cols = K.orset_ops_to_columns(ops, members, replicas)
    E, R = len(members), len(replicas)
    n = len(cols.kind)
    pad_to = max(pad_to or 0, _bucket(n))
    if pad_to > n:  # bucket padding with sentinel rows
        padn = pad_to - n
        cols.kind = np.concatenate([cols.kind, np.zeros(padn, np.int8)])
        cols.member = np.concatenate([cols.member, np.zeros(padn, np.int32)])
        cols.actor = np.concatenate([cols.actor, np.full(padn, R, np.int32)])
        cols.counter = np.concatenate([cols.counter, np.zeros(padn, np.int32)])
    clock, add, rm = K.orset_fold(
        clock0,
        add0,
        rm0,
        cols.kind,
        cols.member,
        cols.actor,
        cols.counter,
        num_members=E,
        num_replicas=R,
        **fold_kw,
    )
    return K.orset_planes_to_state(clock, add, rm, members, replicas)


@settings(max_examples=120, deadline=None)
@given(orset_script)
def test_orset_fold_matches_host(script):
    host, ops = run_script(script)
    if not ops:
        return
    device = fold_on_device(ORSet(), ops)
    assert canonical_bytes(device) == canonical_bytes(host)


@settings(max_examples=60, deadline=None)
@given(orset_script)
def test_orset_fold_sorted_segments_matches_host(script):
    """The sorted-scatter variant must be bit-identical to the default."""
    host, ops = run_script(script)
    if not ops:
        return
    device = fold_on_device(
        ORSet(), ops, impl="two_pass", sort_segments=True
    )
    assert canonical_bytes(device) == canonical_bytes(host)


@settings(max_examples=60, deadline=None)
@given(orset_script)
def test_orset_fold_two_pass_matches_host(script):
    """The original two-scatter variant must stay bit-identical."""
    host, ops = run_script(script)
    if not ops:
        return
    device = fold_on_device(ORSet(), ops, impl="two_pass")
    assert canonical_bytes(device) == canonical_bytes(host)


@settings(max_examples=60, deadline=None)
@given(orset_script, orset_script)
def test_orset_fold_fused_i16_from_nonempty_state(script_a, script_b):
    """int16 fast path (counters < 2**15), incl. nonzero initial planes."""
    base, _ = run_script(script_a)
    host2, ops = run_script(script_b, ORSet.from_obj(base.to_obj()))
    if not ops:
        return
    device = fold_on_device(
        ORSet.from_obj(base.to_obj()), ops, small_counters=True
    )
    assert canonical_bytes(device) == canonical_bytes(host2)


@settings(max_examples=60, deadline=None)
@given(orset_script, orset_script)
def test_orset_fold_from_nonempty_state(script_a, script_b):
    base, _ = run_script(script_a)
    host = ORSet.from_obj(base.to_obj())
    host2, ops = run_script(script_b, host)
    if not ops:
        return
    device = fold_on_device(ORSet.from_obj(base.to_obj()), ops)
    assert canonical_bytes(device) == canonical_bytes(host2)


def test_orset_fold_with_padding():
    host, ops = run_script([(0, "add", 0), (1, "add", 1), (0, "rm", 0), (2, "add", 0)])
    device = fold_on_device(ORSet(), ops, pad_to=64)
    assert canonical_bytes(device) == canonical_bytes(host)


@settings(max_examples=60, deadline=None)
@given(orset_script, orset_script)
def test_orset_merge_matches_host(script_a, script_b):
    sa, _ = run_script(script_a)
    sb, _ = run_script(script_b)
    host = ORSet.from_obj(sa.to_obj())
    host.merge(sb)

    members, replicas = fixed_vocabs()
    ca, aa, ra = K.orset_state_to_planes(sa, members, replicas)
    cb, ab, rb = K.orset_state_to_planes(sb, members, replicas)
    clock, add, rm = K.orset_merge(ca, aa, ra, cb, ab, rb)
    device = K.orset_planes_to_state(clock, add, rm, members, replicas)
    assert canonical_bytes(device) == canonical_bytes(host)


def test_orset_merge_many_tree():
    states = []
    for i in range(5):
        s, _ = run_script([(i % 5, "add", i % 4), ((i + 1) % 5, "add", (i + 2) % 4)])
        states.append(s)
    host = ORSet()
    for s in states:
        host.merge(s)

    members, replicas = fixed_vocabs()
    planes = [K.orset_state_to_planes(s, members, replicas) for s in states]
    clocks = np.stack([p[0] for p in planes])
    adds = np.stack([p[1] for p in planes])
    rms = np.stack([p[2] for p in planes])
    clock, add, rm = K.orset_merge_many(clocks, adds, rms)
    device = K.orset_planes_to_state(clock, add, rm, members, replicas)
    assert canonical_bytes(device) == canonical_bytes(host)


# ---- counters ------------------------------------------------------------


@settings(max_examples=80, deadline=None)
@given(
    st.lists(
        st.tuples(
            st.integers(0, 4), st.sampled_from(["inc", "dec"]), st.integers(1, 6)
        ),
        min_size=1,
        max_size=40,
    )
)
def test_pncounter_fold_matches_host(script):
    host = PNCounter()
    ops = []
    for actor_i, kind, steps in script:
        a = ACTORS[actor_i]
        op = host.inc(a, steps) if kind == "inc" else host.dec(a, steps)
        host.apply(op)
        ops.append(op)
    cols = K.counter_ops_to_columns(ops, replicas=K.Vocab(ACTORS))
    R = len(cols.replicas)
    n_rows = len(cols.sign)
    pad = _bucket(n_rows) - n_rows
    sign = np.concatenate([cols.sign, np.zeros(pad, np.int8)])
    actor = np.concatenate([cols.actor, np.full(pad, R, np.int32)])
    counter = np.concatenate([cols.counter, np.zeros(pad, np.int32)])
    p0 = np.zeros(R, np.int32)
    n0 = np.zeros(R, np.int32)
    p, n, value = K.pncounter_fold(p0, n0, sign, actor, counter, num_replicas=R)
    device = PNCounter(
        GCounter(K.dense_to_vclock(p, cols.replicas)),
        GCounter(K.dense_to_vclock(n, cols.replicas)),
    )
    assert int(value) == host.read()
    assert canonical_bytes(device) == canonical_bytes(host)


def test_gcounter_fold_matches_host():
    host = GCounter()
    ops = []
    for i in range(20):
        op = host.inc(ACTORS[i % 5], (i % 3) + 1)
        host.apply(op)
        ops.append(op)
    cols = K.counter_ops_to_columns(ops)
    R = len(cols.replicas)
    clock, value = K.gcounter_fold(
        np.zeros(R, np.int32), cols.actor, cols.counter, num_replicas=R
    )
    device = GCounter(K.dense_to_vclock(clock, cols.replicas))
    assert int(value) == host.read()
    assert canonical_bytes(device) == canonical_bytes(host)


# ---- LWW -----------------------------------------------------------------


@settings(max_examples=80, deadline=None)
@given(
    st.lists(
        st.tuples(
            st.integers(0, 4),  # actor
            st.integers(0, 3),  # key
            st.integers(0, 15),  # ts
            st.integers(0, 4),  # value
            st.booleans(),
        ),
        min_size=1,
        max_size=30,
    )
)
def test_lww_fold_matches_host(script):
    host = LWWMap()
    ops = []
    for actor_i, key_i, ts, val, tomb in script:
        a = ACTORS[actor_i]
        op = host.delete(key_i, ts, a) if tomb else host.put(key_i, ts, a, val)
        host.apply(op)
        ops.append(op)
    device = lww_fold_on_device(ops, keys=K.Vocab([0, 1, 2, 3]))
    assert canonical_bytes(device) == canonical_bytes(host)


def lww_fold_on_device(ops, keys=None) -> LWWMap:
    cols = K.lww_ops_to_columns(ops, keys=keys)
    Kn = len(cols.keys)
    n_rows = len(cols.key)
    pad = _bucket(n_rows) - n_rows
    key = np.concatenate([cols.key, np.full(pad, Kn, np.int32)])
    ts_hi = np.concatenate([cols.ts_hi, np.zeros(pad, np.int32)])
    ts_lo = np.concatenate([cols.ts_lo, np.zeros(pad, np.int32)])
    actor = np.concatenate([cols.actor, np.zeros(pad, np.int32)])
    value = np.concatenate([cols.value, np.zeros(pad, np.int32)])
    m_hi, m_lo, m_actor, m_value, present = K.lww_fold(
        key, ts_hi, ts_lo, actor, value, num_keys=Kn
    )
    device = LWWMap()
    for k in range(Kn):
        if not bool(present[k]):
            continue  # key in vocab but no ops touched it
        ts = (int(m_hi[k]) << 31) | int(m_lo[k])
        val = cols.values_sorted[int(m_value[k])]
        # find tombstone-ness: winner rows with this (key, ts, actor, value)
        mask = (
            (cols.key == k)
            & (cols.ts_hi == int(m_hi[k]))
            & (cols.ts_lo == int(m_lo[k]))
            & (cols.actor == int(m_actor[k]))
            & (cols.value == int(m_value[k]))
        )
        tomb = bool(cols.tombstone[np.nonzero(mask)[0][0]])
        device.entries[cols.keys.items[k]] = [
            ts,
            cols.actors_sorted[int(m_actor[k])],
            None if tomb else val,
            tomb,
        ]
    return device


def test_lww_fold_large_timestamps():
    # unix-nanos-scale timestamps must not truncate (the int32/x64 trap)
    base = 1_753_000_000_000_000_000  # ≈ 2025 in unix nanos
    host = LWWMap()
    ops = []
    for i, (ts, a) in enumerate(
        [(base + 5, 0), (base + 9, 1), (base + 9, 2), (base + 1, 3)]
    ):
        op = host.put(b"k", ts, ACTORS[a], i)
        host.apply(op)
        ops.append(op)
    device = lww_fold_on_device(ops)
    assert canonical_bytes(device) == canonical_bytes(host)
    assert device.get(b"k") == 2  # ts tie at base+9 → higher actor wins


def test_lww_fold_into_equals_fold_of_whole():
    # fold(A ++ B) == fold_into(fold(A), B): the incremental fold is exact
    rng = np.random.default_rng(11)
    Kn, n = 8, 64
    key = rng.integers(0, Kn, n).astype(np.int32)
    ts_hi = rng.integers(0, 4, n).astype(np.int32)
    ts_lo = rng.integers(0, 100, n).astype(np.int32)
    actor = rng.integers(0, 5, n).astype(np.int32)
    value = rng.integers(0, 20, n).astype(np.int32)

    whole = K.lww_fold(key, ts_hi, ts_lo, actor, value, num_keys=Kn)
    h = n // 2
    first = K.lww_fold(key[:h], ts_hi[:h], ts_lo[:h], actor[:h], value[:h], num_keys=Kn)
    second = K.lww_fold_into(
        first, key[h:], ts_hi[h:], ts_lo[h:], actor[h:], value[h:], num_keys=Kn
    )
    for a, b in zip(whole, second):
        assert np.array_equal(np.asarray(a), np.asarray(b))


# ---- MVReg ---------------------------------------------------------------


def test_mvreg_dominance_matches_host():
    r1, r2, r3 = MVReg(), MVReg(), MVReg()
    r1.apply(r1.write_ctx(ACTORS[0], b"a"))
    r2.apply(r2.write_ctx(ACTORS[1], b"b"))
    r3.merge(r1)
    r3.apply(r3.write_ctx(ACTORS[2], b"c"))  # supersedes r1's write
    host = MVReg()
    for r in (r1, r2, r3):
        host.merge(r)

    pairs = []
    for r in (r1, r2, r3):
        pairs.extend(r.vals)
    # host-side (clock, value) dedup per kernel contract
    seen = {}
    for c, v in pairs:
        seen[canonical_bytes(MVReg([(c, v)]))] = (c, v)
    pairs = list(seen.values())
    replicas = K.Vocab()
    for c, _ in pairs:
        for a in c.counters:
            replicas.intern(a)
    clocks = np.stack([K.vclock_to_dense(c, replicas) for c, _ in pairs])
    keep = K.mvreg_dominance_keep(clocks, np.ones(len(pairs), bool))
    device = MVReg([p for p, k in zip(pairs, keep.tolist()) if k])
    device._canonicalize()
    assert canonical_bytes(device) == canonical_bytes(host)


def test_orset_fold_coo_matches_dense():
    """The device sparse kernel (sort + run-max COO) must agree with the
    dense scatter fold, including against a non-zero starting clock."""
    from crdt_enc_tpu.ops.columnar import orset_apply_coo, orset_planes_to_state

    rng = np.random.default_rng(21)
    E, R, n = 16, 8, 256
    kind = (rng.random(n) < 0.3).astype(np.int8)
    member = rng.integers(0, E, n).astype(np.int32)
    actor = rng.integers(0, R + 1, n).astype(np.int32)  # R ⇒ padding rows
    counter = rng.integers(1, 12, n).astype(np.int32)
    clock0 = rng.integers(0, 4, R).astype(np.int32)

    members = K.Vocab(range(E))
    replicas = K.Vocab(ACTORS[:R]) if len(ACTORS) >= R else K.Vocab(
        [bytes([i] * 16) for i in range(R)]
    )

    dense = K.orset_fold(
        clock0, np.zeros((E, R), np.int32), np.zeros((E, R), np.int32),
        kind, member, actor, counter, num_members=E, num_replicas=R,
    )
    dense_state = orset_planes_to_state(
        np.asarray(dense[0]), np.asarray(dense[1]), np.asarray(dense[2]),
        members, replicas,
    )

    clock, skey, smax, is_max = K.orset_fold_coo(
        clock0, kind, member, actor, counter, num_members=E, num_replicas=R
    )
    coo_state = ORSet()
    # seed the starting clock exactly as the accel does
    coo_state.clock = K.dense_to_vclock(clock0, replicas)
    orset_apply_coo(
        coo_state, np.asarray(clock), np.asarray(skey), np.asarray(smax),
        np.asarray(is_max), members, replicas,
    )
    assert canonical_bytes(coo_state) == canonical_bytes(dense_state)


def test_orset_fold_stream_matches_whole_batch():
    """Chunked/donated streaming fold ≡ whole-batch fold ≡ host, on a
    causal history (the delivery contract the core guarantees)."""
    host, ops = run_script(
        [(i % 5, "add" if i % 4 else "rm", i % 4) for i in range(120)]
    )
    if not ops:
        return
    members, replicas = fixed_vocabs()
    cols = K.orset_ops_to_columns(ops, members, replicas)
    E, R = len(members), len(replicas)

    whole = fold_on_device(ORSet(), ops)

    clock, add, rm = K.orset_fold_stream(
        np.zeros(R, np.int32), np.zeros((E, R), np.int32),
        np.zeros((E, R), np.int32),
        K.iter_orset_chunks(cols.kind, cols.member, cols.actor, cols.counter,
                            chunk_rows=16, num_replicas=R),
        num_members=E, num_replicas=R,
    )
    streamed = K.orset_planes_to_state(
        np.asarray(clock), np.asarray(add), np.asarray(rm), members, replicas
    )
    assert canonical_bytes(streamed) == canonical_bytes(whole)
    assert canonical_bytes(streamed) == canonical_bytes(host)

    # the Pallas chunk route (interpret mode here; real MXU on TPU) must
    # produce the same planes; one tile_cap over the whole member column
    from crdt_enc_tpu.ops.pallas_fold import fold_cap

    clock, add, rm = K.orset_fold_stream(
        np.zeros(R, np.int32), np.zeros((E, R), np.int32),
        np.zeros((E, R), np.int32),
        K.iter_orset_chunks(cols.kind, cols.member, cols.actor, cols.counter,
                            chunk_rows=16, num_replicas=R),
        num_members=E, num_replicas=R, impl="pallas",
        tile_cap=fold_cap(cols.member, E), interpret=True,
    )
    streamed_p = K.orset_planes_to_state(
        np.asarray(clock), np.asarray(add), np.asarray(rm), members, replicas
    )
    assert canonical_bytes(streamed_p) == canonical_bytes(host)


# ---- round 5: sorted segment-max counter path (sort + run-end gather)


def test_counter_sorted_vs_scatter_paths():
    """The sorted (N ≥ SORTED_MIN_ROWS) and scatter routes must agree
    exactly — including pad rows, empty segments, and ties — and both
    must match a numpy reference."""
    import numpy as np

    import crdt_enc_tpu.ops.counters as C

    rng = np.random.default_rng(17)
    for R in (1, 7, 1000):
        N = 9000  # above SORTED_MIN_ROWS → sorted path
        actor = rng.integers(0, R + 1, N).astype(np.int32)
        sign = (rng.random(N) < 0.5).astype(np.int8)
        counter = rng.integers(0, 1 << 14, N).astype(np.int32)
        p0 = rng.integers(0, 100, R).astype(np.int32)
        n0 = rng.integers(0, 100, R).astype(np.int32)
        pe, ne = p0.copy(), n0.copy()
        for a, s, c in zip(actor, sign, counter):
            if a >= R:
                continue
            if s == 0:
                pe[a] = max(pe[a], c)
            else:
                ne[a] = max(ne[a], c)
        p, n, v = C.pncounter_fold(p0, n0, sign, actor, counter,
                                   num_replicas=R)
        np.testing.assert_array_equal(np.asarray(p), pe)
        np.testing.assert_array_equal(np.asarray(n), ne)
        assert int(v) == int(pe.sum()) - int(ne.sum())
        # scatter route on the same data (shrunk below the threshold)
        cut = C.SORTED_MIN_ROWS - 1
        ps, ns, _ = C.pncounter_fold(p0, n0, sign[:cut], actor[:cut],
                                     counter[:cut], num_replicas=R)
        pe2, ne2 = p0.copy(), n0.copy()
        for a, s, c in zip(actor[:cut], sign[:cut], counter[:cut]):
            if a >= R:
                continue
            if s == 0:
                pe2[a] = max(pe2[a], c)
            else:
                ne2[a] = max(ne2[a], c)
        np.testing.assert_array_equal(np.asarray(ps), pe2)
        np.testing.assert_array_equal(np.asarray(ns), ne2)


def test_counter_sorted_hypothesis():
    from _hyp import given, settings, st  # hypothesis, or skip-stubs

    import numpy as np

    import crdt_enc_tpu.ops.counters as C

    @settings(max_examples=25, deadline=None)
    @given(
        seed=st.integers(0, 2**31 - 1),
        r=st.integers(1, 40),
        pad_frac=st.floats(0, 0.5),
    )
    def run(seed, r, pad_frac):
        rng = np.random.default_rng(seed)
        # force the sorted route regardless of batch size by routing on
        # a monkeypatched threshold — the public API stays untouched
        N = 400
        actor = rng.integers(0, r, N).astype(np.int32)
        pad = rng.random(N) < pad_frac
        actor = np.where(pad, r, actor).astype(np.int32)
        counter = rng.integers(0, 3000, N).astype(np.int32)
        clock0 = rng.integers(0, 1500, r).astype(np.int32)
        ce = clock0.copy()
        for a, c in zip(actor, counter):
            if a < r:
                ce[a] = max(ce[a], c)
        old = C.SORTED_MIN_ROWS
        C.SORTED_MIN_ROWS = 1
        try:
            ck, tot = C.gcounter_fold.__wrapped__(
                clock0, actor, counter, num_replicas=r)
        finally:
            C.SORTED_MIN_ROWS = old
        np.testing.assert_array_equal(np.asarray(ck), ce)
        assert int(tot) == int(ce.sum())

    run()
