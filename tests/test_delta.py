"""Delta-state replication + composed adapters (ISSUE 10).

The acceptance gate is differential: a consumer that folds
``full-at-base + delta chain`` must end byte-identical to one that
re-reads every full snapshot — across adapters (including the composed
resettable counter), across storage backends, and under every doubt
path (gap, GC'd link, torn file, wrong adapter, no base), where the
fallback to the snapshot path must be automatic and traced.
"""

import asyncio
import random

import pytest

from crdt_enc_tpu.backends import (
    FsStorage,
    IdentityCryptor,
    MemoryRemote,
    MemoryStorage,
    PlainKeyCryptor,
)
from crdt_enc_tpu.core import (
    Core,
    OpenOptions,
    gcounter_adapter,
    gset_adapter,
    orset_adapter,
    pncounter_adapter,
)
from crdt_enc_tpu.delta import (
    MAX_CHAIN,
    ResettableCounter,
    UndoError,
    codec_for,
    rcounter_adapter,
)
from crdt_enc_tpu.delta import wire as delta_wire
from crdt_enc_tpu.models import ORSet, canonical_bytes
from crdt_enc_tpu.utils import codec, trace
from crdt_enc_tpu.utils.versions import DEFAULT_DATA_VERSION_1


def run(coro):
    return asyncio.run(coro)


def make_opts(storage, adapter, create=True, **kw):
    return OpenOptions(
        storage=storage,
        cryptor=IdentityCryptor(),
        key_cryptor=PlainKeyCryptor(),
        adapter=adapter,
        supported_data_versions=(DEFAULT_DATA_VERSION_1,),
        current_data_version=DEFAULT_DATA_VERSION_1,
        create=create,
        **kw,
    )


@pytest.fixture(params=["memory", "fs"])
def storage_factory(request, tmp_path):
    if request.param == "memory":
        remote = MemoryRemote()
        instances: dict = {}

        def make(name="a"):
            return instances.setdefault(name, MemoryStorage(remote))

        make.remote = remote
        return make
    remote_dir = tmp_path / "remote"

    def make(name="a"):
        return FsStorage(str(tmp_path / f"local-{name}"), str(remote_dir))

    make.remote = None
    return make


async def apply_each(core, builders):
    """One op file per builder — dots mint against the live state, the
    way real writers interleave build/apply."""
    for build in builders:
        await core.update(build)


def counters():
    return trace.snapshot()["counters"]


# ---- codec unit level ------------------------------------------------------


def _rand_orset_history(seed, n_actors=4, n_members=10, n_ops=120):
    """Three causally related Orswot states: base B, its extension N
    (same replica after more folding), and a consumer X that merged B
    and then independently folded more third-party ops — the exact
    precondition shape the codec contract names."""
    rng = random.Random(seed)
    actors = [bytes([i]) * 16 for i in range(n_actors)]
    members = [b"m%d" % i for i in range(n_members)]

    producer = ORSet()
    third = ORSet()  # a peer whose ops only X sees

    def mutate(s, owner):
        m = rng.choice(members)
        if rng.random() < 0.65 or not s.contains(m):
            s.apply(s.add_ctx(owner, m))
        else:
            s.apply(s.rm_ctx(m))

    for _ in range(n_ops):
        mutate(producer, actors[0])
    base = ORSet.from_obj(producer.to_obj())

    X = ORSet.from_obj(producer.to_obj())  # X merged the base exactly
    for _ in range(n_ops // 2):
        mutate(third, actors[1])
    X.merge(third)
    for _ in range(n_ops // 3):
        mutate(X, actors[2])

    # the producer keeps going: more own ops AND it folds some of the
    # third party too (so the window kills dots X independently holds)
    for _ in range(n_ops):
        mutate(producer, actors[0])
    half = ORSet.from_obj(third.to_obj())
    producer.merge(half)
    for _ in range(n_ops // 4):
        mutate(producer, actors[3])
    new = ORSet.from_obj(producer.to_obj())
    return base, new, X


@pytest.mark.parametrize("seed", range(8))
def test_orset_delta_apply_equals_full_merge(seed):
    from crdt_enc_tpu.delta.codec import orset_delta_apply, orset_delta_diff

    base, new, consumer = _rand_orset_history(seed)
    dobj = orset_delta_diff(base, new)
    # the delta must survive the wire (msgpack round-trip)
    dobj = codec.unpack(codec.pack(dobj))

    via_delta = ORSet.from_obj(consumer.to_obj())
    orset_delta_apply(via_delta, dobj)
    via_merge = ORSet.from_obj(consumer.to_obj())
    via_merge.merge(new)
    assert canonical_bytes(via_delta) == canonical_bytes(via_merge)

    # and on the base itself (the sealer's self-verify shape)
    refold = ORSet.from_obj(base.to_obj())
    orset_delta_apply(refold, dobj)
    assert canonical_bytes(refold) == canonical_bytes(new)


def test_orset_delta_remove_only_window():
    """Removes never advance the Orswot clock, so a remove-only delta
    has an empty window — the apply's cheap path — and must still kill
    exactly the removed dots."""
    from crdt_enc_tpu.delta.codec import orset_delta_apply, orset_delta_diff

    a = bytes([7]) * 16
    s = ORSet()
    for m in (b"x", b"y", b"z"):
        s.apply(s.add_ctx(a, m))
    base = ORSet.from_obj(s.to_obj())
    s.apply(s.rm_ctx(b"y"))
    new = ORSet.from_obj(s.to_obj())
    dobj = orset_delta_diff(base, new)
    assert not dobj[b"e"]  # no adds: pure removal
    consumer = ORSet.from_obj(base.to_obj())
    orset_delta_apply(consumer, dobj)
    assert canonical_bytes(consumer) == canonical_bytes(new)


def test_counter_and_gset_codecs_are_sub_lattices():
    from crdt_enc_tpu.models import GCounter, GSet, PNCounter

    for make, mutate in (
        (GCounter, lambda s, a, i: s.apply(s.inc(a, i + 1))),
        (PNCounter, lambda s, a, i: s.apply(
            s.inc(a, i + 1) if i % 3 else s.dec(a, i + 1))),
        (GSet, lambda s, a, i: s.apply(b"m%d" % i)),
    ):
        name = {GCounter: b"gcounter", PNCounter: b"pncounter",
                GSet: b"gset"}[make]
        cdc = codec_for(name)
        a, b = bytes([1]) * 16, bytes([2]) * 16
        s = make()
        for i in range(6):
            mutate(s, a, i)
        base = make.from_obj(codec.unpack(codec.pack(s.to_obj())))
        for i in range(6, 12):
            mutate(s, a, i)
        new = make.from_obj(codec.unpack(codec.pack(s.to_obj())))
        dobj = codec.unpack(codec.pack(cdc.diff(base, new)))
        # consumer ahead of the base on another actor
        consumer = make.from_obj(codec.unpack(codec.pack(base.to_obj())))
        mutate(consumer, b, 20)
        via_merge = make.from_obj(codec.unpack(codec.pack(consumer.to_obj())))
        via_merge.merge(new)
        cdc.apply(consumer, dobj)
        assert canonical_bytes(consumer) == canonical_bytes(via_merge)


def test_delta_wire_rejects_malformed():
    rec = delta_wire.DeltaRecord(
        base_name="b", new_name="n",
        base_cursor=__import__(
            "crdt_enc_tpu.models.vclock", fromlist=["VClock"]).VClock(),
        new_cursor=__import__(
            "crdt_enc_tpu.models.vclock", fromlist=["VClock"]).VClock(),
        sealer=b"\x01" * 16, adapter=b"orset", watermark={}, delta_obj={},
    )
    good = delta_wire.build_delta_obj(rec)
    assert delta_wire.parse_delta_obj(
        codec.unpack(codec.pack(good))
    ).new_name == "n"
    for breakage in (
        lambda o: o.pop(b"wm"),            # missing base watermark
        lambda o: o.pop(b"new"),
        lambda o: o.pop(b"d"),
        lambda o: o.__setitem__(b"s", b"short"),
        lambda o: o.__setitem__(b"v", 99),
    ):
        bad = dict(good)
        breakage(bad)
        with pytest.raises(ValueError):
            delta_wire.parse_delta_obj(bad)


# ---- core differential: delta path ≡ snapshot path -------------------------

ADAPTER_CASES = {
    "orset": (
        orset_adapter,
        lambda actor, r: [
            (lambda s, m=b"m%d-%d" % (r, i): s.add_ctx(actor, m))
            for i in range(6)
        ] + [(lambda s, m=b"m%d-0" % max(0, r - 1):
              s.rm_ctx(m) if s.contains(m) else None)],
    ),
    "rcounter": (
        rcounter_adapter,
        lambda actor, r: [
            (lambda s: ResettableCounter.inc(s, actor, r + 1))
            for _ in range(5)
        ] + ([lambda s: ResettableCounter.reset(s)] if r == 2 else []),
    ),
    "gcounter": (
        gcounter_adapter,
        lambda actor, r: [(lambda s: s.inc(actor, r + 1))] * 4,
    ),
    "pncounter": (
        pncounter_adapter,
        lambda actor, r: [
            (lambda s: s.inc(actor, r + 2)), (lambda s: s.dec(actor, 1))
        ] * 2,
    ),
    "gset": (
        gset_adapter,
        lambda actor, r: [
            (lambda s, m=b"g%d-%d" % (r, i): s.insert_ctx(m))
            for i in range(5)
        ],
    ),
}


@pytest.mark.parametrize("which", sorted(ADAPTER_CASES))
def test_differential_delta_vs_snapshot_path(storage_factory, which):
    """≥3 adapters × memory+fs: after R producer compactions, a chained
    delta consumer and a full-snapshot consumer are byte-identical —
    and the delta consumer really did use the chain."""
    make_adapter, round_ops = ADAPTER_CASES[which]

    async def go():
        producer = await Core.open(
            make_opts(storage_factory("p"), make_adapter())
        )
        c_delta = await Core.open(
            make_opts(storage_factory("cd"), make_adapter())
        )
        c_snap = await Core.open(
            make_opts(storage_factory("cs"), make_adapter(), delta=False)
        )
        # a fleet of seed writers widens the state (multi-actor clocks)
        # so a one-writer round's delta beats the full snapshot even for
        # counter types, whose whole state is one small clock
        for w in range(6):
            writer = await Core.open(
                make_opts(storage_factory(f"w{w}"), make_adapter())
            )
            await apply_each(writer, round_ops(writer.actor_id, 0))
        # round 0 builds a base big enough that deltas beat full states
        await apply_each(
            producer,
            [b for r in range(3) for b in round_ops(producer.actor_id, r)],
        )
        await producer.compact()
        await c_delta.read_remote()
        await c_snap.read_remote()
        applied_total = 0
        for r in range(3, 7):
            await apply_each(producer, round_ops(producer.actor_id, r))
            await producer.compact()
            trace.reset()
            await c_delta.read_remote()
            applied_total += counters().get("delta_applied", 0)
            await c_snap.read_remote()
            assert (
                c_delta.with_state(canonical_bytes)
                == c_snap.with_state(canonical_bytes)
                == producer.with_state(canonical_bytes)
            ), f"{which}: delta path diverged at round {r}"
            assert (
                c_delta.info().next_op_versions
                == c_snap.info().next_op_versions
            )
        assert applied_total > 0, f"{which}: chain never applied"

    run(go())


def test_delta_files_smaller_than_snapshots(storage_factory):
    """The point of the subsystem: on an incremental workload the delta
    payloads are a small fraction of the snapshot they replace."""

    async def go():
        producer = await Core.open(
            make_opts(storage_factory("p"), orset_adapter())
        )
        for i in range(150):
            m = b"member-%04d" % i
            await producer.update(lambda s, m=m: s.add_ctx(producer.actor_id, m))
        await producer.compact()
        trace.reset()
        await producer.update(
            lambda s: s.add_ctx(producer.actor_id, b"tail-1")
        )
        await producer.compact()
        c = counters()
        assert c.get("delta_files_sealed") == 1
        snap_bytes = None
        names = await producer.storage.list_state_names()
        loaded = await producer.storage.load_states(names)
        snap_bytes = max(len(raw) for _, raw in loaded)
        assert c["delta_bytes_sealed"] * 5 <= snap_bytes

    run(go())


# ---- fallbacks: every doubt path reads the full snapshot -------------------


def test_fallback_on_gc_mid_chain(storage_factory):
    async def go():
        producer = await Core.open(
            make_opts(storage_factory("p"), orset_adapter())
        )
        consumer = await Core.open(
            make_opts(storage_factory("c"), orset_adapter())
        )
        for i in range(80):
            await producer.update(
                lambda s, m=b"m%d" % i: s.add_ctx(producer.actor_id, m)
            )
        await producer.compact()
        await consumer.read_remote()
        await producer.update(lambda s: s.add_ctx(producer.actor_id, b"t1"))
        await producer.compact()
        # the hostile move: the whole delta log vanishes mid-chain
        await producer.storage.remove_deltas([(producer.actor_id, 1 << 62)])
        await producer.update(lambda s: s.add_ctx(producer.actor_id, b"t2"))
        await producer.compact()
        await producer.storage.remove_deltas([(producer.actor_id, 1 << 62)])
        trace.reset()
        await consumer.read_remote()
        c = counters()
        assert not c.get("delta_applied")
        assert consumer.with_state(canonical_bytes) == producer.with_state(
            canonical_bytes
        )
        # next round the consumer re-anchors at the full snapshot it
        # just read and rejoins the chain
        await producer.update(lambda s: s.add_ctx(producer.actor_id, b"t3"))
        await producer.compact()
        trace.reset()
        await consumer.read_remote()
        assert counters().get("delta_applied") == 1
        assert consumer.with_state(canonical_bytes) == producer.with_state(
            canonical_bytes
        )

    run(go())


def test_compacting_consumer_keeps_a_base_it_has_gcd_and_counts_a_gap(
    storage_factory,
):
    """Two compactors on one remote (ISSUE 32).  B's compact() GCs the
    snapshot of A it merged and drops the name from ``read_states``; A's
    next link names exactly that snapshot as its base.  The base
    outlives its file (``merged_bases``), so B applies the link and
    loads no snapshot.  A link that never arrives costs one snapshot
    load, and is counted (reason ``gap``) when A's next link is read."""

    async def go():
        a = await Core.open(make_opts(storage_factory("a"), orset_adapter()))
        b = await Core.open(make_opts(storage_factory("b"), orset_adapter()))

        async def a_round(tag):
            await a.update(lambda s: s.add_ctx(a.actor_id, tag))
            await a.compact()

        async def b_round():
            trace.reset()
            await b.compact()
            assert b.with_state(canonical_bytes) == a.with_state(
                canonical_bytes
            )
            return counters()

        for i in range(80):
            await a.update(lambda s, m=b"m%d" % i: s.add_ctx(a.actor_id, m))
        await a.compact()
        c = await b_round()  # the baseless head: a whole snapshot
        assert c.get("states_merged") == 1 and not c.get("delta_applied")
        head = b._data.merged_bases[a.actor_id]
        assert head not in b._data.read_states, "merged, GC'd and forgotten"

        await a_round(b"t1")
        c = await b_round()
        assert c.get("delta_applied") == 1 and not c.get("states_merged")
        assert not c.get("delta_fallbacks")
        assert c.get("delta_passes") == 1 and c.get("delta_apply_slots") >= 80
        assert b._data.merged_bases[a.actor_id] != head

        # A's next link never arrives; its snapshot does
        await a_round(b"t2")
        await a.storage.remove_deltas([(a.actor_id, 1 << 62)])
        c = await b_round()
        assert c.get("states_merged") == 1 and not c.get("delta_applied")
        await a_round(b"t3")
        c = await b_round()
        assert c.get("delta_applied") == 1 and not c.get("states_merged")
        assert c.get("delta_fallbacks") == 1
        assert b.last_delta_fallback_reason == "gap"

    run(go())


def test_fallback_on_torn_delta_and_base_doubt(storage_factory):
    async def go():
        producer = await Core.open(
            make_opts(storage_factory("p"), orset_adapter())
        )
        late = await Core.open(
            make_opts(storage_factory("l"), orset_adapter())
        )
        for i in range(60):
            await producer.update(
                lambda s, m=b"m%d" % i: s.add_ctx(producer.actor_id, m)
            )
        await producer.compact()
        await producer.update(lambda s: s.add_ctx(producer.actor_id, b"x"))
        await producer.compact()
        # a consumer that never saw the base: base-name doubt → full read
        trace.reset()
        await late.read_remote()
        c = counters()
        assert c.get("delta_fallbacks", 0) >= 1
        assert late.last_delta_fallback_reason == "base_missing"
        assert not c.get("delta_applied")
        assert late.with_state(canonical_bytes) == producer.with_state(
            canonical_bytes
        )

        # torn delta file: unreadable → traced fallback, snapshot wins
        consumer = await Core.open(
            make_opts(storage_factory("c2"), orset_adapter())
        )
        await late.read_remote()
        await producer.update(lambda s: s.add_ctx(producer.actor_id, b"y"))
        await producer.compact()
        files = await producer.storage.load_deltas([(producer.actor_id, 1)])
        actor, version, raw = files[-1]
        await producer.storage.remove_deltas([(actor, version)])
        await producer.storage.store_delta(actor, version, raw[: len(raw) // 2])
        trace.reset()
        await consumer.read_remote()
        c = counters()
        assert c.get("delta_fallbacks", 0) >= 1
        assert consumer.with_state(canonical_bytes) == producer.with_state(
            canonical_bytes
        )

    run(go())


def test_fallback_on_adapter_mismatch(storage_factory):
    """A delta sealed by an orset fleet read by an rcounter-configured
    replica: fingerprint doubt (adapter name), full snapshot path."""

    async def go():
        producer = await Core.open(
            make_opts(storage_factory("p"), orset_adapter())
        )
        reader = await Core.open(
            make_opts(storage_factory("r"), rcounter_adapter())
        )
        for i in range(60):
            await producer.update(
                lambda s, m=b"m%d" % i: s.add_ctx(producer.actor_id, m)
            )
        await producer.compact()
        await reader.read_remote()
        await producer.update(lambda s: s.add_ctx(producer.actor_id, b"z"))
        await producer.compact()
        trace.reset()
        await reader.read_remote()
        assert reader.last_delta_fallback_reason == "adapter"
        assert not counters().get("delta_applied")
        assert reader.with_state(canonical_bytes) == producer.with_state(
            canonical_bytes
        )

    run(go())


def test_delta_disabled_seals_and_reads_nothing(storage_factory):
    async def go():
        producer = await Core.open(
            make_opts(storage_factory("p"), orset_adapter(), delta=False)
        )
        for i in range(40):
            await producer.update(
                lambda s, m=b"m%d" % i: s.add_ctx(producer.actor_id, m)
            )
        await producer.compact()
        await producer.update(lambda s: s.add_ctx(producer.actor_id, b"t"))
        await producer.compact()
        assert not await producer.storage.list_delta_actors()

    run(go())


# ---- GC discipline ---------------------------------------------------------


def test_compact_gcs_consumed_foreign_deltas(storage_factory):
    async def go():
        producer = await Core.open(
            make_opts(storage_factory("p"), orset_adapter())
        )
        compactor = await Core.open(
            make_opts(storage_factory("c"), orset_adapter())
        )
        for i in range(60):
            await producer.update(
                lambda s, m=b"m%d" % i: s.add_ctx(producer.actor_id, m)
            )
        await producer.compact()
        await compactor.read_remote()
        await producer.update(lambda s: s.add_ctx(producer.actor_id, b"t"))
        await producer.compact()
        assert await producer.storage.list_delta_actors() == [
            producer.actor_id
        ]
        # the second compactor consumes the chain, then its compaction
        # removes the consumed prefix (covered by its new snapshot)
        await compactor.compact()
        files = await compactor.storage.load_deltas([(producer.actor_id, 1)])
        assert files == []

    run(go())


def test_own_log_bounded_at_max_chain(storage_factory):
    async def go():
        producer = await Core.open(
            make_opts(storage_factory("p"), orset_adapter())
        )
        for i in range(80):
            await producer.update(
                lambda s, m=b"base%d" % i: s.add_ctx(producer.actor_id, m)
            )
        await producer.compact()
        for r in range(MAX_CHAIN + 4):
            await producer.update(
                lambda s, m=b"r%d" % r: s.add_ctx(producer.actor_id, m)
            )
            await producer.compact()
        files = await producer.storage.load_deltas([(producer.actor_id, 1)])
        versions = [v for _, v, _ in files]
        assert len(versions) == MAX_CHAIN
        assert max(versions) - min(versions) == MAX_CHAIN - 1

    run(go())


def test_deltaless_compact_wipes_own_stale_chain(storage_factory):
    """A cold reopen (no delta base) compacts without a delta; its old
    chain cannot extend to the new snapshot and is removed rather than
    left for every consumer to scan and fall back on."""

    async def go():
        producer = await Core.open(
            make_opts(storage_factory("p"), orset_adapter())
        )
        for i in range(60):
            await producer.update(
                lambda s, m=b"m%d" % i: s.add_ctx(producer.actor_id, m)
            )
        await producer.compact()
        await producer.update(lambda s: s.add_ctx(producer.actor_id, b"t"))
        await producer.compact()
        assert await producer.storage.load_deltas([(producer.actor_id, 1)])
        # cold restart: checkpoint disabled ⇒ no delta base survives
        reopened = await Core.open(
            make_opts(
                storage_factory("p"), orset_adapter(), create=False,
                checkpoint=False,
            )
        )
        await reopened.read_remote()
        await reopened.update(
            lambda s: s.add_ctx(reopened.actor_id, b"after")
        )
        await reopened.compact()
        assert not await reopened.storage.load_deltas(
            [(reopened.actor_id, 1)]
        )

    run(go())


def test_warm_reopen_extends_chain(storage_factory):
    """Checkpoint continuity (b"snap"): a warm-reopened compactor keeps
    sealing deltas against its pre-crash snapshot — the chain never
    breaks, and a steady consumer applies straight through."""

    async def go():
        producer = await Core.open(
            make_opts(storage_factory("p"), orset_adapter())
        )
        consumer = await Core.open(
            make_opts(storage_factory("c"), orset_adapter())
        )
        for i in range(60):
            await producer.update(
                lambda s, m=b"m%d" % i: s.add_ctx(producer.actor_id, m)
            )
        await producer.compact()
        await consumer.read_remote()
        reopened = await Core.open(
            make_opts(storage_factory("p"), orset_adapter(), create=False)
        )
        assert reopened.opened_from_checkpoint
        await reopened.update(
            lambda s: s.add_ctx(reopened.actor_id, b"post-reopen")
        )
        await reopened.compact()
        trace.reset()
        await consumer.read_remote()
        assert counters().get("delta_applied") == 1
        assert consumer.with_state(canonical_bytes) == reopened.with_state(
            canonical_bytes
        )

    run(go())


def test_stale_checkpoint_reanchors_chain_without_fsck_errors(storage_factory):
    """A reopen from a one-generation-stale checkpoint (the simulator's
    ``stale_checkpoint`` fault) re-anchors the delta chain at an EARLIER
    own snapshot.  The resulting link skips its predecessor's target —
    which must stay fsck-clean (warn at most), apply on consumers that
    hold the old anchor, and converge byte-identically."""

    async def go():
        producer = await Core.open(
            make_opts(storage_factory("p"), orset_adapter())
        )
        consumer = await Core.open(
            make_opts(storage_factory("c"), orset_adapter())
        )
        for i in range(70):
            await producer.update(
                lambda s, m=b"m%d" % i: s.add_ctx(producer.actor_id, m)
            )
        await producer.compact()  # S1
        await consumer.read_remote()
        await producer.update(lambda s: s.add_ctx(producer.actor_id, b"a"))
        await producer.compact()  # S2 + D1(S1→S2); checkpoint gen A
        stale_ckpt = await producer.storage.load_local_checkpoint()
        await consumer.read_remote()
        await producer.update(lambda s: s.add_ctx(producer.actor_id, b"b"))
        await producer.compact()  # S3 + D2(S2→S3); checkpoint gen B
        # the fault: the resume point lags one generation
        await producer.storage.store_local_checkpoint(stale_ckpt)
        reopened = await Core.open(
            make_opts(storage_factory("p"), orset_adapter(), create=False)
        )
        assert reopened.opened_from_checkpoint
        await reopened.read_remote()  # applies D2 from the old anchor
        await reopened.update(
            lambda s: s.add_ctx(reopened.actor_id, b"c")
        )
        await reopened.compact()  # S4 + D3(base = S2, not S3!)
        report = await _fsck(storage_factory("fsck"))
        assert report.ok, [str(i) for i in report.issues]
        trace.reset()
        await consumer.read_remote()
        assert counters().get("delta_applied", 0) >= 1
        assert consumer.with_state(canonical_bytes) == reopened.with_state(
            canonical_bytes
        )

    run(go())


# ---- composed resettable counter (semidirect product) ----------------------


def test_rcounter_inc_value_reset_undo():
    s = ORSet()
    a = bytes([3]) * 16
    op1 = ResettableCounter.inc(s, a, 5)
    s.apply(op1)
    op2 = ResettableCounter.inc(s, a, 2)
    s.apply(op2)
    assert ResettableCounter.value(s) == 7
    assert len(ResettableCounter.tokens(s)) == 2
    # exact inverse of one observed increment
    s.apply(ResettableCounter.undo(s, op1))
    assert ResettableCounter.value(s) == 2
    # undo twice: nothing left to invert
    with pytest.raises(UndoError):
        ResettableCounter.undo(s, op1)
    # resets admit no inverse (arXiv:2006.10494)
    rm_ops = ResettableCounter.reset(s)
    for op in rm_ops:
        with pytest.raises(UndoError):
            ResettableCounter.undo(s, op)
        s.apply(op)
    assert ResettableCounter.value(s) == 0


def test_rcounter_concurrent_inc_survives_reset(storage_factory):
    """The semidirect action law: a reset cancels what it observed; a
    concurrent unobserved increment survives."""

    async def go():
        a = await Core.open(
            make_opts(storage_factory("a"), rcounter_adapter())
        )
        b = await Core.open(
            make_opts(storage_factory("b"), rcounter_adapter())
        )
        await a.update(lambda s: ResettableCounter.inc(s, a.actor_id, 10))
        await b.read_remote()
        # concurrent: a increments again, b resets what it has seen (10)
        await a.update(lambda s: ResettableCounter.inc(s, a.actor_id, 4))
        await b.update(lambda s: ResettableCounter.reset(s))
        await a.read_remote()
        await b.read_remote()
        await a.read_remote()
        va = a.with_state(ResettableCounter.value)
        vb = b.with_state(ResettableCounter.value)
        assert va == vb == 4  # the unobserved +4 survived the reset

    run(go())


def test_rcounter_rides_device_kernels_and_delta_chain(storage_factory):
    """No new kernels: the composed counter folds through the OR-Set
    accelerator (TpuAccelerator on the CPU backend here) and replicates
    through the same delta chains, byte-identical to the host path."""
    from crdt_enc_tpu.parallel import TpuAccelerator

    async def go():
        producer = await Core.open(
            make_opts(
                storage_factory("p"), rcounter_adapter(),
                accelerator=TpuAccelerator(min_device_batch=1),
            )
        )
        host = await Core.open(
            make_opts(storage_factory("h"), rcounter_adapter())
        )
        for i in range(40):
            await producer.update(
                lambda s: ResettableCounter.inc(s, producer.actor_id, 1)
            )
        await producer.compact()
        await host.read_remote()
        await producer.update(
            lambda s: ResettableCounter.inc(s, producer.actor_id, 2)
        )
        await producer.compact()
        trace.reset()
        await host.read_remote()
        assert counters().get("delta_applied") == 1
        assert host.with_state(canonical_bytes) == producer.with_state(
            canonical_bytes
        )
        assert host.with_state(ResettableCounter.value) == 42

    run(go())


# ---- fsck: delta family validation -----------------------------------------


async def _fsck(storage):
    from crdt_enc_tpu.tools.fsck import fsck_remote

    return await fsck_remote(
        storage, IdentityCryptor(), PlainKeyCryptor(), deep=True
    )


def test_fsck_accepts_healthy_delta_chain(storage_factory):
    async def go():
        producer = await Core.open(
            make_opts(storage_factory("p"), orset_adapter())
        )
        for i in range(60):
            await producer.update(
                lambda s, m=b"m%d" % i: s.add_ctx(producer.actor_id, m)
            )
        await producer.compact()
        for r in range(3):
            await producer.update(
                lambda s, m=b"t%d" % r: s.add_ctx(producer.actor_id, m)
            )
            await producer.compact()
        report = await _fsck(storage_factory("fsck"))
        assert report.ok, [str(i) for i in report.issues]
        assert report.delta_files == 3

    run(go())


def test_fsck_flags_orphan_gap_and_divergence(storage_factory):
    """The three ISSUE-named defect classes each produce an error row
    (CLI exit 1): a misfiled orphan delta, an interior chain gap, and
    delta-vs-refold byte divergence."""

    async def go():
        producer = await Core.open(
            make_opts(storage_factory("p"), orset_adapter())
        )
        for i in range(60):
            await producer.update(
                lambda s, m=b"m%d" % i: s.add_ctx(producer.actor_id, m)
            )
        await producer.compact()
        storage = producer.storage
        base_name = base_blob = None
        for r in range(3):
            await producer.update(
                lambda s, m=b"t%d" % r: s.add_ctx(producer.actor_id, m)
            )
            await producer.compact()
            if r == 1:
                # keep the last delta's BASE snapshot bytes: re-storing
                # them later (content addressing restores the exact
                # name) recreates the both-endpoints-listed window the
                # refold check needs
                (base_name, base_blob), = await storage.load_states(
                    await storage.list_state_names()
                )

        # interior gap: damage (GC only removes prefixes)
        files = await storage.load_deltas([(producer.actor_id, 1)])
        assert len(files) == 3
        _, v_mid, _ = files[1]
        if hasattr(storage, "_deltas_dir"):
            import os

            os.remove(
                os.path.join(storage._deltas_dir(producer.actor_id),
                             str(v_mid))
            )
        else:
            del storage.remote.deltas[producer.actor_id][v_mid]
        report = await _fsck(storage_factory("f1"))
        assert not report.ok
        assert any(
            "broken chain: gap" in str(i) for i in report.issues
        ), [str(i) for i in report.issues]

        # misfiled orphan: a delta filed under a foreign sealer's log
        _, v_last, raw_last = files[-1]
        stranger = bytes([9]) * 16
        await storage.store_delta(stranger, 1, raw_last)
        report = await _fsck(storage_factory("f2"))
        assert any("orphan delta" in str(i) for i in report.issues), [
            str(i) for i in report.issues
        ]
        await storage.remove_deltas([(stranger, 1 << 62)])

        # delta-vs-refold divergence: tamper the NEWEST delta's body
        # (its base is the snapshot captured above, its target is the
        # current snapshot), re-store the GC'd base, and the refold
        # check must catch base+delta != target
        from crdt_enc_tpu.core.core import open_sealed_blob

        actor, version, raw = files[-1]
        obj = await open_sealed_blob(
            producer._data.keys, producer.cryptor, raw
        )
        rec = delta_wire.parse_delta_obj(obj)
        assert rec.base_name == base_name
        rec.delta_obj[b"e"] = {}  # drop every add: body no longer refolds
        tampered = await producer._seal(delta_wire.build_delta_obj(rec))
        await storage.remove_deltas([(actor, version)])
        await storage.store_delta(actor, version, tampered)
        assert await storage.store_state(base_blob) == base_name
        report = await _fsck(storage_factory("f3"))
        assert any(
            "divergence" in str(i) and i.severity == "error"
            for i in report.issues
        ), [str(i) for i in report.issues]

    run(go())


# ---- CI trend gate ---------------------------------------------------------


def test_delta_metric_rides_the_trend_gate():
    """The committed e2e-delta BENCH_LOCAL record is a first-class
    config for ``obs_report trend`` and its ``--fail-on-regression``
    CI gate — same machinery, new metric, ≥5× acceptance pinned."""
    import pathlib

    from crdt_enc_tpu.obs import fleet, sink

    bench_local = pathlib.Path(__file__).parent.parent / "BENCH_LOCAL.jsonl"
    records = sink.read_records(str(bench_local))
    trend = fleet.bench_trend(
        records, metric="orset_e2e_delta_bytes_reduction"
    )
    assert trend, "committed BENCH_LOCAL carries no e2e-delta record"
    cfg = trend[0]
    assert cfg["latest"] >= 5  # the ISSUE-10 acceptance floor
    assert cfg["shape"]["tail_pct"] <= 1.0
    regressed = dict(
        records[-1], metric=cfg["metric"], value=cfg["best"] / 2,
        backend=cfg["backend"], shape=cfg["shape"],
    )
    t2 = fleet.bench_trend(
        list(records) + [regressed],
        metric="orset_e2e_delta_bytes_reduction",
    )
    assert fleet.trend_regressions(t2, 10)


# ---- simulator vocabulary --------------------------------------------------


def test_sim_delta_schedule_all_faults_tier1():
    from crdt_enc_tpu.sim import FaultConfig, generate, run_schedule

    sched = generate(
        11, 4, 70, FaultConfig.all_faults(), members=10, deltas=True
    )
    assert sched.deltas
    kinds = {s.kind for s in sched.steps}
    assert kinds & {"dseal", "dread", "dgc"}, kinds
    result = run_schedule(sched)
    assert result.ok, result.violation


def test_sim_delta_fixture_fallback_to_snapshot():
    """The committed fixture: seal-delta / read-delta-chain / GC-mid-
    chain, driving the fallback-to-snapshot path to convergence."""
    import json
    import os

    path = os.path.join(
        os.path.dirname(__file__), "data", "sim",
        "delta_gc_fallback_snapshot.json",
    )
    from crdt_enc_tpu.sim import Schedule, run_schedule

    with open(path) as f:
        sched = Schedule.from_obj(json.load(f))
    assert sched.deltas
    result = run_schedule(sched)
    assert result.ok, result.violation


def test_sim_8_replica_all_fault_delta_schedule_deterministic():
    """ISSUE-10 acceptance: an 8-replica all-fault schedule with the
    delta-sync vocabulary passes every quiescence invariant AND
    replays to the same fingerprint bit-for-bit."""
    from crdt_enc_tpu.sim import FaultConfig, generate, run_schedule

    def one():
        return run_schedule(
            generate(31, 8, 120, FaultConfig.all_faults(), members=12,
                     deltas=True)
        )

    r1, r2 = one(), one()
    assert r1.ok, r1.violation
    assert r1.fingerprint == r2.fingerprint
    assert sum(r1.fault_stats.values()) > 0


def test_foldservice_seals_per_tenant_deltas(storage_factory):
    """The serving layer rides the same seal tail: a FoldService cycle
    seals each tenant's delta in the same dispatch, chains verify
    byte-identical to a solo compact, and steady consumers apply them."""
    from crdt_enc_tpu.serve import FoldService, ServeConfig

    async def go():
        t1 = await Core.open(make_opts(storage_factory("t1"), orset_adapter()))
        consumer = await Core.open(
            make_opts(storage_factory("c"), orset_adapter())
        )
        for i in range(70):
            await t1.update(
                lambda s, m=b"m%d" % i: s.add_ctx(t1.actor_id, m)
            )
        service = FoldService([t1], ServeConfig())
        (res1,) = await service.run_cycle()
        assert res1.error is None
        await consumer.read_remote()
        await t1.update(lambda s: s.add_ctx(t1.actor_id, b"tail"))
        trace.reset()
        (res2,) = await service.run_cycle()
        assert res2.error is None
        assert counters().get("delta_files_sealed") == 1
        trace.reset()
        await consumer.read_remote()
        assert counters().get("delta_applied") == 1
        assert consumer.with_state(canonical_bytes) == t1.with_state(
            canonical_bytes
        )

    run(go())


def test_schedule_deltas_roundtrip_and_default_off():
    from crdt_enc_tpu.sim import FaultConfig, Schedule, generate

    old = generate(5, 3, 40, FaultConfig.none())
    assert not old.deltas
    assert "deltas" in old.to_obj()
    # pre-delta fixture objects (no "deltas" key) default off
    obj = old.to_obj()
    del obj["deltas"]
    assert not Schedule.from_obj(obj).deltas
    new = generate(5, 3, 40, FaultConfig.none(), deltas=True)
    assert Schedule.from_obj(new.to_obj()).deltas
    # the pre-delta RNG stream is untouched: same seed, same steps
    assert [s.to_obj() for s in old.steps] == [
        s.to_obj() for s in generate(5, 3, 40, FaultConfig.none()).steps
    ]


# ------------------------------------- seal tail: one job vs stepwise
# The same history sealed with the tail as ONE worker-thread job (the
# ports' sync twins) and call by call on the loop (tests/_seal_drive.py):
# one ordered body serves both, so every published byte and every piece of
# the Core's bookkeeping must agree.


async def _seal_history(fleet, case):
    """Seal a scripted history in ``fleet``'s drive; the last seal is the
    case under test.  Returns (files, bookkeeping, counters of that seal)."""
    from _seal_drive import add_members, bookkeeping, published, remove_members

    from crdt_enc_tpu.parallel import TpuAccelerator
    from crdt_enc_tpu.serve import FoldService, ServeConfig

    writer = await fleet.open("w")
    await add_members(writer, [b"a%d" % i for i in range(12)])
    if case in ("device", "host_served"):
        served = await fleet.open(
            "s", accelerator=TpuAccelerator(min_device_batch=1)
        )
        cfg = ServeConfig() if case == "device" else ServeConfig(warm=False)
        service = FoldService([served], cfg)
        (res,) = await service.run_cycle()
        assert res.sealed
        await add_members(writer, [b"b%d" % i for i in range(5)])
        await remove_members(writer, [b"a3"])
        trace.reset()
        (res,) = await service.run_cycle()
        assert res.sealed and res.error is None
        service.close()
        sealer = served
    else:
        sealer = await fleet.open("s")
        await sealer.compact()  # no base yet: a snapshot-only link
        if case == "prior":
            trace.reset()
            await sealer.compact()  # unchanged state: the same name again
        else:
            await add_members(writer, [b"b%d" % i for i in range(5)])
            await remove_members(writer, [b"a3"])
            if case == "exists":
                # a file a crashed incarnation left at the next version
                await fleet.inner["s"].store_delta(
                    sealer.actor_id, 1, b"left behind"
                )
            if case == "skipped":
                await sealer.compact()  # publishes delta v1
                await remove_members(
                    writer, [b"a%d" % i for i in range(12) if i != 3]
                    + [b"b%d" % i for i in range(5)]
                )
            trace.reset()
            await sealer.compact()
    counted = dict(trace.snapshot()["counters"])
    return await published(fleet.inner["s"]), bookkeeping(sealer), counted


@pytest.mark.parametrize("case", [
    "host", "device", "host_served", "skipped", "exists", "prior",
])
@pytest.mark.parametrize("kind", ["memory", "fs"])
def test_seal_job_and_stepwise_publish_identical_bytes(kind, case, tmp_path):
    from _seal_drive import DRIVES, Fleet, run_pinned

    seen = {}
    for drive in DRIVES:
        fleet = Fleet(kind, drive, tmp_path / drive)
        seen[drive] = run_pinned(lambda: _seal_history(fleet, case))
        trace.reset()
    (files, book, counted), (files_s, book_s, counted_s) = (
        seen["job"], seen["stepwise"]
    )
    assert counted.get("seal_jobs") == 1 and not counted.get("seal_stepwise")
    assert counted_s.get("seal_stepwise") == 1
    assert not counted_s.get("seal_jobs")
    # the case is the one its name says
    want = {
        "host": {"delta_files_sealed": 1},
        "device": {"delta_files_sealed": 1, "delta_device_cuts": 1},
        "host_served": {"delta_files_sealed": 1},
        "skipped": {"delta_seal_skipped": 1, "delta_pruned": 1},
        "exists": {"delta_files_sealed": 1},
        "prior": {"seal_gc_deferred": 1},
    }[case]
    for name, n in want.items():
        assert counted.get(name) == n == counted_s.get(name), name
    if case == "host_served":
        assert not counted.get("delta_device_cuts")
    if case == "exists":
        actor = book["local_meta"][b"actor"]
        assert files["deltas"][(actor, 1)] == b"left behind"
        assert (actor, 2) in files["deltas"]
        assert book["local_meta"][b"last_delta"] == 2
    assert files["states"] and files["checkpoint"] and files["local_meta"]
    for family, got in files.items():
        assert got == files_s[family], family
    assert book == book_s
    drift = {
        k for k in set(counted) | set(counted_s)
        # the second drive finds the first one's programs compiled; the
        # job's hand-offs and the collector's passes are times and chance
        if k not in ("seal_jobs", "seal_stepwise", "ingest_jobs",
                     "ingest_stepwise", "jax_compiles")
        and not k.startswith(("gc_", "seal_job_", "ingest_job_"))
        and counted.get(k) != counted_s.get(k)
    }
    assert not drift, drift


def test_pack_array_is_pack_of_the_list():
    """The snapshot payload is spliced from its items' own packed bytes (the
    state is held once, as the delta plan's bytes)."""
    rng = random.Random(5)
    state = {
        b"c": {rng.randbytes(16): rng.randrange(1, 99) for _ in range(20)},
        b"e": {b"m%d" % i: {rng.randbytes(16): i + 1} for i in range(40)},
        b"d": {},
    }
    cursor = {rng.randbytes(16): 7 for _ in range(5)}
    sealer = rng.randbytes(16)
    assert codec.pack([state, cursor, sealer]) == codec.pack_array(
        [codec.pack(state), codec.pack(cursor), codec.pack(sealer)]
    )
    assert codec.pack([]) == codec.pack_array([])
    with pytest.raises(ValueError):
        codec.pack_array([b"\x00"] * 16)
