"""Warm-open fold checkpoints (ISSUE 4): safety, fidelity, fallbacks.

The local checkpoint is a CACHE, never a source of truth — every test
here pins one side of that contract: a verified checkpoint restores a
state byte-identical to a cold refold (across model adapters and both
storage backends), and ANY doubt (torn file, rotated key, wiped remote,
wrong adapter) falls back to the cold path with the reason traced.
"""

import asyncio
import copy
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
    lwwmap_adapter,
    map_adapter,
    mvreg_adapter,
    orset_adapter,
    pncounter_adapter,
)
from crdt_enc_tpu.models import canonical_bytes
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
    """() -> Storage factories sharing one remote; same-name reuse gives
    the same local dir (the warm-open identity)."""
    if request.param == "memory":
        remote = MemoryRemote()
        instances: dict = {}

        def make(name="a"):
            return instances.setdefault(name, MemoryStorage(remote))

        return make
    remote_dir = tmp_path / "remote"

    def make(name="a"):
        return FsStorage(str(tmp_path / f"local-{name}"), str(remote_dir))

    return make


# ---- checkpoint codec ------------------------------------------------------


def test_columnar_checkpoint_roundtrip_randomized():
    from crdt_enc_tpu.models import ORSet
    from crdt_enc_tpu.models.orset import AddOp, RmOp
    from crdt_enc_tpu.models.vclock import Dot, VClock
    from crdt_enc_tpu.ops.columnar import (
        orset_pack_checkpoint,
        orset_unpack_checkpoint,
    )

    rng = random.Random(7)
    actors = [bytes([i]) * 16 for i in range(12)]
    s = ORSet()
    for _ in range(1500):
        a = rng.choice(actors)
        m = rng.choice([b"b", 3, "s", (1, "t"), rng.randrange(40)])
        s.apply(AddOp(m, s.clock.inc(a)))
        if rng.random() < 0.25 and s.entries:
            m2 = rng.choice(list(s.entries))
            s.apply(RmOp(m2, VClock(dict(s.entries[m2]))))
    s.apply(RmOp(b"ahead", VClock({b"z" * 16: 9})))  # deferred horizon
    wire = codec.unpack(codec.pack(orset_pack_checkpoint(s)))
    r = orset_unpack_checkpoint(wire)
    assert codec.pack(r.to_obj()) == codec.pack(s.to_obj())


def test_columnar_checkpoint_empty_and_overflow():
    from crdt_enc_tpu.models import ORSet
    from crdt_enc_tpu.ops.columnar import (
        orset_pack_checkpoint,
        orset_unpack_checkpoint,
    )

    empty = orset_unpack_checkpoint(
        codec.unpack(codec.pack(orset_pack_checkpoint(ORSet())))
    )
    assert codec.pack(empty.to_obj()) == codec.pack(ORSet().to_obj())
    big = ORSet()
    big.clock.counters[b"a" * 16] = 2**70  # outside int64
    assert orset_pack_checkpoint(big) is None  # generic fmt takes over


# ---- the dict pass: one native walk a table, the Python loop its oracle -----


def _pack_counts() -> dict:
    """How often each producer of the format-1 payload has run."""
    c = trace.snapshot()["counters"]
    return {
        how: c.get(f"checkpoint_pack_{how}", 0)
        for how in ("native", "walk", "rows")
    }


def _walk_only(monkeypatch):
    """``orset_pack_checkpoint`` by the Python loop alone (the native
    pass made to decline): the oracle."""
    from crdt_enc_tpu.ops import columnar as C

    monkeypatch.setattr(
        C, "_dicts_grouped_rows_native", lambda table, members, actors: None
    )


def _state_empty():
    from crdt_enc_tpu.models import ORSet

    return ORSet()


def _fresh_fold(seed: int, R: int, E: int, N: int):
    """``(state, actors, counters)`` after one combined fold of ``N``
    random rows into an empty state (``orset_fold_sparse_host``): adds,
    removes the clock covers, and future-horizon removes that survive
    it, so the DEFERRED table (dm/da/dc) gets real coverage too."""
    import numpy as np

    from crdt_enc_tpu.models import ORSet
    from crdt_enc_tpu.ops import columnar as C

    rng = np.random.default_rng(seed)
    actors = sorted(rng.bytes(16) for _ in range(R))
    counters = np.zeros(R, np.int64)
    kind = np.zeros(N, np.int8)
    member = rng.integers(0, E, N).astype(np.int32)
    actor = rng.integers(0, R, N).astype(np.int32)
    ctr = np.zeros(N, np.int32)
    for i in range(N):
        a = int(actor[i])
        roll = rng.random()
        if roll < 0.05:
            kind[i], ctr[i] = 1, counters[a] + 3
        elif roll < 0.18 and counters[a]:
            kind[i], ctr[i] = 1, counters[a]
        else:
            counters[a] += 1
            ctr[i] = counters[a]
    state = ORSet()
    C.orset_fold_sparse_host(
        state, kind, member, actor, ctr,
        C.Vocab(list(range(E))), C.Vocab(actors),
    )
    assert state.entries and state.deferred
    return state, actors, counters


def _state_fresh_fold():
    return _fresh_fold(11, 32, 120, 4000)[0]


def _state_removes_live_horizons():
    from crdt_enc_tpu.models import ORSet
    from crdt_enc_tpu.models.orset import AddOp, RmOp
    from crdt_enc_tpu.models.vclock import VClock

    rng = random.Random(5)
    actors = [bytes([i]) * 16 for i in range(9)]
    s = ORSet()
    for i in range(600):
        a = rng.choice(actors)
        s.apply(AddOp(rng.randrange(50), s.clock.inc(a)))
        if i % 5 == 0:
            m = rng.choice(list(s.entries))
            # a remove that saw dots this replica has not: a live horizon
            ahead = {r: c + 2 for r, c in s.entries[m].items()}
            s.apply(RmOp(m, VClock(ahead)))
    assert s.deferred
    return s


def _state_deferred_actor_off_clock():
    from crdt_enc_tpu.models import ORSet
    from crdt_enc_tpu.models.orset import AddOp, RmOp
    from crdt_enc_tpu.models.vclock import VClock

    s = ORSet()
    a = b"a" * 16
    for m in (b"x", b"y", b"z"):
        s.apply(AddOp(m, s.clock.inc(a)))
    # the clock never saw b"q"*16: it is interned after the clock's
    # actors, by the deferred table's walk
    s.apply(RmOp(b"y", VClock({b"q" * 16: 7})))
    s.apply(RmOp(b"never-added", VClock({b"r" * 16: 2, a: 99})))
    assert b"q" * 16 not in s.clock.counters and s.deferred
    return s


def _state_mixed_member_types():
    from crdt_enc_tpu.models import ORSet
    from crdt_enc_tpu.models.orset import AddOp, RmOp
    from crdt_enc_tpu.models.vclock import VClock

    s = ORSet()
    actors = [bytes([i]) * 16 for i in range(4)]
    for i, m in enumerate([7, b"7", "7", (1, "t"), -3, "", b"", 2**40]):
        s.apply(AddOp(m, s.clock.inc(actors[i % 4])))
        s.apply(AddOp(m, s.clock.inc(actors[(i + 1) % 4])))
    s.apply(RmOp("7", VClock({actors[0]: 50})))
    return s


def _state_10k_actors_sparse():
    from crdt_enc_tpu.models import ORSet
    from crdt_enc_tpu.models.vclock import VClock

    rng = random.Random(3)
    actors = [i.to_bytes(16, "big") for i in range(10_000)]
    s = ORSet()
    s.clock = VClock({a: rng.randrange(1, 2**40) for a in actors})
    for _ in range(3000):
        a = rng.choice(actors)
        s.entries.setdefault(rng.randrange(500), {})[a] = rng.randrange(
            1, s.clock.counters[a] + 1
        )
    for _ in range(40):
        a = rng.choice(actors)
        s.deferred.setdefault(rng.randrange(600), {})[a] = (
            s.clock.counters[a] + rng.randrange(1, 9)
        )
    return s


def _state_counter_2_63(table):
    def build():
        s = _state_deferred_actor_off_clock()
        next(iter(getattr(s, table).values()))[b"a" * 16] = 2**63
        return s

    return build


class _Slots(dict):
    """A slot map that is a mapping but not exactly a ``dict``."""


def _state_slot_map_not_a_dict():
    s = _state_removes_live_horizons()
    m = list(s.entries)[3]
    s.entries[m] = _Slots(s.entries[m])
    return s


def _state_counter_not_an_int():
    import numpy as np

    s = _state_removes_live_horizons()
    slots = s.entries[list(s.entries)[2]]
    r = next(iter(slots))
    slots[r] = np.int64(slots[r])
    return s


@pytest.mark.parametrize(
    "build,library,ran",
    [
        (_state_empty, True, "native"),
        (_state_fresh_fold, True, "native"),
        (_state_removes_live_horizons, True, "native"),
        (_state_deferred_actor_off_clock, True, "native"),
        (_state_mixed_member_types, True, "native"),
        (_state_10k_actors_sparse, True, "native"),
        (_state_counter_2_63("entries"), True, None),
        (_state_counter_2_63("deferred"), True, None),
        (_state_slot_map_not_a_dict, True, "walk"),
        (_state_counter_not_an_int, True, "walk"),
        (_state_removes_live_horizons, False, "walk"),
        (_state_empty, False, "walk"),
    ],
    ids=[
        "empty", "fresh_fold", "removes_live_horizons",
        "deferred_actor_off_clock", "mixed_member_types",
        "10k_actors_sparse", "entry_counter_2_63", "deferred_counter_2_63",
        "slot_map_not_a_dict", "counter_not_an_int", "library_fails_to_load",
        "library_fails_to_load_empty",
    ],
)
def test_dict_pass_payload_equals_the_loops(build, library, ran, monkeypatch):
    """The native pass over the state's dicts and the Python loop give
    one payload, key for key and byte for byte; where the pass declines
    (or the library is not there) the loop's payload is what comes out;
    a counter outside int64 is ``None`` from both (the pass declines,
    the loop overflows) and counts for neither; the counter says which
    producer made the payload; and the payload unpacks to the state."""
    from crdt_enc_tpu import native
    from crdt_enc_tpu.ops import columnar as C

    def tables(s):
        return s.clock.counters, s.entries, s.deferred

    state = build()
    before = copy.deepcopy(state)
    if not library:
        def broken():
            raise RuntimeError("no C-API library on this box")

        monkeypatch.setattr(native, "load_state", broken)
    counts = _pack_counts()
    got = C.orset_pack_checkpoint(state)
    if ran:
        counts[ran] += 1
    assert _pack_counts() == counts
    _walk_only(monkeypatch)
    want = C.orset_pack_checkpoint(state)
    assert tables(state) == tables(before)  # neither touched it
    if any(c == 2**63 for t in (state.entries, state.deferred)
           for slots in t.values() for c in slots.values()):
        assert got is None and want is None
        return
    assert got is not None
    assert list(got) == list(want)  # key for key, in order
    for k in want:
        assert type(got[k]) is type(want[k]), k
        assert got[k] == want[k], k
    assert codec.pack(got) == codec.pack(want)
    back = C.orset_unpack_checkpoint(codec.unpack(codec.pack(got)))
    assert tables(back) == tables(before)
    if build is not _state_counter_not_an_int:  # msgpack packs no numpy int
        assert codec.pack(back.to_obj()) == codec.pack(before.to_obj())


def test_compact_after_a_mutating_round_packs_natively(tmp_path):
    """Over ``FsStorage``: the round after the first is a steady round
    (the state mutated since any fresh fold), its checkpoint comes from
    the native dict pass, once, and a fresh ``open()`` restores that
    checkpoint to the same canonical bytes."""
    def storage():
        return FsStorage(str(tmp_path / "local"), str(tmp_path / "remote"))

    async def go():
        c1 = await Core.open(make_opts(storage(), orset_adapter()))
        for i in range(20):
            await c1.apply_ops([_ops_orset_rm(c1, i)])
        await c1.compact()
        for i in range(20, 35):
            await c1.apply_ops([_ops_orset_rm(c1, i)])
        counts = _pack_counts()
        await c1.compact()
        counts["native"] += 1
        assert _pack_counts() == counts
        want = c1.with_state(canonical_bytes)
        warm = await Core.open(
            make_opts(storage(), orset_adapter(), create=False)
        )
        assert warm.opened_from_checkpoint, warm.checkpoint_fallback_reason
        assert warm.with_state(canonical_bytes) == want

    run(go())


def test_checkpoint_of_either_producer_opens_under_the_other(
    tmp_path, monkeypatch
):
    """A checkpoint the Python loop wrote (the program before the native
    pass) opens in a replica that packs natively, and the reverse: the
    stored payloads are one."""
    def storage():
        return FsStorage(str(tmp_path / "local"), str(tmp_path / "remote"))

    async def go():
        c1 = await Core.open(make_opts(storage(), orset_adapter()))
        for i in range(30):
            await c1.apply_ops([_ops_orset_rm(c1, i)])
        await c1.compact()
        native_blob = await storage().load_local_checkpoint()
        want = c1.with_state(canonical_bytes)
        _walk_only(monkeypatch)
        assert await c1.save_checkpoint()
        loop_blob = await storage().load_local_checkpoint()
        monkeypatch.undo()
        loop_ckpt = await c1._open_sealed(loop_blob)
        native_ckpt = await c1._open_sealed(native_blob)
        assert loop_ckpt[b"state"] == native_ckpt[b"state"]
        assert int(loop_ckpt[b"fmt"]) == int(native_ckpt[b"fmt"]) == 1
        warm = await Core.open(
            make_opts(storage(), orset_adapter(), create=False)
        )  # the loop's file, read back by the native program
        assert warm.opened_from_checkpoint, warm.checkpoint_fallback_reason
        assert warm.with_state(canonical_bytes) == want

    run(go())


# ---- warm open == cold open, across adapters (differential) ----------------


def _ops_orset(core, i):
    return core.with_state(
        lambda s: s.add_ctx(core.actor_id, b"m%d" % (i % 7))
    )


def _ops_orset_rm(core, i):
    if i % 5 == 4:
        return core.with_state(lambda s: s.rm_ctx(b"m%d" % (i % 7)))
    return _ops_orset(core, i)


def _ops_gcounter(core, i):
    return core.with_state(lambda s: s.inc(core.actor_id, 1 + i % 3))


def _ops_pncounter(core, i):
    if i % 3 == 2:
        return core.with_state(lambda s: s.dec(core.actor_id))
    return core.with_state(lambda s: s.inc(core.actor_id))


def _ops_mvreg(core, i):
    return core.with_state(lambda s: s.write_ctx(core.actor_id, [b"v", i]))


def _ops_gset(core, i):
    return [b"g%d" % (i % 9)]  # the op IS the member


def _ops_lwwmap(core, i):
    from crdt_enc_tpu.models import LWWOp

    return LWWOp(b"k%d" % (i % 4), 1000 + i, core.actor_id, b"v%d" % i)


def _ops_map(core, i):
    from crdt_enc_tpu.models.orset import AddOp

    def build(s):
        return s.update_ctx(
            core.actor_id, "k%d" % (i % 3), lambda c, d: AddOp(i % 5, d)
        )

    return core.with_state(build)


ADAPTER_CASES = [
    ("orset", orset_adapter, _ops_orset_rm),
    ("gcounter", gcounter_adapter, _ops_gcounter),
    ("pncounter", pncounter_adapter, _ops_pncounter),
    ("mvreg", mvreg_adapter, _ops_mvreg),
    ("gset", gset_adapter, _ops_gset),
    ("lwwmap", lwwmap_adapter, _ops_lwwmap),
    ("map+orset", lambda: map_adapter(b"orset"), _ops_map),
]


@pytest.mark.parametrize(
    "name,mk_adapter,build", ADAPTER_CASES, ids=[c[0] for c in ADAPTER_CASES]
)
def test_warm_open_byte_identical_to_cold(storage_factory, name, mk_adapter, build):
    """The differential: compact → warm reopen vs a cold replica, plus a
    post-checkpoint tail only the ingest path can deliver — resulting
    states must be byte-identical for every adapter."""

    async def go():
        s_a = storage_factory("a")
        c1 = await Core.open(make_opts(s_a, mk_adapter()))
        for i in range(24):
            op = build(c1, i)
            await c1.apply_ops(op if isinstance(op, list) else [op])
        await c1.compact()
        # a tail past the checkpoint, from another replica
        w = await Core.open(make_opts(storage_factory("w"), mk_adapter()))
        for i in range(24, 30):
            op = build(w, i)
            await w.apply_ops(op if isinstance(op, list) else [op])
        # warm reopen of replica A's local dir
        warm = await Core.open(
            make_opts(storage_factory("a"), mk_adapter(), create=False)
        )
        assert warm.opened_from_checkpoint, warm.checkpoint_fallback_reason
        await warm.read_remote()
        # cold replica refolds everything
        cold = await Core.open(make_opts(storage_factory("c"), mk_adapter()))
        await cold.read_remote()
        assert warm.with_state(canonical_bytes) == cold.with_state(
            canonical_bytes
        )

    run(go())


def test_warm_open_skips_refold(storage_factory):
    """Warm open must not re-read the compacted history: the tail ingest
    touches only files past the cursor."""

    async def go():
        s_a = storage_factory("a")
        c1 = await Core.open(make_opts(s_a, orset_adapter()))
        for i in range(40):
            await c1.apply_ops([_ops_orset(c1, i)])
        await c1.compact()
        w = await Core.open(make_opts(storage_factory("w"), orset_adapter()))
        await w.apply_ops([_ops_orset(w, 99)])
        trace.reset()
        warm = await Core.open(
            make_opts(storage_factory("a"), orset_adapter(), create=False)
        )
        assert warm.opened_from_checkpoint
        await warm.read_remote()
        counters = trace.snapshot()["counters"]
        trace.reset()
        folded = counters.get("ops_folded", 0) + counters.get(
            "op_files_bulk_folded", 0
        )
        assert folded <= 1, f"warm open refolded history: {counters}"
        # and the warm state still contains the full history
        assert warm.with_state(lambda s: s.contains(b"m0"))

    run(go())


def test_checkpoint_on_read_consumer_replica(storage_factory):
    """A pure consumer (never compacts) with checkpoint_on_read reseals
    after each ingest and warm-opens from it."""

    async def go():
        w = await Core.open(make_opts(storage_factory("w"), orset_adapter()))
        for i in range(20):
            await w.apply_ops([_ops_orset(w, i)])
        s_r = storage_factory("r")
        reader = await Core.open(
            make_opts(s_r, orset_adapter(), checkpoint_on_read=True)
        )
        await reader.read_remote()
        reopened = await Core.open(
            make_opts(storage_factory("r"), orset_adapter(), create=False)
        )
        assert reopened.opened_from_checkpoint
        assert reopened.with_state(canonical_bytes) == reader.with_state(
            canonical_bytes
        )

    run(go())


# ---- fallbacks -------------------------------------------------------------


def _truncate_checkpoint(storage) -> None:
    if isinstance(storage, MemoryStorage):
        assert storage._local_checkpoint
        storage._local_checkpoint = storage._local_checkpoint[:-7]
    else:
        import os

        path = storage._local_checkpoint_path()
        data = open(path, "rb").read()
        with open(path, "wb") as f:
            f.write(data[:-7])


def test_torn_checkpoint_falls_back_cold(storage_factory):
    async def go():
        s_a = storage_factory("a")
        c1 = await Core.open(make_opts(s_a, orset_adapter()))
        for i in range(25):
            await c1.apply_ops([_ops_orset_rm(c1, i)])
        await c1.compact()
        cold_bytes = c1.with_state(canonical_bytes)
        _truncate_checkpoint(storage_factory("a"))
        trace.reset()
        warm = await Core.open(
            make_opts(storage_factory("a"), orset_adapter(), create=False)
        )
        assert not warm.opened_from_checkpoint
        assert warm.checkpoint_fallback_reason == "unreadable"
        assert trace.snapshot()["counters"].get("checkpoint_fallbacks") == 1
        trace.reset()
        await warm.read_remote()
        assert warm.with_state(canonical_bytes) == cold_bytes

    run(go())


def test_key_rotation_invalidates_checkpoint(storage_factory):
    async def go():
        s_a = storage_factory("a")
        c1 = await Core.open(make_opts(s_a, orset_adapter()))
        for i in range(10):
            await c1.apply_ops([_ops_orset(c1, i)])
        await c1.compact()
        await c1.rotate_key()  # checkpoint now belongs to an old generation
        warm = await Core.open(
            make_opts(storage_factory("a"), orset_adapter(), create=False)
        )
        assert not warm.opened_from_checkpoint
        assert warm.checkpoint_fallback_reason == "key_rotation"
        await warm.read_remote()
        assert warm.with_state(canonical_bytes) == c1.with_state(
            canonical_bytes
        )

    run(go())


def test_adapter_mismatch_falls_back(storage_factory):
    async def go():
        s_a = storage_factory("a")
        c1 = await Core.open(make_opts(s_a, gcounter_adapter()))
        await c1.apply_ops([c1.with_state(lambda s: s.inc(c1.actor_id, 3))])
        await c1.compact()
        warm = await Core.open(
            make_opts(storage_factory("a"), orset_adapter(), create=False)
        )
        assert not warm.opened_from_checkpoint
        assert warm.checkpoint_fallback_reason == "adapter"

    run(go())


def test_wiped_remote_rejects_checkpoint(tmp_path):
    """A checkpoint must never install over a remote it did not come
    from: wipe the remote, re-bootstrap, reopen the old local dir."""
    import shutil

    remote = tmp_path / "remote"

    async def go():
        c1 = await Core.open(
            make_opts(
                FsStorage(str(tmp_path / "localA"), str(remote)),
                orset_adapter(),
            )
        )
        for i in range(12):
            await c1.apply_ops([_ops_orset(c1, i)])
        await c1.compact()
        shutil.rmtree(remote)
        # someone re-creates a fresh remote under the same path
        boot = await Core.open(
            make_opts(
                FsStorage(str(tmp_path / "localB"), str(remote)),
                orset_adapter(),
            )
        )
        await boot.apply_ops([_ops_orset(boot, 0)])
        warm = await Core.open(
            make_opts(
                FsStorage(str(tmp_path / "localA"), str(remote)),
                orset_adapter(),
                create=False,
            )
        )
        assert not warm.opened_from_checkpoint
        # the fresh remote bootstrapped a new key generation (and new
        # metadata) — either fingerprint check must trip
        assert warm.checkpoint_fallback_reason in (
            "key_rotation", "remote_meta", "unreadable",
        )

    run(go())


def test_checkpoint_disabled_never_writes(storage_factory):
    async def go():
        s_a = storage_factory("a")
        c1 = await Core.open(
            make_opts(s_a, orset_adapter(), checkpoint=False)
        )
        for i in range(8):
            await c1.apply_ops([_ops_orset(c1, i)])
        await c1.compact()
        assert not await c1.save_checkpoint()
        assert await s_a.load_local_checkpoint() is None

    run(go())


# ---- fsck --verify-checkpoint ---------------------------------------------


def test_fsck_verify_checkpoint_ok_and_divergent(storage_factory):
    from crdt_enc_tpu.tools.fsck import verify_checkpoint

    async def go():
        s_a = storage_factory("a")
        c1 = await Core.open(make_opts(s_a, orset_adapter()))
        for i in range(25):
            await c1.apply_ops([_ops_orset_rm(c1, i)])
        # pre-compact: refold replays op files
        await c1.save_checkpoint()
        r = await verify_checkpoint(
            s_a, storage_factory("x"), IdentityCryptor(), PlainKeyCryptor()
        )
        assert r.ok and r.op_files > 0, [str(i) for i in r.issues]
        # post-compact: refold goes through the snapshot
        await c1.compact()
        r = await verify_checkpoint(
            s_a, storage_factory("x"), IdentityCryptor(), PlainKeyCryptor()
        )
        assert r.ok and r.state_files == 1, [str(i) for i in r.issues]
        # forge a diverging checkpoint (sealed correctly, wrong state)
        from crdt_enc_tpu.models import ORSet
        from crdt_enc_tpu.models.orset import AddOp
        from crdt_enc_tpu.models.vclock import Dot

        real = c1._data.state
        bogus = ORSet()
        bogus.apply(AddOp(b"bogus", Dot(c1.actor_id, 1)))
        c1._data.state = bogus
        await c1.save_checkpoint()
        c1._data.state = real
        r = await verify_checkpoint(
            s_a, storage_factory("x"), IdentityCryptor(), PlainKeyCryptor()
        )
        assert not r.ok
        assert any(
            i.family == "checkpoint" and "diverges" in i.problem
            for i in r.issues
        )

    run(go())


def test_fsck_cli_verify_checkpoint_flag(tmp_path):
    """End-to-end CLI: a real XChaCha-sealed remote, --verify-checkpoint
    passes on an honest local dir and exits 1 on a forged one."""
    pytest.importorskip("crdt_enc_tpu.native")
    from crdt_enc_tpu.backends import XChaChaCryptor
    from crdt_enc_tpu.tools import fsck as fsck_cli

    try:
        from crdt_enc_tpu import native

        native.load()
    except Exception:
        pytest.skip("native crypto unavailable")

    remote = str(tmp_path / "remote")
    local = str(tmp_path / "localA")

    async def build():
        c1 = await Core.open(
            OpenOptions(
                storage=FsStorage(local, remote),
                cryptor=XChaChaCryptor(),
                key_cryptor=PlainKeyCryptor(),
                adapter=orset_adapter(),
                supported_data_versions=(DEFAULT_DATA_VERSION_1,),
                current_data_version=DEFAULT_DATA_VERSION_1,
                create=True,
            )
        )
        for i in range(20):
            await c1.apply_ops([_ops_orset(c1, i)])
        await c1.compact()
        return c1

    run(build())
    assert fsck_cli.main([remote, "--verify-checkpoint", local]) == 0
    # a torn checkpoint is an error row for fsck (the core would fall
    # back silently; fsck's job is to say so loudly)
    import os

    path = os.path.join(local, "checkpoint.msgpack")
    data = open(path, "rb").read()
    with open(path, "wb") as f:
        f.write(data[:-5])
    assert fsck_cli.main([remote, "--verify-checkpoint", local]) == 1


# ---- checkpoint from streaming-fold rows (ISSUE 13: zero dict walk) -------


def test_pack_checkpoint_rows_semantically_equal_to_dict_walk():
    """A fresh streaming fold stashes its surviving rows; packing the
    checkpoint from them must unpack to a state canonically identical
    to the dict-walk pack, and the stash must be mut-epoch-guarded."""
    from crdt_enc_tpu.models.orset import AddOp
    from crdt_enc_tpu.models.vclock import Dot
    from crdt_enc_tpu.ops import columnar as C

    # ≥ CKPT_STASH_MIN_ROWS surviving rows
    state, actors, counters = _fresh_fold(4, 64, 200, 9000)
    stash = getattr(state, "_ckpt_rows", None)
    assert stash is not None and stash[0] == state._mut
    from_rows = C.orset_unpack_checkpoint(
        C.orset_pack_checkpoint_rows(*stash[1])
    )
    from_dicts = C.orset_unpack_checkpoint(C.orset_pack_checkpoint(state))
    assert codec.pack(from_rows.to_obj()) == codec.pack(state.to_obj())
    assert codec.pack(from_rows.to_obj()) == codec.pack(from_dicts.to_obj())
    # a later mutation invalidates the stash via the epoch guard
    state.apply(AddOp(0, Dot(actors[0], int(counters[0]) + 1)))
    assert stash[0] != state._mut


def test_streaming_compact_checkpoints_from_rows(storage_factory, monkeypatch):
    """End-to-end: a core whose ingest ran the fresh streaming fold
    seals its warm-open checkpoint FROM THE STASHED ROWS (the dict-walk
    packer is forbidden by the spy), and the warm reopen restores a
    state byte-identical to a cold refold."""
    import crdt_enc_tpu.core.core as core_mod
    from crdt_enc_tpu.ops import columnar as C
    from crdt_enc_tpu.parallel.accel import TpuAccelerator

    monkeypatch.setattr(C, "CKPT_STASH_MIN_ROWS", 1)
    # the tiny test shape would pick the dense device fold; the rows
    # stash rides the sparse host regime (the config-5 streaming shape)
    monkeypatch.setattr(
        TpuAccelerator, "_use_sparse", lambda self, E, R, n: True
    )

    async def go():
        writer = await Core.open(
            make_opts(storage_factory("w"), orset_adapter())
        )
        for i in range(core_mod.BULK_MIN_FILES + 8):
            await writer.apply_ops(
                [writer.with_state(
                    lambda s: s.add_ctx(writer.actor_id, i % 9)
                )]
            )
        reader = await Core.open(make_opts(
            storage_factory("r"), orset_adapter(),
            accelerator=TpuAccelerator(min_device_batch=1),
        ))

        def forbidden(state):
            raise AssertionError(
                "dict-walk checkpoint pack ran despite a fresh rows stash"
            )

        monkeypatch.setattr(C, "orset_pack_checkpoint", forbidden)
        counts = _pack_counts()
        await reader.compact()
        monkeypatch.undo()
        counts["rows"] += 1
        assert _pack_counts() == counts

        warm = await Core.open(make_opts(
            storage_factory("r"), orset_adapter(), create=False,
        ))
        assert warm.checkpoint_fallback_reason is None
        cold = await Core.open(make_opts(
            storage_factory("cold"), orset_adapter(),
        ))
        await cold.read_remote()
        assert warm.with_state(canonical_bytes) == cold.with_state(
            canonical_bytes
        )

    run(go())


# ------------------------------------- the seal tail's one worker job


def test_mutation_while_the_seal_job_is_parked(tmp_path):
    """A local write lands while a tenant's seal job sits in a blocking
    storage twin.  The files the job goes on to write are the plan-time
    triple; the bookkeeping keeps the epochs apart, so the next cycle seals
    again; the checkpoint's ``snap`` names a snapshot its own state equals;
    and the producer cursor the write persisted is not written back stale."""
    import threading

    from crdt_enc_tpu.core.core import LocalMeta, unpack_checkpoint_state
    from crdt_enc_tpu.serve import FoldService
    from crdt_enc_tpu.utils import VersionBytes

    parked, release = threading.Event(), threading.Event()

    class Parking(MemoryStorage):
        def store_state_sync(self, data):
            name = super().store_state_sync(data)
            parked.set()
            assert release.wait(30)
            return name

    async def go():
        remote = MemoryRemote()
        adapter = orset_adapter()
        writer = await Core.open(make_opts(MemoryStorage(remote), adapter))
        for i in range(9):
            await writer.update(
                lambda s, i=i: s.add_ctx(writer.actor_id, b"m%d" % i)
            )
        storage = Parking(remote)
        served = await Core.open(make_opts(storage, adapter))
        service = FoldService([served])
        release.set()
        (res,) = await service.run_cycle()
        assert res.sealed
        for i in range(9, 14):
            await writer.update(
                lambda s, i=i: s.add_ctx(writer.actor_id, b"m%d" % i)
            )
        parked.clear()
        release.clear()
        trace.reset()
        cycle = asyncio.ensure_future(service.run_cycle())
        assert await asyncio.to_thread(parked.wait, 30)
        plan_time = served.with_state(canonical_bytes)
        await served.update(lambda s: s.add_ctx(served.actor_id, b"late"))
        late_version = served._local_meta.last_op_version
        release.set()
        (res,) = await cycle
        assert res.sealed and res.error is None
        assert trace.snapshot()["counters"].get("seal_jobs") == 1

        name = served.delta_base_name
        (_, blob), = await storage.load_states([name])
        snapshot = await served._open_sealed(blob)
        assert codec.pack(snapshot[0]) == plan_time
        ckpt = await served._open_sealed(await storage.load_local_checkpoint())
        assert bytes(ckpt[b"snap"]).decode() == name
        assert name in ckpt[b"rs"]
        assert canonical_bytes(unpack_checkpoint_state(
            adapter, int(ckpt[b"fmt"]), ckpt[b"state"]
        )) == plan_time
        assert ckpt[b"cursor"] == snapshot[1]
        # every delta of the chain refolds to a published snapshot
        consumer = await Core.open(make_opts(MemoryStorage(remote), adapter))
        await consumer.read_remote()
        assert consumer.with_state(canonical_bytes) == served.with_state(
            canonical_bytes
        )
        # the write's durable cursor survived the job's local-meta write
        meta = LocalMeta.from_obj(codec.unpack(
            VersionBytes.deserialize(await storage.load_local_meta()).content
        ))
        assert meta.last_op_version == late_version > 0
        assert meta.last_delta_version == served._local_meta.last_delta_version
        # the seal is not mistaken for one of the state as it is now
        assert served._seal_signature() != served._last_seal_sig
        trace.reset()
        (res,) = await service.run_cycle()
        assert res.sealed and not trace.snapshot()["counters"].get(
            "serve_noop_cycles"
        )
        (_, blob), = await storage.load_states([served.delta_base_name])
        assert b"late" in (await served._open_sealed(blob))[0][b"e"]
        service.close()
        # a warm reopen restores a state equal to the snapshot it names
        storage2 = Parking(remote)
        storage2._local_meta = storage._local_meta
        storage2._local_checkpoint = storage._local_checkpoint
        warm = await Core.open(make_opts(storage2, adapter, create=False))
        assert warm.opened_from_checkpoint
        assert warm.delta_base_name == served.delta_base_name
        assert warm.with_state(canonical_bytes) == served.with_state(
            canonical_bytes
        )

    release.set()
    run(go())
    trace.reset()


# ---- format 2: the snapshot's bytes (ISSUE 51) ------------------------------

GENERIC_CASES = [c for c in ADAPTER_CASES if c[0] != "orset"]


async def _compacted(storage_factory, mk_adapter, build, n=24):
    core = await Core.open(make_opts(storage_factory("a"), mk_adapter()))
    for i in range(n):
        op = build(core, i)
        await core.apply_ops(op if isinstance(op, list) else [op])
    await core.compact()
    return core


async def _rewrite_checkpoint(core, storage, change) -> dict:
    """Seal the stored checkpoint again with ``change`` applied to its
    payload, under the same key and through the same port."""
    ckpt = dict(await core._open_sealed(await storage.load_local_checkpoint()))
    change(ckpt)
    await storage.store_local_checkpoint(
        await core._seal_packed(
            core._latest_key(), codec.pack(ckpt), core.cryptor.encrypt
        )
    )
    return ckpt


@pytest.mark.parametrize(
    "name,mk_adapter,build", GENERIC_CASES, ids=[c[0] for c in GENERIC_CASES]
)
def test_format_2_checkpoint_opens_warm_equal_to_cold(
    storage_factory, name, mk_adapter, build
):
    """A state with no columnar format is checkpointed as its canonical
    bytes, the snapshot's own, and a warm open from them ends where a cold
    open does."""

    async def go():
        trace.reset()
        c1 = await _compacted(storage_factory, mk_adapter, build)
        counters = trace.snapshot()["counters"]
        assert counters.get("checkpoint_pack_shared") == 1
        assert not any(_pack_counts().values())
        ckpt = await c1._open_sealed(
            await storage_factory("a").load_local_checkpoint()
        )
        assert int(ckpt[b"fmt"]) == 2
        assert ckpt[b"state"] == c1.with_state(canonical_bytes)
        w = await Core.open(make_opts(storage_factory("w"), mk_adapter()))
        for i in range(24, 30):
            op = build(w, i)
            await w.apply_ops(op if isinstance(op, list) else [op])
        warm = await Core.open(
            make_opts(storage_factory("a"), mk_adapter(), create=False)
        )
        assert warm.opened_from_checkpoint, warm.checkpoint_fallback_reason
        assert warm.with_state(canonical_bytes) == ckpt[b"state"]
        await warm.read_remote()
        cold = await Core.open(make_opts(storage_factory("c"), mk_adapter()))
        await cold.read_remote()
        assert not cold.opened_from_checkpoint
        assert warm.with_state(canonical_bytes) == cold.with_state(
            canonical_bytes
        )

    run(go())


@pytest.mark.parametrize(
    "name,mk_adapter,build", GENERIC_CASES, ids=[c[0] for c in GENERIC_CASES]
)
def test_format_0_checkpoint_of_an_older_program_still_opens_warm(
    storage_factory, name, mk_adapter, build
):
    """Format 0 (the state as an object nested in the payload) is what the
    program wrote until ISSUE 51: read, never written."""

    async def go():
        c1 = await _compacted(storage_factory, mk_adapter, build)
        expected = c1.with_state(canonical_bytes)

        def to_format_0(ckpt):
            assert int(ckpt[b"fmt"]) == 2
            ckpt[b"fmt"] = 0
            ckpt[b"state"] = codec.unpack(ckpt[b"state"])

        await _rewrite_checkpoint(c1, storage_factory("a"), to_format_0)
        trace.reset()
        warm = await Core.open(
            make_opts(storage_factory("a"), mk_adapter(), create=False)
        )
        assert warm.opened_from_checkpoint, warm.checkpoint_fallback_reason
        assert warm.with_state(canonical_bytes) == expected
        assert "checkpoint_fallbacks" not in trace.snapshot()["counters"]
        # and what it writes next is format 2 again
        await warm.save_checkpoint()
        ckpt = await warm._open_sealed(
            await storage_factory("a").load_local_checkpoint()
        )
        assert int(ckpt[b"fmt"]) == 2 and ckpt[b"state"] == expected

    run(go())


@pytest.mark.parametrize("fmt", [3, 9, -1])
def test_unknown_checkpoint_format_falls_back_malformed(storage_factory, fmt):
    """What an older reader does with a format 2 file, shown on this one
    with a format it does not know: drop the file, open cold."""

    async def go():
        c1 = await _compacted(storage_factory, lwwmap_adapter, _ops_lwwmap)
        expected = c1.with_state(canonical_bytes)
        await _rewrite_checkpoint(
            c1, storage_factory("a"), lambda ckpt: ckpt.update({b"fmt": fmt})
        )
        trace.reset()
        cold = await Core.open(
            make_opts(storage_factory("a"), lwwmap_adapter(), create=False)
        )
        assert not cold.opened_from_checkpoint
        assert cold.checkpoint_fallback_reason == "malformed"
        assert trace.snapshot()["counters"].get("checkpoint_fallbacks") == 1
        assert await storage_factory("a").load_local_checkpoint() is None
        await cold.read_remote()
        assert cold.with_state(canonical_bytes) == expected

    run(go())


def test_fsck_verify_checkpoint_accepts_format_2(storage_factory):
    from crdt_enc_tpu.tools.fsck import verify_checkpoint

    async def go():
        c1 = await _compacted(storage_factory, lwwmap_adapter, _ops_lwwmap)
        s_a = storage_factory("a")
        ckpt = await c1._open_sealed(await s_a.load_local_checkpoint())
        assert int(ckpt[b"fmt"]) == 2

        async def verify():
            return await verify_checkpoint(
                s_a, storage_factory("x"), IdentityCryptor(),
                PlainKeyCryptor(), adapter=lwwmap_adapter(),
            )

        r = await verify()
        assert r.ok and r.state_files == 1, [str(i) for i in r.issues]
        # sealed correctly, wrong state: the divergence is still found
        real = c1._data.state
        c1._data.state = type(real)()
        c1._data.state.apply(_ops_lwwmap(c1, 99))
        await c1.save_checkpoint()
        c1._data.state = real
        r = await verify()
        assert any(
            i.family == "checkpoint" and "diverges" in i.problem
            for i in r.issues
        )

    run(go())


def test_fsck_cli_verify_checkpoint_accepts_format_2(tmp_path):
    pytest.importorskip("crdt_enc_tpu.native")
    from crdt_enc_tpu.backends import XChaChaCryptor
    from crdt_enc_tpu.tools import fsck as fsck_cli

    try:
        from crdt_enc_tpu import native

        native.load()
    except Exception:
        pytest.skip("native crypto unavailable")

    remote = str(tmp_path / "remote")
    local = str(tmp_path / "localA")

    async def build():
        c1 = await Core.open(
            OpenOptions(
                storage=FsStorage(local, remote),
                cryptor=XChaChaCryptor(),
                key_cryptor=PlainKeyCryptor(),
                adapter=lwwmap_adapter(),
                supported_data_versions=(DEFAULT_DATA_VERSION_1,),
                current_data_version=DEFAULT_DATA_VERSION_1,
                create=True,
            )
        )
        for i in range(20):
            await c1.apply_ops([_ops_lwwmap(c1, i)])
        await c1.compact()
        ckpt = await c1._open_sealed(
            await c1.storage.load_local_checkpoint()
        )
        assert int(ckpt[b"fmt"]) == 2

    run(build())
    args = [remote, "--verify-checkpoint", local, "--adapter", "lwwmap"]
    assert fsck_cli.main(args) == 0
