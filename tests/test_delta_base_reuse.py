"""The retained delta base as an object (ISSUE 42, docs/delta.md "The
retained base").

A seal-time verify that holds ends with the plan's base copy equal to the
snapshot just sealed; the Core keeps that object beside the bytes and the
next host-route plan diffs against it instead of unpacking the bytes.  The
gate is differential: every file a reusing Core publishes is the file a Core
forced onto the bytes route publishes (the object dropped before each plan),
byte for byte, for every delta codec; and the object has exactly one source
and one owner at a time.

Entropy is pinned as in tests/_seal_drive.py, so two runs of one script seal
equal payloads to equal files.
"""

import asyncio
import copy
import random
import threading

import pytest
from _seal_drive import (
    DRIVES,
    Fleet,
    Injected,
    add_members,
    failing,
    published,
    remove_members,
    run_pinned,
)

from crdt_enc_tpu.backends import FsStorage, MemoryStorage
from crdt_enc_tpu.core import (
    gcounter_adapter,
    gset_adapter,
    orset_adapter,
    pncounter_adapter,
)
from crdt_enc_tpu.delta import ResettableCounter, rcounter_adapter
from crdt_enc_tpu.models import ORSet, canonical_bytes
from crdt_enc_tpu.models.orset import AddOp, RmOp
from crdt_enc_tpu.models.vclock import Dot, VClock
from crdt_enc_tpu.utils import codec, trace

ROUNDS = 7
GHOST = b"\xee" * 16  # a device whose files arrive late: its dots are horizons
WIDE = [bytes([0xA0 + i]) * 16 for i in range(8)]  # widen a counter's clock


def counters():
    return trace.snapshot()["counters"]


def drop_object(core):
    """Force the bytes route: what every plan found before this mechanism."""
    if core._delta_base is not None:
        core._delta_base["state"] = None


# ---- a round's writes, one script per codec -------------------------------


def _orset_round(rng, actor, r):
    """Adds and removes every round; a remove horizon above the clock in
    round 2 (it rides the link's ``t``), the ghost's own adds of ANOTHER
    member in round 3 (the clock catches up: the horizon is inert, and only a
    global normalisation retires it), a higher horizon on the same member in
    round 4 (the diff reads the base's as its floor)."""
    ops = [
        (lambda s, m=b"m%d-%d" % (r, i): s.add_ctx(actor, m))
        for i in range(5 + rng.randrange(4))
    ]
    for back in range(1, 3):
        m = b"m%d-%d" % (max(0, r - back), rng.randrange(5))
        ops.append(lambda s, m=m: s.rm_ctx(m) if s.contains(m) else None)
    if r == 2:
        ops.append(lambda s: RmOp(b"m0-4", VClock({GHOST: 3})))
        ops.append(lambda s: RmOp(b"never", VClock({GHOST: 2})))
    if r == 3:
        ops += [
            (lambda s, c=c: AddOp(b"ghost-wrote", Dot(GHOST, c)))
            for c in (1, 2, 3)
        ]
    if r == 4:
        ops.append(lambda s: RmOp(b"m0-4", VClock({GHOST: 6})))
    return ops


def _rcounter_round(rng, actor, r):
    ops = [
        (lambda s, n=1 + rng.randrange(3): ResettableCounter.inc(s, actor, n))
        for _ in range(4)
    ]
    if r == 3:
        ops.append(lambda s: ResettableCounter.reset(s))
    return ops


def _gcounter_round(rng, actor, r):
    who = [actor] + (WIDE if r == 0 else [WIDE[r % len(WIDE)]])
    return [(lambda s, a=a, n=1 + rng.randrange(4): s.inc(a, n)) for a in who]


def _pncounter_round(rng, actor, r):
    who = [actor] + (WIDE if r == 0 else [WIDE[r % len(WIDE)]])
    ops = [(lambda s, a=a, n=1 + rng.randrange(4): s.inc(a, n)) for a in who]
    return ops + [
        (lambda s, a=a: s.dec(a, 1)) for a in (who if r == 0 else who[:1])
    ]


def _gset_round(rng, actor, r):
    return [
        (lambda s, m=b"g%d-%d" % (r, i): s.insert_ctx(m))
        for i in range(4 + rng.randrange(4))
    ] + [lambda s, m=(r, b"pair"): s.insert_ctx(m)]


CODECS = {
    "orset": (orset_adapter, _orset_round),
    "rcounter": (rcounter_adapter, _rcounter_round),  # rides the OR-Set codec
    "gcounter": (gcounter_adapter, _gcounter_round),
    "pncounter": (pncounter_adapter, _pncounter_round),
    "gset": (gset_adapter, _gset_round),
}


async def _apply_each(core, builders):
    for build in builders:
        await core.update(build)


async def _history(fleet, which, reuse, fail_round=None):
    """``ROUNDS`` compactions of one sealer over a writer's ops and, every
    other round, a foreign compactor's snapshot.  Returns what each round
    published, the counters of each round, and what the sealer held as its
    retained object before each plan."""
    make_adapter, round_ops = CODECS[which]
    rng = random.Random(42)
    writer = await fleet.open("w", adapter=make_adapter())
    peer = await fleet.open("p", adapter=make_adapter())
    sealer = await fleet.open("s", adapter=make_adapter())
    storage = fleet.inner["s"]
    rounds, counted, held = [], [], []
    for r in range(ROUNDS):
        await _apply_each(writer, round_ops(rng, writer.actor_id, r))
        if r % 2:
            # a foreign merge: the peer folds, seals and GCs on its own, and
            # the sealer's next read finds a snapshot it has not merged
            await _apply_each(peer, round_ops(rng, peer.actor_id, r)[:3])
            await peer.compact()
        if not reuse:
            drop_object(sealer)
        base = sealer._delta_base
        # a copy: the object itself moves on from round to round, mutated
        held.append(None if base is None else copy.deepcopy(base["state"]))
        trace.reset()
        if r == fail_round:
            storage.fail = "store_delta"
            with pytest.raises(Injected):
                await sealer.compact()
            storage.fail = None
            rounds.append(("failed", dict(base), await published(storage)))
            counted.append(dict(counters()))
            continue
        await sealer.compact()
        counted.append(dict(counters()))
        rounds.append(await published(storage))
    # whatever route cut the links, a cold reader of the remote agrees
    cold = await fleet.open("cold", adapter=make_adapter())
    await cold.read_remote()
    assert cold.with_state(canonical_bytes) == sealer.with_state(
        canonical_bytes
    )
    return rounds, counted, held


def _both_routes(kind, which, tmp_path, drive="job", cls=None, **kw):
    seen = {}
    for reuse in (True, False):
        fleet = Fleet(kind, drive, tmp_path / f"reuse-{reuse}", cls=cls)
        seen[reuse] = run_pinned(lambda: _history(fleet, which, reuse, **kw))
        trace.reset()
    return seen[True], seen[False]


# ---- (a) byte identity over a seeded history, every codec -----------------


@pytest.mark.parametrize("which", sorted(CODECS))
@pytest.mark.parametrize("kind", ["memory", "fs"])
def test_reused_object_seals_the_bytes_routes_files(kind, which, tmp_path):
    (rounds, counted, held), (rounds_b, counted_b, held_b) = _both_routes(
        kind, which, tmp_path
    )
    assert len(rounds) == ROUNDS >= 6
    for r, (got, want) in enumerate(zip(rounds, rounds_b)):
        assert got["states"] and got["deltas"] is not None
        for family in got:  # snapshots, links, checkpoint, local meta, ...
            assert got[family] == want[family], (which, r, family)
    # the comparison compared something: links were sealed, by both routes
    sealed = [c.get("delta_files_sealed", 0) for c in counted]
    assert sealed == [c.get("delta_files_sealed", 0) for c in counted_b]
    assert sum(sealed) >= ROUNDS - 3, sealed
    assert sum(c.get("delta_base_reused", 0) for c in counted) >= ROUNDS - 3
    assert not any(c.get("delta_base_reused") for c in counted_b)
    assert all(h is None for h in held_b)
    assert not any(c.get("delta_seal_divergence") for c in counted + counted_b)


def test_orset_object_differs_from_its_bytes_and_cuts_the_same_link(tmp_path):
    """The case the argument in docs/delta.md is about: the retained object
    holds a horizon the clock has caught up with (``to_obj`` filters it, so it
    packs to the snapshot's bytes), the state unpacked from the bytes does
    not, and the next round raises a horizon on that very member."""
    (rounds, _, held), (rounds_b, _, _) = _both_routes(
        "memory", "orset", tmp_path
    )
    inert = [
        r for r, obj in enumerate(held)
        if obj is not None and any(
            h <= obj.clock.get(a)
            for hs in obj.deferred.values() for a, h in hs.items()
        )
    ]
    assert 4 in inert, inert
    obj = held[4]
    assert ORSet.from_obj(obj.to_obj()).deferred != obj.deferred
    assert rounds[4] == rounds_b[4]
    # and the link of that round carries the raised horizon
    link = max(rounds[4]["deltas"])
    assert b"m0-4" in rounds[4]["deltas"][link]


# ---- (f) the two counters --------------------------------------------------


@pytest.mark.parametrize("which", sorted(CODECS))
def test_counters_name_the_route_of_every_plan(which, tmp_path):
    (_, counted, held), (_, counted_b, _) = _both_routes(
        "memory", which, tmp_path
    )

    def route(c):
        return c.get("delta_base_reused", 0), c.get("delta_base_unpacked", 0)

    # no base, then its bytes alone; from then on the object wherever the
    # round before sealed a link (its verify held), the bytes where the size
    # guard kept the link back: one of the two a round, never both
    assert route(counted[0]) == (0, 0) and held[0] is None
    assert route(counted[1]) == (0, 1) and held[1] is None
    for r in range(2, ROUNDS):
        linked = counted[r - 1].get("delta_files_sealed") == 1
        assert route(counted[r]) == ((1, 0) if linked else (0, 1)), (which, r)
        assert (held[r] is not None) == linked
    assert sum(route(c)[0] for c in counted) >= ROUNDS - 3
    assert route(counted[-1]) == (1, 0)
    assert [route(c) for c in counted_b] == [(0, 0)] + [(0, 1)] * (ROUNDS - 1)


def test_base_unpack_span_opens_only_on_the_bytes_route(tmp_path):
    async def go():
        fleet = Fleet("memory", "job", tmp_path)
        writer = await fleet.open("w")
        sealer = await fleet.open("s")
        spans = []
        for r in range(4):
            await add_members(writer, [b"r%d-%d" % (r, i) for i in range(6)])
            trace.reset()
            await sealer.compact()
            spans.append(trace.snapshot()["spans"])
        return spans

    spans = run_pinned(go)
    assert "delta.base_unpack" not in spans[0]
    assert spans[1]["delta.base_unpack"]["count"] == 1
    for phases in spans[2:]:
        assert "delta.base_unpack" not in phases
        assert phases["delta.diff"]["count"] == 1
        assert phases["delta.verify"]["count"] == 1


# ---- (b) a tail that fails after the verify --------------------------------


@pytest.mark.parametrize("drive", DRIVES)
@pytest.mark.parametrize("kind", ["memory", "fs"])
def test_failed_tail_leaves_the_old_base_without_an_object(
    kind, drive, tmp_path
):
    """``store_delta`` raises once the verify has mutated the plan's object:
    the Core names the old snapshot, holds its bytes and no object, and the
    rounds after it seal what the bytes-route Core seals."""
    cls = failing(MemoryStorage if kind == "memory" else FsStorage)
    (rounds, counted, held), (rounds_b, _, _) = _both_routes(
        kind, "orset", tmp_path, drive=drive, cls=cls, fail_round=3
    )
    tag, before, after = rounds[3]
    assert tag == "failed" and held[3] is not None
    assert counted[3].get("delta_base_reused") == 1  # the object was taken,
    assert not counted[3].get("delta_files_sealed")  # the verify ran, no link
    assert not counted[3].get("delta_seal_divergence")
    # ``before`` is the dict as the failed round left it: the old name
    assert before["name"] in rounds[2]["states"]
    assert before["bytes"] is not None and before["state"] is None
    assert rounds[3][2] == rounds_b[3][2]
    # the retry unpacks the old bytes; the object is back a round later
    assert counted[4].get("delta_base_unpacked") == 1 and held[4] is None
    assert counted[5].get("delta_base_reused") == 1
    assert rounds[4:] == rounds_b[4:]


# ---- (c) nothing but a verify that held retains an object ------------------


async def _seal_case(fleet, case):
    from crdt_enc_tpu.parallel import TpuAccelerator
    from crdt_enc_tpu.serve import FoldService, ServeConfig

    writer = await fleet.open("w")
    await add_members(writer, [b"a%d" % i for i in range(12)])
    if case in ("device_cut", "host_served"):
        sealer = await fleet.open(
            "s", accelerator=TpuAccelerator(min_device_batch=1)
        )
        cfg = ServeConfig() if case == "device_cut" else ServeConfig(warm=False)
        service = FoldService([sealer], cfg)
        (res,) = await service.run_cycle()
        assert res.sealed
        await add_members(writer, [b"b%d" % i for i in range(5)])
        trace.reset()
        (res,) = await service.run_cycle()
        assert res.sealed and res.error is None
        service.close()
        return sealer, dict(counters())
    sealer = await fleet.open("s")
    trace.reset()
    await sealer.compact()  # no base: a delta-less round
    if case == "deltaless":
        return sealer, dict(counters())
    await add_members(writer, [b"b%d" % i for i in range(5)])
    if case == "size_guard":
        await sealer.compact()
        assert sealer._delta_base["state"] is not None
        # a state smaller than the link that would describe its change
        await remove_members(
            writer, [b"a%d" % i for i in range(12)]
            + [b"b%d" % i for i in range(5)]
        )
    trace.reset()
    await sealer.compact()
    return sealer, dict(counters())


@pytest.mark.parametrize("case, want, retained", [
    ("verified", {"delta_files_sealed": 1, "delta_base_unpacked": 1}, True),
    ("host_served", {"delta_files_sealed": 1, "delta_base_unpacked": 1}, True),
    ("verify_off", {"delta_files_sealed": 1, "delta_base_unpacked": 1}, False),
    ("size_guard", {"delta_seal_skipped": 1, "delta_base_reused": 1}, False),
    ("deltaless", {}, False),
    ("device_cut", {"delta_files_sealed": 1, "delta_device_cuts": 1}, False),
])
def test_only_a_verify_that_held_retains_an_object(
    case, want, retained, tmp_path, monkeypatch
):
    if case == "verify_off":
        monkeypatch.setenv("CRDT_DELTA_VERIFY", "0")
    fleet = Fleet("memory", "job", tmp_path)
    sealer, counted = run_pinned(lambda: _seal_case(fleet, case))
    for name, n in want.items():
        assert counted.get(name) == n, (name, counted)
    if case in ("deltaless", "device_cut"):
        assert not counted.get("delta_base_reused")
        assert not counted.get("delta_base_unpacked")
    base = sealer._delta_base
    assert base["name"] == sealer.delta_base_name
    assert (base["state"] is not None) == retained
    assert (base["bytes"] is None) == (case == "device_cut")
    if retained:
        # the object IS the snapshot: it packs to the retained bytes
        assert codec.pack(base["state"].to_obj()) == base["bytes"]
        assert base["state"] is not sealer._data.state


def test_verify_that_fails_retains_no_object(tmp_path, monkeypatch):
    """A codec whose link does not refold: the guard refuses to publish it
    and the mutated base copy is dropped with the plan."""
    from crdt_enc_tpu.delta import codec as delta_codec

    async def go():
        fleet = Fleet("memory", "job", tmp_path)
        writer = await fleet.open("w")
        sealer = await fleet.open("s")
        for r in range(3):
            await add_members(writer, [b"r%d-%d" % (r, i) for i in range(8)])
            await sealer.compact()
        assert sealer._delta_base["state"] is not None
        real = delta_codec._OrsetCodec.diff

        def lossy(base, new):
            dobj = real(base, new)
            dobj[b"e"].pop(next(iter(dobj[b"e"])))
            return dobj

        monkeypatch.setattr(delta_codec._OrsetCodec, "diff", staticmethod(lossy))
        await add_members(writer, [b"late-%d" % i for i in range(8)])
        trace.reset()
        await sealer.compact()
        assert counters().get("delta_seal_divergence") == 1
        assert counters().get("delta_base_reused") == 1
        assert sealer._delta_base["state"] is None
        assert sealer._delta_base["bytes"] == sealer.with_state(
            canonical_bytes
        )
        monkeypatch.undo()
        await add_members(writer, [b"after-%d" % i for i in range(8)])
        trace.reset()
        await sealer.compact()
        assert counters().get("delta_base_unpacked") == 1
        assert counters().get("delta_files_sealed") == 1
        assert sealer._delta_base["state"] is not None

    run_pinned(go)


def test_warm_open_restores_the_bytes_alone(tmp_path):
    async def go():
        fleet = Fleet("fs", "job", tmp_path)
        writer = await fleet.open("w")
        sealer = await fleet.open("s")
        for r in range(3):
            await add_members(writer, [b"r%d-%d" % (r, i) for i in range(8)])
            await sealer.compact()
        held = sealer._delta_base
        assert held["state"] is not None
        opts = fleet.opts("s")
        opts.create = False
        from crdt_enc_tpu.core import Core

        warm = await Core.open(opts)
        assert warm.opened_from_checkpoint
        assert warm._delta_base["name"] == held["name"]
        assert warm._delta_base["bytes"] == held["bytes"]
        assert warm._delta_base["state"] is None

    run_pinned(go)


# ---- (d) an idempotent re-seal ---------------------------------------------


async def _reseal_history(fleet, reuse):
    writer = await fleet.open("w")
    sealer = await fleet.open("s")
    storage = fleet.inner["s"]
    rounds, counted = [], []
    for r in range(6):
        if r not in (3, 4):  # two rounds in which nothing was written
            await add_members(writer, [b"r%d-%d" % (r, i) for i in range(6)])
            if r:
                await remove_members(writer, [b"r%d-0" % (r - 1)])
        if not reuse:
            drop_object(sealer)
        trace.reset()
        await sealer.compact()
        counted.append(dict(counters()))
        rounds.append(await published(storage))
    return rounds, counted, sealer._delta_base


@pytest.mark.parametrize("kind", ["memory", "fs"])
def test_idempotent_reseal_publishes_nothing_and_stays_identical(
    kind, tmp_path
):
    seen = {}
    for reuse in (True, False):
        fleet = Fleet(kind, "job", tmp_path / f"reuse-{reuse}")
        seen[reuse] = run_pinned(lambda: _reseal_history(fleet, reuse))
        trace.reset()
    (rounds, counted, base), (rounds_b, counted_b, _) = seen[True], seen[False]
    for r in (3, 4):
        for family in ("states", "deltas"):
            assert rounds[r][family] == rounds[2][family], (r, family)
        assert not counted[r].get("delta_files_sealed")
        assert counted[r].get("seal_gc_deferred") == 1
    assert rounds == rounds_b
    # the re-seal's plan took the object and its tail named no new base: the
    # second re-seal and the round after them unpack, then the object is back
    assert counted[3].get("delta_base_reused") == 1
    assert counted[4].get("delta_base_unpacked") == 1
    assert counted[5].get("delta_base_unpacked") == 1
    assert counted[5].get("delta_files_sealed") == 1
    assert base["state"] is not None
    assert [c.get("delta_files_sealed") for c in counted] == [
        c.get("delta_files_sealed") for c in counted_b
    ]


# ---- (e) a mutation while the tail runs ------------------------------------


@pytest.mark.parametrize("park_at", ["store_state", "store_delta"])
def test_mutation_while_the_tail_runs_never_reaches_the_object(
    park_at, tmp_path
):
    """A local write lands while the seal job is parked before the verify
    (``store_state``) or after it (``store_delta``).  While the tail runs the
    Core holds no object (the plan took it: a second plan would unpack); what
    the commit retains packs to the plan-time snapshot, without the write; the
    next round's link carries the write, as the bytes route's would."""
    parked, release = threading.Event(), threading.Event()

    class Parking(MemoryStorage):
        pass

    def park(self, *args):
        out = getattr(super(Parking, self), park_at + "_sync")(*args)
        parked.set()
        assert release.wait(30)
        return out

    setattr(Parking, park_at + "_sync", park)

    async def go(reuse):
        fleet = Fleet("memory", "job", tmp_path / f"reuse-{reuse}", cls=Parking)
        writer = await fleet.open("w")
        sealer = await fleet.open("s")
        storage = fleet.inner["s"]
        release.set()
        for r in range(3):
            await add_members(writer, [b"r%d-%d" % (r, i) for i in range(6)])
            await sealer.compact()
        await add_members(writer, [b"r3-%d" % i for i in range(6)])
        if not reuse:
            drop_object(sealer)
        held = sealer._delta_base["state"]
        assert (held is not None) == reuse
        parked.clear()
        release.clear()
        trace.reset()
        tail = asyncio.ensure_future(sealer.compact())
        assert await asyncio.to_thread(parked.wait, 30)
        assert sealer._delta_base["state"] is None  # taken, not borrowed
        plan_time = sealer.with_state(canonical_bytes)
        await sealer.update(lambda s: s.add_ctx(sealer.actor_id, b"late"))
        release.set()
        await tail
        assert counters().get("seal_jobs") == 1
        assert counters().get("delta_files_sealed") == 1
        base = sealer._delta_base
        assert base["bytes"] == plan_time
        obj = base["state"]
        assert obj is not None and obj is not sealer._data.state
        if reuse:
            assert obj is held  # the very object moved on, the link applied
        assert codec.pack(obj.to_obj()) == plan_time
        assert not obj.contains(b"late")
        assert sealer.with_state(lambda s: s.contains(b"late"))
        rounds = [await published(storage)]
        if not reuse:
            drop_object(sealer)
        await sealer.compact()
        rounds.append(await published(storage))
        link = max(rounds[1]["deltas"])
        assert b"late" in rounds[1]["deltas"][link]
        return rounds

    release.set()
    got = run_pinned(lambda: go(True))
    want = run_pinned(lambda: go(False))
    assert got == want
    trace.reset()
