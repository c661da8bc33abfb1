"""The seal-time self-verify compares objects where the plan owns the one its
bytes were packed from (ISSUE 46, docs/delta.md "seal-time self-verify").

A host-route plan whose ``state_obj`` was built by ``_plan_seal`` itself keeps
it to the verify, which accepts the delta when the applied base, as an object,
is canonically the same (``codec.canon_same``: true only where the two pack to
the same bytes) and packs nothing; every other plan, and every other answer,
is decided by ``pack(...) == new_bytes`` as before.  The gates: which of the
two comparisons decided is counted and is what the plan's route says; a delta
that does not refold is still refused, by the bytes; every published file is
the file the byte comparison alone publishes; the object lives from the plan
to the comparison and no further; and no adapter's object is reached by a
later mutation of its state, which is what the comparison's soundness rests
on.
"""

import copy
import random

import pytest
from _seal_drive import Fleet, add_members, remove_members, run_pinned
from test_delta_base_reuse import CODECS, ROUNDS, _apply_each, _history

from crdt_enc_tpu.core import Core
from crdt_enc_tpu.delta import codec as delta_codec
from crdt_enc_tpu.delta import codec_for
from crdt_enc_tpu.models import canonical_bytes
from crdt_enc_tpu.utils import codec, trace


def counters():
    return trace.snapshot()["counters"]


def decided(c):
    return c.get("delta_verify_structural", 0), c.get("delta_verify_bytes", 0)


@pytest.fixture
def plans(monkeypatch):
    """Every delta plan made, and what its ``state_obj`` was when its verify
    began (absent: no verify ran)."""
    seen = []
    plan_real = Core._plan_delta_seal
    verify_real = Core._verify_delta_plan

    def plan(self, *args, **kw):
        out = plan_real(self, *args, **kw)
        seen.append({"plan": out, "planned": out and out["state_obj"]})
        return out

    def verify(self, dp):
        (rec,) = [r for r in seen if r["plan"] is dp]
        rec["at_verify"] = dp["state_obj"]
        rec["held"] = verify_real(self, dp)
        rec["after_verify"] = dp["state_obj"]
        return rec["held"]

    monkeypatch.setattr(Core, "_plan_delta_seal", plan)
    monkeypatch.setattr(Core, "_verify_delta_plan", verify)
    return seen


async def _two_rounds(fleet, which="orset", before_second=None):
    """A sealer with a base (round one), then a host-route round with a link
    to cut (round two, counted)."""
    make_adapter, round_ops = CODECS[which]
    rng = random.Random(46)
    writer = await fleet.open("w", adapter=make_adapter())
    sealer = await fleet.open("s", adapter=make_adapter())
    for r in range(3):  # a base several times the size of a round's link
        await _apply_each(writer, round_ops(rng, writer.actor_id, r))
    await sealer.compact()
    await _apply_each(writer, round_ops(rng, writer.actor_id, 3))
    if before_second is not None:
        before_second(sealer)
    trace.reset()
    await sealer.compact()
    return sealer, dict(counters()), trace.snapshot()["spans"]


# ---- which comparison decided ---------------------------------------------


@pytest.mark.parametrize("which", sorted(CODECS))
def test_host_route_round_is_decided_by_the_objects(which, tmp_path, plans):
    fleet = Fleet("memory", "job", tmp_path)
    sealer, counted, spans = run_pinned(lambda: _two_rounds(fleet, which))
    if not counted.get("delta_files_sealed"):
        assert which not in ("orset", "gset")
        # a counter's whole state (and the resettable one's after its reset)
        # is no larger than its link: the size guard kept the link back
        # before any verify, and dropped the object
        assert counted.get("delta_seal_skipped") == 1
        assert decided(counted) == (0, 0)
        assert plans[-1]["planned"] is not None
        assert plans[-1]["plan"]["state_obj"] is None
        return
    assert decided(counted) == (1, 0)
    assert spans["delta.verify.pack"]["count"] == 1
    assert spans["delta.verify"]["count"] == 1
    assert not counted.get("delta_seal_divergence")
    rec = plans[-1]
    # the plan kept the object it packed, the verify read it and dropped it
    assert rec["planned"] is not None and rec["at_verify"] is rec["planned"]
    assert rec["held"] is True and rec["after_verify"] is None
    assert codec.pack(rec["planned"]) == rec["plan"]["new_bytes"]
    # and what it vouched for is what the bytes say
    base = sealer._delta_base
    assert codec.pack(sealer.adapter.state_to_obj(base["state"])) == base["bytes"]


def test_every_verified_link_of_a_history_is_counted_once(tmp_path):
    fleet = Fleet("fs", "job", tmp_path)
    _, counted, _ = run_pinned(lambda: _history(fleet, "orset", True))
    sealed = [c.get("delta_files_sealed", 0) for c in counted]
    assert sum(sealed) >= ROUNDS - 3
    assert [decided(c) for c in counted] == [(n, 0) for n in sealed]


# ---- a delta that does not refold is refused, by the bytes ----------------


def _drop_one_add(dobj):
    dobj[b"e"].pop(next(iter(dobj[b"e"])))


def _raise_one_counter(dobj):
    adds = dobj[b"e"]
    member = next(iter(adds))
    slots = adds[member]
    actor = next(iter(slots))
    adds[member] = {**slots, actor: slots[actor] + 1}


@pytest.mark.parametrize("corrupt", [_drop_one_add, _raise_one_counter])
def test_corrupted_link_is_refused_and_the_bytes_decided(
    corrupt, tmp_path, monkeypatch, plans
):
    real = delta_codec._OrsetCodec.diff

    def lossy(base, new):
        dobj = real(base, new)
        corrupt(dobj)
        return dobj

    def arm(sealer):
        monkeypatch.setattr(
            delta_codec._OrsetCodec, "diff", staticmethod(lossy)
        )

    fleet = Fleet("memory", "job", tmp_path)
    sealer, counted, spans = run_pinned(
        lambda: _two_rounds(fleet, before_second=arm)
    )
    assert counted.get("delta_seal_divergence") == 1
    assert not counted.get("delta_files_sealed")
    assert decided(counted) == (0, 1)
    assert spans["delta.verify.pack"]["count"] == 1
    rec = plans[-1]
    assert rec["at_verify"] is not None and rec["held"] is False
    assert rec["after_verify"] is None
    # snapshot only: the base moved on as bytes, with no object
    assert sealer._delta_base["state"] is None
    assert sealer._delta_base["bytes"] == sealer.with_state(canonical_bytes)


def test_a_difference_the_walk_cannot_name_goes_to_the_bytes(
    tmp_path, monkeypatch, plans
):
    """``None`` from the comparison (no native build, a key it declines) is
    not a refusal: the bytes decide, and here they hold."""
    monkeypatch.setattr(codec, "canon_same", lambda a, b: None)
    fleet = Fleet("memory", "job", tmp_path)
    _, counted, _ = run_pinned(lambda: _two_rounds(fleet))
    assert counted.get("delta_files_sealed") == 1
    assert decided(counted) == (0, 1)
    assert plans[-1]["held"] is True and plans[-1]["after_verify"] is None


# ---- a plan that does not own its object keeps the byte comparison --------


def test_callers_object_is_never_kept(tmp_path, plans):
    """The service's form: an object that aliases the live entry dicts,
    valid at its epoch alone."""

    async def go():
        fleet = Fleet("memory", "job", tmp_path)
        writer = await fleet.open("w")
        sealer = await fleet.open("s")
        await add_members(writer, [b"a%d" % i for i in range(12)])
        await sealer.compact()
        await add_members(writer, [b"b%d" % i for i in range(5)])
        await sealer.read_remote()
        state = sealer._data.state
        aliased = {
            b"c": state.clock.to_obj(), b"e": state.entries,
            b"d": state.deferred,
        }
        trace.reset()
        await sealer._compact_seal(_state_obj=(aliased, state._mut))
        first = dict(counters())
        # the same caller an epoch late: the plan builds its own, and owns it
        await add_members(writer, [b"c%d" % i for i in range(5)])
        await sealer.read_remote()
        trace.reset()
        await sealer._compact_seal(_state_obj=(aliased, state._mut - 1))
        return first, dict(counters())

    first, second = run_pinned(go)
    assert first.get("delta_files_sealed") == 1 and decided(first) == (0, 1)
    assert plans[-2]["planned"] is None and plans[-2]["at_verify"] is None
    assert second.get("delta_files_sealed") == 1 and decided(second) == (1, 0)
    assert plans[-1]["planned"] is not None


@pytest.mark.parametrize("case", ["device_cut", "host_served"])
def test_served_tenants_keep_the_byte_comparison(case, tmp_path, plans):
    from test_delta_base_reuse import _seal_case

    fleet = Fleet("memory", "job", tmp_path)
    _, counted = run_pinned(lambda: _seal_case(fleet, case))
    assert counted.get("delta_files_sealed") == 1
    assert decided(counted) == (0, 1)
    assert bool(counted.get("delta_device_cuts")) == (case == "device_cut")
    assert plans[-1]["planned"] is None and plans[-1]["held"] is True


# ---- byte identity with the fast path forced away -------------------------


@pytest.mark.parametrize("which", sorted(CODECS))
@pytest.mark.parametrize("kind", ["memory", "fs"])
def test_every_file_is_the_byte_comparisons_file(
    kind, which, tmp_path, monkeypatch
):
    fleet = Fleet(kind, "job", tmp_path / "objects")
    rounds, counted, _ = run_pinned(lambda: _history(fleet, which, True))
    trace.reset()
    with monkeypatch.context() as m:
        m.setattr(codec, "canon_same", lambda a, b: None)
        fleet_b = Fleet(kind, "job", tmp_path / "bytes")
        rounds_b, counted_b, _ = run_pinned(
            lambda: _history(fleet_b, which, True)
        )
    assert len(rounds) == ROUNDS >= 3
    for r, (got, want) in enumerate(zip(rounds, rounds_b)):
        for family in got:  # snapshots, links, checkpoint, local meta, ...
            assert got[family] == want[family], (which, r, family)
    verified = [sum(decided(c)) for c in counted]
    assert verified == [sum(decided(c)) for c in counted_b]
    assert [decided(c) for c in counted] == [(n, 0) for n in verified]
    assert [decided(c) for c in counted_b] == [(0, n) for n in verified]
    sealed = sum(c.get("delta_files_sealed", 0) for c in counted)
    assert sealed == sum(c.get("delta_files_sealed", 0) for c in counted_b)
    assert ROUNDS - 3 <= sealed <= sum(verified)
    assert not any(c.get("delta_seal_divergence") for c in counted + counted_b)


# ---- the object lives from the plan to the comparison, no further ---------


async def _tail(fleet, case, monkeypatch):
    writer = await fleet.open("w")
    sealer = await fleet.open("s")
    await add_members(writer, [b"a%d" % i for i in range(12)])
    await sealer.compact()
    if case == "reseal":  # nothing written: the name it seals is its base's
        await sealer.compact()
        return
    await add_members(writer, [b"b%d" % i for i in range(5)])
    if case == "crashed":
        def boom(state, dobj):
            raise RuntimeError("apply crashed")

        monkeypatch.setattr(
            delta_codec._OrsetCodec, "apply", staticmethod(boom)
        )
    if case == "refused":
        real = delta_codec._OrsetCodec.diff

        def lossy(base, new):
            dobj = real(base, new)
            _drop_one_add(dobj)
            return dobj

        monkeypatch.setattr(
            delta_codec._OrsetCodec, "diff", staticmethod(lossy)
        )
    if case == "size_guard":
        await sealer.compact()
        await remove_members(
            writer, [b"a%d" % i for i in range(12)]
            + [b"b%d" % i for i in range(5)]
        )
    trace.reset()
    await sealer.compact()


@pytest.mark.parametrize("case, planned, verdict", [
    ("held", True, True),
    ("refused", True, False),
    ("crashed", True, False),
    ("size_guard", True, None),
    ("reseal", True, None),
    ("verify_off", False, None),
])
def test_plans_object_is_gone_when_the_verify_ends(
    case, planned, verdict, tmp_path, monkeypatch, plans
):
    if case == "verify_off":
        monkeypatch.setenv("CRDT_DELTA_VERIFY", "0")
    fleet = Fleet("memory", "job", tmp_path)
    run_pinned(lambda: _tail(fleet, case, monkeypatch))
    rec = plans[-1]
    assert (rec["planned"] is not None) == planned
    assert rec.get("held") is verdict
    if verdict is not None:
        assert rec["at_verify"] is rec["planned"]
        assert rec["after_verify"] is None
    assert rec["plan"]["state_obj"] is None
    want = {
        "held": (1, 0), "refused": (0, 1), "crashed": (0, 0),
    }.get(case, (0, 0))
    assert decided(counters()) == want
    assert bool(counters().get("delta_seal_divergence")) == (verdict is False)


def test_first_seal_and_a_plan_with_no_delta_carry_none(tmp_path, plans):
    async def go():
        fleet = Fleet("memory", "job", tmp_path)
        writer = await fleet.open("w")
        await add_members(writer, [b"a%d" % i for i in range(4)])
        sealer = await fleet.open("s")
        await sealer.compact()

    run_pinned(go)
    assert plans and all(r["planned"] is None for r in plans)
    assert all("held" not in r for r in plans)


# ---- what the comparison's soundness rests on -----------------------------


@pytest.mark.parametrize("which", sorted(CODECS))
def test_no_later_mutation_of_the_state_reaches_its_object(which, tmp_path):
    """``state_to_obj`` of every adapter with a delta codec is a copy: the
    plan's object is read on a worker thread while the loop goes on folding
    into the live state."""
    make_adapter, round_ops = CODECS[which]
    assert codec_for(make_adapter().name) is not None

    async def go():
        fleet = Fleet("memory", "job", tmp_path)
        rng = random.Random(7)
        core = await fleet.open("w", adapter=make_adapter())
        peer = await fleet.open("p", adapter=make_adapter())
        for r in range(3):
            await _apply_each(core, round_ops(rng, core.actor_id, r))
        state = core._data.state
        obj = core.adapter.state_to_obj(state)
        packed, twin = codec.pack(obj), copy.deepcopy(obj)
        # local ops, a foreign snapshot merged in, a link applied in place
        for r in range(3, ROUNDS):
            await _apply_each(core, round_ops(rng, core.actor_id, r))
            await _apply_each(peer, round_ops(rng, peer.actor_id, r))
        await peer.compact()
        await core.read_remote()
        assert core._data.state is state
        newer = peer._data.state
        codec_cls = codec_for(core.adapter.name)
        dobj = codec_cls.diff(core.adapter.state_from_obj(twin), newer)
        if dobj is not None:
            codec_cls.apply(state, dobj)
        assert codec.pack(core.adapter.state_to_obj(state)) != packed
        assert codec.pack(obj) == packed
        assert obj == twin
        assert codec.canon_same(obj, twin) is True

    run_pinned(go)
