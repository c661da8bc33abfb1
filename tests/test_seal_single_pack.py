"""A seal serialises the state once (ISSUE 51).

Where no delta link will be planned, ``Core._plan_seal`` takes the state's
canonical bytes from the adapter's own pack (``CrdtAdapter.state_pack``) and
builds no object; the checkpoint of a state with no columnar format carries
those same bytes as one ``bin`` (format 2).  What that rests on is held here:
the pack equals ``codec.pack(state_to_obj(state))`` for every adapter and for
every way an ``LWWMap``'s entries come to be, and the sealed files are the
parent's byte for byte.  All structural: nothing here reads a time.
"""

import asyncio
import inspect
import random

import pytest

from crdt_enc_tpu.backends import (
    FsStorage,
    IdentityCryptor,
    MemoryRemote,
    MemoryStorage,
    PlainKeyCryptor,
)
from crdt_enc_tpu.core import Core, OpenOptions, adapters, lwwmap_adapter, orset_adapter
from crdt_enc_tpu.core.core import (
    CHECKPOINT_FMT_BYTES,
    CHECKPOINT_FMT_ORSET,
    unpack_checkpoint_state,
)
from crdt_enc_tpu.models import LWWMap, LWWOp, canonical_bytes
from crdt_enc_tpu.models.orset import AddOp
from crdt_enc_tpu.parallel import TpuAccelerator
from crdt_enc_tpu.utils import VersionBytes, codec, trace
from crdt_enc_tpu.utils.versions import DEFAULT_DATA_VERSION_1


@pytest.fixture(autouse=True)
def clean_registry():
    trace.reset()
    yield
    trace.reset()


def counters() -> dict:
    return trace.snapshot()["counters"]


# ---- (a) the entries invariant, over every writer of ``entries`` -----------

ACTORS = [bytes([a]) * 16 for a in range(1, 6)]
# what a caller may hand ``LWWOp`` as its flag: the pack must not see them
FLAGS = (False, True, 0, 1)


def _random_ops(rng: random.Random, n: int) -> list:
    """Writes and deletes over a small key space, so that keys collide, with
    timestamp ties and flags that are not ``bool``."""
    return [
        LWWOp(
            rng.choice([rng.randrange(40), b"k%d" % rng.randrange(40)]),
            rng.randrange(1 << 40) if rng.random() < 0.9 else 7,
            rng.choice(ACTORS),
            rng.choice([rng.randrange(100), b"v", None, [1, b"x"]]),
            rng.choice(FLAGS),
        )
        for _ in range(n)
    ]


def _by_apply(rng):
    m = LWWMap()
    for op in _random_ops(rng, 300):
        # both doors of ``apply``: the op, and its wire form
        m.apply(op if rng.random() < 0.5 else op.to_obj())
    return m


def _by_merge(rng):
    m = _by_apply(rng)
    for _ in range(3):
        m.merge(_by_apply(rng))
    return m


def _by_from_obj(rng):
    # through the wire: arrays come back as tuples, flags as they were packed
    return LWWMap.from_obj(codec.unpack(codec.pack(_by_merge(rng).to_obj())))


def _by_fold_lww(rng):
    """The accelerator's writeback, into an empty map (entries installed
    whole) and then into a map that has entries (resolved one by one)."""
    accel = TpuAccelerator(min_device_batch=1)
    m = LWWMap()
    for _ in range(2):
        ops = [
            LWWOp(op.key, op.ts, op.actor, rng.randrange(100), bool(op.tombstone))
            for op in _random_ops(rng, 300)
        ]
        m = accel.fold_ops(m, ops)
    assert counters().get("lww_folds") == 2
    return m


BUILDERS = {
    "apply": _by_apply,
    "merge": _by_merge,
    "from_obj": _by_from_obj,
    "fold_lww": _by_fold_lww,
}


@pytest.mark.parametrize("seed", range(3))
@pytest.mark.parametrize("how", list(BUILDERS))
def test_lww_pack_of_the_live_entries_is_the_pack_of_to_obj(how, seed):
    m = BUILDERS[how](random.Random(f"{how}:{seed}"))
    assert m.entries
    for entry in m.entries.values():
        ts, actor, _, tomb = entry
        assert len(entry) == 4
        assert type(ts) is int and type(actor) is bytes and type(tomb) is bool
    assert lwwmap_adapter().state_pack(m) == codec.pack(m.to_obj())


def test_lww_apply_stores_the_flag_as_a_bool():
    m = LWWMap()
    m.apply(LWWOp("k", 1, ACTORS[0], "v", tombstone=1))
    m.apply(LWWOp("j", 1, ACTORS[0], "v", tombstone=0))
    assert m.entries == {"k": [1, ACTORS[0], None, True], "j": [1, ACTORS[0], "v", False]}
    assert all(type(e[3]) is bool for e in m.entries.values())


# ---- (b) the default pack, for every adapter ------------------------------


def _state_gcounter(s):
    s.apply(s.inc(ACTORS[0], 3))
    s.apply(s.inc(ACTORS[1], 1))


def _state_pncounter(s):
    s.apply(s.inc(ACTORS[0]))
    s.apply(s.dec(ACTORS[1]))


def _state_orset(s):
    for i in range(5):
        s.apply(s.add_ctx(ACTORS[i % 2], b"m%d" % i))
    s.apply(s.rm_ctx(b"m1"))


def _state_lwwmap(s):
    for op in _random_ops(random.Random(51), 50):
        s.apply(op)


def _state_mvreg(s):
    s.apply(s.write_ctx(ACTORS[0], [b"v", 1]))


def _state_gset(s):
    for i in (3, 1, 2):
        s.apply(s.insert_ctx(i))


def _state_lwwreg(s):
    s.apply(s.write(9, ACTORS[0], "new"))


def _state_merklereg(s):
    s.apply(s.write_ctx("x"))


def _state_list(s):
    s.apply(s.insert_ctx(ACTORS[0], 0, "b"))
    s.apply(s.insert_ctx(ACTORS[0], 0, "a"))


def _state_map(s):
    for i in range(4):
        s.apply(s.update_ctx(ACTORS[0], "k%d" % (i % 2), lambda c, d, i=i: AddOp(i, d)))


STATES = {
    "gcounter": _state_gcounter,
    "pncounter": _state_pncounter,
    "orset": _state_orset,
    "lwwmap": _state_lwwmap,
    "mvreg": _state_mvreg,
    "gset": _state_gset,
    "lwwreg": _state_lwwreg,
    "merklereg": _state_merklereg,
    "list": _state_list,
    "map": _state_map,
    "empty": lambda s: None,
}
ADAPTER_FNS = {
    name[: -len("_adapter")]: fn
    for name, fn in inspect.getmembers(adapters, inspect.isfunction)
    if name.endswith("_adapter")
}


def test_every_adapter_of_the_module_has_a_case():
    assert sorted(ADAPTER_FNS) == sorted(STATES)


@pytest.mark.parametrize("name", sorted(STATES))
def test_state_pack_is_the_pack_of_state_to_obj(name):
    adapter = ADAPTER_FNS[name]()
    for fill in (lambda s: None, STATES[name]):
        state = adapter.new()
        fill(state)
        packed = adapter.state_pack(state)
        assert packed == codec.pack(adapter.state_to_obj(state))
        assert packed == canonical_bytes(state)
        again = adapter.state_from_obj(codec.unpack(packed))
        assert adapter.state_pack(again) == packed


def test_an_adapter_that_says_nothing_packs_through_its_own_state_to_obj():
    calls = []
    adapter = adapters.CrdtAdapter(
        name=b"x", new=LWWMap, state_from_obj=LWWMap.from_obj,
        state_to_obj=lambda s: calls.append(s) or s.to_obj(),
    )
    m = LWWMap()
    m.apply(LWWOp("k", 1, ACTORS[0], "v"))
    assert adapter.state_pack(m) == codec.pack(m.to_obj())
    assert calls == [m]


# ---- (c), (f) what one compact() seals -------------------------------------


def make_opts(storage, adapter, **kw):
    return OpenOptions(
        storage=storage,
        cryptor=IdentityCryptor(),
        key_cryptor=PlainKeyCryptor(),
        adapter=adapter,
        supported_data_versions=(DEFAULT_DATA_VERSION_1,),
        current_data_version=DEFAULT_DATA_VERSION_1,
        create=True,
        **kw,
    )


@pytest.fixture(params=["memory", "fs"])
def storage(request, tmp_path):
    if request.param == "memory":
        return MemoryStorage(MemoryRemote())
    return FsStorage(str(tmp_path / "local"), str(tmp_path / "remote"))


async def _sealed_content(core, blob: bytes) -> bytes:
    """The canonically packed payload inside a sealed blob, as bytes."""
    outer = VersionBytes.deserialize(blob)
    key_id, middle = codec.unpack(outer.content)
    key = core._data.keys.get_key(bytes(key_id))
    clear = await core.cryptor.decrypt(key.material, bytes(middle))
    return VersionBytes.deserialize(clear).content


# the spans a compaction opens from its seal plan on, in the order they
# begin, as the parent of ISSUE 51 opened them (a first seal: no base yet;
# a later one: a link cut, verified and published)
ORSET_TAIL_FIRST = [
    "seal.state_obj", "delta.plan", "delta.pack", "checkpoint.save", "compact.seal",
    "compact.write", "compact.gc", "checkpoint.save",
]
ORSET_TAIL_LATER = [
    "seal.state_obj", "delta.plan", "delta.pack", "delta.base_unpack", "delta.diff",
    "checkpoint.save", "compact.seal", "compact.write", "delta.size", "delta.verify",
    "delta.verify.apply", "delta.verify.pack", "delta.seal", "repl.watermark",
    "compact.gc", "checkpoint.save",
]
LWW_TAIL = [
    "seal.state_obj", "checkpoint.save", "compact.seal", "compact.write", "compact.gc",
    "checkpoint.save",
]


def _tail_spans() -> list:
    # a collector pass that lands in the tail is a ``pause`` entry with an id
    # of its own (``runtime.gc``), and no span of the seal
    names = [
        e["name"] for e in sorted(
            (e for e in trace.events() if "id" in e and e.get("kind") != "pause"),
            key=lambda e: e["id"],
        )
    ]
    return names[names.index("seal.state_obj"):names.index("repl.status")]


def test_lww_compact_serialises_the_map_once(storage, monkeypatch):
    to_obj_calls = []
    to_obj = LWWMap.to_obj
    monkeypatch.setattr(
        LWWMap, "to_obj", lambda self: to_obj_calls.append(1) or to_obj(self)
    )

    async def go():
        core = await Core.open(make_opts(storage, lwwmap_adapter()))
        rng = random.Random(51)
        for round_ in range(2):
            await core.apply_ops(_random_ops(rng, 60))
            trace.reset()
            trace.enable_events()
            del to_obj_calls[:]
            await core.compact()
            assert not to_obj_calls, "a seal built the map as an object"
            c = counters()
            assert c.get("seal_pack_inplace") == 1 and "seal_pack_obj" not in c
            assert c.get("checkpoint_pack_shared") == 1
            assert "checkpoint_pack_bytes" not in c
            assert _tail_spans() == LWW_TAIL
            # the snapshot: the parent's form, byte for byte
            state_bytes = codec.pack(to_obj(core._data.state))
            (name,) = await storage.list_state_names()
            ((_, blob),) = await storage.load_states([name])
            assert await _sealed_content(core, blob) == codec.pack_array((
                state_bytes,
                codec.pack(core._data.next_op_versions.to_obj()),
                codec.pack(core.actor_id),
            ))
            # the checkpoint: format 2, and its state IS those bytes
            ckpt = await core._open_sealed(await storage.load_local_checkpoint())
            assert int(ckpt[b"fmt"]) == CHECKPOINT_FMT_BYTES == 2
            assert ckpt[b"state"] == state_bytes
            assert bytes(ckpt[b"snap"]).decode() == name and name in ckpt[b"rs"]
            restored = unpack_checkpoint_state(core.adapter, 2, ckpt[b"state"])
            assert canonical_bytes(restored) == state_bytes

    asyncio.run(go())


def test_orset_compact_keeps_its_object_its_spans_and_format_1(storage):
    async def go():
        core = await Core.open(make_opts(storage, orset_adapter()))
        for round_, expected in enumerate((ORSET_TAIL_FIRST, ORSET_TAIL_LATER)):
            for i in range(6):
                await core.update(
                    lambda s, i=i: s.add_ctx(core.actor_id, b"m%d" % (i + 6 * round_))
                )
            trace.reset()
            trace.enable_events()
            await core.compact()
            c = counters()
            assert c.get("seal_pack_obj") == 1 and "seal_pack_inplace" not in c
            assert "checkpoint_pack_shared" not in c and "checkpoint_pack_bytes" not in c
            assert c.get("checkpoint_pack_native", 0) + c.get("checkpoint_pack_walk", 0) == 1
            assert _tail_spans() == expected
            ckpt = await core._open_sealed(await storage.load_local_checkpoint())
            assert int(ckpt[b"fmt"]) == CHECKPOINT_FMT_ORSET == 1

    asyncio.run(go())


def test_orset_compact_with_deltas_off_packs_in_place_and_keeps_format_1(storage):
    """What decides is whether a link will be planned, never the adapter's
    name: an OR-Set with deltas off has no plan to read an object either."""

    async def go():
        core = await Core.open(make_opts(storage, orset_adapter(), delta=False))
        for i in range(6):
            await core.update(lambda s, i=i: s.add_ctx(core.actor_id, b"m%d" % i))
        trace.reset()
        await core.compact()
        c = counters()
        assert c.get("seal_pack_inplace") == 1 and "seal_pack_obj" not in c
        assert "checkpoint_pack_shared" not in c
        (name,) = await storage.list_state_names()
        ((_, blob),) = await storage.load_states([name])
        snapshot = await core._open_sealed(blob)
        assert codec.pack(snapshot[0]) == core.with_state(canonical_bytes)
        ckpt = await core._open_sealed(await storage.load_local_checkpoint())
        assert int(ckpt[b"fmt"]) == CHECKPOINT_FMT_ORSET

    asyncio.run(go())


def test_save_checkpoint_alone_makes_the_bytes_itself(storage):
    async def go():
        core = await Core.open(make_opts(storage, lwwmap_adapter()))
        await core.apply_ops(_random_ops(random.Random(3), 40))
        trace.reset()
        assert await core.save_checkpoint()
        c = counters()
        assert c.get("checkpoint_pack_bytes") == 1
        assert "checkpoint_pack_shared" not in c and "seal_pack_inplace" not in c
        ckpt = await core._open_sealed(await storage.load_local_checkpoint())
        assert int(ckpt[b"fmt"]) == 2
        assert ckpt[b"state"] == core.with_state(canonical_bytes)

    asyncio.run(go())
