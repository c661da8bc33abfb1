"""``canon_same`` (statebuild.cpp, reached through ``codec.canon_same``):
do two object graphs pack to the same canonical bytes, found without packing
either (ISSUE 46).

The whole contract: ``True`` ONLY IF ``canon_pack(a) == canon_pack(b)``;
``False`` where a difference was found; ``None`` where it cannot say cheaply.
The seal-time self-verify publishes a delta on a ``True`` alone, so a ``True``
that the bytes do not bear out is the one fault that matters here, and Python's
hashing is where it would come from: ``1``, ``True`` and ``1.0`` are one dict
key, ``0.0 == -0.0``, and all of them pack apart.
"""

from __future__ import annotations

import copy
import logging
import random

import numpy as np
import pytest
from _hyp import given, settings  # hypothesis, or skip-stubs
from test_canon_pack import EDGES, _value

from crdt_enc_tpu.utils import codec


@pytest.fixture(scope="module")
def lib():
    from crdt_enc_tpu import native

    try:
        return native.load_state()
    except Exception:
        pytest.skip("native state library unavailable")


def holds(lib, a, b):
    """``canon_same(a, b)``, checked against the bytes it speaks for."""
    same = lib.canon_same(a, b)
    assert same is True or same is False or same is None
    pa, pb = lib.canon_pack(a), lib.canon_pack(b)
    if same is True:
        assert pa is not None and pa == pb, (a, b)
    elif same is False:
        assert pa != pb or pa is None, (a, b)
    return same


# ---- every pair of the packer's own case list -----------------------------


def test_every_pair_of_the_packers_edge_cases(lib):
    packed = [lib.canon_pack(c) for c in EDGES]
    for i, a in enumerate(EDGES):
        for j, b in enumerate(EDGES):
            same = lib.canon_same(a, b)
            if same is True:
                assert packed[i] == packed[j], (i, j)
            elif same is False:
                assert packed[i] != packed[j], (i, j)
            else:
                assert same is None
        # none of these holds a key it must decline: equal to itself
        assert lib.canon_same(a, copy.deepcopy(a)) is True, i


@settings(max_examples=150, deadline=None)
@given(a=_value, b=_value)
def test_hypothesis_pairs_never_claim_more_than_the_bytes(lib, a, b):
    holds(lib, a, b)
    holds(lib, a, copy.deepcopy(a))


# ---- seeded random nested objects -----------------------------------------


def _scalar(rng):
    return rng.choice([
        None, True, False, rng.randrange(-40, 40), rng.randrange(2 ** 40),
        2 ** 63 + rng.randrange(100), -(2 ** 62) - rng.randrange(100),
        rng.random(), 0.0, -0.0, 1.0, rng.randbytes(rng.randrange(6)),
        "s%d" % rng.randrange(5), b"", "",
    ])


def _key(rng, loose):
    keys = [
        rng.randrange(6), rng.randbytes(2), "k%d" % rng.randrange(4), None,
        (rng.randrange(3), rng.randbytes(1)),
    ]
    if loose:  # keys whose Python equality is wider than their bytes
        keys += [True, False, 1.0, 0.0, -0.0, (1, True), (0.0,)]
    return rng.choice(keys)


def _obj(rng, depth, loose):
    roll = rng.random()
    if depth == 0 or roll < 0.3:
        return _scalar(rng)
    if roll < 0.6:
        seq = [_obj(rng, depth - 1, loose) for _ in range(rng.randrange(4))]
        return seq if rng.random() < 0.5 else tuple(seq)
    return {
        _key(rng, loose): _obj(rng, depth - 1, loose)
        for _ in range(rng.randrange(5))
    }


def _respell(rng, obj):
    """The same bytes, another object: maps in another insertion order, lists
    for tuples and tuples for lists (never in a key)."""
    if isinstance(obj, dict):
        items = [(k, _respell(rng, v)) for k, v in obj.items()]
        rng.shuffle(items)
        return dict(items)
    if isinstance(obj, (list, tuple)):
        seq = [_respell(rng, x) for x in obj]
        return tuple(seq) if rng.random() < 0.5 else seq
    return obj


@pytest.mark.parametrize("seed", range(8))
@pytest.mark.parametrize("loose", [False, True], ids=["exact", "loose"])
def test_seeded_random_pairs(lib, seed, loose):
    rng = random.Random(4600 + seed)
    objs = [_obj(rng, 4, loose) for _ in range(60)]
    seen = {True: 0, False: 0, None: 0}
    for a in objs:
        for b in objs:
            seen[holds(lib, a, b)] += 1
        twin = _respell(rng, a)
        same = holds(lib, a, twin)
        seen[same] += 1
        if not loose:
            assert same is True, a
        else:
            assert same is not False, a
    assert seen[True] and seen[False]
    assert bool(seen[None]) == loose  # exact keys and packable values: it says


# ---- the traps, by name ---------------------------------------------------

X = {b"slot": 7}
NAN = float("nan")

TRAPS = [
    ("int key against bool key", {1: X}, {True: X}, None),
    ("bool key against int key", {True: X}, {1: X}, None),
    ("int key against float key", {1: X}, {1.0: X}, None),
    ("float key against int key", {1.0: X}, {1: X}, None),
    ("zero key against False key", {0: 1}, {False: 1}, None),
    ("bool key on both sides", {True: X}, {True: X}, None),
    ("float key on both sides", {1.5: X}, {1.5: X}, None),
    ("signed zero keys", {0.0: 1}, {-0.0: 1}, None),
    ("tuple key holding a bool", {(1, b"a"): 1}, {(True, b"a"): 1}, None),
    ("tuple key holding a float", {(1, 2): 1}, {(1.0, 2): 1}, None),
    ("a loose key beside the one that matters", {1: X, 2.5: 0}, {True: X, 2.5: 0}, None),
    ("signed zero values", 0.0, -0.0, False),
    ("signed zero in a list", [1, 0.0], [1, -0.0], False),
    ("int against bool as values", 1, True, False),
    ("zero against False as values", {b"k": 0}, {b"k": False}, False),
    ("int against float as values", [1], [1.0], False),
    ("bytes against str", b"a", "a", False),
    ("bytes key against str key", {b"a": 1}, {"a": 1}, False),
    ("None against False", None, False, False),
    ("empty list against empty map", [], {}, False),
    ("a set", {1, 2}, {1, 2}, None),
    ("a set in one side's value", {b"k": {1}}, {b"k": [1]}, None),
    ("a frozenset key", {frozenset((1,)): 1}, {frozenset((1,)): 1}, None),
    ("a numpy scalar", np.int32(5), 5, None),
    ("a numpy scalar in a map", {b"k": np.int64(5)}, {b"k": 5}, None),
    ("a numpy scalar as a key", {np.int64(5): 1}, {5: 1}, None),
    ("a bytearray", bytearray(b"a"), b"a", None),
    ("an int past the packer's range", 2 ** 64, 2 ** 64, None),
    ("an int under the packer's range", -(2 ** 63) - 1, -(2 ** 63) - 1, None),
    ("2**63 against its negative", 2 ** 63, -(2 ** 63), False),
    ("2**63 against 2**63 - 1", 2 ** 63, 2 ** 63 - 1, False),
    ("two NaNs of other bits", NAN, -NAN, False),
]


@pytest.mark.parametrize("a, b, want", [t[1:] for t in TRAPS], ids=[t[0] for t in TRAPS])
def test_trap(lib, a, b, want):
    for left, right in ((a, b), (b, a)):
        assert holds(lib, left, right) is want
        assert codec.canon_same(left, right) is want


class Loud(int):
    def __eq__(self, other):
        raise AssertionError("a key's own code must never run")

    __hash__ = int.__hash__


def test_a_subclass_key_is_declined_before_it_is_looked_up(lib):
    assert lib.canon_same({Loud(1): 2}, {1: 2}) is None
    assert lib.canon_same({1: 2}, {Loud(1): 2}) is None
    assert lib.canon_same(Loud(1), 1) is None


def test_depth_limit_is_the_packers(lib):
    def nest(n, leaf):
        for _ in range(n):
            leaf = [leaf]
        return leaf

    assert lib.canon_same(nest(200, 1), nest(200, 1)) is True
    assert lib.canon_pack(nest(200, 1)) is not None
    assert lib.canon_same(nest(201, 1), nest(201, 1)) is None
    assert lib.canon_pack(nest(201, 1)) is None
    key = nest(0, 1)
    for _ in range(205):
        key = (key,)
    assert lib.canon_same({key: 1}, {key: 1}) is None


SAME = [
    ("list against tuple", [1, [2, (3,)], b"x"], (1, (2, [3]), b"x")),
    ("two insertion orders of one map",
     {b"a": 1, b"b": 2, 3: "c", "d": None, (1, b"k"): [0]},
     {(1, b"k"): (0,), "d": None, 3: "c", b"b": 2, b"a": 1}),
    ("the same NaN bits", [NAN], [float("nan")]),
    ("the same float", 1.5, 1.5),
    ("bools", [True, False], (True, False)),
    ("None keys", {None: 1}, {None: 1}),
    ("empty maps", {}, {}),
    ("2**64 - 1", 2 ** 64 - 1, int("18446744073709551615")),
    ("-2**63", -(2 ** 63), int("-9223372036854775808")),
    ("a big int built twice", 10 ** 15 + 7, int("1000000000000007")),
    ("text beyond ASCII", "é" * 40, "".join(["é"] * 40)),
    ("70,000 keys in another order",
     {i: i * 2 for i in range(70000)},
     {i: i * 2 for i in reversed(range(70000))}),
]


@pytest.mark.parametrize("a, b", [t[1:] for t in SAME], ids=[t[0] for t in SAME])
def test_same(lib, a, b):
    assert holds(lib, a, b) is True
    assert holds(lib, b, a) is True
    assert codec.canon_same(a, b) is True


# ---- state-shaped objects: what the verify compares -----------------------


def _state_obj(rng, members=40, actors=12):
    who = [rng.randbytes(16) for _ in range(actors)]
    clock = {a: 50 + rng.randrange(50) for a in who}
    entries = {
        b"m%d" % m: {a: 1 + rng.randrange(50) for a in rng.sample(who, 4)}
        for m in range(members)
    }
    deferred = {b"m%d" % m: {who[m]: 200 + m} for m in range(3)}
    return {b"c": clock, b"e": entries, b"d": deferred}


def _raise_one_counter(obj, rng):
    slots = obj[b"e"][b"m7"]
    slots[next(iter(slots))] += 1


def _drop_one_member(obj, rng):
    del obj[b"e"][b"m11"]


def _one_more_horizon(obj, rng):
    obj[b"d"][b"m30"] = {next(iter(obj[b"c"])): 999}


def _swap_one_actor(obj, rng):
    slots = obj[b"e"][b"m3"]
    slots[rng.randbytes(16)] = slots.pop(next(iter(slots)))


def _clock_behind(obj, rng):
    obj[b"c"][next(iter(obj[b"c"]))] -= 1


def _counter_as_bool(obj, rng):
    slots = obj[b"e"][b"m5"]
    slots[next(iter(slots))] = True


@pytest.mark.parametrize("change", [
    _raise_one_counter, _drop_one_member, _one_more_horizon, _swap_one_actor,
    _clock_behind, _counter_as_bool,
], ids=lambda f: f.__name__.strip("_"))
def test_one_change_in_a_state_is_found(lib, change):
    rng = random.Random(46)
    a = _state_obj(rng)
    b = _respell(rng, copy.deepcopy(a))
    assert holds(lib, a, b) is True
    change(b, rng)
    assert holds(lib, a, b) is False
    assert holds(lib, b, a) is False


# ---- an environment without the native build ------------------------------


def test_without_the_native_build_there_is_no_fast_path(monkeypatch, caplog):
    from crdt_enc_tpu import native

    monkeypatch.setattr(codec, "_native_same", None)
    monkeypatch.setattr(
        native, "load_state",
        lambda: (_ for _ in ()).throw(RuntimeError("no build")),
    )
    with caplog.at_level(logging.WARNING, logger="crdt_enc_tpu.codec"):
        assert codec.canon_same({b"a": 1}, {b"a": 1}) is None
        assert codec.canon_same(1, 2) is None
    warns = [r for r in caplog.records if "canon_same unavailable" in r.message]
    assert len(warns) == 1  # once a process, as the packer's
