"""The native canonical msgpack packer (statebuild.cpp ``canon_pack``)
must emit byte-identical output to the Python canonical path
(``msgpack.packb(_canon(obj))``) on everything it accepts, and decline
(return None) anything it cannot — ``codec.pack`` falls back silently,
so a silent divergence here would corrupt every persisted state.
"""

from __future__ import annotations

import random

import msgpack
import pytest
from _hyp import given, settings, st  # hypothesis, or skip-stubs

from crdt_enc_tpu.utils import codec


def _native():
    from crdt_enc_tpu import native

    try:
        return native.load_state()
    except Exception:
        pytest.skip("native state library unavailable")


def _python_pack(obj) -> bytes:
    return msgpack.packb(codec._canon(obj), use_bin_type=True)


EDGES = [
    None, True, False,
    0, 1, 127, 128, 255, 256, 65535, 65536, 2 ** 32 - 1, 2 ** 32,
    2 ** 63 - 1, 2 ** 63, 2 ** 64 - 1,
    -1, -31, -32, -33, -128, -129, -32768, -32769, -2 ** 31, -2 ** 31 - 1,
    -2 ** 63,
    1.5, -0.0,
    b"", b"x" * 255, b"y" * 256, b"z" * 70000,
    "", "a" * 31, "b" * 32, "c" * 255, "d" * 256, "é" * 100,
    [], [1, 2, 3], tuple(range(20)),
    {}, {b"b": 1, b"a": 2}, {1: "x", "1": "y", b"1": b"z"},
    {b"c": {b"k": [1, b"v", None]}, b"e": {5: {b"a": 2 ** 40}}, b"d": {}},
    [{"k": (1, 2)}, {2: [3, {4: 5}]}],
    list(range(70000)),           # array32 header
    {i: i * 2 for i in range(70000)},  # map32 header + big sort
]


def test_edge_cases_byte_identical():
    lib = _native()
    for case in EDGES:
        assert lib.canon_pack(case) == _python_pack(case), repr(case)[:80]


def test_unsupported_types_decline():
    import numpy as np

    lib = _native()
    for case in ({1, 2}, object(), np.int32(5), 2 ** 64, -2 ** 63 - 1):
        assert lib.canon_pack(case) is None
    # the fallback still packs what msgpack can take
    assert codec.pack(5) == _python_pack(5)
    # ...and raises identically on what it can't (set → the Python
    # packer's TypeError, not silence)
    with pytest.raises(TypeError):
        codec.pack({1, 2})


_scalar = st.one_of(
    st.none(),
    st.booleans(),
    st.integers(min_value=-(2 ** 63), max_value=2 ** 64 - 1),
    st.binary(max_size=40),
    st.text(max_size=20),
    st.floats(allow_nan=False),
)
_key = st.one_of(
    st.integers(min_value=0, max_value=2 ** 20),
    st.binary(min_size=1, max_size=16),
    st.text(min_size=1, max_size=8),
    # composite map keys are real in this codebase ((replica, counter)
    # dots stay hashable through codec.unpack's use_list=False)
    st.tuples(
        st.integers(min_value=0, max_value=255), st.binary(max_size=8)
    ),
)
_value = st.recursive(
    _scalar,
    lambda children: st.one_of(
        st.lists(children, max_size=5),
        st.dictionaries(_key, children, max_size=5),
    ),
    max_leaves=30,
)


@settings(max_examples=150, deadline=None)
@given(obj=_value)
def test_hypothesis_byte_identical(obj):
    lib = _native()
    assert lib.canon_pack(obj) == _python_pack(obj)


def test_codec_pack_routes_native():
    # pack() itself (with the lazy native hook) agrees with the pure
    # Python expression on a state-shaped object
    obj = {b"c": {b"a%d" % i: i for i in range(100)},
           b"e": {i: {b"x": i} for i in range(50)}, b"d": {}}
    assert codec.pack(obj) == _python_pack(obj)


# ---- the index sort (ISSUE 52) --------------------------------------------
# The packer writes every entry into one buffer and orders a map by sorting
# 24-byte records: the key's first eight packed bytes as one word, then the
# rest, then the length.  The cases the old vector-a-key packer never had a
# reason to meet are the ones where that word does not decide.

_ACTOR = bytes(range(16))


def _packed_key(k) -> bytes:
    return msgpack.packb(codec._canon(k, as_key=True), use_bin_type=True)


def _ordered(keys, order, seed=0):
    keys = sorted(keys, key=_packed_key)
    if order == "reversed":
        keys.reverse()
    elif order == "shuffled":
        random.Random(seed).shuffle(keys)
    return keys


def _reorder(obj, rng):
    """``obj`` with every map's insertion order shuffled, all the way down."""
    if isinstance(obj, dict):
        items = [(k, _reorder(v, rng)) for k, v in obj.items()]
        rng.shuffle(items)
        return dict(items)
    if isinstance(obj, list):
        return [_reorder(x, rng) for x in obj]
    return obj


TIE_KEYS = {
    # bin8 of one length: tag, length and six bytes fill the word
    "bytes_differ_at_9": [b"prefix" + bytes([i]) + b"tail" for i in range(40)],
    "bytes_differ_last": [b"x" * 30 + bytes([i]) for i in range(40)],
    # fixstr: tag and seven bytes
    "str_differ_late": ["sameseven" + chr(97 + i) * (1 + i % 3)
                        for i in range(26)],
    # the (actor, counter) dots of this codebase: fixarray tag, bin8 tag,
    # length and five actor bytes fill the word; the counter decides
    "dots": [(_ACTOR, c) for c in (0, 1, 127, 128, 255, 256, 65535, 65536,
                                   2 ** 32, 2 ** 40)],
    "dots_two_actors": [(_ACTOR[:15] + bytes([a]), c)
                        for a in range(4) for c in range(12)],
    # a key that is a strict prefix of another, as values: their packed
    # forms differ in the length byte, inside the word or past it
    "prefix_bytes": [b"ab", b"ab\x00", b"a", b"", b"ab\x00\x00"],
    "prefix_str": ["a" * 31, "a" * 32, "a" * 30, "a" * 33, "a" * 255,
                   "a" * 256],
    "prefix_long_bytes": [b"k" * n for n in (5, 6, 7, 8, 9, 254, 255, 256,
                                             257)],
    # packed keys of one to four bytes beside keys of tens
    "short_beside_long": [0, 1, 127, 128, 255, 256, -1, -32, -33, None,
                          b"", b"\x00", "", "a", b"\x00" * 40, "a" * 40,
                          (1, 2), (), 2 ** 63, -2 ** 63],
    # two keys that are different objects and pack alike keep their
    # arrival order (the Python path's sort is stable)
    "two_nans": [float("nan"), float("nan"), 1.5, -0.0],
}


@pytest.mark.parametrize("order", ["in_order", "reversed", "shuffled"])
@pytest.mark.parametrize("name", sorted(TIE_KEYS))
def test_keys_the_first_word_does_not_decide(name, order):
    lib = _native()
    keys = _ordered(TIE_KEYS[name], order, seed=len(name))
    obj = {k: [i, b"v%d" % i] for i, k in enumerate(keys)}
    assert len(obj) == len(keys)
    assert lib.canon_pack(obj) == _python_pack(obj)
    # and as an inner map, which its parent then moves as bytes
    outer = {b"z": obj, b"a": {k: None for k in reversed(keys)}, b"m": 1}
    assert lib.canon_pack(outer) == _python_pack(outer)


@pytest.mark.parametrize("order", ["in_order", "reversed", "shuffled"])
@pytest.mark.parametrize("n", [0, 1, 8, 9, 15, 16, 17, 65535, 65536])
def test_map_sizes_across_headers_and_record_storage(n, order):
    """fixmap / map16 / map32, records on the stack and on the heap, and the
    path that neither sorts nor copies."""
    lib = _native()
    keys = _ordered(range(n), order, seed=n)
    obj = {k: k & 0xff for k in keys}
    want = _python_pack(obj)
    assert lib.canon_pack(obj) == want
    if order != "in_order":  # one canonical form whatever the arrival order
        assert want == _python_pack({k: k & 0xff for k in range(n)})


@pytest.mark.parametrize("seed", range(4))
def test_out_of_order_maps_nested_three_deep(seed):
    lib = _native()
    rng = random.Random(seed)

    def level(depth):
        if depth == 0:
            return [rng.randrange(2 ** 40), rng.randbytes(16), None]
        n = rng.choice([2, 3, 17, 40])
        keys = [rng.randbytes(rng.choice([1, 4, 16, 33])) for _ in range(n)]
        return {k: level(depth - 1) for k in keys}

    obj = _reorder(level(3), rng)
    got = lib.canon_pack(obj)
    assert got == _python_pack(obj)
    assert codec.unpack(got) == codec.unpack(_python_pack(_reorder(obj, rng)))


def _deep(n, leaf):
    for _ in range(n):
        leaf = [leaf]
    return leaf


@pytest.mark.parametrize("what", ["set", "numpy_scalar", "depth_201"])
def test_a_decline_deep_inside_an_out_of_order_map(what):
    """The packer gives up with entries already in its buffer and records
    half filled: it answers ``None`` and ``codec.pack`` takes the Python
    path, which packs what msgpack can and raises what it cannot."""
    import numpy as np

    lib = _native()
    # ``bad`` sits at depth 3: 198 lists more put the leaf at depth 201,
    # one over the limit
    bad = {"set": {1, 2}, "numpy_scalar": np.int64(5),
           "depth_201": _deep(198, 7)}[what]
    inner = {k: k for k in reversed(range(40))}
    inner[17] = {b"z": 1, b"a": bad}
    obj = {b"y": [1, 2], b"b": inner, b"a": {3: 4, 1: 2}}
    before = _counters()
    assert lib.canon_pack(obj) is None
    grown = _grown(before)
    assert grown["canon_packs"] == 1 and grown["canon_declined"] == 1
    if what == "depth_201":
        assert lib.canon_pack(_deep(200, 7)) is not None  # leaf at 200
        assert lib.canon_pack(_deep(201, 7)) is None
        assert codec.pack(obj) == _python_pack(obj)
    else:
        with pytest.raises(TypeError):
            codec.pack(obj)
    # nothing of the abandoned pack is left in the next one
    good = {b"b": 1, b"a": 2}
    assert lib.canon_pack(good) == _python_pack(good)


@settings(max_examples=150, deadline=None)
@given(obj=_value, seed=st.integers(min_value=0, max_value=2 ** 32))
def test_hypothesis_shuffled_insertion_order(obj, seed):
    lib = _native()
    shuffled = _reorder(obj, random.Random(seed))
    got = lib.canon_pack(shuffled)
    assert got == _python_pack(shuffled)
    assert got == lib.canon_pack(obj)


# ---- the four counters ----------------------------------------------------


def _counters() -> dict:
    return dict(zip(codec.CANON_COUNTERS, _native().canon_counters()))


def _grown(before: dict) -> dict:
    now = _counters()
    return {k: now[k] - before[k] for k in now}


def test_counters_say_how_the_packer_engaged():
    lib = _native()
    before = _counters()
    lib.canon_pack({1: {2: 3}, 4: {}})  # three maps, all in order
    assert _grown(before) == {
        "canon_packs": 1, "canon_maps": 3, "canon_maps_sorted": 0,
        "canon_declined": 0,
    }
    before = _counters()
    lib.canon_pack({4: {2: 3, 1: 0}, 1: {}})  # the outer and one inner sort
    assert _grown(before) == {
        "canon_packs": 1, "canon_maps": 3, "canon_maps_sorted": 2,
        "canon_declined": 0,
    }
    before = _counters()
    assert lib.canon_pack([1, {2, 3}]) is None
    assert _grown(before) == {
        "canon_packs": 1, "canon_maps": 0, "canon_maps_sorted": 0,
        "canon_declined": 1,
    }


def test_counters_reach_the_registry_and_metrics():
    """No ``trace.add`` a pack: the library keeps the totals and the
    registry folds their growth in wherever it is read."""
    from crdt_enc_tpu.obs import sink
    from crdt_enc_tpu.utils import trace

    _native()
    codec.pack(0)  # resolves the native packer, which registers the fold
    trace.reset()
    assert not set(codec.CANON_COUNTERS) & set(trace.snapshot()["counters"])
    codec.pack({1: 2, 3: 4})
    codec.pack({3: 4, 1: 2})
    got = trace.snapshot()["counters"]
    # all four once any has grown: a healthy process reads 0 declined
    assert {k: got[k] for k in codec.CANON_COUNTERS} == {
        "canon_packs": 2, "canon_maps": 2, "canon_maps_sorted": 1,
        "canon_declined": 0,
    }
    with pytest.raises(TypeError):
        codec.pack({1, 2})
    assert trace.snapshot()["counters"]["canon_declined"] == 1
    prom = sink.to_prometheus()
    for name in codec.CANON_COUNTERS:
        assert f"# TYPE crdt_{name}_total counter" in prom
        assert f"# HELP crdt_{name}_total" in prom
    assert "crdt_canon_packs_total 3" in prom
    assert "crdt_canon_declined_total 1" in prom
    trace.reset()  # clears the registry's share; the library counts on
    codec.pack(1)
    assert trace.snapshot()["counters"]["canon_packs"] == 1


def test_concurrent_readers_lose_and_double_no_pack():
    """Packs on eight threads while four more read the registry: every
    reader folds the library's growth in, and the sum is the library's."""
    import sys
    import threading

    from crdt_enc_tpu.utils import trace

    _native()
    codec.pack(0)
    trace.reset()
    before = _counters()
    stop = threading.Event()

    def packer():
        for i in range(2000):
            codec.pack({2: i, 1: {4: 5, 3: 6}})

    def reader():
        while not stop.is_set():
            trace.snapshot()

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        readers = [threading.Thread(target=reader) for _ in range(4)]
        packers = [threading.Thread(target=packer) for _ in range(8)]
        for t in readers + packers:
            t.start()
        for t in packers:
            t.join(timeout=60)
        stop.set()
        for t in readers:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in readers + packers)
    finally:
        sys.setswitchinterval(old)
    got = trace.snapshot()["counters"]
    assert {k: got[k] for k in codec.CANON_COUNTERS} == _grown(before) == {
        "canon_packs": 16000, "canon_maps": 32000,
        "canon_maps_sorted": 32000, "canon_declined": 0,
    }


def test_large_packs_step_to_the_last_large_length():
    """Past 1 MB the buffer's capacity follows the last large pack's length
    (a larger one, a smaller one, one that is a single large ``bin``): a
    capacity only, the bytes are the Python path's every time."""
    lib = _native()
    rng = random.Random(52)
    big = {k: [rng.randrange(2 ** 40), rng.randbytes(16)]
           for k in rng.sample(range(10 ** 6), 120_000)}
    small = {k: big[k] for k in list(big)[:50_000]}
    for obj in (big, big, small, big, {b"fmt": 2, b"state": bytes(3 << 20)},
                big, {b"s": bytes(1 << 20) + b"x"}, small):
        got = lib.canon_pack(obj)
        assert len(got) > 1 << 20
        assert got == _python_pack(obj)
