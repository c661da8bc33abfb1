"""Replication & convergence observability (ISSUE 6).

The watermark math is exactly asserted — not shape-checked — on both
the pure function (synthetic clocks) and a real 3-device remote where
devices seal/read at skewed rates, including the all-converged fixed
point and the one-silent-actor collapse.  The fleet aggregator and the
bench trend gate are asserted against hand-computed distributions and a
committed golden rendering (the same golden tools/run_checks.sh diffs).
"""

import asyncio
import json
import pathlib
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
    Core, OpenOptions, gcounter_adapter, orset_adapter,
)
from crdt_enc_tpu.obs import fleet, replication, sink
from crdt_enc_tpu.utils import trace
from crdt_enc_tpu.utils.versions import DEFAULT_DATA_VERSION_1
from crdt_enc_tpu.models.vclock import VClock

DATA = pathlib.Path(__file__).parent / "data"

A = b"\xaa" * 16
B = b"\xbb" * 16
C = b"\xcc" * 16
RID = b"\x99" * 32


def run(coro):
    return asyncio.run(coro)


def make_opts(storage, **kw):
    return OpenOptions(
        storage=storage,
        cryptor=IdentityCryptor(),
        key_cryptor=PlainKeyCryptor(),
        adapter=gcounter_adapter(),
        supported_data_versions=(DEFAULT_DATA_VERSION_1,),
        current_data_version=DEFAULT_DATA_VERSION_1,
        create=True,
        **kw,
    )


# ---- compute_status: the pure math ----------------------------------------


def test_compute_status_all_converged_fixed_point():
    """Every replica published a cursor equal to the union: the
    watermark IS the union, every divergence gauge is zero."""
    local = VClock({A: 3, B: 2})
    status = replication.compute_status(
        A, local, {B: VClock({A: 3, B: 2})}, [], RID,
        {A: 3, B: 2}, True,
    )
    assert status == {
        "actor": A.hex(),
        "remote_id": RID.hex(),
        "local_clock": {A.hex(): 3, B.hex(): 2},
        "union_clock": {A.hex(): 3, B.hex(): 2},
        "watermark": {A.hex(): 3, B.hex(): 2},
        "matrix": {B.hex(): {A.hex(): 3, B.hex(): 2}},
        "backlog": {"files": 0, "bytes": 0, "per_actor": {}},
        "divergence": {
            "actors_behind": 0,
            "version_lag": 0,
            "watermark_lag": 0,
            "known_replicas": 2,
        },
        "checkpoint": {
            "enabled": True,
            "sealed": True,
            "staleness_versions": 0,
        },
    }


def test_compute_status_one_silent_actor_collapses_watermark():
    """B produced ops but never published a cursor: silence is
    indistinguishable from lag, so B's row (0) kills every other
    actor's watermark entry — but B's OWN ops keep a watermark up to
    what this replica has seen (implied self-knowledge caps B's row at
    the union, the local row at 2)."""
    local = VClock({A: 3, B: 2})
    status = replication.compute_status(
        A, local, {}, [(B, 3, 100), (B, 4, 50)], RID, None, False,
    )
    assert status == {
        "actor": A.hex(),
        "remote_id": RID.hex(),
        "local_clock": {A.hex(): 3, B.hex(): 2},
        "union_clock": {A.hex(): 3, B.hex(): 4},
        "watermark": {B.hex(): 2},
        "matrix": {},
        "backlog": {
            "files": 2,
            "bytes": 150,
            "per_actor": {B.hex(): {"files": 2, "bytes": 150}},
        },
        "divergence": {
            "actors_behind": 1,
            "version_lag": 2,
            "watermark_lag": 5,  # A: 3-0, B: 4-2
            "known_replicas": 2,
        },
        "checkpoint": {
            "enabled": False,
            "sealed": False,
            "staleness_versions": 5,
        },
    }


def test_compute_status_byte_stable():
    """Same inputs → byte-identical JSON (sorted keys everywhere), so
    differential tests and fleet goldens can compare strings."""
    args = (
        C, VClock({B: 1, A: 2}), {A: VClock({A: 2})},
        [(B, 2, 7)], RID, {A: 2}, True,
    )
    one = json.dumps(replication.compute_status(*args), sort_keys=True)
    two = json.dumps(replication.compute_status(*args), sort_keys=True)
    assert one == two
    # insertion-order independence: a permuted-clock twin renders the same
    permuted = (
        C, VClock({A: 2, B: 1}), {A: VClock({A: 2})},
        [(B, 2, 7)], RID, {A: 2}, True,
    )
    assert json.dumps(
        replication.compute_status(*permuted), sort_keys=True
    ) == one


def test_compute_status_checkpoint_staleness_counts_new_versions():
    status = replication.compute_status(
        A, VClock({A: 5, B: 3}), {}, [], RID, {A: 2, B: 3}, True,
    )
    assert status["checkpoint"] == {
        "enabled": True, "sealed": True, "staleness_versions": 3,
    }


# ---- stability_watermark: differential against the loop it replaced --------


def _watermark_loop(actor_id, local_clock, cursor_matrix, union, replicas=None):
    """The replica x actor enumeration ``stability_watermark`` was until
    PR 33, verbatim: the oracle the structural function is held to."""
    if replicas is None:
        replicas = set(cursor_matrix) | set(union.counters) | {actor_id}
    watermark = {}
    for a in union.counters:
        lo = None
        for r in replicas:
            if r == actor_id:
                k = local_clock.get(a)
            else:
                published = cursor_matrix.get(r)
                k = published.get(a) if published is not None else 0
            if r == a:
                # implied self-knowledge: a replica has certainly seen
                # its own sealed ops, published cursor or not
                k = max(k, union.get(a))
            lo = k if lo is None else min(lo, k)
        if lo:
            watermark[a] = lo
    return watermark


def _actor(i: int) -> bytes:
    return bytes([i + 1]) * 16


def _union_of(local, matrix, extra=()):
    union = local.copy()
    for clock in matrix.values():
        union.merge(clock)
    union.merge(VClock(dict(extra)))
    return union


def _random_fleet(seed: int, dense: bool):
    """1-8 actors, 0-8 publishers (some of them pure consumers that never
    wrote), rows sparse or dense, zeros in rows; the union is what
    ``compute_status`` would build: local, rows, and a sealed tail."""
    rng = random.Random(seed)
    actors = [_actor(i) for i in range(rng.randint(1, 8))]
    pool = actors + [_actor(8 + i) for i in range(3)]  # + pure consumers
    me = rng.choice(pool)
    keep = 0.9 if dense else 0.4

    def row():
        return VClock({
            a: rng.choice([0, rng.randint(1, 9)])
            for a in actors if rng.random() < keep
        })

    publishers = rng.sample(
        [r for r in pool if r != me], rng.randint(0, min(8, len(pool) - 1))
    )
    local, matrix = row(), {r: row() for r in publishers}
    tail = [(a, rng.randint(1, 12)) for a in actors if rng.random() < 0.5]
    return me, local, matrix, _union_of(local, matrix, tail), pool, rng


@pytest.mark.parametrize("explicit", [False, True], ids=["default", "replicas"])
@pytest.mark.parametrize("dense", [False, True], ids=["sparse", "dense"])
@pytest.mark.parametrize("seed", range(12))
def test_watermark_equals_the_loop_on_random_fleets(seed, dense, explicit):
    me, local, matrix, union, pool, rng = _random_fleet(seed, dense)
    replicas = None
    if explicit:
        # a policy's denominator: any subset of the known members, with
        # or without this replica, plus now and then a stranger
        replicas = {r for r in pool + [_actor(20)] if rng.random() < 0.6}
    want = _watermark_loop(me, local, matrix, union, replicas)
    got = replication.stability_watermark(me, local, matrix, union, replicas)
    assert got == want
    assert all(type(c) is int and c > 0 for c in got.values())


D = b"\xdd" * 16
_FULL = VClock({A: 5, B: 4, C: 3})
WATERMARK_CASES = {
    # every replica speaks: the pointwise min of what was published
    "none_silent": (A, VClock({A: 5, B: 3, C: 3}),
                    {B: VClock({A: 4, B: 4, C: 1}), C: VClock({A: 5, B: 2, C: 3})},
                    _FULL, None),
    # C wrote ops and never published: only C's own entry can survive
    "one_silent_with_ops": (A, VClock({A: 5, B: 3, C: 2}),
                            {B: VClock({A: 4, B: 4, C: 1})}, _FULL, None),
    # the one silent replica is a pinned consumer that never wrote
    "one_silent_without_ops": (A, VClock({A: 5, B: 3}), {B: VClock({A: 4, B: 4})},
                               VClock({A: 5, B: 4}), {A, B, D}),
    "one_silent_local_never_saw_it": (A, VClock({A: 5}), {B: VClock({A: 4, B: 4})},
                                      _FULL, None),
    # the folder cells: writers that never publish a cursor
    "two_silent": (A, VClock({A: 5, B: 4, C: 3}), {}, _FULL, None),
    "two_silent_one_publisher": (A, VClock({A: 5, B: 4, C: 3, D: 1}),
                                 {B: VClock({A: 5, B: 4, C: 3, D: 1})},
                                 VClock({A: 5, B: 4, C: 3, D: 1}), None),
    # a reader outside its own denominator: its clock is not consulted
    "actor_not_in_replicas": (D, VClock({A: 1}),
                              {A: VClock({A: 5, B: 2}), B: VClock({A: 3, B: 4})},
                              VClock({A: 5, B: 4}), {A, B}),
    "actor_not_in_replicas_one_silent": (D, VClock({A: 9, C: 9}),
                                         {A: VClock({A: 5, C: 2})},
                                         VClock({A: 9, C: 9}), {A, C}),
    # read/policy.py: a pinned member nobody has heard from is silent ...
    "pinned_unheard_member": (A, VClock({A: 5, B: 4}), {B: VClock({A: 5, B: 4})},
                              VClock({A: 5, B: 4}), {A, B, D}),
    # ... and a quarantined one is simply absent from the denominator
    "quarantined_member_absent": (A, VClock({A: 5, B: 3, C: 3}),
                                  {B: VClock({A: 4, B: 4, C: 1})}, _FULL, {A, B}),
    "publisher_with_empty_row": (A, VClock({A: 5, B: 4}), {B: VClock()},
                                 VClock({A: 5, B: 4}), None),
    "consumer_publisher_empty_row": (A, VClock({A: 5}), {D: VClock()},
                                     VClock({A: 5}), None),
    "empty_union": (A, VClock(), {B: VClock()}, VClock(), None),
    "empty_union_pinned": (A, VClock(), {}, VClock(), {A, B, C}),
    "empty_denominator": (A, VClock({A: 5}), {}, VClock({A: 5}), set()),
    "alone": (A, VClock({A: 5}), {}, VClock({A: 5}), None),
    # a caller's union need not dominate the rows it is given
    "union_below_a_row": (A, VClock({A: 5, B: 6}), {B: VClock({A: 7, B: 9})},
                          VClock({A: 2, B: 3}), None),
    "union_lacks_a_published_actor": (A, VClock({A: 5, C: 2}),
                                      {B: VClock({A: 4, B: 4, C: 2})},
                                      VClock({A: 5, B: 4}), None),
    "zero_entries_in_union_and_rows": (A, VClock({A: 5, B: 0}),
                                       {B: VClock({A: 0, B: 4})},
                                       VClock({A: 5, B: 4, C: 0}), None),
    # the local replica's own published row is never read for itself
    "own_row_in_the_matrix": (A, VClock({A: 5, B: 2}),
                              {A: VClock({A: 1}), B: VClock({A: 3, B: 4})},
                              VClock({A: 5, B: 4}), None),
    "denominator_as_a_list": (A, VClock({A: 5, B: 4, C: 3}),
                              {B: VClock({A: 5, B: 4, C: 3})}, _FULL,
                              [C, A, B, C]),
}


@pytest.mark.parametrize("case", list(WATERMARK_CASES))
def test_watermark_equals_the_loop_on_named_cases(case):
    args = WATERMARK_CASES[case]
    assert replication.stability_watermark(*args) == _watermark_loop(*args)


def test_watermark_values_of_the_named_structures():
    """The three structures, by hand (not only against the oracle)."""
    wm = replication.stability_watermark
    assert wm(*WATERMARK_CASES["none_silent"]) == {A: 4, B: 2, C: 1}
    assert wm(*WATERMARK_CASES["one_silent_with_ops"]) == {C: 1}
    assert wm(*WATERMARK_CASES["one_silent_without_ops"]) == {}
    assert wm(*WATERMARK_CASES["two_silent"]) == {}
    assert wm(*WATERMARK_CASES["quarantined_member_absent"]) == {A: 4, B: 3, C: 1}
    assert wm(*WATERMARK_CASES["alone"]) == {A: 5}


def test_watermark_wire_and_status_bytes_equal_under_both(monkeypatch):
    """What leaves the process is byte-identical: the sealed delta
    link's cleartext (``wire.build_delta_obj`` packed) and the status
    JSON, computed with the function and with the loop it replaced."""
    from crdt_enc_tpu.delta import wire
    from crdt_enc_tpu.utils import codec

    def link(watermark):
        return codec.pack(wire.build_delta_obj(wire.DeltaRecord(
            base_name="b" * 8, new_name="n" * 8,
            base_cursor=VClock({A: 1}), new_cursor=VClock({A: 2}),
            sealer=A, adapter=b"orset", watermark=watermark,
            delta_obj={b"k": 1},
        )))

    def status(me, local, matrix):
        return json.dumps(replication.compute_status(
            me, local, matrix, [(C, 4, 9)], RID, {A: 1}, True,
        ), sort_keys=True)

    fleets = [WATERMARK_CASES[c] for c in (
        "none_silent", "one_silent_with_ops", "two_silent",
        "publisher_with_empty_row", "own_row_in_the_matrix",
    )] + [_random_fleet(seed, dense)[:4] + (None,)
          for seed in range(6) for dense in (False, True)]
    for me, local, matrix, union, replicas in fleets:
        new_link = link(replication.stability_watermark(
            me, local, matrix, union, replicas))
        new_status = status(me, local, matrix)
        with monkeypatch.context() as m:
            m.setattr(replication, "stability_watermark", _watermark_loop)
            assert status(me, local, matrix) == new_status
        assert link(_watermark_loop(me, local, matrix, union, replicas)) == new_link


class _CountingClock(VClock):
    """A clock that counts the lookups made through ``get``."""

    lookups = 0

    def get(self, actor):
        _CountingClock.lookups += 1
        return super().get(actor)


def _scaling_case(case: str, n: int):
    """``(args, expected watermark, bound on lookups)`` at ``n`` devices."""
    devices = [i.to_bytes(16, "big") for i in range(1, n + 1)]
    me = (n + 1).to_bytes(16, "big")
    pubs = [(n + 2 + i).to_bytes(16, "big") for i in range(4)]
    clock = {a: 1 + i % 7 for i, a in enumerate(devices)}
    local, union = _CountingClock(dict(clock)), _CountingClock(dict(clock))
    # the denominator in one fixed order at every size: as a set of bytes
    # its order, and with it where an actor's min stops, followed the hash seed
    five = [me, *pubs]
    if case == "silent_devices":
        # the folder: writers that never publish a cursor; two silent
        # replicas end the pass, no pair is visited
        return (me, local, {}, union), {}, 0
    dense = {p: _CountingClock({a: c + j for a, c in clock.items()})
             for j, p in enumerate(pubs)}
    if case == "one_silent_device":
        # a pinned membership of five publishers and one silent writer:
        # one entry, one pass over the rows
        return ((me, local, dense, union, five + [devices[0]]),
                {devices[0]: clock[devices[0]]}, 2 * (len(five) + 1))
    if case == "dense_publishers":
        # 5 publishers x n actors, nobody silent: what was published
        return (me, local, dense, union, five), clock, 2 * (n + 5 + 5 * n)
    if case == "sparse_publishers":
        # an actor's min stops at the first row that lacks it, so the
        # rows nobody filled cost nothing
        sparse = {p: _CountingClock({a: 1 for a in devices[j::4]})
                  for j, p in enumerate(pubs)}
        return (me, local, sparse, union, five), {}, 2 * (n + 5 + 2 * n)
    raise AssertionError(case)


@pytest.mark.parametrize("case", [
    "silent_devices", "one_silent_device", "dense_publishers",
    "sparse_publishers",
])
def test_watermark_lookups_scale_with_what_was_published(case):
    """No wall clock is read: the lookups through ``VClock.get`` are bounded
    by a small constant x (actors + replicas + published entries), where
    the replica x actor loop makes 9 million at 3,000 devices; and the
    count is linear, twice the devices at most twice the lookups."""
    counts = []
    for n in (1500, 3000):
        args, want, bound = _scaling_case(case, n)
        _CountingClock.lookups = 0
        assert replication.stability_watermark(*args) == want
        assert _CountingClock.lookups <= bound
        counts.append(_CountingClock.lookups)
    assert counts[1] <= 2 * counts[0]


def test_watermark_span_nests_under_its_callers():
    """``repl.watermark`` is a child of ``repl.compute`` (the sample) and
    of ``delta.seal`` (the link's tag), never of ``core.compact``: the
    ``unattributed_ms`` metrics subtract the same direct children."""
    async def go():
        opts = make_opts(MemoryStorage(MemoryRemote()))
        opts.adapter = orset_adapter()
        core = await Core.open(opts)
        for i in range(60):
            await core.update(
                lambda s, m=b"m%d" % i: s.add_ctx(core.actor_id, m))
        await core.compact()
        await core.update(lambda s: s.add_ctx(core.actor_id, b"tail"))
        await core.compact()  # seals a delta link, tagged with the watermark

    trace.reset()
    run(go())
    snap, tree = trace.snapshot(), trace.tree()
    trace.reset()
    assert snap["spans"]["delta.seal"]["count"] == 1
    assert snap["spans"]["repl.watermark"]["parents"] == [
        "delta.seal", "repl.compute"]
    assert "repl.watermark" not in tree.get("core.compact", [])
    assert snap["spans"]["repl.watermark"]["count"] == 1 + snap["spans"][
        "repl.compute"]["count"]


# ---- the 3-device differential fixture ------------------------------------


async def _three_devices(remote):
    """A seals early, B writes without publishing, C only reads — the
    skewed-rate choreography every stage below asserts against."""
    a = await Core.open(make_opts(MemoryStorage(remote)))
    for _ in range(3):
        await a.apply_ops([a.with_state(lambda s: s.inc(a.actor_id))])
    await a.compact()  # publishes cursor {A:3}, GCs A's op files

    b = await Core.open(make_opts(MemoryStorage(remote)))
    await b.read_remote()  # learns A's published cursor
    for _ in range(2):
        await b.apply_ops([b.with_state(lambda s: s.inc(b.actor_id))])

    c = await Core.open(make_opts(MemoryStorage(remote)))
    await c.read_remote()  # snapshot + B's op tail
    return a, b, c


def test_three_device_watermark_backlog_divergence_exact():
    async def go():
        remote = MemoryRemote()
        a, b, c = await _three_devices(remote)
        ah, bh, ch = a.actor_id.hex(), b.actor_id.hex(), c.actor_id.hex()

        # ---- stage 1: C folded everything, but B never published ----
        st = await c.replication_status()
        assert st["actor"] == ch
        assert st["local_clock"] == {ah: 3, bh: 2}
        assert st["union_clock"] == {ah: 3, bh: 2}
        assert st["matrix"] == {ah: {ah: 3}}
        # B is silent → every watermark entry collapses: A's because B
        # may know nothing of A, B's because nobody else saw past B:2
        # and B:2 needs C's OWN row too — C has it, A's published
        # cursor does not
        assert st["watermark"] == {}
        assert st["backlog"] == {"files": 0, "bytes": 0, "per_actor": {}}
        assert st["divergence"] == {
            "actors_behind": 0,
            "version_lag": 0,
            "watermark_lag": 5,
            "known_replicas": 3,
        }
        # C never sealed a checkpoint: staleness is the whole fold
        assert st["checkpoint"] == {
            "enabled": True, "sealed": False, "staleness_versions": 5,
        }
        # byte-stable across repeated probes of the same state
        again = await c.replication_status()
        assert json.dumps(st, sort_keys=True) == json.dumps(
            again, sort_keys=True
        )
        assert c.last_replication_status == again

        # ---- stage 2: B seals two MORE ops C hasn't read ----
        for _ in range(2):
            await b.apply_ops([b.with_state(lambda s: s.inc(b.actor_id))])
        st = await c.replication_status()
        nbytes = len(remote.ops[b.actor_id][3]) + len(
            remote.ops[b.actor_id][4]
        )
        assert st["backlog"] == {
            "files": 2,
            "bytes": nbytes,
            "per_actor": {bh: {"files": 2, "bytes": nbytes}},
        }
        assert st["union_clock"] == {ah: 3, bh: 4}
        assert st["divergence"] == {
            "actors_behind": 1,
            "version_lag": 2,
            "watermark_lag": 7,
            "known_replicas": 3,
        }

        # ---- stage 3: B compacts (publishes {A:3,B:4}), C reads ----
        await b.compact()
        # B's own post-compaction view: backlog zero by construction,
        # checkpoint freshly sealed, A's entry watermarked (A published
        # A:3 and B folded it), B's own entry still pinned by A's stale
        # published cursor
        stb = await b.replication_status()
        assert stb["watermark"] == {ah: 3}
        assert stb["backlog"] == {"files": 0, "bytes": 0, "per_actor": {}}
        assert stb["checkpoint"] == {
            "enabled": True, "sealed": True, "staleness_versions": 0,
        }
        assert stb["divergence"]["watermark_lag"] == 4  # B: 4-0
        # between B's compact and C's read, C is BLIND to B:3-4: the op
        # files were GC'd into a snapshot C hasn't read yet, and an
        # unread snapshot name carries no clock — divergence measures
        # what is KNOWN to exist, so it legitimately drops to 0 here
        # (the fleet view, which has B's sink record, still shows it)
        st_c = await c.replication_status()
        assert st_c["union_clock"] == {ah: 3, bh: 2}
        assert st_c["divergence"]["version_lag"] == 0
        assert st_c["backlog"] == {"files": 0, "bytes": 0, "per_actor": {}}
        await c.read_remote()
        st = await c.replication_status()
        assert st["local_clock"] == {ah: 3, bh: 4}
        assert st["matrix"] == {ah: {ah: 3}, bh: {ah: 3, bh: 4}}
        # A's published cursor predates B's ops → B's entry still open
        assert st["watermark"] == {ah: 3}
        assert st["divergence"] == {
            "actors_behind": 0,
            "version_lag": 0,
            "watermark_lag": 4,
            "known_replicas": 3,
        }

        # ---- stage 4: A re-reads + republishes → fixed point ----
        await a.read_remote()
        await a.compact()
        await c.read_remote()
        st = await c.replication_status()
        assert st["matrix"] == {
            ah: {ah: 3, bh: 4}, bh: {ah: 3, bh: 4},
        }
        assert st["watermark"] == st["union_clock"] == st["local_clock"]
        assert st["divergence"] == {
            "actors_behind": 0,
            "version_lag": 0,
            "watermark_lag": 0,
            "known_replicas": 3,
        }
        assert st["backlog"] == {"files": 0, "bytes": 0, "per_actor": {}}
        # remote identity agrees across all three devices
        assert st["remote_id"] == stb["remote_id"]
        assert st["remote_id"] == (await a.replication_status())["remote_id"]

    run(go())


def test_fs_stat_ops_matches_load_ops_sizes(tmp_path):
    """The fs backlog probe (native scan_op_sizes / stat fallback)
    sizes exactly the files load_ops would read, without reading."""
    async def go():
        remote_dir = str(tmp_path / "remote")
        s = FsStorage(str(tmp_path / "local"), remote_dir)
        core = await Core.open(make_opts(s))
        for _ in range(4):
            await core.apply_ops(
                [core.with_state(lambda st: st.inc(core.actor_id))]
            )
        wanted = [(core.actor_id, 2)]  # tail past a nonzero cursor
        stats = await s.stat_ops(wanted)
        loaded = await s.load_ops(wanted)
        assert stats == [(a, v, len(raw)) for a, v, raw in loaded]
        assert len(stats) == 3 and all(n > 0 for _, _, n in stats)
        # fully-consumed tail: empty, and cheap by construction
        assert await s.stat_ops([(core.actor_id, 5)]) == []

    run(go())


# ---- gauge sampling + sink wiring -----------------------------------------


def test_replication_gauges_sampled_on_lifecycle():
    trace.reset()

    async def go():
        remote = MemoryRemote()
        w = await Core.open(make_opts(MemoryStorage(remote)))
        await w.apply_ops([w.with_state(lambda s: s.inc(w.actor_id))])
        await w.compact()
        r = await Core.open(make_opts(MemoryStorage(remote)))
        # a fresh consumer BEFORE read_remote: open sampled its backlog
        return r

    run(go())
    snap = trace.snapshot()
    g = snap["gauges"]
    for name in (
        "repl_backlog_files", "repl_backlog_bytes", "repl_actors_behind",
        "repl_version_lag", "repl_watermark_lag", "repl_known_replicas",
        "checkpoint_staleness_versions",
    ):
        assert name in g, name
    assert snap["counters"]["repl_samples"] >= 3  # 2 opens + compact
    assert snap["spans"]["repl.status"]["count"] >= 3
    trace.reset()


def test_read_remote_sample_skips_storage_probe():
    """The read_remote sample reuses the ingest's own work: the poll
    just folded everything its listing found, so it must not pay a
    second per-actor stat_ops probe (the polling hot path) — and the
    sampled backlog gauges are zero by construction."""
    trace.reset()

    async def go():
        remote = MemoryRemote()
        w = await Core.open(make_opts(MemoryStorage(remote)))
        for _ in range(3):
            await w.apply_ops([w.with_state(lambda s: s.inc(w.actor_id))])
        r = await Core.open(make_opts(MemoryStorage(remote)))
        probes = []
        orig = r.storage.stat_ops

        async def counting(wanted):
            probes.append(wanted)
            return await orig(wanted)

        r.storage.stat_ops = counting
        await r.read_remote()
        assert probes == []  # sampled, but no storage probe
        status = r.last_replication_status
        assert status is not None
        assert status["backlog"] == {"files": 0, "bytes": 0, "per_actor": {}}
        # an explicit status call still probes for real
        await r.replication_status()
        assert len(probes) == 1

    run(go())
    g = trace.snapshot()["gauges"]
    assert g["repl_backlog_files"] == 0
    assert g["repl_backlog_bytes"] == 0
    trace.reset()


def test_repl_sample_opt_out(monkeypatch):
    monkeypatch.setenv("CRDT_REPL_SAMPLE", "0")
    trace.reset()

    async def go():
        w = await Core.open(make_opts(MemoryStorage(MemoryRemote())))
        await w.apply_ops([w.with_state(lambda s: s.inc(w.actor_id))])
        await w.compact()
        assert w.last_replication_status is None
        # the public API still works on demand — opt-out only silences
        # the automatic sampling
        st = await w.replication_status()
        assert st["backlog"]["files"] == 0

    run(go())
    assert "repl_samples" not in trace.snapshot()["counters"]
    trace.reset()


def test_compact_sink_record_carries_replication(tmp_path, monkeypatch):
    path = tmp_path / "dev.jsonl"
    # before the configure: monkeypatch restores what it found
    monkeypatch.setattr(sink, "_configured", False)
    sink.configure(str(path))
    try:
        async def go():
            w = await Core.open(make_opts(MemoryStorage(MemoryRemote())))
            for _ in range(2):
                await w.apply_ops(
                    [w.with_state(lambda s: s.inc(w.actor_id))]
                )
            await w.compact()
            return w

        w = run(go())
    finally:
        monkeypatch.setattr(sink, "_configured", False)
    rec = json.loads(path.read_text().splitlines()[-1])
    assert rec["schema"] == sink.SCHEMA_VERSION
    rep = rec["replication"]
    assert rep["actor"] == w.actor_id.hex()
    assert rep["local_clock"] == {w.actor_id.hex(): 2}
    assert rep["backlog"]["files"] == 0
    assert rep["checkpoint"]["sealed"] is True
    # and the file feeds straight into the fleet aggregator
    [summary] = fleet.device_summaries([str(path)])
    assert summary["replication"] == rep


def test_checkpoint_preserves_cursor_matrix():
    """A warm reopen keeps the replication view: the cursor matrix
    rides in the checkpoint, so watermark continuity survives restarts
    without re-reading any snapshot."""
    async def go():
        remote = MemoryRemote()
        a = await Core.open(make_opts(MemoryStorage(remote)))
        await a.apply_ops([a.with_state(lambda s: s.inc(a.actor_id))])
        await a.compact()
        storage_c = MemoryStorage(remote)
        c = await Core.open(make_opts(storage_c, checkpoint_on_read=True))
        await c.read_remote()  # learns matrix[A], reseals checkpoint
        before = await c.replication_status()
        assert before["matrix"] == {a.actor_id.hex(): {a.actor_id.hex(): 1}}
        c2 = await Core.open(make_opts(storage_c, checkpoint_on_read=True))
        assert c2.opened_from_checkpoint
        after = await c2.replication_status()
        assert after["matrix"] == before["matrix"]
        assert after["watermark"] == before["watermark"]

    run(go())


# ---- sink hardening: schema stamp + rotation ------------------------------


def test_check_schema_rejects_unknown_versions():
    sink.check_schema([{"schema": 1}, {"schema": 2}, {}])  # all readable
    with pytest.raises(sink.SinkSchemaError, match="record 2 has sink"):
        sink.check_schema([{"schema": 2}, {"schema": 99}], source="x.jsonl")
    with pytest.raises(sink.SinkSchemaError):
        sink.check_schema([{"schema": "2"}])  # stringly-typed → reject
    with pytest.raises(sink.SinkSchemaError):
        # bool is an int subclass and True == 1 — must not read as v1
        sink.check_schema([{"schema": True}])


def test_sink_rotation_bounds_file(tmp_path, monkeypatch):
    trace.reset()  # small records: the 500-byte cap must exceed one line
    path = tmp_path / "rot.jsonl"
    s = sink.MetricsSink(str(path))
    monkeypatch.setenv(sink.ENV_MAX_MB, "0.0005")  # 500 bytes
    for i in range(20):
        s.write(f"r{i}")
    assert path.stat().st_size <= 500
    rotated = tmp_path / "rot.jsonl.1"
    assert rotated.exists() and rotated.stat().st_size <= 500
    # every surviving record parses; labels continue across the seam
    recs = sink.read_records(str(rotated)) + sink.read_records(str(path))
    labels = [r["label"] for r in recs]
    assert labels == sorted(labels, key=lambda x: int(x[1:]))
    assert labels[-1] == "r19"
    # off by default: unset → no rotation however large the file
    monkeypatch.delenv(sink.ENV_MAX_MB)
    big = sink.MetricsSink(str(tmp_path / "big.jsonl"))
    for i in range(20):
        big.write(f"b{i}")
    assert not (tmp_path / "big.jsonl.1").exists()


def test_to_prometheus_timestamp_and_help(tmp_path):
    trace.reset()
    trace.add("ops_folded", 3)
    trace.gauge("stream_producers", 2)
    out = sink.to_prometheus(timestamp=1700000000.5)
    trace.reset()
    assert "crdt_ops_folded_total 3 1700000000500" in out
    assert "crdt_stream_producers 2 1700000000500" in out
    # HELP text is pulled from the registry tables in the docs
    help_ = sink.registry_help()
    assert "ops_folded" in help_ and "per-op path" in help_["ops_folded"]
    assert "# HELP crdt_ops_folded_total " + help_["ops_folded"] in out


# ---- fleet aggregation ----------------------------------------------------


def _dev_record(actor, local, union, files, nbytes, wm_lag, ts=100.0,
                remote=RID):
    return {
        "schema": 2, "label": "compact", "ts": ts,
        "spans": {}, "counters": {}, "gauges": {},
        "replication": {
            "actor": actor.hex(),
            "remote_id": remote.hex(),
            "local_clock": {k.hex(): v for k, v in local.items()},
            "union_clock": {k.hex(): v for k, v in union.items()},
            "watermark": {}, "matrix": {},
            "backlog": {"files": files, "bytes": nbytes, "per_actor": {}},
            "divergence": {
                "actors_behind": 0, "version_lag": 0,
                "watermark_lag": wm_lag, "known_replicas": 2,
            },
            "checkpoint": {
                "enabled": True, "sealed": True, "staleness_versions": 0,
            },
        },
    }


def _write_jsonl(path, records):
    path.write_text("".join(json.dumps(r) + "\n" for r in records))


def test_fleet_report_watermark_and_lag_distribution(tmp_path):
    pa, pb = tmp_path / "a.jsonl", tmp_path / "b.jsonl"
    _write_jsonl(pa, [_dev_record(A, {A: 3, B: 2}, {A: 3, B: 2}, 0, 0, 0)])
    _write_jsonl(pb, [
        # an older record first — the NEWEST replication payload wins
        _dev_record(B, {A: 1}, {A: 1}, 0, 0, 0, ts=50.0),
        _dev_record(B, {A: 3}, {A: 3, B: 2}, 2, 410, 5, ts=150.0),
    ])
    report = fleet.fleet_report(
        fleet.device_summaries([str(pa), str(pb)])
    )
    assert report["n_devices"] == 2
    [r] = report["remotes"]
    assert r["remote_id"] == RID.hex()
    assert r["converged"] is False
    # fleet union {A:3,B:2}; stable watermark = pointwise min of local
    # clocks → A: min(3,3)=3, B: min(2,0)=0 → dropped
    assert r["union_clock"] == {A.hex(): 3, B.hex(): 2}
    assert r["stable_watermark"] == {A.hex(): 3}
    assert [d["lag"] for d in r["devices"]] == [0, 2]
    assert r["lag"] == {"min": 0, "p50": 0, "p99": 2, "max": 2}
    assert r["backlog_files"] == {"p50": 0, "p99": 2}
    assert r["backlog_bytes"] == {"p50": 0, "p99": 410}


def test_fleet_converged_fixed_point_and_remote_grouping(tmp_path):
    other = b"\x77" * 32
    paths = []
    for i, actor in enumerate((A, B)):
        p = tmp_path / f"dev{i}.jsonl"
        _write_jsonl(p, [
            _dev_record(actor, {A: 3, B: 2}, {A: 3, B: 2}, 0, 0, 0)
        ])
        paths.append(str(p))
    # a third device on a DIFFERENT remote must not average in
    p = tmp_path / "other.jsonl"
    _write_jsonl(p, [_dev_record(C, {C: 9}, {C: 9}, 0, 0, 0, remote=other)])
    paths.append(str(p))
    report = fleet.fleet_report(fleet.device_summaries(paths))
    assert [r["remote_id"] for r in report["remotes"]] == sorted(
        [other.hex(), RID.hex()]
    )
    main = next(r for r in report["remotes"] if r["remote_id"] == RID.hex())
    assert main["converged"] is True
    assert main["stable_watermark"] == {A.hex(): 3, B.hex(): 2}
    assert main["lag"] == {"min": 0, "p50": 0, "p99": 0, "max": 0}


def test_fleet_rejects_inputs_loudly(tmp_path):
    # no replication payload at all
    p = tmp_path / "plain.jsonl"
    _write_jsonl(p, [{"schema": 2, "label": "compact", "spans": {}}])
    with pytest.raises(fleet.FleetInputError, match="no record carries"):
        fleet.device_summaries([str(p)])
    # unreadable schema fails BEFORE any aggregation
    p2 = tmp_path / "future.jsonl"
    _write_jsonl(p2, [{"schema": 3, "replication": {}}])
    with pytest.raises(sink.SinkSchemaError):
        fleet.device_summaries([str(p2)])


def test_fleet_cli_end_to_end_two_real_devices(tmp_path, capsys,
                                               monkeypatch):
    """Two real cores compact into per-device sink files; `obs_report
    fleet` reports the true fleet watermark and lag."""
    from crdt_enc_tpu.tools import obs_report

    remote = MemoryRemote()
    pa, pb = tmp_path / "deva.jsonl", tmp_path / "devb.jsonl"
    # before any configure: monkeypatch restores what it found
    monkeypatch.setattr(sink, "_configured", False)

    async def device(path, n_ops, read_first):
        sink.configure(str(path))
        w = await Core.open(make_opts(MemoryStorage(remote)))
        if read_first:
            await w.read_remote()
        for _ in range(n_ops):
            await w.apply_ops([w.with_state(lambda s: s.inc(w.actor_id))])
        await w.compact()
        return w

    try:
        a = run(device(pa, 3, False))
        b = run(device(pb, 2, True))
    finally:
        monkeypatch.setattr(sink, "_configured", False)
    assert obs_report.main(["fleet", str(pa), str(pb)]) == 0
    out = capsys.readouterr().out
    ah, bh = a.actor_id.hex(), b.actor_id.hex()
    # device A compacted before B wrote: fleet watermark = A's clock
    # min B's clock pointwise = {A:3}; A lags B's 2 unseen versions
    assert "# fleet: 2 device(s), 1 remote(s)" in out
    assert f"    {ah} = 3" in out
    assert f"device {ah}  lag=2" in out
    assert f"device {bh}  lag=0" in out
    # --json emits the structured report
    assert obs_report.main(["fleet", "--json", str(pa), str(pb)]) == 0
    rep = json.loads(capsys.readouterr().out)
    [r] = rep["remotes"]
    assert r["stable_watermark"] == {ah: 3}
    assert r["union_clock"] == {ah: 3, bh: 2}
    # a deviceless file exits 2 with a pointed message
    empty = tmp_path / "none.jsonl"
    _write_jsonl(empty, [{"schema": 2, "label": "x", "spans": {}}])
    assert obs_report.main(["fleet", str(empty)]) == 2
    assert "no record carries" in capsys.readouterr().err


def test_fleet_golden(capsys, monkeypatch):
    """The committed fixture files render byte-identically to the
    committed golden — the same diff tools/run_checks.sh runs (both
    pin the default SLO config: the SLO column deliberately follows
    CRDT_SLO_*, so the golden must not inherit ambient env)."""
    from crdt_enc_tpu.tools import obs_report

    monkeypatch.delenv("CRDT_SLO_FRESHNESS_LAG", raising=False)
    monkeypatch.delenv("CRDT_SLO_OBJECTIVE", raising=False)
    assert obs_report.main([
        "fleet",
        str(DATA / "fleet_device_a.jsonl"),
        str(DATA / "fleet_device_b.jsonl"),
    ]) == 0
    out = capsys.readouterr().out
    assert out == (DATA / "obs_fleet_golden.txt").read_text()


# ---- bench trend + regression gate ----------------------------------------


def _bench(metric, value, ts, shape=None, backend="cpu"):
    return {
        "metric": metric, "value": value, "ts": ts, "unit": "ops/s",
        "backend": backend, "shape": shape or {"n": 1000},
        "best_variant": "v",
    }


def test_bench_trend_trajectory_and_regressions():
    records = [
        _bench("fold", 100.0, "t1"),
        _bench("fold", 120.0, "t2"),
        _bench("fold", 90.0, "t3"),
        _bench("fold", 500.0, "t1", shape={"n": 9}),  # separate config
        _bench("merge", 50.0, "t1"),                  # single run
        {"schema": 2, "label": "compact", "spans": {}},  # sink noise
    ]
    trend = fleet.bench_trend(records)
    by = {(c["metric"], json.dumps(c["shape"], sort_keys=True)): c
          for c in trend}
    fold = by[("fold", '{"n": 1000}')]
    assert [r["value"] for r in fold["runs"]] == [100.0, 120.0, 90.0]
    assert fold["latest"] == 90.0 and fold["prior_best"] == 120.0
    assert fold["latest_vs_prior_best_pct"] == -25.0
    assert "prior_best" not in by[("merge", '{"n": 1000}')]
    assert by[("fold", '{"n": 9}')]["latest"] == 500.0
    # regression gate: -25% flags at 10, passes at 30; single-run and
    # single-config-improved never flag
    assert [c["metric"] for c in fleet.trend_regressions(trend, 10)] == [
        "fold"
    ]
    assert fleet.trend_regressions(trend, 30) == []
    # metric filter narrows the table
    only = fleet.bench_trend(records, metric="merge")
    assert [c["metric"] for c in only] == ["merge"]


def test_bench_trend_shapeless_records_key_by_config():
    """Shapeless records (the sim bench) fall back to their config
    string — a 4r×50s and an 8r×250s sim run are different workloads
    and must not collapse into one regression trajectory (the ISSUE-11
    ratchet would otherwise compare apples to oranges)."""
    records = [
        {"metric": "sim_schedules_per_sec", "value": 1.3, "ts": "t1",
         "backend": "cpu", "config": "sim_4r_50s_all"},
        {"metric": "sim_schedules_per_sec", "value": 0.5, "ts": "t2",
         "backend": "cpu", "config": "sim_8r_250s_all"},
    ]
    trend = fleet.bench_trend(records)
    assert len(trend) == 2
    assert sorted(c["shape"]["config"] for c in trend) == [
        "sim_4r_50s_all", "sim_8r_250s_all",
    ]
    # one run each → no trajectory, no false regression
    assert fleet.trend_regressions(trend, 10) == []
    # the committed BENCH_LOCAL passes the run_checks.sh ratchet at 45%
    repo_records = sink.read_records(
        str(pathlib.Path(__file__).parent.parent / "BENCH_LOCAL.jsonl")
    )
    repo_trend = fleet.bench_trend(repo_records)
    assert fleet.trend_regressions(repo_trend, 45) == []


def test_trend_cli_fail_on_regression(tmp_path, capsys):
    from crdt_enc_tpu.tools import obs_report

    p = tmp_path / "bench.jsonl"
    _write_jsonl(p, [
        _bench("fold", 100.0, "t1"), _bench("fold", 80.0, "t2"),
    ])
    assert obs_report.main(["trend", str(p)]) == 0
    out = capsys.readouterr().out
    assert "-20.00%" in out and "REGRESSION" not in out
    assert obs_report.main(["trend", str(p), "--fail-on-regression", "10"]
                           ) == 1
    cap = capsys.readouterr()
    assert "** REGRESSION **" in cap.out
    assert "1 config(s) regressed" in cap.err
    assert obs_report.main(["trend", str(p), "--fail-on-regression", "25"]
                           ) == 0
    capsys.readouterr()
    # mixed-version input fails loudly, exit 2
    bad = tmp_path / "bad.jsonl"
    _write_jsonl(bad, [_bench("fold", 1.0, "t1"), {"schema": 42}])
    assert obs_report.main(["trend", str(bad)]) == 2
    assert "sink schema 42" in capsys.readouterr().err
    # the repo's own BENCH_LOCAL.jsonl parses (real-shape regression)
    bench_local = pathlib.Path(__file__).parent.parent / "BENCH_LOCAL.jsonl"
    if bench_local.exists():
        assert obs_report.main(["trend", str(bench_local)]) == 0
        assert "orset" in capsys.readouterr().out
