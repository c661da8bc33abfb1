"""O(tail) steady state (ISSUE 16): persistent fold continuations +
device-cut delta sealing.

The contract under test: the serve tier's steady-state cost must scale
with the TAIL (new ops since the last seal), not with resident STATE —
without moving a single sealed byte.  Three seams, each pinned
differentially against the paths they replace:

* **Device-cut deltas** — ``ops.orset_plane_diff`` (+ the rows gather
  and the host builder ``delta.codec.orset_delta_from_rows``) must
  reproduce the host dict-walk ``orset_delta_diff`` wire form
  byte-for-byte, solo and on the virtual mesh.
* **Persistent continuations** — a FoldService cycle that folds a
  tenant's new rows onto warm resident planes and seals the delta by
  device cut (dropping the retained host base) must stay byte-identical
  to solo ``Core.compact()``, cold readers, and delta-chain consumers,
  with the seal-time self-verify still on.
* **Honest no-ops** — a quiet tenant (no new rows, no local mutation)
  skips device dispatch, state H2D, and every non-listing storage
  probe; eviction or a mut-epoch bump degrade to the full re-fold with
  the reason counted, never to silence.
"""

import asyncio
import copy
import random

import numpy as np
import pytest

from crdt_enc_tpu import ops as K
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
    orset_adapter,
)
from crdt_enc_tpu.delta import ResettableCounter, rcounter_adapter
from crdt_enc_tpu.delta.codec import orset_delta_diff, orset_delta_from_rows
from crdt_enc_tpu.models import ORSet, VClock, canonical_bytes
from crdt_enc_tpu.models.orset import AddOp, Dot, RmOp
from crdt_enc_tpu.obs import runtime as obs_runtime
from crdt_enc_tpu.parallel import TpuAccelerator
from crdt_enc_tpu.parallel import mesh as pmesh
from crdt_enc_tpu.serve import FoldService, ServeConfig
from crdt_enc_tpu.utils import codec as ucodec
from crdt_enc_tpu.utils import trace
from crdt_enc_tpu.utils.versions import DEFAULT_DATA_VERSION_1


def run(coro):
    return asyncio.run(coro)


def make_opts(storage, adapter=None, create=True, **kw):
    kw.setdefault("accelerator", TpuAccelerator(min_device_batch=1))
    return OpenOptions(
        storage=storage,
        cryptor=IdentityCryptor(),
        key_cryptor=PlainKeyCryptor(),
        adapter=adapter if adapter is not None else orset_adapter(),
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


def counters():
    return trace.snapshot()["counters"]


def gauges():
    return trace.snapshot()["gauges"]


# ------------------------------------------------- kernel differentials


def _rand_orset(rng, rounds):
    s = ORSet()
    for _ in range(rounds):
        m = b"m%d" % rng.randrange(8)
        r = b"r%d" % rng.randrange(4)
        if rng.random() < 0.65:
            s.apply(AddOp(m, Dot(r, s.clock.get(r) + rng.randrange(1, 3))))
        else:
            s.apply(RmOp(m, VClock(dict(s.clock.counters))))
    return s


def _evolve(rng, s, rounds):
    n = copy.deepcopy(s)
    for _ in range(rounds):
        m = b"m%d" % rng.randrange(10)
        r = b"r%d" % rng.randrange(4)
        if rng.random() < 0.6:
            n.apply(AddOp(m, Dot(r, n.clock.get(r) + rng.randrange(1, 3))))
        else:
            n.apply(RmOp(m, VClock(dict(n.clock.counters))))
    return n


def _bucket(n, floor=8):
    b = floor
    while b < n:
        b *= 2
    return b


def _cut_on_device(base, new, *, mesh=None):
    """The full device-cut pipeline on two host states: scan a union
    vocab, plane both, diff on device, gather the rows, rebuild the
    wire object with the host builder."""
    members, replicas = K.Vocab(), K.Vocab()
    K.orset_scan_vocab(base, members, replicas)
    K.orset_scan_vocab(new, members, replicas)
    cb, ab, rb = K.orset_state_to_planes(base, members, replicas, scanned=True)
    cn, an, rn = K.orset_state_to_planes(new, members, replicas, scanned=True)
    E, R = len(members), len(replicas)
    if mesh is None:
        code, count = K.orset_plane_diff(cb, ab, rb, cn, an, rn)
    else:
        stack = lambda x: np.broadcast_to(np.asarray(x), (8,) + x.shape)
        code_s, count_s = pmesh.tenant_diff_step(mesh)(
            stack(cb), stack(ab), stack(rb), stack(cn), stack(an), stack(rn)
        )
        code, count = np.asarray(code_s)[0], int(np.asarray(count_s)[0])
    size = min(_bucket(max(int(count), 1)), E * R)
    rows = K.orset_plane_diff_rows(code, ab, an, rn, size=size)
    return orset_delta_from_rows(
        tuple(np.asarray(x) for x in rows),
        members=members.items, replicas=replicas.items, row_width=R,
        base_clock=np.asarray(cb), new_clock=np.asarray(cn),
    )


@pytest.mark.parametrize("seed", range(8))
def test_plane_diff_kernel_matches_host_dict_walk(seed):
    """Randomized causal pairs: the device cut's wire object is
    byte-identical (canonical pack) to the host ``orset_delta_diff`` —
    adds, re-add-over-remove confirmations, removals, and horizons."""
    rng = random.Random(seed)
    base = _rand_orset(rng, 60)
    new = _evolve(rng, base, 40)
    host = orset_delta_diff(base, new)
    dev = _cut_on_device(base, new)
    assert ucodec.pack(host) == ucodec.pack(dev)


def test_plane_diff_of_identical_states_is_empty():
    """diff(x, x) = 0 under the canonical plane laws — the property
    that lets ineligible bucket slots ride the diff dispatch free."""
    rng = random.Random(99)
    s = _rand_orset(rng, 50)
    members, replicas = K.Vocab(), K.Vocab()
    K.orset_scan_vocab(s, members, replicas)
    c, a, r = K.orset_state_to_planes(s, members, replicas, scanned=True)
    code, count = K.orset_plane_diff(c, a, r, c, a, r)
    assert int(count) == 0
    assert not np.asarray(code).any()


@pytest.mark.parametrize("dp,mp", [(8, 1), (4, 2), (2, 4)])
def test_plane_diff_sharded_twin_differential(dp, mp):
    """The shard_map twin returns the same per-tenant code planes and
    (mp-psummed) counts as the vmapped single-device kernel."""
    rng = np.random.default_rng(dp * 10 + mp)
    mesh = pmesh.make_mesh((dp, mp))
    T, R = 8, 4
    E = max(8, mp * 4)
    mk = lambda: np.where(
        rng.random((T, E, R)) < 0.3, rng.integers(1, 9, (T, E, R)), 0
    ).astype(np.int32)
    cb = rng.integers(0, 5, (T, R)).astype(np.int32)
    cn = cb + rng.integers(0, 3, (T, R)).astype(np.int32)
    ab, rb, an, rn = mk(), mk(), mk(), mk()
    ref_code, ref_count = K.orset_plane_diff_tenants(cb, ab, rb, cn, an, rn)
    got_code, got_count = pmesh.tenant_diff_step(mesh)(cb, ab, rb, cn, an, rn)
    assert np.array_equal(np.asarray(ref_code), np.asarray(got_code))
    assert np.array_equal(np.asarray(ref_count), np.asarray(got_count))


def test_plane_diff_sharded_rejects_undivisible():
    mesh = pmesh.make_mesh((8, 1))
    z = np.zeros((6, 8, 4), np.int32)
    c = np.zeros((6, 4), np.int32)
    with pytest.raises(ValueError, match="pad first"):
        pmesh.tenant_plane_diff_sharded(mesh, c, z, z, c, z, z)


@pytest.mark.parametrize("use_mesh", [False, True])
def test_cut_pipeline_differential_through_mesh(use_mesh):
    """The same randomized pair cut solo and through the mesh twin
    packs to the same bytes as the host diff."""
    rng = random.Random(31)
    base = _rand_orset(rng, 50)
    new = _evolve(rng, base, 30)
    mesh = pmesh.make_mesh((8, 1)) if use_mesh else None
    dev = _cut_on_device(base, new, mesh=mesh)
    assert ucodec.pack(orset_delta_diff(base, new)) == ucodec.pack(dev)


def _seeded_bucket(seed, T=8, E=16, R=8):
    """A bucket's base and post-fold stacks with every kind of slot the
    service meets: random diffs, a slot with none (same clock and
    horizons, no entries), and a dummy slot (all zero)."""
    rng = np.random.default_rng(seed)
    mk = lambda: np.where(
        rng.random((T, E, R)) < 0.25, rng.integers(1, 9, (T, E, R)), 0
    ).astype(np.int32)
    cb = rng.integers(0, 5, (T, R)).astype(np.int32)
    cn = cb + rng.integers(0, 3, (T, R)).astype(np.int32)
    ab, rb, an, rn = mk(), mk(), mk(), mk()
    cn[2], rn[2], ab[2], an[2] = cb[2], rb[2], 0, 0  # nothing to cut
    for x in (cb, cn, ab, rb, an, rn):
        x[T - 1] = 0  # dummy slot
    return cb, ab, rb, cn, an, rn


@pytest.mark.parametrize("size_of", ["largest_count", "bucket_class", "cells"])
@pytest.mark.parametrize("seed", [3, 4])
def test_batched_row_gather_equals_per_tenant_gather(seed, size_of):
    """``orset_plane_diff_rows_tenants`` is the per-tenant gather slot by
    slot, all five arrays, padding included: for a slot with no diff, a
    dummy slot, and a slot whose count equals ``size`` exactly."""
    cb, ab, rb, cn, an, rn = _seeded_bucket(seed)
    code, counts = K.orset_plane_diff_tenants(cb, ab, rb, cn, an, rn)
    counts = np.asarray(counts)
    assert counts[2] == 0 and counts[-1] == 0 and counts.max() > 0
    cells = ab.shape[1] * ab.shape[2]
    size = {
        "largest_count": int(counts.max()),  # one slot fills it exactly
        "bucket_class": min(_bucket(int(counts.max())), cells),
        "cells": cells,
    }[size_of]
    got = K.orset_plane_diff_rows_tenants(code, ab, an, rn, size=size)
    assert all(np.asarray(g).shape == (len(counts), size) for g in got)
    for t in range(len(counts)):
        want = K.orset_plane_diff_rows(code[t], ab[t], an[t], rn[t], size=size)
        for g, w in zip(got, want):
            assert np.array_equal(np.asarray(g)[t], np.asarray(w)), t


@pytest.mark.parametrize("live,chunk", [(8, 128), (5, 128), (5, 4), (3, 2)])
def test_stack_and_unstack_tenants_roundtrip(live, chunk, monkeypatch):
    """The bucket's stacks from lists of host and device rows mixed, the
    dummy slots zeroed inside the programs, in one chunk or several (a
    whole chunk of dummies among them); and the per-slot arrays back,
    each an array of its own."""
    import jax.numpy as jnp

    from crdt_enc_tpu.ops import orset as orset_ops

    monkeypatch.setattr(orset_ops, "TENANT_CHUNK", chunk)
    cb, ab, rb, *_ = _seeded_bucket(11)
    T = len(cb)
    mix = lambda x: [
        jnp.asarray(r) if t % 2 else r for t, r in enumerate(x[:live])
    ]
    got = K.orset_stack_tenants(mix(cb), mix(ab), mix(rb), slots=T)
    for g, want in zip(got, (cb, ab, rb)):
        want = want.copy()
        want[live:] = 0
        assert np.array_equal(np.asarray(g), want)
    rows = K.orset_unstack_tenants(*got)
    assert [len(r) for r in rows] == [T, T, T]
    for r, g in zip(rows, got):
        for t in range(T):
            assert np.array_equal(np.asarray(r[t]), np.asarray(g)[t])


# --------------------------------------- service: device cut + no-op


async def _write_orset(core, n, tag):
    for i in range(n):
        m = b"%s-%d" % (tag, i % 13)
        await core.apply_ops(
            [core.with_state(lambda s, m=m: s.add_ctx(core.actor_id, m))]
        )
        if i % 7 == 6:
            victim = b"%s-%d" % (tag, (i * 3) % 13)

            def rm(s, victim=victim):
                return s.rm_ctx(victim) if victim in s.entries else None

            op = core.with_state(rm)
            if op is not None:
                await core.apply_ops([op])


@pytest.mark.parametrize("mesh_spec", [None, (8, 1)])
def test_device_cut_cycle_differential(storage_factory, mesh_spec):
    """The ISSUE-16 end-to-end contract, memory+fs × solo/mesh: a
    continuation cycle seals its delta by device cut (base bytes
    dropped, ``delta_base_bytes`` 0), a quiet cycle honestly no-ops,
    the next active cycle cuts again from the re-stamped planes — and
    at every step the served tenant is byte-identical to a cold reader
    and a delta-chain consumer, with the seal-time self-verify on."""
    mesh = pmesh.make_mesh(mesh_spec) if mesh_spec else None

    async def go():
        writer = await Core.open(make_opts(storage_factory("w")))
        served = await Core.open(
            make_opts(storage_factory("s"), delta=True)
        )
        service = FoldService([served], ServeConfig(), mesh=mesh)

        await _write_orset(writer, 30, b"a")
        trace.reset()
        (r1,) = await service.run_cycle()
        assert r1.sealed and r1.path == "batched"
        assert counters().get("serve_continuations") == 1

        await _write_orset(writer, 10, b"b")
        trace.reset()
        (r2,) = await service.run_cycle()
        assert r2.sealed
        assert counters().get("delta_device_cuts") == 1
        assert counters().get("delta_files_sealed") == 1
        assert not counters().get("delta_seal_divergence")
        assert gauges().get("delta_base_bytes") == 0

        # quiet cycle: the honest no-op (and no re-seal)
        trace.reset()
        (r3,) = await service.run_cycle()
        assert r3.path == "empty" and not r3.sealed
        assert counters().get("serve_noop_cycles") == 1
        assert not counters().get("delta_device_cuts")

        # the continuation survives the no-op: next active cycle cuts
        await _write_orset(writer, 7, b"c")
        trace.reset()
        (r4,) = await service.run_cycle()
        assert r4.sealed
        assert counters().get("delta_device_cuts") == 1

        cold = await Core.open(make_opts(storage_factory("cold")))
        await cold.read_remote()
        assert cold.with_state(canonical_bytes) == served.with_state(
            canonical_bytes
        )
        trace.reset()
        consumer = await Core.open(
            make_opts(storage_factory("consumer"), delta=True)
        )
        await consumer.read_remote()
        assert consumer.with_state(canonical_bytes) == served.with_state(
            canonical_bytes
        )

    run(go())


def test_device_cut_matches_host_diff_arm(storage_factory):
    """Differential against the path it replaces: an op stream served
    with the warm tier OFF (host dict-walk diff, retained base bytes)
    and ON (device cut, dropped base) must each stay byte-identical to
    the authoritative solo ``Core.compact()`` of their remote."""

    async def go():
        for arm in ("host", "cut"):
            writer = await Core.open(make_opts(storage_factory(f"w-{arm}")))
            served = await Core.open(
                make_opts(storage_factory(f"s-{arm}"), delta=True)
            )
            cfg = ServeConfig() if arm == "cut" else ServeConfig(warm=False)
            service = FoldService([served], cfg)
            trace.reset()
            for rnd in range(3):
                await _write_orset(writer, 12, b"r%d" % rnd)
                (res,) = await service.run_cycle()
                assert res.sealed
            if arm == "cut":
                assert counters().get("delta_device_cuts")
                assert gauges().get("delta_base_bytes") == 0
            else:
                assert not counters().get("delta_device_cuts")
            assert not counters().get("delta_seal_divergence")
            solo = await Core.open(make_opts(storage_factory(f"x-{arm}")))
            await solo.compact()
            assert solo.with_state(canonical_bytes) == served.with_state(
                canonical_bytes
            ), arm

    run(go())


async def _fleet_two_cycles(n, mesh=None):
    """``n`` one-writer tenants of one bucket class through a stamping
    cycle and a cutting cycle; returns the service, the served cores and
    the second cycle's counters."""
    remotes = [MemoryRemote() for _ in range(n)]
    writers = [
        await Core.open(make_opts(MemoryStorage(r))) for r in remotes
    ]
    served = [
        await Core.open(make_opts(MemoryStorage(r), delta=True))
        for r in remotes
    ]
    service = FoldService(served, ServeConfig(), mesh=mesh)
    for rnd, count in enumerate((9, 5)):
        for t, w in enumerate(writers):
            await w.apply_ops([
                w.with_state(
                    lambda s, m=b"%d-%d-%d" % (rnd, t, i): s.add_ctx(
                        w.actor_id, m
                    )
                )
                for i in range(count)
            ])
        trace.reset()
        res = await service.run_cycle()
        assert all(r.sealed and r.path == "batched" for r in res)
        assert gauges().get("serve_buckets") == 1
    return service, served, counters()


@pytest.mark.parametrize(
    "rows_on,mesh_spec,chunk",
    [
        ("host", None, 128),
        ("device", None, 128),
        ("device", (8, 1), 128),
        ("device", (8, 1), 4),
    ],
)
def test_device_is_spoken_to_per_bucket_not_per_tenant(
    rows_on, mesh_spec, chunk, monkeypatch
):
    """The per-bucket law (ISSUE 25): a one-bucket cycle issues the same
    number of blocking pulls for 4 tenants as for 12, every tenant cut
    on device.  ``device`` steers the service onto the accelerator's
    branch (the warm tier keeps the unstack programs' device arrays and
    next cycle's stack takes them as they are), which the CPU backend
    otherwise never runs, alone and on the mesh, in one chunk of slots
    and in several."""
    import jax

    from crdt_enc_tpu.ops import orset as orset_ops

    monkeypatch.setattr(orset_ops, "TENANT_CHUNK", chunk)
    mesh = pmesh.make_mesh(mesh_spec) if mesh_spec else None
    if rows_on == "device":
        monkeypatch.setattr(jax, "default_backend", lambda: "not-the-cpu")

    async def go():
        pulls = {}
        for n in (4, 12):
            service, served, c = await _fleet_two_cycles(n, mesh)
            assert c.get("delta_device_cuts") == n
            assert c.get("delta_files_sealed") == n
            assert not c.get("delta_seal_divergence")
            pulls[n] = c.get("d2h_pulls")
            entry = service.warm.lookup(served[0]._data.state)
            assert all(
                isinstance(p, jax.Array) == (rows_on == "device")
                for p in entry.planes
            )
            assert [p.shape for p in entry.planes] == [(8,), (16, 8), (16, 8)]
        # scatter 3, counts 1, diff rows 5, base stacks 3
        assert pulls == {4: 12, 12: 12}

    run(go())


def test_cut_hands_the_verify_host_views_and_tampering_is_refused(
    monkeypatch,
):
    """``delta_cut["base_planes"]`` are numpy views of the bucket's one
    pull: the seal-time self-verify rebuilds the base from them without
    a device sync, passes the genuine delta and still refuses a
    tampered one."""
    plans = []
    real = Core._verify_delta_plan

    def spy(self, plan):
        plans.append((self, plan))
        return real(self, plan)

    monkeypatch.setattr(Core, "_verify_delta_plan", spy)

    async def go():
        await _fleet_two_cycles(3)
        assert len(plans) == 3
        for core, plan in plans:
            assert plan.get("device_cut") and plan["base_state"] is None
            clock, add, rm, _, _ = plan["base_planes"]
            assert all(type(x) is np.ndarray for x in (clock, add, rm))
            assert add.base is not None  # a view of the bucket's stack
            trace.reset()
            assert real(core, plan)
            assert not counters().get("d2h_pulls")
            bad = copy.deepcopy(plan["dobj"])
            member = next(iter(bad[b"e"]))
            rep = next(iter(bad[b"e"][member]))
            bad[b"e"][member][rep] += 1
            assert not real(core, dict(plan, dobj=bad))

    run(go())


@pytest.mark.parametrize("which", ["rcounter", "gcounter"])
def test_other_kinds_ride_the_continuation(storage_factory, which):
    """rcounter states ARE ORSets (adapter inheritance law) so they
    ride the device cut; gcounters take the continuation + no-op path
    with their own codec.  Both stay byte-identical to solo compact."""

    async def go():
        if which == "rcounter":
            adapter, delta = rcounter_adapter, True

            async def write(core, n, r):
                for i in range(n):
                    await core.apply_ops([core.with_state(
                        lambda s, i=i: ResettableCounter.inc(
                            s, core.actor_id, i + r + 1)
                    )])
        else:
            adapter, delta = gcounter_adapter, False

            async def write(core, n, r):
                for _ in range(n):
                    await core.apply_ops([core.with_state(
                        lambda s: s.inc(core.actor_id)
                    )])

        writer = await Core.open(make_opts(storage_factory("w"), adapter()))
        served = await Core.open(
            make_opts(storage_factory("s"), adapter(), delta=delta)
        )
        service = FoldService([served])
        trace.reset()
        for rnd in range(3):
            await write(writer, 10, rnd)
            (res,) = await service.run_cycle()
            assert res.sealed
        if which == "rcounter":
            assert counters().get("delta_device_cuts")
            assert not counters().get("delta_seal_divergence")
        # quiet cycle no-ops for every kind
        trace.reset()
        (rq,) = await service.run_cycle()
        assert rq.path == "empty" and not rq.sealed
        assert counters().get("serve_noop_cycles") == 1

        solo = await Core.open(make_opts(storage_factory("solo"), adapter()))
        await solo.compact()
        assert solo.with_state(canonical_bytes) == served.with_state(
            canonical_bytes
        )

    run(go())


# ----------------------------------------- fallbacks: doubt re-folds


def test_eviction_mid_continuation_falls_back_and_recovers(storage_factory):
    """A warm budget that only holds ONE tenant evicts the other each
    cycle: the evicted tenant full-re-folds next cycle (reason counted:
    ``serve_warm_evictions`` then ``serve_warm_misses``), no device cut
    for it — and every tenant still matches solo compact."""

    async def go():
        writers, served = [], []
        for t in range(2):
            writers.append(
                await Core.open(make_opts(storage_factory(f"w{t}")))
            )
            served.append(await Core.open(
                make_opts(storage_factory(f"s{t}"), delta=True)
            ))
        service = FoldService(served, ServeConfig(warm_bytes=64))
        for t in range(2):
            await _write_orset(writers[t], 20, b"t%d" % t)
        trace.reset()
        r = await service.run_cycle()
        assert all(x.sealed for x in r)
        assert counters().get("serve_warm_evictions")

        for t in range(2):
            await _write_orset(writers[t], 8, b"u%d" % t)
        trace.reset()
        r = await service.run_cycle()
        assert all(x.sealed for x in r)
        assert counters().get("serve_warm_misses")  # the evicted tenant
        # at most one tenant can be plane-resident under this budget
        assert counters().get("delta_device_cuts", 0) <= 1
        assert not counters().get("delta_seal_divergence")

        for t in range(2):
            solo = await Core.open(make_opts(storage_factory(f"solo{t}")))
            await solo.compact()
            assert solo.with_state(canonical_bytes) == served[
                t
            ].with_state(canonical_bytes)

    run(go())


@pytest.mark.parametrize("mesh_spec", [None, (8, 1)])
def test_mut_epoch_bump_mid_continuation_refolds(storage_factory, mesh_spec):
    """A local mutation on the served core between cycles bumps the mut
    epoch: the stamped warm entry's token no longer matches, the next
    cycle counts ``serve_warm_expired`` and re-folds fully — and the
    result is still byte-identical to solo compact."""
    mesh = pmesh.make_mesh(mesh_spec) if mesh_spec else None

    async def go():
        writer = await Core.open(make_opts(storage_factory("w")))
        served = await Core.open(
            make_opts(storage_factory("s"), delta=True)
        )
        service = FoldService([served], mesh=mesh)
        await _write_orset(writer, 20, b"a")
        (r1,) = await service.run_cycle()
        assert r1.sealed

        # the mid-continuation local mutation
        await served.apply_ops([served.with_state(
            lambda s: s.add_ctx(served.actor_id, b"local-op")
        )])
        await _write_orset(writer, 8, b"b")
        trace.reset()
        (r2,) = await service.run_cycle()
        assert r2.sealed
        assert counters().get("serve_warm_expired")
        assert not counters().get("delta_device_cuts")
        assert not counters().get("delta_seal_divergence")

        solo = await Core.open(make_opts(storage_factory("solo")))
        await solo.compact()
        assert solo.with_state(canonical_bytes) == served.with_state(
            canonical_bytes
        )

    run(go())


def test_dropped_base_without_cut_reanchors_snapshot_only(storage_factory):
    """After a device cut dropped the base bytes, a cycle whose cut is
    invalid (fresh service: no stamped planes) must NOT fabricate a
    delta: it counts ``delta_cut_fallbacks`` + ``delta_seal_skipped``,
    re-anchors with a snapshot-only link, and the NEXT cycle deltas
    again — consumers stay byte-identical throughout."""

    async def go():
        writer = await Core.open(make_opts(storage_factory("w")))
        served = await Core.open(
            make_opts(storage_factory("s"), delta=True)
        )
        service = FoldService([served])
        await _write_orset(writer, 20, b"a")
        await service.run_cycle()
        await _write_orset(writer, 8, b"b")
        trace.reset()
        await service.run_cycle()
        assert counters().get("delta_device_cuts") == 1
        assert gauges().get("delta_base_bytes") == 0

        # a FRESH service has no warm planes for the stamped seal — the
        # dropped base cannot be diffed on host either
        service2 = FoldService([served])
        await _write_orset(writer, 8, b"c")
        trace.reset()
        (r,) = await service2.run_cycle()
        assert r.sealed
        assert counters().get("delta_cut_fallbacks") == 1
        assert counters().get("delta_seal_skipped") == 1
        assert not counters().get("delta_files_sealed")

        # self-healing: the snapshot-only link re-retained bytes, so
        # the chain deltas again (host diff now, cut after re-stamp)
        await _write_orset(writer, 6, b"d")
        trace.reset()
        await service2.run_cycle()
        assert counters().get("delta_files_sealed") == 1

        consumer = await Core.open(
            make_opts(storage_factory("consumer"), delta=True)
        )
        await consumer.read_remote()
        assert consumer.with_state(canonical_bytes) == served.with_state(
            canonical_bytes
        )
        from crdt_enc_tpu.tools.fsck import fsck_remote

        report = await fsck_remote(
            storage_factory("fsck"), IdentityCryptor(), PlainKeyCryptor(),
            deep=True,
        )
        assert report.ok, [str(i) for i in report.issues]

    run(go())


# ------------------------------------------------ the CI idle gate


class SpyStorage(MemoryStorage):
    """Counts every storage call, split into LISTING probes (cursor
    staleness checks — allowed every cycle) and everything else (loads,
    stores, removes — forbidden for a quiet tenant's no-op cycle)."""

    LISTING = frozenset({
        "list_remote_meta_names", "list_state_names", "list_op_actors",
        "stat_ops", "list_delta_actors",
    })

    def __init__(self, remote):
        super().__init__(remote)
        self.calls: dict = {}

    def __getattribute__(self, name):
        attr = super().__getattribute__(name)
        if (not name.startswith("_") and callable(attr)
                and name not in ("calls",)
                and asyncio.iscoroutinefunction(attr)):
            calls = super().__getattribute__("calls")

            async def counted(*a, **kw):
                calls[name] = calls.get(name, 0) + 1
                return await attr(*a, **kw)

            return counted
        return attr


def test_quiet_steady_state_cycle_is_listing_only():
    """The run_checks idle-cycle gate: a quiet tenant's steady-state
    cycle performs ZERO XLA compiles, ZERO state H2D bytes, ZERO
    storage calls beyond the listing probes — and honestly counts
    itself as a no-op, one per tenant."""
    obs_runtime.track_recompiles()

    async def go():
        tenants = 4
        spies, served = [], []
        for t in range(tenants):
            remote = MemoryRemote()
            writer = await Core.open(make_opts(MemoryStorage(remote)))
            await _write_orset(writer, 15, b"t%d" % t)
            spy = SpyStorage(remote)
            spies.append(spy)
            served.append(
                await Core.open(make_opts(spy, delta=True))
            )
        service = FoldService(served)
        await service.run_cycle()  # active: fold + seal + stamp
        await service.run_cycle()  # first quiet: settles bookkeeping

        for spy in spies:
            spy.calls.clear()
        trace.reset()
        results = await service.run_cycle()  # THE quiet cycle
        assert all(r.path == "empty" and not r.sealed for r in results)
        c = counters()
        assert c.get("serve_noop_cycles") == tenants
        assert not c.get("jax_compiles")
        assert not c.get("h2d_bytes")
        assert not c.get("delta_device_cuts")
        for spy in spies:
            beyond = {
                k: v for k, v in spy.calls.items()
                if k not in SpyStorage.LISTING
            }
            assert not beyond, beyond

    run(go())


def test_noop_skip_off_is_the_reseal_arm():
    """``ServeConfig(noop_skip=False)`` restores the O(state) steady
    state the bench compares against: every quiet cycle re-seals."""

    async def go():
        remote = MemoryRemote()
        writer = await Core.open(make_opts(MemoryStorage(remote)))
        await _write_orset(writer, 15, b"a")
        served = await Core.open(make_opts(MemoryStorage(remote)))
        service = FoldService([served], ServeConfig(noop_skip=False))
        await service.run_cycle()
        trace.reset()
        (r,) = await service.run_cycle()  # quiet, but re-seals
        assert r.path == "empty" and r.sealed
        assert not counters().get("serve_noop_cycles")

    run(go())


# -------------------------------------------------- CI trend gate


def test_idle_cycle_metric_rides_the_trend_gate():
    """The committed ``--e2e-idle-cycle`` record is a first-class
    ``obs_report trend`` config: ≥10x at 1% active on a 256-tenant
    fleet, and the ``--fail-on-regression`` gate math applies to it."""
    import pathlib

    from crdt_enc_tpu.obs import fleet, sink

    bench_local = pathlib.Path(__file__).parent.parent / "BENCH_LOCAL.jsonl"
    records = sink.read_records(str(bench_local))
    trend = fleet.bench_trend(records, metric="idle_cycle_speedup")
    assert trend, "committed BENCH_LOCAL carries no idle-cycle record"
    cfg = trend[0]
    assert cfg["shape"]["tenants"] >= 256
    assert cfg["latest"] >= 10.0  # the ISSUE-16 bar
    rec = next(r for r in records if r.get("metric") == "idle_cycle_speedup")
    one_pct = [r for r in rec["continuation"]
               if r["active_fraction"] == 0.01][0]
    assert one_pct["jax_compiles"] == 0
    assert one_pct["delta_base_bytes"] == 0
    assert one_pct["serve_noop_cycles"] > 0
    assert rec["byte_identical"] is True
    regressed = dict(rec, value=cfg["best"] / 2)
    t2 = fleet.bench_trend(
        records + [regressed], metric="idle_cycle_speedup"
    )
    assert fleet.trend_regressions(t2, 10)
