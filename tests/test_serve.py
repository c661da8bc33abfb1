"""Multi-tenant fold service (ISSUE 7): byte-identity, bucketing, probes.

The serving contract under test: batching N tenants into shared device
dispatches must be an *invisible* optimization — every tenant's folded
state and sealed snapshot is byte-identical to what its own solo
``Core.compact()`` would have produced (the degenerate 1-tenant case is
the refactor's safety net), the compiled-shape set is bounded by size
classes (shuffled tenant mixes of one class set cannot recompile), and
the batch never pays the PR-6 per-tenant replication probe N times per
dispatch.
"""

import asyncio
import copy
import random

import numpy as np
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
)
from crdt_enc_tpu.models import canonical_bytes
from crdt_enc_tpu.obs import runtime as obs_runtime
from crdt_enc_tpu.parallel import TpuAccelerator
from crdt_enc_tpu.serve import (
    FoldService,
    PlaneWarmTier,
    ServeConfig,
    TenantShape,
    plan_buckets,
)
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
def remote_duo(request, tmp_path):
    """Two byte-identical but independent remotes: ``writer()`` is the
    storage the fixture's writer populates, ``split()`` freezes the
    remote into two copies and hands out ``(solo, served, cold)``
    storages — solo on copy A, served + cold on copy B."""
    if request.param == "memory":
        remote_a = MemoryRemote()

        class Duo:
            def writer(self):
                return MemoryStorage(remote_a)

            def split(self):
                remote_b = copy.deepcopy(remote_a)
                return (
                    MemoryStorage(remote_a),
                    MemoryStorage(remote_b),
                    MemoryStorage(remote_b),
                )

        return Duo()

    class Duo:
        def writer(self):
            return FsStorage(str(tmp_path / "local-w"), str(tmp_path / "rA"))

        def split(self):
            import shutil

            shutil.copytree(str(tmp_path / "rA"), str(tmp_path / "rB"))
            return (
                FsStorage(str(tmp_path / "local-s"), str(tmp_path / "rA")),
                FsStorage(str(tmp_path / "local-v"), str(tmp_path / "rB")),
                FsStorage(str(tmp_path / "local-c"), str(tmp_path / "rB")),
            )

    return Duo()


async def write_orset(storage, n_ops, tag, rm_every=7):
    """Populate a tenant remote with adds + causal removes."""
    core = await Core.open(make_opts(storage))
    for i in range(n_ops):
        m = b"%s-%d" % (tag, i % 31)
        await core.apply_ops(
            [core.with_state(lambda s, m=m: s.add_ctx(core.actor_id, m))]
        )
        if rm_every and i % rm_every == rm_every - 1:
            victim = b"%s-%d" % (tag, (i * 3) % 31)

            def rm(s, victim=victim):
                return s.rm_ctx(victim) if victim in s.entries else None

            op = core.with_state(rm)
            if op is not None:
                await core.apply_ops([op])
    return core


async def write_gcounter(storage, n_ops):
    core = await Core.open(make_opts(storage, gcounter_adapter()))
    for _ in range(n_ops):
        await core.apply_ops(
            [core.with_state(lambda s: s.inc(core.actor_id))]
        )
    return core


# ------------------------------------------------------- bucket planning


def test_plan_buckets_quantizes_and_groups():
    shapes = [
        TenantShape(0, "orset", 100, 20, 5),
        TenantShape(1, "orset", 90, 17, 7),  # same classes as tenant 0
        TenantShape(2, "orset", 1000, 20, 5),  # different row class
        TenantShape(3, "gcounter", 100, 0, 5),  # different kind
        TenantShape(4, "orset", 0, 0, 0),  # empty: not planned at all
    ]
    buckets, solo = plan_buckets(shapes)
    assert solo == []
    keyed = {
        (b.kind, b.rows, b.members, b.replicas): b.tenants for b in buckets
    }
    assert keyed[("orset", 128, 32, 8)] == [0, 1]
    assert keyed[("orset", 1024, 32, 8)] == [2]
    assert keyed[("gcounter", 128, 0, 8)] == [3]
    assert all(4 not in b.tenants for b in buckets)
    # slots quantize with floor 1: two tenants need exactly 2 lanes
    assert {b.slots for b in buckets} == {2, 1}


def test_plan_buckets_shuffle_invariant_shapes():
    """Shuffled mixes of one size-class set plan the same compiled-shape
    set — the pure half of the bounded-jax_compiles acceptance."""
    rng = random.Random(3)
    base = [
        TenantShape(i, "orset", 50 + (i % 3), 10, 4) for i in range(20)
    ] + [TenantShape(100 + i, "orset", 700, 40, 12) for i in range(5)]
    shapes_a = list(base)
    shapes_b = list(base)
    rng.shuffle(shapes_b)
    shape_set = lambda bs: sorted(
        (b.kind, b.rows, b.members, b.replicas, b.slots) for b in bs
    )
    a, _ = plan_buckets(shapes_a)
    b, _ = plan_buckets(shapes_b)
    assert shape_set(a) == shape_set(b)


def test_plan_buckets_spills_and_splits():
    shapes = [
        TenantShape(0, "orset", 10_000, 10, 4),  # rows past cap → solo
        TenantShape(1, "orset", 100, 3000, 600),  # cells past cap → solo
        TenantShape(2, "orset", 100, 10, 4),
        TenantShape(3, "orset", 100, 10, 4),
        TenantShape(4, "orset", 100, 10, 4),
    ]
    buckets, solo = plan_buckets(
        shapes, rows_cap=1024, cells_cap=1 << 20, tenants_cap=2
    )
    assert solo == [0, 1]
    # the 3-tenant group splits at tenants_cap=2 into 2+1, same class
    assert [b.tenants for b in buckets] == [[2, 3], [4]]
    assert [(b.rows, b.members, b.replicas) for b in buckets] == [
        (128, 16, 8), (128, 16, 8),
    ]
    with pytest.raises(ValueError):
        plan_buckets(shapes, rows_cap=0)


# ------------------------------------------------- differential: 1 tenant


def test_single_tenant_service_equals_solo_compact(remote_duo):
    """Satellite 1: the degenerate 1-tenant FoldService dispatch is
    byte-identical to the existing solo ``Core.compact`` path — state,
    sealed snapshot (as read by a cold replica), and op GC — across the
    memory and fs backends.  Solo and served run over byte-identical
    copies of one remote, so the comparison is apples to apples."""

    async def scenario():
        await write_orset(remote_duo.writer(), 60, b"solo")
        solo_s, served_s, cold_s = remote_duo.split()
        solo = await Core.open(make_opts(solo_s))
        served = await Core.open(make_opts(served_s))
        await solo.compact()
        service = FoldService([served])
        (res,) = await service.run_cycle()
        assert res.error is None and res.path == "batched" and res.sealed
        assert solo.with_state(canonical_bytes) == served.with_state(
            canonical_bytes
        )
        # the service-sealed snapshot reads back into the same state on
        # a cold replica, and the covered op files are GC'd
        cold = await Core.open(make_opts(cold_s))
        await cold.read_remote()
        assert cold.with_state(canonical_bytes) == solo.with_state(
            canonical_bytes
        )
        stats = await served.storage.stat_ops(
            [(a, 1) for a in await served.storage.list_op_actors()]
        )
        assert stats == []  # every covered op file removed

    run(scenario())


def test_multitenant_mixed_fleet_differential():
    """Mixed fleet: ragged ORSets, a G-Counter, a solo-type (G-Set) and
    an empty tenant — every tenant's serviced state is byte-identical
    to its solo compact, whatever path it took."""

    async def scenario():
        remotes, adapters, n_ops = [], [], [0, 23, 57, 110, 40, 40]
        for t, n in enumerate(n_ops):
            remote = MemoryRemote()
            remotes.append(remote)
            if t == 4:
                adapters.append(gcounter_adapter)
                await write_gcounter(MemoryStorage(remote), n)
            elif t == 5:
                adapters.append(gset_adapter)
                core = await Core.open(
                    make_opts(MemoryStorage(remote), gset_adapter())
                )
                for i in range(n):
                    await core.apply_ops([b"m%d" % (i % 13)])
            else:
                adapters.append(orset_adapter)
                if n:
                    await write_orset(MemoryStorage(remote), n, b"t%d" % t)

        twins = [copy.deepcopy(r) for r in remotes]
        solo_cores = []
        for ad, r in zip(adapters, twins):
            c = await Core.open(make_opts(MemoryStorage(r), ad()))
            await c.compact()
            solo_cores.append(c)

        served = [
            await Core.open(make_opts(MemoryStorage(r), ad()))
            for ad, r in zip(adapters, remotes)
        ]
        results = await FoldService(served).run_cycle()
        paths = [r.path for r in results]
        assert paths[0] == "empty"
        assert paths[1] == paths[2] == paths[3] == "batched"
        assert paths[4] == "batched"  # gcounter rides its own bucket
        assert paths[5] == "solo"  # gset: accel bulk path, not batched
        for i, (a, b) in enumerate(zip(solo_cores, served)):
            assert a.with_state(canonical_bytes) == b.with_state(
                canonical_bytes
            ), f"tenant {i} diverged ({paths[i]})"
        assert all(r.sealed for r in results)

    run(scenario())


# --------------------------------------------------- ragged edge cases


def test_empty_tenant_seal_parity_and_opt_out():
    async def scenario():
        remote = MemoryRemote()
        served = await Core.open(make_opts(MemoryStorage(remote)))
        (res,) = await FoldService([served]).run_cycle()
        assert res.path == "empty" and res.sealed
        assert len(remote.states) == 1  # solo-compact parity: seals

        remote2 = MemoryRemote()
        served2 = await Core.open(make_opts(MemoryStorage(remote2)))
        (res2,) = await FoldService(
            [served2], ServeConfig(seal_empty=False)
        ).run_cycle()
        assert res2.path == "empty" and not res2.sealed
        assert len(remote2.states) == 0  # quiet tenant costs nothing

    run(scenario())


def test_oversize_tenant_spills_to_solo_path():
    """A tenant past the bucket row cap leaves the mega-fold (solo
    accelerator path) and still lands byte-identical."""

    async def scenario():
        remotes = [MemoryRemote(), MemoryRemote()]
        await write_orset(MemoryStorage(remotes[0]), 120, b"big")
        await write_orset(MemoryStorage(remotes[1]), 30, b"small")
        twins = [copy.deepcopy(r) for r in remotes]
        solo_cores = []
        for r in twins:
            c = await Core.open(make_opts(MemoryStorage(r)))
            await c.compact()
            solo_cores.append(c)
        served = [
            await Core.open(make_opts(MemoryStorage(r))) for r in remotes
        ]
        trace.reset()
        results = await FoldService(
            served, ServeConfig(rows_cap=64)
        ).run_cycle()
        assert results[0].path == "solo"
        assert results[1].path == "batched"
        assert trace.snapshot()["counters"]["serve_solo_spills"] == 1
        for a, b in zip(solo_cores, served):
            assert a.with_state(canonical_bytes) == b.with_state(
                canonical_bytes
            )

    run(scenario())


def test_zero_row_op_files_still_advance_cursors():
    """Validated op files that decode to ZERO columnar rows (an
    empty-ctx remove) must still advance cursors and GC exactly as the
    solo path — or the sealed snapshot carries a stale cursor and the
    files are re-read every cycle forever."""

    async def scenario():
        from crdt_enc_tpu.models.orset import RmOp
        from crdt_enc_tpu.models.vclock import VClock

        remote = MemoryRemote()
        w = await Core.open(make_opts(MemoryStorage(remote)))
        await w.apply_ops([RmOp(b"ghost", VClock())])  # 0-row op file
        twin = copy.deepcopy(remote)
        solo = await Core.open(make_opts(MemoryStorage(twin)))
        await solo.compact()
        served = await Core.open(make_opts(MemoryStorage(remote)))
        service = FoldService([served])
        (res,) = await service.run_cycle()
        assert res.error is None and res.sealed and res.path == "batched"
        assert (
            served._data.next_op_versions.counters
            == solo._data.next_op_versions.counters
        )
        assert await served.storage.list_op_actors() == []  # GC'd
        assert solo.with_state(canonical_bytes) == served.with_state(
            canonical_bytes
        )
        (res2,) = await service.run_cycle()  # nothing left to re-read
        assert res2.path == "empty"

    run(scenario())


def test_all_tenants_land_in_one_bucket():
    async def scenario():
        remotes = [MemoryRemote() for _ in range(5)]
        for t, r in enumerate(remotes):
            await write_orset(MemoryStorage(r), 40, b"same%d" % t)
        served = [
            await Core.open(make_opts(MemoryStorage(r))) for r in remotes
        ]
        trace.reset()
        results = await FoldService(served).run_cycle()
        snap = trace.snapshot()
        assert snap["gauges"]["serve_buckets"] == 1
        assert all(r.path == "batched" for r in results)
        assert snap["counters"]["serve_rows_folded"] == sum(
            r.rows for r in results
        )

    run(scenario())


def test_bounded_compiles_across_shuffled_tenant_mixes():
    """Acceptance: ``jax_compiles`` is constant after warmup across two
    different shuffled tenant mixes of the same size classes — bucket
    quantization as a machine-checked property, not a hope."""

    async def build_fleet(sizes, tag):
        served = []
        for t, n in enumerate(sizes):
            remote = MemoryRemote()
            await write_orset(
                MemoryStorage(remote), n, b"%s%d" % (tag, t), rm_every=5
            )
            served.append(await Core.open(make_opts(MemoryStorage(remote))))
        return served

    async def scenario():
        obs_runtime.track_recompiles()
        sizes = [20, 25, 30, 90, 100, 40]
        fleet_a = await build_fleet(sizes, b"a")
        await FoldService(fleet_a).run_cycle()  # warmup compiles
        baseline = obs_runtime.recompile_count()
        shuffled = list(sizes)
        random.Random(11).shuffle(shuffled)
        fleet_b = await build_fleet(shuffled, b"b")
        await FoldService(fleet_b).run_cycle()
        assert obs_runtime.recompile_count() == baseline, (
            "a shuffled tenant mix of the same size classes recompiled "
            "the mega-fold"
        )

    run(scenario())


# ----------------------------------------------------- replication probes


class _ProbeCountingStorage(MemoryStorage):
    def __init__(self, remote):
        super().__init__(remote)
        self.stat_calls = 0
        self.list_calls = 0

    def reset_counts(self):
        self.stat_calls = 0
        self.list_calls = 0

    async def stat_ops(self, actor_first_versions):
        self.stat_calls += 1
        return await super().stat_ops(actor_first_versions)

    async def list_op_actors(self):
        self.list_calls += 1
        return await super().list_op_actors()


def test_service_cycle_pays_zero_replication_probes():
    """Satellite 3: the batch seal samples replication once per tenant
    per cycle REUSING the ingest's own listing (``_backlog=[]``, the
    read_remote contract) — per tenant the cycle pays exactly ONE
    ``list_op_actors`` (its own ingest) and ZERO ``stat_ops``, where a
    solo compact pays a second listing for its post-GC status probe.
    Every tenant still publishes a sample."""

    async def scenario():
        n = 4
        storages = []
        served = []
        for t in range(n):
            remote = MemoryRemote()
            await write_orset(MemoryStorage(remote), 25, b"p%d" % t)
            st = _ProbeCountingStorage(remote)
            storages.append(st)
            served.append(await Core.open(make_opts(st)))
        for st in storages:
            st.reset_counts()  # open() legitimately probes once
        trace.reset()
        results = await FoldService(served).run_cycle()
        assert all(r.sealed for r in results)
        assert [st.stat_calls for st in storages] == [0] * n
        assert [st.list_calls for st in storages] == [1] * n
        assert trace.snapshot()["counters"]["repl_samples"] == n
        # ...and the sampled status is the post-compaction fixed point
        for c in served:
            assert c.last_replication_status["backlog"]["files"] == 0

        # the solo path on the same remotes pays a SECOND listing per
        # tenant for its status sample — the probe cost the service
        # amortizes away (regression anchor: if the solo path stops
        # probing, rethink this test, not the service)
        for st in storages:
            st.reset_counts()
        for c in served:
            await c.compact()
        assert [st.list_calls for st in storages] == [2] * n

    run(scenario())


# ------------------------------------------------------------- warm tier


def test_warm_tier_unit_lru_budget_and_invalidation():
    class S:  # minimal state stand-in with a mutation epoch
        _mut = 0

    tier = PlaneWarmTier(byte_budget=100)
    states = [S(), S(), S()]
    planes = lambda n: (np.zeros(n, np.int32),)  # n*4 bytes
    trace.reset()
    tier.store(states[0], None, None, planes(10))  # 40 bytes
    tier.store(states[1], None, None, planes(10))  # 80 bytes
    assert tier.lookup(states[0]) is not None  # refreshes LRU: 1 is oldest
    tier.store(states[2], None, None, planes(10))  # 120 → evict state 1
    assert len(tier) == 2 and tier.bytes_held == 80
    assert tier.lookup(states[1]) is None
    snap = trace.snapshot()["counters"]
    assert snap["serve_warm_evictions"] == 1
    # mutation-epoch invalidation
    assert tier.lookup(states[2]) is not None
    states[2]._mut = 99
    assert tier.lookup(states[2]) is None
    assert len(tier) == 1
    with pytest.raises(ValueError):
        PlaneWarmTier(byte_budget=0)


def test_warm_tier_reuse_across_cycles_byte_identical():
    """Cycle 2 on un-mutated tenants hits the warm tier (no state
    re-scan) and still folds byte-identically vs a cold reader; a local
    apply between cycles invalidates that tenant's entry."""

    async def scenario():
        remotes = [MemoryRemote() for _ in range(3)]
        for t, r in enumerate(remotes):
            await write_orset(MemoryStorage(r), 35, b"w%d" % t)
        served = [
            await Core.open(make_opts(MemoryStorage(r))) for r in remotes
        ]
        service = FoldService(served)
        await service.run_cycle()
        assert len(service.warm) == 3
        for t, r in enumerate(remotes):  # second round of remote writes
            await write_orset(MemoryStorage(r), 12, b"x%d" % t, rm_every=0)
        # tenant 0 also applies locally → its warm entry must invalidate
        await served[0].apply_ops(
            [served[0].with_state(
                lambda s: s.add_ctx(served[0].actor_id, b"local")
            )]
        )
        trace.reset()
        results = await service.run_cycle()
        snap = trace.snapshot()["counters"]
        assert snap["serve_warm_hits"] == 2
        assert snap["serve_warm_misses"] == 1
        assert all(r.path == "batched" for r in results)
        for c, r in zip(served, remotes):
            cold = await Core.open(make_opts(MemoryStorage(r)))
            await cold.read_remote()
            assert c.with_state(canonical_bytes) == cold.with_state(
                canonical_bytes
            )

    run(scenario())


# --------------------------------------------- planes-packed checkpoints


def test_pack_checkpoint_planes_roundtrip_equals_sparse_pack():
    """The service's vectorized checkpoint payload (packed from dense
    planes) unpacks to the same state as the sparse dict-walk pack —
    including bucket-padded planes, deferred-only members, and an
    empty state."""
    import random

    from crdt_enc_tpu.models import ORSet
    from crdt_enc_tpu.models.orset import AddOp, RmOp
    from crdt_enc_tpu.models.vclock import Dot, VClock
    from crdt_enc_tpu.ops.columnar import (
        Vocab,
        orset_pack_checkpoint,
        orset_pack_checkpoint_planes,
        orset_state_to_planes,
        orset_unpack_checkpoint,
    )
    from crdt_enc_tpu.utils import codec

    rng = random.Random(13)
    actors = [bytes([i]) * 16 for i in range(9)]
    s = ORSet()
    for _ in range(800):
        a = rng.choice(actors)
        m = rng.choice([b"x", 5, "s", (2, "t"), rng.randrange(25)])
        s.apply(AddOp(m, s.clock.inc(a)))
        if rng.random() < 0.3 and s.entries:
            m2 = rng.choice(list(s.entries))
            s.apply(RmOp(m2, VClock(dict(s.entries[m2]))))
    s.apply(RmOp(b"ahead", VClock({b"z" * 16: 7})))  # deferred-only member
    members, replicas = Vocab(), Vocab()
    clock, add, rm = orset_state_to_planes(s, members, replicas)
    # bucket-pad the planes as the service would
    add_p = np.pad(add, ((0, 5), (0, 3)))
    rm_p = np.pad(rm, ((0, 5), (0, 3)))
    clock_p = np.pad(clock, (0, 3))
    via_planes = orset_unpack_checkpoint(codec.unpack(codec.pack(
        orset_pack_checkpoint_planes(clock_p, add_p, rm_p, members, replicas)
    )))
    via_sparse = orset_unpack_checkpoint(codec.unpack(codec.pack(
        orset_pack_checkpoint(s)
    )))
    assert codec.pack(via_planes.to_obj()) == codec.pack(s.to_obj())
    assert codec.pack(via_planes.to_obj()) == codec.pack(via_sparse.to_obj())
    empty = orset_unpack_checkpoint(codec.unpack(codec.pack(
        orset_pack_checkpoint_planes(
            np.zeros(4, np.int32), np.zeros((4, 4), np.int32),
            np.zeros((4, 4), np.int32), Vocab(), Vocab(),
        )
    )))
    assert codec.pack(empty.to_obj()) == codec.pack(ORSet().to_obj())


def test_service_sealed_checkpoint_warm_opens():
    """A tenant closed after a service cycle warm-opens from the
    service-sealed (planes-packed) checkpoint, byte-identical."""

    async def scenario():
        remote = MemoryRemote()
        await write_orset(MemoryStorage(remote), 45, b"ck")
        storage = MemoryStorage(remote)
        served = await Core.open(make_opts(storage))
        (res,) = await FoldService([served]).run_cycle()
        assert res.path == "batched" and res.sealed
        reopened = await Core.open(make_opts(storage, create=False))
        assert reopened.opened_from_checkpoint, (
            reopened.checkpoint_fallback_reason
        )
        assert reopened.with_state(canonical_bytes) == served.with_state(
            canonical_bytes
        )

    run(scenario())


# -------------------------------------------------------- CI trend gate


def test_multitenant_metric_rides_the_trend_gate():
    """Satellite 5: the committed multitenant BENCH_LOCAL record is a
    first-class config for ``obs_report trend`` and its
    ``--fail-on-regression`` CI gate — same machinery, new metric."""
    import pathlib

    from crdt_enc_tpu.obs import fleet, sink

    bench_local = pathlib.Path(__file__).parent.parent / "BENCH_LOCAL.jsonl"
    records = sink.read_records(str(bench_local))
    trend = fleet.bench_trend(
        records, metric="orset_multitenant_agg_ops_per_sec"
    )
    assert trend, "committed BENCH_LOCAL carries no multitenant record"
    cfg = trend[0]
    assert cfg["shape"]["tenants"] >= 256
    assert cfg["latest"] > 0
    # the gate math applies to it exactly like every other config: a
    # synthetic regressed run after the committed one must trip
    regressed = dict(records[-1], metric=cfg["metric"], value=cfg["best"] / 2,
                     backend=cfg["backend"], shape=cfg["shape"])
    t2 = fleet.bench_trend(
        [r for r in records] + [regressed],
        metric="orset_multitenant_agg_ops_per_sec",
    )
    assert fleet.trend_regressions(t2, 10)


# --------------------------------------------------- lifecycle guards


def test_close_is_idempotent_and_cycle_after_close_refuses():
    """Satellite 2 (ISSUE 12): a second ``close()`` is a logged no-op
    (never a hang), and ``run_cycle`` on a closed service is a loud
    error instead of silently cycling released resources."""

    async def scenario():
        core = await Core.open(make_opts(MemoryStorage(MemoryRemote())))
        service = FoldService([core], live_port=0)
        port = service.live.port
        await service.run_cycle()
        service.close()
        assert service.closed
        service.close()  # idempotent — must return, not hang
        with pytest.raises(RuntimeError, match="closed"):
            await service.run_cycle()
        # the live listener really stopped
        import socket

        with socket.socket() as s:
            assert s.connect_ex(("127.0.0.1", port)) != 0

    run(scenario())


def test_run_cycle_is_not_reentrant():
    """An overlapping ``run_cycle`` raises immediately: the fold phase
    assumes exclusive ownership of the cycle's tenants, so interleaving
    two cycles would interleave two fleets' folds."""

    class StallingStorage(MemoryStorage):
        def __init__(self, remote, gate):
            super().__init__(remote)
            self._gate = gate

        async def list_op_actors(self):
            await self._gate.wait()
            return await super().list_op_actors()

    async def scenario():
        gate = asyncio.Event()
        gate.set()  # open() samples replication through the listing
        remote = MemoryRemote()
        await write_orset(MemoryStorage(remote), 10, b"re")
        core = await Core.open(make_opts(StallingStorage(remote, gate)))
        service = FoldService([core])
        gate.clear()
        first = asyncio.ensure_future(service.run_cycle())
        await asyncio.sleep(0)  # first cycle enters its ingest stall
        with pytest.raises(RuntimeError, match="not reentrant"):
            await service.run_cycle()
        gate.set()
        results = await first
        assert results[0].error is None
        # the guard resets: a sequential second cycle is fine
        (res2,) = await service.run_cycle()
        assert res2.error is None

    run(scenario())


def test_run_cycle_subset_override():
    """``run_cycle(tenants=...)`` cycles exactly the given subset (the
    daemon's staleness scheduler) without touching the rest."""

    async def scenario():
        remotes = [MemoryRemote() for _ in range(3)]
        for t, r in enumerate(remotes):
            await write_orset(MemoryStorage(r), 20, b"s%d" % t)
        served = [
            await Core.open(make_opts(MemoryStorage(r))) for r in remotes
        ]
        service = FoldService(served)
        results = await service.run_cycle(served[:2])
        assert len(results) == 2
        assert all(r.sealed for r in results)
        # tenant 2 untouched: its remote still has its op backlog
        assert await served[2].storage.list_op_actors() != []

    run(scenario())


# ------------------------------------------------------- fault isolation


def test_tenant_failure_is_isolated():
    class BrokenStorage(MemoryStorage):
        async def list_op_actors(self):
            raise OSError("remote unreachable")

    async def scenario():
        ok_remote = MemoryRemote()
        await write_orset(MemoryStorage(ok_remote), 20, b"ok")
        broken = await Core.open(make_opts(MemoryStorage(MemoryRemote())))
        broken.storage.__class__ = BrokenStorage  # break AFTER open
        healthy = await Core.open(make_opts(MemoryStorage(ok_remote)))
        results = await FoldService([broken, healthy]).run_cycle()
        assert results[0].path == "error"
        assert "remote unreachable" in results[0].error
        assert not results[0].sealed
        assert results[1].path == "batched" and results[1].sealed

    run(scenario())


# ------------------------------------- seal tail: one worker job a tenant


class _SealPhaseProbe:
    """Counts ``asyncio.to_thread`` hops and flushes made while
    ``FoldService._seal_all`` is running: ``os.fsync`` from the Python
    file helpers, ``file_step_flushes`` from the native file steps."""

    def __init__(self, monkeypatch):
        import os

        from crdt_enc_tpu.obs import sink

        # no metrics sink: its record would be a second hop a tenant
        monkeypatch.setattr(sink, "_configured", None)
        self.hops = self.fsyncs = 0
        self.inside = False
        real_hop, real_fsync = asyncio.to_thread, os.fsync
        real_seal_all = FoldService._seal_all

        async def hop(fn, *args, **kw):
            self.hops += self.inside
            return await real_hop(fn, *args, **kw)

        def fsync(fd):
            self.fsyncs += self.inside
            return real_fsync(fd)

        from crdt_enc_tpu import native

        native_flushes = native.load().file_step_flushes

        async def seal_all(service, works, t0):
            self.inside = True
            before = native_flushes()
            try:
                return await real_seal_all(service, works, t0)
            finally:
                self.inside = False
                self.fsyncs += native_flushes() - before

        monkeypatch.setattr(asyncio, "to_thread", hop)
        monkeypatch.setattr(os, "fsync", fsync)
        monkeypatch.setattr(FoldService, "_seal_all", seal_all)


@pytest.mark.parametrize("kind", ["memory", "fs"])
def test_busy_cycle_seals_each_tenant_in_one_job(kind, tmp_path, monkeypatch):
    """A toy busy cycle: every tenant has new files, and each tenant's
    whole seal tail is ONE hop to a worker thread (snapshot, delta with its
    verify, local meta, GC, checkpoint), with the flushes it always made:
    file and directory for each of the four files it publishes, whichever
    of the two (native step, Python helper) made them."""
    tenants = 5

    def storage(t, name):
        if kind == "memory":
            return MemoryStorage(remotes[t])
        base = tmp_path / f"t{t}"
        return FsStorage(str(base / name), str(base / "remote"))

    remotes = [MemoryRemote() for _ in range(tenants)]

    async def go():
        served = [
            await Core.open(make_opts(storage(t, "s"))) for t in range(tenants)
        ]
        service = FoldService(served)
        for t in range(tenants):
            await write_orset(storage(t, "w1"), 12, b"a%d" % t)
        await service.run_cycle()  # snapshot-only links: no base yet
        for t in range(tenants):
            await write_orset(storage(t, "w2"), 6, b"b%d" % t)
        probe = _SealPhaseProbe(monkeypatch)
        trace.reset()
        results = await service.run_cycle()
        assert all(r.sealed and r.error is None for r in results)
        counted = trace.snapshot()["counters"]
        assert counted.get("seal_jobs") == tenants
        assert not counted.get("seal_stepwise")
        assert counted.get("delta_files_sealed") == tenants
        assert probe.hops == tenants
        if kind == "fs":
            assert probe.fsyncs == 8 * tenants
        service.close()

    run(go())
    trace.reset()


def test_seal_tail_behind_a_fault_wrapper_stays_on_the_loop():
    """``FaultyStorage`` defines no sync twin, so its tenants' tails are
    awaited call by call, through the wrapper's own awaitables (where the
    faults are rolled), and are counted as such."""
    from crdt_enc_tpu.sim.faults import FaultConfig, FaultyStorage

    tenants = 3

    async def go():
        remotes = [MemoryRemote() for _ in range(tenants)]
        for t, r in enumerate(remotes):
            await write_orset(MemoryStorage(r), 12, b"f%d" % t)
        wrapped = [
            FaultyStorage(
                MemoryStorage(r), FaultConfig.none(), seed=27, name=f"t{t}"
            )
            for t, r in enumerate(remotes)
        ]
        seen: list = []
        for w in wrapped:
            real = w._write

            async def write(family, thunk, landed=None, real=real):
                seen.append(family)
                return await real(family, thunk, landed)

            w._write = write
        served = [await Core.open(make_opts(w)) for w in wrapped]
        del seen[:]
        trace.reset()
        results = await FoldService(served).run_cycle()
        assert all(r.sealed for r in results)
        counted = trace.snapshot()["counters"]
        assert counted.get("seal_stepwise") == tenants
        assert not counted.get("seal_jobs")
        for call in ("store_state", "remove_states", "remove_ops",
                     "store_local_checkpoint"):
            assert seen.count(call) == tenants, call

    run(go())
    trace.reset()


# ---- the ingest job (ISSUE 31): a tenant's poll of its remote is one
# worker job over the storage's sync twins, or today's awaited calls ----


class _AwaitedOnly(FsStorage):
    """A storage without the twins, as ``twins.offers`` judges it: this
    class overrides one awaitable, and so left the inherited twins
    behind.  Its polls are awaited call by call."""

    async def load_ops(self, actor_first_versions):
        return await super().load_ops(actor_first_versions)


def _fleet_storage(cls, root, t: int, name: str):
    base = root / f"t{t}"
    return cls(str(base / name), str(base / "remote"))


async def _toy_fleet(root, tenants: int) -> None:
    """``tenants`` remotes a service has sealed once, each with new op
    files of two writers since, but the last, which has none."""
    served = [
        await Core.open(make_opts(_fleet_storage(FsStorage, root, t, "s")))
        for t in range(tenants)
    ]
    for t in range(tenants):
        await write_orset(_fleet_storage(FsStorage, root, t, "w1"), 12, b"a%d" % t)
    service = FoldService(served)
    assert all(r.error is None for r in await service.run_cycle())
    service.close()
    for t in range(tenants - 1):
        await write_orset(_fleet_storage(FsStorage, root, t, "w2"), 6, b"b%d" % t)
        await write_orset(_fleet_storage(FsStorage, root, t, "w3"), 3, b"c%d" % t)


async def _serve_once(root, cls, tenants: int, monkeypatch):
    """One cycle of a service reopened over ``root``: every tenant's
    ``(actors, files, groups)``, its result less the clock, the tree
    left behind, and the counters."""
    from test_fs_native_steps import tree

    cores = [
        await Core.open(make_opts(_fleet_storage(cls, root, t, "s")))
        for t in range(tenants)
    ]
    polled = {}
    real = Core.poll_sealed_ops

    async def poll(core):
        actors, files, groups = await real(core)
        polled[cores.index(core)] = (
            actors, files,
            [(key.id, key.material, idxs, mids) for key, idxs, mids in groups],
        )
        return actors, files, groups

    monkeypatch.setattr(Core, "poll_sealed_ops", poll)
    trace.reset()
    service = FoldService(cores)
    results = await service.run_cycle()
    service.close()
    monkeypatch.setattr(Core, "poll_sealed_ops", real)
    counted = trace.snapshot()["counters"]
    trace.reset()
    return (
        [polled[t] for t in range(tenants)],
        [(r.path, r.rows, r.sealed, r.error) for r in results],
        tree(str(root)), counted,
    )


def _read_body(body: str, monkeypatch) -> None:
    """How ``FsStorage`` makes a poll's reads: ``native`` (one call
    each), ``python`` (no library: what a machine without a toolchain
    runs) or ``status`` (the library answers with a non-zero status)."""
    from test_fs_native_steps import no_toolchain

    from crdt_enc_tpu import native

    lib = native.load()
    if body == "python":
        no_toolchain(monkeypatch)
    elif body == "status":
        monkeypatch.setattr(lib, "list_dir_names", lambda *a: 5)  # EIO, say
        monkeypatch.setattr(lib, "load_op_window", lambda *a: 5)


@pytest.mark.parametrize("storage", ["twins", "awaited"])
@pytest.mark.parametrize("body", ["native", "python", "status"])
def test_ingest_job_and_awaited_poll_agree(body, storage, tmp_path, monkeypatch):
    """The job and the call-by-call poll, over the native reads, the
    Python ones and a library that reports a surprise, are held to ONE
    outcome: what ``poll_sealed_ops`` hands the cycle, each tenant's
    result, and every byte on disk afterwards (sealed snapshots, deltas,
    checkpoints), against the awaited calls over the Python bodies."""
    import shutil

    tenants = 4

    async def go():
        await _toy_fleet(tmp_path / "a", tenants)
        shutil.copytree(tmp_path / "a", tmp_path / "b")
        with monkeypatch.context() as m:
            _read_body("python", m)
            expected = await _serve_once(tmp_path / "a", _AwaitedOnly, tenants, m)
        assert expected[3].get("ingest_stepwise") == tenants
        with monkeypatch.context() as m:
            _read_body(body, m)
            cls = FsStorage if storage == "twins" else _AwaitedOnly
            got = await _serve_once(tmp_path / "b", cls, tenants, m)
        assert got[:3] == expected[:3]
        assert [r for r in got[1] if r[0] == "batched"] and all(
            r[3] is None for r in got[1]
        )
        counted = got[3]
        jobs = tenants if storage == "twins" else 0
        assert counted.get("ingest_jobs", 0) == jobs
        assert counted.get("ingest_stepwise", 0) == tenants - jobs
        # three listings a tenant, and a load where ops/ holds an actor
        reads = 4 * tenants - 1
        native_reads = reads if body == "native" else 0
        assert counted.get("fs_reads_native", 0) == native_reads
        assert counted.get("fs_reads_python", 0) == reads - native_reads

    run(go())


@pytest.mark.parametrize("unread", ["meta", "states"])
def test_an_unread_name_stops_the_job_and_the_tenant_converges(
    unread, tmp_path
):
    """A name the tenant has not read, in ``meta/`` (a rotated key: the
    ops behind it cannot be unwrapped before it is merged) or in
    ``states/`` (a foreign snapshot: its merge moves the cursor the load
    is planned from), ends the job at that listing; the poll goes on
    call by call with the listing it has, is counted as stepwise, and
    lists nothing twice."""
    tenants = 3

    async def go():
        await _toy_fleet(tmp_path, tenants)
        other = await Core.open(
            make_opts(_fleet_storage(FsStorage, tmp_path, 0, "other"))
        )
        await other.read_remote()
        if unread == "meta":
            await other.rotate_key()
            await other.apply_ops(
                [other.with_state(lambda s: s.add_ctx(other.actor_id, b"late"))]
            )
        else:
            await other.compact()
        cores = [
            await Core.open(make_opts(_fleet_storage(FsStorage, tmp_path, t, "s")))
            for t in range(tenants)
        ]
        trace.reset()
        service = FoldService(cores)
        results = await service.run_cycle()
        service.close()
        assert all(r.error is None for r in results), results
        snap = trace.snapshot()
        assert snap["counters"].get("ingest_stepwise") == 1
        assert snap["counters"].get("ingest_jobs") == tenants - 1
        for listing in ("meta.list", "states.list", "ops.list"):
            assert snap["spans"][listing]["count"] == tenants, listing
        await other.read_remote()
        cold = await Core.open(
            make_opts(_fleet_storage(FsStorage, tmp_path, 0, "cold"))
        )
        await cold.read_remote()
        want = other.with_state(canonical_bytes)
        assert cores[0].with_state(canonical_bytes) == want
        assert cold.with_state(canonical_bytes) == want
        if unread == "meta":
            assert cores[0].with_state(lambda s: b"late" in s.entries)

    run(go())
    trace.reset()


def test_one_tenants_job_raising_is_that_tenants_error_alone(tmp_path):
    tenants = 3

    async def go():
        await _toy_fleet(tmp_path, tenants)
        cores = [
            await Core.open(make_opts(_fleet_storage(FsStorage, tmp_path, t, "s")))
            for t in range(tenants)
        ]

        def broken(actor_first_versions):
            raise PermissionError("ops/ is not to be read (test)")

        cores[1].storage.load_ops_sync = broken
        trace.reset()
        service = FoldService(cores)
        results = await service.run_cycle()
        service.close()
        assert results[1].path == "error" and "PermissionError" in results[1].error
        assert not results[1].sealed
        assert results[0].error is None and results[0].sealed
        assert results[2].error is None
        counted = trace.snapshot()["counters"]
        # one a tenant polled, the one whose job raised among them
        assert counted.get("ingest_jobs") == tenants
        assert not counted.get("ingest_stepwise")

    run(go())
    trace.reset()
