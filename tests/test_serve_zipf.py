"""The fold service over tenants of skewed sizes (ISSUE 26): several bucket
classes and a solo spill in one cycle, against the plain reference.

Tenants of four size classes, the largest past ``cells_cap``; seeded ops in the
program's wire form; after each ``run_cycle()`` every tenant's canonical state
equals ``cellbench.reference`` applied op by op, and the cycle's new counters
(``serve_buckets_folded``, ``serve_stack_cells``, ``serve_tenant_cells``) are
what ``plan_buckets`` says of those shapes.  The spilled tenant folds at
power-of-two vocabulary classes, so its growing vocabulary compiles nothing
new from one cycle to the next.
"""

import asyncio

import numpy as np

from cellbench import gen, gen_zipf, reference
from crdt_enc_tpu.backends import IdentityCryptor, MemoryRemote, MemoryStorage, PlainKeyCryptor
from crdt_enc_tpu.core import Core, OpenOptions, orset_adapter
from crdt_enc_tpu.obs import runtime as obs_runtime
from crdt_enc_tpu.parallel import TpuAccelerator
from crdt_enc_tpu.serve import FoldService, ServeConfig, TenantShape, plan_buckets
from crdt_enc_tpu.serve.bucketing import _bucket
from crdt_enc_tpu.utils import trace
from crdt_enc_tpu.utils.versions import DEFAULT_DATA_VERSION_1

CONFIG = {
    "tenants": 10, "devices": 4, "team_devices": 16, "team_ranks": 1,
    "members": 512, "members_floor": 8, "ops_per_file": 24,
    "remove_fraction": 0.1, "initial_files_per_device": 1,
}
MIX = {"active_tenants": 10, "active_devices": 2, "files_per_device": 1,
       "warmup_rounds": 0, "max_ops_per_s": 1000}
CELLS_CAP = 2048  # rank 1: 512 members x 16 writers, past it; rank 2 (256 x 8) at it


def open_core():
    return Core.open(OpenOptions(
        storage=MemoryStorage(MemoryRemote()),
        cryptor=IdentityCryptor(),
        key_cryptor=PlainKeyCryptor(),
        adapter=orset_adapter(),
        supported_data_versions=(DEFAULT_DATA_VERSION_1,),
        current_data_version=DEFAULT_DATA_VERSION_1,
        create=True,
        accelerator=TpuAccelerator(),
    ))


def expected_counters(plan, r: int, reached) -> dict:
    """What the planner makes of round ``r``'s shapes, as the counters sum it."""
    rows = plan.rows_per_tenant(r)
    shapes = [TenantShape(t, "orset", int(rows[t]), int(reached[t]), int(plan.writers[t]))
              for t in range(plan.tenants)]
    buckets, solo = plan_buckets(shapes, cells_cap=CELLS_CAP)
    return {
        "serve_buckets_folded": len(buckets),
        "serve_solo_spills": len(solo),
        "serve_stack_cells": sum(
            b.slots * _bucket(b.members) * _bucket(b.replicas) for b in buckets),
        "serve_tenant_cells": sum(
            int(reached[t]) * int(plan.writers[t]) for b in buckets for t in b.tenants),
    }


def test_cycle_over_four_size_classes_and_a_spill_matches_the_plain_reference():
    async def scenario():
        obs_runtime.track_recompiles()
        uniform = gen.plan_run(CONFIG, MIX, 2**31 + 26, 3)
        plan = gen_zipf.plan_zipf(CONFIG, uniform)
        reached = plan.reached()
        cores = [await open_core() for _ in range(plan.tenants)]
        service = FoldService(cores, ServeConfig(cells_cap=CELLS_CAP))
        classes = set()
        compiles = []
        for r in range(-1, plan.n_rounds):
            await gen.store_blobs([c.storage for c in cores],
                                  await gen.seal_round(plan, r, cores))
            trace.reset()
            trace.enable_events()
            results = await service.run_cycle()
            counters = trace.snapshot()["counters"]
            compiles.append(counters.get("jax_compiles", 0))
            spilled = int(np.argmin(plan.rank))
            assert [res.path for res in results] == [
                "solo" if t == spilled else "batched" for t in range(plan.tenants)]
            assert all(res.sealed and res.error is None for res in results)
            want = expected_counters(plan, r, reached[r + 1])
            assert {k: counters.get(k, 0) for k in want} == want
            assert want["serve_buckets_folded"] >= 3 and want["serve_solo_spills"] == 1
            # the spilled tenant has no planes pack: its checkpoint comes from
            # its own dicts; the batched tenants' from the planes, which count
            # for none of the three producers
            packs = {k: v for k, v in counters.items() if k.startswith("checkpoint_pack_")}
            assert packs == {"checkpoint_pack_native": 1}
            folds = [e["meta"] for e in trace.events() if e["name"] == "serve.fold"]
            assert len(folds) == want["serve_buckets_folded"]
            classes |= {tuple(int(x) for x in m.split(":")[1].split("x")[2:]) for m in folds}
            solo = [e for e in trace.events() if e["name"] == "serve.solo"]
            assert [e["meta"] for e in solo] == [spilled]
            # every tenant against the reference, op by op
            rows = plan.live_rows(range(-1, r + 1))
            tenant = plan.actor[rows] // plan.devices
            for t, core in enumerate(cores):
                mine = reference.fold_rows(plan, rows[tenant == t]).canonical()
                assert reference.differing(gen.state_obj(core), mine) == 0, (r, t)
        service.close()
        trace.reset()
        # three batched member classes beside the spilled tenant's own
        assert len({e for e, _ in classes}) >= 3
        assert _bucket(int(reached[-1][spilled])) * 16 > CELLS_CAP
        # the head and the first round compile; the spilled tenant's vocabulary
        # grows in every round after (it is far from full), and compiles nothing
        assert reached[-1][spilled] > reached[-2][spilled] > reached[-3][spilled]
        assert compiles[0] > 0 and compiles[-1] == 0, compiles

    asyncio.run(scenario())
