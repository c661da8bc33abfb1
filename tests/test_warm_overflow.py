"""The fold service over a fleet whose warm planes outgrow the warm tier
(ISSUE 53): what eviction may cost, and what it may never change.

One seeded fleet of 24 tenants is served twice over the same cycles, by one
service whose tier holds every tenant's planes and by one whose tier holds a
quarter of them (and, as further cases, a sixteenth, and a single entry's
worth).  After every cycle every tenant's canonical bytes are equal in the two
arms and equal the plain reference applied op by op (``cellbench/reference.py``,
which imports nothing of the program); the snapshots the two arms sealed hold
the same state and cursor bytes; the small tier stays inside its budget, evicts, rebuilds and
seals some tenants without a delta link, and the large one does none of that;
a consumer that follows the links and falls back to the snapshot where a cycle
published none reaches the same bytes; and the new counters add up.
"""

import asyncio

import numpy as np
import pytest

from cellbench import gen, gen_hotset, reference
from crdt_enc_tpu.backends import IdentityCryptor, MemoryRemote, MemoryStorage, PlainKeyCryptor
from crdt_enc_tpu.core import Core, OpenOptions, orset_adapter
from crdt_enc_tpu.core.adapters import HostAccelerator
from crdt_enc_tpu.models import canonical_bytes
from crdt_enc_tpu.parallel import TpuAccelerator
from crdt_enc_tpu.serve import FoldService, ServeConfig
from crdt_enc_tpu.serve.warm import PlaneWarmTier
from crdt_enc_tpu.utils import codec, trace
from crdt_enc_tpu.utils.versions import DEFAULT_DATA_VERSION_1

CONFIG = {
    "tenants": 24, "devices": 4, "team_devices": 4, "team_ranks": 0,
    "members": 256, "members_floor": 128, "ops_per_file": 24,
    "remove_fraction": 0.1, "initial_files_per_device": 1,
}
MIX = {"active_tenants": 6, "active_devices": 2, "files_per_device": 1,
       "warmup_rounds": 0, "max_ops_per_s": 1000,
       "popularity": {"law": "zipfian", "constant": 0.99}, "drift_ranks_per_cycle": 2}
ROUNDS = 14
COUNTED = ("serve_warm_evictions", "serve_warm_evicted_bytes", "serve_warm_rebuilds",
           "serve_warm_rebuild_bytes", "serve_warm_hits", "serve_warm_misses",
           "delta_cut_fallbacks", "delta_device_cuts", "delta_seal_skipped",
           "delta_files_sealed")


def open_core(remote, accel):
    return Core.open(OpenOptions(
        storage=MemoryStorage(remote),
        cryptor=IdentityCryptor(),
        key_cryptor=PlainKeyCryptor(),
        adapter=orset_adapter(),
        supported_data_versions=(DEFAULT_DATA_VERSION_1,),
        current_data_version=DEFAULT_DATA_VERSION_1,
        create=True,
        accelerator=accel,
    ))


class Arm:
    """One service over its own copies of the fleet's remotes, and a consumer
    of every tenant that follows the delta links."""

    async def open(self, plan, warm_bytes: int) -> "Arm":
        self.remotes = [MemoryRemote() for _ in range(plan.tenants)]
        self.cores = [await open_core(r, TpuAccelerator()) for r in self.remotes]
        self.consumers = [await open_core(r, HostAccelerator()) for r in self.remotes]
        self.service = FoldService(self.cores, ServeConfig(warm_bytes=warm_bytes))
        self.totals = {k: 0 for k in COUNTED}
        self.dropped = 0  # summed nbytes of the entries the tier dropped
        return self

    async def cycle(self, plan, r: int) -> list:
        await gen.store_blobs([c.storage for c in self.cores],
                              await gen.seal_round(plan, r, self.cores))
        with trace.counter_tap() as tap:
            results = await self.service.run_cycle()
        assert all(res.error is None for res in results)
        for k in COUNTED:
            self.totals[k] += tap.get(k, 0)
        return results

    async def snapshot(self, t: int) -> bytes:
        """The one snapshot tenant ``t``'s remote holds, opened: its state
        and its cursor as canonical bytes (the third field names the sealer,
        which is each arm's own replica)."""
        storage = self.cores[t].storage
        (name,) = await storage.list_state_names()
        ((_, raw),) = await storage.load_states([name])
        state, cursor, sealer = await self.cores[t]._open_sealed(raw)
        assert sealer == self.cores[t].actor_id
        return codec.pack([state, cursor])

    async def consume(self, t: int) -> dict:
        with trace.counter_tap() as tap:
            await self.consumers[t].read_remote()
        return tap


def spy_on_the_tier(monkeypatch, arms: dict) -> None:
    """Sum, per arm, the ``nbytes`` of every entry its tier drops for the
    budget's sake: what ``serve_warm_evicted_bytes`` is held against."""
    by_tier = {id(arm.service.warm): arm for arm in arms.values()}
    store = PlaneWarmTier.store

    def counting_store(self, state, *a, **kw):
        before = dict(self._entries)
        entry = store(self, state, *a, **kw)
        by_tier[id(self)].dropped += sum(
            e.nbytes for k, e in before.items()
            if k not in self._entries and k != id(state))
        return entry

    monkeypatch.setattr(PlaneWarmTier, "store", counting_store)


async def served_twice(plan, budgets: dict, monkeypatch) -> dict:
    arms = {name: await Arm().open(plan, b) for name, b in budgets.items()}
    spy_on_the_tier(monkeypatch, arms)
    fallbacks = {name: 0 for name in arms}
    linked = {name: 0 for name in arms}
    for r in range(-1, plan.n_rounds):
        rows = plan.live_rows(range(-1, r + 1))
        tenant = plan.actor[rows] // plan.devices
        want = [reference.fold_rows(plan, rows[tenant == t]).canonical()
                for t in range(plan.tenants)]
        results = {}
        for name, arm in arms.items():
            results[name] = await arm.cycle(plan, r)
            warm = arm.service.warm
            # the tier's law: inside the budget, or down to the one entry it
            # was just handed (a sixteenth is less than the largest tenant)
            assert warm.bytes_held <= budgets[name] or len(warm) == 1, (name, r)
        active = [t for t, res in enumerate(results["large"]) if res.sealed]
        assert active == [t for t, res in enumerate(results["small"]) if res.sealed]
        assert len(active) == (plan.tenants if r < 0 else MIX["active_tenants"])
        for t in range(plan.tenants):
            a, b = arms["large"].cores[t], arms["small"].cores[t]
            assert a.with_state(canonical_bytes) == b.with_state(canonical_bytes), (r, t)
            assert reference.differing(gen.state_obj(b), want[t]) == 0, (r, t)
        for t in active:
            # what the two arms sealed for the tenant is the same snapshot
            assert await arms["large"].snapshot(t) == await arms["small"].snapshot(t), (r, t)
            for name, arm in arms.items():
                tap = await arm.consume(t)
                assert arm.consumers[t].with_state(canonical_bytes) == \
                    arm.cores[t].with_state(canonical_bytes), (name, r, t)
                # one way or the other: the link, or the snapshot it fell back to
                assert (tap.get("delta_applied", 0), tap.get("states_merged", 0)) \
                    in ((1, 0), (0, 1)), (name, r, t, tap)
                linked[name] += tap.get("delta_applied", 0)
                fallbacks[name] += r >= 0 and tap.get("states_merged", 0)
    for arm in arms.values():
        arm.service.close()
    return {"arms": arms, "fallbacks": fallbacks, "linked": linked}


@pytest.mark.parametrize("share, least_entries", [
    (4, 4), (16, 1), (0, 1),
], ids=["a_quarter", "a_sixteenth", "one_entry"])
def test_a_tier_that_evicts_changes_no_byte_any_tenant_publishes(
        share, least_entries, monkeypatch):
    uniform = gen.plan_run(CONFIG, MIX, 53, ROUNDS)
    plan = gen_hotset.plan_hotset(CONFIG, uniform)
    # a tenant's entry by the planner's law: two planes and the clock
    e_b = np.maximum(8, 2 ** np.ceil(np.log2(plan.reached()[-1])).astype(np.int64))
    entry = 8 * e_b * 8 + 4 * 8
    working_set = int(entry.sum())
    small = working_set // share if share else int(entry.max())
    budgets = {"large": 2 * working_set, "small": small}
    trace.reset()
    out = asyncio.run(served_twice(plan, budgets, monkeypatch))
    large, small_arm = out["arms"]["large"], out["arms"]["small"]
    # the tier that holds everything: no eviction, one rebuild a tenant (its
    # first fold), every later seal a link, never a fallback
    assert large.totals["serve_warm_evictions"] == 0
    assert large.totals["serve_warm_evicted_bytes"] == 0 == large.dropped
    assert large.totals["serve_warm_rebuilds"] == plan.tenants
    assert large.totals["delta_cut_fallbacks"] == 0 == out["fallbacks"]["large"]
    assert large.totals["serve_warm_misses"] == plan.tenants
    assert len(large.service.warm) == plan.tenants
    # the tier that does not
    t = small_arm.totals
    assert t["serve_warm_evictions"] > plan.tenants - len(small_arm.service.warm) - 1
    assert t["serve_warm_rebuilds"] == t["serve_warm_misses"] > plan.tenants
    assert t["delta_cut_fallbacks"] > 0 < t["delta_device_cuts"]
    assert len(small_arm.service.warm) >= least_entries
    # a consumer fell back to the snapshot exactly where a seal published no link
    assert out["fallbacks"]["small"] == t["delta_seal_skipped"] == t["delta_cut_fallbacks"]
    assert out["linked"]["small"] == t["delta_files_sealed"]
    assert out["linked"]["large"] == large.totals["delta_files_sealed"] \
        == ROUNDS * MIX["active_tenants"]
    # the evicted bytes are the dropped entries' (the rebuilt bytes: below)
    assert t["serve_warm_evicted_bytes"] == small_arm.dropped > 0
    assert t["serve_warm_rebuild_bytes"] > large.totals["serve_warm_rebuild_bytes"] > 0
    trace.reset()


def test_rebuilt_bytes_are_the_rows_the_fold_built(monkeypatch):
    """``serve_warm_rebuild_bytes`` against the rows themselves: every array
    ``orset_state_to_planes`` hands a bucket, as padded for the stack."""
    import crdt_enc_tpu.ops as K

    uniform = gen.plan_run(CONFIG, MIX, 2**31 + 53, 3)
    plan = gen_hotset.plan_hotset(CONFIG, uniform)
    built = []
    planes = K.orset_state_to_planes

    def spying(state, members, replicas, **kw):
        out = planes(state, members, replicas, **kw)
        built.append((len(members), len(replicas)))
        return out

    monkeypatch.setattr(K, "orset_state_to_planes", spying)

    async def scenario():
        arm = await Arm().open(plan, 40_000)
        for r in range(-1, plan.n_rounds):
            await arm.cycle(plan, r)
        arm.service.close()
        return arm

    trace.reset()
    arm = asyncio.run(scenario())
    assert arm.totals["serve_warm_rebuilds"] == len(built) > plan.tenants
    padded = lambda n: max(8, 1 << (n - 1).bit_length())  # noqa: E731
    assert arm.totals["serve_warm_rebuild_bytes"] == sum(
        8 * padded(e) * padded(r) + 4 * padded(r) for e, r in built)
    trace.reset()
