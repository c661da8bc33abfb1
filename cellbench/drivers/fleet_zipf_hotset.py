"""One sync service on one chip over a fleet whose warm planes outgrow the warm
tier: ``drivers/fleet_zipf.py`` with a schedule of its own and a tier that
evicts.

The plan is ``gen_hotset.plan_hotset``'s: ``gen_zipf``'s sizes and head, the
rounds' writers by Zipfian popularity with a drifting hot set (the mix's
``popularity`` and ``drift_ranks_per_cycle``).  The timed call, the two
end-to-end metrics, the warm-up of bucket shapes over throw-away tenants and
the three exact comparisons are ``fleet_zipf``'s and ``fleet``'s.  What is new:

**The refusal.**  The cell is there to measure a tier that evicts.  Before
anything is opened the driver sums the fleet's warm entries by the planner's
published law (``8 * E_b * R_b`` plane bytes and the clock a tenant, ``E_b``
and ``R_b`` the power-of-two classes of the members its ops name and of its
devices) and holds them against the configuration's ``serve.warm_bytes``:
under ``OVERFLOW`` times the budget it says so in one line and the run ends
with status 2 and nothing on standard output, since on such a configuration
the cell would measure a cache that fits.

**The head in blocks.**  One cycle over all the tenants would stack every
tenant's head at once, a device peak no timed cycle comes near; the head is
taken in by untimed ``run_cycle(tenants=...)`` over ``HEAD_BLOCK`` tenants at a
time (the daemon's subset cycles), so ``memory_peak_bytes`` is the window's:
the tier, one bucket's stacks in and out.  The tier fills and evicts during
the head already.

**Compile classes.**  Which buckets cut a delta on the device depends on what
the tier still holds, so every bucket shape any round can meet is folded once
with its cut over throw-away tenants (``fleet_zipf._fold_once``: the fold's
programs are the uncut variant's too), as is every growth of a warm entry from
one class to the next.

**A model of the tier** (``TierModel``): LRU by bytes, looked up in tenant
order and stored bucket by bucket as ``FoldService`` does, replayed from the
plan alone.  It names the tenants that were evicted and then rebuilt inside
the window, of which the fresh-replica sample takes ``REBUILT`` on top of
``fleet_zipf``'s 32, and it is held against the program's own counters call by
call: ``close()`` prints how many calls disagreed.

**The check** adds ``warm_bytes_over_budget``: timed calls after which
``service.warm.bytes_held`` exceeded ``serve.warm_bytes``, limit 0.
"""

from __future__ import annotations

import sys
import time
from collections import OrderedDict

import numpy as np

from cellbench import gen, gen_hotset
from cellbench.drivers import fleet, fleet_zipf

OVERFLOW = 1.5  # plane bytes over warm_bytes under which the cell is refused
HEAD_BLOCK = 128  # tenants whose heads one untimed cycle takes in
REBUILT = 8  # evicted-and-rebuilt tenants added to the fresh-replica sample
# what the window's counters are printed from, in this order
COUNTED = ("serve_warm_hits", "serve_warm_misses", "serve_warm_evictions",
           "serve_warm_rebuilds", "delta_cut_fallbacks", "delta_device_cuts")


def classes(n: np.ndarray) -> np.ndarray:
    """The planner's quantizer (``fleet_zipf.pow2``, floor 8) over an array."""
    return np.array([fleet_zipf.pow2(x, 8) for x in n.ravel().tolist()],
                    np.int64).reshape(n.shape)


class TierModel:
    """``PlaneWarmTier`` replayed from the plan: LRU by bytes, a cycle's
    tenants looked up in tenant order (a hit moves to the young end), then
    stored bucket by bucket; a store evicts from the old end past the budget,
    never the entry it brings."""

    def __init__(self, budget: int):
        self.budget = budget
        self.entries: OrderedDict = OrderedDict()  # tenant -> bytes, oldest first
        self.held = 0

    def cycle(self, tenants, nbytes, order) -> dict:
        """One cycle over ``tenants`` (ascending), whose entries will hold
        ``nbytes[t]`` and are stored in the order of ``order[t]`` (the
        bucket's class, then the tenant)."""
        hits = [t for t in tenants if t in self.entries]
        for t in hits:
            self.entries.move_to_end(t)
        evicted = []
        for t in sorted(tenants, key=lambda t: (order[t], t)):
            self.held -= self.entries.pop(t, 0)
            self.entries[t] = nbytes[t]
            self.held += nbytes[t]
            while self.held > self.budget and len(self.entries) > 1:
                oldest = next(iter(self.entries))
                if oldest == t:
                    break
                self.held -= self.entries.pop(oldest)
                evicted.append(oldest)
        return {"hits": hits, "misses": [t for t in tenants if t not in hits],
                "evicted": evicted}


class Driver(fleet_zipf.Driver):
    def __init__(self, config: dict, plan: gen.Plan, workdir: str):
        from crdt_enc_tpu.serve import ServeConfig

        fleet.Driver.__init__(self, config, gen_hotset.plan_hotset(config, plan), workdir)
        self.config = config
        self._classes: dict = {}
        self.serve_config = ServeConfig(**config["serve"])
        self.budget = self.serve_config.warm_bytes
        self.entry_bytes = self._entry_bytes()  # [round + 1, tenant]
        self._refuse_unless_overflowing()
        self.model = TierModel(self.budget)
        self.rebuilt: set = set()  # evicted, then missed inside the window
        self.window = {"calls": 0, "over_budget": 0, "quiet": 0, "model_off": 0,
                       **{k: 0 for k in COUNTED}}

    def _entry_bytes(self) -> np.ndarray:
        """Per round and tenant, the bytes of the tenant's warm entry once it
        has folded that round: two planes of ``E_b x R_b`` int32 and the
        clock; 0 for a tenant past ``cells_cap``, which folds alone and has
        none."""
        e_b = classes(self.plan.reached())
        r_b = classes(self.plan.writers)[None, :]
        return np.where(e_b * r_b > self.serve_config.cells_cap, 0,
                        8 * e_b * r_b + 4 * r_b)

    def _refuse_unless_overflowing(self) -> None:
        planes = int(self.entry_bytes[-1].sum())
        if planes >= OVERFLOW * self.budget:
            return
        print(f"cellbench: this configuration's warm planes are {planes} bytes by the "
              f"planner's law, {planes / self.budget:.2f} times its warm_bytes "
              f"({self.budget}): under {OVERFLOW} times the tier never has to evict, "
              "and the cell would measure a cache that fits; it does not run on it",
              file=sys.stderr, flush=True)
        raise SystemExit(2)

    # ------------------------------------------------------------ set-up

    async def open(self) -> None:
        import asyncio

        from crdt_enc_tpu.parallel import TpuAccelerator
        from crdt_enc_tpu.serve import FoldService

        plan = self.plan
        t0 = time.perf_counter()
        self.cores = []
        for first in range(0, plan.tenants, fleet.OPEN_WIDTH):
            self.cores += await asyncio.gather(*(
                self._replica(t, "served", TpuAccelerator())
                for t in range(first, min(first + fleet.OPEN_WIDTH, plan.tenants))
            ))
        t1 = time.perf_counter()
        self.storages = [c.storage for c in self.cores]
        self.batches = {
            r: await gen.seal_round(plan, r, self.cores)
            for r in range(-1, plan.n_rounds)
        }
        t2 = time.perf_counter()
        self.service = FoldService(self.cores, self.serve_config)
        await self.publish(-1)
        for lo in range(0, plan.tenants, HEAD_BLOCK):
            block = range(lo, min(lo + HEAD_BLOCK, plan.tenants))
            results = await self.service.run_cycle([self.cores[t] for t in block])
            off = [t for t, res in zip(block, results) if res.error or not res.sealed]
            if off:
                raise RuntimeError(f"the head of tenants {off[:8]} was not taken in")
            self._model_cycle(list(block), -1)
        t3 = time.perf_counter()
        rounds = range(plan.n_rounds)
        shapes = sorted(set().union(*(self.bucket_shapes(r) for r in rounds)))
        grown = sorted(set().union(*(self.growths(r) for r in rounds)))
        for shape in shapes:
            await self._fold_once(*shape)
        for e_from, e_to, r_b in grown:
            await self._fold_once(1, max(s[1] for s in shapes), e_to, r_b, e_from)
        print(f"cellbench: set-up: opening the tenants {t1 - t0:.1f} s, sealing every "
              f"op file {t2 - t1:.1f} s, publishing and taking in the initial ops in "
              f"blocks of {HEAD_BLOCK} tenants {t3 - t2:.1f} s; folded once over "
              f"throw-away tenants in {time.perf_counter() - t3:.1f} s: bucket shapes "
              f"(slots, rows, members, replicas) {shapes}, growths {grown}",
              file=sys.stderr)
        planes = int(self.entry_bytes[-1].sum())
        print(f"cellbench: warm planes by the planner's law: {planes} bytes, "
              f"{planes / self.budget:.2f} times warm_bytes ({self.budget}); after the "
              f"head the tier holds {self.service.warm.bytes_held} bytes in "
              f"{len(self.service.warm)} entries (the model: {self.model.held} in "
              f"{len(self.model.entries)})", file=sys.stderr)

    def _model_cycle(self, tenants: list, r: int) -> dict:
        """The model's account of a cycle over ``tenants`` in round ``r``."""
        rows_b, e_b, r_b = self.size_classes(r)
        nbytes = self.entry_bytes[r + 1]
        batched = [t for t in tenants if nbytes[t]]
        order = {t: (int(rows_b[t]), int(e_b[t]), int(r_b[t])) for t in batched}
        return self.model.cycle(batched, nbytes, order)

    # ------------------------------------------------------- the window

    async def call(self, r: int) -> dict:
        from crdt_enc_tpu.utils import trace

        said = self._model_cycle(self.plan.tenants_of_round(r).tolist(), r)
        with trace.counter_tap() as tap:
            outcome = await super().call(r)
        if r < self.plan.traffic["warmup_rounds"]:
            return outcome
        w = self.window
        w["calls"] += 1
        w["over_budget"] += self.service.warm.bytes_held > self.budget
        w["quiet"] += not tap.get("serve_warm_evictions")
        for k in COUNTED:
            w[k] += tap.get(k, 0)
        w["model_off"] += (
            (len(said["hits"]), len(said["misses"]), len(said["evicted"]))
            != tuple(tap.get(k, 0) for k in COUNTED[:3]))
        self.rebuilt.update(said["misses"])  # every tenant entered in the head
        return outcome

    async def close(self) -> None:
        w = getattr(self, "window", None)
        if w is not None and getattr(self, "service", None) is not None:
            import jax

            stats = jax.devices()[0].memory_stats() or {}
            print("cellbench: the window's " + ", ".join(
                f"{k} {w[k]}" for k in COUNTED)
                + f" in {w['calls']} timed calls, {w['quiet']} of them without an "
                f"eviction, {w['over_budget']} ending over the budget; the model "
                f"of the tier disagreed with the counters in {w['model_off']}; "
                f"device bytes in use at close {stats.get('bytes_in_use')}, peak "
                f"{stats.get('peak_bytes_in_use')}", file=sys.stderr)
        await super().close()

    # --------------------------------------------------------- the check

    def fresh_sample(self) -> list:
        """``fleet_zipf``'s sample and ``REBUILT`` seeded tenants more of
        those the window evicted and rebuilt."""
        sample = super().fresh_sample()
        more = np.array(sorted(self.rebuilt - set(sample)), np.int64)
        rng = np.random.default_rng([self.plan.seed, 2])
        return sample + rng.choice(more, min(REBUILT, len(more)), replace=False).tolist()

    async def check(self) -> list:
        return await super().check() + [
            ("warm_bytes_over_budget", int(self.window["over_budget"]), 0)]
