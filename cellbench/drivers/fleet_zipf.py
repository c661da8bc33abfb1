"""One sync service on one chip over tenants of skewed sizes: ``drivers/fleet.py``
with a plan of its own.

The harness hands every driver the uniform ``gen.Plan`` of ``gen.plan_run``;
this one rebuilds the run with ``gen_zipf.plan_zipf`` (same seed, same rounds,
same round schedule, tenant sizes by the rank-size law) and uses that plan for
everything: sealing, publishing, the ops a call is credited with, the check.
The timed call and the two end-to-end metrics are ``fleet.py``'s.

Keys of the configuration beyond ``orset_fleet_1024``'s:

* ``members``: the vocabulary of the largest tenant; ``members_floor``: the
  smallest a tenant may have (``gen_zipf`` has the law);
* ``team_devices``, ``team_ranks``: the ``team_ranks`` largest tenants are
  written by ``team_devices`` devices instead of ``devices``;
* ``serve``: keyword arguments of ``ServeConfig``; ``{}`` is every default.  A
  test lays ``{"cells_cap": ...}`` over it to reach the spill at a toy size.

**Warm-up.**  A tenant's vocabulary grows with every round, so tenants move up
a size class (a power of two) while the window runs, and a bucket whose tenant
count crosses a power of two is a shape the mix's one warm-up round never
compiled; so is a bucket that cuts its delta on the device for the first time
(a tenant over ``rows_cap`` in the head folds alone there and has warm planes,
which the cut needs, only from round 0 on), and the growth of a tenant's warm
planes from one class to the next.  The plan says which: before the first
timed call ``open()`` takes every bucket shape, cut and growth of the later
rounds that round 0 lacks and folds it once over throw-away tenants of that
shape, served by a second ``FoldService`` in
the same process (the compiled programs are the process's).  The shapes are
worked out here from the plan by the planner's published law (sizes and slots
to powers of two, floors 8 and 1, a tenant past ``cells_cap`` or ``rows_cap``
alone), not asked of the program.  A tenant over ``rows_cap`` in the head
enters the warm tier a cycle late with the members its state still holds,
fewer than its ops named: for it the count here is an upper bound.

The fresh-replica sample is the ``LARGEST`` tenants by rank and seeded others
up to ``fleet.FRESH_SAMPLE``, so the spilled tenant and the largest batched
ones are reopened in every run.
"""

from __future__ import annotations

import asyncio
import sys
import time

import numpy as np

from cellbench import gen, gen_zipf, reference
from cellbench.drivers import fleet

LARGEST = 8  # tenants the fresh-replica sample always holds, by rank


def pow2(n: int, floor: int) -> int:
    b = floor
    while b < n:
        b *= 2
    return b


class Driver(fleet.Driver):
    def __init__(self, config: dict, plan: gen.Plan, workdir: str):
        super().__init__(config, gen_zipf.plan_zipf(config, plan), workdir)
        self.config = config
        self._classes: dict = {}  # round -> size_classes(round)

    # ------------------------------------------------------------ set-up

    async def open(self) -> None:
        from crdt_enc_tpu.parallel import TpuAccelerator
        from crdt_enc_tpu.serve import FoldService, ServeConfig

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
        self.serve_config = ServeConfig(**self.config["serve"])
        self.service = FoldService(self.cores, self.serve_config)
        await self.publish(-1)
        await self.service.run_cycle()  # takes in every tenant's initial ops
        t3 = time.perf_counter()
        rounds = range(plan.n_rounds)
        shapes = [self.bucket_shapes(r) for r in rounds]
        cuts = [self.bucket_shapes(r, cut=True) for r in rounds]
        growths = [self.growths(r) for r in rounds]
        later = sorted((set().union(*shapes[1:]) - shapes[0])
                       | (set().union(*cuts[1:]) - cuts[0]))
        grown = sorted(set().union(*growths[1:]) - growths[0])
        for shape in later:
            await self._fold_once(*shape)
        for e_from, e_to, r_b in grown:
            await self._fold_once(1, max(s[1] for s in shapes[0]), e_to, r_b, e_from)
        print(f"cellbench: set-up: opening the tenants {t1 - t0:.1f} s, sealing every "
              f"op file {t2 - t1:.1f} s, publishing and taking in the initial ops "
              f"{t3 - t2:.1f} s; folded once over throw-away tenants in "
              f"{time.perf_counter() - t3:.1f} s: later bucket shapes {later}, "
              f"later growths {grown}", file=sys.stderr)
        print("cellbench: bucket shapes (slots, rows, members, replicas) of round 0:",
              sorted(shapes[0]), file=sys.stderr)

    def size_classes(self, r: int) -> tuple:
        """Per tenant, the planner's classes of round ``r`` (``-1`` the head):
        rows, members, replicas; members 0 where the tenant has no rows or
        folds alone."""
        if not self._classes:
            plan, caps = self.plan, self.serve_config
            r_b = np.array([pow2(n, 8) for n in plan.writers.tolist()])
            for k, reached in enumerate(plan.reached().tolist(), start=-1):
                rows = plan.rows_per_tenant(k)
                e_b = np.array([pow2(n, 8) for n in reached])
                alone = (rows > caps.rows_cap) | (e_b * r_b > caps.cells_cap)
                self._classes[k] = (np.array([pow2(n, 8) for n in rows.tolist()]),
                                    np.where((rows == 0) | alone, 0, e_b), r_b)
        return self._classes[r]

    def bucket_shapes(self, r: int, cut: bool = False) -> set:
        """The ``(slots, rows, members, replicas)`` of every bucket the
        service's planner makes of round ``r``; with ``cut``, of those that
        cut a delta on the device: the buckets with a tenant whose warm planes
        the round before left (a tenant that folded alone then has none)."""
        cap = self.serve_config.tenants_cap
        rows_b, e_b, r_b = self.size_classes(r)
        warm = self.size_classes(r - 1)[1] > 0 if cut and r >= 0 else e_b > 0
        shapes = set()
        for key in set(zip(*(x[e_b > 0].tolist() for x in (rows_b, e_b, r_b)))):
            mine = np.flatnonzero((rows_b == key[0]) & (e_b == key[1]) & (r_b == key[2]))
            for lo in range(0, len(mine), cap):
                if warm[mine[lo:lo + cap]].any():
                    shapes.add((pow2(len(mine[lo:lo + cap]), 1), *key))
        return shapes

    def growths(self, r: int) -> set:
        """The ``(members before, members after, replicas)`` classes of every
        tenant whose warm planes round ``r`` has to grow."""
        _, before, _ = self.size_classes(r - 1)
        _, after, r_b = self.size_classes(r)
        moved = (before > 0) & (after > before)
        return set(zip(before[moved].tolist(), after[moved].tolist(),
                       r_b[moved].tolist()))

    async def _fold_once(self, slots: int, rows_b: int, e_b: int, r_b: int,
                         e_from: int = 0) -> None:
        """Every program of one bucket shape, compiled by use: throw-away
        tenants (more than half the slots) whose head names more than half of
        ``e_b`` members, then one round of more than half of ``rows_b`` adds
        over those members: with the head's warm entry it is the shape's
        fold and its device-cut.  With ``e_from``, the head names exactly that
        many members and the round enough new ones to reach ``e_b``'s class:
        the warm planes' growth from the one class to the other."""
        from crdt_enc_tpu.parallel import TpuAccelerator
        from crdt_enc_tpu.serve import FoldService

        opf = self.config["ops_per_file"]
        n = slots // 2 + 1
        writers = r_b if r_b > 8 else min(self.config["devices"], 8)
        if not e_from and e_b // 2 + 1 > self.serve_config.rows_cap >= e_b // 2:
            # one row more would send the head to the solo path (and its own
            # programs): name half the class there, and let the round grow it
            e_from = e_b // 2
        head = e_from or e_b // 2 + 1
        vocab = max(head, e_b // 2 + 1)
        rows = max(rows_b // 2 + 1 if rows_b > 8 else 1, vocab - head)
        ids = gen.actor_table(writers)
        cores = []
        for first in range(0, n, fleet.OPEN_WIDTH):
            cores += await asyncio.gather(*(
                self._replica(f"once{slots}x{rows_b}x{e_b}x{r_b}x{e_from}-{t}",
                              "served", TpuAccelerator())
                for t in range(first, min(first + fleet.OPEN_WIDTH, n))
            ))
        service = FoldService(cores, self.serve_config)
        dots = [0] * writers
        versions = [0] * writers

        async def cycle(sizes: list, start: int, modulo: int) -> None:
            """One file a device of ``sizes`` adds each (members from ``start``
            on, wrapping at ``modulo``), the same for every tenant, then one
            cycle."""
            blobs, m = [], start
            for d, size in enumerate(sizes):
                ops = [[0, (m + i) % modulo, [ids[d], dots[d] + i + 1]]
                       for i in range(size)]
                m, dots[d], versions[d] = m + size, dots[d] + size, versions[d] + 1
                for t, core in enumerate(cores):
                    blobs.append((t, ids[d], versions[d], await core._seal(ops)))
            await gen.store_blobs([c.storage for c in cores], blobs)
            await service.run_cycle()

        try:
            # every device has written, so the replicas class is r_b's
            await cycle([-(-head // writers)] * writers, 0, head)
            per_file = [min(opf, rows - lo) for lo in range(0, rows, opf)]
            if len(per_file) > writers:
                per_file = [-(-rows // writers)] * writers
            # a head over rows_cap folds alone and leaves no warm entry: then
            # the first round makes it and the second is the cut
            for _ in range(2 if head > self.serve_config.rows_cap else 1):
                await cycle(per_file, head % vocab, vocab)
        finally:
            service.close()

    # ------------------------------------------------------- the window

    def warm_object(self):
        """The largest state a seal worker packs: the rank-1 tenant's."""
        return gen.state_obj(self.cores[int(np.argmin(self.plan.rank))])

    async def close(self) -> None:
        warm = getattr(getattr(self, "service", None), "warm", None)
        if warm is not None:  # what the device holds for the tenants at the end
            print(f"cellbench: warm tier: {warm.bytes_held} plane bytes in "
                  f"{len(warm)} entries", file=sys.stderr)
        await super().close()

    # --------------------------------------------------------- the check

    def fresh_sample(self) -> list:
        plan = self.plan
        largest = np.argsort(plan.rank)[:LARGEST]
        others = np.setdiff1d(np.arange(plan.tenants), largest)
        rng = np.random.default_rng([plan.seed, 1])
        more = rng.choice(others, min(max(fleet.FRESH_SAMPLE - len(largest), 0),
                                      len(others)), replace=False)
        return largest.tolist() + more.tolist()

    async def check(self) -> list:
        """``fleet.py``'s three exact comparisons, the fresh replicas drawn
        by ``fresh_sample``."""
        from crdt_enc_tpu.core.adapters import HostAccelerator
        from crdt_enc_tpu.models import canonical_bytes

        plan = self.plan
        rows = plan.live_rows(self.published)
        tenant = plan.actor[rows] // plan.devices
        order = np.argsort(tenant, kind="stable")
        cuts = np.searchsorted(tenant[order], np.arange(plan.tenants + 1))
        want = [
            reference.fold_rows(plan, rows[order[cuts[t]:cuts[t + 1]]]).canonical()
            for t in range(plan.tenants)
        ]
        served_off = sum(
            1 for t, core in enumerate(self.cores)
            if reference.differing(gen.state_obj(core), want[t])
        )
        fresh_off = bytes_off = 0
        for t in self.fresh_sample():
            fresh = await self._replica(t, "fresh", HostAccelerator())
            await fresh.read_remote()
            fresh_off += bool(reference.differing(gen.state_obj(fresh), want[t]))
            bytes_off += (fresh.with_state(canonical_bytes)
                          != self.cores[t].with_state(canonical_bytes))
        return [
            ("tenants_vs_reference", served_off, 0),
            ("fresh_replicas_vs_reference", int(fresh_off), 0),
            ("fresh_replica_bytes_vs_served", int(bytes_off), 0),
        ]
