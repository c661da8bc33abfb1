"""One sync service on one chip: the timed call is ``FoldService.run_cycle()``.

Every tenant is a ``Core`` with its own key, its own remote and ``FsStorage``
(what ``tools/daemon.py`` deploys), all served by one ``FoldService`` with its
default configuration.  Each tenant's op files are sealed with that tenant's
key by its own ``Core``: a second, writing replica per tenant would double the
set-up and seal the same bytes.
"""

from __future__ import annotations

import asyncio
import os
import sys
import time

import numpy as np

from cellbench import gen, reference, stats

FRESH_SAMPLE = 32  # tenants whose compacted remote a fresh replica reopens
OPEN_WIDTH = 32  # tenants opened at a time during set-up


class Driver:
    def __init__(self, config: dict, plan: gen.Plan, workdir: str):
        self.plan = plan
        self.workdir = workdir
        self.published: list = []  # rounds whose files the reference counts

    def _replica(self, tenant: int, name: str, accel):
        from crdt_enc_tpu.backends import FsStorage
        from crdt_enc_tpu.core import Core

        base = os.path.join(self.workdir, f"t{tenant}")
        storage = FsStorage(os.path.join(base, name), os.path.join(base, "remote"))
        return Core.open(gen.core_opts(storage, accel))

    async def open(self) -> None:
        from crdt_enc_tpu.parallel import TpuAccelerator
        from crdt_enc_tpu.serve import FoldService

        t0 = time.perf_counter()
        self.cores = []
        for first in range(0, self.plan.tenants, OPEN_WIDTH):
            self.cores += await asyncio.gather(*(
                self._replica(t, "served", TpuAccelerator())
                for t in range(first, min(first + OPEN_WIDTH, self.plan.tenants))
            ))
        t1 = time.perf_counter()
        self.storages = [c.storage for c in self.cores]
        self.batches = {
            r: await gen.seal_round(self.plan, r, self.cores)
            for r in range(-1, self.plan.n_rounds)
        }
        t2 = time.perf_counter()
        self.service = FoldService(self.cores)
        await self.publish(-1)
        await self.service.run_cycle()  # takes in every tenant's initial ops
        print(f"cellbench: set-up: opening the tenants {t1 - t0:.1f} s, sealing every "
              f"op file {t2 - t1:.1f} s, publishing and taking in the initial ops "
              f"{time.perf_counter() - t2:.1f} s", file=sys.stderr)

    async def publish(self, r: int, withhold: bool = False) -> None:
        """Round ``r``'s op files land in their tenants' remotes.  ``withhold``
        is the control: the last of them never arrives, though the reference
        counts it."""
        blobs = self.batches.pop(r)
        self.published.append(r)
        await gen.store_blobs(self.storages, blobs[:-1] if withhold else blobs)

    def warm_object(self):
        """What a tenant's seal tail packs off the loop: its state."""
        return gen.state_obj(self.cores[0])

    async def call(self, r: int) -> dict:
        plan = self.plan
        files = plan.files_of_round(r)
        tenants = plan.f_actor[files.start:files.stop] // plan.devices
        active = np.unique(tenants)
        try:
            results = await self.service.run_cycle()
        except Exception as e:  # a raised cycle fails every tenant that had files
            print(f"cellbench: cycle {r} raised {e!r}")
            return {"ops": 0, "attempted": len(active), "failed": len(active),
                    "latencies": []}
        sealed = {t for t in active.tolist()
                  if results[t].sealed and results[t].error is None}
        per_file = plan.live[plan.rows_of_round(r)].reshape(-1, plan.opf).sum(axis=1)
        return {
            "ops": int(per_file[np.isin(tenants, list(sealed))].sum()),
            "attempted": len(active),
            "failed": sum(1 for res in results if res.error is not None),
            "latencies": [res.latency_s for res in results if res.sealed],
        }

    def end_to_end(self, calls: list) -> dict:
        latencies = [s for c in calls for s in c["latencies"]]
        out = {"serve_ops_per_s":
               sum(c["ops"] for c in calls) / sum(c["wall"] for c in calls)}
        if latencies:
            out["seal_p95_ms"] = 1e3 * stats.p95(latencies)
        return out

    async def check(self) -> list:
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
        rng = np.random.default_rng([plan.seed, 1])
        sample = rng.choice(plan.tenants, min(FRESH_SAMPLE, plan.tenants), replace=False)
        fresh_off = bytes_off = 0
        for t in sample.tolist():
            fresh = await self._replica(t, "fresh", HostAccelerator())
            await fresh.read_remote()
            fresh_off += bool(reference.differing(
                gen.state_obj(fresh), want[t]))
            bytes_off += (fresh.with_state(canonical_bytes)
                          != self.cores[t].with_state(canonical_bytes))
        return [
            ("tenants_vs_reference", served_off, 0),
            ("fresh_replicas_vs_reference", int(fresh_off), 0),
            ("fresh_replica_bytes_vs_served", int(bytes_off), 0),
        ]

    async def close(self) -> None:
        if getattr(self, "service", None) is not None:
            self.service.close()
