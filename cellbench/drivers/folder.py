"""One sync folder, one compactor: the timed call is ``Core.compact()``.

A host-engine writer ``Core`` stands for the other devices: it creates the
remote, seals every op file of the run with the folder's key, and publishes
the head and then each round's batch through ``FsStorage``.  The compactor is
a second replica on the same remote with the default ``TpuAccelerator()``.
"""

from __future__ import annotations

import os
import sys
import time

from cellbench import gen, reference, stats


class Driver:
    def __init__(self, config: dict, plan: gen.Plan, workdir: str):
        self.plan = plan
        self.workdir = workdir
        self.remote = os.path.join(workdir, "remote")
        self.published: list = []  # rounds whose files the reference counts

    def _replica(self, name: str, accel):
        from crdt_enc_tpu.backends import FsStorage
        from crdt_enc_tpu.core import Core

        local = os.path.join(self.workdir, name)
        return Core.open(gen.core_opts(FsStorage(local, self.remote), accel))

    async def open(self) -> None:
        from crdt_enc_tpu.core.adapters import HostAccelerator
        from crdt_enc_tpu.parallel import TpuAccelerator

        t0 = time.perf_counter()
        self.writer = await self._replica("writer", HostAccelerator())
        self.batches = {
            r: await gen.seal_round(self.plan, r, [self.writer])
            for r in range(-1, self.plan.n_rounds)
        }
        t1 = time.perf_counter()
        await self.publish(-1)
        self.compactor = await self._replica("compactor", TpuAccelerator())
        await self.compactor.compact()  # takes in the initial ops and seals them
        print(f"cellbench: set-up: sealing every op file {t1 - t0:.1f} s, publishing "
              f"and taking in the initial ops {time.perf_counter() - t1:.1f} s",
              file=sys.stderr)

    async def publish(self, r: int, withhold: bool = False) -> None:
        """Round ``r``'s op files land in the remote.  ``withhold`` is the
        control: the last of them never arrives, though the reference counts
        it."""
        blobs = self.batches.pop(r)
        self.published.append(r)
        await gen.store_blobs([self.writer.storage], blobs[:-1] if withhold else blobs)

    def warm_object(self):
        """What the seal tail packs off the loop: the whole state."""
        return gen.state_obj(self.compactor)

    async def call(self, r: int) -> dict:
        failed = 0
        try:
            await self.compactor.compact()
        except Exception as e:  # a raised call is a failed round, not a crash
            print(f"cellbench: round {r} raised {e!r}")
            failed = 1
        return {"ops": int(self.plan.live[self.plan.rows_of_round(r)].sum()),
                "attempted": 1, "failed": failed}

    def end_to_end(self, calls: list) -> dict:
        walls = [c["wall"] for c in calls]
        done = [c for c in calls if not c["failed"]]
        return {
            "compact_ops_per_s": sum(c["ops"] for c in done) / sum(walls),
            "compact_ms": 1e3 * stats.median(walls),
        }

    async def check(self) -> list:
        from crdt_enc_tpu.core.adapters import HostAccelerator
        from crdt_enc_tpu.models import canonical_bytes

        rows = self.plan.live_rows(self.published)
        want = reference.fold_rows(self.plan, rows).canonical()
        got = gen.state_obj(self.compactor)
        fresh = await self._replica("fresh", HostAccelerator())
        await fresh.read_remote()
        return [
            ("compactor_vs_reference", reference.differing(got, want), 0),
            ("fresh_replica_vs_reference",
             reference.differing(gen.state_obj(fresh), want), 0),
            ("fresh_replica_bytes_vs_compactor",
             int(fresh.with_state(canonical_bytes)
                 != self.compactor.with_state(canonical_bytes)), 0),
        ]

    async def close(self) -> None:
        pass
