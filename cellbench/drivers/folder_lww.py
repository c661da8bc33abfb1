"""``folder``'s driver for a folder that holds an LWW-register map.

The same folder, writer, compactor and timed ``Core.compact()``; three things
are the map's own.  Every replica opens with ``lwwmap_adapter()``
(``gen_lww.core_opts``); the op files are timestamped writes in the map's wire
form (the plan is a ``gen_lww.LwwPlan``, so ``gen.seal_round`` seals
``[key, ts, actor, value, tombstone]`` rows); and the check holds the program
to ``reference_lww.py``, under ``folder``'s three names.  ``publish`` is
inherited, so the control's withheld file is missed here as there.

One question is asked of the program before anything is opened, as
``folder_10k`` asks one: does a second fold of another batch of the same class
compile a program?  The program before ISSUE 50 folds every batch with a
program compiled for that batch's own count of distinct keys, which is another
number every round, and the Pallas fold's compile is the four-key sort's
(some 48 s at a round's 65,536 rows on the chip): its every timed call is a
compile and a 30-second window a measurement of the compiler (with this
question taken out, that program completed one call of 50,965 ms in its
window, one compile inside it; PERF.md section 6, PR 50).  Two small
seeded batches go through ``TpuAccelerator().fold_ops`` (300 writes each,
naming 280 and 270 keys: one class of rows, of keys and of values); if the
second grows ``jax_compiles`` the driver says so in one line and the run ends
at once, with nothing on standard output and exit code 2.  The decision is
the program's behaviour, never a version or a commit.  Where the round's rows
then fold is not asked: a program that sent them elsewhere reads so in
``device_row_pct.folder`` and ``lww_pallas_pct.folder_lww``.
"""

from __future__ import annotations

import os
import sys
import time

from cellbench import gen, gen_lww, reference_lww
from cellbench.drivers import folder

PROBE_WRITES = 300  # past the accelerator's smallest device batch (256)
PROBE_KEYS = (280, 270)  # two counts of distinct keys inside one class (512)


def refuse_unless_steady(accel) -> None:
    """Exit with status 2 if ``accel`` compiles a program for the second of
    two LWW batches that differ only inside their classes."""
    from crdt_enc_tpu.models import LWWMap
    from crdt_enc_tpu.obs import runtime as obs_runtime

    actors = gen.actor_table(4)
    compiled = []
    for keys in PROBE_KEYS:
        ops = [[i % keys, i + 1, actors[i % 4], i % 100, False]
               for i in range(PROBE_WRITES)]
        before = obs_runtime.recompile_count()
        accel.fold_ops(LWWMap(), ops)
        compiled.append(obs_runtime.recompile_count() - before)
    if compiled[1]:
        print(f"cellbench: this program compiled {compiled[1]} program(s) for a "
              f"second LWW batch of the first one's class ({PROBE_WRITES} writes "
              f"naming {PROBE_KEYS[1]} keys after {PROBE_KEYS[0]}): it compiles in "
              "every round of a folder, a timed call would measure the compiler, "
              "and the cell does not run on it", file=sys.stderr, flush=True)
        raise SystemExit(2)


class Driver(folder.Driver):
    def __init__(self, config: dict, plan: gen.Plan, workdir: str):
        from crdt_enc_tpu.parallel import TpuAccelerator

        t0 = time.perf_counter()
        refuse_unless_steady(TpuAccelerator())
        print(f"cellbench: set-up: the two probe folds {time.perf_counter() - t0:.1f} s",
              file=sys.stderr)
        super().__init__(config, gen_lww.plan_lww(config, plan), workdir)

    def _replica(self, name: str, accel):
        from crdt_enc_tpu.backends import FsStorage
        from crdt_enc_tpu.core import Core

        local = os.path.join(self.workdir, name)
        return Core.open(gen_lww.core_opts(FsStorage(local, self.remote), accel))

    async def check(self) -> list:
        from crdt_enc_tpu.core.adapters import HostAccelerator
        from crdt_enc_tpu.models import canonical_bytes

        rows = self.plan.live_rows(self.published)
        want = reference_lww.fold_rows(self.plan, rows).canonical()
        fresh = await self._replica("fresh", HostAccelerator())
        await fresh.read_remote()
        return [
            ("compactor_vs_reference",
             reference_lww.differing(gen.state_obj(self.compactor), want), 0),
            ("fresh_replica_vs_reference",
             reference_lww.differing(gen.state_obj(fresh), want), 0),
            ("fresh_replica_bytes_vs_compactor",
             int(fresh.with_state(canonical_bytes)
                 != self.compactor.with_state(canonical_bytes)), 0),
        ]
