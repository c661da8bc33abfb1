"""One sync folder with several compactors: ``drivers/folder.py`` whose timed
``Core.compact()`` meets snapshots it did not write.

The folder's devices are cut into ``compactors`` shares (device ``i`` belongs
to share ``i * compactors // devices``).  The last share is the measured
compactor's: its devices' op files reach the measured remote as op files.
Every other share belongs to a **peer**, a host-engine ``Core`` on a remote of
its own that holds the folder's ``meta/`` (one data key) and only what that
peer has seen: its share, and the first ``peer_overlap`` of the next peer's
share (so neighbouring snapshots hold the same dots and disagree on clocks).
Every round a peer folds the new op files it sees, seals one snapshot and
garbage-collects what it folded.  What syncs from a peer is its ``states/``
file and nothing else (``peer_protocol: reference``): **an op file a peer
folded reaches the measured remote only inside that peer's snapshot.**

The window's clock runs through ``publish``, so the peers' rounds are run in
``open()``, every peer through every prepared round, and each round's sealed
snapshot is kept; ``publish(r)`` only stores files: the peers' snapshots of
round ``r`` and the op files of the measured share.  ``call``, ``end_to_end``
and the three comparisons of ``check`` are ``folder.py``'s, against the plain
fold of every op file any device published, whatever route it took.

Two controls.  ``withhold`` (the harness's) keeps back the last op file of
the measured share.  The configuration key ``withhold_peer`` (laid over the
file by ``cellbench.control_peers`` and by a test, never in the file) is
``{"peer": k, "from_round": r}``: peer ``k``'s snapshots stop arriving from
round ``r`` on, though the reference counts its files; a single lost snapshot
would be healed by the peer's next, which covers it.
"""

from __future__ import annotations

import asyncio
import os
import shutil
import sys
import time

import numpy as np

from cellbench import gen
from cellbench.drivers import folder


def shares(devices: int, compactors: int) -> np.ndarray:
    """Device index -> the share it belongs to."""
    return np.arange(devices) * compactors // devices


def peer_view(devices: int, compactors: int, overlap: float) -> list:
    """For each peer, the devices it sees as a boolean mask: its share and
    the first ``overlap`` (rounded down) of the next peer's."""
    share = shares(devices, compactors)
    peers = compactors - 1
    views = []
    for k in range(peers):
        after = np.flatnonzero(share == (k + 1) % peers)
        seen = share == k
        seen[after[:int(overlap * len(after))]] = True
        views.append(seen)
    return views


class Driver(folder.Driver):
    def __init__(self, config: dict, plan: gen.Plan, workdir: str):
        super().__init__(config, plan, workdir)
        n = config["compactors"]
        self.views = peer_view(plan.devices, n, config["peer_overlap"])
        self.mine = shares(plan.devices, n) == n - 1
        self.fault = config.get("withhold_peer")
        self.snapshots: dict = {}  # round -> the peers' sealed snapshots
        self.peer_names = [[] for _ in self.views]  # as published, in order

    async def _peer(self, k: int):
        """Peer ``k``: a host-engine replica on a remote of its own that
        shares the folder's key metadata."""
        from crdt_enc_tpu.backends import FsStorage
        from crdt_enc_tpu.core import Core
        from crdt_enc_tpu.core.adapters import HostAccelerator

        remote = os.path.join(self.workdir, f"peer{k}-remote")
        shutil.copytree(os.path.join(self.remote, "meta"),
                        os.path.join(remote, "meta"))
        storage = FsStorage(os.path.join(self.workdir, f"peer{k}"), remote)
        return await Core.open(gen.core_opts(storage, HostAccelerator()))

    async def _peer_round(self, peer, seen: np.ndarray, r: int) -> bytes:
        """One round of one peer: the new op files it sees land in its
        remote, it compacts, and its one snapshot is read back."""
        files = self.plan.files_of_round(r)
        await gen.store_blobs([peer.storage], [
            blob for f, blob in zip(files, self.batches[r])
            if seen[self.plan.f_actor[f]]
        ])
        await peer.compact()
        (name,) = await peer.storage.list_state_names()
        ((_, raw),) = await peer.storage.load_states([name])
        return raw

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
        peers = [await self._peer(k) for k in range(len(self.views))]
        for r in range(-1, self.plan.n_rounds):
            self.snapshots[r] = await asyncio.gather(*(
                self._peer_round(peer, seen, r)
                for peer, seen in zip(peers, self.views)
            ))
        t2 = time.perf_counter()
        await self.publish(-1)
        self.compactor = await self._replica("compactor", TpuAccelerator())
        await self.compactor.compact()  # merges the peers' heads, folds its own
        sizes = [len(raw) for raw in self.snapshots[self.plan.n_rounds - 1]]
        print(f"cellbench: set-up: sealing every op file {t1 - t0:.1f} s, "
              f"{len(peers)} peers through {self.plan.n_rounds + 1} rounds "
              f"{t2 - t1:.1f} s (each sees {[int(v.sum()) for v in self.views]} "
              f"devices; last snapshots {sizes} bytes), publishing and taking in "
              f"the heads {time.perf_counter() - t2:.1f} s", file=sys.stderr)

    async def publish(self, r: int, withhold: bool = False) -> None:
        """Round ``r`` lands in the measured remote: one new snapshot of every
        peer, and the op files of the measured share.  ``withhold`` is the
        harness's control: the last of those op files never arrives, though
        the reference counts it."""
        files = self.plan.files_of_round(r)
        own = [blob for f, blob in zip(files, self.batches.pop(r))
               if self.mine[self.plan.f_actor[f]]]
        self.published.append(r)
        fault = self.fault
        for k, raw in enumerate(self.snapshots.pop(r)):
            if fault and k == fault["peer"] and r >= fault["from_round"]:
                continue
            self.peer_names[k].append(await self.writer.storage.store_state(raw))
        await gen.store_blobs([self.writer.storage], own[:-1] if withhold else own)

    async def check(self) -> list:
        checks = await super().check()
        left = set(await self.writer.storage.list_state_names())
        stale = sum(name in left for names in self.peer_names for name in names[:-1])
        return checks + [("stale_peer_snapshots_left", stale, 0)]
