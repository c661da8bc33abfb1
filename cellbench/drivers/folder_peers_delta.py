"""One sync folder whose compactors all run this program:
``drivers/folder_peers.py`` whose peers' delta links sync beside their
snapshots (``peer_protocol: delta``).

A compactor of this program seals, with every snapshot after its first, a
delta link ``deltas/<its actor>/<N>`` whose base is its snapshot before
(``docs/delta.md``), and a sync tool carries the whole folder.  So here every
round ``r >= 0`` of a peer reaches the measured remote as two files, the
snapshot and the link, under the actor and the version the peer gave them;
the head (round -1) is baseless and arrives as a snapshot alone, which
``open()``'s untimed ``compact()`` merges, so that every later link finds its
base's name among the snapshots the measured compactor has merged.  The timed
``Core.compact()`` then takes the peers' work in through
``Core._read_remote_deltas`` and loads no snapshot.  Everything else (the
shares, the peers, what reaches the compactor as op files, the call, the
end-to-end metrics and the comparisons of ``check``) is ``folder_peers``'.

Three controls.  ``withhold`` (the harness's) keeps back the last op file of
the measured share.  ``withhold_peer`` (``folder_peers``', laid over the file
by ``cellbench.control_peers_delta`` and by a test, never in the file) keeps
back a peer's snapshot **and** link from a round on: nothing else carries that
peer's files, and the run must read ``correct: false``.  ``withhold_link`` is
``{"peer": k, "round": r}``: that one link never arrives, its snapshot does.
The program must load and merge that snapshot in round ``r``, count the gap as
a fallback when it reads the peer's next link, apply that one again, and the
run must read ``correct: true``: a lost link costs bytes, never data.
"""

from __future__ import annotations

import sys

import numpy as np

from cellbench.drivers import folder_peers


class Driver(folder_peers.Driver):
    def __init__(self, config: dict, plan, workdir: str):
        super().__init__(config, plan, workdir)
        self.lost_link = config.get("withhold_link")
        self.peer_actors: list = []  # peer k's actor id, the name of its log
        self.links: dict = {}  # round -> {actor: (version, sealed link)}
        self.sealed_to: dict = {}  # actor -> the newest version read back

    async def _peer(self, k: int):
        peer = await super()._peer(k)
        assert len(self.peer_actors) == k
        self.peer_actors.append(peer.actor_id)
        return peer

    async def _peer_round(self, peer, seen: np.ndarray, r: int) -> bytes:
        """``folder_peers``' round of one peer, and the link it sealed beside
        its snapshot read back from its own log.  The baseless head seals
        none; nor does a round whose delta is no smaller than the peer's
        state (the program's size guard: toy sizes only), and then the
        snapshot alone syncs, as it would."""
        raw = await super()._peer_round(peer, seen, r)
        actor = peer.actor_id
        sealed = await peer.storage.load_deltas([(actor, self.sealed_to.get(actor, 0) + 1)])
        assert len(sealed) <= (r >= 0), "one compact() seals at most one link, the head none"
        for _, version, link in sealed:
            self.sealed_to[actor] = version
            self.links.setdefault(r, {})[actor] = (version, link)
        return raw

    async def open(self) -> None:
        await super().open()
        first, last = (sorted(len(link) for _, link in self.links.get(r, {}).values())
                       for r in (0, self.plan.n_rounds - 1))
        print(f"cellbench: set-up: the peers' links of the first round {first} "
              f"bytes, of the last {last} bytes", file=sys.stderr)

    async def publish(self, r: int, withhold: bool = False) -> None:
        """Round ``r`` lands in the measured remote: ``folder_peers``' files
        and, beside every snapshot that arrives, the link its peer sealed."""
        gone, lost = self.fault, self.lost_link
        for k, actor in enumerate(self.peer_actors):
            if actor not in self.links.get(r, {}):
                continue  # the head, or a round the size guard sealed no link in
            version, link = self.links[r].pop(actor)
            if gone and k == gone["peer"] and r >= gone["from_round"]:
                continue
            if lost and k == lost["peer"] and r == lost["round"]:
                continue
            await self.writer.storage.store_delta(actor, version, link)
        await super().publish(r, withhold)

    async def check(self) -> list:
        """``folder_peers``' checks, and: no link of a peer is left in the
        measured remote.  Every one that arrived did so before the last call,
        which scanned it, so the call's GC owed its removal."""
        checks = await super().check()
        left = await self.writer.storage.load_deltas(
            [(actor, 1) for actor in self.peer_actors]
        )
        return checks + [("stale_peer_links_left", len(left), 0)]
