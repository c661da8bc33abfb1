"""Seeded traffic for a fleet whose tenants are skewed: sizes by a rank-size
law, the mix's schedule unchanged.

``gen.plan_run`` makes every tenant one size.  Here tenant of rank ``k`` (1 the
largest) draws its members uniformly from a vocabulary of

    ``E_k = max(min(members_floor, members), members // k)``

where ``members`` is the largest tenant's vocabulary and ``members_floor`` the
smallest a tenant may have (the source's 64-member tenant; under a toy overlay
with ``members`` below the floor every tenant has ``members``).  The
``team_ranks`` largest tenants are written by ``team_devices`` devices, every
other tenant by ``devices``.  A tenant's head (what its devices wrote before
the service came) holds

    ``max(initial_files_per_device * devices * ops_per_file, E_k)`` ops

in ``initial_files_per_device`` files a device, all of one size for the
tenant, rounded up to whole ops: a small tenant's head is the source's (24-op
files), a large tenant's files are a device's first sync of a full folder.
Which tenant index holds which rank, which devices write and which members are
drawn come from the seed; the multiset of sizes, and the files and ops of
every round, do not.

The rounds are those of the uniform plan the harness made from the same seed
and the same mix (``cellbench/run.py`` owns ``gen.plan_run``): the same
tenants, in the same order, one ``ops_per_file`` file from as many devices of
each; a team tenant's writer is lifted from the uniform plan's device ``d`` to
one of its own ``d, d + devices, d + 2 * devices, ...``.

The plan is a ``gen.Plan`` with ``devices`` the widest tenant's count (the
small tenants write from their first ``devices``) and files of unequal length:
``f_start`` cuts the rows into files, and the three methods that assumed
``opf`` rows to a file are overridden.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from cellbench import gen


@dataclass
class ZipfPlan(gen.Plan):
    f_start: np.ndarray  # int64 per file + 1: rows of file f are f_start[f:f+2]
    rank: np.ndarray  # tenant index -> rank, 1 the largest
    vocab: np.ndarray  # tenant index -> E_k
    writers: np.ndarray  # tenant index -> devices that write to it

    def rows_of_round(self, r: int) -> slice:
        a, b = self.round_files[r + 1]
        return slice(int(self.f_start[a]), int(self.f_start[b]))

    def wire_file(self, f: int) -> tuple:
        rows = slice(int(self.f_start[f]), int(self.f_start[f + 1]))
        ab = self.actor_bytes[int(self.f_actor[f]) % self.devices]
        live = self.live[rows]
        ops = [
            [0, m, [ab, c]] if k == 0 else [1, m, {ab: c}]
            for k, m, c in zip(
                self.kind[rows][live].tolist(),
                self.member[rows][live].tolist(),
                self.counter[rows][live].tolist(),
            )
        ]
        return int(self.f_actor[f]) // self.devices, ab, int(self.f_version[f]), ops

    def reached(self) -> np.ndarray:
        """``[round + 1, tenant]``: the distinct members a tenant's ops on the
        wire have named up to and including that round (row 0 is the head):
        the vocabulary a replica that folded them all holds planes for."""
        rows = np.flatnonzero(self.live)
        tenant = self.actor[rows].astype(np.int64) // self.devices
        _, first = np.unique(tenant * (self.members + 1) + self.member[rows],
                             return_index=True)  # rows are in round order
        row_round = np.searchsorted(
            [self.f_start[b] for _, b in self.round_files], rows[first], side="right")
        new = np.zeros((len(self.round_files), self.tenants), np.int64)
        np.add.at(new, (row_round, tenant[first]), 1)
        return np.cumsum(new, axis=0)

    def rows_per_tenant(self, r: int) -> np.ndarray:
        """The ops on the wire each tenant has in round ``r``."""
        rows = self.rows_of_round(r)
        tenant = self.actor[rows][self.live[rows]] // self.devices
        return np.bincount(tenant, minlength=self.tenants)


def vocabularies(config: dict) -> np.ndarray:
    """``E_k`` for rank ``k = 1 .. tenants``."""
    members = config["members"]
    floor = min(config["members_floor"], members)
    return np.maximum(floor, members // np.arange(1, config["tenants"] + 1))


def plan_zipf(config: dict, uniform: gen.Plan) -> ZipfPlan:
    """The skewed run for ``config`` from the seed, the rounds and the round
    schedule of ``uniform``, the plan the harness made for the same cell."""
    rng = np.random.default_rng([uniform.seed, 26])
    T, D, opf = config["tenants"], config["devices"], config["ops_per_file"]
    team = config["team_devices"]
    wide = max(D, team)
    if (uniform.tenants, uniform.devices, uniform.opf) != (T, D, opf):
        raise ValueError("the uniform plan is not this configuration's")
    rank = rng.permutation(T) + 1
    vocab = vocabularies(config)[rank - 1]
    writers = np.where(rank <= config["team_ranks"], team, D)
    ifpd = config["initial_files_per_device"]
    head_files = writers * ifpd
    head_len = -(-np.maximum(ifpd * D * opf, vocab) // head_files)  # ops a file

    # the head: tenant by tenant, device by device
    f_tenant = np.repeat(np.arange(T), head_files)
    within = np.arange(len(f_tenant)) - np.repeat(np.cumsum(head_files) - head_files, head_files)
    f_actor = [(f_tenant * wide + within // ifpd).astype(np.int32)]
    f_rows = [head_len[f_tenant]]
    bounds = [(0, len(f_tenant))]
    # the rounds: the uniform plan's writers, a team's lifted to its own
    for r in range(uniform.n_rounds):
        files = uniform.files_of_round(r)
        u = uniform.f_actor[files.start:files.stop].astype(np.int64)
        tenant, device = u // D, u % D
        lift = rng.integers(0, np.maximum(1, writers[tenant] // D))
        f_actor.append((tenant * wide + device + D * lift).astype(np.int32))
        f_rows.append(np.full(len(u), opf))
        bounds.append((bounds[-1][1], bounds[-1][1] + len(u)))
    f_actor = np.concatenate(f_actor)
    f_rows = np.concatenate(f_rows)
    f_start = np.concatenate([[0], np.cumsum(f_rows)]).astype(np.int64)
    f_version = gen._dense_rank(f_actor, np.ones(len(f_actor), np.int64)).astype(np.int32)

    n = int(f_start[-1])
    actor = np.repeat(f_actor, f_rows)
    kind = (rng.random(n) < config["remove_fraction"]).astype(np.int8)
    member = rng.integers(0, vocab[actor // wide]).astype(np.int32)
    counter = gen._dense_rank(actor, kind == 0).astype(np.int32)
    live = ~((kind == 1) & (counter == 0))
    return ZipfPlan(
        seed=uniform.seed, traffic=uniform.traffic, tenants=T, devices=wide, members=config["members"],
        opf=opf, n_rounds=uniform.n_rounds,
        kind=kind, member=member, actor=actor, counter=counter, live=live,
        f_actor=f_actor, f_version=f_version, round_files=bounds,
        actor_bytes=gen.actor_table(wide),
        f_start=f_start, rank=rank, vocab=vocab, writers=writers,
    )
