"""Seeded traffic for a fleet whose tenants differ in popularity, and whose
popular set drifts: ``gen_zipf``'s sizes, a schedule of its own.

``gen.plan_run`` draws a round's writing tenants uniformly.  Here tenant ``t``
has, in cycle ``r`` (a round of the plan, counted from the first warm-up
round), the popularity rank

    ``((perm[t] + drift_ranks_per_cycle * r) mod tenants) + 1``

where ``perm`` is a seeded permutation of the tenants, drawn independently of
``gen_zipf``'s size ranks (a large tenant is no likelier to be busy than a
small one).  A cycle's ``active_tenants`` writers are drawn **without
replacement** with weight ``1 / rank ** constant`` (``popularity.constant``:
YCSB's ``requestdistribution=zipfian`` has 0.99), and ``active_devices``
devices of each write ``files_per_device`` files of ``ops_per_file`` ops.
Every cycle the ``drift`` coldest tenants become the hottest and every other
tenant cools by ``drift`` ranks: the hot set moves through the fleet.

Every seed gives the same number of tenants, files and ops in every round,
other tenants, devices and members.

The harness hands every driver ``gen.plan_run``'s uniform plan.
``plan_hotset`` keeps its head and its file counts, puts this schedule's
writers in place of the uniform rounds', and hands the result to
``gen_zipf.plan_zipf`` (sizes by the rank-size law, the head, the ops): neither
of those files is edited.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass

import numpy as np

from cellbench import gen, gen_zipf


@dataclass
class HotsetPlan(gen_zipf.ZipfPlan):
    perm: np.ndarray  # tenant index -> popularity rank - 1 in cycle 0
    drift: int  # ranks a tenant cools by a cycle

    def popularity_rank(self, r: int) -> np.ndarray:
        """Tenant index -> popularity rank (1 the hottest) in cycle ``r``."""
        return popularity_rank(self.perm, self.drift, r)

    def tenants_of_round(self, r: int) -> np.ndarray:
        """The distinct tenants that write in round ``r``, ascending."""
        files = self.files_of_round(r)
        return np.unique(self.f_actor[files.start:files.stop] // self.devices)


def popularity_rank(perm: np.ndarray, drift: int, r: int) -> np.ndarray:
    return (perm + drift * r) % len(perm) + 1


def weights(rank: np.ndarray, constant: float) -> np.ndarray:
    """The share of a draw each tenant has, by its popularity rank."""
    w = 1.0 / rank.astype(np.float64) ** constant
    return w / w.sum()


def schedule(config: dict, traffic: dict, seed: int, n_rounds: int) -> tuple:
    """``(perm, rounds)``: the popularity permutation and, per round, the
    global writer index ``tenant * devices + device`` of each of its files."""
    rng = np.random.default_rng([seed, 53])
    T, D = config["tenants"], config["devices"]
    at, ad = traffic["active_tenants"], traffic["active_devices"]
    constant = traffic["popularity"]["constant"]
    drift = traffic["drift_ranks_per_cycle"]
    perm = rng.permutation(T)
    rounds = []
    for r in range(n_rounds):
        p = weights(popularity_rank(perm, drift, r), constant)
        tenants = np.sort(rng.choice(T, at, replace=False, p=p))
        devices = gen._choose(rng, D, ad, at)
        actors = (tenants[:, None] * D + devices).reshape(-1).astype(np.int32)
        rounds.append(np.repeat(actors, traffic["files_per_device"]))
    return perm, rounds


def plan_hotset(config: dict, uniform: gen.Plan) -> HotsetPlan:
    """The run for ``config`` from the seed and the rounds of ``uniform``,
    the plan the harness made for the same cell: its mix is ``uniform``'s."""
    traffic = uniform.traffic
    perm, rounds = schedule(config, traffic, uniform.seed, uniform.n_rounds)
    f_actor = uniform.f_actor.copy()
    for r, actors in enumerate(rounds):
        files = uniform.files_of_round(r)
        if len(actors) != len(files):
            raise ValueError("the uniform plan's rounds are not this mix's")
        f_actor[files.start:files.stop] = actors
    sized = gen_zipf.plan_zipf(config, dataclasses.replace(uniform, f_actor=f_actor))
    return HotsetPlan(**vars(sized), perm=perm,
                      drift=traffic["drift_ranks_per_cycle"])
