"""Seeded traffic for every cell, and the helpers that hand it to the program.

The generators are copies of ``chip_smoke.py``'s (``actor_table``, ``gen_ops``,
``op_files``, ``core_opts``, ``store_blobs``): copied, not imported, because
later PRs may change the program and not the yardstick.  They differ from the
originals in one respect: the smoke drew each op's actor at random and cut
files afterwards; a cell fixes who writes a file in which round, so here the
file schedule comes first and the ops fill it.

One generator serves every deployment and every mix.  A deployment is ``T``
tenants of ``D`` devices over ``E`` members (a solo folder is ``T == 1``); a
mix says how many tenants, and how many devices of each, write one op file per
round.  Every seed gives the same number of files and ops per round, only other
writers and other members, so runs with different seeds do the same work.
"""

from __future__ import annotations

import asyncio
import math
import uuid
from dataclasses import dataclass

import numpy as np


def actor_table(n: int) -> list:
    """``n`` actor ids whose byte order equals their index order."""
    return [uuid.UUID(int=i + 1).bytes for i in range(n)]


@dataclass
class Plan:
    """A whole run's ops as columns, cut into op files.

    Rows are in file order, ``opf`` rows to a file; files are in round order,
    the head (round -1) first.  ``actor`` is the global writer index
    ``tenant * D + device``.  ``traffic`` is the mix the rounds were drawn
    from (the cell's, under any overlay): a driver that needs more of its
    cell's mix than the rounds show reads it here."""

    seed: int
    traffic: dict
    tenants: int
    devices: int
    members: int
    opf: int
    n_rounds: int
    kind: np.ndarray  # int8, 0 add / 1 remove
    member: np.ndarray  # int32
    actor: np.ndarray  # int32, global writer index
    counter: np.ndarray  # int32: an add's dot, a remove's horizon
    live: np.ndarray  # bool: False for a remove before its actor's first add
    f_actor: np.ndarray  # int32 per file
    f_version: np.ndarray  # int32 per file, dense from 1 per actor
    round_files: list  # round -> (first file, one past the last); [0] is the head
    actor_bytes: list  # device index -> actor id on the wire

    def files_of_round(self, r: int) -> range:
        """File indices of round ``r``; ``-1`` is the head."""
        a, b = self.round_files[r + 1]
        return range(a, b)

    def rows_of_round(self, r: int) -> slice:
        a, b = self.round_files[r + 1]
        return slice(a * self.opf, b * self.opf)

    def live_rows(self, rounds) -> np.ndarray:
        """Row indices of the ops on the wire in ``rounds``, in file order."""
        spans = [self.rows_of_round(r) for r in sorted(rounds)]
        rows = np.concatenate([np.arange(s.start, s.stop) for s in spans])
        return rows[self.live[rows]]

    def wire_file(self, f: int) -> tuple:
        """File ``f`` as ``(tenant, actor id, version, ops)`` in the ORSet
        wire form: an add is ``[0, member, [actor, dot]]``, a remove is
        ``[1, member, {actor: horizon}]``."""
        rows = slice(f * self.opf, (f + 1) * self.opf)
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
        tenant = int(self.f_actor[f]) // self.devices
        return tenant, ab, int(self.f_version[f]), ops

    def round_shape(self, r: int) -> dict:
        """What round ``r`` asks of a fold: rows, and the distinct plane
        cells and actors they touch (the inputs of ``kernel_bytes``)."""
        rows = self.rows_of_round(r)
        live = self.live[rows]
        actor = self.actor[rows][live].astype(np.int64)
        cell = actor * self.members + self.member[rows][live]
        return {
            "rows": int(live.sum()),
            "cells": int(np.unique(cell).size),
            "actors": int(np.unique(actor).size),
        }


def rounds_for(traffic: dict, config: dict, seconds: float) -> int:
    """Rounds to prepare: ``seconds`` x the mix's ``max_ops_per_s``, plus the
    warm-up rounds."""
    per_round = (
        traffic["active_tenants"] * traffic["active_devices"]
        * traffic["files_per_device"] * config["ops_per_file"]
    )
    return traffic["warmup_rounds"] + max(
        1, math.ceil(seconds * traffic["max_ops_per_s"] / per_round)
    )


def _choose(rng, n: int, k: int, rows: int) -> np.ndarray:
    """``rows`` independent draws of ``k`` distinct values below ``n``,
    each sorted."""
    if k == n:
        return np.tile(np.arange(n), (rows, 1))
    picks = np.argsort(rng.random((rows, n)), axis=1)[:, :k]
    return np.sort(picks, axis=1)


def _dense_rank(group: np.ndarray, weight: np.ndarray) -> np.ndarray:
    """For each position, the running sum of ``weight`` within its group, in
    position order (the element's own weight included)."""
    order = np.argsort(group, kind="stable")
    g, w = group[order], weight[order].astype(np.int64)
    cum = np.cumsum(w)
    first = np.flatnonzero(np.r_[True, g[1:] != g[:-1]])
    base = np.repeat(cum[first] - w[first], np.diff(np.r_[first, len(g)]))
    out = np.empty(len(group), np.int64)
    out[order] = cum - base
    return out


def plan_run(config: dict, traffic: dict, seed: int, n_rounds: int) -> Plan:
    """The whole run from ``seed``: the head every device wrote before the
    compactor came, then ``n_rounds`` rounds of the mix."""
    rng = np.random.default_rng(seed)
    T, D = config["tenants"], config["devices"]
    E, opf = config["members"], config["ops_per_file"]
    at, ad = traffic["active_tenants"], traffic["active_devices"]
    fpd = traffic["files_per_device"]
    if at > T or ad > D:
        raise ValueError("the mix asks for more writers than the deployment has")

    head = np.repeat(np.arange(T * D, dtype=np.int32), config["initial_files_per_device"])
    f_actor, bounds = [head], [(0, len(head))]
    for r in range(n_rounds):
        tenants = _choose(rng, T, at, 1)[0]
        devices = _choose(rng, D, ad, at)
        actors = (tenants[:, None] * D + devices).reshape(-1).astype(np.int32)
        actors = np.repeat(actors, fpd)
        start = bounds[-1][1]
        bounds.append((start, start + len(actors)))
        f_actor.append(actors)
    f_actor = np.concatenate(f_actor)
    f_version = _dense_rank(f_actor, np.ones(len(f_actor), np.int64)).astype(np.int32)

    n = len(f_actor) * opf
    actor = np.repeat(f_actor, opf)
    kind = (rng.random(n) < config["remove_fraction"]).astype(np.int8)
    member = rng.integers(0, E, n, dtype=np.int32)
    # an add's dot is its actor's add count so far; a remove's horizon is the
    # same count, so it observes exactly the adds its own device has made
    counter = _dense_rank(actor, kind == 0).astype(np.int32)
    live = ~((kind == 1) & (counter == 0))
    return Plan(
        seed=seed, traffic=traffic, tenants=T, devices=D, members=E, opf=opf,
        n_rounds=n_rounds,
        kind=kind, member=member, actor=actor, counter=counter, live=live,
        f_actor=f_actor, f_version=f_version,
        round_files=bounds, actor_bytes=actor_table(D),
    )


# ------------------------------------------------------- handing it over


def core_opts(storage, accel):
    """Open options of every replica a cell opens: XChaCha20-Poly1305,
    plain key wrapping, the OR-Set adapter, every default left on."""
    from crdt_enc_tpu.backends import PlainKeyCryptor, XChaChaCryptor
    from crdt_enc_tpu.core import OpenOptions, orset_adapter
    from crdt_enc_tpu.utils.versions import DEFAULT_DATA_VERSION_1

    return OpenOptions(
        storage=storage,
        cryptor=XChaChaCryptor(),
        key_cryptor=PlainKeyCryptor(),
        adapter=orset_adapter(),
        supported_data_versions=(DEFAULT_DATA_VERSION_1,),
        current_data_version=DEFAULT_DATA_VERSION_1,
        create=True,
        accelerator=accel,
    )


def state_obj(core) -> dict:
    """A replica's state as the program's canonical object (``c`` the clock,
    ``e`` the entries, ``d`` the deferred horizons)."""
    return core.with_state(lambda s: s.to_obj())


async def seal_round(plan: Plan, r: int, cores: list) -> list:
    """Round ``r`` as sealed blobs ``(tenant, actor id, version, blob)`` in
    the program's real three-layer wire format, each sealed with its
    tenant's key."""
    out = []
    for f in plan.files_of_round(r):
        tenant, ab, version, ops = plan.wire_file(f)
        out.append((tenant, ab, version, await cores[tenant]._seal(ops)))
    return out


async def store_blobs(storages: list, blobs: list) -> None:
    """What the other devices do: publish their op files into the remotes."""
    sem = asyncio.Semaphore(64)

    async def one(tenant, ab, version, blob):
        async with sem:
            await storages[tenant].store_ops(ab, version, blob)

    await asyncio.gather(*(one(*b) for b in blobs))
