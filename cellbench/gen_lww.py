"""Seeded traffic for the LWW-map folder: ``gen.py``'s schedule, made
timestamped writes.

The harness draws a ``gen.Plan`` for every cell: who writes which op file in
which round, every op's key (``member``), whether it deletes (``kind``) and
whether it is on the wire at all (``live``: a device's deletes before its
first put are not).  An LWW write needs a timestamp and a value besides, and
they are drawn here from the same seed, by a stream of their own, so that the
file schedule, ``run.py``'s count of ops and ``shapes.rows`` stay the
harness's.  Every seed gives the same files and ops in every round.

Timestamps are uniform in ``[1, 2^40)`` and values in ``0..99``, as the
source's bench draws them (``benchmarks/suite.py`` ``bench_lwwmap``).
Forty-bit timestamps drawn alone never tie, and then the order's later keys
never decide an entry.  So a share ``tie_fraction`` of the writes repeats the
timestamp of the latest earlier write to its key *by another device*, which
the actor id then decides (a write whose key no other device wrote before it
keeps the timestamp it drew).
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass

import numpy as np

from cellbench import gen

TS_BITS = 40
VALUES = 100


@dataclass
class LwwPlan(gen.Plan):
    """A ``gen.Plan`` whose rows are LWW writes.  ``member`` is the key (the
    harness's column as it drew it); ``kind`` 1 is a delete."""

    ts: np.ndarray  # int64
    value: np.ndarray  # int32, 0..99 (a delete's is not on the wire)

    def wire_file(self, f: int) -> tuple:
        """File ``f`` as ``(tenant, actor id, version, ops)`` in the LWW map's
        wire form ``[key, ts, actor, value, tombstone]``."""
        rows = slice(f * self.opf, (f + 1) * self.opf)
        ab = self.actor_bytes[int(self.f_actor[f]) % self.devices]
        live = self.live[rows]
        ops = [
            [key, ts, ab, None if k else v, bool(k)]
            for key, ts, v, k in zip(
                self.member[rows][live].tolist(),
                self.ts[rows][live].tolist(),
                self.value[rows][live].tolist(),
                self.kind[rows][live].tolist(),
            )
        ]
        return int(self.f_actor[f]) // self.devices, ab, int(self.f_version[f]), ops


def _earlier_write_by_another(member: np.ndarray, actor: np.ndarray) -> np.ndarray:
    """For each row, the latest earlier row with its key, or -1 where there
    is none or the same device wrote it."""
    order = np.lexsort((np.arange(len(member)), member))
    before = np.full(len(member), -1, np.int64)
    same_key = member[order][1:] == member[order][:-1]
    before[order[1:][same_key]] = order[:-1][same_key]
    before[(before >= 0) & (actor[before] == actor)] = -1
    return before


def _follow(link: np.ndarray) -> np.ndarray:
    """Every row's last source along ``link`` (itself where it has none).
    Links point to earlier rows only, so the chains end."""
    src = np.where(link >= 0, link, np.arange(len(link)))
    while True:
        nxt = src[src]
        if np.array_equal(nxt, src):
            return src
        src = nxt


def plan_lww(config: dict, plan: gen.Plan) -> LwwPlan:
    """``plan`` with a timestamp and a value for every row, and the ties."""
    rng = np.random.default_rng([plan.seed, TS_BITS, VALUES])
    n = len(plan.kind)
    ts = rng.integers(1, 1 << TS_BITS, n, dtype=np.int64)
    value = rng.integers(0, VALUES, n).astype(np.int32)
    tie = rng.random(n) < config["tie_fraction"]
    other = _earlier_write_by_another(plan.member, plan.actor)
    link = np.where(tie & (other >= 0), other, -1)
    return LwwPlan(**vars(plan), ts=ts[_follow(link)], value=value)


def core_opts(storage, accel):
    """Open options of every replica the cell opens: ``gen.core_opts`` with
    the LWW map's adapter in the OR-Set's place, every default left on."""
    from crdt_enc_tpu.core import lwwmap_adapter

    return dataclasses.replace(gen.core_opts(storage, accel), adapter=lwwmap_adapter())
