"""The plain reference: an observed-remove set applied op by op.

It states the semantics the configurations promise (``crdt_enc_tpu/models/
orset.py`` documents the same ones) in plain dicts, and imports nothing of the
program.  State is three maps:

* ``clock[actor]``: the largest add dot seen from ``actor``;
* ``entries[member][actor]``: the latest add dot of ``member`` from ``actor``
  that no remove has observed;
* ``deferred[member][actor]``: a remove horizon that runs ahead of ``clock``.

An add whose dot the clock has seen is a replay and changes nothing.  A remove
kills every entry at or under its horizon, per actor, and parks what it
observed beyond the clock.  The canonical form drops empty maps and horizons
the clock has caught up with; two replicas that folded the same ops hold equal
canonical forms.
"""

from __future__ import annotations


class PlainORSet:
    def __init__(self):
        self.clock: dict = {}
        self.entries: dict = {}
        self.deferred: dict = {}

    def add(self, member, actor: bytes, dot: int) -> None:
        if dot <= self.clock.get(actor, 0):
            return
        self.clock[actor] = dot
        if self.deferred.get(member, {}).get(actor, 0) < dot:
            self.entries.setdefault(member, {})[actor] = dot
        self._settle(member)

    def remove(self, member, horizon: dict) -> None:
        entry = self.entries.get(member)
        for actor, c in horizon.items():
            if entry is not None and entry.get(actor, 0) <= c:
                entry.pop(actor, None)
            if c > self.clock.get(actor, 0):
                parked = self.deferred.setdefault(member, {})
                parked[actor] = max(parked.get(actor, 0), c)
        self._settle(member)

    def _settle(self, member) -> None:
        entry = self.entries.get(member)
        parked = self.deferred.get(member)
        if entry is not None and parked:
            for actor in [a for a, c in entry.items() if c <= parked.get(a, 0)]:
                del entry[actor]
        if entry is not None and not entry:
            del self.entries[member]

    def canonical(self) -> dict:
        """The state as the program's canonical object names it: ``c`` the
        clock, ``e`` the entries, ``d`` the horizons still ahead of it."""
        parked = {
            m: {a: c for a, c in v.items() if c > self.clock.get(a, 0)}
            for m, v in self.deferred.items()
        }
        return {
            b"c": {a: c for a, c in self.clock.items() if c > 0},
            b"e": {m: dict(v) for m, v in self.entries.items() if v},
            b"d": {m: v for m, v in parked.items() if v},
        }


def fold_rows(plan, rows, state: PlainORSet | None = None) -> PlainORSet:
    """Apply the live ops of ``rows`` (row indices of ``plan``, in order) to
    ``state``.  All rows belong to one tenant."""
    state = PlainORSet() if state is None else state
    ids = plan.actor_bytes
    D = plan.devices
    for k, m, a, c in zip(
        plan.kind[rows].tolist(), plan.member[rows].tolist(),
        plan.actor[rows].tolist(), plan.counter[rows].tolist(),
    ):
        if k == 0:
            state.add(m, ids[a % D], c)
        else:
            state.remove(m, {ids[a % D]: c})
    return state


def differing(a: dict, b: dict) -> int:
    """How many clock entries and members two canonical forms disagree on."""
    n = 0
    for key in (b"c", b"e", b"d"):
        x, y = a.get(key) or {}, b.get(key) or {}
        n += sum(1 for k in x.keys() | y.keys() if x.get(k) != y.get(k))
    return n
