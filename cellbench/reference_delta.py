"""The plain reference of the OR-Set window delta: what a compactor of this
program publishes beside a snapshot, and what a consumer does with it, written
from the rule in ``docs/delta.md`` ("Codec correctness contract").

Imports nothing of the program.  ``base`` and ``new`` are two snapshots of one
sealer, ``new`` the later; the window is ``(base.clock, new.clock]``, per
actor.  The delta carries

* ``bc``, ``c``: both clocks, the ends of the window;
* ``e``: every slot of ``new`` whose dot lies past ``base.clock`` (the new
  adds, and the confirmation that keeps a window dot alive at the consumer);
* ``x``: every slot of ``base`` that ``new`` no longer holds, with the dot it
  had (dot-exact: a consumer's newer slot of that actor is not meant);
* ``t``: every remove horizon of ``new`` that runs ahead of ``new.clock`` and
  past what ``base`` had parked.

A consumer that has merged ``base`` applies it so: a slot of its own dies iff
``x`` names its very dot, or its dot lies in the window and ``e`` does not
confirm it (``new`` saw that dot and no longer holds it); the horizons of ``t``
act as removes; a slot of ``e`` lands where the consumer has never seen its
dot; the clocks merge by maximum.  The outcome equals
``reference_peers.merge(consumer, new)`` (a test holds the two together on
seeded states), so the delta route and the snapshot route reach one state.
"""

from __future__ import annotations

from cellbench.reference import PlainORSet


def _clock(state: PlainORSet) -> dict:
    return {actor: dot for actor, dot in state.clock.items() if dot > 0}


def diff(base: PlainORSet, new: PlainORSet) -> dict:
    """The delta from ``base`` to ``new`` as the program's wire object names
    its parts."""
    adds, removed, horizons = {}, {}, {}
    for member, slots in new.entries.items():
        past = {a: dot for a, dot in slots.items() if dot > base.clock.get(a, 0)}
        if past:
            adds[member] = past
    for member, slots in base.entries.items():
        kept = new.entries.get(member, {})
        gone = {a: dot for a, dot in slots.items() if a not in kept}
        if gone:
            removed[member] = gone
    for member, parked in new.deferred.items():
        before = base.deferred.get(member, {})
        raised = {a: h for a, h in parked.items()
                  if h > before.get(a, 0) and h > new.clock.get(a, 0)}
        if raised:
            horizons[member] = raised
    return {b"bc": _clock(base), b"c": _clock(new),
            b"e": adds, b"x": removed, b"t": horizons}


def apply(x: PlainORSet, delta: dict) -> PlainORSet:
    """``x`` with the delta taken in, as a new state; ``x`` is left alone.
    ``x`` must have merged the delta's base."""
    lo, hi = delta[b"bc"], delta[b"c"]
    adds, removed = delta[b"e"], delta[b"x"]
    out = PlainORSet()
    out.clock = dict(x.clock)
    out.deferred = {m: dict(v) for m, v in x.deferred.items()}
    for member, slots in x.entries.items():
        exact, confirmed = removed.get(member, {}), adds.get(member, {})
        alive = {
            a: dot for a, dot in slots.items()
            if exact.get(a) != dot
            and not (lo.get(a, 0) < dot <= hi.get(a, 0) and confirmed.get(a) != dot)
        }
        if alive:
            out.entries[member] = alive
    for member, horizon in delta[b"t"].items():
        out.remove(member, horizon)
    for member, slots in adds.items():
        for a, dot in slots.items():
            unseen = dot > out.clock.get(a, 0)
            if unseen and dot > out.deferred.get(member, {}).get(a, 0):
                out.entries.setdefault(member, {})[a] = dot
    for a, dot in hi.items():
        out.clock[a] = max(out.clock.get(a, 0), dot)
    for member in list(out.deferred):
        out._settle(member)
    return out
