"""The plain reference of a folder with several compactors: the op-by-op
fold of ``reference.py``, and a state merge written from the merge rule.

Imports nothing of the program.  A snapshot is a whole ``PlainORSet``; two of
them, each the fold of some of the op files, merge into the fold of the union
when every writer's files reach a folder in version order (the program's own
contract):

* the clocks merge by maximum, per actor;
* an add dot that only one side holds survives if the other side's clock has
  not seen it (a dot the other side has seen and no longer holds was removed
  there); a dot both sides hold survives;
* remove horizons merge by maximum, kill what they cover, and stay parked
  only while they run ahead of the merged clock.
"""

from __future__ import annotations

from cellbench.reference import PlainORSet, differing, fold_rows  # noqa: F401


def merge(a: PlainORSet, b: PlainORSet) -> PlainORSet:
    """The merge of two states as a new one; ``a`` and ``b`` are left alone."""
    out = PlainORSet()
    out.clock = {
        actor: max(a.clock.get(actor, 0), b.clock.get(actor, 0))
        for actor in a.clock.keys() | b.clock.keys()
    }
    for member in a.entries.keys() | b.entries.keys():
        mine, theirs = a.entries.get(member, {}), b.entries.get(member, {})
        alive = {}
        for actor in mine.keys() | theirs.keys():
            x, y = mine.get(actor, 0), theirs.get(actor, 0)
            if x == y:
                alive[actor] = x
                continue
            # each side's dot lives on only where the other never saw it
            x = x if x > b.clock.get(actor, 0) else 0
            y = y if y > a.clock.get(actor, 0) else 0
            if max(x, y):
                alive[actor] = max(x, y)
        if alive:
            out.entries[member] = alive
    for member in a.deferred.keys() | b.deferred.keys():
        mine, theirs = a.deferred.get(member, {}), b.deferred.get(member, {})
        out.deferred[member] = {
            actor: max(mine.get(actor, 0), theirs.get(actor, 0))
            for actor in mine.keys() | theirs.keys()
        }
    for member in list(out.deferred):
        out._settle(member)
    return out


def merge_all(states: list) -> PlainORSet:
    out = PlainORSet()
    for s in states:
        out = merge(out, s)
    return out


def from_canonical(obj: dict) -> PlainORSet:
    """A state from the canonical object (``c``, ``e``, ``d``) a snapshot
    carries."""
    s = PlainORSet()
    s.clock = dict(obj.get(b"c") or {})
    s.entries = {m: dict(v) for m, v in (obj.get(b"e") or {}).items()}
    s.deferred = {m: dict(v) for m, v in (obj.get(b"d") or {}).items()}
    return s
