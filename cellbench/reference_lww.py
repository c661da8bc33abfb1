"""The plain reference of the LWW-register map: timestamped writes applied
one by one to a dict.

It states the semantics ``lwwmap_folder_10k`` promises
(``crdt_enc_tpu/models/lwwmap.py`` documents the same ones) in its own words,
and imports nothing of the program.  State is one map, ``key -> (timestamp,
actor, value, tombstone)``: the write that holds the key.  A write replaces
the entry iff it is greater in the total order

1. timestamp, as a number;
2. then the actor id, as bytes;
3. then the value, in the order of its canonical bytes.  The configuration's
   values are the ints 0 to 99, each one byte that is the number itself, so
   they order as numbers; a delete carries no value (``None``), whose one byte
   (0xc0) lies above every one of them;
4. then the tombstone: of two writes equal in all of the above the delete
   wins.

A delete is a write like any other.  Its key stays in the state with the
timestamp and actor that won, value ``None`` and the tombstone set, so that
an older put that arrives later loses to it and never resurrects the key.
Two replicas that took in the same writes, in any order, hold equal states.
"""

from __future__ import annotations

NO_VALUE_RANK = 0xC0  # above every value of the configuration's domain, 0..99


def order_key(ts: int, actor: bytes, value, tombstone: bool) -> tuple:
    """A write's place in the total order, for ints 0..99 and ``None``."""
    if value is not None and not 0 <= value < NO_VALUE_RANK:
        raise ValueError(f"value {value!r} is outside the reference's domain")
    return (ts, actor, NO_VALUE_RANK if value is None else value, bool(tombstone))


class PlainLWWMap:
    def __init__(self):
        self.entries: dict = {}

    def write(self, key, ts: int, actor: bytes, value, tombstone: bool) -> None:
        """A put (``tombstone`` false) or a delete (true; its value is
        dropped)."""
        new = (ts, actor, None if tombstone else value, bool(tombstone))
        held = self.entries.get(key)
        if held is None or order_key(*new) > order_key(*held):
            self.entries[key] = new

    def canonical(self) -> dict:
        """The state as the program's canonical object names it:
        ``key -> [timestamp, actor, value, tombstone]``, tombstones kept."""
        return {k: list(e) for k, e in self.entries.items()}


def fold_rows(plan, rows, state: PlainLWWMap | None = None) -> PlainLWWMap:
    """Apply the writes of ``rows`` (row indices of a ``gen_lww.LwwPlan``, in
    order) to ``state``."""
    state = PlainLWWMap() if state is None else state
    ids = plan.actor_bytes
    D = plan.devices
    for k, key, a, ts, v in zip(
        plan.kind[rows].tolist(), plan.member[rows].tolist(),
        plan.actor[rows].tolist(), plan.ts[rows].tolist(),
        plan.value[rows].tolist(),
    ):
        state.write(key, ts, ids[a % D], v, k == 1)
    return state


def differing(got: dict, want: dict) -> int:
    """How many keys two canonical forms disagree on: an entry that differs
    in timestamp, actor, value or tombstone, or a key only one side holds."""
    return sum(
        1 for k in got.keys() | want.keys()
        if k not in got or k not in want or list(got[k]) != list(want[k])
    )
