"""The least bytes a snapshot merge must move, from the sizes of its call.

The twin of ``kernel_bytes.py`` for the merge kernels, whose sizes come from
the program's counters and not from the plan: what is merged is the states a
call found, not the ops of a round.
"""

from __future__ import annotations


def orset_merge(state_cells: int, out_cells: int, clock_cells: int) -> int:
    """Merging ``S`` OR-Set states over ``E`` members and ``R`` replicas.

    ``state_cells`` is ``S x E x R``: every state's add word and remove word
    of every cell is read once.  ``out_cells`` is ``E x R``: the merged add
    and remove planes are written once.  ``clock_cells`` is ``(S + 1) x R``:
    each state's clock read, the merged one written.  All are 4-byte words,
    counted at the sizes of the states as merged: a kernel's own padding to
    lanes or to a compile class moves more and counts for nothing here."""
    return 4 * (2 * state_cells + 2 * out_cells + clock_cells)


FUNCTIONS = {"orset_merge": orset_merge}
