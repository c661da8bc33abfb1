"""The least bytes an LWW winner fold must move, from the sizes of its call.

The twin of ``kernel_bytes.py`` for the LWW map's fold, whose sizes come from
the program's counters (``lww_fold_rows``, ``lww_fold_keys``) and not from the
plan: the count then reads the same work whatever implements the fold, and a
program that folds the rows elsewhere has nothing to read.
"""

from __future__ import annotations


def lww_fold(rows: int, keys: int) -> int:
    """Selecting the per-key winners of ``rows`` timestamped writes that name
    ``keys`` distinct keys.

    Every row is read once: its key, the two words of its timestamp, its
    actor and its value, five 4-byte words.  Every named key's winner is
    written once: the same four words of the winning tuple and one byte of
    presence.  Rows a kernel pads its batch with, keys it pads its table
    with, and whatever a sort or a cascade streams besides count for
    nothing here, so the share cannot pass 100% and a fold that touches
    less comes closer to it."""
    return 20 * rows + 17 * keys


FUNCTIONS = {"lww_fold": lww_fold}
