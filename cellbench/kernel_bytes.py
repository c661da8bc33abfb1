"""The least bytes a kernel must move, from the shapes of its call.

A roofline share divides these by the device's peak rate (``peaks.json``) and
by the kernel's time in the trace.  Each function counts only what any
implementation of the operation has to touch, never what the present kernel
happens to stream, so the share cannot pass 100% and a kernel that touches
less comes closer to it.
"""

from __future__ import annotations


def orset_fold(rows: int, cells: int, actors: int) -> int:
    """Folding ``rows`` op rows into OR-Set planes held on the device.

    Every row is read once: kind (1 byte), member, actor and counter (4 bytes
    each).  The rows touch ``cells`` distinct (member, actor) cells, each a
    word in the add plane and one in the remove plane, read and written once;
    and ``actors`` distinct clock words, read and written once.  Cells the
    batch does not name need not move."""
    return 13 * rows + 2 * 2 * 4 * cells + 2 * 4 * actors


FUNCTIONS = {"orset_fold": orset_fold}
