"""The least bytes a gather of plane cells must move, from the shapes of the
rounds it served.

The twin of ``kernel_bytes.py`` for the program that, after a fold over planes
resident on the device, collects the cells the round's rows named so that only
those go back to the host.
"""

from __future__ import annotations


def orset_gather(cells: int, actors: int) -> int:
    """Gathering what a round changed out of resident OR-Set planes.

    The round's rows touch ``cells`` distinct (member, actor) cells: the add
    word and the remove word of each are read from their planes and written
    to the arrays that go home, 4 bytes each way.  The ``actors`` clock words
    the round can have moved are read once.  A program that gathers a value
    for every row, duplicates and padding included, or pulls the whole clock,
    moves more and counts for nothing here."""
    return 2 * 2 * 4 * cells + 4 * actors


FUNCTIONS = {"orset_gather": orset_gather}
