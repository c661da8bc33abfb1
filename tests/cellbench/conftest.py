"""One case of the benchmark's own tests holds for OR-Set cells only.

``test_cellbench.py::test_narrowed_counters_in_the_timed_path_are_not_correct``
breaks the OR-Set's two writebacks (``ops.orset_planes_to_state``,
``ops.orset_cells_to_state``) and expects ``correct: false`` of every cell
whose name ends in ``.backlog`` or ``.busy``.  A cell of another CRDT never
calls them, so its run stays correct, and rightly: the case has nothing to say
about it.  That file is the accepted benchmark's and a PR that adds a cell
edits no file that is there (ISSUE 50 adds the first deployment that is no
OR-Set), so the case is skipped here, with its reason, for a cell whose
configuration says another ``crdt``; such a cell's own test file breaks its
own fold's hand-back instead (``test_folder_lww.py``:
``test_narrowed_timestamps_in_the_timed_path_are_not_correct``).  The next
``benchmark`` PR should move the condition into the test's own list of cells
and delete this file (``PERF.md`` section 7).
"""

import pytest

from cellbench import run

ORSET_ONLY = "test_narrowed_counters_in_the_timed_path_are_not_correct"


def pytest_collection_modifyitems(config, items):
    for item in items:
        if (getattr(item, "originalname", None) != ORSET_ONLY
                or item.module.__name__ != "test_cellbench"):
            continue
        cell = item.callspec.params["cell"]
        crdt = run.load_cell(run.ROOT, cell)["config"].get("crdt", "orset")
        if crdt != "orset":
            item.add_marker(pytest.mark.skip(
                reason=f"{cell} is a {crdt}: the case breaks the OR-Set's "
                       "writebacks, which it never calls"))
