"""The two per-layer metrics of a solo folder's native reads and file steps
(PR 39): ``native_chunk_reads_pct.folder`` whole, and
``native_file_steps_pct.folder``, which is the fleet's file with another
``moves`` and layer (a folder has no serve tail), so a definition of its own.
The nineteen other files PR 39 brought were copies for cells of other drivers;
since ISSUE 43 those cells are listed by the originals' entries
(``test_metric_cell_pairs.py`` holds every pair).  A CPU toy run of the folder
driver reads the share of native read windows as 100, and a window without
the counters leaves it out.

Nothing here is a measurement: the toy run is on the CPU at toy sizes.
"""

import json

import pytest

from cellbench import run
from cellbench.readers import counter_ratio

import manifest_checks as checks
from test_cellbench import tiny

ROOT = run.ROOT
MANIFEST = run.load_json(ROOT, "BENCHMARK.json")
FOLDER = ["orset_folder_1k.backlog", "orset_folder_1k.trickle"]

# PERF.md section 7 "After PR 35" gave the file whole
CHUNK_READS = {
    "layer": "storage list/load/GC", "unit": "%", "better": "higher",
    "source": "program_counter", "moves": "compact_ops_per_s",
    "reader": "counter_ratio",
    "args": {"num": ["fs_chunk_reads_native"],
             "den": ["fs_chunk_reads_native", "fs_chunk_reads_python"],
             "scale": 100},
}

# file -> (the file it was made from, the keys in which it differs from it)
NEW = {
    "native_chunk_reads_pct.folder": (None, {}),
    # the folder has no serve seal tail: its file steps are the snapshot's
    # publish, the local meta's replace and the GC lists of one compact()
    "native_file_steps_pct.folder": (
        "native_file_steps_pct.fleet",
        {"moves": "compact_ms", "layer": "storage list/load/GC"}),
}


def spec_of(metric: str) -> dict:
    return run.load_json(ROOT, "cellbench", "layer_metrics", metric + ".json")


@pytest.mark.parametrize("metric", list(NEW))
def test_file_is_what_it_was_made_from_but_for_what_differs(metric):
    original, differs = NEW[metric]
    spec = spec_of(metric)
    assert (spec["name"], spec["driver"]) == (metric, "folder")
    want = {**CHUNK_READS, "what": spec["what"]} if original is None else spec_of(original)
    assert spec == {**want, **differs, "name": metric, "driver": "folder"}
    assert spec["what"]
    checks.check_layer_metric(MANIFEST, ROOT, metric)  # the entry is the file's


def check_the_solo_folders_cells_read_both(manifest: dict, root: str) -> None:
    for metric in NEW:
        checks.hold_metric(manifest, metric, cells=FOLDER)


def test_the_solo_folders_cells_are_listed_in_their_order():
    check_the_solo_folders_cells_read_both(MANIFEST, ROOT)


@pytest.mark.parametrize("w", [
    {"ops_folded": 5},                                     # the parent of PR 35
    {"fs_chunk_reads_native": 0, "fs_chunk_reads_python": 0},
])
def test_a_window_without_the_counters_leaves_the_share_out(w):
    window = {"calls": 3, "ops": 100, "spans": {}, "counters": w, "trace": None,
              "shapes": [], "peaks": {}}
    assert counter_ratio.read(window, CHUNK_READS["args"]) is None


def test_a_window_that_fell_back_reads_less_than_all():
    window = {"calls": 3, "ops": 100, "spans": {}, "trace": None, "shapes": [],
              "peaks": {}, "counters": {"fs_chunk_reads_native": 6,
                                        "fs_chunk_reads_python": 2}}
    assert counter_ratio.read(window, CHUNK_READS["args"]) == 75.0


def test_toy_run_of_the_folder_driver_reads_every_window_natively(capsys):
    cell = FOLDER[0]
    assert run.run_cell(cell, 2**31 + 39, 0.5, True, require_tpu=False,
                        shrink=tiny(cell)) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    value = {k: v["value"] for k, v in line["metrics"].items()}
    assert value["native_chunk_reads_pct.folder"] == 100
    assert value["native_file_steps_pct.folder"] == 100
    assert line["metrics"]["native_chunk_reads_pct.folder"]["unit"] == "%"
