"""One case for every (per-layer metric, cell) pair the manifest lists: the
cell's driver is of the metric's family, the cell reports the end-to-end
metric that the metric moves, and a traced CPU toy line of the cell carries
the metric, unless it reads the device trace or its own file says
``may_be_absent``.  That last is the rule PR 40 was refused for (a listed
metric of a span the delivered program could not reach was missing from the
traced line), held for every pair and not only for the cells whose own test
file thought of it.

A metric is one file and one entry, whatever the number of cells that read
it (ISSUE 43): no two files define the same thing under two names.

Nothing here is a measurement: the toy runs are on the CPU at toy sizes, one
a cell, shared by the cell's pairs.
"""

import contextlib
import functools
import io
import json
import os
import shutil

import pytest

from cellbench import run

import manifest_checks as checks

ROOT = run.ROOT
MANIFEST = run.load_json(ROOT, "BENCHMARK.json")
PAIRS = checks.pairs(MANIFEST)


@functools.lru_cache(maxsize=None)
def toy_line(cell: str) -> dict:
    """The result line of one traced toy run of ``cell``."""
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        assert run.run_cell(cell, 2**31 + 43, 0.5, True, require_tpu=False,
                            shrink=checks.toy(MANIFEST, ROOT, cell)) == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


listed = functools.lru_cache(maxsize=None)(functools.partial(checks.listed, ROOT))


def test_every_pair_is_a_case_and_no_pair_is_listed_twice():
    assert len(PAIRS) == len(set(PAIRS)) >= 175, "the pairs PR 42 listed, and more"
    assert {cell for _, cell in PAIRS} == set(checks.cells(MANIFEST))


@pytest.mark.parametrize("metric, cell", PAIRS)
def test_pair_is_of_the_family_moves_what_the_cell_reports_and_is_in_its_toy_line(
        metric, cell):
    checks.check_pair(MANIFEST, ROOT, metric, cell)
    line = toy_line(cell)
    assert line["correct"] is True and line["failed"] == 0
    spec = listed(cell)[metric]
    if checks.demanded(spec):
        assert metric in line["metrics"], (
            f"{cell} lists {metric} and its toy line has no number for it")
    if metric in line["metrics"]:
        assert spec["source"] != "device_trace", "the CPU has no device trace to read"
        assert line["metrics"][metric]["unit"] == spec["unit"]


@pytest.mark.parametrize("cell", checks.cells(MANIFEST))
def test_toy_line_carries_nothing_unlisted_and_everything_listed(cell):
    checks.check_toy_line(ROOT, cell, toy_line(cell)["metrics"])


def test_no_two_metric_files_define_the_same_thing():
    checks.check_no_two_files_define_the_same(MANIFEST, ROOT)
    # and as many entries as the driver admits, which this file does not
    # restate (ISSUE 49: the count it held, 84, refused every later metric and
    # guarded nothing the next test does not)
    checks.check_list_lengths(MANIFEST, ROOT)


def test_a_copy_of_a_file_under_another_name_is_refused(tmp_path):
    """What PRs 26 to 41 did for want of another way: it fails now."""
    shutil.copytree(os.path.join(ROOT, "cellbench", "layer_metrics"),
                    tmp_path / "cellbench" / "layer_metrics")
    spec = run.load_json(ROOT, "cellbench", "layer_metrics", "storage_ms.folder.json")
    copy = {**spec, "name": "storage_ms.folder_x", "driver": "folder_x",
            "what": "the same spans for another cell"}
    (tmp_path / "cellbench" / "layer_metrics" / "storage_ms.folder_x.json").write_text(
        json.dumps(copy))
    manifest = json.loads(json.dumps(MANIFEST))
    entry = checks.entry_of(manifest, "per_layer", "storage_ms.folder")
    manifest["per_layer"].append({**entry, "name": "storage_ms.folder_x"})
    with pytest.raises(AssertionError, match="storage_ms.folder again"):
        checks.check_no_two_files_define_the_same(manifest, str(tmp_path))


@pytest.mark.parametrize("drivers, admitted, refused", [
    ("folder", ["folder", "folder_peers", "folder_peers_delta", "folder_10k",
                "folder_scratch"], ["fleet", "fleet_zipf"]),
    ("folder_peers", ["folder_peers", "folder_peers_delta"], ["folder", "folder_10k"]),
    ("folder_peers_delta", ["folder_peers_delta"], ["folder_peers", "folder"]),
    ("fleet", ["fleet", "fleet_zipf"], ["folder"]),
    (["fleet_zipf", "folder_10k"], ["fleet_zipf", "folder_10k"], ["fleet", "folder"]),
])
def test_a_metric_files_driver_names_a_family_by_prefix(drivers, admitted, refused):
    family = checks.families({"driver": drivers})
    assert all(d.startswith(family) for d in admitted)
    assert not any(d.startswith(family) for d in refused)
