"""The proof that a later PR can add a deployment, a mix, a cell (on one chip
or on four) and per-layer metrics as files and appended manifest entries, and
edit no file the benchmark has, its tests among them.

A temporary root gets ``BENCHMARK.json``, the data directories and the test
files, byte for byte, and then what such a PR would bring: a configuration
whose ``driver`` is a new module ``folder_scratch`` (a subclass of
``folder.Driver``) and whose name has no "folder" in it, a fifth mix, a
one-chip cell on them, a four-chip cell, and its per-layer metrics **both
ways in** (ISSUE 43): the cell takes ``storage_ms.folder`` and
``gc_pause_ms.folder`` by appending its name to those entries' ``workloads``
and nothing else (its driver is of the family ``folder``; no metric file is
touched, no copy made), and brings what is new as new files with new entries:
a span metric of the layer "host runtime: waits and pauses" whose file names
two families, a kernel metric with ``args.modules`` and the test file that
pins its module strings.  Every manifest-level check of the benchmark's tests
is then made on that root through the functions the tests themselves call,
the one-chip cell runs traced at toy size on the CPU with the shared and the
new metrics in its line, and what was there is still there, in its place.

**Every manifest-level assertion of every test file runs here** (ISSUE 49).
PR 45's test file pinned positions inline (``MANIFEST["configs"][-1]``,
``per_layer[-5:]``, ``workloads == [CELL]``) and this file, which ran only the
functions it knew, did not see them; ``test_metric_cell_pairs.py`` held the
count of entries at 84.  Now a test file reaches the manifest only through
``manifest_checks.py`` (``test_no_test_file_reaches_into_the_manifest...``
reads the sources), states what it holds in functions ``check_*(manifest,
root)``, and ``test_every_manifest_level_check_holds_on_the_augmented_root``
runs every such function of every file on the root with the additions, among
them a second configuration of a driver ``fleet_daemon_scratch`` whose cell
reads ``poll_ms.fleet_daemon`` by its name appended to that entry's
``workloads``, and the one-chip cell's name appended to
``gather_kernel_ms.folder``: ``== [CELL]`` and ``== SOLO + [CELL]`` fail there.
``test_the_pins_of_the_parent_fail...`` keeps the parent's assertions, word
for word, as the proof that they do.

Which case fails when a pin comes back (ISSUE 39, items 1-7):
``test_the_sets_the_tests_pin_are_subsets`` for "the nine are the manifest's
last / the layer's only" (1), "the zipf cell lists thirteen" (2) and "the
peers cell lists NEW + COPIED and no more" (3); ``test_toy_line...`` for a
toy line that must carry every listed host metric (3);
``test_overlay_is_chosen...`` and ``test_one_chip_cell_runs...`` for an
overlay chosen by the configuration's name or a mix looked up in a table (4);
``test_every_cell_agrees...`` for ``chips == 1`` (5);
``test_kernel_metric_is_pinned...`` for a table in ``test_new_readers.py``
that must hold every kernel metric (6); ``test_every_layer_metric_agrees...``
for a metric file's ``driver`` that must be one name, or the cell's own (7).

Nothing here is a measurement, and nothing scratch is in the real manifest.
"""

import filecmp
import glob
import json
import os
import re
import shutil
import sys
import types

import pytest

from cellbench import run
from cellbench.drivers import folder

import manifest_checks as checks
import test_fleet_daemon as daemon
import test_fleet_zipf as zipf
import test_folder_10k as ten_k
import test_folder_peers_delta as delta
import test_wait_metrics as waits

ROOT = run.ROOT
REAL = run.load_json(ROOT, "BENCHMARK.json")
CELL, CELL4 = "orset_scratch.drip", "orset_scratch.backlog"
# a second deployment a later PR adds: of the daemon's family, never run here
DAEMON_CELL, DAEMON_DRIVER = "orset_scratch_fleet.steady", "fleet_daemon_scratch"
SPAN = "scratch_gc_ms.folders"
# what the cell shares with the solo folder: taken by its name appended to
# these entries' ``workloads``, and by nothing else
SHARED = ["storage_ms.folder", "gc_pause_ms.folder"]
# every entry that gets a scratch cell appended, and the cells it gets: the
# last two are entries whose ``workloads`` a cell's test file once held whole
APPENDED = {**{name: [CELL] for name in SHARED},
            "gather_kernel_ms.folder": [CELL],
            "poll_ms.fleet_daemon": [DAEMON_CELL]}
DRIVER = "folder_scratch"
# in two parts, so that this file does not itself name the kernel metric whole
# and pass for the test file that pins it
KERNEL = "scratch_fold_kernel_ms" + ".folder"
PIN = f'''"""What a PR that brings a kernel metric brings with it."""
METRIC = "{KERNEL}"
MODULES = ["jit__fold_ablk", "jit_orset_fold"]
'''


def write(path, obj) -> None:
    path.write_text(json.dumps(obj, indent=2) + "\n")


@pytest.fixture(scope="module")
def added(tmp_path_factory):
    """``(root, manifest)`` of the temporary root with the additions."""
    root = tmp_path_factory.mktemp("additions")
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    bench = root / "cellbench"
    for sub in ("configs", "traffic", "cells", "layer_metrics"):
        shutil.copytree(os.path.join(ROOT, "cellbench", sub), bench / sub)
    shutil.copy(os.path.join(ROOT, "cellbench", "peaks.json"), bench)
    tests = root / "tests" / "cellbench"
    tests.mkdir(parents=True)
    for path in glob.glob(os.path.join(ROOT, "tests", "cellbench", "*.py")):
        shutil.copy(path, tests)

    config = run.load_json(ROOT, "cellbench", "configs", "orset_folder_1k.json")
    config.update(name="orset_scratch", driver=DRIVER)
    write(bench / "configs" / "orset_scratch.json", config)
    write(bench / "traffic" / "drip.json", {
        "name": "drip", "what": "every round three devices wrote one op file",
        "loop": "closed, one caller", "active_tenants": 1, "active_devices": 3,
        "files_per_device": 1, "warmup_rounds": 1, "max_ops_per_s": 2500})
    daemon = run.load_json(ROOT, "cellbench", "configs", "orset_fleet_daemon.json")
    daemon.update(name="orset_scratch_fleet", driver=DAEMON_DRIVER)
    write(bench / "configs" / "orset_scratch_fleet.json", daemon)
    cells = [
        {"name": CELL, "config": "orset_scratch", "traffic": "drip", "chips": 1,
         "why": "a cell a later PR adds: a deployment and a mix no test has heard of"},
        {"name": CELL4, "config": "orset_scratch", "traffic": "backlog", "chips": 4,
         "why": "stands for a cell whose state is sharded over four chips; never run here"},
        {"name": DAEMON_CELL, "config": "orset_scratch_fleet", "traffic": "steady",
         "chips": 1,
         "why": "a later cell of the daemon's family that reads poll_ms.fleet_daemon; never run here"},
    ]
    for cell in cells:
        write(bench / "cells" / (cell["name"] + ".json"), cell)
    span = {"name": SPAN, "unit": "ms", "better": "lower", "source": "program_span",
            "layer": waits.LAYER, "moves": "compact_ms"}
    write(bench / "layer_metrics" / (SPAN + ".json"), {
        **span, "driver": [DRIVER, "folder_peers_delta"], "reader": "span_ms",
        "args": {"spans": ["compact.gc"]}})
    kernel = {"name": KERNEL, "unit": "ms", "better": "lower", "source": "device_trace",
              "layer": "fold kernels", "moves": "compact_ops_per_s"}
    write(bench / "layer_metrics" / (KERNEL + ".json"), {
        **kernel, "driver": "folder", "reader": "trace_modules_ms",
        "args": {"line": "XLA Modules", "modules": ["_fold_ablk", "orset_fold"]}})
    (tests / "test_scratch_kernel.py").write_text(PIN)

    manifest = json.loads((root / "BENCHMARK.json").read_text())
    manifest["configs"].append({
        "name": "orset_scratch", "source": config["source"],
        "file": "cellbench/configs/orset_scratch.json",
        "reduced": sorted(config["reduced"]), "why": "a later PR's deployment"})
    manifest["configs"].append({
        "name": "orset_scratch_fleet", "source": daemon["source"],
        "file": "cellbench/configs/orset_scratch_fleet.json",
        "reduced": sorted(daemon["reduced"]), "why": "a later PR's daemon deployment"})
    manifest["workloads"] += cells
    manifest["per_layer"].append({**span, "workloads": [
        CELL, CELL4, "orset_folder_peers_delta.backlog"]})
    manifest["per_layer"].append({**kernel, "workloads": [CELL]})
    for name, new in APPENDED.items():
        checks.entry_of(manifest, "per_layer", name)["workloads"] += new
    for m in manifest["end_to_end"]:
        if m["name"] in ("compact_ms", "compact_ops_per_s"):
            m["workloads"] += [CELL, CELL4]
        if m["name"] == "seal_p95_ms":
            m["workloads"].append(DAEMON_CELL)
    write(root / "BENCHMARK.json", manifest)
    return str(root), manifest


@pytest.fixture
def scratch_driver(monkeypatch):
    """The driver module such a PR brings as ``cellbench/drivers/folder_scratch.py``
    (code is always the checkout's, so here it is put where the import finds it)."""
    module = types.ModuleType(f"cellbench.drivers.{DRIVER}")
    module.Driver = type("Driver", (folder.Driver,), {})
    monkeypatch.setitem(sys.modules, module.__name__, module)


# ----------------------------------------- (c) what was there is still there


def test_additions_are_a_suffix_of_each_list_and_nothing_else(added):
    root, manifest = added
    same = lambda a, b: json.dumps(a) == json.dumps(b)  # noqa: E731
    for kind in ("configs", "workloads"):
        assert same(manifest[kind][:len(REAL[kind])], REAL[kind]), kind
        assert len(manifest[kind]) > len(REAL[kind])
    # a metric entry is what it was; a cell that reports the metric is
    # appended to its ``workloads``, as every PR that added a cell has done
    # with the end-to-end entries and, since ISSUE 43, does with the per-layer
    # entries whose spans and counters it shares
    assert len(manifest["end_to_end"]) == len(REAL["end_to_end"])
    assert len(manifest["per_layer"]) == len(REAL["per_layer"]) + 2
    for kind in ("end_to_end", "per_layer"):
        for got, was in zip(manifest[kind], REAL[kind]):
            cells = was.get("workloads", [])
            assert same({**got, "workloads": got.get("workloads", [])[:len(cells)]},
                        {**was, "workloads": cells})
            added_cells = got.get("workloads", [])[len(cells):]
            if kind == "per_layer":
                assert added_cells == APPENDED.get(got["name"], []), got["name"]
    for key in ("command", "paths", "run_seconds"):
        assert manifest[key] == REAL[key]
    # and every file that was there, the tests among them, byte for byte
    for sub, pattern in [("tests/cellbench", "*.py")] + [
            (f"cellbench/{d}", "*.json")
            for d in ("configs", "traffic", "cells", "layer_metrics")]:
        for path in glob.glob(os.path.join(ROOT, sub, pattern)):
            copy = os.path.join(root, sub, os.path.basename(path))
            assert filecmp.cmp(path, copy, shallow=False), f"{path} was edited"


# --------------------- (a) every manifest-level check, on the root with them


def test_contract_keys_names_and_units_hold(added):
    root, manifest = added
    checks.check_contract_keys(manifest, root)
    checks.check_names_and_units(manifest, root)


def test_every_cell_agrees_and_one_of_nine_may_ask_for_four_chips(added):
    root, manifest = added
    assert len(manifest["workloads"]) == len(REAL["workloads"]) + 3
    for cell in checks.cells(manifest):
        checks.check_cell(manifest, root, cell)
    assert checks.entry_of(manifest, "workloads", CELL4)["chips"] == 4
    checks.check_four_chip_share(manifest, root)


def test_every_layer_metric_agrees_and_a_file_may_name_several_drivers(added):
    root, manifest = added
    for m in manifest["per_layer"]:
        checks.check_layer_metric(manifest, root, m["name"])
    # the shared entries admit the cell by its driver's prefix, with their files
    # byte for byte what they were (``test_additions_are_a_suffix...``)
    for name in SHARED:
        assert CELL in checks.entry_of(manifest, "per_layer", name)["workloads"]
        assert run.load_json(root, "cellbench", "layer_metrics", name + ".json")["driver"] == "folder"
    assert checks.config_of(manifest, root, CELL)["driver"] == DRIVER
    # a fleet's entry does not: the family is a prefix of the driver module's name
    wrong = json.loads(json.dumps(manifest))
    checks.entry_of(wrong, "per_layer", "seal_ms.fleet")["workloads"].append(CELL)
    with pytest.raises(AssertionError):
        checks.check_layer_metric(wrong, root, "seal_ms.fleet")
    checks.check_no_two_files_define_the_same(manifest, root)
    # the span metric lists cells of two families; a driver of neither fails
    wrong = json.loads(json.dumps(manifest))
    checks.entry_of(wrong, "per_layer", SPAN)["workloads"].append("orset_folder_peers.backlog")
    with pytest.raises(AssertionError):
        checks.check_layer_metric(wrong, root, SPAN)


def test_the_sets_the_tests_pin_are_subsets(added):
    root, manifest = added
    names = [m["name"] for m in manifest["per_layer"]]
    assert names[-2:] == [SPAN, KERNEL], "appended: nothing of the nine is last"
    assert checks.entry_of(manifest, "per_layer", SPAN)["layer"] == waits.LAYER
    waits.check_the_nine(manifest, root)
    zipf.check_the_thirteen(manifest, root)
    assert len(checks.listed(root, zipf.CELL)) > 13
    delta.check_new_and_copied_are_listed(manifest, root)
    assert SPAN in checks.listed(root, delta.CELL)


def test_kernel_metric_is_pinned_by_the_test_file_it_brings(added):
    root, _ = added
    assert checks.kernel_strings(root)[KERNEL] == ["_fold_ablk", "orset_fold"]
    for metric in checks.kernel_strings(root):
        checks.check_kernel_metric_is_pinned(root, metric)
    assert KERNEL not in checks.kernel_strings(ROOT)
    # without the file it brings, no test the benchmark has names it
    pin = os.path.join(root, "tests", "cellbench", "test_scratch_kernel.py")
    os.rename(pin, pin + ".away")
    try:
        with pytest.raises(AssertionError, match="new test file"):
            checks.check_kernel_metric_is_pinned(root, KERNEL)
    finally:
        os.rename(pin + ".away", pin)


def test_overlay_is_chosen_by_the_driver_and_a_fifth_mix_by_its_file(added):
    root, manifest = added
    assert "folder" not in checks.entry_of(manifest, "workloads", CELL)["config"]
    assert checks.tiny(manifest, root, CELL) == {
        "config": checks.TINY["folder"],
        "traffic": {"active_devices": 3, "max_ops_per_s": 2500}}
    # a mix that asks for more writers than the toy deployment has gets them all
    assert checks.tiny(manifest, root, CELL4)["traffic"]["active_devices"] == 8
    for cell in checks.cells(REAL):
        assert checks.tiny(manifest, root, cell) == checks.tiny(REAL, ROOT, cell)


# ------------- every manifest-level check of every test file (ISSUE 49)

# found when this file is collected, so that each is a case of its own; the
# test files are imported from the checkout, as a later PR's would be
EVERY_CHECK = checks.manifest_level_checks()
# what a test file may not do with the real manifest: subscript it, iterate
# over it, or take an entry out of it (``entry_of`` is for ``manifest_checks``)
REACHES_IN = re.compile(r"\b(MANIFEST|REAL)\s*\[|\bin\s+(MANIFEST|REAL)\b"
                        r"|entry_of\(\s*(MANIFEST|REAL)\b")


def test_the_checks_are_found_in_this_module_and_in_the_cells_test_files():
    modules = {name.split(".")[0] for name in EVERY_CHECK}
    assert {"manifest_checks", "test_fleet_daemon", "test_folder_10k", "test_wait_metrics",
            "test_fleet_zipf", "test_folder_peers", "test_folder_peers_delta",
            "test_chunk_reads_metric"} <= modules
    assert {"manifest_checks.check_list_lengths", "manifest_checks.check_every_cell",
            "manifest_checks.check_every_layer_metric",
            "test_fleet_daemon.check_the_daemons_entries"} <= set(EVERY_CHECK)
    # a check of ``manifest_checks`` with other parameters is reached through
    # one that takes a manifest and a root
    per_item = {name for name, fn in vars(checks).items()
                if name.startswith("check_") and callable(fn)
                and "manifest_checks." + name not in EVERY_CHECK}
    assert per_item == {"check_cell", "check_pair", "check_layer_metric",
                        "check_kernel_metric_is_pinned", "check_toy_line"}


@pytest.mark.parametrize("name", sorted(EVERY_CHECK))
def test_every_manifest_level_check_holds_on_the_augmented_root(added, name):
    root, manifest = added
    EVERY_CHECK[name](manifest, root)


def test_no_test_file_reaches_into_the_manifest_but_through_manifest_checks():
    """The other half of the proof: what is not in a ``check_*(manifest,
    root)`` cannot look at the manifest's lists at all."""
    this = os.path.basename(__file__)
    paths = sorted(glob.glob(os.path.join(ROOT, "tests", "cellbench", "test_*.py")))
    assert len(paths) > 10
    for path in paths:
        if os.path.basename(path) == this:
            continue  # compares the augmented manifest with the real one
        with open(path) as fh:
            for n, text in enumerate(fh, 1):
                assert not REACHES_IN.search(text), (
                    f"{path}:{n} reaches into the manifest: state it in a "
                    f"check_*(manifest, root) built from manifest_checks.hold_*")
    for text in ('assert MANIFEST["configs"][-1]["name"] == x', "for m in MANIFEST:",
                 'checks.entry_of(MANIFEST, "per_layer", name)["workloads"] == [CELL]',
                 'len(REAL ["per_layer"]) <= 84'):
        assert REACHES_IN.search(text), text
    for text in ("checks.check_cell(MANIFEST, ROOT, cell)", "TOY = checks.toy(MANIFEST, ROOT, CELL)",
                 "manifest = json.loads(json.dumps(MANIFEST))"):
        assert not REACHES_IN.search(text), text


def parents_daemon_pins(manifest: dict, root: str) -> None:
    """``test_fleet_daemon.py:78-92`` of the parent (PR 45), word for word
    but for ``MANIFEST`` and ``ROOT``."""
    cell = daemon.CELL
    assert manifest["configs"][-1]["name"] == "orset_fleet_daemon"
    assert manifest["workloads"][-1] == {
        k: run.load_cell(root, cell)["cell"][k]
        for k in ("name", "config", "traffic", "chips", "why")}
    for metric in ("serve_ops_per_s", "seal_p95_ms"):
        assert checks.entry_of(manifest, "end_to_end", metric)["workloads"][-1] == cell
    assert [m["name"] for m in manifest["per_layer"][-5:]] == daemon.FIVE
    for name in daemon.NEW:
        assert checks.entry_of(manifest, "per_layer", name)["workloads"] == [cell]


def test_the_pins_of_the_parent_fail_on_the_augmented_root(added):
    """What this file did not see until ISSUE 49, it sees."""
    root, manifest = added
    with pytest.raises(AssertionError):
        parents_daemon_pins(manifest, root)
    with pytest.raises(AssertionError):  # test_metric_cell_pairs.py:75 of the parent
        assert len(manifest["per_layer"]) <= 84
    # the one line of the five that the appended configurations do not reach
    entry = checks.entry_of(manifest, "per_layer", "poll_ms.fleet_daemon")
    assert entry["workloads"] != [daemon.CELL] and daemon.CELL in entry["workloads"]
    # and the 10,000-device cell's: ``== SOLO + [CELL]``
    entry = checks.entry_of(manifest, "per_layer", "gather_kernel_ms.folder")
    assert entry["workloads"][:3] == ten_k.SOLO + [ten_k.CELL] != entry["workloads"]


# ------------------------- (b) the one-chip cell, end to end at toy size


@pytest.mark.usefixtures("scratch_driver")
def test_one_chip_cell_runs_and_its_traced_line_carries_shared_and_new_metrics(added, capsys):
    root, manifest = added
    shrink = checks.tiny(manifest, root, CELL)
    assert run.run_cell(CELL, 2**31 + 39, 0.5, True, root=root,
                        require_tpu=False, shrink=shrink) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert set(line) == {"correct", "attempted", "failed", "metrics", "device",
                         "breakdown", "compared"}
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {SPAN, *SHARED}, (
        "the kernel metric has no device trace to read")
    assert line["metrics"][SPAN]["value"] > 0 and line["metrics"][SPAN]["unit"] == "ms"
    assert line["metrics"]["storage_ms.folder"]["value"] > line["metrics"][SPAN]["value"], (
        "compact.gc is one of the four spans storage_ms sums")
    checks.check_toy_line(root, CELL, line["metrics"])
    assert run.run_cell(CELL, 2**31 + 39, 0.5, False, root=root,
                        require_tpu=False, shrink=shrink) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True
    assert set(line["metrics"]) == {"compact_ms", "compact_ops_per_s", "setup_s"}


def test_toy_line_may_lack_only_what_a_metric_file_says_it_may(added):
    root, _ = added
    checks.check_toy_line(root, CELL, {SPAN, *SHARED})
    with pytest.raises(AssertionError):
        checks.check_toy_line(root, CELL, {SPAN})           # a listed, shared host metric
    with pytest.raises(AssertionError):
        checks.check_toy_line(root, CELL, {SPAN, *SHARED, "unlisted_ms.folder"})
    with pytest.raises(AssertionError):
        checks.check_toy_line(root, CELL, {SPAN, *SHARED, KERNEL})  # no trace on the CPU
    # the slot wait says ``may_be_absent``: a toy fleet opens no such span
    listed = checks.listed(root, zipf.CELL)
    assert listed["slot_wait_ms.fleet"]["may_be_absent"] is True
    host = {name for name, spec in listed.items() if spec["source"] != "device_trace"}
    checks.check_toy_line(root, zipf.CELL, host - {"slot_wait_ms.fleet"})
    with pytest.raises(AssertionError):
        checks.check_toy_line(root, zipf.CELL, host - {"seal_job_pct.fleet"})
