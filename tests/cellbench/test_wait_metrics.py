"""The nine metrics of the layer "host runtime: waits and pauses" (ISSUE 34):
the reader ``counter_per_call`` on hand-made windows, each metric file's
reader, arguments and driver, and one toy traced run a driver, whose line
carries the metrics where the program has the counters and leaves every one
of them out where it has not (the parent's program under these files).

Nothing here is a measurement: the toy runs are on the CPU at toy sizes.
"""

import json

import pytest

from cellbench import run
from cellbench.readers import counter_per_call, span_ms
from crdt_enc_tpu.obs import runtime as obs_runtime

import manifest_checks as checks
from test_cellbench import tiny

ROOT = run.ROOT
MANIFEST = run.load_json(ROOT, "BENCHMARK.json")
LAYER = "host runtime: waits and pauses"
FOLDER = ["orset_folder_1k.backlog", "orset_folder_1k.trickle"]
FLEET = ["orset_fleet_1024.busy", "orset_fleet_1024.quiet"]
# the cells of other drivers of the two families, which read the layer from
# the same entries since ISSUE 43 (PR 39 had given them copies of the files)
FOLDERS = FOLDER + ["orset_folder_peers.backlog", "orset_folder_peers_delta.backlog"]
FLEETS = FLEET + ["orset_fleet_zipf.busy"]


def counted(counters, scale, present):
    return {"reader": "counter_per_call",
            "args": {"counters": [counters], "scale": scale, "present": present}}


# metric -> (cells, moves, source, reader and args)
NINE = {
    "gc_pause_ms.folder": (
        FOLDERS, "compact_ms", "program_counter",
        counted("gc_pause_us", 0.001, "gc_passes")),
    "gc_full_pause_ms.folder": (
        FOLDERS, "compact_ms", "program_counter",
        counted("gc_full_pause_us", 0.001, "gc_passes")),
    "gc_pause_ms.fleet": (
        FLEETS, "serve_ops_per_s", "program_counter",
        counted("gc_pause_us", 0.001, "gc_passes")),
    "gc_full_pause_ms.fleet": (
        FLEETS, "seal_p95_ms", "program_counter",
        counted("gc_full_pause_us", 0.001, "gc_passes")),
    "slot_wait_ms.fleet": (
        FLEETS, "seal_p95_ms", "program_span",
        {"reader": "span_ms", "args": {"spans": ["serve.slot_wait"]}}),
    "ingest_job_queue_ms.fleet": (
        FLEETS, "serve_ops_per_s", "program_counter",
        counted("ingest_job_queue_us", 0.001, "ingest_job_queue_us")),
    "ingest_job_return_ms.fleet": (
        FLEETS, "serve_ops_per_s", "program_counter",
        counted("ingest_job_return_us", 0.001, "ingest_job_return_us")),
    "seal_job_queue_ms.fleet": (
        FLEETS, "seal_p95_ms", "program_counter",
        counted("seal_job_queue_us", 0.001, "seal_job_queue_us")),
    "seal_job_return_ms.fleet": (
        FLEETS, "seal_p95_ms", "program_counter",
        counted("seal_job_return_us", 0.001, "seal_job_return_us")),
}


def window(**kw):
    return {"calls": 4, "ops": 100, "spans": {}, "counters": {}, "trace": None,
            "shapes": [], "peaks": {}, **kw}


# --------------------------------------------------------- counter_per_call


def test_counter_per_call_sums_scales_and_divides_by_the_calls():
    w = window(counters={"a_us": 6000, "b_us": 2000, "here": 1, "other": 9})
    args = {"counters": ["a_us", "b_us"], "scale": 0.001, "present": "here"}
    assert counter_per_call.read(w, args) == pytest.approx(2.0)
    assert counter_per_call.read(w, {**args, "counters": ["a_us"]}) == pytest.approx(1.5)


def test_counter_per_call_scale_defaults_to_one():
    w = window(counters={"a": 6, "here": 1})
    assert counter_per_call.read(w, {"counters": ["a"], "present": "here"}) == 1.5


def test_counter_per_call_a_counter_the_window_never_bumped_counts_zero():
    """A window without a full pass: the tracker counted, and counted none."""
    w = window(counters={"gc_passes": 31, "gc_pause_us": 900})
    args = {"counters": ["gc_full_pause_us"], "scale": 0.001, "present": "gc_passes"}
    assert counter_per_call.read(w, args) == 0


@pytest.mark.parametrize("w", [
    # the parent's program: no tracker, so no 0 is read where nothing counted
    window(counters={"ops_folded": 5}),
    window(counters={"gc_pause_us": 900}),  # the counter alone is no evidence
    window(counters={"gc_passes": 3, "gc_pause_us": 900}, calls=0),
])
def test_counter_per_call_has_nothing_to_read(w):
    args = {"counters": ["gc_pause_us"], "scale": 0.001, "present": "gc_passes"}
    assert counter_per_call.read(w, args) is None


def test_a_metric_present_by_itself_is_left_out_of_a_window_that_lacks_it():
    args = NINE["seal_job_queue_ms.fleet"][3]["args"]
    assert counter_per_call.read(window(counters={"seal_jobs": 7}), args) is None
    w = window(counters={"seal_jobs": 8, "seal_job_queue_us": 4400})
    assert counter_per_call.read(w, args) == pytest.approx(1.1)


def test_slot_wait_is_summed_over_the_tenants_as_seal_ms_is():
    w = window(spans={"serve.slot_wait": {"count": 2016, "seconds": 8.0},
                      "serve.seal": {"count": 1024, "seconds": 4.0}})
    assert span_ms.read(w, NINE["slot_wait_ms.fleet"][3]["args"]) == pytest.approx(2000.0)
    seal = run.load_json(ROOT, "cellbench", "layer_metrics", "seal_ms.fleet.json")
    assert seal["reader"] == "span_ms"
    assert span_ms.read(window(), NINE["slot_wait_ms.fleet"][3]["args"]) is None


# ---------------------------------------------------------- the nine files


@pytest.mark.parametrize("metric", list(NINE))
def test_metric_file_reads_what_the_issue_names(metric):
    cells, moves, source, how = NINE[metric]
    spec = run.load_json(ROOT, "cellbench", "layer_metrics", metric + ".json")
    assert spec["reader"] == how["reader"] and spec["args"] == how["args"]
    assert spec["driver"] == metric.rsplit(".", 1)[1], "the family is the name's suffix"
    assert spec["layer"] == LAYER
    assert (spec["unit"], spec["better"]) == ("ms", "lower")
    assert spec["moves"] == moves and spec["source"] == source
    assert spec["what"]
    checks.check_layer_metric(MANIFEST, ROOT, metric)  # the entry is the file's


def check_the_nine(manifest: dict, root: str) -> None:
    """The nine are among the layer's metrics and the manifest's entries, in
    their order, each in its own cells.  No position and no count of the
    layer: later entries of it may follow."""
    checks.hold_metrics_in_order(manifest, list(NINE), layer=LAYER)
    for metric, (cells, moves, source, _) in NINE.items():
        # its own cells first among themselves; later cells of the family follow
        checks.hold_metric(manifest, metric, cells=cells, moves=moves, layer=LAYER,
                           source=source)
    for cell, n in [(c, 2) for c in FOLDERS] + [(c, 7) for c in FLEETS]:
        listed = [m["name"] for m in run.load_cell(root, cell)["per_layer"]]
        assert sum(name in NINE for name in listed) == n, cell


def test_the_nine_are_among_the_layers_metrics_in_their_order():
    check_the_nine(MANIFEST, ROOT)


# ------------------------------------------- a toy traced run of each driver


@pytest.mark.parametrize("cell", [FOLDER[0], FLEET[0]])
def test_toy_traced_line_carries_them_or_leaves_every_one_out(cell, capsys):
    """Where the program tracks the collector and counts its hand-offs (this
    tree's) the line carries the counter metrics of the cell; where it does
    not (the parent's program, which these files are laid over) the readers
    find nothing, raise nothing and the line leaves all nine out.  At a toy's
    six tenants nobody waits for one of sixteen slots: no ``slot_wait_ms``,
    and its file says so (``may_be_absent``)."""
    assert run.run_cell(cell, 2**31 + 34, 0.5, True, require_tpu=False,
                        shrink=tiny(cell)) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    got = {name for name in line["metrics"] if name in NINE}
    listed = {m["name"] for m in run.load_cell(ROOT, cell)["per_layer"]}
    if not hasattr(obs_runtime, "track_gc"):
        assert got == set()
        return
    specs = {m["name"]: m for m in run.load_cell(ROOT, cell)["per_layer"]}
    absent = {name for name in listed & set(NINE) if specs[name].get("may_be_absent")}
    assert absent == {"slot_wait_ms.fleet"} & listed
    assert got == (listed & set(NINE)) - absent
    values = {name: line["metrics"][name]["value"] for name in got}
    assert all(v >= 0 for v in values.values())
    driver = "folder" if cell in FOLDER else "fleet"
    assert values[f"gc_full_pause_ms.{driver}"] <= values[f"gc_pause_ms.{driver}"]
