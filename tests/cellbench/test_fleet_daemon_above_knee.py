"""The fleet daemon above its knee (ISSUE 49): the mix against ``steady.json``'s
knee, what the cell is judged on and what it reads, the rows classes the
set-up folds (``shape_files``), the driver's mix being its cell's own, and the
cell end to end at toy size on the CPU.  ``test_fleet_daemon.py`` holds the
driver itself (the clock, which seal takes a file in, the two questions).
Nothing here is a measurement: a toy fleet of six tenants never fills
``batch``, so the cap binds only at the cell's own size, on the chip.
"""

import contextlib
import io
import json
import math

import pytest

from cellbench import gen, run
from cellbench.drivers import fleet_daemon

import manifest_checks as checks

ROOT = run.ROOT
MANIFEST = run.load_json(ROOT, "BENCHMARK.json")
CELL, STEADY = "orset_fleet_daemon.above_knee", "orset_fleet_daemon.steady"
# what moves seal_p95_ms in the steady cell: the cell reads all twelve by its
# name appended to their ``workloads``, and brings no file of its own
TWELVE = [m + ".fleet" for m in (
    "seal_ms", "seal_wall_ms", "noop_cycles_pct", "gc_full_pause_ms", "slot_wait_ms",
    "seal_job_queue_ms", "seal_job_return_ms")] + [m + ".fleet_daemon" for m in (
    "poll_ms", "pace_ms", "select_ms", "selected_per_cycle", "deferred_per_cycle")]
TOY = checks.toy(MANIFEST, ROOT, CELL)


def mix_file(name: str) -> dict:
    return run.load_json(ROOT, "cellbench", "traffic", name + ".json")


# ------------------------------------------------- the files and the manifest


def check_the_cell_above_the_knee(manifest: dict, root: str) -> None:
    """The cell is on the daemon's own configuration, is judged on
    ``seal_p95_ms`` and on nothing else (``serve_ops_per_s`` reads the offered
    rate wherever the loop reaches a steady state), and reads the twelve, each
    after the steady cell; whatever else lists it moves that metric too
    (``check_pair``: it reports no other)."""
    checks.hold_cell(manifest, root, CELL, config="orset_fleet_daemon",
                     traffic="above_knee", chips=1, end_to_end=["seal_p95_ms"])
    for metric in TWELVE:
        checks.hold_metric(manifest, metric, cells=[STEADY, CELL], moves="seal_p95_ms")
    listed = checks.hold_cell_lists(root, CELL, TWELVE)
    assert all(spec["moves"] == "seal_p95_ms" for spec in listed.values())


def test_the_cell_is_judged_on_the_tail_and_reads_the_twelve():
    check_the_cell_above_the_knee(MANIFEST, ROOT)
    cell = run.load_cell(ROOT, CELL)["cell"]
    assert "serve_ops_per_s" in cell["judged_on"] and "offer" in cell["why"]
    assert "the batch cap" in cell["exercises"]


def test_the_mix_is_an_open_loop_well_above_the_steady_mixes_knee():
    mix, steady = mix_file("above_knee"), mix_file("steady")
    assert mix["loop"].startswith("open") and mix["tick_s"] == steady["tick_s"] == 0.25
    assert mix["active_devices"] == mix["files_per_device"] == 1
    assert run.load_cell(ROOT, CELL)["traffic"] == mix
    offered, knee = mix["offered"], steady["offered"]["knee"]
    for key in ("files_per_s", "ops_per_s", "active_tenants", "seed", "date", "device"):
        assert offered["knee"][key] == knee[key], "the knee is cited, not measured again"
    files_per_s = mix["active_tenants"] / mix["tick_s"]
    assert (offered["files_per_s"], offered["ops_per_s"]) == (files_per_s, 24 * files_per_s)
    assert offered["share_of_knee"] >= 1.5
    assert offered["share_of_knee"] == round(files_per_s / knee["files_per_s"], 2) == 1.67
    # no bursts, no hot set, no churn: the generator's uniform draw, as steady's
    assert set(mix) - set(steady) == {"shape_files", "shape_files_why"}
    assert {k for k in steady if k not in mix} == set()


def test_the_warm_up_and_the_clock_cover_what_the_window_needs():
    mix, steady = mix_file("above_knee"), mix_file("steady")
    daemon = run.load_cell(ROOT, CELL)["config"]
    blocks = math.ceil(daemon["tenants"] / daemon["daemon"]["batch"])
    assert mix["warmup_rounds"] >= steady["warmup_rounds"] + blocks == 13
    assert mix["warmup_rounds"] % 2 == 1, "a toy fleet in lockstep seals in its one timed step"
    ticks = gen.rounds_for(mix, daemon, 30)
    assert ticks * mix["tick_s"] >= mix["min_clock_s"]
    # warm-up steps of some 3.5 s, the window, a step's overrun: a third to spare
    assert mix["min_clock_s"] >= (mix["warmup_rounds"] * 3.5 + 30 + 4) * 4 / 3


# -------------------------------------- the rows classes the set-up folds


def test_one_cycle_a_rows_class_and_three_files_where_the_mix_says_nothing():
    assert fleet_daemon.shape_file_counts(3, 24) == [1, 2, 3], "the steady cell's 27 shapes"
    assert fleet_daemon.shape_file_counts(5, 24) == [1, 2, 3]
    assert fleet_daemon.shape_file_counts(21, 24) == [1, 2, 3, 6, 11]
    assert fleet_daemon.shape_file_counts(22, 24) == [1, 2, 3, 6, 11, 22]
    assert fleet_daemon.SHAPE_FILES == 3 and "shape_files" not in mix_file("steady")
    most = mix_file("above_knee")["shape_files"]
    assert most > fleet_daemon.SHAPE_FILES and mix_file("above_knee")["shape_files_why"]
    # the most a visit may find compiled is the last count of its class
    assert fleet_daemon.shape_file_counts(most + 1, 24) != fleet_daemon.shape_file_counts(most, 24)


def driver_of(cell: str, monkeypatch, tmp_path, **over):
    monkeypatch.setattr(fleet_daemon, "refuse_unless_daemon_serves", lambda config: None)
    loaded = run.load_cell(ROOT, cell)
    config = {**loaded["config"], **TOY["config"]}
    traffic = {**loaded["traffic"], "active_tenants": 3, **over}
    return fleet_daemon.Driver(config, gen.plan_run(config, traffic, 49, 4), str(tmp_path))


def test_the_driver_takes_its_cells_own_mix_from_the_plan(monkeypatch, tmp_path):
    """Two cells on one configuration: which mix is the driver's is the
    plan's ``traffic`` (what the harness loaded for the cell, under any
    overlay), never the first open mix the manifest has for the deployment."""
    above = driver_of(CELL, monkeypatch, tmp_path)
    steady = driver_of(STEADY, monkeypatch, tmp_path)
    assert above.shape_files == mix_file("above_knee")["shape_files"]
    assert steady.shape_files == 3, "absent gives 3"
    assert above.plan.traffic["name"] == "above_knee" and steady.plan.traffic["name"] == "steady"
    # the clock's ticks are the mix's own: 110 s against 60 s
    assert above.plan.n_rounds == 440 and steady.plan.n_rounds == 240
    assert driver_of(CELL, monkeypatch, tmp_path, shape_files=7).shape_files == 7


def test_a_closed_loop_mix_is_refused_in_one_line(monkeypatch, tmp_path):
    with pytest.raises(SystemExit, match="needs an open-loop mix"):
        driver_of(CELL, monkeypatch, tmp_path, loop="closed, one caller")


# --------------------------------------------- the cell, through run_cell


def run_toy(traced: bool, fault=None, seed=2**31 + 49) -> tuple:
    out, err = io.StringIO(), io.StringIO()
    with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
        assert run.run_cell(CELL, seed, 0.5, traced, require_tpu=False,
                            shrink=TOY, fault=fault) == 0
    return json.loads(out.getvalue().strip().splitlines()[-1]), err.getvalue()


def test_traced_toy_line_carries_exactly_what_the_cell_lists_less_the_device_traces():
    line, err = run_toy(True)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    checks.check_toy_line(ROOT, CELL, line["metrics"])
    listed = checks.listed(ROOT, CELL)
    absent = set(listed) - set(line["metrics"])
    assert absent == {n for n, s in listed.items() if not checks.demanded(s)}
    assert absent == {"slot_wait_ms.fleet"}, "nobody waits for a slot in a toy fleet"
    # a toy never fills ``batch``: 0, and not nothing
    assert line["metrics"]["deferred_per_cycle.fleet_daemon"]["value"] == 0
    assert line["compared"] == {name: {"value": 0, "limit": 0} for name in (
        "tenants_vs_reference", "fresh_replicas_vs_reference",
        "fresh_replica_bytes_vs_served", "files_never_sealed")}
    steps = [l for l in err.splitlines() if l.startswith("cellbench: step ")]
    assert len(steps) >= 14, "thirteen warm-up steps under the clock, then the window"
    assert all(" in the fullest visit, " in l for l in steps)
    shapes = len(fleet_daemon.shape_file_counts(TOY["traffic"]["shape_files"], 24))
    assert f" {4 * shapes} bucket shapes folded once" in err, "slots 8, 4, 2, 1"


def test_untraced_toy_line_has_the_tail_and_set_up_and_no_rate():
    line, _ = run_toy(False, seed=2**31 + 50)
    assert line["correct"] is True
    assert set(line["metrics"]) == {"seal_p95_ms", "setup_s"}


def test_control_a_withheld_file_fails_the_cell_by_files_never_sealed():
    line, err = run_toy(False, fault="withhold_file", seed=2**31 + 51)
    assert line["correct"] is False
    assert line["compared"]["files_never_sealed"]["value"] >= 1
    assert line["compared"]["files_never_sealed"]["limit"] == 0
    assert "check files_never_sealed: value" in err and " FAILED\n" in err


def test_narrowed_counters_in_the_timed_path_fail_the_cell(monkeypatch):
    """The timed path broken underneath, as ``test_cellbench.py`` breaks the
    closed-loop cells': what the service's fold hands back loses every counter
    bit above the low 7-bit limb.  The rest of the run is driven as always
    (the daemon picks, folds, seals; every file is in a seal) and must say
    ``correct: false`` by the served states and by the fresh replicas'."""
    import crdt_enc_tpu.ops as K

    whole = K.orset_planes_to_state
    monkeypatch.setattr(
        K, "orset_planes_to_state",
        lambda clock, add, rm, members, replicas: whole(
            clock & 0x7F, add & 0x7F, rm & 0x7F, members, replicas))
    line, _ = run_toy(False, seed=2**31 + 52)
    assert line["correct"] is False
    assert line["compared"]["tenants_vs_reference"]["value"] > 0
    assert line["compared"]["fresh_replicas_vs_reference"]["value"] > 0
    assert line["compared"]["files_never_sealed"] == {"value": 0, "limit": 0}
