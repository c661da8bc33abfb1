"""The fleet daemon's cell (ISSUE 45): its configuration against the fleet's
and against ``DaemonConfig``, the open-loop clock, which seal a file is
credited to, the driver's two questions, and the cell end to end at toy size
on the CPU (the daemon-served states and a fresh replica's bytes against
``cellbench/reference.py``, the control, the traced line).  Nothing here is a
measurement.
"""

import asyncio
import contextlib
import dataclasses
import io
import json
import time
import types

import pytest

from cellbench import gen, run
from cellbench.drivers import fleet_daemon
from crdt_enc_tpu.serve import DaemonConfig, FleetDaemon, ServeConfig

import manifest_checks as checks

ROOT = run.ROOT
MANIFEST = run.load_json(ROOT, "BENCHMARK.json")
CELL = "orset_fleet_daemon.steady"
LAYER = "daemon control plane"
# the five that ISSUE 45 brought, in the order it appended them
FIVE = [m + ".fleet_daemon" for m in (
    "poll_ms", "pace_ms", "select_ms", "selected_per_cycle", "deferred_per_cycle")]
NEW = set(FIVE)
# what the cell takes from the uniform fleet's entries at the least (ISSUE 45)
SHARED = {m + ".fleet" for m in (
    "ingest_wall_ms", "listing_ms", "fold_wall_ms", "seal_wall_ms", "unattributed_ms",
    "tenant_fold_kernel_ms", "device_launches", "h2d_bytes_per_op", "d2h_bytes_per_op")}
TOY = checks.toy(MANIFEST, ROOT, CELL)


def config_file(name: str) -> dict:
    return run.load_json(ROOT, "cellbench", "configs", name + ".json")


# ------------------------------------------------- the files and the manifest


def test_configuration_is_the_fleets_but_for_what_the_daemon_adds():
    fleet, daemon = config_file("orset_fleet_1024"), config_file("orset_fleet_daemon")
    differ = {"name", "driver", "source", "deployment", "layout", "accelerator",
              "daemon", "assumed", "guarantees"}
    assert set(daemon) - set(fleet) == {"daemon"} and set(fleet) <= set(daemon)
    assert {k for k in fleet if fleet[k] != daemon[k]} <= differ
    assert daemon["driver"] == "fleet_daemon" and daemon["tenants"] == 1024
    assert daemon["guarantees"][:4] == fleet["guarantees"]
    assert len(daemon["guarantees"]) == 6


@pytest.mark.parametrize("block", [config_file("orset_fleet_daemon")["daemon"],
                                   TOY["config"]["daemon"]], ids=["cell", "toy"])
def test_daemon_block_is_what_the_cli_builds_field_for_field(block):
    """The driver passes the interval and nothing else; every other value
    the block states is the dataclass's default (a toy may shorten the
    interval; the cell's is the CLI's 1.0)."""
    built = fleet_daemon.daemon_config({"daemon": block})
    assert built == DaemonConfig(interval_s=block["interval_s"])
    stated = {k: v for k, v in block.items() if k not in ("what", "serve")}
    fields = {f.name for f in dataclasses.fields(DaemonConfig)}
    assert set(stated) <= fields and {"interval_s", "interval_auto", "batch",
                                      "min_backlog_files", "max_idle_cycles"} <= set(stated)
    assert all(getattr(built, k) == v for k, v in stated.items())
    assert built.serve == ServeConfig(**block["serve"]) and block["serve"] == {
        "seal_empty": False}
    if "what" in block:
        assert block["interval_s"] == 1.0


def check_the_daemons_entries(manifest: dict, root: str) -> None:
    """What ISSUE 45 appended, held as what must be there (ISSUE 49: the
    configuration and the cell are in their lists, the five entries are there
    in their order and each lists the cell), never as where it stands: later
    configurations, cells and entries follow, and a later cell of the family
    reads the five by its name appended to their ``workloads``."""
    daemon = checks.hold_config(manifest, root, "orset_fleet_daemon", reduced=["storage"])
    assert daemon["driver"] == "fleet_daemon"
    checks.hold_cell(manifest, root, CELL, config="orset_fleet_daemon", traffic="steady",
                     chips=1, end_to_end=["serve_ops_per_s", "seal_p95_ms"])
    checks.hold_metrics_in_order(manifest, FIVE, layer=LAYER)
    for name in FIVE:
        checks.hold_metric(manifest, name, cells=[CELL], moves="seal_p95_ms", layer=LAYER)
    listed = checks.hold_cell_lists(root, CELL, NEW | SHARED)
    # every metric the cell lists is of a family its driver belongs to
    assert all(daemon["driver"].startswith(checks.families(spec))
               for spec in listed.values())
    assert not checks.kernel_strings(root).keys() & NEW, "no new kernel, no new pin"


def test_the_entries_are_there_and_the_cell_lists_what_the_issue_names():
    check_the_daemons_entries(MANIFEST, ROOT)


def test_the_mix_is_an_open_loop_below_the_knee():
    mix = run.load_json(ROOT, "cellbench", "traffic", "steady.json")
    assert mix["loop"].startswith("open") and mix["tick_s"] == 0.25
    assert mix["active_devices"] == mix["files_per_device"] == 1
    assert run.load_cell(ROOT, CELL)["traffic"] == mix, "the cell's mix, as the harness loads it"
    assert "shape_files" not in mix, "the steady cell's set-up folds the parent's 27 shapes"
    offered = mix["offered"]
    files_per_s = mix["active_tenants"] / mix["tick_s"]
    assert offered["share_of_knee"] == 0.8
    assert offered["files_per_s"] == files_per_s
    assert offered["ops_per_s"] == files_per_s * 24
    assert files_per_s <= 0.8 * offered["knee"]["files_per_s"]
    assert offered["knee"]["ops_per_s"] == offered["knee"]["files_per_s"] * 24
    assert offered["knee"]["seed"] > 2**31 and offered["knee"]["date"]
    # ticks for the warm-up's steps, the window and a step's overrun, to spare
    ticks = mix["warmup_rounds"] + 30 * mix["max_ops_per_s"] / (24 * mix["active_tenants"])
    assert ticks * mix["tick_s"] >= mix["min_clock_s"] + 30


# ------------------------------------------------------------- the clock


class Stores:
    """A writer's storage as far as the clock uses it."""

    def __init__(self):
        self.stored = []

    async def store_ops(self, actor, version, blob):
        self.stored.append((actor, version, blob, time.perf_counter()))


def clock(n_ticks: int, tick_s: float, files: int = 2):
    stores = [Stores() for _ in range(files)]
    batches = {k: [(t, b"a", k + 1, b"blob", 24) for t in range(files)]
               for k in range(n_ticks)}
    return stores, fleet_daemon.Arrivals(stores, batches, tick_s, n_ticks)


def test_ticks_land_at_their_instants_while_a_step_holds_the_loop():
    """Open loop, proved: the program's event loop is held by a synchronous
    stretch (a stub step that never yields) and the clock's ticks land all
    the same, each within a few ms of its instant."""

    async def scenario():
        stores, arrivals = clock(9, 0.1)
        await arrivals.start()
        assert [k for k, _, _ in arrivals.ticks] == [0], "tick 0 before start() returns"
        time.sleep(0.65)  # the stub step: the loop runs nothing meanwhile
        landed = [k for k, _, _ in arrivals.ticks]
        arrivals.stop()
        return arrivals, stores, landed

    arrivals, stores, landed = asyncio.run(scenario())
    assert landed[:6] == list(range(6)), "ticks 1 to 5 fell due inside the step"
    for k, late, done in arrivals.ticks[1:6]:
        # within a tick of its instant, on a machine the other tests share
        assert 0 <= late < 0.09 and done - (arrivals.t0 + k * 0.1) < 0.095
    # every file is recorded as its store returns, and nothing after stop()
    n = len(arrivals.ticks)
    assert len(arrivals.landed) == 2 * n == sum(len(s.stored) for s in stores)
    assert arrivals.published == list(range(n))
    time.sleep(0.25)
    assert len(arrivals.ticks) == n


def test_withhold_takes_the_last_file_of_the_next_tick_to_land():
    async def scenario():
        stores, arrivals = clock(3, 0.02)
        arrivals.withhold_next = True
        await arrivals.start()
        while not arrivals.ran_out:
            await asyncio.sleep(0.01)
        arrivals.stop()
        return stores, arrivals

    stores, arrivals = asyncio.run(scenario())
    assert arrivals.withheld == [(1, b"a", 1, 24)]
    assert arrivals.published == [0, 1, 2], "the reference counts the tick whole"
    assert [len(s.stored) for s in stores] == [3, 2]


def test_a_short_plan_is_drawn_again_with_the_clocks_ticks(monkeypatch, tmp_path):
    monkeypatch.setattr(fleet_daemon, "refuse_unless_daemon_serves", lambda config: None)
    cell = run.load_cell(ROOT, CELL)
    config = {**cell["config"], **TOY["config"]}
    traffic = {**cell["traffic"], **TOY["traffic"]}
    short = gen.plan_run(config, traffic, 7, 12)
    driver = fleet_daemon.Driver(config, short, str(tmp_path))
    need = cell["traffic"]["min_clock_s"] / cell["traffic"]["tick_s"]
    assert driver.plan.n_rounds == need > short.n_rounds and driver.plan.seed == 7
    files = short.round_files[-1][1]
    assert (driver.plan.f_actor[:files] == short.f_actor).all(), "the same schedule"
    long = gen.plan_run(config, traffic, 7, int(need) + 5)
    assert fleet_daemon.Driver(config, long, str(tmp_path)).plan is long


# ------------------------------------------- which seal took a file in


@pytest.mark.parametrize("ingested_first", [True, False],
                         ids=["stored_before_the_ingest", "stored_after_the_ingest"])
def test_a_file_stored_mid_cycle_goes_to_the_seal_that_folded_it(ingested_first):
    """Two files of one tenant, the second stored while cycle 1 runs.  Where
    the cycle's ingest found it the cursor covers it and cycle 1's seal is
    its seal; where it did not, the file waits for the seal that takes it in,
    however early the clock says it landed."""
    driver = fleet_daemon.Driver.__new__(fleet_daemon.Driver)
    cursor = {b"a": 2 if ingested_first else 1}
    driver.cores = [types.SimpleNamespace(
        info=lambda: types.SimpleNamespace(next_op_versions=cursor))]
    driver.pending = {0: [[b"a", 1, 24, 10.0], [b"a", 2, 20, 12.5]]}
    driver.unsealed = 2

    def report(latency_s: float, outcome: str = "sealed") -> dict:
        return {"selected": ["t0"], "results": {"t0": {
            "outcome": outcome, "error": None, "latency_s": latency_s}}}

    first = driver._account(report(1.0), 12.0)  # cycle 1 started at 12.0
    if ingested_first:
        assert first["ops"] == 44 and first["latencies"] == [3.0, 0.5]
        assert first["most"] == 2, "both files in one visit: the rows class of two"
        assert driver.unsealed == 0 and driver.pending[0] == []
        return
    assert first["ops"] == 24 and first["latencies"] == [3.0] and driver.unsealed == 1
    # a cycle that only polled the tenant, or found it empty, seals nothing
    for outcome in ("polled", "empty"):
        idle = driver._account(report(0.2, outcome), 14.0)
        assert idle["ops"] == 0 and idle["latencies"] == [] and idle["attempted"] == 1
    cursor[b"a"] = 2
    later = driver._account(report(0.5), 16.0)
    assert later["ops"] == 20 and later["latencies"] == [4.0] and driver.unsealed == 0
    assert later["failed"] == 0 and later["attempted"] == 1
    # a cycle that raised fails every tenant with files waiting
    driver.pending[0] = [[b"a", 3, 24, 17.0]]
    raised = driver._account(None, 18.0)
    assert raised == {"ops": 0, "attempted": 1, "failed": 1, "latencies": [], "most": 0}


# ------------------------------------------------------ the two questions


def refused(capsys, config) -> str:
    with pytest.raises(SystemExit) as stop:
        fleet_daemon.refuse_unless_daemon_serves(config)
    cap = capsys.readouterr()
    assert stop.value.code == 2 and cap.out == ""
    assert cap.err.count("\n") == 1 and cap.err.startswith("cellbench: this program")
    return cap.err


def test_the_delivered_program_answers_both_questions(capsys):
    fleet_daemon.refuse_unless_daemon_serves(config_file("orset_fleet_daemon"))
    assert capsys.readouterr().err == ""


def test_a_daemon_without_step_is_refused(capsys, monkeypatch):
    monkeypatch.delattr(FleetDaemon, "step")
    assert "no step()" in refused(capsys, config_file("orset_fleet_daemon"))


def test_a_daemon_that_refuses_the_fleet_is_refused(capsys, monkeypatch):
    """The parent's admission: 1 MiB a tenant whatever its size."""
    monkeypatch.setattr(FleetDaemon, "_admission_cost",
                        lambda self, core: (self.config.tenant_cost_bytes, False))
    line = refused(capsys, config_file("orset_fleet_daemon"))
    assert "refuses a fleet of 1024 tenants" in line and "byte budget: 257 tenants" in line
    # and a fleet that fits the estimate is served by that program too
    fleet_daemon.refuse_unless_daemon_serves(
        {**config_file("orset_fleet_daemon"), "tenants": 256})


# --------------------------------------------- the cell, through run_cell


def run_toy(traced: bool, fault=None, seed=2**31 + 45) -> tuple:
    out, err = io.StringIO(), io.StringIO()
    with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
        assert run.run_cell(CELL, seed, 0.5, traced, require_tpu=False,
                            shrink=TOY, fault=fault) == 0
    return json.loads(out.getvalue().strip().splitlines()[-1]), err.getvalue()


@pytest.fixture(scope="module")
def traced():
    return run_toy(True)


def test_traced_toy_line_carries_exactly_the_listed_metrics_less_the_device_traces(traced):
    line, _ = traced
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    checks.check_toy_line(ROOT, CELL, line["metrics"])
    listed = checks.listed(ROOT, CELL)
    absent = set(listed) - set(line["metrics"])
    assert absent == {n for n, s in listed.items() if not checks.demanded(s)}
    assert {"tenant_fold_kernel_ms.fleet", "device_launches.fleet"} <= absent
    # the served states and a fresh replica's bytes against the plain
    # reference, and every stored file in a seal
    assert line["compared"] == {name: {"value": 0, "limit": 0} for name in (
        "tenants_vs_reference", "fresh_replicas_vs_reference",
        "fresh_replica_bytes_vs_served", "files_never_sealed")}


@pytest.mark.parametrize("metric", sorted(NEW))
def test_each_new_metric_reads_the_daemons_own_span_or_counter(traced, metric):
    value = traced[0]["metrics"][metric]["value"]
    interval_ms = 1e3 * TOY["config"]["daemon"]["interval_s"]
    low, high = {
        "pace_ms": (interval_ms, 3 * interval_ms),  # the wait, once a step
        "poll_ms": (0.0, 1e3), "select_ms": (0.0, 50.0),
        "selected_per_cycle": (0.0, TOY["config"]["tenants"]),
        "deferred_per_cycle": (0.0, 0.0),  # "0", and not nothing
    }[metric.split(".")[0]]
    assert low <= value <= high and (value > 0 or metric.startswith("deferred"))


def test_the_steps_are_the_daemons_and_ticks_land_inside_them(traced):
    _, err = traced
    steps = [l for l in err.splitlines() if l.startswith("cellbench: step ")]
    assert len(steps) >= 10, "nine warm-up steps under the clock, then the window"
    assert any(" selected 0 " not in l for l in steps) and any(
        " polled 0;" not in l for l in steps)
    inside = sum(int(l.split(" ticks landed inside it")[0].rsplit(" ", 1)[1])
                 for l in steps)
    assert inside >= 2, "files were stored while steps were in flight"


def test_control_a_withheld_file_fails_the_cell_by_both_checks():
    line, err = run_toy(False, fault="withhold_file", seed=2**31 + 46)
    assert line["correct"] is False
    assert line["compared"]["tenants_vs_reference"]["value"] >= 1
    assert line["compared"]["files_never_sealed"]["value"] >= 1
    assert line["compared"]["files_never_sealed"]["limit"] == 0
    assert "check files_never_sealed: value" in err and " FAILED\n" in err
