"""The benchmark's own tests: the manifest is valid, the harness finds every
configuration, mix, cell and per-layer metric by name, each driver runs end to
end (here on the CPU, at a tiny size, through the Python API), and the oracle
says ``correct: false`` when it should.

Nothing here is a measurement: the CPU backend takes the product's XLA fold
(the product never interprets a Pallas kernel on its own), and the sizes are
toys.
"""

import json
import os
import shutil
import subprocess
import sys

import pytest

from cellbench import gen, reference, run

import manifest_checks as checks

ROOT = run.ROOT
MANIFEST = run.load_json(ROOT, "BENCHMARK.json")
CELLS = checks.cells(MANIFEST)
LAYER = checks.metrics(MANIFEST)
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device", "compared"}


def tiny(cell: str) -> dict:
    """The toy overlay of a cell (``manifest_checks.tiny``: by the
    configuration file's driver; a mix it does not know, from the mix file)."""
    return checks.tiny(MANIFEST, ROOT, cell)


def last_line(capsys) -> dict:
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


# ------------------------------------------------------------ the manifest


def test_manifest_has_exactly_the_contract_keys():
    checks.check_contract_keys(MANIFEST, ROOT)


def test_each_list_is_within_what_the_driver_admits():
    checks.check_list_lengths(MANIFEST, ROOT)
    assert checks.LIMITS["per_layer"] == 128, "the driver's, not a count of what is there"


def test_names_and_units_use_the_allowed_characters():
    checks.check_names_and_units(MANIFEST, ROOT)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_file_agrees_with_the_manifest(cell):
    checks.check_cell(MANIFEST, ROOT, cell)


def test_at_most_half_of_the_cells_ask_for_four_chips():
    checks.check_four_chip_share(MANIFEST, ROOT)
    cell = {"name": "c", "config": "x", "traffic": "t", "why": "w"}
    for chips, ok in [([4], True), ([4, 1], True), ([4, 4, 1], False),
                      ([4, 4, 1, 1], True), ([4, 4, 4, 1, 1], False)]:
        manifest = {"workloads": [{**cell, "chips": c} for c in chips]}
        if ok:
            checks.check_four_chip_share(manifest, ROOT)
        else:
            with pytest.raises(AssertionError):
                checks.check_four_chip_share(manifest, ROOT)


@pytest.mark.parametrize("metric", LAYER)
def test_layer_metric_file_agrees_and_moves_a_metric_its_cells_report(metric):
    checks.check_layer_metric(MANIFEST, ROOT, metric)


# --------------------------------------------------- the drivers, end to end


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_end_to_end_and_prints_the_contract_line(cell, capsys):
    assert run.run_cell(cell, 2**31 + 11, 0.5, False, require_tpu=False,
                        shrink=tiny(cell)) == 0
    cap = capsys.readouterr()
    line = json.loads(cap.out.strip().splitlines()[-1])
    assert set(line) == RESULT_KEYS
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    # every number compared, beside its limit: the line's last key, and the
    # last lines of standard error
    assert list(line)[-1] == "compared" and line["compared"]
    assert all(c == {"value": 0, "limit": 0} for c in line["compared"].values())
    assert cap.err.strip().splitlines()[-len(line["compared"]):] == [
        f"cellbench: check {name}: value 0 limit 0 ok" for name in line["compared"]]
    wanted = {m["name"] for m in run.load_cell(ROOT, cell)["end_to_end"]}
    assert set(line["metrics"]) == wanted
    assert all(v["value"] > 0 for v in line["metrics"].values())
    assert set(line["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}


@pytest.mark.parametrize("cell", CELLS[:2])
def test_traced_run_reports_layer_metrics_by_name(cell, capsys):
    assert run.run_cell(cell, 12, 0.5, True, require_tpu=False,
                        shrink=tiny(cell)) == 0
    line = last_line(capsys)
    assert set(line) == RESULT_KEYS | {"breakdown"}
    assert line["correct"] is True
    listed = {m["name"] for m in run.load_cell(ROOT, cell)["per_layer"]}
    # what reads the device trace finds nothing on the CPU and is left out
    assert set(line["metrics"]) <= listed and len(line["metrics"]) >= 3
    assert {"busy_s", "window_s"} <= set(line["device"])
    assert line["device"]["window_s"] > 0
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}


@pytest.mark.parametrize("cell", [c for c in CELLS if c.endswith((".backlog", ".busy"))])
def test_control_a_withheld_op_file_is_not_correct(cell, capsys):
    """The control: the guarantee 'an op file that was published is folded'
    broken for one file of the first timed batch."""
    assert run.run_cell(cell, 13, 0.5, False, require_tpu=False,
                        shrink=tiny(cell), fault="withhold_file") == 0
    cap = capsys.readouterr()
    line = json.loads(cap.out.strip().splitlines()[-1])
    assert line["correct"] is False
    assert any(c["value"] > c["limit"] for name, c in line["compared"].items()
               if name.endswith("vs_reference"))
    assert "vs_reference: value" in cap.err and " FAILED\n" in cap.err


@pytest.mark.parametrize("cell", [c for c in CELLS if c.endswith((".backlog", ".busy"))])
def test_narrowed_counters_in_the_timed_path_are_not_correct(cell, capsys, monkeypatch):
    """The timed path broken underneath: what the fold hands back loses every
    counter bit above the low 7-bit limb (what skipping the kernel's
    high-limb pass on large counters would do), at both doors a writeback
    goes through: whole planes (a round that built them, a merge, and the
    clock of every round) and, since PR 41, the touched cells of a fold over
    resident planes.  The rest of the run is driven as always and must say
    ``correct: false``."""
    import crdt_enc_tpu.ops as K

    whole, partial = K.orset_planes_to_state, K.orset_cells_to_state

    def low_limb_planes(clock, add, rm, members, replicas):
        return whole(clock & 0x7F, add & 0x7F, rm & 0x7F, members, replicas)

    def low_limb_cells(state, member, actor, add_c, rm_c, members, replicas):
        return partial(state, member, actor, add_c & 0x7F, rm_c & 0x7F,
                       members, replicas)

    monkeypatch.setattr(K, "orset_planes_to_state", low_limb_planes)
    monkeypatch.setattr(K, "orset_cells_to_state", low_limb_cells)
    assert run.run_cell(cell, 14, 0.5, False, require_tpu=False,
                        shrink=tiny(cell)) == 0
    assert last_line(capsys)["correct"] is False


def test_command_exits_non_zero_without_a_tpu():
    done = subprocess.run(
        [sys.executable, "-m", "cellbench.run", "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert done.returncode != 0
    assert done.stdout == ""


# ------------------------------------------------------------ driven by data


def test_new_config_mix_cell_and_metric_are_found_by_name(tmp_path, capsys):
    """A later PR adds a deployment, a mix, a cell and a per-layer metric as
    files plus one manifest entry each, and edits no file that is there."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for sub in ("configs", "traffic", "cells", "layer_metrics"):
        shutil.copytree(os.path.join(ROOT, "cellbench", sub),
                        tmp_path / "cellbench" / sub)
    shutil.copy(os.path.join(ROOT, "cellbench", "peaks.json"), tmp_path / "cellbench")
    bench = tmp_path / "cellbench"
    config = run.load_json(ROOT, "cellbench", "configs", "orset_folder_1k.json")
    config.update(name="orset_folder_8", devices=8, members=32,
                  initial_files_per_device=3)
    (bench / "configs" / "orset_folder_8.json").write_text(json.dumps(config))
    (bench / "traffic" / "drip.json").write_text(json.dumps({
        "name": "drip", "active_tenants": 1, "active_devices": 3,
        "files_per_device": 1, "warmup_rounds": 1, "max_ops_per_s": 2500,
    }))
    cell = {"name": "orset_folder_8.drip", "config": "orset_folder_8",
            "traffic": "drip", "chips": 1, "why": "a cell a later PR adds"}
    (bench / "cells" / "orset_folder_8.drip.json").write_text(json.dumps(cell))
    metric = {"name": "gc_ms.folder", "unit": "ms", "better": "lower",
              "source": "program_span", "layer": "storage list/load/GC",
              "moves": "compact_ms"}
    (bench / "layer_metrics" / "gc_ms.folder.json").write_text(json.dumps({
        **metric, "driver": "folder", "reader": "span_ms",
        "args": {"spans": ["compact.gc"]},
    }))
    manifest = json.loads((tmp_path / "BENCHMARK.json").read_text())
    manifest["configs"].append({
        "name": "orset_folder_8", "source": config["source"],
        "file": "cellbench/configs/orset_folder_8.json",
        "reduced": sorted(config["reduced"]), "why": "a toy",
    })
    manifest["workloads"].append(cell)
    manifest["per_layer"].append({**metric, "workloads": [cell["name"]]})
    for m in manifest["end_to_end"]:
        if m["name"] in ("compact_ms", "compact_ops_per_s"):
            m["workloads"].append(cell["name"])
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(manifest))

    assert run.run_cell(cell["name"], 15, 0.4, True, root=str(tmp_path),
                        require_tpu=False) == 0
    line = last_line(capsys)
    assert line["correct"] is True
    assert set(line["metrics"]) == {"gc_ms.folder"}
    assert run.run_cell(cell["name"], 15, 0.4, False, root=str(tmp_path),
                        require_tpu=False) == 0
    assert {"compact_ms", "compact_ops_per_s", "setup_s"} == set(
        last_line(capsys)["metrics"])


# ---------------------------------------------- the generator and the oracle


def test_every_seed_gives_the_same_work_in_another_order():
    config = {"tenants": 3, "devices": 4, "members": 16, "ops_per_file": 24,
              "remove_fraction": 0.1, "initial_files_per_device": 2}
    mix = {"active_tenants": 2, "active_devices": 2, "files_per_device": 1,
           "warmup_rounds": 1, "max_ops_per_s": 100}
    a = gen.plan_run(config, mix, 1, 5)
    b = gen.plan_run(config, mix, 2**31 + 5, 5)
    assert a.round_files == b.round_files and len(a.kind) == len(b.kind)
    assert (a.f_actor != b.f_actor).any() or (a.member != b.member).any()
    again = gen.plan_run(config, mix, 1, 5)
    assert (a.member == again.member).all() and (a.f_actor == again.f_actor).all()
    # versions are dense from 1 per writer, dots dense from 1 per writer
    for actor in set(a.f_actor.tolist()):
        versions = a.f_version[a.f_actor == actor]
        assert versions.tolist() == list(range(1, len(versions) + 1))
        adds = a.counter[(a.actor == actor) & (a.kind == 0)]
        assert adds.tolist() == list(range(1, len(adds) + 1))
    assert gen.rounds_for(mix, config, 10) == 1 + 11


def test_plain_reference_is_an_observed_remove_set():
    s = reference.PlainORSet()
    a, b = b"a" * 16, b"b" * 16
    s.add(7, a, 1)
    s.add(7, b, 1)
    s.remove(7, {a: 1})           # observes a's add only: b's survives
    assert s.canonical()[b"e"] == {7: {b: 1}}
    s.add(7, a, 1)                # a replay of a seen dot changes nothing
    assert s.canonical()[b"e"] == {7: {b: 1}}
    s.remove(9, {a: 3})           # a remove that runs ahead of the clock waits
    assert s.canonical()[b"d"] == {9: {a: 3}}
    s.add(9, a, 2)                # ... and the add it observed is born dead
    assert 9 not in s.canonical()[b"e"]
    s.add(9, a, 4)
    assert s.canonical()[b"e"][9] == {a: 4} and s.canonical()[b"d"] == {}
    other = {b"c": {a: 4, b: 1}, b"e": {7: {b: 1}, 9: {a: 5}}, b"d": {}}
    assert reference.differing(s.canonical(), s.canonical()) == 0
    assert reference.differing(s.canonical(), other) == 1
