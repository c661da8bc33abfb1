"""The folder with five compactors: the share law, the plain state merge, the
driver end to end at toy sizes on the CPU (the merge takes the XLA tree there,
the Pallas kernel only on the chip), its own control, the two readers it
brings and the device module names they match.  Nothing here is a measurement.
"""

import asyncio
import json

import numpy as np
import pytest

from cellbench import gen, merge_bytes, reference_peers, run
from cellbench.drivers import folder_peers
from cellbench.readers import roofline_counters_pct, trace_modules_ms

import manifest_checks as checks

ROOT = run.ROOT
MANIFEST = run.load_json(ROOT, "BENCHMARK.json")
CELL = "orset_folder_peers.backlog"
# 40 devices, every share 8 (tests/cellbench/toys/<cell>.json says why)
TOY = checks.toy(MANIFEST, ROOT, CELL)
DEVICE_ONLY = {"merge_kernel_ms.folder_peers", "orset_merge_roofline.folder_peers",
               "device_launches.folder"}


def toy_driver(workdir: str, seed: int):
    cell = run.load_cell(ROOT, CELL)
    config = {**cell["config"], **TOY["config"]}
    traffic = {**cell["traffic"], **TOY["traffic"]}
    return folder_peers.Driver(config, gen.plan_run(config, traffic, seed, 2), workdir)


# ------------------------------------------------------------ the share law


@pytest.mark.parametrize("devices, sizes, seen", [
    (1000, [200] * 5, [220] * 4),
    (8, [2, 2, 1, 2, 1], [2, 2, 1, 2]),  # a tenth of two devices is none
    (40, [8] * 5, [8] * 4),
])
def test_share_law(devices, sizes, seen):
    share = folder_peers.shares(devices, 5)
    assert np.bincount(share).tolist() == sizes
    assert (np.diff(share) >= 0).all(), "a share is a run of neighbouring devices"
    views = folder_peers.peer_view(devices, 5, 0.1)
    assert [int(v.sum()) for v in views] == seen
    for k, view in enumerate(views):
        assert view[share == k].all(), "a peer sees its whole share"
        assert not view[share == 4].any(), "no peer sees the measured share"
        after = np.flatnonzero(share == (k + 1) % 4)
        extra = np.flatnonzero(view & (share != k))
        assert extra.tolist() == after[:len(after) // 10].tolist()
    covered = np.sum(views, axis=0) + (share == 4)
    assert covered.min() == 1 and covered.max() == (2 if devices >= 50 else 1)


# ------------------------------------------------- the plain reference's merge


def test_plain_merge_follows_the_rule():
    a, b = b"a" * 16, b"b" * 16
    left, right = reference_peers.PlainORSet(), reference_peers.PlainORSet()
    left.add(1, a, 1)
    left.add(2, a, 2)
    right.add(1, a, 1)
    right.add(2, a, 2)
    right.remove(2, {a: 2})    # right saw a's add of 2 and removed it
    right.add(3, b, 1)         # left never saw b
    left.add(4, a, 3)          # right never saw a's third add
    both = reference_peers.merge(left, right).canonical()
    assert both[b"c"] == {a: 3, b: 1}
    assert both[b"e"] == {1: {a: 1}, 3: {b: 1}, 4: {a: 3}}, (
        "a dot the other side has seen and dropped is dead; an unseen one lives")
    assert both == reference_peers.merge(right, left).canonical()
    assert both == reference_peers.merge(reference_peers.merge(left, right),
                                         right).canonical()
    # a horizon ahead of one side's clock kills the add when it arrives
    ahead = reference_peers.PlainORSet()
    ahead.remove(5, {b: 2})
    late = reference_peers.PlainORSet()
    late.add(5, b, 1)
    late.add(6, b, 2)
    met = reference_peers.merge(ahead, late).canonical()
    assert 5 not in met[b"e"] and met[b"d"] == {}
    again = reference_peers.from_canonical(both).canonical()
    assert again == both
    assert left.canonical()[b"e"] == {1: {a: 1}, 2: {a: 2}, 4: {a: 3}}, "inputs are left alone"


@pytest.mark.parametrize("seed", [5, 2**31 + 30])
def test_fold_of_a_share_is_that_peers_snapshot_and_the_merge_is_the_whole_fold(seed, tmp_path):
    """The two identities the cell's check rests on, held here against the
    program's own peers: a peer's sealed snapshot is the plain fold of the op
    files it saw, and the plain merge of the four snapshots, then the plain
    fold of the measured share's files, is the plain fold of everything."""
    driver = toy_driver(str(tmp_path), seed)
    plan = driver.plan

    async def heads():
        await driver.open()
        return [(await driver.writer._open_sealed(raw))[0]
                for raw in driver.snapshots[0]]

    snapshots = asyncio.run(heads())  # the peers' states after the head and round 0
    rows = plan.live_rows([-1, 0])
    device = plan.actor[rows] % plan.devices
    merged = reference_peers.PlainORSet()
    for view, snap in zip(driver.views, snapshots):
        want = reference_peers.fold_rows(plan, rows[view[device]]).canonical()
        assert reference_peers.differing(snap, want) == 0
        merged = reference_peers.merge(merged, reference_peers.from_canonical(snap))
    whole = reference_peers.fold_rows(plan, rows[driver.mine[device]], merged)
    everything = reference_peers.fold_rows(plan, rows).canonical()
    assert reference_peers.differing(whole.canonical(), everything) == 0
    assert len(everything[b"c"]) == plan.devices


# --------------------------------------------------- the cell, end to end


def test_traced_toy_run_reports_the_merge(capsys):
    assert run.run_cell(CELL, 2**31 + 30, 0.5, True, require_tpu=False,
                        shrink=TOY) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["compared"]["stale_peer_snapshots_left"] == {"value": 0, "limit": 0}
    # what reads the device trace finds nothing on the CPU and is left out;
    # every other listed metric is there unless its own file says it may not be
    checks.check_toy_line(ROOT, CELL, line["metrics"])
    specs = checks.listed(ROOT, CELL)
    assert all(specs[name]["source"] == "device_trace" for name in DEVICE_ONLY)
    value = {k: v["value"] for k, v in line["metrics"].items()}
    assert value["snapshots_per_merge.folder_peers"] == 4
    assert value["plane_cache_drops_per_merge.folder_peers"] >= 1
    assert value["snapshot_merge_ms.folder_peers"] > 0
    assert 0 < value["merge_host_ms.folder_peers"] < value["snapshot_merge_ms.folder_peers"]
    assert value["snapshot_bytes_per_op.folder_peers"] > 0
    # the stack alone: 5 states x 2 planes of the toy's cells, every call
    ops = 40 * 48
    assert value["h2d_bytes_per_op.folder"] * ops >= 5 * 2 * 4 * 32 * 40
    assert value["d2h_bytes_per_op.folder"] * ops >= 2 * 2 * 4 * 32 * 40, (
        "the merged planes and the folded planes come back through pull")


@pytest.mark.parametrize("peer", [0, 3])
def test_control_a_withheld_peer_snapshot_is_not_correct(peer, capsys):
    """The cell's own control: 'a snapshot that was published is merged
    whole' broken for one peer from the first timed round on."""
    first = run.load_cell(ROOT, CELL)["traffic"]["warmup_rounds"]
    fault = {"withhold_peer": {"peer": peer, "from_round": first}}
    shrink = {**TOY, "config": {**TOY["config"], **fault}}
    assert run.run_cell(CELL, 31, 0.5, False, require_tpu=False, shrink=shrink) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is False
    assert line["compared"]["compactor_vs_reference"]["value"] > 0


def test_configuration_keeps_the_solo_folders_widths():
    solo = run.load_json(ROOT, "cellbench", "configs", "orset_folder_1k.json")
    peers = run.load_json(ROOT, "cellbench", "configs", "orset_folder_peers.json")
    for key in ("tenants", "devices", "members", "ops_per_file", "remove_fraction",
                "initial_files_per_device", "initial_ops", "storage", "cryptor",
                "key_cryptor", "accelerator", "source_sizes", "crdt"):
        assert peers[key] == solo[key], key
    assert peers["guarantees"][:len(solo["guarantees"])] == solo["guarantees"]
    assert len(peers["guarantees"]) == len(solo["guarantees"]) + 2
    assert (peers["compactors"], peers["peer_overlap"]) == (5, 0.1)
    assert peers["peer_protocol"] == "reference" and "withhold_peer" not in peers
    assert {"members_and_devices", "remove_fraction", "peer_protocol",
            "share_law"} <= set(peers["assumed"])
    assert len(peers["source"]) <= 200


def check_the_cell_reads_the_solo_folders_entries(manifest: dict, root: str) -> None:
    """The timed call is the same ``Core.compact()``: the cell is listed by
    the ``.folder`` entries of the spans and counters it shares (ISSUE 43;
    it had ``.folder_peers`` copies of them before), beside the ten of its
    own path, which keep their names."""
    shared = {m + ".folder" for m in (
        "unattributed_ms", "storage_ms", "delta_plan_ms", "delta_seal_ms",
        "repl_status_ms", "ingest_wait_ms", "h2d_bytes_per_op", "d2h_bytes_per_op",
        "device_launches", "native_chunk_reads_pct", "gc_pause_ms",
        "gc_full_pause_ms", "watermark_ms")}
    own = {m + ".folder_peers" for m in (
        "snapshot_ingest_ms", "snapshot_merge_ms", "merge_host_ms",
        "snapshots_per_merge", "plane_cache_drops_per_merge", "snapshot_bytes_per_op",
        "merge_kernel_ms", "orset_merge_roofline", "op_fold_ms", "delta_read_ms")}
    listed = checks.hold_cell_lists(root, CELL, shared | own)
    assert all(listed[name]["driver"] == "folder" for name in shared)
    assert all(listed[name]["driver"] == "folder_peers" for name in own)


def test_the_cell_reads_the_solo_folders_metrics_from_the_solo_folders_entries():
    check_the_cell_reads_the_solo_folders_entries(MANIFEST, ROOT)


# ------------------------------------------------------------ the readers


def window(**kw):
    return {"calls": 2, "ops": 100, "spans": {}, "counters": {}, "trace": None,
            "shapes": [], "peaks": {"hbm_bytes_per_s": 1e9}, **kw}


def device_trace(**events):
    """One device plane whose ``XLA Modules`` line holds ``name -> [ns, ...]``."""
    line = [[name, 10.0 * i, float(ns)] for i, (name, durations)
            in enumerate(events.items()) for ns in durations]
    return {"planes": [{"name": "/device:TPU:0",
                        "lines": [{"name": "XLA Modules", "events": line}]}]}


def test_merge_bytes_on_a_hand_made_shape():
    # 5 states over 3 members x 2 replicas: 30 cells in, 6 out, 12 clock words
    assert merge_bytes.orset_merge(30, 6, 12) == 4 * (2 * 30 + 2 * 6 + 12)
    assert merge_bytes.orset_merge(0, 0, 0) == 0
    assert merge_bytes.FUNCTIONS["orset_merge"] is merge_bytes.orset_merge


def test_trace_modules_ms_sums_the_named_programs_per_call():
    args = {"line": "XLA Modules", "modules": ["orset_merge_many_pallas", "_merge_halves"]}
    trace = device_trace(**{"jit_orset_merge_many_pallas(1)": [3e6, 5e6],
                            "jit__fold_ablk(2)": [9e6]})
    assert trace_modules_ms.read(window(trace=trace), args) == pytest.approx(4.0)
    assert trace_modules_ms.read(window(), args) is None
    other = device_trace(**{"jit__fold_ablk(2)": [9e6]})
    assert trace_modules_ms.read(window(trace=other), args) is None


def test_roofline_counters_pct_is_least_bytes_over_peak_over_device_time():
    spec = run.load_json(ROOT, "cellbench", "layer_metrics",
                         "orset_merge_roofline.folder_peers.json")
    counters = {"merge_state_cells": 60, "merge_out_cells": 12, "merge_clock_cells": 24}
    trace = device_trace(**{"jit_orset_merge_many_pallas(1)": [1000.0, 1000.0]})
    least = merge_bytes.orset_merge(60, 12, 24)
    got = roofline_counters_pct.read(window(trace=trace, counters=counters), spec["args"])
    assert got == pytest.approx(100.0 * least / 1e9 / 2e-6)
    # a program without the counters (the parent of the PR that added them),
    # no trace, or no such program on the device: nothing to read
    assert roofline_counters_pct.read(window(trace=trace), spec["args"]) is None
    assert roofline_counters_pct.read(window(counters=counters), spec["args"]) is None
    fold_only = device_trace(**{"jit__fold_ablk(2)": [9e6]})
    assert roofline_counters_pct.read(
        window(trace=fold_only, counters=counters), spec["args"]) is None


# ------------------------------------------- module names the readers match


def merge_modules() -> list:
    """Names of the device modules of the jitted functions ``K.orset_merge_many``
    runs a stacked merge through.  The tree merge lowers here; the Pallas
    kernel lowers only on the chip, so the ``__name__`` that ``jax.jit``
    derives the module name from is pinned instead."""
    from crdt_enc_tpu.ops import orset as O
    from crdt_enc_tpu.ops import pallas_merge

    S, E, R = 2, 8, 8
    i32 = lambda *shape: np.zeros(shape, np.int32)  # noqa: E731
    text = O._merge_halves.lower(
        i32(S, R), i32(S, E, R), i32(S, E, R), i32(S, R), i32(S, E, R), i32(S, E, R)
    ).as_text()
    tree = text.split("module @", 1)[1].split()[0]
    pallas = "jit_" + pallas_merge.orset_merge_many_pallas.__name__
    assert (tree, pallas) == ("jit__merge_halves", "jit_orset_merge_many_pallas")
    return [tree, pallas]


@pytest.mark.parametrize("metric", ["merge_kernel_ms.folder_peers",
                                    "orset_merge_roofline.folder_peers"])
def test_module_strings_name_the_programs_the_merge_launches(metric):
    args = run.load_json(ROOT, "cellbench", "layer_metrics", metric + ".json")["args"]
    assert "match" not in args, "test_new_readers.py's table holds every `match`"
    launched = merge_modules()
    for module in launched:
        assert any(m in module for m in args["modules"]), (module, args["modules"])
    for m in args["modules"]:
        assert any(m in module for module in launched), (
            f"{m!r} matches no module the merge launches")
    fold = run.load_json(ROOT, "cellbench", "layer_metrics",
                         "fold_kernel_ms.folder.json")["args"]["match"]
    assert not any(f in module for f in fold for module in launched), (
        "the fold's metrics must not read the merge's programs")
