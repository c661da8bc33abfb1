"""``orset_folder_10k.backlog`` (PR 41): BASELINE.json's config 3 whole, with
its planes resident on the chip.  The configuration is the 1,000-device
folder's but for its scale; the cell reads the solo folder's metrics by being
listed in their entries (ISSUE 43: its driver ``folder_10k`` is of the family
``folder``; the ``.folder_10k`` copies it came with are gone) and three that
came with it, which the 1,000-device cells read too since they fold over
resident planes as well; the driver refuses a program whose routing does not
keep the planes on the chip; the module strings of the gather's two metrics
are pinned against the jitted function the product path calls; and **a traced
toy line carries every metric the cell lists** but the readings of the device
trace: the rule PR 40 was refused for (a listed metric of a span its program
could not reach was missing from the line).

Nothing here is a measurement: the toy run is on the CPU at toy sizes.
"""

import json

import numpy as np
import pytest

from cellbench import gather_bytes, run
from cellbench.drivers import folder, folder_10k
from cellbench.readers import counter_ratio, gather_roofline_pct, trace_kernel_ms

import manifest_checks as checks

ROOT = run.ROOT
MANIFEST = run.load_json(ROOT, "BENCHMARK.json")
CELL = "orset_folder_10k.backlog"
SOLO = ["orset_folder_1k.backlog", "orset_folder_1k.trickle"]

# the solo folder's entries that list the cell: the eight it had copies of,
# the four PR 41 had to cut for want of room, the five ISSUE 43 adds
SHARED = [m + ".folder" for m in (
    "device_row_pct", "orset_fold_roofline", "h2d_bytes_per_op", "d2h_bytes_per_op",
    "delta_plan_ms", "delta_seal_ms", "storage_ms", "writeback_ms",
    "fold_kernel_ms", "device_launches", "aead_ms", "ingest_load_ms",
    "gather_kernel_ms", "cells_pulled_per_op", "delta_base_reuse_pct",
    "delta_verify_pack_ms", "delta_seal_only_ms")]
# what came with the cell keeps its name
OWN = [m + ".folder_10k" for m in ("plane_cache_hit_pct", "gather_roofline", "fold_pull_ms")]
# what reads the profiler's trace of the device: absent from a CPU line
DEVICE_ONLY = {"orset_fold_roofline.folder", "fold_kernel_ms.folder",
               "device_launches.folder", "gather_kernel_ms.folder",
               "gather_roofline.folder_10k"}
# the device programs the kernels' metrics match
FOLD = ["_fold_ablk", "_fold_wide", "orset_fold"]
GATHER = ["orset_gather_cells"]
PINS = {"gather_roofline.folder_10k": GATHER, "gather_kernel_ms.folder": GATHER,
        "orset_fold_roofline.folder": FOLD, "fold_kernel_ms.folder": FOLD}


def spec_of(metric: str) -> dict:
    return run.load_json(ROOT, "cellbench", "layer_metrics", metric + ".json")


# ------------------------------------------------ the manifest and the files


def check_the_10k_cells_entries(manifest: dict, root: str) -> None:
    """What the manifest says of the cell, its configuration and the metrics
    of the resident fold: what must be there, in its order; later cells and
    entries may follow anywhere (ISSUE 49)."""
    checks.hold_config(manifest, root, "orset_folder_10k", reduced=["initial_ops"])
    checks.hold_cell(manifest, root, CELL, config="orset_folder_10k", traffic="backlog",
                     chips=1, end_to_end=["compact_ops_per_s", "compact_ms"])
    # every cell that folds over resident planes reads the fold's metrics:
    # the two solo cells, then this one
    for metric in OWN + ["gather_kernel_ms.folder", "cells_pulled_per_op.folder"]:
        checks.hold_metric(manifest, metric, cells=SOLO + [CELL])
    listed = checks.hold_cell_lists(root, CELL, SHARED + OWN)
    # a toy round takes the per-file path, where no decode span opens (PR 41)
    assert "decode_ms.folder" not in listed
    for name, spec in listed.items():
        text = json.dumps(spec)
        # spans and counters the delivered program cannot reach in this cell
        assert "host_sparse" not in text and "fold.planes" not in text, name
        assert spec.get("args", {}).get("counter") != "fold_rows_host", name
        assert not spec.get("may_be_absent"), name


def test_the_cell_lists_these_and_no_metric_its_program_cannot_reach():
    check_the_10k_cells_entries(MANIFEST, ROOT)


@pytest.mark.parametrize("metric", OWN + ["gather_kernel_ms.folder",
                                          "cells_pulled_per_op.folder"])
def test_metric_of_the_resident_fold_is_read_by_every_cell_that_folds_so(metric):
    checks.check_layer_metric(MANIFEST, ROOT, metric)
    spec = spec_of(metric)
    assert spec["driver"] == "folder" and spec["what"]


def test_configuration_is_config_3_whole_and_the_solo_folder_otherwise():
    solo = run.load_json(ROOT, "cellbench", "configs", "orset_folder_1k.json")
    whole = run.load_json(ROOT, "cellbench", "configs", "orset_folder_10k.json")
    baseline = run.load_json(ROOT, "BASELINE.json")["configs"][2]
    assert "10k replicas" in baseline and "OR-Set" in baseline and baseline in whole["source"]
    for key in ("tenants", "members", "ops_per_file", "remove_fraction", "storage",
                "cryptor", "key_cryptor", "accelerator", "source_sizes", "crdt",
                "assumed", "guarantees"):
        assert whole[key] == solo[key], key
    assert whole["devices"] == whole["source_sizes"]["devices"] == 10_000
    assert whole["initial_ops"] == 10_000 * 2 * 48 == 960_000
    assert whole["initial_files_per_device"] == 2
    assert list(whole["reduced"]) == ["initial_ops"]
    assert "4096 x 10000" in whole["layout"] and "resident" in whole["layout"]
    assert whole["driver"] == "folder_10k" and whole["driver"].startswith("folder")
    assert whole["source"] != solo["source"] and len(whole["source"]) <= 200


# ------------------------------------------------------------- the driver


class Routes:
    def __init__(self, answer):
        self.answer, self.asked = answer, []

    def orset_fold_route(self, members, devices, rows):
        self.asked.append((members, devices, rows))
        return self.answer


@pytest.mark.parametrize("accel", [Routes("host"), Routes("dense"), object()],
                         ids=["host", "dense", "no answer"])
def test_driver_refuses_a_program_that_does_not_keep_the_planes_resident(accel, capsys):
    with pytest.raises(SystemExit) as stop:
        folder_10k.refuse_unless_resident(accel, 4096, 10_000, 48_000)
    assert stop.value.code == 2
    cap = capsys.readouterr()
    assert cap.out == ""
    assert cap.err.count("\n") == 1 and "4096 x 10000" in cap.err
    assert "does not run on it" in cap.err


def test_driver_accepts_a_program_that_answers_resident_and_asks_the_round():
    accel = Routes("resident")
    folder_10k.refuse_unless_resident(accel, 4096, 10_000, 48_000)
    assert accel.asked == [(4096, 10_000, 48_000)]
    assert issubclass(folder_10k.Driver, folder.Driver)
    added = set(vars(folder_10k.Driver)) - {"__module__", "__doc__", "__qualname__",
                                            "__firstlineno__", "__static_attributes__"}
    assert added == {"__init__"}, "the subclass adds the question and nothing else"


def test_the_program_answers_resident_for_the_toy_and_host_for_the_cell_without_a_chip():
    from crdt_enc_tpu.parallel import TpuAccelerator

    accel = TpuAccelerator()
    assert accel.orset_fold_route(32, 8, 384) == "resident"
    # no TPU under the tests: the real shape's planes cannot stay anywhere
    assert accel.orset_fold_route(4096, 10_000, 48_000) == "host"


# --------------------------------- the module strings of the kernel metrics


def lowered_name(jitted, *args, **kw) -> str:
    text = jitted.lower(*args, **kw).as_text()
    return text.split("module @", 1)[1].split()[0]


def test_pins_are_the_files_strings():
    strings = checks.kernel_strings(ROOT)
    for metric, want in PINS.items():
        assert strings[metric] == want, metric
        checks.check_kernel_metric_is_pinned(ROOT, metric)


def test_match_strings_name_the_modules_the_product_path_launches():
    import crdt_enc_tpu.ops as K
    from crdt_enc_tpu.ops import pallas_fold as PF

    E, R, N = 8, 8, 16
    i32 = lambda *shape: np.zeros(shape, np.int32)  # noqa: E731
    fold = lowered_name(
        K.orset_fold, i32(R), i32(E, R), i32(E, R), np.zeros(N, np.int8),
        i32(N), i32(N), i32(N), num_members=E, num_replicas=R)
    # accel._pick_dense_fold: the Pallas fold where eligible (its two layouts
    # lower only on the chip: the name jax.jit derives the module's from)
    folds = [fold] + ["jit_" + f.__name__ for f in (PF._fold_ablk, PF._fold_wide)]
    gather = lowered_name(K.orset_gather_cells, i32(E, R), i32(E, R), i32(N), i32(N))
    assert gather == "jit_orset_gather_cells"
    for modules, match, other in ((folds, FOLD, [gather]), ([gather], GATHER, folds)):
        for module in modules:
            assert any(m in module for m in match), (module, match)
        for m in match:
            assert any(m in module for module in modules), m
            assert not any(m in module for module in other), (
                f"{m!r} also matches a module of the other kernel")


# --------------------------------------------------- the gather's roofline


def test_gather_bytes_count_two_words_each_way_a_cell_and_the_clock_once():
    assert gather_bytes.orset_gather(cells=1, actors=0) == 16
    assert gather_bytes.orset_gather(cells=47_000, actors=1000) == 16 * 47_000 + 4000
    assert set(gather_bytes.FUNCTIONS) == {"orset_gather"}


def plane(name, **lines):
    return {"name": name, "lines": [{"name": k.replace("_", " "), "events": v}
                                    for k, v in lines.items()]}


def test_gather_roofline_reads_the_gathers_events_over_the_rounds_shapes():
    args = spec_of("gather_roofline.folder_10k")["args"]
    host = plane("/host:CPU", python=[["cellbench.call", 0.0, 1e9]])
    dev = plane("/device:TPU:0", XLA_Modules=[
        ["jit_orset_gather_cells(1)", 10.0, 2e6],    # 2 ms, in ns
        ["jit__fold_ablk(2)", 20.0, 8e6],
        ["jit_orset_gather_cells(1)", 5e8, 2e6]])
    shapes = [{"rows": 48_000, "cells": 47_000, "actors": 1000}] * 2
    window = {"calls": 2, "ops": 96_000, "spans": {}, "counters": {},
              "trace": {"planes": [host, dev]}, "shapes": shapes,
              "peaks": {"hbm_bytes_per_s": 819e9}}
    least = 2 * (16 * 47_000 + 4000)
    assert gather_roofline_pct.read(window, args) == pytest.approx(
        100 * least / 819e9 / 4e-3)
    assert 0 < gather_roofline_pct.read(window, args) < 100
    assert gather_roofline_pct.read({**window, "trace": None}, args) is None
    assert gather_roofline_pct.read({**window, "shapes": []}, args) is None
    only_fold = {"planes": [host, plane("/device:TPU:0", XLA_Modules=[
        ["jit__fold_ablk(2)", 20.0, 8e6]])]}
    assert gather_roofline_pct.read({**window, "trace": only_fold}, args) is None
    # the roofline's denominator by itself: the gather's 4 ms over two calls
    kernel = spec_of("gather_kernel_ms.folder")["args"]
    assert kernel["match"] == args["match"] and kernel["line"] == args["line"]
    assert trace_kernel_ms.read(window, kernel) == pytest.approx(2.0)
    assert trace_kernel_ms.read({**window, "trace": only_fold}, kernel) is None


def test_base_reuse_share_reads_the_two_counters_of_a_host_route_plan():
    args = spec_of("delta_base_reuse_pct.folder")["args"]
    window = {"calls": 4, "ops": 100, "spans": {}, "trace": None, "shapes": [],
              "peaks": {}, "counters": {"delta_base_reused": 3, "delta_base_unpacked": 1}}
    assert counter_ratio.read(window, args) == 75.0
    assert counter_ratio.read({**window, "counters": {"delta_base_reused": 4}}, args) == 100.0
    # a program that unpacks every round (the parent of PR 42 has neither counter)
    assert counter_ratio.read({**window, "counters": {"delta_base_unpacked": 4}}, args) == 0
    assert counter_ratio.read({**window, "counters": {"ops_folded": 5}}, args) is None


# ------------------------------------------------ the cell, end to end (toy)


def test_traced_toy_line_carries_every_listed_metric_but_the_device_trace(capsys):
    shrink = checks.tiny(MANIFEST, ROOT, CELL)
    assert shrink["config"] == checks.TINY["folder"], "picked by the driver's prefix"
    assert run.run_cell(CELL, 2**31 + 41, 0.5, True, require_tpu=False,
                        shrink=shrink) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert all(c == {"value": 0, "limit": 0} for c in line["compared"].values())
    listed = checks.listed(ROOT, CELL)
    assert DEVICE_ONLY <= {n for n, spec in listed.items()
                           if spec["source"] == "device_trace"}
    assert set(SHARED + OWN) - DEVICE_ONLY <= set(line["metrics"])
    checks.check_toy_line(ROOT, CELL, line["metrics"])
    for name, reading in line["metrics"].items():
        assert reading["unit"] == listed[name]["unit"], name
    value = {k: v["value"] for k, v in line["metrics"].items()}
    assert value["device_row_pct.folder"] == 100
    assert value["plane_cache_hit_pct.folder_10k"] == 100
    # 8 files of 48 ops a round: 384 rows in a class of 512, two words a row
    assert value["h2d_bytes_per_op.folder"] == pytest.approx(13 * 512 / 384)
    assert value["d2h_bytes_per_op.folder"] == pytest.approx((8 * 512 + 4 * 8) / 384)
    assert value["cells_pulled_per_op.folder"] == pytest.approx(2 * 512 / 384)
    assert value["fold_pull_ms.folder_10k"] > 0 and value["writeback_ms.folder"] > 0
    # every plan of the window diffed against the object the last verify left
    assert value["delta_base_reuse_pct.folder"] == 100
    assert 0 < value["delta_verify_pack_ms.folder"] < value["delta_seal_ms.folder"]
    assert 0 < value["delta_seal_only_ms.folder"] < value["delta_seal_ms.folder"]


def test_untraced_toy_line_reports_the_three_end_to_end_metrics(capsys):
    assert run.run_cell(CELL, 2**31 + 42, 0.5, False, require_tpu=False,
                        shrink=checks.tiny(MANIFEST, ROOT, CELL)) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True
    assert set(line["metrics"]) == {"compact_ops_per_s", "compact_ms", "setup_s"}
