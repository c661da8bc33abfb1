"""``lwwmap_folder_10k.backlog`` (ISSUE 50): the first deployment that is no
OR-Set.  ``BASELINE.json``'s config 4 (an LWW-register map of 1M keys that
10,000 devices write timestamped writes into) through ``Core.compact()``: the
whole-batch ingest door, the per-op decode, the LWW column build, the winner
fold and its writeback, then a whole-snapshot seal.

What is held here: the manifest's entries for the cell (what must be there,
nothing positional); the configuration's widths are the source's; the
generator's ties; the plain reference on hand-made cases and against the
program's model on seeded data; the least-bytes count and the roofline reader
on made-up windows; the module strings of the two kernel metrics
(``lww_fold_kernel_ms.folder_lww``, ``lww_fold_roofline.folder_lww``:
``jit_lww_fold``, ``_lww_fold_pallas_impl``) pinned against the functions
``TpuAccelerator._fold_lww`` calls; the new spans' place in the tree; and the
cell end to end at toy size, with its two controls.

Nothing here is a measurement: the toy runs are on the CPU at toy sizes.
"""

import asyncio
import collections
import inspect
import json

import numpy as np
import pytest

from cellbench import gen, gen_lww, lww_bytes, reference_lww, run
from cellbench.drivers import folder, folder_lww
from cellbench.readers import counter_per_call, lww_roofline_pct, trace_kernel_ms

import manifest_checks as checks

ROOT = run.ROOT
MANIFEST = run.load_json(ROOT, "BENCHMARK.json")
CONFIG = "lwwmap_folder_10k"
CELL = "lwwmap_folder_10k.backlog"
LAYER = "LWW fold"

# the solo folder's entries the cell's program reaches, taken by its name
# appended to their ``workloads``
SHARED = [m + ".folder" for m in (
    "repl_status_ms", "watermark_ms", "storage_ms", "delta_plan_ms", "delta_seal_ms",
    "aead_ms", "aead_bytes_per_op", "unattributed_ms", "native_file_steps_pct", "gc_pause_ms",
    "gc_full_pause_ms", "device_launches", "device_row_pct", "h2d_bytes_per_op",
    "d2h_bytes_per_op")]
# what came with the cell: name -> (layer, the end-to-end metric it moves, source)
OWN = {
    "lww_decode_ms.folder_lww": (LAYER, "compact_ops_per_s", "program_span"),
    "lww_device_ms.folder_lww": (LAYER, "compact_ops_per_s", "program_span"),
    "lww_writeback_ms.folder_lww": (LAYER, "compact_ms", "program_span"),
    "ingest_decrypt_ms.folder_lww": ("AEAD open/seal", "compact_ops_per_s", "program_span"),
    "lww_pallas_pct.folder_lww": (LAYER, "compact_ops_per_s", "program_counter"),
    "lww_keys_written_pct.folder_lww": (LAYER, "compact_ms", "program_counter"),
    "lww_compiles_per_call.folder_lww": (LAYER, "compact_ms", "program_counter"),
    "lww_fold_kernel_ms.folder_lww": (LAYER, "compact_ops_per_s", "device_trace"),
    "lww_fold_roofline.folder_lww": (LAYER, "compact_ops_per_s", "device_trace"),
}
DEVICE_ONLY = {"device_launches.folder", "lww_fold_kernel_ms.folder_lww",
               "lww_fold_roofline.folder_lww"}
# the spans an LWW round opens that a listed span metric may read; the map has
# no fold session, no chunked ingest, no delta codec and no resident planes,
# so a metric that reads only their spans has nothing to read here
REACHED = {"repl.status", "repl.watermark", "ops.list", "ops.load", "compact.write",
           "compact.gc", "seal.state_obj", "delta.plan", "checkpoint.save", "compact.seal",
           "ops.bulk_decrypt", "ops.bulk_decode", "fold.lww.columns", "fold.lww.device",
           "fold.lww.writeback"}
# the device programs the kernel metrics match
LWW = ["jit_lww_fold", "_lww_fold_pallas_impl"]
PINS = {"lww_fold_kernel_ms.folder_lww": LWW, "lww_fold_roofline.folder_lww": LWW}
TOY = checks.toy(MANIFEST, ROOT, CELL)


def spec_of(metric: str) -> dict:
    return run.load_json(ROOT, "cellbench", "layer_metrics", metric + ".json")


# ------------------------------------------------ the manifest and the files


def check_lwwmap_folder_10k(manifest: dict, root: str) -> None:
    """What the manifest says of the cell, its configuration and its metrics:
    what must be there; later cells and entries may follow anywhere."""
    checks.hold_config(manifest, root, CONFIG, reduced=["initial_ops"])
    checks.hold_cell(manifest, root, CELL, config=CONFIG, traffic="backlog", chips=1,
                     end_to_end=["compact_ops_per_s", "compact_ms"])
    for metric, (layer, moves, source) in OWN.items():
        checks.hold_metric(manifest, metric, cells=[CELL], moves=moves, layer=layer,
                           source=source)
    checks.hold_metrics_in_order(manifest, [m for m in OWN if OWN[m][0] == LAYER],
                                 layer=LAYER)
    listed = checks.hold_cell_lists(root, CELL, SHARED + list(OWN))
    for name, spec in listed.items():
        assert not spec.get("may_be_absent"), name
        if spec["reader"] == "span_ms":
            assert REACHED & set(spec["args"]["spans"]), name
    for name in ("checkpoint_native_pct.folder", "decode_ms.folder", "writeback_ms.folder",
                 "fold_kernel_ms.folder", "orset_fold_roofline.folder",
                 "plane_cache_hit_pct.folder_10k", "ingest_load_ms.folder",
                 "delta_seal_only_ms.folder", "delta_base_reuse_pct.folder"):
        assert name not in listed, name


def test_the_cell_lists_these_and_no_metric_its_program_cannot_reach():
    check_lwwmap_folder_10k(MANIFEST, ROOT)


@pytest.mark.parametrize("metric", list(OWN))
def test_new_metric_file_agrees_with_its_entry(metric):
    checks.check_layer_metric(MANIFEST, ROOT, metric)
    spec = spec_of(metric)
    assert spec["driver"] == "folder_lww" and spec["what"]


def test_configuration_is_config_4_at_the_sources_widths():
    config = run.load_json(ROOT, "cellbench", "configs", CONFIG + ".json")
    ten_k = run.load_json(ROOT, "cellbench", "configs", "orset_folder_10k.json")
    baseline = run.load_json(ROOT, "BASELINE.json")["configs"][3]
    assert "LWW-Register map" in baseline and "10k replicas" in baseline
    assert "BASELINE.json configs[3]" in config["source"] and len(config["source"]) <= 200
    assert config["driver"] == "folder_lww" and config["crdt"] == "lwwmap"
    sizes = config["source_sizes"]
    assert config["devices"] == sizes["devices"] == 10_000
    assert config["members"] == sizes["keys"] == 1_000_000
    assert gen_lww.TS_BITS == 40 and "2^40" in sizes["timestamps"]
    assert gen_lww.VALUES == 100 and "0..99" in sizes["values"]
    # the one cut: two whole 48-op files a device, as the 10,000-device OR-Set
    assert list(config["reduced"]) == ["initial_ops"]
    assert sizes["initial_writes"] == 1_000_000
    assert config["initial_ops"] == 10_000 * 2 * 48 == ten_k["initial_ops"]
    for key in ("tenants", "ops_per_file", "remove_fraction", "initial_files_per_device",
                "storage", "cryptor", "key_cryptor", "accelerator"):
        assert config[key] == ten_k[key], key
    assert config["tie_fraction"] == 1 / 256
    assert {"ops_per_file", "remove_fraction", "tie_fraction",
            "keys_and_devices"} == set(config["assumed"])
    assert "host" in config["layout"] and "holds nothing between rounds" in config["layout"]
    assert len(config["guarantees"]) == 5
    assert any("resurrected" in g for g in config["guarantees"])
    cell = run.load_json(ROOT, "cellbench", "cells", CELL + ".json")
    assert {"fold session", "resident planes", "delta seal"} == set(cell["bypasses"])
    assert cell["users"] and len(cell["exercises"]) == 6


# ------------------------------------------------------------ the generator


def toy_plans(seed: int, **config) -> tuple:
    cell = run.load_cell(ROOT, CELL)
    config = {**cell["config"], **TOY["config"], **config}
    traffic = {**cell["traffic"], **TOY["traffic"]}
    plan = gen.plan_run(config, traffic, seed, 4)
    return plan, gen_lww.plan_lww(config, plan)


def test_generator_keeps_the_harness_schedule_and_draws_the_sources_columns():
    plan, lww = toy_plans(2**31 + 50)
    again = toy_plans(2**31 + 50)[1]
    other = toy_plans(2**31 + 51)[1]
    for key in ("ts", "value", "member"):
        assert np.array_equal(getattr(lww, key), getattr(again, key)), key
    assert not np.array_equal(lww.ts, other.ts)
    for key in ("kind", "actor", "live", "f_actor", "f_version"):
        assert np.array_equal(getattr(lww, key), getattr(plan, key)), key
    assert lww.round_files == plan.round_files and lww.opf == plan.opf
    assert lww.ts.min() >= 1 and lww.ts.max() < 2**40 and lww.ts.dtype == np.int64
    assert lww.value.min() >= 0 and lww.value.max() <= 99
    for r in range(-1, lww.n_rounds):
        assert lww.round_shape(r)["rows"] == plan.round_shape(r)["rows"]
    # the wire form of a file: [key, ts, actor, value, tombstone], live rows only
    tenant, actor, version, ops = lww.wire_file(len(lww.f_actor) - 1)
    assert tenant == 0 and version == int(lww.f_version[-1]) and len(actor) == 16
    rows = np.flatnonzero(lww.live[-lww.opf:]) + len(lww.kind) - lww.opf
    assert len(ops) == len(rows)
    for row, (key, ts, a, value, dead) in zip(rows.tolist(), ops):
        assert (key, ts, a, dead) == (lww.member[row], lww.ts[row], actor, lww.kind[row] == 1)
        assert value is None if dead else value == lww.value[row]


def test_ties_repeat_an_earlier_write_to_the_key_by_another_device():
    plan, lww = toy_plans(2**31 + 52, tie_fraction=0.25)
    loose = toy_plans(2**31 + 52, tie_fraction=0.0)[1]
    assert np.array_equal(lww.member, plan.member), "the keys are the harness's draw"
    assert len(np.unique(loose.ts)) == len(loose.ts), "40-bit draws alone never tie"
    tied = np.flatnonzero(lww.ts != loose.ts)
    assert 0.1 * len(lww.ts) < len(tied) < 0.3 * len(lww.ts)
    rows_of_ts: dict = {}
    for row, ts in enumerate(lww.ts.tolist()):
        rows_of_ts.setdefault(ts, []).append(row)
    for row in tied.tolist():
        twins = [j for j in rows_of_ts[int(lww.ts[row])] if j < row]
        assert twins, "a tie repeats an earlier write"
        assert all(lww.member[j] == lww.member[row] for j in twins), "to its own key"
        assert lww.actor[max(twins)] != lww.actor[row], "by another device"
    # the reference and the program's model agree on what the ties decide
    from crdt_enc_tpu.models import LWWMap

    rows = lww.live_rows(range(-1, lww.n_rounds))
    want = reference_lww.fold_rows(lww, rows).canonical()
    state = LWWMap()
    for f in range(len(lww.f_actor)):
        for op in lww.wire_file(f)[3]:
            state.apply(op)
    assert reference_lww.differing(state.to_obj(), want) == 0
    written = collections.Counter(zip(lww.member[rows].tolist(), lww.ts[rows].tolist()))
    assert any(written[k, e[0]] > 1 for k, e in want.items()), (
        "no entry was decided past the timestamp")


# ------------------------------------------------------------ the reference


A, B = bytes([1]) * 16, bytes([2]) * 16


def folded(*writes) -> dict:
    state = reference_lww.PlainLWWMap()
    for w in writes:
        state.write(*w)
    return state.canonical()


@pytest.mark.parametrize("first, second, held", [
    # an older put after a newer one changes nothing
    (("k", 9, A, 1, False), ("k", 8, B, 2, False), [9, A, 1, False]),
    # a delete against a concurrent, older put: the tombstone stays, and an
    # older put that arrives after it does not resurrect the key
    (("k", 9, A, None, True), ("k", 8, B, 2, False), [9, A, None, True]),
    # a newer put over a delete brings the key back
    (("k", 9, A, None, True), ("k", 10, B, 2, False), [10, B, 2, False]),
    # the three tie-breaks: actor bytes, then the value, then the tombstone
    (("k", 9, A, 99, False), ("k", 9, B, 1, False), [9, B, 1, False]),
    (("k", 9, A, 7, False), ("k", 9, A, 70, False), [9, A, 70, False]),
    (("k", 9, A, 99, False), ("k", 9, A, None, True), [9, A, None, True]),
    (("k", 9, A, None, False), ("k", 9, A, None, True), [9, A, None, True]),
], ids=["older_put_after_newer", "delete_against_older_put", "newer_put_over_delete",
        "actor", "value", "delete_over_every_value", "tombstone"])
def test_reference_on_hand_made_cases(first, second, held):
    assert folded(first, second) == folded(second, first) == {"k": held}


def test_reference_agrees_with_the_programs_model_on_the_same_cases_and_imports_none_of_it():
    from crdt_enc_tpu.models import LWWMap

    writes = [("k", 9, A, 99, False), ("k", 9, A, None, True), ("k", 9, B, 1, False),
              ("j", 3, B, 5, False), ("j", 3, B, 50, False), ("i", 1, A, None, True)]
    state = LWWMap()
    for key, ts, actor, value, dead in writes:
        state.apply([key, ts, actor, value, dead])
    want = folded(*writes)
    assert reference_lww.differing(state.to_obj(), want) == 0
    # differing counts a changed entry, and a key on either side alone
    got = {**state.to_obj(), "k": [9, B, 2, False], "extra": [1, A, 1, False]}
    del got["i"]
    assert reference_lww.differing(got, want) == 3
    with pytest.raises(ValueError):
        reference_lww.order_key(1, A, 200, False)
    source = inspect.getsource(reference_lww)
    assert "crdt_enc_tpu" not in source.split('"""', 2)[2]
    assert "import" not in source.split('"""', 2)[2].replace(
        "from __future__ import annotations", "")


# -------------------------------------------- the bytes and the roofline


def plane(name, **lines):
    return {"name": name, "lines": [{"name": k.replace("_", " "), "events": v}
                                    for k, v in lines.items()]}


def test_least_bytes_are_five_words_a_row_and_a_winner_tuple_a_key():
    assert lww_bytes.lww_fold(rows=1, keys=0) == 20
    assert lww_bytes.lww_fold(rows=0, keys=1) == 17
    assert lww_bytes.lww_fold(rows=48_000, keys=46_860) == 20 * 48_000 + 17 * 46_860
    assert set(lww_bytes.FUNCTIONS) == {"lww_fold"}


def test_roofline_reads_the_fold_programs_events_over_the_counters_sizes():
    args = spec_of("lww_fold_roofline.folder_lww")["args"]
    assert args["sizes"] == {"rows": "lww_fold_rows", "keys": "lww_fold_keys"}
    host = plane("/host:CPU", python=[["cellbench.call", 0.0, 1e9]])
    dev = plane("/device:TPU:0", XLA_Modules=[
        ["jit__lww_fold_pallas_impl(7)", 10.0, 3e5],   # 0.3 ms, in ns
        ["jit__fold_ablk(2)", 20.0, 8e6],              # the OR-Set's: not matched
        ["jit_lww_fold(9)", 5e8, 1e5]])
    window = {"calls": 2, "ops": 96_000, "spans": {}, "shapes": [],
              "counters": {"lww_fold_rows": 96_000, "lww_fold_keys": 93_700},
              "trace": {"planes": [host, dev]}, "peaks": {"hbm_bytes_per_s": 819e9}}
    least = 20 * 96_000 + 17 * 93_700
    assert lww_roofline_pct.read(window, args) == pytest.approx(
        100 * least / 819e9 / 4e-4)
    assert 0 < lww_roofline_pct.read(window, args) < 100
    # nothing to read: no trace, a program without the counters (the parent),
    # a window in which no fold program ran on the device
    assert lww_roofline_pct.read({**window, "trace": None}, args) is None
    assert lww_roofline_pct.read(
        {**window, "counters": {"lww_fold_rows": 96_000}}, args) is None
    assert lww_roofline_pct.read({**window, "counters": {}}, args) is None
    other = {"planes": [host, plane("/device:TPU:0", XLA_Modules=[
        ["jit__fold_ablk(2)", 20.0, 8e6]])]}
    assert lww_roofline_pct.read({**window, "trace": other}, args) is None
    # the roofline's denominator by itself: 0.4 ms over two calls
    kernel = spec_of("lww_fold_kernel_ms.folder_lww")["args"]
    assert kernel["match"] == args["match"] and kernel["line"] == args["line"]
    assert trace_kernel_ms.read(window, kernel) == pytest.approx(0.2)
    assert trace_kernel_ms.read({**window, "trace": other}, kernel) is None


@pytest.mark.parametrize("rows, keys", [(48_000, 46_860), (384, 32), (65_536, 65_536),
                                        (300, 1), (1, 1)])
def test_share_cannot_pass_100_by_the_construction_of_the_count(rows, keys):
    """Whatever folds reads every row's five words and writes every named
    key's tuple at the least; the program's kernels stream their padded
    classes, which are no smaller.  At the peak rate that alone takes the
    time the share divides by."""
    from crdt_enc_tpu.parallel.accel import _bucket

    args = spec_of("lww_fold_roofline.folder_lww")["args"]
    streamed = 20 * _bucket(rows) + 17 * _bucket(keys)
    assert lww_bytes.lww_fold(rows, keys) <= streamed
    ns = 1e9 * streamed / 819e9
    window = {"calls": 1, "counters": {"lww_fold_rows": rows, "lww_fold_keys": keys},
              "peaks": {"hbm_bytes_per_s": 819e9},
              "trace": {"planes": [plane("/device:TPU:0", XLA_Modules=[
                  ["jit__lww_fold_pallas_impl(7)", 0.0, ns]])]}}
    assert 0 < lww_roofline_pct.read(window, args) <= 100


def test_compiles_per_call_reads_zero_of_a_steady_fold_and_nothing_of_the_parent():
    args = spec_of("lww_compiles_per_call.folder_lww")["args"]
    window = {"calls": 4, "counters": {"lww_folds": 4}}
    assert counter_per_call.read(window, args) == 0
    window["counters"]["jax_compiles"] = 4  # the parent's behaviour, had it the counter
    assert counter_per_call.read(window, args) == 1
    assert counter_per_call.read({"calls": 4, "counters": {"jax_compiles": 4}}, args) is None


# --------------------------------- the module strings of the kernel metrics


def lowered_name(jitted, *args, **kw) -> str:
    text = jitted.lower(*args, **kw).as_text()
    return text.split("module @", 1)[1].split()[0]


def test_pins_are_the_files_strings():
    strings = checks.kernel_strings(ROOT)
    for metric, want in PINS.items():
        assert strings[metric] == want, metric
        checks.check_kernel_metric_is_pinned(ROOT, metric)


def test_match_strings_name_the_two_programs_fold_lww_dispatches():
    import crdt_enc_tpu.ops as K
    from crdt_enc_tpu.ops import pallas_lww as PL
    from crdt_enc_tpu.parallel.accel import TpuAccelerator

    i32 = lambda *shape: np.zeros(shape, np.int32)  # noqa: E731
    cascade = lowered_name(K.lww_fold, *(i32(16),) * 5, num_keys=8, num_values=8)
    pallas = lowered_name(PL._lww_fold_pallas_impl, *(i32(256),) * 5, num_keys=8,
                          num_values=8, tile_cap=256, interpret=True, limbs=(1, 1, 1))
    assert (cascade, pallas) == ("jit_lww_fold", "jit__lww_fold_pallas_impl")
    # the functions ``_fold_lww`` calls: the cascade by its name, the Pallas
    # fold through its wrapper, which jits nothing of its own
    source = inspect.getsource(TpuAccelerator._fold_lww)
    assert "K.lww_fold(" in source and "lww_fold_pallas(" in source
    assert "_lww_fold_pallas_impl(" in inspect.getsource(PL.lww_fold_pallas)
    orset = lowered_name(
        K.orset_fold, i32(8), i32(8, 8), i32(8, 8), np.zeros(16, np.int8),
        i32(16), i32(16), i32(16), num_members=8, num_replicas=8)
    gather = lowered_name(K.orset_gather_cells, i32(8, 8), i32(8, 8), i32(16), i32(16))
    others = [orset, gather, "jit__fold_ablk", "jit__fold_wide"]
    for module, match in ((cascade, LWW[0]), (pallas, LWW[1])):
        assert match in module
    for m in LWW:
        assert sum(m in module for module in (cascade, pallas)) == 1, m
        assert not any(m in module for module in others), m


# ------------------------------------------------------------- the driver


def test_driver_is_folders_with_three_things_replaced_and_publish_inherited():
    assert issubclass(folder_lww.Driver, folder.Driver)
    added = set(vars(folder_lww.Driver)) - {"__module__", "__doc__", "__qualname__",
                                            "__firstlineno__", "__static_attributes__"}
    assert added == {"__init__", "_replica", "check"}
    for name in ("open", "publish", "call", "end_to_end", "warm_object", "close"):
        assert getattr(folder_lww.Driver, name) is getattr(folder.Driver, name), name


class Folds:
    """An accelerator that compiles ``per_fold`` programs in every fold."""

    def __init__(self, per_fold):
        self.per_fold, self.asked = per_fold, []

    def fold_ops(self, state, ops):
        from crdt_enc_tpu.utils import trace

        self.asked.append((len(ops), len({op[0] for op in ops}),
                           len({op[3] for op in ops})))
        if self.per_fold[len(self.asked) - 1]:
            trace.add("jax_compiles", self.per_fold[len(self.asked) - 1])
        return state


def test_driver_refuses_a_program_that_compiles_for_every_batch(capsys):
    accel = Folds([1, 1])
    with pytest.raises(SystemExit) as stop:
        folder_lww.refuse_unless_steady(accel)
    assert stop.value.code == 2
    cap = capsys.readouterr()
    assert cap.out == "" and cap.err.count("\n") == 1
    assert "compiles in every round" in cap.err and "does not run on it" in cap.err
    # two batches of one class of rows, of keys and of values
    assert accel.asked == [(300, 280, 100), (300, 270, 100)]


def test_driver_accepts_a_program_whose_second_fold_compiles_nothing():
    from crdt_enc_tpu.parallel import TpuAccelerator

    folder_lww.refuse_unless_steady(Folds([1, 0]))
    folder_lww.refuse_unless_steady(Folds([0, 0]))  # a warm compile cache
    folder_lww.refuse_unless_steady(TpuAccelerator())  # the delivered program


def test_new_spans_hang_under_the_ingest_and_the_root_keeps_its_children(tmp_path):
    """``unattributed_ms.folder`` subtracts ``core.compact``'s direct
    children: the LWW fold's spans open under ``compact.ingest`` and add none."""
    from crdt_enc_tpu.utils import trace

    async def one_timed_call():
        cell = run.load_cell(ROOT, CELL)
        config = {**cell["config"], **TOY["config"]}
        plan = gen.plan_run(config, {**cell["traffic"], **TOY["traffic"]}, 2**31 + 53, 3)
        driver = folder_lww.Driver(config, plan, str(tmp_path))
        await driver.open()
        for r in range(2):
            await driver.publish(r)
            await driver.call(r)
        await driver.publish(2)
        trace.reset()
        outcome = await driver.call(2)
        return trace.tree(), trace.snapshot(), outcome

    tree, snap, outcome = asyncio.run(one_timed_call())
    trace.reset()
    assert outcome["failed"] == 0 and tree[None] == ["core.compact"]
    children = spec_of("unattributed_ms.folder")["args"]["children"]
    assert set(tree["core.compact"]) <= set(children)
    assert {"ops.bulk_decrypt", "ops.bulk_fold"} <= set(tree["compact.ingest"])
    assert snap["spans"]["ops.bulk_decode"]["parents"] == ["ops.bulk_fold"]
    for name in ("fold.lww.columns", "fold.lww.device", "fold.lww.writeback"):
        assert snap["spans"][name]["parents"] == ["ops.bulk_fold"], name
        assert snap["spans"][name]["count"] == 1


# ------------------------------------------------ the cell, end to end (toy)


def test_traced_toy_line_carries_every_listed_metric_but_the_device_trace(capsys):
    assert TOY["config"]["devices"] >= 16, "the whole-batch door opens at 16 files"
    assert run.run_cell(CELL, 2**31 + 54, 0.5, True, require_tpu=False, shrink=TOY) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert set(line["compared"]) == {"compactor_vs_reference", "fresh_replica_vs_reference",
                                     "fresh_replica_bytes_vs_compactor"}
    assert all(c == {"value": 0, "limit": 0} for c in line["compared"].values())
    listed = checks.listed(ROOT, CELL)
    assert DEVICE_ONLY == {n for n, spec in listed.items()
                           if spec["source"] == "device_trace"}
    assert set(line["metrics"]) == set(SHARED + list(OWN)) - DEVICE_ONLY == (
        set(listed) - DEVICE_ONLY)
    checks.check_toy_line(ROOT, CELL, line["metrics"])
    for name, reading in line["metrics"].items():
        assert reading["unit"] == listed[name]["unit"], name
    value = {k: v["value"] for k, v in line["metrics"].items()}
    assert value["device_row_pct.folder"] == 100
    assert value["lww_pallas_pct.folder_lww"] == 0, "no TPU: the cascade folds"
    # 16 files of 48 writes a round: 768 rows in a class of 1,024, five words
    # a row up; the winner tables of the some 400 keys they name, in a class
    # of 512, down, 17 bytes a key
    assert value["h2d_bytes_per_op.folder"] == pytest.approx(20 * 1024 / 768)
    assert value["d2h_bytes_per_op.folder"] == pytest.approx(17 * 512 / 768)
    assert value["lww_compiles_per_call.folder_lww"] == 0
    assert 0 < value["lww_keys_written_pct.folder_lww"] <= 100
    for name in ("lww_decode_ms", "lww_device_ms", "lww_writeback_ms", "ingest_decrypt_ms"):
        assert value[name + ".folder_lww"] > 0, name


def test_untraced_toy_line_reports_the_three_end_to_end_metrics(capsys):
    assert run.run_cell(CELL, 2**31 + 55, 0.5, False, require_tpu=False, shrink=TOY) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True
    assert set(line["metrics"]) == {"compact_ops_per_s", "compact_ms", "setup_s"}


def test_control_a_withheld_op_file_is_not_correct(capsys):
    assert run.run_cell(CELL, 2**31 + 56, 0.5, False, require_tpu=False, shrink=TOY,
                        fault="withhold_file") == 0
    cap = capsys.readouterr()
    line = json.loads(cap.out.strip().splitlines()[-1])
    assert line["correct"] is False
    assert line["compared"]["compactor_vs_reference"]["value"] > 0
    assert line["compared"]["fresh_replica_vs_reference"]["value"] > 0
    assert " FAILED\n" in cap.err


def test_narrowed_timestamps_in_the_timed_path_are_not_correct(capsys, monkeypatch):
    """The timed path broken underneath, as ``test_cellbench.py`` breaks the
    OR-Set's: what the fold hands back loses the top limb of the timestamp's
    low word (what a limb count one too small would do)."""
    import crdt_enc_tpu.ops as K

    whole = K.lww_fold

    def low_limbs(*columns, **static):
        m_hi, m_lo, m_actor, m_value, present = whole(*columns, **static)
        return m_hi, m_lo & 0xFFFFFF, m_actor, m_value, present

    monkeypatch.setattr(K, "lww_fold", low_limbs)
    assert run.run_cell(CELL, 2**31 + 57, 0.5, False, require_tpu=False, shrink=TOY) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is False
    assert line["compared"]["compactor_vs_reference"]["value"] > 0
