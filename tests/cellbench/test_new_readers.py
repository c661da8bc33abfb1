"""The two readers PR 24 adds, on hand-made windows and on the recorded chip
trace, and the device module names that the ``match`` readers depend on,
pinned against the functions the product path calls."""

import glob
import json
import os

import numpy as np
import pytest

from cellbench import run
from cellbench.readers import span_self_ms, trace_launches

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = run.ROOT


def window(**kw):
    return {"calls": 2, "ops": 100, "spans": {}, "counters": {}, "trace": None,
            "shapes": [], "peaks": {}, **kw}


def span(seconds, count=1):
    return {"count": count, "seconds": seconds}


# ------------------------------------------------------------- span_self_ms


def test_span_self_ms_is_the_parent_less_its_children_per_call():
    w = window(spans={"root": span(3.0, 2), "a": span(1.0, 2), "b": span(0.5, 40),
                      "elsewhere": span(9.0)})
    args = {"span": "root", "children": ["a", "b", "never_fired"]}
    assert span_self_ms.read(w, args) == pytest.approx(1e3 * (3.0 - 1.5) / 2)


@pytest.mark.parametrize("w", [
    window(spans={"a": span(1.0)}),  # the parent did not fire: an older program
    # the parent fired under another meaning and no child did (PR 23's
    # serve.cycle): its whole wall is not a remainder
    window(spans={"root": span(1.0), "elsewhere": span(0.2)}),
    window(spans={"root": span(1.0), "a": span(0.5)}, calls=0),
])
def test_span_self_ms_has_nothing_to_read(w):
    assert span_self_ms.read(w, {"span": "root", "children": ["a"]}) is None


# ----------------------------------------------------------- trace_launches


def plane(name, **lines):
    return {"name": name, "lines": [{"name": k.replace("_", " "), "events": v}
                                    for k, v in lines.items()]}


def test_trace_launches_counts_what_starts_inside_the_calls_on_the_first_device():
    host = plane("/host:CPU", python=[["cellbench.call", 100.0, 50.0],
                                      ["cellbench.call", 300.0, 50.0],
                                      ["compact.gc", 110.0, 5.0]])
    dev0 = plane("/device:TPU:0", XLA_Modules=[
        ["jit_a", 90.0, 5.0],  # before the first call: the harness publishing
        ["jit_a", 100.0, 5.0], ["jit_b", 149.0, 5.0],  # starts inside, may end after
        ["jit_a", 150.0, 5.0],  # at the call's end: outside
        ["jit_a", 320.0, 1.0],
    ], XLA_Ops=[["fusion", 101.0, 1.0]])
    dev1 = plane("/device:TPU:1", XLA_Modules=[["jit_a", 101.0, 1.0]] * 7)
    w = window(trace={"planes": [host, dev0, dev1]})
    assert trace_launches.read(w, {"line": "XLA Modules"}) == pytest.approx(3 / 2)
    assert trace_launches.read(w, {"line": "XLA Ops"}) == pytest.approx(1 / 2)
    assert trace_launches.read(w, {"line": "no such line"}) is None
    assert trace_launches.read(window(), {"line": "XLA Modules"}) is None
    cpu_only = window(trace={"planes": [host]})
    assert trace_launches.read(cpu_only, {"line": "XLA Modules"}) is None


def test_trace_launches_on_the_recorded_chip_trace():
    with open(os.path.join(HERE, "data", "folder_backlog_2calls.json")) as fh:
        recorded = json.load(fh)
    w = window(trace=recorded)
    # one fold program a compact(), and its 284 ops in two calls
    assert trace_launches.read(w, {"line": "XLA Modules"}) == 1.0
    assert trace_launches.read(w, {"line": "XLA Ops"}) == 142.0


# ------------------------------------------- module names the readers match


def lowered_name(jitted, *args, **kw) -> str:
    """The name of the module ``jitted`` lowers to, as the profiler's
    ``XLA Modules`` line shows it."""
    text = jitted.lower(*args, **kw).as_text()
    return text.split("module @", 1)[1].split()[0]


def product_modules() -> dict:
    """metric -> names of the device modules of the jitted functions the
    product path calls for it.  The XLA folds lower here; the two Pallas
    layouts lower only on the chip, so the ``__name__`` that ``jax.jit``
    derives the module name from is pinned instead."""
    import crdt_enc_tpu.ops as K
    from crdt_enc_tpu.ops import pallas_fold as PF

    E, R, N, T = 8, 8, 16, 2
    i32 = lambda *shape: np.zeros(shape, np.int32)  # noqa: E731
    solo = lowered_name(
        K.orset_fold, i32(R), i32(E, R), i32(E, R), np.zeros(N, np.int8),
        i32(N), i32(N), i32(N), num_members=E, num_replicas=R)
    tenants = lowered_name(
        K.orset_fold_tenants, i32(T, R), i32(T, E, R), i32(T, E, R),
        np.zeros((T, N), np.int8), i32(T, N), i32(T, N), i32(T, N),
        num_members=E, num_replicas=R)
    pallas = ["jit_" + f.__name__ for f in (PF._fold_ablk, PF._fold_wide)]
    assert pallas == ["jit__fold_ablk", "jit__fold_wide"]
    # accel._pick_dense_fold: the Pallas fold where eligible, else K.orset_fold
    dense = [solo] + pallas
    return {"fold_kernel_ms.folder": dense, "orset_fold_roofline.folder": dense,
            "tenant_fold_kernel_ms.fleet": [tenants]}


MATCHING = sorted(
    os.path.basename(p)[:-len(".json")]
    for p in glob.glob(os.path.join(ROOT, "cellbench", "layer_metrics", "*.json"))
    if "match" in run.load_json(p).get("args", {})
)


@pytest.fixture(scope="module")
def modules():
    return product_modules()


@pytest.mark.parametrize("metric", MATCHING)
def test_match_strings_name_the_modules_the_product_path_launches(metric, modules):
    assert metric in modules, "a new `match` metric: pin its module here"
    match = run.load_json(ROOT, "cellbench", "layer_metrics", metric + ".json")["args"]["match"]
    for module in modules[metric]:
        assert any(m in module for m in match), (module, match)
    for m in match:
        assert any(m in module for module in modules[metric]), (
            f"{m!r} matches no module the product path launches")
