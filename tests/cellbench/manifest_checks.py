"""What the benchmark's tests hold a manifest and its data files to, as
functions of a manifest and the root it was loaded from: the tests of the
committed benchmark call them with ``BENCHMARK.json`` and the checkout, and
``test_harness_takes_additions.py`` calls them again on a temporary root to
which configurations, a mix, cells and per-layer metrics were appended.  A
check says what must be there, never how many entries there are or where in a
list one stands, so a later PR that appends needs no edit here.

**This module is the only way into the manifest** (ISSUE 49).  A test file
hands ``BENCHMARK.json`` whole to a function of this module and never
subscripts it, iterates over it or takes an entry out of it.  What a cell's
test file holds about its own entries it states in a function of its own named
``check_*`` with exactly the parameters ``(manifest, root)``, built from the
``hold_*`` functions here, which can say "is in", "in this order" and "lists",
and cannot say "is last" or "lists nothing else".
``manifest_level_checks()`` finds every such function, here and in every
``test_*.py`` beside this file (a later PR's among them), and the harness test
runs them all on its augmented root: a pin of a position or a count fails
there, in the PR that writes it, and not in the next PR that appends.

Code (readers, drivers) is always the checkout's; only data is per root.
"""

import glob
import importlib
import inspect
import json
import os
import re

from cellbench import run

CODE = run.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
KINDS = ("configs", "workloads", "end_to_end", "per_layer")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
# how many entries the driver of the round admits in each list (its contract):
# the only counts a test holds, and no number a later PR has to move
LIMITS = {"configs": 24, "workloads": 24, "end_to_end": 16, "per_layer": 128}

# toy sizes laid over the real files: an argument the command never passes
TINY = {
    "folder": {"devices": 8, "members": 32, "initial_files_per_device": 3},
    "fleet": {"tenants": 6, "members": 16, "initial_files_per_device": 8},
}
# the writers of a toy round, per kind of deployment: (the mix's key, the toy's size)
WRITERS = {"folder": ("active_devices", "devices"), "fleet": ("active_tenants", "tenants")}
# trickle: 6 files of 48 ops are 288 rows, past the accelerator's smallest
# device batch (256), so a toy round folds on the device as the cell's does
TINY_WRITERS = {"backlog": 8, "trickle": 6, "busy": 6, "quiet": 2}


def cells(manifest: dict) -> list:
    return [w["name"] for w in manifest["workloads"]]


def metrics(manifest: dict) -> list:
    return [m["name"] for m in manifest["per_layer"]]


def pairs(manifest: dict) -> list:
    """Every (per-layer metric, cell) pair the manifest lists."""
    return [(m["name"], cell) for m in manifest["per_layer"] for cell in m["workloads"]]


def entry_of(manifest: dict, kind: str, name: str) -> dict:
    return next(x for x in manifest[kind] if x["name"] == name)


def config_of(manifest: dict, root: str, cell: str) -> dict:
    """The configuration file of a cell, found through the manifest."""
    entry = entry_of(manifest, "workloads", cell)
    return run.load_json(root, entry_of(manifest, "configs", entry["config"])["file"])


def tiny(manifest: dict, root: str, cell: str) -> dict:
    """The toy overlay of a cell.  Which overlay is the configuration file's
    ``driver`` (``folder*`` or ``fleet*``), not its name; the writers of a mix
    the table does not know are what its file asks for, as far as the toy
    deployment has them."""
    entry = entry_of(manifest, "workloads", cell)
    driver = config_of(manifest, root, cell)["driver"]
    kind = next(k for k in TINY if driver.startswith(k))
    key, size = WRITERS[kind]
    writers = TINY_WRITERS.get(entry["traffic"])
    if writers is None:
        mix = run.load_json(root, "cellbench", "traffic", entry["traffic"] + ".json")
        writers = min(mix[key], TINY[kind][size])
    return {"config": TINY[kind], "traffic": {key: writers, "max_ops_per_s": 2500}}


def toy(manifest: dict, root: str, cell: str) -> dict:
    """The overlay under which a traced CPU line of ``cell`` carries every
    metric the cell lists: ``tests/cellbench/toys/<cell>.json`` where the
    cell's tests brought one (a toy of the family's size has no peer with a
    share of its own, no tenant large enough to spill), else ``tiny()``."""
    path = os.path.join(root, "tests", "cellbench", "toys", cell + ".json")
    return run.load_json(path) if os.path.exists(path) else tiny(manifest, root, cell)


def check_contract_keys(manifest: dict, root: str) -> None:
    assert set(manifest) == {"command", "paths", "run_seconds", *KINDS}
    assert os.path.getsize(os.path.join(root, "BENCHMARK.json")) <= 64 * 1024
    assert 1 <= manifest["run_seconds"] <= 51
    assert manifest["paths"] == ["cellbench", "tests/cellbench"]
    assert all(not w.startswith("/") and ".." not in w for w in manifest["command"])


def check_list_lengths(manifest: dict, root: str) -> None:
    """Each list holds at least one entry and no more than the driver admits."""
    for kind, most in LIMITS.items():
        assert 1 <= len(manifest[kind]) <= most, (kind, len(manifest[kind]), most)


def check_names_and_units(manifest: dict, root: str) -> None:
    names = [x["name"] for kind in KINDS for x in manifest[kind]]
    names += [w["traffic"] for w in manifest["workloads"]]
    names += [k for c in manifest["configs"] for k in c["reduced"]]
    assert all(NAME.match(n) for n in names), [n for n in names if not NAME.match(n)]
    for kind in KINDS:
        listed = [x["name"] for x in manifest[kind]]
        assert len(listed) == len(set(listed))
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for m in manifest["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}


def check_four_chip_share(manifest: dict, root: str) -> None:
    """At most half of the cells, rounded down, but always one, may ask for 4."""
    four = [w["name"] for w in manifest["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(manifest["workloads"]) // 2), four


def check_cell(manifest: dict, root: str, cell: str) -> None:
    entry = entry_of(manifest, "workloads", cell)
    assert set(entry) == {"name", "config", "traffic", "chips", "why"}
    assert entry["chips"] in (1, 4)
    assert len(entry["why"]) <= 200
    loaded = run.load_cell(root, cell)
    assert {k: loaded["cell"][k] for k in entry} == entry
    config = entry_of(manifest, "configs", entry["config"])
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    assert config["file"].startswith("cellbench/")
    assert sorted(loaded["config"]["reduced"]) == config["reduced"]
    assert loaded["config"]["guarantees"], "a deployment states its guarantees"
    # every cell reports set-up, another end-to-end metric and a layer metric
    reported = [m["name"] for m in loaded["end_to_end"]]
    assert "setup_s" in reported and len(reported) >= 2
    assert loaded["per_layer"]


def check_every_cell(manifest: dict, root: str) -> None:
    for cell in cells(manifest):
        check_cell(manifest, root, cell)


def families(spec: dict) -> tuple:
    """The driver families of a metric file: its ``driver`` is a prefix, or a
    list of prefixes, of the driver modules whose cells may list it
    (``"folder"`` admits ``folder``, ``folder_peers``, ``folder_10k``)."""
    return tuple(spec["driver"]) if isinstance(spec["driver"], list) else (spec["driver"],)


def check_pair(manifest: dict, root: str, metric: str, cell: str) -> None:
    """One (metric, cell) pair of the manifest: the cell exists, reports the
    end-to-end metric that the metric moves, and is driven by a module of the
    metric's family.  Which cells a metric has is its entry's ``workloads``
    and nothing else: a later cell of the family takes the metric by
    appending its name there, with no metric file touched."""
    entry = entry_of(manifest, "per_layer", metric)
    spec = run.load_json(root, "cellbench", "layer_metrics", metric + ".json")
    known = cells(manifest)
    assert cell in known, (metric, cell)
    moved = entry_of(manifest, "end_to_end", entry["moves"])
    assert cell in moved.get("workloads", known), (metric, cell)
    driver = config_of(manifest, root, cell)["driver"]
    assert driver.startswith(families(spec)), (metric, cell, driver)


def check_layer_metric(manifest: dict, root: str, metric: str) -> None:
    entry = entry_of(manifest, "per_layer", metric)
    assert set(entry) <= {"name", "unit", "better", "source", "layer", "moves",
                          "workloads"}
    assert entry["source"] in SOURCES
    spec = run.load_json(root, "cellbench", "layer_metrics", metric + ".json")
    assert spec["name"] == metric
    for key in ("layer", "unit", "better", "source", "moves"):
        assert spec[key] == entry[key], key
    assert os.path.exists(
        os.path.join(CODE, "cellbench", "readers", spec["reader"] + ".py"))
    assert entry["workloads"] and len(set(entry["workloads"])) == len(entry["workloads"])
    for cell in entry["workloads"]:
        check_pair(manifest, root, metric, cell)
    if "_roofline" in metric:
        assert entry["unit"] == "%"


def check_every_layer_metric(manifest: dict, root: str) -> None:
    """Every entry against its file, and every (metric, cell) pair in it."""
    for metric in metrics(manifest):
        check_layer_metric(manifest, root, metric)


def definition(spec: dict) -> dict:
    """What a metric file defines, less what two files of one definition may
    differ in: the name, the family, the words."""
    return {k: v for k, v in spec.items()
            if k not in ("name", "driver", "what", "may_be_absent")}


def check_no_two_files_define_the_same(manifest: dict, root: str) -> None:
    """Every file under ``layer_metrics/`` is an entry's and every entry has
    its file; no two files are equal but for name, driver, words and
    ``may_be_absent``: a cell that wants a metric that is there is appended
    to its entry's ``workloads``, never served by a copy of the file."""
    paths = sorted(glob.glob(os.path.join(root, "cellbench", "layer_metrics", "*.json")))
    names = [os.path.basename(p)[:-len(".json")] for p in paths]
    assert sorted(m["name"] for m in manifest["per_layer"]) == names
    seen = {}
    for name, path in zip(names, paths):
        key = json.dumps(definition(run.load_json(path)), sort_keys=True)
        assert key not in seen, f"{name} is {seen[key]} again: list the cell there"
        seen[key] = name


def listed(root: str, cell: str) -> dict:
    """name -> metric file (with the manifest's name and unit) of every
    per-layer metric that lists ``cell``."""
    return {m["name"]: m for m in run.load_cell(root, cell)["per_layer"]}


def demanded(spec: dict) -> bool:
    """Whether a CPU toy line of a cell that lists the metric must carry it."""
    return spec["source"] != "device_trace" and not spec.get("may_be_absent")


def check_toy_line(root: str, cell: str, carried) -> None:
    """A traced CPU toy line of ``cell`` against what the cell lists.  Nothing
    the line carries is unlisted; everything listed is there but what the
    metric's own file says cannot be: a reading of the device trace (the CPU
    has none), and a metric whose reader by design leaves a quiet counter or
    an unopened span out of a window (``may_be_absent``: at a toy's six
    tenants nobody waits for one of sixteen slots)."""
    specs = listed(root, cell)
    carried = set(carried)
    assert carried <= set(specs), carried - set(specs)
    must = {name for name, spec in specs.items() if demanded(spec)}
    assert must <= carried, must - carried
    assert not any(specs[name]["source"] == "device_trace" for name in carried)


def kernel_strings(root: str) -> dict:
    """metric -> the device module strings its reader matches, for every
    metric file with ``args.match`` or ``args.modules``."""
    out = {}
    for path in sorted(glob.glob(os.path.join(root, "cellbench", "layer_metrics", "*.json"))):
        args = run.load_json(path).get("args", {})
        strings = list(args.get("match", [])) + list(args.get("modules", []))
        if strings:
            out[os.path.basename(path)[:-len(".json")]] = strings
    return out


def check_kernel_metric_is_pinned(root: str, metric: str) -> None:
    """Some test file under ``<root>/tests/cellbench`` names the metric and
    every module string it matches: a kernel metric brings the pin of its
    module names in a test file of its own."""
    strings = kernel_strings(root)[metric]
    for path in sorted(glob.glob(os.path.join(root, "tests", "cellbench", "*.py"))):
        with open(path) as fh:
            text = fh.read()
        if metric in text and all(s in text for s in strings):
            return
    raise AssertionError(
        f"no file under tests/cellbench names {metric} and its module strings "
        f"{strings}: pin them against the jitted functions in a new test file")


def check_every_kernel_metric_is_pinned(manifest: dict, root: str) -> None:
    for metric in kernel_strings(root):
        check_kernel_metric_is_pinned(root, metric)


# --------------- what a cell's own ``check_*(manifest, root)`` is built from


def in_order(listed, wanted) -> bool:
    """``wanted`` appears in ``listed`` in that order; others may stand
    before, between and after."""
    rest = iter(listed)
    return all(name in rest for name in wanted)


def hold_cell(manifest: dict, root: str, cell: str, *, config: str, traffic: str,
              chips: int, end_to_end) -> None:
    """The cell is among the workloads as its file has it (``check_cell``),
    on that configuration, mix and chips, and reports exactly ``end_to_end``
    and ``setup_s`` (what a cell is judged on is its own, and no later PR's)."""
    check_cell(manifest, root, cell)
    entry = entry_of(manifest, "workloads", cell)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (config, traffic, chips)
    reported = {m["name"] for m in manifest["end_to_end"]
                if cell in m.get("workloads", [cell])}
    assert reported == {*end_to_end, "setup_s"}, reported


def hold_config(manifest: dict, root: str, config: str, *, reduced) -> dict:
    """The configuration's entry gives its file's source (at most 200
    characters) and its cuts; returns the file."""
    entry = entry_of(manifest, "configs", config)
    file = run.load_json(root, entry["file"])
    assert entry["source"] == file["source"] and len(file["source"]) <= 200
    assert entry["reduced"] == sorted(file["reduced"]) == sorted(reduced)
    return file


def hold_metric(manifest: dict, metric: str, *, cells=(), moves=None, layer=None,
                source=None) -> None:
    """The entry lists ``cells`` in that order (a later cell may be listed
    anywhere) and, where given, moves that metric from that layer."""
    entry = entry_of(manifest, "per_layer", metric)
    assert in_order(entry["workloads"], cells), (metric, entry["workloads"])
    for key, want in (("moves", moves), ("layer", layer), ("source", source)):
        assert want is None or entry[key] == want, (metric, key, entry[key])


def hold_metrics_in_order(manifest: dict, names, layer=None) -> None:
    """``names`` are entries, in that order among themselves (of ``layer``,
    where one is given: each is then of it)."""
    listed = [m["name"] for m in manifest["per_layer"]
              if layer is None or m["layer"] == layer]
    assert in_order(listed, names), (names, listed)


def hold_cell_lists(root: str, cell: str, names) -> dict:
    """The cell lists ``names`` at the least; returns all it lists."""
    got = listed(root, cell)
    assert set(names) <= set(got), set(names) - set(got)
    return got


# ------------------------------------------------ every check there is


def manifest_level_checks() -> dict:
    """``"<module>.<function>" -> function`` for every function named
    ``check_*`` whose parameters are exactly ``(manifest, root)``, in this
    module and in every ``test_*.py`` beside it."""
    here = os.path.dirname(os.path.abspath(__file__))
    names = [__name__] + sorted(
        os.path.basename(p)[:-len(".py")]
        for p in glob.glob(os.path.join(here, "test_*.py")))
    found = {}
    for name in names:
        module = importlib.import_module(name)
        for attr, fn in sorted(vars(module).items()):
            if (attr.startswith("check_") and inspect.isfunction(fn)
                    and fn.__module__ == module.__name__
                    and tuple(inspect.signature(fn).parameters) == ("manifest", "root")):
                found[f"{name}.{attr}"] = fn
    return found
