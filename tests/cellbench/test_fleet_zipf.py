"""The skewed fleet: its generator, its driver's warm-up of the shapes a window
drifts into, and the cell end to end with several bucket classes and a solo
spill, all at toy sizes on the CPU.  Nothing here is a measurement.
"""

import asyncio
import importlib
import json

import numpy as np
import pytest

from cellbench import gen, gen_zipf, run
from crdt_enc_tpu.serve import TenantShape, plan_buckets
from crdt_enc_tpu.utils import trace

import manifest_checks as checks

ROOT = run.ROOT
MANIFEST = run.load_json(ROOT, "BENCHMARK.json")
CELL = "orset_fleet_zipf.busy"
# the metrics the cell came with (PR 26): five of its own, and eight of the
# uniform fleet's that it reads from their entries (ISSUE 43; it had copies);
# later entries may list the cell too
THIRTEEN = {m + ".fleet_zipf" for m in (
    "buckets_per_cycle", "solo_spills_per_cycle", "stack_fill_pct", "warm_hit_pct",
    "solo_fold_ms")} | {m + ".fleet" for m in (
    "ingest_wall_ms", "fold_wall_ms", "seal_wall_ms",
    "unattributed_ms", "device_launches", "h2d_bytes_per_op", "d2h_bytes_per_op",
    "d2h_pulls_per_tenant")}
# the overlay tests/cellbench/test_cellbench.py lays over every fleet cell
OVERLAY = {"tenants": 6, "members": 16, "initial_files_per_device": 8}
# 12 tenants, several bucket classes and a solo spill
# (tests/cellbench/toys/<cell>.json says why)
SPILL = checks.toy(MANIFEST, ROOT, CELL)
# 24 tenants whose vocabularies are still filling: tenants change class, and
# buckets their slot count, from one round to the next; rank 2's head (1,024
# ops) is past rows_cap, so it folds alone there and first cuts in round 1
DRIFT = {"tenants": 24, "members": 2048, "team_ranks": 1,
         "initial_files_per_device": 1, "serve": {"cells_cap": 8192, "rows_cap": 900}}


def cell_config(**over) -> tuple:
    cell = run.load_cell(ROOT, CELL)
    config = {**cell["config"], **over}
    traffic = {**cell["traffic"], "active_tenants": config["tenants"],
               "max_ops_per_s": 6000}
    return config, traffic


def plans(seed: int, n_rounds: int = 4, **over):
    config, traffic = cell_config(**over)
    uniform = gen.plan_run(config, traffic, seed, n_rounds)
    return config, uniform, gen_zipf.plan_zipf(config, uniform)


# ------------------------------------------------------------ the generator


def test_sizes_follow_the_rank_size_law():
    config, _ = cell_config()
    sizes = gen_zipf.vocabularies(config)
    assert sizes[0] == 131072 and sizes[1] == 65536 and sizes[-1] == 128
    assert all(sizes[k - 1] == max(64, 131072 // k) for k in (3, 7, 100, 683, 1024))
    small = gen_zipf.vocabularies({**config, "tenants": 4096})
    assert small[-1] == 64, "the floor binds in a larger fleet"
    assert (gen_zipf.vocabularies({**config, **OVERLAY}) == 16).all()


def test_every_seed_gives_the_same_sizes_and_work_on_other_tenants():
    over = {"tenants": 40, "members": 4096}
    config, ua, a = plans(1, **over)
    _, ub, b = plans(2**31 + 26, **over)
    assert sorted(a.vocab.tolist()) == sorted(b.vocab.tolist())
    assert sorted(a.vocab.tolist()) == sorted(gen_zipf.vocabularies(config).tolist())
    assert a.round_files == b.round_files
    assert sorted(np.diff(a.f_start).tolist()) == sorted(np.diff(b.f_start).tolist())
    assert len(a.kind) == len(b.kind)
    for r in range(a.n_rounds):  # every round: the mix's files, ops_per_file each
        assert len(a.files_of_round(r)) == len(ua.files_of_round(r))
        rows = a.rows_of_round(r)
        assert rows.stop - rows.start == len(a.files_of_round(r)) * a.opf
    assert (a.rank != b.rank).any() and (a.member[:1000] != b.member[:1000]).any()
    _, _, again = plans(1, **over)
    assert (again.member == a.member).all() and (again.rank == a.rank).all()
    # a tenant's head: files of one size, initial_files_per_device a writer,
    # max(the source's head, its vocabulary) ops rounded up to whole files
    for t in range(a.tenants):
        files = [f for f in a.files_of_round(-1) if a.f_actor[f] // a.devices == t]
        sizes = {int(a.f_start[f + 1] - a.f_start[f]) for f in files}
        assert len(files) == a.writers[t] * config["initial_files_per_device"]
        assert len(sizes) == 1
        want = max(config["initial_files_per_device"] * 4 * 24, int(a.vocab[t]))
        assert want <= sizes.pop() * len(files) < want + len(files)
        assert a.member[a.actor // a.devices == t].max() < a.vocab[t]
    assert sorted(a.writers.tolist())[-5:] == [4, 16, 16, 16, 16]
    # versions dense from 1 per writer, dots dense from 1 per writer
    for actor in set(a.f_actor.tolist()):
        versions = a.f_version[a.f_actor == actor]
        assert versions.tolist() == list(range(1, len(versions) + 1))
        adds = a.counter[(a.actor == actor) & (a.kind == 0)]
        assert adds.tolist() == list(range(1, len(adds) + 1))
    # a round's writers are the uniform plan's, a team's lifted to its own
    files = a.files_of_round(0)
    mine = a.f_actor[files.start:files.stop]
    theirs = ua.f_actor[ua.files_of_round(0).start:ua.files_of_round(0).stop]
    assert (mine // a.devices == theirs // ua.devices).all()
    assert (mine % a.devices % ua.devices == theirs % ua.devices).all()
    assert (mine % a.devices < a.writers[mine // a.devices]).all()


def test_the_tests_overlay_gives_a_runnable_plan():
    _, uniform, plan = plans(3, **OVERLAY)
    assert (plan.vocab == 16).all() and plan.tenants == 6
    tenant, actor, version, ops = plan.wire_file(0)
    assert tenant == 0 and version == 1 and len(actor) == 16 and ops
    assert plan.reached()[-1].max() <= 16
    assert plan.rows_per_tenant(0).sum() == plan.live[plan.rows_of_round(0)].sum()
    assert len(plan.live_rows([-1, 0])) == plan.live[:plan.rows_of_round(0).stop].sum()


# ------------------------------------------------- the cell, through run_cell


def check_the_thirteen(manifest: dict, root: str) -> None:
    """The thirteen are among what the cell lists, whatever else lists it."""
    assert len(THIRTEEN) == 13
    checks.hold_cell_lists(root, CELL, THIRTEEN)


def test_cell_runs_several_bucket_classes_and_a_solo_spill(capsys):
    assert run.run_cell(CELL, 2**31 + 26, 0.5, True, require_tpu=False,
                        shrink=SPILL) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    metrics = {k: v["value"] for k, v in line["metrics"].items()}
    assert metrics["buckets_per_cycle.fleet_zipf"] >= 3
    assert metrics["solo_spills_per_cycle.fleet_zipf"] >= 1
    assert metrics["solo_fold_ms.fleet_zipf"] > 0
    assert 0 < metrics["stack_fill_pct.fleet_zipf"] < 100
    # the spilled tenant is looked up, and missed, every cycle
    assert metrics["warm_hit_pct.fleet_zipf"] == pytest.approx(100 * 11 / 12)
    check_the_thirteen(MANIFEST, ROOT)
    checks.check_toy_line(ROOT, CELL, metrics)
    assert THIRTEEN - set(metrics) == {"device_launches.fleet"}, "the CPU has no device trace"


# ------------------------------------- the driver's warm-up, the span tree


async def drifting_window(workdir: str) -> dict:
    """Every prepared round of the drifting fleet through its driver, as the
    harness runs them: what the planner made of each round, the compiles
    after the first, and the span tree of the last call."""
    from crdt_enc_tpu.obs import runtime as obs_runtime

    obs_runtime.track_recompiles()
    config, traffic = cell_config(**DRIFT)
    plan = gen.plan_run(config, traffic, 7, 6)
    module = importlib.import_module(f"cellbench.drivers.{config['driver']}")
    driver = module.Driver(config, plan, workdir)
    await driver.open()
    try:
        seen, compiles = [], []
        for r in range(plan.n_rounds):
            await driver.publish(r)
            trace.reset()
            trace.enable_events()
            outcome = await driver.call(r)
            assert outcome["failed"] == 0 and outcome["ops"] > 0
            snap = trace.snapshot()
            seen.append({tuple(int(x) for x in e["meta"].split(":")[1].split("x"))
                         for e in trace.events() if e["name"] == "serve.fold"})
            compiles.append(snap["counters"].get("jax_compiles", 0))
        tree = trace.tree()
        checks = await driver.check()
        # a shape whose half class is exactly rows_cap: the throw-away head
        # names that half and stays batched, the round grows it and cuts
        driver.serve_config = type(driver.serve_config)(cells_cap=1 << 15, rows_cap=1024)
        trace.reset()
        await driver._fold_once(1, 64, 2048, 8)
        once = trace.snapshot()
        return {"driver": driver, "seen": seen, "compiles": compiles, "tree": tree,
                "snap": snap, "checks": checks, "once": once}
    finally:
        trace.reset()
        await driver.close()


@pytest.fixture(scope="module")
def drifted(tmp_path_factory):
    return asyncio.run(drifting_window(str(tmp_path_factory.mktemp("drift"))))


def test_driver_knows_the_bucket_shapes_the_planner_makes(drifted):
    driver = drifted["driver"]
    want = [driver.bucket_shapes(r) for r in range(driver.plan.n_rounds)]
    assert drifted["seen"] == want
    assert set().union(*want[1:]) - want[0], "the toy drifts: later rounds bring new shapes"
    assert set().union(*(driver.growths(r) for r in range(1, driver.plan.n_rounds)))
    alone = (1, 64, 1024, 8)  # rank 2, alone in its class and without warm planes in round 0
    assert alone in want[0] and alone not in driver.bucket_shapes(0, cut=True)
    assert alone in driver.bucket_shapes(1, cut=True)
    # and the law is the planner's own
    rows_b, e_b, r_b = driver.size_classes(0)
    reached = driver.plan.reached()[1]
    shapes = [TenantShape(t, "orset", int(n), int(reached[t]), int(driver.plan.writers[t]))
              for t, n in enumerate(driver.plan.rows_per_tenant(0).tolist())]
    buckets, solo = plan_buckets(shapes, **DRIFT["serve"])
    assert {(b.slots, b.rows, b.members, b.replicas) for b in buckets} == want[0]
    assert solo == np.flatnonzero(e_b == 0).tolist() and len(solo) == 1


def test_no_shape_first_compiles_after_the_warm_up_round(drifted):
    assert drifted["compiles"][0] > 0, "round 0 is the mix's warm-up: it compiles"
    assert drifted["compiles"][1:] == [0] * (len(drifted["compiles"]) - 1)
    assert all(value == 0 for _, value, _ in drifted["checks"])


def test_throw_away_head_at_the_rows_cap_stays_batched(drifted):
    once = drifted["once"]
    assert "serve_solo_spills" not in once["counters"]
    assert once["counters"]["serve_buckets_folded"] == 2
    assert once["counters"]["serve_stack_cells"] == 1024 * 8 + 2048 * 8
    assert once["spans"]["delta.cut"]["count"] == 1


def test_span_tree_stays_closed_with_the_solo_span(drifted):
    tree, snap = drifted["tree"], drifted["snap"]
    assert tree[None] == ["serve.run_cycle"], "one root"
    reached, frontier = set(), ["serve.run_cycle"]
    while frontier:
        name = frontier.pop()
        if name not in reached:
            reached.add(name)
            frontier += tree.get(name, [])
    assert reached == set(snap["spans"]), "a span fired outside the tree"
    assert snap["spans"]["serve.solo"]["parents"] == ["serve.phase.fallback"]
    assert snap["spans"]["serve.solo"]["count"] == 1
    assert "serve.solo" in tree["serve.phase.fallback"]
    # the metric's children are still the spans that partition the root
    spec = run.load_json(ROOT, "cellbench", "layer_metrics", "unattributed_ms.fleet.json")
    parts = sorted(part for child in tree["serve.run_cycle"]
                   for part in (tree[child] if child == "serve.cycle" else [child]))
    assert spec["args"]["children"] == parts


def test_fresh_sample_always_holds_the_largest_tenants(drifted):
    driver = drifted["driver"]
    sample = driver.fresh_sample()
    assert len(sample) == len(set(sample)) == min(32, driver.plan.tenants)
    assert sorted(driver.plan.rank[sample[:8]].tolist()) == list(range(1, 9))
    full = importlib.import_module("cellbench.drivers.fleet_zipf").Driver.fresh_sample
    big = type("P", (), {"tenants": 1024, "seed": 5,
                         "rank": np.random.default_rng(5).permutation(1024) + 1})
    holder = type("D", (), {"plan": big})()
    picked = full(holder)
    assert len(set(picked)) == 32 and sorted(big.rank[picked[:8]].tolist()) == list(range(1, 9))
