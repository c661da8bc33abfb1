"""The fleet that outgrows its warm tier (ISSUE 53): the generator's law, the
driver's refusal of a fleet that fits, its model of the tier, and the cell end
to end with a tier that evicts, all at toy sizes on the CPU.  Nothing here is
a measurement.
"""

import asyncio
import importlib
import json

import numpy as np
import pytest

from cellbench import gen, gen_hotset, gen_zipf, run
from cellbench.drivers import fleet_zipf_hotset as hotset
from crdt_enc_tpu.serve.warm import PlaneWarmTier
from crdt_enc_tpu.utils import trace

import manifest_checks as checks

ROOT = run.ROOT
MANIFEST = run.load_json(ROOT, "BENCHMARK.json")
CONFIG, CELL, DRIVER = "orset_fleet_hotset", "orset_fleet_hotset.drift", "fleet_zipf_hotset"
# what the cell brings: the metrics of the overflow, in the manifest's order
FOUR = [m + "." + DRIVER for m in (
    "warm_evictions_per_cycle", "warm_rebuild_ms", "warm_rebuild_bytes_per_op",
    "delta_cut_fallbacks_pct")]
# what it shares with ``orset_fleet_zipf.busy``, by its name appended to their
# lists: every ``*.fleet`` entry that cell lists, three of its own, and the
# tenant fold's device time (the chip's traced line carries it: PR 53's runs)
SHARED = {m + ".fleet_zipf" for m in (
    "warm_hit_pct", "buckets_per_cycle", "stack_fill_pct")} | {m + ".fleet" for m in (
    "ingest_wall_ms", "fold_wall_ms", "seal_wall_ms", "unattributed_ms", "device_launches",
    "tenant_fold_kernel_ms",
    "h2d_bytes_per_op", "d2h_bytes_per_op", "d2h_pulls_per_tenant", "seal_job_pct",
    "native_file_steps_pct", "ingest_job_pct", "native_reads_pct", "gc_pause_ms",
    "gc_full_pause_ms", "slot_wait_ms", "ingest_job_queue_ms", "ingest_job_return_ms",
    "seal_job_queue_ms", "seal_job_return_ms")}
TOY = checks.toy(MANIFEST, ROOT, CELL)
SEEDS = (1, 53, 2**31 + 53)


def cell_files(**traffic) -> tuple:
    cell = run.load_cell(ROOT, CELL)
    return cell["config"], {**cell["traffic"], **traffic}


def toy_files() -> tuple:
    cell = run.load_cell(ROOT, CELL)
    return {**cell["config"], **TOY["config"]}, {**cell["traffic"], **TOY["traffic"]}


# ------------------------------------------------------------- the manifest


def check_the_hotset_entries(manifest: dict, root: str) -> None:
    """The configuration, the cell, the four new metrics and the lists the
    cell joined, whatever else a later PR appends."""
    file = checks.hold_config(manifest, root, CONFIG,
                              reduced=["storage", "tenants", "warm_bytes"])
    assert file["driver"] == DRIVER and file["serve"] == {"warm_bytes": 256 << 20}
    assert (file["tenants"], file["devices"], file["team_devices"], file["team_ranks"]) \
        == (1024, 32, 32, 0)
    assert (file["members"], file["members_floor"], file["ops_per_file"],
            file["remove_fraction"], file["initial_files_per_device"]) \
        == (32768, 4096, 24, 0.1, 1)
    assert file["source_sizes"]["tenants"] == 16 * file["tenants"]
    assert file["source_sizes"]["warm_bytes"] == 16 * file["serve"]["warm_bytes"]
    assert len(file["guarantees"]) == 6
    # the driver refuses a ``why`` over 200 characters before any run, and
    # ``check_cell`` holds the cell's alone
    assert 1 <= len(checks.entry_of(manifest, "configs", CONFIG)["why"]) <= 200
    checks.hold_cell(manifest, root, CELL, config=CONFIG, traffic="drift", chips=1,
                     end_to_end=("serve_ops_per_s", "seal_p95_ms"))
    mix = run.load_json(root, "cellbench", "traffic", "drift.json")
    assert mix["loop"].startswith("closed")
    assert {k: mix[k] for k in (
        "active_tenants", "active_devices", "files_per_device",
        "drift_ranks_per_cycle", "warmup_rounds", "max_ops_per_s")} == {
        "active_tenants": 128, "active_devices": 2, "files_per_device": 1,
        "drift_ranks_per_cycle": 8, "warmup_rounds": 6, "max_ops_per_s": 4000}
    assert (mix["popularity"]["law"], mix["popularity"]["constant"]) == ("zipfian", 0.99)
    for name in FOUR[:3]:
        checks.hold_metric(manifest, name, cells=[CELL], moves="serve_ops_per_s",
                           layer="serve fold")
    checks.hold_metric(manifest, FOUR[3], cells=[CELL], moves="seal_p95_ms",
                       layer="serve seal tail", source="program_counter")
    checks.hold_metrics_in_order(manifest, FOUR)
    for name in SHARED:
        checks.hold_metric(manifest, name, cells=[CELL])
    listed = checks.hold_cell_lists(root, CELL, SHARED | set(FOUR))
    # nothing spills here: the solo span never opens, and the cell does not
    # list what reads it
    assert not {"solo_spills_per_cycle.fleet_zipf", "solo_fold_ms.fleet_zipf"} & set(listed)
    for name in FOUR:
        assert listed[name]["driver"] == DRIVER


def test_the_manifest_holds_the_deployment_the_cell_and_its_metrics():
    check_the_hotset_entries(MANIFEST, ROOT)
    assert len(FOUR) == 4 and len(SHARED) == 23


def test_the_planes_are_four_times_the_tier_by_the_planners_law():
    """The configuration's own arithmetic, from the rank-size law and the mean
    share of a vocabulary that a head of as many uniform draws names
    (1 - 1/e): no plan is made at this size here."""
    config, _ = cell_files()
    reached = (1 - np.exp(-1)) * gen_zipf.vocabularies(config)
    entry = 8 * hotset.classes(reached) * hotset.classes(np.array([config["devices"]]))
    assert sorted(entry.tolist())[:1017] == [1 << 20] * 1017
    assert 4.0 < entry.sum() / config["serve"]["warm_bytes"] < 4.1
    assert entry.max() == 8 << 20, "rank 1: 32,768 x 32 cells, at cells_cap and batched"
    assert hotset.classes(np.array([1, 8, 9, 4096, 4097])).tolist() == [8, 8, 16, 4096, 8192]


# ------------------------------------------------------------ the generator


@pytest.mark.parametrize("seed", SEEDS)
def test_every_cycle_is_the_same_work_on_other_tenants(seed):
    config, traffic = cell_files()
    perm, rounds = gen_hotset.schedule(config, traffic, seed, 26)
    assert sorted(perm.tolist()) == list(range(1024))
    for actors in rounds:
        tenants, devices = actors // config["devices"], actors % config["devices"]
        assert len(actors) == 256 and len(set(tenants.tolist())) == 128
        assert len(actors) * config["ops_per_file"] == 6144
        # two distinct devices of each tenant, one file each
        assert len(set(zip(tenants.tolist(), devices.tolist()))) == 256
    again = gen_hotset.schedule(config, traffic, seed, 26)
    assert (again[0] == perm).all() and all((a == b).all() for a, b in zip(again[1], rounds))
    other = gen_hotset.schedule(config, traffic, seed + 1, 26)
    assert (other[0] != perm).any()


@pytest.mark.parametrize("seed", SEEDS)
def test_the_ranks_move_eight_a_cycle_and_the_hot_set_with_them(seed):
    config, traffic = cell_files()
    perm, rounds = gen_hotset.schedule(config, traffic, seed, 300)
    drift = traffic["drift_ranks_per_cycle"]
    share = np.zeros(1025)
    for r, actors in enumerate(rounds):
        rank = gen_hotset.popularity_rank(perm, drift, r)
        later = gen_hotset.popularity_rank(perm, drift, r + 1)
        assert sorted(rank.tolist()) == list(range(1, 1025))
        assert ((rank - 1 + 8) % 1024 + 1 == later).all()
        # the eight coldest become the eight hottest, every other cools by 8
        assert sorted(later[rank > 1016].tolist()) == list(range(1, 9))
        np.add.at(share, rank[np.unique(actors // config["devices"])], 1)
    share /= len(rounds)
    # 128 drawn without replacement: the hottest are in nearly every cycle,
    # the coldest eighth in one cycle of thirty (replayed: 0.958 and 0.035)
    assert share[1:17].mean() > 0.93 and 0.025 < share[897:].mean() < 0.045
    assert share[1:17].mean() > share[100:200].mean() > share[513:].mean()
    # over a run's 26 rounds 26 x 8 tenants pass through the top eight
    top = {t for r in range(26) for t in np.flatnonzero(
        gen_hotset.popularity_rank(perm, drift, r) <= 8).tolist()}
    assert len(top) == 208


def test_single_draws_follow_one_over_rank_to_the_constant():
    """One tenant a cycle and no drift: the draw is the law itself.  Bands: the
    eight hottest ranks each within 15% of their weight (20,000 draws put
    rank 8 at 336 +- 18), ranks 9-64 and the colder half within 5% and 10%
    of theirs."""
    config, traffic = cell_files(active_tenants=1, active_devices=1,
                                 drift_ranks_per_cycle=0)
    perm, rounds = gen_hotset.schedule(config, traffic, 5, 20000)
    rank = gen_hotset.popularity_rank(perm, 0, 0)
    drawn = np.bincount([rank[a[0] // config["devices"]] for a in rounds],
                        minlength=1025) / len(rounds)
    want = gen_hotset.weights(np.arange(1, 1025), 0.99)
    assert want[0] / want[1] == pytest.approx(2 ** 0.99) and want.sum() == pytest.approx(1)
    assert np.abs(drawn[1:9] / want[:8] - 1).max() < 0.15
    assert drawn[9:65].sum() == pytest.approx(want[8:64].sum(), rel=0.05)
    assert drawn[513:].sum() == pytest.approx(want[512:].sum(), rel=0.10)


@pytest.mark.parametrize("seed", SEEDS)
def test_popularity_and_size_are_independent_draws(seed):
    config, traffic = toy_files()
    config = {**config, "tenants": 512}
    traffic = {**traffic, "active_tenants": 64}
    uniform = gen.plan_run(config, traffic, seed, 2)
    plan = gen_hotset.plan_hotset(config, uniform)
    popularity, size = plan.popularity_rank(0), plan.rank
    assert sorted(size.tolist()) == sorted(popularity.tolist()) == list(range(1, 513))
    assert (popularity != size).any()
    # two independent permutations of 512: Spearman's rho is 0 +- 0.044
    assert abs(np.corrcoef(popularity, size)[0, 1]) < 0.15
    # the sizes are gen_zipf's for the same seed, the head the uniform plan's
    sized = gen_zipf.plan_zipf(config, uniform)
    assert (plan.rank == sized.rank).all() and (plan.vocab == sized.vocab).all()
    head = plan.files_of_round(-1)
    assert (plan.f_actor[head.start:head.stop] == sized.f_actor[head.start:head.stop]).all()
    for r in range(plan.n_rounds):
        tenants = plan.tenants_of_round(r)
        assert len(tenants) == 64 and len(plan.files_of_round(r)) == 128
        rows = plan.rows_of_round(r)
        assert rows.stop - rows.start == 128 * plan.opf


# --------------------------------------------- the driver: refusal and model


def test_a_fleet_under_one_and_a_half_times_its_budget_is_refused(tmp_path, capsys):
    """The family's overlay (six tenants of 16 members under the real 256
    MiB), and the toy fleet under a budget that holds two thirds of it."""
    assert run.run_cell(CELL, 3, 0.5, False, require_tpu=False, shrink=TOY) == 0
    assert json.loads(capsys.readouterr().out.strip().splitlines()[-1])["correct"] is True
    with pytest.raises(SystemExit) as refused:
        run.run_cell(CELL, 3, 0.5, False, require_tpu=False,
                     shrink=checks.tiny(MANIFEST, ROOT, CELL))
    cap = capsys.readouterr()
    assert refused.value.code == 2 and cap.out == ""
    assert "a cache that fits" in cap.err and "0.00 times its warm_bytes" in cap.err
    config, traffic = toy_files()
    plan = gen.plan_run(config, traffic, 3, 4)
    held = hotset.Driver(config, plan, str(tmp_path))
    planes = int(held.entry_bytes[-1].sum())
    assert planes > 4 * config["serve"]["warm_bytes"]
    for budget, runs in [(planes * 2 // 3 - 1, True), (planes * 2 // 3 + 1, False)]:
        fits = {**config, "serve": {"warm_bytes": budget}}
        if runs:
            hotset.Driver(fits, plan, str(tmp_path))
        else:
            with pytest.raises(SystemExit):
                hotset.Driver(fits, plan, str(tmp_path))
    assert "1.50 times its warm_bytes" in capsys.readouterr().err


def test_the_model_is_the_programs_tier_cycle_for_cycle():
    """``TierModel`` against ``PlaneWarmTier`` itself over seeded cycles of
    lookups in tenant order and stores in bucket order, entries of three
    sizes, one of them over half the budget."""
    class State:
        _mut = 0

    rng = np.random.default_rng(53)
    states = [State() for _ in range(40)]
    nbytes = {t: int(rng.choice([400, 800, 6000])) for t in range(40)}
    order = {t: (64, nbytes[t] // 8, 8) for t in range(40)}
    tier, model = PlaneWarmTier(byte_budget=10_000), hotset.TierModel(10_000)
    for _ in range(60):
        tenants = sorted(rng.choice(40, 9, replace=False).tolist())
        trace.reset()
        found = [t for t in tenants if tier.lookup(states[t]) is not None]
        for t in sorted(tenants, key=lambda t: (order[t], t)):
            tier.store(states[t], None, None, (np.zeros(nbytes[t] // 4, np.int32),))
        said = model.cycle(tenants, nbytes, order)
        counters = trace.snapshot()["counters"]
        assert said["hits"] == found and said["misses"] == sorted(set(tenants) - set(found))
        assert len(said["evicted"]) == counters.get("serve_warm_evictions", 0)
        assert sum(nbytes[t] for t in said["evicted"]) == counters.get(
            "serve_warm_evicted_bytes", 0)
        assert (model.held, len(model.entries)) == (tier.bytes_held, len(tier))
    trace.reset()


async def toy_window(workdir: str) -> dict:
    """Every prepared round of the toy fleet through the driver, as the
    harness runs them: compiles and the tier's bytes after every call."""
    from crdt_enc_tpu.obs import runtime as obs_runtime

    obs_runtime.track_recompiles()
    config, traffic = toy_files()
    plan = gen.plan_run(config, traffic, 2**31 + 53, 12)
    driver = importlib.import_module(f"cellbench.drivers.{config['driver']}").Driver(
        config, plan, workdir)
    await driver.open()
    try:
        compiles, held, seen = [], [], []
        for r in range(plan.n_rounds):
            await driver.publish(r)
            trace.reset()
            trace.enable_events()
            outcome = await driver.call(r)
            assert outcome["failed"] == 0 and outcome["ops"] > 0
            compiles.append(trace.snapshot()["counters"].get("jax_compiles", 0))
            held.append(driver.service.warm.bytes_held)
            seen.append({tuple(int(x) for x in e["meta"].split(":")[1].split("x"))
                         for e in trace.events() if e["name"] == "serve.fold"})
        trace.reset()
        return {"driver": driver, "compiles": compiles, "held": held, "seen": seen,
                "checks": await driver.check()}
    finally:
        trace.reset()
        await driver.close()


@pytest.fixture(scope="module")
def window(tmp_path_factory):
    return asyncio.run(toy_window(str(tmp_path_factory.mktemp("hotset"))))


def test_no_round_compiles_whatever_the_tier_holds(window):
    driver = window["driver"]
    assert window["compiles"] == [0] * driver.plan.n_rounds, (
        "every bucket shape was folded once, with its cut, before round 0")
    want = [driver.bucket_shapes(r) for r in range(driver.plan.n_rounds)]
    assert all(seen <= shapes for seen, shapes in zip(window["seen"], want)), (
        "a tenant rebuilt from its state may fold a class under its ops' own, "
        "never a shape the plan has not")
    assert len(set().union(*want)) >= 3


def test_the_tier_stays_inside_its_budget_and_the_window_counts_it(window):
    driver = window["driver"]
    w = driver.window
    assert max(window["held"]) <= driver.budget < int(driver.entry_bytes[-1].sum()) / 4
    assert w["calls"] == driver.plan.n_rounds - driver.plan.traffic["warmup_rounds"]
    assert w["over_budget"] == 0 and w["quiet"] == 0
    assert w["serve_warm_evictions"] >= w["calls"]
    assert w["serve_warm_rebuilds"] == w["serve_warm_misses"] > 0 < w["serve_warm_hits"]
    assert w["delta_cut_fallbacks"] > 0 < w["delta_device_cuts"]
    assert w["model_off"] <= 2, "the model's sizes are an upper bound at a class boundary"
    assert window["checks"] == [
        ("tenants_vs_reference", 0, 0), ("fresh_replicas_vs_reference", 0, 0),
        ("fresh_replica_bytes_vs_served", 0, 0), ("warm_bytes_over_budget", 0, 0)]


def test_fresh_sample_holds_tenants_the_window_evicted_and_rebuilt(window):
    driver = window["driver"]
    sample = driver.fresh_sample()
    assert len(sample) == len(set(sample)) == driver.plan.tenants, (
        "the toy has 24 tenants: zipf's sample is all of them")
    assert len(driver.rebuilt) >= hotset.REBUILT
    # at the cell's size: zipf's 32 and eight of the rebuilt besides
    big = type("P", (), {"tenants": 1024, "seed": 5,
                         "rank": np.random.default_rng(5).permutation(1024) + 1})
    holder = type("D", (hotset.Driver,), {"__init__": lambda self: None})()
    holder.plan, holder.rebuilt = big, set(range(100, 400))
    picked = holder.fresh_sample()
    assert len(set(picked)) == 40 and sorted(big.rank[picked[:8]].tolist()) == list(range(1, 9))
    assert set(picked[32:]) <= holder.rebuilt and not set(picked[32:]) & set(picked[:32])


# ------------------------------------------------- the cell, through run_cell


def test_traced_toy_line_carries_exactly_what_the_cell_lists(capsys):
    assert run.run_cell(CELL, 2**31 + 53, 0.5, True, require_tpu=False, shrink=TOY) == 0
    cap = capsys.readouterr()
    line = json.loads(cap.out.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert "jax compiles inside it: 0" in cap.out
    metrics = {k: v["value"] for k, v in line["metrics"].items()}
    checks.check_toy_line(ROOT, CELL, metrics)
    assert set(FOUR) <= set(metrics)
    assert (SHARED | set(FOUR)) - set(metrics) == {
        "device_launches.fleet", "tenant_fold_kernel_ms.fleet"}, "the CPU has no device trace"
    assert metrics[FOUR[0]] >= 1 and metrics[FOUR[1]] > 0 and metrics[FOUR[2]] > 0
    assert 0 <= metrics[FOUR[3]] < 100
    assert 0 < metrics["warm_hit_pct.fleet_zipf"] < 100
    # the rebuilt rows are part of what is uploaded
    assert metrics[FOUR[2]] < metrics["h2d_bytes_per_op.fleet"]
    assert line["compared"]["warm_bytes_over_budget"] == {"value": 0, "limit": 0}
    assert "the model of the tier disagreed with the counters in" in cap.err
    assert "timed calls, 0 of them without an eviction, 0 ending over the budget" in cap.err


def test_control_a_withheld_op_file_is_not_correct_at_toy_size(capsys):
    assert run.run_cell(CELL, 13, 0.5, False, require_tpu=False, shrink=TOY,
                        fault="withhold_file") == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is False
    assert line["compared"]["tenants_vs_reference"]["value"] > 0
    assert line["compared"]["warm_bytes_over_budget"] == {"value": 0, "limit": 0}
