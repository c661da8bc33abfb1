"""The program's span tree is closed on the served path, and the two
``unattributed_ms`` metrics subtract exactly the spans a run shows directly
under each root.

One toy ``Core.compact()`` (``FsStorage``, delta and checkpoint on) and one
toy ``FoldService.run_cycle()``, driven through the cells' own drivers at a
tiny size.  Structural only: which span was opened under which, never how
long it took.
"""

import asyncio
import contextlib
import importlib

import pytest

from cellbench import gen, run
from crdt_enc_tpu.obs import attribution, sink
from crdt_enc_tpu.utils import trace

ROOT = run.ROOT
TOYS = {
    "core.compact": {
        "cell": "orset_folder_1k.backlog", "metric": "unattributed_ms.folder",
        "config": {"devices": 8, "members": 32, "initial_files_per_device": 3},
        "traffic": {"active_devices": 8, "max_ops_per_s": 2500},
    },
    "serve.run_cycle": {
        "cell": "orset_fleet_1024.busy", "metric": "unattributed_ms.fleet",
        # serve.cycle only groups the seven phases, under the name and the
        # extent it had before them: the metric subtracts what is in it
        "groups": ["serve.cycle"],
        "config": {"tenants": 6, "members": 16, "initial_files_per_device": 8},
        "traffic": {"active_tenants": 6, "max_ops_per_s": 2500},
    },
}


@contextlib.asynccontextmanager
async def opened(toy: dict, workdir: str):
    """The cell's own driver over the toy's sizes, opened."""
    cell = run.load_cell(ROOT, toy["cell"])
    config = {**cell["config"], **toy["config"]}
    traffic = {**cell["traffic"], **toy["traffic"]}
    plan = gen.plan_run(config, traffic, 2**31 + 24, 3)
    module = importlib.import_module(f"cellbench.drivers.{config['driver']}")
    driver = module.Driver(config, plan, workdir)
    await driver.open()
    try:
        yield driver
    finally:
        await driver.close()


async def one_timed_call(toy: dict, workdir: str) -> tuple:
    """The span tree and the snapshot of one call of the program, after two
    warm-up calls (so that a delta base and a checkpoint exist)."""
    async with opened(toy, workdir) as driver:
        for r in range(2):
            await driver.publish(r)
            await driver.call(r)
        await driver.publish(2)
        trace.reset()
        outcome = await driver.call(2)
        return trace.tree(), trace.snapshot(), outcome


@pytest.mark.parametrize("root", list(TOYS))
def test_every_span_of_a_call_hangs_under_its_root(root, tmp_path):
    toy = TOYS[root]
    tree, snap, outcome = asyncio.run(one_timed_call(toy, str(tmp_path)))
    trace.reset()
    assert outcome["failed"] == 0 and outcome["ops"] > 0
    assert tree[None] == [root], "one root"
    reached, frontier = set(), [root]
    while frontier:
        name = frontier.pop()
        if name not in reached:
            reached.add(name)
            frontier += tree.get(name, [])
    assert reached == set(snap["spans"]), "a span fired outside the tree"
    assert snap["spans"][root]["count"] == 1
    spec = run.load_json(ROOT, "cellbench", "layer_metrics", toy["metric"] + ".json")
    assert spec["args"]["span"] == root
    groups = toy.get("groups", [])
    parts = sorted(
        part for child in tree[root]
        for part in (tree[child] if child in groups else [child])
    )
    assert spec["args"]["children"] == parts, (
        "the metric's children are the spans that partition the root in a run"
    )
    # the parts run one after another, each once a call, or once a tenant
    # inside a phase: the subtraction is sound
    for child in tree[root]:
        assert snap["spans"][child]["parents"] == [root]
        if child in groups:
            assert snap["spans"][child]["count"] == 1
            for part in tree[child]:
                assert snap["spans"][part]["parents"] == [child]


@pytest.mark.parametrize("root, label, inside", [
    ("core.compact", "compact", ["compact.ingest", "compact.seal", "repl.status"]),
    ("serve.run_cycle", "serve_cycle",
     ["serve.cycle", "serve.phase.seal", "serve.publish"]),
])
def test_kth_sink_record_carries_k_calls(root, label, inside, tmp_path, monkeypatch):
    """The call's sink record is written with every span of the call closed:
    each record holds its own call, root included (``obs_report gap`` divides
    the stages' seconds by ``serve.cycle``'s), and its own events."""
    path = str(tmp_path / "sink.jsonl")

    async def two_calls():
        async with opened(TOYS[root], str(tmp_path)) as driver:
            trace.reset()
            trace.enable_events()
            monkeypatch.setattr(sink, "_configured", sink.MetricsSink(path))
            for r in range(2):
                await driver.publish(r)
                await driver.call(r)

    asyncio.run(two_calls())
    trace.reset()
    records = [r for r in sink.read_records(path) if r["label"] == label]
    if label == "compact":  # the reopening replica of the cell's check aside
        records = [r for r in records if root in r["spans"]]
    assert len(records) == 2
    for k, rec in enumerate(records, start=1):
        for name in [root] + inside:
            assert rec["spans"][name]["count"] == k, name
        roots = [e for e in rec["events"] if e["name"] == root]
        assert len(roots) == 1, "a record's events are its own call's"
        # the wall that obs_report gap infers from the events is one call's
        # (serve.tenant is observed from a t0 taken just before the root's)
        wall = roots[0]["t1"] - roots[0]["t0"]
        assert 0.99 * wall <= attribution.from_record(rec)["wall_s"] < 1.5 * wall
