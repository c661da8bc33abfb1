"""The folder whose compactors all run this program: the configuration beside
``orset_folder_peers``', the plain window delta against the plain merge and
against the program's codec, the link a toy peer published, the driver end to
end at toy sizes on the CPU with its two controls, and the copied metric
files.  Nothing here is a measurement.
"""

import asyncio
import json

import pytest

from cellbench import gen, reference_delta, reference_peers, run
from cellbench.drivers import folder_peers_delta

import manifest_checks as checks

ROOT = run.ROOT
MANIFEST = run.load_json(ROOT, "BENCHMARK.json")
CELL = "orset_folder_peers_delta.backlog"
SUFFIX = ".folder_peers_delta"
# test_folder_peers.py's toy with a wider vocabulary and a longer head
# (tests/cellbench/toys/<cell>.json says why)
TOY = checks.toy(MANIFEST, ROOT, CELL)
NEW = [m + SUFFIX for m in (
    "delta_ingest_ms", "delta_apply_ms", "delta_route_pct", "delta_links_per_pass",
    "delta_fallbacks_pct", "delta_bytes_per_op", "delta_slots_per_op",
    "plane_cache_drops_per_pass")]
# the pass that was the snapshot merge's preamble is this cell's whole route:
# the peers folder's file with another layer, so a definition of its own
OWN_LAYER = "delta_read_ms" + SUFFIX
# what the cell shares with the peers folder and with the solo folder, read
# from their entries (ISSUE 43; it had copies of all ten before)
SHARED = ["op_fold_ms.folder_peers"] + [m + ".folder" for m in (
    "ingest_wait_ms", "storage_ms", "delta_plan_ms", "delta_seal_ms",
    "repl_status_ms", "unattributed_ms", "h2d_bytes_per_op", "d2h_bytes_per_op",
    "device_launches")]
DEVICE_ONLY = {"device_launches.folder"}


def toy_driver(workdir: str, seed: int, rounds: int = 3):
    cell = run.load_cell(ROOT, CELL)
    config = {**cell["config"], **TOY["config"]}
    traffic = {**cell["traffic"], **TOY["traffic"]}
    plan = gen.plan_run(config, traffic, seed, rounds)
    return folder_peers_delta.Driver(config, plan, workdir)


def fold(plan, rounds, seen=None) -> reference_peers.PlainORSet:
    """The plain fold of the ops of ``rounds`` that the devices ``seen`` wrote."""
    rows = plan.live_rows(rounds)
    if seen is not None:
        rows = rows[seen[plan.actor[rows] % plan.devices]]
    return reference_peers.fold_rows(plan, rows)


def triples(plan, views) -> list:
    """``(base, new, X)``: two snapshots of one peer a round apart, and a
    consumer that has merged ``base``, is behind ``new`` on that peer's
    devices, and holds what ``new`` never saw (another peer's files, some of
    them from devices both peers see, and a round ``new`` has not reached)."""
    out = []
    for k, seen in enumerate(views):
        other = views[(k - 1) % len(views)]  # sees the first devices of peer k's share
        for r in range(plan.n_rounds):
            base = fold(plan, range(-1, r), seen)
            new = fold(plan, range(-1, r + 1), seen)
            ahead = fold(plan, range(-1, min(r + 2, plan.n_rounds)), other)
            out.append((base, new, reference_peers.merge(ahead, base)))
    return out


# ------------------------------------------------------- the configuration


def test_configuration_is_the_peers_folder_but_for_what_syncs():
    peers = run.load_json(ROOT, "cellbench", "configs", "orset_folder_peers.json")
    delta = run.load_json(ROOT, "cellbench", "configs", "orset_folder_peers_delta.json")
    differs = {"name", "driver", "source", "deployment", "layout", "peer_protocol",
               "assumed", "guarantees"}
    assert set(delta) == set(peers)
    for key in set(peers) - differs:
        assert delta[key] == peers[key], key
    assert delta["peer_protocol"] == "delta" and delta["driver"] == "folder_peers_delta"
    assert delta["guarantees"][:6] == peers["guarantees"] and len(delta["guarantees"]) == 8
    assert {"members_and_devices", "remove_fraction", "arrival", "share_law"} == set(delta["assumed"])
    for key in ("members_and_devices", "remove_fraction", "share_law"):
        assert delta["assumed"][key] == peers["assumed"][key]
    assert len(delta["source"]) <= 200
    assert not {"withhold_peer", "withhold_link"} & set(delta)
    assert sorted(delta["reduced"]) == ["devices", "initial_ops"]


# --------------------------------------------------------- the plain rule


def test_plain_delta_follows_the_rule():
    a, b = b"a" * 16, b"b" * 16
    base = reference_peers.PlainORSet()
    base.add(1, a, 1)
    base.add(2, a, 2)
    new = reference_peers.from_canonical(base.canonical())
    new.remove(2, {a: 2})       # a base slot dropped: dot-exact removal
    new.add(3, a, 3)            # a window dot that survives
    new.add(4, a, 4)
    new.remove(4, {a: 4})       # a window dot that died inside the window
    new.remove(5, {b: 7})       # a horizon ahead of the clock
    delta = reference_delta.diff(base, new)
    assert delta == {b"bc": {a: 2}, b"c": {a: 4}, b"e": {3: {a: 3}},
                     b"x": {2: {a: 2}}, b"t": {5: {b: 7}}}
    x = reference_peers.from_canonical(base.canonical())
    x.add(3, a, 3)              # the consumer got the window's dots by another route
    x.add(4, a, 4)
    x.add(6, b, 1)              # and holds what the sealer never saw
    got = reference_delta.apply(x, delta).canonical()
    assert got[b"e"] == {1: {a: 1}, 3: {a: 3}, 6: {b: 1}}, (
        "2 dies dot-exactly, 3 is confirmed, 4 dies in the window unconfirmed, 6 is not the sealer's to kill")
    assert got[b"c"] == {a: 4, b: 1} and got[b"d"] == {5: {b: 7}}
    assert got == reference_peers.merge(x, new).canonical()
    assert x.canonical()[b"e"] == {1: {a: 1}, 2: {a: 2}, 3: {a: 3}, 4: {a: 4}, 6: {b: 1}}, (
        "inputs are left alone")


@pytest.mark.parametrize("seed", [7, 2**31 + 32])
def test_plain_apply_of_the_plain_diff_is_the_plain_merge(seed, tmp_path):
    driver = toy_driver(str(tmp_path), seed)
    cases = triples(driver.plan, driver.views)
    assert len(cases) == 12
    windows = 0
    for base, new, x in cases:
        delta = reference_delta.diff(base, new)
        windows += bool(delta[b"x"]) and bool(delta[b"e"])
        got = reference_delta.apply(x, delta).canonical()
        want = reference_peers.merge(x, new).canonical()
        assert reference_peers.differing(got, want) == 0
        assert got != x.canonical(), "the delta carried something"
    assert windows, "some delta both adds and removes"


@pytest.mark.parametrize("seed", [8, 2**31 + 33])
def test_program_codec_is_byte_equal_to_the_plain_pair(seed, tmp_path):
    from crdt_enc_tpu.core import orset_adapter
    from crdt_enc_tpu.delta.codec import orset_delta_apply, orset_delta_diff
    from crdt_enc_tpu.models import canonical_bytes
    from crdt_enc_tpu.utils import codec

    adapter = orset_adapter()
    state = lambda plain: adapter.state_from_obj(plain.canonical())  # noqa: E731
    driver = toy_driver(str(tmp_path), seed)
    for base, new, x in triples(driver.plan, driver.views):
        plain = reference_delta.diff(base, new)
        cut = orset_delta_diff(state(base), state(new))
        assert codec.pack(cut) == codec.pack(plain)
        theirs = state(x)
        walked = orset_delta_apply(theirs, codec.unpack(codec.pack(cut)))
        assert canonical_bytes(theirs) == codec.pack(reference_delta.apply(x, plain).canonical())
        assert walked == sum(map(len, x.canonical()[b"e"].values())), (
            "a link with a window walks every live slot of the consumer")


@pytest.mark.parametrize("seed", [9, 2**31 + 34])
def test_link_a_toy_peer_published_is_the_plain_diff_of_its_two_states(seed, tmp_path):
    from crdt_enc_tpu.delta import wire
    from crdt_enc_tpu.utils import codec

    driver = toy_driver(str(tmp_path), seed)
    plan = driver.plan

    async def opened():
        await driver.open()
        out = {}
        for r, links in driver.links.items():
            for actor, (version, raw) in links.items():
                out[r, actor] = version, wire.parse_delta_obj(
                    await driver.writer._open_sealed(raw))
        return out

    links = asyncio.run(opened())
    # open() published the head, so rounds 0.. are still held; the head has no link
    assert sorted(r for r, _ in links) == sorted(list(range(plan.n_rounds)) * 4)
    for (r, actor), (version, rec) in links.items():
        seen = driver.views[driver.peer_actors.index(actor)]
        assert version == r + 1 and rec.sealer == actor and rec.base_name and rec.new_name
        want = reference_delta.diff(fold(plan, range(-1, r), seen),
                                    fold(plan, range(-1, r + 1), seen))
        assert codec.pack(rec.delta_obj) == codec.pack(want)


# --------------------------------------------------- the cell, end to end


def check_new_and_copied_are_listed(manifest: dict, root: str) -> None:
    """The nineteen the cell came with (PR 32) are among what it lists; its
    configuration's entry is its file's; and the fold that follows the pass
    is the peers folder's entry, both cells in it in their order."""
    checks.hold_config(manifest, root, "orset_folder_peers_delta",
                       reduced=["devices", "initial_ops"])
    checks.hold_cell_lists(root, CELL, NEW + [OWN_LAYER] + SHARED)
    checks.hold_metric(manifest, "op_fold_ms.folder_peers",
                       cells=["orset_folder_peers.backlog", CELL])


def traced(capsys, fault: dict | None = None, seconds: float = 3.0):
    """A traced toy run that ends when its three prepared rounds are used up."""
    shrink = {"config": {**TOY["config"], **(fault or {})},
              "traffic": {**TOY["traffic"], "max_ops_per_s": 1500}}
    assert run.run_cell(CELL, 2**31 + 35, seconds, True, require_tpu=False,
                        shrink=shrink) == 0
    out = capsys.readouterr().out
    line = json.loads(out.strip().splitlines()[-1])
    calls = int(out.split("calls completed in the window: ")[1].split(";")[0])
    return out, line, {k: v["value"] for k, v in line["metrics"].items()}, calls


def test_traced_toy_run_takes_every_foreign_state_in_through_a_link(capsys):
    out, line, value, calls = traced(capsys)
    assert line["correct"] is True and line["failed"] == 0 and calls == 3
    assert line["compared"]["stale_peer_snapshots_left"] == {"value": 0, "limit": 0}
    assert line["compared"]["stale_peer_links_left"] == {"value": 0, "limit": 0}
    check_new_and_copied_are_listed(MANIFEST, ROOT)
    # what reads the device trace finds nothing on the CPU and is left out;
    # every other listed metric is there unless its own file says it may not be
    checks.check_toy_line(ROOT, CELL, line["metrics"])
    assert set(NEW + [OWN_LAYER] + SHARED) - set(line["metrics"]) == DEVICE_ONLY
    assert value["delta_route_pct" + SUFFIX] == 100, "states_merged is 0 in the window"
    assert value["delta_links_per_pass" + SUFFIX] == 4
    assert value["delta_fallbacks_pct" + SUFFIX] == 0
    assert value["plane_cache_drops_per_pass" + SUFFIX] == 1
    assert 0 < value["delta_apply_ms" + SUFFIX] < value["delta_read_ms" + SUFFIX]
    assert 0 < value["delta_ingest_ms" + SUFFIX] < value["delta_read_ms" + SUFFIX]
    assert value["delta_bytes_per_op" + SUFFIX] > 0 and value["delta_slots_per_op" + SUFFIX] > 0
    assert "states.merge" not in out and "states.load" not in out, "no snapshot was loaded"


def test_control_a_withheld_link_costs_a_snapshot_and_no_data(capsys):
    """The link of peer 0's first timed round never arrives; its snapshot
    does.  That round loads and merges the snapshot, the next counts the gap
    and applies the peer's next link again."""
    first = run.load_cell(ROOT, CELL)["traffic"]["warmup_rounds"]
    out, line, value, calls = traced(
        capsys, {"withhold_link": {"peer": 0, "round": first}})
    assert line["correct"] is True and calls == 3
    applied, merged, scanned = 4 * calls - 1, 1, 5 * calls - 1
    assert value["delta_route_pct" + SUFFIX] == pytest.approx(100 * applied / (applied + merged))
    assert value["delta_links_per_pass" + SUFFIX] == pytest.approx(applied / calls)
    assert value["delta_fallbacks_pct" + SUFFIX] == pytest.approx(100 * 1 / scanned)
    assert line["compared"]["stale_peer_links_left"] == {"value": 0, "limit": 0}


@pytest.mark.parametrize("peer", [0, 3])
def test_control_a_withheld_peer_is_not_correct(peer, capsys):
    """Snapshot and link of one peer stop arriving from the first timed round
    on: nothing else carries that peer's files."""
    first = run.load_cell(ROOT, CELL)["traffic"]["warmup_rounds"]
    fault = {"withhold_peer": {"peer": peer, "from_round": first}}
    shrink = {**TOY, "config": {**TOY["config"], **fault}}
    assert run.run_cell(CELL, 36, 0.5, False, require_tpu=False, shrink=shrink) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is False
    assert line["compared"]["compactor_vs_reference"]["value"] > 0


def test_a_compactor_keeps_its_peers_bases_after_it_has_gcd_them(tmp_path):
    """What the deployment forced in the program: ``compact()`` GCs a merged
    snapshot and forgets its name, and the sealer's next link names exactly
    that snapshot as its base.  The base outlives its file in
    ``merged_bases``; without it every link of every round fell back."""
    from crdt_enc_tpu.utils import trace

    driver = toy_driver(str(tmp_path), 37)

    async def two_rounds():
        await driver.open()
        compactor = driver.compactor
        heads = set(compactor._data.merged_bases.values())
        assert len(heads) == 4 and not heads & compactor._data.read_states
        trace.reset()
        for r in range(2):
            await driver.publish(r)
            await driver.call(r)
        return trace.snapshot()["counters"], compactor._data.merged_bases

    counters, bases = asyncio.run(two_rounds())
    trace.reset()
    assert counters["delta_applied"] == 8 and counters["delta_passes"] == 2
    assert not counters.get("delta_fallbacks") and not counters.get("states_merged")
    assert set(bases) == set(driver.peer_actors)
    assert [names[-1] for names in driver.peer_names] == [bases[a] for a in driver.peer_actors]


# ------------------------------------------------------- the metric files


def test_delta_read_is_the_peers_folders_file_in_this_cells_layer():
    peers = run.load_json(ROOT, "cellbench", "layer_metrics", "delta_read_ms.folder_peers.json")
    own = run.load_json(ROOT, "cellbench", "layer_metrics", OWN_LAYER + ".json")
    for key in ("reader", "args", "unit", "better", "source", "moves"):
        assert own[key] == peers[key], key
    assert (own["driver"], own["layer"]) == ("folder_peers_delta", "delta consumer")
    assert peers["layer"] == "snapshot merge"


@pytest.mark.parametrize("metric", NEW)
def test_new_metric_uses_a_reader_that_is_there_and_names_no_kernel(metric):
    spec = run.load_json(ROOT, "cellbench", "layer_metrics", metric + ".json")
    assert spec["reader"] in ("span_ms", "counter_ratio", "counter_per_op")
    assert spec["layer"] == "delta consumer" and "match" not in spec["args"]
    assert "roofline" not in metric, "no new kernel, so no new roofline share"
