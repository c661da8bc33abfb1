"""``Core.read_remote()`` over several overlapping snapshots plus op tails,
against the plain reference of ``cellbench/reference_peers.py``.

The consumer path the reference protocol rests on (states first, then op
tails, ``lib.rs:390-399``) with more than one sealer: each sealer saw some of
the devices and the start of the next sealer's, so the snapshots hold the
same dots and disagree on clocks.  Three or more states take the stacked
device merge of ``TpuAccelerator`` (the XLA tree here on the CPU); the host
engine merges pairwise.  Both must reach the plain merge-then-fold, exactly,
and the same canonical bytes.  Small sizes, seeded; nothing is measured.
"""

import asyncio
import os
import shutil

import numpy as np
import pytest

from cellbench import gen, reference_peers
from crdt_enc_tpu.backends import FsStorage
from crdt_enc_tpu.core import Core
from crdt_enc_tpu.core.adapters import HostAccelerator
from crdt_enc_tpu.models import canonical_bytes
from crdt_enc_tpu.parallel import TpuAccelerator
from crdt_enc_tpu.utils import trace

CONFIG = {"tenants": 1, "devices": 24, "members": 32, "ops_per_file": 24,
          "remove_fraction": 0.2, "initial_files_per_device": 2}
MIX = {"active_tenants": 1, "active_devices": 24, "files_per_device": 1,
       "warmup_rounds": 0, "max_ops_per_s": 1}


async def replica(base: str, name: str, remote: str, accel):
    return await Core.open(gen.core_opts(FsStorage(os.path.join(base, name), remote), accel))


async def sealed_remote(base: str, views: list, files: list) -> tuple:
    """A remote holding one snapshot of every view (a host-engine sealer on a
    remote of its own folds the op files ``files[k]`` and seals) and no op
    file yet.  Returns ``(remote, writer)``; the writer holds the key."""
    remote = os.path.join(base, "remote")
    writer = await replica(base, "writer", remote, HostAccelerator())
    os.makedirs(os.path.join(remote, "states"), exist_ok=True)
    for k in range(len(views)):
        own = os.path.join(base, f"sealer{k}-remote")
        shutil.copytree(os.path.join(remote, "meta"), os.path.join(own, "meta"))
        sealer = await replica(base, f"sealer{k}", own, HostAccelerator())
        for ab, version, ops in files[k]:
            await sealer.storage.store_ops(ab, version, await writer._seal(ops))
        await sealer.compact()
        (name,) = await sealer.storage.list_state_names()
        ((_, raw),) = await sealer.storage.load_states([name])
        assert await writer.storage.store_state(raw) == name
    return remote, writer


async def read_with(base: str, remote: str, accel_cls) -> tuple:
    reader = await replica(base, "reader-" + accel_cls.__name__, remote, accel_cls())
    trace.reset()
    await reader.read_remote()
    counters = trace.snapshot()["counters"]
    trace.reset()
    return (reader.with_state(lambda s: s.to_obj()),
            reader.with_state(canonical_bytes), counters)


@pytest.mark.parametrize("seed", [7, 2**31 + 30])
@pytest.mark.parametrize("sealers", [3, 4, 5])
def test_read_remote_over_overlapping_snapshots_and_op_tails(sealers, seed, tmp_path):
    plan = gen.plan_run(CONFIG, MIX, seed, 2)
    D = plan.devices
    share = np.arange(D) * (sealers + 1) // D  # the last share seals nothing
    views = []
    for k in range(sealers):
        after = np.flatnonzero(share == (k + 1) % sealers)
        seen = share == k
        seen[after[:len(after) // 3]] = True
        views.append(seen)
    # sealers fold the head and round 0 of what they see; round 1 of every
    # device, and every file of the last share, arrive as op tails
    early = [f for r in (-1, 0) for f in plan.files_of_round(r)]
    files = [[plan.wire_file(f)[1:] for f in early if view[plan.f_actor[f]]]
             for view in views]
    tails = [f for r in (-1, 0, 1) for f in plan.files_of_round(r)
             if r == 1 or share[plan.f_actor[f]] == sealers]

    async def go():
        remote, writer = await sealed_remote(str(tmp_path), views, files)
        for f in tails:
            _, ab, version, ops = plan.wire_file(f)
            await writer.storage.store_ops(ab, version, await writer._seal(ops))
        return (await read_with(str(tmp_path), remote, TpuAccelerator),
                await read_with(str(tmp_path), remote, HostAccelerator))

    (dev_obj, dev_bytes, dev_counters), (host_obj, host_bytes, host_counters) = (
        asyncio.run(go()))

    rows = plan.live_rows([-1, 0])
    device = plan.actor[rows] % D
    snapshots = [reference_peers.fold_rows(plan, rows[view[device]]) for view in views]
    want = reference_peers.merge_all(snapshots)
    tail_rows = np.concatenate([rows[share[device] == sealers], plan.live_rows([1])])
    want = reference_peers.fold_rows(plan, tail_rows, want).canonical()
    everything = reference_peers.fold_rows(plan, plan.live_rows([-1, 0, 1])).canonical()
    assert reference_peers.differing(want, everything) == 0, "merge then fold = fold"
    assert reference_peers.differing(dev_obj, want) == 0
    assert reference_peers.differing(host_obj, want) == 0
    assert dev_bytes == host_bytes
    assert dev_counters["states_merged"] == host_counters["states_merged"] == sealers
    # the stacked merge ran once, over the sealers' states and the reader's
    # own empty one, and its pulls are counted; the host engine has neither
    assert dev_counters["snapshot_merges"] == 1
    E, R = len(everything[b"e"]), len(everything[b"c"])
    assert dev_counters["merge_state_cells"] == (sealers + 1) * dev_counters["merge_out_cells"]
    assert dev_counters["merge_out_cells"] <= E * R
    assert dev_counters["d2h_pulls"] >= 3 and dev_counters["d2h_bytes"] >= (
        2 * 4 * dev_counters["merge_out_cells"])
    assert dev_counters["snapshot_bytes_opened"] == host_counters["snapshot_bytes_opened"] > 0
    assert "snapshot_merges" not in host_counters


def test_a_dot_one_snapshot_holds_and_another_has_seen_removed_stays_removed(tmp_path):
    """Sealer 0 saw device A's add of member 7; sealer 1 saw the add and A's
    remove of it, so its clock covers the dot and it holds it no longer;
    sealer 2 saw another device.  Merged, in either engine, the dot is dead:
    the clock is the tombstone."""
    a, b = gen.actor_table(2)
    add, remove = [0, 7, [a, 1]], [1, 7, {a: 1}]
    keep = [0, 9, [a, 2]]
    other = [[0, 7, [b, 1]], [0, 11, [b, 2]]]
    files = [
        [(a, 1, [add])],
        [(a, 1, [add]), (a, 2, [remove, keep])],
        [(b, 1, other)],
    ]

    async def go():
        remote, _ = await sealed_remote(str(tmp_path), files, files)
        return (await read_with(str(tmp_path), remote, TpuAccelerator),
                await read_with(str(tmp_path), remote, HostAccelerator))

    (dev_obj, dev_bytes, dev_counters), (host_obj, host_bytes, _) = asyncio.run(go())
    plain = []
    for seen in files:
        s = reference_peers.PlainORSet()
        for _, _, ops in seen:
            for kind, member, payload in ops:
                if kind == 0:
                    s.add(member, payload[0], payload[1])
                else:
                    s.remove(member, payload)
        plain.append(s)
    assert plain[0].canonical()[b"e"] == {7: {a: 1}}, "alive in the first snapshot"
    assert 7 not in plain[1].canonical()[b"e"] and plain[1].clock[a] == 2
    want = reference_peers.merge_all(plain).canonical()
    assert want[b"e"] == {7: {b: 1}, 9: {a: 2}, 11: {b: 2}} and want[b"d"] == {}
    assert reference_peers.differing(dev_obj, want) == 0
    assert reference_peers.differing(host_obj, want) == 0
    assert dev_bytes == host_bytes
    assert dev_counters["snapshot_merges"] == 1 and dev_counters["states_merged"] == 3
