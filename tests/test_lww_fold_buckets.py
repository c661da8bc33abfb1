"""The LWW map's device fold compiles by bucket, not by batch (ISSUE 50).

``TpuAccelerator._fold_lww`` handed the batch's own count of distinct keys to
the jitted fold as a static argument, so every round of a folder (46,860 keys
named, then 46,871, then ...) compiled a program of its own.  Every static
argument now comes from a bucket: rounds whose counts of distinct keys and of
distinct values stay inside one bucket fold with the first round's program,
and every state equals the host fold's bytes: ``HostAccelerator``'s and the
plain ``LWWMap.apply`` loop's, with timestamp ties, deletes, and a round that
only loses to what the map already holds among them.

On the CPU the fold is ``ops.lww.lww_fold`` (the Pallas twin takes the same
``num_keys`` / ``num_values`` and lowers only on the chip).
"""

import uuid

import numpy as np
import pytest

from crdt_enc_tpu.core.adapters import HostAccelerator
from crdt_enc_tpu.models import LWWMap, canonical_bytes
from crdt_enc_tpu.models.lwwmap import LWWOp
from crdt_enc_tpu.obs import runtime as obs_runtime
from crdt_enc_tpu.parallel.accel import TpuAccelerator, _bucket
from crdt_enc_tpu.utils import trace

ACTORS = [uuid.UUID(int=i + 1).bytes for i in range(16)]
ROWS = 400  # past MIN_DEVICE_BATCH (256), in the row class of 512


def seeded_round(rng, keys: int, earlier: list) -> list:
    """``ROWS`` writes over ``keys`` keys: a tenth deletes, and one in eight
    repeats the (key, timestamp) of an earlier write under another actor,
    value or tombstone, so that every tie-break decides entries."""
    ops = []
    for _ in range(ROWS):
        a = ACTORS[int(rng.integers(len(ACTORS)))]
        dead = bool(rng.random() < 0.1)
        value = None if dead else int(rng.integers(100))
        if earlier and rng.random() < 0.125:
            old = earlier[int(rng.integers(len(earlier)))]
            # half keep the actor too, so the value and the tombstone decide
            a = old.actor if rng.random() < 0.5 else a
            ops.append(LWWOp(old.key, old.ts, a, value, dead))
        else:
            ts = int(rng.integers(2, 1 << 40))
            ops.append(LWWOp(int(rng.integers(keys)), ts, a, value, dead))
    return ops


def counter(name: str) -> int:
    return trace.snapshot()["counters"].get(name, 0)


def fold_all_three(states: dict, ops: list) -> None:
    """Fold ``ops`` into the three states, each its own way, and hold the
    device fold to the bytes of both host folds."""
    states["tpu"] = TpuAccelerator().fold_ops(states["tpu"], list(ops))
    states["host"] = HostAccelerator().fold_ops(states["host"], list(ops))
    for op in ops:
        states["loop"].apply(op)
    want = canonical_bytes(states["loop"])
    assert canonical_bytes(states["host"]) == want
    assert canonical_bytes(states["tpu"]) == want


def test_rounds_inside_one_bucket_compile_once_and_equal_the_host_fold():
    obs_runtime.track_recompiles()
    rng = np.random.default_rng(50)
    states = {"tpu": LWWMap(), "host": LWWMap(), "loop": LWWMap()}
    earlier, named, compiled = [], [], []
    for _ in range(5):
        ops = seeded_round(rng, 3000, earlier)
        named.append(len({op.key for op in ops}))
        before = (counter("jax_compiles"), counter("lww_folds"),
                  counter("lww_fold_keys"), counter("fold_rows_device"))
        fold_all_three(states, ops)
        compiled.append(counter("jax_compiles") - before[0])
        assert counter("lww_folds") - before[1] == 1
        assert counter("lww_fold_keys") - before[2] == named[-1]
        assert counter("fold_rows_device") - before[3] == ROWS
        earlier += ops
    # the rounds name other numbers of keys, all of one bucket
    assert len(set(named)) > 1 and {_bucket(k) for k in named} == {512}, named
    assert compiled[1:] == [0, 0, 0, 0], (
        f"a fold compiled after the first round: {compiled} for {named} keys")

    # a count that crosses into another bucket is another program: it may
    # compile once more, and then that bucket is warm too
    crossing = []
    for _ in range(2):
        ops = seeded_round(rng, 200, earlier)
        assert _bucket(len({op.key for op in ops})) == 256
        before = counter("jax_compiles")
        fold_all_three(states, ops)
        crossing.append(counter("jax_compiles") - before)
        earlier += ops
    assert crossing[0] <= 1 and crossing[1] == 0, crossing


def test_a_round_that_only_loses_writes_nothing_and_compiles_nothing():
    obs_runtime.track_recompiles()
    rng = np.random.default_rng(51)
    states = {"tpu": LWWMap(), "host": LWWMap(), "loop": LWWMap()}
    first = seeded_round(rng, 3000, [])
    fold_all_three(states, first)
    held = canonical_bytes(states["tpu"])
    # the same keys and values again (so the same buckets), every write
    # older than anything the map holds
    losers = [LWWOp(op.key, 1, ACTORS[-1], op.value, op.tombstone) for op in first]
    before = (counter("jax_compiles"), counter("lww_keys_written"),
              counter("lww_fold_rows"))
    fold_all_three(states, losers)
    assert canonical_bytes(states["tpu"]) == held
    assert counter("jax_compiles") == before[0]
    assert counter("lww_keys_written") == before[1]
    assert counter("lww_fold_rows") - before[2] == ROWS


def test_a_batch_under_the_device_floor_counts_as_host_rows():
    before = (counter("fold_rows_host"), counter("lww_folds"))
    few = [LWWOp(k, 5, ACTORS[0], k, False) for k in range(10)]
    state = TpuAccelerator().fold_ops(LWWMap(), few)
    assert len(state.entries) == 10
    assert counter("fold_rows_host") - before[0] == 10
    assert counter("lww_folds") == before[1]


A, B = ACTORS[0], ACTORS[1]  # A's bytes sort under B's


@pytest.mark.parametrize("winner, loser", [
    (LWWOp("k", 9, A, 1, False), LWWOp("k", 8, B, 99, False)),    # timestamp
    (LWWOp("k", 9, B, 1, False), LWWOp("k", 9, A, 99, False)),    # actor
    (LWWOp("k", 9, A, 70, False), LWWOp("k", 9, A, 7, False)),    # value
    (LWWOp("k", 9, A, None, True), LWWOp("k", 9, A, 99, False)),  # delete's None
    (LWWOp("k", 9, A, None, True), LWWOp("k", 9, A, None, False)),  # tombstone
], ids=["timestamp", "actor", "value", "delete_over_value", "tombstone"])
@pytest.mark.parametrize("resident", [False, True], ids=["in_batch", "against_resident"])
def test_each_tie_break_decides_on_the_device_as_on_the_host(winner, loser, resident):
    """The pair decides inside one batch (the kernel's order) and against an
    entry the map already holds (the writeback's), in either arrival order."""
    filler = [LWWOp(f"f{i}", 3, A, i, False) for i in range(300)]
    for first, second in ((winner, loser), (loser, winner)):
        want = LWWMap()
        for op in [first, second, *filler]:
            want.apply(op)
        assert want.entries["k"] == [
            winner.ts, winner.actor, None if winner.tombstone else winner.value,
            winner.tombstone]
        got = LWWMap()
        if resident:
            got.apply(first)
            got = TpuAccelerator().fold_ops(got, [second, *filler])
        else:
            got = TpuAccelerator().fold_ops(got, [first, second, *filler])
        assert canonical_bytes(got) == canonical_bytes(want)
        host = HostAccelerator().fold_ops(LWWMap(), [first, second, *filler])
        assert canonical_bytes(host) == canonical_bytes(want)
