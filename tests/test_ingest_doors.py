"""Op ingest has three doors, and one home for each rule.

* **solo pipelined** — ``Core.read_remote()`` on a session-capable
  accelerator: ``_read_remote_ops_pipelined`` over ``iter_op_chunks``;
* **solo whole batch** — ``Core.read_remote()`` on ``HostAccelerator``:
  ``_read_remote_ops_bulk`` over one ``load_ops``;
* **serve** — ``Core.load_sealed_ops`` inside a ``FoldService`` cycle.

The unwrap rule (outer envelope → group by sealing key → resolve the key)
is written once under ``crdt_enc_tpu/core/`` and every door reaches it: the
matrix below gives all three the same damaged or awkward remote and holds
state, cursors, the quarantine counter and the error's text to ONE
expectation, so a door that grows its own copy which disagrees fails here.
The second half pins the pipelined door's own seams (promotion threshold,
producer errors, in-flight width, cuts carried across chunks).
"""

import asyncio
import threading
import time
import uuid

import pytest

import crdt_enc_tpu.core.core as core_mod
from _ingest_doors import (
    apply_files,
    chunked,
    fork,
    make_opts,
    orset_workload,
    read_pipelined,
    seed_remote,
    write_files,
)
from crdt_enc_tpu.backends import MemoryStorage
from crdt_enc_tpu.core import Core, MissingKeyError
from crdt_enc_tpu.core.adapters import HostAccelerator
from crdt_enc_tpu.core.core import OpOrderError
from crdt_enc_tpu.core.key_cryptor import Key
from crdt_enc_tpu.models import ORSet, canonical_bytes
from crdt_enc_tpu.parallel import TpuAccelerator
from crdt_enc_tpu.parallel import session as psession
from crdt_enc_tpu.utils import trace

R, PER_ACTOR = 3, 8  # 24 files: past BULK_MIN_FILES, so every door batches


def run(coro):
    return asyncio.run(coro)


def _fold(files):
    """The per-op host truth of ``files`` (``(actor, [op obj, ...])``)."""
    return canonical_bytes(apply_files(ORSet(), files))


def _versions(files):
    """``(actor, version)`` of each file: an actor's run is dense from 1."""
    seen: dict = {}
    out = []
    for actor, _ in files:
        seen[actor] = seen.get(actor, 0) + 1
        out.append((actor, seen[actor]))
    return out


def _without_cut(files, torn):
    """``files`` as a reader folds them when ``torn`` ``(actor, version)``
    is damaged: that actor's run ends just below the hole."""
    actor, version = torn
    return [
        f for f, (a, v) in zip(files, _versions(files))
        if a != actor or v < version
    ]


# ------------------------------------------------------------- the inputs
# Each builds a remote and says what EVERY door must make of it:
# (remote, files_per_chunk for the pipelined door, folded files,
#  cursors, quarantined, error text).


async def _torn_mid_run():
    """A torn outer envelope in the middle of an actor's run, the rest of
    the run in the same chunk (a listing replays actor by actor, so a
    chunk of PER_ACTOR files is one actor's whole run)."""
    files, actors, _ = orset_workload(n_files=R * PER_ACTOR, R=R)
    remote, _ = await seed_remote(files)
    remote.ops[actors[1]][4] = remote.ops[actors[1]][4][:5]
    cursors = {a: PER_ACTOR for a in actors} | {actors[1]: 3}
    return remote, PER_ACTOR, _without_cut(files, (actors[1], 4)), cursors, 1, None


async def _cut_carries():
    """An actor cut in chunk k stays cut in chunk k+1 (for the whole-batch
    doors: in the rest of the batch)."""
    files, actors, _ = orset_workload(n_files=R * PER_ACTOR, R=R)
    remote, _ = await seed_remote(files)
    remote.ops[actors[1]][3] = b""
    cursors = {a: PER_ACTOR for a in actors} | {actors[1]: 2}
    return remote, 4, _without_cut(files, (actors[1], 3)), cursors, 1, None


async def _unknown_key():
    """One file sealed with a key whose metadata never synced: loud, not
    damage, and nothing of the batch folds."""
    files, actors, _ = orset_workload(n_files=R * PER_ACTOR, R=R)
    remote, writer = await seed_remote(files[:-1])
    stranger = Key.new(await writer.cryptor.gen_key())
    await write_files(writer, files[-1:], key=stranger)
    text = (
        f"ops sealed with unknown key {uuid.UUID(bytes=stranger.id)}; "
        "key metadata may not have synced yet"
    )
    return remote, len(files), [], {}, 0, text


async def _two_keys():
    """Two sealing keys in one chunk (a rotation mid-history)."""
    files, actors, _ = orset_workload(n_files=R * PER_ACTOR, R=R)
    remote, writer = await seed_remote(files[:10])
    await writer.rotate_key()
    await write_files(writer, files[10:])
    return remote, len(files), files, {a: PER_ACTOR for a in actors}, 0, None


INPUTS = {
    "torn_mid_run": _torn_mid_run,
    "cut_carries_across_chunks": _cut_carries,
    "unknown_sealing_key": _unknown_key,
    "two_keys_one_chunk": _two_keys,
}


# -------------------------------------------------------------- the doors
# Each ingests the remote and returns (core, error text or None); which of
# the three marker spans it emitted proves which door ran.


async def _read_solo(storage, accel):
    reader = await Core.open(make_opts(storage, accel=accel))
    try:
        await reader.read_remote()
    except MissingKeyError as e:
        return reader, str(e)
    return reader, None


async def _door_pipelined(remote, files_per_chunk):
    return await _read_solo(
        chunked(remote, files_per_chunk), TpuAccelerator(min_device_batch=1)
    )


async def _door_whole_batch(remote, files_per_chunk):
    return await _read_solo(MemoryStorage(remote), HostAccelerator())


async def _door_serve(remote, files_per_chunk):
    from crdt_enc_tpu.serve import FoldService

    tenant = await Core.open(make_opts(
        MemoryStorage(remote), accel=TpuAccelerator(min_device_batch=1)
    ))
    (res,) = await FoldService([tenant]).run_cycle()
    if res.error is None:
        return tenant, None
    # the service reports a tenant's failure as the exception's repr
    prefix, suffix = "MissingKeyError('", "')"
    assert res.error.startswith(prefix) and res.error.endswith(suffix)
    return tenant, res.error[len(prefix) : -len(suffix)]


MARKERS = {"ops.chunk_unwrap", "ops.bulk_unwrap", "serve.ingest"}
DOORS = {
    "pipelined": (_door_pipelined, {"ops.chunk_unwrap"}),
    "whole_batch": (_door_whole_batch, {"ops.bulk_unwrap"}),
    "serve": (_door_serve, {"ops.bulk_unwrap", "serve.ingest"}),
}


@pytest.mark.parametrize("input_name", INPUTS)
@pytest.mark.parametrize("door_name", DOORS)
def test_unwrap_rule_is_one_rule_at_every_door(door_name, input_name):
    door, markers = DOORS[door_name]

    async def go():
        remote, per_chunk, folded, cursors, quarantined, error = (
            await INPUTS[input_name]()
        )
        trace.reset()
        core, got_error = await door(fork(remote), per_chunk)
        snap = trace.snapshot()
        assert MARKERS & set(snap["spans"]) == markers, "another door ran"
        assert got_error == error
        assert snap["counters"].get("ingest_quarantined", 0) == quarantined
        assert core.with_state(canonical_bytes) == _fold(folded)
        assert core.info().next_op_versions.counters == cursors

    run(go())


# ------------------------------------------------- the pipelined door's seams


@pytest.mark.parametrize("n_files", [core_mod.BULK_MIN_FILES - 1,
                                     core_mod.BULK_MIN_FILES])
def test_promotion_threshold(n_files):
    """Below ``BULK_MIN_FILES`` an ingest never promotes into the session
    and folds per op; at the threshold every file goes through it."""
    files, _, host = orset_workload(n_files=n_files, R=R)

    async def go():
        remote, _ = await seed_remote(files)
        trace.reset()
        reader = await read_pipelined(remote, 3)
        snap = trace.snapshot()
        if n_files < core_mod.BULK_MIN_FILES:
            assert "ops.chunk_fold" not in snap["spans"]
            assert "op_files_bulk_folded" not in snap["counters"]
            assert snap["counters"]["ops_folded"] == sum(
                len(ops) for _, ops in files
            )
        else:
            assert snap["counters"]["op_files_bulk_folded"] == n_files
            assert "ops_folded" not in snap["counters"]
        assert reader.with_state(canonical_bytes) == canonical_bytes(host)

    run(go())


def test_producer_error_leaves_fed_chunks_folded():
    """A producer error after n chunks were reduced leaves exactly those n
    folded with their cursors advanced; the chunk still decoding is
    dropped and stays re-readable (here the error is the loud one: the
    last chunk holds a file sealed with an unsynced key)."""
    files, actors, _ = orset_workload(n_files=R * PER_ACTOR, R=R)

    async def go():
        remote, writer = await seed_remote(files[:-1])
        await write_files(
            writer, files[-1:], key=Key.new(await writer.cryptor.gen_key())
        )
        # four files a chunk, a listing that replays actor by actor: six
        # chunks, the sixth raising.  At width 1 two chunks are in flight,
        # so when the error surfaces chunks 0-3 (two whole runs) are
        # reduced and chunk 4 is still decoding
        reader = await Core.open(make_opts(
            chunked(remote, 4),
            accel=TpuAccelerator(min_device_batch=1, stream_producers=1),
        ))
        trace.reset()
        with pytest.raises(MissingKeyError):
            await reader.read_remote()
        assert trace.snapshot()["counters"]["op_files_bulk_folded"] == (
            2 * PER_ACTOR
        )
        fed = [f for f in files if f[0] != actors[2]]
        assert reader.with_state(canonical_bytes) == _fold(fed)
        assert reader.info().next_op_versions.counters == {
            actors[0]: PER_ACTOR, actors[1]: PER_ACTOR,
        }

    run(go())


@pytest.mark.parametrize("stream_producers,in_flight", [(1, 2), (4, 4)])
def test_in_flight_width_follows_stream_producers(
    stream_producers, in_flight, monkeypatch
):
    """``stream_producers`` bounds the chunks decoding at once (never
    under two: one decode of lookahead IS the pipeline), and the state is
    the same at any width."""
    files, _, host = orset_workload(n_files=48, ops_per_file=7, seed=21)
    lock = threading.Lock()
    running = peak = 0
    real_decode = psession.OrsetFoldSession.decode_chunk

    def slow_decode(self, payloads):
        nonlocal running, peak
        with lock:
            running += 1
            peak = max(peak, running)
        try:
            time.sleep(0.05)
            return real_decode(self, payloads)
        finally:
            with lock:
                running -= 1

    monkeypatch.setattr(
        psession.OrsetFoldSession, "decode_chunk", slow_decode
    )

    async def go():
        remote, _ = await seed_remote(files)
        return await read_pipelined(
            remote, 4,
            accel=TpuAccelerator(stream_producers=stream_producers),
        )

    reader = run(go())
    # the drain starts when the in-flight list reaches the bound, so the
    # decodes that overlap are one fewer, and at least three at width 4
    assert in_flight - 1 <= peak <= in_flight
    assert reader.with_state(canonical_bytes) == canonical_bytes(host)


def test_gap_in_a_later_chunk_holds_cursors_then_recovers():
    """An op file beyond the expected version in chunk k raises
    ``OpOrderError`` with the chunks ahead of it folded and no cursor
    past the hole; once the missing file syncs in, a re-read recovers
    everything (the pipelined twin of
    test_bulk_ingest.test_bulk_gap_leaves_cursors_consistent)."""
    files, actors, host = orset_workload(n_files=R * PER_ACTOR, R=R)

    async def go():
        remote, _ = await seed_remote(files)
        missing = remote.ops[actors[1]].pop(5)

        class Gapped(type(chunked(remote, 1))):
            async def load_ops(self, wanted):
                # a listing that does not stop at the hole: v6.. stranded
                out = await super().load_ops(wanted)
                for actor, first in wanted:
                    if actor == actors[1] and first <= 5:
                        out += [
                            (actor, v, raw)
                            for v, raw in sorted(remote.ops[actor].items())
                            if v > 5
                        ]
                return sorted(out, key=lambda f: f[:2])

        reader = await Core.open(make_opts(
            chunked(remote, 4, base=Gapped),
            accel=TpuAccelerator(min_device_batch=1),
        ))
        with pytest.raises(OpOrderError):
            await reader.read_remote()
        assert reader.info().next_op_versions.get(actors[1]) <= 4
        remote.ops[actors[1]][5] = missing
        await reader.read_remote()
        assert reader.with_state(canonical_bytes) == canonical_bytes(host)
        assert reader.info().next_op_versions.counters == {
            a: PER_ACTOR for a in actors
        }

    run(go())


def test_decrypt_quarantine_cuts_the_actor_in_later_chunks():
    """A file whose ciphertext fails authentication in chunk k (the
    consumer-side cut: it unwraps, then does not open) ends its actor's
    run for chunk k+1 as well — no fold past the hole, no gap error."""
    files, actors, _ = orset_workload(n_files=R * PER_ACTOR, R=R)

    async def go():
        remote, _ = await seed_remote(files)
        blob = bytearray(remote.ops[actors[1]][3])
        blob[-1] ^= 1  # break the tag; the outer envelope still parses
        remote.ops[actors[1]][3] = bytes(blob)
        trace.reset()
        reader = await read_pipelined(remote, 4)
        assert trace.snapshot()["counters"]["ingest_quarantined"] == 1
        assert reader.with_state(canonical_bytes) == _fold(
            _without_cut(files, (actors[1], 3))
        )
        assert reader.info().next_op_versions.counters == (
            {a: PER_ACTOR for a in actors} | {actors[1]: 2}
        )

    run(go())
