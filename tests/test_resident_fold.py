"""The resident dense fold (ISSUE 41): planes that stay on the device between
rounds, only the cells a batch names and the clock pulled back, and a
writeback that applies those to the host state.  Every state it produces is
byte-equal to the host loop's and to the sorted host fold's; the routing
says "resident" or "host" from the platform, the shape and the device's
memory; a session's finished ingest leaves its planes installed; a collision
falls back to the host fold, drops the planes and counts it.

Everything here runs on the CPU at toy sizes, through the code path a TPU
takes at 4,096 x 10,000.
"""

import asyncio

import numpy as np
import pytest

from crdt_enc_tpu import ops as K
from crdt_enc_tpu.backends import FsStorage
from crdt_enc_tpu.core import Core
from crdt_enc_tpu.core.adapters import HostAccelerator
from crdt_enc_tpu.models import ORSet, canonical_bytes
from crdt_enc_tpu.models.orset import AddOp, RmOp
from crdt_enc_tpu.models.vclock import Dot, VClock
from crdt_enc_tpu.parallel import TpuAccelerator
from crdt_enc_tpu.parallel import session as S
from crdt_enc_tpu.utils import codec, trace

ACTORS = [bytes([i + 1]) * 16 for i in range(16)]


def counters():
    return trace.snapshot()["counters"]


def batch(rng, clock, n, *, actors=ACTORS, members=40, remove=0.15, replay=()):
    """``n`` well-formed ops over ``actors`` (dots dense per actor, a remove
    observing its own actor's adds so far, some running ahead of the clock),
    with ``replay`` appended: ops the state has seen already."""
    ops = []
    for _ in range(n):
        a = actors[int(rng.integers(len(actors)))]
        m = int(rng.integers(members))
        seen = clock.get(a, 0)
        if seen and rng.random() < remove:
            ahead = int(rng.integers(3)) if rng.random() < 0.3 else 0
            ops.append(RmOp(m, VClock({a: seen + ahead})))
        else:
            clock[a] = seen + 1
            ops.append(AddOp(m, Dot(a, seen + 1)))
    return ops + list(replay)


def sparse_host():
    """An accelerator whose every fold takes the sorted host fold."""
    a = TpuAccelerator(min_device_batch=1)
    a.SPARSE_MIN_CELLS = a.SPARSE_CELLS_PER_ROW = 0
    return a


def fold_three_ways(rounds):
    """Fold ``rounds`` (lists of ops) into three states: resident device
    planes, the sorted host fold, the host loop.  After every round the three
    are byte-equal."""
    resident, sparse, host = TpuAccelerator(min_device_batch=1), sparse_host(), HostAccelerator()
    states = ORSet(), ORSet(), ORSet()
    for ops in rounds:
        for accel, state in zip((resident, sparse, host), states):
            accel.fold_ops(state, list(ops))
        want = canonical_bytes(states[2])
        assert canonical_bytes(states[0]) == want
        assert canonical_bytes(states[1]) == want
    return states[0]


# ---------------------------------------------- (a) the fold and the pull


@pytest.mark.parametrize("seed", range(6))
def test_resident_rounds_equal_the_host_loop_and_the_sorted_host_fold(seed):
    rng, clock = np.random.default_rng(seed), {}
    rounds, seen = [], []
    for r in range(5):
        replay = [seen[int(i)] for i in rng.integers(len(seen), size=20)] if seen else []
        ops = batch(rng, clock, 300 + 37 * r, replay=replay)
        rounds.append(ops)
        seen += ops
    trace.reset()
    fold_three_ways(rounds)
    c = counters()
    assert (c["plane_cache_misses"], c["plane_cache_hits"]) == (1, 4)
    assert c["fold_rows_host"] == c["fold_rows_device"], "one sparse and one resident accelerator"
    assert "plane_cache_drops" not in c
    trace.reset()


@pytest.mark.parametrize("rows", [255, 256, 257, 511, 512, 513])
def test_a_hit_pulls_two_words_a_padded_row_and_the_clock(rows):
    """The gather's shape class is the fold's row bucket: at a boundary and
    one past it, what comes back is 2 x 4 B x bucket(rows) and the clock."""
    rng, clock = np.random.default_rng(rows), {}
    accel, host = TpuAccelerator(min_device_batch=1), HostAccelerator()
    s_acc, s_host = ORSet(), ORSet()
    first = batch(rng, clock, 400, remove=0)
    accel.fold_ops(s_acc, list(first))
    host.fold_ops(s_host, list(first))
    ops = batch(rng, clock, rows, remove=0)  # adds only: one row an op
    trace.reset()
    accel.fold_ops(s_acc, list(ops))
    host.fold_ops(s_host, list(ops))
    c = counters()
    bucket = 1 << (rows - 1).bit_length()
    assert c["plane_cache_hits"] == 1 and "plane_cache_misses" not in c
    assert c["fold_cells_pulled"] == 2 * bucket
    assert c["d2h_bytes"] == 2 * 4 * bucket + 4 * len(ACTORS)
    assert c["d2h_pulls"] == 3
    assert c["h2d_bytes"] == 13 * bucket, "the row columns, once"
    assert "fold.pull" in trace.snapshot()["spans"]
    assert canonical_bytes(s_acc) == canonical_bytes(s_host)
    trace.reset()


@pytest.mark.parametrize("grow", ["members", "actors", "both"])
def test_vocabulary_grows_while_the_planes_are_resident(grow):
    rng, clock = np.random.default_rng(7), {}
    few, later = ACTORS[:6], ACTORS if grow != "members" else ACTORS[:6]
    wide = 90 if grow != "actors" else 40
    rounds = [batch(rng, clock, 400, actors=few),
              batch(rng, clock, 400, actors=later, members=wide),
              batch(rng, clock, 200, actors=later, members=wide)]
    trace.reset()
    fold_three_ways(rounds)
    c = counters()
    assert (c["plane_cache_misses"], c["plane_cache_hits"]) == (1, 2)
    trace.reset()


def test_a_batch_that_names_no_cell_and_one_that_is_all_replays():
    rng, clock = np.random.default_rng(11), {}
    accel, host = TpuAccelerator(min_device_batch=1), HostAccelerator()
    s_acc, s_host = ORSet(), ORSet()
    first = batch(rng, clock, 500)
    for a, s in ((accel, s_acc), (host, s_host)):
        a.fold_ops(s, list(first))
    cache = accel._plane_cache
    before = canonical_bytes(s_acc)
    none = np.zeros(0, np.int32)
    trace.reset()
    accel._fold_orset_columns(s_acc, np.zeros(0, np.int8), none, none, none,
                              K.Vocab(), K.Vocab())
    assert counters()["plane_cache_hits"] == 1
    assert canonical_bytes(s_acc) == before
    accel.fold_ops(s_acc, list(first))  # every op a replay
    assert canonical_bytes(s_acc) == before == canonical_bytes(s_host)
    assert counters()["plane_cache_hits"] == 2
    assert accel._plane_cache is not cache and accel._plane_cache.ref() is s_acc
    trace.reset()


@pytest.mark.parametrize("seed", range(4))
def test_the_partial_writeback_builds_what_the_whole_one_builds(seed):
    """``orset_cells_to_state`` over the cells a batch named against
    ``orset_planes_to_state`` over the whole planes, horizons that only the
    advanced clock retires among them."""
    rng = np.random.default_rng(seed)
    E, R, N = 24, 9, 150
    members = K.Vocab([f"m{i}" for i in range(E)])
    replicas = K.Vocab(ACTORS[:R])
    clock0 = rng.integers(0, 6, R).astype(np.int32)
    add0 = np.where(rng.random((E, R)) < 0.3, rng.integers(1, 6, (E, R)), 0).astype(np.int32)
    add0 = np.minimum(add0, clock0[None, :])
    rm0 = np.where(rng.random((E, R)) < 0.2, clock0[None, :] + rng.integers(1, 9, (E, R)), 0)
    rm0 = rm0.astype(np.int32)
    add0 = np.where(add0 > rm0, add0, 0)  # normalized, as every stored state is
    state = K.orset_planes_to_state(clock0, add0, rm0, members, replicas)
    kind = (rng.random(N) < 0.3).astype(np.int8)
    member = rng.integers(0, E, N).astype(np.int32)
    actor = rng.integers(0, R, N).astype(np.int32)
    counter = rng.integers(1, 16, N).astype(np.int32)
    clock, add, rm = (np.asarray(x) for x in K.orset_fold(
        clock0, add0, rm0, kind, member, actor, counter,
        num_members=E, num_replicas=R))
    assert (rm0[(rm == 0) & (rm0 > 0)] > 0).any(), "a horizon was retired"
    whole = K.orset_planes_to_state(clock, add, rm, members, replicas)
    state.clock = whole.clock
    K.orset_cells_to_state(state, member, actor,
                           add[member, actor], rm[member, actor], members, replicas)
    assert state.to_obj() == whole.to_obj()
    assert state.deferred == whole.deferred and state.entries == whole.entries


def test_the_gather_clamps_padding_rows_and_is_one_program_a_shape():
    add = np.arange(12, dtype=np.int32).reshape(3, 4)
    member = np.array([2, 0, 1, 0], np.int32)
    actor = np.array([3, 1, 0, 4], np.int32)  # the last: a padding row
    got_add, got_rm = K.orset_gather_cells(add, -add, member, actor)
    assert np.asarray(got_add).tolist() == [11, 1, 4, 3]
    assert np.asarray(got_rm).tolist() == [-11, -1, -4, -3]
    text = K.orset_gather_cells.lower(add, add, member, actor).as_text()
    assert text.split("module @", 1)[1].split()[0] == "jit_orset_gather_cells"


# ------------------------------------------------------------- the routing


class FakeDevice:
    def __init__(self, limit):
        self.limit = limit

    def memory_stats(self):
        return {"bytes_limit": self.limit} if self.limit else None


def on_a_chip(monkeypatch, limit):
    import jax

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "local_devices", lambda: [FakeDevice(limit)])


CONFIG3 = (4096, 10_000, 48_000)  # BASELINE.json configs[2], one backlog round


@pytest.mark.parametrize("shape, limit, kw, route", [
    (CONFIG3, 16 << 30, {}, "resident"),        # 984 MB of planes, twice over, in 8 GiB
    (CONFIG3, 1 << 30, {}, "host"),             # ... not in half a GiB
    (CONFIG3, 16 << 30, {"plane_reuse": False}, "host"),
    (CONFIG3, 16 << 30, {"sparse_device": True, "plane_reuse": False}, "device_coo"),
    ((4096, 100_000, 48_000), 16 << 30, {}, "host"),   # 410M cells: 9.8 GB twice over
    ((4096, 1000, 2400), 1 << 20, {}, "resident"),     # under SPARSE_MIN_CELLS: always dense
    ((4096, 10_000, 1 << 20), 1 << 20, {}, "resident"),  # a row-heavy batch: dense
    ((32, 8, 384), 16 << 30, {"plane_reuse": False}, "dense"),
])
def test_the_route_follows_platform_shape_and_memory(monkeypatch, shape, limit, kw, route):
    on_a_chip(monkeypatch, limit)
    assert TpuAccelerator(**kw).orset_fold_route(*shape) == route


@pytest.mark.parametrize("shape, route", [
    (CONFIG3, "host"),            # no TPU: planes of 41M cells do not stay
    ((32, 8, 384), "resident"),   # the toy the CPU tests run
])
def test_the_route_without_a_chip(shape, route):
    assert TpuAccelerator().orset_fold_route(*shape) == route


def test_no_new_option_argument_or_variable():
    import inspect

    assert list(inspect.signature(TpuAccelerator.__init__).parameters) == [
        "self", "min_device_batch", "mesh", "sparse_device", "map_fold_impl",
        "sharded_stream", "stream_producers", "plane_reuse", "bucket_vocab"]
    import re

    src = inspect.getsource(inspect.getmodule(TpuAccelerator))
    src += inspect.getsource(S) + inspect.getsource(inspect.getmodule(K.orset_fold))
    assert sorted(set(re.findall(r"CRDT_[A-Z_]+", src))) == [
        "CRDT_BUCKET_VOCAB", "CRDT_PLANE_REUSE", "CRDT_SHARDED_STREAM",
        "CRDT_STREAM_PRODUCERS"], "the variables these three modules knew before"


def test_resident_planes_are_used_whatever_the_batch(monkeypatch):
    """A state whose planes are resident never takes the sparse branch, and
    so never loses them to it: the thresholds are asked only for planes that
    are not on the device."""
    rng, clock = np.random.default_rng(5), {}
    accel, host = TpuAccelerator(min_device_batch=1), HostAccelerator()
    s_acc, s_host = ORSet(), ORSet()
    first = batch(rng, clock, 500)
    for a, s in ((accel, s_acc), (host, s_host)):
        a.fold_ops(s, list(first))
    accel.SPARSE_MIN_CELLS = accel.SPARSE_CELLS_PER_ROW = 0  # sparse for any new state
    ops = batch(rng, clock, 300)
    trace.reset()
    accel.fold_ops(s_acc, list(ops))
    host.fold_ops(s_host, list(ops))
    c = counters()
    assert c["plane_cache_hits"] == 1 and "fold_rows_host" not in c
    assert "plane_cache_drops" not in c and accel._plane_cache.ref() is s_acc
    assert canonical_bytes(s_acc) == canonical_bytes(s_host)
    fresh = ORSet()
    accel.fold_ops(fresh, list(first))
    assert counters()["fold_rows_host"] == len(first), "no planes: the question is asked"
    trace.reset()


# ------------------------------- (b), (c) rounds through Core on FsStorage


def replica(tmp_path, folder, name, accel):
    """A replica of ``folder``, opened as the benchmark's cells open theirs:
    FsStorage, XChaCha20-Poly1305, every default."""
    from crdt_enc_tpu.backends import PlainKeyCryptor, XChaChaCryptor
    from crdt_enc_tpu.core import OpenOptions, orset_adapter
    from crdt_enc_tpu.utils.versions import DEFAULT_DATA_VERSION_1

    return Core.open(OpenOptions(
        storage=FsStorage(str(tmp_path / folder / name), str(tmp_path / folder / "remote")),
        cryptor=XChaChaCryptor(), key_cryptor=PlainKeyCryptor(),
        adapter=orset_adapter(),
        supported_data_versions=(DEFAULT_DATA_VERSION_1,),
        current_data_version=DEFAULT_DATA_VERSION_1, create=True,
        accelerator=accel,
    ))


async def rounds_through_core(tmp_path, n_rounds, collide_at=None):
    """Two folders get the same op files of sixteen devices round by round
    (sixteen files: the pipelined ingest).  One has a compactor with the
    default accelerator, the other one on the host engine; both compact()
    after every round, and a fresh host replica of the first folder reads
    what its compactor sealed.  Returns the per-round counters of the
    accelerated compactor; the bytes are held equal here."""
    written = ORSet()  # what the devices wrote, applied op by op
    rng = np.random.default_rng(3)
    writers = [await replica(tmp_path, f, "writer", HostAccelerator()) for f in "ab"]
    chip = await replica(tmp_path, "a", "compactor", TpuAccelerator(min_device_batch=1))
    host = await replica(tmp_path, "b", "compactor", HostAccelerator())
    out = []
    for r in range(n_rounds):
        for d in ACTORS:
            members = [int(m) for m in rng.integers(64, size=24)]
            if r == collide_at and d is ACTORS[0]:
                members[:2] = [True, 1.0]  # collide with the int 1 as values
            ops = []
            for m in members:
                ops.append(written.add_ctx(d, m))
                written.apply(ops[-1])
            if r % 2 and written.contains(members[-1]):
                ops.append(written.rm_ctx(members[-1]))
                written.apply(ops[-1])
            for w in writers:
                blob = await w._seal([op.to_obj() for op in ops])
                await w.storage.store_ops(d, r + 1, blob)
        trace.reset()
        await chip.compact()
        out.append(dict(counters()))
        await host.compact()
        want = host.with_state(canonical_bytes)
        assert want == canonical_bytes(written)
        assert chip.with_state(canonical_bytes) == want, f"round {r}"
        fresh = await replica(tmp_path, "a", f"fresh{r}", HostAccelerator())
        await fresh.read_remote()
        assert fresh.with_state(canonical_bytes) == want, "the sealed snapshot"
    trace.reset()
    return out


def test_rounds_of_compact_on_fs_storage_stay_equal_and_resident(tmp_path):
    per_round = asyncio.run(rounds_through_core(tmp_path, 5))
    assert per_round[0].get("plane_cache_misses") == 1
    for c in per_round[1:]:
        assert c["plane_cache_hits"] == 1 and "plane_cache_misses" not in c
        assert "plane_cache_drops" not in c and "fold_rows_host" not in c
        bucket = 1 << (c["fold_rows_device"] - 1).bit_length()
        assert c["d2h_bytes"] == 2 * 4 * bucket + 4 * 16, "a round's cells and the clock"


def test_a_collision_mid_run_falls_back_drops_the_planes_and_counts_it(tmp_path):
    per_round = asyncio.run(rounds_through_core(tmp_path, 5, collide_at=2))
    assert per_round[1]["plane_cache_hits"] == 1
    # the round with True and 1.0 beside 1: dense planes cannot hold it, the
    # host folds it, the planes held for the state go and are counted
    fell_back = per_round[2]
    assert "plane_cache_hits" not in fell_back and "fold_rows_device" not in fell_back
    assert fell_back["plane_cache_drops"] == 1 and fell_back["fold_rows_host"] > 0
    assert per_round[3]["plane_cache_misses"] == 1, "the planes are built again"
    assert per_round[4]["plane_cache_hits"] == 1, "and stay"


def test_a_collision_with_the_resident_vocabulary_takes_the_host_fold():
    """``_remap_to_cache`` returning ``None``: the batch folds on the host,
    keyed by the objects themselves as the host loop keys them, and the
    planes go at once."""
    accel, host = TpuAccelerator(min_device_batch=1), HostAccelerator()
    s_acc, s_host = ORSet(), ORSet()
    a = ACTORS[0]
    first = [AddOp(m, Dot(a, i + 1)) for i, m in enumerate([1, 2, b"x", 3])]
    second = [AddOp(True, Dot(a, 5)), AddOp(2, Dot(a, 6)), RmOp(1, VClock({a: 1}))]
    for acc, s in ((accel, s_acc), (host, s_host)):
        acc.fold_ops(s, list(first))
    trace.reset()
    accel.fold_ops(s_acc, list(second))
    host.fold_ops(s_host, list(second))
    c = counters()
    assert c["plane_cache_drops"] == 1 and c["fold_rows_host"] == 3
    assert "plane_cache_hits" not in c and accel._plane_cache is None
    assert canonical_bytes(s_acc) == canonical_bytes(s_host)
    trace.reset()


# ------------------------------------------- (d) the head, in every regime


@pytest.fixture
def thresholds():
    saved = S.BUFFER_BYTES, S.HOST_PLANE_CELLS
    yield
    S.BUFFER_BYTES, S.HOST_PLANE_CELLS = saved


@pytest.mark.parametrize("mode", ["buffer", "host_reduce", "device_stream"])
@pytest.mark.parametrize("chunk_files", [3, 40])
def test_the_head_in_every_session_mode_then_a_resident_round(mode, chunk_files, thresholds):
    """A shrunk 960k-op head (two files a device) through each regime the
    session can take, equal to the host; the planes it leaves are installed,
    so the round after it is a hit with nothing walked or uploaded but its
    rows."""
    if mode != "buffer":
        S.BUFFER_BYTES = 0
    if mode == "device_stream":
        S.HOST_PLANE_CELLS = -1
    rng, clock = np.random.default_rng(17), {}
    head = batch(rng, clock, 960)
    files = [codec.pack([op.to_obj() for op in head[i:i + 48]])
             for i in range(0, len(head), 48)]
    accel, host = TpuAccelerator(min_device_batch=1), HostAccelerator()
    s_acc, s_host = ORSet(), ORSet()
    session = accel.open_fold_session(s_acc, actors_hint=ACTORS)
    for i in range(0, len(files), chunk_files):
        session.feed(files[i:i + chunk_files])
    assert session.mode == mode
    session.finish()
    host.fold_ops(s_host, list(head))
    assert canonical_bytes(s_acc) == canonical_bytes(s_host)
    assert accel._plane_cache is not None and accel._plane_cache.ref() is s_acc
    ops = batch(rng, clock, 480)
    trace.reset()
    accel.fold_ops(s_acc, list(ops))
    host.fold_ops(s_host, list(ops))
    c = counters()
    assert c["plane_cache_hits"] == 1 and "plane_cache_misses" not in c
    assert c["h2d_bytes"] == 13 * 512
    assert "fold.planes" not in trace.snapshot()["spans"]
    assert canonical_bytes(s_acc) == canonical_bytes(s_host)
    trace.reset()
