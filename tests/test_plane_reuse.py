"""Device-resident plane reuse (ISSUE 4): the accelerator keeps a fold's
result planes on device between rounds, so repeated ``read_remote`` /
``compact`` rounds in one process stop re-issuing the full-state
``device_put`` — provable via the ``h2d_bytes`` counter — while every
byte of every resulting state stays identical to the host reference.
Plus the persistent-compilation-cache contract (enable_compilation_cache).
"""

import asyncio

import numpy as np
import pytest

from crdt_enc_tpu.core.adapters import HostAccelerator
from crdt_enc_tpu.models import ORSet
from crdt_enc_tpu.models.orset import AddOp, RmOp
from crdt_enc_tpu.models.vclock import Dot, VClock
from crdt_enc_tpu.parallel import TpuAccelerator
from crdt_enc_tpu.utils import codec, trace

R, E = 16, 64
ACTORS = [bytes([i]) * 16 for i in range(R)]


def gen_ops(n, seed, clock):
    rng = np.random.default_rng(seed)
    ops = []
    for _ in range(n):
        a = ACTORS[int(rng.integers(R))]
        m = int(rng.integers(E))
        if rng.random() < 0.15 and clock.get(a, 0):
            ops.append(RmOp(m, VClock({a: clock[a]})))
        else:
            clock[a] = clock.get(a, 0) + 1
            ops.append(AddOp(m, Dot(a, clock[a])))
    return ops


def h2d():
    return trace.snapshot()["counters"].get("h2d_bytes", 0)


def row_bytes(n_rows):
    """What a dense fold uploads on a plane-cache hit: only its row
    columns (int8 kind + int32 member, actor, counter), padded to the
    power-of-two row class."""
    return 13 * max(8, 1 << (n_rows - 1).bit_length())


def states_equal(a, b):
    return codec.pack(a.to_obj()) == codec.pack(b.to_obj())


def test_round2_fold_reuses_device_planes():
    accel, host = TpuAccelerator(min_device_batch=1), HostAccelerator()
    s_acc, s_host, clock = ORSet(), ORSet(), {}
    trace.reset()
    ops = gen_ops(2000, 1, clock)
    accel.fold_ops(s_acc, ops)
    host.fold_ops(s_host, list(ops))
    plane_bytes = 4 * (R + 2 * E * R)
    assert h2d() >= plane_bytes  # round 1 uploads the state planes
    trace.reset()
    ops = gen_ops(2000, 2, clock)
    accel.fold_ops(s_acc, ops)
    host.fold_ops(s_host, list(ops))
    assert h2d() == row_bytes(len(ops)), (
        "round 2 re-uploaded state planes despite the cache"
    )
    assert states_equal(s_acc, s_host)
    trace.reset()


def test_host_mutation_invalidates_plane_cache():
    accel, host = TpuAccelerator(min_device_batch=1), HostAccelerator()
    s_acc, s_host, clock = ORSet(), ORSet(), {}
    ops = gen_ops(1500, 3, clock)
    accel.fold_ops(s_acc, ops)
    host.fold_ops(s_host, list(ops))
    # a host-side apply lands between rounds (the cache MUST notice)
    clock[ACTORS[0]] += 1
    side = AddOp(E + 5, Dot(ACTORS[0], clock[ACTORS[0]]))
    s_acc.apply(side)
    s_host.apply(side)
    trace.reset()
    ops = gen_ops(1500, 4, clock)
    accel.fold_ops(s_acc, ops)
    host.fold_ops(s_host, list(ops))
    assert h2d() > row_bytes(len(ops)), (
        "stale device planes were trusted after a host apply"
    )
    assert states_equal(s_acc, s_host)
    # …and the refreshed cache hits again on round 3
    trace.reset()
    ops = gen_ops(1500, 5, clock)
    accel.fold_ops(s_acc, ops)
    host.fold_ops(s_host, list(ops))
    assert h2d() == row_bytes(len(ops))
    assert states_equal(s_acc, s_host)
    trace.reset()


def test_plane_cache_grows_with_vocab():
    """Round 2 introduces members AND actors the cache has never seen:
    the cached planes must pad on device and stay byte-correct."""
    accel, host = TpuAccelerator(min_device_batch=1), HostAccelerator()
    s_acc, s_host, clock = ORSet(), ORSet(), {}
    ops = gen_ops(1000, 6, clock)
    accel.fold_ops(s_acc, ops)
    host.fold_ops(s_host, list(ops))
    extra = [bytes([100 + i]) * 16 for i in range(5)]
    ops2 = []
    for i, a in enumerate(extra):
        for k in range(40):
            clock[a] = clock.get(a, 0) + 1
            ops2.append(AddOp(E + 50 + (k % 30), Dot(a, clock[a])))
    ops2.extend(gen_ops(500, 7, clock))
    trace.reset()
    accel.fold_ops(s_acc, ops2)
    host.fold_ops(s_host, list(ops2))
    assert h2d() == row_bytes(len(ops2)), (
        "vocab growth fell off the cached-plane path"
    )
    assert states_equal(s_acc, s_host)
    trace.reset()


def test_plane_reuse_off_switch(monkeypatch):
    monkeypatch.setenv("CRDT_PLANE_REUSE", "0")
    accel = TpuAccelerator(min_device_batch=1)
    assert not accel.plane_reuse
    s, clock = ORSet(), {}
    accel.fold_ops(s, gen_ops(800, 8, clock))
    trace.reset()
    accel.fold_ops(s, gen_ops(800, 9, clock))
    assert h2d() >= 4 * (R + 2 * E * R), "opt-out still cached planes"
    trace.reset()


def test_two_round_compact_product_path():
    """The ISSUE-4 acceptance shape through the REAL product path:
    compact → pipelined session (BUFFER) → dense fold.  Round 2's obs
    snapshot shows no full-state h2d re-upload (the 60 op rows' columns
    only), and the state equals
    a cold host replica's."""
    from crdt_enc_tpu.backends import (
        IdentityCryptor, MemoryRemote, MemoryStorage, PlainKeyCryptor,
    )
    from crdt_enc_tpu.core import Core, OpenOptions, orset_adapter
    from crdt_enc_tpu.models import canonical_bytes
    from crdt_enc_tpu.utils.versions import DEFAULT_DATA_VERSION_1

    def opts(storage, accel=None):
        return OpenOptions(
            storage=storage, cryptor=IdentityCryptor(),
            key_cryptor=PlainKeyCryptor(), adapter=orset_adapter(),
            supported_data_versions=(DEFAULT_DATA_VERSION_1,),
            current_data_version=DEFAULT_DATA_VERSION_1, create=True,
            accelerator=accel
            if accel is not None
            else TpuAccelerator(min_device_batch=1),
        )

    async def go():
        remote = MemoryRemote()
        reader = await Core.open(opts(MemoryStorage(remote)))
        writer = await Core.open(
            opts(MemoryStorage(remote), HostAccelerator())
        )

        async def write(n, tag):
            for i in range(n):
                await writer.apply_ops([writer.with_state(
                    lambda s: s.add_ctx(writer.actor_id, b"%s-%d" % (tag, i))
                )])

        await write(60, b"r1")
        trace.reset()
        await reader.compact()
        r1 = h2d()
        await write(60, b"r2")
        trace.reset()
        await reader.compact()
        r2 = h2d()
        trace.reset()
        assert r1 > 0, "round 1 should upload the state planes"
        assert r2 == row_bytes(60), f"round 2 re-uploaded {r2} bytes"
        cold = await Core.open(
            opts(MemoryStorage(remote), HostAccelerator())
        )
        await cold.read_remote()
        assert reader.with_state(canonical_bytes) == cold.with_state(
            canonical_bytes
        )

    asyncio.run(go())


def test_device_stream_seeds_planes_on_device(monkeypatch):
    """DEVICE_STREAM promotion creates its zero accumulator planes ON
    device (XLA fill) — no plane-sized host buffer is uploaded, so
    h2d_bytes carries only the op chunks."""
    from crdt_enc_tpu.parallel import session as S

    monkeypatch.setattr(S, "BUFFER_BYTES", 0)
    monkeypatch.setattr(S, "HOST_PLANE_CELLS", -1)
    accel = TpuAccelerator(min_device_batch=1)
    state, clock = ORSet(), {}
    ops = gen_ops(1200, 10, clock)
    payload = [codec.pack([op.to_obj() for op in ops[i : i + 24]])
               for i in range(0, len(ops), 24)]
    session = accel.open_fold_session(state, actors_hint=ACTORS)
    trace.reset()
    session.feed(payload)
    assert session.mode == "device_stream"
    plane_bytes = 4 * (session.R + 2 * session._d_E * session.R)
    assert h2d() < plane_bytes, (
        "device-stream promotion uploaded plane-sized zero buffers"
    )
    session.finish()
    trace.reset()
    host_state = ORSet()
    HostAccelerator().fold_ops(host_state, list(ops))
    assert states_equal(state, host_state)


def test_jit_cache_second_instance_recompiles_nothing(tmp_path, monkeypatch):
    """``enable_compilation_cache`` wires jax's persistent compilation
    cache: after a simulated process restart (jax.clear_caches), a
    second accelerator instance serves every compile request it can from
    the disk cache — zero new jax_cache_misses.  The directory comes
    from ``JAX_COMPILATION_CACHE_DIR`` (jax reads it into its config at
    import, simulated here), and the code sets none."""
    import jax

    import crdt_enc_tpu
    from crdt_enc_tpu.obs import runtime

    cache_dir = str(tmp_path / "jit-cache")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", cache_dir)
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    runtime.track_recompiles()

    def fold_once():
        assert crdt_enc_tpu.enable_compilation_cache() == cache_dir
        accel = TpuAccelerator(min_device_batch=1)
        s, clock = ORSet(), {}
        rng = np.random.default_rng(42)  # identical batch both runs
        ops = []
        for _ in range(600):
            a = ACTORS[int(rng.integers(R))]
            clock[a] = clock.get(a, 0) + 1
            ops.append(AddOp(int(rng.integers(E)), Dot(a, clock[a])))
        accel.fold_ops(s, ops)
        return s

    prev_min = jax.config.jax_persistent_cache_min_compile_time_secs
    try:
        # CPU compiles are sub-second: persist them all for the test
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        # earlier tests may have compiled these very shapes: drop the
        # in-memory jit cache so run 1 really compiles (into the fresh
        # cache dir, so they are misses)
        jax.clear_caches()
        fold_once()  # real compiles, all persisted to the cache dir
        first_misses = trace.snapshot()["counters"].get(
            "jax_cache_misses", 0
        )
        assert first_misses > 0, "first run should miss the empty cache"
        jax.clear_caches()  # simulate a fresh process
        before = trace.snapshot()["counters"]
        fold_once()
        after = trace.snapshot()["counters"]
        new_misses = after.get("jax_cache_misses", 0) - before.get(
            "jax_cache_misses", 0
        )
        new_hits = after.get("jax_cache_hits", 0) - before.get(
            "jax_cache_hits", 0
        )
        assert new_misses == 0, (
            f"{new_misses} compiles missed the persistent cache"
        )
        assert new_hits > 0, "nothing was served from the persistent cache"
    finally:
        jax.config.update(
            "jax_persistent_cache_min_compile_time_secs", prev_min
        )
        jax.config.update("jax_compilation_cache_dir", None)
        from jax.experimental.compilation_cache import compilation_cache

        compilation_cache.reset_cache()
        trace.reset()
