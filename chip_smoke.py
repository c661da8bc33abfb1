#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the served path still starts on
the chip.

One Python process drives the system's normal entry points once, on one
TPU, at the size a deployment would hold there, and byte-checks everything
that comes out against the host reference (``models.ORSet`` applied op by
op).  It measures nothing: the wall seconds it prints are single-run smoke
timings, compilation included, and are never a metric.

Phases (the first five are entry points, never trimmed):

* ``bulk_northstar`` — BASELINE.json config 3 at full scale (1M add/remove
  ops, 10k replicas, 4096 members) as three-layer-sealed
  XChaCha20-Poly1305 op files on ``FsStorage``: a fresh replica with
  ``TpuAccelerator()`` does open → read_remote → compact, a second fresh
  replica opens the compacted remote, then a ~10% tail lands and the first
  replica compacts again.  The route each round took is REPORTED, not
  asserted.
* ``bulk_device`` — the same entry points at the largest deployment the
  code's own routing sends to the device fold in every round (derived
  from ``session.BUFFER_BYTES`` and ``accel.SPARSE_MIN_CELLS``), three
  rounds in one process.  Device evidence is ASSERTED.
* ``merge_northstar`` — a fresh replica opens a north-star remote holding
  four snapshots sealed by compactors that read at different points, so
  the snapshot merge takes the Pallas merge kernel.
* ``serve`` — ``FoldService.run_cycle`` over 1024 small tenants, three
  cycles: cold, 1% of tenants with a new tail, idle.
* ``reads`` — eventual and linearizable reads on the north-star replicas.
* ``kernels`` — every kernel the product can route to on a TPU, compiled
  (never interpreted) at north-star width and byte-checked against its
  XLA twin or the host.  Trimmed from the back when the run nears its
  deadline; what was trimmed is named in the result.
* ``mesh`` — only when at least four devices are visible.

Contract: the default invocation exits non-zero, printing no result,
unless ``jax.devices()[0].platform == "tpu"``.  ``--tiny`` exists only so
the same code can be pre-flighted under ``JAX_PLATFORMS=cpu``; it prints
``"chip": false``.  Any phase failure, any byte mismatch, any Pallas
kernel that would run interpreted, or a native library that does not
build from the committed sources and load, is a non-zero exit.

Standard output is two JSON lines.  The first is the report: versions,
native SIMD lanes, the compile cache with hit/miss counts, and per phase
the routing evidence (``"chip"``, ``"phases"``, ``"trimmed"``,
``"claim": null``).  The last is the verdict, with exactly these keys::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

One process uses the chip: the only child this script starts is ``make``
(the native build), and it does so before JAX is imported.
"""

from __future__ import annotations

import argparse
import asyncio
import copy
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time
import traceback
import uuid
from dataclasses import dataclass

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
NATIVE_DIR = os.path.join(REPO, "crdt_enc_tpu", "native")

ENTRY_PHASES = (
    "bulk_northstar", "bulk_device", "merge_northstar", "serve", "reads",
)
PHASES = ENTRY_PHASES + ("kernels", "mesh")
# what each phase needs to have run first
NEEDS = {"merge_northstar": ("bulk_northstar",),
         "reads": ("bulk_northstar", "bulk_device")}


def log(*a):
    print(*a, file=sys.stderr, flush=True)


# --------------------------------------------------------------------- sizes


@dataclass(frozen=True)
class Sizes:
    """One deployment size.  FULL is what the chip runs; TINY only
    pre-flights the same code on the CPU."""

    # BASELINE.json config 3 (bulk_northstar, merge_northstar, reads)
    ns_ops: int
    ns_replicas: int
    ns_members: int
    ns_opf: int  # ops per op file
    # bulk_device: members fixed, replicas and ops derived from routing
    dev_members: int
    # the committed multitenant_1024t shape (bench.py e2e_multitenant)
    sv_tenants: int
    sv_ops: int
    sv_replicas: int
    sv_members: int
    sv_opf: int
    # kernels: north-star width
    k_rows: int
    k_members: int
    k_replicas: int
    k_lww_keys: int
    k_slots: int  # tenant-bucket slots
    k_map_ops: int
    k_mvreg: tuple  # (V, R)


FULL = Sizes(
    ns_ops=1_000_000, ns_replicas=10_000, ns_members=4096, ns_opf=48,
    dev_members=4096,
    sv_tenants=1024, sv_ops=384, sv_replicas=4, sv_members=64, sv_opf=24,
    k_rows=1_000_000, k_members=4096, k_replicas=10_000,
    k_lww_keys=1_000_000, k_slots=1024, k_map_ops=20_000,
    k_mvreg=(2048, 128),
)
TINY = Sizes(
    ns_ops=6_000, ns_replicas=40, ns_members=64, ns_opf=12,
    dev_members=64,
    sv_tenants=8, sv_ops=96, sv_replicas=4, sv_members=16, sv_opf=12,
    k_rows=3_000, k_members=40, k_replicas=24,
    k_lww_keys=500, k_slots=8, k_map_ops=300,
    k_mvreg=(64, 16),
)


# ------------------------------------------------------------ native + device


def build_native(rebuild: bool) -> dict:
    """Build both native libraries from the committed sources and load
    them; raises unless both build and load.  ``rebuild`` forces a full
    rebuild (``make -B``): a copied ``build/`` may hold a library
    compiled for another machine's CPU, which make would find fresh.
    The CPU pre-flight builds incrementally — a measurement never does."""
    t0 = time.perf_counter()
    r = subprocess.run(
        ["make", *(["-B"] if rebuild else []), "-C", NATIVE_DIR, "all"],
        capture_output=True, text=True,
    )
    if r.returncode != 0:
        raise RuntimeError(
            f"native build failed (exit {r.returncode}):\n{r.stdout[-2000:]}"
            f"\n{r.stderr[-4000:]}"
        )
    from crdt_enc_tpu import native

    # the loaders run `make <target>` (a no-op now) and memoize: after
    # this no code path starts a child again
    lib = native.load()
    native.load_state()
    return {
        "rebuilt_from_source": rebuild,
        "build_s": round(time.perf_counter() - t0, 1),
        "simd_lanes": int(lib.crdt_simd_lanes()),
    }


def first_env_platform() -> str:
    platforms = os.environ.get("JAX_PLATFORMS", "").lower()
    return platforms.split(",")[0].strip() if platforms else ""


class PallasGuard:
    """Records every ``pallas_call`` traced in this process and refuses an
    interpreted one: a kernel in interpret mode is the Pallas interpreter,
    not the chip."""

    def __init__(self, allow_interpret: bool):
        self.allow_interpret = allow_interpret
        self.calls: list = []

    def install(self) -> None:
        from jax.experimental import pallas as pl

        orig = pl.pallas_call

        def guarded(kernel, *args, **kw):
            fn = getattr(kernel, "func", kernel)
            name = getattr(fn, "__name__", repr(fn))
            interpreted = bool(kw.get("interpret", False))
            self.calls.append((name, interpreted))
            if interpreted and not self.allow_interpret:
                raise RuntimeError(
                    f"Pallas kernel {name} would run interpreted"
                )
            return orig(kernel, *args, **kw)

        pl.pallas_call = guarded

    def drain(self) -> dict:
        """Kernel name → times traced since the last drain."""
        out: dict = {}
        for name, interpreted in self.calls:
            key = name + (" (interpreted)" if interpreted else "")
            out[key] = out.get(key, 0) + 1
        self.calls = []
        return out


# ------------------------------------------------------------------- context


class Ctx:
    def __init__(self, args, sizes: Sizes, chip: bool, dev, guard, tmp):
        self.args = args
        self.sz = sizes
        self.chip = chip
        self.dev = dev
        self.guard = guard
        self.tmp = tmp
        self.rng = np.random.default_rng(args.seed)
        self.t_start = time.monotonic()
        self.deadline = self.t_start + args.deadline
        self.shared: dict = {}  # what later phases read from earlier ones
        self.totals = {"jax_compiles": 0, "jax_cache_hits": 0,
                       "jax_cache_misses": 0}
        self.trimmed: list = []

    def child_rng(self):
        return np.random.default_rng(self.rng.integers(1 << 62))


class Obs:
    """One observation window over the repo's own trace registry."""

    SPAN_FAMILIES = ("session.", "fold.", "serve.", "states.", "stream.fold",
                     "stream.h2d", "delta.", "read.", "ops.", "compact.",
                     "checkpoint.", "repl.")

    def __init__(self, ctx: Ctx):
        self.ctx = ctx
        from crdt_enc_tpu.utils import trace

        self.trace = trace
        trace.reset()
        ctx.guard.drain()
        self.pallas: dict = {}  # kernel name → times traced in the window
        self.t0 = time.perf_counter()

    def read(self) -> dict:
        """The evidence so far; the window stays open."""
        snap = self.trace.snapshot()
        c = snap["counters"]
        self.pallas.update(self.ctx.guard.drain())
        spans = {
            k: [v["count"], round(v["seconds"], 3)]
            for k, v in sorted(snap["spans"].items())
            if k.startswith(self.SPAN_FAMILIES)
        }
        return {
            "wall_s": round(time.perf_counter() - self.t0, 2),
            "jax_compiles": int(c.get("jax_compiles", 0)),
            "jax_cache_hits": int(c.get("jax_cache_hits", 0)),
            "jax_cache_misses": int(c.get("jax_cache_misses", 0)),
            "h2d_bytes": int(c.get("h2d_bytes", 0)),
            "rows_device": int(c.get("fold_rows_device", 0)),
            "rows_host": int(c.get("fold_rows_host", 0)),
            "pallas_routed": int(c.get("pallas_routed", 0)),
            "spans": spans,
            "counters": {
                k: int(v) for k, v in sorted(c.items())
                if k.startswith(("serve_", "states_merged", "read_",
                                 "op_files_", "ops_folded"))
            },
            "pallas_traced": dict(self.pallas),
        }

    def stop(self) -> dict:
        ev = self.read()
        for k in self.ctx.totals:
            self.ctx.totals[k] += ev[k]
        return ev


def routing(ev: dict) -> dict:
    """Name the regime and fold function a round took, from its spans and
    the routing counters (``fold_rows_*``, ``pallas_routed``)."""
    spans, pallas = ev["spans"], ev["pallas_routed"] > 0

    def fired(name):
        return spans.get(name, [0])[0] > 0

    if fired("session.host_reduce"):
        mode = "host_reduce"
        fn = ("session._host_reduce (native orset_host_reduce) + "
              "apply_batch_planes_host")
    elif fired("session.device_fold"):
        mode = "device_stream"
        fn = ("ops.stream._fold_donated_pallas" if pallas
              else "ops.stream._fold_donated (XLA)")
    else:
        mode = "buffer"
        if fired("fold.device"):
            fn = "orset_fold_stream" if fired("stream.fold") else "orset_fold"
            fn += "_pallas" if pallas else " (XLA scatter)"
        elif ev["rows_device"]:  # the mesh route has no fold.device span
            fn = "orset_fold_sharded (eager shard_map"
            fn += ", Pallas per shard)" if pallas else ", XLA scatter)"
        elif ev["rows_host"]:
            fn = "orset_fold_sparse_host (host sort)"
        elif ev["counters"].get("ops_folded"):
            fn = "per-op host loop"
        else:
            fn = "none (nothing folded)"
    return {"session_mode": mode, "fold_fn": fn,
            "rows_device": ev["rows_device"], "rows_host": ev["rows_host"],
            "h2d_bytes": ev["h2d_bytes"]}


def check(cond: bool, what: str) -> None:
    """A smoke assertion that survives ``python -O``."""
    if not cond:
        raise AssertionError(what)


# ---------------------------------------------------------------------- data


def actor_table(R: int) -> list:
    """R actor ids whose byte order equals their index order."""
    return [uuid.UUID(int=i + 1).bytes for i in range(R)]


def gen_ops(rng, N: int, R: int, E: int, cells: int | None = None):
    """An add/remove op stream as columns: ~10% removes; every actor's add
    dots are sequential in row order; a remove's horizon is its actor's
    add count so far (a remove before the actor's first add is a
    sentinel row, ``actor == R``, which every kernel masks out).
    ``cells`` draws the (member, actor) pairs from that many distinct
    cells instead of uniformly."""
    kind = (rng.random(N) < 0.10).astype(np.int8)
    if cells is None:
        member = rng.integers(0, E, N, dtype=np.int32)
        actor = rng.integers(0, R, N, dtype=np.int32)
    else:
        pool = rng.integers(0, E * R, cells)
        cell = pool[rng.integers(0, cells, N)]
        member = (cell // R).astype(np.int32)
        actor = (cell % R).astype(np.int32)
    is_add = kind == 0
    order = np.argsort(actor, kind="stable")
    s_actor = actor[order]
    cum = np.cumsum(is_add[order].astype(np.int64))
    starts = np.searchsorted(s_actor, np.arange(R))
    first = np.minimum(starts, N - 1)
    base = np.where(
        starts < N, cum[first] - is_add[order][first].astype(np.int64), 0
    )
    counter = np.empty(N, np.int64)
    counter[order] = cum - base[s_actor]
    counter = counter.astype(np.int32)
    dead = (~is_add) & (counter == 0)
    actor = np.where(dead, R, actor).astype(np.int32)
    return kind, member, actor, counter


def op_files(cols, actors: list, opf: int) -> list:
    """Columns → ``(actor, version, ops)`` op files in the ORSet wire
    form: per actor, dense versions from 1, at most ``opf`` ops each."""
    kind, member, actor, counter = cols
    R = len(actors)
    live = actor < R
    order = np.argsort(actor[live], kind="stable")
    k_l = kind[live][order].tolist()
    m_l = member[live][order].tolist()
    a_l = actor[live][order]
    c_l = counter[live][order].tolist()
    files, versions = [], {}
    i, n = 0, len(k_l)
    while i < n:
        j = min(i + opf, n)
        j = i + int(np.searchsorted(a_l[i:j], a_l[i], side="right"))
        ab = actors[int(a_l[i])]
        ops = [
            [0, m_l[t], [ab, c_l[t]]] if k_l[t] == 0
            else [1, m_l[t], {ab: c_l[t]}]
            for t in range(i, j)
        ]
        v = versions.get(ab, 0) + 1
        versions[ab] = v
        files.append((ab, v, ops))
        i = j
    return files


def split_tail(files: list, frac: float):
    """Hold back the last file of successive actors until the tail holds
    ``frac`` of all ops.  Returns ``(prefix, tail)``."""
    total = sum(len(ops) for _, _, ops in files)
    last = {}
    for idx, (ab, _, _) in enumerate(files):
        last[ab] = idx
    tail_idx, n_tail = set(), 0
    for ab in sorted(last):
        if n_tail >= total * frac:
            break
        tail_idx.add(last[ab])
        n_tail += len(files[last[ab]][2])
    return (
        [f for i, f in enumerate(files) if i not in tail_idx],
        [f for i, f in enumerate(files) if i in tail_idx],
    )


def host_apply(state, files) -> None:
    """THE host reference: ``models.ORSet`` applied op by op."""
    from crdt_enc_tpu.models.orset import op_from_obj

    for _, _, ops in files:
        for obj in ops:
            state.apply(op_from_obj(obj))


def core_opts(storage, accel, membership=None):
    from crdt_enc_tpu.backends import PlainKeyCryptor, XChaChaCryptor
    from crdt_enc_tpu.core import OpenOptions, orset_adapter
    from crdt_enc_tpu.utils.versions import DEFAULT_DATA_VERSION_1

    return OpenOptions(
        storage=storage,
        cryptor=XChaChaCryptor(),
        key_cryptor=PlainKeyCryptor(),
        adapter=orset_adapter(),
        supported_data_versions=(DEFAULT_DATA_VERSION_1,),
        current_data_version=DEFAULT_DATA_VERSION_1,
        create=True,
        accelerator=accel,
        membership=membership,
    )


async def seal_files(core, files) -> list:
    """Seal op files in the core's real three-layer wire format."""
    return [(ab, v, await core._seal(ops)) for ab, v, ops in files]


async def store_blobs(storage, blobs) -> None:
    sem = asyncio.Semaphore(64)

    async def one(ab, v, blob):
        async with sem:
            await storage.store_ops(ab, v, blob)

    await asyncio.gather(*(one(*b) for b in blobs))


def state_bytes(core) -> bytes:
    from crdt_enc_tpu.models import canonical_bytes

    return core.with_state(canonical_bytes)


# -------------------------------------------------------------- bulk phases


async def bulk_rounds(ctx: Ctx, name: str, N, R, E, opf, tail_fracs,
                      accel_factory, assert_device: bool) -> dict:
    """open → read_remote → compact on a fresh replica, a second fresh
    replica on the compacted remote, then one compact per tail."""
    from crdt_enc_tpu.backends import FsStorage
    from crdt_enc_tpu.core import Core
    from crdt_enc_tpu.core.adapters import HostAccelerator
    from crdt_enc_tpu.models import ORSet, canonical_bytes
    from crdt_enc_tpu.read import MembershipPolicy

    t0 = time.perf_counter()
    actors = actor_table(R)
    files = op_files(gen_ops(ctx.child_rng(), N, R, E), actors, opf)
    rest, tails = files, []
    for frac in reversed(tail_fracs):
        rest, tail = split_tail(rest, frac)
        tails.insert(0, tail)
    prefix = rest
    base = os.path.join(ctx.tmp, name)
    remote = os.path.join(base, "remote")
    writer = await Core.open(core_opts(
        FsStorage(os.path.join(base, "writer"), remote), HostAccelerator()
    ))
    blobs = await seal_files(writer, files)
    by_id = {(ab, v): blob for ab, v, blob in blobs}
    await store_blobs(
        writer.storage, [(ab, v, by_id[ab, v]) for ab, v, _ in prefix]
    )
    out = {
        "shape": {"ops": sum(len(o) for _, _, o in files), "replicas": R,
                  "members": E, "cells": E * R, "op_files": len(files),
                  "ops_per_file": opf,
                  "tail_ops": [sum(len(o) for _, _, o in t) for t in tails]},
        "build_s": round(time.perf_counter() - t0, 1),
        "rounds": [],
    }
    ref = ORSet()
    host_apply(ref, prefix)

    obs = Obs(ctx)
    a = await Core.open(core_opts(
        FsStorage(os.path.join(base, "a"), remote), accel_factory()
    ))
    await a.read_remote()
    await a.compact()
    ev = obs.stop()
    ev["route"] = routing(ev)
    out["rounds"].append(ev)
    ref_bytes = canonical_bytes(ref)
    check(state_bytes(a) == ref_bytes,
          f"{name}: round 1 state differs from the host reference")

    obs = Obs(ctx)
    b = await Core.open(core_opts(
        FsStorage(os.path.join(base, "b"), remote), accel_factory(),
        membership=MembershipPolicy(expected=[a.actor_id]),
    ))
    await b.read_remote()
    out["reopen"] = obs.stop()
    check(state_bytes(b) == ref_bytes,
          f"{name}: a replica opened on the compacted remote differs")

    for tail in tails:
        await store_blobs(
            writer.storage, [(ab, v, by_id[ab, v]) for ab, v, _ in tail]
        )
        host_apply(ref, tail)
        obs = Obs(ctx)
        await a.compact()
        ev = obs.stop()
        ev["route"] = routing(ev)
        out["rounds"].append(ev)
        ref_bytes = canonical_bytes(ref)
        check(state_bytes(a) == ref_bytes,
              f"{name}: round {len(out['rounds'])} state differs from the "
              "host reference")
    out["byte_identical"] = True

    if assert_device:
        plane_bytes = 4 * E * R
        r1 = out["rounds"][0]
        check(r1["spans"].get("fold.device", [0])[0] > 0,
              f"{name}: no fold.device span in round 1")
        check(r1["rows_device"] > 0 and r1["rows_host"] == 0,
              f"{name}: round 1 rows device/host = "
              f"{r1['rows_device']}/{r1['rows_host']}")
        check(r1["h2d_bytes"] >= 2 * plane_bytes,
              f"{name}: round 1 uploaded {r1['h2d_bytes']} bytes, less than "
              "the state planes")
        check(r1["jax_compiles"] > 0, f"{name}: round 1 compiled nothing")
        if ctx.chip:
            check(any("ablk" in k for k in r1["pallas_traced"]),
                  f"{name}: the dense fold did not take the Pallas kernel "
                  f"(traced: {r1['pallas_traced']})")
            check(all(rd["pallas_routed"] for rd in out["rounds"]),
                  f"{name}: a round was not routed to the Pallas fold")
        for i, rd in enumerate(out["rounds"][1:], start=2):
            check(rd["spans"].get("fold.device", [0])[0] > 0
                  and rd["rows_host"] == 0,
                  f"{name}: round {i} did not fold on the device")
            check(rd["h2d_bytes"] < plane_bytes,
                  f"{name}: round {i} re-uploaded {rd['h2d_bytes']} bytes "
                  "(device-resident planes were not reused)")
        check(out["rounds"][-1]["jax_compiles"] == 0,
              f"{name}: the steady round compiled "
              f"{out['rounds'][-1]['jax_compiles']} programs")
    ctx.shared[name] = {
        "a": a, "b": b, "ref": ref, "ref_bytes": ref_bytes,
        "files": files, "by_id": by_id,
        "remote": remote, "actors": actors,
    }
    return out


async def phase_bulk_northstar(ctx: Ctx) -> dict:
    from crdt_enc_tpu.parallel import TpuAccelerator

    s = ctx.sz
    return await bulk_rounds(
        ctx, "bulk_northstar", s.ns_ops, s.ns_replicas, s.ns_members,
        s.ns_opf, [0.10], TpuAccelerator, assert_device=False,
    )


def device_deployment(ctx: Ctx) -> tuple:
    """The largest deployment whose every round the code's own routing
    sends to the dense device fold: the ingest stays in the session's
    BUFFER regime (≤ BUFFER_BYTES of 13-byte rows, kept 5% under), and
    the planes stay under SPARSE_MIN_CELLS so no tail, however small, is
    diverted to the host sparse fold (``_use_sparse``)."""
    from crdt_enc_tpu.parallel import session
    from crdt_enc_tpu.parallel.accel import TpuAccelerator

    n_ops = int(0.95 * session.BUFFER_BYTES / 13)
    replicas = (TpuAccelerator.SPARSE_MIN_CELLS - 1) // ctx.sz.dev_members
    if not ctx.chip:  # the pre-flight only walks the code
        n_ops, replicas = min(n_ops, 4000), min(replicas, 24)
    return n_ops, replicas


async def phase_bulk_device(ctx: Ctx) -> dict:
    from crdt_enc_tpu.parallel import TpuAccelerator

    n_ops, replicas = device_deployment(ctx)
    return await bulk_rounds(
        ctx, "bulk_device", n_ops, replicas, ctx.sz.dev_members,
        ctx.sz.ns_opf,
        [0.05, 0.05], TpuAccelerator, assert_device=True,
    )


# -------------------------------------------------------------------- merge


async def phase_merge_northstar(ctx: Ctx) -> dict:
    """Four compactors, each seeing its own quarter of the actors and the
    first tenth of the next quarter's (so neighbouring snapshots share
    dots and disagree on clocks), seal one snapshot each; the snapshots
    sync into one remote, and a fresh replica merges them.  The union of
    the four cuts is the whole history."""
    from crdt_enc_tpu.backends import FsStorage
    from crdt_enc_tpu.core import Core
    from crdt_enc_tpu.models import ORSet, canonical_bytes
    from crdt_enc_tpu.parallel import TpuAccelerator

    ns = ctx.shared["bulk_northstar"]
    files, by_id, actors = ns["files"], ns["by_id"], ns["actors"]
    S = 4
    base = os.path.join(ctx.tmp, "merge")
    meta = os.path.join(ns["remote"], "meta")
    per = -(-len(actors) // S)
    group = {ab: i // per for i, ab in enumerate(actors)}
    shared = {ab for i, ab in enumerate(actors) if i % per < per // 10}
    merged = os.path.join(base, "all")
    shutil.copytree(meta, os.path.join(merged, "meta"))
    os.makedirs(os.path.join(merged, "states"))
    t0 = time.perf_counter()
    for k in range(S):
        remote = os.path.join(base, f"r{k}")
        shutil.copytree(meta, os.path.join(remote, "meta"))
        storage = FsStorage(os.path.join(base, f"c{k}"), remote)
        mine = [
            (ab, v, by_id[ab, v]) for ab, v, _ in files
            if group[ab] == k
            or (group[ab] == (k + 1) % S and ab in shared)
        ]
        await store_blobs(storage, mine)
        c = await Core.open(core_opts(storage, TpuAccelerator()))
        await c.read_remote()
        await c.compact()
        states = os.path.join(remote, "states")
        for fname in os.listdir(states):
            shutil.copy(os.path.join(states, fname),
                        os.path.join(merged, "states", fname))
    n_snapshots = len(os.listdir(os.path.join(merged, "states")))
    check(n_snapshots == S, f"expected {S} snapshots, found {n_snapshots}")
    out = {"snapshots": n_snapshots,
           "build_s": round(time.perf_counter() - t0, 1)}

    obs = Obs(ctx)
    f = await Core.open(core_opts(
        FsStorage(os.path.join(base, "f"), merged), TpuAccelerator()
    ))
    await f.read_remote()
    ev = obs.stop()
    out["merge"] = ev
    # the host merge: models.ORSet.merge over the same four snapshots
    host = ORSet()
    names = await f.storage.list_state_names()
    for _, raw in await f.storage.load_states(names):
        obj = await f._open_sealed(raw)
        host.merge(ORSet.from_obj(obj[0]))
    got = state_bytes(f)
    check(got == canonical_bytes(host),
          "merge_northstar: device merge differs from the host merge")
    check(got == ns["ref_bytes"],
          "merge_northstar: merged state differs from the host reference")
    out["byte_identical"] = True
    E, R = ctx.sz.ns_members, ctx.sz.ns_replicas
    out["stack_shape"] = [S + 1, E, R]
    check(ev["counters"].get("states_merged") == S,
          f"merged {ev['counters'].get('states_merged')} states, not {S}")
    check(ev["spans"].get("states.merge", [0])[0] > 0, "no states.merge span")
    check(ev["h2d_bytes"] >= 2 * S * 4 * E * R,
          f"merge uploaded {ev['h2d_bytes']} bytes, less than the stack")
    check(ev["jax_compiles"] > 0, "the merge compiled nothing")
    if ctx.chip:
        check(ev["pallas_routed"] == 1
              and any("merge" in k for k in ev["pallas_traced"]),
              "the snapshot merge did not take the Pallas merge kernel "
              f"(traced: {ev['pallas_traced']})")
    return out


# -------------------------------------------------------------------- serve


async def build_tenants(ctx: Ctx, T: int, n_hot: int):
    """``T`` small tenants on the memory backend (XChaCha AEAD), the
    first ``n_hot`` with a 10% tail held back as sealed blobs.  Returns
    ``(remotes, tails, head_ops)``."""
    from crdt_enc_tpu.backends import MemoryRemote, MemoryStorage
    from crdt_enc_tpu.core import Core
    from crdt_enc_tpu.core.adapters import HostAccelerator

    s = ctx.sz
    actors = actor_table(s.sv_replicas)
    remotes, tails, head_ops = [], [], 0
    for t in range(T):
        files = op_files(
            gen_ops(ctx.child_rng(), s.sv_ops, s.sv_replicas, s.sv_members),
            actors, s.sv_opf,
        )
        head, tail = (split_tail(files, 0.10) if t < n_hot else (files, []))
        remote = MemoryRemote()
        writer = await Core.open(core_opts(
            MemoryStorage(remote), HostAccelerator()
        ))
        await store_blobs(writer.storage, await seal_files(writer, head))
        head_ops += sum(len(o) for _, _, o in head)
        remotes.append(remote)
        tails.append(await seal_files(writer, tail))
    return remotes, tails, head_ops


async def solo_bytes(remote) -> bytes:
    """The tenant reference: a solo ``Core.compact`` of a copy of its
    remote through the host engine."""
    from crdt_enc_tpu.backends import MemoryStorage
    from crdt_enc_tpu.core import Core
    from crdt_enc_tpu.core.adapters import HostAccelerator

    c = await Core.open(core_opts(
        MemoryStorage(copy.deepcopy(remote)), HostAccelerator()
    ))
    await c.compact()
    return state_bytes(c)


async def phase_serve(ctx: Ctx) -> dict:
    """FoldService over many small tenants (memory backend, XChaCha
    AEAD): cold cycle, a cycle after 1% of tenants got a tail, an idle
    cycle.  Every tenant is compared with a solo ``Core.compact`` of a
    copy of its remote through the host engine."""
    import jax

    from crdt_enc_tpu.backends import MemoryStorage
    from crdt_enc_tpu.core import Core
    from crdt_enc_tpu.parallel import TpuAccelerator
    from crdt_enc_tpu.serve import FoldService

    s = ctx.sz
    T = s.sv_tenants
    n_hot = max(1, T // 100)
    t0 = time.perf_counter()
    remotes, tails, total_ops = await build_tenants(ctx, T, n_hot)
    out = {
        "shape": {"tenants": T, "ops_per_tenant": s.sv_ops,
                  "replicas": s.sv_replicas, "members": s.sv_members,
                  "ops_per_file": s.sv_opf, "head_ops": total_ops,
                  "tail_tenants": n_hot},
        "build_s": round(time.perf_counter() - t0, 1),
        "cycles": [],
    }

    t0 = time.perf_counter()
    solo = [await solo_bytes(r) for r in remotes]
    out["solo_reference_s"] = round(time.perf_counter() - t0, 1)
    served = [
        await Core.open(core_opts(MemoryStorage(r), TpuAccelerator()))
        for r in remotes
    ]
    svc = FoldService(served)

    async def cycle(label: str) -> dict:
        obs = Obs(ctx)
        results = await svc.run_cycle()
        ev = obs.stop()
        errors = [(i, r.error) for i, r in enumerate(results) if r.error]
        check(not errors, f"serve {label}: tenant errors {errors[:3]}")
        paths: dict = {}
        for r in results:
            paths[r.path] = paths.get(r.path, 0) + 1
        ev["label"], ev["paths"] = label, paths
        out["cycles"].append(ev)
        return ev

    cold = await cycle("cold")
    check(cold["paths"].get("batched") == T,
          f"serve cold: fold paths {cold['paths']}, expected {T} batched")
    check(all(state_bytes(c) == ref for c, ref in zip(served, solo)),
          "serve cold: a tenant differs from its solo Core.compact")
    check(cold["jax_compiles"] > 0, "serve cold: compiled nothing")
    check(cold["spans"].get("serve.fold", [0])[0] > 0,
          "serve cold: no serve.fold span")
    check(cold["h2d_bytes"] > 0, "serve cold: no bytes went to the device")

    # warm entries: device arrays on an accelerator, not host copies
    # (on the CPU backend the tier keeps host copies by design)
    entries = [svc.warm.lookup(c._data.state) for c in served]
    check(all(e is not None for e in entries),
          "serve: a tenant has no warm entry after the cold cycle")
    on_device = sum(
        all(isinstance(p, jax.Array) for p in e.planes) for e in entries
    )
    out["warm_entries_on_device"] = on_device
    if ctx.chip:
        check(on_device == T,
              f"serve: {T - on_device} warm entries hold host copies")
        check(all(p.devices() == {ctx.dev}
                  for e in entries for p in e.planes),
              "serve: warm planes are not on the chip")

    for t in range(n_hot):
        await store_blobs(served[t].storage, tails[t])
    hot_solo = [await solo_bytes(remotes[t]) for t in range(n_hot)]
    tail_ev = await cycle("tail_1pct")
    check(tail_ev["paths"].get("batched") == n_hot,
          f"serve tail: fold paths {tail_ev['paths']}")
    check(all(state_bytes(served[t]) == hot_solo[t] for t in range(n_hot)),
          "serve tail: a tenant differs from its solo Core.compact")
    check(all(state_bytes(c) == ref
              for c, ref in list(zip(served, solo))[n_hot:]),
          "serve tail: a quiet tenant's state moved")
    check(tail_ev["counters"].get("serve_warm_hits", 0) >= n_hot,
          "serve tail: the hot tenants missed the warm tier")

    idle = await cycle("idle")
    check(idle["jax_compiles"] == 0,
          f"serve idle: compiled {idle['jax_compiles']} programs")
    check(idle["paths"] == {"empty": T}, f"serve idle: {idle['paths']}")
    out["byte_identical"] = True
    svc.close()
    return out


# -------------------------------------------------------------------- reads


async def phase_reads(ctx: Ctx) -> dict:
    """Both read tiers on the north-star replicas.  A is the compactor; B,
    the replica that opened the compacted remote, pins its watermark
    denominator to A, so its strong read returns — and is compared with
    the oracle fold of exactly the cut it names.  A replica WITHOUT a
    membership policy counts every silent producer in the denominator,
    so its zero-staleness strong read must be REFUSED by name; that probe
    runs on the bulk_device compactor (the unpinned watermark is
    O(replicas × actors) host work — about a minute at 10k producers)."""
    from crdt_enc_tpu.models import ORSet, canonical_bytes
    from crdt_enc_tpu.read import StalenessError
    from crdt_enc_tpu.sim.linearize import oracle_fold

    ns = ctx.shared["bulk_northstar"]
    a, b, ref, files = ns["a"], ns["b"], ns["ref"], ns["files"]
    oplog = {(ab, v): ops for ab, v, ops in files}
    rng = ctx.child_rng()
    probes = [int(m) for m in rng.integers(0, ctx.sz.ns_members + 8, 12)]
    out: dict = {"probes": len(probes)}

    obs = Obs(ctx)
    res = await a.read()
    check(res.consistency == "eventual", "A.read() tier")
    check(canonical_bytes(ORSet.from_obj(res.obj)) == ns["ref_bytes"],
          "reads: eventual read differs from the host reference")
    for m in probes:
        check(await a.contains(m) == ref.contains(m),
              f"reads: eventual contains({m}) wrong")
    out["eventual"] = {"wall_s": obs.stop()["wall_s"]}

    obs = Obs(ctx)
    dv = ctx.shared["bulk_device"]
    refused = None
    try:
        res = await dv["a"].read(linearizable=True, max_lag=0)
    except StalenessError as e:
        refused = e.reason
        check(refused in ("lag_exceeded", "uncovered_target", "timeout"),
              f"reads: unnamed refusal {refused!r}")
    else:
        oracle, missing = oracle_fold(
            {(ab, v): ops for ab, v, ops in dv["files"]}, res.cursor)
        check(not missing and canonical_bytes(ORSet.from_obj(res.obj))
              == canonical_bytes(oracle),
              "reads: the unpinned strong read differs from the oracle of "
              "its cut")
    out["strong_no_policy"] = {"refused": refused,
                               "replicas": len(dv["actors"]),
                               "wall_s": obs.stop()["wall_s"]}

    obs = Obs(ctx)
    res = await b.read(linearizable=True)
    check(res.consistency == "strong", "B.read(linearizable=True) tier")
    oracle, missing = oracle_fold(oplog, res.cursor)
    check(not missing, f"reads: B's cut names {len(missing)} missing files")
    check(canonical_bytes(ORSet.from_obj(res.obj)) == canonical_bytes(oracle),
          "reads: B's strong read differs from the oracle of its cut")
    covered = sum(res.cursor.counters.values())
    check(covered == len(files),
          f"reads: B's stable prefix covers {covered} of {len(files)} files")
    for m in probes:
        got = await b.contains(m, linearizable=True, refresh=False)
        check(got == oracle.contains(m), f"reads: strong contains({m}) wrong")
    ev = obs.stop()
    out["strong_pinned"] = {
        "covered_files": covered, "lag": res.view.lag,
        "wall_s": ev["wall_s"], "counters": ev["counters"],
    }
    return out


# ------------------------------------------------------------------ kernels


def same(a, b) -> bool:
    """Exact equality of two arrays or tuples of arrays, on the device."""
    import jax.numpy as jnp

    if isinstance(a, (tuple, list)):
        return len(a) == len(b) and all(same(x, y) for x, y in zip(a, b))
    return bool(jnp.array_equal(jnp.asarray(a), jnp.asarray(b)))


class KernelData:
    """North-star-width inputs shared by the kernel checks."""

    def __init__(self, ctx: Ctx):
        import jax

        from crdt_enc_tpu import ops as K

        s = ctx.sz
        self.E, self.R, self.N = s.k_members, s.k_replicas, s.k_rows
        E, R, N = self.E, self.R, self.N
        n0 = N // 5
        cols = gen_ops(ctx.child_rng(), n0 + N, R, E)
        self.prior = tuple(jax.device_put(c[:n0]) for c in cols)
        self.host_cols = tuple(c[n0:] for c in cols)
        self.cols = tuple(jax.device_put(c) for c in self.host_cols)
        self.interpret = not ctx.chip
        zeros = (np.zeros(R, np.int32), np.zeros((E, R), np.int32),
                 np.zeros((E, R), np.int32))
        self.zeros = tuple(jax.device_put(z) for z in zeros)
        self.fold = lambda planes, cols: K.orset_fold(
            *planes, *cols, num_members=E, num_replicas=R
        )
        # a non-empty prior state (the replay gate and the normalization
        # then have something to do), and the XLA twin every ORSet fold
        # variant is compared with
        self.p1 = self.fold(self.zeros, self.pad_rows(self.prior, N))
        self.ref = self.fold(self.p1, self.cols)

    def pad_rows(self, cols, n: int):
        import jax.numpy as jnp

        kind, member, actor, counter = cols
        pad = n - kind.shape[0]
        return (
            jnp.pad(kind, (0, pad)), jnp.pad(member, (0, pad)),
            jnp.pad(actor, (0, pad), constant_values=self.R),
            jnp.pad(counter, (0, pad)),
        )


def k_orset_pallas(ctx, kd, obs, layout):
    from crdt_enc_tpu.ops import pallas_fold as PF

    cap = PF.fold_cap(kd.host_cols[1], kd.E)
    got = PF.orset_fold_pallas(
        *kd.p1, *kd.cols, num_members=kd.E, num_replicas=kd.R,
        tile_cap=cap, layout=layout, interpret=kd.interpret,
    )
    check(same(got, kd.ref), f"orset_fold_pallas[{layout}] != orset_fold")
    return {"tile_cap": cap}


def k_orset_fused(ctx, kd, obs):
    from crdt_enc_tpu.ops import pallas_fold as PF

    E, R = kd.E, kd.R
    cap = PF.fold_cap(kd.host_cols[1], E)
    fd = PF.fused_defaults(E, R, int(kd.host_cols[3].max()))
    padded = PF.orset_pad_state(
        *kd.p1, num_members=E, num_replicas=R, h_blk=fd["h_blk"]
    )
    got = PF.orset_fold_pallas_fused(
        *padded, *kd.cols, num_members=E, num_replicas=R, tile_cap=cap,
        interpret=kd.interpret, **fd,
    )
    got = PF.orset_unpad_state(*got, num_members=E, num_replicas=R)
    check(same(got, kd.ref), "orset_fold_pallas_fused != orset_fold")
    return fd


def k_stream_pallas(ctx, kd, obs):
    """The accelerator's blockwise route: more rows than
    STREAM_CHUNK_ROWS fold as MAX_ROWS-row chunks through the donated
    Pallas step (on a TPU), staged through the ChunkPool."""
    from crdt_enc_tpu import ops as K
    from crdt_enc_tpu.models import ORSet, canonical_bytes
    from crdt_enc_tpu.parallel.accel import TpuAccelerator

    E, R = kd.E, kd.R
    accel = TpuAccelerator()
    if ctx.chip:
        n = accel.STREAM_CHUNK_ROWS + (1 << 18)
    else:
        accel.STREAM_CHUNK_ROWS = 1 << 10  # instance-local, tiny only
        n = (1 << 10) + 300
    kind, member, actor, counter = gen_ops(
        ctx.child_rng(), n, R, E, cells=max(64, n // 10)
    )
    live = actor < R
    cols = tuple(c[live] for c in (kind, member, actor, counter))
    actors = actor_table(R)
    got = ORSet()
    accel._fold_orset_columns(
        got, *cols, K.Vocab(range(E)), K.Vocab.presorted_unique(actors)
    )
    ev = obs.read()
    want = K.orset_fold_sparse_host(
        ORSet(), *cols, K.Vocab(range(E)), K.Vocab.presorted_unique(actors)
    )
    check(canonical_bytes(got) == canonical_bytes(want),
          "blockwise stream fold != host sparse fold")
    check(ev["spans"].get("stream.fold", [0])[0] >= 2,
          f"expected ≥2 stream chunks, spans: {ev['spans']}")
    if ctx.chip:
        check(ev["pallas_routed"] == 1
              and any("ablk" in k for k in ev["pallas_traced"]),
              f"stream chunks did not take Pallas: {ev['pallas_traced']}")
    return {"rows": int(live.sum()), "chunks": ev["spans"]["stream.fold"][0]}


def k_session_device_stream(ctx, kd, obs):
    """The fold session's DEVICE_STREAM regime — the donated Pallas step
    with ``retire_rm=False`` at the 4×-overshot member axis.  No Core
    ingest reaches it below HOST_PLANE_CELLS, so the regime is forced
    here, for this check only, the way the tier-1 tests force it."""
    from crdt_enc_tpu import ops as K
    from crdt_enc_tpu.models import ORSet, canonical_bytes
    from crdt_enc_tpu.parallel import session as S
    from crdt_enc_tpu.parallel.accel import TpuAccelerator

    E, R = kd.E, kd.R
    kind, member, actor, counter = kd.host_cols
    live = actor < R
    cols = [c[live] for c in (kind, member, actor, counter)]
    actors = actor_table(R)
    state = ORSet()
    host_cells, buffer_bytes = S.HOST_PLANE_CELLS, S.BUFFER_BYTES
    force = S.FORCE_PALLAS_STREAM
    S.HOST_PLANE_CELLS = 0
    if not ctx.chip:
        S.BUFFER_BYTES = 1 << 10
        S.FORCE_PALLAS_STREAM = "interpret"
    try:
        sess = S.OrsetFoldSession(TpuAccelerator(), state, actors)
        n = len(cols[0])
        step = -(-n // 4)
        for lo in range(0, n, step):
            sess.reduce_chunk((
                cols[0][lo:lo + step], cols[1][lo:lo + step],
                cols[2][lo:lo + step], cols[3][lo:lo + step],
                list(range(E)),
            ))
        mode, d_E = sess.mode, sess._d_E
        sess.finish()
    finally:
        S.HOST_PLANE_CELLS, S.BUFFER_BYTES = host_cells, buffer_bytes
        S.FORCE_PALLAS_STREAM = force
    ev = obs.read()
    check(mode == "device_stream", f"session mode {mode}")
    want = K.orset_fold_sparse_host(
        ORSet(), *cols, K.Vocab(range(E)), K.Vocab.presorted_unique(actors)
    )
    check(canonical_bytes(state) == canonical_bytes(want),
          "DEVICE_STREAM session != host sparse fold")
    check(any("ablk" in k for k in ev["pallas_traced"]),
          f"the session step did not take Pallas: {ev['pallas_traced']}")
    return {"member_axis": d_E, "rows_device": ev["rows_device"],
            "regime_forced": True}


def k_merge_pallas(ctx, kd, obs):
    import jax.numpy as jnp

    from crdt_enc_tpu import ops as K

    S, n = 4, kd.N // 4
    parts = []
    for i in range(S):
        cols = tuple(c[i * n:(i + 1) * n] for c in kd.cols)
        parts.append(kd.fold(kd.zeros if i % 2 else kd.p1,
                             kd.pad_rows(cols, kd.N)))
    clocks = jnp.stack([p[0] for p in parts])
    adds = jnp.stack([p[1] for p in parts])
    rms = jnp.stack([p[2] for p in parts])
    del parts
    tree = K.orset_merge_many(clocks, adds, rms, impl="tree")
    got = K.orset_merge_many(clocks, adds, rms, impl="pallas",
                             interpret=kd.interpret)
    check(same(got, tree), "orset_merge_many_pallas != tree merge")
    return {"stack": list(adds.shape)}


def k_lww_pallas(ctx, kd, obs):
    import jax

    from crdt_enc_tpu import ops as K
    from crdt_enc_tpu.ops.lww import ts_split
    from crdt_enc_tpu.ops.pallas_lww import (
        lww_fold_pallas, lww_limbs, lww_tile_cap,
    )

    rng = ctx.child_rng()
    N, keys, R = kd.N, ctx.sz.k_lww_keys, kd.R
    key = rng.integers(0, keys, N, dtype=np.int32)
    hi, lo = ts_split(rng.integers(1, 1 << 40, N, dtype=np.int64))
    actor = rng.integers(0, R, N, dtype=np.int32)
    n_values = 100  # single-byte msgpack domain: value rank == value
    value = rng.integers(0, n_values, N, dtype=np.int32)
    args = [jax.device_put(x) for x in (key, hi, lo, actor, value)]
    want = K.lww_fold(*args, num_keys=keys, num_values=n_values)
    got = lww_fold_pallas(
        *args, num_keys=keys, num_values=n_values,
        tile_cap=lww_tile_cap(key, keys),
        limbs=lww_limbs(hi, lo, actor, n_values), interpret=kd.interpret,
    )
    check(same(got, want), "lww_fold_pallas != lww_fold")
    return {"keys": keys, "rows": N}


def k_tenant_folds(ctx, kd, obs):
    """The serving mega-folds at one full bucket of the committed tenant
    shape: ``orset_fold_tenants`` against the solo kernel slot by slot,
    ``gcounter_fold_tenants`` against numpy; then the device cut of the
    window delta on the same bucket and at the planner's cells cap."""
    from crdt_enc_tpu import ops as K
    from crdt_enc_tpu.serve import bucketing
    from crdt_enc_tpu.serve.bucketing import _bucket

    s = ctx.sz
    T = s.k_slots
    Nb, Eb, Rb = (_bucket(s.sv_ops), _bucket(s.sv_members),
                  _bucket(s.sv_replicas))
    rng = ctx.child_rng()
    cols = [gen_ops(rng, Nb, s.sv_replicas, s.sv_members) for _ in range(T)]
    kind, member, actor, counter = (
        np.stack([c[i] for c in cols]) for i in range(4)
    )
    actor = np.where(actor >= s.sv_replicas, Rb, actor).astype(np.int32)
    clock0 = np.zeros((T, Rb), np.int32)
    plane0 = np.zeros((T, Eb, Rb), np.int32)
    got = K.orset_fold_tenants(
        clock0, plane0, plane0, kind, member, actor, counter,
        num_members=Eb, num_replicas=Rb,
    )
    got = [np.asarray(x) for x in got]
    for t in range(0, T, max(1, T // 32)):
        want = K.orset_fold(
            clock0[t], plane0[t], plane0[t], kind[t], member[t], actor[t],
            counter[t], num_members=Eb, num_replicas=Rb,
        )
        check(all(np.array_equal(g[t], np.asarray(w))
                  for g, w in zip(got, want)),
              f"orset_fold_tenants slot {t} != orset_fold")
    add_rows = kind == 0
    g_actor = np.where(add_rows, actor, Rb).astype(np.int32)
    g_clock = np.asarray(K.gcounter_fold_tenants(
        clock0, g_actor, counter, num_replicas=Rb
    ))
    want = np.zeros((T, Rb + 1), np.int32)
    rows = np.repeat(np.arange(T), Nb)
    np.maximum.at(want, (rows, g_actor.ravel()), counter.ravel())
    check(np.array_equal(g_clock, want[:, :Rb]),
          "gcounter_fold_tenants != numpy segment max")
    # the device cut of the window delta: the batched plane diff of the
    # same bucket, and one tenant's diff-row gather, against numpy
    diff_vs_numpy(clock0, plane0, plane0, *got)
    # …and at the largest planes the bucket planner admits per tenant
    # (past DEFAULT_CELLS_CAP a tenant spills to the solo path)
    Ec = Rc = int(bucketing.DEFAULT_CELLS_CAP ** 0.5) if ctx.chip else 16
    Tc = 4
    cols = [gen_ops(rng, 8 * Ec, Rc, Ec) for _ in range(2 * Tc)]
    kind, member, actor, counter = (
        np.stack([c[i] for c in cols]) for i in range(4)
    )
    zc, zp = np.zeros((Tc, Rc), np.int32), np.zeros((Tc, Ec, Rc), np.int32)
    base = K.orset_fold_tenants(
        zc, zp, zp, kind[:Tc], member[:Tc], actor[:Tc], counter[:Tc],
        num_members=_bucket(Ec), num_replicas=_bucket(Rc),
    )
    new = K.orset_fold_tenants(
        *base, kind[Tc:], member[Tc:], actor[Tc:], counter[Tc:],
        num_members=_bucket(Ec), num_replicas=_bucket(Rc),
    )
    n_diff = diff_vs_numpy(*(np.asarray(x) for x in base),
                           *(np.asarray(x) for x in new))
    return {"bucket": [T, Nb, Eb, Rb], "cap_bucket": [Tc, 8 * Ec, Ec, Rc],
            "cap_diff_cells": n_diff}


def diff_vs_numpy(cb, ab, rb, cn, an, rn) -> int:
    """``orset_plane_diff_tenants`` over a bucket, and
    ``orset_plane_diff_rows`` for its first tenant, against numpy; the
    bucket's batched gather against that tenant's.
    Returns the first tenant's diff-cell count."""
    from crdt_enc_tpu import ops as K
    from crdt_enc_tpu.serve.bucketing import _bucket

    code, counts = K.orset_plane_diff_tenants(cb, ab, rb, cn, an, rn)
    want = (
        (an > cb[:, None, :]).astype(np.int8) * K.DIFF_ADD
        | ((ab > 0) & (an == 0)).astype(np.int8) * K.DIFF_REMOVED
        | ((rn > rb) & (rn > cn[:, None, :])).astype(np.int8) * K.DIFF_HORIZON
    )
    check(np.array_equal(np.asarray(code), want)
          and np.array_equal(np.asarray(counts),
                             np.count_nonzero(want, axis=(1, 2))),
          "orset_plane_diff_tenants != numpy")
    n_diff = int(np.count_nonzero(want[0]))
    size = min(_bucket(n_diff), want[0].size)
    idx, c, v_ab, v_an, v_rn = (
        np.asarray(x) for x in K.orset_plane_diff_rows(
            code[0], ab[0], an[0], rn[0], size=size
        )
    )
    flat = np.flatnonzero(want[0].ravel())
    check(np.array_equal(idx[:n_diff], flat)
          and np.all(idx[n_diff:] == want[0].size)
          and np.array_equal(c[:n_diff], want[0].ravel()[flat])
          and np.array_equal(v_ab[:n_diff], ab[0].ravel()[flat])
          and np.array_equal(v_an[:n_diff], an[0].ravel()[flat])
          and np.array_equal(v_rn[:n_diff], rn[0].ravel()[flat]),
          "orset_plane_diff_rows != numpy")
    # the gather the service runs: every slot of the bucket at once
    batched = K.orset_plane_diff_rows_tenants(code, ab, an, rn, size=size)
    check(all(np.array_equal(np.asarray(b)[0], solo)
              for b, solo in zip(batched, (idx, c, v_ab, v_an, v_rn))),
          "orset_plane_diff_rows_tenants[0] != orset_plane_diff_rows")
    return n_diff


def k_coo(ctx, kd, obs):
    from crdt_enc_tpu import ops as K
    from crdt_enc_tpu.models import ORSet, canonical_bytes

    E, R = kd.E, kd.R
    actors = actor_table(R)

    def vocabs():
        return K.Vocab(range(E)), K.Vocab.presorted_unique(actors)

    clock, skey, smax, is_max = K.orset_fold_coo(
        kd.zeros[0], *kd.cols, num_members=E, num_replicas=R
    )
    got = K.orset_apply_coo(
        ORSet(), np.asarray(clock), np.asarray(skey), np.asarray(smax),
        np.asarray(is_max), *vocabs(),
    )
    kind, member, actor, counter = kd.host_cols
    live = actor < R
    want = K.orset_fold_sparse_host(
        ORSet(), kind[live], member[live], actor[live], counter[live],
        *vocabs(),
    )
    check(canonical_bytes(got) == canonical_bytes(want),
          "orset_fold_coo != host sparse fold")
    return {}


def k_mvreg(ctx, kd, obs):
    from crdt_enc_tpu import ops as K

    V, R = ctx.sz.k_mvreg
    rng = ctx.child_rng()
    clocks = rng.integers(0, 4, (V, R), dtype=np.int32)
    valid = rng.random(V) < 0.9
    got = np.asarray(K.mvreg_dominance_keep(clocks, valid))
    dominated = np.zeros(V, bool)
    for lo in range(0, V, 64):
        blk = clocks[lo:lo + 64]  # is row i of blk dominated by some j?
        ge = np.all(clocks[:, None, :] >= blk[None, :, :], axis=-1)
        gt = np.any(clocks[:, None, :] > blk[None, :, :], axis=-1)
        dominated[lo:lo + 64] = np.any(ge & gt & valid[:, None], axis=0)
    check(np.array_equal(got, valid & ~dominated),
          "mvreg_dominance_keep != numpy")
    return {"shape": [V, R]}


def map_history(rng, n_ops: int, n_actors: int, n_keys: int, n_members: int):
    """A causally consistent CrdtMap<orset> history: one oracle applies
    every op in order and is the per-op host reference; the ops are
    also split into per-actor streams (the only order a fold needs)."""
    from crdt_enc_tpu.models import CrdtMap
    from crdt_enc_tpu.models.orset import AddOp

    actors = actor_table(n_actors)
    oracle = CrdtMap(child=b"orset")
    streams: dict = {a: [] for a in actors}
    for _ in range(n_ops):
        actor = actors[int(rng.integers(n_actors))]
        key = f"k{int(rng.integers(n_keys))}"
        member = int(rng.integers(n_members))
        roll = rng.random()
        if roll < 0.05:
            op = oracle.rm_ctx(key)
            if op.ctx.is_empty():
                continue
        elif roll < 0.15:
            child = oracle.get(key)
            if child is None or not child.contains(member):
                continue
            op = oracle.update_ctx(
                actor, key, lambda c, dot, m=member: c.rm_ctx(m)
            )
        else:
            op = oracle.update_ctx(
                actor, key, lambda c, dot, m=member: AddOp(m, dot)
            )
        oracle.apply(op)
        streams[actor].append(op)
    return oracle, actors, [s for s in streams.values() if s]


def k_map_scatter(ctx, kd, obs):
    """``crdtmap_scatter_phase`` through the accelerator's map front end
    (native four-family decode → device scatter), against the per-op
    oracle."""
    from crdt_enc_tpu.models import CrdtMap, canonical_bytes
    from crdt_enc_tpu.parallel.accel import TpuAccelerator
    from crdt_enc_tpu.utils import codec

    oracle, actors, streams = map_history(
        ctx.child_rng(), ctx.sz.k_map_ops, 16, 256, 64
    )
    proto = CrdtMap(child=b"orset")
    payloads = [
        codec.pack([proto.op_to_obj(op) for op in s[i:i + 24]])
        for s in streams for i in range(0, len(s), 24)
    ]
    got = CrdtMap(child=b"orset")
    accepted = TpuAccelerator(map_fold_impl="device").fold_payloads(
        got, payloads, actors_hint=actors
    )
    check(accepted, "the map front end declined the batch")
    check(canonical_bytes(got) == canonical_bytes(oracle),
          "crdtmap device scatter != per-op oracle")
    return {"ops": sum(len(s) for s in streams)}


def k_shard_map(ctx, kd, obs):
    """The ``shard_map`` programs on a (1, 1) mesh of the chip: what a
    mesh deployment runs per device must lower here too.  The whole-batch
    sharded folds are called under ``jax.jit`` here: the product calls
    them eagerly, which dispatches the body one primitive at a time
    (~110 compiles a fold, minutes at this width on a cold cache) — that
    form runs in the ``mesh`` phase, where a mesh deployment exists."""
    import jax

    from crdt_enc_tpu import ops as K
    from crdt_enc_tpu.ops import pallas_fold as PF
    from crdt_enc_tpu.parallel import mesh as pmesh

    E, R = kd.E, kd.R
    mesh = pmesh.make_mesh((1, 1), devices=jax.devices()[:1])

    def jitted(fn, **kw):
        return jax.jit(lambda *a: fn(mesh, *a, **kw))

    got = jitted(pmesh.orset_fold_sharded)(*kd.p1, *kd.cols)
    check(same(got, kd.ref), "orset_fold_sharded[xla] != orset_fold")
    if PF.ablk_key_space_fits(E, R):
        got = jitted(
            pmesh.orset_fold_sharded, impl="pallas",
            tile_cap=PF.fold_cap(kd.host_cols[1], E), interpret=kd.interpret,
        )(*kd.p1, *kd.cols)
        check(same(got, kd.ref), "orset_fold_sharded[pallas] != orset_fold")
    got = jitted(pmesh.orset_merge_sharded)(*kd.p1, *kd.ref)
    check(same(got, K.orset_merge(*kd.p1, *kd.ref)),
          "orset_merge_sharded != orset_merge")
    step = pmesh.sharded_stream_fold_step(mesh)
    got = step(*pmesh.sharded_stream_planes(mesh, E, R), *kd.cols)
    # the step is a partial reduction (retire_rm=False): retiring it
    # against its own clock gives the whole fold from zero
    check(same((got[0], got[1], PF.orset_retire(got[0], got[2])),
               kd.fold(kd.zeros, kd.cols)),
          "sharded_stream_fold_step != orset_fold")

    # the small programs: counters, LWW, MVReg, the tenant-axis folds
    rng = ctx.child_rng()
    n, Rs = 4096, 64
    kind, member, actor, counter = gen_ops(rng, n, Rs, 256)
    sign = (kind != 0).astype(np.int8)
    zc = np.zeros(Rs, np.int32)
    got = jitted(pmesh.pncounter_fold_sharded)(zc, zc, sign, actor, counter)
    check(same(got, K.pncounter_fold(zc, zc, sign, actor, counter,
                                     num_replicas=Rs)),
          "pncounter_fold_sharded != pncounter_fold")
    got = jitted(pmesh.gcounter_fold_sharded)(zc, actor, counter)
    check(same(got, K.gcounter_fold(zc, actor, counter, num_replicas=Rs)),
          "gcounter_fold_sharded != gcounter_fold")
    lww = (member, counter, counter, np.minimum(actor, Rs - 1),
           kind.astype(np.int32))
    got = jitted(pmesh.lww_fold_sharded, num_keys=256)(*lww)
    check(same(got, K.lww_fold(*lww, num_keys=256)),
          "lww_fold_sharded != lww_fold")
    V, Rm = ctx.sz.k_mvreg
    clocks = rng.integers(0, 4, (V, Rm), dtype=np.int32)
    valid = rng.random(V) < 0.9
    check(same(jitted(pmesh.mvreg_keep_sharded)(clocks, valid),
               K.mvreg_dominance_keep(clocks, valid)),
          "mvreg_keep_sharded != mvreg_dominance_keep")
    T, Nb, Eb, Rb = min(ctx.sz.k_slots, 64), 64, 16, 8
    cols = [gen_ops(rng, Nb, 4, Eb) for _ in range(T)]
    tk, tm, ta, tc = (np.stack([c[i] for c in cols]) for i in range(4))
    ta = np.where(ta >= 4, Rb, ta).astype(np.int32)
    c0 = np.zeros((T, Rb), np.int32)
    p0 = np.zeros((T, Eb, Rb), np.int32)
    orset_step, gcounter_step = pmesh.tenant_fold_steps(mesh)
    got = orset_step(c0, p0, p0, tk, tm, ta, tc)
    want = K.orset_fold_tenants(c0, p0, p0, tk, tm, ta, tc,
                                num_members=Eb, num_replicas=Rb)
    check(same(got, want), "orset_fold_tenants_sharded != orset_fold_tenants")
    check(same(gcounter_step(c0, ta, tc),
               K.gcounter_fold_tenants(c0, ta, tc, num_replicas=Rb)),
          "gcounter_fold_tenants_sharded != gcounter_fold_tenants")
    check(same(pmesh.tenant_diff_step(mesh)(c0, p0, p0, *want),
               K.orset_plane_diff_tenants(c0, p0, p0, *want)),
          "tenant_plane_diff_sharded != orset_plane_diff_tenants")
    return {"mesh": [1, 1]}


# in order of how directly a one-chip deployment reaches the kernel; the
# back of the list is trimmed first when the deadline nears
KERNEL_CHECKS = (
    ("orset_fold_pallas[ablk]",
     lambda c, k, o: k_orset_pallas(c, k, o, "ablk")),
    ("orset_merge_many_pallas", k_merge_pallas),
    ("session_device_stream[pallas,retire_rm=False]",
     k_session_device_stream),
    ("orset_fold_stream[pallas,MAX_ROWS]", k_stream_pallas),
    ("tenant_folds+plane_diff", k_tenant_folds),
    ("crdtmap_scatter_phase", k_map_scatter),
    ("lww_fold_pallas", k_lww_pallas),
    ("orset_fold_pallas_fused", k_orset_fused),
    ("orset_fold_pallas[wide]",
     lambda c, k, o: k_orset_pallas(c, k, o, "wide")),
    ("orset_fold_coo", k_coo),
    ("mvreg_dominance_keep", k_mvreg),
    ("shard_map[1x1]", k_shard_map),
)


async def phase_kernels(ctx: Ctx) -> dict:
    obs = Obs(ctx)
    kd = KernelData(ctx)
    out: dict = {"shape": {"members": kd.E, "replicas": kd.R, "rows": kd.N},
                 "setup": obs.stop(), "checks": {}}
    failed = []
    for name, fn in KERNEL_CHECKS:
        if time.monotonic() > ctx.deadline:
            ctx.trimmed.append(name)
            out["checks"][name] = {"trimmed": "deadline"}
            continue
        obs = Obs(ctx)
        try:
            info = fn(ctx, kd, obs) or {}
            ok, err = True, None
        except Exception as e:  # reported below; the phase then fails
            ok, err, info = False, f"{type(e).__name__}: {e}"[:1500], {}
            traceback.print_exc()
            failed.append(name)
        ev = obs.stop()
        check(not any("interpreted" in k for k in ev["pallas_traced"])
              or not ctx.chip, f"{name}: a kernel ran interpreted")
        out["checks"][name] = {
            "ok": ok, **({"error": err} if err else {}), **info,
            "wall_s": ev["wall_s"], "jax_compiles": ev["jax_compiles"],
            "jax_cache_hits": ev["jax_cache_hits"],
            "pallas_traced": ev["pallas_traced"],
        }
        log(f"  kernel {name}: {'ok' if ok else 'FAILED'} "
            f"({ev['wall_s']}s, {ev['jax_compiles']} compiles)")
    if failed:
        out["failed"] = failed
        raise PhaseFailed(f"kernels failed: {failed}", out)
    return out


# --------------------------------------------------------------------- mesh


async def phase_mesh(ctx: Ctx) -> dict:
    """Four devices: the bulk_device deployment once more through a
    (2, 2) mesh accelerator, and one mesh-backed FoldService cycle.  Not
    a gate for this system (its north star is one chip)."""
    import jax

    from crdt_enc_tpu.backends import MemoryStorage
    from crdt_enc_tpu.core import Core
    from crdt_enc_tpu.parallel import TpuAccelerator
    from crdt_enc_tpu.parallel.mesh import make_mesh
    from crdt_enc_tpu.serve import FoldService

    n_dev = len(jax.devices())
    if n_dev < 4:
        return {"skipped": f"{n_dev} device" + ("s" if n_dev > 1 else "")}
    mesh = make_mesh((2, 2), devices=jax.devices()[:4])
    E = ctx.sz.dev_members
    n_ops, replicas = device_deployment(ctx)
    out = {"mesh": [2, 2]}
    out["bulk"] = await bulk_rounds(
        ctx, "mesh_bulk", n_ops, replicas, E, ctx.sz.ns_opf, [0.05],
        lambda: TpuAccelerator(mesh=mesh), assert_device=False,
    )
    for rd in out["bulk"]["rounds"]:
        check(rd["rows_device"] > 0 and rd["rows_host"] == 0,
              "mesh: a round did not fold on the devices")

    # planes really live on four devices
    from crdt_enc_tpu.parallel import mesh as pmesh

    planes = pmesh.sharded_stream_planes(mesh, E, replicas)
    spread = len(planes[1].sharding.device_set)
    check(spread == 4, f"mesh: planes live on {spread} devices")
    out["plane_devices"] = spread

    remotes, _, _ = await build_tenants(ctx, min(ctx.sz.sv_tenants, 64), 0)
    solo = [await solo_bytes(r) for r in remotes]
    served = [
        await Core.open(core_opts(MemoryStorage(r), TpuAccelerator()))
        for r in remotes
    ]
    svc = FoldService(served, mesh=mesh)
    obs = Obs(ctx)
    results = await svc.run_cycle()
    ev = obs.stop()
    svc.close()
    check(not [r.error for r in results if r.error], "mesh serve errors")
    check(all(state_bytes(c) == ref for c, ref in zip(served, solo)),
          "mesh serve: a tenant differs from its solo Core.compact")
    check(ev["spans"].get("serve.shard", [0])[0] > 0,
          "mesh serve: no serve.shard span")
    out["serve"] = ev
    out["byte_identical"] = True
    return out


# --------------------------------------------------------------------- main


class PhaseFailed(Exception):
    """A phase that failed after gathering evidence worth printing."""

    def __init__(self, msg: str, evidence: dict):
        super().__init__(msg)
        self.evidence = evidence


PHASE_FNS = {
    "bulk_northstar": phase_bulk_northstar,
    "bulk_device": phase_bulk_device,
    "merge_northstar": phase_merge_northstar,
    "serve": phase_serve,
    "reads": phase_reads,
    "kernels": phase_kernels,
    "mesh": phase_mesh,
}


async def run_phases(ctx: Ctx, selected: list) -> dict:
    phases: dict = {}
    for name in PHASES:
        if name not in selected:
            phases[name] = {"ok": False, "skipped": "not selected"}
            continue
        missing = [n for n in NEEDS.get(name, ()) if not phases[n]["ok"]]
        if missing:
            phases[name] = {"ok": False,
                            "skipped": f"needs {', '.join(missing)}"}
            continue
        log(f"phase {name} …")
        t0 = time.perf_counter()
        try:
            ev = await PHASE_FNS[name](ctx)
            ok = True
        except PhaseFailed as e:
            ev, ok = {**e.evidence, "error": str(e)}, False
        except Exception as e:  # the run goes on; the exit code does not
            traceback.print_exc()
            ev, ok = {"error": f"{type(e).__name__}: {e}"[:2000]}, False
        phases[name] = {"ok": ok, "wall_s": round(time.perf_counter() - t0, 1),
                        **ev}
        log(f"phase {name}: {'ok' if ok else 'FAILED'} "
            f"({phases[name]['wall_s']}s)")
    return phases


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="every op stream is generated from this seed")
    ap.add_argument("--tiny", action="store_true",
                    help="pre-flight the same code at toy sizes on any "
                    "backend; prints \"chip\": false and proves nothing "
                    "about the chip")
    ap.add_argument("--phases", default=",".join(PHASES),
                    help="comma-separated subset (a partial run can never "
                    "report ok)")
    ap.add_argument("--deadline", type=float, default=900.0,
                    help="seconds after which remaining kernel checks are "
                    "trimmed (entry-point phases never are)")
    args = ap.parse_args(argv)
    selected = [p.strip() for p in args.phases.split(",") if p.strip()]
    unknown = sorted(set(selected) - set(PHASES))
    if unknown:
        ap.error(f"unknown phases {unknown}; choose from {list(PHASES)}")

    t_start = time.perf_counter()
    if not args.tiny and first_env_platform() not in ("", "tpu"):
        log(f"chip_smoke: JAX_PLATFORMS={os.environ['JAX_PLATFORMS']!r} "
            "pins a non-TPU platform; refusing (use --tiny to pre-flight)")
        return 2
    # the one child (make), before JAX is touched
    native = build_native(rebuild=not args.tiny)

    import jax

    import crdt_enc_tpu

    dev = jax.devices()[0]
    chip = dev.platform == "tpu"
    if not chip and not args.tiny:
        log(f"chip_smoke: no TPU — jax.devices()[0] is {dev.platform} "
            f"({dev.device_kind}); refusing (use --tiny to pre-flight)")
        return 2
    cache_dir = crdt_enc_tpu.enable_compilation_cache()
    cache_before = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    guard = PallasGuard(allow_interpret=not chip)
    guard.install()
    from crdt_enc_tpu.obs import runtime as obs_runtime

    obs_runtime.track_recompiles()

    tmp = tempfile.mkdtemp(prefix="chip-smoke-")
    try:
        ctx = Ctx(args, TINY if args.tiny else FULL, chip, dev, guard, tmp)
        phases = asyncio.run(run_phases(ctx, selected))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)

    from importlib import metadata

    verdict = {
        "ok": all(phases[p]["ok"] for p in PHASES),
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices())},
    }
    report = {
        **verdict,
        "chip": chip,
        "versions": {
            "python": sys.version.split()[0], "jax": jax.__version__,
            "jaxlib": metadata.version("jaxlib"),
            "libtpu": metadata.version("libtpu"), "numpy": np.__version__,
        },
        "native": native,
        "seed": args.seed,
        "tiny": bool(args.tiny),
        "cache": {
            "dir": cache_dir,
            "from_env": bool(os.environ.get("JAX_COMPILATION_CACHE_DIR")),
            "entries_before": cache_before,
            "entries_after": len(os.listdir(cache_dir))
            if os.path.isdir(cache_dir) else 0,
            "hits": ctx.totals["jax_cache_hits"],
            "misses": ctx.totals["jax_cache_misses"],
            "compile_requests": ctx.totals["jax_compiles"],
        },
        "phases": phases,
        "phases_not_selected": [p for p in PHASES if p not in selected],
        "trimmed": ctx.trimmed,
        # single run, compilation included: smoke timings, not metrics
        "smoke_wall_s": round(time.perf_counter() - t_start, 1),
        "claim": None,
    }
    print(json.dumps(report))
    print(json.dumps(verdict), flush=True)
    return 0 if verdict["ok"] else 1


if __name__ == "__main__":
    raise SystemExit(main())
