"""North-star benchmark: OR-Set compaction fold, TPU vs single-core host.

Config #3 from BASELINE.md — 10k replicas / 1M add+remove ops — folded by
the jitted ``orset_fold`` kernel (the TPU replacement for the reference's
per-op host loop, crdt-enc/src/lib.rs:533-539).  The single-core baseline
is this repo's host-reference ORSet (identical semantics, verified
byte-identical on a subsample here and exhaustively in tests/).

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.
value = TPU ops merged/sec (post-compile); vs_baseline = speedup over the
single-core host fold (host rate measured on a capped subsample of the
same op stream — the host loop is O(n), so the per-op rate transfers).

Timing method: per-fold device time is measured as the MARGINAL cost of
one fold inside a K-chained ``lax.scan`` (time(K=1+CHAIN) − time(K=1))
/ CHAIN — the chain carries the state planes through each fold, so no
iteration can be elided, and the fixed per-dispatch cost (launch, sync,
result pull) cancels in the subtraction.  Single-dispatch wall-clock
(dispatch cost included) is logged to stderr too.  One process holds
the chip: JAX initializes in this process and no other is started.

Env knobs: BENCH_OPS (1_000_000), BENCH_REPLICAS (10_000),
BENCH_MEMBERS (4096), BENCH_HOST_OPS (100_000), BENCH_ITERS (3),
BENCH_CHAIN (20).
"""

from __future__ import annotations

import datetime
import json
import os
import sys
import time

import numpy as np


def log(*a):
    print(*a, file=sys.stderr, flush=True)


REPO_ROOT = os.path.dirname(os.path.abspath(__file__))
# Every successful run appends its full per-variant record here (committed),
# so a failure while the driver captures the final line cannot erase a
# round's perf evidence.
LOCAL_LOG = os.path.join(REPO_ROOT, "BENCH_LOCAL.jsonl")


def _append_local(rec: dict) -> None:
    try:
        line = json.dumps(rec)  # serialize before touching the file
        with open(LOCAL_LOG, "a") as f:
            f.write(line + "\n")
    except (OSError, TypeError, ValueError) as e:
        # never let bookkeeping kill a good run
        log(f"WARNING: could not append {LOCAL_LOG}: {e!r}")


def expects_tpu(smoke: bool) -> bool:
    """A TPU is expected unless the caller pinned a host-first platform
    list (JAX_PLATFORMS=cpu — tests, tools) or asked for ``--smoke``."""
    platforms = os.environ.get("JAX_PLATFORMS", "").lower()
    first_platform = platforms.split(",")[0].strip() if platforms else ""
    return first_platform != "cpu" and not smoke


def init_jax(want_tpu: bool):
    """Initialize JAX in THIS process — the one process that holds the
    chip — and return ``(jax, device)``.  When a TPU was expected and the
    default device is something else, print ONE diagnostic JSON line
    (value null, the platform found) and exit 3: a measurement path that
    finds no chip fails, it never falls back to the CPU."""
    import jax

    dev = jax.devices()[0]
    if want_tpu and dev.platform != "tpu":
        print(json.dumps({
            "metric": "orset_compaction_fold_ops_per_sec",
            "value": None,
            "unit": "ops/s",
            "vs_baseline": None,
            "error": "tpu_backend_unavailable",
            "platform": dev.platform,
            "device_kind": dev.device_kind,
        }), flush=True)
        raise SystemExit(3)
    return jax, dev


# Pinned host-baseline protocol (single source of truth — suite.py imports
# these): the 1-core per-op loop on this box shows ±30% run-to-run spread,
# so no speedup may rest on a single host sample.  Host baselines are the
# MEDIAN of BENCH_HOST_RUNS (default 5) with raw samples published; device
# times stay best-of (their marginal-chain timing is low-noise and
# interference is one-sided), an asymmetry stated in BASELINE.md — the
# recorded samples let anyone recompute a min-based ratio.
HOST_RUNS = int(os.environ.get("BENCH_HOST_RUNS", 5))


def host_median(run_once, n: int = 0):
    """Median-of-n host baseline.  ``run_once`` returns (seconds, payload);
    returns (median_seconds, sorted_samples, first_payload) — the payload
    (usually the folded host state) feeds byte-equality checks."""
    n = n or HOST_RUNS
    runs = [run_once() for _ in range(n)]
    times = sorted(t for t, _ in runs)
    return times[n // 2], times, runs[0][1]


def host_stats(times: list) -> dict:
    """The protocol's reporting fields for a result record."""
    med = times[len(times) // 2]
    return dict(
        host_samples_s=[round(t, 4) for t in times],
        host_spread_pct=round(100.0 * (times[-1] - times[0]) / med, 1),
    )


# Canonical pinned host baselines (VERDICT r4 weak items 1/6): same-run
# host rates swing 1.5× with machine weather even under the median-of-5
# protocol, so published ratios use ONE committed idle-box measurement
# per config (benchmarks/pinned_baselines.json, written by
# benchmarks/pin_baselines.py with raw samples).  Same-run rates are
# still recorded for drift detection.
PINNED_PATH = os.path.join(REPO_ROOT, "benchmarks", "pinned_baselines.json")


def load_pinned(config: str, shape: dict):
    """The pinned host record for ``config``, or None when absent or
    measured at a different workload shape (ratios across shapes would
    be meaningless — e.g. smoke runs)."""
    try:
        with open(PINNED_PATH) as f:
            pins = json.load(f)
    except (OSError, ValueError):
        return None
    rec = pins.get(config)
    if not rec or rec.get("shape") != shape:
        return None
    return rec


def pinned_ratio_fields(config: str, shape: dict, device_rate: float,
                        same_run_ratio: float) -> dict:
    """vs_baseline resolution: the pinned ratio when a matching pin
    exists (the stable denominator of record), same-run otherwise —
    with both always recorded explicitly."""
    rec = load_pinned(config, shape)
    out = {"vs_same_run_host": round(same_run_ratio, 2)}
    if rec:
        raw = device_rate / rec["host_rate"]
        out["vs_pinned_baseline"] = round(raw, 2)
        out["pinned_host_rate"] = rec["host_rate"]
        out["vs_baseline"] = out["vs_pinned_baseline"]
    else:
        raw = same_run_ratio
        out["vs_baseline"] = round(same_run_ratio, 2)
    # full-precision ratio for aggregation (geomeans must not
    # accumulate display rounding); underscore = not a record field
    out["_ratio_raw"] = raw
    return out


# Floor on a believable marginal: host-clock noise of one dispatch + sync,
# spread over the chain (single source of truth — benchmarks/suite.py
# imports it).  A marginal per-fold time below DISPATCH_NOISE_S / chain is
# noise, not device time.  Set conservatively on a link with ~100 ms
# dispatches; not re-derived on a directly attached chip.
DISPATCH_NOISE_S = 40e-3

# HBM peak by ``device_kind``: the roofline every marginal is checked
# against.  A fold whose bytes-touched lower bound divided by its measured
# marginal exceeds this rate is IMPOSSIBLE — the chain was hoisted/elided —
# and the measurement is rejected (the round-1 hoisting bug, mechanized).
# A kind that is not in the table is an error, not a default.
HBM_PEAK_GBPS = {
    # Google Cloud documentation, "TPU v5e": 16 GB of HBM2e at 819 GB/s
    "TPU v5 lite": 819.0,
}


def orset_fold_bytes_model(N: int, E: int, R: int) -> int:
    """Bytes ANY implementation of the dense ORSet fold must touch:
    read + write both (E, R) planes, the op columns, the clock."""
    return 2 * (2 * E * R * 4) + 13 * N + 2 * 4 * R


def roofline_pct(bytes_model: float, t_dev: float, dev):
    """% of ``dev``'s HBM peak implied by touching ``bytes_model`` bytes
    in ``t_dev`` seconds; None off-TPU (the table holds TPU peaks)."""
    if dev.platform != "tpu" or t_dev <= 0:
        return None
    peak = HBM_PEAK_GBPS.get(dev.device_kind)
    if peak is None:
        raise SystemExit(
            f"no HBM peak recorded for device kind {dev.device_kind!r}; "
            "add it to bench.HBM_PEAK_GBPS with its source"
        )
    return round(100.0 * bytes_model / t_dev / (peak * 1e9), 1)


def gen_columns(N: int, R: int, E: int, seed: int = 7):
    """Vectorized op-stream generator: per-actor sequential add dots,
    ~10% removes whose horizon is the actor's add-count so far."""
    rng = np.random.default_rng(seed)
    kind = (rng.random(N) < 0.10).astype(np.int8)
    member = rng.integers(0, E, N, dtype=np.int32)
    actor = rng.integers(0, R, N, dtype=np.int32)
    is_add = kind == 0
    # per-actor running count of adds, in row order (stable sort trick)
    order = np.argsort(actor, kind="stable")
    s_actor = actor[order]
    s_isadd = is_add[order].astype(np.int64)
    cum = np.cumsum(s_isadd)
    starts = np.searchsorted(s_actor, np.arange(R))
    base = np.where(starts < N, cum[np.minimum(starts, N - 1)] - s_isadd[np.minimum(starts, N - 1)], 0)
    within = cum - base[s_actor]
    counter = np.empty(N, np.int64)
    counter[order] = within
    counter = counter.astype(np.int32)
    # removes before the actor ever added → sentinel padding rows
    dead_rm = (~is_add) & (counter == 0)
    actor = np.where(dead_rm, R, actor)
    return kind, member, actor, counter


def host_fold(kind, member, actor, counter, R: int):
    """Single-core baseline: the host-reference ORSet applied op-by-op."""
    from crdt_enc_tpu.models import ORSet
    from crdt_enc_tpu.models.orset import AddOp, RmOp
    from crdt_enc_tpu.models.vclock import Dot, VClock

    state = ORSet()
    t0 = time.perf_counter()
    for k, m, a, c in zip(kind.tolist(), member.tolist(), actor.tolist(), counter.tolist()):
        if a >= R:
            continue
        if k == 0:
            state.apply(AddOp(m, Dot(a, c)))
        else:
            state.apply(RmOp(m, VClock({a: c})))
    return state, time.perf_counter() - t0


def _mesh_arg():
    """``--mesh dp=N[,mp=M]`` → ``(dp, mp)`` for the sharded-service
    arm of the multitenant sweep, else None (single-chip only)."""
    if "--mesh" not in sys.argv:
        return None
    i = sys.argv.index("--mesh")
    if i + 1 >= len(sys.argv):
        raise SystemExit("--mesh wants dp=N[,mp=M]")
    spec = sys.argv[i + 1]
    from crdt_enc_tpu.parallel.mesh import parse_mesh_spec

    try:
        return parse_mesh_spec(spec)
    except ValueError as e:
        raise SystemExit(f"--mesh: {e} (got {spec!r})")


def _tenants_arg(default: int) -> int:
    """``--tenants N`` (the multitenant sweep size), else ``default``."""
    if "--tenants" in sys.argv:
        i = sys.argv.index("--tenants")
        if i + 1 < len(sys.argv):
            try:
                n = int(sys.argv[i + 1])
            except ValueError:
                raise SystemExit(f"--tenants wants N, got {sys.argv[i + 1]!r}")
            if n > 0:
                return n
        raise SystemExit("--tenants wants a positive count")
    return default


def _nearest_rank(vals: list, frac: float):
    """THE nearest-rank quantile (ceil(frac·n)-th smallest) — one
    implementation for every bench family, so p99 can never silently
    mean different things across records."""
    import math

    s = sorted(vals)
    return s[min(len(s) - 1, max(0, math.ceil(frac * len(s)) - 1))]


def _quantiles_ms(samples_s: list) -> dict:
    """Exact nearest-rank p50/p99 of a latency sample set, in ms (the
    obs histograms are ±9% bucketed; the bench records exact values)."""
    return {
        "p50_ms": round(_nearest_rank(samples_s, 0.50) * 1e3, 2),
        "p99_ms": round(_nearest_rank(samples_s, 0.99) * 1e3, 2),
        "max_ms": round(max(samples_s) * 1e3, 2),
    }


def e2e_multitenant(smoke: bool):
    """ISSUE-7 acceptance: the multi-tenant fold service
    (crdt_enc_tpu/serve/) vs sequential per-tenant solo compacts.

    T tenants, each its own encrypted remote (memory backend, XChaCha
    AEAD, three-layer wire format) populated with a config-3-shaped op
    stream across a few replica actors.  The remotes are duplicated;
    one copy is compacted tenant-by-tenant through the normal solo
    ``Core.compact()`` loop, the other through ONE
    ``FoldService.run_cycle()`` — ragged-bucketed mega-folds, shared
    decode fan-out, per-tenant sealed snapshots.  Byte equality of
    every tenant's state is ASSERTED (the run refuses to record
    otherwise); the headline is *aggregate* ops/s and the p50/p99
    per-tenant completion latency (sequential tenants queue behind each
    other — that IS the serving model being replaced).  A second
    service cycle over a ~10% op tail measures the warm-tier path
    (plane reuse across cycles).  Appends the full record + obs
    snapshot to BENCH_LOCAL.jsonl (CPU records need BENCH_LOCAL_ALL=1,
    as for the other e2e benches).

    The default shape is the many-SMALL-tenants fleet the serving layer
    exists for: 384 ops per tenant flushed as 24-op files (16 pending
    files), where a solo compact's cost is machinery-bound (the
    pipelined ingest engages at 16 files and costs ~7-8ms/tenant on
    this box almost independent of op count) — exactly the per-tenant
    overhead the batch amortizes.  Bigger tenants shift the balance
    toward shared work (decrypt/decode/fold) that both sides pay;
    sweep BENCH_MT_OPS/BENCH_MT_OPF to map the landscape.

    Env knobs: BENCH_MT_TENANTS (256; --tenants N overrides),
    BENCH_MT_OPS (384 per tenant), BENCH_MT_REPLICAS (4 per tenant),
    BENCH_MT_MEMBERS (64 per tenant), BENCH_MT_OPF (24 ops/file),
    BENCH_MT_TAIL_PCT (10), BENCH_MT_ITERS (3 — best-of passes per
    side, each on fresh fleet copies).

    ``--mesh dp=N[,mp=M]`` adds the SHARDED arm (ISSUE 14): the same
    fleet through a mesh-backed FoldService — tenant lanes over dp,
    member planes over mp — byte-compared against both other arms and
    recorded under its own metric/config with per-arm steady-state
    compile counts.  On a CPU box the virtual mesh
    (XLA_FLAGS=--xla_force_host_platform_device_count=8) exercises the
    exact SPMD programs a pod would run, but all "devices" share the
    host's cores — the CPU record is a correctness + compile-count
    witness, not a speedup claim (that awaits TPU hardware, the PR-7
    caveat verbatim).
    """
    import asyncio
    import copy

    T = _tenants_arg(int(os.environ.get(
        "BENCH_MT_TENANTS", 16 if smoke else 256)))
    N = int(os.environ.get("BENCH_MT_OPS", 96 if smoke else 384))
    R = int(os.environ.get("BENCH_MT_REPLICAS", 4))
    E = int(os.environ.get("BENCH_MT_MEMBERS", 64))
    OPF = int(os.environ.get("BENCH_MT_OPF", 24))
    TAIL_PCT = float(os.environ.get("BENCH_MT_TAIL_PCT", 10.0))

    jax, dev = init_jax(expects_tpu(smoke))

    import crdt_enc_tpu
    from benchmarks.suite import actor_bytes_table
    from crdt_enc_tpu.backends import (
        MemoryRemote, MemoryStorage, PlainKeyCryptor, XChaChaCryptor,
    )
    from crdt_enc_tpu.core import Core, OpenOptions, orset_adapter
    from crdt_enc_tpu.models import canonical_bytes
    from crdt_enc_tpu.parallel import TpuAccelerator
    from crdt_enc_tpu.serve import FoldService
    from crdt_enc_tpu.utils import trace
    from crdt_enc_tpu.utils.versions import DEFAULT_DATA_VERSION_1

    crdt_enc_tpu.enable_compilation_cache()

    # --mesh dp=N[,mp=M]: a third arm runs the SAME fleet through a
    # mesh-backed FoldService (tenant lanes over dp, member planes over
    # mp — parallel/mesh.py), byte-compared against both other arms
    mesh_shape = _mesh_arg()
    mesh = None
    if mesh_shape is not None:
        dp_m, mp_m = mesh_shape
        if len(jax.devices()) < dp_m * mp_m:
            raise SystemExit(
                f"--mesh dp={dp_m},mp={mp_m} needs {dp_m * mp_m} devices, "
                f"found {len(jax.devices())}; on a CPU box set XLA_FLAGS="
                "--xla_force_host_platform_device_count=8 (the virtual "
                "mesh the tier-1 differential tests use)"
            )
        from crdt_enc_tpu.parallel.mesh import make_mesh

        mesh = make_mesh((dp_m, mp_m))

    def opts(storage):
        return OpenOptions(
            storage=storage,
            cryptor=XChaChaCryptor(),
            key_cryptor=PlainKeyCryptor(),
            adapter=orset_adapter(),
            supported_data_versions=(DEFAULT_DATA_VERSION_1,),
            current_data_version=DEFAULT_DATA_VERSION_1,
            create=True,
            accelerator=TpuAccelerator(),
        )

    actors = actor_bytes_table(R)

    def tenant_files(seed: int):
        """One tenant's op-file payload stream (config-3-shaped adds +
        removes over R actors, OPF ops/file, dense versions per actor)."""
        kind, member, actor, counter = gen_columns(N, R, E, seed=seed)
        live = actor < R
        order = np.argsort(actor[live], kind="stable")
        k_l, m_l = kind[live][order], member[live][order]
        a_l, c_l = actor[live][order], counter[live][order]
        i, n = 0, len(k_l)
        versions: dict = {}
        out = []
        while i < n:
            j = min(i + OPF, n)
            j = i + int(np.searchsorted(a_l[i:j], a_l[i], side="right"))
            ab = actors[int(a_l[i])]
            ops = []
            for t in range(i, j):
                if k_l[t] == 0:
                    ops.append([0, int(m_l[t]), [ab, int(c_l[t])]])
                else:
                    ops.append([1, int(m_l[t]), {ab: int(c_l[t])}])
            v = versions.get(ab, 0) + 1
            versions[ab] = v
            out.append((ab, v, ops))
            i = j
        return out

    async def build():
        """Per tenant: a pristine remote of sealed head files, plus the
        tail PRE-SEALED as raw blobs (so the warm-cycle phase can drop
        them into any fleet copy's storage)."""
        remotes, tails, total_ops = [], [], 0
        for t in range(T):
            files = tenant_files(seed=100 + t)
            n_tail = max(1, int(len(files) * TAIL_PCT / 100.0))
            head, tail = files[:-n_tail], files[-n_tail:]
            remote = MemoryRemote()
            writer = await Core.open(opts(MemoryStorage(remote)))
            for ab, v, ops in head:
                blob = await writer._seal(ops)
                await writer.storage.store_ops(ab, v, blob)
            total_ops += sum(len(ops) for _, _, ops in head)
            remotes.append(remote)
            tails.append([
                (ab, v, await writer._seal(ops), len(ops))
                for ab, v, ops in tail
            ])
        return remotes, tails, total_ops

    remotes, tails, total_ops = asyncio.run(build())
    log(
        f"e2e_multitenant: device {dev.platform}; {T} tenants, "
        f"{total_ops} head ops total, R={R}/tenant E={E}/tenant"
    )

    ITERS = max(1, int(os.environ.get("BENCH_MT_ITERS", 1 if smoke else 3)))

    async def measure():
        # ---- warmup: compile exclusion, the repo's standard protocol.
        # A throwaway copy of the fleet runs one full service cycle (the
        # mega-fold compiles per size class, T included) and a few solo
        # compacts (the session fold's buckets) — the measured passes
        # below are steady-state on both sides.
        warm_fleet = [
            await Core.open(opts(MemoryStorage(copy.deepcopy(r))))
            for r in remotes
        ]
        await FoldService(warm_fleet).run_cycle()
        for r in remotes[: min(8, T)]:
            c = await Core.open(opts(MemoryStorage(copy.deepcopy(r))))
            await c.compact()
        del warm_fleet
        if mesh is not None:  # compile the sharded bucket classes too
            mesh_warm = [
                await Core.open(opts(MemoryStorage(copy.deepcopy(r))))
                for r in remotes
            ]
            await FoldService(mesh_warm, mesh=mesh).run_cycle()
            del mesh_warm

        # ---- best-of-ITERS passes (each on fresh fleet copies, byte
        # equality asserted on EVERY pair — the e2e-streaming protocol:
        # wall minima, with the full sample sets recorded)
        t_seq = t_serve = t_shard = float("inf")
        seq_lat = serve_lat = shard_lat = None
        obs_seq = obs_serve = obs_shard = None
        equal = True
        paths: dict = {}
        shard_paths: dict = {}
        service = None
        for _ in range(ITERS):
            solo_cores = [
                await Core.open(opts(MemoryStorage(copy.deepcopy(r))))
                for r in remotes
            ]
            served_cores = [
                await Core.open(opts(MemoryStorage(copy.deepcopy(r))))
                for r in remotes
            ]
            # sequential baseline: tenant-by-tenant solo compacts; a
            # tenant's completion latency includes its queue wait — that
            # is the one-remote-at-a-time serving model being replaced
            trace.reset()
            lat = []
            t0 = time.perf_counter()
            for c in solo_cores:
                await c.compact()
                lat.append(time.perf_counter() - t0)
            t = time.perf_counter() - t0
            if t < t_seq:
                t_seq, seq_lat, obs_seq = t, lat, trace.snapshot()

            # one service cycle over the whole fleet
            svc = FoldService(served_cores)
            trace.reset()
            t0 = time.perf_counter()
            results = await svc.run_cycle()
            t = time.perf_counter() - t0
            errors = [
                (i, r.error) for i, r in enumerate(results) if r.error
            ]
            assert not errors, f"service tenant errors: {errors[:3]}"
            equal = equal and all(
                a.with_state(canonical_bytes)
                == b.with_state(canonical_bytes)
                for a, b in zip(solo_cores, served_cores)
            )
            if t < t_serve:
                t_serve = t
                serve_lat = [r.latency_s for r in results]
                obs_serve = trace.snapshot()
                paths = {}
                for r in results:
                    paths[r.path] = paths.get(r.path, 0) + 1
                service = svc
                warm_fleet_cores = served_cores

            if mesh is not None:
                # sharded arm: one mesh-backed cycle on a third fresh
                # fleet copy, byte-compared against the solo arm (the
                # record REFUSES on any per-tenant divergence)
                shard_cores = [
                    await Core.open(opts(MemoryStorage(copy.deepcopy(r))))
                    for r in remotes
                ]
                svc_m = FoldService(shard_cores, mesh=mesh)
                trace.reset()
                t0 = time.perf_counter()
                results_m = await svc_m.run_cycle()
                t = time.perf_counter() - t0
                errors = [
                    (i, r.error) for i, r in enumerate(results_m) if r.error
                ]
                assert not errors, f"sharded tenant errors: {errors[:3]}"
                equal = equal and all(
                    a.with_state(canonical_bytes)
                    == b.with_state(canonical_bytes)
                    for a, b in zip(solo_cores, shard_cores)
                )
                if t < t_shard:
                    t_shard = t
                    shard_lat = [r.latency_s for r in results_m]
                    obs_shard = trace.snapshot()
                    shard_paths = {}
                    for r in results_m:
                        shard_paths[r.path] = shard_paths.get(r.path, 0) + 1

        # ---- warm cycle: the TAIL_PCT op tail lands on the best pass's
        # fleet, the service folds it through the warm plane tier
        n_tail_ops = 0
        for core, tail in zip(warm_fleet_cores, tails):
            for ab, v, blob, n_ops in tail:
                await core.storage.store_ops(ab, v, blob)
                n_tail_ops += n_ops
        trace.reset()
        t0 = time.perf_counter()
        results2 = await service.run_cycle()
        t_warm = time.perf_counter() - t0
        snap2 = trace.snapshot()
        warm_hits = snap2["counters"].get("serve_warm_hits", 0)
        assert all(r.error is None for r in results2)

        return (
            t_seq, t_serve, seq_lat, serve_lat, equal, paths, obs_seq,
            obs_serve, t_warm, n_tail_ops, warm_hits,
            t_shard, shard_lat, obs_shard, shard_paths,
        )

    (t_seq, t_serve, seq_lat, serve_lat, equal, paths, obs_seq, obs_serve,
     t_warm, n_tail_ops, warm_hits,
     t_shard, shard_lat, obs_shard, shard_paths) = asyncio.run(measure())

    agg_serve = total_ops / t_serve
    agg_seq = total_ops / t_seq
    speedup = t_seq / t_serve
    # critical-path attribution of the best service cycle (obs
    # .attribution; the serve twin of the streaming gap report)
    from crdt_enc_tpu.obs import attribution

    gap_report = attribution.attribute_cycle(
        obs_serve, pipeline="serve", wall_s=t_serve, ops=total_ops
    )
    log(
        f"sequential {t_seq:.2f}s ({agg_seq:,.0f} ops/s) vs service "
        f"{t_serve:.2f}s ({agg_serve:,.0f} ops/s) → {speedup:.2f}x; "
        f"byte-identical: {equal}; paths: {paths}"
    )
    log(
        f"warm cycle: {n_tail_ops} tail ops in {t_warm:.2f}s "
        f"({n_tail_ops / t_warm:,.0f} ops/s, warm hits {warm_hits}/{T})"
    )
    compiles = lambda snap: int(
        (snap or {}).get("counters", {}).get("jax_compiles", 0)
    )
    sharded_rec = None
    if mesh is not None:
        agg_shard = total_ops / t_shard
        log(
            f"sharded (dp={dp_m},mp={mp_m}): {t_shard:.2f}s "
            f"({agg_shard:,.0f} ops/s) = {t_serve / t_shard:.2f}x vs "
            f"single-chip service; paths: {shard_paths}; steady-state "
            f"compiles seq/service/sharded = {compiles(obs_seq)}/"
            f"{compiles(obs_serve)}/{compiles(obs_shard)}"
        )
        sharded_rec = {
            "mesh": {"dp": dp_m, "mp": mp_m},
            "cycle_s": round(t_shard, 4),
            "agg_ops_per_sec": round(agg_shard, 1),
            "vs_single_chip": round(t_serve / t_shard, 2),
            "tenant_latency": _quantiles_ms(shard_lat),
            "fold_paths": shard_paths,
        }
    result = {
        "metric": "orset_multitenant_agg_ops_per_sec",
        "config": f"multitenant_{T}t",
        "value": round(agg_serve, 1),
        "unit": "ops/s",
        "vs_baseline": round(speedup, 2),
        "sequential_agg_ops_per_sec": round(agg_seq, 1),
        "service_cycle_s": round(t_serve, 4),
        "sequential_s": round(t_seq, 4),
        "tenant_latency": _quantiles_ms(serve_lat),
        "sequential_tenant_latency": _quantiles_ms(seq_lat),
        "fold_paths": paths,
        "gap_report": gap_report,
        "warm_cycle": {
            "tail_ops": n_tail_ops,
            "cycle_s": round(t_warm, 4),
            "ops_per_sec": round(n_tail_ops / t_warm, 1),
            "warm_hits": warm_hits,
        },
        "byte_identical": bool(equal),
        "backend": dev.platform,
        # steady-state XLA compiles in the measured passes (post-warmup
        # — zero is the bucket quantization contract, mesh included)
        "compile_counts": {
            "sequential": compiles(obs_seq),
            "service": compiles(obs_serve),
            **({"sharded": compiles(obs_shard)} if mesh is not None else {}),
        },
    }
    if sharded_rec is not None:
        # its own metric/config so the trend gate tracks the sharded
        # trajectory separately from the single-chip one
        result["metric"] = "orset_multitenant_sharded_agg_ops_per_sec"
        result["config"] = f"multitenant_{T}t_mesh{dp_m}x{mp_m}"
        result["value"] = sharded_rec["agg_ops_per_sec"]
        result["sharded"] = sharded_rec
        result["single_chip_agg_ops_per_sec"] = round(agg_serve, 1)
    print(json.dumps(result))
    if not equal:
        log("FAILED: per-tenant states diverged — refusing to record")
        raise SystemExit(1)
    if os.environ.get("BENCH_LOCAL_DISABLE") == "1":
        return
    if dev.platform != "tpu" and os.environ.get("BENCH_LOCAL_ALL") != "1":
        return
    _append_local({
        **result,
        "ts": datetime.datetime.now(datetime.timezone.utc).isoformat(
            timespec="seconds"),
        "device_kind": dev.device_kind,
        # with 2 cores the decode fan-out and the consumer share
        # silicon; the dispatch-amortization win is what remains —
        # large-tenant-count and TPU numbers await hardware (same
        # caveat as the PR-1/PR-3 records)
        "host_cpus": os.cpu_count(),
        "shape": {"tenants": T, "ops_per_tenant": N, "replicas": R,
                  "members": E, "ops_per_file": OPF,
                  "total_ops": total_ops, "iters": ITERS},
        "obs": obs_serve,
        "obs_sequential": obs_seq,
        **({"obs_sharded": obs_shard} if mesh is not None else {}),
    })


def _daemon_fleet_shape(smoke: bool):
    """The --e2e-daemon workload shape (env knobs BENCH_DMN_*): T
    single-remote tenants of N config-3-shaped ops in OPF-op encrypted
    files — the many-small-tenants fleet of docs/multitenant.md, plus a
    churn script (joiners, leavers, bursters) sized off T."""
    T = _tenants_arg(int(os.environ.get(
        "BENCH_DMN_TENANTS", 16 if smoke else 256)))
    N = int(os.environ.get("BENCH_DMN_OPS", 96 if smoke else 256))
    R = int(os.environ.get("BENCH_DMN_REPLICAS", 4))
    E = int(os.environ.get("BENCH_DMN_MEMBERS", 64))
    OPF = int(os.environ.get("BENCH_DMN_OPF", 24))
    CYCLES = int(os.environ.get("BENCH_DMN_CYCLES", 4 if smoke else 6))
    return T, N, R, E, OPF, CYCLES


def _daemon_tenant_files(N, R, E, OPF, seed):
    """One tenant's (actor, version, ops) file stream — the
    e2e-multitenant generator shape, shared by the daemon bench and its
    pinned host baseline."""
    from benchmarks.suite import actor_bytes_table

    actors = actor_bytes_table(R)
    kind, member, actor, counter = gen_columns(N, R, E, seed=seed)
    live = actor < R
    order = np.argsort(actor[live], kind="stable")
    k_l, m_l = kind[live][order], member[live][order]
    a_l, c_l = actor[live][order], counter[live][order]
    i, n = 0, len(k_l)
    versions: dict = {}
    out = []
    while i < n:
        j = min(i + OPF, n)
        j = i + int(np.searchsorted(a_l[i:j], a_l[i], side="right"))
        ab = actors[int(a_l[i])]
        ops = []
        for t in range(i, j):
            if k_l[t] == 0:
                ops.append([0, int(m_l[t]), [ab, int(c_l[t])]])
            else:
                ops.append([1, int(m_l[t]), {ab: int(c_l[t])}])
        v = versions.get(ab, 0) + 1
        versions[ab] = v
        out.append((ab, v, ops))
        i = j
    return out


async def _daemon_build_remotes(opts_fn, n_tenants, N, R, E, OPF, seed0):
    """``n_tenants`` pristine encrypted remotes + per-tenant head op
    counts; burst tails are returned PRE-SEALED so churn can drop them
    into a live tenant's storage mid-run."""
    import math

    from crdt_enc_tpu.backends import MemoryRemote, MemoryStorage
    from crdt_enc_tpu.core import Core

    remotes, bursts, head_ops = [], [], []
    for t in range(n_tenants):
        files = _daemon_tenant_files(N, R, E, OPF, seed=seed0 + t)
        n_tail = max(1, math.ceil(len(files) * 0.1))
        head, tail = files[:-n_tail], files[-n_tail:]
        remote = MemoryRemote()
        writer = await Core.open(opts_fn(MemoryStorage(remote)))
        for ab, v, ops in head:
            blob = await writer._seal(ops)
            await writer.storage.store_ops(ab, v, blob)
        head_ops.append(sum(len(ops) for _, _, ops in head))
        bursts.append([
            (ab, v, await writer._seal(ops), len(ops))
            for ab, v, ops in tail
        ])
        remotes.append(remote)
    return remotes, bursts, head_ops


def _daemon_opts_fn():
    from crdt_enc_tpu.backends import (
        PlainKeyCryptor, XChaChaCryptor,
    )
    from crdt_enc_tpu.core import OpenOptions, orset_adapter
    from crdt_enc_tpu.parallel import TpuAccelerator
    from crdt_enc_tpu.utils.versions import DEFAULT_DATA_VERSION_1

    def opts(storage):
        return OpenOptions(
            storage=storage,
            cryptor=XChaChaCryptor(),
            key_cryptor=PlainKeyCryptor(),
            adapter=orset_adapter(),
            supported_data_versions=(DEFAULT_DATA_VERSION_1,),
            current_data_version=DEFAULT_DATA_VERSION_1,
            create=True,
            accelerator=TpuAccelerator(),
        )
    return opts


def e2e_daemon_host(runs: int = 0):
    """Pinned host baseline for the daemon family (pin_baselines.py
    config 6): sequential solo ``Core.compact()`` over the default
    daemon fleet's HEAD shape (no churn — the pin is the steady-state
    denominator), median-of-N on fresh fleet copies per pass."""
    import asyncio
    import copy

    T, N, R, E, OPF, _ = _daemon_fleet_shape(smoke=False)
    opts = _daemon_opts_fn()

    async def build():
        return await _daemon_build_remotes(opts, T, N, R, E, OPF, 500)

    remotes, _bursts, head_ops = asyncio.run(build())
    total_ops = sum(head_ops)

    def run_once():
        async def one():
            from crdt_enc_tpu.backends import MemoryStorage
            from crdt_enc_tpu.core import Core

            cores = [
                await Core.open(opts(MemoryStorage(copy.deepcopy(r))))
                for r in remotes
            ]
            t0 = time.perf_counter()
            for c in cores:
                await c.compact()
            return time.perf_counter() - t0

        return asyncio.run(one()), None

    median_s, times, _ = host_median(run_once, runs)
    return {
        "config": f"daemon_{T}t",
        "host_rate": total_ops / median_s,
        "n_ops": total_ops,
        "shape": {"tenants": T, "ops_per_tenant": N, "replicas": R,
                  "members": E, "ops_per_file": OPF},
        "median_s": median_s,
        **host_stats(times),
    }


def e2e_daemon(smoke: bool):
    """ISSUE-12 acceptance: the always-on FleetDaemon under churn.

    T encrypted single-remote tenants are admitted into a
    :class:`~crdt_enc_tpu.serve.FleetDaemon` (staleness-driven
    scheduling: compaction is backlog-triggered, quiet tenants are
    stat-polled) and the daemon runs CYCLES supervised cycles while the
    fleet churns — T/8 tenants JOIN mid-run (admission), T/4 receive a
    ~10% op-tail BURST, T/8 are EVICTED with a final checkpoint.  The
    record is aggregate ops/s over the cycle loop, p99 freshness lag
    (the ``watermark_lag`` samples the scheduler itself consumed), and
    p99 per-tenant seal latency.  After the drain, every tenant's
    remote — including evicted ones — is refolded by a fresh solo
    ``Core.compact()`` on a copy; ANY byte divergence refuses the
    record (the standard e2e evidence guard).

    Env knobs: BENCH_DMN_TENANTS (256; --tenants N overrides),
    BENCH_DMN_OPS (256/tenant), BENCH_DMN_REPLICAS (4),
    BENCH_DMN_MEMBERS (64), BENCH_DMN_OPF (24), BENCH_DMN_CYCLES (6).
    """
    import asyncio
    import copy

    T, N, R, E, OPF, CYCLES = _daemon_fleet_shape(smoke)
    # T=1 evicts nobody: the burst target and the evictee would be the
    # same tenant, and an evictee with a fresh unfolded burst is stale
    # by construction — not a divergence the guard should compare
    JOIN, BURST = max(1, T // 8), max(1, T // 4)
    LEAVE = 0 if T == 1 else max(1, T // 8)

    jax, dev = init_jax(expects_tpu(smoke))

    import crdt_enc_tpu
    from crdt_enc_tpu.backends import MemoryStorage
    from crdt_enc_tpu.core import Core
    from crdt_enc_tpu.models import canonical_bytes
    from crdt_enc_tpu.serve import DaemonConfig, FleetDaemon, ServeConfig
    from crdt_enc_tpu.utils import trace

    crdt_enc_tpu.enable_compilation_cache()
    opts = _daemon_opts_fn()

    async def scenario():
        remotes, bursts, head_ops = await _daemon_build_remotes(
            opts, T + JOIN, N, R, E, OPF, 500
        )
        log(
            f"e2e_daemon: device {dev.platform}; {T} tenants "
            f"(+{JOIN} join, -{LEAVE} evict, {BURST} burst), "
            f"{sum(head_ops[:T])} head ops"
        )
        cores = [
            await Core.open(opts(MemoryStorage(r))) for r in remotes[:T]
        ]
        cfg = DaemonConfig(
            interval_s=0.0, batch=T + JOIN,
            min_backlog_files=1, max_idle_cycles=CYCLES + 10,
            # admission sized to the fleet the scenario intends to
            # admit: the default warm-budget gate at the pre-
            # observation 1MiB/tenant estimate would refuse joiners
            # past 256 tenants (the operator's knob, set like one)
            admission_bytes=(T + JOIN + 1) << 20,
            serve=ServeConfig(seal_empty=False),
        )
        daemon = FleetDaemon(cores, cfg, seed=7)

        # warmup compiles on a throwaway copy fleet (repo protocol)
        warm = [
            await Core.open(opts(MemoryStorage(copy.deepcopy(r))))
            for r in remotes[: min(8, T)]
        ]
        await daemon.service.run_cycle(warm)
        del warm

        total_ops = sum(head_ops[:T])
        seal_lat: list = []
        fresh_lag: list = []
        churn = {"joined": 0, "evicted": 0, "burst_tenants": 0,
                 "burst_ops": 0}
        trace.reset()
        t0 = time.perf_counter()
        for c in range(CYCLES):
            if c == 1:  # joiners: admission while running
                for j in range(JOIN):
                    core = await Core.open(
                        opts(MemoryStorage(remotes[T + j]))
                    )
                    await daemon.admit(core)
                    cores.append(core)
                    total_ops += head_ops[T + j]
                    churn["joined"] += 1
            if c == 2:  # burst: op tails land on live tenants
                for t in range(BURST):
                    # distinct targets past the future evictees (wraps
                    # only at T=1, where BURST is also 1)
                    idx = (LEAVE + t) % T
                    core = cores[idx]
                    for ab, v, blob, n_ops in bursts[idx]:
                        await core.storage.store_ops(ab, v, blob)
                        total_ops += n_ops
                        churn["burst_ops"] += n_ops
                    churn["burst_tenants"] += 1
            if c == 3:  # leavers: eviction with a final checkpoint
                for t in range(LEAVE):
                    await daemon.evict(f"t{t}")
                    churn["evicted"] += 1
            report = await daemon.run_cycle()
            for res in report["results"].values():
                if res.get("latency_s") is not None:
                    seal_lat.append(res["latency_s"])
            for tid in daemon.tenant_ids:
                status = daemon.entry(tid).status()
                if status is not None:
                    fresh_lag.append(
                        float(status["divergence"]["watermark_lag"])
                    )
        wall = time.perf_counter() - t0
        obs = trace.snapshot()
        await daemon.drain()

        # the no-divergence guard: EVERY tenant's remote (evicted ones
        # included) must refold solo to the daemon tenant's final state
        diverged = []
        for i, core in enumerate(cores):
            solo = await Core.open(
                opts(MemoryStorage(copy.deepcopy(remotes[i])))
            )
            await solo.compact()
            if solo.with_state(canonical_bytes) != core.with_state(
                canonical_bytes
            ):
                diverged.append(i)
        return (
            wall, total_ops, seal_lat, fresh_lag, churn, obs, diverged,
            daemon.health(),
        )

    (wall, total_ops, seal_lat, fresh_lag, churn, obs, diverged,
     health) = asyncio.run(scenario())

    rate = total_ops / wall
    # freshness lag is in VERSIONS (not a latency) — exact nearest-rank
    q = _nearest_rank

    result = {
        "metric": "daemon_e2e_agg_ops_per_sec",
        "config": f"daemon_{T}t",
        "value": round(rate, 1),
        "unit": "ops/s",
        "cycles": CYCLES,
        "wall_s": round(wall, 4),
        "total_ops": total_ops,
        "seal_latency": _quantiles_ms(seal_lat) if seal_lat else {},
        "freshness_lag_versions": {
            "p50": q(fresh_lag, 0.50), "p99": q(fresh_lag, 0.99),
            "max": max(fresh_lag),
        } if fresh_lag else {},
        "churn": churn,
        "daemon": {k: health[k] for k in
                   ("cycles", "tenants", "quarantined", "degraded")},
        "byte_identical": not diverged,
        "backend": dev.platform,
    }
    pin_shape = {"tenants": T, "ops_per_tenant": N, "replicas": R,
                 "members": E, "ops_per_file": OPF}
    pin = load_pinned(f"daemon_{T}t", pin_shape)
    if pin:
        result["vs_pinned_baseline"] = round(rate / pin["host_rate"], 2)
        result["pinned_host_rate"] = pin["host_rate"]
        result["vs_baseline"] = result["vs_pinned_baseline"]
    print(json.dumps(result))
    if diverged:
        log(
            f"FAILED: tenants {diverged[:5]} diverged from solo "
            "compact() — refusing to record"
        )
        raise SystemExit(1)
    if os.environ.get("BENCH_LOCAL_DISABLE") == "1":
        return
    if dev.platform != "tpu" and os.environ.get("BENCH_LOCAL_ALL") != "1":
        return
    _append_local({
        **result,
        "ts": datetime.datetime.now(datetime.timezone.utc).isoformat(
            timespec="seconds"),
        "device_kind": dev.device_kind,
        "host_cpus": os.cpu_count(),
        "shape": {**pin_shape, "cycles": CYCLES, "join": JOIN,
                  "leave": LEAVE, "burst": BURST},
        "obs": obs,
    })


def e2e_idle_cycle(smoke: bool):
    """ISSUE-16 acceptance: the O(tail) steady state.

    A T-tenant fleet (the daemon shape, BENCH_DMN_* knobs) is folded
    once to seed warm planes + delta bases, then served at three ACTIVE
    FRACTIONS — 100%, 10%, 1% of tenants receiving one new op file per
    cycle — under two arms:

    * ``continuation`` — the default :class:`ServeConfig`: warm planes
      are the fold accumulator, quiet tenants no-op via the seal
      signature (``serve_noop_cycles``), active tenants seal deltas by
      device cut (``delta_device_cuts``).
    * ``full_refold`` — ``ServeConfig(warm=False, noop_skip=False)``:
      the O(state) steady state every cycle (quiet tenants re-seal
      their whole snapshot; actives refold from the stored base).

    The record's headline value is the 1%-active cycle-wall ratio
    full_refold/continuation (≥10x is the ISSUE-16 bar).  Per-fraction
    rows carry wall/cycle, per-quiet-tenant cost (an all-quiet cycle /
    T), ``jax_compiles`` and ``h2d_bytes`` deltas over the measured
    window, ``serve_noop_cycles`` and ``delta_base_bytes``.  After the
    run EVERY tenant in BOTH arms must byte-match a fresh solo
    ``Core.compact()`` of its remote — divergence refuses the record
    (the standard e2e evidence guard)."""
    import asyncio
    import copy

    T, N, R, E, OPF, _ = _daemon_fleet_shape(smoke)
    FRACTIONS = (1.0, 0.1, 0.01)
    CYC = int(os.environ.get("BENCH_IDLE_CYCLES", 2 if smoke else 3))

    jax, dev = init_jax(expects_tpu(smoke))

    import crdt_enc_tpu
    from crdt_enc_tpu.backends import (
        MemoryRemote, MemoryStorage, PlainKeyCryptor, XChaChaCryptor,
    )
    from crdt_enc_tpu.core import Core, OpenOptions, orset_adapter
    from crdt_enc_tpu.models import canonical_bytes
    from crdt_enc_tpu.obs import runtime as obs_runtime
    from crdt_enc_tpu.parallel import TpuAccelerator
    from crdt_enc_tpu.serve import FoldService, ServeConfig
    from crdt_enc_tpu.utils import trace
    from crdt_enc_tpu.utils.versions import DEFAULT_DATA_VERSION_1

    crdt_enc_tpu.enable_compilation_cache()
    obs_runtime.track_recompiles()

    def opts(storage):
        return OpenOptions(
            storage=storage,
            cryptor=XChaChaCryptor(),
            key_cryptor=PlainKeyCryptor(),
            adapter=orset_adapter(),
            supported_data_versions=(DEFAULT_DATA_VERSION_1,),
            current_data_version=DEFAULT_DATA_VERSION_1,
            create=True,
            accelerator=TpuAccelerator(),
            delta=True,
        )

    # one drip file per active tenant per cycle: CYC measured cycles
    # plus one untimed warmup cycle per fraction (the warmup settles
    # the fraction's compile classes so the measured window is
    # steady-state, not compile wall)
    need_drip = len(FRACTIONS) * (CYC + 1)

    async def build():
        from benchmarks.suite import actor_bytes_table

        # the drip writer is its own actor (one PAST the R plane
        # replicas) so drip file versions never collide with head files
        drip_ab = actor_bytes_table(R + 1)[R]
        remotes, drips = [], []
        for t in range(T):
            files = _daemon_tenant_files(N, R, E, OPF, seed=900 + t)
            # take files from the end until the tail holds at least one
            # op per drip file (but always keep one head file)
            n_tail, got = 0, 0
            while got < need_drip and n_tail < len(files) - 1:
                n_tail += 1
                got += len(files[-n_tail][2])
            n_tail = max(n_tail, min(len(files) - 1, len(files) // 3))
            head, tail = files[:-n_tail], files[-n_tail:]
            # re-chunk the tail's ops into exactly need_drip files (the
            # op payload carries its own dot, so the drip writer can
            # relay any actor's ops)
            tail_ops = [op for _ab, _v, ops in tail for op in ops]
            if len(tail_ops) < need_drip or not head:
                raise SystemExit(
                    f"shape too small: tenant {t} has {len(tail_ops)} "
                    f"tail ops for a {need_drip}-file drip schedule"
                )
            step = len(tail_ops) / need_drip
            cuts = [round(i * step) for i in range(need_drip + 1)]
            remote = MemoryRemote()
            writer = await Core.open(opts(MemoryStorage(remote)))
            for ab, v, ops in head:
                blob = await writer._seal(ops)
                await writer.storage.store_ops(ab, v, blob)
            drips.append([
                (drip_ab, i + 1,
                 await writer._seal(tail_ops[cuts[i]:cuts[i + 1]]))
                for i in range(need_drip)
            ])
            remotes.append(remote)
        return remotes, drips

    remotes, drips = asyncio.run(build())
    log(
        f"e2e_idle_cycle: device {dev.platform}; {T} tenants, "
        f"{CYC} cycles/fraction, fractions {FRACTIONS}"
    )

    async def run_arm(arm: str):
        cfg = (ServeConfig() if arm == "continuation"
               else ServeConfig(warm=False, noop_skip=False))
        arm_remotes = [copy.deepcopy(r) for r in remotes]
        cores = [
            await Core.open(opts(MemoryStorage(r))) for r in arm_remotes
        ]
        service = FoldService(cores, cfg)
        # seed cycle: folds every head, seals, stamps continuations
        await service.run_cycle()
        await service.run_cycle()  # settle compiles on the quiet shape

        drip_pos = [0] * T
        fraction_rows = []
        obs_1pct = None
        for frac in FRACTIONS:
            n_active = max(1, round(T * frac))

            async def drip_actives():
                for t in range(n_active):
                    ab, v, blob = drips[t][drip_pos[t]]
                    drip_pos[t] += 1
                    await cores[t].storage.store_ops(ab, v, blob)

            # untimed warmup at THIS fraction's bucket shape
            await drip_actives()
            await service.run_cycle()
            trace.reset()
            walls = []
            for _c in range(CYC):
                await drip_actives()
                t0 = time.perf_counter()
                await service.run_cycle()
                walls.append(time.perf_counter() - t0)
            counters = trace.snapshot()["counters"]
            gauges = trace.snapshot()["gauges"]
            # all-quiet cycle: the pure per-quiet-tenant marginal
            tq = time.perf_counter()
            await service.run_cycle()
            quiet_wall = time.perf_counter() - tq
            row = {
                "active_fraction": frac,
                "active_tenants": n_active,
                "wall_per_cycle_s": round(sorted(walls)[len(walls) // 2], 5),
                "quiet_cycle_s": round(quiet_wall, 5),
                "per_quiet_tenant_us": round(quiet_wall / T * 1e6, 2),
                "jax_compiles": counters.get("jax_compiles", 0),
                "h2d_bytes": counters.get("h2d_bytes", 0),
                "serve_noop_cycles": counters.get("serve_noop_cycles", 0),
                "delta_device_cuts": counters.get("delta_device_cuts", 0),
                "delta_base_bytes": gauges.get("delta_base_bytes"),
            }
            if frac == 0.01 and arm == "continuation":
                obs_1pct = trace.snapshot()
            fraction_rows.append(row)

        # fold any unused drip files so both arms end byte-comparable,
        # then guard: every tenant must match a fresh solo compact
        for t in range(T):
            while drip_pos[t] < need_drip:
                ab, v, blob = drips[t][drip_pos[t]]
                drip_pos[t] += 1
                await cores[t].storage.store_ops(ab, v, blob)
        await service.run_cycle()
        diverged = []
        for i, core in enumerate(cores):
            solo = await Core.open(
                opts(MemoryStorage(copy.deepcopy(arm_remotes[i])))
            )
            await solo.compact()
            if solo.with_state(canonical_bytes) != core.with_state(
                canonical_bytes
            ):
                diverged.append(i)
        service.close()
        return fraction_rows, diverged, obs_1pct

    async def scenario():
        cont, div_c, obs_1pct = await run_arm("continuation")
        full, div_f, _ = await run_arm("full_refold")
        return cont, full, div_c + div_f, obs_1pct

    cont, full, diverged, obs_1pct = asyncio.run(scenario())

    by_frac = {r["active_fraction"]: r for r in full}
    speedup = round(
        by_frac[0.01]["wall_per_cycle_s"]
        / max(cont[-1]["wall_per_cycle_s"], 1e-9), 2
    )
    result = {
        "metric": "idle_cycle_speedup",
        "config": f"idle_{T}t",
        "value": speedup,
        "unit": "x_at_1pct_active",
        "continuation": cont,
        "full_refold": full,
        "byte_identical": not diverged,
        "backend": dev.platform,
    }
    print(json.dumps(result))
    if diverged:
        log(
            f"FAILED: tenants {sorted(set(diverged))[:5]} diverged from "
            "solo compact() — refusing to record"
        )
        raise SystemExit(1)
    if os.environ.get("BENCH_LOCAL_DISABLE") == "1":
        return
    if dev.platform != "tpu" and os.environ.get("BENCH_LOCAL_ALL") != "1":
        return
    _append_local({
        **result,
        "ts": datetime.datetime.now(datetime.timezone.utc).isoformat(
            timespec="seconds"),
        "device_kind": dev.device_kind,
        "host_cpus": os.cpu_count(),
        "shape": {"tenants": T, "ops_per_tenant": N, "replicas": R,
                  "members": E, "ops_per_file": OPF, "cycles": CYC},
        "obs": obs_1pct,
    })


def e2e_warm_open(smoke: bool):
    """ISSUE-4 acceptance: cold open vs checkpointed (warm) open of a
    config-5-shaped un-compacted remote with a 1% op tail.

    A real FS remote is populated with N three-layer-sealed op files
    across R actors; replica A reads it all once and seals a local fold
    checkpoint.  Then a 1% tail of new op files lands and we measure,
    on the SAME remote:

    * **cold** — a fresh replica (no local state) opens and refolds the
      entire history through the streaming ingest, and
    * **warm** — replica A reopens: the checkpoint restores the
      materialized state + cursor and only the tail is decrypted,
      decoded and folded.

    Byte equality of the two resulting states is asserted, both obs
    snapshots are recorded, and a two-round-compact h2d_bytes sample
    proves the device-resident plane reuse (round 2 re-uploads no
    full-state planes).  Appends the record to BENCH_LOCAL.jsonl
    (BENCH_LOCAL_ALL=1 to record CPU runs).

    Env knobs: BENCH_WARM_OPS (1_000_000), BENCH_WARM_REPLICAS (10_000),
    BENCH_WARM_MEMBERS (1024), BENCH_WARM_OPF (48, ops per file),
    BENCH_WARM_TAIL_PCT (1.0).
    """
    import asyncio
    import tempfile

    N = int(os.environ.get("BENCH_WARM_OPS", 20_000 if smoke else 1_000_000))
    R = int(os.environ.get("BENCH_WARM_REPLICAS", 200 if smoke else 10_000))
    E = int(os.environ.get("BENCH_WARM_MEMBERS", 128 if smoke else 1024))
    OPF = int(os.environ.get("BENCH_WARM_OPF", 48))
    TAIL_PCT = float(os.environ.get("BENCH_WARM_TAIL_PCT", 1.0))

    jax, dev = init_jax(expects_tpu(smoke))

    import crdt_enc_tpu
    from benchmarks.suite import actor_bytes_table
    from crdt_enc_tpu.backends import (
        FsStorage, PlainKeyCryptor, XChaChaCryptor,
    )
    from crdt_enc_tpu.core import Core, OpenOptions, orset_adapter
    from crdt_enc_tpu.models import canonical_bytes
    from crdt_enc_tpu.parallel import TpuAccelerator
    from crdt_enc_tpu.utils import trace
    from crdt_enc_tpu.utils.versions import DEFAULT_DATA_VERSION_1

    crdt_enc_tpu.enable_compilation_cache()

    def opts(storage, create):
        return OpenOptions(
            storage=storage,
            cryptor=XChaChaCryptor(),
            key_cryptor=PlainKeyCryptor(),
            adapter=orset_adapter(),
            supported_data_versions=(DEFAULT_DATA_VERSION_1,),
            current_data_version=DEFAULT_DATA_VERSION_1,
            create=create,
            accelerator=TpuAccelerator(),
        )

    # ---- per-actor op files from the config-3/5 column generator,
    # sealed in the core's real three-layer wire format
    kind, member, actor, counter = gen_columns(N, R, E, seed=11)
    actors = actor_bytes_table(R)
    live = actor < R
    order = np.argsort(actor[live], kind="stable")
    k_l = kind[live][order]
    m_l = member[live][order]
    a_l = actor[live][order]
    c_l = counter[live][order]

    def file_payloads():
        """Yield (actor_bytes, version, ops_obj) per file, versions dense
        from 1 per actor."""
        i, n = 0, len(k_l)
        versions: dict = {}
        while i < n:
            j = min(i + OPF, n)
            j = i + int(np.searchsorted(a_l[i:j], a_l[i], side="right"))
            ab = actors[int(a_l[i])]
            ops = []
            for t in range(i, j):
                if k_l[t] == 0:
                    ops.append([0, int(m_l[t]), [ab, int(c_l[t])]])
                else:
                    ops.append([1, int(m_l[t]), {ab: int(c_l[t])}])
            v = versions.get(ab, 0) + 1
            versions[ab] = v
            yield ab, v, ops
            i = j

    files = list(file_payloads())
    # the TAIL_PCT% op tail: final files (one per contributing actor)
    # held back until the checkpoint is sealed, accumulating actors
    # until the tail holds ~TAIL_PCT% of all ops
    total_ops = sum(len(ops) for _, _, ops in files)
    last_file_idx = {}
    for idx, (ab, v, _) in enumerate(files):
        last_file_idx[ab] = idx
    target_ops = max(1, int(total_ops * TAIL_PCT / 100.0))
    tail_idx: set = set()
    n_tail_ops = 0
    for ab in actors:
        idx = last_file_idx.get(ab)
        if idx is None:
            continue
        tail_idx.add(idx)
        n_tail_ops += len(files[idx][2])
        if n_tail_ops >= target_ops:
            break
    prefix = [f for i, f in enumerate(files) if i not in tail_idx]
    tail = [f for i, f in enumerate(files) if i in tail_idx]

    tmp = tempfile.mkdtemp(prefix="crdt-warm-open-")
    remote = os.path.join(tmp, "remote")
    log(
        f"e2e_warm_open: device {dev.platform}; {len(files)} files "
        f"({len(tail)} tail), {total_ops} ops ({n_tail_ops} tail), "
        f"R={R} E={E} remote={remote}"
    )

    async def build_and_measure():
        storage_a = FsStorage(os.path.join(tmp, "localA"), remote)
        core_a = await Core.open(opts(storage_a, create=True))

        async def store_files(batch):
            sem = asyncio.Semaphore(64)

            async def one(ab, v, ops):
                async with sem:
                    blob = await core_a._seal(ops)
                    await core_a.storage.store_ops(ab, v, blob)

            await asyncio.gather(*(one(*f) for f in batch))

        t0 = time.perf_counter()
        CHUNK = 2048  # bound in-flight seal buffers
        for i in range(0, len(prefix), CHUNK):
            await store_files(prefix[i : i + CHUNK])
        t_build = time.perf_counter() - t0
        log(f"remote built: {len(prefix)} files in {t_build:.1f}s")

        # replica A folds the full history once and seals its resume point
        t0 = time.perf_counter()
        await core_a.read_remote()
        t_first = time.perf_counter() - t0
        trace.reset()
        await core_a.save_checkpoint()
        ck_bytes = trace.snapshot()["counters"].get("checkpoint_bytes", 0)
        log(f"first full fold: {t_first:.2f}s; checkpoint sealed "
            f"({ck_bytes} bytes)")

        await store_files(tail)

        # ---- cold: a fresh replica refolds EVERYTHING
        trace.reset()
        t0 = time.perf_counter()
        core_cold = await Core.open(
            opts(FsStorage(os.path.join(tmp, "localB"), remote), create=True)
        )
        await core_cold.read_remote()
        t_cold = time.perf_counter() - t0
        obs_cold = trace.snapshot()

        # ---- warm: replica A reopens from its checkpoint + 1% tail
        trace.reset()
        t0 = time.perf_counter()
        core_warm = await Core.open(
            opts(FsStorage(os.path.join(tmp, "localA"), remote), create=False)
        )
        warm_hit = core_warm.opened_from_checkpoint
        await core_warm.read_remote()
        t_warm = time.perf_counter() - t0
        obs_warm = trace.snapshot()

        equal = core_cold.with_state(canonical_bytes) == core_warm.with_state(
            canonical_bytes
        )
        return (
            t_build, t_first, t_cold, t_warm, warm_hit, equal,
            obs_cold, obs_warm, core_warm.checkpoint_fallback_reason,
            ck_bytes,
        )

    (t_build, t_first, t_cold, t_warm, warm_hit, equal, obs_cold, obs_warm,
     fallback, ck_bytes) = asyncio.run(build_and_measure())

    # ---- device-resident plane reuse: two-round compact h2d sample
    plane_proof = asyncio.run(_plane_reuse_rounds())

    speedup = t_cold / t_warm
    log(
        f"cold open {t_cold:.2f}s vs warm open {t_warm:.3f}s → "
        f"{speedup:.1f}x (warm hit: {warm_hit}, equal: {equal})"
    )
    result = {
        "metric": "orset_e2e_warm_open_speedup",
        "config": f"warm_open_{N}ops_{R}r_{TAIL_PCT:g}pct_tail",
        "value": round(speedup, 2),
        "unit": "x",
        "cold_open_s": round(t_cold, 4),
        "warm_open_s": round(t_warm, 4),
        "first_fold_s": round(t_first, 4),
        "build_s": round(t_build, 1),
        "opened_from_checkpoint": bool(warm_hit),
        "checkpoint_fallback_reason": fallback,
        "byte_identical": bool(equal),
        "checkpoint_bytes": ck_bytes,
        "plane_reuse": {
            k: v for k, v in plane_proof.items() if k != "obs"
        },
        "backend": dev.platform,
    }
    print(json.dumps(result))
    # the bench exists to prove these — a run that silently fell back to
    # a cold open or diverged must fail loudly (diagnostic JSON above is
    # printed, but nothing lands in the evidence file)
    if not (warm_hit and equal):
        log(
            f"FAILED: warm_hit={warm_hit} (fallback: {fallback}) "
            f"byte_identical={equal} — refusing to record"
        )
        raise SystemExit(1)
    if os.environ.get("BENCH_LOCAL_DISABLE") != "1" and (
        dev.platform == "tpu" or os.environ.get("BENCH_LOCAL_ALL") == "1"
    ):
        _append_local({
            **result,
            "ts": datetime.datetime.now(datetime.timezone.utc).isoformat(
                timespec="seconds"),
            "device_kind": dev.device_kind,
            "host_cpus": os.cpu_count(),
            "shape": {"N": N, "R": R, "E": E, "ops_per_file": OPF,
                      "files": len(files), "tail_files": len(tail),
                      "tail_ops": n_tail_ops, "total_ops": total_ops},
            "obs_cold": obs_cold,
            "obs_warm": obs_warm,
            "obs_plane_reuse": plane_proof.get("obs"),
        })


async def _plane_reuse_rounds():
    """Two compaction rounds in one process on a small dense-regime
    workload: round 1 uploads the full state planes (counted in
    h2d_bytes at issue), round 2 hits the accelerator's device-resident
    plane cache — ~zero full-state re-upload (ISSUE-4 acceptance)."""
    from crdt_enc_tpu.backends import (
        IdentityCryptor, MemoryRemote, MemoryStorage, PlainKeyCryptor,
    )
    from crdt_enc_tpu.core import Core, OpenOptions, orset_adapter
    from crdt_enc_tpu.parallel import TpuAccelerator
    from crdt_enc_tpu.utils import trace
    from crdt_enc_tpu.utils.versions import DEFAULT_DATA_VERSION_1

    def opts(storage, accel=None):
        return OpenOptions(
            storage=storage, cryptor=IdentityCryptor(),
            key_cryptor=PlainKeyCryptor(), adapter=orset_adapter(),
            supported_data_versions=(DEFAULT_DATA_VERSION_1,),
            current_data_version=DEFAULT_DATA_VERSION_1, create=True,
            accelerator=accel if accel is not None else TpuAccelerator(),
        )

    remote = MemoryRemote()
    reader = await Core.open(
        opts(MemoryStorage(remote), TpuAccelerator(min_device_batch=1))
    )
    writer = await Core.open(opts(MemoryStorage(remote)))

    async def write(n, tag):
        for i in range(n):
            await writer.apply_ops([writer.with_state(
                lambda s: s.add_ctx(writer.actor_id, b"%s-%d" % (tag, i))
            )])

    rounds = {}
    for rd in (1, 2):
        await write(60, b"r%d" % rd)
        trace.reset()
        await reader.compact()
        snap = trace.snapshot()
        rounds[rd] = {
            "h2d_bytes": snap["counters"].get("h2d_bytes", 0),
            "obs": snap,
        }
    return {
        "round1_h2d_bytes": rounds[1]["h2d_bytes"],
        "round2_h2d_bytes": rounds[2]["h2d_bytes"],
        "round2_full_state_reupload": rounds[2]["h2d_bytes"] > 0,
        "obs": rounds[2]["obs"],
    }


def _flag_int(flag: str, default: int) -> int:
    """``--flag N`` from argv, else ``default`` (the --tenants pattern,
    shared by the sim sweep's --replicas/--steps)."""
    if flag in sys.argv:
        i = sys.argv.index(flag)
        if i + 1 < len(sys.argv):
            try:
                n = int(sys.argv[i + 1])
            except ValueError:
                raise SystemExit(f"{flag} wants N, got {sys.argv[i + 1]!r}")
            if n > 0:
                return n
        raise SystemExit(f"{flag} wants a positive count")
    return default


class _CountingStorage:
    """Wrap a Storage, counting every remote payload byte the core
    reads (states + op files + deltas) — the e2e-delta bench's
    measurement instrument.  Everything else forwards untouched."""

    def __init__(self, inner):
        self._inner = inner
        self.bytes_read = 0
        self.files_read = 0

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def _count(self, loaded):
        for item in loaded:
            self.bytes_read += len(item[-1])
            self.files_read += 1
        return loaded

    async def load_states(self, names):
        return self._count(await self._inner.load_states(names))

    async def load_ops(self, wanted):
        return self._count(await self._inner.load_ops(wanted))

    async def load_deltas(self, wanted):
        return self._count(await self._inner.load_deltas(wanted))

    async def iter_op_chunks(self, wanted, max_bytes=None):
        kw = {} if max_bytes is None else {"max_bytes": max_bytes}
        async for chunk in self._inner.iter_op_chunks(wanted, **kw):
            yield self._count(chunk)


def e2e_delta(smoke: bool):
    """ISSUE-10 acceptance: remote bytes read by an INCREMENTAL consumer
    — delta-chain path vs full-snapshot path — on the same remote.

    One producer builds a real three-layer-sealed FS remote, folds it,
    and compacts (snapshot + delta per round, docs/delta.md).  Two
    consumers track it: A with delta-state replication on (folds
    ``known-base + delta chain``), B with it off (re-downloads the full
    snapshot every round).  Each round lands a ~BENCH_DELTA_TAIL_PCT%
    op tail before the producer compacts again.  The record is the
    bytes-read reduction A/B plus wall times; byte-identity of all
    three states is ASSERTED and the run refuses to record otherwise
    (the divergence guard every e2e bench carries).

    Env knobs: BENCH_DELTA_OPS (200_000), BENCH_DELTA_REPLICAS (2_000),
    BENCH_DELTA_MEMBERS (512), BENCH_DELTA_OPF (48, ops/file),
    BENCH_DELTA_ROUNDS (5), BENCH_DELTA_TAIL_PCT (1.0).
    """
    import asyncio
    import tempfile

    N = int(os.environ.get("BENCH_DELTA_OPS", 6_000 if smoke else 200_000))
    R = int(os.environ.get("BENCH_DELTA_REPLICAS", 60 if smoke else 2_000))
    E = int(os.environ.get("BENCH_DELTA_MEMBERS", 64 if smoke else 512))
    OPF = int(os.environ.get("BENCH_DELTA_OPF", 48))
    ROUNDS = int(os.environ.get("BENCH_DELTA_ROUNDS", 2 if smoke else 5))
    TAIL_PCT = float(os.environ.get("BENCH_DELTA_TAIL_PCT", 1.0))

    jax, dev = init_jax(expects_tpu(smoke))

    from benchmarks.suite import actor_bytes_table
    from crdt_enc_tpu.backends import (
        FsStorage, PlainKeyCryptor, XChaChaCryptor,
    )
    from crdt_enc_tpu.core import Core, OpenOptions, orset_adapter
    from crdt_enc_tpu.models import canonical_bytes
    from crdt_enc_tpu.parallel import TpuAccelerator
    from crdt_enc_tpu.utils import trace
    from crdt_enc_tpu.utils.versions import DEFAULT_DATA_VERSION_1

    def opts(storage, create, delta=True):
        return OpenOptions(
            storage=storage,
            cryptor=XChaChaCryptor(),
            key_cryptor=PlainKeyCryptor(),
            adapter=orset_adapter(),
            supported_data_versions=(DEFAULT_DATA_VERSION_1,),
            current_data_version=DEFAULT_DATA_VERSION_1,
            create=create,
            accelerator=TpuAccelerator(),
            delta=delta,
        )

    kind, member, actor, counter = gen_columns(N, R, E, seed=23)
    actors = actor_bytes_table(R)
    live = actor < R
    order = np.argsort(actor[live], kind="stable")
    k_l = kind[live][order]
    m_l = member[live][order]
    a_l = actor[live][order]
    c_l = counter[live][order]

    def file_payloads():
        i, n = 0, len(k_l)
        versions: dict = {}
        while i < n:
            j = min(i + OPF, n)
            j = i + int(np.searchsorted(a_l[i:j], a_l[i], side="right"))
            ab = actors[int(a_l[i])]
            ops = []
            for t in range(i, j):
                if k_l[t] == 0:
                    ops.append([0, int(m_l[t]), [ab, int(c_l[t])]])
                else:
                    ops.append([1, int(m_l[t]), {ab: int(c_l[t])}])
            v = versions.get(ab, 0) + 1
            versions[ab] = v
            yield ab, v, ops
            i = j
        # the per-round incremental tails continue each actor's log
        while True:
            target = max(1, int(N * TAIL_PCT / 100.0))
            got = 0
            batch = []
            for ab in actors:
                if got >= target:
                    break
                v = versions.get(ab, 0) + 1
                versions[ab] = v
                ops = [
                    [0, int((v * 37 + t) % E), [ab, 1_000_000 + v * OPF + t]]
                    for t in range(min(OPF, target - got))
                ]
                got += len(ops)
                batch.append((ab, v, ops))
            yield ("round", batch)

    gen = file_payloads()
    prefix = []
    for item in gen:
        if isinstance(item[0], str):
            break
        prefix.append(item)

    tmp = tempfile.mkdtemp(prefix="crdt-e2e-delta-")
    remote = os.path.join(tmp, "remote")
    log(
        f"e2e_delta: device {dev.platform}; {len(prefix)} files, {N} ops, "
        f"R={R} E={E} rounds={ROUNDS} tail={TAIL_PCT:g}% remote={remote}"
    )

    async def build_and_measure():
        producer = await Core.open(
            opts(FsStorage(os.path.join(tmp, "localP"), remote), create=True)
        )

        async def store_files(batch):
            sem = asyncio.Semaphore(64)

            async def one(ab, v, ops):
                async with sem:
                    blob = await producer._seal(ops)
                    await producer.storage.store_ops(ab, v, blob)

            await asyncio.gather(*(one(*f) for f in batch))

        t0 = time.perf_counter()
        CHUNK = 2048
        for i in range(0, len(prefix), CHUNK):
            await store_files(prefix[i : i + CHUNK])
        t_build = time.perf_counter() - t0
        await producer.compact()
        log(f"remote built + first compact: {t_build:.1f}s")

        storage_a = _CountingStorage(
            FsStorage(os.path.join(tmp, "localA"), remote)
        )
        storage_b = _CountingStorage(
            FsStorage(os.path.join(tmp, "localB"), remote)
        )
        c_delta = await Core.open(opts(storage_a, create=True))
        c_snap = await Core.open(opts(storage_b, create=True, delta=False))
        await c_delta.read_remote()
        await c_snap.read_remote()
        # the incremental phase is the measurement window
        storage_a.bytes_read = storage_a.files_read = 0
        storage_b.bytes_read = storage_b.files_read = 0
        trace.reset()
        t_delta = t_snap = 0.0
        for _ in range(ROUNDS):
            tag, batch = next(gen)
            assert tag == "round"
            await store_files(batch)
            await producer.compact()
            t0 = time.perf_counter()
            await c_delta.read_remote()
            t_delta += time.perf_counter() - t0
            t0 = time.perf_counter()
            await c_snap.read_remote()
            t_snap += time.perf_counter() - t0
        obs = trace.snapshot()
        pa = producer.with_state(canonical_bytes)
        equal = (
            c_delta.with_state(canonical_bytes) == pa
            and c_snap.with_state(canonical_bytes) == pa
        )
        return (
            t_build, t_delta, t_snap, equal,
            storage_a.bytes_read, storage_b.bytes_read,
            storage_a.files_read, storage_b.files_read, obs,
        )

    (t_build, t_delta, t_snap, equal, bytes_delta, bytes_snap,
     files_delta, files_snap, obs) = asyncio.run(build_and_measure())

    counters = obs.get("counters", {})
    applied = counters.get("delta_applied", 0)
    reduction = bytes_snap / bytes_delta if bytes_delta else float("inf")
    log(
        f"incremental consumer over {ROUNDS} rounds: delta path "
        f"{bytes_delta}B / snapshot path {bytes_snap}B → {reduction:.1f}x "
        f"fewer remote bytes (chains applied: {applied}; "
        f"wall {t_delta:.2f}s vs {t_snap:.2f}s)"
    )
    result = {
        "metric": "orset_e2e_delta_bytes_reduction",
        "config": f"delta_{N}ops_{R}r_{ROUNDS}x{TAIL_PCT:g}pct_tail",
        "value": round(reduction, 2),
        "unit": "x",
        "bytes_read_delta_path": int(bytes_delta),
        "bytes_read_snapshot_path": int(bytes_snap),
        "files_read_delta_path": int(files_delta),
        "files_read_snapshot_path": int(files_snap),
        "read_wall_delta_s": round(t_delta, 4),
        "read_wall_snapshot_s": round(t_snap, 4),
        "build_s": round(t_build, 1),
        "deltas_applied": int(applied),
        "deltas_sealed": int(counters.get("delta_files_sealed", 0)),
        "delta_bytes_sealed": int(counters.get("delta_bytes_sealed", 0)),
        "delta_fallbacks": int(counters.get("delta_fallbacks", 0)),
        "byte_identical": bool(equal),
        "backend": dev.platform,
    }
    print(json.dumps(result))
    # the divergence guard: a run whose delta path did not converge
    # byte-identically (or never used the chain) proves nothing and
    # must not become perf evidence
    if not equal or applied < ROUNDS:
        log(
            f"FAILED: byte_identical={equal} chains_applied={applied}/"
            f"{ROUNDS} — refusing to record"
        )
        raise SystemExit(1)
    if os.environ.get("BENCH_LOCAL_DISABLE") == "1":
        return
    if dev.platform != "tpu" and os.environ.get("BENCH_LOCAL_ALL") != "1":
        return
    _append_local({
        **result,
        "ts": datetime.datetime.now(datetime.timezone.utc).isoformat(
            timespec="seconds"),
        "device_kind": dev.device_kind,
        "host_cpus": os.cpu_count(),
        "shape": {"N": N, "R": R, "E": E, "ops_per_file": OPF,
                  "rounds": ROUNDS, "tail_pct": TAIL_PCT},
        "obs": obs,
    })


def e2e_strong_read(smoke: bool):
    """ISSUE-15 acceptance: linearizable point reads at the stability
    watermark under producer churn (docs/strong_reads.md).

    R producer replicas and one reader share an XChaCha-encrypted
    remote.  Each round every producer seals a wave of op files and —
    on a staggered cadence — compacts (publishing its cursor, which is
    what advances the watermark); the reader interleaves EVENTUAL reads
    (``read_remote`` + ``Core.read()``) with STRONG reads
    (``Core.read(linearizable=True)``, which refreshes, recomputes the
    watermark and advances the stable prefix), sampling the
    watermark-advance lag (union versions ahead of the served frontier)
    at every strong read plus an untimed ``max_lag=0`` refusal probe
    (``refusals`` = how often a zero-staleness caller would have been
    refused under this churn).  The record is strong reads/s with
    p50/p99 latency for both tiers and the lag distribution — the
    price of the guarantee, measured, not asserted.

    Evidence guard: the final strong read (everything published) must
    be byte-identical to a pure-Python oracle fold of exactly the cut
    it names — ANY divergence refuses the record.  Protocol-level and
    CPU-bound by design (the fold tails are host-side), so records land
    in BENCH_LOCAL.jsonl without the TPU gate, like ``--sim``.

    Env knobs: BENCH_SR_PRODUCERS (4), BENCH_SR_ROUNDS (6),
    BENCH_SR_WAVE (24 ops/producer/round), BENCH_SR_READS (6
    strong+eventual pairs/round), BENCH_SR_PUB_EVERY (2 — rounds
    between a producer's cursor publications).
    """
    import asyncio

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    R = int(os.environ.get("BENCH_SR_PRODUCERS", 2 if smoke else 4))
    ROUNDS = int(os.environ.get("BENCH_SR_ROUNDS", 2 if smoke else 6))
    WAVE = int(os.environ.get("BENCH_SR_WAVE", 8 if smoke else 24))
    READS = int(os.environ.get("BENCH_SR_READS", 2 if smoke else 6))
    PUB_EVERY = int(os.environ.get("BENCH_SR_PUB_EVERY", 2))

    from crdt_enc_tpu.backends import MemoryRemote, MemoryStorage
    from crdt_enc_tpu.core import Core
    from crdt_enc_tpu.models import canonical_bytes
    from crdt_enc_tpu.models.orset import ORSet, op_from_obj
    from crdt_enc_tpu.read.stable import StalenessError
    from crdt_enc_tpu.sim.linearize import oracle_fold

    opts = _daemon_opts_fn()

    async def scenario():
        remote = MemoryRemote()
        producers = [
            await Core.open(opts(MemoryStorage(remote))) for _ in range(R)
        ]
        reader = await Core.open(opts(MemoryStorage(remote)))
        oplog: dict = {}  # (actor, version) -> [op_obj, ...] plaintext
        total_ops = 0
        strong_s: list = []
        eventual_s: list = []
        lag_samples: list = []
        refusals = 0
        t0 = time.perf_counter()
        for rnd in range(ROUNDS):
            for pi, p in enumerate(producers):
                for w in range(WAVE):
                    member = f"m{pi}-{rnd}-{w}".encode()
                    ops = await p.update(
                        lambda s, a=p.actor_id, m=member: s.add_ctx(a, m)
                    )
                    oplog[(p.actor_id, p._local_meta.last_op_version)] = [
                        op.to_obj() for op in ops
                    ]
                    total_ops += 1
                if (rnd + pi) % PUB_EVERY == 0:
                    await p.compact()  # publish the cursor
            for _ in range(READS):
                te = time.perf_counter()
                await reader.read_remote()
                await reader.read()
                eventual_s.append(time.perf_counter() - te)
                ts = time.perf_counter()
                res = await reader.read(linearizable=True)
                strong_s.append(time.perf_counter() - ts)
                lag_samples.append(res.view.lag)
                # refusal-rate probe, untimed: a zero-staleness demand
                # refuses whenever the frontier trails the union — the
                # fraction of the run a max_lag=0 caller would have
                # been refused under this churn
                try:
                    await reader.read(
                        linearizable=True, max_lag=0, refresh=False
                    )
                except StalenessError:
                    refusals += 1
        # drain to full stability: every producer publishes its final
        # cursor and the reader observes EACH publication before the
        # next compact garbage-collects the snapshot that carries it —
        # cursor knowledge lives in snapshots, so a reader that never
        # sees one never counts that replica as caught up (the honest
        # wedge docs/strong_reads.md describes)
        for p in producers:
            await p.compact()
            await reader.read_remote()
        res = await reader.read(linearizable=True)
        wall = time.perf_counter() - t0
        lag_samples.append(res.view.lag)
        oracle, missing = oracle_fold(oplog, res.cursor)
        identical = (
            not missing
            and canonical_bytes(ORSet.from_obj(res.obj))
            == canonical_bytes(oracle)
        )
        covered = sum(res.cursor.counters.values())
        return (
            wall, total_ops, covered, strong_s, eventual_s, lag_samples,
            refusals, identical,
        )

    (wall, total_ops, covered, strong_s, eventual_s, lag_samples,
     refusals, identical) = asyncio.run(scenario())

    q = _nearest_rank

    result = {
        "metric": "strong_read_e2e_reads_per_sec",
        "config": f"strongread_{R}p",
        "value": round(len(strong_s) / sum(strong_s), 1),
        "unit": "reads/s",
        "reads_strong": len(strong_s),
        "reads_eventual": len(eventual_s),
        "refusals": refusals,
        "strong_ms": _quantiles_ms(strong_s),
        "eventual_ms": _quantiles_ms(eventual_s),
        "watermark_lag_versions": {
            "p50": q(lag_samples, 0.50),
            "p99": q(lag_samples, 0.99),
            "max": max(lag_samples),
        },
        "total_ops": total_ops,
        "final_covered_versions": covered,
        "wall_s": round(wall, 3),
        "byte_identical": identical,
        "backend": "cpu",
    }
    log(
        f"strong-read: {len(strong_s)} strong reads "
        f"(p99 {result['strong_ms'].get('p99_ms')}ms) vs eventual p99 "
        f"{result['eventual_ms'].get('p99_ms')}ms; watermark lag p99 "
        f"{result['watermark_lag_versions']['p99']} versions; "
        f"byte_identical={identical}"
    )
    print(json.dumps(result))
    if not identical:
        log(
            "FAILED: final strong read diverges from the oracle fold "
            "of its own cut — refusing to record"
        )
        raise SystemExit(1)
    if os.environ.get("BENCH_LOCAL_DISABLE") == "1":
        return
    _append_local({
        **result,
        "ts": datetime.datetime.now(datetime.timezone.utc).isoformat(
            timespec="seconds"),
        "host_cpus": os.cpu_count(),
        "shape": {"producers": R, "rounds": ROUNDS, "wave": WAVE,
                  "reads_per_round": READS, "pub_every": PUB_EVERY},
    })


def bench_sim(smoke: bool):
    """Adversarial-simulator throughput (docs/simulation.md): schedules
    per second over seeded all-fault runs — the explorable-schedule
    depth per CI minute, tracked like any other perf surface.  The run
    refuses to record if ANY schedule violates an invariant (a broken
    protocol has no meaningful throughput).  Protocol-level simulation
    is CPU-bound by design, so records land in BENCH_LOCAL.jsonl
    without the TPU gate.

    Flags/envs: ``--replicas N`` (8), ``--steps M`` (250), ``--faults
    all|none|cls,cls`` (all), ``--population P`` (run P schedules
    concurrently through one shared substrate, sim/population.py — the
    record's config gains a ``_pP`` suffix so the serial baseline stays
    a separate trend series), BENCH_SIM_SEEDS (4 serial; 2·P
    population).

    Population refusal guard: after the clock stops, every schedule is
    re-run SERIALLY and its fingerprint compared — any divergence
    refuses the record (a population throughput that changed the
    results measured nothing)."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import logging

    logging.disable(logging.WARNING)  # quarantine warns are the point
    from crdt_enc_tpu.sim import generate, run_schedule
    from crdt_enc_tpu.tools.sim import _build_faults

    replicas = _flag_int("--replicas", 4 if smoke else 8)
    steps = _flag_int("--steps", 50 if smoke else 250)
    population = _flag_int("--population", 0)
    spec = "all"
    if "--faults" in sys.argv:
        i = sys.argv.index("--faults")
        if i + 1 >= len(sys.argv):
            raise SystemExit("--faults wants all|none|class,class")
        spec = sys.argv[i + 1]
    faults = _build_faults(spec)
    n_seeds = int(os.environ.get(
        "BENCH_SIM_SEEDS",
        (2 if smoke else 4) if population < 2 else 2 * population,
    ))

    from collections import Counter

    totals: Counter = Counter()
    total_steps = total_checks = quarantined = 0
    report = None
    t0 = time.perf_counter()
    if population > 1:
        from crdt_enc_tpu.sim import run_population

        schedules = [
            generate(seed, replicas, steps, faults)
            for seed in range(n_seeds)
        ]
        report = run_population(schedules, population=population)
        results = list(zip(schedules, report.results))
    else:
        results = []
        for seed in range(n_seeds):
            schedule = generate(seed, replicas, steps, faults)
            results.append((schedule, run_schedule(schedule)))
    wall = time.perf_counter() - t0
    for schedule, result in results:
        if not result.ok:
            raise SystemExit(
                f"sim seed {schedule.seed} violated an invariant: "
                f"{result.violation}"
                " — fix the bug (and commit the shrunk fixture); a broken"
                " protocol has no throughput to record"
            )
        totals.update(result.fault_stats)
        total_steps += result.steps_run
        total_checks += result.checks_run
        quarantined += result.quarantined
    if report is not None:
        # the serial-equivalence refusal guard (untimed: the record is
        # the population wall, the guard is the evidence behind it)
        from crdt_enc_tpu.sim import verify_serial_equality

        problems = verify_serial_equality(report)
        if problems:
            raise SystemExit(
                "population run diverged from its serial twins — "
                "refusing to record:\n  " + "\n  ".join(problems)
            )
    suffix = f"_p{population}" if population > 1 else ""
    result_rec = {
        "metric": "sim_schedules_per_sec",
        "config": f"sim_{replicas}r_{steps}s_{spec}{suffix}",
        "value": round(n_seeds / wall, 3),
        "unit": "schedules/s",
        "steps_per_sec": round(total_steps / wall, 1),
        "schedules": n_seeds,
        "replicas": replicas,
        "steps": steps,
        "faults": spec,
        "faults_survived": dict(sorted(totals.items())),
        "faults_survived_total": sum(totals.values()),
        "ingest_quarantined": quarantined,
        "quiescence_checks": total_checks,
        "violations": 0,
        "wall_s": round(wall, 3),
        "backend": "cpu",
    }
    if population > 1:
        result_rec["population"] = population
        result_rec["serial_equivalent"] = True
    log(
        f"sim: {n_seeds} schedules ({replicas} replicas x {steps} steps, "
        f"faults={spec}"
        + (f", population={population}" if population > 1 else "")
        + f") in {wall:.2f}s = {result_rec['value']} sched/s, "
        f"{result_rec['faults_survived_total']} faults survived"
    )
    print(json.dumps(result_rec))
    if os.environ.get("BENCH_LOCAL_DISABLE") == "1":
        return
    _append_local({
        **result_rec,
        "ts": datetime.datetime.now(datetime.timezone.utc).isoformat(
            timespec="seconds"),
        "host_cpus": os.cpu_count(),
    })


def main():
    smoke = "--smoke" in sys.argv
    if "--sim" in sys.argv:
        bench_sim(smoke)
        return
    if "--e2e-strong-read" in sys.argv:
        e2e_strong_read(smoke)
        return
    if "--e2e-delta" in sys.argv:
        e2e_delta(smoke)
        return
    if "--e2e-warm-open" in sys.argv:
        e2e_warm_open(smoke)
        return
    if "--e2e-multitenant" in sys.argv:
        e2e_multitenant(smoke)
        return
    if "--e2e-daemon" in sys.argv:
        e2e_daemon(smoke)
        return
    if "--e2e-idle-cycle" in sys.argv:
        e2e_idle_cycle(smoke)
        return
    N = int(os.environ.get("BENCH_OPS", 50_000 if smoke else 1_000_000))
    R = int(os.environ.get("BENCH_REPLICAS", 500 if smoke else 10_000))
    E = int(os.environ.get("BENCH_MEMBERS", 256 if smoke else 4096))
    N_HOST = min(N, int(os.environ.get("BENCH_HOST_OPS", 20_000 if smoke else 100_000)))
    ITERS = int(os.environ.get("BENCH_ITERS", 3))

    # ``--smoke`` (and JAX_PLATFORMS=cpu) is the harness check: tiny
    # shapes on whatever backend is there, the Pallas variants left out,
    # nothing recorded.  Every other invocation expects a TPU and exits 3
    # without one (init_jax).
    jax, dev = init_jax(expects_tpu(smoke))

    import crdt_enc_tpu
    from crdt_enc_tpu import ops as K

    # compiles are excluded from the marginal timing, but the persistent
    # cache cuts the bench's own wall-clock on repeat runs
    crdt_enc_tpu.enable_compilation_cache()
    log(f"device: {dev.platform} ({dev.device_kind}); N={N} R={R} E={E}")

    kind, member, actor, counter = gen_columns(N, R, E)
    small = bool(counter.max() < 2 ** 15)
    variant_kws = {
        "fused": dict(impl="fused"),
        "two_pass": dict(impl="two_pass"),
    }
    if small:
        variant_kws["fused_i16"] = dict(impl="fused", small_counters=True)

    # the Pallas sorted one-hot-matmul fold (ops/pallas_fold.py): the
    # scatter phase rides the MXU instead of XLA's serialized scatter
    from crdt_enc_tpu.ops.pallas_fold import (
        MAX_COUNTER, MAX_ROWS, fold_cap, fused_defaults,
        orset_fold_pallas, orset_fold_pallas_fused, orset_pad_state,
        orset_retire, orset_unpad_state,
    )

    # an interpreted Pallas kernel is never timed: off-TPU the Pallas
    # variants are left out of the comparison (the XLA variants remain)
    on_tpu = dev.platform == "tpu"
    if not on_tpu:
        log("not on a TPU: Pallas variants are not timed (an interpreted "
            "kernel is no measurement)")
    if on_tpu and counter.max() < MAX_COUNTER and N <= MAX_ROWS:
        tile_cap = fold_cap(member, E)

        def pallas_variant(layout):
            return dict(
                _fold=lambda c, a, r, kind, member, actor, counter:
                orset_fold_pallas(
                    c, a, r, kind, member, actor, counter,
                    num_members=E, num_replicas=R, tile_cap=tile_cap,
                    layout=layout,
                ),
            )

        # the MXU-native actor-blocked layout, and the wide round-3
        # layout as an on-hardware A/B
        variant_kws["pallas_bf16"] = pallas_variant("ablk")
        variant_kws["pallas_wide"] = pallas_variant("wide")

        # round-5 flagship: normalize tail fused into the kernel
        # epilogue, deferred rm retirement, host-routed hi-limb skip
        fd = fused_defaults(E, R, int(counter.max()))

        def fused_single(c, a, r, kind, member, actor, counter):
            cp, ap, rp = orset_pad_state(
                c, a, r, num_members=E, num_replicas=R, h_blk=fd["h_blk"])
            out = orset_fold_pallas_fused(
                cp, ap, rp, kind, member, actor, counter,
                num_members=E, num_replicas=R, tile_cap=tile_cap, **fd)
            return orset_unpad_state(*out, num_members=E, num_replicas=R)

        def fused_chained(n_folds):
            import jax.numpy as jnp

            @jax.jit
            def run(c, a, r, kind, member, actor, counter):
                cp, ap, rp = orset_pad_state(
                    c, a, r, num_members=E, num_replicas=R,
                    h_blk=fd["h_blk"])

                def body(carry, _):
                    shift = (carry[0][0] + carry[1][0, 0]) % jnp.int32(
                        kind.shape[0])
                    rolled = [
                        jnp.roll(x, shift)
                        for x in (kind, member, actor, counter)
                    ]
                    # fixed initial planes + carry-derived roll (the
                    # protocol of `chained` below); deferred retirement
                    # inside the chain, one finalize after — byte-equal
                    # to the eager chain (ops/pallas_fold.py proof)
                    out = orset_fold_pallas_fused(
                        cp, ap, rp, *rolled,
                        num_members=E, num_replicas=R, tile_cap=tile_cap,
                        retire_rm=False, **fd)
                    return out, ()
                carry, _ = jax.lax.scan(
                    body, (cp, ap, rp), None, length=n_folds)
                ck, ad, rmv = carry
                return orset_unpad_state(
                    ck, ad, orset_retire(ck, rmv),
                    num_members=E, num_replicas=R)
            return run

        variant_kws["pallas_fused"] = dict(
            _fold=fused_single, _chained=fused_chained)

    def fold_call(kw):
        """A (carry, rows...) -> carry fold closure for one variant."""
        fold = kw.get("_fold")
        if fold is not None:
            return fold
        kw = {k: v for k, v in kw.items() if not k.startswith("_")}
        return lambda c, a, r, kind, member, actor, counter: K.orset_fold(
            c, a, r, kind, member, actor, counter,
            num_members=E, num_replicas=R, **kw,
        )

    # ---- correctness spot-check: host vs TPU byte equality on a subsample,
    # for EVERY variant that competes below (the published number must come
    # from a checked code path)
    n_chk = min(N, 20_000)
    h_state, _ = host_fold(kind[:n_chk], member[:n_chk], actor[:n_chk], counter[:n_chk], R)
    from crdt_enc_tpu.ops.columnar import Vocab, orset_planes_to_state
    from crdt_enc_tpu.utils import codec

    mem_v = Vocab(range(E))
    rep_v = Vocab(range(R))
    c0 = np.zeros(R, np.int32)
    a0 = np.zeros((E, R), np.int32)
    r0 = np.zeros((E, R), np.int32)
    h_bytes = codec.pack(h_state.to_obj())
    diverged = []
    for name, kw in variant_kws.items():
        try:
            ck, ad, rmv = fold_call(kw)(
                c0, a0, r0, kind[:n_chk], member[:n_chk], actor[:n_chk],
                counter[:n_chk],
            )
        except Exception as e:  # e.g. a dot dtype Mosaic can't lower
            log(f"WARNING: variant {name} failed to compile/run ({e!r}); excluded")
            diverged.append(name)
            continue
        t_state = orset_planes_to_state(
            np.asarray(ck), np.asarray(ad), np.asarray(rmv), mem_v, rep_v
        )
        ok = codec.pack(t_state.to_obj()) == h_bytes
        log(f"byte-equality[{name}] (n={n_chk}): {'OK' if ok else 'MISMATCH'}")
        if not ok:
            log(f"WARNING: variant {name} diverged from host reference; excluded")
            diverged.append(name)
    for name in diverged:
        del variant_kws[name]
    if not variant_kws:
        raise SystemExit("every fold variant diverged from the host reference")

    # ---- full-batch byte equality: the PUBLISHED shape (all N rows), not
    # just the 20k prefix — tile skew, the sliding windows, and the
    # hi-limb skip only engage at scale.  Host truth at N=1M is the
    # vectorized sparse host fold, itself tied to the per-op host
    # reference on the subsample right here; the first variant is checked
    # byte-for-byte through planes→state→pack, the rest plane-equal on
    # device against it (equality is transitive, and one 300MB+ plane
    # pull to the host is enough).
    full_checked = False
    if os.environ.get("BENCH_FULL_CHECK", "1") == "1":
        import jax.numpy as jnp

        from crdt_enc_tpu.models import ORSet as HostORSet
        from crdt_enc_tpu.ops.columnar import orset_fold_sparse_host

        sub_sparse = orset_fold_sparse_host(
            HostORSet(), kind[:n_chk], member[:n_chk], actor[:n_chk],
            counter[:n_chk], mem_v, rep_v,
        )
        if codec.pack(sub_sparse.to_obj()) != h_bytes:
            raise SystemExit(
                "sparse host fold diverged from the per-op host reference "
                "on the subsample — full-batch truth source is broken"
            )
        t0 = time.perf_counter()
        full_host = orset_fold_sparse_host(
            HostORSet(), kind, member, actor, counter, mem_v, rep_v
        )
        full_bytes = codec.pack(full_host.to_obj())
        log(f"full-batch host fold (N={N}): {time.perf_counter() - t0:.2f}s")
        full_args = [
            jax.device_put(x, dev)
            for x in (c0, a0, r0, kind, member, actor, counter)
        ]
        ref_planes = None
        for name, kw in list(variant_kws.items()):
            out = fold_call(kw)(*full_args)
            jax.block_until_ready(out)
            if ref_planes is None:
                ck, ad, rmv = (np.asarray(x) for x in out)
                st = orset_planes_to_state(ck, ad, rmv, mem_v, rep_v)
                ok = codec.pack(st.to_obj()) == full_bytes
                if ok:
                    ref_planes = out
            else:
                ok = all(
                    bool(jnp.array_equal(x, y))
                    for x, y in zip(out, ref_planes)
                )
            log(
                f"full-batch byte-equality[{name}] (N={N}): "
                f"{'OK' if ok else 'MISMATCH'}"
            )
            if not ok:
                log(f"WARNING: variant {name} diverged at the full batch; "
                    "excluded")
                del variant_kws[name]
        if not variant_kws:
            raise SystemExit("every variant diverged at the full batch")
        del full_args, ref_planes
        full_checked = True

    # ---- single-core host baseline (capped subsample; O(n) per-op loop)
    # under the pinned median-of-N protocol (see host_median above)
    def host_once():
        state, t = host_fold(
            kind[:N_HOST], member[:N_HOST], actor[:N_HOST], counter[:N_HOST], R
        )
        return t, state

    t_host, host_times, _ = host_median(host_once)
    host_rate = N_HOST / t_host
    stats = host_stats(host_times)
    log(
        f"host: {N_HOST} ops, median of {len(host_times)}: {t_host:.3f}s → "
        f"{host_rate:,.0f} ops/s (samples {stats['host_samples_s']}, "
        f"spread {stats['host_spread_pct']:.0f}%)"
    )

    # ---- TPU fold: full batch, compile excluded.  Per-fold device time is
    # the marginal cost inside a K-chained scan (see module docstring) —
    # the chain carry makes every fold data-dependent on the last.
    # Tiny smoke shapes fold in ~µs — chain enough folds that the marginal
    # signal clears the dispatch-noise floor.
    CHAIN = int(os.environ.get("BENCH_CHAIN", 1000 if smoke else 20))
    args = [jax.device_put(x, dev) for x in (c0, a0, r0, kind, member, actor, counter)]

    def chained(n_folds, **kw):
        """Marginal-measurement chain.  Anchoring: each iteration feeds
        the FIXED initial planes and a carry-derived roll of the op rows
        (legal — the fold is order-independent, so every iteration
        computes the same planes), rather than chaining the fold onto its
        own output.  The roll makes every iteration data-dependent on the
        last (XLA cannot hoist or elide any), and the fixed initial clock
        keeps the replay gate OPEN every iteration — a fold chained to
        its own fixpoint sees every add stale, which under-measures any
        variant with value-dependent work (e.g. the Pallas kernel's
        hi-limb skip)."""
        if "_chained" in kw:  # variant with its own carry layout
            return kw["_chained"](n_folds)
        fold = fold_call(kw)

        @jax.jit
        def run(c, a, r, kind, member, actor, counter):
            import jax.numpy as jnp

            def body(carry, _):
                shift = (carry[0][0] + carry[1][0, 0]) % jnp.int32(
                    kind.shape[0]
                )
                rolled = [
                    jnp.roll(x, shift)
                    for x in (kind, member, actor, counter)
                ]
                return fold(c, a, r, *rolled), ()
            carry, _ = jax.lax.scan(body, (c, a, r), None, length=n_folds)
            return carry
        return run

    def timed(fn):
        out = fn(*args)
        jax.block_until_ready(out)  # compile + warmup
        times = []
        for _ in range(ITERS):
            t0 = time.perf_counter()
            out = fn(*args)
            jax.block_until_ready(out)
            times.append(time.perf_counter() - t0)
        return min(times)

    # Below this marginal the measurement is dispatch noise, not device time
    # (noise spread over CHAIN folds).  A variant whose marginal lands
    # under the floor is NOISE — it must not win "best" and its rate must
    # not be published; raise BENCH_CHAIN until the signal clears the floor.
    NOISE_FLOOR = DISPATCH_NOISE_S / CHAIN
    # Round-robin timing (round 5): single-position measurements swing
    # ±2-3ms with device weather, so sequential per-variant
    # timing hands the last-measured variant the weather lottery.
    # Compile everything first, then interleave BENCH_ROUNDS passes
    # across variants and keep per-variant minima — variants compete
    # under the same weather.
    ROUNDS = int(os.environ.get("BENCH_ROUNDS", 2))
    fns = {}
    for name, kw in variant_kws.items():
        fns[name] = (chained(1, **kw), chained(1 + CHAIN, **kw))
        for f in fns[name]:
            import jax as _jax

            _jax.block_until_ready(f(*args))  # compile now
        log(f"compiled {name}")
    # Every pass's marginal is RECORDED per variant (the same
    # transparency the host samples get): the published number is the
    # min of the above-floor samples, and auditors can see the whole
    # distribution — including discarded sub-floor glitches — in the
    # evidence file, so a single-sample minimum can be judged against
    # its siblings.
    samples, single_dispatch = {n: [] for n in variant_kws}, {}
    for rd in range(ROUNDS):
        for name in variant_kws:
            f1, fk = fns[name]
            t1 = timed(f1)
            tk = timed(fk)
            t_marginal = (tk - t1) / CHAIN
            single_dispatch[name] = min(
                single_dispatch.get(name, t1), t1
            )
            samples[name].append(t_marginal)
            flag = (
                "" if t_marginal > NOISE_FLOOR
                else "  [sub-floor: noise, not device time]"
            )
            log(f"  round {rd} {name}: {t_marginal * 1e3:.2f} ms{flag}")
    variants, sub_floor_discards = {}, {}
    for name, ts in samples.items():
        valid = [t for t in ts if t > NOISE_FLOOR]
        sub_floor_discards[name] = len(ts) - len(valid)
        if not valid:
            log(
                f"tpu[{name}]: every pass below the "
                f"{NOISE_FLOOR * 1e3:.2f}ms noise floor — excluded"
            )
            continue
        variants[name] = min(valid)
        log(
            f"tpu[{name}]: single-dispatch {single_dispatch[name]:.4f}s "
            f"(dispatch + sync included); best marginal "
            f"{variants[name] * 1e3:.2f}ms/fold → "
            f"{N / variants[name]:,.0f} ops/s"
            + (f"  [{sub_floor_discards[name]} sub-floor discarded]"
               if sub_floor_discards[name] else "")
        )
    method = "marginal_chain"
    if not variants:
        log(
            f"WARNING: every variant fell below the {NOISE_FLOOR * 1e3:.2f}ms "
            f"noise floor; rerun with a larger BENCH_CHAIN (current {CHAIN}). "
            "Falling back to single-dispatch wall-clock (dispatch cost "
            "INCLUDED) — a strict over-estimate of device time."
        )
        variants = single_dispatch
        method = "single_dispatch_upper_bound"
    # Roofline gate: any variant whose marginal implies more than HBM
    # peak on the fold's minimum traffic (read+write both planes + the
    # op columns + the clock) is a measurement artifact, not a kernel —
    # drop it loudly instead of publishing an impossible number.
    bytes_model = orset_fold_bytes_model(N, E, R)
    for name in list(variants):
        pct = roofline_pct(bytes_model, variants[name], dev)
        if pct is not None and pct > 100.0:
            log(
                f"WARNING: variant {name} implies {pct:.0f}% of HBM peak "
                f"({variants[name]*1e3:.2f}ms for ≥{bytes_model/1e6:.0f}MB) "
                "— impossible; chain was hoisted/elided. Excluded."
            )
            del variants[name]
    if not variants:
        raise SystemExit("every variant failed the roofline sanity gate")
    best = min(variants, key=variants.get)
    t_tpu = variants[best]
    tpu_rate = N / t_tpu
    log(f"best variant: {best}")
    pct_hbm = roofline_pct(bytes_model, t_tpu, dev)
    log(f"roofline: ≥{bytes_model/1e6:.0f}MB/fold → {pct_hbm}% of HBM peak")

    # same key + workload as suite config 3 — one pin serves both
    ratio_fields = pinned_ratio_fields(
        "orset_10kx1M", {"N": N, "R": R, "E": E, "n_host": N_HOST},
        tpu_rate, tpu_rate / host_rate,
    )
    ratio_fields.pop("_ratio_raw", None)  # aggregation-only field
    result = {
        "metric": "orset_compaction_fold_ops_per_sec",
        "value": round(tpu_rate, 1),
        "unit": "ops/s",
        **ratio_fields,
        # which timing method produced `value` — consumers must not compare
        # a latency-bound fallback number against a marginal-chain number
        "method": method,
        "best_variant": best,
        # bytes any implementation of this fold must touch, and the % of
        # v5e HBM peak the measured marginal implies on that model —
        # regressions and headroom visible mechanically (>100% = rejected)
        "bytes_model": bytes_model,
        "pct_hbm_peak": pct_hbm,
        # byte equality was checked at the full published shape, not just
        # the subsample (VERDICT r3 item 4)
        "full_batch_equal": full_checked,
        "backend": dev.platform,
    }
    print(json.dumps(result))
    # persist the run (full per-variant table) so a later capture-time
    # failure cannot erase this round's verified numbers.  Only
    # real-TPU runs go into the committed evidence file — CPU smoke runs
    # would pollute it (override with BENCH_LOCAL_ALL=1 for testing).
    if os.environ.get("BENCH_LOCAL_DISABLE") == "1":  # e.g. harness tests
        return
    if dev.platform != "tpu" and os.environ.get("BENCH_LOCAL_ALL") != "1":
        return
    _append_local({
        **result,
        "ts": datetime.datetime.now(datetime.timezone.utc).isoformat(
            timespec="seconds"),
        "device_kind": dev.device_kind,
        "shape": {"N": N, "R": R, "E": E, "chain": CHAIN, "iters": ITERS},
        "host_rate": round(host_rate, 1),
        **stats,
        "marginals_ms": {
            k: round(v * 1e3, 3) for k, v in variants.items()
        },
        # the full per-variant sample distributions (incl. sub-floor
        # glitches), so a published minimum can be audited against its
        # sibling passes — a lone fast outlier is visible as such
        "marginal_samples_ms": {
            k: [round(t * 1e3, 3) for t in ts]
            for k, ts in samples.items()
        },
        "single_dispatch_s": {
            k: round(v, 4) for k, v in single_dispatch.items()
        },
    })


if __name__ == "__main__":
    main()
