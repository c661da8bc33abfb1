"""Four of the five BASELINE.json benchmark configs, host-reference vs
device (config 5, the encrypted streaming pipeline, went with the
bench-only front door that it drove: PR 28).

Each config measures: single-core host-reference fold rate (the per-op
loop the reference runs, capped to a subsample for the big configs — the
loop is O(n) so per-op rate transfers), device fold rate, and a
byte-equality check of the folded state against the host reference on a
common subsample.

Every config times the fold as the MARGINAL cost inside a chained
``lax.scan`` (``timeit_marginal``) so the fixed per-dispatch cost
cancels.

Run:  python benchmarks/suite.py [--smoke] [--config N] [--cpu]
Prints one JSON line per config and a trailing summary line.

Sizes are env-tunable (SUITE_SCALE=0.1 scales every N down 10x).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import uuid
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def running_count(group: np.ndarray, n_groups: int) -> np.ndarray:
    """1-based running occurrence count per group id, in row order."""
    n = len(group)
    order = np.argsort(group, kind="stable")
    g = group[order]
    cum = np.arange(1, n + 1, dtype=np.int64)
    starts = np.searchsorted(g, np.arange(n_groups))
    base = starts[g]
    within = cum - base
    out = np.empty(n, np.int64)
    out[order] = within
    return out.astype(np.int32)


# Pinned host-baseline protocol — the single implementation lives in
# bench.py (median-of-BENCH_HOST_RUNS with raw samples recorded); every
# config here measures through it so the two harnesses cannot drift.
from bench import host_median, host_stats, load_pinned  # noqa: E402


def _host_only_record(config, n_ops, shape, t_host, host_times):
    """What the pinning tool (pin_baselines.py) needs: the config's host
    rate under the exact workload the suite runs, with raw samples."""
    return dict(
        config=config, host_only=True, n_ops=n_ops, shape=shape,
        host_rate=n_ops / t_host, median_s=t_host,
        **host_stats(host_times),
    )


def timeit(fn, iters: int) -> float:
    import jax

    jax.block_until_ready(fn())  # compile + warmup
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        out = fn()
        jax.block_until_ready(out)
        best = min(best, time.perf_counter() - t0)
    return best


def timeit_marginal(make_chained, iters: int, chain: int) -> tuple[float, str]:
    """Per-fold device time as the marginal cost inside a chained scan.

    ``make_chained(n)`` returns a zero-arg callable running n
    data-dependent folds in ONE dispatch.  Single-dispatch timing carries
    the fixed dispatch + sync cost and overstates small folds; the
    chained difference cancels it (same method and noise constant as
    bench.py).  Falls back to single-dispatch wall-clock (dispatch cost
    INCLUDED — a strict over-estimate) when the marginal signal is below
    the noise floor.

    Returns ``(seconds_per_fold, method)`` where method is
    ``"marginal_chain"`` or ``"single_dispatch_upper_bound"``."""
    from bench import DISPATCH_NOISE_S

    t1 = timeit(make_chained(1), iters)
    # escalate the chain until the marginal signal clears the jitter floor
    # (folds keep getting faster; a fixed chain length goes deaf), bounded
    # so a pathological near-zero marginal can't spin forever
    max_chain = max(chain * 100, 1_000_000)
    while True:
        tk = timeit(make_chained(1 + chain), iters)
        marginal = (tk - t1) / chain
        floor = DISPATCH_NOISE_S / chain
        if marginal > floor:
            return marginal, "marginal_chain"
        if chain * 10 > max_chain:
            log(
                f"  marginal {marginal * 1e3:.3f}ms/fold below noise floor "
                f"{floor * 1e3:.3f}ms at chain={chain}; using single-dispatch "
                f"{t1 * 1e3:.1f}ms (dispatch cost included)"
            )
            return t1, "single_dispatch_upper_bound"
        log(
            f"  chain={chain} below noise floor "
            f"({marginal * 1e3:.4f}ms ≤ {floor * 1e3:.4f}ms); escalating"
        )
        chain *= 10


def actor_bytes_table(R: int) -> list:
    """R actor ids whose byte order equals their index order."""
    return [uuid.UUID(int=i + 1).bytes for i in range(R)]


# --------------------------------------------------------------- config 1+2


def bench_gcounter(N: int, R: int, iters: int, cmul: int = 1,
                   host_only: bool = False) -> dict:
    """Config 1: G-Counter, 4 replicas, 1k increment ops."""
    import jax
    import jax.numpy as jnp

    from crdt_enc_tpu import ops as K
    from crdt_enc_tpu.models import GCounter
    from crdt_enc_tpu.models.vclock import Dot

    rng = np.random.default_rng(1)
    actor = rng.integers(0, R, N, dtype=np.int32)
    counter = running_count(actor, R)
    actors = actor_bytes_table(R)

    def host_once():
        state = GCounter()
        t0 = time.perf_counter()
        for a, c in zip(actor.tolist(), counter.tolist()):
            state.apply(Dot(actors[a], c))
        return time.perf_counter() - t0, state

    t_host, host_times, state = host_median(host_once)
    if host_only:
        return _host_only_record(
            "gcounter_4x1k", N, dict(N=N, R=R), t_host, host_times)

    clock0 = np.zeros(R, np.int32)
    dev_args = [jax.device_put(x) for x in (clock0, actor, counter)]

    def make_chained(n):
        @jax.jit
        def run(clock0, actor, counter):
            def body(carry, _):
                # anchor the batch to the carry: min(clock[0], 0) is 0 at
                # runtime (counters are ≥ 0) but XLA cannot prove it, so
                # the scatter cannot be hoisted out of the loop — without
                # this the chain times only the elementwise tail
                # (measured: marginal flat in N, >HBM-peak "rates")
                c2 = counter + jnp.minimum(carry[0], 0)
                clock, total = K.gcounter_fold(carry, actor, c2, num_replicas=R)
                return clock, total
            return jax.lax.scan(body, clock0, None, length=n)
        return lambda: run(*dev_args)

    # sub-µs fold: only a very long chain resolves it above the jitter
    t_dev, timing = timeit_marginal(make_chained, iters, chain=500_000)
    clock, total = K.gcounter_fold(*dev_args, num_replicas=R)
    dev_clock = {actors[i]: int(c) for i, c in enumerate(np.asarray(clock)) if c}
    equal = dev_clock == state.clock.counters and int(total) == state.read()
    return dict(
        config="gcounter_4x1k", metric="ops_folded_per_sec", N=N, R=R,
        _pin_shape=dict(N=N, R=R),
        host_rate=N / t_host, device_rate=N / t_dev, byte_equal=bool(equal),
        timing=timing, bytes_model=8 * N + 2 * 4 * R, **host_stats(host_times),
    )


def bench_pncounter(N: int, R: int, iters: int, cmul: int = 1,
                    host_only: bool = False) -> dict:
    """Config 2: PN-Counter, 1k replicas, 100k mixed inc/dec ops."""
    import jax
    import jax.numpy as jnp

    from crdt_enc_tpu import ops as K
    from crdt_enc_tpu.models import PNCounter
    from crdt_enc_tpu.models.counters import NEG, POS
    from crdt_enc_tpu.models.vclock import Dot

    rng = np.random.default_rng(2)
    actor = rng.integers(0, R, N, dtype=np.int32)
    sign = (rng.random(N) < 0.3).astype(np.int8)  # ~30% decrements
    counter = running_count(actor * 2 + sign, R * 2)
    actors = actor_bytes_table(R)

    n_host = min(N, 200_000)

    def host_once():
        state = PNCounter()
        t0 = time.perf_counter()
        for a, s, c in zip(
            actor[:n_host].tolist(), sign[:n_host].tolist(),
            counter[:n_host].tolist(),
        ):
            state.apply((int(s), Dot(actors[a], c)))
        return time.perf_counter() - t0, state

    t_host, host_times, state = host_median(host_once)
    if host_only:
        return _host_only_record(
            "pncounter_1kx100k", n_host, dict(N=N, R=R, n_host=n_host),
            t_host, host_times)

    p0 = np.zeros(R, np.int32)
    n0 = np.zeros(R, np.int32)
    dev_args = [jax.device_put(x) for x in (p0, n0, sign, actor, counter)]

    def make_chained(n):
        @jax.jit
        def run(p0, n0, sign, actor, counter):
            def body(carry, _):
                # carry-anchor the batch so the segment-max cannot be
                # hoisted out of the loop (see bench_gcounter)
                c2 = counter + jnp.minimum(carry[0][0], 0)
                p, nn, value = K.pncounter_fold(
                    *carry, sign, actor, c2, num_replicas=R
                )
                return (p, nn), value
            return jax.lax.scan(body, (p0, n0), None, length=n)
        return lambda: run(*dev_args)

    t_dev, timing = timeit_marginal(make_chained, iters, chain=5_000 * cmul)
    # byte equality on the host subsample
    ps, ns, val = K.pncounter_fold(
        p0, n0, sign[:n_host], actor[:n_host], counter[:n_host], num_replicas=R
    )
    dev_p = {actors[i]: int(c) for i, c in enumerate(np.asarray(ps)) if c}
    dev_n = {actors[i]: int(c) for i, c in enumerate(np.asarray(ns)) if c}
    equal = (
        dev_p == state.p.clock.counters
        and dev_n == state.n.clock.counters
        and int(val) == state.read()
    )
    return dict(
        config="pncounter_1kx100k", metric="ops_folded_per_sec", N=N, R=R,
        _pin_shape=dict(N=N, R=R, n_host=n_host),
        host_rate=n_host / t_host, device_rate=N / t_dev, byte_equal=bool(equal),
        timing=timing, bytes_model=9 * N + 4 * 4 * R,
        **host_stats(host_times),
    )


# ----------------------------------------------------------------- config 3


from bench import orset_fold_bytes_model as _orset_bytes_model


def bench_orset(N: int, R: int, E: int, n_host: int, iters: int, cmul: int = 1,
                host_only: bool = False) -> dict:
    """Config 3 (north star): OR-Set, 10k replicas, 1M add/remove ops."""
    import jax

    import bench as north

    from crdt_enc_tpu import ops as K
    from crdt_enc_tpu.ops.columnar import Vocab, orset_planes_to_state
    from crdt_enc_tpu.utils import codec

    kind, member, actor, counter = north.gen_columns(N, R, E)
    if host_only:
        def host_once():
            state, t = north.host_fold(
                kind[:n_host], member[:n_host], actor[:n_host],
                counter[:n_host], R)
            return t, state

        t_host, host_times, _ = host_median(host_once)
        return _host_only_record(
            "orset_10kx1M", n_host, dict(N=N, R=R, E=E, n_host=n_host),
            t_host, host_times)

    # the Pallas sorted one-hot-matmul fold when eligible (the north-star
    # winner, see bench.py), else the fused XLA scatter
    from crdt_enc_tpu.ops.pallas_fold import (
        MAX_COUNTER, MAX_ROWS, fold_cap, orset_fold_pallas,
    )

    # an interpreted Pallas kernel is never timed: off-TPU config 3
    # times the XLA fold
    use_pallas = (
        jax.default_backend() == "tpu"
        and counter.max() < MAX_COUNTER and N <= MAX_ROWS
    )
    if use_pallas:
        # round 5: the fused-tail kernel with host-routed defaults —
        # the same flagship path bench.py publishes (pad/unpad ride
        # inside the fold here; the bench's padded chain amortizes them)
        from crdt_enc_tpu.ops.pallas_fold import (
            fused_defaults, orset_fold_pallas_fused, orset_pad_state,
            orset_unpad_state,
        )

        tile_cap = fold_cap(member, E)
        fd = fused_defaults(E, R, int(counter.max()))

        def fold(c, a, r, kind, member, actor, counter):
            cp, ap, rp = orset_pad_state(
                c, a, r, num_members=E, num_replicas=R, h_blk=fd["h_blk"])
            out = orset_fold_pallas_fused(
                cp, ap, rp, kind, member, actor, counter,
                num_members=E, num_replicas=R, tile_cap=tile_cap, **fd)
            return orset_unpad_state(*out, num_members=E, num_replicas=R)
    else:
        def fold(c, a, r, kind, member, actor, counter):
            return K.orset_fold(
                c, a, r, kind, member, actor, counter,
                num_members=E, num_replicas=R,
            )

    n_chk = min(N, 20_000)
    h_state, _ = north.host_fold(
        kind[:n_chk], member[:n_chk], actor[:n_chk], counter[:n_chk], R
    )
    c0 = np.zeros(R, np.int32)
    a0 = np.zeros((E, R), np.int32)
    r0 = np.zeros((E, R), np.int32)
    ck, ad, rm = fold(
        c0, a0, r0, kind[:n_chk], member[:n_chk], actor[:n_chk], counter[:n_chk]
    )
    t_state = orset_planes_to_state(
        np.asarray(ck), np.asarray(ad), np.asarray(rm), Vocab(range(E)), Vocab(range(R))
    )
    equal = codec.pack(t_state.to_obj()) == codec.pack(h_state.to_obj())

    def host_once():
        state, t = north.host_fold(
            kind[:n_host], member[:n_host], actor[:n_host], counter[:n_host], R
        )
        return t, state

    t_host, host_times, _ = host_median(host_once)
    args = [jax.device_put(x) for x in (c0, a0, r0, kind, member, actor, counter)]

    def make_chained(n):
        import jax.numpy as jnp

        if use_pallas:
            from crdt_enc_tpu.ops.pallas_fold import orset_retire

            @jax.jit
            def run(c, a, r, kind, member, actor, counter):
                # padded-plane deferred chain, identical to bench.py's
                # pallas_fused protocol: pad once, deferred rm
                # retirement inside, one finalize after the scan
                cp, ap, rp = orset_pad_state(
                    c, a, r, num_members=E, num_replicas=R,
                    h_blk=fd["h_blk"])

                def body(carry, _):
                    shift = (carry[0][0] + carry[1][0, 0]) % jnp.int32(
                        kind.shape[0]
                    )
                    rolled = [
                        jnp.roll(x, shift)
                        for x in (kind, member, actor, counter)
                    ]
                    out = orset_fold_pallas_fused(
                        cp, ap, rp, *rolled,
                        num_members=E, num_replicas=R, tile_cap=tile_cap,
                        retire_rm=False, **fd)
                    return out, ()
                carry, _ = jax.lax.scan(
                    body, (cp, ap, rp), None, length=n)
                ck, ad, rmv = carry
                return orset_unpad_state(
                    ck, ad, orset_retire(ck, rmv),
                    num_members=E, num_replicas=R)
            return lambda: run(*args)

        @jax.jit
        def run(c, a, r, kind, member, actor, counter):
            # roll-anchored chain (see bench.py): fixed initial planes,
            # carry-derived row permutation — every iteration does the
            # full live-add workload and nothing can hoist
            def body(carry, _):
                shift = (carry[0][0] + carry[1][0, 0]) % jnp.int32(
                    kind.shape[0]
                )
                rolled = [
                    jnp.roll(x, shift)
                    for x in (kind, member, actor, counter)
                ]
                return fold(c, a, r, *rolled), ()
            carry, _ = jax.lax.scan(body, (c, a, r), None, length=n)
            return carry
        return lambda: run(*args)

    t_dev, timing = timeit_marginal(make_chained, iters, chain=20 * cmul)
    return dict(
        config="orset_10kx1M", metric="ops_folded_per_sec", N=N, R=R, E=E,
        _pin_shape=dict(N=N, R=R, E=E, n_host=n_host),
        host_rate=n_host / t_host, device_rate=N / t_dev, byte_equal=bool(equal),
        timing=timing, bytes_model=_orset_bytes_model(N, E, R),
        **host_stats(host_times),
    )


# ----------------------------------------------------------------- config 4


def bench_lwwmap(N: int, K_keys: int, R: int, n_host: int, iters: int,
                 cmul: int = 1, host_only: bool = False) -> dict:
    """Config 4: LWW-map, 1M keys, 10k replicas, timestamped writes."""
    import jax
    import jax.numpy as jnp

    from crdt_enc_tpu import ops as K
    from crdt_enc_tpu.models import LWWMap
    from crdt_enc_tpu.models.lwwmap import LWWOp
    from crdt_enc_tpu.ops.lww import ts_split

    rng = np.random.default_rng(4)
    key = rng.integers(0, K_keys, N, dtype=np.int32)
    ts = rng.integers(1, 1 << 40, N, dtype=np.int64)
    actor = rng.integers(0, R, N, dtype=np.int32)
    # single-byte msgpack domain so value rank == numeric value
    value = rng.integers(0, 100, N, dtype=np.int32)
    hi, lo = ts_split(ts)
    actors = actor_bytes_table(R)

    def host_once():
        state = LWWMap()
        t0 = time.perf_counter()
        for k, t, a, v in zip(
            key[:n_host].tolist(), ts[:n_host].tolist(),
            actor[:n_host].tolist(), value[:n_host].tolist(),
        ):
            state.apply(LWWOp(k, t, actors[a], v))
        return time.perf_counter() - t0, state

    t_host, host_times, state = host_median(host_once)
    if host_only:
        return _host_only_record(
            "lwwmap_1Mx10k", n_host,
            dict(N=N, K=K_keys, R=R, n_host=n_host), t_host, host_times)

    args = [jax.device_put(x) for x in (key, hi, lo, actor, value)]
    # value domain is 0..99 rank-interned, so the (actor, value) cascades
    # pack into one (R * V = 1M ≪ 2^31)
    n_values = int(value.max()) + 1

    def make_chained_impl(impl, tile_cap, limbs=None):
        def make_chained(n):
            @jax.jit
            def run(key, hi, lo, actor, value):
                win0 = (
                    jnp.full(K_keys, -1, jnp.int32),
                    jnp.full(K_keys, -1, jnp.int32),
                    jnp.full(K_keys, -1, jnp.int32),
                    jnp.full(K_keys, -1, jnp.int32),
                    jnp.zeros(K_keys, bool),
                )

                def body(carry, _):
                    # rotate the batch by a carry-derived offset: the fold
                    # is order-independent so the result is identical, but
                    # the inputs are loop-varying as far as XLA can tell,
                    # so the scatter passes cannot be hoisted out of the
                    # loop (measured un-anchored: marginal shrinks as N
                    # grows — the chain was timing only the compete)
                    off = jnp.abs(carry[0][0]) % jnp.int32(len(key))
                    rolled = [
                        jnp.roll(x, off)
                        for x in (key, hi, lo, actor, value)
                    ]
                    return (
                        K.lww_fold_into(
                            carry, *rolled,
                            num_keys=K_keys, num_values=n_values,
                            impl=impl, tile_cap=tile_cap, limbs=limbs,
                        ),
                        (),
                    )

                carry, _ = jax.lax.scan(body, win0, None, length=n)
                return carry
            return lambda: run(*args)
        return make_chained

    # NOTE: each chained fold competes N new rows + K_keys carried winners,
    # so device_rate = N / t_dev UNDERSTATES per-row throughput (by up to
    # ~2x when K_keys ≈ N) — conservative by construction.
    t_dev, timing = timeit_marginal(
        make_chained_impl("xla", 0), iters, chain=20 * cmul
    )
    lww_variant = "xla_cascades"
    if jax.default_backend() == "tpu":
        # the Pallas winner fold (ops/pallas_lww.py): time it as a second
        # variant and take the better, gated on exact equality with the
        # XLA fold on the full batch (parity is also pinned in tests)
        from crdt_enc_tpu.ops.pallas_lww import (
            lww_fold_pallas, lww_limbs, lww_tile_cap,
        )

        cap = lww_tile_cap(key, K_keys)
        limbs = lww_limbs(hi, lo, actor, n_values)
        ref_tbl = K.lww_fold(*args, num_keys=K_keys, num_values=n_values)
        pal_tbl = lww_fold_pallas(
            *args, num_keys=K_keys, num_values=n_values, tile_cap=cap,
            limbs=limbs,
        )
        pallas_ok = all(
            bool(jnp.array_equal(a, b)) for a, b in zip(ref_tbl, pal_tbl)
        )
        if pallas_ok:
            t_pal, timing_pal = timeit_marginal(
                make_chained_impl("pallas", cap, limbs), iters,
                chain=20 * cmul,
            )
            log(f"  lww pallas marginal {t_pal * 1e3:.2f}ms vs xla "
                f"{t_dev * 1e3:.2f}ms")
            if t_pal < t_dev:
                t_dev, timing, lww_variant = t_pal, timing_pal, "pallas_mxu"
        else:
            log("WARNING: pallas LWW fold diverged on the full batch; "
                "excluded from timing")

    # The timed path is lww_fold_into: check IT (incremental, two halves)
    # against the whole-batch fold on the host subsample, then the whole
    # fold against the host reference
    h2 = n_host // 2
    inc = K.lww_fold_into(
        K.lww_fold(key[:h2], hi[:h2], lo[:h2], actor[:h2], value[:h2],
                   num_keys=K_keys, num_values=n_values),
        key[h2:n_host], hi[h2:n_host], lo[h2:n_host], actor[h2:n_host],
        value[h2:n_host], num_keys=K_keys, num_values=n_values,
    )
    whole = K.lww_fold(
        key[:n_host], hi[:n_host], lo[:n_host], actor[:n_host], value[:n_host],
        num_keys=K_keys,
    )
    inc_equal = all(
        np.array_equal(np.asarray(a), np.asarray(b)) for a, b in zip(whole, inc)
    )
    m_hi, m_lo, m_actor, m_value, present = whole
    m_hi, m_lo = np.asarray(m_hi), np.asarray(m_lo)
    m_actor, m_value = np.asarray(m_actor), np.asarray(m_value)
    idx = np.flatnonzero(np.asarray(present))
    dev_map = LWWMap()
    dev_map.entries = {
        int(k): [
            (int(m_hi[k]) << 31) | int(m_lo[k]),
            actors[int(m_actor[k])],
            int(m_value[k]),
            False,
        ]
        for k in idx
    }
    equal = (dev_map == state) and inc_equal
    return dict(
        config="lwwmap_1Mx10k", metric="writes_folded_per_sec", N=N,
        _pin_shape=dict(N=N, K=K_keys, R=R, n_host=n_host),
        K=K_keys, R=R,
        host_rate=n_host / t_host, device_rate=N / t_dev, byte_equal=bool(equal),
        timing=timing, variant=lww_variant,
        bytes_model=20 * N + 2 * 20 * K_keys,
        **host_stats(host_times),
    )


# --------------------------------------------------------------------- main


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--config", type=int, default=0, help="run one config (1-4)")
    ap.add_argument("--iters", type=int, default=3)
    ap.add_argument(
        "--cpu", action="store_true",
        help="force the CPU backend (same as JAX_PLATFORMS=cpu)",
    )
    args = ap.parse_args()

    import jax

    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
    dev = jax.devices()[0]
    log(f"device: {dev.platform} ({dev.device_kind})")
    scale = float(os.environ.get("SUITE_SCALE", 0.02 if args.smoke else 1.0))

    def S(n, lo=64):
        return max(lo, int(n * scale))

    # smaller configs fold faster: lengthen the timing chain so the
    # marginal signal still clears the dispatch-jitter noise floor
    cmul = max(1, min(100, round(1.0 / max(scale, 0.01))))

    runners = {
        1: lambda: bench_gcounter(S(1_000), 4, args.iters, cmul),
        2: lambda: bench_pncounter(S(100_000), min(1_000, S(1_000)), args.iters, cmul),
        3: lambda: bench_orset(
            S(1_000_000), min(10_000, S(10_000)), min(4096, S(4096)),
            n_host=S(100_000, lo=2_000), iters=args.iters, cmul=cmul,
        ),
        4: lambda: bench_lwwmap(
            S(1_000_000), min(1_000_000, S(1_000_000)), min(10_000, S(10_000)),
            n_host=S(50_000, lo=2_000), iters=args.iters, cmul=cmul,
        ),
    }
    from bench import roofline_pct

    wanted = [args.config] if args.config else sorted(runners)
    results, ratios = [], []
    for c in wanted:
        log(f"config {c}…")
        r = runners[c]()
        # roofline check (round-3 item 6): bytes any implementation must
        # touch ÷ measured marginal; >100% of HBM peak is impossible —
        # the chain was hoisted — so the number is flagged and its config
        # excluded from the geomean rather than published as a speedup
        bm = r.get("bytes_model")
        pct = (
            roofline_pct(bm, r["N"] / r["device_rate"], dev)
            if bm else None
        )
        r["pct_hbm_peak"] = pct
        r["super_roofline"] = bool(pct is not None and pct > 100.0)
        from bench import pinned_ratio_fields

        r.update(pinned_ratio_fields(
            r["config"], r.pop("_pin_shape", None) or {},
            r["device_rate"], r["device_rate"] / r["host_rate"],
        ))
        if r["super_roofline"]:
            r.pop("_ratio_raw", None)  # excluded — and never published
            log(
                f"WARNING: config {c} marginal implies {pct:.0f}% of HBM "
                "peak — impossible (hoisted chain); excluded from geomean"
            )
        else:
            # the geomean of record uses the pinned denominator when
            # available (VERDICT r4: same-run host rates swing 1.5×),
            # at full precision (not the 2-decimal display rounding)
            ratios.append(r.pop("_ratio_raw"))
        r["host_rate"] = round(r["host_rate"], 1)
        r["device_rate"] = round(r["device_rate"], 1)
        results.append(r)
        print(json.dumps(r), flush=True)
    ok = all(r["byte_equal"] for r in results)
    summary = {
        "suite": "baseline_configs", "device": str(dev.device_kind),
        "configs_run": wanted, "all_byte_equal": ok,
        "geomean_speedup": round(
            float(np.exp(np.mean(np.log(ratios)))), 2
        ) if ratios else None,
    }
    print(json.dumps(summary))
    # real-TPU runs persist to the committed evidence file (same policy
    # as bench.py's BENCH_LOCAL.jsonl): a capture-time failure
    # must not erase in-round suite results
    if dev.platform == "tpu":
        import datetime

        rec = {
            "ts": datetime.datetime.now(datetime.timezone.utc).isoformat(
                timespec="seconds"),
            **summary,
            "results": results,
        }
        try:
            path = Path(__file__).resolve().parent.parent / "SUITE_LOCAL.jsonl"
            with open(path, "a") as f:
                f.write(json.dumps(rec) + "\n")
        except (OSError, TypeError, ValueError) as e:
            log(f"WARNING: could not append SUITE_LOCAL.jsonl: {e!r}")


if __name__ == "__main__":
    main()
