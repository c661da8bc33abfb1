"""Micro-benchmarks characterizing the north-star fold's component costs
on the real chip, to size the Pallas fold kernel (round-3 item 1).

Measures, each as a chained-scan marginal (fixed dispatch cost cancelled):
  1. fused i16 scatter alone (the suspected serialization wall)
  2. elementwise plane pass (read 2 planes, write 2 planes)
  3. jax.lax.sort of the op batch by segment key
  4. one-hot matmul segment-max prototype (scatter -> MXU reformulation)
"""
from __future__ import annotations

import os
import sys
import time
from functools import partial

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

import crdt_enc_tpu
from bench import gen_columns

# persistent compile cache: repeat profile runs skip the 30-60s jits
crdt_enc_tpu.enable_compilation_cache()

N = int(os.environ.get("MB_OPS", 1_000_000))
R = int(os.environ.get("MB_REPLICAS", 10_000))
E = int(os.environ.get("MB_MEMBERS", 4096))
CHAIN = int(os.environ.get("MB_CHAIN", 20))
ITERS = 3


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def round_robin(variants, rounds_env="MB_FUSED_ROUNDS", rounds_default=6):
    """The interleaved A/B protocol (round 5): single-position marginal
    measurements swing ±2-3ms with device weather, so compile
    every variant FIRST, then rotate timing passes across variants and
    keep per-variant minima — only interleaved comparisons count.
    ``variants`` is [(name, mk)] where mk(n) builds the n-fold chain."""
    rounds = int(os.environ.get(rounds_env, rounds_default))
    fns = {}
    for name, mk in variants:
        fns[name] = (mk(1), mk(1 + CHAIN))
        for f in fns[name]:
            jax.block_until_ready(f())  # compile now
        log(f"compiled {name}")

    def time_once(fn):
        ts = []
        for _ in range(ITERS):
            t0 = time.perf_counter()
            out = fn()
            jax.block_until_ready(out)
            ts.append(time.perf_counter() - t0)
        return min(ts)

    best = {name: float("inf") for name, _ in variants}
    for rd in range(rounds):
        for name, _ in variants:
            f1, fk = fns[name]
            t = (time_once(fk) - time_once(f1)) / CHAIN
            best[name] = min(best[name], t)
            log(f"  round {rd} {name}: {t*1e3:.2f} ms")
    return best


def marginal(make_chain):
    def timed(fn):
        out = fn()
        jax.block_until_ready(out)
        ts = []
        for _ in range(ITERS):
            t0 = time.perf_counter()
            out = fn()
            jax.block_until_ready(out)
            ts.append(time.perf_counter() - t0)
        return min(ts)

    t1 = timed(make_chain(1))
    tk = timed(make_chain(1 + CHAIN))
    return (tk - t1) / CHAIN


def main():
    which = set((os.environ.get("MB_WHICH") or
                 "scatter,elem,sort,onehot,i8,f32").split(","))
    dev = jax.devices()[0]
    log(f"device: {dev.platform} ({dev.device_kind}); N={N} R={R} E={E} CHAIN={CHAIN}")
    kind, member, actor, counter = gen_columns(N, R, E)
    pad = actor >= R
    actor_ix = np.minimum(actor, R - 1)
    seg = (member.astype(np.int64) * R + actor_ix).astype(np.int32)
    is_rm = (kind == 1) & ~pad
    seg2 = np.where(is_rm, seg + E * R, seg).astype(np.int32)
    vals = np.where(~pad, counter, 0).astype(np.int16)

    seg2_d = jax.device_put(seg2, dev)
    vals_d = jax.device_put(vals, dev)
    c0 = jax.device_put(np.zeros(R, np.int32), dev)
    a0 = jax.device_put(np.zeros((E, R), np.int32), dev)
    r0 = jax.device_put(np.zeros((E, R), np.int32), dev)

    # 1. fused i16 scatter alone, carry-anchored (offset added to values so
    # the scatter depends on the carry; values stay positive)
    def mk_scatter(n):
        @jax.jit
        def run():
            def body(carry, _):
                z = jnp.zeros((2 * E * R,), jnp.int16)
                both = z.at[seg2_d].max(vals_d + carry.astype(jnp.int16), mode="drop")
                return both.max().astype(jnp.int32) % 2, ()
            c, _ = jax.lax.scan(body, jnp.int32(0), None, length=n)
            return c
        return run

    if "scatter" in which:
        t = marginal(mk_scatter)
        log(f"scatter i16 alone: {t*1e3:.2f} ms  ({N/t/1e6:.0f}M rows/s)")

    # 2. elementwise plane pass: read add0/rm0 + new planes, write both
    def mk_elem(n):
        @jax.jit
        def run():
            def body(carry, _):
                a, r = carry
                an = jnp.maximum(a0, a + 1)
                rn = jnp.maximum(r0, r + 1)
                an = jnp.where(an > rn, an, 0)
                return (an, rn), ()
            carry, _ = jax.lax.scan(body, (a0, r0), None, length=n)
            return carry
        return run

    if "elem" in which:
        t = marginal(mk_elem)
        log(f"elementwise 2-plane pass: {t*1e3:.2f} ms")

    # 3. sort 1M rows by (key, counter)
    key_d = jax.device_put(seg2, dev)
    cnt_d = jax.device_put(counter, dev)

    def mk_sort(n):
        @jax.jit
        def run():
            def body(carry, _):
                k, c = jax.lax.sort((key_d + carry, cnt_d), num_keys=2)
                return k[0] % 2, ()
            c, _ = jax.lax.scan(body, jnp.int32(0), None, length=n)
            return c
        return run

    if "sort" in which:
        t = marginal(mk_sort)
        log(f"sort 1M x (key,counter): {t*1e3:.2f} ms")

    # 4. one-hot matmul prototype: per member-tile segment-max as
    #    A^T @ B over padded per-tile row chunks.  Uses sorted+deduped rows
    #    (dedup zeroes non-run-max), f32 MXU.  Prototype only measures the
    #    matmul+onehot cost on pre-binned data (binning cost = sort above).
    TILE_E = 8
    T = E // TILE_E
    CMAX = int(os.environ.get("MB_CMAX", 4096))  # rows per tile, padded
    # host-side binning for the prototype
    order = np.argsort(seg, kind="stable")
    smem, sact, scnt = member[order], actor_ix[order], counter[order].astype(np.int32)
    tile = smem // TILE_E
    rows_m = np.zeros((T, CMAX), np.int32)
    rows_a = np.zeros((T, CMAX), np.int32)
    rows_v = np.zeros((T, CMAX), np.float32)
    for t_ix in range(T):
        lo, hi = np.searchsorted(tile, [t_ix, t_ix + 1])
        n_t = min(hi - lo, CMAX)
        rows_m[t_ix, :n_t] = smem[lo:lo + n_t] % TILE_E
        rows_a[t_ix, :n_t] = sact[lo:lo + n_t]
        rows_v[t_ix, :n_t] = scnt[lo:lo + n_t]
    H = (R + 127) // 128
    rm_d = jax.device_put(rows_m, dev)
    ra_d = jax.device_put(rows_a, dev)
    rv_d = jax.device_put(rows_v, dev)

    @jax.jit
    def onehot_tile(m, a, v, bump):
        # A: (C, TILE_E*H) val * onehot(m*H + a_hi); B: (C, 128) onehot(a_lo)
        a_hi, a_lo = a // 128, a % 128
        mh = m * H + a_hi
        A = (mh[:, None] == jnp.arange(TILE_E * H)[None, :]) * (v + bump)[:, None]
        B = (a_lo[:, None] == jnp.arange(128)[None, :]).astype(jnp.float32)
        acc = A.T @ B  # (TILE_E*H, 128)
        return acc.reshape(TILE_E, H * 128)[:, :R]

    def mk_onehot(n):
        @jax.jit
        def run():
            def body(carry, _):
                out = jax.lax.map(
                    lambda t: onehot_tile(rm_d[t], ra_d[t], rv_d[t], carry),
                    jnp.arange(T), batch_size=64,
                )
                return out.max() % 2, ()
            c, _ = jax.lax.scan(body, jnp.float32(0), None, length=n)
            return c
        return run

    if "onehot" in which:
        t = marginal(mk_onehot)
        log(f"one-hot matmul f32 (T={T}, CMAX={CMAX}): {t*1e3:.2f} ms")

    # 5. int8 matmul probe: does lax.dot_general int8xint8->int32 compile+run fast?
    ai8 = jax.device_put(np.random.randint(0, 127, (4096, 4096), np.int8), dev)
    bi8 = jax.device_put(np.random.randint(0, 127, (4096, 4096), np.int8), dev)

    def mk_i8(n):
        @jax.jit
        def run():
            def body(carry, _):
                o = jax.lax.dot_general(
                    ai8, bi8, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.int32,
                ) + carry
                return o[0, 0], ()
            c, _ = jax.lax.scan(body, jnp.int32(0), None, length=n)
            return c
        return run

    if "i8" in which:
        try:
            t = marginal(mk_i8)
            gf = 2 * 4096**3 / t / 1e12
            log(f"int8 4096^3 matmul: {t*1e3:.2f} ms ({gf:.0f} Tops)")
        except Exception as e:
            log(f"int8 matmul failed: {e}")

    # 6. f32 4096^3 matmul for reference
    af = jax.device_put(np.random.rand(4096, 4096).astype(np.float32), dev)

    def mk_f32(n):
        @jax.jit
        def run():
            def body(carry, _):
                o = af @ (af + carry)
                return o[0, 0], ()
            c, _ = jax.lax.scan(body, jnp.float32(0), None, length=n)
            return c
        return run

    if "f32" in which:
        t = marginal(mk_f32)
        log(f"f32 4096^3 matmul: {t*1e3:.2f} ms ({2*4096**3/t/1e12:.0f} TFLOPs)")




def pallas_sections(which):
    """Round-3 additions: time the Pallas fold's XLA prologue (sort +
    dedup + edges) separately from the full fold, to locate the wall."""
    import jax
    import jax.numpy as jnp

    from bench import gen_columns
    from crdt_enc_tpu.ops.pallas_fold import (
        TILE_E, fold_cap, orset_fold_pallas,
    )

    dev = jax.devices()[0]
    kind, member, actor, counter = gen_columns(N, R, E)
    c0 = jax.device_put(np.zeros(R, np.int32), dev)
    a0 = jax.device_put(np.zeros((E, R), np.int32), dev)
    r0 = jax.device_put(np.zeros((E, R), np.int32), dev)
    rows = [jax.device_put(x, dev) for x in (kind, member, actor, counter)]
    tile_cap = fold_cap(member, E)

    if "prologue" in which:
        T = -(-E // TILE_E)

        def mk(n):
            @jax.jit
            def run():
                def body(carry, _):
                    shift = carry % jnp.int32(N)
                    k, m, a, c = (jnp.roll(x, shift) for x in rows)
                    pad = a >= R
                    a_ix = jnp.minimum(a, R - 1)
                    is_add = (k == 0) & ~pad
                    is_rm = (k == 1) & ~pad
                    tile = m // TILE_E
                    key = jnp.where(
                        is_add | is_rm,
                        (tile * 2 + is_rm) * (TILE_E * R)
                        + (m - tile * TILE_E) * R + a_ix,
                        T * 2 * TILE_E * R,
                    )
                    # cell-level replay gate lives in the kernel tail now
                    gv = jnp.where(is_add | is_rm, c, 0)
                    sk, sv = jax.lax.sort((key, gv), num_keys=2)
                    nxt = jnp.concatenate([sk[1:], jnp.full((1,), -1, sk.dtype)])
                    sv = jnp.where((sk != nxt), sv, 0)
                    bounds = jnp.arange(2 * T + 1, dtype=jnp.int32) * (TILE_E * R)
                    edges = jnp.searchsorted(sk, bounds).astype(jnp.int32)
                    return edges[0] + sv[0], ()
                out, _ = jax.lax.scan(body, jnp.int32(0), None, length=n)
                return out
            return run

        t = marginal(mk)
        log(f"pallas prologue (sort+dedup+edges): {t*1e3:.2f} ms")

    if "pallasfold" in which:
        def mk(n):
            @jax.jit
            def run():
                def body(carry, _):
                    shift = (carry[0][0] + carry[1][0, 0]) % jnp.int32(N)
                    k, m, a, c = (jnp.roll(x, shift) for x in rows)
                    out = orset_fold_pallas(
                        c0, a0, r0, k, m, a, c,
                        num_members=E, num_replicas=R, tile_cap=tile_cap,
                    )
                    return out, ()
                carry, _ = jax.lax.scan(
                    body, (c0, a0, r0), None, length=n
                )
                return carry
            return run

        t = marginal(mk)
        log(f"pallas full fold: {t*1e3:.2f} ms  ({N/t/1e6:.0f}M ops/s)")


def ablk_sections(which):
    """Round-4 phase profile of the ablk Pallas fold: where do 7.5ms go?

    Sections:
      sort1      — the 2-operand bitonic sort comparing ONLY the key
                   (num_keys=1) vs the production num_keys=2 sort
      ablkpro    — the full XLA prologue of the ablk path (key calc +
                   sort + dedup + searchsorted edges + padding)
      ablkscan   — scatter-phase marginals across kernel-body modes
                   (hi_mode x win_mode) and sub_rows, isolating the
                   per-chunk branch overhead and chunk-size sweet spot
    """
    import jax
    import jax.numpy as jnp

    from bench import gen_columns
    from crdt_enc_tpu.ops.pallas_fold import (
        LANE, TILE_E, fold_cap, orset_scatter_pallas,
    )

    dev = jax.devices()[0]
    log(f"device: {dev.platform} ({dev.device_kind}); N={N} R={R} E={E}")
    kind, member, actor, counter = gen_columns(N, R, E)
    rows = [jax.device_put(x, dev) for x in (kind, member, actor, counter)]
    tile_cap = fold_cap(member, E)
    log(f"tile_cap={tile_cap}, counter.max()={counter.max()}")

    key_np = (member.astype(np.int64) * R + np.minimum(actor, R - 1)) % (2**31 - 1)
    key_d = jax.device_put(key_np.astype(np.int32), dev)
    cnt_d = jax.device_put(counter, dev)

    if "sort1" in which:
        cnt16_d = jax.device_put(counter.astype(np.int16), dev)
        for nk, val, tag in (
            (1, cnt_d, "i32 val"),
            (2, cnt_d, "i32 val"),
            (2, cnt16_d, "i16 val"),
        ):
            def mk(n, nk=nk, val=val):
                @jax.jit
                def run():
                    def body(carry, _):
                        k, c = jax.lax.sort((key_d + carry, val), num_keys=nk)
                        return k[0] % 2, ()
                    c, _ = jax.lax.scan(body, jnp.int32(0), None, length=n)
                    return c
                return run

            t = marginal(mk)
            log(f"sort 1M rows, num_keys={nk}, {tag}: {t*1e3:.2f} ms")

    if "ablkpro" in which:
        # the exact prologue orset_scatter_pallas runs, minus pallas_call
        from crdt_enc_tpu.ops.pallas_fold import ablk_key_space_fits

        assert ablk_key_space_fits(E, R)
        Ep = -(-E // TILE_E) * TILE_E
        T = Ep // TILE_E
        H = -(-R // LANE)
        H_BLK = 16 if H > 8 else 8
        Hp = -(-H // H_BLK) * H_BLK
        A_BLK = Hp // H_BLK
        SEG = TILE_E * H_BLK * LANE
        n_segs = 2 * T * A_BLK

        def mk(n):
            @jax.jit
            def run():
                def body(carry, _):
                    k, m, a, c = rows
                    c = c + carry  # carry-anchor
                    pad = a >= R
                    a_ix = jnp.minimum(a, R - 1)
                    is_add = (k == 0) & ~pad
                    is_rm = (k == 1) & ~pad
                    tile = m // TILE_E
                    m_local = m - tile * TILE_E
                    plane = is_rm.astype(jnp.int32)
                    a_hi = a_ix // LANE
                    a_lo = a_ix - a_hi * LANE
                    blk = a_hi // H_BLK
                    a_hil = a_hi - blk * H_BLK
                    seg_id = (tile * 2 + plane) * A_BLK + blk
                    within = (m_local * H_BLK + a_hil) * LANE + a_lo
                    sentinel = n_segs * SEG
                    key = jnp.where(
                        is_add | is_rm, seg_id * SEG + within, sentinel
                    )
                    gval = jnp.where(is_add | is_rm, c, 0)
                    skey, sval = jax.lax.sort((key, gval), num_keys=2)
                    nxt = jnp.concatenate(
                        [skey[1:], jnp.full((1,), -1, skey.dtype)]
                    )
                    sval = jnp.where((skey != nxt) & (skey < sentinel), sval, 0)
                    bounds = jnp.arange(n_segs + 1, dtype=jnp.int32) * SEG
                    edges = jnp.searchsorted(skey, bounds).astype(jnp.int32)
                    return edges[0] + sval[0], ()
                out, _ = jax.lax.scan(body, jnp.int32(0), None, length=n)
                return out
            return run

        t = marginal(mk)
        log(f"ablk prologue (keys+sort+dedup+edges): {t*1e3:.2f} ms")

    if "ablkscan" in which:
        default_modes = [
            ("cond", "cond", 256, "bf16"),    # round-3 default
            # production default is ("cond", "select") — win_mode
            # "select" won the round-4 A/B and the wrappers default to it
            ("fused", "cond", 256, "bf16"),   # no hi-limb branch
            ("cond", "select", 256, "bf16"),  # no window branch
            ("fused", "select", 256, "bf16"), # fully branchless body
            ("fused", "select", 128, "bf16"),
            ("fused", "select", 512, "bf16"),
        ]
        round2_modes = [
            # round 2 of the profile: SUBK sweep under the round-1
            # winner (cond hi-limb, branchless window loads).  int8 was
            # tried and REJECTED: Mosaic cannot legalize the int8 vector
            # multiply in the one-hot build (arith.muli on vector<...xi8>,
            # 2026-07-31), so the MXU dtype stays bf16.
            ("cond", "select", 128, "bf16"),
            ("cond", "select", 512, "bf16"),
        ]
        round3_modes = [
            # round 3: accumulator layout under the winning config —
            # blocked = one contiguous 128-row add per chunk + an XLA
            # transpose, member = 8 strided slice-adds, free reshape
            ("cond", "select", 256, "bf16", "blocked"),
            ("cond", "select", 256, "bf16", "member"),
        ]
        round4_modes = [
            # round 4: key-only sort + in-kernel segmented run-max
            # (dedup_mode="kernel") vs the 2-key sort + XLA dedup, both
            # under the round-3 winner (blocked accumulator).  Repeated
            # A/B/A/B in ONE process: single-shot runs swung 4.5-6.1ms
            # on the same config, so only interleaved deltas count.
            ("cond", "select", 256, "bf16", "blocked", "kernel"),
            ("cond", "select", 256, "bf16", "blocked", "sorted"),
            ("cond", "select", 256, "bf16", "blocked", "kernel"),
            ("cond", "select", 256, "bf16", "blocked", "sorted"),
        ]
        round5_modes = [
            # round 5: dedup A/B under the PRODUCTION accumulator
            # (member-major) — round 4's A/B ran under blocked.
            # Interleaved A/B/A/B; only the deltas count.
            ("cond", "select", 256, "bf16", "member", "kernel"),
            ("cond", "select", 256, "bf16", "member", "sorted"),
            ("cond", "select", 256, "bf16", "member", "kernel"),
            ("cond", "select", 256, "bf16", "member", "sorted"),
        ]
        mb_round = os.environ.get("MB_ABLK_ROUND")
        mode_list = (
            round5_modes if mb_round == "5"
            else round4_modes if mb_round == "4"
            else round3_modes if mb_round == "3"
            else round2_modes if mb_round == "2"
            else default_modes
        )
        for hi_mode, win_mode, subk, dt, *rest in mode_list:
            acc = rest[0] if rest else "member"
            dd = rest[1] if len(rest) > 1 else "sorted"

            def mk(n, hi=hi_mode, win=win_mode, sr=subk, dt=dt, acc=acc,
                   dd=dd):
                @jax.jit
                def run():
                    def body(carry, _):
                        k, m, a, c = rows
                        out = orset_scatter_pallas(
                            k, m, a, c + carry, num_members=E,
                            num_replicas=R, tile_cap=tile_cap,
                            sub_rows=sr, hi_mode=hi, win_mode=win,
                            dot_impl=dt, acc_mode=acc, dedup_mode=dd,
                        )
                        # keep the anchor to {0,1}: counters must stay in
                        # the production range or the hi-limb branch
                        # frequency (and exactness) would drift
                        return out[0][0, 0] % 2, ()
                    o, _ = jax.lax.scan(body, jnp.int32(0), None, length=n)
                    return o
                return run

            try:
                t = marginal(mk)
                log(
                    f"ablk scatter hi={hi_mode} win={win_mode} "
                    f"SUBK={subk} dot={dt} acc={acc} dedup={dd}: "
                    f"{t*1e3:.2f} ms"
                )
            except Exception as e:
                log(
                    f"ablk scatter hi={hi_mode} win={win_mode} "
                    f"SUBK={subk} dot={dt} acc={acc} dedup={dd}: FAILED "
                    f"{type(e).__name__}: {e}"
                )


def fused_sections(which):
    """Round-5: the fused-tail fold (padded-plane carry) vs the unfused
    full fold, plus the hi_mode=skip/limb_bits=8 ablation."""
    import jax
    import jax.numpy as jnp

    from bench import gen_columns
    from crdt_enc_tpu.ops.pallas_fold import (
        fold_cap, orset_fold_pallas, orset_fold_pallas_fused,
        orset_pad_state,
    )

    dev = jax.devices()[0]
    kind, member, actor, counter = gen_columns(N, R, E)
    log(f"device: {dev.platform} ({dev.device_kind}); N={N} R={R} E={E} "
        f"counter.max()={counter.max()}")
    c0 = jax.device_put(np.zeros(R, np.int32), dev)
    a0 = jax.device_put(np.zeros((E, R), np.int32), dev)
    r0 = jax.device_put(np.zeros((E, R), np.int32), dev)
    rows = [jax.device_put(x, dev) for x in (kind, member, actor, counter)]
    tile_cap = fold_cap(member, E)
    skip_ok = counter.max() < 256

    def mk_fused(hi, lb, ret, h_blk=None, subk=None):
        from crdt_enc_tpu.ops.pallas_fold import SUB_ABLK, orset_retire
        sr = subk or SUB_ABLK

        def mk(n):
            @jax.jit
            def run():
                cp, ap, rp = orset_pad_state(
                    c0, a0, r0, num_members=E, num_replicas=R, h_blk=h_blk)

                def body(carry, _):
                    # fixed initial planes + carry-derived roll: the
                    # same marginal protocol as the pallasfold section
                    shift = (carry[0][0] + carry[1][0, 0]) % jnp.int32(N)
                    k, m, a, c = (jnp.roll(x, shift) for x in rows)
                    out = orset_fold_pallas_fused(
                        cp, ap, rp, k, m, a, c,
                        num_members=E, num_replicas=R, tile_cap=tile_cap,
                        hi_mode=hi, limb_bits=lb, retire_rm=ret,
                        h_blk=h_blk, sub_rows=sr,
                    )
                    return out, ()
                carry, _ = jax.lax.scan(body, (cp, ap, rp), None, length=n)
                if not ret:  # deferred chain: one finalize (cancels in
                    # the marginal — present in both chain lengths)
                    carry = (carry[0], carry[1],
                             orset_retire(carry[0], carry[2]))
                return carry
            return run
        return mk

    def mk_unfused(n):
        @jax.jit
        def run():
            def body(carry, _):
                shift = (carry[0][0] + carry[1][0, 0]) % jnp.int32(N)
                k, m, a, c = (jnp.roll(x, shift) for x in rows)
                out = orset_fold_pallas(
                    c0, a0, r0, k, m, a, c,
                    num_members=E, num_replicas=R, tile_cap=tile_cap,
                )
                return out, ()
            carry, _ = jax.lax.scan(body, (c0, a0, r0), None, length=n)
            return carry
        return run

    variants = [("unfused", mk_unfused),
                ("fused cond/7", mk_fused("cond", 7, True))]
    if skip_ok:
        variants += [
            ("fused skip/8 eager", mk_fused("skip", 8, True)),
            ("fused skip/8 defer", mk_fused("skip", 8, False)),
            ("fused skip/8 defer hblk32", mk_fused("skip", 8, False, 32)),
            ("fused skip/8 defer hblk80", mk_fused("skip", 8, False, 80)),
            ("fused skip/8 defer hblk32 subk512",
             mk_fused("skip", 8, False, 32, 512)),
        ]
    if os.environ.get("MB_FUSED_HBLK2") == "1" and skip_ok:
        # round-5 second sweep: larger actor blocks cut n_segs further
        # (48 → A_BLK=2, 64 → A_BLK=2 at R=10k) at the cost of taller
        # one-hots (384/512 rows — VPU/MXU still far from the wall)
        variants = [
            ("fused skip/8 defer hblk32", mk_fused("skip", 8, False, 32)),
            ("fused skip/8 defer hblk48", mk_fused("skip", 8, False, 48)),
            ("fused skip/8 defer hblk64", mk_fused("skip", 8, False, 64)),
        ]

    best = round_robin(variants)
    for name, _ in variants:
        t = best[name]
        log(f"BEST {name}: {t*1e3:.2f} ms ({N/t/1e6:.0f}M ops/s)")


def lww_sections(which):
    """Round-4 LWW kernel A/B: window-load cond vs select on the
    config-4 shape (1M rows, 1M keys)."""
    import jax
    import jax.numpy as jnp

    from crdt_enc_tpu.ops.lww import ts_split
    from crdt_enc_tpu.ops.pallas_lww import lww_fold_pallas, lww_tile_cap

    dev = jax.devices()[0]
    NK = int(os.environ.get("MB_LWW_KEYS", 1_000_000))
    RA, V = 10_000, 1 << 15
    rng = np.random.default_rng(5)
    key = rng.integers(0, NK, N, dtype=np.int32)
    hi, lo = ts_split(rng.integers(0, 10 ** 12, N))
    actor = rng.integers(0, RA, N, dtype=np.int32)
    value = rng.integers(0, V, N, dtype=np.int32)
    cap = lww_tile_cap(key, NK)
    log(f"device: {dev.platform}; LWW N={N} K={NK} tile_cap={cap}")
    cols = [jax.device_put(x, dev) for x in (key, hi, lo, actor, value)]

    from crdt_enc_tpu.ops.pallas_lww import lww_limbs

    lb = lww_limbs(hi, lo, actor, V)
    log(f"static limbs: {lb}")

    def mk_fold(wm, limbs):
        def mk(n):
            @jax.jit
            def run():
                def body(carry, _):
                    k, h, l, a, v = cols
                    out = lww_fold_pallas(
                        k, h, l, a, v + (carry % 2), num_keys=NK,
                        num_values=V, tile_cap=cap, win_mode=wm,
                        limbs=limbs,
                    )
                    return out[3][0], ()
                o, _ = jax.lax.scan(body, jnp.int32(0), None, length=n)
                return o
            return run
        return mk

    # the 4-operand sort alone (the kernel's XLA prologue wall candidate)
    def mk_sort(n):
        @jax.jit
        def run():
            def body(carry, _):
                k, h, l, a, v = cols
                av = a * V + (v + carry % 2)
                sk, sh, sl, sav = jax.lax.sort((k, h, l, av), num_keys=4)
                return sav[0] % 2, ()
            o, _ = jax.lax.scan(body, jnp.int32(0), None, length=n)
            return o
        return run

    variants = [
        ("sort4 only", mk_sort),
        ("lww cond dyn-limb", mk_fold("cond", None)),
        ("lww select dyn-limb", mk_fold("select", None)),
        ("lww cond static-limb", mk_fold("cond", lb)),
        ("lww select static-limb", mk_fold("select", lb)),
    ]
    best = round_robin(variants, rounds_default=4)
    for name, _ in variants:
        t = best[name]
        log(f"BEST {name}: {t*1e3:.2f} ms  ({N/t/1e6:.0f}M rows/s)")


if __name__ == "__main__":
    which = set((os.environ.get("MB_WHICH") or "").split(","))
    if which & {"fused"}:
        fused_sections(which)
    elif which & {"lwwscan"}:
        lww_sections(which)
    elif which & {"sort1", "ablkpro", "ablkscan"}:
        ablk_sections(which)
    elif which & {"prologue", "pallasfold"}:
        pallas_sections(which)
    else:
        main()
