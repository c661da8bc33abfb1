"""Pallas TPU kernel: single-pass S-way ORSet merge.

``orset_merge_many`` (ops/orset.py) reduces S stacked states as a
⌈log2 S⌉-level tree; every level reads two plane sets from HBM and writes
one, so total HBM traffic is ≈3× the input.  Snapshot-heavy compactions
(hundreds of state files, SURVEY.md §3.3 HOT LOOP #1) are pure bandwidth,
so this kernel streams all S states through VMEM **once**: grid =
(member-tiles, S), the output block for a member tile stays resident in
VMEM across the S steps, and each step applies exactly the pairwise
clock-filter merge + normalization of ``orset_merge`` (left fold; legal
for any order because merge is associative — tests/test_crdt_laws.py).

Inputs are the stacked planes ``clocks (S, R) int32``, ``adds/rms
(S, E, R) int32``.  The wrapper precomputes the running clock prefix-max
(cummax over S) host-of-kernel — it is S×R, negligible — because step s
of the fold needs ``clock(acc after s-1)`` for the survival rule.

Tests on host backends pass ``interpret=True`` themselves (slow);
``orset_merge_many`` only routes here on TPU by default, compiled.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TILE_E = 8  # sublane tile for the member axis (int32 min tile is (8, 128))
LANE = 128


def _merge_step_kernel(clocks_ref, prev_run_ref, run_ref, adds_ref, rms_ref,
                       out_add_ref, out_rm_ref):
    s = pl.program_id(1)

    @pl.when(s == 0)
    def _():
        out_add_ref[...] = adds_ref[0]
        out_rm_ref[...] = rms_ref[0]

    @pl.when(s > 0)
    def _():
        from .orset import merge_rule

        # clock blocks arrive (1, 1, R) — the singleton middle axis exists
        # only to satisfy the TPU (8,128) tiling rule on the last two block
        # dims; [0] yields (1, R), broadcasting over the member sublanes.
        # prev_run is the clock of the accumulated left fold, run the
        # merged clock after this step
        add, rm = merge_rule(
            prev_run_ref[0], out_add_ref[...], out_rm_ref[...],
            clocks_ref[0], adds_ref[0], rms_ref[0],
            run_ref[0],
        )
        out_add_ref[...] = add
        out_rm_ref[...] = rm


def _pad_to(x, axis, mult):
    n = x.shape[axis]
    padn = (-n) % mult
    if padn == 0:
        return x
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, padn)
    return jnp.pad(x, pads)


@partial(jax.jit, static_argnames=("interpret",))
def orset_merge_many_pallas(clocks, adds, rms, *, interpret: bool = False):
    """Merge S stacked ORSet states in one HBM pass.  Returns
    ``(clock, add, rm)`` identical to ``orset_merge_many``."""
    clocks = jnp.asarray(clocks, jnp.int32)
    adds = jnp.asarray(adds, jnp.int32)
    rms = jnp.asarray(rms, jnp.int32)
    S, E, R = adds.shape

    run = jax.lax.cummax(clocks, axis=0)  # (S, R) running merged clock
    prev_run = jnp.concatenate([jnp.zeros((1, R), jnp.int32), run[:-1]], axis=0)

    # pad E to the sublane tile and R to the lane width; padded members and
    # replicas are all-zero — absent everywhere, invisible to the merge rule
    adds_p = _pad_to(_pad_to(adds, 1, TILE_E), 2, LANE)
    rms_p = _pad_to(_pad_to(rms, 1, TILE_E), 2, LANE)
    clocks_p = _pad_to(clocks, 1, LANE)
    run_p = _pad_to(run, 1, LANE)
    prev_run_p = _pad_to(prev_run, 1, LANE)
    Ep, Rp = adds_p.shape[1], adds_p.shape[2]

    # clocks get a singleton middle axis: a (1, 1, Rp) block's last two
    # dims equal the array dims, which the TPU tiling rule accepts (a
    # (1, Rp) block over (S, Rp) does not — 1 is neither divisible by 8
    # nor equal to S)
    clocks_p = clocks_p[:, None, :]
    run_p = run_p[:, None, :]
    prev_run_p = prev_run_p[:, None, :]

    grid = (Ep // TILE_E, S)
    clock_spec = pl.BlockSpec(
        (1, 1, Rp), lambda e, s: (s, 0, 0), memory_space=pltpu.VMEM
    )
    plane_spec = pl.BlockSpec(
        (1, TILE_E, Rp), lambda e, s: (s, e, 0), memory_space=pltpu.VMEM
    )
    out_spec = pl.BlockSpec(
        (TILE_E, Rp), lambda e, s: (e, 0), memory_space=pltpu.VMEM
    )
    out_add, out_rm = pl.pallas_call(
        _merge_step_kernel,
        grid=grid,
        in_specs=[clock_spec, clock_spec, clock_spec, plane_spec, plane_spec],
        out_specs=[out_spec, out_spec],
        out_shape=[
            jax.ShapeDtypeStruct((Ep, Rp), jnp.int32),
            jax.ShapeDtypeStruct((Ep, Rp), jnp.int32),
        ],
        interpret=interpret,
    )(clocks_p, prev_run_p, run_p, adds_p, rms_p)
    return run[-1], out_add[:E, :R], out_rm[:E, :R]
