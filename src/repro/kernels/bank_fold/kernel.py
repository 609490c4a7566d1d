"""Pallas TPU megakernel: a whole bank round in ONE launch.

``Bank.execute`` used to issue one ``pallas_call`` per instance per
round -- a TP=3.5 plan (3 Star + 1 CT=2 FB) paid 4 launches per cycle
where the paper's folded silicon is a single clocked datapath.  Folding
theory (Möller et al., "Model-based Hardware Design for FPGAs using
Folding Transformations") says a resource-shared schedule should
compile to *one* time-multiplexed circuit; this kernel is that circuit
for the TPU: the plan's static schedule flattened into a single Pallas
grid of ``(row_tile, instance, grid_step)``.

Structure per grid step (see :mod:`.geometry` for the shape contract):

  schedule table -> SMEM scalar prefetch: ``(lo, hi)`` B-limb window of
                    (instance, step); ``(0, 0)`` masks idle steps of
                    short-CT instances (the heterogeneity handling)
  PPM            -> static limb loop of 16x16->32 lane products over
                    the *masked* B operand -- limbs sit at absolute
                    positions, so columns land at their final weights
                    without any per-step shift
  compressor     -> full-width uint32 carry-save accumulator in VMEM
                    scratch (the fused analogue of the FF register
                    file), carries deferred
  final adder    -> one carry-propagation pass on the last grid step,
                    retiring the whole LA+LB product

Grid dimensions 1 and 2 are sequential on TPU: instances stream through
the same datapath one after another, each folding over its own CT
windows -- many multiplier instances share one circuit, which is the
fused generalization of the paper's resource-sharing use case.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.mcim_fold.kernel import carry_normalize, ppm_columns


def _bank_kernel(tbl_ref, a_ref, b_ref, out_ref, acc_ref, *,
                 la, lb, max_steps):
    """One grid step = one clock cycle of one instance's folded pass."""
    i = pl.program_id(1)                    # instance index
    j = pl.program_id(2)                    # grid step within the fold
    lo = tbl_ref[i, j, 0]                   # this step's B-limb window
    hi = tbl_ref[i, j, 1]                   # (lo == hi: masked idle step)
    a = a_ref[0]                            # (TR, LA) canonical limbs
    b = b_ref[0]                            # (TR, LB) canonical limbs

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # ---- window mask: idle steps and out-of-window limbs contribute 0 ----
    limb = jax.lax.broadcasted_iota(jnp.int32, (1, lb), 1)
    mask = ((limb >= lo) & (limb < hi)).astype(jnp.uint32)
    bm = b * mask

    # ---- PPM + compressor: masked column sums, carries deferred ---------
    acc_ref[...] = acc_ref[...] + ppm_columns(a, bm, la + lb)

    # ---- last step: single final-adder pass retires the product ---------
    @pl.when(j == max_steps - 1)
    def _finish():
        out_ref[0] = carry_normalize(acc_ref[...], la + lb)


@functools.partial(jax.jit,
                   static_argnames=("max_steps", "tile_r", "interpret"))
def fused_bank_mul(a_blocks: jax.Array, b_blocks: jax.Array,
                   table: jax.Array, *, max_steps: int, tile_r: int,
                   interpret: bool = True) -> jax.Array:
    """One launch: (N_INST, R, LA) x (N_INST, R, LB) -> (N_INST, R, LA+LB).

    ``table`` is the (N_INST, max_steps, 2) int32 schedule table from
    :meth:`.geometry.SuperGeometry.table`, prefetched into SMEM so the
    kernel body reads its window scalars before touching VMEM.  ``R``
    must be divisible by ``tile_r``; rows are independent
    multiplications (an instance's assigned ops, padded), so row tiles
    stream through the same folded datapath.
    """
    n_inst, rows, la = a_blocks.shape
    lb = b_blocks.shape[-1]
    if rows % tile_r:
        raise ValueError(f"rows {rows} not divisible by tile {tile_r}")
    if table.shape != (n_inst, max_steps, 2):
        raise ValueError(f"schedule table {table.shape} does not match "
                         f"{(n_inst, max_steps, 2)}")
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(rows // tile_r, n_inst, max_steps),
        in_specs=[
            pl.BlockSpec((1, tile_r, la), lambda r, i, j, tbl: (i, r, 0)),
            pl.BlockSpec((1, tile_r, lb), lambda r, i, j, tbl: (i, r, 0)),
        ],
        out_specs=pl.BlockSpec((1, tile_r, la + lb),
                               lambda r, i, j, tbl: (i, r, 0)),
        scratch_shapes=[pltpu.VMEM((tile_r, la + lb), jnp.uint32)],
    )
    kernel = functools.partial(_bank_kernel, la=la, lb=lb,
                               max_steps=max_steps)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_inst, rows, la + lb), jnp.uint32),
        interpret=interpret,
    )(table, a_blocks, b_blocks)
