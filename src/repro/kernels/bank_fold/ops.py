"""Dispatch glue: one plan round -> one fused megakernel launch.

:func:`make_fused_dispatch` turns a scheduler assignment (which ops run
on which instance) into a pure closure ``run(a, b) -> products`` that

  1. gathers each instance's assigned operand rows into a padded
     ``(N_INST, R, L)`` block (static numpy indices -- jit lowers them
     to constant gathers),
  2. runs :func:`.kernel.fused_bank_mul` ONCE -- the whole bank round is
     a single ``pallas_call``,
  3. scatters the valid rows back to batch order, and
  4. for signed designs, applies the shared two's-complement correction
     pass (:func:`repro.core.mcim.signed_correction`) on the unsigned
     products -- pure jnp, so the round still costs one kernel launch.

Padding rows re-gather op 0's operands; their products are computed and
dropped (never scattered), which keeps every block rectangular without
data-dependent control flow.
"""
from __future__ import annotations

import numpy as np
import jax.numpy as jnp

from repro.core import limbs as L
from repro.core.mcim import signed_correction
from repro.kernels import runtime
from repro.kernels.mcim_fold import batch_tile
from .geometry import super_geometry
from .kernel import fused_bank_mul


def fused_block_rows(assign) -> tuple:
    """(rows, tile_r) of the padded per-instance op blocks.

    ``rows`` is the per-instance row count after padding the largest
    assignment up to a :func:`batch_tile` multiple; ``tile_r`` the row
    tile the kernel grids over.
    """
    max_ops = max((len(ops) for ops in assign), default=0)
    max_ops = max(max_ops, 1)         # degenerate all-empty round
    tile_r, pad = batch_tile(max_ops)
    return max_ops + pad, tile_r


def launch_contract(configs, la: int, lb: int, rows: int = 64,
                    tile_r: int = None, table=None):
    """Static :class:`~repro.kernels.introspect.LaunchContract`.

    Declares the fused megakernel launch for a bank of ``configs``
    instances: the ``(row tile, instance, grid step)`` grid, the
    full-width scratch accumulator, the concrete SMEM window table and
    -- crucially -- which grid steps the super-geometry pads as *idle*
    (short-CT instances after their last real window), which the
    dataflow analyzer must prove are no-ops on scratch.

    ``table`` overrides the super-geometry's schedule table; the
    override flows into both the traced kernel and the declaration, so
    a corrupted table is analyzed exactly like a shipped one (this is
    how the property tests inject hazards).
    """
    import jax

    from repro.kernels.introspect import LaunchContract
    sg = super_geometry(configs, la, lb)
    if tile_r is None:
        tile_r, pad = batch_tile(rows)
        rows += pad
    n_inst = sg.n_instances
    a = jax.ShapeDtypeStruct((n_inst, rows, la), L.LIMB_DTYPE)
    b = jax.ShapeDtypeStruct((n_inst, rows, lb), L.LIMB_DTYPE)
    if table is None:
        table = sg.table()
    table = np.asarray(table, np.int32)
    tbl = jnp.asarray(table)
    max_steps = sg.max_steps

    def fn(av, bv):
        return fused_bank_mul(av, bv, tbl, max_steps=max_steps,
                              tile_r=tile_r, interpret=True)

    idle = tuple((None, i, j) for i, geo in enumerate(sg.rows)
                 for j in range(geo.ct_run, max_steps))
    from .geometry import vmem_bytes_per_step
    return LaunchContract(
        name=(f"bank_fold[la={la},lb={lb},n={n_inst},"
              f"steps={max_steps}]"),
        fn=fn, args=(a, b),
        grid=(rows // tile_r, n_inst, max_steps),
        scratch_shapes=(((tile_r, la + lb), "uint32"),),
        vmem_model_bytes=vmem_bytes_per_step(la, lb, tile_r, n_inst,
                                             max_steps),
        idle_steps=idle, table=table,
        meta={"super_geometry": sg, "tile_r": tile_r, "rows": rows})


def make_fused_dispatch(assign, configs, la: int, lb: int, batch: int, *,
                        signed: bool = False):
    """Build the one-launch dispatch closure for one (schedule, batch).

    ``assign`` is the scheduler's static assignment (tuple per instance
    of op indices into the batch), ``configs`` the flat instance list
    aligned with it.  The returned closure maps ``(B, LA) x (B, LB) ->
    (B, LA+LB)`` limb products, bit-exact vs the per-instance path.
    Its ``kernel_rows`` attribute is the ``N_INST x R`` rows the kernel
    computes, padding included.
    """
    sg = super_geometry(configs, la, lb)
    n_inst = sg.n_instances
    if len(assign) != n_inst:
        raise ValueError(
            f"assignment covers {len(assign)} instances, plan has {n_inst}")
    rows, tile_r = fused_block_rows(assign)

    # static gather: padded rows re-fetch op 0 (computed, never scattered)
    gather = np.zeros((n_inst, rows), np.int32)
    inst_ids, row_ids, op_ids = [], [], []
    for i, ops in enumerate(assign):
        for r, op in enumerate(ops):
            gather[i, r] = op
            inst_ids.append(i)
            row_ids.append(r)
            op_ids.append(op)
    inst_ids = np.asarray(inst_ids, np.int32)
    row_ids = np.asarray(row_ids, np.int32)
    op_ids = np.asarray(op_ids, np.int32)

    table = jnp.asarray(sg.table())
    max_steps = sg.max_steps
    interpret = runtime.interpret_mode()

    def run(a, b):
        a_blocks = a[gather]                   # (N_INST, R, LA)
        b_blocks = b[gather]                   # (N_INST, R, LB)
        prod = fused_bank_mul(a_blocks, b_blocks, table,
                              max_steps=max_steps, tile_r=tile_r,
                              interpret=interpret)
        out = jnp.zeros((batch, la + lb), L.LIMB_DTYPE)
        out = out.at[op_ids].set(prod[inst_ids, row_ids])
        if signed:
            out = signed_correction(a, b, out)
        return out

    run.kernel_rows = n_inst * rows
    return run
