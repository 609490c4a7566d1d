"""Dispatch glue: one plan round -> one fused megakernel launch.

:func:`make_fused_dispatch` turns a scheduler assignment (how many ops
each instance runs) into a pure closure ``run(a, b) -> products`` that

  1. cuts each instance's operand rows out of the batch as one
     contiguous run of static slices -- instance i takes ops
     ``[s_i, s_i + c_i)``, ``c_i = len(assign[i])``, in instance
     order -- into a padded ``(N_INST, R, L)`` block,
  2. runs :func:`.kernel.fused_bank_mul` ONCE -- the whole bank round is
     a single ``pallas_call``,
  3. joins each instance's first ``c_i`` product rows back in batch
     order (static slices and one concatenate), and
  4. for signed designs, applies the shared two's-complement correction
     pass (:func:`repro.core.mcim.signed_correction`) on the unsigned
     products -- pure jnp, so the round still costs one kernel launch.

Which instance computes which op does not change a product (every
instance's windows cover all of B), so the device gives instance i a
contiguous run of the scheduler's count rather than the scheduler's op
indices: the round holds no gather and no scatter.  Padding rows of an
instance's block are the next instance's operands (or zeros past the
batch); their products are computed and dropped, which keeps every
block rectangular without data-dependent control flow.
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
    aligned with it; only its per-instance counts reach the device.  The
    returned closure maps ``(B, LA) x (B, LB) -> (B, LA+LB)`` limb
    products, bit-exact vs the per-instance path.  Its ``kernel_rows``
    attribute is the ``N_INST x R`` rows the kernel computes, padding
    included.
    """
    sg = super_geometry(configs, la, lb)
    n_inst = sg.n_instances
    if len(assign) != n_inst:
        raise ValueError(
            f"assignment covers {len(assign)} instances, plan has {n_inst}")
    counts = [len(ops) for ops in assign]
    if sum(counts) != batch:
        raise ValueError(
            f"assignment holds {sum(counts)} ops, batch has {batch}")
    rows, tile_r = fused_block_rows(assign)
    starts = np.cumsum([0] + counts[:-1]).tolist()
    tail = starts[-1] + rows - batch       # the last window's rows past B

    table = jnp.asarray(sg.table())
    max_steps = sg.max_steps
    interpret = runtime.interpret_mode()

    # The windows are cut from the transposed (L, B) operand and the
    # products joined as (L, B), both as 2-D arrays: a (B, L) array with
    # few limbs keeps B on the TPU's lanes, so these slices are dense and
    # the one transpose into (and out of) the kernel's limb-minor blocks
    # is the only full pass over them.  (Slicing the 3-D blocks instead
    # compiles, for the v5e, to a cut of the lane-padded blocks ahead of
    # the transpose: one more full pass.)
    def blocks(x):                         # (B, L) -> (N_INST, R, L)
        width = x.shape[-1]
        xt = jnp.pad(x.T, ((0, 0), (0, tail)))
        runs = jnp.concatenate([xt[:, s:s + rows] for s in starts])
        return runs.reshape(n_inst, width, rows).transpose(0, 2, 1)

    def run(a, b):
        prod = fused_bank_mul(blocks(a), blocks(b), table,
                              max_steps=max_steps, tile_r=tile_r,
                              interpret=interpret)
        width = la + lb
        prod_t = prod.transpose(0, 2, 1).reshape(n_inst * width, rows)
        out = jnp.concatenate(
            [prod_t[i * width:(i + 1) * width, :c]
             for i, c in enumerate(counts)], axis=1).T
        if signed:
            out = signed_correction(a, b, out)
        return out

    run.kernel_rows = n_inst * rows
    return run
