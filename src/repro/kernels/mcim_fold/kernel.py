"""Pallas TPU kernel: batched multi-cycle folded big-integer multiply.

TPU adaptation of the paper's Feedback (FB) architecture (Fig. 1).  The
hardware folds one M x (N/CT) PPM over CT clock cycles; the TPU kernel
folds one (TILE_B, LA) x (TILE_B, CHUNK) limb-product pass over CT grid
steps.  The mapping of hardware stages to kernel structure:

  PPM          -> static limb-loop of 16x16->32 lane products (VPU ops,
                  TILE_B integers per vector op)
  compressor   -> uint32 column-sum accumulator in VMEM scratch,
                  carries deferred (carry-save)
  final adder  -> static carry-propagation loop, run once per grid step
                  over the (LA + CHUNK + 1)-limb window (the paper's
                  M + N/CT adder), retiring CHUNK limbs per step

"Area" in hardware corresponds to the *per-step VMEM working set* here:
it scales with LA + LB/CT instead of LA + LB, so CT folds the footprint
exactly the way the silicon PPM is folded.  Grid dimension 1 (the cycle
axis) is sequential on TPU, which is what lets the scratch accumulator
play the role of the feedback register.

The grid is (batch_tiles, CT): batch tiles stream through the same
folded datapath, i.e. many independent multiplications share one
"multiplier instance", the paper's resource-sharing use case.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# single source of truth for MASK / RADIX_BITS / LIMB_DTYPE: core.limbs
# (the verifier's interval bounds are authoritative only because every
# kernel shares the core constants instead of re-declaring them)
from repro.core import limbs as L


@dataclasses.dataclass(frozen=True)
class FoldGeometry:
    """Static shape contract of one folded schedule.

    Derived in exactly one place so the kernel plumbing, the VMEM
    area model (:func:`.ops.vmem_bytes_per_step`) and the static
    verifier (:mod:`repro.verify.contracts`) can never disagree.
    """
    schedule: str       # fb | ff | karatsuba
    la: int             # A limbs
    lb: int             # B limbs
    chunk: int          # B limbs consumed per grid cycle
    ct_run: int         # grid cycles actually folded (<= requested CT)
    scratch_width: int  # VMEM accumulator columns
    out_width: int      # retired product limbs

    @property
    def b_windows(self) -> tuple:
        """Per-cycle (lo, hi) B-limb windows the PPM consumes (fb/ff)."""
        return tuple((t * self.chunk, (t + 1) * self.chunk)
                     for t in range(self.ct_run))


def fold_geometry(la: int, lb: int, ct: int,
                  schedule: str = "fb") -> FoldGeometry:
    """Static geometry of a folded schedule for (LA, LB) limb operands."""
    if schedule == "karatsuba":
        if ct != 3:
            raise ValueError("the folded Karatsuba schedule is fixed to CT=3")
        n = max(la, lb)
        n += n % 2                               # even split point
        return FoldGeometry(schedule=schedule, la=la, lb=lb,
                            chunk=n // 2 + 1, ct_run=3,
                            scratch_width=2 * n, out_width=la + lb)
    if schedule not in ("fb", "ff"):
        raise ValueError(f"schedule must be fb, ff or karatsuba, "
                         f"got {schedule!r}")
    chunk = -(-lb // ct)
    # CT > LB leaves trailing all-zero chunks: fold only the LB real
    # limbs (the silicon would idle those cycles; the extra cycles exist
    # in the throughput accounting, not in the datapath).
    ct_run = -(-lb // chunk)
    if schedule == "fb":
        scratch = la + chunk + 1                 # M + N/CT folded window
    else:
        scratch = la + ct_run * chunk + 1        # full FF register file
    return FoldGeometry(schedule=schedule, la=la, lb=lb, chunk=chunk,
                        ct_run=ct_run, scratch_width=scratch,
                        out_width=la + lb)


def place(x, shift: int, width: int):
    """``x``'s columns moved up by ``shift`` inside ``width`` columns.

    A static pad, so placing partial products is a full-width vector add:
    the Pallas TPU lowering has no scatter-add (``.at[...].add``).
    """
    return jnp.pad(x, ((0, 0), (shift, width - shift - x.shape[1])))


def ppm_columns(a, b, width: int):
    """PPM + compressor: carry-save column sums of ``a * b``.

    One exact 16x16->32 lane product per B limb over the whole tile (one
    "row" of the hardware PPM array); its low and high halves land at
    columns ``jj`` and ``jj + 1``.  Carries stay deferred.
    """
    cols = None
    for jj in range(b.shape[1]):
        p = a * b[:, jj:jj + 1]
        row = (place(p & L.MASK, jj, width)
               + place(p >> L.RADIX_BITS, jj + 1, width))
        cols = row if cols is None else cols + row
    return cols


def carry_normalize(cols, out_limbs: int):
    """Final adder: ``out_limbs`` canonical limbs out of column sums."""
    carry = jnp.zeros((cols.shape[0],), jnp.uint32)
    outs = []
    for k in range(out_limbs):
        tot = (cols[:, k] if k < cols.shape[1]
               else jnp.zeros_like(carry)) + carry
        outs.append(tot & L.MASK)
        carry = tot >> L.RADIX_BITS
    return jnp.stack(outs, axis=1)


def _fb_kernel(a_ref, b_ref, out_ref, acc_ref, *, la, lb, ct, chunk):
    """One grid step = one MCIM clock cycle for a tile of multiplications."""
    j = pl.program_id(1)                       # cycle index within CT
    width = la + chunk + 1                     # M + N/CT (+carry) window
    out_w = la + lb

    a = a_ref[...]                             # (TB, LA) canonical limbs
    b = b_ref[0]                               # (TB, CHUNK) this cycle's chunk

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        out_ref[...] = jnp.zeros_like(out_ref)

    # ---- PPM + compressor into the fed-back window, then the 1CA --------
    normalized = carry_normalize(acc_ref[...] + ppm_columns(a, b, width),
                                 width)
    # ---- feedback register: the window shifted down by CHUNK limbs ------
    acc_ref[...] = place(normalized[:, chunk:], 0, width)

    # ---- retire CHUNK low limbs at t*CHUNK; the last cycle retires every
    # remaining high limb.  One branch per cycle keeps the lane offsets
    # static (the TPU lowering refuses dynamic lane-offset stores).
    for t in range(ct):
        lo = t * chunk
        n = chunk if t < ct - 1 else out_w - lo

        @pl.when(j == t)
        def _retire(lo=lo, n=n):
            out_ref[...] = out_ref[...] + place(normalized[:, :n], lo, out_w)


def _ff_kernel(a_ref, b_ref, out_ref, acc_ref, *, la, lb, ct, chunk):
    """Feed-Forward (FF) schedule, paper Fig. 2.

    No feedback shift: every grid step runs the shared PPM over this
    cycle's B chunk and adds the carry-save columns into a *full-width*
    accumulator at limb offset j*chunk (the "register file" holding all
    CT partial results).  One final adder pass retires the whole product
    on the last cycle.  The working set is the full LA+LB window --
    exactly the paper's FF area trade: no feedback loop (pipelineable,
    any final adder) in exchange for CT-fold register growth.
    """
    j = pl.program_id(1)                       # cycle index within CT
    width = la + ct * chunk + 1

    a = a_ref[...]                             # (TB, LA) canonical limbs
    b = b_ref[0]                               # (TB, CHUNK) this cycle's chunk

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # ---- shared PPM: carry-save columns of a * b_chunk ------------------
    cols = ppm_columns(a, b, la + chunk + 1)

    # ---- 2*CT:2 compressor: add into the register file at j*chunk -------
    # (one branch per cycle: static lane offsets, see _fb_kernel)
    for t in range(ct):
        @pl.when(j == t)
        def _compress(t=t):
            acc_ref[...] = acc_ref[...] + place(cols, t * chunk, width)

    # ---- last cycle: single final-adder pass over the full width --------
    @pl.when(j == ct - 1)
    def _finish():
        out_ref[...] = carry_normalize(acc_ref[...], la + lb)


def _kara_kernel(a_ref, b_ref, out_ref, acc_ref, *, la, lb, n, half):
    """Folded Karatsuba schedule (paper Fig. 3), CT=3: the temporal fold.

    One *shared* half-width PPM runs once per grid step on the cycle's
    operand pair -- cycle 0: (A0, B0) -> T0, cycle 1: (A1, B1) -> T1,
    cycle 2: (A0+A1, B0+B1) -> T2 -- and a compressor feedback loop
    accumulates the placed/complemented terms

        P = T0 + T1<<2h + (T2 - T1 - T0)<<h

    in the VMEM scratch accumulator (subtractions as NOT+1 columns, the
    2**(16*width) wraps vanishing in the final truncation).  The final
    adder runs once, on the last cycle -- in contrast to
    ``karatsuba_ppm`` (the *spatial* fold: three PPMs in one step), this
    kernel keeps exactly one PPM's worth of compute live per step, the
    TPU analogue of the paper's shared-PPM silicon.
    """
    j = pl.program_id(1)                       # Karatsuba cycle, 0..2
    hp = half + 1                              # shared-PPM port width
    width = 2 * n
    a = a_ref[...]                             # (TB, n) padded canonical limbs
    b = b_ref[...]
    tb = a.shape[0]

    a0, a1 = a[:, :half], a[:, half:]
    b0, b1 = b[:, :half], b[:, half:]
    sa = carry_normalize(a0 + a1, hp)          # A0+A1, normalized to hp limbs
    sb = carry_normalize(b0 + b1, hp)

    # this cycle's operands for the ONE shared PPM
    av = jnp.where(j == 0, place(a0, 0, hp),
                   jnp.where(j == 1, place(a1, 0, hp), sa))
    bv = jnp.where(j == 0, place(b0, 0, hp),
                   jnp.where(j == 1, place(b1, 0, hp), sb))

    # shared PPM + its 1CA: T_j normalized to 2*hp canonical limbs
    t = carry_normalize(ppm_columns(av, bv, 2 * hp), 2 * hp)

    def put(shift):
        return place(t[:, :min(2 * hp, width - shift)], shift, width)

    def neg_put(shift):
        # NOT+1 two's complement of (T_j << shift) mod 2**(16*width)
        inv = jnp.full((tb, width), jnp.uint32(L.MASK)) - put(shift)
        return inv + place(jnp.ones((tb, 1), jnp.uint32), 0, width)

    # compressor feedback: accumulate this cycle's placed terms
    @pl.when(j == 0)
    def _t0():                                 # +T0<<0  -T0<<h
        acc_ref[...] = put(0) + neg_put(half)

    @pl.when(j == 1)
    def _t1():                                 # +T1<<2h -T1<<h
        acc_ref[...] = acc_ref[...] + put(2 * half) + neg_put(half)

    # last cycle: +T2<<h, then the single final-adder pass
    @pl.when(j == 2)
    def _t2():
        out_ref[...] = carry_normalize(acc_ref[...] + put(half), la + lb)


def _kara_fold_call(a, b, tile_b, interpret):
    """pallas_call plumbing for the folded Karatsuba CT=3 schedule."""
    bsz, la = a.shape
    lb = b.shape[-1]
    geo = fold_geometry(la, lb, 3, "karatsuba")
    n = geo.scratch_width // 2                  # operands padded even
    a = jnp.pad(a, ((0, 0), (0, n - la)))
    b = jnp.pad(b, ((0, 0), (0, n - lb)))
    kernel = functools.partial(_kara_kernel, la=la, lb=lb, n=n, half=n // 2)
    return pl.pallas_call(
        kernel,
        grid=(bsz // tile_b, geo.ct_run),
        in_specs=[
            pl.BlockSpec((tile_b, n), lambda i, j: (i, 0)),
            pl.BlockSpec((tile_b, n), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((tile_b, geo.out_width), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((bsz, geo.out_width), jnp.uint32),
        scratch_shapes=[pltpu.VMEM((tile_b, geo.scratch_width), jnp.uint32)],
        interpret=interpret,
    )(a, b)


@functools.partial(jax.jit,
                   static_argnames=("ct", "tile_b", "schedule", "interpret"))
def mcim_fold_mul(a: jax.Array, b: jax.Array, *, ct: int = 2,
                  tile_b: int = 256, schedule: str = "fb",
                  interpret: bool = True) -> jax.Array:
    """Batched folded multiply: (B, LA) x (B, LB) -> (B, LA+LB) limbs.

    ``schedule`` picks the paper architecture: "fb" (feedback loop,
    1/CT-width accumulator), "ff" (feed-forward register file, single
    final adder) or "karatsuba" (shared sub-PPM over the fixed CT=3
    Karatsuba fold).  For fb/ff any CT >= 1 folds; the planner emits CT
    in {1, 2, 3, 4, 6} (+8, 12 for deep fractional combinations).

    interpret=True runs the kernel body on CPU for validation; on a real
    TPU pass interpret=False.
    """
    if schedule not in ("fb", "ff", "karatsuba"):
        raise ValueError(
            f"schedule must be fb, ff or karatsuba, got {schedule!r}")
    if schedule == "karatsuba":
        if ct != 3:
            raise ValueError("the folded Karatsuba schedule is fixed to CT=3")
        bsz = a.shape[0]
        tile_b = min(tile_b, bsz)
        if bsz % tile_b:
            raise ValueError(f"batch {bsz} not divisible by tile {tile_b}")
        return _kara_fold_call(a, b, tile_b, interpret)
    if schedule == "ff" and ct < 2:
        raise ValueError("FF is a multi-cycle design: ct >= 2")
    bsz, la = a.shape
    lb = b.shape[-1]
    geo = fold_geometry(la, lb, ct, schedule)
    chunk, ct_run = geo.chunk, geo.ct_run
    # chunk-major B, (CT, B, CHUNK): each cycle's block is one whole
    # (tile, CHUNK) slab, a lane-legal TPU block for any CHUNK
    b = jnp.pad(b, ((0, 0), (0, chunk * ct_run - lb)))
    b = b.reshape(bsz, ct_run, chunk).transpose(1, 0, 2)
    tile_b = min(tile_b, bsz)
    if bsz % tile_b:
        raise ValueError(f"batch {bsz} not divisible by tile {tile_b}")

    body = _fb_kernel if schedule == "fb" else _ff_kernel
    kernel = functools.partial(body, la=la, lb=lb, ct=ct_run, chunk=chunk)
    return pl.pallas_call(
        kernel,
        grid=(bsz // tile_b, ct_run),
        in_specs=[
            pl.BlockSpec((tile_b, la), lambda i, j: (i, 0)),
            pl.BlockSpec((1, tile_b, chunk), lambda i, j: (j, i, 0)),
        ],
        out_specs=pl.BlockSpec((tile_b, geo.out_width), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((bsz, geo.out_width), jnp.uint32),
        scratch_shapes=[pltpu.VMEM((tile_b, geo.scratch_width), jnp.uint32)],
        interpret=interpret,
    )(a, b)
