"""Static dataflow analyzer for every Pallas launch in the tree.

The folded schedules are only correct if their *memory* behavior is:
the fused megakernel accumulates into a shared VMEM scratch ref across
a ``(row tile, instance, grid step)`` grid with idle-step masking and a
scalar-prefetch window table -- exactly where a silent read of
uninitialized scratch, a write-after-write between instances, or an
out-of-bounds window would corrupt products without any test noticing
(a wrong schedule can still be bit-exact on the batches a test happens
to draw).  This module proves four properties per launch *without
executing it*, by abstract interpretation of the traced kernel jaxpr:

  hazards    per-grid-step read/write sets over scratch/output refs:
             no read-before-first-write within a run (a maximal
             sequence of steps sharing output blocks), no two runs
             colliding on the same output block (WAW between
             instances), and declared-idle steps provably no-ops on
             scratch (zero/no-op propagation through the mask);
  bounds     every BlockSpec index-map output lands inside the padded
             operand extents for every grid step, and every
             scalar-prefetch window ``(lo, hi)`` respects the
             super-geometry (:func:`check_window_table`);
  vmem       the measured per-step byte residency obeys the package's
             declared ``vmem_bytes_per_step`` model and a configurable
             budget (:mod:`repro.verify.vmem`);
  roofline   FLOPs per grid step (counted while interpreting) and
             HBM<->VMEM bytes (block-index transition counting) give a
             static ``arith_intensity`` per design point -- the fused
             kernel's deferred roofline model.

The interpreter runs on two value kinds: *concrete* numpy arrays
(program ids, iota, SMEM table scalars, masks -- everything the grid
step determines) and *data* values carrying only shape/dtype, a
maybe-nonzero mask and a provenance token.  A value whose maybe-nonzero
mask is empty is provably zero; a write whose value provably equals the
ref's current contents is a no-op.  That is exactly enough to prove the
idle-step contract of the fused kernel (masked steps add provable
zeros and write back unchanged scratch) while rejecting any corrupted
window table that lets real data through.

Kernel packages declare what their launches look like
(:mod:`repro.kernels.introspect`); the analyzer verifies the traced
jaxpr against the declaration and fails loudly -- an unknown primitive
or indexing pattern is an ``analyzer-gap`` violation, never a silent
pass.
"""
from __future__ import annotations

import dataclasses
import functools

import numpy as np

from repro.kernels import introspect
from repro.verify import vmem
from repro.verify.intervals import Violation

_ANALYZER = "dataflow"

#: ragged/prime batch sizes the tiler must produce safe launches for
RAGGED_BATCHES = (8, 56, 64, 100, 256, 512, 513, 977)


# --------------------------------------------------------------- values

class Data:
    """Abstract array: shape/dtype + maybe-nonzero mask + provenance.

    ``nz`` is an upper bound on where the value can be nonzero;
    ``src = (ref id, version)`` marks a value bitwise-identical to the
    full contents of that ref at that version (a round-trip write of
    such a value is a no-op).
    """
    __slots__ = ("shape", "dtype", "nz", "src")

    def __init__(self, shape, dtype, nz=None, src=None):
        self.shape = tuple(int(d) for d in shape)
        self.dtype = np.dtype(dtype)
        if nz is None:
            nz = True
        self.nz = np.broadcast_to(np.asarray(nz, bool), self.shape)
        self.src = src


def _is_data(v) -> bool:
    return isinstance(v, Data)


def _nz(v) -> np.ndarray:
    return v.nz if _is_data(v) else np.asarray(v) != 0


def _norm(v):
    """Provably-zero data is concrete zeros (zero propagation)."""
    if _is_data(v) and not v.nz.any():
        return np.zeros(v.shape, v.dtype)
    return v


def _shape(v) -> tuple:
    """Shape of either value kind (np.shape sees Data as a scalar)."""
    return v.shape if _is_data(v) else np.shape(v)


class AnalyzerGap(Exception):
    """Kernel construct the analyzer cannot model -- never a pass."""


# ----------------------------------------------------------------- refs

class RefState:
    """One kernel ref's per-run abstract contents.

    Tracks, elementwise: ``written`` (initialized this run), ``nz``
    (maybe-nonzero), and ``known``/``val`` (exact concrete contents
    where known -- scratch starts each run as known zeros after its
    init write, which is what lets idle-step writes of zeros be
    recognized as no-ops).
    """

    def __init__(self, rid: int, name: str, kind: str, shape, dtype,
                 backing=None):
        self.rid, self.name, self.kind = rid, name, kind
        self.shape = tuple(int(d) for d in shape)
        self.dtype = np.dtype(dtype)
        self.backing = backing          # concrete SMEM contents
        self.version = 0
        self.touched = False            # effective write this step
        self.reset_run()

    def reset_run(self):
        self.written = np.zeros(self.shape, bool)
        self.nz = np.zeros(self.shape, bool)
        self.known = np.zeros(self.shape, bool)
        self.val = np.zeros(self.shape, self.dtype)
        self.version += 1

    # -- region helpers -------------------------------------------------
    def _full(self, region) -> bool:
        sel = np.zeros(self.shape, bool)
        sel[region] = True
        return bool(sel.all())

    def read(self, region, where: str, violations: list):
        if self.kind == "smem":
            return np.asarray(self.backing)[region]
        if self.kind == "in":
            return Data(np.empty(self.shape, bool)[region].shape,
                        self.dtype)
        if not self.written[region].all():
            violations.append(Violation(
                _ANALYZER, "read-before-write", where,
                f"ref {self.name} read at {_fmt_region(region)} before "
                f"every element was written this run"))
        if self.known[region].all():
            return self.val[region].copy()
        src = (self.rid, self.version) if self._full(region) else None
        return Data(self.nz[region].shape, self.dtype,
                    nz=self.nz[region].copy(), src=src)

    def write(self, region, v, where: str, violations: list):
        if self.kind in ("smem", "in"):
            violations.append(Violation(
                _ANALYZER, "write-to-readonly", where,
                f"ref {self.name} ({self.kind}) is written"))
            return
        # no-op detection: full-ref round trip, or rewriting contents
        # that are concretely known to be identical already
        if (_is_data(v) and v.src == (self.rid, self.version)
                and self._full(region)):
            return
        if (not _is_data(v) and self.written[region].all()
                and self.known[region].all()
                and np.array_equal(self.val[region],
                                   np.broadcast_to(
                                       np.asarray(v, self.dtype),
                                       self.val[region].shape))):
            return
        self.touched = True
        self.version += 1
        self.written[region] = True
        if _is_data(v):
            self.known[region] = False
            self.nz[region] = np.broadcast_to(v.nz,
                                              self.nz[region].shape)
        else:
            arr = np.broadcast_to(np.asarray(v, self.dtype),
                                  self.val[region].shape)
            self.known[region] = True
            self.val[region] = arr
            self.nz[region] = arr != 0


def _fmt_region(region) -> str:
    parts = []
    for r in region:
        if isinstance(r, slice):
            parts.append(f"{r.start or 0}:{r.stop}")
        else:
            parts.append(str(r))
    return "[" + ", ".join(parts) + "]"


# ---------------------------------------------------------- interpreter

_ELEMENTWISE_ZERO_STRICT = ("mul", "and")
_ELEMENTWISE_UNION = ("add", "sub", "or", "xor", "max", "min", "rem",
                      "div")
_ELEMENTWISE_UNARY = ("neg",)                      # nz-preserving
_SHIFTS = ("shift_right_logical", "shift_right_arithmetic",
           "shift_left")
_COMPARES = ("eq", "ne", "lt", "le", "gt", "ge")
_NP_OPS = {
    "add": np.add, "sub": np.subtract, "mul": np.multiply,
    "and": np.bitwise_and, "or": np.bitwise_or, "xor": np.bitwise_xor,
    "max": np.maximum, "min": np.minimum,
    "shift_right_logical": np.right_shift,
    "shift_right_arithmetic": np.right_shift,
    "shift_left": np.left_shift,
    # lax.rem/div truncate toward zero; index maps only ever apply them
    # to nonnegative grid indices, where they equal numpy's flooring
    "rem": np.remainder, "div": np.floor_divide,
    "eq": np.equal, "ne": np.not_equal, "lt": np.less,
    "le": np.less_equal, "gt": np.greater, "ge": np.greater_equal,
    "neg": np.negative,
}


class _Interp:
    """Abstract interpreter for one grid step of one kernel body."""

    def __init__(self, step, where: str, violations: list):
        self.step = step
        self.where = where
        self.violations = violations
        self.flops = 0

    # -- plumbing -------------------------------------------------------
    def run_jaxpr(self, jaxpr, consts, args):
        env = {}

        def read(v):
            if hasattr(v, "val"):                  # Literal
                return np.asarray(v.val)
            return env[v]

        for var, c in zip(jaxpr.constvars, consts):
            env[var] = c
        for var, a in zip(jaxpr.invars, args):
            env[var] = a
        for eqn in jaxpr.eqns:
            vals = [read(v) for v in eqn.invars]
            name = eqn.primitive.name
            handler = getattr(self, "_p_" + name.replace("-", "_"),
                              None)
            if handler is None:
                handler = self._generic(name)
            if handler is None:
                raise AnalyzerGap(
                    f"primitive {name!r} not modeled")
            outs = handler(eqn, vals)
            for var, out in zip(eqn.outvars, outs):
                if var.__class__.__name__ != "DropVar":
                    env[var] = _norm(out) if out is not None else None
        return [read(v) for v in jaxpr.outvars]

    def _out_aval(self, eqn, i=0):
        return eqn.outvars[i].aval

    def _data(self, eqn, nz=True, i=0):
        aval = self._out_aval(eqn, i)
        return Data(aval.shape, aval.dtype, nz=nz)

    # -- generic elementwise -------------------------------------------
    def _generic(self, name):
        if name in _ELEMENTWISE_ZERO_STRICT:
            return self._ew_strict
        if name in _ELEMENTWISE_UNION:
            return self._ew_union
        if name in _SHIFTS:
            return self._ew_shift
        if name in _COMPARES:
            return self._ew_compare
        if name in _ELEMENTWISE_UNARY:
            return self._ew_unary
        return None

    def _np2(self, eqn, a, b):
        op = _NP_OPS[eqn.primitive.name]
        with np.errstate(over="ignore"):
            out = op(np.asarray(a), np.asarray(b))
        return np.asarray(out, self._out_aval(eqn).dtype)

    def _ew_strict(self, eqn, vals):
        a, b = vals
        self.flops += int(np.prod(self._out_aval(eqn).shape))
        if not (_is_data(a) or _is_data(b)):
            return [self._np2(eqn, a, b)]
        shape = self._out_aval(eqn).shape
        nz = (np.broadcast_to(_nz(a), shape)
              & np.broadcast_to(_nz(b), shape))
        return [Data(shape, self._out_aval(eqn).dtype, nz=nz)]

    def _ew_union(self, eqn, vals):
        a, b = vals
        shape = tuple(self._out_aval(eqn).shape)
        self.flops += int(np.prod(shape))
        if not (_is_data(a) or _is_data(b)):
            return [self._np2(eqn, a, b)]
        # x + 0 (or 0 + x, x | 0 ...) preserves x, provenance included
        if eqn.primitive.name in ("add", "or", "xor"):
            for keep, other in ((a, b), (b, a)):
                if (not _is_data(other) and not np.any(other)
                        and tuple(_shape(keep)) == shape):
                    return [keep]
        if (eqn.primitive.name == "sub" and not _is_data(b)
                and not np.any(b) and tuple(_shape(a)) == shape):
            return [a]
        nz = (np.broadcast_to(_nz(a), shape)
              | np.broadcast_to(_nz(b), shape))
        return [Data(shape, self._out_aval(eqn).dtype, nz=nz)]

    def _ew_shift(self, eqn, vals):
        a, b = vals
        self.flops += int(np.prod(self._out_aval(eqn).shape))
        if not (_is_data(a) or _is_data(b)):
            return [self._np2(eqn, a, b)]
        shape = self._out_aval(eqn).shape
        # shifting can only clear bits: zero stays zero
        nz = np.broadcast_to(_nz(a), shape)
        return [Data(shape, self._out_aval(eqn).dtype, nz=nz)]

    def _ew_compare(self, eqn, vals):
        a, b = vals
        if not (_is_data(a) or _is_data(b)):
            return [self._np2(eqn, a, b)]
        return [self._data(eqn)]

    def _ew_unary(self, eqn, vals):
        (a,) = vals
        self.flops += int(np.prod(self._out_aval(eqn).shape))
        if not _is_data(a):
            with np.errstate(over="ignore"):
                return [np.asarray(_NP_OPS[eqn.primitive.name](
                    np.asarray(a)), self._out_aval(eqn).dtype)]
        return [Data(a.shape, self._out_aval(eqn).dtype, nz=a.nz)]

    # -- structural primitives -----------------------------------------
    def _p_program_id(self, eqn, vals):
        if self.step is None:
            raise AnalyzerGap("program_id outside a grid step")
        return [np.int32(self.step[eqn.params["axis"]])]

    def _p_iota(self, eqn, vals):
        shape = tuple(eqn.params["shape"])
        dim = eqn.params["dimension"]
        ar = np.arange(shape[dim], dtype=eqn.params["dtype"])
        view = [1] * len(shape)
        view[dim] = shape[dim]
        return [np.broadcast_to(ar.reshape(view), shape).copy()]

    def _p_broadcast_in_dim(self, eqn, vals):
        (a,) = vals
        shape = tuple(eqn.params["shape"])
        bdims = eqn.params["broadcast_dimensions"]

        def bcast(x):
            view = [1] * len(shape)
            for i, d in enumerate(bdims):
                view[d] = np.shape(x)[i]
            return np.broadcast_to(np.reshape(x, view), shape)

        if not _is_data(a):
            return [bcast(np.asarray(a)).copy()]
        return [Data(shape, a.dtype, nz=bcast(a.nz))]

    def _p_convert_element_type(self, eqn, vals):
        (a,) = vals
        dt = self._out_aval(eqn).dtype
        if not _is_data(a):
            with np.errstate(over="ignore", invalid="ignore"):
                return [np.asarray(a).astype(dt)]
        return [Data(a.shape, dt, nz=a.nz, src=None)]

    def _p_reshape(self, eqn, vals):
        (a,) = vals
        shape = tuple(self._out_aval(eqn).shape)
        if not _is_data(a):
            return [np.reshape(np.asarray(a), shape)]
        return [Data(shape, a.dtype, nz=np.reshape(a.nz, shape))]

    def _p_squeeze(self, eqn, vals):
        return self._p_reshape(eqn, vals)

    def _p_transpose(self, eqn, vals):
        (a,) = vals
        perm = eqn.params["permutation"]
        if not _is_data(a):
            return [np.transpose(np.asarray(a), perm)]
        return [Data(self._out_aval(eqn).shape, a.dtype,
                     nz=np.transpose(a.nz, perm))]

    def _p_slice(self, eqn, vals):
        (a,) = vals
        starts = eqn.params["start_indices"]
        limits = eqn.params["limit_indices"]
        strides = eqn.params["strides"] or (1,) * len(starts)
        region = tuple(slice(s, l, st)
                       for s, l, st in zip(starts, limits, strides))
        if not _is_data(a):
            return [np.asarray(a)[region].copy()]
        return [Data(self._out_aval(eqn).shape, a.dtype,
                     nz=a.nz[region])]

    def _p_concatenate(self, eqn, vals):
        dim = eqn.params["dimension"]
        if all(not _is_data(v) for v in vals):
            return [np.concatenate([np.asarray(v) for v in vals],
                                   axis=dim)]
        nz = np.concatenate([_nz(v) for v in vals], axis=dim)
        return [Data(self._out_aval(eqn).shape,
                     self._out_aval(eqn).dtype, nz=nz)]

    def _p_pad(self, eqn, vals):
        a, pv = vals
        config = eqn.params["padding_config"]
        if any(interior != 0 for _, _, interior in config):
            raise AnalyzerGap("interior padding not modeled")
        out_shape = tuple(self._out_aval(eqn).shape)

        def padded(x, fill):
            out = np.full(out_shape, fill, dtype=bool if isinstance(
                fill, (bool, np.bool_)) else None)
            src_region, dst_region = [], []
            for (lo, _hi, _), n in zip(config, np.shape(x)):
                src_region.append(slice(max(0, -lo),
                                        min(n, out.shape[len(dst_region)]
                                            - lo)))
                dst_region.append(slice(max(0, lo),
                                        max(0, lo) + (src_region[-1].stop
                                                      - src_region[-1]
                                                      .start)))
            out[tuple(dst_region)] = x[tuple(src_region)]
            return out

        if not (_is_data(a) or _is_data(pv)):
            out = np.full(out_shape, np.asarray(pv),
                          dtype=self._out_aval(eqn).dtype)
            sub = padded(np.asarray(a) != np.asarray(a).dtype.type(0),
                         False)  # placement mask
            # place the actual values (mask tells us where they went)
            vals_out = np.full(out_shape, np.asarray(pv),
                               dtype=self._out_aval(eqn).dtype)
            region = tuple(slice(max(0, lo), max(0, lo) + min(
                n, out_shape[d] - max(0, lo)) - max(0, -lo))
                for d, ((lo, _h, _i), n)
                in enumerate(zip(config, np.shape(a))))
            src = tuple(slice(max(0, -lo), max(0, -lo)
                              + (r.stop - r.start))
                        for (lo, _h, _i), r in zip(config, region))
            vals_out[region] = np.asarray(a)[src]
            del out, sub
            return [vals_out]
        nz = padded(_nz(a), bool(np.any(_nz(pv))))
        return [Data(out_shape, self._out_aval(eqn).dtype, nz=nz)]

    def _p_select_n(self, eqn, vals):
        pred, *cases = vals
        if not _is_data(pred):
            p = np.asarray(pred)
            flat = p.reshape(-1)
            if flat.size and np.all(flat == flat[0]):
                return [cases[int(flat[0])]]
            # elementwise concrete selection
            if all(not _is_data(c) for c in cases):
                out = np.choose(p.astype(np.int64),
                                [np.broadcast_to(np.asarray(c), p.shape)
                                 for c in cases])
                return [np.asarray(out, self._out_aval(eqn).dtype)]
        shape = tuple(self._out_aval(eqn).shape)
        nz = np.zeros(shape, bool)
        for c in cases:
            nz |= np.broadcast_to(_nz(c), shape)
        return [Data(shape, self._out_aval(eqn).dtype, nz=nz)]

    def _p_dot_general(self, eqn, vals):
        a, b = vals
        (lc, rc), _ = eqn.params["dimension_numbers"]
        k = 1
        for d in lc:
            k *= int(_shape(a)[d])
        out_shape = tuple(self._out_aval(eqn).shape)
        self.flops += 2 * k * int(np.prod(out_shape))
        if (not _is_data(a) and not np.any(a)) or \
           (not _is_data(b) and not np.any(b)):
            return [np.zeros(out_shape, self._out_aval(eqn).dtype)]
        return [self._data(eqn)]

    def _p_scatter_add(self, eqn, vals):
        operand, indices, updates = vals
        if _is_data(indices):
            raise AnalyzerGap("dynamic scatter indices not modeled")
        dn = eqn.params["dimension_numbers"]
        upd_shape = _shape(updates)
        if tuple(dn.update_window_dims) != tuple(range(len(upd_shape))):
            raise AnalyzerGap(
                f"scatter pattern {dn} not modeled")
        # reconstruct the full operand-rank window (inserted dims are
        # size-1 slots at the scattered index)
        win_shape, k = [], 0
        for d in range(len(_shape(operand))):
            if d in dn.inserted_window_dims:
                win_shape.append(1)
            else:
                win_shape.append(int(upd_shape[k]))
                k += 1
        if k != len(upd_shape):
            raise AnalyzerGap(f"scatter pattern {dn} not modeled")
        if _is_data(updates):
            updates = Data(win_shape, updates.dtype,
                           nz=np.reshape(updates.nz, win_shape),
                           src=None)
        else:
            updates = np.reshape(np.asarray(updates), win_shape)
        idx = np.asarray(indices).reshape(-1)
        offsets = [0] * len(_shape(operand))
        for pos, od in enumerate(dn.scatter_dims_to_operand_dims):
            offsets[od] = int(idx[pos])
        region = tuple(slice(off, off + size) for off, size
                       in zip(offsets, win_shape))
        for r, n in zip(region, _shape(operand)):
            if r.start < 0 or r.stop > n:
                self.violations.append(Violation(
                    _ANALYZER, "scatter-bounds", self.where,
                    f"scatter-add window {region} exceeds operand "
                    f"shape {_shape(operand)} (FILL_OR_DROP would "
                    f"silently drop it)"))
                return [operand]
        self.flops += int(np.prod(_shape(updates)))
        updates = _norm(updates)
        if not _is_data(updates) and not np.any(updates):
            return [operand]              # identity: provenance kept
        if not (_is_data(operand) or _is_data(updates)):
            out = np.array(operand)
            with np.errstate(over="ignore"):
                out[region] = out[region] + np.asarray(
                    updates, out.dtype)
            return [out]
        nz = np.array(_nz(operand))
        nz[region] |= _nz(updates)
        return [Data(_shape(operand), self._out_aval(eqn).dtype,
                     nz=nz)]

    # -- control flow ---------------------------------------------------
    def _p_cond(self, eqn, vals):
        pred, *ops = vals
        if _is_data(pred):
            raise AnalyzerGap(
                "cond predicate not statically resolvable from the "
                "grid step")
        idx = int(np.asarray(pred).reshape(()))
        branches = eqn.params["branches"]
        idx = max(0, min(idx, len(branches) - 1))
        closed = branches[idx]
        return self.run_jaxpr(closed.jaxpr, closed.consts, ops)

    def _p_jit(self, eqn, vals):
        closed = eqn.params["jaxpr"]
        return self.run_jaxpr(closed.jaxpr, closed.consts, vals)

    def _p_closed_call(self, eqn, vals):
        closed = eqn.params["call_jaxpr"]
        return self.run_jaxpr(closed.jaxpr, closed.consts, vals)

    # -- state primitives -----------------------------------------------
    def _decode_indexer(self, tree, leaves, ref):
        import jax.tree_util as jtu
        from jax._src.state.indexing import NDIndexer
        # a ref access carries a tuple of transforms: none is the whole
        # ref (``ref[...]``), one NDIndexer a sliced/indexed region
        transforms = jtu.tree_unflatten(tree, list(leaves))
        if not transforms:
            return tuple(slice(0, n, 1) for n in ref.shape)
        if len(transforms) != 1 or not isinstance(transforms[0],
                                                  NDIndexer):
            raise AnalyzerGap(f"ref transforms {transforms} not modeled")
        region = []
        for entry in transforms[0].indices:
            if hasattr(entry, "start") and hasattr(entry, "size"):
                start, size = entry.start, entry.size
                stride = getattr(entry, "stride", 1)
                if _is_data(start) or _is_data(size):
                    raise AnalyzerGap("data-dependent slice bounds")
                start = int(np.asarray(start).reshape(()))
                size = int(np.asarray(size).reshape(()))
                stride = int(np.asarray(stride).reshape(()))
                region.append(slice(start, start + size * stride,
                                    stride))
            elif _is_data(entry):
                raise AnalyzerGap("data-dependent scalar index")
            elif np.ndim(entry) == 0:
                region.append(int(np.asarray(entry).reshape(())))
            else:
                raise AnalyzerGap("advanced ref indexing not modeled")
        # bounds of the decoded region vs the ref extents
        for r, n in zip(region, ref.shape):
            lo = r.start if isinstance(r, slice) else r
            hi = (r.stop if isinstance(r, slice) else r + 1)
            if lo < 0 or hi > n:
                self.violations.append(Violation(
                    _ANALYZER, "ref-bounds", self.where,
                    f"ref {ref.name} indexed at {_fmt_region(region)} "
                    f"outside its extents {ref.shape}"))
        return tuple(region)

    def _p_get(self, eqn, vals):
        ref, *leaves = vals
        region = self._decode_indexer(eqn.params["tree"], leaves, ref)
        return [ref.read(region, self.where, self.violations)]

    def _p_swap(self, eqn, vals):
        ref, value, *leaves = vals
        region = self._decode_indexer(eqn.params["tree"], leaves, ref)
        old_nz = ref.nz[region].copy()
        ref.write(region, value, self.where, self.violations)
        return [Data(self._out_aval(eqn).shape,
                     self._out_aval(eqn).dtype, nz=old_nz)]

    def _p_addupdate(self, eqn, vals):
        ref, value, *leaves = vals
        region = self._decode_indexer(eqn.params["tree"], leaves, ref)
        value = _norm(value)
        if not _is_data(value) and not np.any(value):
            return [None]
        old = ref.read(region, self.where, self.violations)
        if _is_data(old) or _is_data(value):
            merged = Data(_shape(old), ref.dtype,
                          nz=_nz(old) | _nz(value))
        else:
            with np.errstate(over="ignore"):
                merged = np.asarray(old) + np.asarray(value, ref.dtype)
        ref.write(region, merged, self.where, self.violations)
        return [None]


# ------------------------------------------------------- launch decoding

@dataclasses.dataclass(frozen=True)
class LaunchReport:
    """Static analysis result of one Pallas launch."""
    name: str
    grid: tuple
    n_steps: int
    flops: int
    hbm_bytes: int
    arith_intensity: float
    vmem: dict                  # VmemBreakdown.as_dict()
    vmem_model_bytes: int
    violations: tuple

    @property
    def ok(self) -> bool:
        return not self.violations

    def as_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d["grid"] = list(self.grid)
        d["ok"] = self.ok
        d["violations"] = [dataclasses.asdict(v)
                           for v in self.violations]
        return d


def _block_dims(bm) -> tuple:
    """Per-dimension block sizes of a BlockMapping (squeezed dims: 1)."""
    from jax.experimental import pallas as pl
    dims = []
    for b in bm.block_shape:
        if isinstance(b, pl.Blocked):
            dims.append(int(b.block_size))
        elif isinstance(b, pl.Squeezed):
            dims.append(1)
        else:
            raise AnalyzerGap(f"block dimension {b!r} not modeled")
    return tuple(dims)


def _eval_index_map(interp, closed, args):
    outs = interp.run_jaxpr(closed.jaxpr, closed.consts, args)
    idx = []
    for o in outs:
        if _is_data(o):
            raise AnalyzerGap("index map output not static")
        idx.append(int(np.asarray(o).reshape(())))
    return tuple(idx)


def _program_id_axes(kernel_jaxpr) -> tuple:
    axes = set()
    for eqn in introspect.walk(kernel_jaxpr, into_pallas=True):
        if eqn.primitive.name == "program_id":
            axes.add(eqn.params["axis"])
    return tuple(sorted(axes))


def analyze_contract(contract, budget=None):
    """Full static analysis of one declared launch -> LaunchReport.

    Proves conformance (traced grid/scratch match the declaration),
    bounds, hazards, idle-step no-ops, VMEM model/budget and the
    static roofline.
    """
    violations = []

    def fail(rule, detail, grid=()):
        violations.append(Violation(_ANALYZER, rule, contract.name,
                                    detail))
        return LaunchReport(
            name=contract.name, grid=tuple(grid), n_steps=0, flops=0,
            hbm_bytes=0, arith_intensity=0.0, vmem={},
            vmem_model_bytes=contract.vmem_model_bytes,
            violations=tuple(violations))

    try:
        closed = contract.trace()
    except Exception as e:                   # noqa: BLE001
        return fail("trace-error", f"tracing raised {e!r}")
    calls = introspect.find_pallas_calls(closed.jaxpr)
    if len(calls) != 1:
        return fail("launch-count",
                    f"expected exactly 1 pallas_call, traced "
                    f"{len(calls)}")
    eqn = calls[0]
    gm = eqn.params["grid_mapping"]
    kernel = eqn.params["jaxpr"]
    grid = tuple(int(g) for g in gm.grid)

    # -- conformance against the package's declaration ------------------
    if grid != tuple(contract.grid):
        return fail("grid-mismatch",
                    f"declared grid {tuple(contract.grid)}, traced "
                    f"{grid}", grid)
    ni, nin = gm.num_index_operands, gm.num_inputs
    nout, nscr = gm.num_outputs, gm.num_scratch_operands
    scratch_avals = [v.aval for v in kernel.invars[ni + nin + nout:]]
    declared = [(tuple(s), np.dtype(d))
                for s, d in contract.scratch_shapes]
    traced = [(tuple(a.shape), np.dtype(a.dtype))
              for a in scratch_avals]
    if declared != traced:
        return fail("scratch-mismatch",
                    f"declared scratch {declared}, traced {traced}",
                    grid)

    # -- window-table checks (super-geometry launches) -------------------
    sg = contract.meta.get("super_geometry")
    if sg is not None:
        violations.extend(check_window_table(sg, contract.table))

    # -- VMEM model / budget --------------------------------------------
    breakdown = vmem.measure(eqn)
    violations.extend(vmem.check(breakdown,
                                 contract.vmem_model_bytes,
                                 contract.name, budget))

    # -- per-step block-index bounds + run segmentation ------------------
    block_mappings = list(gm.block_mappings)    # inputs then outputs
    smem_args = []
    for v in kernel.invars[:ni]:
        if contract.table is not None and not smem_args:
            smem_args.append(np.asarray(contract.table))
        else:
            smem_args.append(np.zeros(v.aval.shape,
                                      np.dtype(v.aval.dtype)))
    steps = [tuple(int(c) for c in s) for s in np.ndindex(*grid)]
    idxer = _Interp(None, contract.name, violations)
    per_map_indices = []
    try:
        for bm in block_mappings:
            bs = _block_dims(bm)
            arr_shape = tuple(bm.array_aval.shape)
            nblocks = tuple(-(-a // b) for a, b in zip(arr_shape, bs))
            seq = []
            for s in steps:
                idx = _eval_index_map(idxer, bm.index_map_jaxpr,
                                      list(s) + smem_args)
                for d, (i, nb) in enumerate(zip(idx, nblocks)):
                    if i < 0 or i >= nb:
                        violations.append(Violation(
                            _ANALYZER, "block-bounds",
                            f"{contract.name} step {s}",
                            f"index map emits block {idx} on dim {d} "
                            f"outside the padded extent "
                            f"({nb} blocks of {bs} over {arr_shape})"))
                seq.append(idx)
            per_map_indices.append(seq)
    except AnalyzerGap as e:
        return fail("analyzer-gap", str(e), grid)

    out_maps = per_map_indices[nin:nin + nout]
    out_sig = [tuple(m[t] for m in out_maps) for t in range(len(steps))]

    # runs: maximal consecutive step groups sharing all output blocks
    runs = []
    for t, s in enumerate(steps):
        if t == 0 or out_sig[t] != out_sig[t - 1]:
            runs.append([t])
        else:
            runs[-1].append(t)

    # WAW between runs: a later run revisiting an earlier run's output
    # block interleaves writes from different grid coordinates
    seen_sigs = {}
    for rn, run in enumerate(runs):
        sig = out_sig[run[0]]
        if sig in seen_sigs:
            violations.append(Violation(
                _ANALYZER, "waw-out",
                f"{contract.name} step {steps[run[0]]}",
                f"output block {sig} already written by the run at "
                f"step {steps[seen_sigs[sig]]} -- write-after-write "
                f"between grid instances"))
        else:
            seen_sigs[sig] = run[0]

    # -- hazard + idle interpretation, deduped by behavior key -----------
    axes = _program_id_axes(kernel)
    flops_total = 0
    run_flops = {}
    for run in runs:
        key = tuple(tuple(steps[t][a] for a in axes) for t in run)
        if key in run_flops:
            flops_total += run_flops[key]
            continue
        refs = []
        for rid, v in enumerate(kernel.invars):
            aval = v.aval
            if rid < ni:
                kind, backing = "smem", smem_args[rid]
            elif rid < ni + nin:
                kind, backing = "in", None
            elif rid < ni + nin + nout:
                kind, backing = "out", None
            else:
                kind, backing = "scratch", None
            refs.append(RefState(rid, f"{kind}{rid}", kind,
                                 aval.shape, aval.dtype,
                                 backing=backing))
        flops = 0
        try:
            for t in run:
                step = steps[t]
                where = f"{contract.name} step {step}"
                interp = _Interp(step, where, violations)
                for r in refs:
                    r.touched = False
                interp.run_jaxpr(kernel, [], refs)
                flops += interp.flops
                if contract.matches_idle(step):
                    for r in refs:
                        if r.kind == "scratch" and r.touched:
                            violations.append(Violation(
                                _ANALYZER, "idle-step-effect", where,
                                f"declared-idle step {step} performs "
                                f"an effective write to scratch ref "
                                f"{r.name} despite its mask"))
        except AnalyzerGap as e:
            violations.append(Violation(
                _ANALYZER, "analyzer-gap",
                f"{contract.name} step {steps[run[0]]}", str(e)))
            run_flops[key] = flops
            flops_total += flops
            continue
        run_flops[key] = flops
        flops_total += flops

    # -- static roofline: HBM<->VMEM traffic by block transitions --------
    hbm = breakdown.smem_bytes                 # table prefetched once
    for mi, seq in enumerate(per_map_indices):
        bm = block_mappings[mi]
        blk_bytes = int(np.prod(_block_dims(bm))) * np.dtype(
            bm.array_aval.dtype).itemsize
        transfers = sum(1 for t in range(len(seq))
                        if t == 0 or seq[t] != seq[t - 1])
        hbm += blk_bytes * transfers
    intensity = flops_total / hbm if hbm else 0.0

    return LaunchReport(
        name=contract.name, grid=grid, n_steps=len(steps),
        flops=flops_total, hbm_bytes=hbm,
        arith_intensity=intensity,
        vmem=breakdown.as_dict(),
        vmem_model_bytes=contract.vmem_model_bytes,
        violations=tuple(violations))


# ----------------------------------------------------- window-table rules

def check_window_table(sg, table=None) -> list:
    """Static rules over a fused launch's scalar-prefetch window table.

    Checked directly on the (instance, step, 2) table so seeded
    corruptions (tests) and the real :meth:`SuperGeometry.table` go
    through one code path:

      window-shape     table shape matches the super-geometry
      window-bounds    0 <= lo <= hi <= LB on every real step
      window-empty     real steps consume at least one limb
      window-overlap   one instance's real windows are pairwise disjoint
      window-coverage  they cover every B limb exactly once
      idle-unmasked    padded idle steps carry the (0, 0) mask
    """
    tbl = np.asarray(sg.table() if table is None else table)
    out = []
    want = (sg.n_instances, sg.max_steps, 2)
    if tbl.shape != want:
        out.append(Violation(
            _ANALYZER, "window-shape", f"fused[{sg.la}x{sg.lb}]",
            f"window table shape {tbl.shape}, super-geometry "
            f"requires {want}"))
        return out
    for i in range(sg.n_instances):
        real = sg.rows[i].ct_run
        covered = np.zeros(sg.lb, int)
        for j in range(sg.max_steps):
            lo, hi = int(tbl[i, j, 0]), int(tbl[i, j, 1])
            where = f"fused[{sg.la}x{sg.lb}] instance {i} step {j}"
            if j >= real:
                if (lo, hi) != (0, 0):
                    out.append(Violation(
                        _ANALYZER, "idle-unmasked", where,
                        f"padded idle step carries window "
                        f"({lo}, {hi}) instead of the (0, 0) mask"))
                continue
            if not (0 <= lo <= hi <= sg.lb):
                out.append(Violation(
                    _ANALYZER, "window-bounds", where,
                    f"window ({lo}, {hi}) outside [0, {sg.lb}]"))
                continue
            if lo == hi:
                out.append(Violation(
                    _ANALYZER, "window-empty", where,
                    "real fold step consumes no B limbs"))
                continue
            covered[lo:hi] += 1
        if (covered > 1).any():
            dup = int(np.argmax(covered > 1))
            out.append(Violation(
                _ANALYZER, "window-overlap",
                f"fused[{sg.la}x{sg.lb}] instance {i}",
                f"B limb {dup} accumulated by overlapping windows -- "
                f"its partial products would be added twice"))
        elif (covered == 0).any():
            miss = int(np.argmax(covered == 0))
            out.append(Violation(
                _ANALYZER, "window-coverage",
                f"fused[{sg.la}x{sg.lb}] instance {i}",
                f"B limb {miss} not covered by any window"))
    return out


# --------------------------------------------------------- plan-level API

def _instance_params(cfg) -> tuple:
    """(schedule, ct) of the mcim_fold launch realizing one config."""
    if cfg.arch == "star":
        return "fb", 1
    if cfg.arch == "karatsuba":
        return "karatsuba", 3
    return cfg.arch, cfg.ct


def _flat_configs(configs) -> tuple:
    flat = []
    for count, cfg in configs:
        flat.extend([cfg] * count)
    return tuple(flat)


@functools.lru_cache(maxsize=2048)
def _kernel_report(la, lb, schedule, ct, batch=256, budget=None):
    from repro.kernels import mcim_fold
    return analyze_contract(
        mcim_fold.launch_contract(la, lb, ct, schedule, batch=batch),
        budget=budget)


@functools.lru_cache(maxsize=2048)
def _fused_report(la, lb, cts, budget=None):
    from repro.core.mcim import MCIMConfig
    from repro.kernels import bank_fold
    configs = tuple(MCIMConfig(arch="fb", ct=ct) for ct in cts)
    return analyze_contract(bank_fold.launch_contract(configs, la, lb),
                            budget=budget)


def analyze_plan(bits_a: int, bits_b: int, configs,
                 substrate: str = "fused", budget=None) -> tuple:
    """LaunchReports of every distinct launch a plan implies.

    ``substrate="fused"``: the one megakernel launch of the whole bank,
    the launch the bank issues.  ``substrate="kernel"``: one standalone
    ``mcim_fold`` launch per distinct (schedule, CT) in the plan.  Signed configs analyze
    identically -- the correction pass is pure jnp outside the kernel,
    so the Pallas launch is the unsigned one.
    """
    from repro.core import limbs as L
    from repro.kernels.bank_fold import fused_ct
    la = L.n_limbs_for_bits(bits_a)
    lb = L.n_limbs_for_bits(bits_b)
    flat = _flat_configs(configs)
    if substrate == "fused":
        cts = tuple(fused_ct(cfg) for cfg in flat)
        return (_fused_report(la, lb, cts, budget),)
    if substrate != "kernel":
        raise ValueError(f"substrate must be kernel or fused, "
                         f"got {substrate!r}")
    reports, seen = [], set()
    for cfg in flat:
        schedule, ct = _instance_params(cfg)
        if (schedule, ct) in seen:
            continue
        seen.add((schedule, ct))
        reports.append(_kernel_report(la, lb, schedule, ct,
                                      budget=budget))
    return tuple(reports)


def verify_plan_dataflow(bits_a: int, bits_b: int, configs,
                         budget=None) -> tuple:
    """All dataflow violations of the one fused launch the bank issues
    for a plan (the core backend issues no Pallas launch)."""
    return tuple(v for rep in analyze_plan(bits_a, bits_b, configs,
                                           budget=budget)
                 for v in rep.violations)


def plan_static_stats(bits_a: int, bits_b: int, configs) -> dict:
    """Fused-launch roofline numbers of a plan (benchmark columns)."""
    rep = analyze_plan(bits_a, bits_b, configs, substrate="fused")[0]
    return {
        "vmem_bytes_step": rep.vmem.get("total_bytes", 0),
        "vmem_model_bytes": rep.vmem_model_bytes,
        "flops_per_launch": rep.flops,
        "hbm_bytes_per_launch": rep.hbm_bytes,
        "arith_intensity": rep.arith_intensity,
    }


def analyze_standalone(budget=None) -> tuple:
    """LaunchReports of the non-bank kernels (full-tree coverage)."""
    from repro.kernels import int8_matmul, karatsuba_ppm, prefix_adder
    contracts = (
        karatsuba_ppm.launch_contract(4),
        prefix_adder.launch_contract(16),
        int8_matmul.launch_contract(),
    )
    return tuple(analyze_contract(c, budget=budget) for c in contracts)


def analyze_tiling(bits: int = 32, batches=RAGGED_BATCHES,
                   budget=None) -> tuple:
    """Bounds/hazard proofs across ragged batch shapes of the tiler."""
    from repro.core import limbs as L
    la = L.n_limbs_for_bits(bits)
    return tuple(_kernel_report(la, la, "fb", 2, batch=b,
                                budget=budget)
                 for b in batches)
