"""Launch contracts, and the one walker that finds launches in a jaxpr.

What each kernel package *declares* about a launch:

The static dataflow analyzer (:mod:`repro.verify.dataflow`) proves
hazard freedom, bounds, VMEM footprint and roofline numbers for every
Pallas launch in the tree.  It must not reverse-engineer grids, scratch
shapes or idle-step masks out of kernel plumbing -- the package that
builds a ``pallas_call`` owns those facts, so each package exposes a
``launch_contract(...)`` hook returning a :class:`LaunchContract`:

  fn / args         a traceable callable + abstract operands; tracing
                    it (no execution) yields the jaxpr whose single
                    ``pallas_call`` the analyzer interprets
  grid              the grid the package intends to launch
  scratch_shapes    (shape, dtype-name) per VMEM scratch ref
  vmem_model_bytes  the package's declared per-grid-step working set
                    (``vmem_bytes_per_step`` of its geometry module);
                    the analyzer checks the measured block bytes are
                    dominated by this model
  idle_steps        grid-step patterns that must be architectural
                    no-ops on scratch (fused-bank idle-mask padding);
                    ``None`` entries are wildcards over that grid dim
  table             the concrete scalar-prefetch table, if any -- the
                    analyzer evaluates SMEM reads against it and
                    bounds-checks every window

The analyzer then *verifies* the traced jaxpr against the declaration:
a package whose kernel drifts from its own contract fails verification
rather than silently analyzing the wrong launch.

Finding launches: ``Bank.launch_count`` (how many launches one bank
round issues) and the dataflow analyzer both need equations inside
arbitrarily nested jaxprs: a jitted call site wraps the program in a
``jit`` equation whose body is a ClosedJaxpr, ``lax.cond`` branches are
ClosedJaxprs, ``scatter-add`` carries a raw update Jaxpr, and
``pallas_call`` holds the kernel body as a raw Jaxpr.  The traversal
rules for all of those live in :func:`walk`, in exactly one place -- an
analyzer that re-implemented them would drift the moment a jax upgrade
moves a sub-jaxpr to a new param name.  By default ``walk`` does NOT
descend into ``pallas_call`` kernel bodies (launch counting wants the
host program only; the dataflow analyzer interprets kernel bodies
itself, step by step).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Mapping, Optional


@dataclasses.dataclass(frozen=True)
class LaunchContract:
    """One kernel package's static declaration of one Pallas launch."""
    name: str                      # e.g. "mcim_fold/fb[la=2,lb=2,ct=2]"
    fn: Callable                   # positional callable over ``args``
    args: tuple                    # jax.ShapeDtypeStruct operands
    grid: tuple                    # declared launch grid
    scratch_shapes: tuple          # ((shape, dtype_name), ...) VMEM refs
    vmem_model_bytes: int          # declared per-step working set
    idle_steps: tuple = ()         # grid patterns (int | None per dim)
    table: Optional[Any] = None    # np.ndarray scalar-prefetch table
    meta: Mapping = dataclasses.field(default_factory=dict)

    def trace(self):
        """ClosedJaxpr of one ``fn(*args)`` call -- no execution."""
        import jax
        return jax.make_jaxpr(self.fn)(*self.args)

    def matches_idle(self, step: tuple) -> bool:
        """Whether ``step`` is declared architecturally idle."""
        return any(all(p is None or p == s for p, s in zip(pat, step))
                   for pat in self.idle_steps)


# ---------------------------------------------------------- jaxpr walking

def subjaxprs(eqn):
    """Every jaxpr nested in one equation's params (open or closed)."""
    for val in eqn.params.values():
        vals = val if isinstance(val, (tuple, list)) else (val,)
        for v in vals:
            inner = getattr(v, "jaxpr", None)   # ClosedJaxpr -> Jaxpr
            if inner is not None:
                yield inner
            elif hasattr(v, "eqns"):            # raw Jaxpr param
                yield v


def walk(jaxpr, into_pallas: bool = False):
    """Yield every equation in ``jaxpr`` and its nested jaxprs.

    Descends through jit / closed-call / cond / scan bodies; kernel
    jaxprs inside ``pallas_call`` equations are skipped unless
    ``into_pallas`` (the host-program and kernel-body instruction
    streams are different machines and almost every analysis wants
    exactly one of them).
    """
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "pallas_call" and not into_pallas:
            # still descend params OTHER than the kernel body (none
            # today, but the rule is: skip the kernel, not the eqn)
            kernel = eqn.params.get("jaxpr")
            for inner in subjaxprs(eqn):
                if inner is not kernel:
                    yield from walk(inner, into_pallas)
            continue
        for inner in subjaxprs(eqn):
            yield from walk(inner, into_pallas)


def count_primitive(jaxpr, name: str, into_pallas: bool = False) -> int:
    """Number of ``name`` equations reachable from ``jaxpr``."""
    return sum(1 for eqn in walk(jaxpr, into_pallas)
               if eqn.primitive.name == name)


def find_pallas_calls(jaxpr) -> list:
    """Every ``pallas_call`` equation reachable from ``jaxpr``."""
    return [eqn for eqn in walk(jaxpr)
            if eqn.primitive.name == "pallas_call"]


def count_pallas_launches(fn, *args) -> int:
    """Kernel launches one call of ``fn(*args)`` issues.

    Traces ``fn`` (no execution) and counts ``pallas_call`` primitives
    recursively through nested jaxprs (jit/closed-call bodies): a fused
    bank round issues exactly one.
    """
    import jax
    closed = jax.make_jaxpr(fn)(*args)
    return count_primitive(closed.jaxpr, "pallas_call")
