"""One shared recursive jaxpr walker for every jaxpr-level analyzer.

Both ``launch.roofline.count_pallas_launches`` (the dispatch-tax metric)
and ``verify.dataflow`` (the static hazard/bounds/roofline analyzer)
need to find equations inside arbitrarily nested jaxprs: a jitted call
site wraps the program in a ``jit`` equation whose body is a
ClosedJaxpr, ``lax.cond`` branches are ClosedJaxprs, ``scatter-add``
carries a raw update Jaxpr, and ``pallas_call`` holds the kernel body
as a raw Jaxpr.  The traversal rules for all of those live here, in
exactly one place -- an analyzer that re-implemented them would drift
the moment a jax upgrade moves a sub-jaxpr to a new param name.

``walk`` yields every equation reachable from a jaxpr; by default it
does NOT descend into ``pallas_call`` kernel bodies (launch counting
wants the host program only; the dataflow analyzer interprets kernel
bodies itself, step by step).
"""
from __future__ import annotations


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
