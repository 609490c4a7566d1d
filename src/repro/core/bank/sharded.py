"""Sharded multi-bank execution: N replicated banks over a mesh axis.

The paper's Sec. V-E bank sustains a fractional throughput on one chip;
production serving replicates that bank across devices.  This module
runs one bank *per device slice* along a named mesh axis via
``jax.shard_map``: the global batch is split evenly, every
device executes its shard through the same static dispatch (scheduler +
backend resolved exactly as in :mod:`.engine`), and the results
concatenate back bit-exactly -- each multiplication is computed by
exactly one instance of one bank replica, so ``sharded_execute`` equals
the single-bank oracle product-for-product.

The batch is split by :func:`bank_batch_spec`, which refuses a batch
that does not divide evenly instead of replicating it.
"""
from __future__ import annotations

import functools

import jax
from jax import shard_map
from jax.sharding import PartitionSpec as P

from .. import limbs as L
from ..planner import Plan
from repro.spans import span
from .engine import Bank, BankReport


def bank_batch_spec(mesh, axis: str, ndim: int, batch_dim_size: int) -> P:
    """Spec for a multiplier-bank batch replicated along one mesh axis.

    Bank replicas each need an equal shard, so a batch that does not
    divide over the axis is an error, not a fallback to replication."""
    if axis not in mesh.axis_names:
        raise ValueError(f"axis {axis!r} not in mesh axes {mesh.axis_names}")
    if batch_dim_size % mesh.shape[axis]:
        raise ValueError(
            f"batch {batch_dim_size} not divisible by mesh axis "
            f"{axis!r} size {mesh.shape[axis]}")
    return P(*((axis,) + (None,) * (ndim - 1)))


def _local_batch(batch: int, mesh, axis: str) -> int:
    # bank_batch_spec is the single owner of the axis-membership and
    # divisibility validation; this just derives the shard size from it
    bank_batch_spec(mesh, axis, 2, batch)
    return batch // mesh.shape[axis]


@functools.lru_cache(maxsize=64)
def _sharded_fn(plan: Plan, bits_a: int, bits_b: int, backend: str,
                scheduler: str, mesh, axis: str, local: int):
    # the body runs only on a cache miss, so this span marks one
    with span("bank.sharded_build"):
        bank = Bank(plan, bits_a, bits_b, backend=backend,
                    scheduler=scheduler)
        run = bank.dispatch_fn(local)
        shards = mesh.shape[axis]
        spec = bank_batch_spec(mesh, axis, 2, local * shards)
        fn = shard_map(run, mesh=mesh, in_specs=(spec, spec),
                       out_specs=spec, check_vma=False)
    args = {"rows": local * shards, "shards": shards, "local_rows": local}
    if hasattr(run, "kernel_rows"):
        args["kernel_rows"] = run.kernel_rows * shards
    return jax.jit(fn), args


def sharded_execute(plan: Plan, a: jax.Array, b: jax.Array, mesh,
                    axis: str, *, backend: str = "core",
                    scheduler: str = "round_robin") -> jax.Array:
    """Replicated-bank execution of (B, LA) x (B, LB) over ``mesh[axis]``.

    Each of the ``mesh.shape[axis]`` device slices runs one full bank
    replica on its B/N shard; the returned (B, LA+LB) limb products are
    bit-exact vs the single-bank (and Python-bigint) oracle.  The global
    batch must divide evenly; compiled sharded dispatches are cached per
    (plan, widths, backend, scheduler, mesh, axis, shard size).
    """
    if a.ndim != 2 or b.ndim != 2:
        raise ValueError("sharded_execute expects batched (B, L) operands")
    if a.shape[0] != b.shape[0]:
        raise ValueError(
            f"batch mismatch: a has {a.shape[0]} ops, b has {b.shape[0]}")
    local = _local_batch(a.shape[0], mesh, axis)
    fn, args = _sharded_fn(plan, a.shape[-1] * L.RADIX_BITS,
                           b.shape[-1] * L.RADIX_BITS, backend,
                           scheduler, mesh, axis, local)
    with span("bank.launch", **args):
        return fn(a, b)


def sharded_report(plan: Plan, batch: int, bits_a: int, bits_b: int,
                   mesh, axis: str, *, backend: str = "core",
                   scheduler: str = "round_robin") -> BankReport:
    """Per-replica cycle accounting: the report of one bank running its
    B/N shard (all replicas are identical, so one report describes the
    whole sharded execution; aggregate throughput is N x measured)."""
    local = _local_batch(batch, mesh, axis)
    bank = Bank(plan, bits_a, bits_b, backend=backend, scheduler=scheduler)
    return bank.report(local)
