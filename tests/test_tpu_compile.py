"""Ahead-of-time compiles of the main-path kernels for a TPU v5e.

The Pallas interpreter accepts programs the TPU compiler refuses
(scatter-adds, lane-illegal block shapes, dynamic lane-offset stores),
so these tests lower every kernel of the multiplier path natively
(``interpret=False``) against a described -- not attached -- ``v5e:2x2``
topology and check that the compiled program holds the Mosaic kernel.
Nothing runs; a pass says the chip's compiler takes the kernel, not
that it is fast or correct (the bit-exact tests say the latter).  The
whole fused dispatch is compiled for one chip, where it must hold the
kernel and no gather or scatter; the replicated bank's sharded dispatch
is compiled over all four described chips, where it must hold the
kernel and no collective.

The topology is described inside a fixture, never at import time: the
TPU library admits one loader per process, and every test worker
imports this file.
"""
import os

import pytest
import jax
import jax.numpy as jnp

from repro import designs
from repro.core import limbs as L
from repro.kernels import runtime
from repro.kernels.bank_fold import make_fused_dispatch, super_geometry
from repro.kernels.bank_fold.kernel import fused_bank_mul
from repro.kernels.mcim_fold.kernel import mcim_fold_mul

#: the fused megakernel at the paper's TP=3.5 bank and the widest
#: shipped design point
FUSED_DESIGNS = ("tp3p5_w32", "tp5over6_w128")
#: (schedule, CT) of the per-instance folded kernel, at 128 bits
FOLD_CASES = (("fb", 2), ("ff", 2), ("karatsuba", 3))
ROWS, TILE = 8192, 512


@pytest.fixture(scope="module")
def topo():
    """A described v5e:2x2, with the compile cache off.

    A compile for a described chip is written to the persistent cache
    but cannot be read back without one, so the cache stays off here.
    """
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    """One device of the described v5e:2x2."""
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text(), \
        "the compiled program holds no Mosaic kernel"


@pytest.mark.parametrize("name", FUSED_DESIGNS)
def test_fused_bank_compiles_for_v5e(one_chip, name):
    design = designs.generate(name)
    la, lb = design.bank.la, design.bank.lb
    sg = super_geometry(design.bank.instances, la, lb)
    n = sg.n_instances
    table = sg.table()
    f = jax.jit(lambda a, b, t: fused_bank_mul(
        a, b, t, max_steps=sg.max_steps, tile_r=TILE, interpret=False))
    compiled = f.lower(
        _spec((n, ROWS, la), L.LIMB_DTYPE, one_chip),
        _spec((n, ROWS, lb), L.LIMB_DTYPE, one_chip),
        _spec(table.shape, jnp.int32, one_chip)).compile()
    _assert_kernel(compiled)


@pytest.mark.parametrize("name", FUSED_DESIGNS)
def test_fused_dispatch_compiles_for_v5e(one_chip, name, monkeypatch):
    # the round as Bank.execute runs it: static slices into the kernel's
    # blocks and out of them, with no index map left for the compiler
    bank = designs.generate(name).bank
    assign, _ = bank.scheduler.schedule(bank._cts, ROWS)
    with monkeypatch.context() as m:
        m.setenv("REPRO_INTERPRET", "0")
        runtime.reset()
        try:
            run = make_fused_dispatch(assign, bank.instances, bank.la,
                                      bank.lb, ROWS)
            compiled = jax.jit(run).lower(
                _spec((ROWS, bank.la), L.LIMB_DTYPE, one_chip),
                _spec((ROWS, bank.lb), L.LIMB_DTYPE, one_chip)).compile()
        finally:
            runtime.reset()
    _assert_kernel(compiled)
    text = compiled.as_text()
    assert "gather(" not in text and "scatter(" not in text


@pytest.mark.parametrize("schedule,ct", FOLD_CASES)
def test_mcim_fold_compiles_for_v5e(one_chip, schedule, ct):
    limbs = L.n_limbs_for_bits(128)
    f = jax.jit(lambda a, b: mcim_fold_mul(
        a, b, ct=ct, tile_b=TILE, schedule=schedule, interpret=False))
    x = _spec((ROWS, limbs), L.LIMB_DTYPE, one_chip)
    _assert_kernel(f.lower(x, x).compile())


def test_replicated_bank_compiles_for_four_v5e_chips(topo, monkeypatch):
    # the sharded dispatch of four tp3p5_w32 replicas, one a chip: each
    # chip runs the kernel on its own rows, with no collective between
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.core.bank import sharded
    mesh = Mesh(np.array(topo.devices), ("data",))
    spec = designs.DesignSpec.from_dict(
        {"bits_a": 32, "bits_b": 32, "throughput": "7/2", "replicas": 4,
         "backend": "fused"})
    with monkeypatch.context() as m:
        m.setenv("REPRO_INTERPRET", "0")
        runtime.reset()
        try:
            design = designs.generate(spec, mesh=mesh)
            fn, args = sharded._sharded_fn(design.plan, 32, 32, "fused",
                                           spec.scheduler, mesh, "data",
                                           ROWS)
            x = _spec((4 * ROWS, design.la), L.LIMB_DTYPE,
                      NamedSharding(mesh, P("data")))
            compiled = fn.lower(x, x).compile()
        finally:
            runtime.reset()
    _assert_kernel(compiled)
    text = compiled.as_text()
    for collective in ("all-gather", "all-reduce", "all-to-all",
                       "collective-permute"):
        assert collective not in text, collective
    assert (args["rows"], args["shards"], args["local_rows"]) == \
        (4 * ROWS, 4, ROWS)
    assert compiled.output_shardings.spec[0] == "data"
