"""Tests of the static Pallas dataflow analyzer (repro.verify.dataflow).

Three angles: (1) every launch the repo can plan proves clean -- the
registry, the autotuner vocabulary on both substrates, the standalone
kernels and ragged batch shapes; (2) seeded corruptions (window tables,
synthetic hazard kernels, understated VMEM models) are REJECTED with
structured violations naming the offending grid step / scratch ref;
(3) the plan-time gate raises DataflowError through the public facade.
"""
from __future__ import annotations

import dataclasses

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import limbs as L
from repro.core.mcim import MCIMConfig
from repro.designs import registry
from repro.kernels import bank_fold, mcim_fold
from repro.kernels import introspect
from repro.kernels.introspect import LaunchContract
from repro.verify import (DataflowError, VerificationError,
                          assert_plan_dataflow, dataflow, vmem)


def _violated(violations, rule):
    return [v for v in violations if v.rule == rule]


# ---------------------------------------------------------------- clean

def test_registry_plans_prove_clean_on_both_substrates():
    """All 13 registry design points: every implied launch verifies
    with zero violations and a positive static arithmetic intensity."""
    from repro.designs.compile import _plan_with_timing
    names = sorted(registry.names())
    assert len(names) >= 13
    for name in names:
        spec = registry.get(name)
        plan, _ = _plan_with_timing(spec)   # already dataflow-gated
        for substrate in ("kernel", "fused"):
            for rep in dataflow.analyze_plan(spec.bits_a, spec.bits_b,
                                             plan.configs,
                                             substrate=substrate):
                assert rep.ok, (name, substrate,
                                [v.describe() for v in rep.violations])
                assert rep.arith_intensity > 0
                assert rep.flops > 0 and rep.hbm_bytes > 0
                assert rep.vmem["total_bytes"] > 0


def test_vocabulary_clean_at_one_width():
    """Every planner-emittable instance arch at 32b, both substrates."""
    vocab = [MCIMConfig(arch="star", ct=1),
             MCIMConfig(arch="karatsuba", ct=3)]
    vocab += [MCIMConfig(arch=a, ct=ct) for a in ("fb", "ff")
              for ct in (2, 3, 12)]
    for cfg in vocab:
        for substrate in ("kernel", "fused"):
            vs = [v for rep in dataflow.analyze_plan(
                32, 32, ((1, cfg),), substrate=substrate)
                for v in rep.violations]
            assert not vs, (cfg, substrate, [v.describe() for v in vs])


def test_signed_configs_analyze_like_unsigned():
    """Signedness is handled outside the kernel; the launches (and the
    cached reports) are identical."""
    cfg = MCIMConfig(arch="fb", ct=2)
    signed = dataclasses.replace(cfg, signed=True)
    a = dataflow.analyze_plan(32, 32, ((1, cfg),), substrate="fused")
    b = dataflow.analyze_plan(32, 32, ((1, signed),), substrate="fused")
    assert a == b


def test_standalone_kernels_and_ragged_batches():
    for rep in dataflow.analyze_standalone():
        assert rep.ok, (rep.name, [v.describe() for v in rep.violations])
        assert rep.arith_intensity > 0
    for rep in dataflow.analyze_tiling(32, batches=(8, 100, 513)):
        assert rep.ok, (rep.name, [v.describe() for v in rep.violations])


def test_report_serializes():
    rep = dataflow.analyze_plan(32, 32,
                                ((1, MCIMConfig(arch="star", ct=1)),),
                                substrate="fused")[0]
    d = rep.as_dict()
    assert d["ok"] and d["violations"] == []
    import json
    json.dumps(d)


# ------------------------------------------------- window-table rejection

def _geo(configs=(MCIMConfig(arch="fb", ct=1), MCIMConfig(arch="fb", ct=2)),
         la=2, lb=2):
    return bank_fold.super_geometry(configs, la, lb)


def test_window_off_by_one_hi_rejected():
    sg = _geo()
    tbl = sg.table()
    tbl[1, 1, 1] += 1                       # hi beyond LB
    vs = dataflow.check_window_table(sg, tbl)
    assert _violated(vs, "window-bounds")
    assert "instance 1 step 1" in _violated(vs, "window-bounds")[0].where


def test_window_overlap_rejected():
    sg = _geo()
    tbl = sg.table()
    tbl[1, 1, 0] -= 1                       # second window re-covers limb 0
    vs = dataflow.check_window_table(sg, tbl)
    assert _violated(vs, "window-overlap")


def test_window_coverage_gap_rejected():
    sg = _geo()
    tbl = sg.table()
    tbl[1, 1] = (0, 0)                      # real step masked out
    vs = dataflow.check_window_table(sg, tbl)
    assert _violated(vs, "window-empty")
    assert _violated(vs, "window-coverage")


def test_unmasked_idle_rejected_by_table_and_interpreter():
    """An idle step carrying a real window is caught twice: by the
    table rule AND independently by the abstract interpreter, which
    proves the step writes effective (maybe-nonzero) data to scratch."""
    configs = (MCIMConfig(arch="fb", ct=1), MCIMConfig(arch="fb", ct=2))
    sg = _geo(configs)
    tbl = sg.table()
    tbl[0, 1] = (0, 2)                      # instance 0 step 1 is idle
    vs = dataflow.check_window_table(sg, tbl)
    assert _violated(vs, "idle-unmasked")
    contract = bank_fold.launch_contract(configs, 2, 2, table=tbl)
    rep = dataflow.analyze_contract(contract)
    hits = _violated(rep.violations, "idle-step-effect")
    assert hits, [v.describe() for v in rep.violations]
    # the violation names the offending grid step and the scratch ref
    assert "(0, 0, 1)" in hits[0].where
    assert "scratch" in hits[0].detail


def test_window_shape_mismatch_rejected():
    sg = _geo()
    vs = dataflow.check_window_table(sg, np.zeros((1, 1, 2), np.int32))
    assert _violated(vs, "window-shape")


def test_hypothesis_random_corruptions_rejected():
    """Property: any single-cell corruption that changes a window table
    is rejected; the pristine table always passes."""
    hyp = pytest.importorskip("hypothesis")
    st = pytest.importorskip("hypothesis.strategies")

    configs = (MCIMConfig(arch="fb", ct=1), MCIMConfig(arch="fb", ct=2),
               MCIMConfig(arch="karatsuba", ct=3))
    sg = _geo(configs, la=4, lb=4)
    good = sg.table()
    assert not dataflow.check_window_table(sg, good)

    @hyp.given(st.integers(0, sg.n_instances - 1),
               st.integers(0, sg.max_steps - 1),
               st.integers(0, 1),
               st.integers(-2, sg.lb + 2))
    @hyp.settings(max_examples=120, deadline=None)
    def prop(i, j, k, val):
        tbl = good.copy()
        tbl[i, j, k] = val
        if np.array_equal(tbl, good):
            assert not dataflow.check_window_table(sg, tbl)
        else:
            assert dataflow.check_window_table(sg, tbl), \
                (i, j, k, val)

    prop()


def test_exhaustive_single_cell_corruptions_rejected():
    """Deterministic edition of the corruption property (runs even when
    the container lacks hypothesis): EVERY single-cell table change is
    rejected; every no-op rewrite passes."""
    configs = (MCIMConfig(arch="fb", ct=1), MCIMConfig(arch="fb", ct=2),
               MCIMConfig(arch="karatsuba", ct=3))
    sg = _geo(configs, la=4, lb=4)
    good = sg.table()
    assert not dataflow.check_window_table(sg, good)
    for i in range(sg.n_instances):
        for j in range(sg.max_steps):
            for k in range(2):
                for val in range(-2, sg.lb + 3):
                    tbl = good.copy()
                    tbl[i, j, k] = val
                    vs = dataflow.check_window_table(sg, tbl)
                    if np.array_equal(tbl, good):
                        assert not vs
                    else:
                        assert vs, (i, j, k, val)


# -------------------------------------------------- synthetic hazards

def _contract(name, fn, args, grid, scratch=(), model=1 << 20):
    return LaunchContract(name=name, fn=fn, args=args, grid=grid,
                          scratch_shapes=scratch,
                          vmem_model_bytes=model)


def test_read_before_write_rejected():
    """A kernel reading VMEM scratch before any write this run."""
    def kernel(x_ref, o_ref, acc_ref):
        o_ref[...] = acc_ref[...] + x_ref[...]

    def fn(x):
        return pl.pallas_call(
            kernel, grid=(1,),
            in_specs=[pl.BlockSpec((8, 8), lambda i: (0, 0))],
            out_specs=pl.BlockSpec((8, 8), lambda i: (0, 0)),
            out_shape=jax.ShapeDtypeStruct((8, 8), jnp.uint32),
            scratch_shapes=[pltpu.VMEM((8, 8), jnp.uint32)],
            interpret=True)(x)

    c = _contract("synthetic/rbw", fn,
                  (jax.ShapeDtypeStruct((8, 8), jnp.uint32),),
                  grid=(1,), scratch=(((8, 8), "uint32"),))
    rep = dataflow.analyze_contract(c)
    hits = _violated(rep.violations, "read-before-write")
    assert hits and "step (0,)" in hits[0].where


def test_waw_between_instances_rejected():
    """Two non-adjacent grid steps writing the same output block."""
    def kernel(x_ref, o_ref):
        o_ref[...] = x_ref[...]

    def fn(x):
        return pl.pallas_call(
            kernel, grid=(3,),
            in_specs=[pl.BlockSpec((8, 8), lambda i: (0, 0))],
            out_specs=pl.BlockSpec((8, 8), lambda i: (i % 2, 0)),
            out_shape=jax.ShapeDtypeStruct((16, 8), jnp.uint32),
            interpret=True)(x)

    c = _contract("synthetic/waw", fn,
                  (jax.ShapeDtypeStruct((8, 8), jnp.uint32),),
                  grid=(3,))
    rep = dataflow.analyze_contract(c)
    assert _violated(rep.violations, "waw-out")


def test_out_of_bounds_index_map_rejected():
    """An index map emitting a block index past the padded extent."""
    def kernel(x_ref, o_ref):
        o_ref[...] = x_ref[...]

    def fn(x):
        return pl.pallas_call(
            kernel, grid=(2,),
            in_specs=[pl.BlockSpec((8, 8), lambda i: (i + 1, 0))],
            out_specs=pl.BlockSpec((8, 8), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((16, 8), jnp.uint32),
            interpret=True)(x)

    c = _contract("synthetic/oob", fn,
                  (jax.ShapeDtypeStruct((16, 8), jnp.uint32),),
                  grid=(2,))
    rep = dataflow.analyze_contract(c)
    hits = _violated(rep.violations, "block-bounds")
    assert hits and "step (1,)" in hits[0].where


def test_grid_mismatch_rejected():
    """A contract whose declared grid disagrees with the traced one."""
    good = mcim_fold.launch_contract(2, 2, 2, "fb")
    bad = dataclasses.replace(good, grid=(1, 7))
    rep = dataflow.analyze_contract(bad)
    assert _violated(rep.violations, "grid-mismatch")


# --------------------------------------------------------------- vmem

def test_understated_vmem_model_rejected():
    good = mcim_fold.launch_contract(2, 2, 2, "fb")
    bad = dataclasses.replace(good, vmem_model_bytes=16)
    rep = dataflow.analyze_contract(bad)
    assert _violated(rep.violations, "vmem-model")


def test_vmem_budget_overflow_rejected():
    c = mcim_fold.launch_contract(2, 2, 2, "fb")
    rep = dataflow.analyze_contract(c, budget=64)
    assert _violated(rep.violations, "vmem-budget")


def test_vmem_breakdown_measures_kernel_refs():
    c = mcim_fold.launch_contract(2, 2, 2, "fb")
    eqn = introspect.find_pallas_calls(c.trace().jaxpr)[0]
    bd = vmem.measure(eqn)
    assert bd.in_bytes > 0 and bd.out_bytes > 0
    assert bd.scratch_bytes > 0
    assert bd.total_bytes == (bd.in_bytes + bd.out_bytes +
                              bd.scratch_bytes + bd.smem_bytes)
    assert bd.fold_bytes <= c.vmem_model_bytes


# ---------------------------------------------------------------- gate

def test_assert_plan_dataflow_passes_clean_plan():
    assert_plan_dataflow(64, 64, ((3, MCIMConfig(arch="star", ct=1)),
                                  (1, MCIMConfig(arch="fb", ct=2))))


def test_assert_plan_dataflow_raises_structured_error():
    """An impossible VMEM budget fails every launch: the gate raises a
    DataflowError (a VerificationError) with structured violations."""
    with pytest.raises(DataflowError) as ei:
        assert_plan_dataflow(64, 64, ((1, MCIMConfig(arch="fb", ct=2)),),
                             budget=64)
    assert isinstance(ei.value, VerificationError)
    assert any(v.rule == "vmem-budget" for v in ei.value.violations)
    assert all(v.analyzer == "dataflow" for v in ei.value.violations)


def test_generate_gates_dataflow(monkeypatch):
    """The designs facade runs the dataflow gate at plan time."""
    from repro import designs, verify
    calls = []
    real = verify.assert_plan_dataflow

    def spy(*a, **kw):
        calls.append(a)
        return real(*a, **kw)

    monkeypatch.setattr(verify, "assert_plan_dataflow", spy)
    designs.generate(designs.DesignSpec(16, 16, "1/2"))
    assert calls


@pytest.mark.parametrize("name", registry.names())
def test_plan_gate_analyzes_one_launch_per_plan(name, monkeypatch):
    """The plan-time gate proves exactly the one fused launch the bank
    issues for the plan, and no per-instance ``mcim_fold`` launch."""
    from repro.designs.compile import _plan_with_timing
    spec = registry.get(name)
    plan, _ = _plan_with_timing(spec)
    seen = []
    real = dataflow.analyze_plan

    def spy(*a, **kw):
        reps = real(*a, **kw)
        seen.extend(reps)
        return reps

    def refuse(*a, **kw):
        raise AssertionError("the gate analyzed an mcim_fold launch")

    monkeypatch.setattr(dataflow, "analyze_plan", spy)
    monkeypatch.setattr(dataflow, "_kernel_report", refuse)
    assert_plan_dataflow(spec.bits_a, spec.bits_b, plan.configs)
    assert [r.name.split("[")[0] for r in seen] == ["bank_fold"]


# ----------------------------------------------------------- roofline

def test_roofline_shares_jaxpr_walker():
    """The bank's launch counting and the analyzer share one walker."""
    c = bank_fold.launch_contract((MCIMConfig(arch="star", ct=1),), 2, 2)
    assert introspect.count_pallas_launches(c.fn, *c.args) == 1
    assert introspect.count_primitive(c.trace().jaxpr, "pallas_call") == 1


def test_static_stats_for_bench_columns():
    configs = ((3, MCIMConfig(arch="star", ct=1)),
               (1, MCIMConfig(arch="fb", ct=2)))
    s = dataflow.plan_static_stats(32, 32, configs)
    assert s["vmem_bytes_step"] > 0
    assert s["arith_intensity"] > 0
    assert s["flops_per_launch"] > 0
    assert s["hbm_bytes_per_launch"] > 0


def test_fused_flops_scale_with_instances():
    """More instances -> more grid steps -> more static FLOPs, while
    the per-step VMEM stays flat (the fused datapath is time-shared)."""
    one = dataflow.plan_static_stats(
        32, 32, ((1, MCIMConfig(arch="fb", ct=2)),))
    four = dataflow.plan_static_stats(
        32, 32, ((4, MCIMConfig(arch="fb", ct=2)),))
    assert four["flops_per_launch"] > one["flops_per_launch"]
    # only the SMEM table grows (3 more instances x 2 steps x 2 int32
    # scalars); the block residency is unchanged -- time-sharing
    assert four["vmem_bytes_step"] - one["vmem_bytes_step"] == 3 * 2 * 2 * 4
