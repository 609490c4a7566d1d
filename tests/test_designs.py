"""The designs facade: DesignSpec -> generate() -> CompiledDesign.

Covers the timing path (a tight clock target must reject
non-pipelineable FB designs and fall back per timing_model.meets_timing;
a latency budget must reject designs whose pipeline depth at the target
exceeds it) and provenance (spec -> json -> spec -> generate is
bit-exact vs the original design's mul on random operands, for every
registered Table-VIII design point)."""
import dataclasses
import json
from fractions import Fraction

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro import designs
from repro.core import limbs as L
from repro.core import timing_model as tm

RNG = np.random.default_rng(23)


def _operands(batch, bits_a, bits_b=None):
    bits_b = bits_b or bits_a
    a = jnp.asarray(L.random_limbs(RNG, (batch,), bits_a))
    b = jnp.asarray(L.random_limbs(RNG, (batch,), bits_b))
    expect = [L.from_limbs(np.asarray(x)) * L.from_limbs(np.asarray(y))
              for x, y in zip(a, b)]
    return a, b, expect


# ------------------------------------------------------------- timing path

def test_tight_clock_rejects_fb_and_falls_back():
    """Relaxed planning picks the FB feedback loop; a 0.31 ns target must
    reject it (FB cannot pipeline) and re-plan per meets_timing."""
    relaxed = designs.generate(designs.DesignSpec(32, 32, Fraction(1, 3)))
    assert any(cfg.arch == "fb" for _, cfg in relaxed.plan.configs)

    tight = designs.generate(
        designs.DesignSpec(32, 32, Fraction(1, 3), clock_ns=0.31))
    assert tight.timing_fallback
    assert all(cfg.arch != "fb" for _, cfg in tight.plan.configs)
    assert all(tm.meets_timing(cfg.arch, 32, 0.31, cfg.adder)
               for _, cfg in tight.plan.configs)
    # the clock customization costs area (synthesis stress) but the
    # compiled design still multiplies bit-exactly
    assert tight.area > tight.plan.area
    a, b, expect = _operands(6, 32)
    assert L.batch_from_limbs(np.asarray(tight.mul(a, b))) == expect


def test_strict_spec_never_plans_feedback_loops():
    d = designs.generate(
        designs.DesignSpec(16, 16, Fraction(1, 2), strict_timing=True))
    assert all(tm.pipelineable(cfg.arch, cfg.adder)
               for _, cfg in d.plan.configs)


def test_latency_budget_rejects_deep_pipelines():
    # 128b Karatsuba at 0.2 ns needs retiming stages beyond CT=3
    with pytest.raises(designs.LatencyError):
        designs.generate(designs.DesignSpec(128, 128, Fraction(1, 3),
                                            clock_ns=0.2, latency_budget=3))
    # the same design fits a looser budget
    d = designs.generate(designs.DesignSpec(128, 128, Fraction(1, 3),
                                            clock_ns=0.2, latency_budget=8))
    assert d.latency_cycles <= 8


def test_timing_properties_are_consistent():
    d = designs.generate(
        designs.DesignSpec(32, 32, Fraction(1, 2), clock_ns=0.31))
    # a met clock target bounds the achievable period from above
    assert d.fmax_estimate >= 1.0 / 0.31 - 1e-9
    assert d.latency_cycles >= 2            # CT=2 base plus any retiming
    relaxed = designs.generate(designs.DesignSpec(32, 32, Fraction(1, 2)))
    assert relaxed.area == pytest.approx(relaxed.plan.area)
    assert relaxed.latency_cycles == 2


# ------------------------------------------------------------- provenance

def test_spec_json_round_trip_is_lossless():
    spec = designs.DesignSpec(32, 32, 3.5, clock_ns=0.8, latency_budget=6,
                              strict_timing=True, signed=False,
                              scheduler="greedy", backend="core",
                              replicas=1)
    assert designs.DesignSpec.from_json(spec.to_json()) == spec
    # fractional TP survives exactly (no float round-trip)
    assert designs.DesignSpec.from_json(spec.to_json()).throughput \
        == Fraction(7, 2)


@pytest.mark.parametrize("name", sorted(designs.TABLE_VIII) + ["tp3p5_w32"])
def test_registered_point_round_trip_bit_exact(name):
    """Acceptance: DesignSpec.from_json(spec.to_json()) compiles to a
    design whose mul output is bit-exact equal to the original's."""
    spec = designs.get(name)
    spec2 = designs.DesignSpec.from_json(spec.to_json())
    assert spec2 == spec
    d1 = designs.generate(spec)
    d2 = designs.generate(spec2)
    batch = 2 * max(spec.throughput.numerator, 1)
    a, b, expect = _operands(batch, spec.bits_a, spec.bits_b)
    out1, out2 = d1.mul(a, b), d2.mul(a, b)
    assert np.array_equal(np.asarray(out1), np.asarray(out2))
    assert L.batch_from_limbs(np.asarray(out1)) == expect


def test_non_decomposable_throughput_raises():
    """plan_throughput silently under-provisions TPs its CT set cannot
    reach (3/10 -> a 1/4 bank); the facade must refuse instead."""
    with pytest.raises(designs.DesignError):
        designs.generate(designs.DesignSpec(32, 32, Fraction(3, 10)))
    # a decomposable neighbour still compiles, at its exact rate
    d = designs.generate(designs.DesignSpec(32, 32, Fraction(5, 12)))
    assert d.throughput == Fraction(5, 12)
    assert d.report(10).measured_throughput == Fraction(5, 12)


def test_generate_accepts_registered_names():
    d = designs.generate("tp3p5_w32")
    assert d.throughput == Fraction(7, 2)
    with pytest.raises(ValueError):
        designs.generate("no_such_design")


def test_registry_refuses_silent_redefinition():
    spec = designs.get("tp3p5_w32")
    designs.register("tp3p5_w32", spec)            # same spec: fine
    other = dataclasses.replace(spec, scheduler="greedy")
    with pytest.raises(ValueError):
        designs.register("tp3p5_w32", other)
    designs.register("_test_tmp", other, overwrite=True)
    assert designs.get("_test_tmp") == other


def test_at_fmax_builder():
    spec = designs.DesignSpec.at_fmax(32, 32, Fraction(1, 2), fmax_ghz=2.0)
    assert spec.clock_ns == pytest.approx(0.5)


# ------------------------------------------------------- execution surface

def test_int_convenience_and_signed_mul():
    d = designs.generate(designs.DesignSpec(32, 32, 1))
    assert d.mul(0xDEADBEEF, 0xCAFEBABE) == 0xDEADBEEF * 0xCAFEBABE
    with pytest.raises(ValueError):
        d.mul(1 << 32, 1)                           # out of range

    ds = designs.generate(designs.DesignSpec(32, 32, 1, signed=True))
    assert ds.mul(-5, 7) == -35
    assert ds.mul(-(2 ** 31), 2 ** 31 - 1) == -(2 ** 31) * (2 ** 31 - 1)
    # signed plans carry the flag down to the instance configs
    assert all(cfg.signed for _, cfg in ds.plan.configs)


def test_spec_refuses_kernel_backend():
    """``backend="kernel"`` is no choice: the constructor and the JSON
    both raise DesignError, naming the choices, signed or not."""
    for signed in (False, True):
        with pytest.raises(designs.DesignError, match="'auto', 'core', "
                                                      "'fused'"):
            designs.DesignSpec(32, 32, 1, signed=signed, backend="kernel")
        doc = designs.DesignSpec(32, 32, 1, signed=signed).to_dict()
        doc["backend"] = "kernel"
        with pytest.raises(designs.DesignError, match="'kernel'"):
            designs.DesignSpec.from_json(json.dumps(doc))


@pytest.mark.parametrize("name", designs.names())
def test_auto_backend_resolves_fused_on_tpu(name, monkeypatch):
    """On a TPU, ``auto`` is the fused megakernel for every registry
    design, signed or not."""
    from repro.kernels import runtime
    runtime.interpret_mode()          # cache the CPU's answer first
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    spec = designs.get(name)
    assert spec.backend == "auto"
    for signed in (False, True):
        design = designs.generate(dataclasses.replace(spec, signed=signed))
        assert design.bank.backend == "fused"


def test_scheduler_flows_from_spec_to_reports():
    d = designs.generate(
        designs.DesignSpec(32, 32, Fraction(7, 2), scheduler="greedy"))
    rep = d.report(14)
    assert rep.scheduler == "greedy"
    assert rep.measured_throughput == Fraction(7, 2)


def test_replay_respects_arrival_trace():
    d = designs.generate("tp3p5_w32")
    eager = d.report(14)
    slow = d.replay(tuple(2 * k for k in range(14)))   # 1 op / 2 cycles
    assert slow.scheduler == "streaming"
    assert slow.cycles > eager.cycles
    assert slow.cycles >= 2 * 13                       # last arrival


def test_plan_describe_distinguishes_adder_and_signed():
    """Satellite fix: two genuinely different plans (3CA vs 1CA final
    adder, signed vs unsigned) must no longer print identically."""
    from repro.core.mcim import MCIMConfig
    from repro.core.planner import Plan
    base = MCIMConfig(arch="karatsuba", ct=3, levels=1)
    p1 = Plan(configs=((1, base),), throughput=Fraction(1, 3), area=1.0)
    p3ca = Plan(configs=((1, dataclasses.replace(base, adder="3ca")),),
                throughput=Fraction(1, 3), area=1.0)
    psgn = Plan(configs=((1, dataclasses.replace(base, signed=True)),),
                throughput=Fraction(1, 3), area=1.0)
    assert len({p1.describe(), p3ca.describe(), psgn.describe()}) == 3
    assert "3ca" in p3ca.describe()
    assert "signed" in psgn.describe()


def test_replicas_validate_against_available_devices():
    import jax
    too_many = len(jax.devices()) + 1
    with pytest.raises(designs.DesignError):
        designs.generate(
            designs.DesignSpec(32, 32, 1, replicas=too_many))
