"""Fused bank megakernel: bit-exactness vs the Python-int oracle across
every registry design point and scheduler, the single-launch jaxpr
contract, a dispatch free of index maps, ragged and signed batches, the
fused verifier rules (including seeded corruptions and the
generate()-time refusal), and the centralized interpret-mode runtime
flag."""
import dataclasses
import functools
import re
from fractions import Fraction

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro import designs, verify
from repro.core import limbs as L
from repro.core import planner
from repro.core.bank import Bank
from repro.core.bank.schedule import StreamingScheduler
from repro.core.mcim import MCIMConfig
from repro.designs import registry
from repro.kernels import runtime
from repro.kernels.bank_fold import (fused_ct, fused_windows,
                                     super_geometry)
from repro.kernels.introspect import count_pallas_launches

RNG = np.random.default_rng(47)


def _operands(batch, bits):
    a = jnp.asarray(L.random_limbs(RNG, (batch,), bits))
    b = jnp.asarray(L.random_limbs(RNG, (batch,), bits))
    expect = [L.from_limbs(np.asarray(x)) * L.from_limbs(np.asarray(y))
              for x, y in zip(a, b)]
    return a, b, expect


# ----------------------------------------------- registry-wide bit-exact

@pytest.mark.parametrize("name", registry.names())
def test_fused_bit_exact_every_registry_point(name):
    """Every named design -- Table VIII strict/relaxed, the TP=3.5 and
    TP=5/6 use-case banks, the _lowpower points -- through the fused
    megakernel, vs the bigint oracle."""
    spec = dataclasses.replace(registry.get(name), backend="fused")
    design = designs.generate(spec)
    assert design.bank.backend == "fused"
    batch = 2 * max(spec.throughput.numerator, 1)
    a, b, expect = _operands(batch, spec.bits_a)
    out = design.mul(a, b)
    assert L.batch_from_limbs(np.asarray(out)) == expect


def test_fused_matches_per_instance_paths():
    """Same plan, same operands: fused == core, bitwise."""
    plan = planner.plan_throughput(32, 32, Fraction(7, 2))
    a, b, expect = _operands(11, 32)
    outs = {}
    for backend in ("core", "fused"):
        bk = Bank(plan, 32, 32, backend=backend)
        outs[backend] = np.asarray(bk.execute(a, b))
        assert L.batch_from_limbs(outs[backend]) == expect
    assert np.array_equal(outs["fused"], outs["core"])


# ------------------------------------- contiguous runs, every scheduler

#: the paper's TP=3.5 bank, the widest shipped point and a signed bank
RUN_DESIGNS = {
    "tp3p5_w32": registry.get("tp3p5_w32"),
    "tp5over6_w128": registry.get("tp5over6_w128"),
    "tp3p5_w32_signed": dataclasses.replace(registry.get("tp3p5_w32"),
                                            signed=True),
}
#: the registered policies; streaming with a rate-limited arrival trace,
#: which assigns ops unlike round_robin
RUN_SCHEDULERS = ("round_robin", "greedy",
                  StreamingScheduler(arrival_rate=2))


@functools.lru_cache(maxsize=None)
def _run_design(name):
    spec = dataclasses.replace(RUN_DESIGNS[name], backend="fused")
    return designs.generate(spec)


def _expected(a, b, bits, signed):
    out = []
    for x, y in zip(np.asarray(a), np.asarray(b)):
        x, y = L.from_limbs(x), L.from_limbs(y)
        if signed:
            x -= (x >> (bits - 1)) << bits
            y -= (y >> (bits - 1)) << bits
        out.append((x * y) % (1 << (2 * bits)))
    return out


@pytest.mark.parametrize("batch", (1, 7, 64, 1000))
@pytest.mark.parametrize("scheduler", RUN_SCHEDULERS,
                         ids=lambda s: getattr(s, "name", s))
@pytest.mark.parametrize("name", tuple(RUN_DESIGNS))
def test_fused_contiguous_runs_bit_exact(name, scheduler, batch):
    """The device gives each instance a contiguous run of its scheduled
    op count: products equal the core backend's and the Python-int
    oracle's bit for bit, and the modeled report is the scheduler's."""
    design = _run_design(name)
    spec = design.spec
    fused = Bank(design.plan, spec.bits_a, spec.bits_b, backend="fused",
                 scheduler=scheduler)
    core = Bank(design.plan, spec.bits_a, spec.bits_b, backend="core",
                scheduler=scheduler)
    a, b, _ = _operands(batch, spec.bits_a)
    out = np.asarray(fused.execute(a, b))
    assert np.array_equal(out, np.asarray(core.execute(a, b)))
    assert L.batch_from_limbs(out) == _expected(a, b, spec.bits_a,
                                                spec.signed)
    report = fused.report(batch)
    for field in dataclasses.fields(report):
        assert getattr(fused.last_report, field.name) == \
            getattr(report, field.name), field.name


def _index_ops(text):
    return re.findall(r"stablehlo\.(gather|scatter)\b", text)


@pytest.mark.parametrize("batch", (64, 4096))
@pytest.mark.parametrize("name", ("tp3p5_w32", "tp5over6_w128"))
def test_fused_dispatch_lowers_without_index_maps(name, batch):
    """The lowered fused round holds no gather and no scatter: operands
    reach the kernel by static slices and products leave it the same
    way.  The per-instance core path, which does gather, is the probe's
    control."""
    design = _run_design(name)
    lowered = {}
    for backend in ("fused", "core"):
        bank = Bank(design.plan, design.spec.bits_a, design.spec.bits_b,
                    backend=backend)
        a = jnp.zeros((batch, bank.la), L.LIMB_DTYPE)
        b = jnp.zeros((batch, bank.lb), L.LIMB_DTYPE)
        lowered[backend] = jax.jit(bank.dispatch_fn(batch)).lower(
            a, b).as_text()
    assert _index_ops(lowered["core"])
    assert _index_ops(lowered["fused"]) == []


# --------------------------------------------------------- ragged batches

@pytest.mark.parametrize("batch", (1, 7, 13, 29))
def test_fused_ragged_prime_batches(batch):
    """Prime/ragged batch sizes force padded block rows; the padding
    must never leak into the joined products."""
    plan = planner.plan_throughput(32, 32, Fraction(7, 2))
    bk = Bank(plan, 32, 32, backend="fused")
    a, b, expect = _operands(batch, 32)
    out = bk.execute(a, b)
    assert L.batch_from_limbs(np.asarray(out)) == expect


# ----------------------------------------------------------------- signed

def test_fused_signed_bit_exact():
    """Signed designs run the fused unsigned kernel plus the shared
    two's-complement correction pass -- still bit-exact, still one
    launch."""
    spec = designs.DesignSpec(32, 32, Fraction(7, 2), signed=True,
                              backend="fused")
    design = designs.generate(spec)
    vals = [int(v) for v in RNG.integers(-2**31, 2**31, 9)]
    for x, y in zip(vals, reversed(vals)):
        assert design.mul(x, y) == x * y
    assert design.bank.launch_count(9) == 1


# ------------------------------------------------------------ launch count

def test_fused_single_launch_per_round():
    """The tentpole contract: a fused bank round of a four-instance bank
    traces to EXACTLY one pallas_call, the core round to none."""
    plan = planner.plan_throughput(32, 32, Fraction(7, 2))
    batch = 14
    fused = Bank(plan, 32, 32, backend="fused")
    assert len(fused.instances) == 4
    assert fused.launch_count(batch) == 1
    core = Bank(plan, 32, 32, backend="core")
    assert core.launch_count(batch) == 0


def test_count_pallas_launches_sees_nested_jits():
    import jax
    from repro.kernels.mcim_fold import big_mul
    a = jnp.asarray(L.random_limbs(RNG, (8,), 32))
    b = jnp.asarray(L.random_limbs(RNG, (8,), 32))

    def two_rounds(x, y):
        return big_mul(x, y, ct=2) + jax.jit(lambda u, v: big_mul(
            u, v, ct=1, schedule="fb"))(x, y)

    assert count_pallas_launches(two_rounds, a, b) == 2


# -------------------------------------------------------- fused geometry

def test_fused_ct_mapping():
    assert fused_ct(MCIMConfig(arch="star", ct=1)) == 1
    assert fused_ct(MCIMConfig(arch="fb", ct=4)) == 4
    assert fused_ct(MCIMConfig(arch="ff", ct=6)) == 6
    assert fused_ct(MCIMConfig(arch="karatsuba", ct=3)) == 3


def test_super_geometry_idle_padding():
    cfgs = (MCIMConfig(arch="star", ct=1), MCIMConfig(arch="fb", ct=4))
    sg = super_geometry(cfgs, 8, 8)
    assert sg.max_steps == 4
    star_wins = sg.windows(0)
    assert star_wins[0] == (0, 8)
    assert star_wins[1:] == ((0, 0),) * 3        # idle steps masked
    tbl = sg.table()
    assert tbl.shape == (2, 4, 2)
    assert tuple(tbl[0, 0]) == (0, 8)
    assert tuple(tbl[0, 3]) == (0, 0)


def test_super_geometry_refuses_empty_bank():
    with pytest.raises(ValueError):
        super_geometry((), 4, 4)


# ---------------------------------------------------------- verifier rules

def test_fused_verifier_proves_registry():
    """verify_plan sweeps the fused substrate + super-geometry contracts
    for every registry plan with zero violations."""
    for name in registry.names():
        spec = registry.get(name)
        design = designs.generate(name)
        violations = verify.verify_plan(spec.bits_a, spec.bits_b,
                                        design.plan.configs,
                                        design.plan.throughput)
        assert not violations, (name, violations)


def test_fused_seeded_window_corruption_caught():
    cfg = MCIMConfig(arch="fb", ct=2)
    good = verify.check_fused_schedule(32, 32, cfg)
    assert not good
    # drop a limb from the second window: missing-product
    bad = verify.check_fused_schedule(
        32, 32, cfg, windows=((0, 1), (1, 1)))
    assert any(v.rule == "missing-product" for v in bad)
    # overlap the windows: double-cover
    bad = verify.check_fused_schedule(
        32, 32, cfg, windows=((0, 2), (1, 2)))
    assert any(v.rule == "double-cover" for v in bad)
    # a window past the last real limb is clipped to empty, so the
    # damage surfaces as the limbs it no longer covers
    bad = verify.check_fused_schedule(
        32, 32, cfg, windows=((0, 1), (2, 3)))
    assert any(v.rule == "missing-product" for v in bad)


def test_fused_seeded_scratch_corruption_caught():
    cfg = MCIMConfig(arch="ff", ct=4)
    assert not verify.check_fused_widths(64, 64, cfg)
    bad = verify.check_fused_widths(64, 64, cfg, scratch_width=7)
    assert any(v.rule == "scratch-too-narrow" for v in bad)
    bad = verify.check_fused_widths(64, 64, cfg, out_width=6)
    assert any(v.rule == "out-width" for v in bad)


def test_generate_refuses_unprovable_fused_plan(monkeypatch):
    """The plan-time gate: when the fused contracts report a violation,
    generate() raises before any bank is built."""
    boom = verify.Violation("contracts", "fused-idle-mask", "seeded",
                            "test-injected violation")
    monkeypatch.setattr(verify.contracts, "check_fused_plan",
                        lambda *a, **k: [boom])
    spec = designs.DesignSpec(32, 32, Fraction(7, 2), backend="fused")
    with pytest.raises(verify.VerificationError):
        designs.generate(spec)


def test_fused_interval_walk_matches_windows():
    """The fused interval substrate exists and its required width is the
    full product width (the shared accumulator contract)."""
    rep = verify.analyze(128, 128, MCIMConfig(arch="fb", ct=8),
                         substrate="fused")
    assert rep.ok
    assert rep.required_width == 16
    wins = fused_windows(MCIMConfig(arch="fb", ct=8), 8, 8)
    assert wins[-1][1] == 8                     # clipped to real limbs


# ----------------------------------------------------- engine integration

def test_fused_working_set_is_max_not_sum():
    """Fused instances time-share one datapath: the bank working set is
    that one footprint, not the per-instance sum the core bank reports."""
    from repro.kernels import bank_fold, mcim_fold
    plan = planner.plan_throughput(32, 32, Fraction(7, 2))
    fused = Bank(plan, 32, 32, backend="fused")
    core = Bank(plan, 32, 32, backend="core")
    rf = fused.report(14)
    rc = core.report(14)
    assert rf.working_set_bytes == bank_fold.vmem_bytes_per_step(2, 2, 256)
    # three Stars (CT=1) and one FB CT=2, each its own mcim_fold datapath
    assert rc.working_set_bytes == (
        3 * mcim_fold.vmem_bytes_per_step(2, 2, 1, 256)
        + mcim_fold.vmem_bytes_per_step(2, 2, 2, 256))
    assert rf.working_set_bytes < rc.working_set_bytes


def test_fused_refuses_mixed_signedness():
    star = MCIMConfig(arch="star", ct=1)
    fb_signed = MCIMConfig(arch="fb", ct=2, signed=True)
    plan = planner.Plan(configs=((1, star), (1, fb_signed)),
                        throughput=Fraction(3, 2), area=1.0)
    with pytest.raises(ValueError, match="signedness"):
        Bank(plan, 32, 32, backend="fused")


def test_auto_backend_resolves_core_on_cpu():
    """The CPU container must not silently pay interpret-mode kernels:
    auto stays on the pure-jnp core path off-TPU."""
    design = designs.generate(designs.DesignSpec(32, 32, Fraction(1, 2)))
    assert design.bank.backend == "core"


# ------------------------------------------------------------ runtime flag

def test_runtime_flag_env_override(monkeypatch):
    monkeypatch.setenv("REPRO_INTERPRET", "0")
    runtime.reset()
    assert runtime.interpret_mode() is False
    monkeypatch.setenv("REPRO_INTERPRET", "1")
    runtime.reset()
    assert runtime.interpret_mode() is True
    # auto: interpret on the CPU container
    monkeypatch.delenv("REPRO_INTERPRET")
    runtime.reset()
    assert runtime.interpret_mode() is True
    runtime.reset()


def test_no_per_module_interpret_constants():
    """The dedup satellite: no kernel ops module owns its own INTERPRET
    flag anymore; runtime.interpret_mode is the single policy."""
    import pathlib
    import repro.kernels as K
    root = pathlib.Path(K.__file__).parent
    for ops in root.glob("*/ops.py"):
        text = ops.read_text()
        assert "INTERPRET =" not in text, f"{ops} still owns a flag"
        assert "runtime.interpret_mode" in text or "interpret" not in \
            text.lower(), f"{ops} bypasses repro.kernels.runtime"
