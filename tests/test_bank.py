"""Bank execution engine: bit-exactness vs the Python-int oracle and
cycle accounting vs Plan.throughput, for every plan the planner emits
at the paper's fractional design points -- under every scheduler policy
and on both backends.  Also covers the generalized mcim_fold kernel
(FB + FF schedules for CT in {2, 3, 4, 6}, the folded Karatsuba CT=3
schedule, and awkward-batch tile padding)."""
from fractions import Fraction

import numpy as np
import pytest
import jax.numpy as jnp

from repro.core import limbs as L
from repro.core import planner, bank
from repro.kernels.mcim_fold import big_mul

RNG = np.random.default_rng(41)

TPS = (Fraction(1, 2), Fraction(7, 2), Fraction(5, 6))
BITS = (32, 64, 128)


def _operands(batch, bits):
    a = jnp.asarray(L.random_limbs(RNG, (batch,), bits))
    b = jnp.asarray(L.random_limbs(RNG, (batch,), bits))
    expect = [L.from_limbs(np.asarray(x)) * L.from_limbs(np.asarray(y))
              for x, y in zip(a, b)]
    return a, b, expect


# --------------------------------------------------------------- bit-exact

@pytest.mark.parametrize("backend", bank.BACKENDS)
@pytest.mark.parametrize("bits", BITS)
@pytest.mark.parametrize("tp", TPS, ids=str)
def test_bank_bit_exact_core(tp, bits, backend):
    plan = planner.plan_throughput(bits, bits, tp)
    a, b, expect = _operands(3 * max(tp.numerator, 1), bits)
    out = bank.execute(plan, a, b, backend=backend)
    assert L.batch_from_limbs(np.asarray(out)) == expect


@pytest.mark.parametrize("scheduler", ("greedy", "streaming"))
@pytest.mark.parametrize("tp", TPS, ids=str)
def test_bank_bit_exact_any_scheduler(tp, scheduler):
    """The dispatch policy must never change the products, only the
    cycle accounting."""
    plan = planner.plan_throughput(64, 64, tp)
    a, b, expect = _operands(3 * max(tp.numerator, 1), 64)
    out = bank.execute(plan, a, b, scheduler=scheduler)
    assert L.batch_from_limbs(np.asarray(out)) == expect
    assert np.array_equal(np.asarray(out),
                          np.asarray(bank.execute(plan, a, b)))


def test_bank_kernel_backend_karatsuba_arch():
    """A karatsuba-bearing plan (128b, CT=3) runs entirely through the
    fused Pallas launch, as a 3-window schoolbook."""
    plan = planner.plan_throughput(128, 128, Fraction(1, 3))
    assert any(cfg.arch == "karatsuba" for _, cfg in plan.configs)
    a, b, expect = _operands(6, 128)
    out = bank.execute(plan, a, b, backend="fused")
    assert L.batch_from_limbs(np.asarray(out)) == expect


def test_bank_single_op_and_width_check():
    plan = planner.plan_throughput(32, 32, Fraction(1, 2))
    a, b, expect = _operands(1, 32)
    out = bank.execute(plan, a[0], b[0])            # 1-D convenience
    assert L.from_limbs(np.asarray(out)) == expect[0]
    bk = bank.Bank(plan, 32, 32)
    with pytest.raises(ValueError):
        bk.execute(jnp.zeros((4, 8), jnp.uint32), jnp.zeros((4, 2),
                                                            jnp.uint32))
    with pytest.raises(ValueError):       # gather would clamp silently
        bk.execute(jnp.zeros((8, 2), jnp.uint32), jnp.zeros((4, 2),
                                                            jnp.uint32))


# --------------------------------------------------------- cycle accounting

@pytest.mark.parametrize("bits", (32, 128))
@pytest.mark.parametrize("tp", TPS, ids=str)
def test_bank_throughput_matches_plan(tp, bits):
    """Over whole hyperperiods the round-robin schedule must sustain
    exactly the plan's claimed ops/cycle."""
    plan = planner.plan_throughput(bits, bits, tp)
    bk = bank.Bank(plan, bits, bits)
    batch = 4 * tp.numerator
    rep = bk.report(batch)
    assert rep.measured_throughput == plan.throughput, rep
    assert rep.utilization == 1.0
    # per-instance busy cycles never exceed the makespan
    assert all(ir.busy_cycles <= rep.cycles for ir in rep.instances)
    # every op is assigned exactly once
    assert sum(ir.n_ops for ir in rep.instances) == batch


def test_bank_report_attached_after_execute():
    plan = planner.plan_throughput(32, 32, Fraction(7, 2))
    bk = bank.Bank(plan, 32, 32)
    a, b, _ = _operands(14, 32)
    bk.execute(a, b)
    assert bk.last_report is not None
    assert bk.last_report.batch == 14
    assert bk.last_report.measured_throughput <= plan.throughput


@pytest.mark.parametrize("scheduler", [
    "round_robin", "greedy", bank.StreamingScheduler(arrival_rate=2)],
    ids=["round_robin", "greedy", "streaming"])
def test_report_is_built_once_per_batch_size(scheduler, monkeypatch):
    """``execute`` keeps one report per compiled batch size: warm calls
    share it, it equals a fresh ``report``, and it is evicted (and
    rebuilt equal) with its dispatch."""
    plan = planner.plan_throughput(32, 32, Fraction(7, 2))
    bk = bank.Bank(plan, 32, 32, scheduler=scheduler)
    a, b, _ = _operands(14, 32)
    bk.execute(a, b)
    first = bk.last_report
    bk.execute(a, b)
    assert bk.last_report is first
    assert first == bk.report(14)
    bk.execute(a[:6], b[:6])
    assert bk.last_report.batch == 6
    assert bk.last_report == bk.report(6)
    bk.execute(a, b)
    assert bk.last_report is first
    # a third batch size pushes the oldest (14) out of a FIFO of two
    monkeypatch.setattr(bk, "MAX_COMPILED", 2)
    bk.execute(a[:3], b[:3])
    assert set(bk._compiled) == {6, 3}
    bk.execute(a, b)
    assert bk.last_report is not first
    assert bk.last_report == first == bk.report(14)


def test_round_robin_schedule_is_work_conserving():
    assign, cycles = bank.round_robin_schedule((1, 1, 1, 2), 56)
    # 3 stars take 16 each, the CT=2 unit 8; last retirement at cycle 16
    assert [len(x) for x in assign] == [16, 16, 16, 8]
    assert cycles == 16


def test_greedy_beats_round_robin_on_heterogeneous_tail():
    """cts=(1,3), 2 ops: round-robin parks op 1 on the slow unit
    (makespan 3); greedy keeps both on the fast unit (makespan 2)."""
    _, rr = bank.round_robin_schedule((1, 3), 2)
    _, greedy = bank.greedy_schedule((1, 3), 2)
    assert (rr, greedy) == (3, 2)


def test_streaming_scheduler_respects_arrivals_in_bank():
    """A Bank with an arrival-rate streaming policy still multiplies
    bit-exactly, and its makespan stretches to cover the arrival tail."""
    plan = planner.plan_throughput(32, 32, Fraction(7, 2))
    batch = 14
    sched = bank.StreamingScheduler(arrival_rate=2)   # 2 ops arrive/cycle
    bk = bank.Bank(plan, 32, 32, scheduler=sched)
    a, b, expect = _operands(batch, 32)
    out = bk.execute(a, b)
    assert L.batch_from_limbs(np.asarray(out)) == expect
    rep = bk.last_report
    assert rep.scheduler == "streaming"
    eager = bank.Bank(plan, 32, 32).report(batch)
    assert rep.cycles >= eager.cycles
    assert rep.cycles >= bank.uniform_arrivals(batch, 2)[-1] + 1


# ------------------------------------------------------- generalized kernel

@pytest.mark.parametrize("ct", (2, 3, 4, 6))
@pytest.mark.parametrize("schedule", ("fb", "ff"))
def test_mcim_fold_kernel_schedules(schedule, ct):
    a, b, expect = _operands(16, 64)
    out = big_mul(a, b, ct=ct, schedule=schedule)
    assert L.batch_from_limbs(np.asarray(out)) == expect
    ref = big_mul(a, b, ct=ct, schedule=schedule, use_kernel=False)
    assert np.array_equal(np.asarray(out), np.asarray(ref))


def test_mcim_fold_kernel_ct_exceeds_limbs():
    """CT larger than the B-limb count: trailing cycles are idle, the
    product must still be exact (32 bits = 2 limbs, CT=6)."""
    a, b, expect = _operands(8, 32)
    out = big_mul(a, b, ct=6, schedule="fb")
    assert L.batch_from_limbs(np.asarray(out)) == expect


def test_ff_kernel_rejects_single_cycle():
    a, b, _ = _operands(4, 32)
    with pytest.raises(ValueError):
        big_mul(a, b, ct=1, schedule="ff")


# --------------------------------------------------- folded Karatsuba kernel

@pytest.mark.parametrize("bits", (16, 32, 48, 64, 128))
def test_kara_fold_kernel_bit_exact(bits):
    a, b, expect = _operands(16, bits)
    out = big_mul(a, b, ct=3, schedule="karatsuba")
    assert L.batch_from_limbs(np.asarray(out)) == expect
    ref = big_mul(a, b, ct=3, schedule="karatsuba", use_kernel=False)
    assert np.array_equal(np.asarray(out), np.asarray(ref))


def test_kara_fold_kernel_rectangular_operands():
    """Unequal widths pad to a common even split inside the kernel --
    the old equal-width-only restriction (and its silent core fallback)
    is gone."""
    a = jnp.asarray(L.random_limbs(RNG, (8,), 64))
    b = jnp.asarray(L.random_limbs(RNG, (8,), 32))
    expect = [L.from_limbs(np.asarray(x)) * L.from_limbs(np.asarray(y))
              for x, y in zip(a, b)]
    out = big_mul(a, b, ct=3, schedule="karatsuba")
    assert L.batch_from_limbs(np.asarray(out)) == expect


def test_kara_fold_kernel_requires_ct3():
    a, b, _ = _operands(4, 32)
    with pytest.raises(ValueError):
        big_mul(a, b, ct=2, schedule="karatsuba")


# ----------------------------------------------------- batch-tile selection

def test_batch_tile_prefers_exact_divisors():
    from repro.kernels.mcim_fold import batch_tile
    assert batch_tile(512) == (512, 0)
    assert batch_tile(48) == (16, 0)
    assert batch_tile(3) == (3, 0)           # tiny batch: one short tile
    assert batch_tile(9) == (9, 0)           # padding 9 -> 16 would waste 78%


def test_batch_tile_pads_awkward_batches():
    """A large prime batch must not degenerate into 1-row tiles (the old
    VMEM-estimate blowup): pad to a near tile multiple instead."""
    from repro.kernels.mcim_fold import batch_tile
    tile, pad = batch_tile(509)
    assert tile >= 64 and (509 + pad) % tile == 0
    assert pad * 8 <= 512                      # bounded waste
    tile, pad = batch_tile(1030)               # 2*5*103: divisor 2 only
    assert tile >= 64 and (1030 + pad) % tile == 0


@pytest.mark.parametrize("batch", (7, 13, 509))
def test_big_mul_awkward_batches_bit_exact(batch):
    a, b, expect = _operands(batch, 32)
    out = big_mul(a, b, ct=2, schedule="fb")
    assert out.shape == (batch, 4)
    assert L.batch_from_limbs(np.asarray(out)) == expect
