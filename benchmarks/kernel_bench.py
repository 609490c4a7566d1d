"""Kernel/system microbenchmarks: wall time (CPU, indicative only) +
derived structural metrics (exact on any backend: op counts, footprints).

Run as a script: ``python benchmarks/kernel_bench.py`` (full sweep) or
``--smoke`` for the CI subset (the structural tables -- the benches that
gate on correctness, not on CPU wall clock).
"""
from __future__ import annotations

import time

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import limbs as L
from repro.core.schoolbook import star_mul, feedback_mul
from repro.core.karatsuba import karatsuba_mul
from repro.kernels.mcim_fold import vmem_bytes_per_step, mcim_fold_mul
from repro.kernels.int8_matmul import int8_matmul_ref, quantized_matmul
from repro.rng import random_uniform
from repro.exact import exact_sum

RNG = np.random.default_rng(11)


def _time(fn, *args, reps=20):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e6


def _row(name, us, derived):
    print(f"{name},{us:.2f},{derived}")


def bench_core_mul():
    """Batched 128-bit multiplies: star vs folded (jnp, jitted)."""
    a = jnp.asarray(L.random_limbs(RNG, (4096,), 128))
    b = jnp.asarray(L.random_limbs(RNG, (4096,), 128))
    star = jax.jit(star_mul)
    us = _time(star, a, b)
    _row("core.star_128x128_b4096", us, "baseline")
    for ct in (2, 3, 4, 8):
        fb = jax.jit(lambda x, y, c=ct: feedback_mul(x, y, ct=c))
        us = _time(fb, a, b)
        ops = 8 * (-(-8 // ct))     # limb-products instantiated per cycle
        _row(f"core.fb_ct{ct}_128x128_b4096", us,
             f"ppm_ops_per_cycle={ops}/64")
    kara = jax.jit(lambda x, y: karatsuba_mul(x, y, levels=2))
    us = _time(kara, a, b)
    _row("core.karat2_128x128_b4096", us, "subquadratic_ppm")


def bench_vmem_fold():
    """The TPU 'area' table: per-step VMEM working set vs CT."""
    base = vmem_bytes_per_step(8, 8, 1, 256)
    for ct in (1, 2, 3, 4, 8):
        v = vmem_bytes_per_step(8, 8, ct, 256)
        _row(f"kernel.vmem_fold_ct{ct}", 0.0,
             f"vmem_bytes={v} saving={1 - v / base:.0%}")


def bench_mcim_kernel_interpret():
    """Pallas interpret-mode sanity timing (not TPU-representative)."""
    a = jnp.asarray(L.random_limbs(RNG, (256,), 64))
    b = jnp.asarray(L.random_limbs(RNG, (256,), 64))
    us = _time(lambda x, y: mcim_fold_mul(x, y, ct=2, tile_b=256,
                                          interpret=True), a, b, reps=3)
    _row("kernel.mcim_fold_interp_64b_b256", us, "interpret_mode")


def bench_int8_matmul():
    x = jnp.asarray(RNG.standard_normal((256, 512)), jnp.float32)
    w = jnp.asarray(RNG.standard_normal((512, 256)), jnp.float32)
    us_ref = _time(jax.jit(lambda a, b: a @ b), x, w)
    _row("kernel.f32_matmul_256x512x256", us_ref, "baseline")
    us_q = _time(lambda a, b: quantized_matmul(a, b, use_kernel=False),
                 x, w)
    _row("kernel.int8_matmul_256x512x256", us_q,
         f"weight_bytes=0.25x activation_bytes=0.25x")


def bench_rng_exact():
    offs = jnp.arange(1 << 16, dtype=jnp.uint32)
    us = _time(jax.jit(lambda o: random_uniform(3, 1, o)), offs)
    _row("rng.philox_64k", us, f"{(1 << 16) / us:.0f} samples/us")
    x = jnp.asarray(RNG.standard_normal(1 << 16), jnp.float32)
    us_f = _time(jax.jit(jnp.sum), x)
    us_e = _time(jax.jit(exact_sum), x)
    _row("exact.sum_64k", us_e,
         f"overhead_vs_f32sum={us_e / max(us_f, 1e-9):.1f}x bit_exact=True")


ALL = [bench_core_mul, bench_vmem_fold, bench_mcim_kernel_interpret,
       bench_int8_matmul, bench_rng_exact]

#: CI subset: the structural metrics; skips the pure wall-clock benches
#: whose CPU numbers gate nothing
SMOKE = [bench_vmem_fold]


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser(
        description="kernel/system microbenchmarks")
    ap.add_argument("--smoke", action="store_true",
                    help="CI subset (structural tables)")
    args = ap.parse_args()
    print("name,us_per_call,derived")
    for fn in (SMOKE if args.smoke else ALL):
        fn()
