"""Run the fused multiplier bank on a TPU through ``repro.designs``.

    python chip_smoke.py             one chip: tp3p5_w32 (the paper's TP=3.5
                                     bank) and tp5over6_w128 (the widest
                                     shipped point) each multiply 2**20
                                     seeded random operand pairs, then
                                     tp3p5_w32 serves a seeded request stream
    python chip_smoke.py --chips 4   tp3p5_w32 with replicas=4 over a
                                     4-device mesh on 2**20 pairs, compared
                                     with the one-chip bank; no other phase

Every product is checked bit for bit against a numpy schoolbook on the
host, which shares no code with the repo.  Lines before the last are for
information only and are not a benchmark.  The last line of standard
output is one JSON object naming the device.  A machine without a TPU, a
forced Pallas interpreter, and any failed check exit nonzero.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
BATCH = 1 << 20
SEED = 0
DESIGNS = ("tp3p5_w32", "tp5over6_w128")
SERVED_DESIGN = "tp3p5_w32"
SERVED_REQUESTS = 300
REPLICATED_DESIGN = "tp3p5_w32"


def fail(msg: str):
    print(f"chip_smoke: FAILED: {msg}", file=sys.stderr)
    sys.exit(1)


def check(cond, msg: str) -> None:
    if not cond:
        fail(msg)


def info(msg: str) -> None:
    print(f"chip_smoke: {msg}", flush=True)


# ------------------------------------------------------------ host side

def operands(rng, batch: int, bits: int) -> np.ndarray:
    """``batch`` random ``bits``-wide integers as 16-bit limbs in uint32."""
    limbs = -(-bits // 16)
    x = rng.integers(0, 1 << 16, size=(batch, limbs), dtype=np.uint32)
    x[:, -1] &= np.uint32((1 << (bits - 16 * (limbs - 1))) - 1)
    return x


def host_products(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Schoolbook limb products (B, LA) x (B, LB) -> (B, LA+LB) in uint64."""
    a = a.astype(np.uint64)
    b = b.astype(np.uint64)
    n, la = a.shape
    lb = b.shape[1]
    cols = np.zeros((n, la + lb + 1), np.uint64)
    for i in range(la):
        for j in range(lb):
            p = a[:, i] * b[:, j]
            cols[:, i + j] += p & np.uint64(0xFFFF)
            cols[:, i + j + 1] += p >> np.uint64(16)
    out = np.empty((n, la + lb), np.uint32)
    carry = np.zeros(n, np.uint64)
    for k in range(la + lb):
        tot = cols[:, k] + carry
        out[:, k] = tot & np.uint64(0xFFFF)
        carry = tot >> np.uint64(16)
    return out


def _as_int(limbs: np.ndarray) -> int:
    return int.from_bytes(limbs.astype("<u2").tobytes(), "little")


def reference(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Host reference, itself checked against Python ints on 256 rows."""
    want = host_products(a, b)
    for r in range(min(256, len(a))):
        check(_as_int(a[r]) * _as_int(b[r]) == _as_int(want[r]),
              f"host schoolbook disagrees with Python ints on row {r}")
    return want


def compare(name: str, got, want: np.ndarray) -> None:
    got = np.asarray(got)
    check(got.shape == want.shape,
          f"{name}: products have shape {got.shape}, want {want.shape}")
    bad = int(np.count_nonzero((got != want).any(axis=1)))
    check(bad == 0, f"{name}: {bad} of {len(want)} products differ from "
                    f"the host reference")


class CompileTimer:
    """Sums JAX's backend-compile durations while it is entered."""

    def __init__(self):
        self.seconds = 0.0

    def _listen(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._listen)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._listen)


# --------------------------------------------------------------- phases

def run_batch(name: str, batch: int, seed: int) -> None:
    """One registry design multiplies ``batch`` pairs through ``mul``."""
    import jax
    from repro import designs
    design = designs.generate(name)
    check(design.bank.backend == "fused",
          f"{name}: backend auto resolved to {design.bank.backend!r}")
    launches = design.bank.launch_count(batch)
    check(launches == 1, f"{name}: one bank round traced to {launches} "
                         f"Pallas launches")
    rng = np.random.default_rng(seed)
    a_np = operands(rng, batch, design.spec.bits_a)
    b_np = operands(rng, batch, design.spec.bits_b)
    a, b = jax.device_put(a_np), jax.device_put(b_np)
    with CompileTimer() as ct:
        t0 = time.perf_counter()
        design.mul(a, b).block_until_ready()
        first = time.perf_counter() - t0
    t0 = time.perf_counter()
    out = design.mul(a, b)
    out.block_until_ready()
    warm = time.perf_counter() - t0
    compare(name, out, reference(a_np, b_np))
    info(f"{name}: {design.plan.describe()}  batch={batch} "
         f"launches_per_round={launches} compile_s={ct.seconds:.3f} "
         f"first_call_s={first:.3f} warm_call_s={warm:.3f} bit_exact=True")


def run_served(name: str, n: int, seed: int) -> None:
    """A seeded request stream through ``serve(check=True)``."""
    from repro import designs
    from repro.serving import poisson_arrivals, synthesize
    design = designs.generate(name)
    check(design.bank.backend == "fused",
          f"{name}: backend auto resolved to {design.bank.backend!r}")
    tp = float(design.plan.throughput)
    arrivals = poisson_arrivals(n, 0.7 * tp, seed=seed)
    reqs = synthesize(arrivals, design.spec.bits_a, design.spec.bits_b,
                      budget=max(8, int(32 / tp)), seed=seed + 1)
    rep, _ = design.serve(reqs, check=True)
    check(rep.n_completed > 0 and rep.n_checked == rep.n_completed,
          f"{name}: served {rep.n_completed} requests, checked "
          f"{rep.n_checked}")
    check(rep.bit_exact is True,
          f"{name}: {rep.n_mismatch} served products differ from the "
          f"Python-int oracle")
    info(f"{name}: served {rep.n_requests} requests, "
         f"{rep.n_completed} completed and checked, {rep.n_refused} "
         f"refused, rounds={rep.rounds} bit_exact=True")


def run_replicated(name: str, batch: int, seed: int, n: int) -> None:
    """``replicas=n`` over an n-device mesh vs the one-chip bank."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro import designs
    spec = designs.registry.get(name)
    wide = designs.generate(dataclasses.replace(spec, replicas=n))
    one = designs.generate(spec)
    for d in (wide, one):
        check(d.bank.backend == "fused",
              f"{name}: backend auto resolved to {d.bank.backend!r}")
    mesh = wide.mesh
    ids = {d.id for d in mesh.devices.flat}
    check(mesh.devices.size == n and len(ids) == n,
          f"{name}: mesh spans devices {sorted(ids)}, want {n}")
    rng = np.random.default_rng(seed)
    a_np = operands(rng, batch, spec.bits_a)
    b_np = operands(rng, batch, spec.bits_b)
    sharding = NamedSharding(mesh, P(spec.mesh_axis))
    a, b = jax.device_put(a_np, sharding), jax.device_put(b_np, sharding)
    with CompileTimer() as ct:
        t0 = time.perf_counter()
        out = wide.mul(a, b)
        out.block_until_ready()
        first = time.perf_counter() - t0
    shards = out.addressable_shards
    rows = sorted((s.device.id, s.data.shape[0]) for s in shards)
    check(len(shards) == n and len({s.device.id for s in shards}) == n
          and all(r == batch // n for _, r in rows),
          f"{name}: output shards (device, rows) = {rows}, want {n} "
          f"devices of {batch // n} rows")
    single = one.mul(jax.device_put(a_np), jax.device_put(b_np))
    want = reference(a_np, b_np)
    compare(f"{name} replicas={n}", out, want)
    compare(f"{name} one chip", single, want)
    check(np.array_equal(np.asarray(out), np.asarray(single)),
          f"{name}: replicated and one-chip products differ")
    info(f"{name}: replicas={n} batch={batch} shards={rows} "
         f"compile_s={ct.seconds:.3f} first_call_s={first:.3f} "
         f"bit_exact_vs_host=True bit_exact_vs_one_chip=True")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(
        description="Run the fused multiplier bank on a TPU and check "
                    "every product.")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the replicated tp3p5_w32 phase, on a "
                         "4-device mesh")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        fail(f"no TPU: JAX's first device is on {dev.platform!r}")
    if len(devices) < args.chips:
        fail(f"--chips {args.chips} needs {args.chips} TPUs, JAX sees "
             f"{len(devices)}")
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.kernels import runtime
    if runtime.interpret_mode():
        fail("REPRO_INTERPRET forces the Pallas interpreter on a TPU")
    cache = runtime.enable_compilation_cache()
    info(f"device={dev.device_kind} count={len(devices)} "
         f"jax={jax.__version__} compilation_cache={cache}")

    if args.chips == 4:
        run_replicated(REPLICATED_DESIGN, BATCH, SEED, 4)
    else:
        for i, name in enumerate(DESIGNS):
            run_batch(name, BATCH, SEED + i)
        run_served(SERVED_DESIGN, SERVED_REQUESTS, SEED)

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
