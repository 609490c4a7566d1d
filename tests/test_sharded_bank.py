"""Sharded multi-bank execution on a 2-device placeholder mesh
(subprocess, like test_distributed_features): ``sharded_execute`` must
be bit-exact vs the Python-bigint oracle and vs the single-bank engine,
for the core and fused backends and both batch-available schedulers.
On four devices, a design generated with ``replicas=4``
multiplies through ``CompiledDesign.mul`` as the Python integers do, on
the fused and the core backend."""
import os
import subprocess
import sys

import pytest

from repro.core import planner

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
from fractions import Fraction
import numpy as np
import jax, jax.numpy as jnp

from repro.core import limbs as L
from repro.core import planner, bank

assert len(jax.devices()) == 2
mesh = jax.make_mesh((2,), ("data",))
rng = np.random.default_rng(5)

# TP=7/2 (star+fb), TP=5/6 at 128b (fb+karatsuba), strict 1/2 (ff)
cases = [
    (planner.plan_throughput(32, 32, Fraction(7, 2)), 32),
    (planner.plan_throughput(128, 128, Fraction(5, 6)), 128),
    (planner.plan_throughput(64, 64, Fraction(1, 2), strict_timing=True),
     64),
]
for plan, bits in cases:
    a = jnp.asarray(L.random_limbs(rng, (28,), bits))
    b = jnp.asarray(L.random_limbs(rng, (28,), bits))
    expect = [L.from_limbs(np.asarray(x)) * L.from_limbs(np.asarray(y))
              for x, y in zip(a, b)]
    for backend in ("core", "fused"):
        for sched in ("round_robin", "greedy"):
            out = bank.sharded_execute(plan, a, b, mesh, "data",
                                       backend=backend, scheduler=sched)
            assert L.batch_from_limbs(np.asarray(out)) == expect, \
                (plan.describe(), backend, sched)
            single = bank.execute(plan, a, b, backend=backend,
                                  scheduler=sched)
            assert np.array_equal(np.asarray(out), np.asarray(single))
print("OK sharded-exact")

# the output really is sharded along the axis
plan, bits = cases[0]
a = jnp.asarray(L.random_limbs(rng, (28,), bits))
b = jnp.asarray(L.random_limbs(rng, (28,), bits))
out = bank.sharded_execute(plan, a, b, mesh, "data")
[spec] = {s.spec for s in [out.sharding]}
assert spec[0] == "data", spec
print("OK sharded-layout")

# per-replica accounting: each bank replica sees B/N ops
rep = bank.sharded_report(plan, 28, bits, bits, mesh, "data")
assert rep.batch == 14
assert sum(ir.n_ops for ir in rep.instances) == 14
print("OK sharded-report")

# divisibility and axis guards
try:
    bank.sharded_execute(plan, a[:27], b[:27], mesh, "data")
    raise AssertionError("ragged batch accepted")
except ValueError:
    pass
try:
    bank.sharded_execute(plan, a, b, mesh, "model")
    raise AssertionError("unknown axis accepted")
except ValueError:
    pass
print("OK sharded-guards")
print("ALLOK")
"""


def test_sharded_bank_bit_exact_two_devices():
    env = dict(os.environ,
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-4000:]
    assert "ALLOK" in out.stdout, out.stdout


REPLICATED = r"""
import dataclasses, os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import numpy as np
import jax, jax.numpy as jnp

from repro import designs
from repro.core import limbs as L

assert len(jax.devices()) == 4
backend = sys.argv[1]
spec = designs.DesignSpec.from_dict(
    {"bits_a": 32, "bits_b": 32, "throughput": "7/2", "replicas": 4})
design = designs.generate(dataclasses.replace(spec, backend=backend))
assert design.mesh.shape == {"data": 4}
assert design.bank.backend == backend
rng = np.random.default_rng(2301)
a = jnp.asarray(L.random_limbs(rng, (96,), 32))
b = jnp.asarray(L.random_limbs(rng, (96,), 32))
out = design.mul(a, b)
expect = [L.from_limbs(np.asarray(x)) * L.from_limbs(np.asarray(y))
          for x, y in zip(a, b)]
assert L.batch_from_limbs(np.asarray(out)) == expect
assert np.array_equal(np.asarray(out), np.asarray(design.bank.execute(a, b)))
shards = sorted((s.device.id, s.data.shape) for s in out.addressable_shards)
assert shards == [(d, (24, 4)) for d in range(4)], shards
try:
    design.mul(a[:90], b[:90])
    raise AssertionError("a batch of 90 split over 4 replicas")
except ValueError:
    pass
print("ALLOK")
"""


@pytest.mark.parametrize("backend", ["fused", "core"])
def test_replicated_design_matches_integers_on_four_devices(backend):
    """``generate(replicas=4)`` then ``CompiledDesign.mul``: the sharded
    path on four devices equals the Python-integer products and the one
    bank's ``execute``, in four shards of a quarter of the rows each."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    out = subprocess.run([sys.executable, "-c", REPLICATED, backend],
                         env=env, capture_output=True, text=True,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    assert "ALLOK" in out.stdout, out.stdout


# ------------------------------------------------------------ backends

def test_unknown_backend_capability_rejected_by_bank():
    from repro.core.bank import Bank
    plan = planner.plan_throughput(32, 32, 1)
    for backend in ("fpga", "kernel"):
        with pytest.raises(ValueError, match="backend must be one of"):
            Bank(plan, 32, 32, backend=backend)
