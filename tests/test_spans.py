"""The program's host spans (``repro.spans``) in a real profiler trace:
``mcim.mul`` holds ``mcim.bank.launch``, whose ``rows`` and
``kernel_rows`` count the rows the dispatch is given and the rows the
fused kernel computes, after an ``mcim.bank.report`` only on the first
call of a batch size (the dispatch-cache miss that builds the report).
On a mesh the sharded dispatch launches once per call with its shard
count and rows per shard, builds no report, and marks the first call at
a new shard size with ``mcim.bank.sharded_build``."""
import dataclasses
import glob
import json
import os
import subprocess
import sys
import tempfile

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import designs, spans
from repro.core import limbs as L
from repro.kernels.bank_fold import fused_block_rows

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def traced_spans(fn) -> list:
    """``(name, start_ns, end_ns, args)`` of each program span that
    ``fn()`` records, in start order."""
    from jax.profiler import ProfileData
    with tempfile.TemporaryDirectory() as tmp:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(tmp, profiler_options=opts)
        try:
            fn()
        finally:
            jax.profiler.stop_trace()
        [path] = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                           recursive=True)
        data = ProfileData.from_file(path)
        found = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                  {k: v for k, v in e.stats})
                 for plane in data.planes if plane.name == "/host:CPU"
                 for line in plane.lines for e in line.events
                 if e.name.startswith(spans.PREFIX)]
    return sorted(found, key=lambda s: s[1])


def _design(backend, replicas=1):
    spec = designs.DesignSpec.from_dict(
        {"bits_a": 32, "bits_b": 32, "throughput": "7/2",
         "replicas": replicas})
    return designs.generate(dataclasses.replace(spec, backend=backend))


def _operands(batch, seed=3):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(L.random_limbs(rng, (batch,), 32)),
            jnp.asarray(L.random_limbs(rng, (batch,), 32)))


def _kernel_rows(bank, batch):
    assign, _ = bank.scheduler.schedule(bank._cts, batch)
    return len(bank.instances) * fused_block_rows(assign)[0]


@pytest.fixture(scope="module")
def fused():
    return _design("fused")


def test_span_names_carry_the_program_prefix():
    assert spans.PREFIX == "mcim."
    with spans.span("bank.launch", rows=3):
        pass                        # no profiler: nothing is recorded


@pytest.mark.parametrize("batch", [64, 44])
def test_mul_holds_the_report_then_the_launch(fused, batch):
    # a warm batch size reuses the report built with its dispatch
    a, b = _operands(batch)
    want = np.asarray(fused.mul(a, b))          # compiled outside
    out = []
    found = traced_spans(lambda: out.append(
        np.asarray(fused.mul(a, b))))
    assert np.array_equal(out[0], want)
    assert [s[0] for s in found] == ["mcim.mul", "mcim.bank.launch"]
    mul, launch = found
    assert mul[1] <= launch[1] and launch[2] <= mul[2]
    assert launch[3] == {"rows": batch,
                         "kernel_rows": _kernel_rows(fused.bank, batch)}
    assert mul[3] == {}


@pytest.mark.parametrize("backend,batch",
                         [("fused", 24), ("fused", 40), ("core", 12)])
def test_first_call_of_a_batch_size_builds_the_report(backend, batch):
    design = _design(backend)
    a, b = _operands(batch)
    found = traced_spans(lambda: design.mul(a, b).block_until_ready())
    assert [s[0] for s in found] == ["mcim.mul", "mcim.bank.report",
                                     "mcim.bank.launch"]
    mul, report, launch = found
    assert mul[1] <= report[1] and report[2] <= launch[1] \
        and launch[2] <= mul[2]
    rows = {"rows": batch}
    if backend == "fused":
        rows["kernel_rows"] = _kernel_rows(design.bank, batch)
    assert launch[3] == rows and report[3] == {}


def test_kernel_rows_of_the_benchmark_batches(fused):
    # 4 instances x 19 rows for the serving round of 64; 4 x 300,032
    # for 2^20 products (87.37% of the kernel's rows are real)
    assert _kernel_rows(fused.bank, 64) == 76
    assert _kernel_rows(fused.bank, 1 << 20) == 4 * 300_032


def test_int_operands_take_the_same_spans(fused):
    found = traced_spans(lambda: fused.mul(0xFFFF_FFFF, 0x1234_5678))
    assert [s[0] for s in found] == ["mcim.mul", "mcim.bank.report",
                                     "mcim.bank.launch"]
    assert found[2][3] == {"rows": 1,
                           "kernel_rows": _kernel_rows(fused.bank, 1)}


def test_other_backends_count_no_kernel_rows():
    design = _design("core")
    a, b = _operands(8)
    design.mul(a, b).block_until_ready()
    found = traced_spans(lambda: design.mul(a, b).block_until_ready())
    assert [s[0] for s in found] == ["mcim.mul", "mcim.bank.launch"]
    assert found[1][3] == {"rows": 8}


def test_report_callers_are_spanned(fused):
    found = traced_spans(lambda: (fused.report(64),
                                  fused.replay([0, 0, 1, 2])))
    assert [s[0] for s in found] == ["mcim.bank.report"] * 2


SHARDED = r"""
import dataclasses, json
import numpy as np
import jax
from repro import designs
from repro.core.bank import Bank
from tests.test_spans import _kernel_rows, _operands, traced_spans

assert len(jax.devices()) == 4
spec = designs.DesignSpec.from_dict(
    {"bits_a": 32, "bits_b": 32, "throughput": "7/2", "replicas": 4})
design = designs.generate(dataclasses.replace(spec, backend="fused"))
a, b = _operands(64)
want = np.asarray(design.mul(a, b))
found = traced_spans(
    lambda: [design.mul(a, b).block_until_ready() for _ in range(2)])
cold = traced_spans(lambda: design.mul(*_operands(32)).block_until_ready())
local = Bank(design.plan, 32, 32, backend="fused")
mul, build, launch = cold
print(json.dumps({"names": [s[0] for s in found],
                  "launch_args": [s[3] for s in found
                                  if s[0] == "mcim.bank.launch"],
                  "kernel_rows_per_shard": _kernel_rows(local, 16),
                  "cold_names": [s[0] for s in cold],
                  "cold_launch_args": launch[3],
                  "cold_kernel_rows_per_shard": _kernel_rows(local, 8),
                  "cold_build_args": build[3],
                  "cold_nested": mul[1] <= build[1] and build[2] <= launch[1]
                  and launch[2] <= mul[2],
                  "same": bool(np.array_equal(
                      np.asarray(design.mul(a, b)), want))}))
"""


def test_sharded_path_launches_once_and_builds_no_report():
    # a warm window: one launch per call, no report and no build; the
    # first call at 32 rows (8 a shard) builds its dispatch, once
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([ROOT,
                                           os.path.join(ROOT, "src")]))
    p = subprocess.run([sys.executable, "-c", SHARDED], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-3000:]
    got = json.loads(p.stdout.strip().splitlines()[-1])
    assert got["names"] == ["mcim.mul", "mcim.bank.launch"] * 2
    assert got["launch_args"] == [
        {"rows": 64, "shards": 4, "local_rows": 16,
         "kernel_rows": 4 * got["kernel_rows_per_shard"]}] * 2
    assert got["kernel_rows_per_shard"] == 4 * 5
    assert got["cold_names"] == ["mcim.mul", "mcim.bank.sharded_build",
                                 "mcim.bank.launch"]
    assert got["cold_build_args"] == {}
    assert got["cold_launch_args"] == {
        "rows": 32, "shards": 4, "local_rows": 8,
        "kernel_rows": 4 * got["cold_kernel_rows_per_shard"]}
    assert got["cold_nested"]
    assert got["same"]
