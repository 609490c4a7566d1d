"""A whole run of a cell, rehearsed on the CPU at a tiny batch, with the
timed path sound, replaced by the control, and broken underneath.

Rehearsal skips the look for a chip; everything else is the run the
benchmark makes: set-up through the front door, the closed-loop window,
the check against the host reference, and the result line.
"""
import os
import subprocess
import sys
import types

import numpy as np
import pytest

from bench import cells, harness, reference, run, traffic

ROOT = str(cells.ROOT)


@pytest.fixture(scope="module")
def small():
    bench = harness.Bench(cells.resolve("tp3p5_w32.small"), rehearse=True)
    bench.setup(seed=2**31 + 12345)
    sound = bench.multiply
    yield bench
    bench.multiply = sound


@pytest.fixture
def bench(small):
    sound = small.multiply
    yield small
    small.multiply = sound
    small.warm()


def test_sound_run_is_correct(bench):
    res = run.measure(bench, 0.3, traced=False)
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] == bench.batch * len(bench.calls) > 0
    assert set(res["metrics"]) == {"products_per_s", "latency_p95_ms",
                                   "setup_s"}
    assert list(res)[-1] == "checks"
    assert res["checks"] == {"mismatched_products": {"value": 0,
                                                     "limit": 0}}
    assert bench.window_compiles == 0


def test_window_keeps_every_output_and_checks_after_it(bench):
    bench.window(0.3)
    assert len(bench.outputs) == len(bench.calls) > 0
    # host-resident traffic: every call's products came back to the host
    assert all(isinstance(out, np.ndarray) and out.shape == (64, 4)
               for _, out in bench.outputs)
    attempted, failed = bench.check()
    assert attempted == 44 * len(bench.calls) and failed == 0
    assert bench.outputs == []


def test_past_its_budget_the_window_keeps_a_seeded_sample(bench,
                                                          monkeypatch):
    monkeypatch.setattr(harness, "KEEP_BYTES_PER_CHIP", 10 * 64 * 4 * 4)
    bench.window(1.0)
    calls = len(bench.calls)
    assert calls > 20 and len(bench.outputs) == 10
    assert bench.check() == (44 * calls, 0) and bench.checked == 440
    # a fault in every call is in every sampled output, one row each
    bench.multiply = _later_call(
        bench.multiply, lambda out: out.at[0, 0].set(out[0, 0] ^ 1), after=0)
    bench.window(1.0)
    assert len(bench.calls) > 20 and bench.check()[1] == 10


def test_round_traffic_is_padded_to_a_power_of_two():
    small = cells.resolve("tp3p5_w32.small").traffic
    assert traffic.batch(small, 1) == 44 and traffic.rows(small, 1) == 64
    bulk = cells.resolve("tp3p5_w32.bulk").traffic
    assert traffic.rows(bulk, 4) == traffic.batch(bulk, 4) == 4 << 20
    # 2 chips of 8 rows, the first 6 of each live and the rest zero
    (a, b), = traffic.make_operands(5, 1, 16, 32, 32, products=12,
                                    chips=2)
    a, b = np.asarray(a), np.asarray(b)
    for x in (a, b):
        assert not x[6:8].any() and not x[14:].any()
        assert x[:6].any(axis=1).all() and x[8:14].any(axis=1).all()


def test_device_resident_run_is_checked_after_the_window():
    b = harness.Bench(cells.resolve("tp3p5_w32.bulk"), rehearse=True)
    b.setup(seed=2**33 + 7)
    res = run.measure(b, 0.3, traced=False)
    assert res["correct"] is True and res["attempted"] == 512 * len(b.calls)
    b.multiply = _later_call(
        b.multiply, lambda out: out.at[7, 1].set(out[7, 1] ^ 4), after=4)
    res = run.measure(b, 1.0, traced=False)
    assert res["correct"] is False and res["failed"] >= 1


def test_traced_window_closes_after_its_calls(bench):
    assert bench.cell.traffic["trace_max_calls"] == 3000
    bench.window(60.0, trace=True)
    assert len(bench.calls) == 3000 and bench.window_s < 60.0
    assert bench.check() == (44 * 3000, 0)


def test_slow_calls_are_attributed(bench):
    import time
    sound = bench.multiply

    def slow(a, b):
        time.sleep(0.05)
        return sound(a, b)
    bench.multiply = slow
    bench.stall_s = 0.01
    try:
        bench.window(0.2)
    finally:
        bench.stall_s = None
    where = " ".join(bench.stall_stacks)
    assert sum(bench.stall_stacks.values()) == len(bench.calls)
    assert "slow" in where
    assert bench.gc.within(0.0, 0.0) == 0.0


def test_traced_run_reports_per_layer_metrics(bench):
    res = run.measure(bench, 0.3, traced=True)
    assert res["correct"] is True
    assert {"generate_s", "compile_s", "host_dispatch_ms"} <= \
        set(res["metrics"])
    assert res["device"]["window_s"] > 0
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


def test_control_in_the_programs_place_is_not_correct(bench):
    import jax
    bench.multiply = jax.jit(reference.control_products)
    bench.warm()
    res = run.measure(bench, 0.3, traced=False)
    assert res["correct"] is False
    assert res["failed"] >= bench.n_sets


def _later_call(sound, alter, after=5):
    count = {"n": 0}

    def mul(a, b):
        count["n"] += 1
        out = sound(a, b)
        return alter(out) if count["n"] > after else out
    return mul


def test_one_answer_altered_where_it_is_produced(bench):
    # a later call, which only the on-device comparison sees
    bench.multiply = _later_call(
        bench.multiply, lambda out: out.at[3, 0].set(out[3, 0] ^ 1))
    res = run.measure(bench, 0.3, traced=False)
    assert res["correct"] is False and res["failed"] >= 1


def test_half_the_batch_left_out(bench):
    half = bench.batch // 2
    bench.multiply = _later_call(
        bench.multiply, lambda out: out.at[half:].set(0), after=0)
    res = run.measure(bench, 0.3, traced=False)
    assert res["correct"] is False and res["failed"] >= half


def test_half_the_rows_returned(bench):
    half = bench.batch // 2
    sound = bench.multiply
    bench.multiply = lambda a, b: sound(a[:half], b[:half])
    res = run.measure(bench, 0.3, traced=False)
    assert res["correct"] is False
    assert res["failed"] == res["attempted"]


EXCHANGE_LEFT_OUT = """
import jax, jax.numpy as jnp
from bench import cells, harness, run
import json
root = cells.BENCH_DIR
cell = cells.Cell(
    name="tp3p5_w32_rep4.bulk", chips=4,
    config=json.loads((root / "configs/tp3p5_w32_rep4.json").read_text()),
    traffic=json.loads((root / "traffic/bulk.json").read_text()),
    end_to_end=cells.resolve("tp3p5_w32.bulk").end_to_end, per_layer=())
b = harness.Bench(cell, rehearse=True)
b.setup(seed=99)
sound = b.multiply
def mul(a, c):
    out = sound(a, c)
    shard0 = out.addressable_shards[0].data
    # every replica keeps its own rows; shard 0's are never exchanged
    return jax.device_put(jnp.concatenate([shard0] * 4), out.sharding)
assert run.measure(b, 0.3, traced=False)["correct"] is True
b.multiply = mul
res = run.measure(b, 0.3, traced=False)
print("CORRECT", res["correct"], res["failed"])
"""


def _env(**extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([ROOT, os.path.join(ROOT, "src")]))
    env.update(extra)
    return env


def test_exchange_between_chips_left_out():
    p = subprocess.run(
        [sys.executable, "-c", EXCHANGE_LEFT_OUT], cwd=ROOT,
        env=_env(XLA_FLAGS="--xla_force_host_platform_device_count=4"),
        capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-3000:]
    assert "CORRECT False" in p.stdout


def test_no_accelerator_no_result():
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "tp3p5_w32.small",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=_env(), capture_output=True, text=True, timeout=300)
    assert p.returncode == 2
    assert p.stdout == ""
    assert "no accelerator" in p.stderr


def test_forced_interpreter_is_refused(monkeypatch):
    import jax
    fake = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    monkeypatch.setattr(jax, "devices", lambda *a: [fake])
    monkeypatch.setenv("REPRO_INTERPRET", "1")
    b = harness.Bench(cells.resolve("tp3p5_w32.small"))
    with pytest.raises(harness.BenchError, match="REPRO_INTERPRET"):
        b.setup(seed=1)


def test_benchmark_files_alone_give_no_result(tmp_path):
    import shutil
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = _env()
    env.pop("PYTHONPATH")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "tp3p5_w32.small",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout == ""
