"""Metric arithmetic: rates over the whole window, tails over every
call, work counted from the configuration's shapes."""
import json
import math
import pathlib

import pytest

from bench import cells, harness, work
from bench import trace as tr


def _run(calls, window_s, batch=1000, chips=1, la=2, lb=2, trace=None,
         peaks=None, **kw):
    return harness.Run(
        cell=None, root=cells.ROOT, batch=batch, chips=chips, la=la, lb=lb,
        device_kind="TPU v5 lite", peaks=peaks, setup_s=kw.get("setup_s", 1),
        generate_s=0.2, compile_s=0.5, calls=calls, window_s=window_s,
        memory_peak_bytes=kw.get("memory_peak_bytes", 0), trace=trace)


def read(name, run):
    return cells.reader(name)(run)


def test_rate_is_all_work_over_the_whole_window():
    # three calls in a 2 s window that also holds 0.5 s between calls
    calls = [(0.0, 0.1, 0.5), (0.5, 0.6, 1.0), (1.5, 1.6, 2.0)]
    run = _run(calls, window_s=2.0, batch=1000)
    assert read("products_per_s", run) == pytest.approx(3000 / 2.0)


def test_tail_is_over_every_call():
    calls = [(float(i), float(i), float(i) + (i + 1) * 1e-3)
             for i in range(100)]
    run = _run(calls, window_s=100.0)
    # nearest rank: the 95th of 100 latencies, 1..100 ms
    assert read("latency_p95_ms", run) == pytest.approx(95.0)
    one = _run([(0.0, 0.0, 0.004)], window_s=0.004)
    assert read("latency_p95_ms", one) == pytest.approx(4.0)


def test_host_dispatch_is_the_mean_time_until_mul_returns():
    calls = [(0.0, 0.010, 0.5), (0.5, 0.530, 1.0)]
    assert read("host_dispatch_ms", _run(calls, 1.0)) == \
        pytest.approx(20.0)


def test_interface_bytes_per_configuration():
    # uint32 limbs in and out: 32 MiB per 2^20 products at 32 bits,
    # 128 MiB at 128 bits, whatever the kernel pads them to
    for name, mib in (("tp3p5_w32", 32), ("tp5over6_w128", 128),
                      ("tp3p5_w32_rep4", 32)):
        cfg = json.loads((cells.BENCH_DIR / "configs" / f"{name}.json")
                         .read_text())["spec"]
        la = math.ceil(cfg["bits_a"] / 16)
        lb = math.ceil(cfg["bits_b"] / 16)
        assert work.interface_bytes(1 << 20, la, lb) == mib << 20
    assert work.limb_products(1 << 20, 8, 8) == 64 << 20


def _trace(device_ops, window=(0.0, 1e9)):
    return tr.Trace(ops=device_ops, spans=[], window=window)


def test_kernel_time_roofline_and_idle_from_a_trace():
    peaks = cells.peaks("TPU v5 lite")
    ms = 1e6
    dev = [tr.Event("fusion.1", 0, 2 * ms, "jit_run"),
           tr.Event("fused_bank_mul.1", 2 * ms, 6 * ms, "jit_run"),
           tr.Event("fusion.2", 6 * ms, 7 * ms, "jit_run"),
           tr.Event("fused_bank_mul.1", 500 * ms, 504 * ms, "jit_run"),
           tr.Event("reduce.3", 504 * ms, 505 * ms, "jit_bench_mismatches")]
    run = _run([(0, 0, 0.4), (0.5, 0.5, 0.9)], 1.0, batch=1 << 20,
               trace=_trace({"/device:TPU:0": dev}), peaks=peaks)
    assert read("bank_fold_ms", run) == pytest.approx(4.0)
    # 7 ms of the program's ops over 2 calls, less the kernel's 4 ms
    assert read("gather_scatter_ms", run) == pytest.approx(11 / 2 - 4.0)
    least = (32 << 20) / peaks["hbm_bytes_per_s"]
    assert read("bank_fold_roofline", run) == \
        pytest.approx(least / 4e-3 * 100)
    # the harness's 1 ms is no busy time of the program
    assert read("device_idle_share", run) == pytest.approx(100 - 1.1)


def test_readers_find_nothing_without_a_trace():
    run = _run([(0.0, 0.1, 0.2)], 0.2, memory_peak_bytes=0)
    for name in ("bank_fold_ms", "gather_scatter_ms", "bank_fold_roofline",
                 "device_idle_share", "peak_hbm_gb"):
        assert read(name, run) is None


def test_roofline_is_silent_when_the_kernel_left_the_path():
    dev = [tr.Event("fusion.1", 0, 1e6, "jit_run")]
    run = _run([(0, 0, 0.5)], 1.0, trace=_trace({"/device:TPU:0": dev}),
               peaks=cells.peaks("TPU v5 lite"))
    assert read("bank_fold_ms", run) is None
    assert read("bank_fold_roofline", run) is None


def test_every_metric_has_its_reader():
    bench = cells.load_benchmark()
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert (pathlib.Path(cells.BENCH_DIR) / "metrics"
                / f"{m['name']}.py").is_file(), m["name"]
