"""The reduction from a profiler trace to busy time, idle gaps, kernel
time and the breakdown."""
import pathlib

import pytest

from bench import trace as tr

TESTDATA = pathlib.Path(__file__).parent / "testdata"


def ev(name, s, e, module=""):
    return tr.Event(name, s, e, module)


def test_union_merges_overlaps_and_clips_to_the_window():
    events = [ev("a", 0, 10), ev("b", 5, 15), ev("c", 20, 30),
              ev("d", 25, 26), ev("e", 90, 200)]
    assert tr.union_ns(events, 0, 100) == 15 + 10 + 10
    assert tr.union_ns(events, 8, 22) == 7 + 2
    assert tr.union_ns([], 0, 100) == 0


def test_idle_gaps_cover_the_rest_of_the_window():
    events = [ev("a", 10, 20), ev("b", 15, 30), ev("c", 50, 60)]
    gaps = tr.idle_gaps(events, 0, 100)
    assert gaps == [(0, 10), (30, 50), (60, 100)]
    busy = tr.union_ns(events, 0, 100)
    assert busy + sum(e - s for s, e in gaps) == 100


def test_gaps_are_named_by_the_host_span_that_overlaps_most():
    spans = [ev("bench.window", 0, 100), ev("bench.mul", 0, 40),
             ev("bench.wait", 40, 45), ev("bench.check", 45, 47)]
    assert tr.host_label((30, 50), spans) == "bench.mul"
    assert tr.host_label((41, 46), spans) == "bench.wait"
    assert tr.host_label((80, 90), spans) == "none"


def test_breakdown_averages_over_devices_and_orders_by_time():
    t = tr.Trace(
        ops={"/device:TPU:0": [ev("k.1", 0, 4e8), ev("g.2", 4e8, 5e8)],
             "/device:TPU:1": [ev("k.1", 0, 2e8)]},
        window=(0, 1e9),
        spans=[ev("bench.window", 0, 1e9), ev("bench.mul", 5e8, 1e9)])
    b = tr.breakdown(t)
    assert b["device_ops"] == [["k.1", pytest.approx(0.3)],
                               ["g.2", pytest.approx(0.05)]]
    assert b["idle_gaps"][0] == ["bench.mul", pytest.approx(0.8)]
    assert t.mean_busy_s() == pytest.approx(0.35)
    assert t.window_s == pytest.approx(1.0)


def test_harness_programs_are_not_the_programs():
    t = tr.Trace(ops={"/device:TPU:0": [
        ev("f", 0, 1, "jit_run"), ev("r", 1, 2, "jit_bench_mismatches"),
        ev("late", 5e9, 6e9, "jit_run")]}, spans=[],
        window=(0, 10))
    assert [e.name for e in t.program_ops("/device:TPU:0")] == ["f"]


def recorded_run():
    """The traced run of ``tp3p5_w32.bulk`` whose trace is committed in
    ``testdata`` (one TPU v5 lite, 6 calls of 2^20 products), as the
    metric readers see it; call times come from the trace's spans.  The
    source paths in the trace's metadata read ``<checkout>/``."""
    from bench import cells, harness
    t = tr.load(str(TESTDATA / "tp3p5_w32.bulk.xplane.pb"))
    calls = []
    for mul, wait in zip(t.spans_named("bench.mul"),
                         t.spans_named("bench.wait")):
        calls.append((mul.start_ns * 1e-9, mul.end_ns * 1e-9,
                      wait.end_ns * 1e-9))
    return harness.Run(
        cell=None, root=cells.ROOT, batch=1 << 20, chips=1, la=2, lb=2,
        device_kind="TPU v5 lite", peaks=cells.peaks("TPU v5 lite"),
        setup_s=0.0, generate_s=0.0, compile_s=0.0, calls=calls,
        window_s=t.window_s, memory_peak_bytes=182414336, trace=t)


def test_recorded_chip_trace_reduces_to_what_the_chip_run_printed():
    run = recorded_run()
    t = run.trace
    assert t.device_names() == ["/device:TPU:0"]
    assert len(run.calls) == 6
    assert t.window_s == pytest.approx(3.521057492)
    # busy: the program's ops alone; the run printed the union of all
    # ops, the harness's check of that time included
    assert t.mean_busy_s() == pytest.approx(0.682121103)
    assert tr.union_ns(t.ops["/device:TPU:0"], *t.window) * 1e-9 == \
        pytest.approx(0.682245037)
    assert run.metric("bank_fold_ms") == pytest.approx(4.606721666666666)
    assert run.metric("gather_scatter_ms") == \
        pytest.approx(109.08012883333333)
    assert run.metric("bank_fold_roofline") == \
        pytest.approx(0.8893526765129601)
    assert run.metric("device_idle_share") == \
        pytest.approx(80.62737957134158)
    # the harness's own check runs on the device but is no program op
    mods = {e.module for e in t.in_window(t.ops["/device:TPU:0"])}
    assert {"jit_run", "jit_bench_mismatches"} <= mods
    assert all(e.module != "jit_bench_mismatches"
               for e in t.program_ops("/device:TPU:0"))


def test_recorded_chip_trace_breakdown():
    b = tr.breakdown(recorded_run().trace)
    assert b["device_ops"][0] == ["jit_run/fusion.3",
                                  pytest.approx(0.532430792)]
    assert "jit_run/fused_bank_mul.1" in dict(b["device_ops"])
    assert len(b["device_ops"]) == len(b["idle_gaps"]) == 10
    assert b["idle_gaps"][0] == ["bench.mul", pytest.approx(0.486334129)]
