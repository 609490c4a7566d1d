"""The program's spans read from a trace: per-call host time in the
report and the launch, the idle time inside the program, the kernel's
row occupancy, and idle gaps named by self time."""
import pathlib

import pytest

from bench import program_spans as ps
from bench import trace as tr

TESTDATA = pathlib.Path(__file__).parent / "testdata"
MS = 1e6                        # ns


def ev(name, s, e, module=""):
    return tr.Event(name, s * MS, e * MS, module)


def sp(name, s, e, **args):
    return ps.Span(name, s * MS, e * MS, args=args)


def two_calls():
    """Two calls of a 1 s window: each ``mcim.mul`` holds a 280 ms
    report and a 70 ms launch; the device runs 70 ms after each."""
    bench = [ev("bench.window", 0, 1000)]
    program = []
    for t in (0, 500):
        bench += [ev("bench.mul", t, t + 400), ev("bench.wait", t + 400,
                                                  t + 500)]
        program += [sp("mcim.mul", t + 10, t + 390),
                    sp("mcim.bank.report", t + 20, t + 300),
                    sp("mcim.bank.launch", t + 310, t + 380, rows=64,
                       kernel_rows=76)]
    ops = {"/device:TPU:0": [ev("fusion.1", 380, 450, "jit_run"),
                             ev("fusion.1", 880, 950, "jit_run")]}
    return tr.Trace(ops=ops, spans=bench, window=(0, 1000 * MS)), program


def test_readings_of_a_hand_built_trace():
    t, program = two_calls()
    r = ps.readings(t, program)
    assert r["calls"] == r["mul_spans"] == 2
    assert r["mul_ms"] == pytest.approx(380)
    assert r["bank_report_ms"] == pytest.approx(280)
    assert r["bank_launch_ms"] == pytest.approx(70)
    # idle gaps [0, 380], [450, 880], [950, 1000] against the program's
    # [10, 390] and [510, 890]: 370 + 370 of 1000 ms
    assert r["idle_in_program_share"] == pytest.approx(74.0)
    assert r["device_idle_share"] == pytest.approx(86.0)
    assert r["idle_in_program_share"] <= r["device_idle_share"]
    assert r["kernel_row_occupancy"] == pytest.approx(64 / 76 * 100)
    assert r["idle_gaps"] == [["mcim.bank.report", pytest.approx(0.43)],
                              ["mcim.bank.report", pytest.approx(0.38)],
                              ["bench.wait", pytest.approx(0.05)]]


def test_readings_are_silent_without_program_spans():
    t, _ = two_calls()
    r = ps.readings(t, [])
    for name in ("mul_ms", "bank_report_ms", "bank_launch_ms",
                 "idle_in_program_share", "kernel_row_occupancy"):
        assert r[name] is None, name
    # without program spans each gap keeps the benchmark's own name
    assert [g[0] for g in r["idle_gaps"]] == ["bench.mul", "bench.mul",
                                              "bench.wait"]


def test_launches_of_other_backends_count_no_occupancy():
    t, program = two_calls()
    core = [ps.Span(s.name, s.start_ns, s.end_ns, args={"rows": 64})
            for s in program]
    assert ps.kernel_row_occupancy(t, core) is None
    assert ps.bank_launch_ms(t, core, 2) == pytest.approx(70)


@pytest.mark.parametrize("gap, want", [
    ((0, 380), "mcim.bank.report"),     # raw overlap: bench.mul
    ((300, 312), "mcim.mul"),           # 10 ms between report and launch
    ((312, 380), "mcim.bank.launch"),
    ((385, 420), "bench.wait"),
    ((1000, 1100), "none"),
])
def test_gaps_are_named_by_the_span_whose_self_time_overlaps_most(gap,
                                                                  want):
    t, program = two_calls()
    every = list(t.spans) + program
    assert ps.self_label((gap[0] * MS, gap[1] * MS), every) == want


def test_self_time_rule_is_the_benchmarks_rule_on_flat_spans():
    t, _ = two_calls()
    for gap in ((0, 380), (385, 420), (450, 880), (950, 1000), (-5, 0)):
        g = (gap[0] * MS, gap[1] * MS)
        assert ps.self_label(g, t.spans) == tr.host_label(g, t.spans)


def test_recorded_bulk_trace_has_no_program_spans():
    """The trace committed before the program had spans reads as the
    benchmark's breakdown reads it, and every program reading is
    silent."""
    t, spans = ps.load(str(TESTDATA / "tp3p5_w32.bulk.xplane.pb"))
    assert spans == []
    r = ps.readings(t, spans)
    assert r["calls"] == 6 and r["mul_spans"] == 0
    assert r["bank_report_ms"] is None and r["bank_launch_ms"] is None
    assert r["idle_in_program_share"] is None
    assert r["kernel_row_occupancy"] is None
    assert r["idle_gaps"] == tr.breakdown(t)["idle_gaps"]
    assert r["device_idle_share"] == pytest.approx(80.62737957134158)


def test_recorded_small_trace_with_program_spans():
    """A traced window of ``tp3p5_w32.small`` on one TPU v5 lite, 118
    calls, recorded with the program's spans (``--seconds 0.2
    --keep-trace``); the source paths in it read ``<checkout>/``."""
    t, spans = ps.load(str(TESTDATA / "tp3p5_w32.small.spans.xplane.pb"))
    launches = [s for s in spans if s.name == ps.LAUNCH]
    assert len(launches) == 118
    assert all(s.args == {"rows": 64, "kernel_rows": 76} for s in launches)
    r = ps.readings(t, spans)
    assert r["calls"] == r["mul_spans"] == 118
    assert r["mul_ms"] == pytest.approx(0.27571430508474576)
    assert r["bank_report_ms"] == pytest.approx(0.06584777118644068)
    assert r["bank_launch_ms"] == pytest.approx(0.19315611016949152)
    assert r["idle_in_program_share"] == pytest.approx(16.191485913763533)
    assert r["device_idle_share"] == pytest.approx(99.65026821492731)
    assert r["kernel_row_occupancy"] == pytest.approx(6400 / 76)
    # the round's longest gaps wait on the device and the copy back
    assert r["idle_gaps"][0] == ["bench.wait", pytest.approx(0.002188595)]
    assert {g[0] for g in r["idle_gaps"]} == {"bench.wait"}
