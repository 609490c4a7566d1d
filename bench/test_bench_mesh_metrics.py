"""The mesh's metrics on a hand-built four-chip trace: the spread of the
chips' busy time, and the share of the window in which some chip is
idle.  Neither has anything to read on one chip."""
import pytest

from bench import trace as tr
from bench.test_bench_metrics import _run, _trace, read

MS = 1e6


def _chip(*spans, module="jit_run"):
    return [tr.Event("fusion.3", s * MS, e * MS, module) for s, e in spans]


@pytest.fixture
def four_chips():
    # a 100 ms window, two calls a chip: chip 2 starts 5 ms late, chip 3
    # runs 5 ms longer; the harness's op on chip 0 is no busy time
    ops = {"/device:TPU:0": _chip((0, 10), (20, 90))
           + _chip((92, 99), module="jit_bench_mismatches"),
           "/device:TPU:1": _chip((-3, 10), (20, 90)),
           "/device:TPU:2": _chip((5, 10), (20, 90)),
           "/device:TPU:3": _chip((0, 10), (20, 95))}
    return _run([(0, 0, 0.05), (0.05, 0.05, 0.1)], 0.1, chips=4,
                trace=_trace(ops, window=(0.0, 100 * MS)))


def test_busy_spread_is_largest_less_smallest_over_the_mean(four_chips):
    # busy 80, 80, 75, 85 ms: (85 - 75) / 80
    assert read("chip_busy_spread", four_chips) == pytest.approx(12.5)


def test_mesh_idle_is_where_any_chip_is_idle(four_chips):
    # some chip idle in [0, 5], [10, 20] and [90, 100]: 25 of 100 ms,
    # above the mean over chips, 20%, by the chips' stagger
    assert read("mesh_idle_share", four_chips) == pytest.approx(25.0)
    assert read("device_idle_share", four_chips) == pytest.approx(20.0)


def test_in_step_chips_read_no_spread_and_the_mean_idle():
    ops = {f"/device:TPU:{i}": _chip((10, 60)) for i in range(4)}
    run = _run([(0, 0, 0.1)], 0.1, chips=4,
               trace=_trace(ops, window=(0.0, 100 * MS)))
    assert read("chip_busy_spread", run) == 0.0
    assert read("mesh_idle_share", run) == pytest.approx(50.0)
    assert read("device_idle_share", run) == pytest.approx(50.0)


def test_nothing_to_read_on_one_chip_or_without_a_trace():
    one = _run([(0, 0, 0.1)], 0.1,
               trace=_trace({"/device:TPU:0": _chip((0, 50))},
                            window=(0.0, 100 * MS)))
    for name in ("chip_busy_spread", "mesh_idle_share"):
        assert read(name, one) is None
        assert read(name, _run([(0, 0, 0.1)], 0.1)) is None
