"""The harness finds a configuration, a traffic mix and a per-layer
metric that are dropped in as new files, with no existing file edited."""
import json
import shutil

import pytest

from bench import cells, harness


@pytest.fixture
def dropped_in(tmp_path):
    """A copy of the benchmark with one more cell, mix and metric, each
    added as files and entries only."""
    root = tmp_path / "checkout"
    shutil.copytree(cells.BENCH_DIR, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "testdata"))
    before = {p.relative_to(root): p.read_bytes()
              for p in (root / "bench").rglob("*") if p.is_file()}
    (root / "bench" / "configs" / "tbl8_w16_relaxed.json").write_text(
        json.dumps({"name": "tbl8_w16_relaxed", "chips": 1,
                    "spec": {"bits_a": 16, "bits_b": 16,
                             "throughput": "1/2"},
                    "source": "paper Table VIII", "assumed": []}))
    (root / "bench" / "traffic" / "tiny.json").write_text(json.dumps(
        {"loop": "closed", "callers": 1, "operand_sets": 2,
         "resident": "device", "products_per_call_per_chip": 128,
         "rehearse_products_per_call_per_chip": 8}))
    (root / "bench" / "metrics" / "calls_per_s.py").write_text(
        "def read(run):\n    return len(run.calls) / run.window_s\n")
    bench = json.loads((cells.ROOT / "BENCHMARK.json").read_text())
    bench["configs"].append({
        "name": "tbl8_w16_relaxed", "source": "https://arxiv.org/abs/2301.13332",
        "file": "bench/configs/tbl8_w16_relaxed.json", "reduced": [],
        "why": "one FB CT=2 instance"})
    bench["workloads"].append({
        "name": "tbl8_w16_relaxed.tiny", "config": "tbl8_w16_relaxed",
        "traffic": "tiny", "chips": 1, "why": "drop-in"})
    bench["per_layer"].append({
        "name": "calls_per_s", "unit": "1/s", "better": "higher",
        "source": "host_clock", "layer": "bank engine host path",
        "moves": "products_per_s", "workloads": ["tbl8_w16_relaxed.tiny"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    yield root
    after = {p.relative_to(root): p.read_bytes()
             for p in (root / "bench").rglob("*")
             if p.is_file() and p.relative_to(root) in before}
    assert after == before, "an existing benchmark file was edited"


def test_new_cell_resolves_from_files(dropped_in):
    cell = cells.resolve("tbl8_w16_relaxed.tiny", dropped_in)
    assert cell.config["spec"]["throughput"] == "1/2"
    assert cell.traffic["products_per_call_per_chip"] == 128
    assert [m["name"] for m in cell.per_layer] == ["calls_per_s"]
    assert {m["name"] for m in cell.end_to_end} == {"products_per_s",
                                                    "setup_s"}
    # the cells already there keep what they had
    old = cells.resolve("tp3p5_w32.small", dropped_in)
    assert "calls_per_s" not in {m["name"] for m in old.per_layer}


def test_new_metric_reader_is_found(dropped_in):
    run = harness.Run(cell=None, root=dropped_in, batch=128, chips=1, la=1,
                      lb=1, device_kind="cpu", peaks=None, setup_s=1.0,
                      generate_s=0.1, compile_s=0.1,
                      calls=[(0, 0, 0.25)] * 8, window_s=2.0,
                      memory_peak_bytes=0)
    assert run.metric("calls_per_s") == 4.0
    assert cells.reader("products_per_s", dropped_in)(run) == 512.0


def test_unknown_cell_and_device_are_errors():
    with pytest.raises(KeyError):
        cells.resolve("no_such.cell")
    with pytest.raises(KeyError, match="no peaks"):
        cells.peaks("TPU v999")
