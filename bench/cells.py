"""Find a cell's configuration, traffic mix and metric readers by name.

``BENCHMARK.json`` names them; each lives in a file of its own under
``bench/``, so a later cell, mix or metric is added as files and
entries, never as an edit to this module.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib

BENCH_DIR = pathlib.Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


@dataclasses.dataclass(frozen=True)
class Cell:
    """One entry of ``workloads`` with its files read."""
    name: str
    chips: int
    config: dict              # configs/<config>.json
    traffic: dict             # traffic/<traffic>.json
    end_to_end: tuple         # metric entries this cell reports untraced
    per_layer: tuple          # metric entries this cell reports traced


def load_benchmark(root=ROOT) -> dict:
    return json.loads((pathlib.Path(root) / "BENCHMARK.json").read_text())


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(name: str, root=ROOT) -> Cell:
    """The cell ``name`` of ``root/BENCHMARK.json``."""
    root = pathlib.Path(root)
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((root / configs[w["config"]]["file"]).read_text())
    traffic_file = root / "bench" / "traffic" / f"{w['traffic']}.json"
    traffic = json.loads(traffic_file.read_text())
    if config["chips"] != w["chips"]:
        raise ValueError(f"{name}: configuration {w['config']} runs on "
                         f"{config['chips']} chips, the cell asks for "
                         f"{w['chips']}")
    return Cell(
        name=name, chips=w["chips"], config=config, traffic=traffic,
        end_to_end=tuple(m for m in bench["end_to_end"]
                         if _reports(m, name)),
        per_layer=tuple(m for m in bench["per_layer"] if _reports(m, name)))


def reader(metric: str, root=ROOT):
    """The ``read(run)`` function of ``bench/metrics/<metric>.py``."""
    path = pathlib.Path(root) / "bench" / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{metric.replace('.', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def peaks(device_kind: str, root=ROOT) -> dict:
    """Published peaks of ``device_kind`` from ``bench/peaks.json``.

    A device that is not in the table is an error, not a default.
    """
    table = json.loads(
        (pathlib.Path(root) / "bench" / "peaks.json").read_text())
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json")
    return table["devices"][device_kind]
