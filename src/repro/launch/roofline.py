"""Roofline-term computation from compiled dry-run artifacts.

Hardware model (TPU v5e):
  peak bf16 compute : 197 TFLOP/s per chip
  HBM bandwidth     : 819 GB/s per chip
  ICI link bandwidth: ~50 GB/s per link

Terms (seconds, per step, per chip -- cost_analysis of the SPMD
executable is already per-device):

  compute    = flops_per_device / PEAK_FLOPS
  memory     = bytes_per_device / HBM_BW
  collective = collective_link_bytes_per_device / ICI_BW

collective_link_bytes uses ring-cost accounting per op type: an
all-reduce of R result bytes moves 2R(k-1)/k per device; an all-gather
of R result bytes moves R(k-1)/k; reduce-scatter R(k-1)/k of its operand
(= result*k); all-to-all R(k-1)/k; collective-permute R.
"""
from __future__ import annotations

import re

PEAK_FLOPS = 197e12
HBM_BW = 819e9
ICI_BW = 50e9

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1,
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_OP_RE = re.compile(
    r"=\s+((?:\([^)]*\))|(?:[a-z0-9]+\[[0-9,]*\](?:\{[^}]*\})?))\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")
_GROUPS_RE = re.compile(r"replica_groups=\{\{([0-9,]+)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _shape_bytes(text: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(text):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def parse_collectives(hlo_text: str) -> dict:
    """Per-collective-type {count, result_bytes, link_bytes} from HLO."""
    out = {c: {"count": 0, "result_bytes": 0, "link_bytes": 0.0}
           for c in _COLLECTIVES}
    for line in hlo_text.splitlines():
        m = _OP_RE.search(line)
        if not m:
            continue
        result_text, op = m.group(1), m.group(2)
        rbytes = _shape_bytes(result_text)
        k = 1
        gm = _GROUPS_RE.search(line)
        if gm:
            k = len(gm.group(1).split(","))
        else:
            gi = _GROUPS_IOTA_RE.search(line)
            if gi:
                k = int(gi.group(2))
        if k <= 1 and op != "collective-permute":
            continue
        frac = (k - 1) / max(k, 1)
        if op == "all-reduce":
            link = 2.0 * rbytes * frac
        elif op == "all-gather":
            link = rbytes * frac
        elif op == "reduce-scatter":
            link = rbytes * k * frac
        elif op == "all-to-all":
            link = rbytes * frac
        else:                         # collective-permute
            link = float(rbytes)
        out[op]["count"] += 1
        out[op]["result_bytes"] += rbytes
        out[op]["link_bytes"] += link
    return out


def roofline_terms(flops_per_device: float, bytes_per_device: float,
                   link_bytes_per_device: float) -> dict:
    compute = flops_per_device / PEAK_FLOPS
    memory = bytes_per_device / HBM_BW
    collective = link_bytes_per_device / ICI_BW
    dominant = max(("compute", compute), ("memory", memory),
                   ("collective", collective), key=lambda kv: kv[1])[0]
    bound = max(compute, memory, collective)
    return {
        "compute_s": compute,
        "memory_s": memory,
        "collective_s": collective,
        "dominant": dominant,
        "step_lower_bound_s": bound,
        # fraction of the bound that is pure compute == roofline fraction
        # achievable if the dominant term were fully overlapped
        "compute_fraction": compute / bound if bound > 0 else 0.0,
    }


def model_flops(n_active_params: int, tokens: int, kind: str) -> float:
    """6ND for training, 2ND for forward-only (per the assignment)."""
    mult = 6.0 if kind == "train" else 2.0
    return mult * n_active_params * tokens

