"""The program's own host spans in a kept trace, and what they read.

    python3 bench/program_spans.py DIR [DIR ...]

``DIR`` is a trace kept by ``bench/run.py --trace 1 --keep-trace DIR``
(or an ``.xplane.pb``).  The program marks its host path with spans
named ``repro.spans.PREFIX + ...`` (``mcim.mul``, ``mcim.bank.report``,
``mcim.bank.launch`` with its ``rows`` and ``kernel_rows``) on the
profiler's host plane, the clock of the device trace.  One JSON line per
trace: the four readings below, the calls, and the window's ten longest
idle gaps named by the span whose self time overlaps each most.

``bench/trace.py`` keeps the benchmark's ``bench.`` spans alone, so a
run of a cell does not report these readings: they are read here from
the kept trace.
"""
from __future__ import annotations

import dataclasses
import glob
import json
import os
import sys
import types

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import cells                   # noqa: E402
from bench import trace as tr             # noqa: E402
from repro.spans import PREFIX            # noqa: E402

MUL = PREFIX + "mul"
REPORT = PREFIX + "bank.report"
LAUNCH = PREFIX + "bank.launch"


@dataclasses.dataclass(frozen=True)
class Span(tr.Event):
    """A host span with its arguments (the event's stats)."""
    args: dict = dataclasses.field(default_factory=dict, compare=False)


def _xplane(path: str) -> str:
    if not os.path.isdir(path):
        return path
    found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return found[-1]


def load(path: str) -> tuple:
    """``(trace, spans)``: :func:`bench.trace.load` of ``path`` and the
    program's host spans in it, in start order, with their arguments."""
    from jax.profiler import ProfileData
    path = _xplane(path)
    data = ProfileData.from_file(path)
    spans = [Span(e.name, e.start_ns, e.start_ns + e.duration_ns,
                  args={k: v for k, v in e.stats})
             for plane in data.planes if plane.name == tr.HOST_PLANE
             for line in plane.lines for e in line.events
             if e.name.startswith(PREFIX)]
    spans.sort(key=lambda s: (s.start_ns, -s.end_ns))
    return tr.load(path), spans


def calls(trace: tr.Trace) -> int:
    """Calls of the window: its ``bench.mul`` spans."""
    return len(trace.in_window(trace.spans_named("bench.mul")))


def _ms_per_call(trace, spans, name, n_calls):
    picked = [s for s in trace.in_window(spans) if s.name == name]
    if not picked or not n_calls:
        return None
    return sum(s.seconds for s in picked) / n_calls * 1e3


def mul_ms(trace, spans, n_calls):
    """Host time per call in ``CompiledDesign.mul``."""
    return _ms_per_call(trace, spans, MUL, n_calls)


def bank_report_ms(trace, spans, n_calls):
    """Host time per call in ``Bank.report``."""
    return _ms_per_call(trace, spans, REPORT, n_calls)


def bank_launch_ms(trace, spans, n_calls):
    """Host time per call in the launch of the compiled dispatch."""
    return _ms_per_call(trace, spans, LAUNCH, n_calls)


def _intersection_ns(a, b) -> float:
    """Length of the overlap of two sorted lists of disjoint
    ``(start, end)`` intervals."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def _merged(spans, lo, hi) -> list:
    out = []
    for s, e in sorted((max(sp.start_ns, lo), min(sp.end_ns, hi))
                       for sp in spans):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def idle_in_program_share(trace, spans):
    """Share of the window (%), averaged over the chips, in which no
    operation of the program runs and the host is inside a program
    span."""
    devices = trace.device_names()
    if not spans or not devices:
        return None
    program = _merged(spans, *trace.window)
    idle = sum(_intersection_ns(
        tr.idle_gaps(trace.program_ops(d), *trace.window), program)
        for d in devices)
    return idle / len(devices) / (trace.window[1] - trace.window[0]) * 100


def kernel_row_occupancy(trace, spans):
    """Rows given over rows the fused kernel computes (%), over the
    window's launches."""
    launches = [s for s in trace.in_window(spans)
                if s.name == LAUNCH and "kernel_rows" in s.args]
    if not launches:
        return None
    return (sum(s.args["rows"] for s in launches)
            / sum(s.args["kernel_rows"] for s in launches) * 100)


def _children(spans) -> dict:
    """Index of each span -> indices of the spans directly inside it
    (spans of one thread nest)."""
    order = sorted(range(len(spans)),
                   key=lambda i: (spans[i].start_ns, -spans[i].end_ns))
    kids, stack = {i: [] for i in order}, []
    for i in order:
        while stack and spans[stack[-1]].end_ns < spans[i].end_ns:
            stack.pop()
        if stack:
            kids[stack[-1]].append(i)
        stack.append(i)
    return kids


def _overlap(gap, sp) -> float:
    return max(0.0, min(gap[1], sp.end_ns) - max(gap[0], sp.start_ns))


def self_label(gap, spans, kids=None) -> str:
    """Name of the host span whose self time (its interval less its
    child spans) overlaps ``gap`` most ('none' if none); with no nested
    spans, :func:`bench.trace.host_label`."""
    kids = _children(spans) if kids is None else kids
    best, label = 0.0, "none"
    for i, sp in enumerate(spans):
        if sp.name == tr.WINDOW_SPAN:
            continue
        ov = _overlap(gap, sp) - sum(_overlap(gap, spans[k])
                                     for k in kids[i])
        if ov > best:
            best, label = ov, sp.name
    return label


def idle_gaps_named(trace, spans, top: int = 10) -> list:
    """The ``top`` longest idle gaps of :func:`bench.trace.breakdown`,
    named by :func:`self_label` over the benchmark's and the program's
    spans."""
    every = list(trace.spans) + list(spans)
    kids = _children(every)
    gaps = []
    for d in trace.device_names():
        gaps.extend(tr.idle_gaps(trace.program_ops(d), *trace.window))
    gaps.sort(key=lambda g: g[0] - g[1])
    return [[self_label(g, every, kids), (g[1] - g[0]) * 1e-9]
            for g in gaps[:top]]


def readings(trace, spans) -> dict:
    """Everything the command prints for one trace; the benchmark's
    own ``device_idle_share`` beside the program's share of it."""
    n = calls(trace)
    idle = cells.reader("device_idle_share")(types.SimpleNamespace(
        trace=trace))
    return {
        "calls": n,
        "mul_spans": len([s for s in trace.in_window(spans)
                          if s.name == MUL]),
        "mul_ms": mul_ms(trace, spans, n),
        "bank_report_ms": bank_report_ms(trace, spans, n),
        "bank_launch_ms": bank_launch_ms(trace, spans, n),
        "idle_in_program_share": idle_in_program_share(trace, spans),
        "device_idle_share": idle,
        "kernel_row_occupancy": kernel_row_occupancy(trace, spans),
        "idle_gaps": idle_gaps_named(trace, spans),
    }


def main(argv=None) -> int:
    for path in (sys.argv[1:] if argv is None else argv):
        trace, spans = load(path)
        print(json.dumps({"trace": path, **readings(trace, spans)}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
