"""Reduce a JAX profiler trace to the numbers the metric readers take.

A trace (``*.xplane.pb``) holds one plane per device, named
``/device:<KIND>:<n>``, whose ``XLA Ops`` line has one event per device
operation and whose ``XLA Modules`` line has one event per program
run (an ``Async XLA Ops`` line of copies in flight is not counted as
busy), and the host plane ``/host:CPU``, which holds the benchmark's own
spans (``bench.window``, ``bench.put``, ``bench.mul``, ``bench.wait``).
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import os

HOST_PLANE = "/host:CPU"
DEVICE_PREFIX = "/device:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
#: programs the benchmark itself runs on the device (operands, check)
HARNESS_MODULE_PREFIX = "jit_bench_"


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    end_ns: float
    module: str = ""

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) * 1e-9


@dataclasses.dataclass
class Trace:
    """Device operations and host spans of one traced window."""
    ops: dict                   # device plane name -> [Event]
    spans: list                 # the benchmark's host spans
    window: tuple               # (start_ns, end_ns) of bench.window

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def device_names(self) -> list:
        return sorted(self.ops)

    def in_window(self, events) -> list:
        lo, hi = self.window
        return [e for e in events if e.end_ns > lo and e.start_ns < hi]

    def program_ops(self, device: str) -> list:
        """Operations of the program under test: all but the harness's."""
        return [e for e in self.in_window(self.ops[device])
                if not e.module.startswith(HARNESS_MODULE_PREFIX)]

    def busy_s(self, device: str) -> float:
        """Union of the intervals in which an operation of the program
        ran, in seconds."""
        lo, hi = self.window
        return union_ns(self.program_ops(device), lo, hi) * 1e-9

    def mean_busy_s(self) -> float:
        devices = self.device_names()
        if not devices:
            return 0.0
        return sum(self.busy_s(d) for d in devices) / len(devices)

    def spans_named(self, name: str) -> list:
        return [s for s in self.spans if s.name == name]


def union_ns(events, lo: float, hi: float) -> float:
    """Length of the union of ``events`` clipped to ``[lo, hi]``."""
    total, end = 0.0, lo
    for s, e in sorted((max(ev.start_ns, lo), min(ev.end_ns, hi))
                       for ev in events):
        if e <= s:
            continue
        if s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def idle_gaps(events, lo: float, hi: float) -> list:
    """``(start_ns, end_ns)`` of each stretch of ``[lo, hi]`` with no
    operation running."""
    gaps, end = [], lo
    for s, e in sorted((ev.start_ns, ev.end_ns) for ev in events):
        if s > end:
            gaps.append((end, min(s, hi)))
        end = max(end, e)
        if end >= hi:
            break
    if end < hi:
        gaps.append((end, hi))
    return [(s, e) for s, e in gaps if e > s]


def host_label(gap, spans) -> str:
    """Name of the host span that overlaps ``gap`` most ('none' if none)."""
    best, label = 0.0, "none"
    for sp in spans:
        if sp.name == WINDOW_SPAN:
            continue
        ov = min(gap[1], sp.end_ns) - max(gap[0], sp.start_ns)
        if ov > best:
            best, label = ov, sp.name
    return label


def breakdown(trace: Trace, top: int = 10) -> dict:
    """Top device operations by time, and the longest idle gaps by what
    the host was doing, both averaged over the devices traced."""
    devices = trace.device_names()
    n = max(len(devices), 1)
    by_op = collections.Counter()
    gaps = []
    for d in devices:
        ops = trace.program_ops(d)
        for e in ops:
            by_op[f"{e.module}/{e.name}" if e.module else e.name] += \
                e.seconds / n
        gaps.extend(idle_gaps(ops, *trace.window))
    gaps.sort(key=lambda g: g[0] - g[1])
    return {"device_ops": [[k, v] for k, v in by_op.most_common(top)],
            "idle_gaps": [[host_label(g, trace.spans), (g[1] - g[0]) * 1e-9]
                          for g in gaps[:top]]}


def short_name(name: str) -> str:
    """``fusion.3`` of an op event named by its HLO text
    (``%fusion.3 = u32[...] fusion(...)``), ``jit_run`` of a program
    run named ``jit_run(<fingerprint>)``."""
    name = name.split(" = ", 1)[0].lstrip("%")
    return name.split("(", 1)[0]


def _stats(event) -> dict:
    return {k: v for k, v in event.stats}


def _module_of(op, modules, starts) -> str:
    """Name of the program run (sorted ``modules``) that holds ``op``."""
    i = bisect.bisect_right(starts, op.start_ns) - 1
    if i >= 0 and op.start_ns < modules[i].end_ns:
        return modules[i].name
    return ""


def load(path: str) -> Trace:
    """Read ``path`` (an ``.xplane.pb`` or a directory holding one)."""
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                                 recursive=True))
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = found[-1]
    data = ProfileData.from_file(path)
    ops, spans = {}, []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            dev_ops, dev_mods = [], []
            for line in plane.lines:
                if line.name not in (OPS_LINE, MODULES_LINE):
                    continue
                for e in line.events:
                    ev = Event(short_name(e.name), e.start_ns,
                               e.start_ns + e.duration_ns,
                               short_name(str(_stats(e).get("hlo_module",
                                                            ""))))
                    (dev_ops if line.name == OPS_LINE
                     else dev_mods).append(ev)
            if dev_ops or dev_mods:
                dev_mods.sort(key=lambda m: m.start_ns)
                starts = [m.start_ns for m in dev_mods]
                ops[plane.name] = [
                    ev if ev.module else dataclasses.replace(
                        ev, module=_module_of(ev, dev_mods, starts))
                    for ev in dev_ops]
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append(Event(e.name, e.start_ns,
                                           e.start_ns + e.duration_ns))
    windows = [s for s in spans if s.name == WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"trace holds {len(windows)} {WINDOW_SPAN} spans, "
                         f"want 1")
    return Trace(ops=ops, spans=spans,
                 window=(windows[0].start_ns, windows[0].end_ns))
