"""One cell of the benchmark: set-up, the measured window, the check.

:class:`Bench` drives ``CompiledDesign.mul`` through a configuration
and a traffic mix.  Set-up generates the design, asserts that it runs
as one fused kernel launch natively, makes the operands on the device
and compiles every shape the window uses.  The window is one caller in
a closed loop; each call ends in ``block_until_ready``, or, where the
traffic keeps its operands on the host, copies them to the device and
its products back.  The window keeps the outputs of its calls, all of
them or, past a memory budget, a sample drawn from the seed; once it
has closed, every product they hold is compared with the host
reference.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import gc
import os
import pathlib
import random
import shutil
import signal
import tempfile
import time

import numpy as np

from . import cells, reference, traffic
from . import trace as trace_mod

#: outputs the window keeps for its check, in bytes per chip: at today's
#: call rates every output of a 30 s window fits; a program fast enough
#: to pass it has a uniform sample of its calls checked instead
KEEP_BYTES_PER_CHIP = 4 << 30

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"


class BenchError(RuntimeError):
    """The cell cannot be measured here; no result is printed."""


class CompileTimer:
    """Sums JAX's backend-compile durations and counts compile-cache
    hits and misses while it is entered."""

    def __init__(self):
        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        self.cache_misses = 0

    def _duration(self, event, duration, **_):
        if event == COMPILE_EVENT:
            self.seconds += duration
            self.compiles += 1

    def _event(self, event, **_):
        if event == CACHE_HIT_EVENT:
            self.cache_hits += 1
        elif event == CACHE_MISS_EVENT:
            self.cache_misses += 1

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._duration)
        jax.monitoring.unregister_event_listener(self._event)


@dataclasses.dataclass
class Run:
    """What one run measured; the metric readers take it."""
    cell: cells.Cell
    root: pathlib.Path
    batch: int                  # products per call, all chips
    chips: int
    la: int
    lb: int
    device_kind: str
    peaks: dict | None
    setup_s: float
    generate_s: float
    compile_s: float
    calls: list                 # (t_issue, t_returned, t_ready) per call
    window_s: float
    memory_peak_bytes: int
    trace: trace_mod.Trace | None = None
    rows: int | None = None     # rows per call with padding; None: batch
    setup_peak_bytes: int = 0   # peak on the fullest chip after warm-up

    def __post_init__(self):
        if self.rows is None:
            self.rows = self.batch

    def metric(self, name: str):
        return cells.reader(name, self.root)(self)


def bench_mismatches(acc, out, want):
    import jax.numpy as jnp
    return acc + jnp.sum(jnp.any(out != want, axis=-1), dtype=jnp.int32)


class GcPauses:
    """Counts Python's garbage collections and keeps their pauses."""

    def __init__(self):
        self.collections = [0, 0, 0]
        self.pauses = []            # (start, end) on perf_counter
        self._start = None

    @property
    def longest_s(self) -> float:
        return max((e - s for s, e in self.pauses), default=0.0)

    def _callback(self, phase, info):
        if phase == "start":
            self._start = time.perf_counter()
        elif self._start is not None:
            self.pauses.append((self._start, time.perf_counter()))
            self.collections[info["generation"]] += 1
            self._start = None

    def within(self, lo: float, hi: float) -> float:
        """Seconds of collection inside ``[lo, hi]``."""
        return sum(max(0.0, min(e, hi) - max(s, lo))
                   for s, e in self.pauses)

    def __enter__(self):
        gc.callbacks.append(self._callback)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self._callback)


class StallStacks:
    """Where the caller's thread is when a call has run ``seconds``: a
    timer signal per call, whose handler runs on that thread at its next
    Python instruction and counts the innermost frames it finds."""

    def __init__(self, seconds: float, depth: int = 6):
        self.seconds = seconds
        self.depth = depth
        self.stacks = collections.Counter()

    def _handler(self, signum, frame):
        where = []
        while frame is not None and len(where) < self.depth:
            code = frame.f_code
            where.append(f"{os.path.basename(code.co_filename)}:"
                         f"{frame.f_lineno}:{code.co_name}")
            frame = frame.f_back
        self.stacks[" < ".join(where)] += 1

    def arm(self):
        signal.setitimer(signal.ITIMER_REAL, self.seconds)

    def disarm(self):
        signal.setitimer(signal.ITIMER_REAL, 0)

    def __enter__(self):
        self._old = signal.signal(signal.SIGALRM, self._handler)
        return self

    def __exit__(self, *exc):
        self.disarm()
        signal.signal(signal.SIGALRM, self._old)


def _span(name: str, on: bool):
    if not on:
        return contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation(name)


class Bench:
    """Set-up, window and check of one cell.

    ``rehearse`` runs the cell on the CPU at the traffic's rehearsal
    batch with the Pallas interpreter: it checks paths and control
    flow, and its result is never a measurement.
    """

    def __init__(self, cell: cells.Cell, *, root=cells.ROOT,
                 rehearse: bool = False, t0: float | None = None):
        self.cell = cell
        self.root = pathlib.Path(root)
        self.rehearse = rehearse
        self.t0 = time.perf_counter() if t0 is None else t0
        self.chips = cell.chips
        self.batch = traffic.batch(cell.traffic, cell.chips, rehearse)
        self.rows = traffic.rows(cell.traffic, cell.chips, rehearse)
        self.host = cell.traffic["resident"] == "host"
        self.n_sets = int(cell.traffic["operand_sets"])
        self.stall_s = None

    # ---------------------------------------------------------- set-up
    def setup(self, seed: int) -> None:
        phases = self.phases = {}
        mark = time.perf_counter()

        def lap(name):
            nonlocal mark
            now = time.perf_counter()
            phases[name] = now - mark
            mark = now

        import jax
        from repro import designs
        from repro.kernels import runtime
        lap("imports")

        devices = jax.devices()
        dev = devices[0]
        lap("devices")
        if not self.rehearse:
            if dev.platform != "tpu":
                raise BenchError(f"no accelerator: JAX's first device is "
                                 f"on {dev.platform!r}")
            if os.environ.get("REPRO_INTERPRET") is not None:
                raise BenchError("REPRO_INTERPRET is set; the benchmark "
                                 "measures native kernels only")
            if runtime.interpret_mode():
                raise BenchError("Pallas kernels would run interpreted")
        if len(devices) < self.chips:
            raise BenchError(f"the cell needs {self.chips} chips, JAX sees "
                             f"{len(devices)}")
        self.device_kind = dev.device_kind
        try:
            self.peaks = cells.peaks(dev.device_kind, self.root)
        except KeyError as e:
            if not self.rehearse:
                raise BenchError(str(e)) from None
            self.peaks = None

        spec = designs.DesignSpec.from_dict(self.cell.config["spec"])
        if self.rehearse:
            spec = dataclasses.replace(spec, backend="fused")
        if spec.replicas != self.chips:
            raise BenchError(f"{spec.replicas} replicas on "
                             f"{self.chips} chips")
        with CompileTimer() as ct:
            t = time.perf_counter()
            design = designs.generate(spec)
            self.generate_s = time.perf_counter() - t
            lap("generate")
            self.design = design
            self.la, self.lb = design.la, design.lb
            local = self.rows // self.chips
            if design.bank.backend != "fused":
                raise BenchError(f"backend resolved to "
                                 f"{design.bank.backend!r}, not 'fused'")
            launches = design.bank.launch_count(local)
            if launches != 1:
                raise BenchError(f"one bank round traced to {launches} "
                                 f"Pallas launches")
            lap("launch_count")
            self.sharding = None
            self.devices = [dev]
            if design.mesh is not None:
                from jax.sharding import NamedSharding, PartitionSpec as P
                ids = {d.id for d in design.mesh.devices.flat}
                if len(ids) != self.chips:
                    raise BenchError(f"mesh spans devices {sorted(ids)}, "
                                     f"want {self.chips} distinct")
                self.devices = list(design.mesh.devices.flat)
                self.sharding = NamedSharding(design.mesh,
                                              P(spec.mesh_axis))
            self.multiply = design.mul
            self.make_operands(seed)
            lap("operands")
            self.warm()
            lap("warm_up")
        self.setup_peak_bytes = self.memory_peak_bytes()
        self.compile_s = ct.seconds
        self.compiles = ct.compiles
        self.cache_hits, self.cache_misses = ct.cache_hits, ct.cache_misses
        self.setup_s = time.perf_counter() - self.t0

    def make_operands(self, seed: int) -> None:
        self.seed = seed
        self.sets = traffic.make_operands(
            seed, self.n_sets, self.rows, self.design.spec.bits_a,
            self.design.spec.bits_b, self.sharding, products=self.batch,
            chips=self.chips)
        for a, b in self.sets:
            a.block_until_ready()
            b.block_until_ready()
        if self.host:
            self.sets = [(np.asarray(a), np.asarray(b))
                         for a, b in self.sets]

    def put(self, x):
        """A host operand on the device, as the serving worker puts it."""
        import jax
        import jax.numpy as jnp
        if self.sharding is None:
            return jnp.asarray(x)
        return jax.device_put(x, self.sharding)

    def warm(self) -> None:
        """Run every program the window runs, once, at its shapes."""
        a, b = self.sets[0]
        if self.host:
            a, b = self.put(a), self.put(b)
        out = self.multiply(a, b)
        out.block_until_ready()
        if self.host:
            np.asarray(out)
        if self.chips > 1:
            shards = out.addressable_shards
            rows = sorted((s.device.id, s.data.shape[0]) for s in shards)
            if (len({d for d, _ in rows}) != self.chips
                    or any(r != self.rows // self.chips for _, r in rows)):
                raise BenchError(f"output shards (device, rows) = {rows}, "
                                 f"want {self.chips} devices of "
                                 f"{self.rows // self.chips} rows")

    # ---------------------------------------------------------- window
    def window(self, seconds: float, trace: bool = False,
               keep_trace: str | None = None) -> None:
        """Call ``multiply`` in a closed loop for ``seconds``, keeping
        the outputs for the check after the window: every one while they
        fit in ``KEEP_BYTES_PER_CHIP``, then a uniform sample of the
        calls (reservoir sampling, seeded), so that no output is copied
        or compared inside the window.

        A traced window also closes after the traffic's
        ``trace_max_calls`` calls, where it gives one, so that the trace
        stays small enough to read within the run's time.
        """
        import jax
        host = self.host
        max_calls = (self.cell.traffic.get("trace_max_calls") if trace
                     else None) or float("inf")
        out_bytes = self.rows * (self.la + self.lb) * 4
        keep = max(1, KEEP_BYTES_PER_CHIP * self.chips // out_bytes)
        sample = random.Random(self.seed)
        self.calls = []
        self.outputs = []
        tmp = None
        if trace:
            tmp = tempfile.mkdtemp(prefix="bench-trace-")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(tmp, profiler_options=opts)
        try:
            stalls = (StallStacks(self.stall_s) if self.stall_s
                      else contextlib.nullcontext())
            with CompileTimer() as ct, GcPauses() as gcp, stalls, \
                    _span(trace_mod.WINDOW_SPAN, trace):
                start = time.perf_counter()
                i = 0
                while True:
                    k = i % self.n_sets
                    a, b = self.sets[k]
                    if self.stall_s:
                        stalls.arm()
                    t_issue = time.perf_counter()
                    if host:
                        with _span("bench.put", trace):
                            a, b = self.put(a), self.put(b)
                    with _span("bench.mul", trace):
                        out = self.multiply(a, b)
                    t_returned = time.perf_counter()
                    with _span("bench.wait", trace):
                        out.block_until_ready()
                        if host:
                            out = np.asarray(out)
                    t_ready = time.perf_counter()
                    if self.stall_s:
                        stalls.disarm()
                    self.calls.append((t_issue, t_returned, t_ready))
                    if i < keep:
                        self.outputs.append((k, out))
                    else:
                        j = sample.randrange(i + 1)
                        if j < keep:
                            self.outputs[j] = (k, out)
                    i += 1
                    if t_ready - start >= seconds or i >= max_calls:
                        break
                self.window_s = t_ready - start
            self.window_compiles = ct.compiles
            self.gc = gcp
            self.stall_stacks = getattr(stalls, "stacks", None)
            if trace:
                t = time.perf_counter()
                jax.profiler.stop_trace()
                self.trace = trace_mod.load(tmp)
                self.trace_read_s = time.perf_counter() - t
                if keep_trace:
                    shutil.copytree(tmp, keep_trace, dirs_exist_ok=True)
            else:
                self.trace = None
        finally:
            if tmp is not None:
                shutil.rmtree(tmp, ignore_errors=True)

    def memory_peak_bytes(self) -> int:
        peak = 0
        for d in self.devices:
            stats = d.memory_stats() or {}
            peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
        return peak

    def record(self, memory_peak_bytes: int) -> Run:
        return Run(cell=self.cell, root=self.root, batch=self.batch,
                   chips=self.chips, la=self.la, lb=self.lb,
                   device_kind=self.device_kind, peaks=self.peaks,
                   setup_s=self.setup_s, generate_s=self.generate_s,
                   compile_s=self.compile_s, calls=self.calls,
                   window_s=self.window_s,
                   memory_peak_bytes=memory_peak_bytes, trace=self.trace,
                   rows=self.rows, setup_peak_bytes=self.setup_peak_bytes)

    # ----------------------------------------------------------- check
    def check(self) -> tuple:
        """``(attempted, failed)`` products of the window.

        Run once the window has closed: the host reference multiplies
        each operand set the window used, and every output the window
        kept is compared with it, row by row (padding rows too, whose
        products are zero).  The outputs are then let go.  Products of
        calls the sample left out count as attempted, not as checked.
        """
        import jax
        import jax.numpy as jnp
        attempted = self.batch * len(self.calls)
        shape = (self.rows, self.la + self.lb)
        wants = {}
        failed = 0
        acc = jnp.zeros((), jnp.int32)
        count = jax.jit(bench_mismatches)
        for k, out in self.outputs:
            if out.shape != shape or out.dtype != np.uint32:
                failed += self.batch
                continue
            if k not in wants:
                a, b = self.sets[k]
                want = reference.checked_products(np.asarray(a),
                                                  np.asarray(b))
                wants[k] = want if self.host else jax.device_put(
                    want, out.sharding)
            if self.host:
                failed += int(np.count_nonzero(
                    (out != wants[k]).any(axis=1)))
            else:
                acc = count(acc, out, wants[k])
        failed += int(acc)
        self.checked = self.batch * len(self.outputs)
        self.outputs = []
        return attempted, min(failed, attempted)
