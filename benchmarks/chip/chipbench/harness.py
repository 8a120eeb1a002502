"""Run one cell once and print its result line.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \\
        --seconds <s> --trace <0|1>

The job named by the cell's traffic file does the work through a
``Run``: it sets up (everything before the window opens counts as
``setup_s``), drives the program through the window, reads memory,
frees the program's state and checks the outputs against the plain
reference.  With ``--trace 1`` the window runs under the profiler and
the line carries the cell's per-layer metrics instead of its
end-to-end ones.

The last line of standard output is one JSON object; the numbers that
decide ``correct`` are also the last lines of standard error.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import shutil
import sys
import time
from typing import Any, Dict, List, Optional

from chipbench import cells, device, xplane

# The profiled window is cut to this many seconds: long enough for
# tens of steps, short enough for a trace that reads back quickly.
TRACE_SECONDS = 4.0
TRACE_DIR = cells.CHECKOUT / ".bench_trace"


@dataclasses.dataclass
class Check:
    """One number compared with its limit: correct when value <= limit."""
    name: str
    value: float
    limit: float
    where: str = ""                     # the leaf or item that read worst

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


@dataclasses.dataclass
class Outcome:
    """What a job hands back."""
    attempted: int
    failed: int
    metrics: Dict[str, float]          # end-to-end metric name -> value
    checks: List[Check]
    work: Dict[str, Any] = dataclasses.field(default_factory=dict)


class Window:
    """The measured window: times it and, when tracing, profiles it."""

    def __init__(self, run: "Run", warm=None):
        self.run, self.warm = run, warm
        self.length = (min(run.seconds, TRACE_SECONDS) if run.trace
                       else run.seconds)
        self.t0 = self.elapsed = 0.0
        self.units = 0                  # work done: steps, requests, ...
        self.failed = 0
        self._span = None

    def __enter__(self):
        import jax
        if self.run.trace:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            jax.profiler.start_trace(str(TRACE_DIR))
            if self.warm is not None:
                # the profiler's start-up slows the first call it sees;
                # that call runs before the window opens
                with jax.profiler.TraceAnnotation("bench.warm"):
                    self.warm()
            self._span = jax.profiler.TraceAnnotation("bench.window")
            self._span.__enter__()
        self.t0 = time.perf_counter()
        self.run.setup_s = self.t0 - self.run.t_start
        return self

    def running(self) -> bool:
        return time.perf_counter() - self.t0 < self.length

    def __exit__(self, *exc):
        import jax
        self.elapsed = time.perf_counter() - self.t0
        if self._span is not None:
            self._span.__exit__(*exc)
            jax.profiler.stop_trace()
        self.run.window = self
        return False


class Run:
    """One run of one cell: its arguments, its devices and its clock."""

    def __init__(self, cell: cells.Cell, seed: int, seconds: float,
                 trace: bool, devices, t_start: float):
        self.cell, self.seed, self.seconds = cell, seed, seconds
        self.trace, self.devices, self.t_start = trace, devices, t_start
        self.setup_s: Optional[float] = None
        self.window: Optional[Window] = None
        self.memory_peak: Optional[int] = None

    def measured(self, warm=None) -> Window:
        """The window; with ``--trace 1``, ``warm()`` runs once under the
        profiler before it opens."""
        return Window(self, warm)

    def repeat(self, step) -> Window:
        """Call ``step()``, which returns a loss, back to back for the
        window; ``units`` counts the calls and ``failed`` the losses
        that are not finite."""
        import jax
        times = []
        with self.measured(warm=step) as w:
            while w.running():
                t = time.perf_counter()
                with jax.profiler.TraceAnnotation("bench.step"):
                    loss = step()
                times.append(time.perf_counter() - t)
                w.units += 1
                w.failed += not math.isfinite(loss)
        times.sort()
        self.log(f"window: {w.units} steps in {w.elapsed:.3f} s; step s "
                 f"min {times[0]:.4f} median {times[len(times) // 2]:.4f} "
                 f"max {times[-1]:.4f}")
        return w

    def mark(self, what: str) -> None:
        """Log the seconds since the process started, at ``what``."""
        self.log(f"set-up: {what} at {time.perf_counter() - self.t_start:.1f} s")

    def read_memory(self) -> None:
        self.memory_peak = device.memory_peak_bytes(self.devices)

    @staticmethod
    def log(msg: str) -> None:
        print(msg, flush=True)


def per_layer(run: Run, outcome: Outcome, events) -> Dict[str, float]:
    """The cell's per-layer metrics from the trace and the job's counts;
    a reader that finds nothing to read leaves its metric out."""
    span = xplane.window_of(events)
    ctx = MetricContext(events=events, window=span, work=outcome.work,
                        peaks=device.peaks(run.devices[0].device_kind),
                        steps=run.window.units)
    out = {}
    for m in run.cell.per_layer:
        v = cells.metric_reader(m["name"])(ctx)
        if v is not None:
            out[m["name"]] = v if isinstance(v, tuple) else (v, None)
    return out


@dataclasses.dataclass
class MetricContext:
    """What a per-layer metric's reader reads."""
    events: list                        # xplane.Event list of the window
    window: Optional[tuple]             # (start, end) ns of the window
    work: Dict[str, Any]                # the job's required-work counts
    peaks: Dict[str, float]
    steps: int                          # units of work in the window

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9 if self.window else 0.0

    def busy_s(self) -> float:
        return xplane.busy_seconds(self.events, *self.window) \
            if self.window else 0.0

    def op_seconds(self, match) -> float:
        """Device seconds of the window's ops that ``match``."""
        if not self.window:
            return 0.0
        t0, t1 = self.window
        return xplane.op_seconds(
            [e for e in self.events if t0 <= e.start_ns <= t1], match)


def main(argv=None) -> int:
    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = cells.load_cell(args.workload)
    job = cells.job_module(cell)
    from repro.launch.compile_cache import use_compile_cache
    cache = use_compile_cache()
    try:
        devices = device.require_chips(cell.chips)
    except device.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    import jax
    print(f"bench: {cell.name} seed {args.seed} on "
          f"{devices[0].device_kind} x{len(jax.devices())}; jax "
          f"{jax.__version__}; compile cache {cache}", flush=True)
    run = Run(cell, args.seed, args.seconds, bool(args.trace), devices,
              t_start)
    outcome = job.run(run)
    if run.window is None or run.memory_peak is None:
        raise RuntimeError(f"job {cell.job} measured no window")

    dev = device.describe(jax.devices())
    dev["memory_peak_bytes"] = run.memory_peak
    result: Dict[str, Any] = {}
    if run.trace:
        events = xplane.load(str(TRACE_DIR))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        span = xplane.window_of(events)
        if span is None:
            raise RuntimeError("the trace holds no bench.window span")
        values = per_layer(run, outcome, events)
        bounds = {k: b for k, (v, b) in values.items() if b}
        values = {k: v for k, (v, b) in values.items()}
        units = {m["name"]: m["unit"] for m in cell.per_layer}
        dev["busy_s"] = xplane.busy_seconds(events, *span)
        dev["window_s"] = (span[1] - span[0]) / 1e9
        result["breakdown"] = {
            "device_ops": [list(x) for x in xplane.top_ops(
                [e for e in events if span[0] <= e.start_ns <= span[1]])],
            "idle_gaps": [list(x) for x in xplane.idle_gaps(events,
                                                            *span)]}
    else:
        bounds = {}
        values = dict(outcome.metrics, setup_s=run.setup_s)
        units = {m["name"]: m["unit"] for m in cell.end_to_end}
    missing = set(units) - set(values) if not run.trace else set()
    if missing:
        raise RuntimeError(f"job {cell.job} did not report {sorted(missing)}")
    checks = outcome.checks
    correct = bool(checks) and all(c.ok for c in checks) \
        and outcome.failed == 0
    print(f"correct: {correct}", file=sys.stderr)
    for c in checks:
        print(f"check {c.name}: {c.value!r} limit {c.limit!r} "
              f"{'ok' if c.ok else 'FAILED'}"
              + (f" (worst: {c.where})" if c.where else ""),
              file=sys.stderr, flush=True)
    line = {"correct": correct, "attempted": outcome.attempted,
            "failed": outcome.failed,
            "metrics": {k: dict({"value": v, "unit": units[k]},
                                **({"bound": bounds[k]} if k in bounds
                                   else {}))
                        for k, v in values.items() if k in units},
            "device": dev}
    line.update(result)
    line["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                      for c in checks}
    print(json.dumps(line), flush=True)
    return 0
