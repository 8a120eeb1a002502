"""From a profiler trace to device busy time, kernel time and idle gaps.

The loader turns the ``.xplane.pb`` that ``jax.profiler`` writes into a
flat list of ``Event``s; everything after it works on such lists, so
the reduction is tested on hand-built events and on a trimmed trace
recorded on the chip (``tests/bench/data``).

On a TPU the device planes are named ``/device:TPU:<n>``; their
``XLA Ops`` line holds one event per executed HLO op, Pallas kernels
included.  Host spans (``bench.*`` annotations) are on ``/host:CPU``.
"""
from __future__ import annotations

import glob
import gzip
import json
import os
from collections import defaultdict
from typing import Dict, Iterable, List, NamedTuple, Optional, Sequence, Tuple

OPS_LINE = "XLA Ops"


class Event(NamedTuple):
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float
    category: str = ""      # the op's hlo_category, where the trace has it

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def is_device_plane(plane: str) -> bool:
    return plane.startswith("/device:") and "CPU" not in plane


def load(trace_dir: str) -> List[Event]:
    """Every event of the newest ``.xplane.pb`` under ``trace_dir``:
    device ops and the host's ``bench.*`` spans."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    events: List[Event] = []
    for plane in ProfileData.from_file(files[-1]).planes:
        device = is_device_plane(plane.name)
        for line in plane.lines:
            if device and line.name != OPS_LINE:
                continue
            for e in line.events:
                if not device and not e.name.startswith("bench."):
                    continue
                cat = ""
                if device:
                    for k, v in e.stats:
                        if k == "hlo_category":
                            cat = str(v)
                            break
                events.append(Event(plane.name, line.name, e.name,
                                    float(e.start_ns), float(e.duration_ns),
                                    cat))
    return events


def save(events: Sequence[Event], path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump([list(e) for e in events], f)


def read(path: str) -> List[Event]:
    with gzip.open(path, "rt") as f:
        return [Event(*e) for e in json.load(f)]


def device_ops(events: Iterable[Event]) -> List[Event]:
    return [e for e in events if is_device_plane(e.plane)
            and e.line == OPS_LINE]


def host_spans(events: Iterable[Event]) -> List[Event]:
    return [e for e in events if not is_device_plane(e.plane)]


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merge (start, end) intervals into disjoint sorted ones."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_seconds(events: Iterable[Event], t0: float, t1: float) -> float:
    """Seconds in [t0, t1] (ns) in which an op ran, averaged over the
    device planes that ran any."""
    per_plane: Dict[str, list] = defaultdict(list)
    for e in device_ops(events):
        s, f = max(e.start_ns, t0), min(e.end_ns, t1)
        if f > s:
            per_plane[e.plane].append((s, f))
    if not per_plane:
        return 0.0
    total = sum(sum(f - s for s, f in union(iv))
                for iv in per_plane.values())
    return total / len(per_plane) / 1e9


def op_seconds(events: Iterable[Event], match) -> float:
    """Total device seconds of the ops for which ``match(event)`` holds,
    averaged over the device planes that ran any op."""
    ops = device_ops(events)
    planes = {e.plane for e in ops}
    if not planes:
        return 0.0
    return sum(e.dur_ns for e in ops if match(e)) / len(planes) / 1e9


def op_name(raw: str) -> str:
    """An op's name without the HLO text, '%' and the '.N' suffix the
    compiler adds: ``%fusion.12 = f32[...] ...`` -> ``fusion``."""
    return raw.split(" = ")[0].strip().lstrip("%").split(".")[0]


def self_seconds(events: Iterable[Event]) -> Dict[str, float]:
    """Device seconds per op name, each op counted without the ops
    nested inside it (a loop or a call holds the ops it runs),
    averaged over device planes."""
    ops = device_ops(events)
    planes = max(len({e.plane for e in ops}), 1)
    acc: Dict[str, float] = defaultdict(float)
    by_plane: Dict[str, List[Event]] = defaultdict(list)
    for e in ops:
        by_plane[e.plane].append(e)
    for evs in by_plane.values():
        evs.sort(key=lambda e: (e.start_ns, -e.dur_ns))
        stack: List[List] = []          # [event, time of its children]
        for e in evs:
            while stack and stack[-1][0].end_ns <= e.start_ns:
                done, inner = stack.pop()
                acc[op_name(done.name)] += done.dur_ns - inner
            if stack:
                stack[-1][1] += e.dur_ns
            stack.append([e, 0.0])
        for done, inner in stack:
            acc[op_name(done.name)] += done.dur_ns - inner
    return {k: v / planes / 1e9 for k, v in acc.items()}


def top_ops(events: Iterable[Event], n: int = 10) -> List[Tuple[str, float]]:
    """The ``n`` op names that took the most device seconds of their
    own (``self_seconds``)."""
    return sorted(self_seconds(events).items(), key=lambda kv: -kv[1])[:n]


def idle_gaps(events: Iterable[Event], t0: float, t1: float,
              n: int = 10) -> List[Tuple[str, float]]:
    """The ``n`` longest spans of [t0, t1] in which the first device ran
    nothing, each named by the innermost host span that covers at least
    half of it (else by the one that covers most of it)."""
    events = list(events)
    ops = device_ops(events)
    if not ops:
        return []
    first = sorted({e.plane for e in ops})[0]
    busy = union((max(e.start_ns, t0), min(e.end_ns, t1)) for e in ops
                 if e.plane == first and e.end_ns > t0 and e.start_ns < t1)
    gaps, cur = [], t0
    for s, f in busy:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, f)
    if t1 > cur:
        gaps.append((cur, t1))
    spans = [h for h in host_spans(events) if h.name != "bench.window"]
    out = []
    for s, f in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        best, key = "host", (False, 0.0)
        for h in spans:
            c = min(f, h.end_ns) - max(s, h.start_ns)
            half = 2 * c >= f - s
            k = (half, -h.dur_ns if half else c)
            if c > 0 and k > key:
                best, key = h.name, k
        out.append((best, (f - s) / 1e9))
    return out


def window_of(events: Iterable[Event], span: str = "bench.window"
              ) -> Optional[Tuple[float, float]]:
    """(start, end) ns of the host span that marks the measured window."""
    for e in events:
        if e.name == span and not is_device_plane(e.plane):
            return e.start_ns, e.end_ns
    return None
