"""From a profiler trace to the numbers the per-layer metrics read.

A traced run records one ``jax.profiler`` trace over its window. The
reduction keeps two kinds of events, both on the profiler's one clock:

- device operations: the ``XLA Ops`` line of every ``/device:TPU:<n>``
  plane (each event one operation of a compiled program on that chip);
- host spans: ``TraceAnnotation`` events that the benchmark opened around
  its calls into the program (``span``), and the window itself.

From them: the window's length, each device's busy time (the union of its
operations' intervals inside the window), the operations that took most
device time, and the device's idle gaps, each charged to the innermost host
span that was open at the gap's middle (``host.untraced`` where none was).
"""
from __future__ import annotations

import bisect
import contextlib
import dataclasses
import glob
import os
import re
import shutil
from typing import Dict, Iterable, List, Optional, Tuple

WINDOW = "bench.window"
UNTRACED = "host.untraced"
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"


def short_name(hlo: str) -> str:
    """``%grid_tick_bank_fused_pallas.45 = (...) custom-call(...)`` ->
    ``grid_tick_bank_fused_pallas``: the operation's name without its
    instance number and its HLO text."""
    head = hlo.split(" = ", 1)[0].lstrip("%")
    base, _, num = head.rpartition(".")
    return base if base and num.isdigit() else head


@dataclasses.dataclass
class Event:
    kind: str  # "op" | "span"
    device: int  # device index for "op", -1 for host spans
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: Dict[int, float]  # per device, inside the window
    ops_s: Dict[str, float]  # device self seconds by op name, summed over devices
    idle_gaps_s: Dict[str, float]  # idle device seconds by host span, mean over devices
    n_ops: int

    @property
    def mean_busy_s(self) -> float:
        return sum(self.busy_s.values()) / max(1, len(self.busy_s))


class Tracer:
    """Host spans for a run; a profiler trace when ``enabled``."""

    def __init__(self, directory: str, enabled: bool) -> None:
        self.directory = directory
        self.enabled = enabled

    def span(self, name: str):
        if not self.enabled:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)

    @contextlib.contextmanager
    def window(self):
        """Wrap the measured window: starts and stops the profiler when
        tracing, and marks the window on the trace's clock."""
        if not self.enabled:
            yield
            return
        import jax

        shutil.rmtree(self.directory, ignore_errors=True)
        os.makedirs(self.directory, exist_ok=True)
        jax.profiler.start_trace(self.directory)
        try:
            with jax.profiler.TraceAnnotation(WINDOW):
                yield
        finally:
            jax.profiler.stop_trace()

    def events(self, span_names: Iterable[str]) -> List[Event]:
        """The trace's events of interest; the trace files are deleted.
        ``self.lines`` keeps the names of the trace's planes and lines."""
        files = sorted(glob.glob(os.path.join(self.directory, "**", "*.xplane.pb"),
                                 recursive=True))
        if not files:
            raise RuntimeError(f"no profiler trace under {self.directory}")
        try:
            events, self.lines = read_xplane(files[-1], set(span_names) | {WINDOW})
            return events
        finally:
            shutil.rmtree(self.directory, ignore_errors=True)


def read_xplane(path: str, span_names) -> Tuple[List[Event], Dict[str, List[str]]]:
    """The events of interest in an ``.xplane.pb`` file, and the names of
    its device planes' lines."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out: List[Event] = []
    lines: Dict[str, List[str]] = {}
    for plane in data.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m is not None:
            lines[plane.name] = [line.name for line in plane.lines]
        for line in plane.lines:
            if m is not None and line.name == OPS_LINE:
                dev = int(m.group(1))
                names: Dict[str, str] = {}
                for e in line.events:
                    name = names.get(e.name)
                    if name is None:
                        name = names[e.name] = short_name(e.name)
                    out.append(Event("op", dev, name, float(e.start_ns), float(e.duration_ns)))
            elif m is None and plane.name.startswith("/host:"):
                out.extend(Event("span", -1, e.name, float(e.start_ns), float(e.duration_ns))
                           for e in line.events if e.name in span_names)
    return out, lines


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1] = (merged[-1][0], e)
        else:
            merged.append((s, e))
    return merged


def _clip(s: float, e: float, lo: float, hi: float) -> Optional[Tuple[float, float]]:
    s, e = max(s, lo), min(e, hi)
    return (s, e) if e > s else None


def _self_times(intervals: List[Tuple[float, float, str]], into: Dict[str, float]) -> None:
    """Add each operation's self time, its span less the spans of the
    operations nested in it (the body of a loop inside the loop), by name."""
    stack: List[list] = []  # [end, name, self_ns]
    for s, e, name in sorted(intervals, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            end, n, own = stack.pop()
            into[n] = into.get(n, 0.0) + own * 1e-9
        if stack:
            stack[-1][2] -= min(e, stack[-1][0]) - s
        stack.append([e, name, e - s])
    for end, n, own in stack:
        into[n] = into.get(n, 0.0) + own * 1e-9


def _innermost(spans: List[Event], starts: List[float], t: float) -> str:
    """The latest-opened span that covers ``t`` (``spans`` sorted by start)."""
    for i in range(bisect.bisect_right(starts, t) - 1, -1, -1):
        if spans[i].end_ns > t:
            return spans[i].name
    return UNTRACED


def reduce(events: List[Event], devices: Optional[Iterable[int]] = None) -> Reduced:
    """Window length, per-device busy time, op totals and idle gaps.
    ``devices`` lists the devices the run used (default: every device with
    an operation in the trace)."""
    windows = [e for e in events if e.kind == "span" and e.name == WINDOW]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW} span, found {len(windows)}")
    lo, hi = windows[0].start_ns, windows[0].end_ns
    spans = sorted((e for e in events if e.kind == "span" and e.name != WINDOW
                    and e.end_ns > lo and e.start_ns < hi), key=lambda e: e.start_ns)
    starts = [e.start_ns for e in spans]
    ops = [e for e in events if e.kind == "op"]
    used = sorted(set(devices) if devices is not None else {e.device for e in ops})

    busy: Dict[int, float] = {}
    ops_s: Dict[str, float] = {}
    gaps: Dict[str, float] = {}
    n_ops = 0
    for d in used:
        clipped = []
        for e in ops:
            if e.device != d:
                continue
            c = _clip(e.start_ns, e.end_ns, lo, hi)
            if c is not None:
                clipped.append((c[0], c[1], e.name))
        n_ops += len(clipped)
        _self_times(clipped, ops_s)
        merged = _union([(s, e) for s, e, _ in clipped])
        busy[d] = sum(e - s for s, e in merged) * 1e-9
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                name = _innermost(spans, starts, 0.5 * (s + e))
                gaps[name] = gaps.get(name, 0.0) + (e - s) * 1e-9 / len(used)
    return Reduced(
        window_s=(hi - lo) * 1e-9, busy_s=busy, ops_s=ops_s, idle_gaps_s=gaps, n_ops=n_ops,
    )


def top(d: Dict[str, float], n: int = 10, scale: float = 1.0) -> List[list]:
    return [[k, v * scale] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]


def to_json(events: List[Event]) -> List[list]:
    return [[e.kind, e.device, e.name, e.start_ns, e.dur_ns] for e in events]


def from_json(rows: List[list]) -> List[Event]:
    return [Event(str(k), int(d), str(n), float(s), float(u)) for k, d, n, s, u in rows]
