"""Reduction of a JAX profiler trace (`.xplane.pb`) to device metrics.

Device busy time is the union of the operation intervals on the device
planes (`/device:TPU:<n>`, line "XLA Ops"), averaged over the chips, and
the idle share is 1 - busy / window. Device time per jitted executable is
the summed duration of its events on the "XLA Modules" line, keyed by
module name. Each idle gap of the window is attributed to the
benchmark's own host span (`jax.profiler.TraceAnnotation`) that overlaps
it most; the spans are on the same clock as the device events.

A CPU trace has no device plane: there the operations are the XLA CPU
client's events and the executables its `PjitFunction(...)` host events,
which is what the reduction's own test records.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[str, float, float]          # (name, start_ns, end_ns)

WINDOW_SPAN = "window"
HOST_SPANS = ("frontend.step", "submit", "stream.next_chunk",
              "reference_check", "generator.wait")
_MODULE_ID = re.compile(r"\(\d+\)$")
_PJIT = re.compile(r"^PjitFunction\((.*)\)$")


@dataclasses.dataclass
class TraceEvents:
    ops: Dict[str, List[Interval]]          # device -> op intervals
    modules: Dict[str, List[Interval]]      # device -> executable intervals
    spans: List[Interval]                   # the benchmark's host spans
    window: Optional[Tuple[float, float]]   # the "window" span


def latest_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def _events(line) -> List[Interval]:
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for e in line.events]


def load(path: str) -> TraceEvents:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops: Dict[str, List[Interval]] = {}
    modules: Dict[str, List[Interval]] = {}
    spans: List[Interval] = []
    window = None
    cpu_ops: List[Interval] = []
    cpu_modules: List[Interval] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            lines = {line.name: _events(line) for line in plane.lines}
            if "XLA Modules" in lines:
                modules[plane.name] = [(_MODULE_ID.sub("", n).strip(), s, e)
                                       for n, s, e in lines["XLA Modules"]]
            # a plane without an "XLA Ops" line: every line but the
            # modules' and the steps' holds operations
            plane_ops = lines.get("XLA Ops") or [
                ev for name, evs in lines.items()
                if name not in ("XLA Modules", "Steps") for ev in evs]
            if plane_ops:              # chips the run did not use stay out
                ops[plane.name] = plane_ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for name, s, e in _events(line):
                    if name == WINDOW_SPAN:
                        window = (s, e)
                    elif name in HOST_SPANS:
                        spans.append((name, s, e))
                    elif line.name.startswith("tf_XLA") and e > s and not (
                            name.startswith("ThreadpoolListener")
                            or name.startswith("end: ")):
                        cpu_ops.append((name, s, e))
                    elif _PJIT.match(name):
                        cpu_modules.append((_PJIT.sub(r"\1", name), s, e))
    if not ops and cpu_ops:
        ops = {"/host:CPU": cpu_ops}
        modules = {"/host:CPU": cpu_modules}
    return TraceEvents(ops, modules, spans, window)


def union(intervals: Sequence[Tuple[float, float]]
          ) -> List[Tuple[float, float]]:
    """Sorted, disjoint cover of the given [start, end) intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Sequence[Tuple[float, float]], lo: float, hi: float
         ) -> List[Tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def gaps(busy: Sequence[Tuple[float, float]], lo: float, hi: float
         ) -> List[Tuple[float, float]]:
    """The parts of [lo, hi) that the disjoint sorted `busy` leaves."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def attribute(gap: Tuple[float, float], spans: Sequence[Interval]) -> str:
    """The host span that overlaps the gap most, or "no span"."""
    best, name = 0.0, "no span"
    for n, s, e in spans:
        ov = min(e, gap[1]) - max(s, gap[0])
        if ov > best:
            best, name = ov, n
    return name


@dataclasses.dataclass
class Reduction:
    window_s: float
    busy_s: float                      # averaged over the devices
    module_s: Dict[str, float]         # summed over the devices
    idle_gaps: List[Tuple[str, float]]  # the longest, longest first, named
    device_ops: List[Tuple[str, float]]  # executables, most time first

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def module_seconds(self, name: str) -> float:
        """Device seconds of every executable whose name contains `name`."""
        return sum(v for k, v in self.module_s.items() if name in k)

    def breakdown(self, top: int = 10) -> dict:
        return {"device_ops": [[n, s] for n, s in self.device_ops[:top]],
                "idle_gaps": [[n, s] for n, s in self.idle_gaps[:top]]}


def reduce(ev: TraceEvents, window: Optional[Tuple[float, float]] = None,
           top: int = 10) -> Reduction:
    """Busy time, idle share and per-executable time over the window; the
    `top` longest idle gaps are named by the host span they overlap."""
    lo, hi = window or ev.window or (None, None)
    if lo is None:
        raise ValueError("trace has no window span")
    if not ev.ops:
        raise ValueError("trace has no device operations")
    busy_total, all_gaps = 0.0, []
    for dev, intervals in ev.ops.items():
        busy = union(clip([(s, e) for _, s, e in intervals], lo, hi))
        busy_total += sum(e - s for s, e in busy)
        all_gaps += gaps(busy, lo, hi)
    module_s: Dict[str, float] = {}
    for intervals in ev.modules.values():
        for n, s, e in clip_named(intervals, lo, hi):
            module_s[n] = module_s.get(n, 0.0) + (e - s) * 1e-9
    longest = sorted(all_gaps, key=lambda g: g[0] - g[1])[:top]
    return Reduction(
        window_s=(hi - lo) * 1e-9, busy_s=busy_total * 1e-9 / len(ev.ops),
        module_s=module_s,
        idle_gaps=[(attribute(g, ev.spans), (g[1] - g[0]) * 1e-9)
                   for g in longest],
        device_ops=sorted(module_s.items(), key=lambda kv: -kv[1]))


def clip_named(intervals: Sequence[Interval], lo: float, hi: float
               ) -> List[Interval]:
    return [(n, max(s, lo), min(e, hi)) for n, s, e in intervals
            if e > lo and s < hi]
