#!/usr/bin/env python3
"""What the program names inside itself, read from a profiler trace.

    python3 chipbench/stages.py <trace dir or .xplane.pb> [--decoded-bytes N]

prints one JSON object: the device time of each decode stage, the idle
gaps named by the program's host spans, and the host time of each frontend
step. It reads the names of the program's table (`src/repro/trace.py`),
beside `chipbench/trace.py`, whose reduction, and every metric read from
it, it leaves as they are.

Stage time: every operation of a device's "XLA Ops" line is labelled by
its executable and by the innermost stage scope (`jax.named_scope`, the
table's `SCOPES`) of its HLO `op_name`, or `UNSCOPED`: XLA-made copies
and the ops outside every scope. A chip writes each operation's `op_name`
(`tf_op`) and program id into its event metadata, which the JAX profiler's
Python reader does not show, so `op_names` reads them from the file; a
CPU trace names its operations' program and HLO instruction, mapped
through the HLO protos the profiler records with `enable_hlo_proto`. Every
instant goes to the innermost operation running then, so a `while` and
its body count once, and the stages of a module sum to at most its time.

Idle gaps: each of the longest is named by the innermost host span that
holds most of it, among the benchmark's spans and the program's
(`attribute`).
"""
from __future__ import annotations

import argparse
import bisect
import json
import os
import re
import statistics
import sys
import warnings
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

if __name__ == "__main__":
    _ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path[:0] = [os.path.join(_ROOT, "src"), _ROOT]

from chipbench import trace as tracing  # noqa: E402

try:
    from repro import trace as program
except ImportError:          # a program without the name table
    program = None

Interval = Tuple[str, float, float]          # (name, start_ns, end_ns)
Labelled = Tuple[str, str, float, float]     # (module, stage, start, end)

STAGES = program.SCOPES if program is not None else ()
PROGRAM_SPANS = program.HOST_SPANS if program is not None else ()
SPANS = tracing.HOST_SPANS + PROGRAM_SPANS
UNSCOPED = "unscoped"
_MODULE_ID = re.compile(r"\((\d+)\)$")
_PATH_SEP = re.compile(r"[/()]")


def stage_of(path: str) -> str:
    """The innermost stage scope named in an HLO `op_name` path
    (`jit(f)/vmap(decode.expand)/...`), or `UNSCOPED`."""
    got = [t for t in _PATH_SEP.split(path) if t in STAGES]
    return got[-1] if got else UNSCOPED


# ------------------------------------------- op_name metadata of a trace
# Field numbers of tsl/profiler/protobuf/xplane.proto: XSpace.planes 1;
# XPlane.name 2, .event_metadata 4 and .stat_metadata 5 (map entries: key
# 1, value 2); XEventMetadata.name 2, .stats 5; XStatMetadata.name 2;
# XStat.metadata_id 1, .uint64_value 3, .int64_value 4, .str_value 5,
# .bytes_value 6. And of xla/service/hlo.proto: HloProto.hlo_module 1;
# HloModuleProto.computations 3; HloComputationProto.instructions 2;
# HloInstructionProto.name 1, .metadata 7; OpMetadata.op_name 2.
def _varint(buf, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one protobuf message: an int for a
    varint, a memoryview for a length-delimited or fixed-width field."""
    buf = memoryview(buf)
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {wire} not read here")
        yield key >> 3, value


def _text(v) -> str:
    return bytes(v).decode()


def _stat_ids(plane_fields, names: Sequence[str]) -> Dict[str, int]:
    """Stat name -> its id in the plane's stat metadata."""
    out = {}
    for f, entry in plane_fields:
        if f != 5:
            continue
        entry = dict(_fields(entry))
        name = dict(_fields(entry.get(2, b""))).get(2)
        if name is not None and _text(name) in names:
            out[_text(name)] = entry.get(1)
    return out


def _event_metadata(plane_fields
                    ) -> Iterator[Tuple[int, str, Dict[int, object]]]:
    """(key, name, {stat id: value}) of each event metadata of a plane."""
    for f, entry in plane_fields:
        if f != 4:
            continue
        entry = dict(_fields(entry))
        name, stats = "", {}
        for g, v in _fields(entry.get(2, b"")):
            if g == 2:
                name = _text(v)
            elif g == 5:
                stat = dict(_fields(v))
                stats[stat.get(1)] = next(
                    (stat[k] for k in (5, 3, 4, 6) if k in stat), None)
        yield entry.get(1), name, stats


def _device_op_names(plane_fields) -> Dict[Tuple[int, str], str]:
    """A device plane's (program id, event name) -> op_name: the chip
    writes both into each operation's event metadata."""
    ids = _stat_ids(plane_fields, ("program_id", "tf_op"))
    if len(ids) < 2:
        return {}
    out = {}
    for _, name, stats in _event_metadata(plane_fields):
        path, pid = stats.get(ids["tf_op"]), stats.get(ids["program_id"])
        if path is not None and pid is not None:
            out[(pid, name)] = _text(path)
    return out


def _hlo_op_names(hlo_proto) -> Dict[str, str]:
    """HloProto -> {instruction name: op_name}."""
    out: Dict[str, str] = {}
    for f, module in _fields(hlo_proto):
        if f != 1:
            continue
        for f, comp in _fields(module):
            if f != 3:
                continue
            for f, inst in _fields(comp):
                if f != 2:
                    continue
                name = op_name = None
                for g, v in _fields(inst):
                    if g == 1:
                        name = _text(v)
                    elif g == 7:
                        op_name = dict(_fields(v)).get(2)
                if name is not None and op_name is not None:
                    out[name] = _text(op_name)
    return out


def _proto_op_names(plane_fields) -> Dict[Tuple[int, str], str]:
    """The HLO protos of the "/host:metadata" plane, keyed as the CPU's
    operation events are: (program id, HLO instruction) -> op_name."""
    hlo = _stat_ids(plane_fields, ("Hlo Proto",)).get("Hlo Proto")
    out = {}
    for pid, _, stats in _event_metadata(plane_fields):
        proto = stats.get(hlo) if hlo is not None else None
        if proto is not None:
            for inst, op_name in _hlo_op_names(proto).items():
                out[(pid, inst)] = op_name
    return out


def op_names(path: str) -> Dict[Tuple[int, str], str]:
    """(program id, operation) -> the HLO `op_name` of every operation
    the trace names: from the device planes' event metadata in a chip's
    trace, else from the HLO protos of "/host:metadata" (a CPU's)."""
    with open(path, "rb") as f:
        space = f.read()
    device: Dict[Tuple[int, str], str] = {}
    metadata = None
    for f, plane in _fields(space):
        if f != 1:
            continue
        fields = list(_fields(plane))
        name = next((_text(v) for g, v in fields if g == 2), "")
        if name.startswith("/device:"):
            device.update(_device_op_names(fields))
        elif name == "/host:metadata":
            metadata = fields
    if device or metadata is None:
        return device
    return _proto_op_names(metadata)


# --------------------------------------------------------------- events
def _containing(modules: Sequence[Interval]):
    """start -> (module, program id) of the module event running then;
    a chip's module events are named `<module>(<program id>)`."""
    mods = sorted((s, e, n) for n, s, e in modules)
    starts = [m[0] for m in mods]
    named = {}
    for _, _, n in mods:
        pid = _MODULE_ID.search(n)
        named[n] = (_MODULE_ID.sub("", n).strip(),
                    int(pid.group(1)) if pid else None)

    def find(t: float) -> Tuple[str, Optional[int]]:
        i = bisect.bisect_right(starts, t) - 1
        return (named[mods[i][2]] if i >= 0 and t < mods[i][1]
                else ("", None))
    return find


def load(path: str) -> Tuple[Dict[str, List[Labelled]], List[Interval]]:
    """({device: labelled operations}, host spans of the benchmark and the
    program) of a trace."""
    from jax.profiler import ProfileData
    names = op_names(path)
    of_path: Dict[str, str] = {}

    def stage(pid, op: str) -> str:
        got = names.get((pid, op))
        if got is None:
            return UNSCOPED
        if got not in of_path:
            of_path[got] = stage_of(got)
        return of_path[got]

    labelled: Dict[str, List[Labelled]] = {}
    cpu: List[Labelled] = []
    spans: List[Interval] = []
    with warnings.catch_warnings():
        # reading an event's stats warns that its binding type has no
        # __module__, once per event
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in ProfileData.from_file(path).planes:
            lines = {line.name: line for line in plane.lines}
            if plane.name.startswith("/device:") and "XLA Ops" in lines:
                module_at = _containing(
                    tracing._events(lines["XLA Modules"])
                    if "XLA Modules" in lines else [])
                tagged = []
                for n, s, e in tracing._events(lines["XLA Ops"]):
                    module, pid = module_at(s)
                    tagged.append((module, stage(pid, n), s, e))
                if tagged:
                    labelled[plane.name] = tagged
            elif plane.name.startswith("/host:"):
                for name, line in lines.items():
                    for ev in line.events:
                        s = ev.start_ns
                        if ev.name in SPANS:
                            spans.append((ev.name, s, s + ev.duration_ns))
                        elif name.startswith("tf_XLA"):
                            st = dict(ev.stats)
                            if "hlo_op" in st and ev.duration_ns > 0:
                                cpu.append((
                                    str(st.get("hlo_module", "")),
                                    stage(st.get("program_id"),
                                          str(st["hlo_op"])),
                                    s, s + ev.duration_ns))
    if not labelled and cpu:
        labelled = {"/host:CPU": cpu}
    return labelled, spans


# ------------------------------------------------------------ reduction
def self_times(ops: Sequence[Labelled], lo: float, hi: float
               ) -> Dict[Tuple[str, str], float]:
    """Nanoseconds of [lo, hi) per (module, stage) label: every instant
    goes to the operation that started last among those still running,
    the innermost one where events nest, so an enclosing event counts
    only the time no event inside it covers, and no instant counts
    twice."""
    out: Dict[Tuple[str, str], float] = {}
    stack: List[Tuple[float, Tuple[str, str]]] = []   # (end, label)
    t = lo

    def run_to(x: float) -> None:
        nonlocal t
        while stack and t < x:
            end, label = stack[-1]
            if end <= t:
                stack.pop()
                continue
            stop = min(end, x)
            out[label] = out.get(label, 0.0) + stop - t
            t = stop
        t = max(t, x)

    for s, neg_e, mod, stage in sorted(
            (max(s, lo), -min(e, hi), mod, stage)
            for mod, stage, s, e in ops if e > lo and s < hi):
        run_to(s)
        stack.append((-neg_e, (mod, stage)))
    run_to(hi)
    return out


def attribute(gap: Tuple[float, float], spans: Sequence[Interval]) -> str:
    """The span that overlaps the gap most, then, inside it, the span
    that overlaps the gap most, and so on down while that span overlaps
    the gap more than its parent's own time does (the part of the gap
    the parent holds outside every span inside it): the innermost span
    that holds most of the gap, or "no span". Where a parent and a child
    overlap the gap equally, the child is taken."""
    def overlap(s: float, e: float) -> float:
        return max(0.0, min(e, gap[1]) - max(s, gap[0]))

    name, pool, own = "no span", list(spans), None
    while True:
        best = max(((overlap(s, e), e - s, i)
                    for i, (_, s, e) in enumerate(pool)), default=None)
        if best is None or best[0] <= 0 or (own is not None
                                            and best[0] < own):
            return name
        name, s0, e0 = pool[best[2]]
        pool = [sp for i, sp in enumerate(pool)
                if i != best[2] and s0 <= sp[1] and sp[2] <= e0]
        covered = sum(b - a for a, b in tracing.union(
            [(max(s, gap[0]), min(e, gap[1])) for _, s, e in pool
             if overlap(s, e) > 0]))
        own = best[0] - covered


def host_ms(spans: Sequence[Interval], outer: str, inner: str,
            lo: float, hi: float) -> List[float]:
    """Per `outer` span inside [lo, hi): its milliseconds less those its
    `inner` spans cover (the spans of one thread nest)."""
    inside = tracing.union([(s, e) for n, s, e in spans if n == inner])
    out = []
    for n, s, e in spans:
        if n == outer and lo <= s and e <= hi:
            covered = sum(b - a for a, b in tracing.clip(inside, s, e))
            out.append((e - s - covered) * 1e-6)
    return out


def spans_inside(spans: Sequence[Interval], outer: str,
                 lo: float, hi: float) -> List[int]:
    """Per `outer` span inside [lo, hi): the program spans it holds,
    itself included."""
    prog = sorted((s, e) for n, s, e in spans if n in PROGRAM_SPANS)
    starts = [s for s, _ in prog]
    out = []
    for n, s, e in spans:
        if n == outer and lo <= s and e <= hi:
            i = bisect.bisect_left(starts, s)
            j = bisect.bisect_right(starts, e)
            out.append(sum(1 for a, b in prog[i:j] if b <= e))
    return out


def reduce(path: str, decoded_bytes: int = 0,
           module: str = "_decode_sel_core", top: int = 10) -> dict:
    """The stage split of `module`, the named idle gaps, and the host
    steps, over the trace's "window" span. `decoded_bytes` (of the blocks
    decoded in the window) gives each stage group's GB per device
    second."""
    events = tracing.load(path)
    base = tracing.reduce(events)
    lo, hi = events.window
    labelled, spans = load(path)
    stage_s: Dict[Tuple[str, str], float] = {}
    for ops in labelled.values():
        for key, ns in self_times(ops, lo, hi).items():
            stage_s[key] = stage_s.get(key, 0.0) + ns * 1e-9
    split: Dict[str, float] = {}
    for (m, st), v in stage_s.items():
        if module in m:
            split[st] = split.get(st, 0.0) + v
    module_s = base.module_seconds(module)
    all_gaps = []
    for intervals in events.ops.values():
        busy = tracing.union(tracing.clip([(s, e) for _, s, e in intervals],
                                          lo, hi))
        all_gaps += tracing.gaps(busy, lo, hi)
    longest = sorted(all_gaps, key=lambda g: g[0] - g[1])[:top]
    out = {"window_s": base.window_s, "busy_s": base.busy_s,
           "idle_share": base.idle_share, "module": module,
           "module_s": module_s,
           "stage_s": dict(sorted(split.items(), key=lambda kv: -kv[1])),
           "stage_share": {st: v / module_s for st, v in split.items()}
           if module_s > 0 else {},
           "idle_gaps": [[attribute(g, spans), (g[1] - g[0]) * 1e-9]
                         for g in longest]}
    if program is not None:
        groups = {"entropy": (program.DECODE_RANS, program.DECODE_LINEARIZE),
                  "match": (program.DECODE_EXPAND, program.DECODE_RESOLVE)}
        for group, scopes in groups.items():
            seconds = sum(split.get(st, 0.0) for st in scopes)
            out[f"{group}_stage_s"] = seconds
            if decoded_bytes and seconds > 0:
                out[f"{group}_stage_GBps"] = decoded_bytes / seconds / 1e9
        for outer in (program.FRONTEND_STEP, program.STREAM_CHUNK):
            counts = spans_inside(spans, outer, lo, hi)
            if counts:
                out[f"spans_per.{outer}"] = statistics.median(counts)
        steps = host_ms(spans, program.FRONTEND_STEP, program.TO_HOST,
                        lo, hi)
        if steps:
            out["host_ms_per_step"] = statistics.median(steps)
            out["steps"] = len(steps)
        inner = {}
        for n, s, e in spans:
            if n in PROGRAM_SPANS and n != program.FRONTEND_STEP \
                    and lo <= s and e <= hi:
                inner.setdefault(n, []).append((e - s) * 1e-6)
        out["span_ms_median"] = {n: statistics.median(v)
                                 for n, v in inner.items()}
        out["span_ms_total"] = {n: sum(v) for n, v in inner.items()}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("trace", help="a trace directory or an .xplane.pb")
    ap.add_argument("--decoded-bytes", type=int, default=0)
    args = ap.parse_args(argv)
    path = (args.trace if args.trace.endswith(".xplane.pb")
            else tracing.latest_xplane(args.trace))
    print(json.dumps(reduce(path, args.decoded_bytes)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
