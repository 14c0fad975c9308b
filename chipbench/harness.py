"""One run of one cell: set-up, the measured window, the check against
the host reference, and the result line.

Everything particular to a cell comes from files found by the names in
`BENCHMARK.json`, so a deployment is added as new files alone:

* the configuration (`configs/<config>.json`), whose `corpus` names
* the corpus module (`corpora/<corpus>.py`): `generate(config, seed)`
  gives the corpus bytes, its class `HostReference(corpus)` the plain
  reference (it imports nothing of the program), `build(corpus, config,
  **cache)` the program's archive over the bytes, and `check(ga, ref)`
  raises where archive and reference disagree;
* the traffic mix (`traffic/<mix>.json`), whose `driver` names
* the request driver (`drivers/<driver>.py`, its class `Driver`, see
  `Driver` below);
* each per-layer metric's reader (`metrics/<metric>.py`, a function
  `read(readings)` that returns a number or None);
* for the CPU tests, the cell's tiny size (`tests/tiny/<cell>.json`).
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import os
import shutil
import sys
import time
from pathlib import Path
from typing import Callable, Dict, List, Optional

import numpy as np

from chipbench import loadgen, trace as tracing
from chipbench.archives import ARCHIVE_DIR, open_or_build
from chipbench.peaks import PEAKS, peak
from chipbench.workcount import DecodeLog

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
SRC = ROOT / "src"
BENCHMARK = ROOT / "BENCHMARK.json"
TRACE_DIR = HERE / ".traces"
TRACE_S = 10.0             # a traced run profiles the window's last seconds


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Cell:
    name: str
    config_name: str
    config: dict
    mix: dict
    chips: int
    end_to_end: List[dict]
    per_layer: List[dict]
    home: Path                 # where its files are found by name


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, bench_path: Path = BENCHMARK) -> Cell:
    with open(bench_path) as f:
        bench = json.load(f)
    work = {w["name"]: w for w in bench["workloads"]}
    if name not in work:
        raise KeyError(f"no workload {name!r} in {bench_path} "
                       f"(have {sorted(work)})")
    w = work[name]
    cfg = next(c for c in bench["configs"] if c["name"] == w["config"])
    with open(bench_path.parent / cfg["file"]) as f:
        config = json.load(f)
    home = bench_path.parent / HERE.name
    mix = loadgen.load_mix(home / "traffic" / f"{w['traffic']}.json")
    return Cell(name=name, config_name=w["config"], config=config, mix=mix,
                chips=int(w["chips"]),
                end_to_end=[m for m in bench["end_to_end"]
                            if _applies(m, name)],
                per_layer=[m for m in bench["per_layer"]
                           if _applies(m, name)],
                home=home)


def load_module(home: Path, kind: str, name: str):
    """`<home>/<kind>/<name>.py`, loaded by its path (a metric's name has
    dots in it)."""
    path = home / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"chipbench.{kind}.{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, home: Path = HERE) -> Callable:
    return load_module(home, "metrics", name).read


def driver_class(cell: Cell) -> type:
    return load_module(cell.home, "drivers", cell.mix["driver"]).Driver


@dataclasses.dataclass
class Hooks:
    """What a test or the control run steers; a benchmark run uses none.
    `plant(state)` runs after warm-up, just before the window."""
    require_tpu: bool = True
    config_overrides: Dict = dataclasses.field(default_factory=dict)
    mix_overrides: Dict = dataclasses.field(default_factory=dict)
    peaks: Optional[Dict] = None
    archive_dir: Path = ARCHIVE_DIR
    trace_dir: Path = TRACE_DIR
    plant: Optional[Callable] = None


class CompileCounter:
    """Executables JAX compiled or loaded from its persistent cache, from
    `jax.monitoring` events."""

    def __init__(self):
        import jax
        self.compiles = 0
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.seconds += secs

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


@dataclasses.dataclass
class Readings:
    """What the per-layer readers read: the counters over the window,
    the trace and the decode work over its traced part."""
    kind: str                      # the driver's loop, "open" | "closed"
    requests: int
    cache_before: dict
    cache_after: dict
    work_bytes: int                # compressed + decoded bytes of the
                                   # blocks the traced part decoded
    reduction: Optional[tracing.Reduction]
    peak_bytes_per_s: float
    latencies_ms: Optional[np.ndarray] = None  # point reads of the window

    def cache_delta(self, key: str) -> int:
        return int(self.cache_after[key]) - int(self.cache_before[key])


class Driver:
    """A cell's request driver: the class `Driver` of
    `drivers/<driver>.py`, named by the traffic file's `driver`. Set-up
    makes it once the archive is open and calls `warm()`, which runs
    every shape the window will use. `window(seconds, mark)` drives the
    program and returns a record; `mark` = (at, fn), where given, asks
    for fn() once, between requests, `at` seconds into the window (a
    traced run profiles from there). The check, the result line and the
    readers take from that record: `check(rec)` {name: (number, limit)},
    `failed(rec, checks)`, `requests(rec)`, `end_to_end(rec)` {metric:
    value}, `describe(rec)` and `latencies_ms(rec)`. `drop_half()` makes
    the program lose half of the window's answers, as a fault of the
    control. `loop` is "open" where requests arrive on a schedule at the
    mix's `rate_per_s`, which `sweep.py` sets."""

    loop = "closed"

    def __init__(self, ga, ref, config: dict, mix: dict,
                 rng: np.random.Generator, log: Callable,
                 warm_rng: np.random.Generator, devices: list):
        self.ga, self.ref, self.config, self.mix = ga, ref, config, mix
        self.rng, self.log, self.warm_rng = rng, log, warm_rng
        self.devices = devices

    def decoders(self) -> list:
        """The decoders whose launches the work count sums."""
        return [self.ga.store.decoder]

    def latencies_ms(self, rec) -> Optional[np.ndarray]:
        return None


# ------------------------------------------------------------------- run
def _array_bytes() -> int:
    """Device bytes of every array alive in the process, shard by shard:
    the data the program holds (archive, index, cache). The compiled
    programs' own device memory is not in it."""
    import jax
    return sum(int(sh.data.nbytes) for a in jax.live_arrays()
               for sh in a.addressable_shards)


def _bytes_in_use(device) -> int:
    stats = device.memory_stats()
    if stats and "bytes_in_use" in stats:
        return int(stats["bytes_in_use"])
    return _array_bytes()


def _peak_bytes(device) -> int:
    stats = device.memory_stats()
    if stats and "peak_bytes_in_use" in stats:
        return int(stats["peak_bytes_in_use"])
    return _bytes_in_use(device)


def _profile_options():
    """Device operations and the benchmark's host spans; no Python
    function tracer, which would slow the host loop it is measuring and
    swell the trace, and no HLO protos, which no reading needs."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    return opts


def seed_sequence(seed: int) -> np.random.SeedSequence:
    return np.random.SeedSequence(int(seed) % (1 << 64))


@dataclasses.dataclass
class Session:
    """A cell after set-up: the program's objects, warm, and what the
    measurement needs to read around its window."""
    cell: Cell
    hooks: Hooks
    devices: list
    peak: dict
    log: Callable
    counter: CompileCounter
    ga: object
    ref: object
    state: Driver
    decode_log: DecodeLog
    raw_bytes: int
    resident_bytes: int
    setup_s: float


def open_session(cell: Cell, seed: int, hooks: Optional[Hooks] = None,
                 t_start: Optional[float] = None) -> Session:
    """Everything before the window: device check, corpus, archive,
    warm-up. Raises `NoChip` when JAX finds no TPU (or too few)."""
    hooks = hooks or Hooks()
    t0 = time.perf_counter() if t_start is None else t_start
    gc.collect()
    import jax
    devices = jax.devices()
    d0 = devices[0]
    if hooks.require_tpu and d0.platform != "tpu":
        raise NoChip(f"no TPU: JAX platform is {d0.platform!r}, and there "
                     f"is no CPU fallback")
    if len(devices) < cell.chips:
        raise NoChip(f"{len(devices)} devices, the cell needs {cell.chips}")
    chip_peak = peak(d0.device_kind,
                     PEAKS if hooks.peaks is None else hooks.peaks)
    label = f"[{d0.platform} {d0.device_kind} x{len(devices)}]"

    def log(msg: str) -> None:
        print(f"{label} {msg}", flush=True)

    counter = CompileCounter()
    config = {**cell.config, **hooks.config_overrides}
    mix = {**cell.mix, **hooks.mix_overrides}
    corpus_seq, traffic_seq, warm_seq = seed_sequence(seed).spawn(3)
    t = time.perf_counter()
    corpora = load_module(cell.home, "corpora", config["corpus"])
    corpus = corpora.generate(config, int(corpus_seq.generate_state(1)[0]))
    ref = corpora.HostReference(corpus)
    t_gen = time.perf_counter() - t
    t = time.perf_counter()
    ga, built = open_or_build(cell.config_name, config, seed, corpus,
                              corpora.build, SRC, hooks.archive_dir)
    t_arch = time.perf_counter() - t
    corpora.check(ga, ref)
    rng = np.random.default_rng(traffic_seq)
    state = driver_class(cell)(ga, ref, config, mix, rng, log,
                               np.random.default_rng(warm_seq), devices)
    decode_log = DecodeLog(state.decoders())
    t = time.perf_counter()
    state.warm()
    t_warm = time.perf_counter() - t
    gc.collect()
    # what set-up left behind is long-lived: keep it out of the
    # collector's full passes inside the window
    gc.freeze()
    resident = _array_bytes()
    in_use = _bytes_in_use(d0)
    setup_s = time.perf_counter() - t0
    st = ga.stats()
    log(f"set-up {setup_s:.3f}s: corpus {len(corpus)} bytes in "
        f"{t_gen:.3f}s, archive {'encoded and saved' if built else 'opened'}"
        f" in {t_arch:.3f}s ({st.compressed_device_bytes} bytes compressed,"
        f" {st.n_blocks} blocks), warm-up {t_warm:.3f}s; {resident} bytes "
        f"of arrays on the device, {in_use} in use by its allocator "
        f"(compiled programs hold the rest); {counter.compiles} "
        f"executables compiled "
        f"({counter.seconds:.2f}s), {counter.cache_hits} from the "
        f"persistent cache")
    return Session(cell=cell, hooks=hooks, devices=devices,
                   peak=chip_peak, log=log, counter=counter,
                   ga=ga, ref=ref, state=state, decode_log=decode_log,
                   raw_bytes=len(corpus), resident_bytes=resident,
                   setup_s=setup_s)


def measure(s: Session, seconds: float, trace: bool) -> dict:
    """The measured window, the check, and the result object."""
    import jax
    hooks, state, ga, d0 = s.hooks, s.state, s.ga, s.devices[0]
    if hooks.plant is not None:
        hooks.plant(state)
    compiles0 = s.counter.compiles
    cache0 = dict(ga.cache_info())
    mark = s.decode_log.mark()
    reduction = None
    if trace:
        shutil.rmtree(hooks.trace_dir, ignore_errors=True)
        started = []

        def start() -> None:
            """Profile from here to the window's end, and count the decode
            work from here, so the roofline reads the traced part alone
            (every step and chunk has ended when the window polls)."""
            jax.profiler.start_trace(str(hooks.trace_dir),
                                     profiler_options=_profile_options())
            span = jax.profiler.TraceAnnotation(tracing.WINDOW_SPAN)
            span.__enter__()
            started.extend([time.perf_counter(), span,
                            s.decode_log.mark()])

        try:
            rec = state.window(seconds,
                               mark=(max(0.0, seconds - TRACE_S), start))
        finally:
            if started:
                started[1].__exit__(None, None, None)
                t = time.perf_counter()
                jax.profiler.stop_trace()
                s.log(f"traced the last {t - started[0]:.3f}s of the "
                      f"window; stopping the profiler took "
                      f"{time.perf_counter() - t:.3f}s")
        if not started:
            raise RuntimeError("the window ended before its traced part")
    else:
        rec = state.window(seconds)
    cache1 = dict(ga.cache_info())
    work_bytes = s.decode_log.work_bytes(started[2] if trace else mark)
    in_window = s.counter.compiles - compiles0
    memory_peak = _peak_bytes(d0)
    s.log(state.describe(rec))
    s.log(f"executables compiled inside the window: {in_window}")

    with jax.profiler.TraceAnnotation("reference_check"):
        checks = state.check(rec)
    cell = s.cell
    metrics = {}
    if trace:
        t = time.perf_counter()
        path = tracing.latest_xplane(str(hooks.trace_dir))
        events = tracing.load(path)
        reduction = tracing.reduce(events)
        s.log(f"trace: {os.path.getsize(path)} bytes, "
              f"{sum(map(len, events.ops.values()))} device operations, "
              f"read and reduced in {time.perf_counter() - t:.3f}s")
        readings = Readings(
            kind=state.loop, requests=state.requests(rec),
            cache_before=cache0, cache_after=cache1, work_bytes=work_bytes,
            reduction=reduction, peak_bytes_per_s=s.peak["hbm_bytes_per_s"],
            latencies_ms=state.latencies_ms(rec))
        for m in cell.per_layer:
            value = metric_reader(m["name"], cell.home)(readings)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        e2e = state.end_to_end(rec)
        e2e["setup_s"] = s.setup_s
        e2e["resident_data_bytes_per_raw_byte"] = (s.resident_bytes
                                                   / s.raw_bytes)
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(s.devices), "memory_peak_bytes": memory_peak}
    result = {"correct": all(v <= lim for v, lim in checks.values()),
              "attempted": state.requests(rec),
              "failed": state.failed(rec, checks),
              "metrics": metrics, "device": device}
    if reduction is not None:
        device["busy_s"] = reduction.busy_s
        device["window_s"] = reduction.window_s
        result["breakdown"] = reduction.breakdown()
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    for k, (v, lim) in checks.items():
        print(f"check {k}: {v} (limit {lim})", file=sys.stderr, flush=True)
    gc.unfreeze()
    return result


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             hooks: Optional[Hooks] = None,
             t_start: Optional[float] = None) -> dict:
    """Run the cell once and return its result object (the last line)."""
    return measure(open_session(cell, seed, hooks, t_start), seconds, trace)
