"""The one traffic generator: reads a mix's parameters from
`traffic/<mix>.json`, and gives the mix's driver (`drivers/<driver>.py`)
its draws and its loops:

* an open loop (`run_open_loop`): independent point readers. Arrivals
  follow the mix's process (`poisson`, or `on_off` bursts) at
  `rate_per_s`, keys come from `keys` (`scrambled_zipfian` with `theta`,
  as YCSB's ScrambledZipfianGenerator, or `uniform`), and every request
  is timed from when it was due, so a stall of the server delays the
  requests behind it. Requests are submitted to the program's
  `ServingFrontend` and answered by its `step()`.
* a closed loop (`run_range_stream`): one bulk reader streams
  `ByteRange`s through the program's `StreamingExecutor`: from a
  seed-chosen start, aligned to a whole chunk of blocks, to the end of
  the archive, then from 0 again, until the window has passed; the
  window ends with the last whole chunk.

Every draw comes from the run's seed. The host spans
(`jax.profiler.TraceAnnotation`) are the benchmark's own, around its
calls into the program.
"""
from __future__ import annotations

import dataclasses
import json
import time
from pathlib import Path
from typing import Callable, List, Optional

import numpy as np

_FNV_OFFSET = np.uint64(0xCBF29CE484222325)
_FNV_PRIME = np.uint64(0x100000001B3)


def load_mix(path: Path) -> dict:
    with open(path) as f:
        mix = json.load(f)
    if not isinstance(mix.get("driver"), str):
        raise ValueError(f"{path}: the mix names no driver")
    return mix


# ------------------------------------------------------------------ keys
def fnv1a64(x: np.ndarray) -> np.ndarray:
    """FNV-1a 64 over the 8 little-endian bytes of each value (YCSB's
    scramble of a Zipfian rank)."""
    x = np.asarray(x, np.uint64)
    h = np.full(x.shape, _FNV_OFFSET, np.uint64)
    with np.errstate(over="ignore"):
        for k in range(8):
            h = (h ^ ((x >> np.uint64(8 * k)) & np.uint64(0xFF))) * _FNV_PRIME
    return h


class ScrambledZipfian:
    """YCSB's Zipfian over `n` items (Gray et al.'s method), with the rank
    scrambled by FNV-1a so that hot keys spread over the keyspace."""

    def __init__(self, n: int, theta: float):
        self.n, self.theta = int(n), float(theta)
        i = np.arange(1, self.n + 1, dtype=np.float64)
        self.zetan = float(np.sum(i ** -self.theta))
        zeta2 = 1.0 + 0.5 ** self.theta
        self.alpha = 1.0 / (1.0 - self.theta)
        self.eta = ((1.0 - (2.0 / self.n) ** (1.0 - self.theta))
                    / (1.0 - zeta2 / self.zetan))
        self._half_pow = 1.0 + 0.5 ** self.theta

    def ranks(self, u: np.ndarray) -> np.ndarray:
        uz = u * self.zetan
        r = (self.n * (self.eta * u - self.eta + 1.0) ** self.alpha)
        r = np.where(uz < 1.0, 0, np.where(uz < self._half_pow, 1, r))
        return np.minimum(r.astype(np.int64), self.n - 1)

    def key(self, ranks: np.ndarray) -> np.ndarray:
        return (fnv1a64(ranks) % np.uint64(self.n)).astype(np.int64)

    def draw(self, rng: np.random.Generator, k: int) -> np.ndarray:
        return self.key(self.ranks(rng.random(k)))


def draw_keys(mix: dict, n_keys: int, rng: np.random.Generator,
              k: int) -> np.ndarray:
    if mix["keys"] == "scrambled_zipfian":
        return ScrambledZipfian(n_keys, mix["theta"]).draw(rng, k)
    if mix["keys"] == "uniform":
        return rng.integers(n_keys, size=k)
    raise ValueError(f"unknown key distribution {mix['keys']!r}")


def arrivals(mix: dict, seconds: float, rng: np.random.Generator
             ) -> np.ndarray:
    """Due times (seconds from the window's start) of the requests of a
    `seconds`-long window."""
    rate = float(mix["rate_per_s"])
    if mix["arrivals"] == "poisson":
        n = int(rate * seconds * 1.5) + 64
        t = np.cumsum(rng.exponential(1.0 / rate, n))
        while t[-1] < seconds:
            t = np.concatenate([t, t[-1] + np.cumsum(
                rng.exponential(1.0 / rate, n))])
        return t[t < seconds]
    if mix["arrivals"] == "on_off":
        on, off = float(mix["on_s"]), float(mix["off_s"])
        busy = arrivals(dict(mix, arrivals="poisson",
                             rate_per_s=rate * (on + off) / on),
                        seconds * on / (on + off) + on, rng)
        t = busy + np.floor(busy / on) * off
        return t[t < seconds]
    raise ValueError(f"unknown arrival process {mix['arrivals']!r}")


# ------------------------------------------------------------- open loop
@dataclasses.dataclass
class OpenLoopRecord:
    due: np.ndarray            # f64[n] seconds from window start
    keys: np.ndarray           # i64[n] read ids
    done: np.ndarray           # f64[n] completion, nan = never answered
    status: List[str]          # "ok" | "late" | "shed" | "overloaded" | ...
    payloads: List[Optional[np.ndarray]]
    lateness: np.ndarray       # f64[n] submit time - due time
    steps: int
    window_s: float


def _span(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


class _Mark:
    """Calls `fn` once, the first time it is polled `at` seconds or more
    after the window's start."""

    def __init__(self, mark: Optional[tuple]):
        self.at, self.fn = mark if mark is not None else (None, None)

    def poll(self, elapsed: float) -> None:
        if self.fn is not None and elapsed >= self.at:
            fn, self.fn = self.fn, None
            fn()


def run_open_loop(fe, tenant: str, due: np.ndarray, keys: np.ndarray,
                  window_s: float, drain_s: float = 60.0,
                  clock: Callable[[], float] = time.perf_counter,
                  mark: Optional[tuple] = None) -> OpenLoopRecord:
    """Submit request i at `due[i]` seconds after the start, step the
    frontend while anything is queued, and time every answer from when it
    was due. Requests still unanswered `drain_s` after the window stay
    unanswered. `mark` = (seconds, fn): fn() runs once at that point of
    the window."""
    at = _Mark(mark)
    from repro.serving.frontend import Overloaded
    n = due.size
    done = np.full(n, np.nan)
    lateness = np.zeros(n)
    status = ["missing"] * n
    payloads: List[Optional[np.ndarray]] = [None] * n
    seq_of = {}
    i = steps = 0
    t0 = clock()
    deadline = t0 + window_s + drain_s
    while True:
        now = clock() - t0
        at.poll(now)
        if i < n and due[i] <= now:
            with _span("submit"):
                while i < n and due[i] <= now:
                    ticket = fe.submit(tenant, int(keys[i]))
                    lateness[i] = now - due[i]
                    if isinstance(ticket, Overloaded):
                        status[i] = "overloaded"
                        done[i] = now
                    else:
                        seq_of[ticket.seq] = i
                    i += 1
        if fe.pending():
            with _span("frontend.step"):
                fe.step()
                results = fe.take_results()
            t = clock() - t0
            steps += 1
            for seq, res in results.items():
                j = seq_of.pop(seq)
                done[j], status[j] = t, res.status
                payloads[j] = (res.payload if isinstance(res.payload,
                                                         np.ndarray)
                               else None)
        elif i < n:
            wait = due[i] - (clock() - t0)
            if wait > 0:
                with _span("generator.wait"):
                    time.sleep(wait)
        else:
            break
        if clock() > deadline:
            break
    return OpenLoopRecord(due=due, keys=keys, done=done, status=status,
                          payloads=payloads, lateness=lateness, steps=steps,
                          window_s=window_s)


# ----------------------------------------------------------- closed loop
@dataclasses.dataclass
class StreamRecord:
    seconds: float             # first chunk requested -> last chunk ended
    bytes: int                 # decoded bytes returned in the window
    chunks: int
    passes_ended: int          # passes that ran to the end of the archive
    short_passes: int          # passes whose bytes did not add up
    kept: List[tuple]          # (byte offset, chunk) sample to check


def run_range_stream(ex, raw_size: int, start: int, window_s: float,
                     rng: np.random.Generator, keep_bytes: int,
                     clock: Callable[[], float] = time.perf_counter,
                     mark: Optional[tuple] = None) -> StreamRecord:
    """Stream ByteRange(start, raw_size), then ByteRange(0, raw_size)
    again and again, until `window_s` has passed; the window ends with
    the chunk that is running then. A reservoir sample of the chunks,
    drawn from `rng` and at most `keep_bytes` in all, is kept to check.
    `mark` = (seconds, fn): fn() runs once, between chunks, at that point
    of the window or the first chunk's end after it."""
    at = _Mark(mark)
    from repro.api import ByteRange
    kept: List[tuple] = []
    seen = 0
    total = chunks = ended = short = 0
    lo = start
    t0 = clock()
    at.poll(0.0)
    while True:
        pos = lo
        it = ex.chunks([ByteRange(lo, raw_size)])
        while True:
            with _span("stream.next_chunk"):
                chunk = next(it, None)
            if chunk is None:
                break
            chunks += 1
            total += chunk.size
            # reservoir sample of the chunks, each equally likely
            slots = max(1, keep_bytes // max(chunk.size, 1))
            if len(kept) < slots:
                kept.append((pos, chunk))
            else:
                j = int(rng.integers(seen + 1))
                if j < slots:
                    kept[j] = (pos, chunk)
            seen += 1
            pos += chunk.size
            at.poll(clock() - t0)
            if clock() - t0 >= window_s:
                return StreamRecord(clock() - t0, total, chunks, ended,
                                    short, kept)
        ended += 1
        short += int(pos != raw_size)
        lo = 0
