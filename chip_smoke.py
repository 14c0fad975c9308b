#!/usr/bin/env python3
"""Chip smoke test: the compressed-resident decode and query path on a TPU.

    python chip_smoke.py                # one chip: build 1,000,000 reads,
                                        # decode, query, stream and serve
    python chip_smoke.py --four-chips   # mesh-partitioned residency over
                                        # four chips vs one-device decode

Every phase drives the system through the entry points a user calls
(`GenomicArchive`, `Decoder.decode_all`, `ServingFrontend`,
`partition_archive`, `ShardedExecutor`) and checks every byte against a
host reference built from the corpus bytes alone (a numpy newline scan,
not the repo's own index). Everything runs in this one process: a chip
belongs to one process at a time.

The script exits nonzero, and prints no result line, when JAX finds no
TPU (`JAX_PLATFORMS=cpu python chip_smoke.py` fails) or when any phase
fails. Earlier lines are informational (phase times, rates, resident
bytes, compile counts), each labelled with the device it ran on; they are
not benchmark metrics. The last line of stdout is the result:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

The persistent compile cache lives in $JAX_COMPILATION_CACHE_DIR when
that is set, else in <checkout>/.jax_cache/ (`enable_compile_cache`).
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

# no JAX at module level: the encoder's spawn workers re-import this file
sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

N_READS = 1_000_000               # ~223 MB of 100 bp platinum reads
SEED = 0
BLOCK_SIZE = 65536
CACHE_BLOCKS = 256
STREAM_BUDGET = 32 << 20          # bytes; far below the ~223 MB corpus
N_IDS, N_REGIONS, N_RANGES = 256, 16, 16
SERVE_REQUESTS, SERVE_CONCURRENCY = 48, 8   # per tenant


class SmokeFailure(AssertionError):
    """A phase produced output that differs from the host reference."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


class CompileCounter:
    """Executables built by JAX (compiled or loaded from the persistent
    cache), read from `jax.monitoring` events."""

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


class HostReference:
    """Record boundaries and names of a FASTQ corpus from one newline scan
    (4 lines per record; the name is the header up to its first space)."""

    def __init__(self, corpus: bytes):
        self.corpus = corpus
        nl = np.flatnonzero(np.frombuffer(corpus, np.uint8) == ord("\n"))
        check(nl.size % 4 == 0 and nl.size > 0, "corpus is not whole records")
        self.starts = np.concatenate([[0], nl[3::4] + 1]).astype(np.int64)
        self.header_end = nl[0::4]
        self.n_reads = nl.size // 4

    def record(self, i: int) -> bytes:
        return self.corpus[self.starts[i]:self.starts[i + 1]]

    def name(self, i: int) -> bytes:
        return self.corpus[self.starts[i] + 1:self.header_end[i]].split(
            b" ")[0]


def _rows_match(rows, lens, want) -> bool:
    rows, lens = np.asarray(rows), np.asarray(lens)
    return len(want) == len(lens) and all(
        rows[i, :int(lens[i])].tobytes() == w for i, w in enumerate(want))


def _timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def build(n_reads: int, seed: int, log, cache_blocks: int = CACHE_BLOCKS):
    from repro.api import GenomicArchive
    from repro.data.fastq import make_fastq
    corpus, t_gen = _timed(lambda: make_fastq(
        "platinum", n_reads=n_reads, seed=seed))
    ga, t_build = _timed(lambda: GenomicArchive.from_bytes(
        corpus, block_size=BLOCK_SIZE, mode="ra", entropy="rans",
        cache_blocks=cache_blocks))
    st = ga.stats()
    log(f"build: {n_reads} reads, {len(corpus)} raw bytes, {st.n_blocks} "
        f"blocks of {BLOCK_SIZE}; {st.compressed_device_bytes} bytes "
        f"resident on device, ratio {len(corpus) / st.compressed_device_bytes:.4f}"
        f"; generate {t_gen:.2f}s, encode+index+upload {t_build:.2f}s")
    return corpus, ga


def phase_decode_all(ga, ref: HostReference, log) -> None:
    dec = ga.store.decoder
    for label in ("cold", "warm"):
        out, t = _timed(lambda: dec.decode_all(verify=True))
        check(out.tobytes() == ref.corpus,
              "decode_all(verify=True) differs from the corpus")
        log(f"decode_all(verify=True) {label}: byte-identical, {t:.3f}s, "
            f"{len(ref.corpus) / t / 1e9:.4f} GB/s (compile included when "
            f"cold)")


def query_cases(ref: HostReference, seed: int) -> list:
    """(label, addresses, expected payloads) for read ids, samtools-style
    named regions (1-based inclusive) and absolute byte ranges."""
    from repro.api import ByteRange, ReadId
    rng = np.random.default_rng(seed)
    ids = rng.choice(ref.n_reads, N_IDS, replace=False)
    cases = [("read ids", [ReadId(int(i)) for i in ids],
              [ref.record(int(i)) for i in ids])]
    regions, want = [], []
    for i in rng.choice(ref.n_reads, N_REGIONS, replace=False):
        rec = ref.record(int(i))
        a = int(rng.integers(1, len(rec) // 2))
        b = int(rng.integers(a, len(rec) + 1))
        regions.append(f"{ref.name(int(i)).decode()}:{a}-{b}")
        want.append(rec[a - 1:b])
    cases.append(("named regions", regions, want))
    raw = len(ref.corpus)
    span = rng.integers(1, min(raw, 3 * BLOCK_SIZE), N_RANGES)
    lo = rng.integers(0, raw - span + 1)
    cases.append(("byte ranges",
                  [ByteRange(int(a), int(a + n)) for a, n in zip(lo, span)],
                  [ref.corpus[a:a + n] for a, n in zip(lo, span)]))
    return cases


def phase_queries(ga, ref: HostReference, counter: CompileCounter, seed: int,
                  log) -> None:
    """The batched queries twice, each pass from an empty block cache: the
    second pass repeats the first's shapes and must compile nothing."""
    import jax
    cases = query_cases(ref, seed)
    compiled = []
    for p in (1, 2):
        ga.clear_cache()
        c0 = counter.compiles
        for label, addrs, want in cases:
            (rows, lens), t = _timed(
                lambda: jax.block_until_ready(ga.query(addrs)))
            check(_rows_match(rows, lens, want),
                  f"query pass {p}: {label} differ from the host reference")
            log(f"query pass {p}: {len(addrs)} {label} byte-identical, "
                f"{t * 1e3:.1f} ms")
        compiled.append(counter.compiles - c0)
    log(f"query compiles: pass 1 {compiled[0]}, pass 2 {compiled[1]}")
    check(compiled[1] == 0,
          f"repeat query pass compiled {compiled[1]} executables")


def phase_stream(ga, ref: HostReference, log) -> None:
    from repro.api import ByteRange
    raw = len(ref.corpus)
    t0 = time.perf_counter()
    pos = n_chunks = 0
    for chunk in ga.stream([ByteRange(0, raw)],
                           max_resident_bytes=STREAM_BUDGET):
        check(chunk.tobytes() == ref.corpus[pos:pos + chunk.size],
              f"stream chunk {n_chunks} at byte {pos} differs")
        pos += chunk.size
        n_chunks += 1
    t = time.perf_counter() - t0
    check(pos == raw, f"stream yielded {pos} of {raw} bytes")
    log(f"stream: {raw} bytes under a {STREAM_BUDGET}-byte budget in "
        f"{n_chunks} chunks, byte-identical, {t:.3f}s, "
        f"{raw / t / 1e9:.4f} GB/s (compile included)")


def serve_pass(ga, ref: HostReference, seed: int) -> int:
    """Closed loop through ServingFrontend: tenant "points" sends Zipfian
    read ids, tenant "regions" Zipfian named regions; every answer is
    checked. Returns the number of requests served."""
    from repro.serving.frontend import Overloaded, ServingFrontend
    from repro.serving.traffic import ZipfianSampler
    fe = ServingFrontend({"corpus": ga}, max_batch=32)
    fe.register_tenant("points", "corpus", priority=0)
    fe.register_tenant("regions", "corpus", priority=1)
    samplers = {"points": ZipfianSampler(ref.n_reads, seed=seed + 1),
                "regions": ZipfianSampler(ref.n_reads, seed=seed + 2)}

    def address(tenant: str, rid: int):
        if tenant == "points":
            return rid, ref.record(rid)
        rec = ref.record(rid)
        return f"{ref.name(rid).decode()}:2-{len(rec) - 1}", rec[1:-1]

    issued = {t: 0 for t in samplers}
    open_ = {}
    served = 0
    while any(n < SERVE_REQUESTS for n in issued.values()) or open_:
        for tenant, sampler in samplers.items():
            live = sum(1 for t, _ in open_.values() if t == tenant)
            need = min(SERVE_REQUESTS - issued[tenant],
                       SERVE_CONCURRENCY - live)
            for rid in (sampler.draw(need) if need > 0 else ()):
                addr, want = address(tenant, rid)
                ticket = fe.submit(tenant, addr)
                check(not isinstance(ticket, Overloaded),
                      f"{tenant} request rejected: {ticket}")
                issued[tenant] += 1
                open_[ticket.seq] = (tenant, want)
        fe.step()
        for seq, res in fe.take_results().items():
            tenant, want = open_.pop(seq)
            check(res.status == "ok", f"{tenant} request {seq}: {res.status}")
            check(res.payload.tobytes() == want,
                  f"{tenant} request {seq} differs from the host reference")
            served += 1
    return served


def phase_serving(ga, ref: HostReference, counter: CompileCounter, seed: int,
                  log) -> None:
    compiled = []
    for p in (1, 2):
        ga.clear_cache()
        c0, h0 = counter.compiles, ga.cache_info()["hits"]
        served, t = _timed(lambda: serve_pass(ga, ref, seed))
        hits = ga.cache_info()["hits"] - h0
        compiled.append(counter.compiles - c0)
        log(f"serving pass {p}: {served} requests from 2 tenants "
            f"byte-identical, {hits} block-cache hits, {t:.3f}s")
        check(hits > 0, "serving closed loop saw no block-cache hits")
    log(f"serving compiles: pass 1 {compiled[0]}, pass 2 {compiled[1]}")
    check(compiled[1] == 0,
          f"repeat serving pass compiled {compiled[1]} executables")


def run_one_chip(n_reads: int, seed: int, log) -> None:
    counter = CompileCounter()
    corpus, ga = build(n_reads, seed, log)
    ref = HostReference(corpus)
    check(ref.n_reads == n_reads == ga.n_reads, "record count mismatch")
    for name, phase in (
            ("decode_all", lambda: phase_decode_all(ga, ref, log)),
            ("queries", lambda: phase_queries(ga, ref, counter, seed, log)),
            ("stream", lambda: phase_stream(ga, ref, log)),
            ("serving", lambda: phase_serving(ga, ref, counter, seed, log))):
        _, t = _timed(phase)
        log(f"phase {name}: pass, {t:.3f}s")
    log(f"compiles in this run: {counter.compiles} executables, "
        f"{counter.seconds:.2f}s, {counter.cache_hits} loaded from the "
        f"persistent cache")


def shard_bytes(arrays: dict) -> dict:
    """Bytes each device holds of a sharded pytree."""
    per = {}
    for x in arrays.values():
        for s in x.addressable_shards:
            per[s.device] = per.get(s.device, 0) + s.data.nbytes
    return per


def run_four_chips(n_reads: int, seed: int, log) -> None:
    """Mesh-partitioned residency over four devices, compared with the
    single-device decode of the same archive."""
    from repro.api.executors import ShardedExecutor
    from repro.compat import make_mesh
    from repro.core.sharded_decode import (partition_archive,
                                           partitioned_decode_blocks)
    n_dev = 4
    corpus, ga = build(n_reads, seed, log, cache_blocks=0)
    ref = HostReference(corpus)
    dec = ga.store.decoder
    single, t = _timed(lambda: dec.decode_all(verify=True))
    check(single.tobytes() == corpus, "single-device decode differs")
    log(f"single-device decode_all(verify=True): byte-identical, {t:.3f}s")

    mesh = make_mesh((n_dev,), ("data",))
    part = partition_archive(dec, mesh)
    total = dec.da.device_bytes
    per = shard_bytes(part.arrays)
    log(f"partition: {total} compressed bytes; per device "
        + ", ".join(f"{d.id}: {b}" for d, b in sorted(
            per.items(), key=lambda kv: kv[0].id)))
    check(len(per) == n_dev, f"partition landed on {len(per)} devices")
    for d, b in per.items():
        check(0.9 * total / n_dev <= b <= 1.1 * total / n_dev,
              f"device {d.id} holds {b} of {total} bytes, not about 1/4")

    sel = np.arange(dec.da.n_blocks)
    for label in ("cold", "warm"):
        rows, t = _timed(lambda: np.asarray(partitioned_decode_blocks(
            dec, part, sel, verify=True)))
        out = np.concatenate([rows[i, :int(n)] for i, n in
                              enumerate(dec.archive.block_len)])
        check(out.tobytes() == single.tobytes(),
              "partitioned decode differs from the single-device decode")
        log(f"partitioned decode over {n_dev} devices {label}: "
            f"byte-identical, {t:.3f}s")

    sx = ShardedExecutor(ga.store, mesh, cache_blocks=64)
    cases = query_cases(ref, seed)
    for p in (1, 2):
        for label, addrs, want in cases:
            rows, lens = sx.run(ga.plan(addrs))
            check(_rows_match(rows, lens, want),
                  f"ShardedExecutor pass {p}: {label} differ")
    hits = sx.cache_info()["hits"]
    log(f"ShardedExecutor cached re-read: {sum(len(c[1]) for c in cases)} "
        f"addresses x 2 passes byte-identical, {hits} per-shard cache hits")
    check(hits > 0, "ShardedExecutor re-read saw no cache hits")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the mesh-partitioned path on 4 chips")
    args = ap.parse_args(argv)

    from repro.launch.hygiene import apply_process_hygiene, enable_compile_cache
    apply_process_hygiene()
    cache_dir = enable_compile_cache()
    import jax
    devices = jax.devices()
    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices)}
    label = f"[{d0.platform} {d0.device_kind} x{len(devices)}]"

    def log(msg: str) -> None:
        print(f"{label} {msg}", flush=True)

    log(f"devices: {devices}; jax {jax.__version__}; compile cache "
        f"{cache_dir}")
    if d0.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX platform {d0.platform!r}); there is "
              f"no CPU fallback", file=sys.stderr)
        return 2
    if args.four_chips:
        if len(devices) < 4:
            print(f"chip_smoke --four-chips: {len(devices)} TPU devices, "
                  f"need 4", file=sys.stderr)
            return 2
        run_four_chips(N_READS, SEED, log)
    else:
        run_one_chip(N_READS, SEED, log)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
