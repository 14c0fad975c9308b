"""Mesh-sharded archives: decode throughput and bytes-resident-per-shard
vs mesh width (report-only shard/* rows).

Runs in this process, on the devices that are present: a process that
has touched JAX holds the chip, so a child started after it could not use
it. The widths are the powers of two up to `len(jax.devices())`. On CPU,
force host devices before Python starts
(`XLA_FLAGS=--xla_force_host_platform_device_count=8`).

    shard/decode_partitioned/wN — full-archive decode, blocks partitioned
        over N shards; derived carries per_shard=/total= resident bytes
        (per-shard compressed residency ~ total/N)
    shard/decode_replicated/wN  — the replicated-work fast path at the
        widest width (per_shard == total: every device holds the archive)
    shard/cached_reread/wN      — repeated Zipfian selection through
        ShardedExecutor's per-shard block cache at the widest width;
        derived carries hit=
"""
import time

import numpy as np

from benchmarks.common import row


def main(small: bool = False) -> None:
    import jax
    from jax.sharding import Mesh
    from repro.api.executors import ShardedExecutor
    from repro.api.plan import QueryPlanner
    from repro.core import encoder
    from repro.core.residency import CompressedResidentStore
    from repro.core.sharded_decode import (partition_archive,
                                           partitioned_decode_blocks,
                                           replicate_archive,
                                           sharded_decode_blocks)
    from repro.data.fastq import make_fastq

    devices = jax.devices()
    widths = [1 << k for k in range(len(devices).bit_length())]
    data = make_fastq("platinum", n_reads=1500 if small else 6000, seed=1)
    a = encoder.encode(data, block_size=4096)
    s = CompressedResidentStore(a, backend="auto")
    dec = s.decoder
    total = sum(np.asarray(v).nbytes for v in dec.arrays.values())
    sel = np.arange(a.n_blocks)
    reps = 3 if small else 5

    def best(fn):
        b = float("inf")
        for i in range(reps + 1):
            t0 = time.perf_counter()
            jax.block_until_ready(fn())
            if i:                                   # first pass compiles
                b = min(b, time.perf_counter() - t0)
        return b

    for w in widths:
        if a.n_blocks < w:
            continue
        mesh = Mesh(np.array(devices[:w]), ("data",))
        part = partition_archive(dec, mesh)
        t = best(lambda: partitioned_decode_blocks(dec, part, sel))
        row(f"shard/decode_partitioned/w{w}", t,
            f"GB_s={len(data) / t / 1e9:.3f};"
            f"per_shard={part.per_shard_device_bytes};total={total};"
            f"shards={w}")

    w = widths[-1]
    mesh = Mesh(np.array(devices[:w]), ("data",))
    replicate_archive(dec, mesh)
    t = best(lambda: sharded_decode_blocks(dec, sel, mesh))
    row(f"shard/decode_replicated/w{w}", t,
        f"GB_s={len(data) / t / 1e9:.3f};per_shard={total};total={total};"
        f"shards={w}")

    # cached Zipfian re-read through the per-shard block cache
    s2 = CompressedResidentStore(a, backend="auto")
    sx = ShardedExecutor(s2, mesh, cache_blocks=max(4, a.n_blocks // 4))
    planner = QueryPlanner(s2)
    rng = np.random.default_rng(0)
    bs = a.block_size
    zipf = np.minimum(rng.zipf(1.3, size=64), a.n_blocks - 1)
    spans = np.minimum(np.full(zipf.size, bs), len(data) - zipf * bs)
    plan = planner.plan_spans(zipf * bs, spans)
    jax.block_until_ready(sx.run(plan)[0])          # cold pass installs
    b = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(sx.run(plan)[0])
        b = min(b, time.perf_counter() - t0)
    ci = sx.cache_info()
    hit = ci["hits"] / max(1, ci["hits"] + ci["misses"])
    row(f"shard/cached_reread/w{w}", b,
        f"hit={hit:.2f};per_shard={s2.sharded.per_shard_bytes()};"
        f"shards={w}")
