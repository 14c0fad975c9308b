#!/usr/bin/env bash
# One-command tier-1 reproduction + CI lanes (ROADMAP.md "Tier-1 verify").
#
#   scripts/ci.sh               # compileall + FULL suite + bench gate
#   scripts/ci.sh --fast        # fast lane: skips @pytest.mark.slow
#   scripts/ci.sh --no-bench    # tests only (no bench smoke / gate)
#   scripts/ci.sh --bench-only  # bench smoke + regression gate only
#   scripts/ci.sh -k codec      # any extra pytest args pass through
#
# Works fully offline: when `hypothesis` is absent the property tests run
# through tests/_hypothesis_compat.py instead of failing collection.
#
# The bench gate runs the --small smoke set with a JSON snapshot and
# fails on throughput regression against the committed BENCH_baseline.json
# (>25% for stable rows; rows the baseline observed to be noisy gate at
# their recorded spread x1.5 — see scripts/bench_compare.py). Refresh
# deliberate perf changes with
# `python scripts/bench_compare.py --merge BENCH_baseline.json run*.json`.
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

FAST=0 BENCH=1 TESTS=1
ARGS=()
for a in "$@"; do
  case "$a" in
    --fast) FAST=1 ;;
    --no-bench) BENCH=0 ;;
    --bench-only) TESTS=0 ;;
    *) ARGS+=("$a") ;;
  esac
done

python -m compileall -q src benchmarks scripts

if [ "$TESTS" = 1 ]; then
  if [ "$FAST" = 1 ]; then
    python -m pytest -x -q -m "not slow" ${ARGS[@]+"${ARGS[@]}"}
  else
    python -m pytest -x -q ${ARGS[@]+"${ARGS[@]}"}
  fi
  # end-to-end train smoke (fast tier too): tiny model, 4 steps through
  # the full launcher — encode once + save the archive, async prefetch
  # on, scan-unrolled windows, checkpoint written. Exercises the whole
  # compressed-resident data plane the way a user invokes it.
  TRAIN_TMP=$(mktemp -d)
  python -m repro.launch.train --arch qwen2-1.5b --reduced --steps 4 \
    --batch 2 --seq 32 --reads 300 --block 4096 --prefetch 2 --unroll 2 \
    --archive "$TRAIN_TMP/corpus.acegad" --ckpt-every 4 \
    --ckpt-dir "$TRAIN_TMP/ckpt"
  rm -rf "$TRAIN_TMP"
fi

if [ "$BENCH" = 1 ]; then
  # serving-plane smoke: one closed loop through ServingFrontend with a
  # bit-identity spot check on every request (asserts 0 deadline misses)
  python -m repro.serving.traffic --smoke
  # chaos smoke: every fault scenario (payload flips, double-corruption
  # partial serving, transient launches, prefetch-worker crash, shard
  # loss on 8 forced host devices) through the full detect → recover →
  # degrade loop; output must be bit-perfect or a typed error — the
  # harness exits nonzero on the first silently-wrong byte
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python -m repro.resilience.chaos --smoke
  # sharded smoke: mesh-partitioned residency on 8 forced host devices —
  # partitioned decode bit-identical to the raw corpus, then a cached
  # re-read through the per-shard block cache must report hits (the flag
  # is scoped to this one subprocess; setting it in-process is forbidden)
  XLA_FLAGS=--xla_force_host_platform_device_count=8 python - <<'EOF'
import numpy as np, jax
from repro.data.fastq import make_fastq
from repro.core import encoder
from repro.core.residency import CompressedResidentStore
from repro.core.sharded_decode import (partition_archive,
                                       partitioned_decode_blocks)
from repro.api.executors import ShardedExecutor
from repro.api.plan import QueryPlanner
from repro.compat import make_mesh
data = make_fastq("platinum", n_reads=400, seed=3)
a = encoder.encode(data, block_size=4096)
s = CompressedResidentStore(a, backend="auto")
mesh = make_mesh((8,), ("data",))
part = partition_archive(s.decoder, mesh)
rows = np.asarray(partitioned_decode_blocks(s.decoder, part,
                                            np.arange(a.n_blocks)))
assert rows.reshape(-1)[:len(data)].tobytes() == data, "partition mismatch"
assert part.per_shard_device_bytes * 8 < 2 * sum(
    np.asarray(v).nbytes for v in s.decoder.arrays.values()) + 8 * 4096
sx = ShardedExecutor(s, mesh, cache_blocks=8)
plan = QueryPlanner(s).plan_spans(np.array([0]),
                                  np.array([min(len(data), 32768)]))
sx.run(plan); sx.run(plan)
assert sx.cache_info()["hits"] > 0, "sharded cache reported no hits"
print("sharded smoke OK:", sx.cache_info()["hits"], "hits,",
      part.per_shard_device_bytes, "B/shard")
EOF
  # bench smoke: index/fetch/query planes, the block-size sweep (the
  # regime that exposed the u16 offset truncation), the block cache,
  # random access incl. the checkpointed-wavefront seek, a --small
  # autotuner sweep (tune/sweep, tune/frontier_points), and the
  # multi-tenant serving plane (serve/* rows: closed-loop percentiles,
  # the TinyLFU-vs-admit_after drift duel, flash-crowd backpressure —
  # bench_compare prints deadline-miss and per-tenant hit rates next to
  # each serve/* row). The random_access table exercises BOTH resolver
  # paths every run: the depth-bounded decode of a fresh ACEJAX04
  # archive (ra/full_decode, ra/decode_GBps — asserted bit-identical)
  # and the legacy depth-free early-exit decode (ra/legacy_early_exit),
  # plus the depth-bucketed schedule (ra/depth_bucketed_GBps);
  # bench_compare prints each ra/* row's recorded max_depth and bucket
  # histogram next to its time.
  # (train/* rows assert a bit-identical loss trajectory sync-vs-prefetch
  # and carry the measured speedup in their derived field;
  # resil/* rows are report-only: parity storage cost and one-block
  # parity-reconstruction latency, with the reconstructed/quarantined
  # counters printed next to each row)
  python -m benchmarks.run --small \
    --only index,fetch_batch,query,blocksize,cache,random_access,tune,serving,train,resilience \
    --json bench_current.json
  # the sharded table runs in its own process on 8 forced host devices
  # (mesh widths 1-8); its report-only shard/* rows, with the per-shard
  # resident bytes bench_compare prints next to each, join the snapshot
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python -m benchmarks.run --small --only sharded --json bench_sharded.json
  python - <<'EOF'
import json
cur = json.load(open("bench_current.json"))
cur["rows"] += json.load(open("bench_sharded.json"))["rows"]
with open("bench_current.json", "w") as f:
    json.dump(cur, f, indent=2, sort_keys=True)
    f.write("\n")
EOF
  python scripts/bench_compare.py BENCH_baseline.json bench_current.json
fi
