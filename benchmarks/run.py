"""Benchmark driver — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows. All wall-clock numbers are
THIS container's CPU-device numbers (labeled `cpu`); TPU v5e performance is
projected by the roofline report (EXPERIMENTS.md §Roofline), never faked.

  python -m benchmarks.run [--small] [--only mode2,ratio,...] [--json out]

``--json out.json`` additionally writes a machine-readable snapshot
(every row + run metadata) — the input of `scripts/bench_compare.py`,
which gates CI on regressions against the committed `BENCH_baseline.json`.
"""
import argparse
import json
import platform
import sys
import time
import traceback

import numpy as np

from benchmarks import common


def calibrate_us(iters: int = 5) -> float:
    """Best-of-N wall time of a fixed, seeded reference workload (BLAS
    matmul + memory-bound sort) in µs. Snapshots carry it in meta so
    `bench_compare.py` can normalize away machine-speed drift between the
    baseline runner and the current one: a genuinely slower machine slows
    the reference by the same factor as the benchmarks, a code regression
    slows only the benchmarks."""
    rng = np.random.default_rng(0)
    a = rng.random((384, 384))
    v = rng.integers(0, 1 << 30, size=2_000_000, dtype=np.int64)
    best = float("inf")
    for i in range(iters + 1):
        t0 = time.perf_counter()
        (a @ a).sum()
        np.sort(v, kind="stable")
        if i > 0:                       # first pass is warmup
            best = min(best, time.perf_counter() - t0)
    return best * 1e6

_TABLES = [
    ("mode1", "benchmarks.bench_mode1", "Table 1: Mode 1 host-to-host"),
    ("mode2", "benchmarks.bench_mode2", "Table 2: Mode 2 device-resident"),
    ("random_access", "benchmarks.bench_random_access",
     "Table 3: seek vs full decode"),
    ("index", "benchmarks.bench_index", "§4.1: read index vs .fai"),
    ("fetch_batch", "benchmarks.bench_fetch_batch",
     "serving: batched variable-length random access"),
    ("cache", "benchmarks.bench_cache",
     "serving: device-resident block cache (Zipfian working set)"),
    ("serving", "benchmarks.bench_serving",
     "serving: multi-tenant frontend (closed-loop latency/admission)"),
    ("query", "benchmarks.bench_query",
     "api: unified query plane (plan lowering + region latency)"),
    ("scale", "benchmarks.bench_scale", "§5: range decode / memory budget"),
    ("sharded", "benchmarks.bench_sharded",
     "beyond-paper: mesh-partitioned residency vs width (devices present)"),
    ("e2e", "benchmarks.bench_e2e", "§6.1: host-link ceiling"),
    ("ratio", "benchmarks.bench_ratio", "§6.2: ratio + stream separation"),
    ("entropy", "benchmarks.bench_entropy", "§6.4: open entropy stage"),
    ("blocksize", "benchmarks.bench_blocksize", "§2.1: block-size sweep"),
    ("tune", "benchmarks.bench_tune",
     "autotuner: encode-knob sweep cost + Pareto frontier"),
    ("train", "benchmarks.bench_train",
     "training data plane: sync vs async-prefetch tokens/s"),
    ("resilience", "benchmarks.bench_resilience",
     "robustness: parity recovery latency + storage cost"),
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--small", action="store_true",
                    help="reduced corpora (CI-speed)")
    ap.add_argument("--only", default=None)
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write a machine-readable snapshot of every row "
                         "(for scripts/bench_compare.py gating)")
    args = ap.parse_args()
    only = set(args.only.split(",")) if args.only else None

    print("name,us_per_call,derived")
    common.reset_rows()
    calib0 = calibrate_us() if args.json else None
    failures = []
    for key, mod_name, desc in _TABLES:
        if only and key not in only:
            continue
        print(f"# --- {desc} ({mod_name}) ---", flush=True)
        t0 = time.time()
        try:
            mod = __import__(mod_name, fromlist=["main"])
            mod.main(small=args.small)
        except Exception:                                  # noqa: BLE001
            traceback.print_exc()
            failures.append(key)
        print(f"# {key} done in {time.time()-t0:.1f}s", flush=True)
    if args.json:
        # bracket the run: best machine speed observed (matches the
        # best-of-N the rows themselves record)
        calib = min(calib0, calibrate_us())
        print(f"# calib/reference: {calib:.1f}us")
        snap = {
            "meta": {
                "small": args.small,
                "only": sorted(only) if only else None,
                "platform": platform.platform(),
                "python": platform.python_version(),
                "failures": failures,
                "calib_us": round(calib, 1),
            },
            "rows": common.ROWS,
        }
        with open(args.json, "w") as f:
            json.dump(snap, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"# snapshot: {len(common.ROWS)} rows -> {args.json}")
    if failures:
        print(f"# FAILURES: {failures}")
        sys.exit(1)
    print("# all benchmarks complete")


if __name__ == "__main__":
    main()
