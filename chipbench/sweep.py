#!/usr/bin/env python3
"""Sweep of offered rates for an open-loop cell, on one set-up.

    python3 chipbench/sweep.py --workload ra16k.zipf_open --seed 7 \
        --seconds 15 --rates 4 8 16 32 64 128

For each rate, one window of that many seconds: the answered share, p50,
p95 and p99 latency from the due time, and the p95 of the window's first
and last quarter of requests. The rate is `sustained` when every
request was answered and neither quarter queued (see `sustained`); give
the rates from low to high, the first well below capacity. The sweep
stops at the first rate that is not sustained. The highest sustained
rate is the cell's capacity; its traffic file offers 0.8 of it. Every
answer is checked against the host reference.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
for p in (HERE.parent / "src", HERE.parent):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))


def summarize(rec, checks: dict, failed: int) -> dict:
    """One rate's row, from an open loop's record (`loadgen.OpenLoopRecord`)
    and the cell's correctness check of it (`Driver.check`)."""
    lat = (rec.done - rec.due) * 1e3
    n = lat.size
    q = max(1, n // 4)
    ok = np.asarray([s == "ok" for s in rec.status])
    lat_ok = lat[ok & ~np.isnan(lat)]

    def p(x, k):
        return float(np.percentile(x, k)) if x.size else float("nan")

    return {"offered_per_s": n / rec.window_s, "requests": n,
            "answered": int(ok.sum()), "steps": rec.steps,
            "p50_ms": p(lat_ok, 50), "p95_ms": p(lat_ok, 95),
            "p99_ms": p(lat_ok, 99),
            "p95_first_quarter_ms": p(lat[:q][ok[:q]], 95),
            "p95_last_quarter_ms": p(lat[-q:][ok[-q:]], 95),
            "failed": failed,
            "checks": {k: v for k, (v, _) in checks.items()}}


def sustained(row: dict, base_p50_ms: float) -> bool:
    """Every request answered, and neither the first nor the last quarter
    of the window queued: each quarter's p95 at most 4 x the p50 of the
    sweep's first (lowest) rate plus 50 ms. A backlog that builds and
    drains inside the window fails too, not only one that grows to its
    end."""
    limit = 4 * base_p50_ms + 50
    return bool(row["answered"] == row["requests"]
                and row["p95_first_quarter_ms"] <= limit
                and row["p95_last_quarter_ms"] <= limit)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    from chipbench import harness
    cell = harness.load_cell(args.workload)
    if harness.driver_class(cell).loop != "open":
        print("sweep: the cell's driver has no open loop", file=sys.stderr)
        return 2
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(HERE / ".jax_cache")
    from repro.launch.hygiene import apply_process_hygiene, enable_compile_cache
    apply_process_hygiene()
    enable_compile_cache()
    try:
        s = harness.open_session(cell, args.seed)
    except harness.NoChip as e:
        print(f"sweep: {e}", file=sys.stderr)
        return 2
    base = None
    for rate in args.rates:
        s.state.mix["rate_per_s"] = rate
        compiles = s.counter.compiles
        rec = s.state.window(args.seconds)
        checks = s.state.check(rec)
        row = summarize(rec, checks, s.state.failed(rec, checks))
        row["rate_per_s"] = rate
        row["compiles_in_window"] = s.counter.compiles - compiles
        base = row["p50_ms"] if base is None else base
        row["sustained"] = sustained(row, base)
        print(json.dumps(row), flush=True)
        if not row["sustained"]:
            break
    return 0


if __name__ == "__main__":
    sys.exit(main())
