#!/usr/bin/env python3
"""The control of `correct`, and the faults it has to catch.

The configurations state no precision; the guarantee they state is that
reads are bit-exact. The control breaks it the way a later change would
be tempted to: the match stage resolves the pointer-doubling rounds of
the next lower power-of-two depth bucket instead of those the archive
records for the blocks it decodes (`one_bucket_short`: 7 rounds become
4, 9 become 8). One round short also breaks the guarantee, but only at
the ends of the deepest chains, too rarely for a window of point reads
to meet on every seed. The other faults break the timed path where an
answer is produced (`altered_answer`: every decoded byte flipped) or lose
half of the answers (`dropped_half`: the cell's driver loses them its own
way, as its `drop_half` says). Each is planted after warm-up, just
before the window, on every decoder the driver names.

    python3 chipbench/control.py --workload <cell> --seconds <s> --seeds 1 2 3 \
        [--faults one_bucket_short] [--program]

prints, for each seed, the numbers compared for `correct` with and
without the fault. Benchmark runs never plant one.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
for p in (HERE.parent / "src", HERE.parent):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))


def bucket_below(rounds: int) -> int:
    """The largest depth of the power-of-two bucket below `rounds`'s."""
    return 1 << max(0, (int(rounds) - 1).bit_length() - 1) if rounds > 1 \
        else 0


def _shorten(dec) -> None:
    meta = dec._meta

    def short_meta(n_sel, total=None, n_rounds=-1):
        m = meta(n_sel, total, n_rounds)
        return m if m[-1] is None else m[:-1] + (bucket_below(m[-1]),)

    dec._meta = short_meta


def _flip(dec) -> None:
    decode = dec.decode_blocks

    def flipped(*args, **kwargs):
        return decode(*args, **kwargs) ^ 1

    dec.decode_blocks = flipped


def one_bucket_short(state) -> None:
    for dec in state.decoders():
        _shorten(dec)


def altered_answer(state) -> None:
    for dec in state.decoders():
        _flip(dec)


def dropped_half(state) -> None:
    state.drop_half()


FAULTS = {"one_bucket_short": one_bucket_short,
          "altered_answer": altered_answer,
          "dropped_half": dropped_half}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--faults", nargs="*", default=["one_bucket_short"],
                    choices=sorted(FAULTS))
    ap.add_argument("--program", action="store_true",
                    help="also run each seed with no fault planted")
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(HERE / ".jax_cache")
    from repro.launch.hygiene import apply_process_hygiene, enable_compile_cache
    apply_process_hygiene()
    enable_compile_cache()
    from chipbench import harness
    cell = harness.load_cell(args.workload)
    runs = ([None] if args.program else []) + args.faults
    for seed in args.seeds:
        for fault in runs:
            hooks = harness.Hooks(plant=FAULTS[fault] if fault else None)
            t = time.perf_counter()
            try:
                r = harness.run_cell(cell, seed, args.seconds, False, hooks)
            except harness.NoChip as e:
                print(f"chipbench control: {e}", file=sys.stderr)
                return 2
            print(json.dumps({"seed": seed, "fault": fault or "none",
                              "correct": r["correct"],
                              "attempted": r["attempted"],
                              "failed": r["failed"], "checks": r["checks"],
                              "metrics": r["metrics"],
                              "run_s": time.perf_counter() - t}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
