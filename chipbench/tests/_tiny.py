"""A cell of the benchmark at a size the CPU runs in seconds, steered
through `harness.Hooks` (no command-line option of the benchmark)."""
from __future__ import annotations

from pathlib import Path

from chipbench import harness

# point reads: 10,000 reads in 16 KiB blocks (136 blocks, one depth bucket)
POINT = {"n_reads": 10_000, "cache_blocks": 8, "max_batch": 4}
# streaming: the same corpus in 16 KiB blocks, 8-block chunks
RANGE = {"n_reads": 10_000, "block_size": 16384,
         "max_resident_bytes": 8 * 16384 * 2}
OVERRIDES = {"ra16k.zipf_open": (POINT, {"rate_per_s": 20.0,
                                          "warm_requests": 64}),
             "ra1m.range_stream": (RANGE, {})}


def hooks(cell: str, tmp: Path, plant=None) -> harness.Hooks:
    config, mix = OVERRIDES[cell]
    return harness.Hooks(require_tpu=False,
                         peaks={"cpu": {"hbm_bytes_per_s": 1e10}},
                         config_overrides=config, mix_overrides=mix,
                         archive_dir=tmp / "archives",
                         trace_dir=tmp / "trace", plant=plant)


def run(cell: str, tmp: Path, seed: int = 3, seconds: float = 2.0,
        trace: bool = False, plant=None) -> dict:
    return harness.run_cell(harness.load_cell(cell), seed, seconds, trace,
                            hooks(cell, tmp, plant))
