"""A cell of the benchmark at a size the CPU runs in seconds, steered
through `harness.Hooks` (no command-line option of the benchmark). Each
cell's tiny size is `tests/tiny/<cell>.json`: the keys of its
configuration (`config`) and of its traffic mix (`traffic`) that it
changes."""
from __future__ import annotations

import json
from pathlib import Path

from chipbench import harness


def cells(bench: Path = harness.BENCHMARK) -> list:
    """Every cell of the benchmark, by name."""
    return [w["name"] for w in json.loads(bench.read_text())["workloads"]]


def sizes(cell: str, bench: Path = harness.BENCHMARK) -> dict:
    path = bench.parent / harness.HERE.name / "tests" / "tiny" / f"{cell}.json"
    return json.loads(path.read_text())


def hooks(cell: str, tmp: Path, plant=None,
          bench: Path = harness.BENCHMARK) -> harness.Hooks:
    tiny = sizes(cell, bench)
    return harness.Hooks(require_tpu=False,
                         peaks={"cpu": {"hbm_bytes_per_s": 1e10}},
                         config_overrides=tiny["config"],
                         mix_overrides=tiny["traffic"],
                         archive_dir=tmp / "archives",
                         trace_dir=tmp / "trace", plant=plant)


def run(cell: str, tmp: Path, seed: int = 3, seconds: float = 2.0,
        trace: bool = False, plant=None,
        bench: Path = harness.BENCHMARK) -> dict:
    return harness.run_cell(harness.load_cell(cell, bench), seed, seconds,
                            trace, hooks(cell, tmp, plant, bench))
