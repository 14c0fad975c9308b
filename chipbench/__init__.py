"""Chip benchmark of the compressed-resident genomics system.

One run measures one cell of `BENCHMARK.json` on the accelerator:

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything one configuration, traffic mix or per-layer metric needs sits
in a file of its own (`configs/<config>.json`, `traffic/<mix>.json`,
`metrics/<metric>.py`), found by the names in `BENCHMARK.json`.
"""
