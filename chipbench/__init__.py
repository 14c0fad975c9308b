"""Chip benchmark of the compressed-resident genomics system.

One run measures one cell of `BENCHMARK.json` on the accelerator:

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything one deployment, traffic mix or per-layer metric needs sits in
files of its own, found by the names in `BENCHMARK.json` (see
`harness`): its configuration (`configs/<config>.json`), corpus module
(`corpora/<corpus>.py`), traffic mix (`traffic/<mix>.json`), request
driver (`drivers/<driver>.py`), per-layer readers (`metrics/<metric>.py`)
and tiny CPU size (`tests/tiny/<cell>.json`).
"""
