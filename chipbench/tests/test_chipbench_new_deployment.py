"""A deployment is added as new files alone: a corpus module, a request
driver, a configuration, a traffic mix, a per-layer reader, a tiny size
and entries in `BENCHMARK.json`, written into a copy of the benchmark
and run through `harness.run_cell`, with no file the copy had changed."""
import json
import shutil
from pathlib import Path

import pytest

import _tiny
from chipbench import control

ROOT = Path(__file__).resolve().parents[2]
CELL = "toy.record_batches"
UNTOUCHED = ["harness.py", "archives.py", "loadgen.py", "control.py",
             "workcount.py"]

CORPUS = '''
"""Fixed-width records of bases, re-sampled from a small pool and
mutated, served by record number through an arithmetic index."""
import numpy as np


def generate(config, seed):
    rng = np.random.default_rng(seed)
    n, width = int(config["n_records"]), int(config["record_bytes"])
    pool = rng.integers(4, size=(16, width))
    rows = pool[rng.integers(16, size=n)]
    flips = rng.random(rows.shape) < 0.01
    rows[flips] = rng.integers(4, size=int(flips.sum()))
    out = np.frombuffer(b"ACGT", np.uint8)[rows]
    out[:, -1] = ord("\\n")
    return out.tobytes()


def build(corpus, config, **cache):
    from repro.api import GenomicArchive
    return GenomicArchive.from_records(
        corpus, int(config["record_bytes"]),
        block_size=int(config["block_size"]), mode=config["mode"],
        entropy=config["entropy"], **cache)


def check(ga, ref):
    if ga.n_reads != ref.n_records:
        raise RuntimeError("record counts differ")


class HostReference:
    def __init__(self, corpus):
        self.width = corpus.index(b"\\n") + 1
        self.rows = np.frombuffer(corpus, np.uint8).reshape(-1, self.width)
        self.n_records = self.rows.shape[0]
'''

DRIVER = '''
"""A closed loop of record batches through `GenomicArchive.query`."""
import time

import numpy as np

from chipbench import harness


class Driver(harness.Driver):

    def __init__(self, *args):
        super().__init__(*args)
        self.batch = int(self.mix["batch"])
        self.fetch = self._query

    def _query(self, ids):
        rows, lens = self.ga.query(ids)
        return np.asarray(rows), np.asarray(lens)

    def warm(self):
        self.fetch(np.arange(self.batch))

    def window(self, seconds, mark=None):
        answers = []
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            if mark is not None and time.perf_counter() - t0 >= mark[0]:
                mark, fn = None, mark[1]
                fn()
            ids = self.rng.integers(self.ref.n_records, size=self.batch)
            answers.append((ids, self.fetch(ids)))
        return answers, time.perf_counter() - t0

    def check(self, rec):
        answers, _ = rec
        missing = sum(got is None for _, got in answers)
        wrong = sum(got is not None and not (
            (got[1] == self.ref.width).all()
            and (got[0][:, :self.ref.width] == self.ref.rows[ids]).all())
            for ids, got in answers)
        return {"wrong_batches": (wrong, 0), "missing_batches": (missing, 0)}

    def failed(self, rec, checks):
        return checks["wrong_batches"][0] + checks["missing_batches"][0]

    def requests(self, rec):
        return len(rec[0])

    def end_to_end(self, rec):
        answers, seconds = rec
        return {"toy_records_per_s": len(answers) * self.batch / seconds}

    def describe(self, rec):
        return f"window: {len(rec[0])} batches in {rec[1]:.3f}s"

    def drop_half(self):
        calls = []

        def every_other(ids):
            calls.append(ids)
            return self._query(ids) if len(calls) % 2 else None

        self.fetch = every_other
'''

CONFIG = {"name": "toy_records", "corpus": "toy_records",
          "n_records": 4000, "record_bytes": 64, "block_size": 4096,
          "mode": "ra", "entropy": "rans", "cache_blocks": 8}
TRAFFIC = {"driver": "record_batches", "batch": 8}


@pytest.fixture(scope="module")
def bench(tmp_path_factory) -> Path:
    """A copy of the benchmark with the toy deployment added as files."""
    root = tmp_path_factory.mktemp("toy")
    shutil.copy(ROOT / "BENCHMARK.json", root)
    home = root / "chipbench"
    shutil.copytree(ROOT / "chipbench", home,
                    ignore=shutil.ignore_patterns(".*", "__pycache__"))
    before = {p: (home / p).read_bytes() for p in UNTOUCHED}
    new = {"corpora/toy_records.py": CORPUS,
           "drivers/record_batches.py": DRIVER,
           "configs/toy_records.json": json.dumps(CONFIG),
           "traffic/record_batches.json": json.dumps(TRAFFIC),
           "metrics/decode_roofline.toy.py":
               "from chipbench.layers import decode_roofline as read\n",
           f"tests/tiny/{CELL}.json": json.dumps({"config": {},
                                                  "traffic": {}})}
    for rel, text in new.items():
        assert not (home / rel).exists(), rel
        (home / rel).write_text(text)
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "toy_records", "source": "a test",
                            "file": "chipbench/configs/toy_records.json",
                            "reduced": [], "why": "a test"})
    spec["workloads"].append({"name": CELL, "config": "toy_records",
                              "traffic": "record_batches", "chips": 1,
                              "why": "a test"})
    spec["end_to_end"].insert(0, {
        "name": "toy_records_per_s", "unit": "records/s",
        "better": "higher", "bound": 0.1, "source": "host_clock",
        "workloads": [CELL]})
    spec["per_layer"].append({
        "name": "decode_roofline.toy", "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "decode kernel",
        "moves": "toy_records_per_s", "workloads": [CELL]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    assert {p: (home / p).read_bytes() for p in UNTOUCHED} == before
    return root / "BENCHMARK.json"


def test_a_deployment_added_as_files_runs_through_the_harness(bench,
                                                              tmp_path):
    assert CELL in _tiny.cells(bench) and CELL not in _tiny.cells()
    plain = _tiny.run(CELL, tmp_path, seed=2**31 + 3, bench=bench)
    assert plain["correct"] is True and plain["failed"] == 0
    assert plain["attempted"] > 0
    assert set(plain["metrics"]) == {"toy_records_per_s", "setup_s",
                                     "resident_data_bytes_per_raw_byte"}
    assert all(m["value"] > 0 for m in plain["metrics"].values())
    traced = _tiny.run(CELL, tmp_path, seed=2**31 + 3, seconds=1.0,
                       trace=True, bench=bench)
    assert traced["correct"] is True
    assert set(traced["metrics"]) == {"decode_roofline.toy"}
    assert traced["metrics"]["decode_roofline.toy"]["value"] > 0


@pytest.mark.parametrize("fault", sorted(control.FAULTS))
def test_a_fault_makes_the_new_deployments_run_incorrect(bench, tmp_path,
                                                         fault):
    got = _tiny.run(CELL, tmp_path, seed=41, plant=control.FAULTS[fault],
                    bench=bench)
    assert got["correct"] is False
    assert any(v["value"] > v["limit"] for v in got["checks"].values())
