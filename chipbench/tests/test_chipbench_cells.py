"""Each cell of `BENCHMARK.json` end to end on the CPU at its tiny size,
and the refusals of the entry point and of the sweep."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

import _tiny
from chipbench import harness

ROOT = Path(__file__).resolve().parents[2]
CELLS = _tiny.cells()
POINT = "ra16k.zipf_open"


def _names(metrics):
    return {m["name"] for m in metrics}


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_end_to_end(cell, tmp_path):
    c = harness.load_cell(cell)
    first = _tiny.run(cell, tmp_path, seed=2**31 + 11)
    assert first["correct"] is True and first["failed"] == 0
    assert first["attempted"] > 0
    assert set(first["metrics"]) == _names(c.end_to_end)
    assert all(m["value"] > 0 for m in first["metrics"].values())
    assert list(first)[-1] == "checks"
    assert all(v["value"] == 0 == v["limit"]
               for v in first["checks"].values())
    # the second run of the seed opens the archive the first one saved
    assert len(list((tmp_path / "archives").iterdir())) == 1
    traced = _tiny.run(cell, tmp_path, seed=2**31 + 11, trace=True)
    assert traced["correct"] is True
    assert set(traced["metrics"]) == _names(c.per_layer)
    dev = traced["device"]
    assert 0 < dev["busy_s"] <= dev["window_s"]
    assert set(traced["breakdown"]) == {"device_ops", "idle_gaps"}
    assert len(list((tmp_path / "archives").iterdir())) == 1


def _run_py(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", POINT,
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_exits_nonzero_without_a_result():
    p = _run_py(ROOT)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert '"correct"' not in p.stdout


def test_benchmark_alone_exits_nonzero_without_a_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns(".*", "__pycache__"))
    p = _run_py(tmp_path)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout


def test_point_warmup_leaves_the_cache_in_the_traffics_steady_state(
        tmp_path):
    s = harness.open_session(harness.load_cell(POINT), 5,
                             _tiny.hooks(POINT, tmp_path))
    info = s.ga.cache_info()
    assert info["resident"] == _tiny.sizes(POINT)["config"]["cache_blocks"]
    # the traffic's own keys ran through a full cache, hitting and evicting
    assert info["hits"] > 0 and info["evictions"] > 0


def test_sweep_refuses_a_cell_whose_driver_has_no_open_loop(capsys):
    from chipbench import sweep
    assert sweep.main(["--workload", "ra1m.range_stream", "--seed", "1",
                       "--seconds", "1", "--rates", "1"]) == 2
    assert "no open loop" in capsys.readouterr().err
