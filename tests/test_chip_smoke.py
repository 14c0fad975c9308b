"""`chip_smoke.py` off the chip: it refuses to run without a TPU, and its
phases (and their host reference) pass on CPU at a tiny size."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402


def _run(script: Path, cwd: Path) -> subprocess.CompletedProcess:
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run([sys.executable, str(script)],
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=300)


def _result_line(stdout: str):
    lines = stdout.strip().splitlines()
    try:
        return json.loads(lines[-1]) if lines else None
    except json.JSONDecodeError:
        return None


def test_no_tpu_fails_without_result():
    out = _run(ROOT / "chip_smoke.py", ROOT)
    assert out.returncode != 0
    assert _result_line(out.stdout) is None
    assert "no TPU" in out.stderr


def test_script_alone_fails_without_result(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path / "chip_smoke.py")
    out = _run(tmp_path / "chip_smoke.py", tmp_path)
    assert out.returncode != 0
    assert _result_line(out.stdout) is None


def test_one_chip_phases_pass_on_cpu(monkeypatch):
    """Every phase at 2,000 reads: build, verified decode_all, the query
    passes (the second compiles nothing), a 4-chunk budgeted stream and
    the two-tenant serving loop, all byte-identical to the reference."""
    monkeypatch.setattr(chip_smoke, "STREAM_BUDGET",
                        4 * chip_smoke.BLOCK_SIZE)
    lines = []
    chip_smoke.run_one_chip(2000, 0, lines.append)
    text = "\n".join(lines)
    assert "in 4 chunks, byte-identical" in text
    assert "query compiles: pass 1" in text and "pass 2 0" in text
    assert text.count("phase ") == 4 and "pass, " in text


@pytest.fixture
def restore_cache_config():
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    was = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in was.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


def test_compile_cache_dir(monkeypatch, restore_cache_config):
    """The cache follows JAX_COMPILATION_CACHE_DIR when it is set, and is
    otherwise the fixed `.jax_cache/` of the checkout."""
    import jax
    from repro.launch.hygiene import enable_compile_cache
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    path = enable_compile_cache()
    assert path == str(ROOT / ".jax_cache") == enable_compile_cache()
    assert jax.config.jax_compilation_cache_dir == path
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()


def test_host_reference_and_mismatch_detection():
    from repro.data.fastq import make_fastq
    corpus = make_fastq("platinum", n_reads=50, seed=3)
    ref = chip_smoke.HostReference(corpus)
    assert ref.n_reads == 50
    assert b"".join(ref.record(i) for i in range(50)) == corpus
    assert ref.name(7) == b"SRR0.7"
    with pytest.raises(chip_smoke.SmokeFailure):
        chip_smoke.check(False, "mismatch")
