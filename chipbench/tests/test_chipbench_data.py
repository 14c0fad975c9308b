"""The corpus generator, the host reference, the archive cache key, the
traffic generator, the warm-up batches and the file lookup by name."""
import hashlib
import json
import shutil
from pathlib import Path

import numpy as np
import pytest

import _tiny
from chipbench import archives, harness, loadgen, warmup

ROOT = Path(__file__).resolve().parents[2]
PLATINUM = harness.load_module(harness.HERE, "corpora", "platinum_fastq")
platinum_fastq = PLATINUM.platinum_fastq
HostReference = PLATINUM.HostReference


def test_generator_is_a_function_of_the_seed():
    a = platinum_fastq(500, 100, seed=2**31 + 5)
    assert a == platinum_fastq(500, 100, seed=2**31 + 5)
    assert a != platinum_fastq(500, 100, seed=2**31 + 6)
    assert a.startswith(b"@SRR0.0 0/1\n")


def test_generator_keeps_the_platinum_distributions():
    data = platinum_fastq(20_000, 100, seed=1)
    lines = data.split(b"\n")[:-1]
    quals = np.frombuffer(b"".join(lines[3::4]), np.uint8)
    share = {c: float(np.mean(quals == ord(c))) for c in "F:,"}
    assert share["F"] == pytest.approx(0.97, abs=0.005)
    assert share[":"] == pytest.approx(0.02, abs=0.003)
    assert share[","] == pytest.approx(0.01, abs=0.003)
    seqs = lines[1::4]
    assert all(len(s) == 100 for s in seqs) and set(lines[2::4]) == {b"+"}
    # reads re-sample a pool of n_reads // 120 fragments: heavy duplication
    assert len(set(seqs)) < len(seqs) // 10


def test_host_reference_agrees_with_the_program_index():
    from repro.core.index import parse_fastq_records
    data = platinum_fastq(777, 100, seed=9)
    ref = HostReference(data)
    starts, names = parse_fastq_records(data)
    assert np.array_equal(ref.starts, starts.astype(np.int64))
    assert ref.n_reads == len(names) == 777
    for i in (0, 1, 500, 776):
        rec = ref.record(i)
        assert rec.split(b" ")[0][1:] == names[i]
        assert rec.endswith(b"\n") and rec.count(b"\n") == 4


def test_archive_key_follows_every_source_file(tmp_path):
    src = tmp_path / "src"
    shutil.copytree(ROOT / "src" / "repro" / "api", src / "repro" / "api")
    cfg = {"block_size": 16384}
    key = archives.archive_key("c", cfg, 1, src)
    assert key == archives.archive_key("c", cfg, 1, src)
    assert key != archives.archive_key("c", cfg, 2, src)
    assert key != archives.archive_key("c", {"block_size": 4096}, 1, src)
    for path in sorted((src / "repro" / "api").glob("*.py")):
        text = path.read_bytes()
        path.write_bytes(text + b"\n")
        assert archives.archive_key("c", cfg, 1, src) != key, path.name
        path.write_bytes(text)
    assert archives.archive_key("c", cfg, 1, src) == key


@pytest.mark.parametrize("n_reads,seed,digest", [
    (500, 7,
     "aad88162e4290d4716838e07fb5f28f8475a9fb0a1129885fb0eac6dee323044"),
    (500, 2**31 + 5,
     "d4bfef36d022e09d3140704a13ebe700de5611c0467b21de6e8831cd7dd3fcae"),
    (4321, 7,
     "a896182c89ebbf651444618271d3096db10a363f5be3fce7a32b5a29324d8fc4"),
    (4321, 2**31 + 5,
     "f541a0eb01fe8fc6bcefca119f135c9608d65b7da35090bd128d577c5ae3a3e2")])
def test_platinum_corpus_bytes_are_pinned(n_reads, seed, digest):
    """The corpus module's `generate` gives, for each seed and size, the
    bytes the generator gave before it moved into the module."""
    config = {"n_reads": n_reads, "read_len": 100}
    assert hashlib.sha256(PLATINUM.generate(config, seed)).hexdigest() \
        == digest


@pytest.mark.parametrize("cell", _tiny.cells())
def test_cell_files_are_found_by_name(cell):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    w = next(w for w in bench["workloads"] if w["name"] == cell)
    c = harness.load_cell(cell)
    assert c.config_name == w["config"]
    assert c.config == json.loads((ROOT / next(
        x["file"] for x in bench["configs"] if x["name"] == w["config"]))
        .read_text())
    assert c.mix == json.loads(
        (ROOT / "chipbench" / "traffic" / f"{w['traffic']}.json").read_text())
    assert callable(harness.load_module(c.home, "corpora",
                                        c.config["corpus"]).generate)
    assert issubclass(harness.driver_class(c), harness.Driver)
    assert set(_tiny.sizes(cell)) >= {"config", "traffic"}
    assert {m["name"] for m in c.end_to_end} >= {"setup_s"}
    assert c.per_layer
    for m in c.per_layer:
        assert callable(harness.metric_reader(m["name"]))


def test_scrambled_zipfian_is_skewed_and_in_range():
    z = loadgen.ScrambledZipfian(100_000, 0.99)
    rng = np.random.default_rng(0)
    ranks = z.ranks(rng.random(50_000))
    assert ranks.min() >= 0 and ranks.max() < 100_000
    counts = np.bincount(ranks, minlength=10)
    assert counts[0] > counts[1] > counts[5] > 0
    keys = z.key(ranks)
    assert keys.min() >= 0 and keys.max() < 100_000
    assert keys[ranks == 0].min() == keys[ranks == 0].max()   # one key


def test_poisson_arrivals_fill_the_window_at_the_rate():
    rng = np.random.default_rng(4)
    t = loadgen.arrivals({"arrivals": "poisson", "rate_per_s": 200.0},
                         30.0, rng)
    assert np.all(np.diff(t) >= 0) and t[-1] < 30.0
    assert t.size == pytest.approx(6000, rel=0.05)


def _pow2(n):
    return 1 << max(0, n - 1).bit_length()


def test_warmup_batches_cover_every_padded_shape():
    """Every (padded batch, covering blocks) pair, every batch size, and
    every (padded misses, padded blocks) pair, with few blocks decoded."""
    data = platinum_fastq(10_000, 100, seed=3)
    ref = HostReference(data)
    bs, max_batch = 16384, 4
    b0 = ref.starts[:-1] // bs
    b1 = (ref.starts[1:] - 1) // bs
    gathers, sizes, launches = set(), set(), set()
    resident, decoded = set(), 0
    for clear, ids in warmup.point_requests(ref.starts, bs, max_batch):
        ids = np.asarray(ids)
        assert np.unique(ids).size == ids.size <= max_batch
        blocks = set(np.concatenate([b0[ids], b1[ids]]).tolist())
        if clear:
            resident = set()
        gathers.add((_pow2(ids.size), len(blocks)))
        sizes.add(ids.size)
        misses = len(blocks - resident)
        decoded += misses
        launches.add((_pow2(misses), _pow2(len(blocks))))
        resident |= blocks
    assert {(bp, u) for bp in (1, 2, 4) for u in range(1, 2 * bp + 1)} \
        <= gathers
    assert sizes == {1, 2, 3, 4}
    assert {(mp, up) for up in (1, 2, 4, 8) for mp in (1, 2, 4, 8)
            if mp <= up} <= launches
    assert decoded <= 2 * 8 + sum(mp for up in (1, 2, 4, 8)
                                  for mp in (1, 2, 4, 8) if mp <= up)


def test_stream_warmup_has_one_chunk_per_shape():
    rounds = np.asarray([8, 8, 9, 8, 8, 9, 8, 8, 9, 8, 8])
    got = warmup.stream_chunks(rounds, 11, 10, 105, 3)
    # chunks [0,3) and [3,6) and [6,9) share a shape; [9,11) is the tail
    assert got == [(0, 30), (90, 105)]


def test_on_off_arrivals_keep_the_mean_rate_and_the_gaps():
    rng = np.random.default_rng(5)
    mix = {"arrivals": "on_off", "rate_per_s": 100.0, "on_s": 1.0,
           "off_s": 3.0}
    t = loadgen.arrivals(mix, 40.0, rng)
    assert np.all(np.diff(t) >= 0) and t[-1] < 40.0
    assert t.size == pytest.approx(4000, rel=0.08)
    assert np.all(np.mod(t, 4.0) < 1.0)        # nothing in the off phases
