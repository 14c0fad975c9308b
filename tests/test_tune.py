"""Profile-guided encode autotuner: EncodeProfile, grid validation,
Pareto frontier, objective selection, and the `encode(profile=)` /
`GenomicArchive.create` integration."""
import logging

import numpy as np
import pytest

from repro.api import GenomicArchive
from repro.core.encoder import encode, validate_encode_params
from repro.data.fastq import make_fastq
from repro.tune import (EncodeProfile, TunePoint, autotune, default_grid,
                        pareto_frontier, validate_grid)

CORPUS = make_fastq("platinum", n_reads=800, seed=5)


# -------------------------------------------------------------- profile
def test_profile_defaults_and_describe():
    p = EncodeProfile()
    assert p.block_size == 16 * 1024 and p.mode == "ra"
    assert p.offset_bytes == 2
    assert p.describe() == "ra/rans/block=16384/off=2B"
    assert p.encode_kwargs() == dict(block_size=16 * 1024, mode="ra",
                                     entropy="rans", anchor_interval=0)


def test_profile_offset_bytes_regimes():
    assert EncodeProfile(block_size=64 * 1024).offset_bytes == 4
    assert EncodeProfile(block_size=0xFFFF).offset_bytes == 2
    assert EncodeProfile(mode="global", anchor_interval=4).offset_bytes == 8


def test_profile_validates_knobs_up_front():
    with pytest.raises(ValueError, match="anchor_interval"):
        EncodeProfile(mode="ra", anchor_interval=4)
    with pytest.raises(ValueError, match="block_size"):
        EncodeProfile(block_size=0)
    with pytest.raises(ValueError, match="entropy"):
        EncodeProfile(entropy="zstd")
    with pytest.raises(ValueError, match="mode"):
        EncodeProfile(mode="local")


def test_validate_encode_params_window_guard():
    # an anchored-global window must stay below the 2 GiB flat-pointer
    # horizon — the same constraint the encoder enforces
    with pytest.raises(ValueError, match="2 GiB|anchor_interval"):
        validate_encode_params(1 << 20, "global", "rans", 1 << 12)
    validate_encode_params(16 * 1024, "global", "rans", 4)


# ------------------------------------------------------- encode(profile=)
def test_encode_accepts_profile():
    prof = EncodeProfile(block_size=4096, entropy="raw")
    a = encode(CORPUS, profile=prof)
    assert a.block_size == 4096 and a.entropy == "raw"
    from repro.core.decoder import Decoder
    d = Decoder(a, backend="ref")
    assert bytes(np.asarray(d.decode_all())) == CORPUS


def test_encode_rejects_profile_plus_explicit_knobs():
    prof = EncodeProfile(block_size=4096)
    with pytest.raises(ValueError, match="profile"):
        encode(CORPUS, block_size=8192, profile=prof)
    with pytest.raises(ValueError, match="profile"):
        encode(CORPUS, entropy="raw", profile=prof)


# ------------------------------------------------------------------ grid
def test_default_grid_shape():
    grid = default_grid()
    assert len(grid) == 8                      # 2 blocks × 2 anchors × 2 ent
    for pt in grid:
        assert pt["mode"] == ("global" if pt["anchor_interval"] else "ra")


def test_validate_grid_skips_invalid_with_reason(caplog):
    grid = [dict(block_size=4096, mode="ra", entropy="rans",
                 anchor_interval=0),
            dict(block_size=4096, mode="ra", entropy="rans",
                 anchor_interval=4),            # anchors need global
            dict(block_size=4096, mode="ra", entropy="zstd",
                 anchor_interval=0)]            # unknown entropy
    with caplog.at_level(logging.INFO, logger="repro.tune"):
        valid, skipped = validate_grid(grid, raw_size=100_000)
    assert valid == grid[:1]
    assert len(skipped) == 2
    assert all(reason for _, reason in skipped)
    assert sum("skipping grid point" in r.message for r in caplog.records) == 2


# -------------------------------------------------------------- frontier
def _pt(ratio, seek, gbps):
    return TunePoint(profile=EncodeProfile(), ratio=ratio, seek_us=seek,
                     decode_GBps=gbps)


def test_pareto_frontier_drops_dominated():
    a = _pt(3.0, 100, 1.0)     # best ratio
    b = _pt(2.0, 50, 2.0)      # best seek + throughput
    c = _pt(1.5, 200, 0.5)     # dominated by both
    front = pareto_frontier([a, b, c])
    assert a in front and b in front and c not in front
    assert a.on_frontier and b.on_frontier and not c.on_frontier


# ----------------------------------------------------------------- sweep
@pytest.fixture(scope="module")
def tuned():
    grid = default_grid(block_sizes=(4096, 16 * 1024),
                        anchor_intervals=(0, 4), entropies=("rans", "raw"))
    return autotune(CORPUS, target="seek", grid=grid,
                    sample_bytes=128 * 1024, iters=1)


def test_autotune_sweeps_and_selects(tuned):
    assert len(tuned.points) == 8 and not tuned.skipped
    assert tuned.frontier and tuned.profile in [p.profile
                                                for p in tuned.frontier]
    # the selected point is the frontier's fastest seek
    assert tuned.profile == min(tuned.frontier,
                                key=lambda p: p.seek_us).profile
    assert tuned.sample_bytes <= 128 * 1024
    # frontier table renders one row per frontier point
    table = tuned.table()
    assert table.count("\n") == len(tuned.frontier) + 1


def test_autotune_ratio_target(tuned):
    r = autotune(CORPUS, target="ratio",
                 grid=[p.profile.encode_kwargs() for p in tuned.points],
                 sample_bytes=128 * 1024, iters=1)
    assert r.profile == max(r.frontier, key=lambda p: p.ratio).profile


def test_autotune_latency_budget(tuned):
    # a generous budget selects the best-ratio point on the frontier. The
    # sweep below measures seek times afresh (one iteration, on a loaded
    # host), so a budget just above the first sweep's slowest point can
    # exclude a point whose second reading is slower
    big = 4 * max(p.seek_us for p in tuned.frontier)
    r = autotune(CORPUS, target="seek", latency_budget_us=big,
                 grid=[p.profile.encode_kwargs() for p in tuned.frontier],
                 sample_bytes=128 * 1024, iters=1)
    assert r.profile.entropy == max(
        r.frontier, key=lambda p: p.ratio).profile.entropy


def test_autotune_rejects_bad_target():
    with pytest.raises(ValueError, match="target"):
        autotune(CORPUS, target="vibes", sample_bytes=4096)
    with pytest.raises(ValueError, match="empty"):
        autotune(b"", sample_bytes=4096)


def test_autotune_all_invalid_grid_raises():
    bad = [dict(block_size=4096, mode="ra", entropy="rans",
                anchor_interval=9)]
    with pytest.raises(ValueError, match="invalid"):
        autotune(CORPUS, grid=bad, sample_bytes=4096)


# ------------------------------------------------------------- archive api
def test_genomic_archive_create_tunes_and_decodes(tuned):
    ga = GenomicArchive.create(CORPUS, profile=tuned.profile)
    assert ga.profile == tuned.profile
    assert ga.block_size == tuned.profile.block_size
    lo = 1000
    ref = np.frombuffer(CORPUS, np.uint8)
    assert np.array_equal(ga[lo:lo + 500], ref[lo:lo + 500])


def test_genomic_archive_create_sweeps_when_no_profile():
    small = make_fastq("platinum", n_reads=200, seed=6)
    ga = GenomicArchive.create(small, target="seek",
                               sample_bytes=32 * 1024,
                               grid=default_grid(block_sizes=(4096,),
                                                 anchor_intervals=(0,)),
                               iters=1)
    assert ga.profile is not None and ga.profile.block_size == 4096
    out = bytes(np.asarray(ga.store.decoder.decode_all()))
    assert out == small
