"""Work counts from the archive's block table, and the table of peaks."""
from pathlib import Path

import numpy as np
import pytest

from chipbench import harness, peaks, workcount

platinum_fastq = harness.load_module(harness.HERE, "corpora",
                                     "platinum_fastq").platinum_fastq


@pytest.fixture(scope="module")
def ga():
    from repro.api import GenomicArchive
    data = platinum_fastq(3000, 100, seed=5)
    return GenomicArchive.from_bytes(data, block_size=4096, mode="ra",
                                     entropy="rans", cache_blocks=16)


def test_block_work_reads_word_extents_not_shapes(ga):
    a = ga.store.decoder.archive
    work = workcount.block_work_bytes(a)
    starts = a.word_off[:, 0].astype(np.int64)
    ends = np.append(starts[1:], a.words.size)
    assert np.array_equal(work, 2 * (ends - starts) + a.block_len)
    # the extents differ block to block; an even split of the words would not
    assert np.unique(ends - starts).size > 1
    assert work.sum() == 2 * a.words.size + a.raw_size


def test_pad_rows_add_no_work(ga):
    dec = ga.store.decoder
    log = workcount.DecodeLog([dec])
    try:
        mark = log.mark()
        dec.decode_blocks(np.asarray([3, 9, 9, 9], np.int32))  # pow2 pad
        (got,) = log.since(mark)
    finally:
        del dec.decode_blocks            # back to the class's method
    assert sorted(got.tolist()) == [3, 9]
    assert workcount.decode_work_bytes(dec.archive, got) == int(
        workcount.block_work_bytes(dec.archive)[[3, 9]].sum()) \
        == log.work_bytes(mark)


def test_cache_hits_add_no_work(ga):
    dec = ga.store.decoder
    log = workcount.DecodeLog([dec])
    try:
        ga.clear_cache()
        ids = np.arange(0, 40, 7)
        ga.store.fetch_reads(ids)
        first = log.mark()
        assert first > 0
        ga.store.fetch_reads(ids)            # every block is a hit now
        assert log.work_bytes(first) == 0
    finally:
        del dec.decode_blocks


def test_roofline_share_needs_device_time():
    assert workcount.roofline_share_pct(1000, 0.0, 1e9) is None
    assert workcount.roofline_share_pct(0, 1.0, 1e9) is None
    assert workcount.roofline_share_pct(819e9, 2.0, 819e9) == pytest.approx(50)


def test_peak_table_knows_v5e_and_refuses_others():
    assert peaks.peak("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="TPU v9"):
        peaks.peak("TPU v9")


def test_run_refuses_an_unknown_device_kind(tmp_path):
    hooks = harness.Hooks(require_tpu=False, peaks={},
                          archive_dir=tmp_path)
    with pytest.raises(KeyError, match="no published peak"):
        harness.run_cell(harness.load_cell("ra16k.zipf_open"), 1, 1.0,
                         False, hooks)
