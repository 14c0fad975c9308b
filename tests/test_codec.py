"""ACEAPEX codec: roundtrip properties, serialization, format invariants."""
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:       # offline container - seeded-random shim
    from _hypothesis_compat import given, settings, strategies as st

from repro.core import decoder as dec
from repro.core import encoder as enc
from repro.core import format as fmt


def roundtrip(data: bytes, **kw) -> bool:
    a = enc.encode(data, **kw)
    out = dec.Decoder(a, backend="ref").decode_all()
    return np.array_equal(out, np.frombuffer(data, np.uint8))


@pytest.mark.parametrize("mode", ["ra", "global"])
@pytest.mark.parametrize("entropy", ["rans", "raw"])
def test_roundtrip_fastq(fastq_platinum, mode, entropy):
    assert roundtrip(fastq_platinum[:100_000], block_size=4096, mode=mode,
                     entropy=entropy)


@pytest.mark.parametrize("payload", [
    b"", b"a", b"ab" * 3, b"\x00" * 100_000,
    bytes(range(256)) * 64, b"ACGT" * 10_000,
])
def test_roundtrip_edge_cases(payload):
    if not payload:
        payload = b"\x00"          # empty input → one empty block
    assert roundtrip(payload, block_size=2048)


@pytest.mark.slow
@settings(max_examples=20, deadline=None)
@given(data=st.binary(min_size=1, max_size=30_000),
       block_size=st.sampled_from([512, 2048, 16384]))
def test_roundtrip_property(data, block_size):
    assert roundtrip(data, block_size=block_size)


def test_block_self_containment(fastq_platinum):
    """RA mode: every single block decodes alone, bit-perfect — the §4
    position-invariance property."""
    data = fastq_platinum[:60_000]
    ref = np.frombuffer(data, np.uint8)
    a = enc.encode(data, block_size=4096, mode="ra")
    d = dec.Decoder(a, backend="ref")
    for b in range(a.n_blocks):
        row = np.asarray(d.decode_blocks(np.array([b])))[0]
        s, ln = int(a.block_start[b]), int(a.block_len[b])
        assert np.array_equal(row[:ln], ref[s:s + ln]), f"block {b}"


def test_mode1_equals_mode2(fastq_noisy):
    data = fastq_noisy[:50_000]
    a = enc.encode(data, block_size=4096)
    d = dec.Decoder(a, backend="ref")
    sel = np.arange(a.n_blocks)
    m2 = np.asarray(d.decode_blocks(sel))
    m1 = np.asarray(d.decode_blocks_host_entropy(sel))
    assert np.array_equal(m1, m2)


@pytest.mark.parametrize("block_size", [4096, 1 << 17])
def test_parallel_ra_encode_matches_serial(fastq_platinum, monkeypatch,
                                           block_size):
    """Large "ra" inputs encode their blocks in a spawn process pool; the
    archive must be byte-identical to the serial encode."""
    data = fastq_platinum[:300_000]
    serial = fmt.serialize(enc.encode(data, block_size=block_size))
    monkeypatch.setattr(enc, "_PARALLEL_MIN_BYTES", 0)
    assert fmt.serialize(enc.encode(data, block_size=block_size)) == serial


def test_serialization_roundtrip(fastq_platinum):
    a = enc.encode(fastq_platinum[:30_000], block_size=4096)
    buf = fmt.serialize(a)
    b = fmt.deserialize(buf)
    for f in ("words", "word_off", "n_words", "n_syms", "lanes", "n_cmds",
              "block_start", "block_len", "block_fnv", "freqs"):
        assert np.array_equal(getattr(a, f), getattr(b, f)), f
    assert (a.block_size, a.raw_size, a.mode, a.entropy, a.file_fnv) == \
        (b.block_size, b.raw_size, b.mode, b.entropy, b.file_fnv)
    out = dec.Decoder(b, backend="ref").decode_all()
    assert np.array_equal(out, np.frombuffer(fastq_platinum[:30_000],
                                             np.uint8))


def test_64bit_offsets():
    """The §5 u32-overflow fix: format fields are 64-bit — offsets beyond
    2^32 serialize/deserialize exactly (synthetic table entries; no 4 GB
    buffer needed to prove the field width)."""
    a = enc.encode(b"x" * 10_000, block_size=4096)
    a.block_start = a.block_start + np.int64(2**33)   # 8 GiB offsets
    a.raw_size = int(a.raw_size + 2**33)
    b = fmt.deserialize(fmt.serialize(a))
    assert np.array_equal(b.block_start, a.block_start)
    assert b.raw_size == a.raw_size
    assert b.block_start.dtype == np.int64


def _far_match_payload(seed: int, unit_len: int, gap: int) -> bytes:
    """unit + gap + unit: any correct parse of the second copy needs a
    match whose source lies `unit_len + gap` bytes back — past the 16-bit
    offset horizon when that distance exceeds 0xFFFF."""
    rng = np.random.default_rng(seed)
    unit = rng.integers(0, 256, size=unit_len, dtype=np.uint8).tobytes()
    return unit + bytes(gap) + unit


def test_ra_large_block_offset_truncation_regression():
    """Regression (silent u16 truncation): encode(mode="ra") stored
    block-local offsets as two byte planes while PAPER1_BLOCK_SIZE is
    1 MiB — any match sourcing ≥64 KiB into a block corrupted silently.
    Large blocks must select the 4-plane path and roundtrip bit-perfect."""
    data = _far_match_payload(0, 70_000, 5_000)
    a = enc.encode(data, block_size=fmt.PAPER1_BLOCK_SIZE, mode="ra")
    assert a.offset_bytes == 4            # the 4-byte offset-plane path
    # prove the payload actually exercises the >16-bit offset regime
    streams = dec._entropy_decode_host(a, np.array([0]))
    planes = np.asarray(streams["offsets"])[0]
    nc = int(a.n_cmds[0])
    offs = sum(planes[b * nc:(b + 1) * nc].astype(np.int64) << (8 * b)
               for b in range(4))
    assert (offs > 0xFFFF).any(), "no match beyond the u16 horizon"
    out = dec.Decoder(a, backend="ref").decode_all()
    assert np.array_equal(out, np.frombuffer(data, np.uint8))
    # the serialized form carries the width and roundtrips it
    b = fmt.deserialize(fmt.serialize(a))
    assert b.offset_bytes == 4
    assert np.array_equal(dec.Decoder(b, backend="ref").decode_all(), out)


def test_small_block_keeps_two_offset_planes(fastq_platinum):
    """block_size <= 0xFFFF stays on the compact 2-plane path."""
    a = enc.encode(fastq_platinum[:30_000], block_size=4096, mode="ra")
    assert a.offset_bytes == 2


@pytest.mark.slow
@settings(max_examples=6, deadline=None)
@given(seed=st.integers(0, 2**31 - 1),
       unit_len=st.integers(1_000, 80_000),
       gap=st.integers(0, 40_000),
       block_size=st.sampled_from([16 * 1024, 64 * 1024,
                                   fmt.PAPER1_BLOCK_SIZE]),
       mode=st.sampled_from(["ra", "global"]),
       entropy=st.sampled_from(["rans", "raw"]))
def test_roundtrip_blocksize_mode_entropy_sweep(seed, unit_len, gap,
                                                block_size, mode, entropy):
    """The grid that would have caught the u16 truncation at seed time:
    block_size ∈ {16 KiB, 64 KiB, 1 MiB} × mode × entropy, on payloads
    with match distances up to ~120 KiB (far past the u16 horizon)."""
    data = _far_match_payload(seed, unit_len, gap)
    assert roundtrip(data, block_size=block_size, mode=mode,
                     entropy=entropy), (block_size, mode, entropy)


def test_verify_clean_and_corrupted(fastq_platinum):
    """verify=True recomputes the 8-byte-stride FNV per decoded block on
    device: clean archives pass, a single corrupted entropy word raises
    BlockDigestError naming the block, in both modes."""
    data = fastq_platinum[:40_000]
    ref = np.frombuffer(data, np.uint8)
    a = enc.encode(data, block_size=4096)
    d = dec.Decoder(a, backend="ref")
    sel = np.arange(a.n_blocks)
    assert np.array_equal(
        np.asarray(d.decode_blocks(sel, verify=True)).reshape(-1)[:len(ref)],
        ref)
    assert np.array_equal(d.decode_all(verify=True), ref)
    assert np.array_equal(d.decode_all(chunk_blocks=3, verify=True), ref)
    d.decode_blocks_host_entropy(sel, verify=True)

    bad = enc.encode(data, block_size=4096)
    bad.words = bad.words.copy()
    bad.words[int(bad.word_off[2, fmt.S_LITERALS]) + 3] ^= 0xA5
    db = dec.Decoder(bad, backend="ref")
    assert np.array_equal(np.asarray(db.decode_blocks(np.array([0]),
                                                      verify=True))[0, :4096],
                          ref[:4096])          # untouched blocks still pass
    with pytest.raises(dec.BlockDigestError, match="block 2"):
        db.decode_blocks(sel, verify=True)
    with pytest.raises(dec.BlockDigestError, match="block 2"):
        db.decode_all(verify=True)
    with pytest.raises(dec.BlockDigestError, match="block 2"):
        db.decode_blocks_host_entropy(np.array([2]), verify=True)
    # a tampered digest table is caught by the file-level fold
    bad2 = enc.encode(data, block_size=4096)
    bad2.block_fnv = bad2.block_fnv.copy()
    bad2.block_fnv[0] ^= np.uint64(1)
    with pytest.raises(dec.BlockDigestError, match="file digest"):
        dec.Decoder(bad2, backend="ref").decode_all(verify=True)


def test_decode_all_launches_split_evenly(fastq_platinum, monkeypatch):
    """Verified decode_all splits the archive into launches of one shape,
    padding fewer than one block per launch: 10 blocks under a 6-block
    cap decode as 2 x 5, not 2 x 6."""
    data = fastq_platinum[:40_000]
    d = dec.Decoder(enc.encode(data, block_size=4096), backend="ref")
    launches = []
    decode_blocks = d.decode_blocks

    def record(ids, **kw):
        launches.append(len(ids))
        return decode_blocks(ids, **kw)

    monkeypatch.setattr(d, "decode_blocks", record)
    out = d.decode_all(chunk_blocks=6, verify=True)
    assert np.array_equal(out, np.frombuffer(data, np.uint8))
    assert d.archive.n_blocks == 10 and launches == [5, 5]


@pytest.mark.parametrize("out_len", [dec.F32_EXACT_INTS,
                                     dec.F32_EXACT_INTS + 5])
def test_linearize_exact_at_f32_limit(out_len):
    """_linearize's quotient stays exact on both sides of 2^24 (the f32
    path up to it, the s32 divide past it); checked at both ends."""
    import jax.numpy as jnp
    k, k_max = 7, 8
    rows = (np.arange(-(-out_len // k) * k_max) % 251).astype(np.uint8)
    n = out_len - 3
    got = np.asarray(dec._linearize(jnp.asarray(rows[None]),
                                    jnp.asarray([n], jnp.int32),
                                    jnp.asarray([k], jnp.int32),
                                    out_len, k_max=k_max))[0]
    for i in (np.arange(4096), np.arange(out_len - 4096, out_len)):
        want = np.where(i < n, rows[(i // k) * k_max + i % k], 0)
        assert np.array_equal(got[i], want)


def test_device_fnv_matches_host_recurrence(rng):
    """The u32-limb lax.scan digest == format.fnv1a64_u64_stride for
    ragged row lengths (incl. non-multiple-of-8 and zero)."""
    import jax.numpy as jnp
    rows = rng.integers(0, 256, size=(5, 133), dtype=np.uint8)
    lens = np.array([0, 1, 8, 100, 133], np.int32)
    fhi, flo = dec._fnv_rows_jit(jnp.asarray(rows), jnp.asarray(lens))
    got = ((np.asarray(fhi).astype(np.uint64) << np.uint64(32))
           | np.asarray(flo).astype(np.uint64))
    want = [fmt.fnv1a64_u64_stride(rows[i, :n]) for i, n in enumerate(lens)]
    assert got.tolist() == [int(w) for w in want]


def test_fnv_digests(fastq_platinum):
    data = fastq_platinum[:20_000]
    a = enc.encode(data, block_size=4096)
    ref = np.frombuffer(data, np.uint8)
    for bidx in range(a.n_blocks):
        s, ln = int(a.block_start[bidx]), int(a.block_len[bidx])
        assert int(a.block_fnv[bidx]) == fmt.fnv1a64_u64_stride(ref[s:s+ln])


def test_ratio_regimes(fastq_platinum, fastq_noisy):
    """Paper §3.3: PCR-free-like data compresses far better than noisy."""
    rp = enc.encode(fastq_platinum, block_size=16384).ratio
    rn = enc.encode(fastq_noisy, block_size=16384).ratio
    assert rp > rn > 1.0


def test_wavefront_matches_ra(fastq_platinum):
    data = fastq_platinum[:40_000]
    ref = np.frombuffer(data, np.uint8)
    for mode in ("ra", "global"):
        out = dec.Decoder(enc.encode(data, block_size=4096, mode=mode),
                          backend="ref").decode_all()
        assert np.array_equal(out, ref)
