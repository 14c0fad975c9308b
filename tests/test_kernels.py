"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps + hypothesis
(assignment deliverable c: per-kernel allclose against ref.py)."""
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:       # offline container - seeded-random shim
    from _hypothesis_compat import given, settings, strategies as st

import jax.numpy as jnp

from repro.core import encoder as enc
from repro.core import entropy as ent
from repro.core.decoder import Decoder, to_device
from repro.core.format import N_STREAMS
from repro.kernels import ops, ref
from repro.kernels.lz77_match import lz77_decode_blocks_pallas
from repro.kernels.rans_decode import rans_decode_pallas


def _archive_streams(data: bytes, block_size: int):
    a = enc.encode(data, block_size=block_size)
    da = to_device(a)
    return a, da


# ------------------------------------------------------------ rANS kernel
@pytest.mark.parametrize("size,block", [(3000, 1024), (20000, 4096),
                                        (999, 512), (65536, 16384)])
def test_rans_kernel_vs_ref_shapes(fastq_platinum, size, block):
    a, da = _archive_streams(fastq_platinum[:size], block)
    flat_off = jnp.asarray(a.word_off.reshape(-1).astype(np.int32))
    flat_n = jnp.asarray(a.n_syms.reshape(-1))
    flat_k = jnp.asarray(a.lanes.reshape(-1))
    cls = jnp.asarray(np.tile(np.arange(N_STREAMS, dtype=np.int32),
                              a.n_blocks))
    t_max = max(da.t_max_lit, da.t_max_cmd)
    rows_ref, _ = ref.rans_decode_ref(da.words, flat_off, flat_n, flat_k,
                                      cls, a.freqs, t_max=t_max)
    freqs_t = tuple(map(tuple, a.freqs.tolist()))
    rows_pal = rans_decode_pallas(da.words, flat_off, flat_n, flat_k, cls,
                                  freqs_t, t_max=t_max, interpret=True)
    # compare only the valid symbols of every stream
    rr, rp = np.asarray(rows_ref), np.asarray(rows_pal)
    for s in range(rr.shape[0]):
        n, k = int(flat_n[s]), int(flat_k[s])
        if n == 0:
            continue
        g1 = ent.gather_stream_bytes(rr[s], n, k)
        g2 = ent.gather_stream_bytes(rp[s], n, k)
        np.testing.assert_array_equal(g1, g2)


@pytest.mark.parametrize("group", [1, 4, 8])
def test_rans_kernel_group_sizes(fastq_noisy, group):
    a, da = _archive_streams(fastq_noisy[:8000], 2048)
    flat_off = jnp.asarray(a.word_off.reshape(-1).astype(np.int32))
    flat_n = jnp.asarray(a.n_syms.reshape(-1))
    flat_k = jnp.asarray(a.lanes.reshape(-1))
    cls = jnp.asarray(np.tile(np.arange(N_STREAMS, dtype=np.int32),
                              a.n_blocks))
    t_max = max(da.t_max_lit, da.t_max_cmd)
    freqs_t = tuple(map(tuple, a.freqs.tolist()))
    rows = rans_decode_pallas(da.words, flat_off, flat_n, flat_k, cls,
                              freqs_t, t_max=t_max, group=group,
                              interpret=True)
    rows_ref, _ = ref.rans_decode_ref(da.words, flat_off, flat_n, flat_k,
                                      cls, a.freqs, t_max=t_max)
    rr, rp = np.asarray(rows_ref), np.asarray(rows)
    for s in range(rr.shape[0]):
        n, k = int(flat_n[s]), int(flat_k[s])
        if n:
            np.testing.assert_array_equal(
                ent.gather_stream_bytes(rr[s], n, k),
                ent.gather_stream_bytes(rp[s], n, k))


# ------------------------------------------------------------ LZ77 kernel
def _match_inputs(data: bytes, block_size: int):
    """Raw (pre-entropy) command planes for the match kernel."""
    from repro.core.decoder import (_entropy_decode_host, _u16_from_planes)
    a = enc.encode(data, block_size=block_size)
    sel = np.arange(a.n_blocks)
    streams = _entropy_decode_host(a, sel)
    max_cmds = int(a.n_cmds.max(initial=1))
    n_cmds = jnp.asarray(a.n_cmds)
    ll = _u16_from_planes(streams["commands"], n_cmds, max_cmds)
    ml = _u16_from_planes(streams["lengths"], n_cmds, max_cmds)
    off = _u16_from_planes(streams["offsets"], n_cmds, max_cmds)
    return (a, ll, ml, off, n_cmds, streams["literals"],
            jnp.asarray(a.block_len))


@pytest.mark.parametrize("block_size", [512, 2048, 16384])
def test_lz77_kernel_vs_ref(fastq_platinum, block_size):
    data = fastq_platinum[:40_000]
    a, ll, ml, off, n_cmds, lits, blen = _match_inputs(data, block_size)
    out_ref = ref.lz77_decode_blocks_ref(ll, ml, off, n_cmds, lits, blen,
                                         block_size)
    out_pal = lz77_decode_blocks_pallas(ll, ml, off, n_cmds, lits, blen,
                                        out_size=block_size, interpret=True)
    np.testing.assert_array_equal(np.asarray(out_ref), np.asarray(out_pal))
    # and both equal the original bytes
    refbytes = np.frombuffer(data, np.uint8)
    flat = np.asarray(out_pal).reshape(-1)[:len(refbytes)]
    np.testing.assert_array_equal(flat, refbytes)


@settings(max_examples=15, deadline=None)
@given(data=st.binary(min_size=1, max_size=8000))
def test_lz77_kernel_property(data):
    a, ll, ml, off, n_cmds, lits, blen = _match_inputs(data, 1024)
    out_pal = lz77_decode_blocks_pallas(ll, ml, off, n_cmds, lits, blen,
                                        out_size=1024, interpret=True)
    flat = np.asarray(out_pal).reshape(-1)[:len(data)]
    np.testing.assert_array_equal(flat, np.frombuffer(data, np.uint8))


def test_pallas_backend_end_to_end(fastq_noisy):
    data = fastq_noisy[:20_000]
    a = enc.encode(data, block_size=2048)
    out = Decoder(a, backend="pallas").decode_all()
    np.testing.assert_array_equal(out, np.frombuffer(data, np.uint8))


@pytest.mark.parametrize("platform,interpret", [("cpu", True),
                                                ("tpu", False)])
def test_backend_dispatch_by_platform(monkeypatch, platform, interpret):
    """"auto" is the XLA path on every platform; an explicit "pallas"
    never runs in interpret mode on a TPU."""
    monkeypatch.setattr(ops.jax, "default_backend", lambda: platform)
    assert ops._resolve("auto") == "ref"
    assert ops._resolve("pallas") == "pallas"
    assert ops._interpret() is interpret
