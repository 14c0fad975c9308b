"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps + hypothesis
(assignment deliverable c: per-kernel allclose against ref.py)."""
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:       # offline container - seeded-random shim
    from _hypothesis_compat import given, settings, strategies as st

import jax
import jax.numpy as jnp

from repro.core import depth as dpth
from repro.core import encoder as enc
from repro.core import entropy as ent
from repro.core.decoder import (Decoder, _u16_from_planes,
                                _u32_from_planes, _u64lo_from_planes,
                                to_device)
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


# ------------------------------------------------------ command expansion
def _expand_by_search(lit_lens, match_lens, offsets, n_cmds, block_len,
                      out_size, base=0):
    """Oracle: each byte's command by a binary search over the command
    ends (the form `ref.expand_pointers` had before its scatter + prefix
    sum), then the same per-command fields."""
    C = lit_lens.shape[0]
    valid = jnp.arange(C) < n_cmds
    ll = jnp.where(valid, lit_lens.astype(jnp.int32), 0)
    ml = jnp.where(valid, match_lens.astype(jnp.int32), 0)
    off = offsets.astype(jnp.int32)
    tot = ll + ml
    cum_tot = jnp.cumsum(tot)
    P = cum_tot - tot
    cum_lit = jnp.cumsum(ll) - ll
    i = jnp.arange(out_size, dtype=jnp.int32)
    c = jnp.searchsorted(cum_tot, i, side="right", method="sort")
    c = jnp.minimum(jnp.minimum(c, n_cmds), C - 1)
    rel = i - P[c]
    d = jnp.maximum(base + P[c] + ll[c] - off[c], 1)
    ptr = jnp.where(rel < ll[c], -(cum_lit[c] + rel + 1),
                    off[c] + jnp.remainder(rel - ll[c], d))
    return jnp.where(i < block_len, ptr, -1)


def _random_commands(rng, n_cmds, start=0, overlap=0.3):
    """One block's command stream: about a fifth of the commands empty,
    matches reaching back to any earlier byte of the flat space (the
    block starts at `start`), `overlap` of them closer than their own
    length, the last command a match. Returns i64 literal lengths,
    match lengths, offsets."""
    ll = rng.integers(0, 12, n_cmds)
    ml = rng.integers(0, 24, n_cmds)
    empty = rng.random(n_cmds) < 0.2
    ll[empty] = 0
    ml[empty] = 0
    ml[-1] = max(ml[-1], 1)            # the block's last byte is a copy
    off = np.zeros(n_cmds, np.int64)
    pos = start
    for c in range(n_cmds):
        mstart = pos + ll[c]
        if mstart == 0:
            ml[c] = 0
        if ml[c]:
            near = rng.random() < overlap and ml[c] > 1
            hi = min(ml[c] - 1, mstart) if near else mstart
            off[c] = mstart - rng.integers(1, hi + 1)
        pos = mstart + ml[c]
    return ll, ml, off


def _lz_fill(out, ll, ml, off, lits, start):
    """Byte-serial LZ77 decode of one block into the flat `out`."""
    pos, li = start, 0
    for c in range(ll.size):
        out[pos:pos + ll[c]] = lits[li:li + ll[c]]
        pos, li = pos + ll[c], li + ll[c]
        for k in range(ml[c]):
            out[pos + k] = out[off[c] + k]
        pos += ml[c]
    return pos


def _to_planes(v, n_cmds, max_cmds, n_planes):
    """Little-endian byte planes of one stream: plane b holds byte b of
    the first `n_cmds` values, as the archive stores them."""
    v = np.asarray(v, np.int64).astype(np.uint64)
    body = np.concatenate([(v[:n_cmds] >> np.uint64(8 * b)) & 0xFF
                           for b in range(n_planes)])
    planes = np.zeros(n_planes * max_cmds, np.uint8)
    planes[:body.size] = body
    return jnp.asarray(planes[None])


def _check_pointers(got, ll, ml, off, n_cmds, block_len, out_size, base=0):
    args = (jnp.asarray(ll, jnp.int32), jnp.asarray(ml, jnp.int32),
            jnp.asarray(off, jnp.int32), jnp.int32(n_cmds),
            jnp.int32(block_len))
    want = np.asarray(_expand_by_search(*args, out_size, base=base))
    np.testing.assert_array_equal(np.asarray(got), want)
    twin = dpth.expand_pointers_np(ll[:n_cmds], ml[:n_cmds],
                                   np.asarray(off[:n_cmds], np.int64),
                                   block_len, base=base)
    np.testing.assert_array_equal(np.asarray(got)[:block_len], twin)
    assert (np.asarray(got)[block_len:] == -1).all()


_EXPAND_CASES = ["zero_length", "padding", "short_block", "overlap",
                 "global_base", "planes2", "planes4", "planes8",
                 "ra_vmap", "global_ref"]


@pytest.mark.parametrize("case", _EXPAND_CASES)
def test_expand_pointers_bit_exact(case):
    """`expand_pointers` (scatter + prefix sum) gives the same pointers as
    a binary search over the command ends and as the numpy twin, and the
    decodes built on it give the byte-serial LZ77 output."""
    rng = np.random.default_rng(_EXPAND_CASES.index(case))
    if case in ("zero_length", "overlap", "planes2"):
        ll, ml, off = _random_commands(rng, 300,
                                       overlap=0.9 if case == "overlap"
                                       else 0.3)
        n, blen = 300, int((ll + ml).sum())
        # a full block ends exactly at `out_size`
        out_size = blen if case == "zero_length" else \
            1 << int(np.ceil(np.log2(blen)))
        if case == "planes2":
            # the decoder's path: byte planes -> u16 fields
            ncj = jnp.asarray([n], jnp.int32)
            ll, ml, off = (np.asarray(_u16_from_planes(
                _to_planes(x, n, n, 2), ncj, n))[0] for x in (ll, ml, off))
        got = ref.expand_pointers(jnp.asarray(ll), jnp.asarray(ml),
                                  jnp.asarray(off), n, blen, out_size)
        _check_pointers(got, ll, ml, off, n, blen, out_size)
    elif case in ("padding", "short_block"):
        ll, ml, off = _random_commands(rng, 256)
        n = 200 if case == "padding" else 256
        blen = int((ll[:n] + ml[:n]).sum())
        # "padding": a full block with fewer commands than slots
        out_size = blen if case == "padding" else \
            4 * (1 << int(np.ceil(np.log2(blen))))
        # padding slots hold garbage: only n_cmds bounds the stream
        ll[n:], ml[n:], off[n:] = 7, 9, 123456
        got = ref.expand_pointers(jnp.asarray(ll), jnp.asarray(ml),
                                  jnp.asarray(off), n, blen, out_size)
        _check_pointers(got, ll, ml, off, n, blen, out_size)
    elif case in ("global_base", "planes4", "planes8"):
        start = {"global_base": 5000, "planes4": 0, "planes8": 3000}[case]
        n = 9000 if case == "planes4" else 400
        ll, ml, off = _random_commands(rng, n, start=start)
        blen = int((ll + ml).sum())
        out_size = 1 << int(np.ceil(np.log2(blen)))
        base = start
        if case == "planes4":
            # block-local offsets past 0xFFFF: the 4-plane archives
            assert blen > 0x10000 and off.max() > 0xFFFF
            off = np.asarray(_u32_from_planes(
                _to_planes(off, n, n, 4), jnp.asarray([n]), n))[0]
        elif case == "planes8":
            # absolute 64-bit offsets past 2^32, rebased against the
            # window's base with the decoder's i32 wraparound
            win = (5 << 32) - 1000
            lo32 = _u64lo_from_planes(_to_planes(off + win, n, n, 8),
                                      jnp.asarray([n]), n)[0]
            off = np.asarray(lo32 - jnp.int32(win - (5 << 32)))
        got = ref.expand_pointers(jnp.asarray(ll), jnp.asarray(ml),
                                  jnp.asarray(off), n, blen, out_size,
                                  base=base)
        _check_pointers(got, ll, ml, off, n, blen, out_size, base=base)
    elif case == "ra_vmap":
        B, C, out_size = 5, 220, 4096
        ll, ml, off = np.zeros((3, B, C), np.int64)
        n = np.array([220, 180, 1, 150, 200])
        lits = np.zeros((B, out_size), np.uint8)
        want = np.zeros((B, out_size), np.uint8)
        for b in range(B):
            ll[b, :n[b]], ml[b, :n[b]], off[b, :n[b]] = \
                _random_commands(rng, n[b])
            lits[b] = rng.integers(0, 256, out_size)
            _lz_fill(want[b], ll[b, :n[b]], ml[b, :n[b]], off[b, :n[b]],
                     lits[b], 0)
        blen = (ll + ml).sum(1)
        a = [jnp.asarray(x, jnp.int32) for x in (ll, ml, off, n, blen)]
        ptr = jax.vmap(lambda *r: ref.expand_pointers(*r, out_size))(*a)
        for b in range(B):
            _check_pointers(ptr[b], ll[b], ml[b], off[b], n[b], blen[b],
                            out_size)
        out = ref.lz77_decode_blocks_ref(*a[:4], jnp.asarray(lits), a[4],
                                         out_size)
        for b in range(B):
            np.testing.assert_array_equal(np.asarray(out[b, :blen[b]]),
                                          want[b, :blen[b]])
    else:                                                # global_ref
        B, C, out_size = 4, 200, 4096
        ll, ml, off = np.zeros((3, B, C), np.int64)
        n = np.array([200, 150, 120, 180])
        lits = rng.integers(0, 256, (B, out_size)).astype(np.uint8)
        want = np.zeros(B * out_size, np.uint8)
        starts, pos = [], 0
        for b in range(B):
            starts.append(pos)
            ll[b, :n[b]], ml[b, :n[b]], off[b, :n[b]] = \
                _random_commands(rng, n[b], start=pos)
            pos = _lz_fill(want, ll[b, :n[b]], ml[b, :n[b]],
                           off[b, :n[b]], lits[b], pos)
        blen = (ll + ml).sum(1)
        bstart = np.asarray(starts)
        for b in range(B):
            got = ref.expand_pointers(
                *(jnp.asarray(x[b], jnp.int32) for x in (ll, ml, off)),
                n[b], blen[b], out_size, base=bstart[b])
            _check_pointers(got, ll[b], ml[b], off[b], n[b], blen[b],
                            out_size, base=bstart[b])
        lit_base = jnp.arange(B, dtype=jnp.int32) * out_size
        flat = ref.lz77_decode_global_ref(
            *(jnp.asarray(x, jnp.int32) for x in (ll, ml, off, n)),
            jnp.asarray(lits), lit_base, jnp.asarray(bstart, jnp.int32),
            jnp.asarray(blen, jnp.int32), out_size, B * out_size)
        np.testing.assert_array_equal(np.asarray(flat)[:pos], want[:pos])


def test_fold_matches_remainder():
    """`k mod d` of the match fold, in f32 with corrections, equals the
    integer remainder for every match offset a u16 length allows and
    distances up to 2**31 - 1."""
    k = jnp.arange(1 << 16, dtype=jnp.int32)[None, :]
    rng = np.random.default_rng(0)
    d = np.concatenate([np.arange(1, 513), rng.integers(1, 1 << 16, 512),
                        2 ** np.arange(9, 31), 2 ** np.arange(9, 32) - 1,
                        rng.integers(1 << 16, 2 ** 31 - 1, 64)])
    d = jnp.asarray(d, jnp.int32)[:, None]
    np.testing.assert_array_equal(np.asarray(ref._fold(k, d)),
                                  np.asarray(jnp.remainder(k, d)))
    assert (np.asarray(ref._fold(-k[:, 1:], d)) == 0).all()


def _primitives(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn.primitive.name
        for p in eqn.params.values():
            for sub in (p if isinstance(p, (list, tuple)) else (p,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _primitives(sub)


def test_expand_pointers_has_no_loop():
    """At a 1 MiB block with a real archive's command count, command
    expansion is a scatter and a prefix sum: no `while` or `scan` (a
    binary search per byte would be one)."""
    C = 27_136
    c = jax.ShapeDtypeStruct((C,), jnp.int32)
    s = jax.ShapeDtypeStruct((), jnp.int32)
    jaxpr = jax.make_jaxpr(
        lambda a, b, o, n, L: ref.expand_pointers(a, b, o, n, L, 1 << 20)
    )(c, c, c, s, s)
    prims = set(_primitives(jaxpr.jaxpr))
    assert not prims & {"while", "scan"}, prims
    assert {"scatter-add", "cumsum"} <= prims, prims
