"""Device-resident ACEAPEX decode (paper §3).

Two modes, kept distinct exactly as the paper insists (§3.1):

  Mode 1 ("host-entropy"): entropy decode on the host (numpy), match
      resolution on device — the open `aceapex_cuda`-equivalent path.
  Mode 2 ("device"): entropy *and* match resolution on device, archive
      arrays resident in device memory — the full device-resident pipeline.

Both decode an arbitrary contiguous block range (position-invariant random
access, §4): the unit of work is a *block selection*, and whole-file decode
is simply the selection [0, n_blocks).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from repro import trace
from repro.core import depth as dpth
from repro.core import entropy as ent
from repro.core.format import (FNV_OFFSET, N_STREAMS, S_COMMANDS, S_LENGTHS,
                               S_LITERALS, S_OFFSETS, Archive, MAX_LANES,
                               file_digest)


# Most blocks one whole-archive decode launch takes. Compiled for a TPU
# v5e, a launch's temp memory grows with it (6.5 GB at 3405 x 64 KiB
# blocks, 1 GB at 512) and so, unevenly, does its compile time (~100 s
# at 3405 blocks, ~20 s at 512), so `decode_all` walks an "ra" archive
# in launches of this many blocks.
MAX_LAUNCH_BLOCKS = 512

# f32 represents every integer up to this exactly (24-bit significand).
F32_EXACT_INTS = 1 << 24


class BlockDigestError(ValueError):
    """A decoded block's FNV-1a-64 digest does not match the archive's."""


def _pad_pow2(ids: np.ndarray, fill=None) -> np.ndarray:
    """Pad a request batch to the next power of two (bounded jit variants);
    pad slots repeat the last element — so they add no unique blocks —
    unless an explicit `fill` is given (e.g. an out-of-range sentinel)."""
    n = ids.size
    cap = 1 << max(0, n - 1).bit_length() if n > 1 else 1
    if cap == n:
        return ids
    return np.concatenate(
        [ids, np.full(cap - n, ids[-1] if fill is None else fill,
                      ids.dtype)])


def _check_window_bytes(first: int, last: int, block_size: int) -> None:
    """Both global window decodes (mode 1 and mode 2) resolve matches in
    ONE flat int32 pointer space — a window spanning >= 2 GiB must be a
    loud error, not silent position overflow."""
    if (last - first + 1) * block_size >= 2**31:
        raise ValueError(
            f"decode window [{first}, {last}] spans "
            f"{(last - first + 1) * block_size} bytes >= 2 GiB — the flat "
            f"pointer space is int32; decode narrower ranges (or re-encode "
            f"with a smaller anchor_interval)")


# --------------------------------------------------------------- device form
@dataclasses.dataclass
class DeviceArchive:
    """The compressed archive resident in device memory (jnp arrays) plus the
    static decode geometry (python ints — jit-static per archive)."""
    words: jnp.ndarray          # u16[W]
    word_off: jnp.ndarray       # i32[n_blocks, 4]
    n_syms: jnp.ndarray         # i32[n_blocks, 4]
    lanes: jnp.ndarray          # i32[n_blocks, 4]
    n_cmds: jnp.ndarray         # i32[n_blocks]
    block_start: jnp.ndarray    # i32[n_blocks] — low 32 bits of the 64-bit
                                # absolute starts (wraparound semantics:
                                # window rebasing subtracts in i32, which
                                # is exact for any base because windows
                                # span < 2^31 bytes)
    block_len: jnp.ndarray      # i32[n_blocks]
    freqs: np.ndarray           # host (tables are rebuilt on device per call)
    block_size: int
    n_blocks: int
    raw_size: int
    mode: str
    entropy: str
    max_cmds: int               # static padding geometry
    t_max_lit: int              # max rANS steps, literal streams
    t_max_cmd: int              # max rANS steps, plane streams
    offset_bytes: int
    anchor_interval: int = 0    # wavefront restart spacing (0 = anchor-free)
    anchors: Optional[np.ndarray] = None   # host i64 anchor block ids
    max_depth: Optional[int] = None  # archive-wide resolve-round bound
                                 # (jit-static; None = legacy depth-free)
    block_depth: Optional[np.ndarray] = None  # host i32 per-block depths

    @property
    def device_bytes(self) -> int:
        tot = 0
        for f in (self.words, self.word_off, self.n_syms, self.lanes,
                  self.n_cmds, self.block_start, self.block_len):
            tot += f.size * f.dtype.itemsize
        return tot


def to_device(a: Archive) -> DeviceArchive:
    def tmax(col_mask):
        n = a.n_syms[:, col_mask].astype(np.int64)
        k = np.maximum(a.lanes[:, col_mask].astype(np.int64), 1)
        t = np.where(n > 0, -(-n // k), 0)
        return int(t.max(initial=0))

    lit_cols = np.array([S_LITERALS])
    cmd_cols = np.array([S_LENGTHS, S_OFFSETS, S_COMMANDS])
    if (a.mode == "global" and np.asarray(a.anchors).size == 0
            and a.raw_size >= 2**31):
        # anchor-free wavefront decode materializes ONE raw_size-byte flat
        # pointer space — past 2 GiB that cannot fit int32 positions, and
        # before this guard the offsets silently truncated to 31 bits
        raise ValueError(
            f"anchor-free global archive spans {a.raw_size} bytes >= 2 GiB"
            f" — whole-prefix decode needs an int32 flat pointer space; "
            f"re-encode with anchor_interval to bound decode windows")
    return DeviceArchive(
        words=jnp.asarray(a.words),
        word_off=jnp.asarray(a.word_off.astype(np.int32)),
        n_syms=jnp.asarray(a.n_syms),
        lanes=jnp.asarray(a.lanes),
        n_cmds=jnp.asarray(a.n_cmds),
        # astype(int32) keeps the LOW 32 BITS (wraparound) — exactly what
        # window-relative i32 rebasing needs for archives past 2 GiB
        block_start=jnp.asarray(a.block_start.astype(np.int32)),
        block_len=jnp.asarray(a.block_len),
        freqs=np.asarray(a.freqs),
        block_size=int(a.block_size),
        n_blocks=int(a.n_blocks),
        raw_size=int(a.raw_size),
        mode=a.mode,
        entropy=a.entropy,
        max_cmds=int(a.n_cmds.max(initial=1)),
        t_max_lit=tmax(lit_cols),
        t_max_cmd=tmax(cmd_cols),
        offset_bytes=int(a.offset_bytes),
        anchor_interval=int(a.anchor_interval),
        anchors=np.asarray(a.anchors, np.int64),
        max_depth=a.max_depth,
        block_depth=(np.asarray(a.block_depth, np.int32)
                     if a.block_depth is not None else None),
    )


# ------------------------------------------------------------ stream extract
def _linearize(rows: jnp.ndarray, n: jnp.ndarray, k: jnp.ndarray,
               out_len: int, k_max: int = MAX_LANES) -> jnp.ndarray:
    """rows (B, T*k_max) step-major rANS output → (B, out_len) linear bytes.

    Symbol i lives at (i // K) * k_max + (i % K); i >= n → 0.

    Up to 2^24 bytes the quotient is taken in f32 and corrected by one:
    the TPU compiler spends over 100 s on an s32 divide of a large iota
    by a per-row divisor (256 x 64 KiB), and f32 holds every i < 2^24
    exactly, so the rounded quotient is off by at most one. Longer
    streams (blocks of 16 MiB and more) keep the s32 divide; the choice
    is made by the static `out_len`.
    """
    i = jnp.arange(out_len, dtype=jnp.int32)[None, :]
    k = jnp.maximum(k, 1)[:, None]
    if out_len > F32_EXACT_INTS:
        q = i // k
    else:
        q = jnp.floor(i.astype(jnp.float32) / k.astype(jnp.float32)
                      ).astype(jnp.int32)
        r = i - q * k
        q = jnp.where(r < 0, q - 1, jnp.where(r >= k, q + 1, q))
    idx = q * k_max + (i - q * k)
    idx = jnp.clip(idx, 0, rows.shape[1] - 1)
    vals = jnp.take_along_axis(rows, idx, axis=1)
    return jnp.where(i < n[:, None], vals, 0).astype(jnp.uint8)


def _u16_from_planes(planes: jnp.ndarray, n_cmds: jnp.ndarray,
                     max_cmds: int) -> jnp.ndarray:
    """planes (B, 2*max_cmds) = [lo plane | hi plane] → (B, max_cmds) i32."""
    lo = planes[:, :max_cmds].astype(jnp.int32)
    hi_idx = jnp.minimum(n_cmds[:, None] + jnp.arange(max_cmds)[None, :],
                         planes.shape[1] - 1)
    hi = jnp.take_along_axis(planes.astype(jnp.int32), hi_idx, axis=1)
    j = jnp.arange(max_cmds, dtype=jnp.int32)[None, :]
    v = lo | (hi << 8)
    return jnp.where(j < n_cmds[:, None], v, 0)


def _planes_lo32(planes: jnp.ndarray, n_cmds: jnp.ndarray, max_cmds: int,
                 mask_top: bool) -> jnp.ndarray:
    """First-4-plane little-endian word → (B, max_cmds) i32; `mask_top`
    clears bit 31 (positive addresses) vs keeping the full low 32 bits
    (wraparound semantics)."""
    nc = n_cmds[:, None]
    j = jnp.arange(max_cmds, dtype=jnp.int32)[None, :]
    v = jnp.zeros(planes.shape[:1] + (max_cmds,), jnp.int32)
    for b in range(4):
        idx = jnp.minimum(b * nc + j, planes.shape[1] - 1)
        byte = jnp.take_along_axis(planes.astype(jnp.int32), idx, axis=1)
        if b == 3 and mask_top:
            byte = byte & 0x7F
        v = v | (byte << (8 * b))
    return jnp.where(j < nc, v, 0)


def _u32_from_planes(planes: jnp.ndarray, n_cmds: jnp.ndarray,
                     max_cmds: int) -> jnp.ndarray:
    """First-4-plane little-endian u32 → (B, max_cmds) i32 (top bit masked:
    device decode addresses stay < 2^31). Decodes the 4-plane block-local
    offsets of `offset_bytes=4` archives (block_size > 0xFFFF, where two
    planes would truncate)."""
    return _planes_lo32(planes, n_cmds, max_cmds, mask_top=True)


def _u64lo_from_planes(planes: jnp.ndarray, n_cmds: jnp.ndarray,
                       max_cmds: int) -> jnp.ndarray:
    """8-plane global offsets → FULL low 32 bits as i32 (wraparound
    semantics, byte 3 NOT masked). The match phase rebases these against
    the decode window's base with an i32 wraparound subtraction; since
    the anchor guarantee bounds every match source to its window and
    windows span < 2^31 bytes, `(off_lo32 - base_lo32) mod 2^32` equals
    the true 64-bit difference — archives whose windows start past 2 GiB
    rebase exactly instead of truncating to 31 bits first (which
    corrupted them silently)."""
    return _planes_lo32(planes, n_cmds, max_cmds, mask_top=False)


def _entropy_decode_sel(da: DeviceArchive, sel: jnp.ndarray, backend: str):
    """rANS/raw decode of the 4 streams of each selected block.

    Returns dict of per-block linearized stream bytes:
      literals (B, block_size), lengths (B, 2*max_cmds),
      offsets (B, off_planes*max_cmds), commands (B, 2*max_cmds)
    """
    B = sel.shape[0]
    woff = da.word_off[sel]          # (B, 4)
    nsym = da.n_syms[sel]
    lanes = da.lanes[sel]
    off_planes = da.offset_bytes     # one plane per offset byte (2 | 4 | 8)

    if da.entropy == "raw":
        def unpack(col, out_len):
            def one(off, n):
                nw = (out_len + 1) // 2
                idx = off + jnp.arange(nw, dtype=jnp.int32)
                idx = jnp.clip(idx, 0, da.words.shape[0] - 1)
                w = da.words[idx].astype(jnp.uint16)
                b = jnp.stack([w & 0xFF, w >> 8], axis=1).reshape(-1)
                i = jnp.arange(out_len, dtype=jnp.int32)
                return jnp.where(i < n, b[:out_len], 0).astype(jnp.uint8)
            return jax.vmap(one)(woff[:, col], nsym[:, col])
        return {
            "literals": unpack(S_LITERALS, da.block_size),
            "lengths": unpack(S_LENGTHS, 2 * da.max_cmds),
            "offsets": unpack(S_OFFSETS, off_planes * da.max_cmds),
            "commands": unpack(S_COMMANDS, 2 * da.max_cmds),
        }

    from repro.kernels import ops
    # flatten: stream index = block-major, stream-minor
    flat_off = woff.reshape(-1)
    flat_nsym = nsym.reshape(-1)
    flat_lanes = lanes.reshape(-1)
    cls = jnp.tile(jnp.arange(N_STREAMS, dtype=jnp.int32), B)
    t_max = max(da.t_max_lit, da.t_max_cmd)
    with jax.named_scope(trace.DECODE_RANS):
        rows, _ = ops.rans_decode(
            da.words, flat_off, flat_nsym, flat_lanes, cls, da.freqs,
            t_max=t_max, backend=backend)
    rows = rows.reshape(B, N_STREAMS, -1)

    def lin(col, out_len):
        return _linearize(rows[:, col], nsym[:, col], lanes[:, col], out_len)

    with jax.named_scope(trace.DECODE_LINEARIZE):
        return {
            "literals": lin(S_LITERALS, da.block_size),
            "lengths": lin(S_LENGTHS, 2 * da.max_cmds),
            "offsets": lin(S_OFFSETS, off_planes * da.max_cmds),
            "commands": lin(S_COMMANDS, 2 * da.max_cmds),
        }


def _entropy_decode_host(a: Archive, sel: np.ndarray):
    """Mode 1: entropy decode on the host (numpy oracle), return device-ready
    per-block stream bytes."""
    B = len(sel)
    idx = (np.asarray(sel)[:, None] * N_STREAMS
           + np.arange(N_STREAMS)[None, :]).reshape(-1)
    woff = a.word_off.reshape(-1)[idx]
    nsym = a.n_syms.reshape(-1)[idx]
    lanes = a.lanes.reshape(-1)[idx]
    cls = np.tile(np.arange(N_STREAMS, dtype=np.int32), B)
    if a.entropy == "raw":
        streams = []
        for o, n in zip(woff, nsym):
            nw = (int(n) + 1) // 2
            w = a.words[int(o):int(o) + nw]
            b = np.stack([w & 0xFF, w >> 8], axis=1).reshape(-1).astype(np.uint8)
            streams.append(b[:int(n)])
    else:
        streams = ent.rans_decode_batch_np(a.words, woff, nsym, lanes, cls,
                                           a.freqs)
    max_cmds = int(a.n_cmds.max(initial=1))
    off_planes = a.offset_bytes

    def pad_to(arr, L):
        out = np.zeros(L, np.uint8)
        out[:min(arr.size, L)] = arr[:L]
        return out

    lits = np.stack([pad_to(streams[i * N_STREAMS + S_LITERALS], a.block_size)
                     for i in range(B)])
    lens = np.stack([pad_to(streams[i * N_STREAMS + S_LENGTHS], 2 * max_cmds)
                     for i in range(B)])
    offs = np.stack([pad_to(streams[i * N_STREAMS + S_OFFSETS],
                            off_planes * max_cmds) for i in range(B)])
    cmds = np.stack([pad_to(streams[i * N_STREAMS + S_COMMANDS], 2 * max_cmds)
                     for i in range(B)])
    return {"literals": jnp.asarray(lits), "lengths": jnp.asarray(lens),
            "offsets": jnp.asarray(offs), "commands": jnp.asarray(cmds)}


# ------------------------------------------------------------------- decode
def _match_phase(da_mode: str, streams, n_cmds, block_len, block_start,
                 block_size: int, max_cmds: int, backend: str,
                 offset_bytes: int, total_size: Optional[int] = None,
                 win_base=0, n_rounds: Optional[int] = None):
    """`n_rounds` is the archive's recorded chain depth (jit-static):
    every resolver below runs exactly that many doubling rounds. None =
    legacy depth-free archive — the ref resolver early-exits via
    while_loop, pallas falls back to log2(block)."""
    from repro.kernels import ops, ref
    with jax.named_scope(trace.DECODE_EXPAND):
        lit_lens = _u16_from_planes(streams["commands"], n_cmds, max_cmds)
        match_lens = _u16_from_planes(streams["lengths"], n_cmds, max_cmds)
        if offset_bytes == 2:
            offsets = _u16_from_planes(streams["offsets"], n_cmds, max_cmds)
        elif offset_bytes == 4:
            # 4-plane block-local offsets ("ra", block_size > 0xFFFF)
            offsets = _u32_from_planes(streams["offsets"], n_cmds, max_cmds)
        else:
            # 8-plane global offsets: full low-32-bit word, wraparound
            # semantics — rebased below BEFORE any narrowing, so windows
            # starting past 2 GiB resolve exactly
            offsets = _u64lo_from_planes(streams["offsets"], n_cmds,
                                         max_cmds)

    if da_mode == "ra":
        return ops.lz77_decode_blocks(
            lit_lens, match_lens, offsets, n_cmds, streams["literals"],
            block_len, out_size=block_size, backend=backend,
            n_rounds=n_rounds)
    # global/wavefront: one flat pointer space rooted at `win_base` — the
    # low 32 bits of the decode window's absolute byte start (block 0's
    # start when anchor-free). Anchor archives guarantee every match
    # source >= its window's anchor and windows span < 2^31 bytes, so the
    # i32 wraparound subtraction recovers exact window-relative pointers
    # inside [0, total_size) for ANY 64-bit base. Slots of zero-length
    # commands go out of range after rebasing but are never dereferenced
    # (no output byte maps into an empty match region).
    offsets = offsets - win_base
    B = lit_lens.shape[0]
    lit_base = jnp.arange(B, dtype=jnp.int32) * streams["literals"].shape[1]
    flat = ref.lz77_decode_global_ref(
        lit_lens, match_lens, offsets, n_cmds, streams["literals"],
        lit_base, block_start - win_base, block_len, out_size=block_size,
        total_size=total_size, n_rounds=n_rounds)
    return flat


def _decode_sel_core(arrays, sel, da_meta, backend):
    """Mode-2 block-selection decode (unjitted core — reused by the
    shard_map multi-device path). `da_meta` is the static geometry tuple;
    `arrays` the device archive pytree."""
    (block_size, n_blocks, max_cmds, t_lit, t_cmd, mode, entropy,
     offset_bytes, total_size, freqs_t, max_depth) = da_meta
    freqs_host = np.asarray(freqs_t, np.uint16)
    da = DeviceArchive(
        words=arrays["words"], word_off=arrays["word_off"],
        n_syms=arrays["n_syms"], lanes=arrays["lanes"],
        n_cmds=arrays["n_cmds"], block_start=arrays["block_start"],
        block_len=arrays["block_len"], freqs=freqs_host,
        block_size=block_size, n_blocks=n_blocks, raw_size=0, mode=mode,
        entropy=entropy, max_cmds=max_cmds, t_max_lit=t_lit, t_max_cmd=t_cmd,
        offset_bytes=offset_bytes, max_depth=max_depth)
    streams = _entropy_decode_sel(da, sel, backend)
    # global selections are contiguous decode windows (whole prefix or an
    # anchor window); the window's byte base anchors the flat pointer space
    win_base = da.block_start[sel[0]] if mode == "global" else 0
    return _match_phase(mode, streams, da.n_cmds[sel], da.block_len[sel],
                        da.block_start[sel], block_size, max_cmds, backend,
                        offset_bytes, total_size, win_base=win_base,
                        n_rounds=max_depth)


_decode_sel_jit = partial(jax.jit, static_argnames=("da_meta", "backend"))(
    _decode_sel_core)


# ------------------------------------------------------------ digest verify
def _fnv_mul_u32(hi: jnp.ndarray, lo: jnp.ndarray):
    """(hi, lo) u32 pair × FNV prime (2^40 + 0x1B3) mod 2^64, in 16-bit
    limbs — the device runs without x64, so the 64-bit recurrence is
    emulated on u32 halves."""
    m = jnp.uint32(0x1B3)
    c0 = (lo & 0xFFFF) * m
    c1 = (lo >> 16) * m + (c0 >> 16)
    c2 = (hi & 0xFFFF) * m + (c1 >> 16)
    c3 = (hi >> 16) * m + (c2 >> 16)
    t_lo = (c0 & 0xFFFF) | ((c1 & 0xFFFF) << 16)
    t_hi = (c2 & 0xFFFF) | ((c3 & 0xFFFF) << 16)
    # + (value << 40) mod 2^64: only the low word contributes, shifted
    # into the high word
    return t_hi + (lo << 8), t_lo


@jax.named_scope(trace.VERIFY_FNV)
def _fnv_rows_core(rows: jnp.ndarray, block_len: jnp.ndarray):
    """(B, S) u8 decoded rows → per-row 8-byte-stride FNV-1a-64 as u32
    (hi, lo) pairs: the device twin of `format.fnv1a64_u64_stride`.
    Bytes past block_len are zeroed and the word count is
    ceil(block_len / 8), so the digest matches the host recurrence over
    the exact block payload; the recurrence runs as one lax.scan over
    the word axis, vectorized across the row batch."""
    B, S = rows.shape
    i = jnp.arange(S, dtype=jnp.int32)
    masked = jnp.where(i[None, :] < block_len[:, None], rows, 0)
    pad = (-S) % 8
    if pad:
        masked = jnp.pad(masked, ((0, 0), (0, pad)))
    g = masked.reshape(B, -1, 8).astype(jnp.uint32)
    w_lo = g[..., 0] | (g[..., 1] << 8) | (g[..., 2] << 16) | (g[..., 3] << 24)
    w_hi = g[..., 4] | (g[..., 5] << 8) | (g[..., 6] << 16) | (g[..., 7] << 24)
    n_words = (block_len.astype(jnp.int32) + 7) // 8

    def step(carry, xs):
        hi, lo = carry
        whi, wlo, t = xs
        nhi, nlo = _fnv_mul_u32(hi ^ whi, lo ^ wlo)
        live = t < n_words
        return (jnp.where(live, nhi, hi), jnp.where(live, nlo, lo)), None

    off = int(FNV_OFFSET)
    init = (jnp.full((B,), off >> 32, jnp.uint32),
            jnp.full((B,), off & 0xFFFFFFFF, jnp.uint32))
    W = w_lo.shape[1]
    (fhi, flo), _ = jax.lax.scan(
        step, init, (w_hi.T, w_lo.T, jnp.arange(W, dtype=jnp.int32)))
    return fhi, flo


_fnv_rows_jit = jax.jit(_fnv_rows_core)


class Decoder:
    """Stateful wrapper: archive resident on device, jitted selection decode.

    decode_blocks(sel) → (B, block_size) uint8 (Mode 2, device-resident)
    decode_blocks_host_entropy(sel) → same, Mode 1
    decode_from_anchor(first, last) → anchor-window decode ("global")
    decode_all() / decode_range(lo, hi) → bytes (host copy, convenience)

    `decoded_blocks_last` records how many blocks the most recent decode
    call actually materialized (entropy + match work) — for a checkpointed
    wavefront that is the summed anchor-window sizes, not the prefix.
    """

    def __init__(self, archive: Archive, backend: str = "auto"):
        self.archive = archive
        self.da = to_device(archive)
        self.backend = backend
        self._freqs_host = tuple(map(tuple, np.asarray(archive.freqs)))
        self.arrays = {
            "words": self.da.words, "word_off": self.da.word_off,
            "n_syms": self.da.n_syms, "lanes": self.da.lanes,
            "n_cmds": self.da.n_cmds, "block_start": self.da.block_start,
            "block_len": self.da.block_len,
        }
        self._store_view = None
        self.decoded_blocks_last = 0
        # cumulative over the decoder's life (`decode_info`): host ints
        self._decode_counts = {"launches": 0, "rows": 0, "blocks": 0,
                               "pad_rows": 0}
        # ---- depth-bucketed round schedule (PR 6) ----
        # per-block resolve-round counts, pow2-bucketed archive-wide
        # (core.depth.scheduled_rounds): a selection decodes in one launch
        # per distinct scheduled count, so a shallow selection of a deep
        # archive runs its own bucket's rounds instead of the archive
        # bound. "ra" blocks schedule individually; global/wavefront
        # chains cross blocks, so the schedule is per anchor window (a
        # block inherits its window's bucketed max). None = legacy
        # depth-free archive: every launch keeps the early-exit resolver.
        bd = self.da.block_depth
        if bd is None:
            self._block_rounds = None
        elif self.da.mode == "ra":
            self._block_rounds = dpth.scheduled_rounds(bd)
        else:
            anchors = np.asarray(archive.anchors, np.int64)
            n_blocks = self.da.n_blocks
            win_of = (np.searchsorted(anchors, np.arange(n_blocks),
                                      "right") - 1
                      if anchors.size else np.zeros(n_blocks, np.int64))
            wdepth = np.zeros(int(win_of.max(initial=0)) + 1, np.int64)
            np.maximum.at(wdepth, win_of, bd.astype(np.int64))
            self._block_rounds = dpth.scheduled_rounds(wdepth)[win_of]
        # archives whose blocks all share one scheduled count cannot
        # benefit from bucketing (the single bucket IS the archive bound)
        # — executors read this to skip the host covering-set math
        self.multi_bucket = (self._block_rounds is not None
                             and np.unique(self._block_rounds).size > 1)
        # per decode call: the static n_rounds of every launch it issued,
        # in launch order (None = legacy early-exit launch) — the round
        # instrumentation the scheduling tests and bench histogram read
        self.launch_rounds_last: list = []
        # global mode, opt-in (collect_window_rows=True): the decode
        # records (first_block_id, (L, block_size) rows) per anchor
        # window it materialized, so the BlockCache can co-install them
        # into free slots — a window miss warms every sibling block the
        # decode already paid for. Off by default: retaining whole
        # decoded windows on device costs real memory, and only the
        # cache path ever consumes them.
        self.collect_window_rows = False
        self.last_window_rows: list = []
        # ---- detect → recover → degrade state (PR 10) ----
        # blocks proven unrecoverable under on_error="partial": never
        # re-decoded, never cache-installed; "raise"/"repair" requests
        # that touch them fail immediately
        self.quarantined: set = set()
        self._recover = {"reconstructed": 0, "retries": 0,
                         "unrecoverable": 0}
        # global block ids that failed (quarantined or zeroed) in the
        # most recent decode call — callers (cache invalidation,
        # per-address outcomes) read this right after the call
        self.last_bad_blocks = np.zeros(0, np.int64)
        # blocks that failed INITIAL verification in the most recent call
        # even if later repaired — window rows collected before the
        # repair pass may hold their pre-repair garbage, so the cache
        # co-install path must skip them
        self.last_suspect_blocks = np.zeros(0, np.int64)
        # fault-injection hook: called once at the top of every decode
        # call when armed (repro.resilience.faults.FaultInjector)
        self.fault_hook = None

    def _api_store(self):
        """Store-shaped adapter over this decoder so the host APIs ride the
        query plane without duplicating the device archive (lazy import:
        repro.api imports this module)."""
        if self._store_view is None:
            from repro.api.executors import DeviceExecutor, _DecoderStore
            from repro.api.plan import QueryPlanner
            self._store_view = _DecoderStore(self)
            self._store_view.planner = QueryPlanner(self._store_view)
            self._store_view.executor = DeviceExecutor(self._store_view)
        return self._store_view

    def _meta(self, n_sel: int, total: Optional[int] = None,
              n_rounds: Optional[int] = -1):
        """Static geometry tuple for a decode launch. `n_rounds` overrides
        the resolve-round count of THIS launch (the depth-bucketed
        schedule); the default sentinel keeps the archive-wide bound."""
        da = self.da
        if total is None:
            total = da.n_blocks * da.block_size if da.mode == "global" \
                else None
        rounds = da.max_depth if n_rounds == -1 else n_rounds
        return (da.block_size, da.n_blocks, da.max_cmds, da.t_max_lit,
                da.t_max_cmd, da.mode, da.entropy, da.offset_bytes, total,
                self._freqs_host, rounds)

    def decode_info(self) -> dict:
        """Cumulative Mode-2 decode counters: `launches` (dispatches of
        the jitted selection decode), `rows` (rows they materialized),
        `blocks` (distinct real blocks among them, per launch) and
        `pad_rows` (rows that repeat a block of their launch: the pow2
        padding of the cache's miss batch and of the depth groups)."""
        return dict(self._decode_counts)

    def _count_launch(self, ids: np.ndarray) -> None:
        n, distinct = int(ids.size), int(np.unique(ids).size)
        c = self._decode_counts
        c["launches"] += 1
        c["rows"] += n
        c["blocks"] += distinct
        c["pad_rows"] += n - distinct

    def _launch(self, sel_np: np.ndarray, meta: tuple) -> jnp.ndarray:
        """One `_decode_sel_jit` dispatch of the block ids `sel_np`,
        spanned and counted; `meta` is `_meta(...)`, whose last field is
        the launch's resolve rounds."""
        with trace.span(trace.DECODE_LAUNCH, rows=int(sel_np.size),
                        rounds=meta[-1]):
            out = _decode_sel_jit(self.arrays, jnp.asarray(sel_np, jnp.int32),
                                  meta, self.backend)
        self._count_launch(sel_np)
        return out

    # ------------------------------------------------- depth-bucket schedule
    @property
    def block_rounds(self) -> Optional[np.ndarray]:
        """i32[n_blocks] scheduled resolve rounds per block (pow2 depth
        buckets; global blocks inherit their anchor window's schedule), or
        None for legacy depth-free archives."""
        return self._block_rounds

    def _rounds_for_span(self, first: int, last: int) -> Optional[int]:
        """Scheduled rounds for a contiguous window decode [first, last]:
        the max over its blocks (== over its anchor windows)."""
        if self._block_rounds is None:
            return self.da.max_depth        # None: legacy early-exit
        return int(self._block_rounds[first:last + 1].max(initial=0))

    def _ra_groups(self, sel_np: np.ndarray) -> Optional[list]:
        """Partition an "ra" selection by scheduled rounds: [(n_rounds,
        idx-into-sel)] ascending. None = no bucketing possible or useful
        (legacy archive, empty selection, or one group already at the
        archive-wide bound — the existing single-launch path is
        identical then)."""
        if self._block_rounds is None or sel_np.size == 0:
            return None
        r = self._block_rounds[sel_np]
        vals = np.unique(r)
        if vals.size == 1 and int(vals[0]) == (self.da.max_depth or 0):
            return None
        return [(int(v), np.flatnonzero(r == v)) for v in vals]

    def check_digests(self, sel, got: np.ndarray) -> None:
        """Compare computed u64 digests against the archive's `block_fnv`
        table at global block ids `sel`; raises `BlockDigestError` naming
        the first mismatching block. Split out of `verify_rows` so paths
        that compute digests elsewhere (the sharded stacked decode checks
        them shard-locally before assembly) raise the same error with the
        TRUE block id."""
        sel = np.asarray(sel, np.int64).reshape(-1)
        got = np.asarray(got, np.uint64).reshape(-1)
        if sel.size == 0:
            return
        want = self.archive.block_fnv[sel]
        bad = np.flatnonzero(got != want)
        if bad.size:
            b = int(sel[bad[0]])
            raise BlockDigestError(
                f"block {b} digest mismatch: decoded "
                f"{int(got[bad[0]]):#018x} != stored "
                f"{int(want[bad[0]]):#018x} "
                f"({bad.size} of {sel.size} selected blocks corrupt)")

    def verify_rows(self, sel, rows: jnp.ndarray) -> None:
        """Recompute each decoded row's 8-byte-stride FNV-1a-64 on device
        and compare against the archive's `block_fnv` table; raises
        `BlockDigestError` naming the first mismatching block."""
        sel = np.asarray(sel).reshape(-1)
        if sel.size == 0:
            return
        self.check_digests(sel, self._row_digests(sel, rows))

    def _row_digests(self, sel: np.ndarray, rows: jnp.ndarray) -> np.ndarray:
        """Device FNV over decoded rows → host u64 digests (one per row)."""
        fhi, flo = _fnv_rows_jit(
            rows, jnp.asarray(self.archive.block_len[sel]))
        return ((np.asarray(fhi).astype(np.uint64) << np.uint64(32))
                | np.asarray(flo).astype(np.uint64))

    # ------------------------------------------- recover / degrade (PR 10)
    def recover_info(self) -> dict:
        """Cumulative recovery counters: `reconstructed` (blocks healed
        by parity + re-verified bit-perfect), `retries` (recovery decode
        passes), `unrecoverable` (blocks that stayed corrupt after
        reconstruction), `quarantined` (currently quarantined blocks)."""
        info = dict(self._recover)
        info["quarantined"] = len(self.quarantined)
        return info

    def heal_blocks(self, bad) -> np.ndarray:
        """Parity-reconstruct the payloads of `bad` on device (lazy import:
        repro.resilience imports nothing from this module, but core stays
        importable without it on the hot path)."""
        from repro.resilience.parity import reconstruct_blocks
        return reconstruct_blocks(self, bad)

    def _verify_or_recover(self, sel: np.ndarray, rows: jnp.ndarray,
                           on_error: str, redecode) -> jnp.ndarray:
        """Digest-check decoded `rows`; on mismatch, run the detect →
        recover → degrade loop per `on_error`. `redecode(blocks)` must
        return fresh unverified rows for global block ids `blocks`.

        Recovery iterates because corruption is not always where the
        digest fails: in "global" mode a corrupt payload poisons every
        downstream block of its anchor window (the match chain), so only
        the EARLIEST failing block per window is a reconstruction target
        each pass — healing it and re-decoding clears the downstream
        failures (or exposes the next true corruption). "ra" blocks are
        independent, so every failing block is a target at once. The
        loop stops when clean, when the bad set stops shrinking (e.g.
        two corruptions in one parity group reconstruct to garbage), or
        when the archive carries no parity."""
        sel = np.asarray(sel, np.int64).reshape(-1)
        if sel.size == 0:
            return rows
        got = self._row_digests(sel, rows)
        want = self.archive.block_fnv[sel]
        badpos = np.flatnonzero(got != want)
        if badpos.size == 0:
            return rows
        if on_error == "raise":
            self.check_digests(sel, got)        # raises BlockDigestError
        bad = np.unique(sel[badpos])
        self.last_suspect_blocks = np.union1d(self.last_suspect_blocks, bad)
        for _ in range(int(bad.size)):
            if self.da.mode == "global":
                targets = np.asarray(
                    [int(bad[idx].min()) for _, _, idx
                     in self._anchor_groups(bad)], np.int64)
            else:
                targets = bad
            if self.heal_blocks(targets).size == 0:
                break                           # no parity in the archive
            self._recover["retries"] += 1
            new_rows = redecode(bad)
            ok = (self._row_digests(bad, new_rows)
                  == self.archive.block_fnv[bad])
            fixed = set(bad[ok].tolist())
            self._recover["reconstructed"] += int(
                sum(int(t) in fixed for t in targets))
            if fixed:
                pos_in_bad = {int(b): i for i, b in enumerate(bad)}
                fix_sel = np.asarray(
                    [i for i in badpos if int(sel[i]) in fixed], np.int64)
                src = np.asarray([pos_in_bad[int(sel[i])] for i in fix_sel],
                                 np.int64)
                rows = rows.at[fix_sel].set(new_rows[src])
                badpos = np.asarray(
                    [i for i in badpos if int(sel[i]) not in fixed],
                    np.int64)
            new_bad = bad[~ok]
            if new_bad.size == 0 or new_bad.size >= bad.size:
                bad = new_bad
                break
            bad = new_bad
        if bad.size:
            self._recover["unrecoverable"] += int(bad.size)
            self.last_bad_blocks = np.union1d(self.last_bad_blocks, bad)
            if on_error == "repair":
                why = ("archive carries no parity"
                       if not self.archive.parity_group else
                       "reconstruction re-verify failed (sibling or "
                       "digest-table corruption)")
                raise BlockDigestError(
                    f"blocks {bad.tolist()} unrecoverable: {why}")
            self.quarantined.update(int(b) for b in bad)
            if badpos.size:
                rows = rows.at[jnp.asarray(badpos)].set(0)
        return rows

    def _run_decode(self, raw, sel, verify: bool, pad_groups: bool,
                    on_error: str) -> jnp.ndarray:
        """Shared decode entry: on_error validation, fault-injection
        hook, quarantine pre-filter, then `raw(sel_np, pad_groups)` and
        the verify/recover tail."""
        from repro.resilience import check_on_error
        check_on_error(on_error)
        sel_np = np.asarray(sel, np.int64).reshape(-1)
        self.last_bad_blocks = np.zeros(0, np.int64)
        self.last_suspect_blocks = np.zeros(0, np.int64)
        self.launch_rounds_last = []
        if self.fault_hook is not None:
            self.fault_hook()
        keep = None
        quar = np.zeros(0, np.int64)
        work = sel_np
        if self.quarantined and sel_np.size:
            qmask = np.isin(sel_np, np.fromiter(self.quarantined, np.int64,
                                                len(self.quarantined)))
            if qmask.any():
                if on_error != "partial":
                    b = int(sel_np[qmask][0])
                    raise BlockDigestError(
                        f"block {b} is quarantined (unrecoverable in an "
                        f"earlier decode); on_error='partial' degrades "
                        f"instead of raising")
                keep = np.flatnonzero(~qmask)
                quar = np.unique(sel_np[qmask])
                work = sel_np[keep]
        if work.size:
            rows = raw(work, pad_groups)
            if verify:
                rows = self._verify_or_recover(
                    work, rows, on_error,
                    lambda b: raw(np.asarray(b, np.int64).reshape(-1),
                                  pad_groups))
        else:
            rows = jnp.zeros((0, self.da.block_size), jnp.uint8)
        if keep is not None:
            full = jnp.zeros((sel_np.size, self.da.block_size), jnp.uint8)
            if keep.size:
                full = full.at[jnp.asarray(keep)].set(rows)
            rows = full
            self.last_bad_blocks = np.union1d(self.last_bad_blocks, quar)
        return rows

    # ---------------------------------------------------- window decode
    def _window_rows(self, first: int, last: int) -> jnp.ndarray:
        """Mode-2 decode of the contiguous global window [first, last]:
        (last-first+1, block_size) u8 rows. The flat pointer space is the
        window, not the archive — total_size scales with the window."""
        L = last - first + 1
        _check_window_bytes(first, last, self.da.block_size)
        n_rounds = self._rounds_for_span(first, last)
        flat = self._launch(np.arange(first, last + 1, dtype=np.int32),
                            self._meta(L, total=L * self.da.block_size,
                                       n_rounds=n_rounds))
        self.launch_rounds_last.append(n_rounds)
        self.decoded_blocks_last += L
        rows = flat.reshape(L, self.da.block_size)
        if self.collect_window_rows:
            self.last_window_rows.append((first, rows))
        return rows

    def _anchor_groups(self, sel_np: np.ndarray) -> list:
        from repro.api.plan import anchor_window_groups
        return anchor_window_groups(sel_np, self.archive.anchors)

    def _assemble_groups(self, sel_np: np.ndarray, window_rows) -> jnp.ndarray:
        """Group a global selection by governing anchor window, decode each
        window via `window_rows(first, last) -> (L, block_size)`, and
        reassemble rows in the selection's original order."""
        groups = self._anchor_groups(sel_np)
        pieces = [window_rows(first, last)[sel_np[idx] - first]
                  for first, last, idx in groups]
        order = np.concatenate([idx for _, _, idx in groups])
        inv = np.empty(order.size, np.int64)
        inv[order] = np.arange(order.size)
        return jnp.concatenate(pieces, axis=0)[inv]

    def decode_from_anchor(self, first: int, last: int,
                           verify: bool = False) -> jnp.ndarray:
        """Global archives: decode blocks [first, last] by materializing
        only the [nearest-anchor(first), last] window instead of the whole
        prefix — the checkpointed-wavefront random-access path. Returns
        (last-first+1, block_size) u8 rows."""
        if self.da.mode != "global":
            raise ValueError('decode_from_anchor requires mode="global" '
                             '("ra" blocks decode directly)')
        if not 0 <= first <= last < self.da.n_blocks:
            raise IndexError(f"block range [{first}, {last}] outside "
                             f"[0, {self.da.n_blocks})")
        from repro.api.plan import anchor_floor
        win_first = int(anchor_floor(np.asarray([first]),
                                     self.archive.anchors)[0])
        self.decoded_blocks_last = 0
        self.launch_rounds_last = []
        self.last_window_rows = []
        out = self._window_rows(win_first, last)[first - win_first:]
        if verify:
            self.verify_rows(np.arange(first, last + 1), out)
        return out

    def _decode_global_rows(self, sel_np: np.ndarray) -> jnp.ndarray:
        """Arbitrary global block selection → (B, block_size) rows via
        per-anchor-window decodes (whole prefix when anchor-free). The
        selection is grouped by governing anchor so one call never decodes
        across windows it does not need."""
        self.decoded_blocks_last = 0
        self.launch_rounds_last = []
        self.last_window_rows = []
        if sel_np.size == 0:
            return jnp.zeros((0, self.da.block_size), jnp.uint8)
        if self.archive.anchors.size == 0:
            # anchor-free wavefront: decode the whole prefix, NOT
            # [0, max(sel)] — the window length is the jit trace key, and
            # a fixed n_blocks window gives ONE trace for every selection
            # where per-max windows would compile one variant per distinct
            # max (anchored windows don't have this problem: their lengths
            # are bounded by interval + span)
            rows = self._window_rows(0, self.da.n_blocks - 1)
            return rows[sel_np]
        return self._assemble_groups(sel_np, self._window_rows)

    def _assemble_ra_groups(self, sel_np: np.ndarray, groups: list,
                            decode_group, pad_groups: bool) -> jnp.ndarray:
        """Depth-bucketed "ra" decode: one launch per scheduled-rounds
        group via `decode_group(gsel i32[Gp], n_rounds) -> (Gp, bs)`,
        reassembled in the selection's original order. `pad_groups` pow2-
        pads each group (bounded jit retraces — the serving/cache paths);
        the streaming path passes False to keep its exact-size budget
        accounting."""
        pieces, order, n_mat = [], [], 0
        for rounds, idx in groups:
            gsel = sel_np[idx].astype(np.int32)
            g = _pad_pow2(gsel) if pad_groups else gsel
            rows = decode_group(g, rounds)
            self.launch_rounds_last.append(rounds)
            n_mat += int(g.size)
            pieces.append(rows[:idx.size])
            order.append(idx)
        order = np.concatenate(order)
        inv = np.empty(order.size, np.int64)
        inv[order] = np.arange(order.size)
        self.decoded_blocks_last = n_mat
        return jnp.concatenate(pieces, axis=0)[inv]

    def decode_blocks(self, sel, verify: bool = False,
                      pad_groups: bool = True,
                      on_error: str = "raise") -> jnp.ndarray:
        return self._run_decode(self._decode_blocks_raw, sel, verify,
                                pad_groups, on_error)

    def _decode_blocks_raw(self, sel_np: np.ndarray,
                           pad_groups: bool = True) -> jnp.ndarray:
        if self.da.mode == "global":
            return self._decode_global_rows(np.asarray(sel_np, np.int64))
        groups = self._ra_groups(sel_np)
        if groups is None:
            out = self._launch(sel_np, self._meta(len(sel_np)))
            self.launch_rounds_last.append(self.da.max_depth)
            self.decoded_blocks_last = int(sel_np.size)
            return out
        return self._assemble_ra_groups(
            sel_np, groups,
            lambda g, r: self._launch(g, self._meta(g.size, n_rounds=r)),
            pad_groups)

    def decode_blocks_host_entropy(self, sel, verify: bool = False,
                                   pad_groups: bool = True,
                                   on_error: str = "raise") -> jnp.ndarray:
        """Mode 1: host entropy + device match. Global selections decode
        per anchor window ([0, max(sel)] when anchor-free) so every
        cross-block match reference resolves inside the decoded window —
        a partial selection never reads bytes that were not decoded."""
        return self._run_decode(self._decode_blocks_host_raw, sel, verify,
                                pad_groups, on_error)

    def _decode_blocks_host_raw(self, sel: np.ndarray,
                                pad_groups: bool = True) -> jnp.ndarray:
        sel = np.asarray(sel)
        a = self.archive
        max_cmds = int(a.n_cmds.max(initial=1))
        if a.mode == "global":
            self.decoded_blocks_last = 0
            self.last_window_rows = []
            sel64 = sel.astype(np.int64).reshape(-1)
            if sel64.size == 0:
                return jnp.zeros((0, a.block_size), jnp.uint8)

            def window_rows(first: int, last: int) -> jnp.ndarray:
                _check_window_bytes(first, last, a.block_size)
                wsel = np.arange(first, last + 1)
                L = wsel.size
                streams = _entropy_decode_host(a, wsel)
                # low-32-bit window base: the i32 wraparound rebase in
                # _match_phase is exact for archives starting past 2 GiB
                wb = int(np.int64(a.block_start[first]).astype(np.int32))
                n_rounds = self._rounds_for_span(first, last)
                flat = _match_phase(
                    "global", streams, jnp.asarray(a.n_cmds[wsel]),
                    jnp.asarray(a.block_len[wsel]),
                    jnp.asarray(a.block_start[wsel].astype(np.int32)),
                    a.block_size, max_cmds, self.backend, a.offset_bytes,
                    total_size=L * a.block_size, win_base=wb,
                    n_rounds=n_rounds)
                self.launch_rounds_last.append(n_rounds)
                self.decoded_blocks_last += L
                rows = flat.reshape(L, a.block_size)
                if self.collect_window_rows:
                    self.last_window_rows.append((first, rows))
                return rows

            out = self._assemble_groups(sel64, window_rows)
        else:
            def match_group(gsel: np.ndarray, n_rounds) -> jnp.ndarray:
                streams = _entropy_decode_host(a, gsel)
                return _match_phase(
                    a.mode, streams, jnp.asarray(a.n_cmds[gsel]),
                    jnp.asarray(a.block_len[gsel]),
                    jnp.asarray(a.block_start[gsel].astype(np.int32)),
                    a.block_size, max_cmds, self.backend, a.offset_bytes,
                    None, n_rounds=n_rounds)

            sel_np = sel.astype(np.int64).reshape(-1)
            groups = self._ra_groups(sel_np)
            if groups is None:
                out = match_group(sel_np, self.da.max_depth)
                self.launch_rounds_last.append(self.da.max_depth)
                self.decoded_blocks_last = int(sel.size)
            else:
                out = self._assemble_ra_groups(sel_np, groups, match_group,
                                               pad_groups)
        return out

    # ------------------------------------------------------------ host APIs
    def decode_range(self, lo: int, hi: int, mode2: bool = True) -> np.ndarray:
        """Decode output byte range [lo, hi) — touches only covering blocks.
        Compatibility shim: a one-ByteRange plan through the query plane."""
        from repro.api.address import ByteRange
        view = self._api_store()
        plan = view.planner.plan([ByteRange(lo, hi)])
        rows, lens = view.executor.run(plan, mode2=mode2)
        return np.asarray(rows[0])[:int(lens[0])]

    def decode_all(self, chunk_blocks: Optional[int] = None,
                   mode2: bool = True, verify: bool = False,
                   on_error: str = "raise") -> np.ndarray:
        """Whole-file decode; never materializes more than one chunk of
        decompressed output at a time (paper §5 v7-RA). `chunk_blocks`
        (the most blocks per launch) defaults to `MAX_LAUNCH_BLOCKS` for
        "ra" archives and to the whole archive for global ones (their
        decode windows cross blocks).
        Compatibility shim over `StreamingExecutor`.

        verify=True additionally checks `file_fnv` over the block digest
        table, then decodes block-selection-wise with per-block device
        digest verification. `on_error` picks the failure semantics:
        "raise" (`BlockDigestError` on the first mismatch), "repair"
        (parity reconstruction, raise only if unrecoverable), "partial"
        (unrecoverable blocks quarantine and read back as zeros). A
        corrupt digest TABLE (`file_fnv` fold mismatch) always raises:
        no reference digests means nothing can be trusted or repaired."""
        raw = self.da.raw_size
        if raw == 0:
            return np.zeros(0, np.uint8)
        n_blocks = self.da.n_blocks
        if chunk_blocks is None:
            chunk_blocks = (min(n_blocks, MAX_LAUNCH_BLOCKS)
                            if self.da.mode == "ra" else n_blocks)
        # the launches split the archive evenly, so the last one pads
        # fewer than one block per launch
        n_launches = -(-n_blocks // int(chunk_blocks))
        step = -(-n_blocks // n_launches)
        if verify:
            a = self.archive
            if file_digest(a.block_fnv) != a.file_fnv:
                raise BlockDigestError(
                    f"file digest mismatch: block digest table folds to "
                    f"{file_digest(a.block_fnv):#018x} != stored "
                    f"{a.file_fnv:#018x}")
            decode = (self.decode_blocks if mode2
                      else self.decode_blocks_host_entropy)
            parts = []
            for lo in range(0, n_blocks, step):
                sel = np.arange(lo, min(lo + step, n_blocks))
                # the last chunk repeats its final block up to `step`
                # ids, so every launch of the loop shares one shape
                padded = np.minimum(np.arange(lo, lo + step), n_blocks - 1)
                rows = np.asarray(decode(padded, verify=True,
                                         on_error=on_error))
                parts.extend(rows[i, :int(a.block_len[b])]
                             for i, b in enumerate(sel))
            return np.concatenate(parts) if parts else np.zeros(0, np.uint8)
        from repro.api.address import ByteRange
        from repro.api.executors import StreamingExecutor
        ex = StreamingExecutor(self._api_store(), max_blocks_per_chunk=step,
                               mode2=mode2)
        return np.concatenate(list(ex.chunks([ByteRange(0, raw)])))
