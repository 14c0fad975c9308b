"""ACEAPEX encoder (host, numpy, encode-once/decode-many).

Pipeline: partition output space into blocks → match search (per-block in
"ra" mode, global in "global"/wavefront mode) → greedy parse → four byte
streams per block → archive-global entropy tables → one batched rANS encode
over every stream of every block.
"""
from __future__ import annotations

import multiprocessing
import os
from concurrent.futures import ProcessPoolExecutor
from typing import List

import numpy as np

from repro.core import depth as dpth
from repro.core import entropy as ent
from repro.core import match_search as ms
from repro.core.format import (DEFAULT_BLOCK_SIZE, MAX_LEN, N_STREAMS,
                               S_COMMANDS, S_LENGTHS, S_LITERALS, S_OFFSETS,
                               Archive, file_digest, fnv1a64_u64_stride)


def _planes_u16(vals: np.ndarray) -> np.ndarray:
    v = vals.astype(np.uint32)
    return np.concatenate([(v & 0xFF).astype(np.uint8),
                           (v >> 8).astype(np.uint8)])


def _planes_u32(vals: np.ndarray) -> np.ndarray:
    v = vals.astype(np.uint32)
    return np.concatenate([((v >> np.uint32(8 * b)) & np.uint32(0xFF))
                           .astype(np.uint8) for b in range(4)])


def _planes_u64(vals: np.ndarray) -> np.ndarray:
    v = vals.astype(np.uint64)
    return np.concatenate([((v >> np.uint64(8 * b)) & np.uint64(0xFF)).astype(np.uint8)
                           for b in range(8)])


# "ra" inputs at least this large encode their blocks in a process pool;
# below it the pool's start-up (a fresh interpreter per worker) costs
# more than it saves
_PARALLEL_MIN_BYTES = 32 << 20


def _commands(blk: np.ndarray, tokens) -> tuple:
    """Greedy-parse tokens → (lit_lens u32, match_lens u32, offsets u64,
    literals u8) command arrays of one block; literal runs longer than
    MAX_LEN split into literal-only commands."""
    lit_lens: List[int] = []
    mlens: List[int] = []
    offs: List[int] = []
    lit_chunks: List[np.ndarray] = []
    cur = 0
    for (ll, ml, src) in tokens:
        if ll:
            lit_chunks.append(blk[cur:cur + ll])
        cur += ll + ml
        while ll > MAX_LEN:
            lit_lens.append(MAX_LEN)
            mlens.append(0)
            offs.append(0)
            ll -= MAX_LEN
        lit_lens.append(ll)
        mlens.append(ml)
        # "ra": src is already block-local (find_matches base=0);
        # "global": src is absolute
        offs.append(src if ml else 0)
    assert cur == blk.size, f"parse covered {cur} of {blk.size}"
    literals = (np.concatenate(lit_chunks) if lit_chunks
                else np.zeros(0, np.uint8))
    return (np.asarray(lit_lens, np.uint32), np.asarray(mlens, np.uint32),
            np.asarray(offs, np.uint64), literals)


def _ra_block(blk: np.ndarray, hash_bits: int, offset_bytes: int) -> tuple:
    """One self-contained "ra" block → (fnv, n_cmds, depth, [literals,
    lengths, offsets, commands] streams). Depends on the block alone, so
    blocks encode in any order and on any worker."""
    cand, mlen = ms.find_matches(blk, base=0, hash_bits=hash_bits)
    ll_a, ml_a, of_a, literals = _commands(
        blk, ms.greedy_parse(blk.size, cand, mlen))
    planes = _planes_u16 if offset_bytes == 2 else _planes_u32
    return (np.uint64(fnv1a64_u64_stride(blk)), ll_a.size,
            dpth.block_depth_ra(ll_a, ml_a, of_a, blk.size),
            [literals, _planes_u16(ml_a), planes(of_a), _planes_u16(ll_a)])


def _ra_chunk(chunk: bytes, lens: List[int], hash_bits: int,
              offset_bytes: int) -> list:
    """Pool task: consecutive "ra" blocks packed in `chunk`."""
    data = np.frombuffer(chunk, np.uint8)
    out, pos = [], 0
    for ln in lens:
        out.append(_ra_block(data[pos:pos + ln], hash_bits, offset_bytes))
        pos += ln
    return out


def _ra_blocks(data: np.ndarray, block_len: np.ndarray, hash_bits: int,
               offset_bytes: int) -> list:
    """Every "ra" block of `data`, in block order. Large inputs fan out
    over a `spawn` process pool (one worker per usable core; workers run
    numpy only, never JAX); the result is the serial result."""
    lens = block_len.tolist()
    workers = len(os.sched_getaffinity(0))
    if data.size < _PARALLEL_MIN_BYTES or workers < 2 or len(lens) < 2:
        return _ra_chunk(data.tobytes(), lens, hash_bits, offset_bytes)
    per = max(1, -(-len(lens) // (4 * workers)))
    starts = np.concatenate([[0], np.cumsum(block_len, dtype=np.int64)])
    with ProcessPoolExecutor(
            max_workers=workers,
            mp_context=multiprocessing.get_context("spawn")) as pool:
        futs = [pool.submit(_ra_chunk,
                            data[starts[i]:starts[min(i + per, len(lens))]]
                            .tobytes(), lens[i:i + per], hash_bits,
                            offset_bytes)
                for i in range(0, len(lens), per)]
        return [blk for f in futs for blk in f.result()]


def validate_encode_params(block_size: int, mode: str, entropy: str,
                           anchor_interval: int, raw_size: int = 0,
                           origin: int = 0, parity_group: int = 0) -> None:
    """Raise ValueError on any invalid encode-knob combination.

    The single home of the knob constraints, shared by `encode()` and the
    `repro.tune` grid sweep (which must reject a grid point up front with
    a reason instead of raising mid-sweep)."""
    if block_size <= 0:
        raise ValueError(f"block_size must be positive, got {block_size}")
    if mode not in ("ra", "global"):
        raise ValueError(f'mode must be "ra" or "global", got {mode!r}')
    if entropy not in ("rans", "raw"):
        raise ValueError(f"unknown entropy backend {entropy!r}")
    if anchor_interval < 0:
        raise ValueError(
            f"anchor_interval must be >= 0, got {anchor_interval}")
    if anchor_interval and mode != "global":
        raise ValueError(
            'anchor_interval only applies to mode="global" ("ra" blocks '
            "are already self-contained restart points)")
    if origin < 0:
        raise ValueError(f"origin must be >= 0, got {origin}")
    if parity_group < 0:
        raise ValueError(
            f"parity_group must be >= 0 (0 = no parity), got {parity_group}")
    if mode == "global":
        # the device match phase resolves a decode window in one flat
        # int32 pointer space, so a single window must span < 2^31 bytes;
        # anchor-free archives decode whole-prefix (one raw_size window)
        if not anchor_interval and raw_size >= 2**31:
            raise ValueError(
                f"anchor-free global archives decode as ONE {raw_size}-byte "
                f"window, past the device's 2 GiB flat pointer space — "
                f"encode with anchor_interval to bound windows")
        if anchor_interval and anchor_interval * block_size >= 2**31:
            raise ValueError(
                f"anchor window spans {anchor_interval} x {block_size} "
                f">= 2 GiB — the device flat pointer space is int32; "
                f"use a smaller anchor_interval")


def encode(data: bytes | np.ndarray,
           block_size: int = DEFAULT_BLOCK_SIZE,
           mode: str = "ra",
           entropy: str = "rans",
           hash_bits: int = 17,
           anchor_interval: int = 0,
           origin: int = 0,
           parity_group: int = 0,
           profile=None) -> Archive:
    """Compress `data` into an ACEAPEX archive.

    `anchor_interval` (global mode only) emits a wavefront restart point
    every that many blocks: the match window resets at each anchor, so
    every match in blocks [anchor, next_anchor) sources only bytes at or
    after the anchor's start. Any block then decodes from its governing
    anchor instead of the whole prefix (bounded random access), at the
    cost of matches that can no longer cross anchor boundaries.
    0 keeps the anchor-free whole-file window.

    `origin` places the archive at an absolute byte offset of a larger
    logical file (multi-shard archives): block starts and global-mode
    match offsets are recorded relative to that origin. Block-level decode
    APIs are origin-transparent; byte-addressed query-plane entry points
    assume origin == 0.

    `parity_group=k` (k > 0) XORs the compressed payload words of every
    k-block group into a parity block stored in a v4 format tail: any
    SINGLE corrupted payload per group is then reconstructable on device
    (`repro.resilience`). k=1 is payload replication; parity overhead is
    roughly 1/k of the payload bytes. 0 (default) writes a parity-free
    archive, byte-identical to the v3 format.

    `profile` (a `repro.tune.EncodeProfile`) supplies block_size / mode /
    entropy / anchor_interval in one declared object — the autotuner's
    output; explicit keyword knobs must not also be passed alongside it.
    """
    if profile is not None:
        defaults = dict(block_size=DEFAULT_BLOCK_SIZE, mode="ra",
                        entropy="rans", anchor_interval=0)
        given = dict(block_size=block_size, mode=mode, entropy=entropy,
                     anchor_interval=anchor_interval)
        clash = [k for k, v in given.items() if v != defaults[k]]
        if clash:
            raise ValueError(
                f"encode(profile=...) also got explicit {clash} — the "
                f"profile owns those knobs; drop one or the other")
        block_size = profile.block_size
        mode = profile.mode
        entropy = profile.entropy
        anchor_interval = profile.anchor_interval
    data = np.frombuffer(data, np.uint8) if isinstance(data, (bytes, bytearray)) \
        else np.ascontiguousarray(data, np.uint8)
    n = data.shape[0]
    anchor_interval = int(anchor_interval)
    origin = int(origin)
    parity_group = int(parity_group)
    validate_encode_params(block_size, mode, entropy, anchor_interval,
                           raw_size=n, origin=origin,
                           parity_group=parity_group)
    # "ra" offsets are block-local; two planes hold them only while the
    # block fits 16 bits. Larger blocks (e.g. PAPER1_BLOCK_SIZE) switch to
    # four planes — storing a >=64 KiB offset in two would silently
    # truncate it and corrupt every match past the 16-bit horizon.
    offset_bytes = (2 if block_size <= 0xFFFF else 4) if mode == "ra" else 8
    n_blocks = max(1, -(-n // block_size))
    block_start = origin + (np.arange(n_blocks, dtype=np.int64) * block_size)
    block_len = np.minimum(n - (block_start - origin),
                           block_size).astype(np.int32)
    block_len = np.maximum(block_len, 0)

    anchors = np.zeros(0, np.int64)
    if mode == "global":
        if anchor_interval:
            anchors = np.arange(0, n_blocks, anchor_interval, dtype=np.int64)
        if anchors.size:
            # checkpointed wavefront: one independent match search per
            # anchor window — candidates cannot reference bytes before
            # their window's anchor, so [anchor, last] decodes alone
            g_cand = np.full(n, -1, np.int64)
            g_mlen = np.zeros(n, np.int64)
            bounds = np.append(anchors, n_blocks) * block_size
            for ws, we in zip(bounds[:-1], np.minimum(bounds[1:], n)):
                ws, we = int(ws), int(we)
                c, m = ms.find_matches(data[ws:we], base=origin + ws,
                                       hash_bits=hash_bits)
                g_cand[ws:we] = c
                g_mlen[ws:we] = m
        else:
            g_cand, g_mlen = ms.find_matches(data, base=origin,
                                             hash_bits=hash_bits)

    streams: List[np.ndarray] = []
    n_cmds = np.zeros(n_blocks, np.int32)
    block_fnv = np.zeros(n_blocks, np.uint64)
    block_depth = np.zeros(n_blocks, np.int32)
    if mode == "ra":
        # every block resolves alone: its exact pointer-resolution depth
        # is measured with it (the decoder runs exactly that many
        # doubling rounds instead of ceil(log2(block_size)))
        for b, (fnv, nc, depth, blk_streams) in enumerate(
                _ra_blocks(data, block_len, hash_bits, offset_bytes)):
            block_fnv[b], n_cmds[b], block_depth[b] = fnv, nc, depth
            streams.extend(blk_streams)
    else:
        # wavefront chains cross blocks, so depth is measured per anchor
        # window; blocks arrive in order, so one window's pointer arrays
        # (i32, window-relative — windows are guarded < 2^31 bytes) are
        # buffered and freed at the window edge. Peak host memory is a
        # few bytes per byte of ONE window; anchor-free archives have one
        # whole-file window by construction, which the < 2 GiB encode
        # guard above already bounds.
        win_of = (np.searchsorted(anchors, np.arange(n_blocks), "right") - 1
                  if anchors.size else np.zeros(n_blocks, np.int64))
        win_ptrs: List[np.ndarray] = []
        win_first = 0
        for b in range(n_blocks):
            s, ln = int(block_start[b]) - origin, int(block_len[b])
            blk = data[s:s + ln]
            block_fnv[b] = np.uint64(fnv1a64_u64_stride(blk))
            # global candidates; cap match dest inside this block
            c = g_cand[s:s + ln].copy()
            m = g_mlen[s:s + ln].copy()
            m = np.minimum(m, ln - np.arange(ln))
            m = np.where(m >= ms.MIN_MATCH, m, 0)
            ll_a, ml_a, of_a, literals = _commands(
                blk, ms.greedy_parse(ln, np.where(m > 0, c, -1), m))
            n_cmds[b] = ll_a.size
            # pointers buffer per anchor window (rebased to window
            # coordinates — the host twin of the decode's flat pointer
            # space) and resolve at the window edge
            if not win_ptrs:
                win_first = b
            ws = int(block_start[win_first])
            ptr = dpth.expand_pointers_np(ll_a, ml_a, of_a.astype(np.int64),
                                          ln, base=int(block_start[b]))
            win_ptrs.append(np.where(ptr < 0, ptr, ptr - ws)
                            .astype(np.int32))
            if b + 1 == n_blocks or win_of[b + 1] != win_of[b]:
                blks = np.arange(win_first, b + 1)
                block_depth[blks] = dpth.window_depths(win_ptrs,
                                                       block_len[blks])
                win_ptrs = []
            streams.extend([literals, _planes_u16(ml_a), _planes_u64(of_a),
                            _planes_u16(ll_a)])
    # streams are block-major in class order (literals, lengths, offsets,
    # commands)
    class_ids = [S_LITERALS, S_LENGTHS, S_OFFSETS, S_COMMANDS] * n_blocks

    # archive-global entropy tables, one per stream class
    hists = np.zeros((N_STREAMS, 256), np.int64)
    for st, c in zip(streams, class_ids):
        if st.size:
            hists[c] += np.bincount(st, minlength=256)
    freqs = np.stack([ent.normalize_freqs(hists[c]) for c in range(N_STREAMS)])

    if entropy == "rans":
        words, w_off, n_words, n_syms, lanes = ent.rans_encode_batch(
            streams, class_ids, freqs)
    elif entropy == "raw":
        # uncompressed byte-pack fallback (2 bytes/word) — the "other entropy
        # backend" used by the §6.4-style backend comparison
        sizes = np.array([st.size for st in streams], np.int64)
        n_words = (-(-sizes // 2)).astype(np.int32)
        w_off = np.concatenate([[0], np.cumsum(n_words[:-1])]).astype(np.int64)
        words = np.zeros(int(n_words.sum()), np.uint16)
        for i, st in enumerate(streams):
            p = st if st.size % 2 == 0 else np.concatenate(
                [st, np.zeros(1, np.uint8)])
            words[w_off[i]:w_off[i] + n_words[i]] = (
                p[0::2].astype(np.uint16) | (p[1::2].astype(np.uint16) << 8))
        n_syms = sizes.astype(np.int32)
        lanes = np.ones(len(streams), np.int32)
    else:
        raise ValueError(f"unknown entropy backend {entropy!r}")

    S = len(streams)
    assert S == N_STREAMS * n_blocks
    parity_words = np.zeros(0, np.uint16)
    parity_off = np.zeros(1, np.int64)
    if parity_group:
        # block b's payload = words[word_off[b,0] : word_off[b+1,0]) —
        # the four streams lie consecutively, both entropy backends
        from repro.resilience.parity import build_parity
        p_starts = np.asarray(w_off, np.int64).reshape(
            n_blocks, N_STREAMS)[:, 0]
        p_ends = np.append(p_starts[1:], np.int64(words.size))
        parity_words, parity_off = build_parity(words, p_starts, p_ends,
                                                parity_group)
    return Archive(
        block_size=block_size,
        raw_size=n,
        mode=mode,
        entropy=entropy,
        freqs=freqs,
        words=words,
        word_off=np.asarray(w_off, np.int64).reshape(n_blocks, N_STREAMS),
        n_words=np.asarray(n_words, np.int32).reshape(n_blocks, N_STREAMS),
        n_syms=np.asarray(n_syms, np.int32).reshape(n_blocks, N_STREAMS),
        lanes=np.asarray(lanes, np.int32).reshape(n_blocks, N_STREAMS),
        n_cmds=n_cmds,
        block_start=block_start,
        block_len=block_len,
        block_fnv=block_fnv,
        file_fnv=file_digest(block_fnv),
        offset_bytes=offset_bytes,
        anchor_interval=anchor_interval if anchors.size else 0,
        anchors=anchors,
        block_depth=block_depth,
        parity_group=parity_group,
        parity_words=parity_words,
        parity_off=parity_off,
    )
