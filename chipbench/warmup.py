"""Warm-up of exactly the shapes a cell's window can use.

The program pads its launches to powers of two but not every array it
dispatches, so the shapes a point-read window meets are: the batch of
unique reads B (1..max_batch, padded to Bp), the covering blocks U
(1..2 Bp, a read spans at most two blocks), and the cache misses among
them (padded to Mp <= Up). `point_requests` lists batches that meet every
(B, Bp, U) and every (Mp, Up). It assumes one depth bucket, as the
configurations' 16 KiB blocks have; a miss set that spans buckets would
meet launch shapes it does not warm, and the run logs every executable
compiled inside the window. A stream window meets one shape per distinct
chunk: its block count, its decoded length and how its blocks split over
the decoder's resolve-round buckets; `stream_chunks` picks one chunk of
each.
"""
from __future__ import annotations

from collections import Counter
from typing import List, Tuple

import numpy as np


class _ReadPicker:
    """Read ids that cover exactly a run of consecutive blocks."""

    def __init__(self, starts: np.ndarray, block_size: int):
        b0 = starts[:-1] // block_size
        b1 = (starts[1:] - 1) // block_size
        self.n_blocks = int(b1[-1]) + 1
        single = np.flatnonzero(b0 == b1)
        order = np.argsort(b0[single], kind="stable")
        self.single = single[order]
        self.single_at = np.searchsorted(b0[self.single],
                                         np.arange(self.n_blocks + 1))
        self.cross = np.full(self.n_blocks, -1, np.int64)
        cross = np.flatnonzero(b1 == b0 + 1)
        self.cross[b0[cross][::-1]] = cross[::-1]

    def singles(self, b: int) -> np.ndarray:
        return self.single[self.single_at[b]:self.single_at[b + 1]]

    def cover(self, first: int, n_blocks: int, n_reads: int) -> List[int]:
        """`n_reads` distinct reads covering exactly blocks
        [first, first + n_blocks): reads that straddle the pairs
        (first, first+1), (first+2, first+3), ... where there are more
        blocks than reads, and reads inside one block for the rest."""
        n_cross = max(0, n_blocks - n_reads)
        if n_cross > n_reads or first + n_blocks > self.n_blocks:
            raise ValueError(f"{n_reads} reads cannot cover {n_blocks} "
                             f"blocks from block {first}")
        out = [int(self.cross[first + 2 * i]) for i in range(n_cross)]
        inner = [self.singles(b) for b in
                 range(first + 2 * n_cross, first + n_blocks)]
        want = n_reads - n_cross
        k = 0
        while len(out) < n_cross + want:
            row = inner[k % len(inner)]
            j = k // len(inner)
            if j >= row.size:
                raise ValueError(f"too few whole reads in the blocks from "
                                 f"{first + 2 * n_cross}")
            out.append(int(row[j]))
            k += 1
        if min(out, default=0) < 0:
            raise ValueError(f"no read straddles a block pair from {first}")
        return out

    def usable(self, first: int, n_blocks: int) -> bool:
        """Every even pair of the run has a straddling read and every
        block a read of its own."""
        return (first + n_blocks <= self.n_blocks
                and all(self.cross[first:first + n_blocks:2] >= 0)
                and all(self.singles(b).size > 0
                        for b in range(first, first + n_blocks)))


def _pow2s(limit: int) -> List[int]:
    out, p = [], 1
    while p <= limit:
        out.append(p)
        p *= 2
    return out


def point_requests(starts: np.ndarray, block_size: int, max_batch: int
                   ) -> List[Tuple[bool, List[int]]]:
    """[(clear_cache_first, read ids)]: serve each batch in order.

    One launch makes a run of 2 x max_batch blocks resident; batches of
    every size B then cover every U = 1..2 Bp of those blocks as hits
    (every gather and slice shape); last, for every padded block count
    Up and padded miss count Mp <= Up, one batch adds Mp fresh blocks to
    Up - Mp resident ones (every decode and install shape). So the
    warm-up decodes few blocks beyond the shapes it has to compile."""
    pick = _ReadPicker(np.asarray(starts, np.int64), block_size)
    span = 2 * max_batch
    hot = next(b for b in range(pick.n_blocks) if pick.usable(b, span))
    out: List[Tuple[bool, List[int]]] = [
        (True, pick.cover(hot, span, max_batch))]
    for bp in _pow2s(max_batch):
        sizes = list(range(bp // 2 + 1, bp + 1))
        for u in range(1, 2 * bp + 1):
            b = max(sizes[u % len(sizes)], -(-u // 2))
            out.append((False, pick.cover(hot, u, b)))
    fresh = hot + span + 1
    for up in _pow2s(span):
        for mp in _pow2s(up):
            u = up
            if -(-mp // 2) + -(-(u - mp) // 2) > max_batch:
                u -= 1
            while not pick.usable(fresh, mp):
                fresh += 1
            ids = pick.cover(fresh, mp, -(-mp // 2))
            if u > mp:
                ids = pick.cover(hot, u - mp, -(-(u - mp) // 2)) + ids
            out.append((False, ids))
            fresh += mp + 1
    return out


def stream_chunks(block_rounds, n_blocks: int, block_size: int,
                  raw_size: int, blocks_per_chunk: int
                  ) -> List[Tuple[int, int]]:
    """[(lo, hi)] byte ranges, one chunk each, one per distinct chunk
    shape among the chunks a stream from a chunk-aligned start meets."""
    seen = {}
    for b in range(0, n_blocks, blocks_per_chunk):
        e = min(b + blocks_per_chunk, n_blocks)
        hi = min(e * block_size, raw_size)
        rounds = (tuple(sorted(Counter(
            np.asarray(block_rounds)[b:e].tolist()).items()))
            if block_rounds is not None else ())
        seen.setdefault((e - b, hi - b * block_size, rounds),
                        (b * block_size, hi))
    return list(seen.values())
