"""The work a decode launch has to do, counted from the archive's own
block table, never from padded shapes.

Decoding block b reads its compressed payload, the u16 words
`[word_off[b, 0], word_off[b + 1, 0])` (the last block ends at the end of
`words`), and writes its `block_len[b]` decoded bytes. Pad rows of a
launch repeat a real block and cache hits decode nothing, so only the
distinct blocks a launch really decoded count.

Decode moves few bytes per operation (table lookups, gathers, integer
arithmetic on bytes), so its roofline is the HBM bandwidth bound:
(bytes read + bytes written) / peak bytes per second.
"""
from __future__ import annotations

import numpy as np


def block_work_bytes(archive) -> np.ndarray:
    """i64[n_blocks]: compressed payload bytes + decoded bytes per block."""
    starts = np.asarray(archive.word_off, np.int64)[:, 0]
    ends = np.append(starts[1:], np.int64(np.asarray(archive.words).size))
    return 2 * (ends - starts) + np.asarray(archive.block_len, np.int64)


def decode_work_bytes(archive, decoded_blocks) -> int:
    """Bytes moved by decoding each block of `decoded_blocks` once; ids
    repeated as launch padding count once per launch, so pass each
    launch's distinct ids (`DecodeLog.since`)."""
    ids = np.asarray(decoded_blocks, np.int64).reshape(-1)
    return int(block_work_bytes(archive)[ids].sum())


def roofline_share_pct(work_bytes: int, device_seconds: float,
                       peak_bytes_per_s: float):
    """Share of the bandwidth roofline, in percent, or None when there is
    no device time to divide by."""
    if device_seconds <= 0 or work_bytes <= 0:
        return None
    return 100.0 * work_bytes / peak_bytes_per_s / device_seconds


class DecodeLog:
    """Records the distinct blocks of every decode launch that each of
    `decoders` makes through its `decode_blocks` (cache miss decodes and
    stream chunks both go through it). Installed on the decoder instances
    from the benchmark's side; the program is unchanged."""

    def __init__(self, decoders):
        self.decoders = list(decoders)
        self.launches = []         # (position in `decoders`, distinct ids)
        for i, dec in enumerate(self.decoders):
            self._install(i, dec)

    def _install(self, i: int, decoder) -> None:
        decode = decoder.decode_blocks

        def decode_blocks(sel, *args, **kwargs):
            self.launches.append((i, np.unique(np.asarray(sel, np.int64))))
            return decode(sel, *args, **kwargs)

        decoder.decode_blocks = decode_blocks

    def mark(self) -> int:
        return len(self.launches)

    def since(self, mark: int) -> list:
        """For each decoder, the distinct-per-launch block ids it decoded
        since `mark`."""
        got = [[] for _ in self.decoders]
        for i, ids in self.launches[mark:]:
            got[i].append(ids)
        return [np.concatenate(g) if g else np.zeros(0, np.int64)
                for g in got]

    def work_bytes(self, mark: int) -> int:
        """Bytes moved by every decoder's launches since `mark`."""
        return sum(decode_work_bytes(dec.archive, ids)
                   for dec, ids in zip(self.decoders, self.since(mark)))
