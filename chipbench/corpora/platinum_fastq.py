"""The platinum FASTQ corpus: its seeded generator, its plain host
reference, and the archive the deployment opens over it.

`platinum_fastq` is a vectorized copy of the program's
`make_fastq("platinum")`: the same distributions (a fragment pool of
`n_reads // 120` reads re-sampled with PCR-duplicate overlap, base
mutation rate 0.0005, quality alphabet `F:,` at 0.97 / 0.02 / 0.01, header
`@SRR0.<i> <i>/1`), drawn in bulk, so a million reads take seconds. The
bytes differ from `make_fastq`'s for the same seed, because the draws
come in another order.

`HostReference` finds every record from one newline scan of the corpus,
independently of the program's index, and is the reference that every
returned byte is compared with.
"""
from __future__ import annotations

import numpy as np

_BASES = np.frombuffer(b"ACGT", np.uint8)
_QUAL = np.frombuffer(b"F:,", np.uint8)
_QUAL_P = (0.97, 0.02, 0.01)
_MUTATION = 0.0005
_READS_PER_FRAGMENT = 120


def platinum_fastq(n_reads: int, read_len: int, seed: int) -> bytes:
    """NA12878-like FASTQ: `n_reads` records of `read_len` bases."""
    rng = np.random.default_rng(seed)
    n_frags = max(4, n_reads // _READS_PER_FRAGMENT)
    frags = rng.choice(_BASES, size=(n_frags, read_len))
    seqs = frags[rng.integers(n_frags, size=n_reads)]
    flips = rng.random((n_reads, read_len)) < _MUTATION
    seqs[flips] = rng.choice(_BASES, size=int(flips.sum()))
    quals = _QUAL[np.searchsorted(np.cumsum(_QUAL_P),
                                  rng.random((n_reads, read_len)),
                                  side="right").clip(0, _QUAL.size - 1)]

    heads = [b"@SRR0.%d %d/1\n" % (i, i) for i in range(n_reads)]
    head_len = np.fromiter(map(len, heads), np.int64, n_reads)
    rec_len = head_len + 2 * read_len + 4      # seq\n +\n qual\n
    rec_start = np.concatenate([[0], np.cumsum(rec_len)])
    out = np.empty(int(rec_start[-1]), np.uint8)

    head_bytes = np.frombuffer(b"".join(heads), np.uint8)
    head_off = np.concatenate([[0], np.cumsum(head_len)[:-1]])
    within = np.arange(head_bytes.size) - np.repeat(head_off, head_len)
    out[np.repeat(rec_start[:-1], head_len) + within] = head_bytes

    col = np.arange(read_len)
    seq_at = (rec_start[:-1] + head_len)[:, None]
    out[seq_at + col] = seqs
    out[seq_at[:, 0] + read_len] = ord("\n")
    out[seq_at[:, 0] + read_len + 1] = ord("+")
    out[seq_at[:, 0] + read_len + 2] = ord("\n")
    qual_at = seq_at + read_len + 3
    out[qual_at + col] = quals
    out[qual_at[:, 0] + read_len] = ord("\n")
    return out.tobytes()


def generate(config: dict, seed: int) -> bytes:
    return platinum_fastq(int(config["n_reads"]), int(config["read_len"]),
                          seed)


def build(corpus: bytes, config: dict, **cache):
    """The archive a FASTQ deployment opens: encoded with its read index
    and device name table (`GenomicArchive.from_bytes`)."""
    from repro.api import GenomicArchive
    return GenomicArchive.from_bytes(
        corpus, block_size=int(config["block_size"]), mode=config["mode"],
        entropy=config["entropy"], **cache)


def check(ga, ref: "HostReference") -> None:
    if ga.n_reads != ref.n_reads:
        raise RuntimeError(f"archive holds {ga.n_reads} reads, the corpus "
                           f"{ref.n_reads}")


class HostReference:
    """Record boundaries of a FASTQ corpus from one newline scan (4 lines
    per record). `record(i)` is read i's bytes, `span(lo, hi)` raw bytes."""

    def __init__(self, corpus: bytes):
        self.corpus = corpus
        self.array = np.frombuffer(corpus, np.uint8)
        nl = np.flatnonzero(self.array == ord("\n"))
        if nl.size == 0 or nl.size % 4:
            raise ValueError("corpus is not whole 4-line records")
        self.starts = np.concatenate([[0], nl[3::4] + 1]).astype(np.int64)
        self.n_reads = nl.size // 4

    def record(self, i: int) -> bytes:
        return self.corpus[self.starts[i]:self.starts[i + 1]]

    def span(self, lo: int, hi: int) -> np.ndarray:
        return self.array[lo:hi]
