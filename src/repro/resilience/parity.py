"""XOR parity over compressed payload words (group-local RAID-5).

The parity unit is a block's payload word range
(`format.block_payload_bounds`): the contiguous slice of `Archive.words`
holding all four of its entropy streams — identical for both entropy
backends, which lay streams out block-major/cumulative. Group g covers
blocks [g*k, (g+1)*k); its parity row is the XOR of the group's
zero-padded payloads, sized to the group's longest payload. Any SINGLE
corrupted payload in a group is then recoverable as

    payload[b] = parity[g] XOR (XOR of the group's other payloads)

and the reconstruction runs on device as ONE jitted XOR-gather over the
resident words buffer — the compressed archive never round-trips to the
host to heal. Two corruptions in one group reconstruct to garbage, which
the mandatory re-verify catches (unrecoverable, never silent).

k = 1 degenerates to replication (each "group" is one block and its
parity row is a full copy); large k amortizes parity bytes at the cost
of tolerating fewer simultaneous failures — the ratio cost is measured
by `benchmarks/bench_resilience.py` (resil/parity_ratio_cost).
"""
from __future__ import annotations

from typing import Tuple

import numpy as np

import jax
import jax.numpy as jnp

from repro import trace
from repro.core.format import block_payload_bounds


def build_parity(words: np.ndarray, starts: np.ndarray, ends: np.ndarray,
                 parity_group: int) -> Tuple[np.ndarray, np.ndarray]:
    """Host-side parity build (encode time): XOR the payload word ranges
    of every `parity_group`-block group into one parity row per group.
    Returns (parity_words u16 flat, parity_off i64[n_groups + 1])."""
    k = int(parity_group)
    if k <= 0:
        raise ValueError(f"parity_group must be positive, got {k}")
    n_blocks = int(np.asarray(starts).shape[0])
    lens = (np.asarray(ends, np.int64) - np.asarray(starts, np.int64))
    n_groups = -(-n_blocks // k) if n_blocks else 0
    rows = []
    off = [0]
    for g in range(n_groups):
        blks = range(g * k, min((g + 1) * k, n_blocks))
        width = int(max((int(lens[b]) for b in blks), default=0))
        row = np.zeros(width, np.uint16)
        for b in blks:
            pay = words[int(starts[b]):int(ends[b])]
            row[:pay.size] ^= pay
        rows.append(row)
        off.append(off[-1] + width)
    pw = (np.concatenate(rows).astype(np.uint16) if rows
          else np.zeros(0, np.uint16))
    return pw, np.asarray(off, np.int64)


@jax.jit
@jax.named_scope(trace.PARITY_XOR)
def _xor_rebuild(words, sib_start, sib_len, parity_row, bad_start, bad_len):
    """ONE jitted XOR-gather: fold the sibling payloads into the parity
    row (rebuilt = parity XOR siblings), then blend the first `bad_len`
    rebuilt words into the words buffer at the bad block's payload range.
    Returns (patched words, rebuilt row). The buffer is padded by the
    parity width so the dynamic slice windows never clamp-shift at the
    tail; sibling gathers mask past each payload's own length."""
    width = parity_row.shape[0]
    size = words.shape[0]
    idx = jnp.arange(width, dtype=jnp.int32)

    def fold(acc, sl):
        s, ln = sl
        g = jnp.clip(s + idx, 0, size - 1)
        row = jnp.where(idx < ln, words[g], 0).astype(words.dtype)
        return acc ^ row, None

    acc, _ = jax.lax.scan(fold, parity_row.astype(words.dtype),
                          (sib_start, sib_len))
    wpad = jnp.concatenate([words, jnp.zeros((width,), words.dtype)])
    cur = jax.lax.dynamic_slice(wpad, (bad_start,), (width,))
    patch = jnp.where(idx < bad_len, acc, cur)
    wpad = jax.lax.dynamic_update_slice(wpad, patch, (bad_start,))
    return wpad[:size], acc


def reconstruct_blocks(decoder, bad) -> np.ndarray:
    """Reconstruct the payloads of global block ids `bad` from their
    parity groups, on device, patching BOTH the decoder's resident words
    buffer and the host archive copy (the two must stay consistent for
    mode-1 decode, partition rebuilds, and re-serialization). Returns
    the ids actually reconstructed — empty when the archive carries no
    parity. Reconstruction is NOT verification: callers must re-decode
    and re-verify the returned blocks (a corrupt sibling makes the
    rebuilt payload garbage, which only the digest check can tell)."""
    a = decoder.archive
    k = int(a.parity_group)
    bad = np.unique(np.asarray(bad, np.int64).reshape(-1))
    if k <= 0 or bad.size == 0:
        return np.zeros(0, np.int64)
    starts, ends = block_payload_bounds(a)
    lens = (ends - starts).astype(np.int64)
    poff = np.asarray(a.parity_off, np.int64)
    width = int((poff[1:] - poff[:-1]).max(initial=0))
    if width == 0:
        return bad          # every payload is empty: nothing to rebuild
    words = decoder.arrays["words"]
    n_sibs = max(k - 1, 1)
    for b in bad.tolist():
        g = b // k
        sibs = [i for i in range(g * k, min((g + 1) * k, a.n_blocks))
                if i != b]
        sib_start = np.zeros(n_sibs, np.int32)
        sib_len = np.zeros(n_sibs, np.int32)
        sib_start[:len(sibs)] = starts[sibs]
        sib_len[:len(sibs)] = lens[sibs]
        prow = np.zeros(width, np.uint16)
        lo, hi = int(poff[g]), int(poff[g + 1])
        prow[:hi - lo] = a.parity_words[lo:hi]
        words, rebuilt = _xor_rebuild(
            words, jnp.asarray(sib_start), jnp.asarray(sib_len),
            jnp.asarray(prow), jnp.int32(int(starts[b])),
            jnp.int32(int(lens[b])))
        a.words[int(starts[b]):int(ends[b])] = \
            np.asarray(rebuilt)[:int(lens[b])]
    decoder.arrays["words"] = words
    decoder.da.words = words
    return bad
