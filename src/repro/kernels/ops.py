"""jit'd wrappers over the decode kernels with backend dispatch.

backend:
  "ref"     — pure-jnp implementation (`kernels.ref`), lowered by XLA
  "pallas"  — pl.pallas_call; interpret=True off-TPU (validation mode)
  "auto"    — "ref" on every platform

Why "auto" never picks Pallas, on a TPU included: an ahead-of-time compile
for a TPU v5e (Mosaic lowering, jax 0.9.0 / libtpu 0.0.34) refuses both
kernels at every block size from 4 KiB to 1 MiB:

  * the block shapes `(1, C)`, `(1, 1)` and `(1, group)` are neither
    multiples of the (8, 128) tiling nor the full array dims;
  * with whole-array blocks the LZ77 body still fails on `cumsum`
    (unimplemented primitive in the Pallas TPU lowering), and the rANS
    body on its data-dependent `words_ref[...]` gathers ("Cannot do int
    indexing on TPU").

The pure-jnp path compiles for the chip at all three block sizes
(`tests/test_tpu_compile.py`). An explicit backend="pallas" still
compiles with interpret=False on a TPU — and fails loudly there; it never
runs in interpret mode on the chip.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ref as _ref


def _interpret() -> bool:
    """Pallas interpret mode everywhere but a TPU."""
    return jax.default_backend() != "tpu"


def _resolve(backend: str) -> str:
    return "ref" if backend == "auto" else backend


def lz77_decode_blocks(lit_lens, match_lens, offsets, n_cmds, literals,
                       block_len, out_size: int, backend: str = "auto",
                       n_rounds: int | None = None):
    """`n_rounds` = static resolve-round count (the archive's recorded
    chain depth). None = depth unknown: the ref backend early-exits via
    while_loop, the pallas kernel falls back to ceil(log2(out_size))."""
    b = _resolve(backend)
    if b == "ref":
        return _ref.lz77_decode_blocks_ref(
            lit_lens, match_lens, offsets, n_cmds, literals, block_len,
            out_size, n_rounds=n_rounds)
    from repro.kernels.lz77_match import lz77_decode_blocks_pallas
    return lz77_decode_blocks_pallas(
        lit_lens, match_lens, offsets, n_cmds, literals, block_len,
        out_size=out_size, interpret=_interpret(), n_rounds=n_rounds)


def rans_decode(words, word_off, n_syms, lanes, class_ids, freqs,
                t_max: int, backend: str = "auto", k_max: int = 32,
                group: int = 8):
    """→ (rows (S, t_max*k_max) u8 step-major, T per-stream steps)."""
    b = _resolve(backend)
    if b == "ref":
        return _ref.rans_decode_ref(words, word_off, n_syms, lanes,
                                    class_ids, freqs, k_max=k_max,
                                    t_max=t_max)
    from repro.kernels.rans_decode import rans_decode_pallas
    freqs_t = tuple(map(tuple, np.asarray(freqs).tolist()))
    rows = rans_decode_pallas(words, word_off, n_syms, lanes, class_ids,
                              freqs_t, t_max=t_max, k_max=k_max, group=group,
                              interpret=_interpret())
    n = jnp.asarray(n_syms, jnp.int32)
    K = jnp.maximum(jnp.asarray(lanes, jnp.int32), 1)
    return rows, jnp.where(n > 0, -(-n // K), 0)
