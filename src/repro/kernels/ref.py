"""Pure-jnp oracles for the Pallas kernels (DESIGN.md §3.1).

The LZ77 match phase is re-derived for a vector machine: command expansion
scatters each command's field steps at its end and takes their prefix sum
(the Pallas kernel body scatters command-end marks and gathers the fields),
match self-overlap folds via the modulo trick, and cross-command
dependencies resolve with pointer doubling.

On a TPU v5e the expansion takes 46 ms over 16 x 1 MiB blocks and the
whole decode compiles in 6.0 s. Its layout is set by compile time, which
the code does not show: compiled for a v5e, a flat scatter and prefix sum
over 1 to 8 rows of 1 MiB took the compiler several times as long as rows
of `_ROW` bytes summed in two levels, and an s32 remainder behind them 1.5
to 4.5 times as long as `_fold` (PERF.md §5).

Resolution rounds come in three flavors:

  * depth-bounded (`n_rounds = archive max_depth`) — v3 archives record
    the exact chain depth at encode time, so the resolver runs that many
    dense gathers instead of the ⌈log2(block)⌉ worst case (20 at the
    paper-1 1 MiB block; real parses are typically < 5);
  * early-exit (`n_rounds = None`) — a `lax.while_loop` that stops the
    round after no pointer moved: legacy (depth-free) archives converge
    in depth + 1 rounds instead of log2(block);
  * fixed log-N (`n_rounds = log2_rounds(out_size)`) — the historical
    worst case, kept callable for bit-identity regression tests.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro import trace
from repro.core.depth import log2_rounds  # canonical (jax-free) home

__all__ = ["log2_rounds", "expand_pointers", "resolve_pointers",
           "resolve_rounds", "lz77_decode_block_ref",
           "lz77_decode_blocks_ref", "lz77_decode_global_ref",
           "rans_decode_ref"]


_ROW = 1024   # bytes a row of the expansion's two-level prefix sum


def _fold(k, d):
    """`k mod d` for the bytes of a match: 0 <= k < 2**16 (a u16 match
    length bounds k) and d >= 1. An f32 quotient is within one of the
    true one there (k and d exact, far under 2**24), so one correction
    each way makes it exact; k < 0, a literal byte's, gives 0. Behind the
    prefix sums an s32 remainder takes the v5e compiler 1.5 to 4.5 times
    as long as this, and runs no faster."""
    k = jnp.maximum(k, 0)
    q = jnp.floor(k.astype(jnp.float32) / d.astype(jnp.float32))
    r = k - q.astype(jnp.int32) * d
    r = jnp.where(r < 0, r + d, r)
    return jnp.where(r >= d, r - d, r)


def expand_pointers(lit_lens, match_lens, offsets, n_cmds, block_len,
                    out_size: int, base=0):
    """Per-output-byte source pointers for ONE block.

    `offsets` and the returned match pointers live in the coordinate space
    `base + local`: base=0 with block-local offsets ("ra" blocks), or
    base=block_start with absolute offsets ("global"/wavefront mode).

    Returns int32[out_size]: ptr >= 0 → copy from output position ptr;
    ptr < 0 → literal index -(ptr+1). Bytes >= block_len get literal 0.
    """
    C = lit_lens.shape[0]
    lit_lens = lit_lens.astype(jnp.int32)
    match_lens = match_lens.astype(jnp.int32)
    offsets = offsets.astype(jnp.int32)
    cmd_ids = jnp.arange(C, dtype=jnp.int32)
    valid_cmd = cmd_ids < n_cmds
    ll = jnp.where(valid_cmd, lit_lens, 0)
    ml = jnp.where(valid_cmd, match_lens, 0)

    tot = ll + ml
    cum_tot = jnp.cumsum(tot)                      # command end positions
    P = cum_tot - tot                              # command start positions
    cum_lit = jnp.cumsum(ll) - ll                  # literal base per command

    # Byte i belongs to command min(#{c : cum_tot[c] <= i}, n_cmds, C - 1)
    # and reads three of its fields: the local match start, the literal
    # index less the command start, and the offset. Each field is a step
    # function of i that moves only at command ends, so it is the prefix
    # sum of its steps scattered at the ends (zero-length commands add up
    # at one end, ends past the last row drop, i32 wraparound keeps the
    # sum exact). No per-byte gather: one from a command table costs a
    # full pass over the bytes, a prefix sum under a hundredth of that on
    # a v5e. The bytes are laid out in rows of _ROW and summed in two
    # levels, within rows and then over row totals.
    rows = -(-out_size // _ROW)
    fields = jnp.stack([P + ll, cum_lit - P, offsets])
    count = jnp.minimum(jnp.arange(C + 1, dtype=jnp.int32),
                        jnp.minimum(n_cmds, C - 1))
    after = fields[:, count]                 # field after k command ends
    steps = jnp.zeros((3, rows, _ROW), jnp.int32).at[
        :, cum_tot // _ROW, cum_tot % _ROW].add(
        after[:, 1:] - after[:, :-1], mode="drop")
    within = jnp.cumsum(steps, axis=2)
    row_sum = within[:, :, -1]
    runs = within + (jnp.cumsum(row_sum, axis=1) - row_sum)[:, :, None]
    mstart, lit_base, off = (runs.reshape(3, rows * _ROW)[:, :out_size]
                             + after[:, :1])

    i = jnp.arange(out_size, dtype=jnp.int32)
    # match source with self-overlap folding (dest start in `base` coords)
    d = jnp.maximum(base + mstart - off, 1)        # distance >= 1
    mptr = off + _fold(i - mstart, d)
    ptr = jnp.where(i < mstart, -(lit_base + i + 1), mptr)
    ptr = jnp.where(i < block_len, ptr, -1)        # pad bytes → literal 0
    return ptr


def _double_round(p):
    nxt = p[jnp.clip(p, 0, p.shape[0] - 1)]
    return jnp.where(p >= 0, nxt, p)


def resolve_pointers(ptr, literals, n_rounds: Optional[int] = None):
    """Pointer doubling + literal payout for ONE block.

    `n_rounds` is the static round count (the archive's recorded chain
    depth, or `log2_rounds(out_size)` for the historical worst case).
    None runs the early-exit variant: a `lax.while_loop` that stops once
    no pointer moved — legacy depth-free archives converge in chain
    depth + 1 rounds instead of log2(block).
    """
    ptr = resolve_rounds(ptr, n_rounds)
    lit_idx = jnp.clip(-ptr - 1, 0, literals.shape[0] - 1)
    return literals[lit_idx]


def resolve_rounds(ptr, n_rounds: Optional[int] = None):
    """The doubling recurrence alone (shared by block + global paths).

    The early-exit loop is capped at `log2_rounds(len(ptr))`: any VALID
    parse converges within that (chain hops <= array length), so the cap
    never costs a correct archive a round — it only stops a malformed /
    adversarial archive whose pointers form a cycle from hanging the
    decode forever (digest verification then reports the corruption,
    exactly as the fixed-round path always did)."""
    if n_rounds is None:
        cap = jnp.int32(log2_rounds(ptr.shape[0]))

        def cond(carry):
            return carry[1] & (carry[2] < cap)

        def body(carry):
            p, _, r = carry
            q = _double_round(p)
            return q, jnp.any(q != p), r + 1

        ptr, _, _ = jax.lax.while_loop(
            cond, body, (ptr, jnp.any(ptr >= 0), jnp.int32(0)))
        return ptr
    return jax.lax.fori_loop(0, n_rounds, lambda _, p: _double_round(p),
                             ptr)


def lz77_decode_block_ref(lit_lens, match_lens, offsets, n_cmds, literals,
                          block_len, out_size: int,
                          n_rounds: Optional[int] = None):
    """Decode ONE self-contained block (oracle for the Pallas kernel)."""
    with jax.named_scope(trace.DECODE_EXPAND):
        ptr = expand_pointers(lit_lens, match_lens, offsets, n_cmds,
                              block_len, out_size)
    with jax.named_scope(trace.DECODE_RESOLVE):
        return resolve_pointers(ptr, literals, n_rounds)


def lz77_decode_blocks_ref(lit_lens, match_lens, offsets, n_cmds, literals,
                           block_len, out_size: int,
                           n_rounds: Optional[int] = None):
    """vmapped multi-block decode: args batched on axis 0. Under vmap the
    early-exit while_loop runs until the whole batch has converged."""
    fn = lambda a, b, c, d, e, f: lz77_decode_block_ref(a, b, c, d, e, f,
                                                        out_size,
                                                        n_rounds=n_rounds)
    return jax.vmap(fn)(lit_lens, match_lens, offsets, n_cmds, literals,
                        block_len)


def lz77_decode_global_ref(lit_lens, match_lens, offsets, n_cmds, literals,
                           lit_base, block_start, block_len, out_size: int,
                           total_size: int,
                           n_rounds: Optional[int] = None):
    """Wavefront-generalized decode: ALL blocks' pointers in one flat output
    space, offsets window-relative — chains may cross blocks; `n_rounds`
    global gather rounds (the archive's recorded depth; None = early-exit
    while_loop; `log2_rounds(total_size)` = the historical worst case)
    replace the GPU wavefront schedule (DESIGN.md §3.3).

    literals: (B, max_lit) per-block literal arrays; lit_base: global literal
    index base per block (exclusive cumsum of literal counts).
    """
    with jax.named_scope(trace.DECODE_EXPAND):
        B = lit_lens.shape[0]

        def one(ll, mlen, off, nc, bstart, blen, lbase):
            ptr = expand_pointers(ll, mlen, off, nc, blen, out_size,
                                  base=bstart)
            # matches already point at absolute positions (base=bstart
            # above); literals shift by the block's global literal base.
            i_local = jnp.arange(out_size, dtype=jnp.int32)
            is_lit = ptr < 0
            gl = -(jnp.where(is_lit, ptr, -1) + 1) + lbase
            gptr = jnp.where(is_lit, -(gl + 1), ptr)
            valid = i_local < blen
            return jnp.where(valid, gptr, -1)

        gptr = jax.vmap(one)(lit_lens, match_lens, offsets, n_cmds,
                             block_start.astype(jnp.int32),
                             block_len, lit_base.astype(jnp.int32))
        # scatter per-block pointer rows into the flat output space
        flat = jnp.full(total_size, -1, jnp.int32)
        pos = (block_start[:, None].astype(jnp.int32)
               + jnp.arange(out_size, dtype=jnp.int32)[None, :])
        keep = (jnp.arange(out_size, dtype=jnp.int32)[None, :]
                < block_len[:, None])
        flat = flat.at[jnp.where(keep, pos, total_size)].set(
            jnp.where(keep, gptr, -1), mode="drop")

    with jax.named_scope(trace.DECODE_RESOLVE):
        lit_flat = literals.reshape(-1)
        # global literal index -> (block, local) via lit_base is already
        # folded in
        flat = resolve_rounds(flat, n_rounds)
        gl = jnp.clip(-flat - 1, 0, lit_flat.shape[0] - 1)
        return lit_flat[gl]


def rans_decode_ref(words, word_off, n_syms, lanes, class_ids, freqs,
                    k_max: int = 32, t_max: int | None = None):
    """Oracle for the rANS Pallas kernel — delegates to the batched jnp
    decoder in core.entropy (same step math, same layout)."""
    from repro.core.entropy import rans_decode_batch_jnp
    return rans_decode_batch_jnp(words, word_off, n_syms, lanes, class_ids,
                                 freqs, k_max=k_max, t_max=t_max)
