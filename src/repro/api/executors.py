"""Plan executors: the three ways a DecodePlan becomes bytes.

DeviceExecutor     — one jitted pipeline (`_fetch_dev_core` underneath):
                     entropy decode → match resolve → ragged gather, fully
                     on device. Whole-record plans additionally resolve
                     their covering set from the device start table
                     (`_fetch_reads_core`), and the block-cache / Mode-1
                     paths fall back to the staged variant: host covering
                     set from the plan, rows through the device-resident
                     `BlockCache` (CachePlan hit/miss split, one decode
                     launch per miss set), same jitted gather.
StreamingExecutor  — a VRAM-budgeted chunked iterator over a plan: the
                     paper's §5 range-decode contribution generalized so
                     ANY query larger than `max_resident_bytes` streams
                     instead of OOMing.
ShardedExecutor    — the plan's unique-block selection fanned out over a
                     device mesh (`sharded_decode_blocks`), gather on the
                     assembled rows.
"""
from __future__ import annotations

import dataclasses
from typing import Iterator, List, Optional, Sequence, Tuple

import numpy as np

import jax.numpy as jnp

from repro import trace
from repro.api.address import Address
from repro.api.plan import DecodePlan, QueryPlanner, anchor_floor
from repro.core.residency import (_fetch_dev_jit, _fetch_reads_jit,
                                  _gather_jit, _pad_pow2)


class _DecoderStore:
    """Minimal store adapter so a bare `Decoder` rides the query plane
    (no index, no cache) without duplicating its device archive."""

    index = None
    _starts64 = None
    _cache = None
    _cache_cap = 0
    _max_len = _max_span = 1
    verify = False
    on_error = "raise"

    def __init__(self, decoder):
        self.decoder = decoder
        self.block_size = decoder.da.block_size

    def _rows_for_blocks(self, uniq: np.ndarray, mode2: bool,
                         verify: bool = False,
                         on_error: str = "raise") -> jnp.ndarray:
        decode = (self.decoder.decode_blocks if mode2
                  else self.decoder.decode_blocks_host_entropy)
        return decode(_pad_pow2(uniq.astype(np.int32)), verify=verify,
                      on_error=on_error)[:uniq.size]


class DeviceExecutor:
    """Execute a DecodePlan on the store's device pipeline.

    Returns ((n_queries, max_len) u8 zero-padded rows, (n_queries,) i32
    lengths) — padding rows are cropped, padding columns are zero.
    """

    def __init__(self, store):
        self.store = store
        # per-address corrupt mask of the most recent run (bool[B]):
        # all-False unless on_error="partial" met unrecoverable blocks —
        # the typed per-address outcome the serving plane consumes
        self.last_corrupt = np.zeros(0, bool)

    def run(self, plan: DecodePlan, mode2: bool = True,
            verify: Optional[bool] = None, on_error: Optional[str] = None
            ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        store = self.store
        verify = store.verify if verify is None else verify
        on_error = store.on_error if on_error is None else on_error
        B = plan.n_queries
        self.last_corrupt = np.zeros(B, bool)
        if B == 0:
            return (jnp.zeros((0, plan.max_len), jnp.uint8),
                    jnp.zeros((0,), jnp.int32))
        dec = store.decoder
        # checkpointed-wavefront archives take the staged path: the decoder
        # groups the covering set by anchor window (bounded decode instead
        # of the whole prefix the jitted device core would materialize),
        # and the rows ride the block cache when enabled. Verified runs
        # are staged too: the fused cores have no digest check, and the
        # recovery loop composes at the decoder, not in this executor.
        anchored = (dec.da.mode == "global" and dec.da.anchors is not None
                    and dec.da.anchors.size > 0)
        jitted = (mode2 and store._cache_cap == 0 and not anchored
                  and not verify)
        # depth-bucketed reroute: the fused device cores run a static
        # archive-wide round count, so a selection whose covering set sits
        # entirely below the deepest bucket saves rounds only on the
        # staged path (one launch per depth bucket). Reroute exactly then;
        # mixed selections touching the top bucket keep the fused launch.
        if jitted and dec.multi_bucket and plan.block_rounds is not None:
            needed = plan.needed_rounds()
            if needed is not None and needed < (dec.da.max_depth or 0):
                jitted = False
        if jitted and plan.device_ids is not None:
            out, lens = _fetch_reads_jit(
                dec.arrays, store._starts_blk, store._starts_rem,
                jnp.asarray(plan.device_ids, jnp.int32),
                da_meta=dec._meta(plan.batch), backend=dec.backend,
                geom=plan.geom())
            return out[:B], lens[:B]
        lens = jnp.asarray(plan.lengths[:B].astype(np.int32))
        if jitted:
            b0, r0, end_blk = plan.host_spans()
            out = _fetch_dev_jit(
                dec.arrays, jnp.asarray(b0.astype(np.int32)),
                jnp.asarray(r0),
                jnp.asarray(plan.lengths.astype(np.int32)),
                jnp.asarray(end_blk.astype(np.int32)),
                da_meta=dec._meta(plan.batch), backend=dec.backend,
                geom=plan.geom())
            return out[:B], lens
        # staged: rows through the device-resident block cache (one decode
        # launch per miss set) / the Mode-1 host entropy stage, then the
        # same jitted ragged gather. Bytes stay on device throughout.
        _, r0, _, uniq, row_map = plan.host_cover()
        rows = store._rows_for_blocks(uniq, mode2, verify=verify,
                                      on_error=on_error)
        if verify and dec.last_bad_blocks.size:
            # per-address typed outcomes: an address is corrupt iff any
            # of its covering blocks is (its bytes include zeroed rows)
            bad_row = np.isin(uniq, dec.last_bad_blocks)
            self.last_corrupt = bad_row[row_map].any(axis=1)[:B]
        with trace.span(trace.GATHER):
            out = _gather_jit(rows, jnp.asarray(row_map), jnp.asarray(r0),
                              jnp.asarray(plan.lengths.astype(np.int32)),
                              block_size=plan.block_size,
                              max_len=plan.max_len)
        return out[:B], lens


@dataclasses.dataclass
class ChunkStats:
    """Per-chunk residency accounting (asserted against the budget in
    tests: decoded rows + padded gather output are what the chunk
    materializes beyond the compressed archive itself). `decoded_bytes`
    is exact (the block selection is NOT pow2-padded — see `_execute`);
    `gather_bytes` counts the pow2-padded span batch `plan_spans`
    produces, because that padded (batch, max_len) matrix is what the
    gather really materializes."""
    n_spans: int
    n_blocks: int
    decoded_bytes: int        # blocks actually decoded * block_size: the
                              # unique covering rows for "ra", the summed
                              # anchor windows for checkpointed wavefronts
    gather_bytes: int         # padded gather output: pow2(B) * max_len
    yielded_bytes: int

    @property
    def resident_bytes(self) -> int:
        return self.decoded_bytes + self.gather_bytes


class StreamingExecutor:
    """Decode arbitrarily large queries under a byte budget.

    Spans are split at block boundaries into pieces covering at most K
    blocks (K sized so decoded rows + gather output of a chunk fit
    `max_resident_bytes`), then greedily packed into chunks; each chunk is
    one planner lowering + one device execution, yielded as exact payload
    bytes. Concatenating every yielded chunk reproduces the concatenated
    payloads of the addressed spans, bit-perfectly, while no chunk ever
    materializes more than the budget. `chunk_log` records the accounting.

    The decoded-block LRU is bypassed (streaming scans would thrash it).
    The budget must hold the archive's atomic decode unit: one block for
    "ra", one anchor window (`(anchor_interval + 1) * block_size`) for
    checkpointed wavefronts, and the ENTIRE prefix for anchor-free
    wavefront ("global") archives — those decode whole-prefix by
    construction, so a sub-archive budget is rejected up front instead of
    being silently violated on device.

    `verify=True` recomputes each decoded block's FNV-1a-64 digest on
    device before rows are cropped to spans, raising `BlockDigestError`
    naming the true block id on the first corrupt block of any chunk.

    `sharded=` (a `ShardedResidency`) switches the budget to PER-SHARD
    residency: chunks cost the max block count any one shard owns of
    them, decodes run partitioned (each device materializes only its own
    rows, exact-size, cache bypassed), and `ChunkStats.decoded_bytes`
    counts per-shard materialized bytes — so a mesh-partitioned archive
    streams a query n_shards times larger under the same per-device
    budget.
    """

    def __init__(self, store, max_resident_bytes: Optional[int] = None,
                 max_blocks_per_chunk: Optional[int] = None,
                 mode2: bool = True, planner: Optional[QueryPlanner] = None,
                 verify: bool = False, sharded=None,
                 on_error: str = "raise"):
        from repro.resilience import check_on_error
        self.on_error = check_on_error(on_error)
        self.store = store
        self.planner = planner or QueryPlanner(store)
        bs = store.block_size
        da = store.decoder.da
        # mesh-partitioned residency: the budget becomes PER-SHARD — each
        # device materializes only its own rows of a chunk, so a chunk's
        # decode cost is the max blocks any ONE shard owns of it. That is
        # what VRAM-decouples the 50 GB-class range decode per shard.
        if sharded is not None and da.mode == "global":
            raise ValueError(
                "sharded streaming needs a partitioned archive — global/"
                "wavefront archives cannot partition (decode windows "
                "cross block bounds)")
        if sharded is not None and not mode2:
            raise ValueError("sharded streaming is mode-2 only (the host "
                             "entropy stage has no partitioned path)")
        self.sharded = sharded
        anchors = getattr(da, "anchors", None)
        self._anchors = (np.asarray(anchors, np.int64)
                         if anchors is not None and np.asarray(anchors).size
                         and da.mode == "global" else np.zeros(0, np.int64))
        self._global = da.mode == "global"
        # the atomic decode unit a budget must hold: one block for "ra",
        # one anchor window for checkpointed wavefronts (bounded by the
        # archive — an interval beyond n_blocks is one whole-archive
        # window), the ENTIRE prefix for anchor-free global archives
        # (whole-prefix decode by construction; a budget below that would
        # be silently violated on device, so it is rejected up front)
        if not self._global:
            interval = 0
        elif self._anchors.size:
            interval = min(da.anchor_interval, da.n_blocks)
        else:
            interval = da.n_blocks
        if max_resident_bytes is not None:
            need = max(2, interval + 1) * bs
            if max_resident_bytes < need:
                hint = ""
                if interval:
                    hint = (f" ((anchor_interval={interval} + 1) * "
                            f"block_size)" if self._anchors.size else
                            f" (anchor-free global archives decode the "
                            f"whole {da.n_blocks}-block prefix; encode "
                            f"with anchor_interval to stream under a "
                            f"smaller budget)")
                raise ValueError(
                    f"max_resident_bytes={max_resident_bytes} cannot hold "
                    f"one decode window + its output; need >= {need}"
                    + hint)
        self.max_resident_bytes = max_resident_bytes
        if max_blocks_per_chunk is None:
            if max_resident_bytes is not None:
                # anchored global: a K-block piece may decode K+interval-1
                # window blocks and gather K*bs — size K so a lone piece
                # still fits the budget
                max_blocks_per_chunk = max(
                    1, (max_resident_bytes // bs - max(interval - 1, 0)) // 2)
            else:
                max_blocks_per_chunk = store.decoder.da.n_blocks or 1
        self.max_blocks_per_chunk = int(max_blocks_per_chunk)
        self.mode2 = mode2
        self.verify = verify
        self.chunk_log: List[ChunkStats] = []

    # ------------------------------------------------------------- pieces
    def _pieces(self, addrs: Sequence[Address]
                ) -> Iterator[Tuple[int, int]]:
        """Resolved spans split at K-block boundaries into (start, length)
        pieces, each covering at most K blocks — so any single piece fits
        the budget on its own."""
        starts, lengths, _ = self.planner.resolve(addrs)
        bs = self.store.block_size
        K = self.max_blocks_per_chunk
        for s, ln in zip(starts.tolist(), lengths.tolist()):
            pos, end = s, s + ln
            while pos < end:
                nxt = min(end, (pos // bs + K) * bs)
                yield pos, nxt - pos
                pos = nxt

    def _piece_blocks(self, s: int, ln: int) -> set:
        """Blocks a piece's decode materializes: its covering blocks, widened
        to the governing anchor window for checkpointed wavefronts (the
        decode cannot start mid-window). Not used for anchor-free global
        archives — their every chunk decodes the whole prefix, which
        `chunks` accounts as a constant instead of materializing an
        n_blocks-sized set per piece."""
        bs = self.store.block_size
        b_lo, b_hi = s // bs, -(-(s + ln) // bs)
        if self._anchors.size:
            b_lo = int(anchor_floor(np.asarray([b_lo]), self._anchors)[0])
        return set(range(b_lo, b_hi))

    def chunks(self, addrs: Sequence[Address]) -> Iterator[np.ndarray]:
        """Yield u8 chunks; their concatenation == the concatenation of the
        addressed payloads, in address order."""
        bs = self.store.block_size
        budget = self.max_resident_bytes
        cur: List[Tuple[int, int]] = []
        cur_blocks: set = set()
        cur_maxlen = 0

        def pow2(n):
            return 1 << max(0, n - 1).bit_length()

        whole_prefix = self._global and not self._anchors.size
        n_blocks = self.store.decoder.da.n_blocks
        for s, ln in self._pieces(addrs):
            if whole_prefix:
                pb = set()
                nblk = n_blocks
            else:
                pb = self._piece_blocks(s, ln)
                if self.sharded is not None:
                    # per-shard budget: each device materializes only its
                    # own rows, one exact-size launch per depth bucket —
                    # so a chunk's decode cost is the SUM over buckets of
                    # the max block count any one shard owns in that
                    # bucket (exactly what `_decode_uncached(pad=False)`
                    # materializes per shard)
                    part = self.sharded.part
                    blk = np.fromiter(cur_blocks | pb, np.int64)
                    sh = part.shard_of(blk)
                    br = self.store.decoder.block_rounds
                    if br is None:
                        nblk = int(np.bincount(
                            sh, minlength=part.n_shards).max())
                    else:
                        r = br[blk]
                        nblk = sum(
                            int(np.bincount(sh[r == v],
                                            minlength=part.n_shards).max())
                            for v in np.unique(r))
                else:
                    nblk = len(cur_blocks | pb)
            # plan_spans pow2-pads the span batch, so the gather output a
            # chunk materializes is pow2(B) * max_len — cost it that way,
            # or a 5-span chunk would quietly gather 8 rows past budget
            cost = nblk * bs + pow2(len(cur) + 1) * max(cur_maxlen, ln)
            over = ((budget is not None and cost > budget) or
                    (budget is None and nblk > self.max_blocks_per_chunk))
            if cur and over:
                yield self._execute(cur)
                cur, cur_blocks, cur_maxlen = [], set(), 0
            cur.append((s, ln))
            cur_blocks.update(pb)
            cur_maxlen = max(cur_maxlen, ln)
        if cur:
            yield self._execute(cur)

    def _execute(self, pieces) -> np.ndarray:
        """One chunk, in a host span numbered by the chunks before it."""
        with trace.span(trace.STREAM_CHUNK, chunk=len(self.chunk_log)):
            return self._execute_chunk(pieces)

    def _execute_chunk(self, pieces) -> np.ndarray:
        bs = self.store.block_size
        starts = np.asarray([p[0] for p in pieces], np.int64)
        lengths = np.asarray([p[1] for p in pieces], np.int64)
        plan = self.planner.plan_spans(starts, lengths)
        # plan_spans pow2-pads the SPAN batch, so the gather output is
        # pow2(B) * max_len — `chunks` costs it that way and gather_bytes
        # records it. The block-selection decode below stays exact-size
        # (pow2-padding the unique rows could double resident bytes and
        # break the budget); greedy packing keeps chunk shapes
        # near-constant so retracing stays bounded. The block cache is
        # bypassed here — streaming scans would thrash it.
        _, r0, _, uniq, row_map = plan.host_cover()
        dec = self.store.decoder
        if self.sharded is not None:
            # partitioned streaming: exact-size (pad=False) per-shard
            # decode, cache bypassed (streaming scans would thrash it).
            # decoded_blocks_last then counts PER-SHARD materialized rows
            # — the quantity the per-shard budget bounds.
            dec.launch_rounds_last = []
            dec.decoded_blocks_last = 0
            rows = self.sharded.stream_rows(
                uniq.astype(np.int64), verify=self.verify,
                on_error=self.on_error)
        else:
            decode = (dec.decode_blocks if self.mode2
                      else dec.decode_blocks_host_entropy)
            # pad_groups=False: depth-bucket launches stay exact-size here
            # for the same budget reason the selection is not pow2-padded
            rows = decode(uniq.astype(np.int32), verify=self.verify,
                          pad_groups=False, on_error=self.on_error)
        with trace.span(trace.GATHER):
            out = _gather_jit(rows, jnp.asarray(row_map), jnp.asarray(r0),
                              jnp.asarray(plan.lengths.astype(np.int32)),
                              block_size=bs, max_len=plan.max_len)
        with trace.span(trace.TO_HOST):
            host = np.asarray(out[:plan.n_queries])
        with trace.span(trace.STREAM_ASSEMBLE):
            parts = [host[i, :int(lengths[i])] for i in range(len(pieces))]
            payload = (np.concatenate(parts) if parts
                       else np.zeros(0, np.uint8))
        # decoded_blocks_last is what the decoder actually materialized —
        # == uniq for "ra", the summed anchor windows for checkpointed
        # wavefronts, the whole prefix for anchor-free global archives
        n_decoded = int(dec.decoded_blocks_last)
        self.chunk_log.append(ChunkStats(
            n_spans=len(pieces), n_blocks=n_decoded,
            decoded_bytes=n_decoded * bs,
            gather_bytes=plan.batch * plan.max_len,
            yielded_bytes=int(payload.size)))
        return payload


class ShardedExecutor:
    """Execute a plan with the unique-block decode fanned out over a mesh.

    Two residency regimes (`residency`):

      "partition"  — blocks partition into contiguous per-shard ranges
          and each device holds ONLY its slice of the compressed payload
          (`repro.core.residency.ShardedResidency`): compressed residency
          scales with mesh width. Decoded rows ride the per-shard block
          cache when `cache_blocks > 0` (any named policy or zero-arg
          factory, incl. "tinylfu"), and only requested rows assemble
          collectively.
      "replicate"  — the compressed archive is replicated and only the
          decode *work* (the block selection) shards: the small-archive
          fast path.
      "auto" (default) — partition when the archive can ("ra" mode with
          at least one block per shard), replicate otherwise.

    Both regimes are depth-bucketed (one launch per scheduled-rounds
    group) and `verify=True` digest-checks decoded blocks — shard-locally
    BEFORE assembly on the partitioned path, so `BlockDigestError` names
    the true global block id. Mode-2 only.
    """

    def __init__(self, store, mesh, axes: Tuple[str, ...] = ("data",),
                 residency: str = "auto", cache_blocks: int = 0,
                 cache_policy="lru", verify: bool = False,
                 on_error: str = "raise"):
        from repro.core.sharded_decode import _mesh_shards
        from repro.resilience import check_on_error
        if residency not in ("auto", "partition", "replicate"):
            raise ValueError(
                f"residency={residency!r} not in "
                f"('auto', 'partition', 'replicate')")
        self.store = store
        self.mesh = mesh
        self.axes = axes
        self.verify = verify
        self.on_error = check_on_error(on_error)
        dec = store.decoder
        if residency == "auto":
            residency = ("partition"
                         if dec.da.mode == "ra"
                         and dec.da.n_blocks >= _mesh_shards(mesh, axes)
                         else "replicate")
        self.residency = residency
        if residency == "partition":
            attach = getattr(store, "attach_sharded", None)
            if attach is not None:
                self.sharded = attach(mesh, axes=axes,
                                      cache_blocks=cache_blocks,
                                      cache_policy=cache_policy,
                                      verify=verify, on_error=on_error)
            else:   # bare-decoder store adapter: own the residency here
                from repro.core.residency import ShardedResidency
                self.sharded = ShardedResidency(
                    store, mesh, axes=axes, cache_blocks=cache_blocks,
                    cache_policy=cache_policy, verify=verify,
                    on_error=on_error)
        else:
            if cache_blocks:
                raise ValueError(
                    "cache_blocks needs the partitioned regime (the "
                    "replicated path has no per-shard slot buffer) — "
                    "pass residency='partition'")
            self.sharded = None

    def cache_info(self) -> dict:
        if self.sharded is None:
            return {"capacity": 0, "resident": 0, "hits": 0, "misses": 0,
                    "evictions": 0, "installs": 0, "coinstalls": 0,
                    "bytes_resident": 0, "buffer_bytes": 0,
                    "decode_launches": 0, "policy": "off"}
        return self.sharded.cache_info()

    def run(self, plan: DecodePlan) -> Tuple[jnp.ndarray, jnp.ndarray]:
        from repro.core.sharded_decode import sharded_decode_blocks
        B = plan.n_queries
        if B == 0:
            return (jnp.zeros((0, plan.max_len), jnp.uint8),
                    jnp.zeros((0,), jnp.int32))
        _, r0, _, uniq, row_map = plan.host_cover()
        dec = self.store.decoder
        if self.sharded is not None:
            # partitioned: the residency plane owns the per-shard split,
            # cache riding, depth bucketing, shard-local verify and the
            # parity recovery loop — shard-aware work composes there,
            # never in this executor
            rows = self.sharded.rows_for_blocks(uniq,
                                                on_error=self.on_error)
        else:
            dec.launch_rounds_last = []
            # depth-bucketed fan-out: one sharded launch per resolve-round
            # group, so a shallow bucket's shards stop after ITS rounds
            # instead of the archive-wide bound the plan-free path would
            # run. Routing through the plan (not dec._meta's default) is
            # what makes depth a plan-level property here, same as the
            # other executors.
            groups = plan.depth_groups()
            if groups is None or (len(groups) == 1
                                  and groups[0][0] >= (dec.da.max_depth
                                                       or 0)):
                rows = sharded_decode_blocks(dec, uniq, self.mesh,
                                             self.axes)
            else:
                parts = [sharded_decode_blocks(dec, uniq[idx], self.mesh,
                                               self.axes, n_rounds=rounds)
                         for rounds, idx in groups]
                order = np.concatenate([idx for _, idx in groups])
                inv = np.empty(uniq.size, np.int64)
                inv[order] = np.arange(uniq.size)
                rows = jnp.concatenate(parts, axis=0)[jnp.asarray(inv)]
            if self.verify:
                from repro.core.decoder import BlockDigestError
                try:
                    dec.verify_rows(uniq, rows)
                except BlockDigestError:
                    if self.on_error == "raise":
                        raise
                    # replicated regime: the full archive lives on every
                    # device, so recovery is just a verified re-decode
                    # through the decoder's parity loop
                    rows = dec.decode_blocks(
                        _pad_pow2(uniq.astype(np.int32)), verify=True,
                        on_error=self.on_error)[:uniq.size]
        with trace.span(trace.GATHER):
            out = _gather_jit(rows, jnp.asarray(row_map), jnp.asarray(r0),
                              jnp.asarray(plan.lengths.astype(np.int32)),
                              block_size=plan.block_size,
                              max_len=plan.max_len)
        return out[:B], jnp.asarray(plan.lengths[:B].astype(np.int32))
