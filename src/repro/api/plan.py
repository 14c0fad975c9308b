"""Query planning: any batch of addresses → one `DecodePlan`.

This module is THE place the covering-block math lives. Before the query
plane, three near-duplicate implementations of "which blocks cover these
output bytes" existed (`residency._fetch_staged`, `decoder.decode_range`,
and the serving path); they are all shims over `QueryPlanner` now. The
device-side twin of the same arithmetic lives in
`residency._fetch_dev_core` (it must: the jitted fast path computes the
covering set from the device start table), and `covering_blocks` below is
its host mirror — change one, change both.

A `DecodePlan` is the lowered form of a query batch: absolute byte spans,
padded batch/output geometry (jit-static), and — lazily, for the staged
cache/Mode-1/sharded paths — the unique covering-block selection plus the
ragged row map the gather kernel consumes.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Tuple

import numpy as np

from repro import trace
from repro.api.address import (Address, ByteRange, NameTable, ReadId, Region,
                               normalize)


def span_coords(starts: np.ndarray, lengths: np.ndarray, block_size: int
                ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Absolute byte spans → (b0, r0, end_blk): first covering block,
    in-block offset, exclusive covering end. The one host implementation
    of the paper's §4 position-invariant coordinate map."""
    starts = np.asarray(starts, np.int64)
    lengths = np.asarray(lengths, np.int64)
    b0 = starts // block_size
    r0 = (starts - b0 * block_size).astype(np.int32)
    end_blk = -(-(starts + lengths) // block_size)
    return b0, r0, end_blk


def covering_blocks(starts: np.ndarray, lengths: np.ndarray, block_size: int,
                    n_blocks: int, max_span: int
                    ) -> Tuple[np.ndarray, np.ndarray, np.ndarray,
                               np.ndarray]:
    """`span_coords` plus the (B, max_span) cover matrix: slots past a
    span's last block collapse onto its first block (they dedup away
    instead of decoding strangers)."""
    b0, r0, end_blk = span_coords(starts, lengths, block_size)
    cover = b0[:, None] + np.arange(max_span, dtype=np.int64)[None, :]
    cover = np.where(cover < end_blk[:, None], cover, b0[:, None])
    cover = np.clip(cover, 0, n_blocks - 1)
    return b0, r0, end_blk, cover


def anchor_floor(blocks: np.ndarray, anchors: np.ndarray) -> np.ndarray:
    """Per-block governing anchor: the greatest anchor block id <= block.
    `anchors` is the archive's sorted anchor table (anchors[0] == 0);
    empty → everything falls to block 0 (whole-prefix semantics)."""
    blocks = np.asarray(blocks, np.int64)
    anchors = np.asarray(anchors, np.int64)
    if anchors.size == 0:
        return np.zeros(blocks.shape, np.int64)
    i = np.searchsorted(anchors, blocks, side="right") - 1
    return anchors[np.maximum(i, 0)]


def anchor_window_groups(sel: np.ndarray, anchors: np.ndarray
                         ) -> list:
    """Partition a block selection by governing anchor window.

    Returns [(win_first, win_last, idx)] where `idx` are positions into
    `sel` (original order preserved within a group), `win_first` is the
    group's anchor and `win_last` its highest selected block — the decode
    window [win_first, win_last] is what a checkpointed-wavefront decode
    materializes for that group. Empty `anchors` yields one group rooted
    at block 0 (the anchor-free whole-prefix window)."""
    sel = np.asarray(sel, np.int64).reshape(-1)
    if sel.size == 0:
        return []
    gov = anchor_floor(sel, anchors)
    groups = []
    for a in np.unique(gov):
        idx = np.flatnonzero(gov == a)
        groups.append((int(a), int(sel[idx].max()), idx))
    return groups


def split_shards(blocks: np.ndarray, bounds: np.ndarray
                 ) -> Tuple[np.ndarray, np.ndarray]:
    """Global block ids → (owning shard, shard-local id) under a
    contiguous block partition. `bounds` is the i64[n_shards + 1]
    boundary table of a `ShardPartition` (bounds[s] .. bounds[s+1] is
    shard s's range). THE host implementation of the shard coordinate
    map — the residency/cache/executor layers all route through here."""
    blocks = np.asarray(blocks, np.int64).reshape(-1)
    bounds = np.asarray(bounds, np.int64)
    shard = np.searchsorted(bounds[1:], blocks, side="right")
    return shard, blocks - bounds[shard]


def shard_selection(shard: np.ndarray, local: np.ndarray, n_shards: int,
                    pad: bool = True
                    ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Lower a per-shard split to the collective decode geometry:

      loc      (n_shards, S) i32 — shard-local ids, row s holding shard
               s's selections left-packed; pad slots select local id 0
      flat_idx i64[n] — position of each input element in the flattened
               (n_shards * S) stacked decode output (the assembly gather)
      valid    bool(n_shards, S) — False on pad slots (verify masks them:
               a pad row decoded under a shallow bucket's rounds may be
               garbage, and it is never read)

    S is the max per-shard count, pow2-padded unless `pad=False` (the
    streaming budget path keeps exact sizes)."""
    shard = np.asarray(shard, np.int64)
    local = np.asarray(local, np.int64)
    counts = np.bincount(shard, minlength=n_shards)
    S = int(counts.max(initial=1))
    if pad:
        S = 1 << max(0, S - 1).bit_length()
    loc = np.zeros((n_shards, S), np.int32)
    valid = np.zeros((n_shards, S), bool)
    order = np.argsort(shard, kind="stable")
    group_first = np.concatenate([[0], np.cumsum(counts)[:-1]])
    pos_sorted = np.arange(shard.size) - group_first[shard[order]]
    loc[shard[order], pos_sorted] = local[order]
    valid[shard[order], pos_sorted] = True
    flat_idx = np.empty(shard.size, np.int64)
    flat_idx[order] = shard[order] * S + pos_sorted
    return loc, flat_idx, valid


def pad_pow2_spans(starts: np.ndarray, lengths: np.ndarray
                   ) -> Tuple[np.ndarray, np.ndarray]:
    """Pad a span batch to the next power of two by repeating the last span
    (bounded jit variants; dup slots add no unique blocks)."""
    n = starts.size
    cap = 1 << max(0, n - 1).bit_length() if n > 1 else 1
    if cap == n or n == 0:
        return starts, lengths
    reps = np.full(cap - n, -1)
    return (np.concatenate([starts, starts[reps]]),
            np.concatenate([lengths, lengths[reps]]))


@dataclasses.dataclass
class DecodePlan:
    """A lowered query batch. `starts`/`lengths` are pow2-padded absolute
    byte spans; the first `n_queries` rows are the real queries."""
    starts: np.ndarray            # i64[Bp]
    lengths: np.ndarray           # i64[Bp]
    n_queries: int                # pre-padding batch size
    block_size: int
    n_blocks: int
    max_len: int                  # padded output width  (jit-static)
    max_span: int                 # covering-span bound  (jit-static)
    device_ids: Optional[np.ndarray] = None   # i32[Bp]: whole-record ids —
                                  # covering set resolves from the DEVICE
                                  # start table (the fetch_reads fast path)
    max_depth: Optional[int] = None  # archive's recorded resolve-round
                                  # bound (v3 depth metadata; None =
                                  # legacy early-exit decode)
    block_rounds: Optional[np.ndarray] = dataclasses.field(
        default=None, repr=False)  # i32[n_blocks] per-block scheduled
                                  # resolve rounds (pow2 depth buckets,
                                  # `core.depth.scheduled_rounds`; global
                                  # blocks carry their anchor window's
                                  # schedule) — the first-class depth
                                  # field the executors group launches by
    _cover: Optional[tuple] = dataclasses.field(default=None, repr=False)

    # ------------------------------------------------------------- geometry
    @property
    def batch(self) -> int:
        return int(self.starts.size)

    @property
    def u_cap(self) -> int:
        return min(self.batch * self.max_span, self.n_blocks)

    def geom(self) -> tuple:
        """The static geometry tuple the jitted device pipeline keys on."""
        return (self.block_size, self.n_blocks, self.max_len, self.max_span,
                self.u_cap)

    @property
    def total_payload_bytes(self) -> int:
        return int(self.lengths[:self.n_queries].sum())

    @property
    def padded_output_bytes(self) -> int:
        return self.batch * self.max_len

    # ----------------------------------------------------------- host cover
    def host_spans(self) -> tuple:
        """(b0, r0, end_blk) — the cheap per-span covering coordinates the
        jitted `_fetch_dev_core` path consumes (it deduplicates the
        covering set on device, so no host unique/row_map is built)."""
        return span_coords(self.starts, self.lengths, self.block_size)

    def host_cover(self) -> tuple:
        """(b0, r0, end_blk, unique_blocks, row_map) — computed lazily; only
        the staged (LRU / Mode-1) and sharded executors need it, the jitted
        device path recomputes the covering set on device."""
        if self._cover is None:
            b0, r0, end_blk, cover = covering_blocks(
                self.starts, self.lengths, self.block_size, self.n_blocks,
                self.max_span)
            uniq = np.unique(cover)
            row_map = np.searchsorted(uniq, cover).astype(np.int32)
            self._cover = (b0, r0, end_blk, uniq, row_map)
        return self._cover

    def n_cover_blocks(self) -> int:
        """Unique covering blocks of this plan — the decode-work unit the
        serving frontend's service-time estimator prices dispatches in
        (a batch costs roughly fixed launch overhead + per-block decode,
        and hits/misses split from exactly this set at the cache step)."""
        return int(self.host_cover()[3].size)

    def anchor_windows(self, anchors: np.ndarray) -> list:
        """This plan's covering set grouped by governing anchor window:
        [(win_first, win_last, idx-into-uniq)]. The total decode work of a
        checkpointed-wavefront execution is sum(win_last - win_first + 1)
        blocks — bounded by covering-span + anchor_interval per group
        instead of the whole prefix. Cost-prediction API: the execution
        paths use the same `anchor_floor`/`anchor_window_groups`
        primitives (decoder groups, StreamingExecutor widens pieces);
        this method lets planners/telemetry price a plan without running
        it, and the anchor tests assert it against the decoder's actual
        `decoded_blocks_last`."""
        _, _, _, uniq, _ = self.host_cover()
        return anchor_window_groups(uniq, anchors)

    def anchor_decode_blocks(self, anchors: np.ndarray) -> int:
        """Blocks a checkpointed-wavefront ("global") decode of this plan
        touches: the summed anchor-window sizes. Empty `anchors` means one
        window rooted at block 0, i.e. the whole covering prefix."""
        return sum(last - first + 1
                   for first, last, _ in self.anchor_windows(anchors))

    # ---------------------------------------------------------- depth groups
    def depth_groups(self) -> Optional[list]:
        """The plan's unique covering set partitioned by scheduled resolve
        rounds: [(n_rounds, idx-into-uniq)], ascending. The executors
        issue ONE launch per group, so a depth-3 selection of a depth-8
        archive runs 3 rounds, not 8. None = legacy archive without depth
        metadata (every launch keeps the early-exit resolver)."""
        if self.block_rounds is None:
            return None
        _, _, _, uniq, _ = self.host_cover()
        r = self.block_rounds[uniq]
        return [(int(v), np.flatnonzero(r == v)) for v in np.unique(r)]

    # ---------------------------------------------------------- shard split
    def shard_cover(self, bounds: np.ndarray) -> tuple:
        """(shard, local) split of this plan's unique covering set under a
        contiguous block partition — the plan-level entry the sharded
        residency/cache layers compose at (shard-aware work splits HERE,
        never inside executors)."""
        _, _, _, uniq, _ = self.host_cover()
        return split_shards(uniq, bounds)

    def needed_rounds(self) -> Optional[int]:
        """Max scheduled rounds over the covering set — the critical-path
        round count of a bucketed execution. Strictly below `max_depth`
        exactly when the whole selection avoids the archive's deepest
        bucket (the case worth rerouting the jitted fast path for)."""
        if self.block_rounds is None:
            return None
        _, _, _, uniq, _ = self.host_cover()
        return int(self.block_rounds[uniq].max(initial=0))


@dataclasses.dataclass
class CachePlan:
    """The cache step of a DecodePlan: its unique covering set split into
    buffer-resident hits and a miss set, with the cache slots the admitted
    misses will install into. Produced by `BlockCache.plan`
    (`repro.api.cache`) with vectorized numpy — no per-block Python — and
    consumed by one decode launch over the pow2-padded miss set plus one
    jitted scatter/gather that installs the new rows and assembles the
    (U, block_size) row tensor."""
    uniq: np.ndarray            # i64[U] unique covering block ids
    src_is_miss: np.ndarray     # bool[U]: row comes from the miss decode
    src_idx: np.ndarray         # i32[U]: cache slot (hit) | miss row (miss)
    miss_blocks: np.ndarray     # i64[M] blocks needing decode (ONE launch)
    install_slots: np.ndarray   # i32[M]: slot per miss; == capacity when
                                # the policy did not admit the block
    n_hits: int
    n_misses: int
    n_installed: int
    n_evicted: int
    miss_groups: Optional[list] = None  # [(n_rounds, idx-into-miss_blocks)]
                                # ascending — the miss set partitioned by
                                # scheduled resolve rounds (None = legacy
                                # archive). The miss decode buckets these
                                # into one launch per group.

    @property
    def n_uniq(self) -> int:
        return int(self.uniq.size)


def split_cache_hits(uniq: np.ndarray, slot_of: np.ndarray
                     ) -> Tuple[np.ndarray, np.ndarray]:
    """Vectorized hit/miss split of a covering set against a block-id →
    slot map (-1 = absent): returns (hit_mask bool[U], slots i32[U])."""
    slots = slot_of[np.asarray(uniq, np.int64)]
    return slots >= 0, slots


class QueryPlanner:
    """Lowers any batch of addresses to a single DecodePlan.

    Works over a `CompressedResidentStore` (or the bare-decoder adapter in
    `repro.api.executors`); Region addresses additionally need a
    `NameTable`. Every legacy decode entry point routes through here.
    """

    def __init__(self, store, name_table: Optional[NameTable] = None):
        self.store = store
        self.name_table = name_table
        da = store.decoder.da
        self.block_size = da.block_size
        self.n_blocks = da.n_blocks
        self.raw_size = da.raw_size

    # Depth fields come from the LIVE DeviceArchive at plan time, not a
    # construction-time snapshot — a planner built before depth metadata
    # was attached (or against a swapped decoder) would otherwise pin
    # every plan to stale rounds.
    @property
    def max_depth(self) -> Optional[int]:
        return self.store.decoder.da.max_depth

    @property
    def block_rounds(self) -> Optional[np.ndarray]:
        return self.store.decoder.block_rounds

    # ------------------------------------------------------------ fast paths
    @trace.spanned(trace.PLAN)
    def plan_read_ids(self, ids: np.ndarray) -> DecodePlan:
        """All-ReadId batches: geometry is store-static and the covering set
        resolves from the device start table (zero per-query host math)."""
        idx = self.store.index
        if idx is None:
            raise ValueError("read-id addresses require a ReadIndex")
        ids = np.asarray(ids, np.int64).reshape(-1)
        if ids.size and (ids.min() < 0 or ids.max() >= idx.n_reads):
            raise IndexError(
                f"read id out of range [0, {idx.n_reads}): "
                f"{int(ids.min())}..{int(ids.max())}")
        starts64 = self.store._starts64
        starts, lengths = pad_pow2_spans(
            starts64[ids], starts64[ids + 1] - starts64[ids])
        dev_ids = np.empty(starts.size, np.int64)
        dev_ids[:ids.size] = ids
        dev_ids[ids.size:] = ids[-1] if ids.size else 0
        return DecodePlan(
            starts=starts, lengths=lengths, n_queries=ids.size,
            block_size=self.block_size, n_blocks=self.n_blocks,
            max_len=self.store._max_len, max_span=self.store._max_span,
            device_ids=dev_ids.astype(np.int32), max_depth=self.max_depth,
            block_rounds=self.block_rounds)

    def plan_records(self, ids: np.ndarray, record_bytes: int) -> DecodePlan:
        """Fixed-size records: arithmetic spans, no index needed (the
        tokenized-corpus training input path)."""
        ids = np.asarray(ids, np.int64).reshape(-1)
        if ids.size and (ids.min() < 0
                         or (int(ids.max()) + 1) * record_bytes
                         > self.raw_size):
            raise IndexError(
                f"record id out of range for {self.raw_size}-byte archive: "
                f"{int(ids.min())}..{int(ids.max())} × {record_bytes}B")
        starts, lengths = pad_pow2_spans(
            ids * record_bytes,
            np.full(ids.size, record_bytes, np.int64))
        return DecodePlan(
            starts=starts, lengths=lengths, n_queries=ids.size,
            block_size=self.block_size, n_blocks=self.n_blocks,
            max_len=record_bytes,
            max_span=record_bytes // self.block_size + 2,
            max_depth=self.max_depth, block_rounds=self.block_rounds)

    @trace.spanned(trace.PLAN)
    def plan_spans(self, starts: np.ndarray, lengths: np.ndarray,
                   max_len: Optional[int] = None) -> DecodePlan:
        """Raw absolute byte spans (ByteRange batches, streaming chunks).

        `max_len` widens the padded output geometry past the batch's
        longest span — callers that see many distinct lengths (e.g.
        `decode_range`) pass a block-quantized bound so the jitted
        pipeline retraces per block bucket, not per byte length.
        """
        starts = np.asarray(starts, np.int64).reshape(-1)
        lengths = np.asarray(lengths, np.int64).reshape(-1)
        if starts.size:
            if starts.min() < 0 or (starts + lengths).max() > self.raw_size:
                raise IndexError(
                    f"byte span out of range [0, {self.raw_size})")
            if lengths.min() < 0:
                raise IndexError("negative-length byte span")
        n = starts.size
        if max_len is None:
            max_len = max(1, int(lengths.max(initial=1)))
        elif lengths.size and max_len < int(lengths.max()):
            raise ValueError(
                f"max_len={max_len} below longest span {int(lengths.max())}")
        b0 = starts // self.block_size
        end_blk = -(-(starts + lengths) // self.block_size)
        max_span = max(1, int((end_blk - b0).max(initial=1)))
        starts, lengths = pad_pow2_spans(starts, lengths)
        return DecodePlan(
            starts=starts, lengths=lengths, n_queries=n,
            block_size=self.block_size, n_blocks=self.n_blocks,
            max_len=max_len, max_span=max_span, max_depth=self.max_depth,
            block_rounds=self.block_rounds)

    # -------------------------------------------------------------- general
    def resolve(self, addrs: Sequence[Address]
                ) -> Tuple[np.ndarray, np.ndarray, Optional[np.ndarray]]:
        """Addresses → (starts i64[B], lengths i64[B], whole-record ids or
        None). Region names resolve through the device-resident NameTable
        in at most two batched lookups (a full-string pre-pass, then only
        the parse-produced names). Strings follow samtools precedence:
        the FULL string is tried as a record name first, so Illumina-style
        names ending in numeric `:x:y` fields resolve whole-record before
        any `:start-end` suffix is interpreted as coordinates."""
        typed = list(addrs)
        rid_at = {}                    # address index → resolved read id
        strs = [(i, a.encode() if isinstance(a, str) else bytes(a))
                for i, a in enumerate(typed)
                if isinstance(a, (str, bytes))]
        if strs and self.name_table is not None:
            hit = self.name_table.lookup([s for _, s in strs],
                                         missing_ok=True)
            for (i, s), rid in zip(strs, hit):
                if rid >= 0:           # full-string name hit: keep the id
                    typed[i] = Region(s)
                    rid_at[i] = int(rid)
                else:
                    typed[i] = normalize(s)
        typed = [normalize(a) for a in typed]
        pending = [(i, a) for i, a in enumerate(typed)
                   if isinstance(a, Region) and i not in rid_at]
        if pending:
            if self.name_table is None:
                raise ValueError(
                    "Region addresses require a NameTable (build the "
                    "archive with names, e.g. GenomicArchive.from_bytes)")
            looked = self.name_table.lookup([a.name for _, a in pending])
            rid_at.update((i, int(r)) for (i, _), r in zip(pending, looked))

        starts64 = self.store._starts64
        idx = self.store.index
        starts = np.zeros(len(typed), np.int64)
        lengths = np.zeros(len(typed), np.int64)
        ids = np.zeros(len(typed), np.int64)
        whole = True
        for i, a in enumerate(typed):
            if isinstance(a, ByteRange):
                if not 0 <= a.lo <= a.hi <= self.raw_size:
                    raise IndexError(
                        f"byte range [{a.lo}, {a.hi}) outside "
                        f"[0, {self.raw_size})")
                starts[i], lengths[i] = a.lo, a.hi - a.lo
                whole = False
                continue
            if isinstance(a, ReadId):
                if idx is None:
                    raise ValueError("read-id addresses require a ReadIndex")
                if not 0 <= a.i < idx.n_reads:
                    raise IndexError(
                        f"read id {a.i} out of range [0, {idx.n_reads})")
                rid = a.i
                lo, hi = 0, None
            else:                                   # Region
                rid = rid_at[i]
                lo, hi = a.start or 0, a.end
            s, e = int(starts64[rid]), int(starts64[rid + 1])
            if hi is None:
                hi = e - s
            if not 0 <= lo <= hi <= e - s:
                raise IndexError(
                    f"region [{lo}, {hi}) outside record {rid} "
                    f"({e - s} bytes)")
            starts[i], lengths[i] = s + lo, hi - lo
            ids[i] = rid
            whole = whole and lo == 0 and hi == e - s
        return starts, lengths, (ids if whole and typed else None)

    @trace.spanned(trace.PLAN)
    def plan(self, addrs: Sequence[Address]) -> DecodePlan:
        """The general entry: any mix of addresses → one DecodePlan. Pure
        whole-record batches keep the device start-table fast path; span
        batches quantize the padded width to a block multiple so distinct
        byte lengths share a jit trace."""
        if isinstance(addrs, np.ndarray) and addrs.dtype.kind in "iu":
            return self.plan_read_ids(addrs)
        starts, lengths, ids = self.resolve(addrs)
        if ids is not None:
            return self.plan_read_ids(ids)
        quant = -(-max(1, int(lengths.max(initial=1)))
                  // self.block_size) * self.block_size
        return self.plan_spans(starts, lengths, max_len=quant)
