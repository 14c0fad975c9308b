"""Compressed-resident store (paper §4, "compressed-resident genomics").

The archive lives in device memory *compressed*; any region decodes on
demand in one kernel launch without touching the rest. This is the direct
answer to the D2H-ceiling argument of §6.1: the consumer is device-resident,
so decoded bytes never cross the host link.

Batched random access (`fetch_reads`) is the serving / data-pipeline entry
point: N read ids — arbitrary, variable-length FASTQ reads — flow through
ONE pipeline:

    ids → start-table lookup (device-resident, int32 block + in-block
    offset pairs: lossless for ≥ 2 GiB archives where a flat int32 table
    truncates) → covering-block computation → unique-block selection
    decode → ragged per-read gather into a padded (B, max_len) byte matrix
    plus a length vector

entirely on device. `fetch_read` (single read) and `fetch_records`
(fixed-size records, the training input path) are thin views over the same
pipeline. An optional decoded-block cache (`repro.api.cache.BlockCache`:
a preallocated device buffer + CachePlan hit/miss split, pluggable
LRU/frequency/pin-range policies) makes hot blocks skip re-decode across
calls; the gather stage stays jitted either way.

Since the query-plane redesign, `fetch_reads`/`fetch_records` are
compatibility shims over `repro.api` (QueryPlanner → DeviceExecutor): the
covering-block math lives in `repro.api.plan`, and this module keeps the
jitted device cores (`_fetch_reads_core`, `_fetch_dev_core`,
`_gather_reads_core`) plus the block-cache hookup the executors reuse.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional, Sequence, Tuple, Union

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.decoder import (BlockDigestError, Decoder, _decode_sel_core,
                                _pad_pow2)
from repro.core.format import Archive
from repro.core.index import ReadIndex, split_starts


@dataclasses.dataclass
class ResidencyStats:
    compressed_device_bytes: int
    raw_size: int
    n_blocks: int

    @property
    def residency_fraction_of_raw(self) -> float:
        return self.compressed_device_bytes / max(1, self.raw_size)


# --------------------------------------------------------------- jitted core
def _gather_reads_core(rows: jnp.ndarray, row_map: jnp.ndarray,
                       local: jnp.ndarray, lengths: jnp.ndarray,
                       block_size: int, max_len: int) -> jnp.ndarray:
    """(U, block_size) decoded rows + per-read covering-row map → padded
    (B, max_len) u8. The ragged gather: each read pulls its bytes out of
    its covering rows at its in-block offset; beyond-length tail is 0."""
    B, span = row_map.shape
    rec = rows[row_map]                         # (B, span, block_size)
    flat = rec.reshape(B, span * block_size)
    cols = local[:, None] + jnp.arange(max_len, dtype=jnp.int32)[None, :]
    cols = jnp.minimum(cols, span * block_size - 1)
    out = jnp.take_along_axis(flat, cols, axis=1)
    mask = jnp.arange(max_len, dtype=jnp.int32)[None, :] < lengths[:, None]
    return jnp.where(mask, out, 0).astype(jnp.uint8)


_gather_jit = partial(jax.jit,
                      static_argnames=("block_size", "max_len"))(
                          _gather_reads_core)


def _fetch_dev_core(arrays, b0, local, lengths, end_blk, da_meta, backend,
                    geom):
    """Device-side tail of the pipeline: covering blocks → unique selection
    decode → ragged gather. geom = (block_size, n_blocks, max_len,
    max_span, u_cap) — all static."""
    block_size, n_blocks, max_len, max_span, u_cap = geom
    blocks = b0[:, None] + jnp.arange(max_span, dtype=jnp.int32)[None, :]
    # slots past a read's last covering block collapse onto its first
    # block, so they dedup away instead of decoding strangers
    blocks = jnp.where(blocks < end_blk[:, None], blocks, b0[:, None])
    blocks = jnp.clip(blocks, 0, n_blocks - 1)
    uniq, inv = jnp.unique(blocks.reshape(-1), return_inverse=True,
                           size=u_cap, fill_value=0)
    mode = da_meta[5]
    if mode == "global":
        # anchor-free wavefront archives decode whole-prefix by
        # construction (checkpointed wavefronts never reach this core:
        # DeviceExecutor routes them through the staged path, where the
        # decoder bounds the decode to per-plan anchor windows)
        flat = _decode_sel_core(arrays, jnp.arange(n_blocks, dtype=jnp.int32),
                                da_meta, backend)
        rows = flat.reshape(n_blocks, block_size)[uniq]
    else:
        rows = _decode_sel_core(arrays, uniq.astype(jnp.int32), da_meta,
                                backend)
    row_map = inv.reshape(b0.shape[0], max_span).astype(jnp.int32)
    return _gather_reads_core(rows, row_map, local, lengths, block_size,
                              max_len)


def _fetch_reads_core(arrays, starts_blk, starts_rem, ids, da_meta, backend,
                     geom):
    """ids → (padded reads, lengths), start-table lookup on device."""
    block_size = geom[0]
    ids = ids.astype(jnp.int32)
    b0 = starts_blk[ids]
    r0 = starts_rem[ids]
    b1 = starts_blk[ids + 1]
    r1 = starts_rem[ids + 1]
    lengths = (b1 - b0) * block_size + (r1 - r0)
    end_blk = b1 + (r1 > 0).astype(jnp.int32)      # exclusive covering end
    out = _fetch_dev_core(arrays, b0, r0, lengths, end_blk, da_meta,
                          backend, geom)
    return out, lengths


_fetch_reads_jit = partial(jax.jit,
                           static_argnames=("da_meta", "backend", "geom"))(
                               _fetch_reads_core)
_fetch_dev_jit = partial(jax.jit,
                         static_argnames=("da_meta", "backend", "geom"))(
                             _fetch_dev_core)


class CompressedResidentStore:
    """Archive + index resident on device; decode-on-demand reads.

    cache_blocks > 0 enables the device-resident decoded-block cache
    (`repro.api.cache.BlockCache`): hot blocks skip re-decode across
    fetch calls (serving working sets are Zipfian; the cache bounds
    decode work to the cold tail), misses decode in one pow2-padded
    launch, and a single jitted scatter/gather installs/assembles rows —
    decoded bytes never leave the device. `cache_policy` selects
    eviction/admission: "lru", "freq" (frequency-aware admission), or
    any `EvictionPolicy` instance (e.g. `PinRangePolicy`). Mode 1
    fetches (`mode2=False`: host entropy decode, device match
    resolution) always run through the staged path since their entropy
    stage lives on host.
    """

    def __init__(self, archive: Archive, index: Optional[ReadIndex] = None,
                 backend: str = "auto", cache_blocks: int = 0,
                 cache_policy: Union[str, object] = "lru",
                 verify: bool = False, on_error: str = "raise"):
        from repro.resilience import check_on_error
        self.decoder = Decoder(archive, backend=backend)
        self.index = index
        self.block_size = archive.block_size
        # store-wide defaults for the detect→recover→degrade knobs; every
        # fetch entry point accepts per-call overrides
        self.verify = bool(verify)
        self.on_error = check_on_error(on_error)
        self._cache_cap = int(cache_blocks)
        if self._cache_cap > 0:
            from repro.api.cache import BlockCache
            self._cache = BlockCache(self._cache_cap, self.block_size,
                                     archive.n_blocks, policy=cache_policy,
                                     block_rounds=self.decoder.block_rounds)
        else:
            self._cache = None
        if index is not None:
            blk, rem = split_starts(index.starts, self.block_size)
            self._starts_blk = jnp.asarray(blk)       # i32[n_reads + 1]
            self._starts_rem = jnp.asarray(rem)       # i32[n_reads + 1]
            self._starts64 = index.starts.astype(np.int64)
            lens = np.diff(self._starts64)
            self._max_len = max(int(lens.max(initial=1)), 1)
            b0 = self._starts64[:-1] // self.block_size
            eb = -(-self._starts64[1:] // self.block_size)
            self._max_span = max(int((eb - b0).max(initial=1)), 1)
        else:
            self._starts_blk = self._starts_rem = None
            self._starts64 = None
            self._max_len = self._max_span = 1
        self._planner = self._executor = None
        # mesh-partitioned residency, attached on demand (attach_sharded)
        self.sharded: Optional["ShardedResidency"] = None

    def _api(self):
        """Lazy (planner, executor) pair — repro.api imports this module."""
        if self._planner is None:
            from repro.api.executors import DeviceExecutor
            from repro.api.plan import QueryPlanner
            self._planner = QueryPlanner(self)
            self._executor = DeviceExecutor(self)
        return self._planner, self._executor

    # ---------------------------------------------------------------- stats
    def stats(self) -> ResidencyStats:
        return ResidencyStats(
            compressed_device_bytes=self.decoder.da.device_bytes,
            raw_size=self.decoder.da.raw_size,
            n_blocks=self.decoder.da.n_blocks,
        )

    @property
    def cache_hits(self) -> int:
        if self._cache is not None:
            return self._cache.hits
        if self.sharded is not None and self.sharded._cache is not None:
            return self.sharded._cache.hits
        return 0

    @property
    def cache_misses(self) -> int:
        if self._cache is not None:
            return self._cache.misses
        if self.sharded is not None and self.sharded._cache is not None:
            return self.sharded._cache.misses
        return 0

    def cache_info(self) -> dict:
        """The block cache's counters, and the decoder's cumulative
        decode counters behind its misses (`Decoder.decode_info`) under
        `decoder_<counter>`."""
        info = self._cache_counters()
        info.update({f"decoder_{k}": v
                     for k, v in self.decoder.decode_info().items()})
        return info

    def _cache_counters(self) -> dict:
        if self._cache is None:
            # when only the mesh-partitioned residency carries a cache,
            # its per-shard counters ARE the store's cache accounting
            if self.sharded is not None and self.sharded._cache is not None:
                return self.sharded.cache_info()
            # same keys as BlockCache.info(), all zeroed — callers can
            # read counters without checking whether the cache is on
            return {"capacity": 0, "resident": 0, "hits": 0, "misses": 0,
                    "evictions": 0, "installs": 0, "coinstalls": 0,
                    "bytes_resident": 0, "buffer_bytes": 0,
                    "decode_launches": 0, "policy": "off"}
        return self._cache.info()

    # ------------------------------------------------- sharded residency
    def attach_sharded(self, mesh, axes: Tuple[str, ...] = ("data",),
                       cache_blocks: int = 0,
                       cache_policy: Union[str, object] = "lru",
                       verify: bool = False,
                       on_error: str = "raise") -> "ShardedResidency":
        """Partition the compressed archive across `mesh` and attach the
        sharded residency plane (idempotent for a matching mesh/axes —
        repeat calls with the same geometry reuse the existing partition
        and its warm per-shard cache)."""
        sr = self.sharded
        if (sr is not None and sr.part.mesh == mesh and sr.axes == axes
                and sr.cache_blocks == int(cache_blocks)
                and sr.verify == verify and sr.on_error == on_error):
            return sr
        self.sharded = ShardedResidency(
            self, mesh, axes=axes, cache_blocks=cache_blocks,
            cache_policy=cache_policy, verify=verify, on_error=on_error)
        return self.sharded

    # ------------------------------------------------------------ internals
    def _rows_for_blocks(self, uniq: np.ndarray, mode2: bool,
                         verify: bool = False,
                         on_error: str = "raise") -> jnp.ndarray:
        """(U,) unique block ids → (U, block_size) decoded rows, through the
        device-resident block cache when enabled. With `verify`, rows
        digest-check inside the decode (recovering per `on_error`); any
        block the decode reports corrupt (`Decoder.last_bad_blocks`) is
        invalidated from the cache right after — the CachePlan registered
        it resident BEFORE the decode, and a quarantined block's zero row
        must never be served as a hit."""
        dec = self.decoder
        base = (dec.decode_blocks if mode2
                else dec.decode_blocks_host_entropy)
        if verify:
            # an all-hit cache plan never reaches the decoder — clear the
            # per-call outcome state here so stale bad-block reports from
            # an earlier call cannot leak into this one's corrupt mask
            dec.last_bad_blocks = np.zeros(0, np.int64)
            dec.last_suspect_blocks = np.zeros(0, np.int64)
            def decode(sel, pad_groups=True):
                return base(sel, verify=True, pad_groups=pad_groups,
                            on_error=on_error)
        else:
            decode = base
        if self._cache is None:
            # pad the selection to a power of two so random batches don't
            # retrace the decode kernels for every distinct unique count
            return decode(_pad_pow2(uniq.astype(np.int32)))[:uniq.size]
        if dec.da.mode != "global":
            rows = self._cache.rows_for(uniq, decode)
            if verify and dec.last_bad_blocks.size:
                self._cache.invalidate(dec.last_bad_blocks)
            return rows
        # global/wavefront: a miss decode materializes whole anchor
        # windows — co-install the window rows the CachePlan did not ask
        # for into free slots, so a scan over the window is ONE launch.
        # Collection is opt-in (retaining decoded windows costs device
        # memory) and always cleared before returning.
        dec.collect_window_rows = True
        dec.last_window_rows = []
        try:
            rows = self._cache.rows_for(uniq, decode)
            if verify and dec.last_bad_blocks.size:
                self._cache.invalidate(dec.last_bad_blocks)
            # repaired blocks' windows were collected twice (pre-repair
            # garbage first) — exclude every once-suspect block from the
            # speculative co-install, not just the finally-bad ones
            bad = (dec.last_suspect_blocks if verify
                   else np.zeros(0, np.int64))
            for first, wrows in dec.last_window_rows:
                blks = np.arange(first, first + wrows.shape[0])
                if bad.size:
                    # a window touched by corruption may hold pre-repair
                    # garbage rows — only provably-good rows co-install
                    good = np.flatnonzero(~np.isin(blks, bad))
                    if good.size == 0:
                        continue
                    self._cache.install_extras(blks[good],
                                               wrows[jnp.asarray(good)])
                else:
                    self._cache.install_extras(blks, wrows)
        finally:
            dec.collect_window_rows = False
            dec.last_window_rows = []
        return rows

    # -------------------------------------------------------------- lookups
    def fetch_reads(self, ids: Sequence[int], mode2: bool = True,
                    verify: Optional[bool] = None,
                    on_error: Optional[str] = None
                    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Batched variable-length random access.

        (B,) read ids → ((B, max_read_len) u8 zero-padded reads,
        (B,) i32 lengths) in one selection decode. Requires a ReadIndex.
        Compatibility shim: lowers through the query plane
        (`QueryPlanner.plan_read_ids` → `DeviceExecutor`).

        `verify`/`on_error` override the store defaults for this call;
        per-read corrupt outcomes (on_error="partial") are in
        `last_corrupt` afterwards.
        """
        assert self.index is not None, "fetch_reads requires a ReadIndex"
        ids_np = np.asarray(ids, np.int64).reshape(-1)
        if ids_np.size == 0:
            return (jnp.zeros((0, self._max_len), jnp.uint8),
                    jnp.zeros((0,), jnp.int32))
        planner, executor = self._api()
        return executor.run(planner.plan_read_ids(ids_np), mode2=mode2,
                            verify=verify, on_error=on_error)

    @property
    def last_corrupt(self) -> np.ndarray:
        """Per-address corrupt mask of the most recent executor run
        (bool[B]; all-False unless on_error="partial" hit bad blocks)."""
        if self._executor is None:
            return np.zeros(0, bool)
        return self._executor.last_corrupt

    def fetch_read(self, r: int, mode2: bool = True) -> np.ndarray:
        """Single-read random access: the B=1 case of `fetch_reads`."""
        out, lens = self.fetch_reads(np.array([r], np.int64), mode2=mode2)
        return np.asarray(out[0])[:int(lens[0])]

    def fetch_block_range(self, b0: int, b1: int, mode2: bool = True
                          ) -> jnp.ndarray:
        """Position-invariant block-range decode (stays on device): (b1-b0,
        block_size) u8 rows, tail bytes of a partial final block zeroed.

        Routed through the query plane like every other entry point — one
        block-aligned span plan — so ranges ride the block cache when
        enabled and the pow2-padded lowering keeps distinct range lengths
        from retracing the decode kernels (the old direct
        `decoder.decode_blocks(arange)` call did neither)."""
        n_blocks = self.decoder.da.n_blocks
        if not 0 <= b0 <= b1 <= n_blocks:
            raise IndexError(
                f"block range [{b0}, {b1}) outside [0, {n_blocks})")
        if b0 == b1:
            return jnp.zeros((0, self.block_size), jnp.uint8)
        a = self.decoder.archive
        planner, executor = self._api()
        plan = planner.plan_spans(a.block_start[b0:b1],
                                  a.block_len[b0:b1].astype(np.int64),
                                  max_len=self.block_size)
        rows, _ = executor.run(plan, mode2=mode2)
        return rows

    def fetch_records(self, ids: Sequence[int], record_bytes: int,
                      mode2: bool = True) -> jnp.ndarray:
        """Batched fixed-record fetch: (B,) ids → (B, record_bytes) u8.
        Same pipeline as `fetch_reads` with arithmetic start offsets, so it
        needs no index (the tokenized-corpus training input path).
        Compatibility shim over `QueryPlanner.plan_records`."""
        ids_np = np.asarray(ids, np.int64).reshape(-1)
        if ids_np.size == 0:
            return jnp.zeros((0, record_bytes), jnp.uint8)
        planner, executor = self._api()
        out, _ = executor.run(planner.plan_records(ids_np, record_bytes),
                              mode2=mode2)
        return out


class ShardedResidency:
    """Mesh-partitioned compressed residency for one store.

    Owns the `ShardPartition` (each device holds only its contiguous
    block range's payload slice — compressed residency scales with mesh
    width) plus, when `cache_blocks > 0`, the per-shard decoded-block
    cache (`repro.api.cache.ShardedBlockCache`: every shard runs its own
    hit/miss split against its own slot range of one stacked
    mesh-sharded buffer). `verify=True` digest-checks every decoded
    stacked launch shard-locally BEFORE assembly (`BlockDigestError`
    names the true global block id).

    This is the residency plane `ShardedExecutor` and `StreamingExecutor`
    ride; shard-aware work composes here and at `CachePlan`, never inside
    the executors themselves.
    """

    def __init__(self, store: CompressedResidentStore, mesh,
                 axes: Tuple[str, ...] = ("data",), cache_blocks: int = 0,
                 cache_policy: Union[str, object] = "lru",
                 verify: bool = False, on_error: str = "raise"):
        from repro.core.sharded_decode import partition_archive
        from repro.resilience import check_on_error
        self.store = store
        self.decoder = store.decoder
        self.axes = axes
        self.verify = verify
        self.on_error = check_on_error(on_error)
        # partition rebuilds performed by the recovery path (payload
        # corruption healed on the flat copy, or a lost shard re-seeded)
        self.shard_rebuilds = 0
        self.cache_blocks = int(cache_blocks)
        self.part = partition_archive(store.decoder, mesh, axes)
        if self.cache_blocks > 0:
            from repro.api.cache import ShardedBlockCache
            self._cache = ShardedBlockCache(
                self.cache_blocks, store.block_size, self.part.n_blocks,
                self.part, policy=cache_policy,
                block_rounds=store.decoder.block_rounds)
        else:
            self._cache = None

    # ----------------------------------------------------------- accounting
    def per_shard_bytes(self) -> int:
        """Device-resident bytes on ONE shard: its compressed payload
        slice plus its slot range of the decoded-block cache buffer."""
        tot = self.part.per_shard_device_bytes
        if self._cache is not None:
            tot += self._cache.per_shard_buffer_bytes
        return tot

    def device_bytes(self) -> int:
        """Total device-resident bytes across the mesh (what a serving
        budget bounds): sum of every shard's compressed + cache bytes."""
        return self.part.n_shards * self.per_shard_bytes()

    def cache_info(self) -> dict:
        if self._cache is None:
            return {"capacity": 0, "resident": 0, "hits": 0, "misses": 0,
                    "evictions": 0, "installs": 0, "coinstalls": 0,
                    "bytes_resident": 0, "buffer_bytes": 0,
                    "decode_launches": 0, "policy": "off"}
        return self._cache.info()

    # ---------------------------------------------------- recovery (PR 10)
    def _quarantine_hit(self, uniq: np.ndarray) -> bool:
        q = self.decoder.quarantined
        return bool(q) and bool(
            np.isin(uniq, np.fromiter(q, np.int64, len(q))).any())

    def _degraded_rows(self, uniq: np.ndarray,
                       pad: bool = True) -> jnp.ndarray:
        """Partial-failure fallback: serve through the UNPARTITIONED
        decoder with partial semantics (quarantined blocks read zeros,
        nothing installs into the sharded cache)."""
        dec = self.decoder
        sel = (_pad_pow2(uniq.astype(np.int32)) if pad
               else uniq.astype(np.int32))
        return dec.decode_blocks(sel, verify=True, on_error="partial",
                                 pad_groups=pad)[:uniq.size]

    def _heal_and_rebuild(self, uniq: np.ndarray, on_error: str) -> None:
        """A partitioned decode failed its shard-local digest check.
        Recovery composes HERE, at the residency layer (PR 8 rule): heal
        on the UNPARTITIONED decoder — parity reconstruction patches the
        flat device words and the host archive, or simply proves the
        flat copy was never corrupt (lost-shard case) — then re-seed the
        partition's stacked arrays from the healed copy, in place, so
        the sharded cache and the `partitioned_rows` jit cache (keyed on
        geometry, arrays passed as arguments) stay valid."""
        from repro.core.sharded_decode import partition_archive
        dec = self.decoder
        try:
            dec.decode_blocks(_pad_pow2(uniq.astype(np.int32)), verify=True,
                              on_error=("repair" if on_error == "repair"
                                        else "partial"))
        except BlockDigestError:
            if on_error != "partial":
                raise
        fresh = partition_archive(dec, self.part.mesh, self.axes)
        self.part.arrays = fresh.arrays
        self.shard_rebuilds += 1

    def _resilient(self, run, uniq: np.ndarray, on_error: str,
                   pad: bool = True) -> jnp.ndarray:
        """Run a verified partitioned decode with heal-and-rebuild retry
        (one retry: a second failure means genuinely unrecoverable)."""
        if on_error == "partial" and self._quarantine_hit(uniq):
            return self._degraded_rows(uniq, pad=pad)
        try:
            return run()
        except BlockDigestError:
            if on_error == "raise":
                raise
            self._heal_and_rebuild(uniq, on_error)
            if on_error == "partial" and self._quarantine_hit(uniq):
                return self._degraded_rows(uniq, pad=pad)
            return run()

    # ----------------------------------------------------------------- rows
    def rows_for_blocks(self, uniq: np.ndarray,
                        on_error: Optional[str] = None) -> jnp.ndarray:
        """(U,) unique global block ids → (U, block_size) rows through
        the partitioned archive (and the per-shard cache when enabled).
        Resets the decoder's per-call launch instrumentation like
        `decode_blocks` does."""
        dec = self.decoder
        dec.launch_rounds_last = []
        dec.decoded_blocks_last = 0
        on_error = self.on_error if on_error is None else on_error
        uniq = np.asarray(uniq, np.int64).reshape(-1)
        if self._cache is None:
            run = lambda: self._decode_uncached(uniq)  # noqa: E731
        else:
            run = lambda: self._cache.rows_for(  # noqa: E731
                uniq, self._decode_stacked)
        if not self.verify or on_error == "raise":
            return run()
        return self._resilient(run, uniq, on_error)

    def stream_rows(self, uniq: np.ndarray, verify: bool,
                    on_error: str) -> jnp.ndarray:
        """Cache-bypassing exact-size decode with the recovery wrapper —
        the streaming executor's entry point (it never recovers itself)."""
        uniq = np.asarray(uniq, np.int64).reshape(-1)
        run = lambda: self._decode_uncached(  # noqa: E731
            uniq, pad=False, verify=verify)
        if not verify or on_error == "raise":
            return run()
        return self._resilient(run, uniq, on_error, pad=False)

    def _decode_stacked(self, loc: np.ndarray, n_rounds: int,
                        valid: np.ndarray) -> jnp.ndarray:
        """Collective miss decode the sharded cache drives: one stacked
        (n_shards, S) launch at this depth bucket's rounds. Pad slots
        (`~valid`) may hold garbage under a shallow bucket's rounds —
        verification masks them; the cache install drops them."""
        from repro.core.sharded_decode import (partitioned_rows,
                                               verify_stacked)
        dec = self.decoder
        stacked = partitioned_rows(dec, self.part, loc, n_rounds=n_rounds)
        dec.launch_rounds_last.append(
            dec.da.max_depth if n_rounds == -1 else n_rounds)
        dec.decoded_blocks_last += int(loc.shape[1])
        if self.verify:
            verify_stacked(dec, self.part, stacked, loc, valid=valid)
        return stacked

    def _decode_uncached(self, uniq: np.ndarray, pad: bool = True,
                         verify: Optional[bool] = None) -> jnp.ndarray:
        """Cache-bypassing partitioned decode, depth-bucketed: one
        collective launch per scheduled-rounds group (`pad=False` keeps
        exact per-shard widths — the streaming budget path, which also
        passes its own `verify` instead of this residency's default)."""
        from repro.core.sharded_decode import partitioned_decode_blocks
        dec = self.decoder
        verify = self.verify if verify is None else verify
        groups = dec._ra_groups(uniq)
        if groups is None:
            return partitioned_decode_blocks(dec, self.part, uniq,
                                             verify=verify, pad=pad)
        pieces = [partitioned_decode_blocks(dec, self.part, uniq[idx],
                                            n_rounds=rounds,
                                            verify=verify, pad=pad)
                  for rounds, idx in groups]
        order = np.concatenate([idx for _, idx in groups])
        inv = np.empty(order.size, np.int64)
        inv[order] = np.arange(order.size)
        return jnp.concatenate(pieces, axis=0)[jnp.asarray(inv)]
