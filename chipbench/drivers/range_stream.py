"""One closed-loop bulk reader streaming byte ranges through the
program's `StreamingExecutor` under the configuration's device budget;
a sample of the chunks, drawn from the seed, is compared with the host
reference's bytes."""
from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from typing import Dict

import numpy as np

from chipbench import harness, loadgen, warmup

KEEP_STREAM_BYTES = 2 << 30  # stream chunks held for the check, at most


class Driver(harness.Driver):

    def __init__(self, *args):
        super().__init__(*args)
        from repro.api.executors import StreamingExecutor
        ga = self.ga
        self.ex = StreamingExecutor(
            ga.store,
            max_resident_bytes=int(self.config["max_resident_bytes"]),
            planner=ga.planner)
        self.k = self.ex.max_blocks_per_chunk
        n_chunks = -(-ga.store.decoder.da.n_blocks // self.k)
        self.start = int(self.rng.integers(n_chunks)) * self.k \
            * ga.block_size

    def warm(self) -> None:
        from repro.api import ByteRange
        dec = self.ga.store.decoder
        chunks = warmup.stream_chunks(dec.block_rounds, dec.da.n_blocks,
                                      self.ga.block_size, self.ga.raw_size,
                                      self.k)

        def one(span) -> None:
            for _ in self.ex.chunks([ByteRange(*span)]):
                pass

        # every chunk shape has programs of its own, and the decoder's
        # sizes follow the archive, so a fresh seed compiles them all:
        # compile them side by side (the compiler releases the GIL)
        with ThreadPoolExecutor(max_workers=len(chunks)) as pool:
            list(pool.map(one, chunks))
        self.log(f"warm-up: {len(chunks)} distinct chunk shapes of "
                 f"{self.k} blocks, stream starts at byte {self.start}")

    def window(self, seconds: float, mark=None):
        return loadgen.run_range_stream(self.ex, self.ga.raw_size,
                                        self.start, seconds, self.rng,
                                        KEEP_STREAM_BYTES, mark=mark)

    def check(self, rec) -> dict:
        wrong = sum(not np.array_equal(chunk,
                                       self.ref.span(pos, pos + chunk.size))
                    for pos, chunk in rec.kept)
        return {"wrong_chunks": (wrong, 0),
                "short_passes": (rec.short_passes, 0)}

    def failed(self, rec, checks: dict) -> int:
        return checks["wrong_chunks"][0] + rec.short_passes

    def end_to_end(self, rec) -> Dict[str, float]:
        return {"range_decode_GBps": rec.bytes / rec.seconds / 1e9}

    def describe(self, rec) -> str:
        return (f"window: {rec.chunks} chunks, {rec.bytes} bytes in "
                f"{rec.seconds:.3f}s, {rec.passes_ended} passes ended, "
                f"{len(rec.kept)} chunks kept for the check")

    def requests(self, rec) -> int:
        return int(rec.chunks)

    def drop_half(self) -> None:
        chunks = self.ex.chunks

        def every_other(addrs):
            for i, c in enumerate(chunks(addrs)):
                if i % 2 == 0:
                    yield c

        self.ex.chunks = every_other
