"""Open-loop point reads by read id through the program's
`ServingFrontend`: arrivals and keys from the traffic file (`loadgen`),
every request timed from when it was due, every answer compared with the
host reference's record."""
from __future__ import annotations

from typing import Dict

import numpy as np

from chipbench import harness, loadgen, warmup

DRAIN_S = 60.0             # how long answers due in the window are awaited


class Driver(harness.Driver):
    loop = "open"
    tenant = "points"

    def __init__(self, *args):
        super().__init__(*args)
        from repro.serving.frontend import ServingFrontend
        self.fe = ServingFrontend({"corpus": self.ga},
                                  max_batch=int(self.config["max_batch"]))
        self.fe.register_tenant(self.tenant, "corpus",
                                max_queue=int(self.config["max_queue"]),
                                priority=0)

    def _serve(self, ids) -> None:
        for r in ids:
            self.fe.submit(self.tenant, int(r))
        self.fe.drain()
        self.fe.take_results()

    def warm(self) -> None:
        reqs = warmup.point_requests(self.ref.starts, self.ga.block_size,
                                     int(self.config["max_batch"]))
        for clear, ids in reqs:
            if clear:
                self.ga.clear_cache()
            self._serve(ids)
        self.ga.clear_cache()
        self.log(f"warm-up: {len(reqs)} shape batches; "
                 + self._steady_state())

    def _steady_state(self) -> str:
        """Bring the block cache to the state the cell's own traffic keeps
        it in: serve `warm_requests` keys of the mix's distribution, drawn
        from the seed apart from the window's, unmeasured and at full
        speed, and report the hit rate of the second half."""
        n = int(self.mix.get("warm_requests", 0))
        keys = loadgen.draw_keys(self.mix, self.ref.n_reads, self.warm_rng,
                                 n)
        step = int(self.config["max_batch"])
        half = None
        for i in range(0, n, step):
            if half is None and i >= n // 2:
                half = dict(self.ga.cache_info())
            self._serve(keys[i:i + step])
        if half is None:
            return "no steady-state requests"
        end = self.ga.cache_info()
        hits = end["hits"] - half["hits"]
        looked = hits + end["misses"] - half["misses"]
        return (f"{n} steady-state requests, hit rate of the second half "
                f"{100.0 * hits / max(looked, 1):.2f}%, "
                f"{end['resident']} blocks resident")

    def window(self, seconds: float, mark=None):
        due = loadgen.arrivals(self.mix, seconds, self.rng)
        keys = loadgen.draw_keys(self.mix, self.ref.n_reads, self.rng,
                                 due.size)
        return loadgen.run_open_loop(self.fe, self.tenant, due, keys,
                                     seconds, drain_s=DRAIN_S, mark=mark)

    def check(self, rec) -> dict:
        wrong = missing = 0
        for key, status, payload in zip(rec.keys.tolist(), rec.status,
                                        rec.payloads):
            if status == "missing":
                missing += 1
            elif status == "ok" and (payload is None or payload.tobytes()
                                     != self.ref.record(key)):
                wrong += 1
        return {"wrong_answers": (wrong, 0), "missing_answers": (missing, 0)}

    def failed(self, rec, checks: dict) -> int:
        return (sum(s != "ok" for s in rec.status)
                + checks["wrong_answers"][0])

    def latencies_ms(self, rec) -> np.ndarray:
        """Every request of the window, from when it was due to when its
        bytes were on the host; one never answered, or answered with
        anything but its bytes, counts as waiting until the drain ended."""
        done = np.where(np.isnan(rec.done), rec.window_s + DRAIN_S, rec.done)
        lat = (done - rec.due) * 1e3
        bad = np.asarray([s != "ok" for s in rec.status])
        lat[bad] = np.maximum(lat[bad], (rec.window_s + DRAIN_S) * 1e3)
        return lat

    def end_to_end(self, rec) -> Dict[str, float]:
        return {"point_read_p50_ms":
                float(np.percentile(self.latencies_ms(rec), 50))}

    def describe(self, rec) -> str:
        late = rec.lateness
        return (f"window: {rec.due.size} requests in {rec.window_s:.1f}s "
                f"({rec.due.size / rec.window_s:.2f}/s offered), "
                f"{rec.steps} frontend steps, generator lateness p50 "
                f"{np.percentile(late, 50) * 1e3:.3f} ms, p95 "
                f"{np.percentile(late, 95) * 1e3:.3f} ms, max "
                f"{late.max() * 1e3:.3f} ms")

    def requests(self, rec) -> int:
        return int(rec.due.size)

    def drop_half(self) -> None:
        take = self.fe.take_results

        def half():
            return {k: v for k, v in take().items() if k % 2 == 0}

        self.fe.take_results = half
