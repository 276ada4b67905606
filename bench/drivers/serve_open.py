"""Open-loop serving: requests arrive on a Poisson schedule at a fixed
rate, whatever the engine does, and each is timed by the client from the
moment it was due until its future resolved.

Traffic parameters (bench/traffic/<mix>.json):
  rate_per_s            arrivals per second
  size_min, size_max    request sizes in samples, log-uniform integers
  warm_s                seconds of the same traffic during set-up
  pool                  distinct input rows generated from the seed
  wait_s                how long after the window an answer may still come
  check_requests        requests compared with the reference (null: all)
  route, engine         the planned cascade route; LUTServeEngine keywords

The schedule is the same multiset of gaps and sizes for every seed, in a
seed-drawn order, so seeds change which rows and in what order, not how
much work a window holds.
"""
from __future__ import annotations

import math
import time

import numpy as np

from benchkit.cell import BenchError, Window
from benchkit.model import seed_int
from benchkit.serving import Requests, ServingCell
from benchkit.stats import percentile


def schedule(traffic, seconds: float, seed: int, salt: int):
    """(offsets from the window's start, sizes, pool starts) of every
    request due in ``seconds``."""
    rate = traffic["rate_per_s"]
    n = max(1, int(math.ceil(rate * seconds)))
    fixed = np.random.default_rng(0)
    gaps = fixed.exponential(1.0 / rate, n)
    gaps *= seconds / gaps.sum()
    lo, hi = traffic["size_min"], traffic["size_max"]
    sizes = np.clip(np.floor(np.exp(fixed.uniform(
        np.log(lo), np.log(hi + 1), n))), lo, hi).astype(int)
    rng = np.random.default_rng(seed_int(seed, 7, salt))
    gaps, sizes = rng.permutation(gaps), rng.permutation(sizes)
    offsets = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    starts = rng.integers(0, traffic["pool"] - sizes + 1)
    return offsets, sizes, starts


class Driver:
    def __init__(self, cell, seed: int, *, strict: bool, fault: str = ""):
        self.serving = ServingCell(cell, seed, strict=strict, fault=fault)
        self.traffic = cell.traffic
        self.seed, self.strict = seed, strict
        self.reqs = None

    def setup(self) -> None:
        self.serving.build()
        warm = self._send(self.traffic["warm_s"], salt=1, traced=False)
        warm.wait(self.traffic["wait_s"])

    def _send(self, seconds: float, *, salt: int, traced: bool) -> Requests:
        import jax
        eng, pool = self.serving.engine, self.serving.pool
        offsets, sizes, starts = schedule(self.traffic, seconds, self.seed,
                                          salt)
        reqs = Requests()
        t0 = time.perf_counter() + 0.005
        for off, n, st in zip(offsets, sizes, starts):
            due = t0 + off
            d = due - time.perf_counter()
            if d > 0:
                time.sleep(d)
            sent = time.perf_counter()
            x = pool[st:st + n]
            if traced:
                with jax.profiler.TraceAnnotation("bench.submit"):
                    fut = eng.submit(x)
            else:
                fut = eng.submit(x)
            reqs.add(int(st), int(n), due, sent, fut)
        self.t0, self.t1 = t0, t0 + seconds
        return reqs

    def window(self, seconds: float, *, traced: bool) -> Window:
        import jax
        before = self.serving.occupancy_counters()
        if traced:
            with jax.profiler.TraceAnnotation("bench.window"):
                reqs = self._send(seconds, salt=2, traced=True)
                rest = self.t1 - time.perf_counter()
                if rest > 0:
                    time.sleep(rest)
        else:
            reqs = self._send(seconds, salt=2, traced=False)
        after = self.serving.occupancy_counters()
        reqs.wait(self.traffic["wait_s"])
        given_up = time.perf_counter()
        if self.strict and self.serving.downgrades():
            raise BenchError("the serving kernel downgraded to jnp")
        self.reqs = reqs
        answers = reqs.answers()
        lat = sorted(((d if d is not None and a is not None else given_up)
                      - due) for d, due, a in zip(reqs.done, reqs.due,
                                                  answers))
        failed = sum(a is None for a in answers)
        served = sum(n for (_, n), d, a in zip(reqs.rows, reqs.done, answers)
                     if a is not None and d is not None and d <= self.t1)
        return Window(
            seconds=seconds,
            metrics={"serve_p50_ms": percentile(lat, 50) * 1e3,
                     "serve_p99_ms": percentile(lat, 99) * 1e3,
                     "served_samples_per_s": served / seconds},
            attempted=len(reqs.rows), failed=failed,
            counters={"late_ms": sorted((s - d) * 1e3 for s, d in
                                        zip(reqs.sent, reqs.due)),
                      "occupancy": {k: after[k] - before[k]
                                    for k in before},
                      "served_samples": served})

    def release(self) -> None:
        self.serving.release()

    def check(self):
        return self.serving.check(self.reqs)
