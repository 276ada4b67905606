"""Closed-loop serving: a fixed number of clients, each sending its next
request as soon as the previous one is answered.

Traffic parameters (bench/traffic/<mix>.json):
  clients               concurrent clients
  request_size          samples per request
  warm_s                seconds of the same traffic during set-up
  pool                  distinct input rows generated from the seed
  wait_s                how long after the window an answer may still come
  check_requests        requests compared with the reference, drawn from
                        the seed (null: all)
  route, engine         the planned cascade route; LUTServeEngine keywords

A client's requests are contiguous slices of the pool at seed-drawn
offsets; every request has the same size, so seeds change the rows, not
the work.
"""
from __future__ import annotations

import threading
import time

import numpy as np

from benchkit.cell import BenchError, Window
from benchkit.model import seed_int
from benchkit.serving import Requests, ServingCell


class Driver:
    def __init__(self, cell, seed: int, *, strict: bool, fault: str = ""):
        self.serving = ServingCell(cell, seed, strict=strict, fault=fault)
        self.traffic = cell.traffic
        self.seed, self.strict = seed, strict
        self.reqs = None

    def setup(self) -> None:
        self.serving.build()
        self._run(self.traffic["warm_s"], salt=1, traced=False)

    def _run(self, seconds: float, *, salt: int, traced: bool) -> Requests:
        import jax
        eng, pool = self.serving.engine, self.serving.pool
        n = self.traffic["request_size"]
        reqs = Requests()
        t0 = time.perf_counter()
        t1 = t0 + seconds
        errors = []

        def client(c: int) -> None:
            rng = np.random.default_rng(seed_int(self.seed, 8, salt, c))
            try:
                while time.perf_counter() < t1:
                    st = int(rng.integers(0, len(pool) - n + 1))
                    sent = time.perf_counter()
                    fut = eng.submit(pool[st:st + n])
                    reqs.add(st, n, sent, sent, fut)
                    if traced:
                        with jax.profiler.TraceAnnotation("bench.wait"):
                            fut.exception()
                    else:
                        fut.exception()
            except Exception as e:  # recorded and re-raised below
                errors.append(e)

        threads = [threading.Thread(target=client, args=(c,),
                                    name=f"bench-client-{c}")
                   for c in range(self.traffic["clients"])]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        if errors:
            raise errors[0]
        self.t0, self.t1 = t0, t1
        return reqs

    def window(self, seconds: float, *, traced: bool) -> Window:
        import jax
        before = self.serving.occupancy_counters()
        if traced:
            with jax.profiler.TraceAnnotation("bench.window"):
                reqs = self._run(seconds, salt=2, traced=True)
        else:
            reqs = self._run(seconds, salt=2, traced=False)
        after = self.serving.occupancy_counters()
        reqs.wait(self.traffic["wait_s"])
        if self.strict and self.serving.downgrades():
            raise BenchError("the serving kernel downgraded to jnp")
        self.reqs = reqs
        answers = reqs.answers()
        served = sum(n for (_, n), d, a in zip(reqs.rows, reqs.done, answers)
                     if a is not None and d is not None and d <= self.t1)
        return Window(
            seconds=seconds,
            metrics={"served_samples_per_s": served / seconds},
            attempted=len(reqs.rows),
            failed=sum(a is None for a in answers),
            counters={"occupancy": {k: after[k] - before[k]
                                    for k in before},
                      "served_samples": served})

    def release(self) -> None:
        self.serving.release()

    def check(self):
        return self.serving.check(self.reqs)
