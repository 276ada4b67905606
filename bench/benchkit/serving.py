"""What the two serving drivers share: the engine built on the cell's
model, the input pool, the record of every request, and the check of
every served prediction against the reference."""
from __future__ import annotations

import threading
import time
from typing import Dict, List, Optional

import numpy as np

from benchkit import data
from benchkit.cell import BenchError, Check, reference
from benchkit.compile_stats import kernel_calls
from benchkit.model import (Geometry, program_config, reference_model,
                            seed_int, serving_model)

class Requests:
    """Every request of a window: its rows, when it was due, sent and
    answered, and what came back.  Completion is recorded by a callback
    on the future, in whichever thread resolves it."""

    def __init__(self):
        self.rows: List[tuple] = []      # (start, n) into the pool
        self.due: List[float] = []
        self.sent: List[float] = []
        self.done: List[Optional[float]] = []
        self.futures: List = []
        self._lock = threading.Lock()

    def add(self, start: int, n: int, due: float, sent: float, fut) -> None:
        with self._lock:
            i = len(self.rows)
            self.rows.append((start, n))
            self.due.append(due)
            self.sent.append(sent)
            self.done.append(None)
            self.futures.append(fut)

        def finished(_f, i=i):
            self.done[i] = time.perf_counter()
        fut.add_done_callback(finished)

    def wait(self, timeout_s: float) -> None:
        end = time.perf_counter() + timeout_s
        for f in list(self.futures):
            try:
                f.result(timeout=max(0.0, end - time.perf_counter()))
            except Exception:
                pass

    def answers(self) -> List[Optional[np.ndarray]]:
        out = []
        for f in self.futures:
            if f.done() and not f.cancelled() and f.exception() is None:
                out.append(np.asarray(f.result()))
            else:
                out.append(None)
        return out


class ServingCell:
    """Engine, pool and checks of one serving cell.  ``fault`` plants a
    known fault under the timed path: ``alter_answer`` changes one
    prediction of every dispatched batch where the engine produces it."""

    def __init__(self, cell, seed: int, *, strict: bool, fault: str):
        self.cell, self.seed, self.strict = cell, seed, strict
        self.traffic = cell.traffic
        self.geom = Geometry.from_conf(cell.conf)
        self.fault = fault
        self.engine = None

    def build(self) -> None:
        import jax.numpy as jnp
        from repro.serve import LUTServeEngine
        from repro.serve.registry import ServeBundle

        cfg = program_config(self.cell.conf)
        self.served = serving_model(self.geom, self.cell.conf)
        bundle = ServeBundle(
            cfg=cfg, tables=self.served["tables"],
            statics=[{"conn": c} for c in self.served["conns"]],
            in_log_s=self.served["in_log_s"],
            layer_log_s=self.served["layer_log_s"])
        eng = LUTServeEngine(bundle, **self.traffic.get("engine", {}))
        route = eng.plan.route
        if self.strict:
            if route != self.traffic["route"]:
                raise BenchError(f"serving route {route}, planned "
                                 f"{self.traffic['route']}")
            primary = eng._executors[0]._forward.primary
            n = kernel_calls(primary.lower(
                jnp.zeros((8, cfg.in_features), jnp.float32)))
            if n != 1:
                raise BenchError(f"serving forward lowers {n} compiled "
                                 f"kernels, expected 1")
        if self.fault == "alter_answer":
            ex = eng._executors[0]
            fwd, classes = ex._forward, cfg.num_classes

            def altered(x, fwd=fwd):
                # on the host, so that the fault compiles nothing
                out = np.array(fwd(x))
                out[0] = (out[0] + 1) % classes
                return out
            altered.primary = getattr(fwd, "primary", fwd)
            ex._forward = altered
        eng.start()
        eng.warmup()
        self.engine = eng
        gen = getattr(data, self.cell.conf["inputs"])
        self.pool, _ = gen(self.traffic["pool"], seed=seed_int(self.seed, 5))

    def occupancy_counters(self) -> Dict[str, float]:
        m = self.engine.metrics
        with m._lock:
            return {"real": m._real, "padded": m._padded,
                    "batches": m._batches}

    def downgrades(self) -> int:
        return int(self.engine.metrics.report()["kernel_downgrades"])

    def release(self) -> None:
        if self.engine is not None:
            self.engine.close()
            self.engine = None

    # -- the check --------------------------------------------------------

    def gaps(self, reqs: Requests, answers, picks: List[int], *,
             lowp_inputs: bool = False) -> float:
        """Widest gap, over the picked requests' samples, between the
        reference's best class value and that of the served class (or,
        with ``lowp_inputs``, of the control's class)."""
        ref = reference(self.cell.conf)
        model = reference_model(self.geom, self.served)
        xs = np.concatenate([self._rows(reqs.rows[i]) for i in picks])
        ss = np.concatenate([answers[i] for i in picks]).astype(np.int32)
        return float(np.max(ref.served_gaps(xs, ss, model,
                                            lowp_inputs=lowp_inputs)))

    def _rows(self, rows) -> np.ndarray:
        start, n = rows
        return self.pool[start:start + n]

    def picks(self, reqs: Requests, answers) -> List[int]:
        """The requests whose answers are compared: all of them, or a
        sample of ``check_requests`` drawn from the seed that always holds
        the longest."""
        idx = [i for i, a in enumerate(answers) if a is not None]
        k = self.traffic.get("check_requests")
        if not idx or k is None or len(idx) <= k:
            return idx
        longest = max(idx, key=lambda i: reqs.rows[i][1])
        rng = np.random.default_rng(seed_int(self.seed, 6))
        rest = [i for i in idx if i != longest]
        return [longest] + sorted(rng.choice(rest, k - 1, replace=False)
                                  .tolist())

    def check(self, reqs: Requests) -> List[Check]:
        valid = [a if a is not None and a.shape == (n,) else None
                 for a, (_, n) in zip(reqs.answers(), reqs.rows)]
        unanswered = sum(a is None for a in valid)
        picks = self.picks(reqs, valid)
        gap = self.gaps(reqs, valid, picks) if picks else 0.0
        lim = self.cell.limits
        return [Check("gap", gap, lim["gap"]),
                Check("unanswered", float(unanswered), lim["unanswered"])]
