"""Conversion: whole-model ``truth_table.convert_packed`` from seeded
parameters and batch-norm state, repeated back to back; each conversion
ends with every table on the host, unpacked and bit-packed, as users get
them.

Traffic parameters (bench/traffic/<mix>.json):
  route         the planned conversion route of the hidden function
  input_scale   scale of the seeded input quantizer

The window runs whole conversions until its seconds have passed; the rate
is the entries of every conversion over the time they all took.
"""
from __future__ import annotations

import time

import numpy as np

from benchkit.cell import BenchError, Check, Window, reference
from benchkit.model import (Geometry, connectivity, convert_params,
                            fn_params, program_config)
from benchkit.compile_stats import kernel_calls


class Driver:
    def __init__(self, cell, seed: int, *, strict: bool, fault: str = ""):
        self.cell, self.seed, self.strict = cell, seed, strict
        self.fault = fault
        self.traffic = cell.traffic
        self.geom = Geometry.from_conf(cell.conf)

    def setup(self) -> None:
        import jax
        import jax.numpy as jnp
        from repro.core import truth_table as TT
        from repro.core.exec_plan import kernel_compiled, plan_subnet_exec

        cfg = self.cfg = program_config(self.cell.conf)
        plan = plan_subnet_exec(cfg, purpose="convert")
        if self.strict:
            if plan.route != self.traffic["route"] or not kernel_compiled():
                raise BenchError(f"conversion route {plan.route} (planned "
                                 f"{self.traffic['route']})")
            i = 1 if len(cfg.layer_widths) > 1 else 0
            n = kernel_calls(jax.jit(lambda p, x: plan.apply(p, x)).lower(
                self.params_shape(i), jnp.zeros(
                    (4096, cfg.layer_widths[i], cfg.layer_fan_in(i)))))
            if n != 1:
                raise BenchError(f"conversion sweep lowers {n} compiled "
                                 f"kernels, expected 1")
        self.conns = connectivity(self.geom, self.cell.conf["model_seed"])
        self.statics = [{"conn": c} for c in self.conns]
        self.params, self.state = convert_params(
            self.geom, self.seed, self.traffic["input_scale"])
        self.convert = TT.convert_packed
        if self.fault == "alter_answer":
            def altered(*a, **kw):
                tables, packed = TT.convert_packed(*a, **kw)
                tables[0] = tables[0].copy()
                tables[0][0, 0] ^= 1
                return tables, packed
            self.convert = altered
        self.last = self._convert(False)

    def params_shape(self, i: int):
        import jax
        return jax.eval_shape(lambda k: fn_params(self.geom, i, k, 0.1),
                              jax.random.key(0))

    def _convert(self, traced: bool):
        import jax
        if traced:
            with jax.profiler.TraceAnnotation("bench.convert"):
                return self.convert(self.cfg, self.params, self.state,
                                    self.statics)
        return self.convert(self.cfg, self.params, self.state, self.statics)

    def window(self, seconds: float, *, traced: bool) -> Window:
        import jax
        done = 0
        t = time.perf_counter()

        def run():
            nonlocal done
            while True:
                self.last = self._convert(traced)
                done += 1
                if time.perf_counter() - t >= seconds:
                    return

        if traced:
            with jax.profiler.TraceAnnotation("bench.window"):
                run()
        else:
            run()
        elapsed = time.perf_counter() - t
        entries = done * self.geom.table_entries
        return Window(seconds=elapsed,
                      metrics={"convert_entries_per_s": entries / elapsed},
                      attempted=done, failed=0,
                      counters={"conversions": done, "entries": entries})

    def release(self) -> None:
        self.convert = None

    # -- the check --------------------------------------------------------

    def reference_tables(self, precision: str = "highest"):
        import jax
        import jax.numpy as jnp
        ref = reference(self.cell.conf)
        g = self.geom
        out = []
        scales = [jnp.exp(self.params["in_quant"]["log_s"])] + [
            jnp.exp(lp["quant"]["log_s"]) for lp in self.params["layers"]]
        for i in range(len(g.widths)):
            lp = self.params["layers"][i]
            fn = jax.jit(lambda fn, bn, bs, q, sc, i=i: ref.layer_table(
                fn, bn, bs, q, sc, in_bits=g.in_bits[i], beta=g.beta,
                skip=g.skip, precision=precision))
            out.append(np.asarray(fn(
                lp["fn"], lp["bn"], self.state["layers"][i]["bn"],
                lp["quant"]["log_s"],
                scales[i][jnp.asarray(self.conns[i])])))
        return out

    def compare(self, got, ref_tables) -> dict:
        """Entries that differ from the reference (``table_flips``), and
        packed words that differ from the unpacked tables packed by the
        reference (``packed_mismatch``)."""
        ref = reference(self.cell.conf)
        tables, packed = got
        flips = sum(int(np.sum(np.asarray(a, np.int64) != b))
                    for a, b in zip(tables, ref_tables))
        words = sum(int(np.sum(ref.pack_words(np.asarray(t), self.geom.beta)
                               != np.asarray(p)))
                    for t, p in zip(tables, packed))
        return {"table_flips": float(flips), "packed_mismatch": float(words)}

    def check(self):
        """Every number the cell's limits file names, against its limit."""
        values = self.compare(self.last, self.reference_tables())
        return [Check(n, values[n], lim)
                for n, lim in self.cell.limits.items()]
