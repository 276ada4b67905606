"""Training: the trainer's own scanned epoch program and its per-epoch
eval (``core.train``), run back to back over a device-resident data set
with the host synced only at the window's end.

Traffic parameters (bench/traffic/<mix>.json):
  train_rows, test_rows   data set sizes (rows drawn from the seed)
  batch                   rows per step; steps per epoch = train_rows // batch
  optimizer               lr, weight_decay, t0 (SGDR first cycle, steps),
                          lr_min_ratio, beta1, beta2, eps, grad_clip: the
                          trainer's settings, stated for the reference
  route                   the planned training route of the hidden function

Set-up builds the program's training step and the epoch program that
scans it, once.  From the seed's start it drives the step alone through
the first ``CHECK_STEPS`` minibatches of the first epoch, then the epoch
program through that whole first epoch (both results are checked against
the reference after the window), then a second epoch that times one; the
window then runs as many whole epochs, each followed by the eval, as fill
its seconds.
"""
from __future__ import annotations

import math
import time

import numpy as np

from benchkit import data
from benchkit.cell import BenchError, Check, Window, reference
from benchkit.model import (Geometry, chain_config, connectivity,
                            seed_key, seed_int, train_params)
from benchkit.compile_stats import kernel_calls

# Steps that the program's step takes alone, and the reference follows.
CHECK_STEPS = 3
# The control of ``correct``: the reference, with bfloat16 operands in its
# hidden functions, in the program's place.
CONTROL = "control_bfloat16"


def _copy(tree):
    import jax
    import jax.numpy as jnp
    return jax.tree.map(jnp.copy, tree)


def leaf_gaps(prog, ref, include=None):
    """Per leaf, the gap between the norms of two matching trees,
    |‖p‖ - ‖r‖| over the larger of ‖r‖ and the median leaf's ‖r‖;
    ``include`` (a matching tree of bools) leaves leaves out."""
    import jax
    pn = [float(np.linalg.norm(np.asarray(a))) for a in jax.tree.leaves(prog)]
    rn = [float(np.linalg.norm(np.asarray(a))) for a in jax.tree.leaves(ref)]
    keep = ([True] * len(rn) if include is None
            else jax.tree.leaves(include))
    med = float(np.median([r for r, k in zip(rn, keep) if k]))
    return [abs(p - r) / max(r, med)
            for p, r, k in zip(pn, rn, keep) if k]


class Driver:
    def __init__(self, cell, seed: int, *, strict: bool, fault: str = ""):
        self.cell, self.seed, self.strict = cell, seed, strict
        self.fault = fault
        self.traffic = cell.traffic
        self.geom = Geometry.from_conf(cell.conf)
        if not self.geom.is_chain:
            raise BenchError(f"{cell.conf['name']} is a LUT graph "
                             f"configuration: it has no training "
                             f"driver yet")

    def _build(self):
        import jax
        import jax.numpy as jnp
        from repro.core import train as TR
        from repro.core.exec_plan import kernel_compiled, plan_subnet_exec
        from repro.optim import adamw_init

        t = self.traffic
        cfg = chain_config(self.cell.conf)
        plan = plan_subnet_exec(cfg, purpose="train")
        if self.strict and (plan.route != t["route"]
                            or not kernel_compiled()):
            raise BenchError(f"training route {plan.route} (planned "
                             f"{t['route']}), compiled={kernel_compiled()}")
        self.conns = connectivity(self.geom, self.cell.conf["model_seed"])
        statics = [{"conn": c} for c in self.conns]
        gen = getattr(data, self.cell.conf["inputs"])
        x, y = gen(t["train_rows"], seed=seed_int(self.seed, 9))
        xe, ye = gen(t["test_rows"], seed=seed_int(self.seed, 10))
        self.xd, self.yd = jnp.asarray(x), jnp.asarray(y)
        self.xe, self.ye = jnp.asarray(xe), jnp.asarray(ye)
        self.batch = t["batch"]
        self.steps = t["train_rows"] // self.batch
        hp = t["optimizer"]
        step = TR._make_step_fn(cfg, statics, lr=hp["lr"],
                                weight_decay=hp["weight_decay"],
                                t0=hp["t0"], exec_plan=plan)
        if self.fault == "half_batch":
            def planted(p, s, o, xb, yb, step=step):
                h = xb.shape[0] // 2
                return step(p, s, o, xb[:h], yb[:h])
        elif self.fault == "unchanged":
            def planted(p, s, o, xb, yb, step=step):
                return p, s, o, step(p, s, o, xb, yb)[3]
        else:
            planted = step
        self.step_fn = jax.jit(planted)
        self.epoch_fn = TR._make_epoch_fn(planted, t["train_rows"],
                                          self.steps, self.batch)
        self.eval_fn = TR._make_eval_fn(cfg, statics)
        params, state = train_params(self.geom, self.seed, x.std(axis=0))
        opt = adamw_init(params)
        if self.strict:
            n = kernel_calls(jax.jit(self.epoch_fn).lower(
                params, state, opt, seed_key(self.seed, 11), self.xd,
                self.yd))
            if n < 2 * len(self.geom.widths):
                raise BenchError(f"epoch program lowers {n} compiled "
                                 f"kernels")
        self.key = seed_key(self.seed, 11)
        self.start = _copy((params, state))
        return params, state, opt

    def _epoch(self, carry, ep: int, traced: bool):
        import jax
        if traced:
            with jax.profiler.TraceAnnotation("bench.epoch"):
                p, s, o, loss = self.epoch_fn(
                    *carry, jax.random.fold_in(self.key, ep), self.xd,
                    self.yd)
            with jax.profiler.TraceAnnotation("bench.eval"):
                acc = self.eval_fn(p, s, self.xe, self.ye)
        else:
            p, s, o, loss = self.epoch_fn(
                *carry, jax.random.fold_in(self.key, ep), self.xd, self.yd)
            acc = self.eval_fn(p, s, self.xe, self.ye)
        return (p, s, o), loss, acc

    def _first_steps(self, params, state, opt):
        """The program's step alone from the start, on the first epoch's
        first minibatches: (losses, Adam's first moment after one step,
        (params, state) after the last)."""
        losses, m1 = [], None
        for ib in self._check_batches():
            params, state, opt, loss = self.step_fn(
                params, state, opt, self.xd[ib], self.yd[ib])
            losses.append(loss)
            m1 = _copy(opt["m"]) if m1 is None else m1
        return np.asarray(losses), m1, (params, state)

    def _check_batches(self):
        """Row indices of the first epoch's first minibatches, drawn as
        the epoch program draws them."""
        import jax
        n = self.traffic["train_rows"]
        perm = jax.random.permutation(jax.random.fold_in(self.key, 0), n)
        return perm[:CHECK_STEPS * self.batch].reshape(CHECK_STEPS,
                                                       self.batch)

    def setup(self) -> None:
        import jax
        carry = self._build()
        self.first_steps = self._first_steps(*_copy(carry))
        carry, loss, acc = self._epoch(carry, 0, False)
        # the first epoch's result, copied before the next call donates it
        self.first = _copy((carry[0], carry[1], carry[2]["m"], loss))
        if self.fault == CONTROL:
            self.first_steps = self.reference_steps("bfloat16")
            p, s, o, loss = self.reference_epoch("bfloat16")
            self.first = (p, s, o["m"], loss)
        t = time.perf_counter()
        carry, loss, acc = self._epoch(carry, 1, False)
        jax.block_until_ready((carry, loss, acc))
        self.epoch_s = time.perf_counter() - t
        self.carry, self.ep = carry, 2

    def window(self, seconds: float, *, traced: bool) -> Window:
        import jax
        k = max(1, math.ceil(seconds / self.epoch_s))
        carry, losses = self.carry, []

        def run():
            nonlocal carry
            for i in range(k):
                carry, loss, acc = self._epoch(carry, self.ep + i, traced)
                losses.append(loss)
            jax.block_until_ready((carry, losses, acc))

        t = time.perf_counter()
        if traced:
            with jax.profiler.TraceAnnotation("bench.window"):
                run()
        else:
            run()
        elapsed = time.perf_counter() - t
        self.carry, self.ep = carry, self.ep + k
        bad = int(np.sum(~np.isfinite(np.asarray(jax.device_get(losses)))))
        samples = k * self.steps * self.batch
        return Window(seconds=elapsed,
                      metrics={"train_samples_per_s": samples / elapsed},
                      attempted=k * self.steps, failed=bad * self.steps,
                      counters={"samples": samples, "epochs": k,
                                "steps": k * self.steps})

    def release(self) -> None:
        self.carry = None
        self.epoch_fn = self.eval_fn = None

    # -- the check --------------------------------------------------------

    def _ref_kw(self, precision: str, fault: str) -> dict:
        import jax.numpy as jnp
        g = self.geom
        return dict(conns=[jnp.asarray(c) for c in self.conns],
                    in_bits=g.in_bits[0], beta=g.beta, skip=g.skip,
                    momentum=g.momentum, hp=dict(self.traffic["optimizer"]),
                    precision=precision, fault=fault)

    def _ref_start(self):
        import jax
        import jax.numpy as jnp
        p0, s0 = self.start
        opt = {"m": jax.tree.map(jnp.zeros_like, p0),
               "v": jax.tree.map(jnp.zeros_like, p0),
               "count": jnp.zeros((), jnp.int32)}
        return p0, s0, opt

    def reference_epoch(self, precision: str = "highest", fault: str = ""):
        """The reference's first epoch from the same start."""
        import jax
        ref = reference(self.cell.conf)
        kw = self._ref_kw(precision, fault)
        epoch = jax.jit(lambda p, s, o, key, xd, yd: ref.train_epoch(
            p, s, o, key, xd, yd, steps=self.steps, batch=self.batch, **kw))
        return epoch(*self._ref_start(), jax.random.fold_in(self.key, 0),
                     self.xd, self.yd)

    def reference_steps(self, precision: str = "highest", fault: str = ""):
        """The reference's first steps from the same start, on the same
        minibatches, recorded as :meth:`_first_steps` records the
        program's."""
        import jax
        ref = reference(self.cell.conf)
        kw = self._ref_kw(precision, fault)
        step = jax.jit(lambda p, s, o, xb, yb: ref.train_step(
            p, s, o, xb, yb, **kw))
        p, s, o = self._ref_start()
        losses, m1 = [], None
        for ib in self._check_batches():
            p, s, o, loss = step(p, s, o, self.xd[ib], self.yd[ib])
            losses.append(loss)
            m1 = o["m"] if m1 is None else m1
        return np.asarray(losses), m1, (p, s)

    def _keep(self, ref_m):
        """Leaves whose reference moment is at least a thousandth of the
        median leaf's; the others (a gradient that is zero to rounding,
        as a bias before a batch norm has) move by round-off alone."""
        import jax
        rn = [float(np.linalg.norm(np.asarray(a)))
              for a in jax.tree.leaves(ref_m)]
        med = float(np.median(rn))
        return jax.tree.unflatten(jax.tree.structure(ref_m),
                                  [r >= 1e-3 * med for r in rn])

    def _update_gaps(self, got_ps, ref_ps, keep) -> list:
        """Per leaf, the gap of the change of parameters (leaves in
        ``keep``) and batch-norm statistics from the start."""
        import jax
        p0, s0 = self.start

        def delta(a, b):
            return jax.tree.map(lambda x, y: x - y, a, b)
        (p1, s1), (rp, rs) = got_ps, ref_ps
        return (leaf_gaps(delta(p1, p0), delta(rp, p0), keep)
                + leaf_gaps(delta(s1, s0), delta(rs, s0)))

    def compare_steps(self, got, ref) -> dict:
        """The first steps.  ``first_loss_gap``: the first step's loss,
        relative, and ``steps_loss_gap`` the worst step's.
        ``first_grad_gap``: the worst leaf's gap of the norm of Adam's
        first moment after one step (the first gradient as the optimizer
        got it), and ``.median`` the median leaf's.
        ``steps_update_gap``: the worst leaf's gap of the norm of the
        change after the last step, and ``.median`` the median leaf's."""
        losses, m1, ps = got
        rlosses, rm1, rps = ref
        keep = self._keep(rm1)
        loss = np.abs(losses - rlosses) / np.abs(rlosses)
        grads = leaf_gaps(m1, rm1, keep)
        upd = self._update_gaps(ps, rps, keep)
        return {"first_loss_gap": float(loss[0]),
                "steps_loss_gap": float(np.max(loss)),
                "first_grad_gap": max(grads),
                "first_grad_gap.median": float(np.median(grads)),
                "steps_update_gap": max(upd),
                "steps_update_gap.median": float(np.median(upd))}

    def compare(self, got, ref) -> dict:
        """The first epoch, through the window's own program.
        ``loss_gap``: the epoch's mean loss, relative.  ``update_gap``:
        the worst leaf's gap of the norm of the change of the parameters
        and the batch-norm statistics over the epoch."""
        p1, s1, m1, loss = got
        rp, rs, ropt, rloss = ref
        return {"loss_gap": abs(float(loss) - float(rloss))
                / abs(float(rloss)),
                "update_gap": max(self._update_gaps(
                    (p1, s1), (rp, rs), self._keep(ropt["m"])))}

    def readings(self) -> dict:
        return {**self.compare_steps(self.first_steps,
                                     self.reference_steps()),
                **self.compare(self.first, self.reference_epoch())}

    def check(self):
        """Every number the cell's limits file names, against its limit."""
        values = self.readings()
        return [Check(n, values[n], lim)
                for n, lim in self.cell.limits.items()]
