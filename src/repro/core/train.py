"""NeuraLUT training (paper §III-E.1): AdamW (decoupled weight decay)
+ SGDR cosine warm restarts, quantization-aware forward, BN state
threading — as a **device-resident compiled pipeline**.

Each epoch is ONE jitted computation: a ``jax.lax.scan`` over steps with
donated ``(params, state, opt)`` carries, the training set resident on
device, and the minibatch permutation drawn from a JAX PRNG inside the
jit — no per-step Python dispatch, no per-step host sync, no per-step
H2D batch transfer.  Per-epoch metrics stay on device until the end of
training (one deferred fetch), so epochs pipeline back to back; inside
the step the grouped subnet runs through the ``core.exec_plan`` train
route — neuron-leading einsums on CPU, the fused fwd+bwd Pallas kernel
(``kernels/neuralut_grad``) on TPU; ``subnet_route=`` overrides.
Measured on the JSC-5L model this is ~3x the steps/s of the per-step
host-sync loop it replaces (2.98x with intra-op threads pinned;
benchmarks/train_bench.py, BENCH_kernels.json "train").

``train_neuralut_ensemble`` vmaps the same epoch body over S seeds:
one compiled sweep trains S independent restarts (Pareto fronts,
SGDR multi-restart runs) with per-seed permutations and optimizer
state.  ``ensemble_member`` slices one trained network back out.

CPU-sized: the paper's circuit-level models are tiny (10^4..10^6
params); full training runs in seconds-to-minutes here.  Returns the
trained (params, state) and an accuracy trace.
"""
from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import model as M
from repro.core.exec_plan import plan_subnet_exec
from repro.core.nl_config import NeuraLUTConfig
from repro.optim import adamw_init, adamw_update, sgdr_schedule
from repro.runtime import spans as S


def _donate_carries() -> Tuple[int, ...]:
    """Donate (params, state, opt) buffers into the epoch jit.

    XLA:CPU cannot alias donated host buffers and warns instead; keep
    donation for accelerator backends where it elides the carry copies.
    """
    return () if jax.default_backend() == "cpu" else (0, 1, 2)


def make_step_fn_dynamic(cfg: NeuraLUTConfig, *, lr: float,
                         weight_decay: float, t0: int, exec_plan=None):
    """Single SGD step with *traced* statics:
    (params, state, opt, statics, xb, yb) -> (params, state, opt, loss).

    The statics-as-operand form is what lets the sweep engine
    (``repro.sweep``) vmap one compiled step over a stacked geometry
    group — every unit carries its own connectivity arrays.  ``exec_plan``
    routes the grouped subnet (``core.exec_plan``); None uses the
    train-purpose default for this backend (neuron-leading einsums on
    CPU, the fused fwd+bwd Pallas kernel on TPU)."""
    if exec_plan is None:
        exec_plan = plan_subnet_exec(cfg, purpose="train")

    def step_fn(params, state, opt, statics, xb, yb):
        def loss_fn(p):
            logits, _, new_state = M.model_apply(
                cfg, p, state, statics, xb, train=True,
                exec_plan=exec_plan)
            return M.ce_loss(logits, yb), new_state

        (loss, new_state), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params)
        lr_t = sgdr_schedule(opt["count"], lr_max=lr, lr_min=lr * 1e-2,
                             t0=t0, t_mult=2)
        params, opt = adamw_update(grads, opt, params, lr=lr_t,
                                   weight_decay=weight_decay,
                                   grad_clip=1.0)
        return params, new_state, opt, loss

    return step_fn


def _make_step_fn(cfg: NeuraLUTConfig, statics, *, lr: float,
                  weight_decay: float, t0: int, exec_plan=None):
    """Single SGD step: (params, state, opt, xb, yb) -> (..., loss).

    Thin closure over :func:`make_step_fn_dynamic` for the fixed-
    geometry trainers in this module."""
    dyn = make_step_fn_dynamic(cfg, lr=lr, weight_decay=weight_decay,
                               t0=t0, exec_plan=exec_plan)

    def step_fn(params, state, opt, xb, yb):
        return dyn(params, state, opt, statics, xb, yb)

    return step_fn


def _make_epoch_fn(step_fn, n: int, steps_per_epoch: int, batch: int):
    """One whole epoch as a single jitted scan.

    (params, state, opt, key, xd, yd) -> (params, state, opt, mean_loss).
    The permutation is drawn on device from ``key``; minibatches are
    gathered from the device-resident (xd, yd) inside the scan body.
    """

    @jax.named_scope(S.SCOPE_TRAIN_EPOCH)
    def epoch_fn(params, state, opt, key, xd, yd):
        perm = jax.random.permutation(key, n)[: steps_per_epoch * batch]
        idx = perm.reshape(steps_per_epoch, batch)

        def body(carry, ib):
            params, state, opt = carry
            params, state, opt, loss = step_fn(
                params, state, opt, jnp.take(xd, ib, axis=0),
                jnp.take(yd, ib, axis=0))
            return (params, state, opt), loss

        (params, state, opt), losses = jax.lax.scan(
            body, (params, state, opt), idx)
        return params, state, opt, jnp.mean(losses)

    return jax.jit(epoch_fn, donate_argnums=_donate_carries())


def make_eval_fn_dynamic(cfg: NeuraLUTConfig):
    """Eval with traced statics (un-jitted, composable):
    (params, state, statics, xb, yb) -> (acc, acc_q).

    Always the canonical plan — the layout the truth tables are
    bit-exact against (see core/exec_plan.py)."""

    def eval_fn(params, state, statics, xb, yb):
        logits, values, _ = M.model_apply(cfg, params, state, statics, xb,
                                          train=False)
        return (jnp.mean(jnp.argmax(logits, -1) == yb),
                M.accuracy_from_values(values, yb))

    return eval_fn


def _make_eval_fn(cfg: NeuraLUTConfig, statics):
    dyn = make_eval_fn_dynamic(cfg)

    @jax.jit
    def eval_fn(params, state, xb, yb):
        return dyn(params, state, statics, xb, yb)

    return eval_fn


def train_neuralut(
    cfg: NeuraLUTConfig,
    x_train: np.ndarray,
    y_train: np.ndarray,
    x_test: np.ndarray,
    y_test: np.ndarray,
    *,
    epochs: int = 30,
    batch: int = 256,
    lr: float = 2e-3,
    weight_decay: float = 1e-4,
    seed: int = 0,
    sgdr_t0: int = 0,  # 0 -> one cosine cycle over all steps
    subnet_route: Optional[str] = None,
    log_every: int = 0,
) -> Tuple[Dict, Dict, Dict]:
    statics = M.model_static(cfg)
    key = jax.random.PRNGKey(seed)
    params, state = M.model_init(cfg, key)
    params = M.calibrate_in_quant(cfg, params, x_train)
    opt = adamw_init(params)

    n = x_train.shape[0]
    batch = min(batch, n)
    steps_per_epoch = max(1, n // batch)
    total_steps = epochs * steps_per_epoch
    t0 = sgdr_t0 or total_steps

    step_fn = _make_step_fn(
        cfg, statics, lr=lr, weight_decay=weight_decay, t0=t0,
        exec_plan=plan_subnet_exec(cfg, purpose="train",
                                   route=subnet_route))
    epoch_fn = _make_epoch_fn(step_fn, n, steps_per_epoch, batch)
    eval_fn = _make_eval_fn(cfg, statics)

    # Device-resident once, for the whole run — the epoch scan gathers
    # minibatches on device and the per-epoch eval reuses the same test
    # buffers (no fresh transfer per epoch).
    xd, yd = jnp.asarray(x_train), jnp.asarray(y_train)
    xe, ye = jnp.asarray(x_test), jnp.asarray(y_test)

    traces = {"loss": [], "test_acc": [], "test_acc_q": []}
    for ep in range(epochs):
        params, state, opt, mloss = epoch_fn(
            params, state, opt, jax.random.fold_in(key, ep), xd, yd)
        acc, acc_q = eval_fn(params, state, xe, ye)
        # Deferred metric fetch: keep device scalars; one host sync at
        # the end of training (or at an explicit log point).
        traces["loss"].append(mloss)
        traces["test_acc"].append(acc)
        traces["test_acc_q"].append(acc_q)
        if log_every and (ep + 1) % log_every == 0:
            print(f"  epoch {ep+1}/{epochs} loss={float(mloss):.4f} "
                  f"acc={float(acc):.4f} acc_q={float(acc_q):.4f}",
                  flush=True)
    fetched = jax.device_get(traces)
    history = {k: [float(v) for v in vs] for k, vs in fetched.items()}
    return params, state, history


# ---------------------------------------------------------------------------
# Vmapped multi-seed / multi-restart training (one compiled sweep)


def _make_ensemble_epoch_fn(step_fn, n: int, steps_per_epoch: int,
                            batch: int):
    """The scanned epoch vmapped over a leading seed axis.

    (stacked params/state/opt, per-seed keys (S, 2), xd, yd) -> same
    carries + per-seed mean loss (S,).  Each seed draws its own
    minibatch permutation — S independent restarts per scan step.
    """

    def epoch_fn(params, state, opt, ekeys, xd, yd):
        perms = jax.vmap(
            lambda k: jax.random.permutation(k, n)[: steps_per_epoch * batch]
            .reshape(steps_per_epoch, batch))(ekeys)
        idx = jnp.swapaxes(perms, 0, 1)  # (steps, S, batch)

        def body(carry, ib):
            params, state, opt = carry
            params, state, opt, loss = jax.vmap(
                lambda p, s, o, i: step_fn(
                    p, s, o, jnp.take(xd, i, axis=0),
                    jnp.take(yd, i, axis=0)))(params, state, opt, ib)
            return (params, state, opt), loss

        (params, state, opt), losses = jax.lax.scan(
            body, (params, state, opt), idx)
        return params, state, opt, jnp.mean(losses, axis=0)

    return jax.jit(epoch_fn, donate_argnums=_donate_carries())


def init_ensemble(cfg: NeuraLUTConfig, seeds: Sequence[int], x_train
                  ) -> Tuple[Dict, Dict, Dict, jax.Array]:
    """Stacked (params, state, opt, keys) for S independent restarts."""
    S = len(seeds)
    if S == 0:
        raise ValueError("need at least one seed")
    keys = jnp.stack([jax.random.PRNGKey(s) for s in seeds])
    params, state = jax.vmap(lambda k: M.model_init(cfg, k))(keys)
    # Input-quantizer calibration is data-derived — identical per seed.
    calib = M.calibrate_in_quant(cfg, {"in_quant": None}, x_train)
    params["in_quant"] = {"log_s": jnp.broadcast_to(
        calib["in_quant"]["log_s"],
        (S,) + calib["in_quant"]["log_s"].shape)}
    opt = jax.vmap(adamw_init)(params)
    return params, state, opt, keys


def train_neuralut_ensemble(
    cfg: NeuraLUTConfig,
    x_train: np.ndarray,
    y_train: np.ndarray,
    x_test: np.ndarray,
    y_test: np.ndarray,
    *,
    seeds: Sequence[int] = (0, 1, 2, 3),
    epochs: int = 30,
    batch: int = 256,
    lr: float = 2e-3,
    weight_decay: float = 1e-4,
    sgdr_t0: int = 0,
    subnet_route: Optional[str] = None,
    log_every: int = 0,
) -> Tuple[Dict, Dict, Dict]:
    """Train S independent networks (one per seed) in one compiled sweep.

    Every parameter/optimizer leaf gains a leading S axis; each seed
    draws its own init and its own per-epoch minibatch permutation
    (independent restarts, as a Pareto/SGDR sweep needs).  Returns
    (stacked_params, stacked_state, history) where each history entry is
    a float np.ndarray of shape (epochs, S).  Use :func:`ensemble_member`
    to slice one trained network out of the stack.
    """
    statics = M.model_static(cfg)
    params, state, opt, keys = init_ensemble(cfg, seeds, x_train)

    n = x_train.shape[0]
    batch = min(batch, n)
    steps_per_epoch = max(1, n // batch)
    t0 = sgdr_t0 or epochs * steps_per_epoch

    step_fn = _make_step_fn(
        cfg, statics, lr=lr, weight_decay=weight_decay, t0=t0,
        exec_plan=plan_subnet_exec(cfg, purpose="train",
                                   route=subnet_route))
    jepoch = _make_ensemble_epoch_fn(step_fn, n, steps_per_epoch, batch)
    eval_one = _make_eval_fn(cfg, statics)

    @jax.jit
    def eval_all(params, state, xe, ye):
        return jax.vmap(lambda p, s: eval_one(p, s, xe, ye))(params, state)

    xd, yd = jnp.asarray(x_train), jnp.asarray(y_train)
    xe, ye = jnp.asarray(x_test), jnp.asarray(y_test)

    traces = {"loss": [], "test_acc": [], "test_acc_q": []}
    for ep in range(epochs):
        ekeys = jax.vmap(lambda k: jax.random.fold_in(k, ep))(keys)
        params, state, opt, mloss = jepoch(params, state, opt, ekeys,
                                           xd, yd)
        acc, acc_q = eval_all(params, state, xe, ye)
        traces["loss"].append(mloss)
        traces["test_acc"].append(acc)
        traces["test_acc_q"].append(acc_q)
        if log_every and (ep + 1) % log_every == 0:
            aq = np.asarray(acc_q)
            print(f"  epoch {ep+1}/{epochs} "
                  f"loss={float(np.mean(np.asarray(mloss))):.4f} "
                  f"acc_q[best/mean]={aq.max():.4f}/{aq.mean():.4f}",
                  flush=True)
    fetched = jax.device_get(traces)
    history = {k: np.stack([np.asarray(v) for v in vs]).astype(np.float64)
               for k, vs in fetched.items()}  # (epochs, S)
    return params, state, history


def ensemble_member(params: Dict, state: Dict, s: int
                    ) -> Tuple[Dict, Dict]:
    """Slice trained network ``s`` out of an ensemble (params, state)."""
    take = jax.tree.map(lambda a: a[s], (params, state))
    return take[0], take[1]
