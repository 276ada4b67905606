"""Smoke run of the NeuraLUT toolflow on a TPU, through its entry points.

    python chip_smoke.py              # one chip: train -> convert -> serve
    python chip_smoke.py --arch polylut-add-jsc-xl
    python chip_smoke.py --chips 4    # four chips: multi-device paths only

One chip, at the full width of a registered LUT configuration (``--arch``,
default neuralut-jsc-5l; a chain or a LUT graph such as the PolyLUT-Add
adder trees), in one process:

  1. train one epoch on ``data.jsc_synthetic`` from ``--seed`` through
     ``core.train.train_neuralut`` (route ``kernel_train``);
  2. convert to bit-packed truth tables with ``truth_table.convert_packed``
     (a graph's ``convert_graph_packed``; route ``kernel_infer``);
  3. save the bundle to an emptied registry directory and load it back
     with ``load(verify=True)``;
  4. serve a few hundred requests through ``LUTServeEngine`` after
     ``warmup()`` (route ``fused_kernel_tpu``).

It checks that the training kernel's gradients match the jnp
``neuron_leading`` route's at the f32 tolerance of the kernel tests, that
served predictions equal ``lut_infer.lut_forward`` (a graph's
``graph_lut_forward``) on the same tables for every request, and that the
LUT network reproduces the quantized model on the test set.

Four chips (``--chips 4``, a chain) run only the multi-device paths and
what they are compared with: ``LUTServeEngine(replicas=4)`` (each
replica's bundle on its own device), ``make_sharded_forward_fn`` in the
``replicated`` and ``o_sharded`` modes, all three bit-exact against
``lut_forward``, and a small ``run_pareto_sweep`` on
``make_sweep_mesh(4)`` held to the frontier agreement of the
single-program ensemble trainer.

Each phase prints one JSON line.  The last line of standard output is
``{"ok": true, "device": {...}}`` and is printed only when every check
passed; anything else exits non-zero: no TPU, a kernel that would run
interpreted, a route other than the planned kernel, a downgrade, or a
result that disagrees with its reference.
"""
from __future__ import annotations

import argparse
import json
import shutil
import sys
import time
from collections import Counter
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

ARCH = "neuralut-jsc-5l"
REQUESTS = 300
REGISTRY = ROOT / ".smoke_registry"


class SmokeFailure(RuntimeError):
    pass


def lut_config(arch: str):
    """The registered LUT configuration ``arch`` (a chain's
    ``NeuraLUTConfig`` or a graph's ``LUTGraphConfig``)."""
    from repro.config import get_config
    from repro.core.nl_config import LUTGraphConfig, NeuraLUTConfig
    try:
        cfg = get_config(arch)
    except KeyError as e:
        raise SystemExit(f"chip_smoke: {e.args[0]}")
    if not isinstance(cfg, (NeuraLUTConfig, LUTGraphConfig)):
        raise SystemExit(f"chip_smoke: {arch} is not a LUT configuration")
    return cfg


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def emit(phase: str, **rec) -> None:
    print(json.dumps({"phase": phase, **rec}, default=float), flush=True)


class CompileStats:
    """Backend compile seconds and persistent-cache hits, from JAX's own
    monitoring events."""

    def __init__(self):
        from jax import monitoring
        self.events: Counter = Counter()
        self.compile_s = 0.0
        monitoring.register_event_listener(
            lambda event, **kw: self.events.update([event]))
        monitoring.register_event_duration_secs_listener(self._duration)

    def _duration(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += duration

    def snapshot(self) -> dict:
        ev = self.events
        return {"compile_s": round(self.compile_s, 3),
                "cache_hits": ev["/jax/compilation_cache/cache_hits"],
                "cache_misses": ev["/jax/compilation_cache/cache_misses"]}


def kernel_calls(lowered) -> int:
    """Compiled Pallas kernels in a lowered program (an interpreted
    kernel lowers to plain HLO and leaves no custom call)."""
    return lowered.as_text().count("tpu_custom_call")


def oracle_preds(cfg, bundle, x):
    """lut_forward (a graph's graph_lut_forward) on the bundle's own
    tables, on the device."""
    import jax.numpy as jnp
    import numpy as np
    from repro.core import lut_infer as LI
    return np.asarray(LI.predict(cfg, bundle.serve_params(), bundle.tables,
                                 bundle.statics, jnp.asarray(x)))


# ---------------------------------------------------------------------------
# one chip: train -> convert -> registry -> serve


def run_one_chip(args, stats: CompileStats) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import lut_infer as LI
    from repro.core import model as M
    from repro.core import truth_table as TT
    from repro.core.exec_plan import (detect_backend, kernel_compiled,
                                      plan_subnet_exec)
    from repro.core.nl_config import is_graph_config
    from repro.core.train import train_neuralut
    from repro.data import jsc_synthetic
    from repro.serve import (LUTServeEngine, TableRegistry,
                             bundle_from_training)

    cfg = args.cfg
    check(kernel_compiled(), "Pallas kernels would run interpreted")
    xtr, ytr = jsc_synthetic(20000, seed=args.seed)
    xte, yte = jsc_synthetic(4000, seed=args.seed + 1)

    # 1. train
    plan = plan_subnet_exec(cfg, purpose="train")
    check(plan.route == "kernel_train", f"train route {plan.route}")
    statics = M.model_static(cfg)
    p0, s0 = M.model_init(cfg, jax.random.PRNGKey(args.seed))

    def grad_fn(plan):
        def loss(p):
            logits, _, _ = M.model_apply(cfg, p, s0, statics,
                                         jnp.asarray(xtr[:256]), train=True,
                                         exec_plan=plan)
            return M.ce_loss(logits, jnp.asarray(ytr[:256]))
        return jax.jit(jax.grad(loss))

    grad_k = grad_fn(plan)
    n_kern = kernel_calls(grad_k.lower(p0))
    # a forward and a backward kernel per branch (a chain layer is one)
    branches = sum(len(M.node_static_conns(s)) for s in statics)
    check(n_kern >= 2 * branches,
          f"training step lowers {n_kern} compiled kernels")
    # the kernel's gradients vs the jnp route's, at the f32 tolerance of
    # tests/test_train_kernel.py
    gk = jax.tree.leaves(grad_k(p0))
    gj = jax.tree.leaves(grad_fn(plan_subnet_exec(
        cfg, purpose="train", route="neuron_leading"))(p0))
    excess = max(float(np.max(np.abs(a - b) - (3e-5 + 2e-4 * np.abs(b))))
                 for a, b in zip(map(np.asarray, gk), map(np.asarray, gj)))
    emit("grad_vs_neuron_leading", leaves=len(gk), worst_excess=excess)
    check(len(gk) == len(gj) and excess <= 0,
          f"kernel_train gradients differ from neuron_leading by "
          f"{excess} beyond tolerance")
    t0 = time.perf_counter()
    params, state, hist = train_neuralut(cfg, xtr, ytr, xte, yte,
                                         epochs=1, batch=256, seed=args.seed)
    train_s = time.perf_counter() - t0
    check(np.isfinite(hist["loss"][-1]), f"loss {hist['loss'][-1]}")
    emit("train", route=plan.route, interpreted=not kernel_compiled(),
         compiled_kernels_per_step=n_kern, steps=len(xtr) // 256,
         seconds=round(train_s, 3), loss=hist["loss"][-1],
         test_acc_q=hist["test_acc_q"][-1], **stats.snapshot())

    # 2. convert
    cplan = plan_subnet_exec(cfg, purpose="convert")
    check(cplan.route == "kernel_infer", f"convert route {cplan.route}")
    # layer 1's hidden function (a graph node's: its first branch's)
    graph = cfg if is_graph_config(cfg) else cfg.graph()
    fn1 = M.node_branch_params(graph.nodes[1], params["layers"][1],
                               state["layers"][1])[0][0]
    n_kern = kernel_calls(jax.jit(
        lambda p, x: cplan.apply(p, x)).lower(
            fn1, jnp.zeros((4096, cfg.layer_widths[1], cfg.layer_fan_in(1)))))
    check(n_kern == 1, f"conversion sweep lowers {n_kern} compiled kernels")
    t0 = time.perf_counter()
    tables, packed = TT.convert_packed(cfg, params, state, statics)
    convert_s = time.perf_counter() - t0
    entries = int(sum(t.size for t in jax.tree.leaves(tables)))
    emit("convert", route=cplan.route, interpreted=not kernel_compiled(),
         compiled_kernels_per_chunk=n_kern, entries=entries,
         seconds=round(convert_s, 3), **stats.snapshot())

    # LUT network vs the quantized model it was converted from
    xe = jnp.asarray(xte)
    _, values, _ = M.model_apply(cfg, params, state, statics, xe,
                                 train=False)
    codes = LI.input_codes(cfg, params, xe)
    lut_vals = LI.class_values(cfg, params,
                               LI.lut_outputs(cfg, tables, statics, codes))
    differ = np.any(np.asarray(values) != np.asarray(lut_vals), axis=-1)
    emit("lut_vs_quantized", samples=len(xte),
         disagreeing=int(differ.sum()))
    check(not differ.any(),
          f"LUT network disagrees with the quantized model on "
          f"{int(differ.sum())}/{len(xte)} samples")

    # 3. registry round trip
    shutil.rmtree(REGISTRY, ignore_errors=True)
    reg = TableRegistry(str(REGISTRY))
    reg.save(cfg.name, bundle_from_training(cfg, params, tables, statics,
                                            packed_tables=packed))
    bundle = reg.load(cfg.name, verify=True)
    check(all((a == b).all() for a, b in zip(jax.tree.leaves(bundle.tables),
                                             jax.tree.leaves(tables))),
          "loaded tables differ from the saved ones")
    emit("registry", path=str(REGISTRY), verified=True,
         packed_kib=bundle.num_packed_table_bytes / 1024)

    # 4. serve
    rng = np.random.default_rng(args.seed)
    sizes = rng.integers(1, 65, REQUESTS)
    rows = [rng.integers(0, len(xte), n) for n in sizes]
    with LUTServeEngine(bundle) as eng:
        route = eng.plan.route
        check(route == "fused_kernel_tpu", f"serving route {route}")
        interpreted = (detect_backend() != "tpu"
                       if eng.plan.interpret is None else eng.plan.interpret)
        check(not interpreted, "serving plan interprets")
        # the engine's own primary forward, as warmup compiles it
        primary = eng._executors[0]._forward.primary
        n_kern = kernel_calls(primary.lower(
            jnp.zeros((8, cfg.in_features), jnp.float32)))
        check(n_kern == 1, f"serving forward lowers {n_kern} kernels")
        t0 = time.perf_counter()
        eng.warmup()
        warm_s = time.perf_counter() - t0
        futs = [eng.submit(xte[r]) for r in rows]
        got = np.concatenate([f.result(timeout=300) for f in futs])
        rep = eng.metrics.report()
    want = oracle_preds(cfg, bundle, xte[np.concatenate(rows)])
    agree = int((got == want).sum())
    emit("serve", route=route, interpreted=interpreted,
         compiled_kernels_per_call=n_kern, requests=len(rows),
         samples=int(sizes.sum()), agree_with_lut_forward=agree,
         warmup_s=round(warm_s, 3),
         kernel_downgrades=rep["kernel_downgrades"], **stats.snapshot())
    check(rep["kernel_downgrades"] == 0,
          f"{rep['kernel_downgrades']} kernel downgrades")
    check(agree == len(want),
          f"served predictions differ from lut_forward on "
          f"{len(want) - agree}/{len(want)} samples")


# ---------------------------------------------------------------------------
# four chips: replica routing, shard_map'd serving, mesh-parallel sweep


def _random_bundle(cfg, seed):
    """A bundle with random tables in cfg's geometry (lookup semantics
    do not depend on how the tables were made) and seeded quantizers."""
    import jax
    import numpy as np
    from repro.core import model as M
    from repro.serve import bundle_from_training
    rng = np.random.default_rng(seed)
    statics = M.model_static(cfg)
    params, _ = M.model_init(cfg, jax.random.PRNGKey(seed))
    tables = [rng.integers(0, 2 ** cfg.beta, (o, cfg.table_size(i))
                           ).astype(np.uint16)
              for i, o in enumerate(cfg.layer_widths)]
    return bundle_from_training(cfg, params, tables, statics)


def run_four_chips(args, stats: CompileStats) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.nl_config import NeuraLUTConfig, is_graph_config
    from repro.core.train import train_neuralut_ensemble
    from repro.data import jsc_synthetic
    from repro.launch.mesh import make_sweep_mesh
    from repro.serve import LUTServeEngine
    from repro.serve.sharded import make_sharded_forward_fn
    from repro.sweep import SweepPoint, run_pareto_sweep

    devices = jax.devices()[:4]
    cfg = args.cfg
    check(not is_graph_config(cfg), "the four-chip paths take a chain "
                                    f"configuration, not {cfg.name}")
    bundle = _random_bundle(cfg, args.seed)
    xte, _ = jsc_synthetic(4000, seed=args.seed + 1)
    want = oracle_preds(cfg, bundle, xte)

    # replica-routed engine: one resident bundle per device
    rng = np.random.default_rng(args.seed)
    rows = [rng.integers(0, len(xte), n)
            for n in rng.integers(1, 65, REQUESTS)]
    with LUTServeEngine(bundle, replicas=4, devices=devices) as eng:
        placed = eng.replica_devices()
        check(placed == [{d} for d in devices],
              f"replica operands on {placed}")
        eng.warmup()
        futs = [eng.submit(xte[r]) for r in rows]
        got = np.concatenate([f.result(timeout=300) for f in futs])
        rep = eng.metrics.report()
        per = [m.report()["batches"] for m in eng.replica_metrics]
    ref = want[np.concatenate(rows)]
    emit("replicas", route=eng.plan.route, replicas=4,
         operand_devices=[sorted(d.id for d in s) for s in placed],
         batches_per_replica=per, samples=len(ref),
         agree_with_lut_forward=int((got == ref).sum()),
         kernel_downgrades=rep["kernel_downgrades"], **stats.snapshot())
    check((got == ref).all(), "replica engine differs from lut_forward")
    check(rep["kernel_downgrades"] == 0, "replica engine downgraded")

    # shard_map'd serving, both layouts
    mesh = make_sweep_mesh(4)
    for mode in ("replicated", "o_sharded"):
        fwd = make_sharded_forward_fn(bundle, mesh=mesh, mode=mode)
        got = np.asarray(fwd(jnp.asarray(xte)))
        emit("sharded", mode=mode, samples=len(xte),
             agree_with_lut_forward=int((got == want).sum()),
             **stats.snapshot())
        check((got == want).all(), f"{mode} serving differs from "
                                   f"lut_forward")

    # mesh-parallel Pareto sweep vs the single-program ensemble trainer
    def point(name, widths, kind="subnet"):
        extra = (dict(depth=2, width=4, skip=2) if kind == "subnet"
                 else dict(depth=1, width=1, skip=0))
        return SweepPoint(NeuraLUTConfig(
            name=name, in_features=16, layer_widths=widths, num_classes=5,
            beta=2, fan_in=3, kind=kind, **extra), "smoke")

    xtr, ytr = jsc_synthetic(2048, seed=args.seed)
    xts, yts = jsc_synthetic(512, seed=args.seed + 1)
    pts = [point("smoke-a", (32, 5)), point("smoke-b", (24, 5)),
           point("smoke-c", (24, 5), kind="linear")]
    t0 = time.perf_counter()
    res = run_pareto_sweep(pts, xtr, ytr, xts, yts, seeds=(0, 1),
                           epochs=2, batch=64, lr=2e-3, mesh=mesh)
    sweep_s = time.perf_counter() - t0
    worst_acc = worst_loss = 0.0
    for pt, r in zip(pts, res.points):
        _, _, hist = train_neuralut_ensemble(
            pt.cfg, xtr, ytr, xts, yts, seeds=(0, 1), epochs=2, batch=64,
            lr=2e-3)
        ref_acc = np.asarray(hist["test_acc_q"])[-1]
        ref_loss = np.asarray(hist["loss"])[0]
        worst_acc = max(worst_acc, float(np.abs(
            r.history["test_acc_q"][-1] - ref_acc).max()))
        worst_loss = max(worst_loss, float(np.abs(
            r.history["loss"][0] / ref_loss - 1).max()))
    emit("sweep", points=len(pts), units=[g.group.stacked_units
                                          for g in res.groups],
         seconds=round(sweep_s, 3), max_acc_q_gap=worst_acc,
         max_first_loss_rel_gap=worst_loss, **stats.snapshot())
    check(worst_acc <= 0.15, f"sweep frontier off by {worst_acc}")
    check(worst_loss <= 0.15, f"sweep first-epoch loss off by {worst_loss}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the multi-device paths")
    ap.add_argument("--arch", default=ARCH,
                    help="a registered LUT configuration, chain or graph")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    args.cfg = lut_config(args.arch)

    from repro.launch.compile_cache import setup_compile_cache
    cache_dir = setup_compile_cache()
    import jax
    stats = CompileStats()
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    emit("device", **device, arch=args.arch, compile_cache=cache_dir)
    check(device["platform"] == "tpu", f"no TPU: {device}")
    check(len(devs) >= args.chips, f"--chips {args.chips} needs "
                                   f"{args.chips} devices, have {len(devs)}")
    if args.chips == 4:
        run_four_chips(args, stats)
    else:
        run_one_chip(args, stats)
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
