"""Serving launcher.

Two modes, matching the paper's kind (ultra-low-latency inference):

  * ``--mode lut``: serve batched classification requests through the
    production LUT engine (``repro.serve``).  Converted truth tables are a
    deployable artifact: if ``--registry`` already holds a bundle for the
    arch, it is loaded and served directly — *no retraining*.  Otherwise the
    model is trained once, converted, saved to the registry, then served.
    Reports p50/p95/p99 request latency, throughput, queue depth and batch
    occupancy from the engine's metrics tracker.

  * ``--mode lm``: decode tokens from a reduced LM with a KV cache
    (greedy), demonstrating the serve_step path end-to-end.

With ``--tenants N`` the lut mode serves N tenants through one
admission-controlled ``MultiTenantEngine`` (tenant 0 is the registry
bundle; the rest are same-geometry variants), printing per-tenant
metrics; add ``--swap`` to additionally hot-swap tenant 0 onto a
re-packed redeploy under live traffic (shadow bit-exactness check ->
atomic cutover) and print the SwapReport.
"""
from __future__ import annotations

import argparse
import time


def build_lut_bundle(args):
    """Load the serving bundle from the registry, or train-convert-save it
    once if absent (or ``--retrain``)."""
    from repro.config import get_config
    from repro.core import model as M
    from repro.core import truth_table as TT
    from repro.core.train import train_neuralut
    from repro.data import jsc_synthetic
    from repro.serve import TableRegistry, bundle_from_training

    cfg = get_config(args.arch, reduced=args.reduced)
    if getattr(cfg, "in_features", None) != 16:
        raise SystemExit(f"--mode lut expects a JSC NeuraLUT config, got "
                         f"'{args.arch}' — try --mode lm for LM archs")
    reg = TableRegistry(args.registry) if args.registry else None

    if reg is not None and reg.has(cfg.name) and not args.retrain:
        bundle = reg.load(cfg.name)
        # The integrity block is per-array sha256 digests — load() just
        # verified them; print only the human-facing meta.
        meta = {k: v for k, v in bundle.meta.items() if k != "integrity"}
        verified = "integrity verified, " if "integrity" in bundle.meta \
            else ""
        print(f"loaded bundle '{cfg.name}' from {args.registry} "
              f"({verified}tables: {bundle.num_table_bytes/1024:.1f} KiB, "
              f"meta: {meta}) — no retraining", flush=True)
        return bundle

    xtr, ytr = jsc_synthetic(20000, seed=0)
    xte, yte = jsc_synthetic(4000, seed=1)
    print(f"training {cfg.name} ...", flush=True)
    params, state, hist = train_neuralut(
        cfg, xtr, ytr, xte, yte, epochs=args.epochs, batch=256, lr=2e-3,
        log_every=max(1, args.epochs // 4))
    statics = M.model_static(cfg)
    # Fused conversion emits bit-packed tables directly; the bundle is
    # serving-ready without a prepack pass.
    tables, packed = TT.convert_packed(cfg, params, state, statics)
    acc_q = hist["test_acc_q"][-1]
    print(f"accuracy (quantized): {acc_q:.4f}", flush=True)
    bundle = bundle_from_training(cfg, params, tables, statics,
                                  packed_tables=packed,
                                  meta={"train_acc_q": float(acc_q)})
    if reg is not None:
        path = reg.save(cfg.name, bundle)
        print(f"saved bundle -> {path}", flush=True)
    return bundle


def serve_lut(args) -> None:
    from collections import deque

    import numpy as np
    from repro.data import jsc_synthetic
    from repro.serve import LUTServeEngine

    bundle = build_lut_bundle(args)
    xte, yte = jsc_synthetic(4000, seed=1)

    with LUTServeEngine(bundle, max_wait_ms=args.max_wait_ms,
                        use_kernel=args.kernel or None,
                        replicas=args.replicas,
                        sharded=args.sharded) as eng:
        eng.warmup()
        rng = np.random.default_rng(0)
        # Bounded in-flight window: enough concurrency to exercise the
        # batcher, without the unbounded client burst that would make the
        # latency percentiles measure our own backlog.
        correct = total = 0
        pending: "deque" = deque()

        def drain_one():
            nonlocal correct, total
            idx, fut = pending.popleft()
            pred = fut.result()
            correct += int((pred == yte[idx]).sum())
            total += len(idx)

        for _ in range(args.requests):
            idx = rng.integers(0, len(xte), args.batch)
            pending.append((idx, eng.submit(xte[idx])))
            if len(pending) >= args.inflight:
                drain_one()
        while pending:
            drain_one()
        print(f"served {args.requests} requests x batch {args.batch} "
              f"(inflight {args.inflight}): "
              f"{eng.metrics.render()} acc={correct/total:.4f}", flush=True)
        if eng.replicas > 1:
            for i, m in enumerate(eng.replica_metrics):
                print(f"  replica {i}: {m.render()}", flush=True)


def serve_tenants(args) -> None:
    """N tenants behind one MultiTenantEngine: tenant 0 serves the
    registry bundle; tenants 1..N-1 get same-geometry variant bundles
    (fresh random tables — realistic distinct-customer payloads that
    still pack into the same compiled forward)."""
    import numpy as np
    from repro.data import jsc_synthetic
    from repro.serve import (MultiTenantEngine, ServeBundle, Tenant,
                             TenantOverloaded)

    bundle = build_lut_bundle(args)
    cfg = bundle.cfg
    xte, _ = jsc_synthetic(4000, seed=1)
    rng = np.random.default_rng(7)
    tenants = [Tenant("primary", bundle, priority=1)]
    for i in range(1, args.tenants):
        tenants.append(Tenant(
            f"tenant{i}",
            ServeBundle(
                cfg=cfg,
                tables=[rng.integers(0, 2 ** cfg.beta, t.shape)
                        .astype(t.dtype) for t in bundle.tables],
                statics=[{k: v.copy() for k, v in s.items()}
                         for s in bundle.statics],
                in_log_s=bundle.in_log_s.copy(),
                layer_log_s=[s.copy() for s in bundle.layer_log_s]),
            rate_limit=args.rate_limit or None))

    with MultiTenantEngine(tenants,
                           max_wait_ms=args.max_wait_ms) as eng:
        eng.warmup()
        print(f"{len(tenants)} tenants -> {eng.num_groups} geometry "
              f"group(s), one compiled forward each", flush=True)
        for r in range(args.requests):
            name = tenants[r % len(tenants)].name
            idx = rng.integers(0, len(xte), args.batch)
            try:
                eng.predict(name, xte[idx])
            except TenantOverloaded as e:
                print(f"  shed: {e}", flush=True)
        for t in tenants:
            m = eng.tenant_metrics(t.name)
            print(f"  {t.name}: {m.render()} shed={m.shed} "
                  f"shed_rate={m.shed_rate:.2f}", flush=True)
        if args.swap:
            candidate = ServeBundle(
                cfg=cfg, tables=[t.copy() for t in bundle.tables],
                statics=[{k: v.copy() for k, v in s.items()}
                         for s in bundle.statics],
                in_log_s=bundle.in_log_s.copy(),
                layer_log_s=[s.copy() for s in bundle.layer_log_s])
            import threading
            stop = threading.Event()

            def traffic():
                while not stop.is_set():
                    eng.predict("primary", xte[:args.batch])

            th = threading.Thread(target=traffic, daemon=True)
            th.start()
            rep = eng.swap("primary", candidate, shadow_samples=64,
                           timeout_s=60.0)
            stop.set()
            th.join()
            print(f"swap: status={rep.status} states={rep.states} "
                  f"shadow={rep.shadow_samples} "
                  f"mismatches={rep.mismatches} "
                  f"swap={rep.swap_latency_s*1e3:.1f}ms "
                  f"cutover={rep.cutover_latency_s*1e3:.2f}ms", flush=True)
            if rep.status != "committed":
                raise SystemExit(f"hot swap failed: {rep.error}")


def serve_lm(args) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.config import get_config
    from repro.models import api
    from repro.train.step import make_serve_step

    cfg = get_config(args.arch, reduced=True)
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    bsz, ctx = args.batch, 128
    spec = api.decode_state_spec(cfg, bsz, ctx)
    state = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), spec,
                         is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
    state["pos"] = jnp.int32(0)
    step = jax.jit(make_serve_step(cfg), donate_argnums=(1,))
    tok = jnp.ones((bsz, 1), jnp.int32)
    t0 = time.time()
    n = args.requests
    for i in range(n):
        logits, state = step(params, state, tok)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)[:, None] % cfg.vocab_size
    jax.block_until_ready(tok)
    dt = time.time() - t0
    print(f"decoded {n} steps x batch {bsz}: {dt/n*1e3:.2f} ms/token, "
          f"{n*bsz/dt:.0f} tok/s", flush=True)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", default="lut", choices=["lut", "lm"])
    ap.add_argument("--arch", default="neuralut-jsc-2l")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--requests", type=int, default=200)
    ap.add_argument("--epochs", type=int, default=20)
    ap.add_argument("--registry", default="results/registry",
                    help="bundle store dir; '' disables persistence")
    ap.add_argument("--retrain", action="store_true",
                    help="retrain even if a registry bundle exists")
    ap.add_argument("--max-wait-ms", type=float, default=2.0,
                    help="dynamic batcher admission window")
    ap.add_argument("--inflight", type=int, default=4,
                    help="max outstanding requests in the client loop")
    ap.add_argument("--kernel", action="store_true",
                    help="force the Pallas lookup kernel (default: TPU only)")
    ap.add_argument("--replicas", type=int, default=1,
                    help="replica executors to route batches across "
                         "(one per local device, round-robin)")
    ap.add_argument("--sharded", action="store_true",
                    help="serve through the shard_map'd multi-device "
                         "cascade (repro.serve.sharded) instead of "
                         "replica routing")
    ap.add_argument("--tenants", type=int, default=0,
                    help="serve N tenants through one MultiTenantEngine "
                         "(lut mode only)")
    ap.add_argument("--rate-limit", type=float, default=0.0,
                    help="requests/s token-bucket for the secondary "
                         "tenants (0 = unlimited)")
    ap.add_argument("--swap", action="store_true",
                    help="with --tenants: hot-swap tenant 0 onto a "
                         "re-packed redeploy under live traffic")
    args = ap.parse_args()
    from repro.launch.compile_cache import setup_compile_cache
    setup_compile_cache()
    if args.mode == "lut" and args.tenants:
        serve_tenants(args)
    elif args.mode == "lut":
        serve_lut(args)
    else:
        serve_lm(args)


if __name__ == "__main__":
    main()
