"""Pareto sweep launcher: the whole Figs. 6-7 grid as one mesh program.

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python -m repro.launch.sweep --seeds 3 --epochs 10 --devices 8 \
            --track results/sweep.jsonl --registry results/registry

Plans the paper grid (``repro.sweep.paper_sweep_points``) into stacked
geometry groups, trains every (geometry, seed) unit mesh-parallel in one
compiled program per group (``repro.sweep.run_pareto_sweep``), and
streams frontier points to a tracker as each group finishes.  With
``--registry`` every point's best seed is converted through the fused
packed truth-table sweep and saved as a serving-ready bundle.
"""
from __future__ import annotations

import argparse


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--epochs", type=int, default=10)
    ap.add_argument("--n-train", type=int, default=6000)
    ap.add_argument("--n-test", type=int, default=2000)
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--devices", type=int, default=None,
                    help="mesh size (default: all visible devices; "
                         "force host devices via XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N)")
    ap.add_argument("--track", default=None,
                    help="stream per-point records to this JSONL file")
    ap.add_argument("--registry", default=None,
                    help="convert each point's best seed and save "
                         "serving-ready bundles here")
    ap.add_argument("--resume", default=None, metavar="DIR",
                    help="journal finished groups here and, on rerun, "
                         "replay them instead of retraining (resume a "
                         "killed/preempted sweep)")
    ap.add_argument("--max-group-retries", type=int, default=2,
                    help="redispatches (with backoff) before a failing "
                         "group aborts the sweep")
    ap.add_argument("--quiet", action="store_true")
    args = ap.parse_args()

    from repro.launch.compile_cache import setup_compile_cache
    setup_compile_cache()
    from repro.data import device_dataset, mnist_pooled
    from repro.launch.mesh import make_sweep_mesh
    from repro.runtime.straggler import StepWatchdog
    from repro.runtime.tracker import (CompositeTracker, JsonlTracker,
                                       NoopTracker, PrintTracker)
    from repro.sweep import paper_sweep_points, run_pareto_sweep

    trackers = []
    if not args.quiet:
        trackers.append(PrintTracker())
    if args.track:
        trackers.append(JsonlTracker(args.track))
    tracker = (CompositeTracker(trackers) if len(trackers) > 1
               else (trackers[0] if trackers else NoopTracker()))

    xtr, ytr = device_dataset(mnist_pooled, args.n_train, seed=0)
    xte, yte = device_dataset(mnist_pooled, args.n_test, seed=1)
    mesh = make_sweep_mesh(args.devices)
    print(f"mesh: {mesh.devices.size} device(s)", flush=True)

    with tracker:
        result = run_pareto_sweep(
            paper_sweep_points(), xtr, ytr, xte, yte,
            seeds=tuple(range(args.seeds)), epochs=args.epochs,
            batch=args.batch, lr=args.lr, mesh=mesh, tracker=tracker,
            convert=bool(args.registry), resume=args.resume,
            max_group_retries=args.max_group_retries,
            watchdog=StepWatchdog())

    replayed = sum(1 for g in result.groups if g.replayed)
    print(f"{len(result.points)} points / {len(result.groups)} compiled "
          f"group programs on {result.devices} device(s): "
          f"cold {result.cold_s:.1f}s + warm {result.warm_s:.1f}s "
          f"= {result.total_s:.1f}s"
          + (f" ({replayed} group(s) replayed from journal)"
             if replayed else ""), flush=True)
    for res in result.points:
        if res.status != "ok":
            print(f"  [{res.point.tag:>9}] {res.name:<16} FAILED "
                  f"({res.diverged_seeds} diverged seed(s))", flush=True)
            continue
        print(f"  [{res.point.tag:>9}] {res.name:<16} "
              f"err={res.err:.4f} luts={res.est.luts:.0f} "
              f"latency={res.est.latency_ns:.1f}ns", flush=True)

    if args.registry:
        from repro.core import model as M
        from repro.serve import TableRegistry, bundle_from_training
        reg = TableRegistry(args.registry)
        for res in result.points:
            if res.packed is None:          # diverged -> nothing to ship
                continue
            tables, packed = res.packed
            bundle = bundle_from_training(
                res.point.cfg, res.params, tables,
                M.model_static(res.point.cfg), packed_tables=packed,
                meta={"sweep_err": res.err, "tag": res.point.tag})
            path = reg.save(res.name, bundle)
            print(f"saved {res.name} -> {path}", flush=True)


if __name__ == "__main__":
    main()
