"""Offered-load sweep of an open-loop serving cell, to find its knee: the
highest rate it sustains without a growing backlog.  Run on the chip,
once, when a serving cell's rate is chosen; the cell's traffic file then
holds a fixed rate below the knee, and benchmark runs never search.

    python bench/knee.py --workload jsc5l.serve.trigger --seed 1 \
        --seconds 5 --rates 1000,2000,4000

One process sets the cell up once and runs one window per rate.  Per rate
it prints a JSON line: p50 and p99 latency, the generator's p99 lateness,
and the median latency of the window's last quarter of requests over its
first quarter (a backlog that grows shows as a ratio well above 1).
"""
from __future__ import annotations

import argparse
import json
import statistics
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args()
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT / "bench"))
    from benchkit.cell import BENCH, device_info, load_module, resolve
    from benchkit.stats import percentile
    from repro.launch.compile_cache import setup_compile_cache

    setup_compile_cache()
    with open(ROOT / "BENCHMARK.json") as f:
        spec = json.load(f)
    cell = resolve(spec, ROOT, args.workload)
    device_info(True, cell.workload["chips"])
    drv = load_module(BENCH / "drivers" / f"{cell.traffic['driver']}.py"
                      ).Driver(cell, args.seed, strict=True)
    drv.setup()
    for rate in (float(r) for r in args.rates.split(",")):
        drv.traffic = dict(cell.traffic, rate_per_s=rate)
        t = time.perf_counter()
        win = drv.window(args.seconds, traced=False)
        r = drv.reqs
        lat = [(d - due) * 1e3 for d, due in zip(r.done, r.due)
               if d is not None]
        q = max(1, len(lat) // 4)
        print(json.dumps({
            "rate_per_s": rate, "requests": win.attempted,
            "failed": win.failed,
            "p50_ms": percentile(sorted(lat), 50),
            "p99_ms": win.metrics["serve_p99_ms"],
            "gen_late_p99_ms": percentile(win.counters["late_ms"], 99),
            "backlog_ratio": statistics.median(lat[-q:])
            / statistics.median(lat[:q]),
            "served_samples_per_s": win.metrics["served_samples_per_s"],
            "wall_s": time.perf_counter() - t}), flush=True)
    drv.release()
    return 0


if __name__ == "__main__":
    sys.exit(main())
