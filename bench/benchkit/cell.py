"""One run of one cell: resolve it by name, set up, measure, check.

Everything that belongs to one configuration, traffic mix, cell or
per-layer metric lives in a file of its own, found by the name that
``BENCHMARK.json`` gives it:

  configuration   the ``file`` of its ``configs`` entry
  traffic mix     bench/traffic/<traffic>.json; its ``driver`` names
                  bench/drivers/<driver>.py, the general generator that
                  reads it
  cell limits     bench/limits/<workload>.json (the numbers ``correct``
                  is decided by, and the readings they were set from)
  metric          bench/metrics/<metric>.py, a ``read(ctx)`` that returns
                  the value or None when the run holds nothing to read
  kernel counts   bench/counts/<kernel>.py (loaded by the metric readers)

A later cell, mix or metric is added by adding files and entries; this
module does not change.
"""
from __future__ import annotations

import importlib.util
import json
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional

BENCH = Path(__file__).resolve().parents[1]


class BenchError(RuntimeError):
    """The run cannot produce a result (no chip, a route that is not the
    planned one, a compile inside the window): exit non-zero, print no
    result."""


def load_module(path: Path):
    """Import a file by path (metric and count files are named after
    metrics, which may hold dots)."""
    name = "bench_" + "_".join(path.relative_to(BENCH).with_suffix("")
                               .parts).replace(".", "_").replace("-", "_")
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def counts(kernel: str):
    return load_module(BENCH / "counts" / f"{kernel}.py")


def reference(conf: Dict):
    """The plain reference that a configuration file names."""
    return load_module(BENCH / "reference" / f"{conf['reference']}.py")


@dataclass
class Cell:
    root: Path
    workload: Dict
    config: Dict          # the configs entry of BENCHMARK.json
    conf: Dict            # the configuration file's contents
    traffic: Dict
    limits: Dict
    end_to_end: List[Dict]
    per_layer: List[Dict]

    @property
    def name(self) -> str:
        return self.workload["name"]


def _applies(metric: Dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def resolve(spec: Dict, root: Path, workload: str,
            bench: Path = BENCH) -> Cell:
    """The cell named ``workload``, with every file it names loaded."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise BenchError(f"no workload {workload!r} in BENCHMARK.json; "
                         f"have {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    config = configs[w["config"]]
    with open(root / config["file"]) as f:
        conf = json.load(f)
    with open(bench / "traffic" / f"{w['traffic']}.json") as f:
        traffic = json.load(f)
    with open(bench / "limits" / f"{workload}.json") as f:
        limits = json.load(f)
    e2e = [m for m in spec["end_to_end"] if _applies(m, workload)]
    layer = [m for m in spec["per_layer"] if _applies(m, workload)]
    for m in layer:
        if not (bench / "metrics" / f"{m['name']}.py").is_file():
            raise BenchError(f"metric {m['name']} has no reader")
    if not (bench / "drivers" / f"{traffic['driver']}.py").is_file():
        raise BenchError(f"traffic {w['traffic']} names driver "
                         f"{traffic['driver']}, which does not exist")
    return Cell(root, w, config, conf, traffic, limits, e2e, layer)


@dataclass
class Check:
    """One number compared with its limit; ``ok`` when value <= limit."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


@dataclass
class Window:
    """What a driver's window returns: its end-to-end metrics (host
    clock), the attempted and failed units of work, and counters that
    per-layer readers use."""
    seconds: float
    metrics: Dict[str, float]
    attempted: int
    failed: int
    counters: Dict = field(default_factory=dict)


@dataclass
class Context:
    """What a per-layer metric reader sees."""
    cell: Cell
    geom: object
    window: Window
    trace: Optional[Dict]
    peaks: Dict


def device_info(strict: bool, chips: int) -> Dict:
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if strict and info["platform"] != "tpu":
        raise BenchError(f"no TPU: JAX found {info}")
    if strict and len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX found "
                         f"{len(devs)}")
    return info


def memory_peak_bytes() -> Optional[int]:
    import jax
    peaks = []
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def peaks_for(kind: str) -> Dict:
    with open(BENCH / "peaks.json") as f:
        table = json.load(f)
    if kind not in table:
        raise BenchError(f"no peaks for device kind {kind!r} in "
                         f"bench/peaks.json")
    return table[kind]


TRACE_SECONDS = 4.0


def run(spec: Dict, root: Path, workload: str, seed: int, seconds: float,
        trace: bool, *, t_start: float, strict: bool = True,
        fault: str = "") -> Dict:
    """Run one cell and return the result line's object.  ``strict``
    (always on in benchmark runs) requires the TPU, its planned kernel
    routes and compiled kernels; the CPU tests turn it off.  ``fault``
    plants one of a driver's known faults under the timed path, for the
    tests that show ``correct`` comes out false."""
    from benchkit.compile_stats import CompileStats
    from benchkit.model import Geometry
    from benchkit import trace as T

    cell = resolve(spec, root, workload)
    device = device_info(strict, cell.workload["chips"])
    stats = CompileStats()
    driver = load_module(BENCH / "drivers"
                         / f"{cell.traffic['driver']}.py")
    drv = driver.Driver(cell, seed, strict=strict, fault=fault)
    drv.setup()
    setup_s = time.time() - t_start
    before = stats.snapshot()
    rec = None
    if trace:
        cap = T.Capture(root / ".bench_cache" / "trace" / workload)
        with cap:
            win = drv.window(min(seconds, TRACE_SECONDS), traced=True)
        rec = cap.record()
    else:
        win = drv.window(seconds, traced=False)
    after = stats.snapshot()
    if after["compiles"] != before["compiles"]:
        raise BenchError(f"{after['compiles'] - before['compiles']} "
                         f"compilations inside the measured window")
    device["memory_peak_bytes"] = memory_peak_bytes()
    drv.release()
    checks: List[Check] = drv.check()

    if trace:
        ctx = Context(cell, Geometry.from_conf(cell.conf), win, rec,
                      peaks_for(device["kind"]) if strict
                      else {"flops_per_s": 1.0, "hbm_bytes_per_s": 1.0})
        metrics = {}
        for m in cell.per_layer:
            value = load_module(BENCH / "metrics"
                                / f"{m['name']}.py").read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        busy = T.busy_s(rec)
        if strict and not busy:
            raise BenchError("the trace holds no device operation in the "
                             "window")
        device["busy_s"] = busy
        device["window_s"] = T.window_s(rec)
    else:
        metrics = {}
        for m in cell.end_to_end:
            value = setup_s if m["name"] == "setup_s" \
                else win.metrics[m["name"]]
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    result = {"correct": all(c.ok for c in checks),
              "attempted": win.attempted, "failed": win.failed,
              "metrics": metrics, "device": device}
    if trace:
        result["breakdown"] = {"device_ops": T.top_ops(rec),
                               "idle_gaps": T.idle_gaps(rec)}
    result["compile"] = {"setup": before,
                         "window": after["compiles"] - before["compiles"]}
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in checks}
    return result
