"""Device traces: capture with the JAX profiler, reduce to metrics.

A traced run wraps its window in :class:`Capture`.  The profiler's
``.xplane.pb`` is read back with ``jax.profiler.ProfileData`` into a plain
record (:func:`extract`) of

  * device operations: ``[name, label, start_ns, dur_ns, device]`` from the
    op line of every TPU plane: ``name`` is the HLO instruction's name
    (``jvp__.68``), ``label`` the target of a custom call
    (``tpu_custom_call`` for a compiled Pallas kernel) or "";
  * host spans: ``[name, start_ns, dur_ns]`` of the benchmark's own
    ``bench.*`` annotations;
  * the window: ``[start_ns, end_ns]`` of the ``bench.window`` span.

Everything after that is arithmetic on the record (busy time, idle share,
kernel time, the breakdown), kept here so that every run and every PR
computes it the same way; ``tests/bench`` checks it on a hand-built record.
"""
from __future__ import annotations

import bisect
import re
import shutil
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Optional, Tuple

OPS_LINE = "XLA Ops"
WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."


class Capture:
    """Profile the enclosed block into ``log_dir`` (emptied first).  The
    Python tracer is off: it would add a cost to every call the serving
    threads make."""

    def __init__(self, log_dir: Path):
        self.log_dir = Path(log_dir)

    def __enter__(self):
        import jax
        shutil.rmtree(self.log_dir, ignore_errors=True)
        self.log_dir.mkdir(parents=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(self.log_dir), profiler_options=opts)
        return self

    def __exit__(self, *exc):
        import jax
        jax.profiler.stop_trace()

    def record(self) -> Dict:
        files = sorted(self.log_dir.rglob("*.xplane.pb"))
        if not files:
            raise RuntimeError(f"profiler wrote no trace under "
                               f"{self.log_dir}")
        return extract(files[-1])


def _op(text: str) -> Tuple[str, str]:
    """(instruction name, custom-call target) of a TPU op event, whose
    name is the instruction's HLO text ("%jvp__.68 = (...) custom-call(
    ...), custom_call_target="tpu_custom_call", ...")."""
    name = text.split(" = ", 1)[0].lstrip("%")
    m = re.search(r'custom_call_target="([^"]+)"', text)
    return name, (m.group(1) if m else "")


def extract(xplane_path: Path) -> Dict:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(xplane_path))
    device, host = [], []
    window = None
    for plane in data.planes:
        m = re.match(r"/device:TPU:(\d+)", plane.name)
        for line in plane.lines:
            if m and line.name == OPS_LINE:
                dev = int(m.group(1))
                for e in line.events:
                    device.append([*_op(e.name), int(e.start_ns),
                                   int(e.duration_ns), dev])
            elif plane.name.startswith("/host:"):
                for e in line.events:
                    if e.name == WINDOW_SPAN:
                        window = [int(e.start_ns),
                                  int(e.start_ns + e.duration_ns)]
                    elif e.name.startswith(SPAN_PREFIX):
                        host.append([e.name, int(e.start_ns),
                                     int(e.duration_ns)])
    if window is None:
        raise RuntimeError("trace holds no bench.window span")
    return {"device": device, "host": host, "window": window}


# ---------------------------------------------------------------------------
# reduction


def _in_window(rec: Dict) -> List[list]:
    ws, we = rec["window"]
    return [e for e in rec["device"] if e[2] < we and e[2] + e[3] > ws]


def window_s(rec: Dict) -> float:
    ws, we = rec["window"]
    return (we - ws) / 1e9


def devices(rec: Dict) -> List[int]:
    return sorted({e[4] for e in rec["device"]})


def busy_intervals(rec: Dict, device: int) -> List[Tuple[int, int]]:
    """Union of one device's op intervals, clipped to the window."""
    ws, we = rec["window"]
    iv = sorted((max(e[2], ws), min(e[2] + e[3], we))
                for e in _in_window(rec) if e[4] == device)
    merged: List[Tuple[int, int]] = []
    for s, e in iv:
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1] = (merged[-1][0], e)
        else:
            merged.append((s, e))
    return merged


def busy_s(rec: Dict) -> Optional[float]:
    """Seconds in which some operation ran, averaged over the devices
    that ran any; None when no device operation was recorded."""
    devs = devices(rec)
    if not devs:
        return None
    tot = sum(e - s for d in devs for s, e in busy_intervals(rec, d))
    return tot / len(devs) / 1e9


def idle_share(rec: Dict) -> Optional[float]:
    b = busy_s(rec)
    return None if b is None else 1.0 - b / window_s(rec)


def kernel_events(rec: Dict, pattern: str) -> List[list]:
    """Device ops in the window whose name or label matches ``pattern``."""
    rx = re.compile(pattern)
    return [e for e in _in_window(rec)
            if rx.search(e[0]) or rx.search(e[1])]


def kernel_s(rec: Dict, pattern: str) -> Tuple[float, int]:
    """(summed device seconds, number of calls) of a kernel."""
    ev = kernel_events(rec, pattern)
    return sum(e[3] for e in ev) / 1e9, len(ev)


def top_ops(rec: Dict, n: int = 10) -> List[list]:
    tot: Dict[str, float] = defaultdict(float)
    for e in _in_window(rec):
        tot[e[0]] += e[3] / 1e9
    return [[k, v] for k, v in sorted(tot.items(),
                                      key=lambda kv: -kv[1])[:n]]


def idle_gaps(rec: Dict, n: int = 10) -> List[list]:
    """Idle time of device 0 inside the window, summed by the benchmark
    span the host was in at the middle of each gap (innermost span;
    "outside bench spans" when none)."""
    devs = devices(rec)
    if not devs:
        return []
    ws, we = rec["window"]
    busy = busy_intervals(rec, devs[0])
    gaps, t = [], ws
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if t < we:
        gaps.append((t, we))
    spans = sorted(rec["host"], key=lambda h: h[1])
    starts = [h[1] for h in spans]
    tot: Dict[str, float] = defaultdict(float)
    for s, e in gaps:
        mid = (s + e) // 2
        i = bisect.bisect_right(starts, mid)
        # spans that began before the gap's middle and are still open
        # (the nearest few: spans nest shallowly)
        inner = [h for h in spans[max(0, i - 64):i] if mid < h[1] + h[2]]
        label = (min(inner, key=lambda h: h[2])[0] if inner
                 else "outside bench spans")
        tot[label] += (e - s) / 1e9
    return [[k, v] for k, v in sorted(tot.items(),
                                      key=lambda kv: -kv[1])[:n]]
