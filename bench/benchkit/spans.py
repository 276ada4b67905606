"""The program's own spans in a traced run: read, nested, clipped.

The program records spans with ``jax.profiler.TraceAnnotation`` (their
names are listed in ``src/repro/runtime/spans.py``).  They land in the
same ``.xplane.pb`` as the device's operations, on the same clock, so a
span can be lined up with what the device did while it was open.
``trace.extract`` keeps only the benchmark's own ``bench.*`` spans; this
module reads the capture again for the program's, those named with one of
:data:`PREFIXES`, into a plain record: one row per span,

    [name, thread, start_ns, dur_ns, args]

where ``thread`` numbers the host thread (the trace's line) it ran on and
``args`` holds the span's arguments (the event's stats).  Everything
after that is arithmetic on the rows and on ``trace``'s record: nesting
by thread, clipping to the ``bench.window`` interval, self time, and the
device's idle time inside spans by the union-of-ops rule of
:func:`trace.busy_intervals`.  ``tests/bench`` checks it on a hand-built
record and on a trace recorded on the chip.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Tuple

from benchkit import trace as T

PREFIXES = ("serve.", "convert.")


@dataclass(eq=False)
class Span:
    name: str
    thread: int
    start: int                       # ns, the trace's clock
    end: int
    args: Dict = field(default_factory=dict)
    # the innermost enclosing span on the same thread, and the spans
    # this one encloses directly
    parent: Optional["Span"] = field(default=None, repr=False)
    children: List["Span"] = field(default_factory=list, repr=False)

    @property
    def dur(self) -> int:
        return self.end - self.start


def extract(xplane_path: Path) -> List[list]:
    """Rows of every program span in a profiler capture."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(xplane_path))
    rows, thread = [], 0
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIXES):
                    rows.append([e.name, thread, int(e.start_ns),
                                 int(e.duration_ns), dict(e.stats)])
            thread += 1
    return rows


def capture_file(root: Path, workload: str) -> Optional[Path]:
    """The newest capture of a cell's traced run (where ``cell.run``'s
    ``Capture`` writes it), or None."""
    files = sorted((Path(root) / ".bench_cache" / "trace"
                    / workload).rglob("*.xplane.pb"))
    return files[-1] if files else None


@functools.lru_cache(maxsize=1)
def _rows(path: str, mtime_ns: int) -> Tuple[tuple, ...]:
    return tuple(tuple(r) for r in extract(Path(path)))


def nest(rows: Iterable[list]) -> List[Span]:
    """Spans with each one's parent and children: on one thread, spans
    nest by time, so the enclosing span is the innermost open one."""
    spans = [Span(n, th, s, s + d, dict(a)) for n, th, s, d, a in rows]
    stacks: Dict[int, List[Span]] = {}
    for sp in sorted(spans, key=lambda x: (x.thread, x.start, -x.end)):
        stack = stacks.setdefault(sp.thread, [])
        while stack and stack[-1].end <= sp.start:
            stack.pop()
        if stack and sp.end <= stack[-1].end:
            sp.parent = stack[-1]
            stack[-1].children.append(sp)
        stack.append(sp)
    return spans


def of(ctx) -> List[Span]:
    """The traced run's program spans, nested (empty when the capture
    holds none, as with a program that records no spans)."""
    path = capture_file(ctx.cell.root, ctx.cell.name)
    if path is None:
        return []
    return nest(_rows(str(path), path.stat().st_mtime_ns))


def named(spans: Iterable[Span], name: str) -> List[Span]:
    return [s for s in spans if s.name == name]


def starting_in(spans: Iterable[Span], window) -> List[Span]:
    """Spans that began inside ``window`` ([start_ns, end_ns])."""
    ws, we = window
    return [s for s in spans if ws <= s.start < we]


def clip(iv: Tuple[int, int], window) -> Tuple[int, int]:
    """An interval cut to the window (empty: start == end)."""
    s, e = max(iv[0], window[0]), min(iv[1], window[1])
    return (s, max(s, e))


def union(intervals: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    merged: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1] = (merged[-1][0], max(merged[-1][1], e))
        else:
            merged.append((s, e))
    return merged


def covered(intervals: Iterable[Tuple[int, int]]) -> int:
    """Nanoseconds covered by the union of the intervals."""
    return sum(e - s for s, e in union(intervals))


def self_ns(span: Span, window=None) -> int:
    """The span's duration minus what its children cover (both cut to
    ``window`` where one is given)."""
    if window is None:
        window = (span.start, span.end)
    s, e = clip((span.start, span.end), window)
    return (e - s) - covered(clip((c.start, c.end), (s, e))
                             for c in span.children)


def overlap(a: List[Tuple[int, int]], b: List[Tuple[int, int]]) -> int:
    """Nanoseconds in both of two sorted, disjoint interval lists."""
    i = j = tot = 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            tot += e - s
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return tot


def device_idle_ns(rec: Dict, intervals: Iterable[Tuple[int, int]]
                   ) -> Optional[float]:
    """Nanoseconds of the intervals' union (cut to the window) in which
    no operation ran on the device, averaged over the devices that ran
    any; None when the trace holds no device operation."""
    devs = T.devices(rec)
    if not devs:
        return None
    span = union(clip(iv, rec["window"]) for iv in intervals)
    tot = covered(span)
    return sum(tot - overlap(span, T.busy_intervals(rec, d))
               for d in devs) / len(devs)


def waits_us(span: Span) -> List[int]:
    """A ``serve.batch`` span's per-request waits (space-separated
    microseconds; the profiler gives a lone number back as a number)."""
    w = span.args.get("waits_us")
    return [] if w is None else [int(v) for v in str(w).split()]
