"""The program's spans as the benchmark reads them (``benchkit.spans``)
and the per-layer metrics built on them: on a hand-built record, on a
traced CPU run of the tiny cells, and on a window traced on the chip."""
import time
from types import SimpleNamespace

import pytest

from benchkit import spans as SP
from benchkit.cell import BENCH, load_module, run
from test_benchkit_runs import tiny  # noqa: F401  (fixture)

# Window [1000, 9000) ns.  Thread 0 is a replica executor, thread 1 the
# dispatcher.  The second batch runs past the window's end.
WINDOW = [1000, 9000]
SERVE = [
    ["serve.batch", 0, 2000, 4000,
     {"rid": 0, "requests": 2, "samples": 9, "padded": 16, "chunks": 1,
      "waits_us": "1500 700"}],
    ["serve.chunk", 0, 2100, 2900, {"bucket": 8}],
    ["serve.h2d", 0, 2100, 200, {}],
    ["serve.step", 0, 2300, 300, {}],
    ["serve.fetch", 0, 2600, 2400, {}],
    ["serve.resolve", 0, 5200, 700, {}],
    ["serve.batch", 0, 8000, 2000,
     {"rid": 0, "requests": 1, "waits_us": 42}],
    ["serve.await", 1, 500, 1000, {}],
    ["serve.coalesce", 1, 1500, 500, {"requests": 2, "samples": 9}],
]
# Device 0 runs the step over [2400, 4800) and an op over [8500, 8700).
SERVE_TRACE = {"window": WINDOW, "host": [], "device": [
    ["lut_cascade.1", "tpu_custom_call", 2400, 2400, 0],
    ["fusion.3", "", 8500, 200, 0]]}
CONVERT = [
    ["convert.layer", 0, 1000, 2000, {"layer": 0, "entries": 64}],
    ["convert.prepare", 0, 1000, 200, {}],
    ["convert.sweep", 0, 1200, 100, {}],
    ["convert.fetch", 0, 1300, 1600, {}],
    ["convert.layer", 0, 3000, 1000, {"layer": 1, "entries": 16}],
    ["convert.fetch", 0, 3500, 400, {}],
]


def test_nesting_and_self_time():
    spans = SP.nest(SERVE)
    parents = {s.name: (s.parent.name if s.parent else None)
               for s in spans}
    assert parents == {"serve.batch": None, "serve.chunk": "serve.batch",
                       "serve.h2d": "serve.chunk",
                       "serve.step": "serve.chunk",
                       "serve.fetch": "serve.chunk",
                       "serve.resolve": "serve.batch",
                       "serve.await": None, "serve.coalesce": None}
    first, second = SP.named(spans, "serve.batch")
    assert [c.name for c in first.children] == ["serve.chunk",
                                                "serve.resolve"]
    assert SP.self_ns(first) == 4000 - 2900 - 700
    assert SP.self_ns(SP.named(spans, "serve.chunk")[0]) == 0
    # cut to the window: [8000, 9000)
    assert SP.self_ns(second, WINDOW) == 1000
    assert SP.starting_in(spans, WINDOW) == [
        s for s in spans if s.name not in ("serve.await",)]


def test_waits_parse():
    spans = SP.nest(SERVE)
    first, second = SP.named(spans, "serve.batch")
    assert SP.waits_us(first) == [1500, 700]
    assert SP.waits_us(second) == [42]  # a lone wait reads back as a number
    assert SP.waits_us(SP.named(spans, "serve.chunk")[0]) == []


def test_device_idle_inside_spans():
    # batches cover [2000, 6000) and [8000, 9000): 5000 ns, of which the
    # device is busy 2400 + 200
    idle = SP.device_idle_ns(SERVE_TRACE, [(2000, 6000), (8000, 10000)])
    assert idle == 5000 - 2600
    assert SP.device_idle_ns(dict(SERVE_TRACE, device=[]),
                             [(2000, 6000)]) is None


def _ctx(monkeypatch, rows, trace, counters):
    monkeypatch.setattr(SP, "of", lambda ctx: SP.nest(rows))
    return SimpleNamespace(trace=trace,
                           window=SimpleNamespace(counters=counters))


def _metric(name):
    return load_module(BENCH / "metrics" / f"{name}.py")


def test_serving_metrics(monkeypatch):
    ctx = _ctx(monkeypatch, SERVE, SERVE_TRACE, {})
    # waits 42, 700, 1500 us: nearest-rank median 700 us
    assert _metric("serve_queue_wait_p50_ms").read(ctx) == 0.7
    # batches of 4000 and 2000 ns
    assert _metric("serve_batch_p50_ms").read(ctx) == 2000 / 1e6
    assert _metric("chunk_loop_idle.serve_rate").read(ctx) == \
        pytest.approx(100 * 2400 / 8000)


def test_conversion_metrics(monkeypatch):
    trace = {"window": [0, 10000], "host": [], "device": []}
    ctx = _ctx(monkeypatch, CONVERT, trace, {"conversions": 2})
    assert _metric("convert_fetch_ms").read(ctx) == \
        pytest.approx((1600 + 400) / 1e6 / 2)
    assert _metric("convert_host_ms").read(ctx) == \
        pytest.approx((2000 - 1600 + 1000 - 400) / 1e6 / 2)


def test_training_kernel_metric(monkeypatch):
    trace = {"window": [0, 10000], "host": [], "device": [
        ["transpose_jvp_subnet_train_bwd__.3", "tpu_custom_call", 100, 300,
         0],
        ["subnet_train_bwd.7", "tpu_custom_call", 500, 300, 0],
        ["jvp_subnet_train_fwd_.2", "tpu_custom_call", 900, 100, 0]]}
    ctx = _ctx(monkeypatch, [], trace, {"steps": 2})
    assert _metric("subnet_train_bwd_ms").read(ctx) == \
        pytest.approx(600e-9 * 1e3 / 2)
    # the kernels as a program without names shows them
    unnamed = dict(trace, device=[["transpose_jvp___.66", "tpu_custom_call",
                                   100, 300, 0]])
    assert _metric("subnet_train_bwd_ms").read(
        _ctx(monkeypatch, [], unnamed, {"steps": 2})) is None


SPAN_METRICS = ["serve_queue_wait_p50_ms", "serve_batch_p50_ms",
                "chunk_loop_idle.serve_rate", "convert_fetch_ms",
                "convert_host_ms"]


@pytest.mark.parametrize("name", SPAN_METRICS)
def test_a_program_without_spans_reads_nothing(monkeypatch, name):
    ctx = _ctx(monkeypatch, [], SERVE_TRACE, {"conversions": 2})
    assert _metric(name).read(ctx) is None


@pytest.mark.parametrize("workload,names", [
    ("jsc5l.serve.trigger", {"serve_queue_wait_p50_ms",
                             "serve_batch_p50_ms"}),
    ("hdr5l.convert", {"convert_fetch_ms", "convert_host_ms"})])
def test_traced_run_reads_the_programs_spans(tiny, workload,  # noqa: F811
                                             names):
    """A traced run on the CPU: the capture that ``cell.run`` writes holds
    the program's spans, and the readers find them there (the device
    metrics have no device operation to read on the CPU)."""
    spec, root = tiny
    r = run(spec, root, workload, 2 ** 33 + 29, 0.5, True,
            t_start=time.time(), strict=False)
    assert r["correct"], r["checks"]
    got = {k: v["value"] for k, v in r["metrics"].items() if k in names}
    assert set(got) == names and all(v > 0 for v in got.values()), got


# A 0.3 s traced window of ``jsc5l.serve.trigger``, recorded on one TPU v5
# lite by ``bench/run.py --workload jsc5l.serve.trigger --seconds 0.3
# --trace 1`` (seed 3100013002).  The capture is cut to what the readers
# use: the TPU plane's "XLA Ops" line and the host threads' ``bench.*``,
# ``serve.*`` and ``convert.*`` events, with the metadata they reference
# less the ops' source locations.  That run reported
# serve_queue_wait_p50_ms 1.993, serve_batch_p50_ms 2.40812 and
# idle_share.serve_p50 81.40801459895891.
CHIP = BENCH.parent / "tests" / "bench" / "data" / \
    "jsc5l.serve.trigger.xplane.pb"


@pytest.fixture(scope="module")
def chip():
    from benchkit import trace as T
    return SP.nest(SP.extract(CHIP)), T.extract(CHIP)


def test_chip_trace_nests_as_the_program_lists(chip):
    from repro.runtime import spans as S
    spans, _ = chip
    assert {s.name for s in spans} == {n for n in S.PARENT
                                       if n.startswith("serve.")}
    for s in spans:
        assert (s.parent.name if s.parent else None) == S.PARENT[s.name]
        assert SP.self_ns(s) == s.dur - sum(c.dur for c in s.children)
        assert SP.self_ns(s) >= 0


def test_chip_trace_waits_parse(chip):
    spans, _ = chip
    batches = SP.named(spans, "serve.batch")
    assert batches
    for b in batches:
        waits = SP.waits_us(b)
        assert len(waits) == b.args["requests"] and min(waits) >= 0
        assert b.args["samples"] <= b.args["padded"]


def test_chip_trace_shares_the_device_clock(chip):
    """Each bucket-padded call's span holds the end of exactly one
    cascade kernel op: host spans and device ops are on one clock (to
    within the ~0.2 ms by which the device's times lead the host's)."""
    spans, rec = chip
    cascade = [e for e in rec["device"] if e[0].startswith("lut_cascade")]
    assert cascade and all(e[1] == "tpu_custom_call" for e in cascade)
    for ch in SP.named(spans, "serve.chunk"):
        ends = [e for e in cascade if ch.start <= e[2] + e[3] <= ch.end]
        assert len(ends) == 1, (ch.start, ch.end)


def test_chip_trace_reads_as_the_chip_run_reported(chip, monkeypatch):
    from benchkit import trace as T
    spans, rec = chip
    monkeypatch.setattr(SP, "of", lambda ctx: spans)
    ctx = SimpleNamespace(trace=rec, window=SimpleNamespace(counters={}))
    assert _metric("serve_queue_wait_p50_ms").read(ctx) == 1.993
    assert _metric("serve_batch_p50_ms").read(ctx) == 2.40812
    assert 100 * T.idle_share(rec) == pytest.approx(81.40801459895891)
