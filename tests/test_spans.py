"""The program's spans (``repro.runtime.spans``): recorded by the serving
engine and the converter while the JAX profiler records, nested as the
list says, with arguments that agree with the engine's own counters."""
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.core import model as M
from repro.core import truth_table as TT
from repro.core.nl_config import LUTGraphConfig, LUTNodeSpec
from repro.runtime import spans as S
from repro.serve import LUTServeEngine
from repro.serve import engine as E

from test_serve_engine import _tiny_bundle, _tiny_cfg


def _spans(log_dir: Path):
    """(name, parent name or None, args) of every program span in the
    capture: on one thread, the parent is the innermost enclosing span."""
    from jax.profiler import ProfileData
    path = sorted(log_dir.rglob("*.xplane.pb"))[-1]
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            evs = sorted(((e.start_ns, -e.end_ns, e.name, dict(e.stats))
                          for e in line.events
                          if e.name.startswith(("serve.", "convert."))),
                         key=lambda t: t[:2])
            stack = []
            for start, neg_end, name, args in evs:
                while stack and stack[-1][0] <= start:
                    stack.pop()
                out.append((name, stack[-1][1] if stack else None, args))
                stack.append((-neg_end, name))
    return out


def _serve(bundle, sizes, seed=0):
    rng = np.random.default_rng(seed)
    with LUTServeEngine(bundle, use_kernel=False) as eng:
        eng.warmup()
        futs = [eng.submit(rng.normal(0, 1, (n, bundle.cfg.in_features))
                           .astype(np.float32)) for n in sizes]
        for f in futs:
            f.result(timeout=60)
    return eng


SIZES = [1, 3, 300, 7, 64, 1, 520]


def test_engine_spans_nest_and_count_what_the_metrics_count(tmp_path):
    bundle, _ = _tiny_bundle()
    with jax.profiler.trace(str(tmp_path)):
        eng = _serve(bundle, SIZES)
    spans = _spans(tmp_path)
    names = {n for n, _, _ in spans}
    assert names == {n for n in S.PARENT if n.startswith("serve.")}
    for name, parent, _ in spans:
        assert parent == S.PARENT[name], (name, parent)

    batches = [a for n, _, a in spans if n == S.SERVE_BATCH]
    m = eng.metrics
    assert sum(a["requests"] for a in batches) == len(SIZES)
    assert sum(a["samples"] for a in batches) == m._real == sum(SIZES)
    assert sum(a["padded"] for a in batches) == m._padded
    assert sum(a["chunks"] for a in batches) == sum(
        1 for n, _, _ in spans if n == S.SERVE_CHUNK)
    assert all(a["rid"] == 0 for a in batches)
    for a in batches:
        waits = [int(w) for w in str(a["waits_us"]).split()]
        assert len(waits) == a["requests"] and min(waits) >= 0
    coalesced = [a for n, _, a in spans if n == S.SERVE_COALESCE]
    assert sum(a["requests"] for a in coalesced) == len(SIZES)
    assert {a["bucket"] for n, _, a in spans if n == S.SERVE_CHUNK} \
        <= set(eng.buckets)


class _Recorded:
    """Stands in for ``TraceAnnotation`` and keeps every argument that a
    span was given, at its start or later."""
    args = []

    def __init__(self, name, **kw):
        self.args.append(kw)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set_metadata(self, **kw):
        self.args.append(kw)


def test_waits_are_built_only_while_the_profiler_records(monkeypatch,
                                                         tmp_path):
    bundle, _ = _tiny_bundle()
    monkeypatch.setattr(E, "TraceAnnotation", _Recorded)
    monkeypatch.setattr(_Recorded, "args", [])
    _serve(bundle, [2, 5, 1])
    assert _Recorded.args, "the engine recorded no span"
    assert not any("waits_us" in kw for kw in _Recorded.args)
    with jax.profiler.trace(str(tmp_path)):
        _serve(bundle, [2, 5, 1])
    assert any("waits_us" in kw for kw in _Recorded.args)


def _converted_spans(cfg, tmp_path, seed=0):
    statics = M.model_static(cfg)
    params, state = M.model_init(cfg, jax.random.PRNGKey(seed))
    TT.convert_packed(cfg, params, state, statics)  # compiles
    with jax.profiler.trace(str(tmp_path)):
        converted = TT.convert_packed(cfg, params, state, statics)
    return _spans(tmp_path), converted


GRAPH = LUTGraphConfig(
    name="span-dag", in_features=6, num_classes=3, beta=2, kind="linear",
    nodes=(LUTNodeSpec(name="a", width=4, fan_in=2, inputs=("input",),
                       arity=2),
           LUTNodeSpec(name="c", width=3, fan_in=2, inputs=("a",))))


@pytest.mark.parametrize("cfg", [_tiny_cfg(), GRAPH],
                         ids=["chain", "graph"])
def test_converter_spans_nest_per_layer(cfg, tmp_path):
    spans, converted = _converted_spans(cfg, tmp_path)
    tables = converted[0]
    assert {n for n, _, _ in spans} == {
        n for n in S.PARENT if n.startswith("convert.")}
    for name, parent, _ in spans:
        assert parent == S.PARENT[name], (name, parent)
    layers = [a for n, _, a in spans if n == S.CONVERT_LAYER]
    assert [a["layer"] for a in layers] == list(range(cfg.num_layers))
    sizes = [sum(np.asarray(t).size for t in
                 (tb if isinstance(tb, list) else [tb])) for tb in tables]
    assert [a["entries"] for a in layers] == sizes
    # one fetch per conversion, opened after the last sweep, bringing
    # back every table and packed array the conversion returns
    fetches = [a for n, _, a in spans if n == S.CONVERT_FETCH]
    assert len(fetches) == 1 and spans[-1][0] == S.CONVERT_FETCH
    leaves = jax.tree_util.tree_leaves(converted)
    assert fetches[0]["arrays"] == len(leaves)
    assert fetches[0]["bytes"] == sum(a.nbytes for a in leaves)
    # each layer holds one sweep per branch and nothing else: no host
    # work between the layer's start and its dispatch, and no wait
    inner = []
    for name, parent, _ in spans:
        if name == S.CONVERT_LAYER:
            inner.append([])
        elif parent == S.CONVERT_LAYER:
            inner[-1].append(name)
    assert inner == [[S.CONVERT_SWEEP] *
                     (len(tb) if isinstance(tb, list) else 1)
                     for tb in tables]


def test_graph_converter_spans_name_the_branch(tmp_path):
    """A graph node's layer span carries its arity, and each of its
    sweeps the branch it converts, in branch order; the conversion's one
    fetch names no branch."""
    spans, _ = _converted_spans(GRAPH, tmp_path)
    layers = [a for n, _, a in spans if n == S.CONVERT_LAYER]
    assert [a["arity"] for a in layers] == [nd.arity for nd in GRAPH.nodes]
    assert [a["branch"] for n, _, a in spans if n == S.CONVERT_SWEEP] == [
        b for nd in GRAPH.nodes for b in range(nd.arity)]
    assert all("branch" not in a
               for n, _, a in spans if n == S.CONVERT_FETCH)


def test_recording_follows_the_profiler(tmp_path):
    assert not S.recording()
    with jax.profiler.trace(str(tmp_path)):
        assert S.recording()
    assert not S.recording()
