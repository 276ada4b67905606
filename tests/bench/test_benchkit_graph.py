"""A LUT-graph configuration, added by files alone: a tiny graph whose
configuration, limits and cells live only in a test directory resolves,
runs on the CPU with the chip look skipped, passes its check and fails it
when an answer is altered.  Its counts equal hand-worked numbers, and the
harness's reference walks it as the program's own oracle does."""
import ast
import dataclasses
import hashlib
import json
import os
import shutil
import subprocess
import sys
import time

import jax.numpy as jnp
import numpy as np
import pytest

from benchkit.cell import BENCH, BenchError, counts, load_module, \
    reference, resolve, run
from benchkit.model import (Geometry, chain_config, connectivity,
                            program_config, reference_model, serving_model)

ROOT = BENCH.parent
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())

# An arity-2 adder node over the inputs; a node that reads two sources
# (the adder's 3-bit sums and the 3-bit inputs); an arity-1 classifier.
TINY_GRAPH = {
    "name": "tiny-graph", "in_features": 16, "num_classes": 5, "beta": 2,
    "beta_in": 3,
    "nodes": [
        {"name": "a0", "width": 8, "fan_in": 2, "inputs": ["input"],
         "arity": 2},
        {"name": "m1", "width": 6, "fan_in": 2, "inputs": ["a0", "input"],
         "arity": 1},
        {"name": "cls", "width": 5, "fan_in": 3, "inputs": ["m1"],
         "arity": 1}],
    "kind": "subnet", "depth": 2, "width": 4, "skip": 2, "degree": 2,
    "bn_momentum": 0.1, "family": "lutgraph", "reduced": [],
    "reference": "neuralut", "inputs": "jsc_synthetic", "model_seed": 0,
    "serve_input_scale": 0.625}
CELLS = {"tinygraph.serve.bulk": ("bulk", "hdr5l.serve.bulk"),
         "tinygraph.serve.trigger": ("trigger", "jsc5l.serve.trigger")}


def bench_digest() -> str:
    h = hashlib.sha256()
    for p in sorted(BENCH.rglob("*")):
        if p.is_file() and "__pycache__" not in p.parts:
            h.update(str(p.relative_to(BENCH)).encode())
            h.update(p.read_bytes())
    return h.hexdigest()


@pytest.fixture(scope="module")
def graph(tmp_path_factory):
    """BENCHMARK.json with the graph's configuration and two cells added,
    and a benchmark directory of their files: a copy of the traffic,
    limits, metric readers and drivers, with the new cells' limits."""
    root = tmp_path_factory.mktemp("graph")
    bench = root / "bench"
    for d in ("traffic", "limits", "metrics", "drivers"):
        shutil.copytree(BENCH / d, bench / d)
    conf_path = root / "tiny-graph.json"
    conf_path.write_text(json.dumps(TINY_GRAPH))
    spec = json.loads(json.dumps(SPEC))
    spec["configs"].append({"name": "tiny-graph", "source": "a test",
                            "file": str(conf_path), "reduced": [],
                            "why": "a tiny LUT graph"})
    for name, (traffic, like) in CELLS.items():
        (bench / "limits" / f"{name}.json").write_text(
            '{"gap": 0, "unanswered": 0}')
        spec["workloads"].append({"name": name, "config": "tiny-graph",
                                  "traffic": traffic, "chips": 1,
                                  "why": f"{traffic} traffic on a graph"})
        for m in spec["end_to_end"] + spec["per_layer"]:
            if like in m.get("workloads", []):
                m["workloads"].append(name)
    return spec, root, bench


def _graph_run(graph, workload, fault, trace):
    spec, root, bench = graph
    before = bench_digest()
    r = run(spec, root, workload, 2 ** 33 + 43, 0.5, trace,
            t_start=time.time(), strict=False, fault=fault, bench=bench)
    assert bench_digest() == before
    return r


CASES = [(w, f, f == "") for w in CELLS for f in ("", "alter_answer")]


@pytest.mark.parametrize("workload,fault,correct", CASES,
                         ids=[f"{w}-{f or 'sound'}" for w, f, _ in CASES])
def test_a_graph_cell_is_added_by_files_alone(graph, workload, fault,
                                              correct):
    r = _graph_run(graph, workload, fault, False)
    assert r["correct"] is correct, r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    spec = graph[0]
    names = {m["name"] for m in spec["end_to_end"]
             if workload in m.get("workloads", [workload])}
    assert set(r["metrics"]) == names
    assert all(v["value"] > 0 for v in r["metrics"].values())


def test_a_traced_graph_run_builds_its_context(graph):
    """The traced run reads the graph's per-layer metrics: ``serve_mfu``
    from the graph's counts (the CPU run's peaks are 1 op/s)."""
    r = _graph_run(graph, "tinygraph.serve.bulk", "", True)
    assert r["correct"], r["checks"]
    assert r["metrics"]["serve_mfu"]["value"] > 0


def test_graph_counts_are_hand_worked():
    g = Geometry.from_conf(TINY_GRAPH)
    assert g.sources == (16, 24, 6) and g.in_bits == (3, 3, 2)
    assert [n.out_bits for n in g.nodes] == [3, 2, 2]
    # every branch's table: 2^(3*2) entries, 2^(2*3) for the classifier
    assert g.table_entries == 8 * 2 * 64 + 6 * 64 + 5 * 64
    c = counts("lut_cascade")
    # 2 ops per address field and lookup of every branch, and one per
    # adder input beyond the first
    assert c.ops_per_sample(g) == 2 * (8 * 2 * 3 + 6 * 3 + 5 * 4) + 8
    # 16 two-bit codes a word: 4 words a 64-entry row
    assert c.table_bytes(g) == 4 * 4 * (8 * 2 + 6 + 5)
    assert c.call_bytes(g, 10) == c.table_bytes(g) + 4 * 10 * (16 + 5)


def test_polylut_add_counts_are_hand_worked():
    """The registered PolyLUT-Add JSC-5L graph (arXiv:2406.04910)."""
    from repro.config import get_config
    cfg = get_config("polylut-add-jsc-5l")
    conf = {k: getattr(cfg, k) for k in ("name", "in_features", "beta",
                                         "beta_in", "depth", "width",
                                         "skip", "bn_momentum")}
    conf["nodes"] = [dict(name=n.name, width=n.width, fan_in=n.fan_in,
                          inputs=list(n.inputs), arity=n.arity)
                     for n in cfg.nodes]
    g = Geometry.from_conf(conf)
    assert g.table_entries == (64 * 2 * 2 ** 12 + 64 * 2 * 2 ** 15
                               + 32 * 2 * 2 ** 15 + 5 * 2 ** 15) == 6_979_584
    c = counts("lut_cascade")
    assert [c.packed_words(g, i) for i in range(4)] == [512, 4096, 4096,
                                                        4096]
    assert c.ops_per_sample(g) == 2 * 4 * (128 + 128 + 64 + 5) + 160
    assert program_config(dict(conf, num_classes=5, arch=cfg.name)) == cfg


def _program_bundle(geom, served):
    tables = geom.per_node(served["tables"])
    statics = [{"conns": c} for c in geom.per_node(served["conns"])]
    return tables, statics


def _codes(geom, n=512):
    return np.random.default_rng(3).integers(
        0, 2 ** geom.input_bits, (n, geom.in_features)).astype(np.int32)


def test_reference_walks_the_graph_as_the_program_oracle():
    from repro.core.lut_infer import graph_lut_forward
    geom = Geometry.from_conf(TINY_GRAPH)
    served = serving_model(geom, TINY_GRAPH)
    model = reference_model(geom, served)
    codes = _codes(geom)
    got = reference(TINY_GRAPH).lut_outputs(codes, model)
    want = graph_lut_forward(program_config(TINY_GRAPH),
                             *_program_bundle(geom, served),
                             jnp.asarray(codes))
    np.testing.assert_array_equal(got, np.asarray(want))
    assert got.shape == (len(codes), 5) and got.max() < 4


def _parent_chain_forward(codes, tables, conns, in_bits):
    """The reference's chain lookup before it walked graphs."""
    c = codes
    for tbl, conn, bits in zip(tables, conns, in_bits):
        f = conn.shape[1]
        g = c[:, conn]
        addr = jnp.zeros(g.shape[:2], jnp.int32)
        for j in range(f):
            addr = addr * (1 << bits) + g[..., j]
        c = jnp.take_along_axis(tbl.T, addr, axis=0).astype(jnp.int32)
    return c


def test_reference_reads_a_chain_as_the_degenerate_graph():
    from repro.core.lut_infer import graph_lut_forward, lut_forward
    conf = json.loads((BENCH / "configs" / "neuralut-jsc-5l.json")
                      .read_text())
    conf.update(layer_widths=[32, 16, 5], beta=3, beta_in=4, arch=None)
    geom = Geometry.from_conf(conf)
    served = serving_model(geom, conf)
    model = reference_model(geom, served)
    codes = _codes(geom)
    got = reference(conf).lut_outputs(codes, model)
    parent = _parent_chain_forward(jnp.asarray(codes), model["tables"],
                                   model["conns"], geom.in_bits)
    np.testing.assert_array_equal(got, np.asarray(parent))
    cfg = program_config(conf)
    statics = [{"conn": c} for c in served["conns"]]
    np.testing.assert_array_equal(got, np.asarray(lut_forward(
        cfg, served["tables"], statics, jnp.asarray(codes))))
    np.testing.assert_array_equal(got, np.asarray(graph_lut_forward(
        cfg.graph(), served["tables"], statics, jnp.asarray(codes))))


def test_reference_imports_nothing_of_the_program():
    tree = ast.parse((BENCH / "reference" / "neuralut.py").read_text())
    names = [a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
             for a in n.names]
    names += [n.module for n in ast.walk(tree)
              if isinstance(n, ast.ImportFrom)]
    assert names and not any(m.split(".")[0] == "repro" for m in names)


@pytest.mark.parametrize("driver", ["train_epochs", "convert_tables"])
def test_graph_has_no_training_or_conversion_driver_yet(graph, driver):
    spec, root, bench = graph
    cell = resolve(spec, root, "tinygraph.serve.bulk", bench)
    cell = dataclasses.replace(cell, traffic={"driver": driver})
    mod = load_module(BENCH / "drivers" / f"{driver}.py")
    with pytest.raises(BenchError, match="no .* driver yet"):
        mod.Driver(cell, 1, strict=False)


def _chain_as_nodes(conf):
    """A chain file restated as the ``nodes`` of its degenerate graph."""
    geom = Geometry.from_conf(conf)
    out = {k: v for k, v in conf.items()
           if k not in ("layer_widths", "fan_in", "fan_in_0")}
    out["nodes"] = [{"name": f"L{i}", "width": o, "fan_in": f,
                     "inputs": ["input" if i == 0 else f"L{i - 1}"]}
                    for i, (o, f) in enumerate(zip(geom.widths,
                                                   geom.fan_ins))]
    return out


@pytest.mark.parametrize("driver", ["train_epochs", "convert_tables"])
def test_a_chain_stated_as_nodes_is_a_chain(graph, driver):
    """Whether a file is a chain is read from its geometry, not from its
    keys: a chain stated as ``nodes`` gives the chain's arrays and
    program config, and the training and conversion drivers take it."""
    conf = json.loads((BENCH / "configs" / "neuralut-jsc-5l.json")
                      .read_text())
    conf.update(arch=None)
    nodes = _chain_as_nodes(conf)
    geom, gn = Geometry.from_conf(conf), Geometry.from_conf(nodes)
    assert gn == geom and gn.is_chain
    assert not Geometry.from_conf(TINY_GRAPH).is_chain
    for a, b in zip(connectivity(gn, 0), connectivity(geom, 0)):
        np.testing.assert_array_equal(a, b)
    assert chain_config(nodes) == chain_config(conf) == program_config(conf)
    spec, root, bench = graph
    cell = resolve(spec, root, "tinygraph.serve.bulk", bench)
    cell = dataclasses.replace(cell, conf=nodes, traffic={"driver": driver})
    mod = load_module(BENCH / "drivers" / f"{driver}.py")
    assert mod.Driver(cell, 1, strict=False).geom.is_chain


def test_the_suite_takes_a_graph_configuration_added_by_files(tmp_path):
    """A copy of the benchmark with a graph configuration and a cell of it
    added as a later PR adds them (a file under ``bench/configs``, a limits
    file, entries in BENCHMARK.json): the benchmark's tests collect, and
    those that walk every configuration or cell pass."""
    for d in ("bench", "tests/bench"):
        shutil.copytree(ROOT / d, tmp_path / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    conf = dict(TINY_GRAPH,
                table_entries=Geometry.from_conf(TINY_GRAPH).table_entries)
    (tmp_path / "bench" / "configs" / "tiny-graph.json").write_text(
        json.dumps(conf))
    (tmp_path / "bench" / "limits" / "tinygraph.serve.bulk.json"
     ).write_text('{"gap": 0, "unanswered": 0}')
    spec = json.loads(json.dumps(SPEC))
    spec["configs"].append({"name": "tiny-graph", "source": "a test",
                            "file": "bench/configs/tiny-graph.json",
                            "reduced": [], "why": "a tiny LUT graph"})
    spec["workloads"].append({"name": "tinygraph.serve.bulk",
                              "config": "tiny-graph", "traffic": "bulk",
                              "chips": 1, "why": "bulk traffic on a graph"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "hdr5l.serve.bulk" in m.get("workloads", []):
            m["workloads"].append("tinygraph.serve.bulk")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    env = dict(os.environ, PYTHONPATH=str(ROOT / "src"),
               PYTHONDONTWRITEBYTECODE="1")

    def pytest_(*args):
        p = subprocess.run(
            [sys.executable, "-m", "pytest", "-p", "no:cacheprovider",
             "-p", "no:xdist", "-p", "no:randomly", *args],
            cwd=tmp_path, env=env, capture_output=True, text=True,
            timeout=600)
        assert p.returncode == 0, p.stdout[-4000:] + p.stderr[-2000:]
        return p.stdout

    listed = pytest_("--collect-only", "-q", "tests/bench")
    assert "[tiny-graph]" in listed and "error" not in listed.lower()
    out = pytest_(
        "-v", "tests/bench/test_benchkit_spec.py",
        "tests/bench/test_benchkit_chain.py::"
        "test_every_recorded_configuration_file_is_there",
        "tests/bench/test_benchkit_runs.py::test_correct_decides_the_run"
        "[hdr5l.serve.bulk-sound]")
    for test in ("test_config_file_is_the_registered_architecture"
                 "[tiny-graph]",
                 "test_every_cell_resolves_by_name[tinygraph.serve.bulk]"):
        assert f"{test} PASSED" in out
