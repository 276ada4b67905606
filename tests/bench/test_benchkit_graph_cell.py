"""The ``polylut5l.serve.bulk`` cell, from its real configuration file:
the registered PolyLUT-Add JSC-XL-Add2 graph, cut to the registry's reduced
widths, runs through the whole harness on the CPU (the chip look
skipped); a sound run is correct and an altered answer is not.  At its
full widths, the bfloat16-input control fails the cell's limits."""
import json
import time

import numpy as np
import pytest

from benchkit import data
from benchkit.cell import BENCH, reference, run
from benchkit.model import (Geometry, program_config, reference_model,
                            seed_int, serving_model)

ROOT = BENCH.parent
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CELL = "polylut5l.serve.bulk"
ARCH = "polylut-add-jsc-xl"
# the keys that the reduced graph changes
REDUCED_KEYS = ("arch", "beta", "beta_in", "depth", "width", "nodes")


def _file(name):
    c = next(c for c in SPEC["configs"] if c["name"] == name)
    return json.loads((ROOT / c["file"]).read_text())


def _reduced(conf):
    """The configuration file with the registry's reduced graph put in:
    the same keys, the reduced sizes, no registered ``arch``."""
    from repro.config import get_config
    cfg = get_config(ARCH, reduced=True)
    return dict(conf, arch=None, beta=cfg.beta, beta_in=cfg.beta_in,
                depth=cfg.depth, width=cfg.width, skip=cfg.skip,
                nodes=[dict(name=n.name, width=n.width, fan_in=n.fan_in,
                            inputs=list(n.inputs), arity=n.arity)
                       for n in cfg.nodes])


@pytest.fixture(scope="module")
def reduced(tmp_path_factory):
    root = tmp_path_factory.mktemp("polylut")
    conf = _reduced(_file(ARCH))
    path = root / f"{ARCH}.json"
    path.write_text(json.dumps(conf))
    spec = json.loads(json.dumps(SPEC))
    for c in spec["configs"]:
        c["file"] = str(path if c["name"] == ARCH else ROOT / c["file"])
    return spec, root, conf


def test_the_reduced_file_is_the_reduced_graph(reduced):
    from repro.config import get_config
    conf = reduced[2]
    reg = get_config(ARCH, reduced=True)
    cfg = program_config(conf)
    assert cfg.nodes == reg.nodes and not Geometry.from_conf(conf).is_chain
    assert {k: v for k, v in _file(ARCH).items()
            if k not in REDUCED_KEYS} == \
        {k: v for k, v in conf.items() if k not in REDUCED_KEYS}


@pytest.mark.parametrize("fault,correct", [("", True),
                                           ("alter_answer", False)],
                         ids=["sound", "alter_answer"])
def test_correct_decides_the_graph_cell(reduced, fault, correct):
    spec, root, _ = reduced
    r = run(spec, root, CELL, 2 ** 33 + 61, 0.5, False,
            t_start=time.time(), strict=False, fault=fault)
    assert r["correct"] is correct, r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert set(r["metrics"]) == {"served_samples_per_s", "setup_s"}
    assert all(v["value"] > 0 for v in r["metrics"].values())


@pytest.mark.parametrize("seed", [3, 2 ** 31 + 5, 2 ** 33 + 9])
def test_graph_control_is_not_correct(seed):
    """At the published widths: inputs rounded to bfloat16 before the
    input quantizer pick classes below the reference's best, while the
    reference's own picks read a gap of 0."""
    conf = _file(ARCH)
    limits = json.loads((BENCH / "limits" / f"{CELL}.json").read_text())
    geom = Geometry.from_conf(conf)
    model = reference_model(geom, serving_model(geom, conf))
    x, _ = getattr(data, conf["inputs"])(2048, seed=seed_int(seed, 5))
    ref = reference(conf)
    unused = np.zeros(len(x), np.int32)
    assert float(np.max(ref.served_gaps(x, unused, model,
                                        lowp_inputs=True))) > limits["gap"]
    codes, _ = ref.served_input_codes(x, model["in_log_s"], geom.in_bits[0])
    out_s = np.exp(np.asarray(model["out_log_s"], np.float64))
    exact = np.argmax(ref.lut_outputs(codes, model) * out_s
                      - 2 ** (geom.beta - 1) * out_s, -1)
    assert float(np.max(ref.served_gaps(x, exact, model))) == 0.0
