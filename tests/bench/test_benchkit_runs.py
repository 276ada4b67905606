"""Whole runs of each cell on the CPU at a tiny size, with the chip look
skipped: a sound run comes out correct, and each fault that the cell can
have, planted under the timed path, makes ``correct`` come out false."""
import json
import os
import shutil
import subprocess
import sys
import time

import pytest

from benchkit.cell import BENCH, run

ROOT = BENCH.parent
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())

# Tiny geometries of the two configurations (widths cut for the CPU).
TINY = {
    "neuralut-jsc-5l": dict(layer_widths=[32, 16, 5], beta=3, beta_in=4),
    "neuralut-hdr-5l": dict(layer_widths=[32, 16, 10], fan_in=4),
}


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    root = tmp_path_factory.mktemp("tiny")
    spec = json.loads(json.dumps(SPEC))
    for c in spec["configs"]:
        conf = json.loads((ROOT / c["file"]).read_text())
        conf.update(TINY[c["name"]], arch=None)
        path = root / f"{c['name']}.json"
        path.write_text(json.dumps(conf))
        c["file"] = str(path)
    return spec, root


CASES = [
    ("jsc5l.serve.trigger", "", True),
    ("jsc5l.serve.trigger", "alter_answer", False),
    ("hdr5l.serve.bulk", "", True),
    ("hdr5l.serve.bulk", "alter_answer", False),
    ("jsc5l.train", "", True),
    ("jsc5l.train", "unchanged", False),
    ("jsc5l.train", "half_batch", False),
    ("hdr5l.convert", "", True),
    ("hdr5l.convert", "alter_answer", False),
]


@pytest.mark.parametrize("workload,fault,correct", CASES,
                         ids=[f"{w}-{f or 'sound'}" for w, f, _ in CASES])
def test_correct_decides_the_run(tiny, workload, fault, correct):
    spec, root = tiny
    r = run(spec, root, workload, 2 ** 33 + 17, 0.5, False,
            t_start=time.time(), strict=False, fault=fault)
    assert r["correct"] is correct, r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    names = {m["name"] for m in SPEC["end_to_end"]
             if workload in m.get("workloads", [workload])}
    assert set(r["metrics"]) == names
    assert all(v["value"] > 0 for v in r["metrics"].values())
    assert list(r)[-1] == "checks"


def _bench(cwd, env):
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "jsc5l.serve.trigger",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=240)


def test_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = _bench(ROOT, env)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not p.stdout.strip()


def test_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    p = _bench(tmp_path, env)
    assert p.returncode != 0 and not p.stdout.strip()
