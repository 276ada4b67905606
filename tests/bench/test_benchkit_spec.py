"""BENCHMARK.json: every cell resolves its files by name, and the file
keeps to the benchmark's format."""
import json
import re
import shutil

import pytest

from benchkit.cell import BENCH, BenchError, resolve
from benchkit.model import Geometry

ROOT = BENCH.parent
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.mark.parametrize("workload", [w["name"] for w in SPEC["workloads"]])
def test_every_cell_resolves_by_name(workload):
    cell = resolve(SPEC, ROOT, workload)
    assert (BENCH / "drivers" / f"{cell.traffic['driver']}.py").is_file()
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
    assert len(cell.end_to_end) >= 2 and cell.per_layer
    for m in cell.per_layer:
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file()
        # a per-layer metric moves an end-to-end metric its cells report
        assert m["moves"] in {e["name"] for e in cell.end_to_end}
    assert set(cell.limits) and all(v >= 0 for v in cell.limits.values())


def test_spec_format():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    for p in SPEC["paths"]:
        assert (ROOT / p).is_dir() and not p.startswith("/")
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in SPEC[k]]
    assert all(NAME.match(n) for n in names)
    assert len(set(names)) == len(names)
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert (ROOT / c["file"]).is_file()
        assert all(NAME.match(k) for k in c["reduced"])
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and NAME.match(w["traffic"])
    one_line = [c[k] for c in SPEC["configs"] for k in ("why", "source")]
    one_line += [w["why"] for w in SPEC["workloads"]]
    one_line += [m["layer"] for m in SPEC["per_layer"]] + SPEC["command"]
    assert all(1 <= len(t) <= 200 and not re.search(r"[\n\t]", t)
               for t in one_line)
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert set(m) - {"workloads"} <= {"name", "unit", "better", "bound",
                                          "source", "layer", "moves"}
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    for m in SPEC["per_layer"]:
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"


@pytest.mark.parametrize("config", SPEC["configs"], ids=lambda c: c["name"])
def test_config_file_is_the_registered_architecture(config):
    from benchkit.model import program_config
    conf = json.loads((ROOT / config["file"]).read_text())
    cfg = program_config(conf)       # raises when a stated size differs
    assert cfg.name == config["name"] and conf["reduced"] == []
    assert Geometry.from_conf(conf).table_entries == conf["table_entries"]


def test_a_new_cell_is_found_from_new_files_only(tmp_path):
    """A later PR adds a cell, a traffic mix and a metric by adding files
    and entries: the harness finds each by the name BENCHMARK.json gives."""
    bench = tmp_path / "bench"
    shutil.copytree(BENCH / "traffic", bench / "traffic")
    shutil.copytree(BENCH / "limits", bench / "limits")
    shutil.copytree(BENCH / "metrics", bench / "metrics")
    shutil.copytree(BENCH / "drivers", bench / "drivers")
    (bench / "traffic" / "trigger.slow.json").write_text(json.dumps(
        dict(json.loads((BENCH / "traffic" / "trigger.json").read_text()),
             rate_per_s=100)))
    (bench / "limits" / "jsc5l.serve.slow.json").write_text(
        '{"gap": 0, "unanswered": 0}')
    (bench / "metrics" / "queue_wait_ms.py").write_text(
        "def read(ctx):\n    return None\n")
    spec = json.loads(json.dumps(SPEC))
    spec["workloads"].append({"name": "jsc5l.serve.slow",
                              "config": "neuralut-jsc-5l",
                              "traffic": "trigger.slow", "chips": 1,
                              "why": "a slower open-loop rate"})
    spec["end_to_end"][0]["workloads"].append("jsc5l.serve.slow")
    spec["per_layer"].append({"name": "queue_wait_ms", "unit": "ms",
                              "better": "lower", "source": "host_clock",
                              "layer": "serving front end",
                              "moves": "serve_p50_ms",
                              "workloads": ["jsc5l.serve.slow"]})
    cell = resolve(spec, ROOT, "jsc5l.serve.slow", bench=bench)
    assert cell.traffic["rate_per_s"] == 100
    assert [m["name"] for m in cell.per_layer] == ["queue_wait_ms"]
    assert {m["name"] for m in cell.end_to_end} == {"serve_p50_ms",
                                                    "setup_s"}
    (bench / "metrics" / "queue_wait_ms.py").unlink()
    with pytest.raises(BenchError):
        resolve(spec, ROOT, "jsc5l.serve.slow", bench=bench)
