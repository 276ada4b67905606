"""The ``jsc5l.serve.replicas4`` cell resolves to its own traffic (the
open-loop trigger stream behind replicated engines), and its driver, with
the engine cut to two replicas on two CPU devices, serves every request
from a replica whose operands live on its own device, with answers equal
to the reference's."""
import json
import os
import subprocess
import sys

from benchkit.cell import BENCH, resolve

ROOT = BENCH.parent
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CELL = "jsc5l.serve.replicas4"

# In a child process: the tests' own process sees one CPU device.
CHILD = r"""
import dataclasses, json, sys
from pathlib import Path
root = Path(sys.argv[1])
sys.path[:0] = [str(root / "bench"), str(root / "src")]
import jax
from benchkit.cell import BENCH, load_module, resolve
spec = json.loads((root / "BENCHMARK.json").read_text())
for c in spec["configs"]:
    c["file"] = str(root / c["file"])
cell = resolve(spec, root, sys.argv[2])
conf = dict(cell.conf, layer_widths=[32, 16, 5], beta=3, beta_in=4,
            arch=None)
traffic = dict(cell.traffic, engine=dict(cell.traffic["engine"], replicas=2))
cell = dataclasses.replace(cell, conf=conf, traffic=traffic)
drv = load_module(BENCH / "drivers" / f"{traffic['driver']}.py").Driver(
    cell, 2 ** 33 + 71, strict=False)
drv.setup()
eng = drv.serving.engine
placed = [sorted(d.id for d in s) for s in eng.replica_devices()]
win = drv.window(0.5, traced=False)
per = [m.report()["batches"] for m in eng.replica_metrics]
drv.release()
print(json.dumps({"devices": [d.id for d in jax.devices()], "placed": placed,
                  "batches": per, "attempted": win.attempted,
                  "failed": win.failed,
                  "checks": {c.name: [c.value, c.limit, c.ok]
                             for c in drv.check()}}))
"""


def test_the_cell_resolves_to_replicated_open_loop_traffic():
    cell = resolve(SPEC, ROOT, CELL)
    trigger = json.loads((BENCH / "traffic" / "trigger.json").read_text())
    assert cell.workload["chips"] == 4 == cell.traffic["engine"]["replicas"]
    assert cell.conf["name"] == "neuralut-jsc-5l"
    assert {k: v for k, v in cell.traffic.items()
            if k not in ("engine", "rate_per_s")} == \
        {k: v for k, v in trigger.items()
         if k not in ("engine", "rate_per_s")}
    assert cell.traffic["check_requests"] is None     # every request
    assert cell.limits["gap"] == 0 and cell.limits["unanswered"] == 0


def test_two_replicas_on_two_devices_answer_as_the_reference():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2")
    p = subprocess.run([sys.executable, "-c", CHILD, str(ROOT), CELL],
                       env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    r = json.loads(p.stdout.strip().splitlines()[-1])
    assert r["devices"] == [0, 1] and r["placed"] == [[0], [1]]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert all(b > 0 for b in r["batches"]), r["batches"]
    assert r["checks"]["gap"] == [0.0, 0, True]
    assert r["checks"]["unanswered"] == [0.0, 0, True]
