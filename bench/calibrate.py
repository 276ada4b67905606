"""Readings that the limits of ``correct`` are set from (not part of a
benchmark run).  Run on the chip, at the cell's own size, in one process:

    python bench/calibrate.py --workload hdr5l.convert --seeds 1,2,3 \
        --control-seeds 4,5,6 [--seconds 2]

For every seed of ``--seeds`` it sets the cell up as a run does, drives
the timed path (serving cells: a short window at the cell's own load) and
prints the numbers the check compares: the program's readings, whose
largest is a limit's lower reading.  For every seed of
``--control-seeds`` it also prints the control's readings: the reference
put in the program's place and computed one precision below the stated
one (hidden functions at the three-pass ``high`` product and with
bfloat16 operands, instead of float32; serving inputs rounded to bfloat16
before the input quantizer), and, for training, the planted fault
``half_batch`` (the loss over half of each batch).  Their smallest
reading is a limit's upper reading.  One JSON line per seed, then a
summary line.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


CONTROLS = ("high", "bfloat16")


def readings(drv, kind: str, control: bool) -> dict:
    out = {}
    if kind in ("serve_open", "serve_closed"):
        out["program"] = {c.name: c.value for c in drv.check()}
        if control:
            sv, reqs = drv.serving, drv.reqs
            valid = reqs.answers()
            out["control"] = {"gap": sv.gaps(reqs, valid,
                                             sv.picks(reqs, valid),
                                             lowp_inputs=True)}
    elif kind == "train_epochs":
        rsteps, repoch = drv.reference_steps(), drv.reference_epoch()

        def numbers(steps, epoch):
            p, s, o, loss = epoch
            return {**drv.compare_steps(steps, rsteps),
                    **drv.compare((p, s, o["m"], loss), repoch)}
        out["program"] = {**drv.compare_steps(drv.first_steps, rsteps),
                          **drv.compare(drv.first, repoch)}
        if control:
            runs = [(f"control_{p}", {"precision": p}) for p in CONTROLS]
            runs.append(("fault_half_batch", {"fault": "half_batch"}))
            for label, kw in runs:
                out[label] = numbers(drv.reference_steps(**kw),
                                     drv.reference_epoch(**kw))
    elif kind == "convert_tables":
        from benchkit.cell import reference
        ref = drv.reference_tables()
        out["program"] = drv.compare(drv.last, ref)
        if control:
            pack = reference(drv.cell.conf).pack_words
            for p in CONTROLS:
                low = drv.reference_tables(p)
                got = (low, [pack(t, drv.geom.beta) for t in low])
                out[f"control_{p}"] = drv.compare(got, ref)
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=2.0)
    args = ap.parse_args()
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT / "bench"))
    from benchkit.cell import BENCH, device_info, load_module, resolve
    from repro.launch.compile_cache import setup_compile_cache

    setup_compile_cache()
    with open(ROOT / "BENCHMARK.json") as f:
        spec = json.load(f)
    cell = resolve(spec, ROOT, args.workload)
    device_info(True, cell.workload["chips"])
    kind = cell.traffic["driver"]
    mod = load_module(BENCH / "drivers" / f"{kind}.py")
    seeds = [int(s) for s in args.seeds.split(",") if s]
    controls = [int(s) for s in args.control_seeds.split(",") if s]
    lows, highs = {}, {}
    for seed in seeds + [s for s in controls if s not in seeds]:
        drv = mod.Driver(cell, seed, strict=True)
        drv.setup()
        if kind.startswith("serve"):
            drv.window(args.seconds, traced=False)
        drv.release()
        r = readings(drv, kind, seed in controls)
        print(json.dumps({"seed": seed, **r}), flush=True)
        if seed in seeds:
            for k, v in r["program"].items():
                lows[k] = max(lows.get(k, 0.0), v)
        for label, vals in r.items():
            if label != "program":
                for k, v in vals.items():
                    key = f"{label}.{k}"
                    highs[key] = min(highs.get(key, float("inf")), v)
    print(json.dumps({"summary": args.workload, "lower": lows,
                      "upper": highs}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
