"""The controls of ``correct``: the reference put in the program's place
and computed one precision below the configuration's must come out as not
correct.  On the CPU, at the cells' widths and batch sizes; PERF.md gives
the readings that each limit was set from."""
import dataclasses
import json

import jax.numpy as jnp
import numpy as np
import pytest

from benchkit import data
from benchkit.cell import BENCH, reference
from benchkit.model import (Geometry, reference_model, seed_int,
                            serving_model)

ROOT = BENCH.parent
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())


def cell(name):
    w = next(w for w in SPEC["workloads"] if w["name"] == name)
    c = next(c for c in SPEC["configs"] if c["name"] == w["config"])
    conf = json.loads((ROOT / c["file"]).read_text())
    limits = json.loads((BENCH / "limits" / f"{name}.json").read_text())
    return conf, limits


@pytest.mark.parametrize("name", ["jsc5l.serve.trigger", "hdr5l.serve.bulk"])
@pytest.mark.parametrize("seed", [3, 2 ** 31 + 5, 2 ** 33 + 9])
def test_serving_control_is_not_correct(name, seed):
    """Inputs rounded to bfloat16 before the input quantizer: the classes
    the control picks lie below the reference's best on some samples."""
    conf, limits = cell(name)
    geom = Geometry.from_conf(conf)
    model = reference_model(geom, serving_model(geom, conf))
    x, _ = getattr(data, conf["inputs"])(2048, seed=seed_int(seed, 5))
    ref = reference(conf)
    served = np.zeros(len(x), np.int32)          # unused by the control
    gap = float(np.max(ref.served_gaps(x, served, model, lowp_inputs=True)))
    assert gap > limits["gap"]
    codes, _ = ref.served_input_codes(x, model["in_log_s"], geom.in_bits[0])
    exact = np.argmax(ref.lut_outputs(codes, model) * np.exp(
        np.asarray(model["out_log_s"], np.float64)) - 2 ** (geom.beta - 1)
        * np.exp(np.asarray(model["out_log_s"], np.float64)), -1)
    assert float(np.max(ref.served_gaps(x, exact, model))) == 0.0


@pytest.mark.parametrize("name", ["jsc5l.serve.trigger", "hdr5l.serve.bulk"])
def test_serving_check_accepts_faithful_roundings_only(name):
    """An input that lies within float32's reach of a rounding boundary
    may take either code: the answer of either is correct.  A code moved
    on any other input is a wrong answer."""
    conf, _ = cell(name)
    geom = Geometry.from_conf(conf)
    model = reference_model(geom, serving_model(geom, conf))
    ref = reference(conf)
    x, _ = getattr(data, conf["inputs"])(512, seed=7)
    s = np.exp(np.asarray(model["in_log_s"], np.float64))
    out_s = np.exp(np.asarray(model["out_log_s"], np.float64))
    bits, half = geom.in_bits[0], 2 ** (geom.beta - 1)

    def classes(codes):
        return np.argmax((ref.lut_outputs(codes, model) - half) * out_s, -1)
    codes, _ = ref.served_input_codes(x, model["in_log_s"], bits)
    base = classes(codes)
    # on every sample, move the most varied feature onto a boundary
    # between two codes in range: 1e-7 of its size above k + 1/2
    j = int(np.argmax(codes.std(axis=0)))
    k = np.clip(np.floor(x[:, j] / s[j]), -2 ** (bits - 1), 2 ** (bits - 1) - 2)
    xb = x.copy()
    xb[:, j] = ((k + 0.5) * (1 + 1e-7 * np.sign(k + 0.5)) * s[j]).astype(
        np.float32)
    near, alt = ref.served_input_codes(xb, model["in_log_s"], bits)
    assert np.all(near[:, j] != alt[:, j])
    assert np.max(ref.served_gaps(xb, classes(near), model)) == 0.0
    assert np.max(ref.served_gaps(xb, classes(alt), model)) == 0.0
    # the same move of a code far from its boundary is caught
    moved = codes.copy()
    moved[:, j] = np.where(codes[:, j] > 0, codes[:, j] - 1, 1)
    wrong = classes(moved)
    assert np.any(wrong != base)
    assert np.max(ref.served_gaps(x, wrong, model)) > 0.0


def test_conversion_control_is_not_correct():
    """Hidden functions with bfloat16 operands: more table entries differ
    from the float32 reference than the limit allows."""
    from benchkit.cell import resolve, load_module
    conf, limits = cell("hdr5l.convert")
    c = resolve(SPEC, ROOT, "hdr5l.convert")
    drv = load_module(BENCH / "drivers" / "convert_tables.py").Driver(
        c, 2 ** 31 + 11, strict=False)
    drv.setup()
    drv.release()
    ref = drv.reference_tables()
    pack = reference(conf).pack_words
    low = drv.reference_tables("bfloat16")
    got = drv.compare((low, [pack(t, drv.geom.beta) for t in low]), ref)
    assert got["table_flips"] > limits["table_flips"]
    sound = drv.compare(drv.last, ref)
    assert all(sound[k] <= v for k, v in limits.items())
    assert np.isfinite(list(sound.values())).all()


@pytest.mark.parametrize("fault,correct", [
    ("", True), ("control_bfloat16", False)])
def test_training_control_is_not_correct(fault, correct):
    """The first steps, at full width and batch, through the training
    driver's own check: the reference with bfloat16 operands in its hidden
    functions, in the program's place, fails the first gradient's number
    (median leaf); the program passes every number.  The epoch is cut to
    5 steps to fit a test run."""
    from benchkit.cell import load_module, resolve
    c = resolve(SPEC, ROOT, "jsc5l.train")
    c = dataclasses.replace(c, traffic=dict(c.traffic, train_rows=1280))
    drv = load_module(BENCH / "drivers" / "train_epochs.py").Driver(
        c, 2 ** 32 + 21, strict=False, fault=fault)
    drv.setup()
    drv.release()
    checks = {ch.name: ch for ch in drv.check()}
    values = {n: ch.value for n, ch in checks.items()}
    if correct:
        assert all(ch.ok for ch in checks.values()), values
    else:
        assert not checks["first_grad_gap.median"].ok, values
