"""Kernel counts and order statistics against hand-worked numbers."""
import json
import math

import pytest

from benchkit.cell import BENCH, counts
from benchkit.model import Geometry
from benchkit.stats import percentile, quartile_spread


def geom(name):
    return Geometry.from_conf(json.loads(
        (BENCH / "configs" / f"{name}.json").read_text()))


def test_table_entries():
    # jsc-5l: 128 * 2^14 + (128 + 128 + 64 + 5) * 2^12
    assert geom("neuralut-jsc-5l").table_entries == 3_428_352
    # hdr-5l: (256 + 100 + 100 + 100 + 10) * 2^12
    assert geom("neuralut-hdr-5l").table_entries == 2_318_336


@pytest.mark.parametrize("f", [2, 3, 6])
def test_subnet_macs_per_neuron(f):
    g = Geometry.from_conf(dict(
        in_features=16, layer_widths=[4], fan_in=f, beta=2, depth=4,
        width=16, skip=2, bn_momentum=0.1))
    assert counts("neuralut_mlp").macs_per_neuron(g, 0) == 32 * f + 544


def test_forward_and_training_flops():
    mlp, grad = counts("neuralut_mlp"), counts("neuralut_grad")
    jsc = geom("neuralut-jsc-5l")
    # 128 * (32*2 + 544) + (128 + 128 + 64 + 5) * (32*3 + 544)
    assert mlp.forward_macs_per_sample(jsc) == 285_824
    assert grad.train_flops_per_sample(jsc) == 285_824 * 2 * 3
    hdr = geom("neuralut-hdr-5l")
    assert mlp.conversion_flops(hdr) == 2_318_336 * (32 * 6 + 544) * 2


def test_cascade_counts():
    c = counts("lut_cascade")
    jsc = geom("neuralut-jsc-5l")
    # 2 ops per multiply-add: F address fields + 1 lookup per neuron
    assert c.ops_per_sample(jsc) == 2 * (128 * 3 + 325 * 4)
    # packed words: 8 codes of 4 bits per int32 word
    assert c.table_bytes(jsc) == 4 * (128 * 2048 + 325 * 512)
    hdr = geom("neuralut-hdr-5l")
    assert c.table_bytes(hdr) == 4 * 566 * 256   # 16 2-bit codes a word
    assert c.call_bytes(hdr, 256) == c.table_bytes(hdr) + 4 * 256 * 794


def test_nearest_rank_percentile():
    v = sorted([5.0, 1.0, 4.0, 2.0, 3.0])
    assert percentile(v, 50) == 3.0
    assert percentile(v, 99) == 5.0 and percentile(v, 100) == 5.0
    assert percentile(v, 20) == 1.0 and percentile(v, 21) == 2.0
    assert percentile(list(range(1, 101)), 99) == 99
    with pytest.raises(ValueError):
        percentile([], 50)


def test_quartile_spread():
    assert math.isclose(quartile_spread([1, 2, 3, 4, 5]),
                        (4.5 - 1.5) / 3)
