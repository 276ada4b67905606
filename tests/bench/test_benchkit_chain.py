"""The chain cells read as the degenerate LUT graph and did not move: for
each configuration file, at one fixed seed, the digests of every array
the harness makes (connectivity, serving tables and scales, the
reference's model, its served codes and gaps, training and conversion
parameters) and every count equal what the harness computed when it read
chains alone (recorded on that commit, before the graph geometry)."""
import hashlib
import json

import jax
import numpy as np
import pytest

from benchkit import data
from benchkit.cell import BENCH, counts, reference
from benchkit.model import (Geometry, connectivity, convert_params,
                            reference_model, serving_model, train_params)

SEED = 2 ** 33 + 41

RECORDED = {
    "neuralut-hdr-5l": {
        "reference_outputs": "125c4d519c03f9a59c1d089940e8edfe7be2d1f1e4ebd509088c642aba0f52c7",  # noqa: E501
        "reference_gaps": "9f1625102dce02b0d9675233ae292a761acd6dbbf1d20b90994526e905071f87",  # noqa: E501
        "connectivity": "5cb723e05410b02a693e0608bfbe285538ae56ce3197aa9534fe2f7d80fb7e74",  # noqa: E501
        "serving_tables": "e5813e5cb03be8ceea723a5a2a1cf21870981880719de68ce932df2cd738653b",  # noqa: E501
        "serving_scales": "83d46b47872b04c9aca057d82e03683f30bf3788ee34aa8d8f688c99ada23732",  # noqa: E501
        "reference_model": "9c82ea0d6963f85d6b7b097968b1f71d644b2ef1ce492ee3ac93e2ce82811d7d",  # noqa: E501
        "train_params": "59d592bd6f5a53c10930bc29fa290b36ca7108b51ad5466ff0ff22e31f8f148f",  # noqa: E501
        "convert_params": "fc982457b12f78e5e0044a9f551a6d9de3d6a57b552681d33710ea81be16d991",  # noqa: E501
        "ops_per_sample": 7924,
        "table_bytes": 579584,
        "call_bytes": 1392640,
        "table_entries": 2318336,
        "forward_macs_per_sample": 416576,
        "conversion_flops": 3412590592,
        "conversion_bytes": 66729136,
        "train_flops_per_sample": 2499456,
        "step_bytes": 17038864,
    },
    "neuralut-jsc-5l": {
        "reference_outputs": "e3d5b87f3c67d9b625a9137bd0315ef29944218449f10f73a25af78ee2aa0757",  # noqa: E501
        "reference_gaps": "5690cc9bc49bfd6850bc9400252fb9708edb60c37d86d949e2a7b72c2fecb738",  # noqa: E501
        "connectivity": "31ddf04a4b1559f7243fadea503d0e9a7023e7fcce31857dd1b2686c5ccf7d88",  # noqa: E501
        "serving_tables": "bb746ee70993dde46d48cb262b44f9aa1292b8d4d26991fb4cbe8191abeff92f",  # noqa: E501
        "serving_scales": "8b315681cacb7cba4037c243826de0714281174a2bd82d6f3eff7ccdba776b86",  # noqa: E501
        "reference_model": "a5dbd60fbb613908f932339287f75bdd8996531422c0485cd1c605d439e63eca",  # noqa: E501
        "train_params": "9e5b40caa3a458967484bf4f9d58dce286a947f57f409bf5a58c91bcb57bb52c",  # noqa: E501
        "convert_params": "5f5a429da70d9070e4b60e4aeedd13cdd9ed68003bd7bd70d36f9502f192e8cc",  # noqa: E501
        "ops_per_sample": 3368,
        "table_bytes": 1714176,
        "call_bytes": 1735680,
        "table_entries": 3428352,
        "forward_macs_per_sample": 285824,
        "conversion_flops": 4254072832,
        "conversion_bytes": 47727912,
        "train_flops_per_sample": 1714944,
        "step_bytes": 8498040,
    },
}


def digest(*trees) -> str:
    h = hashlib.sha256()
    for leaf in jax.tree.leaves(trees):
        a = np.asarray(leaf)
        h.update(f"{a.dtype}{a.shape}".encode())
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()


def fingerprint(conf):
    geom = Geometry.from_conf(conf)
    served = serving_model(geom, conf)
    model = reference_model(geom, served)
    ref = reference(conf)
    codes = np.random.default_rng(7).integers(
        0, 2 ** geom.in_bits[0], (300, geom.in_features)).astype(np.int32)
    x, _ = getattr(data, conf["inputs"])(300, seed=11)
    answers = np.arange(300, dtype=np.int32) % geom.widths[-1]
    in_std = np.linspace(0.5, 2.0, geom.in_features)
    lc, mlp, grad = (counts("lut_cascade"), counts("neuralut_mlp"),
                     counts("neuralut_grad"))
    return {
        "reference_outputs": digest(ref.lut_outputs(codes, model)),
        "reference_gaps": digest(ref.served_gaps(x, answers, model)),
        "connectivity": digest(connectivity(geom, conf["model_seed"])),
        "serving_tables": digest(served["tables"]),
        "serving_scales": digest(served["in_log_s"], served["layer_log_s"]),
        "reference_model": digest(model["tables"], model["conns"],
                                  model["in_log_s"], model["out_log_s"]),
        "train_params": digest(train_params(geom, SEED, in_std)),
        "convert_params": digest(convert_params(geom, SEED, 0.5)),
        "ops_per_sample": lc.ops_per_sample(geom),
        "table_bytes": lc.table_bytes(geom),
        "call_bytes": lc.call_bytes(geom, 256),
        "table_entries": geom.table_entries,
        "forward_macs_per_sample": mlp.forward_macs_per_sample(geom),
        "conversion_flops": mlp.conversion_flops(geom),
        "conversion_bytes": mlp.conversion_bytes(geom),
        "train_flops_per_sample": grad.train_flops_per_sample(geom),
        "step_bytes": grad.step_bytes(geom, 256),
    }


@pytest.mark.parametrize("name", sorted(RECORDED))
def test_every_recorded_configuration_file_is_there(name):
    """Every recorded file is still there (a later PR adds files beside
    them, which this test leaves alone)."""
    assert (BENCH / "configs" / f"{name}.json").is_file()


@pytest.mark.parametrize("name", sorted(RECORDED))
def test_chain_models_and_counts_did_not_move(name):
    conf = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    assert fingerprint(conf) == RECORDED[name]
