"""A cell's model, made by the benchmark from its configuration file.

The configuration file (``bench/configs/<name>.json``) holds the sizes as
they are run.  From it and a seed this module makes everything both sides
of a comparison need: connectivity, serving tables and quantizer scales,
training and conversion parameters.  The program receives these arrays
through its own entry points; the reference (``bench/reference``) gets the
same arrays, so it takes nothing the program made.

Connectivity, serving tables and scales come from the configuration's
fixed ``model_seed``: the serving forward and the training epoch close
over them as constants of their compiled programs, so a model that
changed with ``--seed`` would miss the compile cache in every run.  What
a run's ``--seed`` changes is its traffic, its data and the parameters
that the program takes as arguments (training and conversion).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# The fields of the program's NeuraLUTConfig that a configuration file
# states; every one of them is compared with the registered architecture.
CONFIG_FIELDS = ("name", "in_features", "layer_widths", "num_classes",
                 "beta", "fan_in", "kind", "depth", "width", "skip",
                 "degree", "beta_in", "fan_in_0", "bn_momentum", "family")


def seed_int(seed: int, *salt: int) -> int:
    """A 31-bit integer from a seed of any size (``jax.random.key`` keeps
    only the low 32 bits of larger seeds)."""
    return int(np.random.default_rng([int(seed) % 2 ** 63, *salt])
               .integers(0, 2 ** 31 - 1))


def seed_key(seed: int, *salt: int):
    return jax.random.key(seed_int(seed, *salt))


@dataclass(frozen=True)
class Geometry:
    """Sizes of a chain NeuraLUT network, read from the configuration
    file alone."""
    in_features: int
    widths: Tuple[int, ...]
    fan_ins: Tuple[int, ...]
    in_bits: Tuple[int, ...]
    beta: int
    depth: int
    width: int
    skip: int
    momentum: float

    @classmethod
    def from_conf(cls, conf: Dict) -> "Geometry":
        widths = tuple(conf["layer_widths"])
        n = len(widths)
        f0 = conf.get("fan_in_0") or conf["fan_in"]
        b0 = conf.get("beta_in") or conf["beta"]
        return cls(in_features=conf["in_features"], widths=widths,
                   fan_ins=(f0,) + (conf["fan_in"],) * (n - 1),
                   in_bits=(b0,) + (conf["beta"],) * (n - 1),
                   beta=conf["beta"], depth=conf["depth"],
                   width=conf["width"], skip=conf["skip"],
                   momentum=conf["bn_momentum"])

    @property
    def sources(self) -> Tuple[int, ...]:
        return (self.in_features,) + self.widths[:-1]

    def table_size(self, i: int) -> int:
        return 2 ** (self.in_bits[i] * self.fan_ins[i])

    @property
    def table_entries(self) -> int:
        return sum(o * self.table_size(i) for i, o in enumerate(self.widths))

    def mlp_widths(self, i: int) -> List[int]:
        """n_0 = F, n_1..n_{L-1} = N, n_L = 1 (one output per neuron)."""
        return [self.fan_ins[i]] + [self.width] * (self.depth - 1) + [1]


def program_config(conf: Dict):
    """The program's config object for this file; when the file names a
    registered ``arch``, every stated field must equal the registered
    one (the benchmark runs the architecture it says it runs)."""
    from repro.core.nl_config import NeuraLUTConfig
    fields = {k: conf[k] for k in CONFIG_FIELDS if k in conf}
    fields["layer_widths"] = tuple(fields["layer_widths"])
    cfg = NeuraLUTConfig(**fields)
    if conf.get("arch"):
        from repro.config import get_config
        reg = get_config(conf["arch"])
        if reg != cfg:
            raise ValueError(f"configuration file differs from the "
                             f"registered {conf['arch']}: {cfg} != {reg}")
    return cfg


def connectivity(geom: Geometry, model_seed: int) -> List[np.ndarray]:
    """(O, F) int32 per layer: F distinct sources per neuron."""
    out = []
    for i, (o, f) in enumerate(zip(geom.widths, geom.fan_ins)):
        rng = np.random.default_rng([model_seed, 1, i])
        order = np.argsort(rng.random((o, geom.sources[i])), axis=1)
        out.append(order[:, :f].astype(np.int32))
    return out


def serving_model(geom: Geometry, conf: Dict) -> Dict:
    """Random tables in the served type (uint16 codes) and seeded
    quantizer scales, made on the device in one call."""
    seed = conf["model_seed"]
    in_scale = conf["serve_input_scale"]

    @jax.jit
    def make(key):
        ks = jax.random.split(key, len(geom.widths) + 3)
        tables = [jax.random.randint(ks[i], (o, geom.table_size(i)), 0,
                                     2 ** geom.beta).astype(jnp.uint16)
                  for i, o in enumerate(geom.widths)]
        in_log_s = jnp.log(in_scale * jax.random.uniform(
            ks[-3], (geom.in_features,), minval=0.8, maxval=1.2))
        out_log_s = jnp.log(jax.random.uniform(
            ks[-2], (geom.widths[-1],), minval=0.5, maxval=1.5))
        hidden_log_s = [jnp.full((o,), jnp.log(0.25), jnp.float32)
                        for o in geom.widths[:-1]]
        return tables, in_log_s, hidden_log_s + [out_log_s]

    tables, in_log_s, layer_log_s = jax.device_get(
        make(seed_key(seed, 2)))
    return {"tables": tables, "in_log_s": in_log_s,
            "layer_log_s": layer_log_s,
            "conns": connectivity(geom, seed)}


def reference_model(geom: Geometry, served: Dict) -> Dict:
    """The serving model as the reference takes it (device arrays)."""
    return {"tables": [jnp.asarray(t.astype(np.int32))
                       for t in served["tables"]],
            "conns": [jnp.asarray(c) for c in served["conns"]],
            "in_log_s": jnp.asarray(served["in_log_s"]),
            "out_log_s": jnp.asarray(served["layer_log_s"][-1]),
            "in_bits": geom.in_bits, "beta": geom.beta}


def fn_params(geom: Geometry, i: int, key, bias_sd: float) -> Dict:
    """Hidden-function parameters of layer ``i``: normal weights scaled
    by 1/sqrt(fan-in) and normal biases of spread ``bias_sd``."""
    o = geom.widths[i]
    w = geom.mlp_widths(i)
    ks = jax.random.split(key, 4 * geom.depth)

    def affine(k1, k2, n_in, n_out):
        return {"w": jax.random.normal(k1, (o, n_in, n_out))
                / np.sqrt(n_in),
                "b": bias_sd * jax.random.normal(k2, (o, n_out))}

    fn = {"layers": [affine(ks[2 * j], ks[2 * j + 1], w[j], w[j + 1])
                     for j in range(geom.depth)]}
    if geom.skip:
        s = geom.skip
        fn["skips"] = [affine(ks[2 * geom.depth + 2 * c],
                              ks[2 * geom.depth + 2 * c + 1],
                              w[c * s], w[(c + 1) * s])
                       for c in range(geom.depth // s)]
    return fn


def train_params(geom: Geometry, seed: int, in_std: np.ndarray
                 ) -> Tuple[Dict, Dict]:
    """Initial (params, state) for training: fan-in scaled normal
    weights, unit batch norm, output scales spanning +-2 sigma, input
    scales spanning +-2.5 standard deviations of the data."""
    half_in = 2 ** (geom.in_bits[0] - 1)
    c = max(1, 2 ** (geom.beta - 1) - 1)

    @jax.jit
    def make(key, in_std):
        ks = jax.random.split(key, len(geom.widths))
        layers, states = [], []
        for i, o in enumerate(geom.widths):
            layers.append({
                "fn": fn_params(geom, i, ks[i], 0.05),
                "bn": {"g": jnp.ones((o,)), "b": jnp.zeros((o,))},
                "quant": {"log_s": jnp.full((o,), np.log(2.0 / c),
                                            jnp.float32)}})
            states.append({"bn": {"mean": jnp.zeros((o,)),
                                  "var": jnp.ones((o,))}})
        params = {"in_quant": {"log_s": jnp.log(2.5 * in_std / half_in)},
                  "layers": layers}
        return params, {"layers": states}

    return make(seed_key(seed, 3), jnp.asarray(np.maximum(in_std, 1e-3),
                                               jnp.float32))


def convert_params(geom: Geometry, seed: int, in_scale: float
                   ) -> Tuple[Dict, Dict]:
    """Trained-looking (params, state) for conversion: seeded weights
    and biases, and batch-norm affine maps and running statistics that
    spread every layer's outputs over its code range."""
    c = max(1, 2 ** (geom.beta - 1) - 1)

    @jax.jit
    def make(key):
        ks = jax.random.split(key, 6 * len(geom.widths) + 1)
        layers, states = [], []
        for i, o in enumerate(geom.widths):
            k = ks[6 * i:6 * i + 6]
            u = jax.random.uniform
            layers.append({
                "fn": fn_params(geom, i, k[0], 0.1),
                "bn": {"g": u(k[1], (o,), minval=0.8, maxval=1.2),
                       "b": 0.1 * jax.random.normal(k[2], (o,))},
                "quant": {"log_s": jnp.log(
                    (2.0 / c) * u(k[3], (o,), minval=0.8, maxval=1.2))}})
            states.append({"bn": {
                "mean": 0.1 * jax.random.normal(k[4], (o,)),
                "var": u(k[5], (o,), minval=0.5, maxval=1.5)}})
        in_log_s = jnp.log(in_scale * jax.random.uniform(
            ks[-1], (geom.in_features,), minval=0.8, maxval=1.2))
        return ({"in_quant": {"log_s": in_log_s}, "layers": layers},
                {"layers": states})

    return make(seed_key(seed, 4))
