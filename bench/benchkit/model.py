"""A cell's model, made by the benchmark from its configuration file.

The configuration file (``bench/configs/<name>.json``) holds the sizes as
they are run: a chain's ``layer_widths``, or a LUT graph's ``nodes``
(each with ``name``, ``width``, ``fan_in``, ``inputs`` and ``arity``, as
the program's ``LUTNodeSpec``).  From it and a seed this module makes
everything both sides of a comparison need: connectivity, serving
tables and quantizer scales, training and conversion parameters.  The
program receives these arrays through its own entry points; the
reference (``bench/reference``) gets the same arrays, so it takes nothing
the program made.

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

# The fields of the program's NeuraLUTConfig that a chain file states, and
# of its LUTGraphConfig that a graph file states besides ``nodes``; every
# one of them is compared with the registered architecture.
CONFIG_FIELDS = ("name", "in_features", "layer_widths", "num_classes",
                 "beta", "fan_in", "kind", "depth", "width", "skip",
                 "degree", "beta_in", "fan_in_0", "bn_momentum", "family")
GRAPH_FIELDS = ("name", "in_features", "num_classes", "beta", "kind",
                "depth", "width", "skip", "degree", "beta_in", "bn_momentum",
                "family")
# The source name of a graph node that reads the model's quantized inputs.
INPUT = "input"


def seed_int(seed: int, *salt: int) -> int:
    """A 31-bit integer from a seed of any size (``jax.random.key`` keeps
    only the low 32 bits of larger seeds)."""
    return int(np.random.default_rng([int(seed) % 2 ** 63, *salt])
               .integers(0, 2 ** 31 - 1))


def seed_key(seed: int, *salt: int):
    return jax.random.key(seed_int(seed, *salt))


@dataclass(frozen=True)
class Node:
    """One node of a LUT graph: ``width`` neurons, each the sum of
    ``arity`` branches; a branch looks its beta-bit code up in its own
    table, addressed by ``fan_in`` codes of ``in_bits`` bits drawn from
    the node's source pool (the buffers ``inputs`` concatenated in that
    order; buffer 0 is the quantized input, buffer j + 1 node j's
    output).  The summed code has ``out_bits`` = beta + log2(arity)."""
    width: int
    fan_in: int
    arity: int
    inputs: Tuple[int, ...]
    in_bits: int
    out_bits: int


@dataclass(frozen=True)
class Geometry:
    """Sizes of a NeuraLUT network as a graph of LUT nodes, read from the
    configuration file alone.  A chain file (``layer_widths``) reads as
    the degenerate graph, node i reading node i - 1; a graph file states
    its ``nodes``.  Per-layer accessors take a node's index; connectivity
    and tables come one per branch, node by node (for a chain, one per
    layer)."""
    in_features: int
    input_bits: int
    nodes: Tuple[Node, ...]
    beta: int
    depth: int
    width: int
    skip: int
    momentum: float

    @classmethod
    def from_conf(cls, conf: Dict) -> "Geometry":
        beta = conf["beta"]
        b0 = conf.get("beta_in") or beta
        if "nodes" in conf:
            nodes = _graph_nodes(conf["nodes"], b0, beta)
        else:
            f0 = conf.get("fan_in_0") or conf["fan_in"]
            nodes = tuple(
                Node(width=o, fan_in=f0 if i == 0 else conf["fan_in"],
                     arity=1, inputs=(i,), in_bits=b0 if i == 0 else beta,
                     out_bits=beta)
                for i, o in enumerate(conf["layer_widths"]))
        return cls(in_features=conf["in_features"], input_bits=b0,
                   nodes=nodes, beta=beta, depth=conf["depth"],
                   width=conf["width"], skip=conf["skip"],
                   momentum=conf["bn_momentum"])

    @property
    def widths(self) -> Tuple[int, ...]:
        return tuple(n.width for n in self.nodes)

    @property
    def fan_ins(self) -> Tuple[int, ...]:
        return tuple(n.fan_in for n in self.nodes)

    @property
    def in_bits(self) -> Tuple[int, ...]:
        return tuple(n.in_bits for n in self.nodes)

    @property
    def num_classes(self) -> int:
        return self.nodes[-1].width

    @property
    def sources(self) -> Tuple[int, ...]:
        """Width of each node's source pool."""
        buffers = (self.in_features,) + self.widths
        return tuple(sum(buffers[b] for b in n.inputs) for n in self.nodes)

    def table_size(self, i: int) -> int:
        """Entries of each branch table of node ``i``."""
        return 2 ** (self.in_bits[i] * self.fan_ins[i])

    @property
    def table_entries(self) -> int:
        return sum(n.width * n.arity * self.table_size(i)
                   for i, n in enumerate(self.nodes))

    @property
    def is_chain(self) -> bool:
        """True for a plain cascade: every node one branch, reading only
        the buffer before it (node 0 the input)."""
        return all(n.arity == 1 and n.inputs == (i,)
                   for i, n in enumerate(self.nodes))

    def mlp_widths(self, i: int) -> List[int]:
        """n_0 = F, n_1..n_{L-1} = N, n_L = 1 (one output per neuron)."""
        return [self.fan_ins[i]] + [self.width] * (self.depth - 1) + [1]

    def per_node(self, branches: List) -> List[List]:
        """A list with one entry per branch, node by node, cut into one
        list per node."""
        out, b = [], 0
        for n in self.nodes:
            out.append(list(branches[b:b + n.arity]))
            b += n.arity
        return out


def _graph_nodes(specs: List[Dict], input_bits: int, beta: int
                 ) -> Tuple[Node, ...]:
    """The nodes of a graph file, in its (topological) order."""
    buf = {INPUT: 0}
    bits = [input_bits]
    nodes = []
    for spec in specs:
        arity = spec.get("arity", 1)
        if arity < 1 or arity & (arity - 1):
            raise ValueError(f"node {spec['name']}: arity {arity} is not a "
                             f"power of two")
        missing = [s for s in spec["inputs"] if s not in buf]
        if missing:
            raise ValueError(f"node {spec['name']} reads {missing}, which "
                             f"are not the input or an earlier node")
        inputs = tuple(buf[s] for s in spec["inputs"])
        if len({bits[b] for b in inputs}) != 1:
            raise ValueError(f"node {spec['name']} concatenates sources "
                             f"of unequal bit widths")
        node = Node(width=spec["width"], fan_in=spec["fan_in"], arity=arity,
                    inputs=inputs, in_bits=bits[inputs[0]],
                    out_bits=beta + arity.bit_length() - 1)
        buf[spec["name"]] = len(nodes) + 1
        bits.append(node.out_bits)
        nodes.append(node)
    return tuple(nodes)


def program_config(conf: Dict):
    """The program's config object for this file: a ``NeuraLUTConfig`` for
    a chain file, a ``LUTGraphConfig`` for a graph file.  When the file
    names a registered ``arch``, every stated field must equal the
    registered one (the benchmark runs the architecture it says it
    runs)."""
    if "nodes" in conf:
        from repro.core.nl_config import LUTGraphConfig, LUTNodeSpec
        fields = {k: conf[k] for k in GRAPH_FIELDS if k in conf}
        fields["nodes"] = tuple(
            LUTNodeSpec(name=n["name"], width=n["width"],
                        fan_in=n["fan_in"], inputs=tuple(n["inputs"]),
                        arity=n.get("arity", 1))
            for n in conf["nodes"])
        cfg = LUTGraphConfig(**fields)
    else:
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


def chain_config(conf: Dict):
    """The program's ``NeuraLUTConfig`` of a chain, whether its file states
    ``layer_widths`` or ``nodes`` that form a chain (``Geometry.is_chain``);
    the training and conversion drivers take chains alone."""
    from repro.core.nl_config import is_graph_config
    cfg = program_config(conf)
    return cfg.as_chain() if is_graph_config(cfg) else cfg


def connectivity(geom: Geometry, model_seed: int) -> List[np.ndarray]:
    """(O, F) int32 per branch, node by node: F distinct sources of the
    node's pool per neuron."""
    out = []
    for i, (n, pool) in enumerate(zip(geom.nodes, geom.sources)):
        for a in range(n.arity):
            # numpy pads a seed with zeros: branch 0 draws what a
            # chain layer drew from [model_seed, 1, i]
            rng = np.random.default_rng([model_seed, 1, i, a])
            order = np.argsort(rng.random((n.width, pool)), axis=1)
            out.append(order[:, :n.fan_in].astype(np.int32))
    return out


def serving_model(geom: Geometry, conf: Dict) -> Dict:
    """Random tables in the served type (uint16 codes), one per branch,
    and seeded quantizer scales, one vector per node, made on the device
    in one call."""
    seed = conf["model_seed"]
    in_scale = conf["serve_input_scale"]
    shapes = [(n.width, geom.table_size(i))
              for i, n in enumerate(geom.nodes) for _ in range(n.arity)]

    @jax.jit
    def make(key):
        ks = jax.random.split(key, len(shapes) + 3)
        tables = [jax.random.randint(ks[b], shape, 0,
                                     2 ** geom.beta).astype(jnp.uint16)
                  for b, shape in enumerate(shapes)]
        in_log_s = jnp.log(in_scale * jax.random.uniform(
            ks[-3], (geom.in_features,), minval=0.8, maxval=1.2))
        out_log_s = jnp.log(jax.random.uniform(
            ks[-2], (geom.num_classes,), minval=0.5, maxval=1.5))
        hidden_log_s = [jnp.full((o,), jnp.log(0.25), jnp.float32)
                        for o in geom.widths[:-1]]
        return tables, in_log_s, hidden_log_s + [out_log_s]

    tables, in_log_s, layer_log_s = jax.device_get(
        make(seed_key(seed, 2)))
    return {"tables": tables, "in_log_s": in_log_s,
            "layer_log_s": layer_log_s,
            "conns": connectivity(geom, seed)}


def reference_model(geom: Geometry, served: Dict) -> Dict:
    """The serving model as the reference takes it (device arrays, one
    table and one connectivity per branch, each node's sources, input
    bits and arity, and the input and class code widths)."""
    return {"tables": [jnp.asarray(t.astype(np.int32))
                       for t in served["tables"]],
            "conns": [jnp.asarray(c) for c in served["conns"]],
            "nodes": [{"inputs": n.inputs, "in_bits": n.in_bits,
                       "arity": n.arity} for n in geom.nodes],
            "in_log_s": jnp.asarray(served["in_log_s"]),
            "out_log_s": jnp.asarray(served["layer_log_s"][-1]),
            "input_bits": geom.input_bits, "beta": geom.beta}


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
