"""Full NeuraLUT circuit-level model (input quantizer + stacked layers).

API:
    statics   = model_static(cfg)                    # connectivity etc.
    p, s      = model_init(cfg, key)                 # trainable / BN state
    logits, values, states = model_apply(cfg, p, s, statics, x, train=...)
    loss through ``logits`` (pre-quant output of the last layer); the
    hardware path uses the quantized values (see truth_table / lut_infer).

Every entry point accepts a ``LUTGraphConfig`` too and routes to the
``graph_*`` twins below, which walk the node DAG instead of the layer
chain.  An arity-A adder-tree node carries A parallel branches — each
with its own connectivity, hidden function and batch norm — summed
*after* quantization through ONE shared quantizer, so the node's output
is exactly a ``beta + log2(A)``-bit code (see core/nl_config.py).  For
a degenerate-chain graph the walk performs literally the layer-cascade
ops in the same order, so outputs are bit-identical to ``model_apply``
on the equivalent ``NeuraLUTConfig``.
"""
from __future__ import annotations

from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import layers as L
from repro.core import quant, subnet
from repro.core.exec_plan import plan_subnet_exec
from repro.core.nl_config import (LUTGraphConfig, LUTNodeSpec,
                                  NeuraLUTConfig, is_graph_config)
from repro.core.sparsity import random_connectivity
from repro.models.layers.common import init_from_spec

Params = Dict[str, Any]


def model_widths(cfg: NeuraLUTConfig) -> List[int]:
    return [cfg.in_features] + list(cfg.layer_widths)


def model_static(cfg) -> List[Dict]:
    if is_graph_config(cfg):
        return graph_static(cfg)
    w = model_widths(cfg)
    return [L.layer_static(cfg, i, w[i], w[i + 1])
            for i in range(cfg.num_layers)]


def model_spec(cfg) -> Tuple[Params, Params]:
    if is_graph_config(cfg):
        return graph_spec(cfg)
    w = model_widths(cfg)
    lp, ls = [], []
    for i in range(cfg.num_layers):
        pi, si = L.layer_spec(cfg, i, w[i + 1])
        lp.append(pi)
        ls.append(si)
    params = {
        "in_quant": quant.quant_spec(cfg.in_features),
        "layers": lp,
    }
    return params, {"layers": ls}


def model_init(cfg, key) -> Tuple[Params, Params]:
    if is_graph_config(cfg):
        return graph_init(cfg, key)
    spec_p, spec_s = model_spec(cfg)
    params = init_from_spec(spec_p, key)
    # quantizer scales and BN need proper init, not trunc-normal
    params["in_quant"] = quant.quant_init(cfg.in_features, 0.25)
    for i, lp in enumerate(params["layers"]):
        # scale such that +-2 sigma of a unit-variance BN output covers the
        # code range
        c = max(1, 2 ** (cfg.beta - 1) - 1)
        lp["quant"] = quant.quant_init(cfg.layer_widths[i], 2.0 / c)
        lp["bn"] = {"g": jnp.ones((cfg.layer_widths[i],), jnp.float32),
                    "b": jnp.zeros((cfg.layer_widths[i],), jnp.float32)}
    state = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), spec_s,
                         is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
    for ls_ in state["layers"]:
        ls_["bn"]["var"] = jnp.ones_like(ls_["bn"]["var"])
    return params, state


# ---------------------------------------------------------------------------
# LUT-graph (DAG) twins


def node_static_conns(static: Dict) -> List[np.ndarray]:
    """Per-branch connectivity of one node's static dict, tolerating the
    legacy chain key: ``{"conns": [...]}`` (graph form) or
    ``{"conn": arr}`` (a single arity-1 branch)."""
    if "conns" in static:
        return list(static["conns"])
    return [static["conn"]]


def node_branch_params(nd: LUTNodeSpec, lp: Params, ls: Params
                       ) -> List[Tuple[Params, Params, Params]]:
    """(fn, bn params, bn state) per branch.  Arity-1 nodes use the flat
    legacy layer tree — chain graphs share param trees (and trained
    checkpoints) with the cascade path verbatim."""
    if nd.arity == 1:
        return [(lp["fn"], lp["bn"], ls["bn"])]
    return [(lp["fn"][a], lp["bn"][a], ls["bn"][a])
            for a in range(nd.arity)]


def _branch_fn_spec(cfg: LUTGraphConfig, fan_in: int, out_width: int):
    if cfg.kind == "linear":
        return subnet.linear_spec(out_width, fan_in)
    if cfg.kind == "poly":
        return subnet.poly_spec(out_width, fan_in, cfg.degree)
    return subnet.subnet_spec(out_width, fan_in, cfg.depth, cfg.width,
                              cfg.skip)


def graph_static(cfg: LUTGraphConfig) -> List[Dict]:
    """Per-node constants: one connectivity per branch over the node's
    concatenated source-channel pool (+ poly exponents).  Branch 0 of
    node ``i`` uses the legacy seed ``hash((name, i))`` so a
    degenerate-chain graph reproduces ``model_static`` exactly."""
    out = []
    for i, nd in enumerate(cfg.nodes):
        pool_w = cfg.node_in_width(i)
        conns = []
        for a in range(nd.arity):
            seed_key = (cfg.name, i) if a == 0 else (cfg.name, i, a)
            conns.append(random_connectivity(
                pool_w, nd.width, nd.fan_in,
                seed=hash(seed_key) % (2 ** 31)))
        st: Dict[str, Any] = {"conns": conns}
        if cfg.kind == "poly":
            st["exps"] = subnet.monomial_exponents(nd.fan_in, cfg.degree)
        out.append(st)
    return out


def graph_spec(cfg: LUTGraphConfig) -> Tuple[Params, Params]:
    lp, ls = [], []
    for nd in cfg.nodes:
        fn = _branch_fn_spec(cfg, nd.fan_in, nd.width)
        bn_p, bn_s = quant.bn_spec(nd.width)
        if nd.arity == 1:
            p = {"fn": fn, "bn": bn_p,
                 "quant": quant.quant_spec(nd.width)}
            s = {"bn": bn_s}
        else:
            p = {"fn": [_branch_fn_spec(cfg, nd.fan_in, nd.width)
                        for _ in range(nd.arity)],
                 "bn": [quant.bn_spec(nd.width)[0]
                        for _ in range(nd.arity)],
                 "quant": quant.quant_spec(nd.width)}
            s = {"bn": [quant.bn_spec(nd.width)[1]
                        for _ in range(nd.arity)]}
        lp.append(p)
        ls.append(s)
    return ({"in_quant": quant.quant_spec(cfg.in_features), "layers": lp},
            {"layers": ls})


def graph_init(cfg: LUTGraphConfig, key) -> Tuple[Params, Params]:
    spec_p, spec_s = graph_spec(cfg)
    params = init_from_spec(spec_p, key)
    params["in_quant"] = quant.quant_init(cfg.in_features, 0.25)
    c = max(1, 2 ** (cfg.beta - 1) - 1)
    for i, nd in enumerate(cfg.nodes):
        lp = params["layers"][i]
        # An adder tree sums A branch codes; give the shared quantizer
        # sqrt(A) more headroom so the per-branch codes start unsaturated.
        lp["quant"] = quant.quant_init(nd.width,
                                       2.0 * (nd.arity ** 0.5) / c)
        # arrays of their own per branch: a training step donates every
        # leaf, and a buffer that two leaves share cannot be donated
        bns = [quant.bn_init(nd.width)[0] for _ in range(nd.arity)]
        lp["bn"] = bns[0] if nd.arity == 1 else bns
    state = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), spec_s,
                         is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
    for i, nd in enumerate(cfg.nodes):
        bs = state["layers"][i]["bn"]
        for b in (bs if nd.arity > 1 else [bs]):
            b["var"] = jnp.ones_like(b["var"])
    return params, state


def graph_pool(cfg: LUTGraphConfig, bufs: List[jax.Array], idx: int
               ) -> jax.Array:
    """Concatenate node ``idx``'s source buffers channel-wise."""
    srcs = cfg.node_sources(idx)
    if len(srcs) == 1:
        return bufs[srcs[0]]
    return jnp.concatenate([bufs[s] for s in srcs], axis=1)


def graph_apply(cfg: LUTGraphConfig, params: Params, state: Params,
                statics: List[Dict], x: jax.Array, *, train: bool,
                exec_plan=None):
    """Graph twin of :func:`model_apply`: same return triple.

    ``logits`` is the final node's pre-quant batch-norm output (the
    classifier node has arity 1 by config contract)."""
    beta_in = cfg.beta_in or cfg.beta
    if exec_plan is None:
        exec_plan = plan_subnet_exec(cfg,
                                     purpose="train" if train else "eval")
    bufs = [quant.quant_apply(params["in_quant"], x, beta_in)]
    new_states = []
    pre = None
    for i, nd in enumerate(cfg.nodes):
        pool = graph_pool(cfg, bufs, i)
        lp, ls = params["layers"][i], state["layers"][i]
        conns = node_static_conns(statics[i])
        exps = statics[i].get("exps")
        y = None
        branch_states = []
        for a, (fnp, bnp, bns) in enumerate(
                node_branch_params(nd, lp, ls)):
            xg = pool[:, jnp.asarray(conns[a])]        # (B, O, F)
            f = exec_plan.apply(fnp, xg, exps=exps)
            pre, nbn = quant.bn_apply(bnp, bns, f, train=train,
                                      momentum=cfg.bn_momentum)
            qa = quant.quant_apply(lp["quant"], pre, cfg.beta)
            y = qa if y is None else y + qa
            branch_states.append(nbn)
        new_states.append({"bn": branch_states[0] if nd.arity == 1
                           else branch_states})
        bufs.append(y)
    return pre, bufs[-1], {"layers": new_states}


def calibrate_in_quant(cfg: NeuraLUTConfig, params: Params,
                       x_train) -> Params:
    """Calibrate the input quantizer on the data: +-2.5 sigma per feature
    spans the signed code range (learned scales then fine-tune from
    here).  Returns ``params`` with ``in_quant.log_s`` replaced."""
    beta_in = cfg.beta_in or cfg.beta
    max_code = 2 ** (beta_in - 1)
    std = np.maximum(np.asarray(x_train).std(axis=0), 1e-3)
    params = dict(params)
    params["in_quant"] = {"log_s": jnp.asarray(
        np.log(2.5 * std / max_code), jnp.float32)}
    return params


def model_apply(cfg: NeuraLUTConfig, params: Params, state: Params,
                statics: List[Dict], x: jax.Array, *, train: bool,
                exec_plan=None):
    """x: (B, in_features) raw features.

    Returns (logits (B, classes) pre-quant, quantized class values,
    new_state).  ``exec_plan`` (a ``core.exec_plan.SubnetExec``) routes
    every layer's hidden function; None uses the planner default for
    the train/eval purpose."""
    if is_graph_config(cfg):
        return graph_apply(cfg, params, state, statics, x, train=train,
                           exec_plan=exec_plan)
    beta_in = cfg.beta_in or cfg.beta
    v = quant.quant_apply(params["in_quant"], x, beta_in)
    new_states = []
    pre = None
    for i in range(cfg.num_layers):
        v, pre, ns = L.layer_apply(
            cfg, i, params["layers"][i], state["layers"][i], statics[i], v,
            train=train, exec_plan=exec_plan)
        new_states.append(ns)
    return pre, v, {"layers": new_states}


def ce_loss(logits: jax.Array, labels: jax.Array) -> jax.Array:
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(lse - ll)


def accuracy_from_values(values: jax.Array, labels: jax.Array) -> jax.Array:
    return jnp.mean(jnp.argmax(values, axis=-1) == labels)


def total_params(cfg: NeuraLUTConfig) -> int:
    p, _ = model_spec(cfg)
    tot = 0
    for leaf in jax.tree.leaves(p):
        n = 1
        for d in leaf.shape:
            n *= d
        tot += n
    return tot
