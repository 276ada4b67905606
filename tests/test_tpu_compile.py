"""Compile the main path's Pallas kernels for a TPU v5e that is described,
not attached.

Interpret mode (every other kernel test) checks the math; only Mosaic,
the TPU kernel compiler, checks what a chip accepts: block shapes
aligned to the (8, 128) tile, the VMEM budget, ops it can lower.  These
tests run the compiler that ships with libtpu against a ``v5e:2x2``
topology description at the real widths of every benchmark geometry:
the serving cascade (``lut_cascade``), the conversion kernel
(``grouped_subnet``) and the training kernel (``subnet_train_op``,
forward and ``jax.grad``) at every layer (a LUT graph: node) shape, and
the whole conversion sweep of each distinct layer or node geometry (its
source bits, its table of up to 2^15 entries walked in chunks).  Nothing
executes, so they say nothing about results or speed.

The topology is described inside a module-scoped fixture, never at
import: only one process may load libtpu, and every test worker imports
this file.
"""
import importlib
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import subnet
from repro.core import truth_table as TT
from repro.core.exec_plan import SubnetExec
from repro.core.lut_infer import packed_slots
from repro.core.nl_config import is_graph_config
from repro.kernels.lut_cascade import (cascade_meta, graph_cascade_meta,
                                       lut_cascade)
from repro.kernels.neuralut_mlp import grouped_subnet
from repro.kernels.ops import subnet_params_to_kernel, subnet_train_apply

GEOMETRIES = ["neuralut_jsc_2l", "neuralut_jsc_5l", "neuralut_hdr_5l",
              "polylut_add_jsc_5l", "polylut_add_jsc_xl"]
SUBNET_GEOMETRIES = ["neuralut_jsc_5l", "neuralut_hdr_5l",
                     "polylut_add_jsc_5l", "polylut_add_jsc_xl"]
SERVE_BATCH = 256   # the serving engine's largest bucket
TRAIN_BATCH = 256   # core.train.train_neuralut's default batch
CONVERT_CHUNK = 4096  # truth_table's default sweep chunk


def _cfg(mod):
    return importlib.import_module(f"repro.configs.{mod}").full()


def _sweeps(mod):
    """(source bits, F, table size, O, source pool) of every distinct
    conversion sweep of a config: one per chain layer, one per graph node
    (its branches share it).  The pool is the width of each source buffer
    the node reads (a chain layer reads the buffer before it), as the
    converter hands the sweep one quantizer scale per source; a graph
    node's source bits are beta + log2 of the arity of the node it
    reads."""
    cfg = _cfg(mod)
    g = cfg if is_graph_config(cfg) else cfg.graph()
    return sorted({(g.layer_in_bits(i), g.layer_fan_in(i), g.table_size(i),
                    g.layer_widths[i],
                    tuple(g.buffer_width(b) for b in g.node_sources(i)))
                   for i in range(g.num_layers)}, reverse=True)


def _subnet_layers(mod):
    """(O, F) of every distinct sub-network layer (graph node) shape."""
    return sorted({(o, f) for _, f, _, o, _ in _sweeps(mod)}, reverse=True)


SUBNET_CASES = [(mod, o, f) for mod in SUBNET_GEOMETRIES
                for o, f in _subnet_layers(mod)]
SWEEP_CASES = [pytest.param(mod, *g, id="-".join(
                   map(str, (mod, *g[:4], "+".join(map(str, g[4]))))))
               for mod in SUBNET_GEOMETRIES for g in _sweeps(mod)]


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip can be written to the persistent
    # cache but never read back without one; keep it out.
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _sds(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args, kernels=()):
    """Compile for the described chip; each of ``kernels`` must name an
    instruction, the name under which a device trace shows the kernel's
    calls: ``%lut_cascade.1``, or under autodiff
    ``%transpose_jvp_subnet_train_bwd__.1``."""
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    for name in kernels:
        assert re.search(rf"%[\w.]*{name}[\w.]* = ", text), name
    return compiled


def _cascade_operands(cfg, sharding):
    """Shapes of the fused cascade's flat (shift mats, packed tables)."""
    p = packed_slots(cfg.beta)
    sms, pts = [], []
    if is_graph_config(cfg):
        meta = graph_cascade_meta(cfg)
        for i, nd in enumerate(cfg.nodes):
            for _ in range(nd.arity):
                sms += [_sds(sharding, (cfg.buffer_width(s), nd.width))
                        for s in cfg.node_sources(i)]
                pts.append(_sds(sharding, (nd.width, cfg.table_size(i) // p),
                                jnp.int32))
        return meta, sms, pts
    w_prev = cfg.in_features
    for i, o in enumerate(cfg.layer_widths):
        sms.append(_sds(sharding, (w_prev, o)))
        pts.append(_sds(sharding, (o, cfg.table_size(i) // p), jnp.int32))
        w_prev = o
    return cascade_meta(cfg), sms, pts


@pytest.mark.parametrize("mod", GEOMETRIES)
def test_lut_cascade_compiles(one_chip, mod):
    cfg = _cfg(mod)
    meta, sms, pts = _cascade_operands(cfg, one_chip)
    codes = _sds(one_chip, (SERVE_BATCH, cfg.in_features), jnp.int32)
    _compile(lambda c, s, t: lut_cascade(c, s, t, meta, block_b=8,
                                         interpret=False),
             codes, sms, pts, kernels=("lut_cascade",))


def _subnet_params(cfg, o, f, sharding):
    spec = subnet.subnet_spec(o, f, cfg.depth, cfg.width, cfg.skip)
    return jax.tree.map(lambda s: _sds(sharding, s.shape), spec)


@pytest.mark.parametrize("mod,o,f", SUBNET_CASES)
def test_grouped_subnet_compiles(one_chip, mod, o, f):
    cfg = _cfg(mod)
    kw = subnet_params_to_kernel(_subnet_params(cfg, o, f, one_chip))
    xg = _sds(one_chip, (CONVERT_CHUNK, o, f))
    _compile(lambda x, kw: grouped_subnet(
        x, kw["layer_ws"], kw["layer_bs"], kw["skip_ws"], kw["skip_bs"],
        skip=cfg.skip, interpret=False), xg, kw,
        kernels=("subnet_infer",))


@pytest.mark.parametrize("mod,o,f", SUBNET_CASES)
def test_subnet_train_op_compiles(one_chip, mod, o, f):
    cfg = _cfg(mod)
    p = _subnet_params(cfg, o, f, one_chip)
    xg = _sds(one_chip, (TRAIN_BATCH, o, f))

    def fwd(p, x):
        return subnet_train_apply(p, x, cfg.skip, interpret=False)

    _compile(fwd, p, xg, kernels=("subnet_train_fwd",))
    _compile(jax.grad(lambda p, x: jnp.sum(fwd(p, x)), argnums=(0, 1)),
             p, xg, kernels=("subnet_train_fwd", "subnet_train_bwd"))


@pytest.mark.parametrize("mod,bits,f,t,o,pool", SWEEP_CASES)
def test_convert_sweep_compiles(one_chip, mod, bits, f, t, o, pool):
    """The jitted sweep as the converter builds it on the chip (route
    ``kernel_infer``): codes of ``bits`` bits enumerated on the device,
    ``t`` entries in chunks of the default batch, packed, over the
    quantizer scales of the node's own source buffers."""
    cfg = _cfg(mod)
    chunk = TT._chunk_for(t, CONVERT_CHUNK)
    plan = SubnetExec(kind="subnet", route="kernel_infer", skip=cfg.skip,
                      interpret=False)
    sweep = TT._make_sweep(plan, bits, cfg.beta, f, t, chunk, True)
    vec = _sds(one_chip, (o,))
    _compile(sweep, tuple(_sds(one_chip, (w,)) for w in pool),
             _sds(one_chip, (o, f), jnp.int32),
             _subnet_params(cfg, o, f, one_chip), {"g": vec, "b": vec},
             {"mean": vec, "var": vec}, {"log_s": vec},
             kernels=("subnet_infer",))
