"""Sub-network -> L-LUT conversion (paper §III-E.2) as a fused,
device-resident enumeration sweep.

For every circuit layer we enumerate all 2^{beta_in * F} input code
combinations, dequantize each code *with the source channel's learned
scale*, evaluate the hidden function exactly as the quantized forward
pass does (same ops — the bit-exactness invariant), and quantize the
outputs back to codes.  The result is one (out_width, 2^{beta*F}) uint
table per layer — the entire network becomes a cascade of lookups (see
lut_infer / rtl).

The sweep is ONE jitted computation per layer: codes are enumerated on
device from an iota (nothing is staged from the host), a ``lax.map``
walks fixed-size chunks bounding peak memory, and the resulting table is
bit-packed on device (``lut_infer.pack_tables_jnp``) so a freshly
converted model is already in the serving fast-path format —
``ServeBundle.prepack`` has nothing left to pack.  Compiled sweeps are
cached by their static geometry ``(exec plan, beta_in, beta, F, T,
chunk)`` (plus operand shapes, via jit), so consecutive layers with
the same shape share one executable and converting a second model of
the same family costs zero recompiles — the per-layer ``@jax.jit`` of
the old converter is gone.  The sweep also computes each slot's scale
(``exp`` of the source quantizers' ``log_s``, gathered by the
connectivity), so a layer is one dispatch with no eager op before it.
The number of source channels enters only through those operands'
shapes, so jit compiles once per distinct (source channels, layer
width) under a static key (hdr-5l: four sweep compiles in all).
``convert_cache_stats`` exposes compile counts for tests and profiling.

No sweep reads another's output, so a conversion dispatches every
layer's sweep first and then brings all the tables back to the host in
one wait (``_fetch``): one device-to-host round trip, not one per array.

The hidden function runs through a ``core.exec_plan.SubnetExec``: the
convert-purpose planner default is the canonical jnp einsum off-TPU
(the oracle the tables stay bit-identical to) and the fused Pallas
inference kernel (route ``kernel_infer``) on TPU;
``use_subnet_kernel=`` forces either side.  Sweep executables are
cached keyed on the plan, so the two routes never share (or clobber)
a compile.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core import quant, subnet
from repro.core.exec_plan import SubnetExec, plan_subnet_exec
from repro.core.lut_infer import pack_tables_jnp, packed_slots
from repro.core.nl_config import (LUTGraphConfig, NeuraLUTConfig,
                                  is_graph_config)
from repro.runtime import spans as S

Params = Dict


def enumerate_codes(beta: int, fan_in: int) -> np.ndarray:
    """(2^{beta*F}, F) all code combinations; slot 0 is the MSB of the LUT
    address (matches lut_infer.pack_index and the Verilog bus order)."""
    t = 2 ** (beta * fan_in)
    idx = np.arange(t, dtype=np.int64)
    cols = []
    for j in range(fan_in):
        shift = beta * (fan_in - 1 - j)
        cols.append((idx >> shift) & (2 ** beta - 1))
    return np.stack(cols, axis=1).astype(np.int32)


def _source_log_s(params: Params, buf: int) -> jax.Array:
    """Log-scale of source buffer ``buf``'s quantizer: buffer 0 is the
    model input, buffer j+1 is layer (or graph node) j's output.

    An adder-tree node's output code is the *sum* of its branch codes
    under one shared quantizer, so its dequantization scale is that
    single quantizer scale — the same formula as a plain code, just at
    ``beta + log2(A)`` bits (handled by the sweep's ``beta_in``)."""
    if buf == 0:
        return params["in_quant"]["log_s"]
    return params["layers"][buf - 1]["quant"]["log_s"]


# ---------------------------------------------------------------------------
# Fused sweep: one cached jitted function per static geometry


_SWEEP_CACHE: Dict[Tuple, object] = {}


def _make_sweep(exec_plan: SubnetExec, beta_in: int, beta: int,
                fan_in: int, table_size: int, chunk: int, pack: bool):
    """Build the jitted enumeration sweep for one layer geometry.

    The returned function maps (src_log_s, conn (O, F) int32,
    fn_params, bn_params, bn_state, quant_params) -> ((O, T) uint16
    table, (O, T//P) int32 packed words or None).  ``src_log_s`` is a
    tuple of the source quantizers' log-scales in pool order (one for a
    chain layer, one per source for a graph node); the program turns
    them into each slot's scale itself.  All enumeration happens on
    device; the hidden function runs whatever route ``exec_plan``
    picked.
    """
    offs = 2 ** (beta_in - 1)
    mask = 2 ** beta_in - 1
    nchunks = table_size // chunk
    shifts = jnp.asarray([beta_in * (fan_in - 1 - j)
                          for j in range(fan_in)], jnp.int32)
    exps = (subnet.monomial_exponents(fan_in, exec_plan.degree)
            if exec_plan.kind == "poly" else None)

    def eval_chunk(start, slot_scale, fnp, bn_p, bn_s, quant_p):
        idx = start * chunk + jax.lax.iota(jnp.int32, chunk)
        codes = (idx[:, None] >> shifts[None, :]) & mask  # (chunk, F)
        # (chunk, O, F) dequantized values: scale of the SOURCE channel.
        vals = (codes[:, None, :].astype(jnp.float32) - offs) \
            * slot_scale[None]
        f = exec_plan.apply(fnp, vals, exps=exps)
        pre, _ = quant.bn_apply(bn_p, bn_s, f, train=False)
        return quant.quant_codes(quant_p, pre, beta)  # (chunk, O) int32

    @jax.named_scope(S.SCOPE_CONVERT_SWEEP)
    def sweep(src_log_s, conn, fnp, bn_p, bn_s, quant_p):
        log_s = (src_log_s[0] if len(src_log_s) == 1
                 else jnp.concatenate(src_log_s))
        slot_scale = jnp.exp(log_s)[conn]  # (O, F): scale of each slot
        if nchunks == 1:
            out = eval_chunk(jnp.int32(0), slot_scale, fnp, bn_p, bn_s,
                             quant_p)  # (T, O)
        else:
            out = jax.lax.map(
                lambda s: eval_chunk(s, slot_scale, fnp, bn_p, bn_s,
                                     quant_p),
                jnp.arange(nchunks, dtype=jnp.int32))
            out = out.reshape(table_size, -1)
        table = out.T.astype(jnp.uint16)  # (O, T)
        packed = pack_tables_jnp(table, beta) if pack else None
        return table, packed

    return jax.jit(sweep)


def _get_sweep(cfg: NeuraLUTConfig, layer_idx: int, chunk: int,
               exec_plan: SubnetExec):
    beta_in = cfg.layer_in_bits(layer_idx)
    fan_in = cfg.layer_fan_in(layer_idx)
    t = cfg.table_size(layer_idx)
    pack = t % packed_slots(cfg.beta) == 0
    # SubnetExec is frozen/hashable and already carries kind/skip/degree
    # — the plan IS the route part of the cache key.
    key = (exec_plan, beta_in, cfg.beta, fan_in, t, chunk, pack)
    fn = _SWEEP_CACHE.get(key)
    if fn is None:
        fn = _make_sweep(*key)
        _SWEEP_CACHE[key] = fn
    return fn


def convert_cache_stats() -> Dict[Tuple, int]:
    """{static sweep key: number of compiled executables} — one entry per
    distinct layer geometry seen this process, one compile per distinct
    operand-shape signature under it.  Tests assert consecutive layers
    sharing a geometry reuse a single compile."""
    return {k: fn._cache_size() for k, fn in _SWEEP_CACHE.items()}


def clear_convert_cache() -> None:
    _SWEEP_CACHE.clear()


def _chunk_for(table_size: int, batch: int) -> int:
    """Largest power of two <= min(batch, T); T is a power of two, so the
    chunk always divides it exactly (no ragged tail on device)."""
    chunk = 1
    while chunk * 2 <= min(batch, table_size):
        chunk *= 2
    return chunk


def _guard_size(cfg: NeuraLUTConfig, layer_idx: int) -> None:
    beta_in = cfg.layer_in_bits(layer_idx)
    fan_in = cfg.layer_fan_in(layer_idx)
    if beta_in * fan_in > 20:
        raise ValueError(
            f"layer {layer_idx}: truth table would have "
            f"2^{beta_in * fan_in} entries (beta_in={beta_in} x "
            f"fan_in={fan_in} > 20 address bits); reduce beta/fan-in "
            f"instead of enumerating it")


def _layer_sweep(cfg: NeuraLUTConfig, params: Params, state: Params,
                 statics: List[Dict], layer_idx: int, *, batch: int,
                 exec_plan: SubnetExec
                 ) -> Tuple[jax.Array, Optional[jax.Array]]:
    """Dispatch one layer's fused sweep -> ((O, T) uint16, packed int32
    | None), left on the device for :func:`_fetch`.

    The sweep gets the source quantizer's ``log_s`` (the input's for
    layer 0, the previous layer's otherwise) and the layer's (O, F)
    connectivity as they are: it computes the slot scales itself, so
    its dispatch is the layer's only host work."""
    _guard_size(cfg, layer_idx)
    t = cfg.table_size(layer_idx)
    chunk = _chunk_for(t, batch)
    fn = _get_sweep(cfg, layer_idx, chunk, exec_plan)
    lp = params["layers"][layer_idx]
    with TraceAnnotation(S.CONVERT_LAYER, layer=layer_idx,
                         entries=t * cfg.layer_widths[layer_idx]):
        with TraceAnnotation(S.CONVERT_SWEEP):
            return fn((_source_log_s(params, layer_idx),),
                      statics[layer_idx]["conn"], lp["fn"], lp["bn"],
                      state["layers"][layer_idx]["bn"], lp["quant"])


def _fetch(arrays):
    """Bring a conversion's device arrays (any nesting of lists) back to
    the host as numpy arrays of the same structure, in one wait:
    ``jax.device_get`` starts every copy before it waits on any."""
    leaves = jax.tree_util.tree_leaves(arrays)
    with TraceAnnotation(S.CONVERT_FETCH, arrays=len(leaves),
                         bytes=sum(a.nbytes for a in leaves)):
        return jax.device_get(arrays)


def _convert_plan(cfg: NeuraLUTConfig,
                  use_subnet_kernel: Optional[bool]) -> SubnetExec:
    """Map the legacy force-flag onto an exec plan (None = planner
    default: canonical off-TPU, kernel_infer on TPU)."""
    route = None
    if use_subnet_kernel is not None and cfg.kind == "subnet":
        route = "kernel_infer" if use_subnet_kernel else "canonical"
    return plan_subnet_exec(cfg, purpose="convert", route=route)


def layer_truth_table(cfg: NeuraLUTConfig, params: Params, state: Params,
                      statics: List[Dict], layer_idx: int, *,
                      batch: int = 4096,
                      use_subnet_kernel: Optional[bool] = None
                      ) -> np.ndarray:
    """uint16 (out_width, 2^{beta_in*F}) output codes for one layer."""
    table, _ = _layer_sweep(cfg, params, state, statics, layer_idx,
                            batch=batch,
                            exec_plan=_convert_plan(cfg,
                                                    use_subnet_kernel))
    return _fetch(table)


def convert(cfg, params: Params, state: Params,
            statics: List[Dict], *, batch: int = 4096,
            use_subnet_kernel: Optional[bool] = None) -> List[np.ndarray]:
    """All layers' truth tables (unpacked uint16).  For a
    ``LUTGraphConfig`` this is :func:`convert_graph` (per-node lists)."""
    if is_graph_config(cfg):
        return convert_graph(cfg, params, state, statics, batch=batch,
                             use_subnet_kernel=use_subnet_kernel)
    exec_plan = _convert_plan(cfg, use_subnet_kernel)
    return _fetch([_layer_sweep(cfg, params, state, statics, i,
                                batch=batch, exec_plan=exec_plan)[0]
                   for i in range(cfg.num_layers)])


def convert_packed(cfg, params: Params, state: Params,
                   statics: List[Dict], *, batch: int = 4096,
                   use_subnet_kernel: Optional[bool] = None
                   ) -> Tuple[List[np.ndarray], List[np.ndarray]]:
    """All layers' tables in both forms: ([unpacked uint16], [bit-packed
    int32]) with the packing fused into the device sweep.  Feed both to
    ``serve.bundle_from_training(..., packed_tables=...)`` and the
    resulting bundle is serving-ready without a prepack step.  Graph
    configs return per-node *lists* of branch tables in both slots."""
    if is_graph_config(cfg):
        return convert_graph_packed(cfg, params, state, statics,
                                    batch=batch,
                                    use_subnet_kernel=use_subnet_kernel)
    exec_plan = _convert_plan(cfg, use_subnet_kernel)
    tables, packeds = [], []
    for i in range(cfg.num_layers):
        table, packed = _layer_sweep(cfg, params, state, statics, i,
                                     batch=batch, exec_plan=exec_plan)
        if packed is None:
            # T < P: the table does not fill one packed word, so the
            # cascade format (and pack_tables itself) cannot hold it.
            raise ValueError(
                f"layer {i}: table size {cfg.table_size(i)} smaller than "
                f"the packed word capacity {packed_slots(cfg.beta)} "
                f"(beta={cfg.beta}); geometry not servable bit-packed")
        tables.append(table)
        packeds.append(packed)
    return _fetch((tables, packeds))


# ---------------------------------------------------------------------------
# Per-node LUT-graph conversion (DAG topologies)


def _graph_node_sweep(cfg: LUTGraphConfig, params: Params, state: Params,
                      statics: List[Dict], idx: int, *, batch: int,
                      exec_plan: SubnetExec):
    """Dispatch one node's fused sweeps -> (per-branch [(O, T) uint16],
    per-branch [packed int32 | None]), left on the device for
    :func:`_fetch`.  Reuses the chain sweep cache: the node's geometry
    key (beta_in, F, T) is all ``_get_sweep`` needs, and every branch of
    a node shares one compiled executable, fed the node's source
    log-scales and the branch's own connectivity."""
    from repro.core.model import node_branch_params, node_static_conns
    _guard_size(cfg, idx)
    nd = cfg.nodes[idx]
    t = cfg.table_size(idx)
    chunk = _chunk_for(t, batch)
    fn = _get_sweep(cfg, idx, chunk, exec_plan)
    conns = node_static_conns(statics[idx])
    lp, ls = params["layers"][idx], state["layers"][idx]
    src_log_s = tuple(_source_log_s(params, b)
                      for b in cfg.node_sources(idx))
    tables, packeds = [], []
    with TraceAnnotation(S.CONVERT_LAYER, layer=idx, arity=nd.arity,
                         entries=t * cfg.layer_widths[idx] * len(conns)):
        for a, (conn, (fnp, bnp, bns)) in enumerate(
                zip(conns, node_branch_params(nd, lp, ls))):
            with TraceAnnotation(S.CONVERT_SWEEP, branch=a):
                table, packed = fn(src_log_s, conn, fnp, bnp, bns,
                                   lp["quant"])
            tables.append(table)
            packeds.append(packed)
    return tables, packeds


def convert_graph(cfg: LUTGraphConfig, params: Params, state: Params,
                  statics: List[Dict], *, batch: int = 4096,
                  use_subnet_kernel: Optional[bool] = None
                  ) -> List[List[np.ndarray]]:
    """Per-node truth tables: ``out[i]`` is node i's per-branch list of
    (O, T) uint16 tables."""
    exec_plan = _convert_plan(cfg, use_subnet_kernel)
    return _fetch([_graph_node_sweep(cfg, params, state, statics, i,
                                     batch=batch, exec_plan=exec_plan)[0]
                   for i in range(cfg.num_layers)])


def convert_graph_packed(cfg: LUTGraphConfig, params: Params, state: Params,
                         statics: List[Dict], *, batch: int = 4096,
                         use_subnet_kernel: Optional[bool] = None
                         ) -> Tuple[List[List[np.ndarray]],
                                    List[List[np.ndarray]]]:
    """Graph twin of :func:`convert_packed`: per-node lists of
    ([unpacked uint16], [bit-packed int32]) branch tables."""
    exec_plan = _convert_plan(cfg, use_subnet_kernel)
    all_tables, all_packed = [], []
    for i in range(cfg.num_layers):
        tables, packeds = _graph_node_sweep(cfg, params, state, statics, i,
                                            batch=batch,
                                            exec_plan=exec_plan)
        if any(p is None for p in packeds):
            raise ValueError(
                f"node {i}: table size {cfg.table_size(i)} smaller than "
                f"the packed word capacity {packed_slots(cfg.beta)} "
                f"(beta={cfg.beta}); geometry not servable bit-packed")
        all_tables.append(tables)
        all_packed.append(packeds)
    return _fetch((all_tables, all_packed))
