"""Bit-exact LUT-network inference: the hardware-equivalent path.

Runs entirely on integer codes — exactly what the generated Verilog ROMs
compute — so it both validates the truth-table conversion against the
quantized float forward pass and serves as the software "serving" engine
(examples/serve_lut.py).  kernels/lut_gather.py provides the Pallas TPU
version of ``lut_forward``; this module is the jnp oracle.
"""
from __future__ import annotations

from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import quant
from repro.core.nl_config import (LUTGraphConfig, NeuraLUTConfig,
                                  is_graph_config)

Params = Dict


def shift_weights(beta: int, fan_in: int) -> np.ndarray:
    """(F,) int32 place values of each fan-in slot; slot 0 = MSB.

    ``pack_index`` is a dot against this vector, which is also what the
    fused cascade kernel (kernels/lut_cascade.py) scatters into its
    per-layer shift matrices.
    """
    return np.asarray([1 << (beta * (fan_in - 1 - j))
                       for j in range(fan_in)], np.int32)


def pack_index(codes: jax.Array, beta: int) -> jax.Array:
    """codes: (..., F) -> LUT addresses; slot 0 = MSB.

    Vectorized as a single dot against the precomputed ``beta``-shift
    vector (no per-slot Python loop): addresses are a linear function of
    the codes, ``addr = sum_j codes[..., j] << (beta * (F-1-j))``.
    """
    f = codes.shape[-1]
    w = jnp.asarray(shift_weights(beta, f))
    return codes.astype(jnp.int32) @ w


def packed_slots(beta: int) -> int:
    """Codes per int32 word when bit-packing ``beta``-bit codes.

    The largest power of two <= 32 // beta: a power of two so the mux
    tree's word select consumes whole address bits (the low ``log2(P)``
    address bits index inside the word)."""
    if not 1 <= beta <= 16:
        raise ValueError(f"beta={beta} not packable into int32 words")
    return 1 << ((32 // beta).bit_length() - 1)


def pack_tables(table: np.ndarray, beta: int) -> np.ndarray:
    """(O, T) beta-bit codes -> (O, T // P) int32 bit-packed words.

    Word ``w`` holds table entries ``w*P + p`` for p in [0, P); entry p
    occupies bits [beta*p, beta*(p+1)).  P = ``packed_slots(beta)``, so
    the footprint shrinks by P (8x for beta=4, 16x for beta=2)."""
    p = packed_slots(beta)
    t = np.asarray(table)
    if t.ndim != 2:
        raise ValueError(f"table must be (O, T), got {t.shape}")
    o, n = t.shape
    if n % p:
        raise ValueError(f"table size {n} not a multiple of P={p} "
                         f"(beta={beta})")
    if t.size and (t.min() < 0 or t.max() >= (1 << beta)):
        raise ValueError(f"table values outside [0, 2^{beta})")
    grouped = t.astype(np.uint32).reshape(o, n // p, p)
    words = np.zeros((o, n // p), np.uint32)
    for j in range(p):
        words |= grouped[:, :, j] << np.uint32(beta * j)
    return words.view(np.int32)


def pack_tables_jnp(table: jax.Array, beta: int) -> jax.Array:
    """Device-side twin of :func:`pack_tables`: (O, T) codes -> (O, T//P)
    int32 words, bit-identical to the numpy packer.

    Runs inside the fused truth-table sweep (core/truth_table.py) so
    freshly converted bundles come off the device already bit-packed and
    ``ServeBundle.prepack`` has nothing left to do.  The OR-accumulation
    is a small unrolled loop over the P slots (P <= 16); the uint32 ->
    int32 reinterpret is a bitcast, not a value conversion.
    """
    p = packed_slots(beta)
    o, n = table.shape
    if n % p:
        raise ValueError(f"table size {n} not a multiple of P={p} "
                         f"(beta={beta})")
    grouped = table.astype(jnp.uint32).reshape(o, n // p, p)
    words = grouped[..., 0]
    for j in range(1, p):
        words = words | (grouped[..., j] << jnp.uint32(beta * j))
    return jax.lax.bitcast_convert_type(words, jnp.int32)


def unpack_tables(packed: np.ndarray, beta: int, *,
                  table_size: Optional[int] = None) -> np.ndarray:
    """Inverse of ``pack_tables``: (O, Tw) int32 -> (O, Tw * P) uint16."""
    p = packed_slots(beta)
    w = np.asarray(packed).view(np.uint32)
    o, nw = w.shape
    mask = np.uint32((1 << beta) - 1)
    cols = [(w >> np.uint32(beta * j)) & mask for j in range(p)]
    out = np.stack(cols, axis=-1).reshape(o, nw * p).astype(np.uint16)
    if table_size is not None:
        out = out[:, :table_size]
    return out


def input_codes(cfg: NeuraLUTConfig, params: Params, x: jax.Array) -> jax.Array:
    beta_in = cfg.beta_in or cfg.beta
    return quant.quant_codes(params["in_quant"], x, beta_in)


def lut_forward(cfg: NeuraLUTConfig, tables: List[np.ndarray],
                statics: List[Dict], codes: jax.Array) -> jax.Array:
    """codes: (B, in_features) int32 -> (B, classes) output codes."""
    c = codes
    for i in range(cfg.num_layers):
        beta_in = cfg.layer_in_bits(i)
        conn = jnp.asarray(statics[i]["conn"])
        gathered = c[:, conn]                      # (B, O, F)
        addr = pack_index(gathered, beta_in)       # (B, O)
        tbl = jnp.asarray(tables[i].astype(np.int32))  # (O, T)
        c = tbl[jnp.arange(tbl.shape[0])[None, :], addr].astype(jnp.int32)
    return c


def graph_lut_forward(cfg: LUTGraphConfig, tables: List, statics: List[Dict],
                      codes: jax.Array) -> jax.Array:
    """Per-node LUT-DAG oracle: codes (B, in_features) int32 -> (B,
    classes) output codes.

    ``tables[i]`` is the node's per-branch table list (a bare array is
    accepted for arity-1 nodes); ``statics[i]`` carries ``"conns"`` (or
    the legacy ``"conn"``).  Each branch looks its beta-bit code up in
    its own table over the node's concatenated source pool; an
    adder-tree node *sums* the branch codes — by the shared-quantizer
    contract the sum IS the node's (beta + log2 A)-bit output code.
    For degenerate chains this computes exactly :func:`lut_forward`.
    """
    bufs = [codes.astype(jnp.int32)]
    for i, nd in enumerate(cfg.nodes):
        srcs = cfg.node_sources(i)
        pool = (bufs[srcs[0]] if len(srcs) == 1
                else jnp.concatenate([bufs[s] for s in srcs], axis=1))
        in_bits = cfg.node_in_bits(i)
        conns = (statics[i]["conns"] if "conns" in statics[i]
                 else [statics[i]["conn"]])
        tbls = (tables[i] if isinstance(tables[i], (list, tuple))
                else [tables[i]])
        out = None
        for a in range(nd.arity):
            conn = jnp.asarray(np.asarray(conns[a]))
            gathered = pool[:, conn]                   # (B, O, F)
            addr = pack_index(gathered, in_bits)       # (B, O)
            tbl = jnp.asarray(np.asarray(tbls[a]).astype(np.int32))
            c = tbl[jnp.arange(tbl.shape[0])[None, :], addr
                    ].astype(jnp.int32)
            out = c if out is None else out + c
        bufs.append(out)
    return bufs[-1]


def class_values(cfg, params: Params, out_codes: jax.Array
                 ) -> jax.Array:
    """Dequantize final-layer codes -> comparable class scores."""
    s = jnp.exp(params["layers"][-1]["quant"]["log_s"])
    return (out_codes.astype(jnp.float32) - 2 ** (cfg.beta - 1)) * s


def lut_outputs(cfg, tables, statics, codes: jax.Array) -> jax.Array:
    """The oracle for ``cfg``: :func:`graph_lut_forward` for a LUT graph,
    :func:`lut_forward` for a chain."""
    fwd = graph_lut_forward if is_graph_config(cfg) else lut_forward
    return fwd(cfg, tables, statics, codes)


def predict(cfg, params: Params, tables, statics,
            x: jax.Array) -> jax.Array:
    out = lut_outputs(cfg, tables, statics, input_codes(cfg, params, x))
    return jnp.argmax(class_values(cfg, params, out), axis=-1)
