"""Pallas TPU kernel: fused multi-layer LUT-cascade inference.

A converted NeuraLUT model is *nothing but* a cascade of table lookups
(one per neuron per layer).  The per-layer serving path dispatches a
gather + address pack + lookup per layer and round-trips the (B, O) code
tensor through HBM between layers; this kernel runs the **entire
multi-layer network per batch tile without leaving VMEM**:

  * every layer's connectivity gather + address pack is fused into one
    f32 *shift-matmul*: ``addr = codes @ S_i`` where ``S_i`` is the
    (W_{i-1}, O_i) matrix scattering ``2^{beta*(F-1-j)}`` at
    ``(conn[o, j], o)`` (see :func:`build_shift_mats`).  Addresses are
    < 2^20 (guarded at conversion time), so the f32 accumulate is exact;

  * tables live in VMEM **bit-packed**: ``beta``-bit output codes packed
    ``P = packed_slots(beta)`` per int32 word (~8x smaller for beta=4),
    so the whole table stack of every paper model fits on-chip;

  * the lookup is the same vectorized binary mux tree as lut_gather.py,
    but over packed *words*: the high ``log2(T/P)`` address bits drive
    the tree, the low ``log2(P)`` bits select inside the word with a
    per-lane logical shift;

  * intermediate codes are carried in registers/VMEM across all layers —
    one kernel launch for the whole network instead of ``3*num_layers``
    dispatches, and zero inter-layer HBM traffic.

Grid tiles the batch only; all per-layer shift matrices and packed
tables are whole-array VMEM operands (constant across the batch loop).
Non-divisible B is handled by internal padding.

The kernel walks a topologically-sorted **DAG schedule**, of which the
linear cascade is the degenerate chain: each node may read several
earlier buffers (concat realized as a sum of per-source shift-matmuls —
no on-chip concatenate) and may be an arity-A adder tree (A sub-LUT
branches whose looked-up codes are summed in VMEM before the next
node's shift-matmul — "one more VMEM-resident reduction").  For a chain
schedule the emitted op sequence is identical to the original per-layer
loop, so legacy callers are bit- and performance-identical.
"""
from __future__ import annotations

import functools
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core.lut_infer import pack_tables, packed_slots, shift_weights
from repro.core.nl_config import LUTGraphConfig

# Static per-layer geometry: (word_bits, slot_bits, beta_out) where
# word_bits = log2(T/P) drives the mux tree, slot_bits = log2(P) selects
# inside the packed word, beta_out is the stored code width.
LayerMeta = Tuple[int, int, int]

# Static per-node DAG geometry: (srcs, arity, word_bits, slot_bits,
# beta_out).  ``srcs`` are *buffer* indices — buffer 0 is the model
# input, buffer j+1 is node j's output — and the flat operand order is
# one shift matrix per (node, branch, src) and one packed table per
# (node, branch), nodes in schedule order.  A chain layer i is the
# degenerate node ((i,), 1, wb, sb, beta).
NodeSched = Tuple[Tuple[int, ...], int, int, int, int]


def as_schedule(meta) -> Tuple[NodeSched, ...]:
    """Normalize kernel geometry: legacy per-layer ``LayerMeta`` 3-tuples
    (``cascade_meta``) or a DAG schedule (``graph_cascade_meta``) ->
    the canonical ``NodeSched`` tuple (hashable, jit-static)."""
    out = []
    for i, m in enumerate(meta):
        if len(m) == 3:
            wb, sb, beta = m
            out.append(((i,), 1, int(wb), int(sb), int(beta)))
        else:
            srcs, arity, wb, sb, beta = m
            out.append((tuple(int(s) for s in srcs), int(arity),
                        int(wb), int(sb), int(beta)))
    return tuple(out)


def schedule_operand_counts(schedule) -> Tuple[int, int]:
    """(num shift mats, num packed tables) the schedule consumes."""
    sched = as_schedule(schedule)
    return (sum(a * len(srcs) for srcs, a, *_ in sched),
            sum(a for _, a, *_ in sched))


def build_shift_mats(cfg, statics: Sequence[dict]) -> List[np.ndarray]:
    """Per-layer (W_{i-1}, O_i) f32 matrices fusing gather + pack_index.

    ``S[conn[o, j], o] += 2^{beta_in*(F-1-j)}`` — duplicates in ``conn``
    accumulate, matching ``pack_index`` applied to the gathered codes.
    """
    mats = []
    w_prev = cfg.in_features
    for i in range(cfg.num_layers):
        conn = np.asarray(statics[i]["conn"])  # (O, F)
        o, f = conn.shape
        w = shift_weights(cfg.layer_in_bits(i), f).astype(np.float32)
        sm = np.zeros((w_prev, o), np.float32)
        np.add.at(sm, (conn, np.broadcast_to(np.arange(o)[:, None],
                                             conn.shape)), w[None, :])
        mats.append(sm)
        w_prev = o
    return mats


def cascade_tables(cfg, tables: Sequence[np.ndarray]) -> List[np.ndarray]:
    """Bit-pack every layer's table with its output code width."""
    return [pack_tables(np.asarray(t), cfg.beta) for t in tables]


def cascade_meta(cfg) -> Tuple[LayerMeta, ...]:
    """Static kernel geometry per layer, derived from the config."""
    meta = []
    for i in range(cfg.num_layers):
        t = cfg.table_size(i)
        p = packed_slots(cfg.beta)
        if t % p:
            raise ValueError(f"layer {i}: table size {t} not a multiple "
                             f"of packed word capacity {p}")
        word_bits = (t // p).bit_length() - 1
        slot_bits = p.bit_length() - 1
        meta.append((word_bits, slot_bits, cfg.beta))
    return tuple(meta)


def graph_cascade_meta(cfg: LUTGraphConfig) -> Tuple[NodeSched, ...]:
    """Static DAG kernel geometry, derived from the graph config alone
    (source indices and table sizes are config-level; only the shift
    matrices depend on the sampled connectivity)."""
    sched = []
    p = packed_slots(cfg.beta)
    for i, nd in enumerate(cfg.nodes):
        t = cfg.table_size(i)
        if t % p:
            raise ValueError(f"node {i}: table size {t} not a multiple "
                             f"of packed word capacity {p}")
        sched.append((cfg.node_sources(i), nd.arity,
                      (t // p).bit_length() - 1, p.bit_length() - 1,
                      cfg.beta))
    return tuple(sched)


def build_graph_shift_mats(cfg: LUTGraphConfig, statics: Sequence[dict]
                           ) -> List[np.ndarray]:
    """Flat shift matrices in (node, branch, src) order.

    Each branch's scatter is built over the node's concatenated source
    pool and then split back per source buffer, so the kernel can sum
    per-source dots instead of concatenating buffers on chip.  For a
    degenerate chain this returns exactly :func:`build_shift_mats`.
    """
    from repro.core.model import node_static_conns
    mats: List[np.ndarray] = []
    for i, nd in enumerate(cfg.nodes):
        srcs = cfg.node_sources(i)
        widths = [cfg.buffer_width(b) for b in srcs]
        offsets = np.concatenate([[0], np.cumsum(widths)]).astype(int)
        pool_w = int(offsets[-1])
        w = shift_weights(cfg.node_in_bits(i), nd.fan_in
                          ).astype(np.float32)
        for conn in node_static_conns(statics[i])[:nd.arity]:
            conn = np.asarray(conn)
            o = conn.shape[0]
            sm = np.zeros((pool_w, o), np.float32)
            np.add.at(sm, (conn, np.broadcast_to(
                np.arange(o)[:, None], conn.shape)), w[None, :])
            for s in range(len(srcs)):
                mats.append(np.ascontiguousarray(
                    sm[offsets[s]:offsets[s + 1]]))
    return mats


def graph_cascade_tables(cfg: LUTGraphConfig, tables: Sequence
                         ) -> List[np.ndarray]:
    """Bit-pack per-node branch tables into the flat (node, branch)
    kernel operand order.  ``tables[i]`` may be a bare array (arity-1
    node) or the per-branch list."""
    out: List[np.ndarray] = []
    for i in range(cfg.num_layers):
        t = tables[i]
        branches = t if isinstance(t, (list, tuple)) else [t]
        for b in branches:
            out.append(pack_tables(np.asarray(b), cfg.beta))
    return out


def _mux_word(packed: jax.Array, wsel: jax.Array, word_bits: int
              ) -> jax.Array:
    """Binary mux tree over packed words.

    packed: (O, Tw) int32; wsel: (Bt, O) word index -> (Bt, O) int32.
    MSB-first halving; the first ``where`` broadcasts the (1, O, Tw)
    table against the per-(token, neuron) bit, so the working set is
    bounded by Bt*O*Tw/2 from level one on.
    """
    live = packed[None]  # (1, O, Tw)
    for k in range(word_bits):
        half = live.shape[-1] // 2
        bit = (wsel >> (word_bits - 1 - k)) & 1  # (Bt, O)
        live = jnp.where(bit[..., None] == 1, live[..., half:],
                         live[..., :half])
    bt, o = wsel.shape
    return jnp.broadcast_to(live[..., 0], (bt, o))


def _cascade_kernel(schedule: Tuple[NodeSched, ...], *refs):
    """refs: codes, then per node / branch: shift mats (one per src)
    followed by the branch's packed table; out last.

    Buffers ride between nodes as exact small f32 integers (the next
    shift-matmul feeds the MXU directly); a buffer is dropped as soon
    as no later node reads it, so a chain keeps exactly one live buffer
    — the original per-layer kernel's working set.
    """
    out_ref = refs[-1]
    bufs: List[Optional[jax.Array]] = [refs[0][...].astype(jnp.float32)]
    last_use = {0: 0}
    for n, (srcs, *_rest) in enumerate(schedule):
        for s in srcs:
            last_use[s] = n
    r = 1
    for n, (srcs, arity, word_bits, slot_bits, beta) in enumerate(schedule):
        node_code = None
        for _a in range(arity):
            addr_f = None
            for s in srcs:
                sm = refs[r][...]           # (W_src, O) f32
                r += 1
                d = jnp.dot(bufs[s], sm,
                            preferred_element_type=jnp.float32)
                addr_f = d if addr_f is None else addr_f + d
            packed = refs[r][...]           # (O, Tw) int32
            r += 1
            addr = addr_f.astype(jnp.int32)  # exact: addr < 2^20 << 2^24
            wsel = jax.lax.shift_right_logical(addr, slot_bits)
            slot = addr & ((1 << slot_bits) - 1)
            word = _mux_word(packed, wsel, word_bits)
            code = jax.lax.shift_right_logical(word, beta * slot) \
                & ((1 << beta) - 1)
            node_code = code if node_code is None else node_code + code
        for s in set(srcs):
            if last_use[s] == n:
                bufs[s] = None
        bufs.append(node_code.astype(jnp.float32))
    out_ref[...] = bufs[-1].astype(out_ref.dtype)


def lut_cascade(
    codes: jax.Array,                      # (B, W_0) int32 input codes
    shift_mats: Sequence[jax.Array],       # flat (node, branch, src) order
    packed_tables: Sequence[jax.Array],    # flat (node, branch) order
    meta,                                  # cascade_meta / graph_cascade_meta
    *,
    block_b: int = 8,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Returns (B, O_last) int32 output codes of the whole LUT network
    — chain or DAG — in ONE launch.

    ``meta`` is either the legacy per-layer ``cascade_meta(cfg)`` or a
    DAG ``graph_cascade_meta(cfg)`` schedule (``as_schedule`` normalizes
    both).  Bit-exact vs ``lut_infer.lut_forward`` /
    ``graph_lut_forward`` (the oracles) for any valid (tables, statics)
    pair.  ``interpret=None`` auto-selects: compiled on TPU,
    interpreter elsewhere.
    """
    meta = as_schedule(meta)
    n_sm, n_pt = schedule_operand_counts(meta)
    if len(shift_mats) != n_sm or len(packed_tables) != n_pt:
        raise ValueError(
            f"schedule consumes {n_sm} shift mats / {n_pt} packed tables, "
            f"got {len(shift_mats)} / {len(packed_tables)}")
    if interpret is None:
        from repro.core.exec_plan import detect_backend
        interpret = detect_backend() != "tpu"
    b = codes.shape[0]
    block_b = min(block_b, b)
    pad_b = (-b) % block_b
    if pad_b:
        codes = jnp.pad(codes, ((0, pad_b), (0, 0)))
    bp = b + pad_b
    o_last = packed_tables[-1].shape[0]

    in_specs = [pl.BlockSpec((block_b, codes.shape[1]), lambda i: (i, 0))]
    operands = [codes.astype(jnp.int32)]
    sm_i = pt_i = 0
    # Operands interleave exactly as the kernel consumes them: per node,
    # per branch, the per-src shift mats then the branch's packed table.
    for srcs, arity, *_rest in meta:
        for _a in range(arity):
            for _s in srcs:
                sm = shift_mats[sm_i]
                sm_i += 1
                in_specs.append(pl.BlockSpec(sm.shape, lambda i: (0, 0)))
                operands.append(sm.astype(jnp.float32))
            tw = packed_tables[pt_i]
            pt_i += 1
            in_specs.append(pl.BlockSpec(tw.shape, lambda i: (0, 0)))
            operands.append(tw.astype(jnp.int32))

    out = pl.pallas_call(
        functools.partial(_cascade_kernel, meta),
        grid=(bp // block_b,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_b, o_last), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((bp, o_last), jnp.int32),
        interpret=interpret,
        name="lut_cascade",
    )(*operands)
    return out[:b] if pad_b else out
