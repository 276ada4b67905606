"""Pallas TPU kernel: truth-table lookup (LUT-network inference).

FPGA synthesis implements a >6-input L-LUT as LUT6 blocks + an F7/F8/LUT
mux tree; the TPU-native analogue is a *vectorized binary mux tree* over the
VMEM-resident table: for address bit k (MSB first) we halve the live table
slice by selecting the upper/lower half per (token, neuron) lane:

    live_0 = table tile (Ot, T)                     broadcast to (Bt, Ot, T)
    live_k = where(bit_k, live_{k-1}[..., T/2:], live_{k-1}[..., :T/2])
    out    = live_{log2 T}

All selects are dense vector ops (no data-dependent addressing, which the
VPU lacks); working set is bounded by the Bt tile: sum_k Bt*Ot*T/2^k ~=
2*Bt*Ot*T elements.  Grid tiles (B, O); table tiles live in VMEM across the
whole batch loop (constant operand).

This kernel is the serving hot path of the converted NeuraLUT model: one
lookup per neuron per token, entirely memory-resident.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(nbits: int, tbl_ref, addr_ref, out_ref):
    tbl = tbl_ref[...]            # (Ot, T) int32
    addr = addr_ref[...]          # (Bt, Ot) int32
    bt = addr.shape[0]
    live = jnp.broadcast_to(tbl[None], (bt,) + tbl.shape)  # (Bt, Ot, T)
    for k in range(nbits):
        half = live.shape[-1] // 2
        bit = (addr >> (nbits - 1 - k)) & 1  # (Bt, Ot)
        lo = live[..., :half]
        hi = live[..., half:]
        live = jnp.where(bit[..., None] == 1, hi, lo)
    out_ref[...] = live[..., 0].astype(out_ref.dtype)


def lut_lookup(
    tables: jax.Array,  # (O, T) int32, T = 2^nbits
    addr: jax.Array,    # (B, O) int32
    *,
    block_b: int = 8,
    block_o: int = 32,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Returns (B, O) int32 == tables[o, addr[b, o]].

    ``interpret=None`` auto-selects the backend: compiled on TPU/GPU,
    interpreter elsewhere.  Non-divisible B/O are padded internally and
    sliced back out (padded lanes read address 0 of a zero table row).
    """
    if interpret is None:
        from repro.core.exec_plan import kernel_compiled
        interpret = not kernel_compiled()
    o, t = tables.shape
    b = addr.shape[0]
    nbits = int(t).bit_length() - 1
    if 2 ** nbits != t:
        raise ValueError(f"table size {t} not a power of two")
    block_b = min(block_b, b)
    block_o = min(block_o, o)
    pad_b = (-b) % block_b
    pad_o = (-o) % block_o
    if pad_b or pad_o:
        addr = jnp.pad(addr, ((0, pad_b), (0, pad_o)))
        tables = jnp.pad(tables, ((0, pad_o), (0, 0)))
    bp, op = b + pad_b, o + pad_o

    out = pl.pallas_call(
        functools.partial(_kernel, nbits),
        grid=(bp // block_b, op // block_o),
        in_specs=[
            pl.BlockSpec((block_o, t), lambda i, j: (j, 0)),
            pl.BlockSpec((block_b, block_o), lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((block_b, block_o), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((bp, op), jnp.int32),
        interpret=interpret,
        name="lut_gather",
    )(tables.astype(jnp.int32), addr.astype(jnp.int32))
    return out[:b, :o] if (pad_b or pad_o) else out
