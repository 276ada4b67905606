"""Pallas TPU kernel: fused grouped sub-network evaluation.

The paper hides a dense MLP inside an FPGA LUT; the TPU analogue is hiding
the whole sub-network in VMEM: one kernel invocation loads a tile of
gathered inputs plus ALL layer/skip weights for a tile of neurons, runs
the L-layer (skip-connected) MLP entirely in VMEM, and writes only the
(Bt, Ot) result — eliminating the L x (B, O, N)-sized HBM round-trips an
einsum-per-layer implementation performs.

Kernel layout (lane-dense): every neuron has its own weights, so there
is no shared matrix to feed the MXU — a sub-network layer is O tiny
independent (n_in x n_out) products.  The kernel therefore puts the
neurons on the 128 lanes and the batch on the sublanes: an activation is
an (n, Bt, Ot) block, one (Bt, Ot) tile per hidden unit, and a layer is
n_in broadcast multiply-adds on the VPU, each over all n_out units at
once.  No operand is padded beyond its (8, 128) tile whatever F and N
are (a minor dimension of size F or N would pad to 128 lanes and blow
the VMEM budget at full width).

Operands in kernel layout (``kernel_operands``):
  x      (F, B, O)          gathered inputs, feature-major
  w      (n_i, n_o, 1, O)   w[i, k, 0] is the lane row of w[:, i, k]
  b      (n_o, 1, O)
The caller-facing layout stays the canonical one: xg (B, O, F), layer i
w (O, n_i, n_{i+1}), b (O, n_{i+1}); skip chunk c has r (O, n_{cS},
n_{(c+1)S}).  The last layer has n_out == 1; output is (B, O).
"""
from __future__ import annotations

import functools
from typing import Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128
SUBLANES = 8
# Batch-tile cap.  The kernel body is unrolled per hidden unit, so its
# Mosaic compile grows with the tile: 32 rows keeps a full-width
# training kernel near 4 s (about 13 s at 128).  Not tuned for speed.
MAX_BLOCK_B = 32


def kernel_weight(w: jax.Array) -> jax.Array:
    """(O, n_i, n_o) -> (n_i, n_o, 1, O): one lane row per (i, k)."""
    return w.transpose(1, 2, 0)[:, :, None, :]


def canonical_weight(wk: jax.Array) -> jax.Array:
    """Inverse of :func:`kernel_weight`."""
    return wk[:, :, 0, :].transpose(2, 0, 1)


def kernel_operands(xg, layer_ws, layer_bs, skip_ws, skip_bs):
    """Canonical operands -> kernel layout: ((F, B, O) inputs, flat
    [w, b, ...] for every layer then every skip chunk)."""
    params = []
    for w, b in zip(list(layer_ws) + list(skip_ws or ()),
                    list(layer_bs) + list(skip_bs or ())):
        params += [kernel_weight(w), b.T[:, None, :]]
    return xg.transpose(2, 0, 1), params


def param_spec(p, block_o: int) -> pl.BlockSpec:
    """Block of a kernel-layout weight or bias: all rows, one O tile."""
    nd = p.ndim
    return pl.BlockSpec(p.shape[:-1] + (block_o,),
                        lambda j, i: (0,) * (nd - 1) + (j,))


def relu(h: jax.Array) -> jax.Array:
    return jnp.maximum(h, 0.0)


def dense(h: jax.Array, w_ref, b_ref) -> jax.Array:
    """One grouped layer on the VPU: z[k] = sum_i h[i] * w[i, k] + b[k].

    h: (n_i, Bt, Ot); w_ref: (n_i, n_o, 1, Ot); b_ref: (n_o, 1, Ot)
    -> (n_o, Bt, Ot)."""
    w = w_ref[...].astype(jnp.float32)
    acc = h[0:1] * w[0]
    for i in range(1, h.shape[0]):
        acc = acc + h[i:i + 1] * w[i]
    return acc + b_ref[...].astype(jnp.float32)


def forward_body(x: jax.Array, params: Sequence, nlayers: int, skip: int,
                 save: Optional[Callable] = None) -> jax.Array:
    """The sub-network on a kernel-layout (F, Bt, Ot) tile -> (Bt, Ot).

    ``params`` is the flat (w, b) ref list of :func:`kernel_operands`;
    ``save(i, h)`` receives the (n_i, Bt, Ot) input of every sub-layer
    i >= 1 (the training kernel's residuals)."""
    ws = [(params[2 * i], params[2 * i + 1]) for i in range(nlayers)]
    nch = (nlayers // skip) if skip else 0
    rs = [(params[2 * (nlayers + c)], params[2 * (nlayers + c) + 1])
          for c in range(nch)]
    h = x
    if skip == 0:
        for i, (w, b) in enumerate(ws):
            if i > 0 and save is not None:
                save(i, h)
            h = dense(h, w, b)
            if i < nlayers - 1:
                h = relu(h)
        return h[0]
    for c in range(nch):
        if c > 0 and save is not None:
            save(c * skip, h)
        res = dense(h, *rs[c])
        hh = h
        for j in range(skip):
            i = c * skip + j
            if j > 0 and save is not None:
                save(i, hh)
            hh = dense(hh, *ws[i])
            if j < skip - 1:
                hh = relu(hh)
        h = hh + res
        if c < nch - 1:
            h = relu(h)
    return h[0]


def _kernel(nlayers: int, skip: int, *refs):
    """refs: x, w_0, b_0, ..., [r_0, rb_0, ...], out."""
    x = refs[0][...].astype(jnp.float32)
    refs[-1][...] = forward_body(x, refs[1:-1], nlayers, skip
                                 ).astype(refs[-1].dtype)


def auto_blocks(b: int, o: int) -> Tuple[int, int]:
    """(block_b, block_o) for a (B, O, F) operand, from the shape alone.

    Neurons sit on the lanes: a 128-lane tile when O is a multiple of
    128, else the whole O (a block dimension equal to the array's is
    always legal).  The batch sits on the sublanes: the largest
    power-of-two divisor of B up to ``MAX_BLOCK_B`` when that is a
    multiple of 8, else the whole B.  Both tile B and O exactly."""
    bo = LANES if o % LANES == 0 else o
    bb = min(b & -b, MAX_BLOCK_B)
    if bb % SUBLANES and bb != b:
        bb = b
    return bb, bo


def grouped_subnet(
    xg: jax.Array,                       # (B, O, F)
    layer_ws: Sequence[jax.Array],       # each (O, n_i, n_{i+1})
    layer_bs: Sequence[jax.Array],
    skip_ws: Optional[Sequence[jax.Array]] = None,
    skip_bs: Optional[Sequence[jax.Array]] = None,
    *,
    skip: int = 0,
    block_b: Optional[int] = None,
    block_o: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Fused sub-network evaluation; returns (B, O) float32.

    ``block_b``/``block_o`` default to :func:`auto_blocks`;
    ``interpret=None`` compiles where Pallas kernels compile
    (``core.exec_plan.kernel_compiled``) and interprets elsewhere."""
    b, o, f = xg.shape
    auto_b, auto_o = auto_blocks(b, o)
    block_b = min(block_b or auto_b, b)
    block_o = min(block_o or auto_o, o)
    if b % block_b or o % block_o:
        raise ValueError(f"(B={b}, O={o}) not divisible by "
                         f"({block_b}, {block_o})")
    if interpret is None:
        from repro.core.exec_plan import kernel_compiled
        interpret = not kernel_compiled()
    x, params = kernel_operands(xg, layer_ws, layer_bs, skip_ws, skip_bs)
    in_specs = [pl.BlockSpec((f, block_b, block_o), lambda j, i: (0, i, j))]
    in_specs += [param_spec(p, block_o) for p in params]
    return pl.pallas_call(
        functools.partial(_kernel, len(layer_ws), skip),
        grid=(o // block_o, b // block_b),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_b, block_o), lambda j, i: (i, j)),
        out_shape=jax.ShapeDtypeStruct((b, o), jnp.float32),
        interpret=interpret,
        name="subnet_infer",
    )(x, *params)
