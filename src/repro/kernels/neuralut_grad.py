"""Pallas TPU kernel: fused fwd+bwd grouped sub-network *training* step.

`kernels/neuralut_mlp.py` fuses the grouped-subnet **inference** pass in
VMEM; this module is its training twin.  The per-layer dW/dx einsums of
the grouped subnet dominate a JSC-5L training step on the jnp routes —
each of the L sub-layers round-trips its (B, O, N) activations and
cotangents through HBM twice (fwd + bwd).  Here one forward launch
evaluates all L sub-layers (+ skip chunks) for a (Bt, Ot) tile entirely
in VMEM and *saves the per-layer activations* as it goes; one backward
launch reloads those activations and produces dW/db/dx for every
sub-layer, accumulating the weight gradients across batch tiles inside
the kernel grid (the B tile is the innermost, fastest-moving grid dim,
so each O-tile's gradient block stays resident while its batch partials
accumulate).

The pair is wired up as a ``jax.custom_vjp`` op (``subnet_train_op``):
the forward primal runs the inference kernel's body, and the backward
matches ``jax.grad`` of the jnp einsum path (the gradient oracle,
tests/test_train_kernel.py) to float32 tolerance — the only divergence
is f32 summation order.

Both launches use the inference kernel's lane-dense layout (neurons on
lanes, batch on sublanes, an (n, Bt, Ot) block per activation — see
kernels/neuralut_mlp.py).  Saved residuals: the input to every
sub-layer ``i >= 1`` (the post-ReLU activation ``a_i``, (n_i, B, O));
layer 0's input is the gathered ``xg``.  ReLU masks are recovered from
the post-activation sign (``a > 0`` ⇔ pre-activation ``> 0``, matching
``jax.nn.relu``'s zero subgradient at 0), so no pre-activation copies
are stored.

The caller-facing layout is the canonical one: xg (B, O, F), layer i
w (O, n_i, n_{i+1}), b (O, n_{i+1}); skip chunk c has r (O, n_{cS},
n_{(c+1)S}).  The last layer has n_out == 1; the primal output is
(B, O).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.neuralut_mlp import (auto_blocks, canonical_weight,
                                        forward_body, kernel_operands,
                                        param_spec)


class GradMeta(NamedTuple):
    """Static geometry of one fused fwd+bwd launch (custom_vjp
    nondiff arg — must stay hashable)."""
    nlayers: int
    skip: int
    block_b: int
    block_o: int
    interpret: Optional[bool]  # None -> compiled on TPU/GPU, else interp


def _interp(meta: GradMeta) -> bool:
    if meta.interpret is None:
        from repro.core.exec_plan import kernel_compiled
        return not kernel_compiled()
    return meta.interpret


def _act_spec(n: int, bb: int, bo: int) -> pl.BlockSpec:
    return pl.BlockSpec((n, bb, bo), lambda j, i: (0, i, j))


# ---------------------------------------------------------------------------
# forward: inference math + saved per-layer activations


def _fwd_kernel(nlayers: int, skip: int, *refs):
    """refs: x, w_0, b_0..w_{L-1}, b_{L-1} [, r_0, rb_0, ...],
    out, act_1..act_{L-1}."""
    nparams = 2 * nlayers + (2 * (nlayers // skip) if skip else 0)
    out_ref = refs[1 + nparams]
    act_refs = refs[2 + nparams:]

    def save(i, h):  # input to sub-layer i (i >= 1)
        act_refs[i - 1][...] = h

    out_ref[...] = forward_body(refs[0][...].astype(jnp.float32),
                                refs[1:1 + nparams], nlayers, skip, save)


def _forward(meta: GradMeta, xg, layer_ws, layer_bs, skip_ws, skip_bs):
    """-> (out (B, O), residuals in kernel layout)."""
    b, o, f = xg.shape
    bb, bo = meta.block_b, meta.block_o
    if b % bb or o % bo:
        raise ValueError(f"(B={b}, O={o}) not divisible by ({bb}, {bo})")
    x, params = kernel_operands(xg, layer_ws, layer_bs, skip_ws, skip_bs)
    widths = [w.shape[1] for w in layer_ws]  # inputs of sub-layers

    out_shapes = [jax.ShapeDtypeStruct((b, o), jnp.float32)]
    out_specs = [pl.BlockSpec((bb, bo), lambda j, i: (i, j))]
    for n in widths[1:]:
        out_shapes.append(jax.ShapeDtypeStruct((n, b, o), jnp.float32))
        out_specs.append(_act_spec(n, bb, bo))

    outs = pl.pallas_call(
        functools.partial(_fwd_kernel, meta.nlayers, meta.skip),
        grid=(o // bo, b // bb),  # B tiles innermost (matches backward)
        in_specs=[_act_spec(f, bb, bo)] + [param_spec(p, bo)
                                           for p in params],
        out_specs=out_specs,
        out_shape=out_shapes,
        interpret=_interp(meta),
        name="subnet_train_fwd",
    )(x, *params)
    return outs[0], (x, tuple(outs[1:]), tuple(params[0::2]))


# ---------------------------------------------------------------------------
# backward: dx, dW, db for every sub-layer and skip chunk in one launch


def _dense_bwd(a, g, w_ref, dw_ref, db_ref):
    """Through one grouped layer z[k] = sum_i a[i] w[i, k] + b[k]: add
    this batch tile's dW/db partials into the resident gradient blocks
    and return the (n_i, Bt, Ot) cotangent wrt the layer input ``a``.

    a: (n_i, Bt, Ot); g: (n_o, Bt, Ot) cotangent of z."""
    w = w_ref[...]
    db_ref[...] += jnp.sum(g, axis=1, keepdims=True)
    dw_ref[...] += jnp.stack([jnp.sum(a[i:i + 1] * g, axis=1, keepdims=True)
                              for i in range(a.shape[0])])
    return jnp.stack([jnp.sum(w[i] * g, axis=0)
                      for i in range(a.shape[0])])


def _bwd_kernel(nlayers: int, skip: int, *refs):
    """refs: g, x, act_1..act_{L-1}, w_0..w_{L-1} [, r_0..],
    dx, dw_0, db_0, .., dw_{L-1}, db_{L-1} [, dr_0, drb_0, ..]."""
    nch = (nlayers // skip) if skip else 0
    g_ref, x_ref = refs[0], refs[1]
    acts = refs[2:1 + nlayers]
    base = 1 + nlayers
    ws = refs[base:base + nlayers]
    rs = refs[base + nlayers:base + nlayers + nch]
    base += nlayers + nch
    dx_ref = refs[base]
    grads = refs[base + 1:]
    dws = [(grads[2 * i], grads[2 * i + 1]) for i in range(nlayers)]
    drs = [(grads[2 * (nlayers + c)], grads[2 * (nlayers + c) + 1])
           for c in range(nch)]

    @pl.when(pl.program_id(1) == 0)
    def _():
        for ref in grads:
            ref[...] = jnp.zeros(ref.shape, ref.dtype)

    x = x_ref[...].astype(jnp.float32)

    def a_in(i):  # input to sub-layer i (saved activation, or x)
        return x if i == 0 else acts[i - 1][...]

    def through_layer(i, gm):
        a = a_in(i)
        return _dense_bwd(a, gm, ws[i], *dws[i]), a

    gh = g_ref[...][None]
    if skip == 0:
        gm = gh
        for i in range(nlayers - 1, -1, -1):
            gm, a = through_layer(i, gm)
            if i > 0:
                gm = gm * (a > 0.0)
        dx_ref[...] = gm
        return
    gout = gh
    for c in range(nch - 1, -1, -1):
        hc = a_in(c * skip)
        ghc = _dense_bwd(hc, gout, rs[c], *drs[c])
        gm = gout
        for i in range((c + 1) * skip - 1, c * skip - 1, -1):
            gm, a = through_layer(i, gm)
            if i > c * skip:
                gm = gm * (a > 0.0)
        ghc = ghc + gm
        if c > 0:
            gout = ghc * (hc > 0.0)  # inter-chunk ReLU boundary
        else:
            dx_ref[...] = ghc


def _backward(meta: GradMeta, g, res):
    x, acts, wks = res
    f, b, o = x.shape
    bb, bo = meta.block_b, meta.block_o
    nl = meta.nlayers
    grad_shapes = []
    for wk in wks:  # dw (n_i, n_o, 1, O), db (n_o, 1, O)
        grad_shapes += [wk.shape, wk.shape[1:]]

    outs = pl.pallas_call(
        functools.partial(_bwd_kernel, nl, meta.skip),
        grid=(o // bo, b // bb),
        in_specs=([pl.BlockSpec((bb, bo), lambda j, i: (i, j)),
                   _act_spec(f, bb, bo)]
                  + [_act_spec(a.shape[0], bb, bo) for a in acts]
                  + [param_spec(wk, bo) for wk in wks]),
        out_specs=[_act_spec(f, bb, bo)] + [
            param_spec(jax.ShapeDtypeStruct(s, jnp.float32), bo)
            for s in grad_shapes],
        out_shape=[jax.ShapeDtypeStruct(s, jnp.float32)
                   for s in [x.shape] + grad_shapes],
        interpret=_interp(meta),
        name="subnet_train_bwd",
    )(g, x, *acts, *wks)
    dws = tuple(canonical_weight(d) for d in outs[1::2])
    dbs = tuple(d[:, 0, :].T for d in outs[2::2])
    return outs[0].transpose(1, 2, 0), dws[:nl], dbs[:nl], dws[nl:], \
        dbs[nl:]


# ---------------------------------------------------------------------------
# custom_vjp wiring


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def subnet_train_op(meta: GradMeta, xg, layer_ws, layer_bs,
                    skip_ws, skip_bs):
    """Differentiable fused grouped-subnet evaluation.

    xg (B, O, F) + per-layer/skip weight tuples -> (B, O) float32.
    Forward and backward each run as ONE Pallas launch per call (see
    module docstring); ``jax.grad`` through this op matches the jnp
    einsum path to float32 tolerance.
    """
    out, _ = _forward(meta, xg, layer_ws, layer_bs, skip_ws, skip_bs)
    return out


def _train_fwd(meta, xg, layer_ws, layer_bs, skip_ws, skip_bs):
    return _forward(meta, xg, layer_ws, layer_bs, skip_ws, skip_bs)


def _train_bwd(meta, res, g):
    return _backward(meta, g, res)


subnet_train_op.defvjp(_train_fwd, _train_bwd)


def subnet_train_meta(b: int, o: int, nlayers: int, skip: int, *,
                      interpret: Optional[bool] = None) -> GradMeta:
    """GradMeta with the tiles :func:`neuralut_mlp.auto_blocks` derives
    from the (B, O) shape."""
    bb, bo = auto_blocks(b, o)
    return GradMeta(nlayers=nlayers, skip=skip, block_b=bb, block_o=bo,
                    interpret=interpret)
