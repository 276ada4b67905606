"""The function hidden inside each L-LUT (paper §III-C, eqs. 1-7).

Three neuron kinds, all batched over the whole circuit layer (O neurons):

  * "subnet":  N_net of depth L, width N, skip period S — eq. (1)-(3):
        f = F_{L/S} o phi o F_{L/S-1} o ... o phi o F_1,
        F_i(x) = hatF_i(x) + R_i(x),
        hatF_i = A_{Si} o phi o ... o phi o A_{S(i-1)+1}
    (S=0: plain MLP, no skips.)
  * "linear":  LogicNets — affine (degenerate subnet with L=1).
  * "poly":    PolyLUT — all monomials of the F inputs up to degree D,
               then affine.

Parameter shapes carry a leading O dim; evaluation is grouped matmuls
('boi,oij->boj'), the compute hot-spot that kernels/neuralut_mlp.py fuses
with the connectivity gather on TPU.

``param_count_formula`` reproduces Table I / eqs. (5)-(7) and is checked
against the actual pytree in tests (property-based over F, L, N, S).
"""
from __future__ import annotations

import itertools
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.nl_config import NeuraLUTConfig

Params = Dict[str, Any]
HIGHEST = jax.lax.Precision.HIGHEST


def _widths(F: int, L: int, N: int) -> List[int]:
    """n_0=F, n_1..n_{L-1}=N, n_L=1 (paper: n_out=1 per L-LUT)."""
    return [F] + [N] * (L - 1) + [1]


def subnet_spec(out_width: int, F: int, L: int, N: int, S: int) -> Params:
    w = _widths(F, L, N)
    layers = [{
        "w": jax.ShapeDtypeStruct((out_width, w[i], w[i + 1]), jnp.float32),
        "b": jax.ShapeDtypeStruct((out_width, w[i + 1]), jnp.float32),
    } for i in range(L)]
    spec: Params = {"layers": layers}
    if S > 0:
        assert L % S == 0, (L, S)
        spec["skips"] = [{
            "w": jax.ShapeDtypeStruct((out_width, w[i * S], w[(i + 1) * S]),
                                      jnp.float32),
            "b": jax.ShapeDtypeStruct((out_width, w[(i + 1) * S]), jnp.float32),
        } for i in range(L // S)]
    return spec


def subnet_apply(p: Params, x: jax.Array, S: int, *,
                 batch_leading: bool = False) -> jax.Array:
    """x: (B, O, F) -> (B, O). phi = ReLU (eq. 4).

    ``batch_leading=True`` runs the stack in neuron-leading (O, B, n)
    layout — one transpose in, one out, and every grouped matmul becomes
    a layout-friendly batched GEMM (no per-op transposes; ~3x faster
    fwd+bwd on XLA:CPU, MXU batch dim on TPU).  The results agree with
    the canonical einsum to float32 rounding but are NOT guaranteed
    bit-identical; which layout (or Pallas kernel) runs where is decided
    by ``core.exec_plan.SubnetExec`` — conversion and eval stay on the
    canonical (B, O, n) einsum the tables are defined against.

    The einsums run at ``HIGHEST`` precision: the hidden function is
    float32 by definition, and a TPU's default matmul precision rounds
    f32 operands to bfloat16, which would part the eval forward from
    the kernel routes (float32 on the vector unit) by far more than
    rounding order.
    """
    if batch_leading:
        def mm(h, w, b):
            return jnp.einsum("obi,oij->obj", h, w,
                              precision=HIGHEST) + b[:, None, :]

        h = x.transpose(1, 0, 2)  # (O, B, F)
    else:
        def mm(h, w, b):
            return jnp.einsum("boi,oij->boj", h, w,
                              precision=HIGHEST) + b[None]

        h = x

    def squeeze(hh):
        return hh[..., 0].T if batch_leading else hh[..., 0]
    layers = p["layers"]
    L = len(layers)
    if S == 0:
        for i, lp in enumerate(layers):
            h = mm(h, lp["w"], lp["b"])
            if i < L - 1:
                h = jax.nn.relu(h)
        return squeeze(h)
    nchunks = L // S
    for c in range(nchunks):
        r = p["skips"][c]
        res = mm(h, r["w"], r["b"])
        hh = h
        for j in range(S):
            lp = layers[c * S + j]
            hh = mm(hh, lp["w"], lp["b"])
            if j < S - 1:
                hh = jax.nn.relu(hh)
        h = hh + res
        if c < nchunks - 1:
            h = jax.nn.relu(h)
    return squeeze(h)


def apply_hidden(kind: str, p: Params, x: jax.Array, *, skip: int = 0,
                 exps=None, batch_leading: bool = False) -> jax.Array:
    """Kind-level dispatch over the jnp evaluation paths.

    x: (B, O, F) -> (B, O).  Route selection (which layout, whether a
    Pallas kernel runs instead) lives one level up in
    ``core.exec_plan.SubnetExec``; this stays the shared jnp reference
    the conversion bit-exactness invariant rides on.
    """
    if kind == "linear":
        return linear_apply(p, x)
    if kind == "poly":
        return poly_apply(p, x, exps)
    return subnet_apply(p, x, skip, batch_leading=batch_leading)


# ---------------------------------------------------------------------------
# LogicNets-style linear neuron


def linear_spec(out_width: int, F: int) -> Params:
    return {"w": jax.ShapeDtypeStruct((out_width, F), jnp.float32),
            "b": jax.ShapeDtypeStruct((out_width,), jnp.float32)}


def linear_apply(p: Params, x: jax.Array) -> jax.Array:
    """x: (B, O, F) -> (B, O)."""
    return jnp.einsum("bof,of->bo", x, p["w"]) + p["b"]


# ---------------------------------------------------------------------------
# PolyLUT-style polynomial neuron


def monomial_exponents(F: int, D: int) -> np.ndarray:
    """All exponent vectors with total degree in [0, D]; C(F+D, D) rows."""
    rows = []
    for deg in range(D + 1):
        for combo in itertools.combinations_with_replacement(range(F), deg):
            e = np.zeros(F, np.int32)
            for i in combo:
                e[i] += 1
            rows.append(e)
    return np.stack(rows)


def poly_spec(out_width: int, F: int, D: int) -> Params:
    m = len(monomial_exponents(F, D))
    return {"w": jax.ShapeDtypeStruct((out_width, m), jnp.float32)}


def poly_apply(p: Params, x: jax.Array, exps: np.ndarray) -> jax.Array:
    """x: (B, O, F) -> (B, O) via monomial features.

    Monomials are built with masked repeated multiplication rather than
    ``jnp.power``: d/dx x**0 = 0 * x**-1 is NaN at the exact zeros that
    quantized activations produce.
    """
    exps = np.asarray(exps)
    m, f = exps.shape
    feats = jnp.ones(x.shape[:-1] + (m,), x.dtype)
    for j in range(f):
        col_max = int(exps[:, j].max())
        if col_max == 0:
            continue
        xj = x[..., j][..., None]          # (B, O, 1)
        ej = jnp.asarray(exps[:, j])[None, None, :]  # (1, 1, M)
        for k in range(1, col_max + 1):
            feats = feats * jnp.where(ej >= k, xj, jnp.ones_like(xj))
    return jnp.einsum("bom,om->bo", feats, p["w"])


# ---------------------------------------------------------------------------
# Table I / eqs. (5)-(7)


def t_affine(d1: int, d2: int) -> int:
    return d1 * d2 + d2


def param_count_formula(F: int, L: int, N: int, S: int) -> int:
    """T_N = T_A + T_R (eqs. 5-7)."""
    if L == 1:
        ta = F + 1
    elif L == 2:
        ta = (F + 2) * N + 1
    else:
        ta = (L - 2) * N * N + (F + L) * N + 1
    if S == 0:
        return ta
    c = L // S
    if c == 1:
        tr = F + 1
    elif c == 2:
        tr = (F + 2) * N + 1
    else:
        tr = (c - 2) * N * N + (F + c) * N + 1
    return ta + tr


def neuron_param_count(cfg: NeuraLUTConfig, layer_idx: int) -> int:
    F = cfg.layer_fan_in(layer_idx)
    if cfg.kind == "linear":
        return F + 1
    if cfg.kind == "poly":
        return len(monomial_exponents(F, cfg.degree))
    return param_count_formula(F, cfg.depth, cfg.width, cfg.skip)
