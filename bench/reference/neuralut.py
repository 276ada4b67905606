"""Plain reference of a NeuraLUT network, written from the paper
(arXiv:2403.00849, §III) and the configuration file alone.

It imports nothing of the program under test and takes no array the
program made: tables, connectivity, weights and quantizer scales are all
made by the benchmark from the seed and handed to both sides.  Training
and conversion are straightforward ``jax.numpy`` in float32; every
contraction of the hidden function runs at ``Precision.HIGHEST`` unless a
control asks for a lower precision (``PRECISIONS``).  Serving quantizes
and compares class values in float64 on the host (a device's ``exp`` and
division are approximate) and runs the integer cascade on the device.

Parameters use the same nested layout as the configuration's pytree:

    params = {"in_quant": {"log_s": (in,)},
              "layers": [{"fn": {"layers": [{"w": (O, n_i, n_i+1),
                                            "b": (O, n_i+1)}, ...],
                                 "skips": [{"w", "b"}, ...]},
                          "bn": {"g": (O,), "b": (O,)},
                          "quant": {"log_s": (O,)}}, ...]}
    state  = {"layers": [{"bn": {"mean": (O,), "var": (O,)}}, ...]}
"""
from __future__ import annotations

from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
BN_EPS = 1e-5

# Precisions a hidden function can be computed in: float32 (HIGHEST), and
# two steps below it for the controls: the three-pass bfloat16 product
# ("high") and bfloat16 operands with float32 accumulation ("bfloat16"),
# both emulated with explicit bfloat16 roundings so that they compute the
# same numbers on every backend.
PRECISIONS = ("highest", "high", "bfloat16")


def _split_bf16(a):
    hi = a.astype(jnp.bfloat16).astype(jnp.float32)
    lo = (a - hi).astype(jnp.bfloat16).astype(jnp.float32)
    return hi, lo


def contract(spec: str, a, b, precision: str = "highest"):
    """``einsum(spec, a, b)`` in the named precision."""
    if precision == "highest":
        return jnp.einsum(spec, a, b, precision=HIGHEST)
    ah, al = _split_bf16(a)
    bh, bl = _split_bf16(b)
    if precision == "bfloat16":     # products of bfloat16 values are exact
        return jnp.einsum(spec, ah, bh, precision=HIGHEST)
    if precision != "high":
        raise ValueError(f"unknown precision {precision!r}")
    return (jnp.einsum(spec, ah, bh, precision=HIGHEST)
            + jnp.einsum(spec, ah, bl, precision=HIGHEST)
            + jnp.einsum(spec, al, bh, precision=HIGHEST))


# ---------------------------------------------------------------------------
# quantizers (signed symmetric, learned per-channel scale)


def quant_codes(x, log_s, bits: int):
    """Unsigned codes in [0, 2^bits): clip(round(x / s)) + 2^(bits-1)."""
    s = jnp.exp(log_s)
    half = 2 ** (bits - 1)
    return jnp.clip(jnp.round(x / s), -half, half - 1).astype(jnp.int32) \
        + half


def fake_quant(x, log_s, bits: int):
    """Quantize-dequantize with a straight-through round."""
    s = jnp.exp(log_s)
    half = 2 ** (bits - 1)
    v = x / s
    r = v + jax.lax.stop_gradient(jnp.round(v) - v)
    return jnp.clip(r, -half, half - 1) * s


# ---------------------------------------------------------------------------
# the hidden function of every neuron of a layer


def subnet(fn: Dict, x, skip: int, precision: str = "highest"):
    """x: (B, O, F) -> (B, O).  Depth-L MLP per neuron with ReLU, and a
    residual affine map around every ``skip`` sub-layers (paper eqs. 1-3)."""
    layers = fn["layers"]

    def dense(h, p):
        return contract("boi,oij->boj", h, p["w"], precision) + p["b"][None]

    h = x
    if not skip:
        for i, p in enumerate(layers):
            h = dense(h, p)
            if i < len(layers) - 1:
                h = jax.nn.relu(h)
        return h[..., 0]
    chunks = len(layers) // skip
    for c in range(chunks):
        res = dense(h, fn["skips"][c])
        hh = h
        for j in range(skip):
            hh = dense(hh, layers[c * skip + j])
            if j < skip - 1:
                hh = jax.nn.relu(hh)
        h = hh + res
        if c < chunks - 1:
            h = jax.nn.relu(h)
    return h[..., 0]


# ---------------------------------------------------------------------------
# serving: the LUT cascade


def lut_forward(codes, tables: Sequence, conns: Sequence,
                in_bits: Sequence[int]):
    """codes (B, in) int32 -> (B, classes) output codes.  Each neuron's
    address is its F input codes concatenated, slot 0 most significant."""
    c = codes
    for tbl, conn, bits in zip(tables, conns, in_bits):
        f = conn.shape[1]
        g = c[:, conn]                                      # (B, O, F)
        addr = jnp.zeros(g.shape[:2], jnp.int32)
        for j in range(f):
            addr = addr * (1 << bits) + g[..., j]
        c = jnp.take_along_axis(tbl.T, addr, axis=0).astype(jnp.int32)
    return c


# The served quantizer and class values are float32 arithmetic.  Where a
# value lies within this share of its own size from a rounding boundary
# (or from another class's value), float32 evaluations that differ by a
# few units in the last place -- x / s against x * (1 / s), an exp rounded
# on the host against one approximated on the device -- rightly disagree,
# so either side is a correct answer.  2^-16 is 512 float32 ulps: above
# the device's exp error (about 2e-6), far below a bfloat16 rounding
# (2^-9), which the control makes.
FAITHFUL = 2.0 ** -16


def served_input_codes(x: np.ndarray, log_s: np.ndarray, bits: int):
    """Input codes in float64 on the host: (codes, other), both (N, in)
    int32.  ``other`` is the code on the other side of the rounding
    boundary where the value lies within ``FAITHFUL`` of it, else the
    code itself."""
    half = 2 ** (bits - 1)
    v = np.asarray(x, np.float64) / np.exp(np.asarray(log_s, np.float64))
    r, lo = np.round(v), np.floor(v)
    near = np.abs(v - lo - 0.5) <= FAITHFUL * np.abs(v)
    alt = np.where(near, np.where(r == lo, lo + 1, lo), r)

    def code(a):
        return (np.clip(a, -half, half - 1) + half).astype(np.int32)
    return code(r), code(alt)


def lut_outputs(codes: np.ndarray, model: Dict,
                block: int = 16384) -> np.ndarray:
    """The cascade on the device (integer arithmetic, exact on every
    backend), in blocks of one shape: (N, in) -> (N, classes) codes."""
    fn = jax.jit(lambda c, t, k: lut_forward(c, t, k, model["in_bits"]))
    block = min(block, len(codes))
    out = []
    for s in range(0, len(codes), block):
        cb = codes[s:s + block]
        pad = block - len(cb)
        if pad:
            cb = np.concatenate([cb, np.repeat(cb[:1], pad, 0)])
        out.append(np.asarray(fn(jnp.asarray(cb), model["tables"],
                                 model["conns"]))[:block - pad])
    return np.concatenate(out)


def served_gaps(x: np.ndarray, served: np.ndarray, model: Dict, *,
                lowp_inputs: bool = False) -> np.ndarray:
    """Per sample, how far the served class's value lies below the best
    class value of the reference: 0 where the served class is (one of)
    the best under some faithful float32 rounding of the sample's inputs.
    Each sample is read with its float64 codes, and where some codes lie
    within ``FAITHFUL`` of a boundary, also with each of those flipped and
    with all of them flipped; its gap is the least over these readings.
    ``lowp_inputs`` puts the control in the program's place: the class
    the reference picks from its inputs rounded to bfloat16."""
    x = np.asarray(x, np.float32)
    bits, beta = model["in_bits"][0], model["beta"]
    in_log_s = np.asarray(model["in_log_s"])
    out_s = np.exp(np.asarray(model["out_log_s"], np.float64))
    half = 2 ** (beta - 1)
    codes, alt = served_input_codes(x, in_log_s, bits)
    rows, owner = [codes], [np.arange(len(x))]
    flip = alt != codes
    for i in np.nonzero(flip.any(axis=1))[0]:
        js = np.nonzero(flip[i])[0]
        variants = [[j] for j in js] + ([list(js)] if len(js) > 1 else [])
        for v in variants:
            c = codes[i].copy()
            c[v] = alt[i, v]
            rows.append(c[None])
            owner.append(np.array([i]))
    allc, owner = np.concatenate(rows), np.concatenate(owner)
    vals = (lut_outputs(allc, model).astype(np.float64) - half) * out_s
    if lowp_inputs:
        xl = np.asarray(jnp.asarray(x).astype(jnp.bfloat16)
                        .astype(jnp.float32))
        cl, _ = served_input_codes(xl, in_log_s, bits)
        lv = (lut_outputs(cl, model).astype(np.float64) - half) * out_s
        served = np.argmax(lv, axis=-1)
    served = np.asarray(served, np.int64)
    best = vals.max(axis=-1)
    gap = best - vals[np.arange(len(allc)), served[owner]]
    gap[gap <= FAITHFUL * np.abs(best)] = 0.0
    out = np.full(len(x), np.inf)
    np.minimum.at(out, owner, gap)
    return out


# ---------------------------------------------------------------------------
# conversion: one truth table per layer


def layer_table(fn: Dict, bn: Dict, bn_state: Dict, out_log_s, slot_scale,
                *, in_bits: int, beta: int, skip: int,
                precision: str = "highest"):
    """Every input code combination of one layer, through the hidden
    function, batch norm (running statistics) and the output quantizer:
    (O, 2^(in_bits*F)) codes.  ``slot_scale`` (O, F) is the scale of the
    source channel feeding each input slot."""
    f = slot_scale.shape[1]
    t = 2 ** (in_bits * f)
    idx = jnp.arange(t, dtype=jnp.int32)
    shifts = jnp.asarray([in_bits * (f - 1 - j) for j in range(f)],
                         jnp.int32)
    codes = (idx[:, None] >> shifts[None]) & (2 ** in_bits - 1)  # (T, F)
    vals = (codes[:, None, :].astype(jnp.float32) - 2 ** (in_bits - 1)) \
        * slot_scale[None]
    h = subnet(fn, vals, skip, precision)                         # (T, O)
    pre = (h - bn_state["mean"]) * jax.lax.rsqrt(bn_state["var"] + BN_EPS) \
        * bn["g"] + bn["b"]
    return quant_codes(pre, out_log_s, beta).T


def pack_words(table: np.ndarray, beta: int) -> np.ndarray:
    """(O, T) codes -> (O, T/P) int32 words, P = the largest power of two
    <= 32 // beta; entry w*P + p sits in bits [beta*p, beta*(p+1))."""
    p = 1 << ((32 // beta).bit_length() - 1)
    t = np.asarray(table, np.uint32)
    o, n = t.shape
    g = t.reshape(o, n // p, p)
    words = np.zeros((o, n // p), np.uint32)
    for j in range(p):
        words |= g[:, :, j] << np.uint32(beta * j)
    return words.view(np.int32)


# ---------------------------------------------------------------------------
# training: the quantization-aware forward, loss and AdamW with SGDR


def train_forward(params: Dict, state: Dict, conns: List, x, *,
                  in_bits: int, beta: int, skip: int, momentum: float,
                  precision: str = "highest"):
    """Batch-statistics forward.  Returns (logits, new_state): the logits
    are the last layer's batch-normed values before its quantizer."""
    v = fake_quant(x, params["in_quant"]["log_s"], in_bits)
    new = []
    pre = None
    for lp, ls, conn in zip(params["layers"], state["layers"], conns):
        h = subnet(lp["fn"], v[:, conn], skip, precision)
        mu = jnp.mean(h, axis=0)
        var = jnp.var(h, axis=0)
        new.append({"bn": {
            "mean": (1 - momentum) * ls["bn"]["mean"] + momentum * mu,
            "var": (1 - momentum) * ls["bn"]["var"] + momentum * var}})
        pre = (h - mu) * jax.lax.rsqrt(var + BN_EPS) * lp["bn"]["g"] \
            + lp["bn"]["b"]
        v = fake_quant(pre, lp["quant"]["log_s"], beta)
    return pre, {"layers": new}


def cross_entropy(logits, labels):
    lse = jax.nn.logsumexp(logits, axis=-1)
    return jnp.mean(lse - jnp.take_along_axis(
        logits, labels[:, None], axis=-1)[:, 0])


def sgdr(step, lr_max: float, lr_min: float, t0: int):
    """Cosine annealing with warm restarts, cycle lengths t0 * 2^i."""
    step = step.astype(jnp.float32)
    i = jnp.floor(jnp.log2(step / t0 + 1.0))
    start = t0 * (2.0 ** i - 1.0)
    length = t0 * 2.0 ** i
    return lr_min + (lr_max - lr_min) * 0.5 * (
        1.0 + jnp.cos(jnp.pi * (step - start) / length))


def adamw(grads, opt: Dict, params, *, lr, hp: Dict):
    """Decoupled weight decay Adam with global-norm clipping."""
    count = opt["count"] + 1
    cf = count.astype(jnp.float32)
    b1, b2 = hp["beta1"], hp["beta2"]
    gl = jax.tree.leaves(grads)
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in gl))
    scale = jnp.minimum(1.0, hp["grad_clip"] / jnp.maximum(gnorm, 1e-12))
    m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g * scale,
                     opt["m"], grads)
    v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * (g * scale) ** 2,
                     opt["v"], grads)
    new = jax.tree.map(
        lambda p, m, v: p - lr * ((m / (1 - b1 ** cf))
                                  / (jnp.sqrt(v / (1 - b2 ** cf))
                                     + hp["eps"])
                                  + hp["weight_decay"] * p),
        params, m, v)
    return new, {"m": m, "v": v, "count": count}


def train_step(p: Dict, s: Dict, o: Dict, xb, yb, conns: List, *,
               in_bits: int, beta: int, skip: int, momentum: float,
               hp: Dict, precision: str = "highest", fault: str = ""):
    """One step on the minibatch (xb, yb).  Returns (params, state, opt,
    loss).  ``fault`` plants a known error for the benchmark's own
    calibration: "half_batch" takes the loss over half of the batch."""
    if fault == "half_batch":
        h = xb.shape[0] // 2
        xb, yb = xb[:h], yb[:h]

    def loss_fn(p):
        logits, ns = train_forward(p, s, conns, xb, in_bits=in_bits,
                                   beta=beta, skip=skip, momentum=momentum,
                                   precision=precision)
        return cross_entropy(logits, yb), ns

    (loss, ns), g = jax.value_and_grad(loss_fn, has_aux=True)(p)
    lr = sgdr(o["count"], hp["lr"], hp["lr"] * hp["lr_min_ratio"], hp["t0"])
    p, o = adamw(g, o, p, lr=lr, hp=hp)
    return p, ns, o, loss


def train_epoch(params: Dict, state: Dict, opt: Dict, key, xd, yd,
                conns: List, *, steps: int, batch: int, **kw):
    """One epoch: a permutation drawn from ``key`` split into ``steps``
    minibatches of ``batch`` rows, each a :func:`train_step` (``kw``).
    Returns (params, state, opt, mean loss)."""
    idx = jax.random.permutation(key, xd.shape[0])[:steps * batch]
    idx = idx.reshape(steps, batch)

    def step(carry, ib):
        p, s, o, loss = train_step(*carry, xd[ib], yd[ib], conns, **kw)
        return (p, s, o), loss

    (params, state, opt), losses = jax.lax.scan(step, (params, state, opt),
                                                idx)
    return params, state, opt, jnp.mean(losses)
