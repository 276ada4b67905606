"""Algorithmic work of the hidden function, and of the subnet inference
kernel (``kernels/neuralut_mlp.grouped_subnet``) that evaluates it for
conversion.

Counts come from the algorithm's shapes: every affine map of every
neuron's MLP (paper eqs. 1-3) is one multiply-add per weight, two
operations; biases, ReLUs and the kernel's own layout are not counted.
With widths [F, N, ..., N, 1] and skips every S sub-layers, one neuron
costs ``sum(n_i * n_i+1) + sum over skips`` multiply-adds per input:
``32F + 544`` for N=16, L=4, S=2.
"""
from __future__ import annotations

# The kernel's device ops in a trace.  Pallas calls carry no name, so the
# trace names them by HLO instruction (``pallas_call.N``; ``jvp__.N`` and
# ``transpose_jvp___.N`` for the training kernel's two passes); each cell
# that reads this count runs no other compiled Pallas kernel, so the
# reader takes every ``tpu_custom_call`` op of its trace.
TRACE_PATTERN = r"^tpu_custom_call$"


def macs_per_neuron(geom, i: int) -> int:
    w = geom.mlp_widths(i)
    macs = sum(a * b for a, b in zip(w[:-1], w[1:]))
    if geom.skip:
        s = geom.skip
        macs += sum(w[c * s] * w[(c + 1) * s]
                    for c in range(geom.depth // s))
    return macs


def params_per_neuron(geom, i: int) -> int:
    w = geom.mlp_widths(i)
    n = sum(a * b + b for a, b in zip(w[:-1], w[1:]))
    if geom.skip:
        s = geom.skip
        n += sum(w[c * s] * w[(c + 1) * s] + w[(c + 1) * s]
                 for c in range(geom.depth // s))
    return n


def forward_macs_per_sample(geom) -> int:
    """Multiply-adds of one sample through every neuron of the model."""
    return sum(o * macs_per_neuron(geom, i)
               for i, o in enumerate(geom.widths))


def conversion_flops(geom) -> int:
    """Operations of one whole conversion: every table entry of every
    neuron through its hidden function."""
    return sum(2 * geom.table_size(i) * o * macs_per_neuron(geom, i)
               for i, o in enumerate(geom.widths))


def conversion_bytes(geom) -> int:
    """Least bytes one conversion's kernel calls move: each entry's F
    input values read and its output written (f32), each neuron's
    weights read once."""
    return sum(4 * (geom.table_size(i) * o * (geom.fan_ins[i] + 1)
                    + o * params_per_neuron(geom, i))
               for i, o in enumerate(geom.widths))
