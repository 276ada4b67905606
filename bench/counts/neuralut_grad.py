"""Algorithmic work of the fused forward+backward subnet kernel
(``kernels/neuralut_grad``) in one training step: three times the
inference operations (forward, gradient of the inputs, gradient of the
weights), from the same shapes as ``neuralut_mlp``."""
from __future__ import annotations

from benchkit.cell import counts

# The kernel's device ops in a trace.  Pallas calls carry no name, so the
# trace names them by HLO instruction (``pallas_call.N``; ``jvp__.N`` and
# ``transpose_jvp___.N`` for the training kernel's two passes); each cell
# that reads this count runs no other compiled Pallas kernel, so the
# reader takes every ``tpu_custom_call`` op of its trace.
TRACE_PATTERN = r"^tpu_custom_call$"


def train_flops_per_sample(geom) -> int:
    mlp = counts("neuralut_mlp")
    return 3 * 2 * mlp.forward_macs_per_sample(geom)


def step_bytes(geom, batch: int) -> int:
    """Least bytes of one step's kernel calls: per layer the inputs read
    by both passes, the output and its gradient, the input gradient, and
    the weights read twice and their gradient written once (f32)."""
    mlp = counts("neuralut_mlp")
    tot = 0
    for i, o in enumerate(geom.widths):
        f = geom.fan_ins[i]
        tot += 4 * (batch * o * (2 * f + 2 + f)
                    + 3 * o * mlp.params_per_neuron(geom, i))
    return tot
