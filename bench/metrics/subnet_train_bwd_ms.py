"""Device time of the subnet training kernel's backward pass, per
training step, in ms: the traced window's device ops of the Pallas
kernel named ``subnet_train_bwd`` (``kernels/neuralut_grad.py``),
summed, over the window's steps.  The trace names the op after the
kernel, inside what autodiff makes of it
(``transpose_jvp_subnet_train_bwd__.3``).  None where no op of that name
ran, as with a program whose kernel has no name."""
from benchkit import trace as T

PATTERN = r"subnet_train_bwd"


def read(ctx):
    secs, calls = T.kernel_s(ctx.trace, PATTERN)
    steps = ctx.window.counters.get("steps")
    if not calls or not steps:
        return None
    return secs * 1e3 / steps
