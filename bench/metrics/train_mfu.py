"""Model FLOP utilisation of training, in per cent: forward and backward
operations per sample (``bench/counts/neuralut_grad.py``: three times the
hidden functions' inference operations) times samples stepped per second
of the traced window, over peak FLOP/s."""
from benchkit.cell import counts


def read(ctx):
    n = ctx.window.counters.get("samples")
    if not n:
        return None
    flops = n * counts("neuralut_grad").train_flops_per_sample(ctx.geom)
    return 100.0 * flops / ctx.window.seconds / ctx.peaks["flops_per_s"]
