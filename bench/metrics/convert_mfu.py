"""Conversion's share of the chip's peak, in per cent: the hidden
functions' operations of one whole conversion
(``bench/counts/neuralut_mlp.py``) times conversions per second of the
traced window, over peak FLOP/s."""
from benchkit.cell import counts


def read(ctx):
    n = ctx.window.counters.get("conversions")
    if not n:
        return None
    flops = n * counts("neuralut_mlp").conversion_flops(ctx.geom)
    return 100.0 * flops / ctx.window.seconds / ctx.peaks["flops_per_s"]
