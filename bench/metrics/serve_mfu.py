"""The served step's share of the chip's peak, in per cent: the cascade's
algorithmic operations (``bench/counts/lut_cascade.py``) for every real
sample answered in the traced window, per second of that window, over
peak FLOP/s.  Padding and the kernel's own way of doing the work are not
counted, so a change that removes the kernel from the path still has to
serve more samples to raise it."""
from benchkit.cell import counts


def read(ctx):
    n = ctx.window.counters.get("served_samples")
    if not n:
        return None
    ops = n * counts("lut_cascade").ops_per_sample(ctx.geom)
    return 100.0 * ops / ctx.window.seconds / ctx.peaks["flops_per_s"]
