"""The subnet inference kernel's share of its roofline, in per cent: the
least time for the window's conversions (operations and bytes from
``bench/counts/neuralut_mlp.py``) over the device time of the kernel's
events in the trace."""
from benchkit import trace as T
from benchkit.cell import counts


def read(ctx):
    c = counts("neuralut_mlp")
    secs, calls = T.kernel_s(ctx.trace, c.TRACE_PATTERN)
    n = ctx.window.counters.get("conversions")
    if not calls or not secs or not n:
        return None
    least = n * max(c.conversion_flops(ctx.geom) / ctx.peaks["flops_per_s"],
                    c.conversion_bytes(ctx.geom)
                    / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / secs
