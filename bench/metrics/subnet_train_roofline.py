"""The fused forward+backward subnet kernel's share of its roofline, in
per cent: the least time for the window's training steps (operations and
bytes from ``bench/counts/neuralut_grad.py``) over the device time of the
kernel's events in the trace."""
from benchkit import trace as T
from benchkit.cell import counts


def read(ctx):
    c = counts("neuralut_grad")
    secs, calls = T.kernel_s(ctx.trace, c.TRACE_PATTERN)
    steps = ctx.window.counters.get("steps")
    if not calls or not secs or not steps:
        return None
    batch = ctx.cell.traffic["batch"]
    ops = steps * batch * c.train_flops_per_sample(ctx.geom)
    nbytes = steps * c.step_bytes(ctx.geom, batch)
    least = max(ops / ctx.peaks["flops_per_s"],
                nbytes / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / secs
