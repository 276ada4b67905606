"""The cascade kernel's share of its roofline, in per cent: the least
time the chip could take for the kernel's calls in the traced window
(operations at peak FLOP/s or bytes at peak HBM bandwidth, whichever is
longer, from ``bench/counts/lut_cascade.py``) over the device time the
trace gives those calls.  Calls and dispatched slots: the trace's kernel
events and the engine's padded-slot counter over the window."""
from benchkit import trace as T
from benchkit.cell import counts


def read(ctx):
    c = counts("lut_cascade")
    secs, calls = T.kernel_s(ctx.trace, c.TRACE_PATTERN)
    occ = ctx.window.counters.get("occupancy")
    if not calls or not secs or not occ or not occ["padded"]:
        return None
    slots = occ["padded"]
    ops = slots * c.ops_per_sample(ctx.geom)
    nbytes = calls * c.table_bytes(ctx.geom) + 4 * slots * (
        ctx.geom.in_features + ctx.geom.num_classes)
    least = max(ops / ctx.peaks["flops_per_s"],
                nbytes / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / secs
