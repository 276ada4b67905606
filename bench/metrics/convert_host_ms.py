"""Time the converter spends in each layer outside the tables' copy back
to the host, per conversion, in ms: the ``convert.layer`` spans of the
traced window less their ``convert.fetch`` children, summed, over the
window's conversions.  It holds the scales' preparation, the sweep's
dispatch and the layer's own host work.  None where the program records
no such span."""
from benchkit import spans as SP


def read(ctx):
    n = ctx.window.counters.get("conversions")
    w = ctx.trace["window"]
    layers = SP.named(SP.of(ctx), "convert.layer")
    if not n or not layers:
        return None
    tot = 0
    for lay in layers:
        s, e = SP.clip((lay.start, lay.end), w)
        tot += (e - s) - SP.covered(
            SP.clip((c.start, c.end), (s, e)) for c in lay.children
            if c.name == "convert.fetch")
    return tot / 1e6 / n
