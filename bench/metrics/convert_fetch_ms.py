"""Time the converter spends copying tables back to the host, per
conversion, in ms: the ``convert.fetch`` spans of the traced window
(each waits for its layer's sweep to finish and copies the table and the
packed words), summed, over the window's conversions.  None where the
program records no such span."""
from benchkit import spans as SP


def read(ctx):
    n = ctx.window.counters.get("conversions")
    w = ctx.trace["window"]
    fetch = [SP.clip((s.start, s.end), w)
             for s in SP.named(SP.of(ctx), "convert.fetch")]
    if not n or not fetch:
        return None
    return sum(e - s for s, e in fetch) / 1e6 / n
