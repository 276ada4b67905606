"""Share of the traced window, in per cent, in which no operation ran on
the device: 1 - (union of the device's op intervals) / window, from the
profiler's trace (``benchkit.trace``)."""
from benchkit import trace as T


def read(ctx):
    share = T.idle_share(ctx.trace)
    return None if share is None else 100.0 * share
