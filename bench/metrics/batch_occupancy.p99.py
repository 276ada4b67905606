"""Real samples over dispatched (bucket-padded) slots, in per cent, over
the window, from the serving engine's own counters
(``engine.metrics``): how much of each dispatched batch was padding.
Read in the cells that report ``serve_p50_ms``."""


def read(ctx):
    occ = ctx.window.counters.get("occupancy")
    if not occ or not occ["padded"]:
        return None
    return 100.0 * occ["real"] / occ["padded"]
