"""Share of the traced window, in per cent, in which a replica executor
was serving a batch (a ``serve.batch`` span was open) and yet no
operation ran on the device: the idle that the executor's serial chunk
loop causes by itself (the copy in, the dispatch, the wait for each
chunk's answer, the futures).  Device idle by the union-of-ops rule of
``benchkit.trace``; None where the program records no such span or the
trace holds no device operation."""
from benchkit import spans as SP
from benchkit import trace as T


def read(ctx):
    batches = SP.named(SP.of(ctx), "serve.batch")
    if not batches:
        return None
    idle = SP.device_idle_ns(ctx.trace, [(b.start, b.end) for b in batches])
    if idle is None:
        return None
    return 100.0 * idle / 1e9 / T.window_s(ctx.trace)
