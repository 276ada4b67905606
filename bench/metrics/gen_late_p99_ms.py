"""How late the load generator sent its requests: the 99th percentile of
send time minus due time over the window (host clock).  A starved
generator reads high here, and its requests' latency is then not the
server's alone."""
from benchkit.stats import percentile


def read(ctx):
    late = ctx.window.counters.get("late_ms")
    return percentile(late, 99) if late else None
