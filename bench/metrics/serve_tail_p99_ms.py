"""The 99th percentile of request latency over the traced window, timed
as ``serve_p50_ms`` is: from each request's due time to its answer (host
clock).  On the chip's host it is set by stalls of the host's threads
(the generator's own lateness tracks it), so it swings from run to run
far more than any end-to-end bound allows, and is read here, beside
``gen_late_p99_ms``."""


def read(ctx):
    return ctx.window.metrics.get("serve_p99_ms")
