"""Median wait of the traced window's requests in the serving engine
before a replica executor took them, in ms: from submit until the
``serve.batch`` span that served each began (the span's ``waits_us``,
recorded by the program).  It holds the admission window and the wait in
the executor's queue.  None where the program records no such span."""
from benchkit import spans as SP
from benchkit.stats import percentile


def read(ctx):
    batches = SP.starting_in(SP.named(SP.of(ctx), "serve.batch"),
                             ctx.trace["window"])
    waits = sorted(w for b in batches for w in SP.waits_us(b))
    return percentile(waits, 50) / 1e3 if waits else None
