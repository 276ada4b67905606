"""Median time a replica executor spends on one batch, in ms: the
``serve.batch`` spans that began in the traced window, from taking the
batch until its last future resolved (padding, copies, the step, the
wait for the answer and the futures).  None where the program records
no such span."""
from benchkit import spans as SP
from benchkit.stats import percentile


def read(ctx):
    batches = SP.starting_in(SP.named(SP.of(ctx), "serve.batch"),
                             ctx.trace["window"])
    durs = sorted(b.dur for b in batches)
    return percentile(durs, 50) / 1e6 if durs else None
