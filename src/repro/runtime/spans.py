"""The spans the program records, by name.

Spans are ``jax.profiler.TraceAnnotation``s, so they are recorded exactly
when the JAX profiler records (``jax.profiler.trace`` or
``start_trace``): into the same trace as the device's operations, on the
same clock, so that an idle gap of the device lines up with the host span
that was open during it.  A span's keyword arguments become stats of its
trace event.  With the profiler off a span costs about a microsecond;
arguments that cost more than that to build are built only while
:func:`recording`.

Each span nests, on its own thread, inside the one listed as its parent:

  serving engine (``serve/engine.py``)
    serve.await     dispatcher: blocked on the request queue for a batch's
                    first request
    serve.coalesce  dispatcher: from the first request taken until the
                    batch is routed (the admission window); ``requests``,
                    ``samples``
    serve.batch     replica executor: from taking a batch until its last
                    future resolves; ``rid``, ``requests``, ``samples``,
                    ``padded``, ``chunks`` and ``waits_us``, each
                    request's wait from submit until the span began, in
                    microseconds, separated by spaces
    serve.chunk     one bucket-padded call, padding included; ``bucket``
    serve.h2d       the chunk's copy to the device
    serve.step      the jitted step's dispatch
    serve.fetch     waiting for the device and copying the answer back
    serve.resolve   setting the futures (clients' callbacks run here)

  converter (``core/truth_table.py``)
    convert.layer   one layer's (or graph node's) sweep; ``layer``,
                    ``entries``, and a graph node's ``arity``
    convert.sweep   the jitted sweep's dispatch (the sweep computes the
                    slot scales on the device); in a graph node, one per
                    branch, with its ``branch``
    convert.fetch   the tables' copy back to the host; in a graph node,
                    one per branch, with its ``branch``

The jitted programs carry ``jax.named_scope``s, so that their device
operations name the layer in their metadata: :data:`SCOPE_SERVE_STEP`,
:data:`SCOPE_CONVERT_SWEEP` and :data:`SCOPE_TRAIN_EPOCH`.
"""
from __future__ import annotations

from typing import Dict, Optional

from jax.profiler import TraceAnnotation

SERVE_AWAIT = "serve.await"
SERVE_COALESCE = "serve.coalesce"
SERVE_BATCH = "serve.batch"
SERVE_CHUNK = "serve.chunk"
SERVE_H2D = "serve.h2d"
SERVE_STEP = "serve.step"
SERVE_FETCH = "serve.fetch"
SERVE_RESOLVE = "serve.resolve"
CONVERT_LAYER = "convert.layer"
CONVERT_SWEEP = "convert.sweep"
CONVERT_FETCH = "convert.fetch"

# Every span and the span it nests in on its thread (None: outermost).
PARENT: Dict[str, Optional[str]] = {
    SERVE_AWAIT: None,
    SERVE_COALESCE: None,
    SERVE_BATCH: None,
    SERVE_CHUNK: SERVE_BATCH,
    SERVE_H2D: SERVE_CHUNK,
    SERVE_STEP: SERVE_CHUNK,
    SERVE_FETCH: SERVE_CHUNK,
    SERVE_RESOLVE: SERVE_BATCH,
    CONVERT_LAYER: None,
    CONVERT_SWEEP: CONVERT_LAYER,
    CONVERT_FETCH: CONVERT_LAYER,
}

SCOPE_SERVE_STEP = "serve_step"
SCOPE_CONVERT_SWEEP = "convert_sweep"
SCOPE_TRAIN_EPOCH = "train_epoch"


def recording() -> bool:
    """Whether the profiler is recording spans now."""
    return TraceAnnotation.is_enabled()
