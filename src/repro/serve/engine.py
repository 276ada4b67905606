"""Batched LUT serving engine: request queue + dynamic bucketed batcher
+ replica routing.

The serving hot path of a converted NeuraLUT model is a cascade of table
lookups (one per neuron per layer).  This engine turns that into a
production-shaped service:

  * Clients ``submit()`` requests of any size; a dispatcher thread coalesces
    whatever is queued into one batch (up to the largest bucket), bounded by
    a ``max_wait_ms`` admission window so a lone request is never stuck
    behind an empty queue.

  * Batches are padded up to a fixed *bucket* size (default 1/8/64/256), so
    ``jax.jit`` sees a bounded set of shapes: at most ``len(buckets)``
    retraces ever, all performed eagerly by ``warmup()``.  Oversized
    requests are served in max-bucket chunks — still no new shapes.

  * Coalesced batches are routed to one of ``replicas`` *executors* — each
    a worker thread owning a jitted forward pinned to its own device (the
    whole bundle is tables, so replicas are cheap: every device holds the
    full bit-packed stack).  Routing is queue-depth-aware round-robin over
    the replicas the :class:`repro.runtime.fault.ReplicaHealthTracker`
    reports healthy: least-loaded wins, ties break in round-robin order.
    A replica whose dispatches keep failing is evicted from rotation and
    the survivors absorb the load; ``replicas=1`` (the default) collapses
    to the single-device engine with identical behavior.

  * The default forward is the *fused cascade*: the whole multi-layer LUT
    network in one dispatch — the Pallas ``lut_cascade`` kernel on TPU
    (bit-packed tables resident in VMEM, zero inter-layer HBM traffic)
    and the single-jit bit-packed jnp cascade
    (``kernels.ref.lut_cascade_packed_ref``, cache-resident packed
    tables) elsewhere.  ``fused=False`` falls back to the per-layer loop
    (Pallas ``lut_gather`` on TPU, jnp gather oracle elsewhere).
    ``sharded=True`` instead serves every batch through the
    ``shard_map``'d multi-device cascade (serve/sharded.py) — one
    executor whose dispatches span the whole replica mesh.  All paths
    are bit-exact vs ``lut_infer.lut_forward`` (tests/test_kernels.py,
    tests/test_lut_cascade.py, tests/test_serve_sharded.py), so
    predictions are identical wherever the engine runs.

  * :class:`repro.serve.metrics.ServeMetrics` records per-request latency,
    throughput, queue depth and batch occupancy, both in aggregate
    (``engine.metrics``) and per replica (``engine.replica_metrics``)
    (EXPERIMENTS.md §Perf and §Scale-out).

The engine serves a :class:`repro.serve.registry.ServeBundle` — a saved
artifact — so serving never retrains (see registry.py).
"""
from __future__ import annotations

import dataclasses
import queue
import threading
import time
from concurrent.futures import Future
from typing import Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core import lut_infer as LI
from repro.core.exec_plan import (CascadeExec, detect_backend,
                                  plan_cascade_exec)
from repro.runtime import spans as S
from repro.runtime.chaos import ChaosHarness
from repro.runtime.fault import ReplicaHealthTracker
from repro.serve.metrics import ServeMetrics
from repro.serve.registry import ServeBundle

DEFAULT_BUCKETS: Tuple[int, ...] = (1, 8, 64, 256)


class DispatchFailed(RuntimeError):
    """A batch failed on a replica and exhausted its redispatch budget;
    every waiting future resolves with this (the original replica error
    is chained as ``__cause__``)."""

    def __init__(self, attempts: int, cause: BaseException):
        self.attempts = attempts
        self.cause = cause
        super().__init__(
            f"replica dispatch failed after {attempts} attempt(s): "
            f"{cause!r}")
        self.__cause__ = cause


class DeadlineExceeded(RuntimeError):
    """A request's ``submit(timeout_s=)`` deadline passed before it was
    served; counted in ``ServeMetrics.deadline_exceeded``."""


class NoHealthyReplicas(RuntimeError):
    """Every replica is evicted and the auto-revive probe (if any)
    could not bring one back; the batch is shed, not queued behind a
    pool that can never serve it."""


def pick_bucket(n: int, buckets: Sequence[int]) -> int:
    """Smallest bucket >= n; callers chunk anything larger than the max."""
    if n <= 0:
        raise ValueError(f"batch size {n} must be positive")
    for b in buckets:
        if n <= b:
            return b
    return buckets[-1]


def _divisor_block(n: int, cap: int) -> int:
    """Largest power-of-two divisor of n that is <= cap, closed form
    (``n & -n`` isolates n's lowest set bit, the cap rounds down to a
    power of two).  Used for the *batch* dimension, where n is a bucket
    size — a power of two — so this returns the full bucket or the cap.
    The neuron dimension no longer needs a divisor at all: the kernels
    pad non-divisible O internally."""
    if n <= 0 or cap <= 0:
        return 1
    return min(n & -n, 1 << (cap.bit_length() - 1))


def make_forward_fn(bundle: ServeBundle, *,
                    use_kernel: Optional[bool] = None,
                    fused: bool = True,
                    block_b: Optional[int] = None, block_o: int = 32,
                    device=None,
                    plan: Optional[CascadeExec] = None
                    ) -> Callable[[jax.Array], jax.Array]:
    """Jitted (B, in_features) float32 -> (B,) int32 class predictions.

    Tables and connectivity are closed-over constants; retraces are per
    batch shape only (bounded by the engine's buckets).  ``device`` pins
    every closed-over operand (tables, shift matrices, quantizer scales)
    to that device — how each replica executor gets its own resident
    copy of the bundle; None keeps jax's default placement.

    ``plan`` (a ``core.exec_plan.CascadeExec``) names the route
    explicitly; the ``use_kernel``/``fused``/``block_b`` keywords are
    the legacy spelling and are folded into an equivalent plan
    (``use_kernel=None`` picks the backend default: the Pallas kernel
    flavor on TPU/GPU, the cache-blocked gather cascade
    ``fused_cpu_blocked`` elsewhere — the shift matrices are closed-over
    constants here, so the blocked route's trace-time gather
    decomposition applies; ``block_b=None`` takes the route's default
    tile).  The fused routes run the whole DAG schedule in one dispatch;
    the per-layer routes walk one buffer per layer and therefore raise
    ``UnsupportedTopology`` here — at build time, not inside a trace —
    for non-chain LUT graphs.  All paths are bit-exact vs
    ``lut_infer.lut_forward`` / ``graph_lut_forward``
    (tests/test_lut_cascade.py, tests/test_lut_graph.py,
    tests/test_backend_matrix.py).
    """
    cfg = bundle.cfg
    if plan is None:
        plan = plan_cascade_exec(cfg, fused=fused, use_kernel=use_kernel,
                                 block_b=block_b)

    def put(a):
        a = jnp.asarray(a)
        return a if device is None else jax.device_put(a, device)

    params = jax.tree.map(put, bundle.serve_params())
    operands = [params]

    if plan.fused:
        # Fused paths only touch the packed tables + shift matrices —
        # the unpacked int32 tables must NOT be uploaded (they are ~8x
        # the packed footprint).
        bundle.prepack()
        packed = [put(t) for t in bundle.packed_tables]
        shift_mats = [put(m) for m in bundle.shift_mats]
        operands += [packed, shift_mats]
        from repro.kernels.ops import cascade_apply
    else:
        # Per-layer dispatch: plan construction already refused
        # non-chain graphs, so a graph cfg here is a degenerate chain —
        # unwrap its single-branch lists to the legacy operands.
        from repro.core.model import node_static_conns
        tables = [put(np.asarray(t[0] if isinstance(t, (list, tuple))
                                 else t).astype(np.int32))
                  for t in bundle.tables]
        conns = [put(node_static_conns(s)[0]) for s in bundle.statics]
        operands += [tables, conns]
        in_bits = tuple(cfg.layer_in_bits(i)
                        for i in range(cfg.num_layers))
        if plan.use_kernel:
            from repro.kernels.ops import lut_lookup_op

    @jax.named_scope(S.SCOPE_SERVE_STEP)
    def forward(x: jax.Array) -> jax.Array:
        codes = LI.input_codes(cfg, params, x)
        c = codes.astype(jnp.int32)
        if plan.fused:
            bb = _divisor_block(c.shape[0], plan.block_b)
            c = cascade_apply(c, shift_mats, packed,
                              plan=dataclasses.replace(plan, block_b=bb))
        else:
            for i in range(cfg.num_layers):
                gathered = c[:, conns[i]]                      # (B, O, F)
                addr = LI.pack_index(gathered, in_bits[i])
                tbl = tables[i]
                if plan.use_kernel:
                    bb = _divisor_block(addr.shape[0], plan.block_b)
                    # O needs no divisor: lut_lookup pads internally
                    c = lut_lookup_op(tbl, addr, block_b=bb,
                                      block_o=block_o)
                else:
                    c = tbl[jnp.arange(tbl.shape[0])[None, :], addr]
                c = c.astype(jnp.int32)
        vals = LI.class_values(cfg, params, c)
        return jnp.argmax(vals, axis=-1).astype(jnp.int32)

    jitted = jax.jit(forward)
    jitted.operands = operands  # the device-resident bundle it closes over
    return jitted


def make_degradable_forward_fn(bundle: ServeBundle, *, plan: CascadeExec,
                               device=None,
                               metrics: Optional[ServeMetrics] = None,
                               chaos: Optional[ChaosHarness] = None
                               ) -> Callable[[jax.Array], jax.Array]:
    """Fused-kernel forward with one-shot graceful degradation: if the
    ``fused_kernel`` route ever raises, the forward permanently flips to
    the bit-exact ``fused_jnp`` reference path (same predictions — the
    routes are interchangeable by the cascade bit-exactness contract),
    records the downgrade in ``metrics``, and serves the failing batch
    through the fallback in the same call, so the triggering client
    never sees the kernel error.  The fallback jit is built lazily — a
    healthy engine pays nothing for carrying it.  ``chaos`` checks the
    ``serve.kernel`` site before each primary call (deterministic
    downgrade tests).  The primary is exposed as ``forward.primary``
    so that warmup can compile it outside the catch: a route that cannot
    compile must fail warmup, not be served by the fallback."""
    primary = make_forward_fn(bundle, plan=plan, device=device)
    state: dict = {"fallback": None}

    def forward(x: jax.Array) -> jax.Array:
        fb = state["fallback"]
        if fb is None:
            try:
                if chaos is not None:
                    chaos.check("serve.kernel")
                return primary(x)
            except Exception:
                fb = state["fallback"] = make_forward_fn(
                    bundle,
                    plan=dataclasses.replace(plan, route="fused_jnp"),
                    device=device)
                if metrics is not None:
                    metrics.record_downgrade()
        return fb(x)

    forward.primary = primary
    return forward


class _Request:
    __slots__ = ("x", "n", "future", "t_submit", "deadline")

    def __init__(self, x: np.ndarray, timeout_s: Optional[float] = None):
        self.x = x
        self.n = x.shape[0]
        self.future: "Future[np.ndarray]" = Future()
        self.t_submit = time.perf_counter()
        self.deadline = (None if timeout_s is None
                         else self.t_submit + timeout_s)


_STOP = object()


def route_least_loaded(executors: Sequence["_ReplicaExecutor"],
                       health: ReplicaHealthTracker,
                       rr: int, *,
                       exclude: Optional[int] = None
                       ) -> Optional["_ReplicaExecutor"]:
    """Queue-depth-aware sticky round-robin over healthy replicas: the
    least-loaded healthy executor wins, with depth ties broken in
    round-robin order *from the last-used replica inclusive* — so light
    load sticks to one warm replica (no cross-device scatter for traffic
    one device can absorb) and spills to the next replica exactly when
    the current one has queued work.  Under saturation every replica
    ends up busy and the policy degenerates to least-loaded.  Returns
    None when no replica is healthy.  ``exclude`` (a replica id) is a
    *preference*, not a bar: the redispatch path avoids the replica that
    just failed when any other healthy replica exists, but a transient
    failure on the only healthy replica may still retry there.  Shared
    by the single-bundle engine and the multi-tenant geometry-group
    pools (serve/tenants.py)."""
    healthy = [ex for ex in executors if health.is_healthy(ex.rid)]
    if not healthy:
        return None
    if exclude is not None:
        others = [ex for ex in healthy if ex.rid != exclude]
        healthy = others or healthy
    n = len(executors)
    return min(healthy, key=lambda ex: (ex.depth(), (ex.rid - rr) % n))


def _drop_expired(batch: List["_Request"],
                  engine_metrics: ServeMetrics) -> List["_Request"]:
    """Resolve every past-deadline request with ``DeadlineExceeded``
    (counted in the engine metrics, and the tenant's where the request
    carries one) and return the still-live remainder.  Called at every
    hand-off point — dispatcher routing and executor serve — so an
    expired request never pays for a forward it can no longer use."""
    now = time.perf_counter()
    live: List[_Request] = []
    for r in batch:
        if r.deadline is not None and now > r.deadline:
            waited = now - r.t_submit
            if _complete(r.future, exc=DeadlineExceeded(
                    f"request expired after {waited * 1e3:.1f}ms in "
                    f"queue (timeout "
                    f"{(r.deadline - r.t_submit) * 1e3:.1f}ms)")):
                engine_metrics.record_deadline_exceeded()
                tenant = getattr(r, "tenant", None)
                if tenant is not None:
                    tenant.metrics.record_deadline_exceeded()
        else:
            live.append(r)
    return live


def _complete(future: Future, result=None, exc=None) -> bool:
    """Resolve a future, tolerating client-side cancel(): a cancelled
    future makes set_result/set_exception raise InvalidStateError, which
    must never kill a serving thread."""
    try:
        if exc is not None:
            future.set_exception(exc)
        else:
            future.set_result(result)
        return True
    except Exception:
        return False


class _ReplicaExecutor:
    """One serving replica: a worker thread draining its own batch queue
    through a jitted forward pinned to one device.

    The dispatcher routes *coalesced* batches here (see
    ``LUTServeEngine._route``); the executor serves them FIFO, records
    into both its per-replica metrics and the engine aggregate, and
    reports every dispatch outcome to the health tracker.  On shutdown
    it drains batches queued before the stop sentinel — an accepted
    batch is never dropped.
    """

    def __init__(self, rid: int, forward: Callable, *,
                 buckets: Sequence[int], device=None,
                 engine_metrics: ServeMetrics,
                 health: ReplicaHealthTracker,
                 redispatch: Optional[Callable] = None,
                 chaos: Optional[ChaosHarness] = None):
        self.rid = rid
        self.device = device
        self.metrics = ServeMetrics()
        self._forward = forward
        self._buckets = tuple(buckets)
        self._engine_metrics = engine_metrics
        self._health = health
        # redispatch(batch, total, attempts, failed_rid) -> bool: the
        # engine's self-healing hook — route the batch to another
        # healthy replica, False once the retry budget is spent.
        self._redispatch = redispatch
        self._chaos = chaos
        self._queue: "queue.Queue" = queue.Queue()
        self._thread: Optional[threading.Thread] = None

    # -- lifecycle --------------------------------------------------------

    def start(self) -> None:
        if self._thread is None:
            self._thread = threading.Thread(
                target=self._loop, daemon=True,
                name=f"lut-serve-replica-{self.rid}")
            self._thread.start()

    def stop(self) -> None:
        """Request shutdown and join; queued batches are served first.
        A batch redispatched here *after* the stop sentinel (a failure
        elsewhere racing shutdown) has no worker left — resolve its
        futures with DispatchFailed rather than stranding them."""
        if self._thread is not None:
            self._queue.put(_STOP)
            self._thread.join()
            self._thread = None
        while True:
            try:
                item = self._queue.get_nowait()
            except queue.Empty:
                break
            if item is _STOP:
                continue
            batch, _, _, attempts = item
            err = DispatchFailed(attempts + 1, RuntimeError(
                "replica stopped during redispatch"))
            for r in batch:
                _complete(r.future, exc=err)

    def warmup(self, in_features: int) -> None:
        """Compile every bucket.  A degradable forward compiles its
        primary route here, outside its downgrade catch, so a route
        that cannot compile raises instead of counting a downgrade."""
        fwd = getattr(self._forward, "primary", self._forward)
        for b in self._buckets:
            x = np.zeros((b, in_features), np.float32)
            fwd(self._put(x)).block_until_ready()

    def operand_devices(self) -> set:
        """Devices holding this replica's resident bundle operands."""
        fwd = getattr(self._forward, "primary", self._forward)
        return {d for a in jax.tree.leaves(getattr(fwd, "operands", []))
                for d in a.devices()}

    def _put(self, x: np.ndarray) -> jax.Array:
        """One host->device transfer, straight to the pinned device (a
        jnp.asarray first would commit to the default device and pay a
        second device-to-device copy per batch)."""
        return (jnp.asarray(x) if self.device is None
                else jax.device_put(x, self.device))

    # -- dispatcher-facing ------------------------------------------------

    def depth(self) -> int:
        """Batches in flight on this replica — queued AND currently
        being served (``unfinished_tasks`` pairs every put() with the
        task_done() below).  The routing load signal: a replica mid-
        dispatch must not look idle, or sticky routing would pile onto
        it while true idle replicas sit empty."""
        return self._queue.unfinished_tasks

    def dispatch(self, batch: List[_Request], total: int,
                 queue_depth: int, attempts: int = 0) -> None:
        self._queue.put((batch, total, queue_depth, attempts))

    # -- worker -----------------------------------------------------------

    def _loop(self) -> None:
        while True:
            item = self._queue.get()
            if item is _STOP:
                self._queue.task_done()
                break
            batch, total, depth, attempts = item
            try:
                self._serve(batch, total, depth, attempts)
            finally:
                self._queue.task_done()

    def _fail_or_redispatch(self, batch: List[_Request], total: int,
                            attempts: int, exc: BaseException) -> None:
        """Shared dispatch-failure tail: report health FIRST (so the
        redispatch route sees the failure it is routing around — the
        tracker guards the user on_evict hook, so nothing here can
        strand a client), then hand the batch to the engine's
        redispatch hook; only when the retry budget is spent do the
        waiters see a typed DispatchFailed chaining the root cause."""
        self._health.record_failure(self.rid, exc)
        if (self._redispatch is not None
                and self._redispatch(batch, total, attempts + 1, self.rid)):
            return
        err = DispatchFailed(attempts + 1, exc)
        for r in batch:
            _complete(r.future, exc=err)

    def _serve(self, batch: List[_Request], total: int, depth: int,
               attempts: int = 0) -> None:
        t_start = time.perf_counter()
        with TraceAnnotation(S.SERVE_BATCH, rid=self.rid) as span:
            batch = _drop_expired(batch, self._engine_metrics)
            if not batch:
                return
            total = sum(r.n for r in batch)
            if S.recording():
                span.set_metadata(
                    requests=len(batch), samples=total,
                    waits_us=" ".join(str(int((t_start - r.t_submit) * 1e6))
                                      for r in batch))
            x = (batch[0].x if len(batch) == 1
                 else np.concatenate([r.x for r in batch], axis=0))
            try:
                if self._chaos is not None:
                    self._chaos.check("serve.replica")
                preds, padded = self._run(x)
            except Exception as e:
                self._fail_or_redispatch(batch, total, attempts, e)
                return
            span.set_metadata(padded=padded,
                              chunks=-(-total // self._buckets[-1]))
            self._health.record_success(self.rid)
            with TraceAnnotation(S.SERVE_RESOLVE):
                t_done = time.perf_counter()
                off = 0
                for r in batch:
                    delivered = _complete(r.future, preds[off:off + r.n])
                    off += r.n
                    if delivered:
                        lat = t_done - r.t_submit
                        self.metrics.record_request(lat, r.n)
                        self._engine_metrics.record_request(lat, r.n)
            self.metrics.record_batch(total, padded, depth)
            self._engine_metrics.record_batch(total, padded, depth)

    def _run(self, x: np.ndarray) -> Tuple[np.ndarray, int]:
        """Serve (n, F) through bucket-padded jitted calls; returns the
        (n,) predictions and the number of dispatched (padded) slots."""
        n = x.shape[0]
        max_bucket = self._buckets[-1]
        outs: List[np.ndarray] = []
        padded = 0
        for s in range(0, n, max_bucket):
            chunk = x[s:s + max_bucket]
            b = pick_bucket(chunk.shape[0], self._buckets)
            with TraceAnnotation(S.SERVE_CHUNK, bucket=b):
                if chunk.shape[0] < b:
                    pad = np.zeros((b - chunk.shape[0], x.shape[1]),
                                   x.dtype)
                    xc = np.concatenate([chunk, pad], axis=0)
                else:
                    xc = chunk
                with TraceAnnotation(S.SERVE_H2D):
                    xd = self._put(xc)
                with TraceAnnotation(S.SERVE_STEP):
                    yd = self._forward(xd)
                with TraceAnnotation(S.SERVE_FETCH):
                    out = np.asarray(yd)
            outs.append(out[:chunk.shape[0]])
            padded += b
        return np.concatenate(outs, axis=0), padded


class LUTServeEngine:
    """Serve a ServeBundle behind a dynamic batcher with replica routing
    (see module docstring)."""

    def __init__(self, bundle: ServeBundle, *,
                 buckets: Sequence[int] = DEFAULT_BUCKETS,
                 max_wait_ms: float = 2.0,
                 use_kernel: Optional[bool] = None,
                 fused: bool = True,
                 metrics: Optional[ServeMetrics] = None,
                 replicas: int = 1,
                 devices: Optional[Sequence] = None,
                 health: Optional[ReplicaHealthTracker] = None,
                 sharded: bool = False,
                 shard_mode: str = "auto",
                 plan: Optional[CascadeExec] = None,
                 max_dispatch_retries: int = 2,
                 revive_probe: Optional[Callable[[int], bool]] = None,
                 chaos: Optional[ChaosHarness] = None):
        if list(buckets) != sorted(set(buckets)):
            raise ValueError(f"buckets must be strictly increasing: {buckets}")
        if replicas < 1:
            raise ValueError(f"replicas={replicas} must be >= 1")
        if sharded and replicas != 1:
            raise ValueError(
                "sharded=True serves through ONE shard_map'd executor "
                "spanning the replica mesh; combine it with replicas=1 "
                "(use plain replicas=N for independent-executor routing)")
        if sharded and plan is not None:
            raise ValueError("sharded=True plans its own shard_map'd "
                             "dispatch; plan= applies to replica engines")
        self.bundle = bundle
        self.buckets = tuple(int(b) for b in buckets)
        self.max_wait_s = max_wait_ms / 1e3
        if plan is None and not sharded:
            plan = plan_cascade_exec(bundle.cfg, fused=fused,
                                     use_kernel=use_kernel)
        self.plan = plan
        kern = plan.use_kernel if plan is not None else (
            (detect_backend() == "tpu") if use_kernel is None
            else use_kernel)
        self.use_kernel = kern
        self.fused = plan.fused if plan is not None else fused
        self.sharded = sharded
        if max_dispatch_retries < 0:
            raise ValueError(f"max_dispatch_retries={max_dispatch_retries} "
                             f"must be >= 0")
        self.max_dispatch_retries = max_dispatch_retries
        self.revive_probe = revive_probe
        self.chaos = chaos
        self.metrics = metrics or ServeMetrics()
        self.health = health or ReplicaHealthTracker(replicas)
        if self.health.num_replicas != replicas:
            raise ValueError(
                f"health tracker covers {self.health.num_replicas} "
                f"replicas, engine has {replicas}")
        if sharded:
            from repro.serve.sharded import make_sharded_forward_fn
            # Pass use_kernel through unresolved: None must stay "auto"
            # so an o_sharded plan can legally fall to the jnp path
            # (an *explicit* True is refused there — see sharded.py).
            forwards = [make_sharded_forward_fn(
                bundle, use_kernel=use_kernel, mode=shard_mode)]
            devs: List = [None]
        elif replicas == 1 and devices is None:
            # Single replica, unpinned: identical to the classic engine
            # (no cross-device transfers on single-device hosts).
            forwards = [self._replica_forward(None)]
            devs = [None]
        else:
            pool = list(devices) if devices is not None \
                else jax.local_devices()
            devs = [pool[i % len(pool)] for i in range(replicas)]
            forwards = [self._replica_forward(d) for d in devs]
        self._executors = [
            _ReplicaExecutor(i, f, buckets=self.buckets, device=d,
                             engine_metrics=self.metrics,
                             health=self.health,
                             redispatch=self._redispatch, chaos=chaos)
            for i, (f, d) in enumerate(zip(forwards, devs))]
        self._rr = 0  # round-robin cursor for routing tie-breaks
        self._queue: "queue.Queue" = queue.Queue()
        self._thread: Optional[threading.Thread] = None
        self._closed = False
        # Serializes the closed-check + enqueue in submit() against close(),
        # so a request can never land behind the _STOP sentinel and hang.
        self._submit_lock = threading.Lock()

    def _replica_forward(self, device) -> Callable:
        """Every fused plan with a faster-but-fallible route (the
        Pallas kernel flavors and the blocked CPU cascade) gets the
        one-shot degradable wrapper — a failing route downgrades that
        replica to the bit-exact ``fused_jnp`` twin instead of failing
        its batches.  ``fused_jnp`` itself has no faster route to
        degrade from and uses the plain forward."""
        if self.plan is not None and self.plan.fused \
                and self.plan.route != "fused_jnp":
            return make_degradable_forward_fn(
                self.bundle, plan=self.plan, device=device,
                metrics=self.metrics, chaos=self.chaos)
        return make_forward_fn(self.bundle, plan=self.plan, device=device)

    @property
    def replicas(self) -> int:
        return len(self._executors)

    @property
    def replica_metrics(self) -> List[ServeMetrics]:
        return [ex.metrics for ex in self._executors]

    # -- lifecycle --------------------------------------------------------

    def start(self) -> "LUTServeEngine":
        if self._thread is None:
            for ex in self._executors:
                ex.start()
            self._thread = threading.Thread(target=self._dispatch_loop,
                                            daemon=True,
                                            name="lut-serve-dispatch")
            self._thread.start()
        return self

    def warmup(self) -> None:
        """Trace/compile every bucket shape on every replica up front so
        no client request ever pays a compile.  Raises if the planned
        route cannot compile (it is never silently downgraded here)."""
        f = self.bundle.cfg.in_features
        for ex in self._executors:
            ex.warmup(f)

    def replica_devices(self) -> List[set]:
        """Per replica, the devices its bundle operands live on (empty
        for the sharded executor, whose forward does not record them)."""
        return [ex.operand_devices() for ex in self._executors]

    def close(self) -> None:
        with self._submit_lock:
            if self._closed:
                return
            self._closed = True
            self._queue.put(_STOP)
        if self._thread is not None:
            self._thread.join()
            self._thread = None
        # Executors drain already-routed batches, then exit.
        for ex in self._executors:
            ex.stop()

    def __enter__(self) -> "LUTServeEngine":
        self.start()
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # -- client API -------------------------------------------------------

    def submit(self, x: np.ndarray, *,
               timeout_s: Optional[float] = None) -> "Future[np.ndarray]":
        """Enqueue a request of shape (n, in_features) or (in_features,).
        The future resolves to the (n,) int32 class predictions ((1,) for a
        single flat sample).  ``timeout_s`` sets a per-request deadline:
        a request still unserved when it passes resolves with a typed
        :class:`DeadlineExceeded` (counted in ``metrics``) instead of
        occupying a dispatch it can no longer use."""
        x = np.asarray(x, np.float32)
        if x.ndim == 1:
            x = x[None, :]
        if x.ndim != 2 or x.shape[1] != self.bundle.cfg.in_features:
            raise ValueError(
                f"request shape {x.shape} != (n, "
                f"{self.bundle.cfg.in_features})")
        if timeout_s is not None and timeout_s <= 0:
            raise ValueError(f"timeout_s={timeout_s} must be positive")
        req = _Request(x, timeout_s)
        with self._submit_lock:
            if self._closed:
                raise RuntimeError("engine is closed")
            if self._thread is None:
                self.start()
            self._queue.put(req)
        return req.future

    def predict(self, x: np.ndarray, *,
                timeout_s: Optional[float] = None) -> np.ndarray:
        """Synchronous convenience wrapper over submit()."""
        return self.submit(x, timeout_s=timeout_s).result()

    # -- dispatcher -------------------------------------------------------

    def _dispatch_loop(self) -> None:
        max_bucket = self.buckets[-1]
        stop = False
        while not stop:
            try:
                with TraceAnnotation(S.SERVE_AWAIT):
                    first = self._queue.get(timeout=0.05)
            except queue.Empty:
                continue
            if first is _STOP:
                break
            with TraceAnnotation(S.SERVE_COALESCE) as span:
                batch: List[_Request] = [first]
                total = first.n
                deadline = time.perf_counter() + self.max_wait_s
                # Coalesce until the largest bucket is full or the
                # admission window closes — whichever is first.
                while total < max_bucket:
                    wait = deadline - time.perf_counter()
                    if wait <= 0:
                        break
                    try:
                        nxt = self._queue.get(timeout=wait)
                    except queue.Empty:
                        break
                    if nxt is _STOP:
                        stop = True
                        break
                    batch.append(nxt)
                    total += nxt.n
                span.set_metadata(requests=len(batch), samples=total)
                self._route(batch, total)
        # fail any requests left behind on shutdown
        while True:
            try:
                r = self._queue.get_nowait()
            except queue.Empty:
                break
            if r is not _STOP:
                _complete(r.future, exc=RuntimeError("engine closed"))

    def _route(self, batch: List[_Request], total: int) -> None:
        """Route one coalesced batch via :func:`route_least_loaded`; with
        no healthy replica left (after one auto-revive probe round),
        shed the batch with a typed :class:`NoHealthyReplicas` instead
        of queueing it behind a pool that can never serve it."""
        batch = _drop_expired(batch, self.metrics)
        if not batch:
            return
        total = sum(r.n for r in batch)
        depth = self._queue.qsize()
        chosen = route_least_loaded(self._executors, self.health, self._rr)
        if chosen is None:
            self._probe_evicted()
            chosen = route_least_loaded(self._executors, self.health,
                                        self._rr)
        if chosen is None:
            err = NoHealthyReplicas(
                f"no healthy replicas (of {len(self._executors)}) — "
                f"failure counts {self.health.failure_counts()}")
            for r in batch:
                if _complete(r.future, exc=err):
                    self.metrics.record_shed()
            return
        self._rr = chosen.rid
        chosen.dispatch(batch, total, depth)

    def _probe_evicted(self) -> None:
        """Auto-revive hook: ask ``revive_probe(rid)`` about every
        evicted replica and re-admit the ones it vouches for.  A
        raising probe counts as 'still down' — a health check must
        never take the dispatcher thread with it."""
        if self.revive_probe is None:
            return
        healthy = set(self.health.healthy_ids())
        for ex in self._executors:
            if ex.rid in healthy:
                continue
            try:
                ok = bool(self.revive_probe(ex.rid))
            except Exception:
                ok = False
            if ok:
                self.health.revive(ex.rid)

    def _redispatch(self, batch: List[_Request], total: int,
                    attempts: int, failed_rid: int) -> bool:
        """Self-healing hook handed to every executor: after a dispatch
        failure, re-route the batch to a healthy replica — preferring
        any replica other than the one that just failed — up to
        ``max_dispatch_retries`` retries.  Operand arrays live on the
        host (each dispatch uploads fresh device buffers), so replaying
        the identical batch is always safe."""
        if attempts > self.max_dispatch_retries:
            return False
        chosen = route_least_loaded(self._executors, self.health, self._rr,
                                    exclude=failed_rid)
        if chosen is None:
            self._probe_evicted()
            chosen = route_least_loaded(self._executors, self.health,
                                        self._rr, exclude=failed_rid)
        if chosen is None:
            return False
        self._rr = chosen.rid
        self.metrics.record_redispatch()
        chosen.dispatch(batch, total, self._queue.qsize(), attempts)
        return True
