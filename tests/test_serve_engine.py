"""Production LUT serving subsystem (repro.serve).

Covers the three pillars of the engine:
  * dynamic batcher: bucket selection, padding accounting, request/response
    ordering under many concurrent single-sample submits;
  * registry: save -> load round-trip is bit-exact vs the lut_forward
    oracle, across the checkpoint-store persistence layer;
  * metrics: nearest-rank percentile math and report invariants.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import lut_infer as LI
from repro.core import model as M
from repro.core import truth_table as TT
from repro.core.nl_config import NeuraLUTConfig
from repro.serve import (LUTServeEngine, ServeMetrics, TableRegistry,
                         bundle_from_training, percentile, pick_bucket)


def _tiny_cfg(name="serve-tiny", kind="subnet"):
    return NeuraLUTConfig(
        name=name, in_features=6, layer_widths=(8, 3), num_classes=3,
        beta=2, fan_in=2, kind=kind, depth=2, width=4, skip=0)


def _tiny_bundle(cfg=None, seed=0):
    cfg = cfg or _tiny_cfg()
    statics = M.model_static(cfg)
    params, state = M.model_init(cfg, jax.random.PRNGKey(seed))
    x = jnp.asarray(np.random.default_rng(seed).normal(
        0, 1, (64, cfg.in_features)), jnp.float32)
    _, _, state = M.model_apply(cfg, params, state, statics, x, train=True)
    tables = TT.convert(cfg, params, state, statics)
    return bundle_from_training(cfg, params, tables, statics), \
        (params, state, tables, statics)


def _oracle_preds(bundle, train, x):
    params, _, tables, statics = train
    codes = LI.input_codes(bundle.cfg, params, jnp.asarray(x))
    out = LI.lut_forward(bundle.cfg, tables, statics, codes)
    return np.asarray(jnp.argmax(
        LI.class_values(bundle.cfg, params, out), -1))


# ---------------------------------------------------------------------------
# Dynamic batcher


def test_pick_bucket_rounds_up():
    buckets = (1, 8, 64, 256)
    assert pick_bucket(1, buckets) == 1
    assert pick_bucket(2, buckets) == 8
    assert pick_bucket(8, buckets) == 8
    assert pick_bucket(9, buckets) == 64
    assert pick_bucket(65, buckets) == 256
    # larger than max -> max (engine chunks)
    assert pick_bucket(1000, buckets) == 256
    with pytest.raises(ValueError):
        pick_bucket(0, buckets)


def test_engine_rejects_bad_buckets_and_shapes():
    bundle, _ = _tiny_bundle()
    with pytest.raises(ValueError):
        LUTServeEngine(bundle, buckets=(8, 1))
    with LUTServeEngine(bundle, use_kernel=False) as eng:
        with pytest.raises(ValueError):
            eng.submit(np.zeros((4, 99), np.float32))


def test_single_sample_ordering_and_bit_exactness():
    bundle, train = _tiny_bundle()
    x = np.random.default_rng(1).normal(
        0, 1, (40, bundle.cfg.in_features)).astype(np.float32)
    ref = _oracle_preds(bundle, train, x)
    with LUTServeEngine(bundle, use_kernel=False, max_wait_ms=1.0,
                        buckets=(1, 8)) as eng:
        eng.warmup()
        futs = [eng.submit(x[i]) for i in range(len(x))]
        got = np.array([f.result()[0] for f in futs])
    assert (got == ref).all()


def test_oversized_request_chunks_through_max_bucket():
    bundle, train = _tiny_bundle()
    buckets = (1, 4)
    n = 11  # 4 + 4 + pad(3->4): three dispatches, 12 padded slots
    x = np.random.default_rng(2).normal(
        0, 1, (n, bundle.cfg.in_features)).astype(np.float32)
    ref = _oracle_preds(bundle, train, x)
    with LUTServeEngine(bundle, use_kernel=False, buckets=buckets) as eng:
        got = eng.predict(x)
    assert got.shape == (n,)
    assert (got == ref).all()
    rep = eng.metrics.report()
    assert rep["batches"] == 1  # one coalesced dispatch group
    assert rep["samples"] == n
    # occupancy accounts padding: 11 real / 12 padded slots
    assert rep["batch_occupancy"] == pytest.approx(11 / 12)


def test_kernel_and_oracle_paths_agree():
    bundle, train = _tiny_bundle()
    x = np.random.default_rng(3).normal(
        0, 1, (16, bundle.cfg.in_features)).astype(np.float32)
    ref = _oracle_preds(bundle, train, x)
    with LUTServeEngine(bundle, use_kernel=True, buckets=(16,)) as eng:
        got = eng.predict(x)  # Pallas interpret mode on CPU
    assert (got == ref).all()


def test_cancelled_future_does_not_kill_dispatcher():
    bundle, train = _tiny_bundle()
    x = np.random.default_rng(6).normal(
        0, 1, (4, bundle.cfg.in_features)).astype(np.float32)
    ref = _oracle_preds(bundle, train, x)
    with LUTServeEngine(bundle, use_kernel=False, max_wait_ms=1.0) as eng:
        eng.warmup()
        doomed = eng.submit(x[0])
        doomed.cancel()  # client walks away while the request is queued
        # the dispatcher must survive and keep serving
        got = eng.predict(x)
    assert (got == ref).all()


def test_submit_after_close_fails_fast_with_clear_error():
    bundle, _ = _tiny_bundle()
    eng = LUTServeEngine(bundle, use_kernel=False)
    eng.start()
    eng.close()
    # Fails at the door (no enqueue, no hang) and says why.
    with pytest.raises(RuntimeError, match="closed"):
        eng.submit(np.zeros((1, bundle.cfg.in_features), np.float32))


def test_double_close_is_idempotent():
    """close() is a terminal no-op after the first call — started or
    not, repeated closes must neither raise nor hang on joined threads."""
    bundle, _ = _tiny_bundle()
    eng = LUTServeEngine(bundle, use_kernel=False)
    eng.start()
    eng.predict(np.zeros((2, bundle.cfg.in_features), np.float32))
    eng.close()
    eng.close()
    with pytest.raises(RuntimeError, match="closed"):
        eng.submit(np.zeros((1, bundle.cfg.in_features), np.float32))
    never_started = LUTServeEngine(bundle, use_kernel=False)
    never_started.close()
    never_started.close()
    with pytest.raises(RuntimeError, match="closed"):
        never_started.submit(
            np.zeros((1, bundle.cfg.in_features), np.float32))


def test_close_resolves_every_inflight_future():
    """Shutdown with a backlog: every submitted future must resolve —
    served if its batch was already accepted by the executor, failed
    with 'engine closed' otherwise — and all threads must join."""
    bundle, _ = _tiny_bundle()
    x = np.random.default_rng(7).normal(
        0, 1, (3, bundle.cfg.in_features)).astype(np.float32)
    eng = LUTServeEngine(bundle, use_kernel=False, buckets=(1, 8),
                         max_wait_ms=10.0)
    eng.start()
    eng.warmup()
    futs = [eng.submit(x) for _ in range(30)]
    eng.close()
    assert eng._thread is None
    assert all(ex._thread is None for ex in eng._executors)
    for f in futs:
        assert f.done()
        if f.exception() is None:
            assert f.result().shape == (3,)
        else:
            assert "engine closed" in str(f.exception())


def test_warmup_raises_when_primary_route_cannot_compile():
    """A fused kernel that the backend refuses to compile (here: a
    Pallas kernel for another platform, compiled rather than
    interpreted) fails warmup loudly; warmup never hands the batch to
    the jnp fallback, so no downgrade is counted."""
    from repro.core.exec_plan import plan_cascade_exec
    bundle, _ = _tiny_bundle()
    foreign = ("fused_kernel_gpu" if jax.default_backend() == "tpu"
               else "fused_kernel_tpu")
    plan = plan_cascade_exec(bundle.cfg, route=foreign, interpret=False)
    eng = LUTServeEngine(bundle, plan=plan)
    try:
        with pytest.raises(Exception):
            eng.warmup()
        assert eng.metrics.downgrades == 0
        assert eng.metrics.report()["kernel_downgrades"] == 0
    finally:
        eng.close()


def test_replica_devices_report_operand_placement():
    """Each replica's bundle operands live on the device it is pinned
    to, for both the plain and the degradable forward."""
    bundle, _ = _tiny_bundle()
    dev = jax.devices()[0]
    for kw in ({"use_kernel": False}, {}):
        with LUTServeEngine(bundle, replicas=2, devices=[dev], **kw) as eng:
            assert eng.replica_devices() == [{dev}, {dev}]


# ---------------------------------------------------------------------------
# Registry


def test_registry_roundtrip_bit_exact(tmp_path):
    bundle, train = _tiny_bundle()
    reg = TableRegistry(str(tmp_path))
    reg.save(bundle.cfg.name, bundle)
    assert reg.has(bundle.cfg.name)
    assert reg.list_models() == [bundle.cfg.name]
    loaded = reg.load(bundle.cfg.name)
    assert loaded.cfg == bundle.cfg
    for a, b in zip(loaded.tables, bundle.tables):
        assert a.dtype == b.dtype and (a == b).all()
    for a, b in zip(loaded.statics, bundle.statics):
        assert (a["conn"] == b["conn"]).all()
    x = np.random.default_rng(4).normal(
        0, 1, (32, bundle.cfg.in_features)).astype(np.float32)
    ref = _oracle_preds(bundle, train, x)
    with LUTServeEngine(loaded, use_kernel=False) as eng:
        got = eng.predict(x)
    assert (got == ref).all()


def test_registry_versioning_and_missing(tmp_path):
    bundle, _ = _tiny_bundle()
    b2, _ = _tiny_bundle(seed=5)  # different weights -> different tables
    assert any((a != b).any() for a, b in zip(bundle.tables, b2.tables))
    reg = TableRegistry(str(tmp_path))
    with pytest.raises(FileNotFoundError):
        reg.load("nope")
    assert not reg.has("nope")
    reg.save("m", bundle, version=0)
    reg.save("m", b2, version=1)
    latest = reg.load("m")
    for a, b in zip(latest.tables, b2.tables):
        assert (a == b).all()
    loaded0 = reg.load("m", version=0)
    for a, b in zip(loaded0.tables, bundle.tables):
        assert (a == b).all()


def test_registry_preserves_meta(tmp_path):
    bundle, _ = _tiny_bundle()
    bundle.meta["train_acc_q"] = 0.875
    reg = TableRegistry(str(tmp_path))
    reg.save("m", bundle)
    assert reg.load("m").meta["train_acc_q"] == 0.875


# ---------------------------------------------------------------------------
# Metrics


def test_percentile_nearest_rank():
    v = sorted([10.0, 20.0, 30.0, 40.0, 50.0, 60.0, 70.0, 80.0, 90.0, 100.0])
    assert percentile(v, 50) == 50.0
    assert percentile(v, 95) == 100.0
    assert percentile(v, 99) == 100.0
    assert percentile(v, 100) == 100.0
    assert percentile(v, 10) == 10.0
    assert percentile(v, 1) == 10.0
    assert percentile([7.0], 50) == 7.0
    with pytest.raises(ValueError):
        percentile([], 50)
    with pytest.raises(ValueError):
        percentile(v, 0)


def test_metrics_report_math():
    m = ServeMetrics()
    for ms in (1, 2, 3, 4, 5, 6, 7, 8, 9, 10):
        m.record_request(ms / 1e3, 2)
    m.record_batch(n_real=6, n_padded=8, queue_depth=3)
    m.record_batch(n_real=2, n_padded=8, queue_depth=1)
    r = m.report()
    assert r["requests"] == 10
    assert r["samples"] == 20
    assert r["batches"] == 2
    assert r["p50_ms"] == pytest.approx(5.0)
    assert r["p95_ms"] == pytest.approx(10.0)
    assert r["p99_ms"] == pytest.approx(10.0)
    assert r["batch_occupancy"] == pytest.approx(0.5)
    assert r["mean_queue_depth"] == pytest.approx(2.0)
    # render/to_json don't blow up and carry the headline numbers
    assert "p50=5.00ms" in m.render()
    assert '"requests": 10.0' in m.to_json()


def test_metrics_empty_report_is_nan_safe():
    r = ServeMetrics().report()
    assert r["requests"] == 0
    assert np.isnan(r["p50_ms"]) and np.isnan(r["throughput_sps"])


def test_metrics_admission_counters():
    """shed_rate = shed / (admitted + shed): the fraction of *offered*
    load turned away at the multi-tenant admission door."""
    m = ServeMetrics()
    assert m.shed == 0 and m.shed_rate == 0.0  # no offered load yet
    m.record_admitted()
    m.record_admitted(2)
    m.record_shed()
    assert m.shed == 1
    assert m.shed_rate == pytest.approx(0.25)
    r = m.report()
    assert r["admitted"] == 3.0 and r["shed"] == 1.0
    assert r["shed_rate"] == pytest.approx(0.25)
