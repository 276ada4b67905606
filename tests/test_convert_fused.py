"""Fused truth-table conversion: the refactor's hard invariant.

The device-resident sweep (on-device enumeration, shared cached compile,
fused bit-packing) must emit tables BIT-IDENTICAL to the pre-refactor
converter for the same (params, state) — ``_legacy_convert`` vendors
that converter (host-side enumeration, fresh ``@jax.jit`` closure per
layer, chunked numpy round-trips) and every paper geometry is compared
table-for-table.  Also covered: packed-direct emission == host
``pack_tables`` of the unpacked result, compile-count caching across
layers that share a geometry, a warm conversion that binds no eager
primitive (the slot scales are computed inside the sweep), the
kernel-routed subnet path vs its jnp oracle, and serving-ready bundles
whose ``prepack`` is a no-op.
"""
import importlib
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from benchmarks.common import cpu_threads_pinned  # noqa: E402
from benchmarks.convert_bench import _legacy_convert  # noqa: E402
from repro.core import lut_infer as LI  # noqa: E402
from repro.core import model as M
from repro.core import truth_table as TT
from repro.core.nl_config import (INPUT, LUTGraphConfig, LUTNodeSpec,
                                  NeuraLUTConfig)

ALL_GEOMETRIES = [
    ("neuralut_hdr_5l", "full"), ("neuralut_hdr_5l", "reduced"),
    ("neuralut_jsc_2l", "full"), ("neuralut_jsc_2l", "reduced"),
    ("neuralut_jsc_5l", "full"), ("neuralut_jsc_5l", "reduced"),
]


def _trained_like(cfg, seed=0):
    statics = M.model_static(cfg)
    params, state = M.model_init(cfg, jax.random.PRNGKey(seed))
    x = jnp.asarray(
        np.random.default_rng(seed).normal(0, 1, (64, cfg.in_features)),
        jnp.float32)
    # a train step so BN state is non-trivial
    _, _, state = M.model_apply(cfg, params, state, statics, x, train=True)
    return statics, params, state


# ---------------------------------------------------------------------------
# THE acceptance gate: fused == pre-refactor, packed == pack_tables,
# over every paper config geometry


@pytest.mark.parametrize("config_mod,variant", ALL_GEOMETRIES)
def test_fused_bit_exact_vs_legacy_all_geometries(config_mod, variant):
    """Legacy and fused converters are two compilations of the same
    math.  With intra-op threads pinned (tests/conftest.py) the
    size-scaling ppm noise floor is retired: the allowance drops to a
    constant two entries, and any allowed mismatch must carry the
    round()-boundary signature (difference of exactly +-1 code).  The
    constant remains because jaxlib 0.4.36's thunk-runtime CPU client
    does not fully honor the eigen pinning flags — ~1 flip per 3.4M
    entries was still observed under heavy runner load with the pin
    active.  Unpinned (an external XLA_FLAGS overrode the conftest
    pin), the old ppm floor applies.  Either way a real converter bug
    (wrong scale/BN/enumeration order) produces mass mismatches with
    arbitrary deltas and still fails loudly."""
    mod = importlib.import_module(f"repro.configs.{config_mod}")
    cfg = getattr(mod, variant)()
    statics, params, state = _trained_like(cfg, seed=len(cfg.name))
    legacy = _legacy_convert(cfg, params, state, statics)
    tables, packed = TT.convert_packed(cfg, params, state, statics)
    entries = sum(t.size for t in tables)
    allowed = 2 if cpu_threads_pinned() \
        else max(3, entries * 3 // 1_000_000)
    total = 0
    for i, (a, b) in enumerate(zip(legacy, tables)):
        diff = a.astype(np.int32) - b.astype(np.int32)
        n = int((diff != 0).sum())
        total += n
        if n:
            assert np.abs(diff).max() == 1, (
                f"{cfg.name} layer {i}: diverges by more than one code "
                f"— not a rounding-boundary flip")
    assert total <= allowed, (
        f"{cfg.name}: {total}/{entries} entries diverge from the "
        f"pre-refactor converter (allowed boundary noise: {allowed})")
    # packed-direct emission == packing the unpacked conversion (pure
    # integer bit movement — strictly exact, no allowance)
    for i, (t, p) in enumerate(zip(tables, packed)):
        assert (LI.pack_tables(t, cfg.beta) == p).all(), \
            f"{cfg.name} layer {i}: device packing diverges"
        assert (LI.unpack_tables(p, cfg.beta) == t).all()


# ---------------------------------------------------------------------------
# compile caching: consecutive layers sharing (kind, beta_in, F, O, T)
# share ONE compiled sweep


def test_sweep_compile_count_shared_across_layers():
    TT.clear_convert_cache()
    cfg = NeuraLUTConfig(name="tt-cache", in_features=8,
                         layer_widths=(8, 8, 8, 4), num_classes=4,
                         beta=3, fan_in=2, kind="subnet", depth=2,
                         width=4, skip=0)
    statics, params, state = _trained_like(cfg)
    TT.convert(cfg, params, state, statics)
    stats = TT.convert_cache_stats()
    # one static geometry key (all layers share beta/F/T) ...
    assert len(stats) == 1, stats
    # ... and two compiled executables under it: O=8 (x3 layers) + O=4.
    assert sum(stats.values()) == 2, stats
    # converting a SECOND model of the same geometry compiles nothing
    statics2, params2, state2 = _trained_like(cfg, seed=9)
    TT.convert(cfg, params2, state2, statics2)
    assert TT.convert_cache_stats() == stats


def test_jit_cache_size_version_safe():
    """``convert_cache_stats`` reads each cached sweep's compile count
    through the jit wrapper's ``_cache_size()``: it must count real
    compiles, not report a placeholder."""
    fn = jax.jit(lambda x: x + 1)
    assert fn._cache_size() == 0
    fn(1)
    fn(2)
    assert fn._cache_size() == 1
    fn(jnp.ones(3))
    assert fn._cache_size() == 2
    TT.clear_convert_cache()
    cfg = NeuraLUTConfig(name="tt-stats", in_features=4,
                         layer_widths=(4, 2), num_classes=2, beta=2,
                         fan_in=2, kind="subnet", depth=2, width=4,
                         skip=0)
    statics, params, state = _trained_like(cfg)
    TT.convert(cfg, params, state, statics)
    stats = TT.convert_cache_stats()
    assert stats and all(n >= 1 for n in stats.values()), stats


# ---------------------------------------------------------------------------
# a warm conversion is one dispatch per sweep and one fetch: the slot
# scales are computed inside the jitted sweep, never by eager ops


CHAIN = NeuraLUTConfig(name="tt-chain", in_features=6,
                       layer_widths=(8, 5, 3), num_classes=3, beta=2,
                       fan_in=2, kind="subnet", depth=2, width=4, skip=0)

# node c concatenates two sources, so its sweep gathers from a pool of
# two quantizers' scales
MULTI_SOURCE_DAG = LUTGraphConfig(
    name="tt-dag", in_features=6, num_classes=3, beta=2, kind="subnet",
    depth=2, width=4, skip=0,
    nodes=(LUTNodeSpec(name="a", width=4, fan_in=2, inputs=(INPUT,),
                       arity=2),
           LUTNodeSpec(name="b", width=5, fan_in=2, inputs=(INPUT,),
                       arity=2),
           LUTNodeSpec(name="c", width=3, fan_in=2, inputs=("a", "b"))))


@pytest.mark.parametrize("cfg", [CHAIN, MULTI_SOURCE_DAG],
                         ids=["chain", "dag"])
def test_warm_conversion_binds_no_eager_primitive(cfg, monkeypatch):
    from jax._src import dispatch
    if not (hasattr(dispatch, "apply_primitive")
            and hasattr(dispatch, "xla_primitive_callable")):
        pytest.skip("jax._src.dispatch has no apply_primitive / "
                    "xla_primitive_callable in this JAX version")
    statics, params, state = _trained_like(cfg)
    first = TT.convert_packed(cfg, params, state, statics)  # compiles
    # Each primitive's eager impl is ``partial(apply_primitive, prim)``,
    # bound at import, so patching ``apply_primitive`` would see nothing;
    # every call of it looks up ``xla_primitive_callable`` anew.
    bound = []
    lookup = dispatch.xla_primitive_callable

    def counting(prim, **kw):
        bound.append(prim.name)
        return lookup(prim, **kw)

    monkeypatch.setattr(dispatch, "xla_primitive_callable", counting)
    second = TT.convert_packed(cfg, params, state, statics)
    monkeypatch.undo()
    assert bound == [], f"eager primitives in a warm conversion: {bound}"
    first, second = (jax.tree_util.tree_leaves(c) for c in (first, second))
    assert len(first) == len(second) > 0
    for a, b in zip(first, second):
        np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------------
# kernel-routed subnet evaluation vs the jnp oracle


def test_kernel_routed_conversion_matches_jnp_oracle():
    cfg = NeuraLUTConfig(name="tt-kroute", in_features=8,
                         layer_widths=(8, 6, 4), num_classes=4, beta=3,
                         fan_in=3, kind="subnet", depth=2, width=4,
                         skip=2, beta_in=4, fan_in_0=2)
    statics, params, state = _trained_like(cfg, seed=1)
    t_jnp = TT.convert(cfg, params, state, statics,
                       use_subnet_kernel=False)
    t_kernel = TT.convert(cfg, params, state, statics,
                          use_subnet_kernel=True)
    for i, (a, b) in enumerate(zip(t_jnp, t_kernel)):
        assert (a == b).all(), f"layer {i}: kernel route diverges"


# ---------------------------------------------------------------------------
# serving handoff: convert_packed bundles need no prepack


def test_convert_packed_bundle_prepack_noop():
    from repro.serve import bundle_from_training
    cfg = NeuraLUTConfig(name="tt-bundle", in_features=6,
                         layer_widths=(6, 3), num_classes=3, beta=2,
                         fan_in=2, kind="subnet", depth=2, width=4,
                         skip=0)
    statics, params, state = _trained_like(cfg)
    tables, packed = TT.convert_packed(cfg, params, state, statics)
    bundle = bundle_from_training(cfg, params, tables, statics,
                                  packed_tables=packed)
    # serving-ready on arrival ...
    assert bundle.packed_tables is not None
    assert bundle.shift_mats is not None and bundle.cascade_geom is not None
    before = (bundle.packed_tables, bundle.shift_mats, bundle.cascade_geom)
    bundle.prepack()
    # ... and prepack touches nothing (no repack, no rebuild)
    assert bundle.packed_tables is before[0]
    assert bundle.shift_mats is before[1]
    assert bundle.cascade_geom is before[2]
    for t, p in zip(bundle.tables, bundle.packed_tables):
        assert (LI.unpack_tables(p, cfg.beta) == t).all()


def test_convert_packed_rejects_unpackable_geometry():
    # beta=2 -> P=16 packed slots; a layer with T=4 entries cannot fill
    # one packed word and must be refused clearly.
    cfg = NeuraLUTConfig(name="tt-toosmall", in_features=4,
                         layer_widths=(3, 2), num_classes=2, beta=2,
                         fan_in=1, kind="linear")
    statics, params, state = _trained_like(cfg)
    with pytest.raises(ValueError, match="packed word capacity"):
        TT.convert_packed(cfg, params, state, statics)


# ---------------------------------------------------------------------------
# guard + chunking behaviour carried over from the old converter


def test_oversized_guard_message_unchanged():
    cfg = NeuraLUTConfig(name="tt-guard2", in_features=8,
                         layer_widths=(4, 2), num_classes=2, beta=6,
                         fan_in=4, kind="linear")  # 24 address bits
    statics = M.model_static(cfg)
    params, state = M.model_init(cfg, jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="> 20 address bits"):
        TT.layer_truth_table(cfg, params, state, statics, 0)


def test_chunked_sweep_equals_single_chunk():
    cfg = NeuraLUTConfig(name="tt-chunk", in_features=6,
                         layer_widths=(6, 3), num_classes=3, beta=3,
                         fan_in=2, kind="subnet", depth=2, width=4,
                         skip=0)
    statics, params, state = _trained_like(cfg)
    # T = 2^6 = 64; batch=24 rounds the chunk down to 16 -> 4 chunks
    small = TT.layer_truth_table(cfg, params, state, statics, 0, batch=24)
    whole = TT.layer_truth_table(cfg, params, state, statics, 0, batch=64)
    assert (small == whole).all()


# ---------------------------------------------------------------------------
# one fetch per conversion: every entry point returns host arrays of the
# documented form, and the packed form's tables equal the unpacked one's


def _per_branch(cfg, tables):
    """Each layer's tables as a list, whatever the config's kind."""
    return [tb if isinstance(cfg, LUTGraphConfig) else [tb]
            for tb in tables]


@pytest.mark.parametrize("cfg", [CHAIN, MULTI_SOURCE_DAG],
                         ids=["chain", "dag"])
def test_convert_and_convert_packed_tables_identical(cfg):
    statics, params, state = _trained_like(cfg)
    unpacked = TT.convert(cfg, params, state, statics)
    tables, packed = TT.convert_packed(cfg, params, state, statics)
    assert len(unpacked) == len(tables) == len(packed) == cfg.num_layers
    for i, (u, t, p) in enumerate(zip(*(_per_branch(cfg, x) for x in
                                        (unpacked, tables, packed)))):
        assert len(u) == len(t) == len(p), f"layer {i}"
        for a, b, w in zip(u, t, p):
            assert a.dtype == b.dtype == np.uint16
            assert w.dtype == np.int32
            np.testing.assert_array_equal(a, b)
            np.testing.assert_array_equal(LI.pack_tables(b, cfg.beta), w)


@pytest.mark.parametrize("cfg", [CHAIN, MULTI_SOURCE_DAG],
                         ids=["chain", "dag"])
def test_convert_returns_uint16_host_tables_of_documented_shapes(cfg):
    statics, params, state = _trained_like(cfg)
    tables = _per_branch(cfg, TT.convert(cfg, params, state, statics))
    arity = ([nd.arity for nd in cfg.nodes]
             if isinstance(cfg, LUTGraphConfig) else [1] * cfg.num_layers)
    assert [len(tb) for tb in tables] == arity
    for i, tb in enumerate(tables):
        for t in tb:
            assert type(t) is np.ndarray and t.dtype == np.uint16
            assert t.shape == (cfg.layer_widths[i], cfg.table_size(i))
            assert int(t.max()) < 2 ** cfg.beta


@pytest.mark.parametrize("cfg,where", [
    # layer 0 (T = 2^(2*2) = 16) fills one word of P = 16 slots;
    # layer 1 (T = 2^(2*1) = 4) cannot
    (NeuraLUTConfig(name="tt-late-small", in_features=4,
                    layer_widths=(4, 2), num_classes=2, beta=2,
                    fan_in=1, fan_in_0=2, kind="linear"), "layer 1"),
    (LUTGraphConfig(name="tt-dag-small", in_features=4, num_classes=2,
                    beta=2, kind="linear",
                    nodes=(LUTNodeSpec(name="a", width=4, fan_in=2,
                                       inputs=(INPUT,)),
                           LUTNodeSpec(name="c", width=2, fan_in=1,
                                       inputs=("a",)))), "node 1")],
    ids=["chain", "dag"])
def test_unpackable_geometry_names_its_layer_before_any_fetch(
        cfg, where, monkeypatch):
    statics, params, state = _trained_like(cfg)
    fetched = []
    monkeypatch.setattr(TT, "_fetch", fetched.append)
    with pytest.raises(ValueError, match=f"^{where}: .*packed word "
                                         f"capacity"):
        TT.convert_packed(cfg, params, state, statics)
    assert fetched == []
