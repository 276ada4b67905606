"""LUT-graph (DAG) generalization: the chain is the degenerate case.

Pins the PR's acceptance invariants:

  * ``graph_from_chain`` round-trips every shipped chain geometry with
    bit-identical cascade operands (schedules, shift matrices, packed
    tables) and bit-identical serving outputs;
  * random small LUT DAGs (adder trees, diamonds/concat) are bit-exact
    across all four execution paths — the ``graph_lut_forward`` oracle,
    the unpacked ``lut_cascade_ref``, the bit-packed jnp walk, and the
    Pallas ``lut_cascade`` kernel in interpret mode;
  * ``CascadeExec`` dispatches identically to the legacy
    ``meta=``/``beta=``/``use_kernel=`` keyword plumbing it replaced;
  * the ``polylut_add_*`` geometries train, convert, and serve
    end-to-end bit-exact vs the jnp reference;
  * chain-only consumers (RTL emitter, o-sharded layout, per-layer
    serving routes) raise typed ``UnsupportedTopology`` on real DAGs;
  * the registry round-trips both schema versions and reports them via
    ``versions(detail=True)``.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import lut_infer as LI
from repro.core import model as M
from repro.core import truth_table as TT
from repro.core.exec_plan import CascadeExec, plan_cascade_exec
from repro.core.nl_config import (INPUT, LUTGraphConfig, LUTNodeSpec,
                                  NeuraLUTConfig, UnsupportedTopology,
                                  graph_from_chain)
from repro.kernels.lut_cascade import (as_schedule, build_graph_shift_mats,
                                       build_shift_mats, cascade_meta,
                                       cascade_tables, graph_cascade_meta,
                                       graph_cascade_tables, lut_cascade)
from repro.kernels.ops import cascade_apply
from repro.kernels.ref import lut_cascade_packed_ref, lut_cascade_ref

SIX_GEOMETRIES = [
    ("neuralut_hdr_5l", "full"), ("neuralut_hdr_5l", "reduced"),
    ("neuralut_jsc_2l", "full"), ("neuralut_jsc_2l", "reduced"),
    ("neuralut_jsc_5l", "full"), ("neuralut_jsc_5l", "reduced"),
]


def _chain_cfg(config_mod, variant):
    import importlib
    mod = importlib.import_module(f"repro.configs.{config_mod}")
    return getattr(mod, variant)()


def _chain_random_net(cfg, seed=0):
    rng = np.random.default_rng(seed)
    statics, tables = [], []
    w_prev = cfg.in_features
    for i, o in enumerate(cfg.layer_widths):
        f = cfg.layer_fan_in(i)
        statics.append({"conn": rng.integers(0, w_prev, (o, f))})
        tables.append(rng.integers(0, 2 ** cfg.beta,
                                   (o, cfg.table_size(i))).astype(np.uint16))
        w_prev = o
    return tables, statics


def _graph_random_net(cfg: LUTGraphConfig, seed=0):
    """Random per-node branch (tables, statics) with cfg's geometry."""
    rng = np.random.default_rng(seed)
    statics, tables = [], []
    for i, nd in enumerate(cfg.nodes):
        pool_w = cfg.node_in_width(i)
        statics.append({"conns": [
            rng.integers(0, pool_w, (nd.width, nd.fan_in))
            for _ in range(nd.arity)]})
        tables.append([
            rng.integers(0, 2 ** cfg.beta,
                         (nd.width, cfg.table_size(i))).astype(np.uint16)
            for _ in range(nd.arity)])
    return tables, statics


def _input_codes(cfg, b, seed=5):
    rng = np.random.default_rng(seed)
    bits = cfg.layer_in_bits(0)
    return jnp.asarray(rng.integers(0, 2 ** bits, (b, cfg.in_features)),
                       jnp.int32)


def _all_graph_paths(cfg: LUTGraphConfig, tables, statics, codes,
                     block_b=8):
    """Oracle + the three cascade implementations, as numpy arrays."""
    oracle = np.asarray(LI.graph_lut_forward(cfg, tables, statics, codes))
    srcs = [cfg.node_sources(i) for i in range(cfg.num_layers)]
    conns = [[jnp.asarray(c) for c in M.node_static_conns(s)]
             for s in statics]
    tbls = [[jnp.asarray(np.asarray(t).astype(np.int32)) for t in node]
            for node in tables]
    betas = tuple(cfg.node_in_bits(i) for i in range(cfg.num_layers))
    unpacked = np.asarray(lut_cascade_ref(codes, conns, tbls, betas,
                                          srcs=srcs))
    sched = graph_cascade_meta(cfg)
    sms = [jnp.asarray(m) for m in build_graph_shift_mats(cfg, statics)]
    pts = [jnp.asarray(p) for p in graph_cascade_tables(cfg, tables)]
    packed = np.asarray(lut_cascade_packed_ref(codes, sms, pts, cfg.beta,
                                               schedule=sched))
    kernel = np.asarray(lut_cascade(codes, sms, pts, sched,
                                    block_b=block_b))
    return oracle, unpacked, packed, kernel


# ---------------------------------------------------------------------------
# config validation


def _node(name, width=4, fan_in=2, inputs=(INPUT,), arity=1):
    return LUTNodeSpec(name=name, width=width, fan_in=fan_in,
                       inputs=inputs, arity=arity)


def _graph(nodes, **kw):
    kw.setdefault("name", "g")
    kw.setdefault("in_features", 6)
    kw.setdefault("num_classes", nodes[-1].width)
    kw.setdefault("beta", 2)
    kw.setdefault("kind", "linear")
    return LUTGraphConfig(nodes=tuple(nodes), **kw)


def test_graph_config_validation():
    with pytest.raises(ValueError, match="power of two"):
        _graph([_node("a", arity=3), _node("c", inputs=("a",))])
    with pytest.raises(ValueError, match="topological order"):
        _graph([_node("a", inputs=("b",)), _node("b")])
    with pytest.raises(ValueError, match="unequal bit-widths"):
        # arity-2 node emits beta+1 bits; INPUT is beta bits
        _graph([_node("a", arity=2),
                _node("c", inputs=("a", INPUT))])
    with pytest.raises(ValueError, match="arity 1"):
        _graph([_node("c", arity=2)], num_classes=4)
    with pytest.raises(ValueError, match="num_classes"):
        _graph([_node("c", width=4)], num_classes=5)
    with pytest.raises(ValueError, match="duplicate"):
        _graph([_node("a"), _node("a")], num_classes=4)


def test_as_chain_roundtrip_and_refusal():
    cfg = _chain_cfg("neuralut_jsc_5l", "full")
    g = graph_from_chain(cfg)
    assert g.is_chain
    assert g.as_chain() == cfg
    dag = _graph([_node("a", arity=2), _node("c", inputs=("a",))])
    assert not dag.is_chain
    with pytest.raises(UnsupportedTopology):
        dag.as_chain()


# ---------------------------------------------------------------------------
# chain <-> graph: the six shipped geometries are bit-identical through
# either representation (acceptance gate)


@pytest.mark.parametrize("config_mod,variant", SIX_GEOMETRIES)
def test_chain_graph_operands_bit_identical(config_mod, variant):
    cfg = _chain_cfg(config_mod, variant)
    g = graph_from_chain(cfg)
    # geometry accessors agree index-for-index
    assert g.layer_widths == tuple(cfg.layer_widths)
    for i in range(cfg.num_layers):
        assert g.layer_fan_in(i) == cfg.layer_fan_in(i)
        assert g.layer_in_bits(i) == cfg.layer_in_bits(i)
        assert g.table_size(i) == cfg.table_size(i)
    # the DAG schedule degenerates to the legacy per-layer meta
    assert graph_cascade_meta(g) == as_schedule(cascade_meta(cfg))
    # identical kernel operands from the same (tables, statics)
    tables, statics = _chain_random_net(cfg, seed=len(cfg.name))
    legacy_sms = build_shift_mats(cfg, statics)
    graph_sms = build_graph_shift_mats(g, statics)
    assert len(legacy_sms) == len(graph_sms)
    for a, b in zip(legacy_sms, graph_sms):
        assert (a == b).all()
    legacy_pts = cascade_tables(cfg, tables)
    graph_pts = graph_cascade_tables(g, tables)
    for a, b in zip(legacy_pts, graph_pts):
        assert (a == b).all()
    # and identical serving outputs: legacy chain walk vs schedule walk
    codes = _input_codes(cfg, 17)
    sms = [jnp.asarray(m) for m in legacy_sms]
    pts = [jnp.asarray(p) for p in legacy_pts]
    chain_out = np.asarray(lut_cascade_packed_ref(codes, sms, pts,
                                                  cfg.beta))
    dag_out = np.asarray(lut_cascade_packed_ref(
        codes, sms, pts, cfg.beta, schedule=graph_cascade_meta(g)))
    assert (chain_out == dag_out).all()


def test_chain_graph_trained_model_bit_identical():
    """Same seed, same chain: the graph representation trains to the
    same params, converts to the same tables, and serves the same
    predictions as the NeuraLUTConfig it was derived from."""
    from repro.serve import bundle_from_training, make_forward_fn
    cfg = _chain_cfg("neuralut_jsc_2l", "reduced")
    g = graph_from_chain(cfg)
    statics_c = M.model_static(cfg)
    statics_g = M.model_static(g)
    for sc, sg in zip(statics_c, statics_g):
        assert (np.asarray(sc["conn"])
                == np.asarray(M.node_static_conns(sg)[0])).all()
    pc, stc = M.model_init(cfg, jax.random.PRNGKey(3))
    pg, stg = M.model_init(g, jax.random.PRNGKey(3))
    for a, b in zip(jax.tree.leaves(pc), jax.tree.leaves(pg)):
        assert (np.asarray(a) == np.asarray(b)).all()
    x = jnp.asarray(np.random.default_rng(0).normal(0, 1, (64, 16)),
                    jnp.float32)
    _, _, stc = M.model_apply(cfg, pc, stc, statics_c, x, train=True)
    _, _, stg = M.model_apply(g, pg, stg, statics_g, x, train=True)
    tc = TT.convert(cfg, pc, stc, statics_c)
    tg = TT.convert(g, pg, stg, statics_g)
    for a, b in zip(tc, tg):
        assert (np.asarray(a) == np.asarray(b[0])).all()
    bc = bundle_from_training(cfg, pc, tc, statics_c)
    bg = bundle_from_training(g, pg, tg, statics_g)
    fc = make_forward_fn(bc)
    fg = make_forward_fn(bg)
    xq = jnp.asarray(np.random.default_rng(1).normal(0, 1, (32, 16)),
                     jnp.float32)
    assert (np.asarray(fc(xq)) == np.asarray(fg(xq))).all()


# ---------------------------------------------------------------------------
# random LUT DAGs: all four paths bit-exact (property test)


def _random_dag_cfg(rng) -> LUTGraphConfig:
    """Adder-tree / diamond topologies: a rank of mid nodes over the
    input (same arity, so equal output bit-widths), then a classifier
    concatenating a nonempty subset of them."""
    beta = int(rng.integers(2, 4))
    arity = int(rng.choice([1, 2, 4]))
    n_mid = int(rng.integers(1, 3))
    mids = [LUTNodeSpec(name=f"m{j}", width=int(rng.integers(2, 5)),
                        fan_in=2, inputs=(INPUT,), arity=arity)
            for j in range(n_mid)]
    picked = sorted(rng.choice(n_mid, size=int(rng.integers(1, n_mid + 1)),
                               replace=False).tolist())
    cls = LUTNodeSpec(name="cls", width=3, fan_in=2,
                      inputs=tuple(f"m{j}" for j in picked), arity=1)
    return LUTGraphConfig(name="dag-prop", in_features=5, num_classes=3,
                          beta=beta, nodes=tuple(mids) + (cls,),
                          kind="linear")


def _check_dag_case(seed: int) -> None:
    rng = np.random.default_rng(seed)
    cfg = _random_dag_cfg(rng)
    tables, statics = _graph_random_net(cfg, seed=seed + 50)
    codes = _input_codes(cfg, 9, seed=seed + 99)
    oracle, unpacked, packed, kernel = _all_graph_paths(
        cfg, tables, statics, codes, block_b=4)
    assert (unpacked == oracle).all()
    assert (packed == oracle).all()
    assert (kernel == oracle).all()


try:  # guard ONLY the property test — the rest of this module must run
    from hypothesis import given, settings, strategies as st
    _HAVE_HYPOTHESIS = True
except ImportError:
    _HAVE_HYPOTHESIS = False

if _HAVE_HYPOTHESIS:
    @settings(max_examples=16, deadline=None)
    @given(seed=st.integers(0, 2 ** 16))
    def test_random_dag_bit_exact_property(seed):
        _check_dag_case(seed)
else:
    @pytest.mark.parametrize("seed", [0, 1, 2, 3, 4, 5, 6, 7])
    def test_random_dag_bit_exact_property(seed):
        # hypothesis not installed: fixed draws through the same checker
        _check_dag_case(seed)


def test_diamond_concat_dag_bit_exact():
    """Deterministic diamond: two arity-2 nodes, classifier concats
    both buffers (per-source shift-mat splits + summed dots)."""
    cfg = _graph([_node("a", width=4, arity=2),
                  _node("b", width=3, arity=2),
                  _node("c", width=4, inputs=("a", "b"))],
                 num_classes=4, beta=2)
    tables, statics = _graph_random_net(cfg, seed=7)
    codes = _input_codes(cfg, 13, seed=8)
    oracle, unpacked, packed, kernel = _all_graph_paths(
        cfg, tables, statics, codes)
    assert (unpacked == oracle).all()
    assert (packed == oracle).all()
    assert (kernel == oracle).all()


# ---------------------------------------------------------------------------
# CascadeExec: the plan object and its deprecation shim


def test_cascade_exec_plan_properties():
    cfg = _chain_cfg("neuralut_jsc_2l", "reduced")
    plan = plan_cascade_exec(cfg, use_kernel=False)
    assert plan.route == "fused_jnp" and plan.fused and plan.is_chain
    assert not plan.use_kernel
    hash(plan)  # frozen + hashable: jit-static and cache-keyable
    assert dataclasses.replace(plan, block_b=4).block_b == 4
    with pytest.raises(ValueError, match="unknown cascade route"):
        CascadeExec(route="warp", beta=2, schedule=plan.schedule)
    dag = _graph([_node("a", arity=2), _node("c", inputs=("a",))])
    for route in ("layer_jnp", "layer_kernel"):
        with pytest.raises(UnsupportedTopology):
            plan_cascade_exec(dag, route=route)
    # fused routes plan fine on the same DAG
    assert not plan_cascade_exec(dag, use_kernel=True).is_chain


def test_cascade_apply_legacy_shim_dispatches_identically():
    cfg = _chain_cfg("neuralut_jsc_2l", "reduced")
    tables, statics = _chain_random_net(cfg, seed=2)
    sms = [jnp.asarray(m) for m in build_shift_mats(cfg, statics)]
    pts = [jnp.asarray(p) for p in cascade_tables(cfg, tables)]
    codes = _input_codes(cfg, 16)
    for use_kernel in (False, True):
        with pytest.deprecated_call():  # legacy trio warns since PR 10
            legacy = np.asarray(cascade_apply(
                codes, sms, pts, meta=cascade_meta(cfg), beta=cfg.beta,
                use_kernel=use_kernel, block_b=8))
        plan = plan_cascade_exec(cfg, use_kernel=use_kernel, block_b=8)
        new = np.asarray(cascade_apply(codes, sms, pts, plan=plan))
        assert (legacy == new).all()
    with pytest.raises(TypeError, match="plan= or the legacy"):
        cascade_apply(codes, sms, pts)  # neither form
    with pytest.raises(TypeError):
        cascade_apply(codes, sms, pts, plan=plan, meta=cascade_meta(cfg),
                      beta=cfg.beta, use_kernel=False)  # both forms


def test_make_forward_fn_plan_equals_keywords():
    from repro.serve import bundle_from_training, make_forward_fn
    cfg = _chain_cfg("neuralut_jsc_2l", "reduced")
    statics = M.model_static(cfg)
    params, state = M.model_init(cfg, jax.random.PRNGKey(0))
    x = jnp.asarray(np.random.default_rng(0).normal(0, 1, (64, 16)),
                    jnp.float32)
    _, _, state = M.model_apply(cfg, params, state, statics, x, train=True)
    tables = TT.convert(cfg, params, state, statics)
    bundle = bundle_from_training(cfg, params, tables, statics)
    xq = jnp.asarray(np.random.default_rng(1).normal(0, 1, (8, 16)),
                     jnp.float32)
    for uk, fu in ((False, True), (True, True), (False, False)):
        kw = make_forward_fn(bundle, use_kernel=uk, fused=fu)
        pl = make_forward_fn(
            bundle, plan=plan_cascade_exec(cfg, fused=fu, use_kernel=uk))
        assert (np.asarray(kw(xq)) == np.asarray(pl(xq))).all()


# ---------------------------------------------------------------------------
# polylut_add geometries: train -> convert -> serve end-to-end


@pytest.mark.parametrize("arch", ["polylut-add-jsc-2l",
                                  "polylut-add-jsc-5l",
                                  "polylut-add-jsc-xl"])
def test_graph_init_gives_every_leaf_its_own_buffer(arch):
    """The training epoch donates every leaf of (params, state, opt) on an
    accelerator, and a buffer that two leaves share cannot be donated
    twice: each adder branch's batch norm needs arrays of its own."""
    from repro.config import get_config
    from repro.optim import adamw_init
    cfg = get_config(arch, reduced=True)
    params, state = M.model_init(cfg, jax.random.PRNGKey(0))
    leaves = jax.tree.leaves((params, state, adamw_init(params)))
    ptrs = [leaf.unsafe_buffer_pointer() for leaf in leaves]
    assert len(set(ptrs)) == len(ptrs)


@pytest.mark.parametrize("arch", ["polylut-add-jsc-2l",
                                  "polylut-add-jsc-5l",
                                  "polylut-add-jsc-xl"])
def test_polylut_add_end_to_end_bit_exact(arch, tmp_path):
    from repro.config import get_config
    from repro.core.train import train_neuralut
    from repro.data.synthetic import jsc_synthetic
    from repro.serve import (LUTServeEngine, TableRegistry,
                             bundle_from_training, make_forward_fn)

    cfg = get_config(arch, reduced=True)
    assert not cfg.is_chain  # real adder-tree DAGs, not chains
    x, y = jsc_synthetic(600, seed=0)
    params, state, info = train_neuralut(
        cfg, x[:500], y[:500], x[500:], y[500:], epochs=2, batch=128,
        seed=0)
    statics = M.model_static(cfg)
    tables, packed = TT.convert_packed(cfg, params, state, statics)
    bundle = bundle_from_training(cfg, params, tables, statics,
                                  packed_tables=packed)
    assert bundle.schema_version == 2
    assert bundle.topology[0] == "dag"

    # serving == the graph LUT oracle, bit for bit
    xq = jnp.asarray(x[500:532], jnp.float32)
    codes = LI.input_codes(cfg, bundle.serve_params(), xq)
    out_codes = LI.graph_lut_forward(cfg, tables, statics, codes)
    vals = LI.class_values(cfg, bundle.serve_params(), out_codes)
    want = np.argmax(np.asarray(vals), axis=-1)
    fwd = make_forward_fn(bundle)
    assert (np.asarray(fwd(xq)) == want).all()
    with LUTServeEngine(bundle, buckets=(32,)) as eng:
        assert (np.asarray(eng.predict(xq)) == want).all()

    # and the quantized float model agrees with its LUT twin (the
    # conversion invariant, now per-node)
    _, values, _ = M.model_apply(cfg, params, state, statics, xq,
                                 train=False)
    assert (np.argmax(np.asarray(values), axis=-1) == want).all()

    # registry round-trip: schema v2, topology descriptor, packed
    # operands re-derived identically at load
    reg = TableRegistry(str(tmp_path))
    reg.save(arch, bundle, version=1)
    got = reg.versions(arch, detail=True)
    assert got[0]["version"] == 1 and got[0]["schema_version"] == 2
    assert got[0]["topology"][0] == "dag"
    loaded = reg.load(arch)
    assert loaded.schema_version == 2
    assert loaded.cfg == cfg
    for a, b in zip(bundle.prepack().packed_tables, loaded.packed_tables):
        assert (np.asarray(a) == np.asarray(b)).all()
    f2 = make_forward_fn(loaded)
    assert (np.asarray(f2(xq)) == want).all()


# ---------------------------------------------------------------------------
# chain-only consumers: typed refusal, chain-view acceptance


def _dag_bundle(seed=0):
    from repro.serve import bundle_from_training
    cfg = _graph([_node("a", width=6, arity=2),
                  _node("c", width=4, inputs=("a",))],
                 num_classes=4, beta=2, in_features=6)
    tables, statics = _graph_random_net(cfg, seed=seed)
    params = {"in_quant": {"log_s": np.zeros(6, np.float32)},
              "layers": [{"quant": {"log_s": np.zeros(w, np.float32)}}
                         for w in cfg.layer_widths]}
    return cfg, bundle_from_training(cfg, params, tables, statics)


def test_rtl_refuses_dag_accepts_chain_graph(tmp_path):
    from repro.core import rtl
    cfg, bundle = _dag_bundle()
    with pytest.raises(UnsupportedTopology, match="linear layer pipeline"):
        rtl.generate_top(cfg, bundle.tables, bundle.statics,
                         str(tmp_path / "v"))
    # a chain-shaped graph unwraps to the legacy emitter
    chain = _chain_cfg("neuralut_jsc_2l", "reduced")
    g = graph_from_chain(chain)
    tables, statics = _chain_random_net(chain, seed=1)
    gtables = [[t] for t in tables]
    gstatics = [{"conns": [s["conn"]]} for s in statics]
    paths_c = rtl.generate_top(chain, tables, statics, str(tmp_path / "c"))
    paths_g = rtl.generate_top(g, gtables, gstatics, str(tmp_path / "g"))
    for pc, pg in zip(paths_c, paths_g):
        with open(pc) as fc, open(pg) as fg:
            assert fc.read() == fg.read()


def test_sharded_o_sharded_refuses_dag():
    from repro.serve.sharded import plan_shards
    _, bundle = _dag_bundle()
    with pytest.raises(UnsupportedTopology):
        plan_shards(bundle, 2, mode="o_sharded")
    # replicated covers DAGs
    plan = plan_shards(bundle, 1, mode="replicated")
    assert plan.mode == "replicated"


def test_cost_model_graph_dispatch():
    from repro.core.cost_model import estimate
    cfg = _chain_cfg("neuralut_jsc_5l", "full")
    chain_est = estimate(cfg)
    graph_est = estimate(graph_from_chain(cfg))
    assert graph_est.luts == chain_est.luts
    assert graph_est.layers == chain_est.layers
    # an adder tree pays ROM area per branch + carry LUTs, but parallel
    # branches do not add pipeline levels
    from repro.config import get_config
    add = estimate(get_config("polylut-add-jsc-2l"))
    assert add.layers == 2  # two levels despite 3 branch ROM banks
    assert add.luts > 0


# ---------------------------------------------------------------------------
# registry: both schema versions side by side


def test_registry_mixed_schema_versions(tmp_path):
    from repro.serve import TableRegistry, bundle_from_training
    chain = _chain_cfg("neuralut_jsc_2l", "reduced")
    tables, statics = _chain_random_net(chain, seed=4)
    params = {"in_quant": {"log_s": np.zeros(16, np.float32)},
              "layers": [{"quant": {"log_s": np.zeros(w, np.float32)}}
                         for w in chain.layer_widths]}
    cb = bundle_from_training(chain, params, tables, statics)
    assert cb.schema_version == 1
    assert cb.topology == ("chain", tuple(chain.layer_widths))
    _, gb = _dag_bundle(seed=5)

    reg = TableRegistry(str(tmp_path))
    reg.save("m", cb, version=1)
    reg.save("m", gb, version=2)
    assert reg.versions("m") == [1, 2]
    detail = reg.versions("m", detail=True)
    assert [d["schema_version"] for d in detail] == [1, 2]
    assert detail[0]["topology"][0] == "chain"
    assert detail[1]["topology"][0] == "dag"

    v1 = reg.load("m", version=1)
    assert isinstance(v1.cfg, NeuraLUTConfig)
    for a, b in zip(v1.tables, tables):
        assert (np.asarray(a) == np.asarray(b)).all()
    v2 = reg.load("m")  # latest = the graph bundle
    assert isinstance(v2.cfg, LUTGraphConfig)
    codes = _input_codes(v2.cfg, 11, seed=6)
    want = np.asarray(LI.graph_lut_forward(gb.cfg, gb.tables, gb.statics,
                                           codes))
    got = np.asarray(lut_cascade_packed_ref(
        codes, [jnp.asarray(m) for m in v2.shift_mats],
        [jnp.asarray(p) for p in v2.packed_tables], v2.cfg.beta,
        schedule=v2.cascade_geom))
    assert (got == want).all()
