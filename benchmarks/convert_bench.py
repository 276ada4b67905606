"""Truth-table conversion benchmark: pre-refactor per-layer converter vs
the fused device-resident sweep (core/truth_table.py), per paper
geometry.

``_legacy_convert`` vendors the pre-refactor converter: per layer it
builds a FRESH ``@jax.jit`` closure over that layer's params (so every
model converted recompiles every layer — the cost a Pareto sweep pays
per candidate), enumerates the codes on the host, and round-trips each
chunk through numpy.  The fused sweep enumerates on device, shares one
cached compiled function across layers and models of the same geometry,
and emits bit-packed tables directly.

Both converters are run on a *fresh model* of each geometry after a
warmup model, so the comparison is the steady-state per-candidate cost
in a sweep: the legacy path recompiles per model by construction, the
fused path hits its geometry cache.  Bit-exactness legacy == fused is
checked on every geometry (it is the conversion's hard invariant; the
strict fixed-seed oracle gate lives in tests/test_convert_fused.py).
The module pins XLA:CPU intra-op parallelism before jax initializes
(see ``benchmarks.common.pin_cpu_intra_op_threads``), which retires the
size-scaling ppm noise floor the comparison used to need: with the pin
in effect only a constant couple of round()-boundary flips are
tolerated (jaxlib 0.4.36's CPU runtime does not fully honor the pin
under heavy load), and without it (backend already live) the old ppm
floor applies.

    PYTHONPATH=src python -m benchmarks.convert_bench
"""
from __future__ import annotations

import pathlib
import sys
import time
from typing import Dict

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1] / "src"))
sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from benchmarks.common import cpu_threads_pinned  # noqa: E402
from benchmarks.common import emit, pin_cpu_intra_op_threads

# Pin BEFORE jax initializes its CPU client: with one intra-op thread
# the contraction partitioning is deterministic and the legacy-vs-fused
# oracle below demands exact equality (no round()-boundary ulp flips
# under runner load, no ppm allowance).  When the pin comes too late
# (another suite already woke the backend) the ppm floor stays on.
pin_cpu_intra_op_threads()

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from repro.core import lut_infer as LI
from repro.core import model as M
from repro.core import quant, subnet
from repro.core import truth_table as TT

FULL_GEOMETRIES = (
    ("neuralut_jsc_2l", "reduced"), ("neuralut_jsc_2l", "full"),
    ("neuralut_jsc_5l", "reduced"), ("neuralut_jsc_5l", "full"),
    ("neuralut_hdr_5l", "reduced"), ("neuralut_hdr_5l", "full"),
)
FAST_GEOMETRIES = (
    ("neuralut_jsc_2l", "reduced"), ("neuralut_jsc_5l", "reduced"),
    ("neuralut_hdr_5l", "reduced"), ("neuralut_jsc_5l", "full"),
)

# Sub-100K-entry geometries convert in single-digit milliseconds —
# pure dispatch noise on a busy runner.  They are still measured and
# bit-exactness-checked, but the CI gate only compares rows above the
# floor (see benchmarks/run.py _check_convert).
GATE_MIN_ENTRIES = 100_000


def _input_scales(cfg, params, layer_idx: int):
    """Per-source-channel scale of the inputs feeding ``layer_idx``."""
    if layer_idx == 0:
        return jnp.exp(params["in_quant"]["log_s"])
    return jnp.exp(params["layers"][layer_idx - 1]["quant"]["log_s"])


def _legacy_convert(cfg, params, state, statics, batch: int = 4096):
    """Pre-refactor converter, vendored (see module docstring)."""
    tables = []
    for layer_idx in range(cfg.num_layers):
        beta_in = cfg.layer_in_bits(layer_idx)
        fan_in = cfg.layer_fan_in(layer_idx)
        conn = statics[layer_idx]["conn"]
        codes = TT.enumerate_codes(beta_in, fan_in)
        t = codes.shape[0]
        src_scales = _input_scales(cfg, params, layer_idx)
        offs = 2 ** (beta_in - 1)
        slot_scale = jnp.asarray(src_scales)[jnp.asarray(conn)]
        lp = params["layers"][layer_idx]
        ls = state["layers"][layer_idx]

        @jax.jit
        def eval_chunk(code_chunk, lp=lp, ls=ls, slot_scale=slot_scale,
                       offs=offs, layer_idx=layer_idx):
            vals = (code_chunk[:, None, :].astype(jnp.float32) - offs) \
                * slot_scale[None]
            f = subnet.apply_hidden(cfg.kind, lp["fn"], vals,
                                    skip=cfg.skip,
                                    exps=statics[layer_idx].get("exps"))
            pre, _ = quant.bn_apply(lp["bn"], ls["bn"], f, train=False,
                                    momentum=cfg.bn_momentum)
            return quant.quant_codes(lp["quant"], pre, cfg.beta)

        b = min(batch, t)
        outs = []
        for s in range(0, t, b):
            chunk = codes[s:s + b]
            n = chunk.shape[0]
            if n < b:
                chunk = np.concatenate(
                    [chunk, np.zeros((b - n, fan_in), chunk.dtype)], axis=0)
            outs.append(np.asarray(eval_chunk(jnp.asarray(chunk)))[:n])
        tables.append(np.concatenate(outs, axis=0).T.astype(np.uint16))
    return tables


def _fresh_model(cfg, seed: int):
    statics = M.model_static(cfg)
    params, state = M.model_init(cfg, jax.random.PRNGKey(seed))
    x = jnp.asarray(
        np.random.default_rng(seed).normal(0, 1, (64, cfg.in_features)),
        jnp.float32)
    _, _, state = M.model_apply(cfg, params, state, statics, x, train=True)
    return statics, params, state


def run(fast: bool = False) -> Dict:
    import importlib
    geoms = FAST_GEOMETRIES if fast else FULL_GEOMETRIES
    out: Dict = {"fast_mode": fast, "geometries": {}}
    for config_mod, variant in geoms:
        mod = importlib.import_module(f"repro.configs.{config_mod}")
        cfg = getattr(mod, variant)()
        entries = sum(cfg.layer_widths[i] * cfg.table_size(i)
                      for i in range(cfg.num_layers))

        # Warmup model: first-candidate cost (compiles for both paths).
        statics, params, state = _fresh_model(cfg, seed=0)
        _legacy_convert(cfg, params, state, statics)
        t0 = time.perf_counter()
        TT.convert_packed(cfg, params, state, statics)
        cold_s = time.perf_counter() - t0

        # Fresh models: the steady-state per-candidate cost in a sweep.
        # Median of 3 candidates — small geometries convert in
        # milliseconds, where a single noisy sample on a busy runner
        # could trip the CI regression gate.
        legacy_ts, fused_ts = [], []
        mismatches = 0
        packed_ok = True
        for seed in (1, 2, 3):
            statics, params, state = _fresh_model(cfg, seed=seed)
            t0 = time.perf_counter()
            legacy = _legacy_convert(cfg, params, state, statics)
            legacy_ts.append(time.perf_counter() - t0)
            t0 = time.perf_counter()
            tables, packed = TT.convert_packed(cfg, params, state, statics)
            fused_ts.append(time.perf_counter() - t0)
            mismatches += sum(int((a != b).sum())
                              for a, b in zip(legacy, tables))
            packed_ok &= all(
                (LI.pack_tables(t, cfg.beta) == p).all()
                for t, p in zip(tables, packed))
        legacy_s = sorted(legacy_ts)[1]
        fused_s = sorted(fused_ts)[1]
        bit_exact = mismatches == 0
        # With intra-op threads pinned (module top) the size-scaling
        # ppm noise floor is retired for a constant two-entry
        # allowance: jaxlib 0.4.36's thunk-runtime CPU client does not
        # fully honor the pin, so a rare round()-boundary flip (~1 per
        # 3.4M entries, observed only under heavy load) can survive it.
        # Unpinned (backend woken by an earlier suite), the ppm floor
        # applies.  Anything above the allowance is a real converter
        # divergence (fail).  The strict oracle gate lives in
        # tests/test_convert_fused.py.
        allowed = 3 if cpu_threads_pinned() \
            else max(3, entries * 3 // 1_000_000)  # 3 models converted
        if not packed_ok or mismatches > allowed:
            # RuntimeError (not SystemExit) so benchmarks/run.py's
            # per-suite handler records the failure and the other
            # suites still run.
            raise RuntimeError(
                f"{cfg.name}: fused conversion diverged from the "
                f"pre-refactor converter ({mismatches}/{3 * entries} "
                f"entries over 3 models, packed_ok={packed_ok})")
        if mismatches:
            print(f"# NOTE {cfg.name}: {mismatches}/{3 * entries} "
                  f"boundary entries flipped (thread-scheduling ulp "
                  f"noise, see module docstring)", flush=True)

        row = {
            "entries": entries,
            "gate": entries >= GATE_MIN_ENTRIES,
            "legacy_s": legacy_s,
            "fused_s": fused_s,
            "fused_cold_s": cold_s,
            "entries_per_s": entries / fused_s,
            "legacy_entries_per_s": entries / legacy_s,
            "speedup": legacy_s / fused_s,
            "bit_exact": bit_exact,
            "mismatched_entries": mismatches,
        }
        out["geometries"][cfg.name] = row
        emit(f"convert/{cfg.name}", fused_s * 1e6,
             f"entries={entries};entries_per_s={row['entries_per_s']:.2e};"
             f"legacy_s={legacy_s:.3f};speedup={row['speedup']:.2f}x;"
             f"bit_exact={bit_exact}")
    return out


if __name__ == "__main__":
    from benchmarks.common import write_bench_summary
    write_bench_summary({"convert": run()})
