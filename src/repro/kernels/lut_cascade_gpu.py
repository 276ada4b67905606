"""Mosaic-GPU lowering of the fused LUT-cascade kernel.

Same algorithm as the Mosaic-TPU kernel (``kernels/lut_cascade``): the
whole topo-sorted ``NodeSched`` DAG walk — per-source shift-matmuls
summed, packed-word mux tree, per-lane slot extraction, branch codes
added — runs per batch tile in ONE launch, reusing the TPU kernel's
backend-agnostic body (``_cascade_kernel``) verbatim.  What changes is
the placement:

  * the grid tiles the batch in **warp-sized blocks** (default 128 =
    4 warps of 32 lanes, one warpgroup per block), mapped to the
    ``parallel`` dimension semantic so batch tiles schedule freely
    across SMs;
  * every shift matrix and bit-packed table is staged in **shared
    memory** (``plgpu.SMEM``) — the packed tables are ~8x smaller than
    their int32 form (``packed_slots(beta)`` codes per word), so the
    full table stack of every paper geometry fits well under the
    ~100 KiB/SM budget and each tile's lookups never touch HBM;
  * the f32 shift-matmuls feed the tensor cores where shapes allow
    (addresses < 2^20, so f32 accumulation stays exact — the same
    guarantee the TPU MXU path rides on).

Availability-gated: ``interpret=None`` compiles only when the active
jax backend is a GPU; anywhere else the same body runs through the
Pallas interpreter (bit-exact emulation — what CI without a device
exercises, see tests/test_backend_matrix.py).
"""
from __future__ import annotations

import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.lut_cascade import (_cascade_kernel, as_schedule,
                                       schedule_operand_counts)


def gpu_kernel_available() -> bool:
    """True when the compiled Mosaic-GPU path can actually run: a GPU
    backend is active and the Mosaic-GPU Pallas lowering imports."""
    from repro.core.exec_plan import detect_backend
    if detect_backend() != "gpu":
        return False
    try:
        from jax.experimental.pallas import mosaic_gpu  # noqa: F401
        return True
    except ImportError:
        return False


def lut_cascade_gpu(
    codes: jax.Array,                      # (B, W_0) int32 input codes
    shift_mats: Sequence[jax.Array],       # flat (node, branch, src) order
    packed_tables: Sequence[jax.Array],    # flat (node, branch) order
    meta,                                  # cascade_meta / graph_cascade_meta
    *,
    block_b: int = 128,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Returns (B, O_last) int32 output codes of the whole LUT network
    — chain or DAG — in ONE launch (see module docstring).

    Bit-exact vs ``lut_infer.lut_forward`` / ``graph_lut_forward`` and
    vs the TPU kernel for any valid (tables, statics) pair.
    ``interpret=None`` auto-selects: compiled Mosaic-GPU on a GPU
    backend, interpreter emulation elsewhere.
    """
    from repro.core.exec_plan import detect_backend
    meta = as_schedule(meta)
    n_sm, n_pt = schedule_operand_counts(meta)
    if len(shift_mats) != n_sm or len(packed_tables) != n_pt:
        raise ValueError(
            f"schedule consumes {n_sm} shift mats / {n_pt} packed tables, "
            f"got {len(shift_mats)} / {len(packed_tables)}")
    if interpret is None:
        interpret = detect_backend() != "gpu"
    b = codes.shape[0]
    block_b = min(block_b, b)
    pad_b = (-b) % block_b
    if pad_b:
        codes = jnp.pad(codes, ((0, pad_b), (0, 0)))
    bp = b + pad_b
    o_last = packed_tables[-1].shape[0]

    # Operands interleave exactly as the kernel consumes them: per node,
    # per branch, the per-src shift mats then the branch's packed table.
    flat_ops = []
    sm_i = pt_i = 0
    for srcs, arity, *_rest in meta:
        for _a in range(arity):
            for _s in srcs:
                flat_ops.append(shift_mats[sm_i].astype(jnp.float32))
                sm_i += 1
            flat_ops.append(packed_tables[pt_i].astype(jnp.int32))
            pt_i += 1
    operands = [codes.astype(jnp.int32)] + flat_ops

    if interpret:
        # CPU emulation of the GPU block layout: identical body,
        # identical batch tiling, plain BlockSpecs.
        in_specs = [pl.BlockSpec((block_b, codes.shape[1]),
                                 lambda i: (i, 0))]
        in_specs += [pl.BlockSpec(op.shape, lambda i: (0, 0))
                     for op in flat_ops]
        out = pl.pallas_call(
            functools.partial(_cascade_kernel, meta),
            grid=(bp // block_b,),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((block_b, o_last), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((bp, o_last), jnp.int32),
            interpret=True,
            name="lut_cascade_gpu",
        )(*operands)
        return out[:b] if pad_b else out

    from jax.experimental.pallas import mosaic_gpu as plgpu
    # Codes stream per batch tile; every shift matrix / packed table is
    # a whole-array operand staged in SMEM, constant across the grid.
    in_specs = [plgpu.GPUBlockSpec((block_b, codes.shape[1]),
                                   lambda i: (i, 0),
                                   memory_space=plgpu.SMEM)]
    in_specs += [plgpu.GPUBlockSpec(op.shape, lambda i: (0, 0),
                                    memory_space=plgpu.SMEM)
                 for op in flat_ops]
    out = pl.pallas_call(
        functools.partial(_cascade_kernel, meta),
        grid=(bp // block_b,),
        in_specs=in_specs,
        out_specs=plgpu.GPUBlockSpec((block_b, o_last), lambda i: (i, 0),
                                     memory_space=plgpu.SMEM),
        out_shape=jax.ShapeDtypeStruct((bp, o_last), jnp.int32),
        compiler_params=plgpu.GPUCompilerParams(
            dimension_semantics=("parallel",)),
        backend="mosaic_gpu",
        name="lut_cascade_gpu",
    )(*operands)
    return out[:b] if pad_b else out
