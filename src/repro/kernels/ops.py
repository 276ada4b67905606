"""Jit'd public wrappers around the Pallas kernels.

On this CPU container the kernels run in interpret mode (the kernel body
executes as Python/jnp — bit-identical semantics, no lowering); on TPU
and GPU backends the defaults flip to compiled
(``core.exec_plan.kernel_compiled`` is the one auto-select predicate;
the TPU-specific Mosaic cascade additionally stays interpreted off-TPU
— its GPU flavor is ``lut_cascade_gpu_op``).
"""
from __future__ import annotations

import functools
import warnings
from typing import Dict, Optional

import jax

from repro.core.exec_plan import detect_backend, kernel_compiled

from .lut_cascade import lut_cascade
from .lut_cascade_gpu import lut_cascade_gpu
from .lut_gather import lut_lookup
from .neuralut_mlp import grouped_subnet


@functools.partial(jax.jit, static_argnames=("skip", "block_b", "block_o",
                                             "interpret"))
def grouped_subnet_op(xg, layer_ws, layer_bs, skip_ws=None, skip_bs=None, *,
                      skip: int = 0, block_b: Optional[int] = None,
                      block_o: Optional[int] = None,
                      interpret: Optional[bool] = None):
    return grouped_subnet(xg, list(layer_ws), list(layer_bs),
                          list(skip_ws) if skip_ws else None,
                          list(skip_bs) if skip_bs else None,
                          skip=skip, block_b=block_b, block_o=block_o,
                          interpret=interpret)


@functools.partial(jax.jit, static_argnames=("block_b", "block_o",
                                             "interpret"))
def lut_lookup_op(tables, addr, *, block_b: int = 8, block_o: int = 32,
                  interpret: Optional[bool] = None):
    interp = (not kernel_compiled()) if interpret is None else interpret
    return lut_lookup(tables, addr, block_b=block_b, block_o=block_o,
                      interpret=interp)


@functools.partial(jax.jit, static_argnames=("meta", "block_b", "interpret"))
def lut_cascade_op(codes, shift_mats, packed_tables, *, meta,
                   block_b: int = 8, interpret: Optional[bool] = None):
    """Fused whole-network LUT cascade, Mosaic-TPU flavor (see
    kernels/lut_cascade.py).

    ``meta`` is ``lut_cascade.cascade_meta(cfg)``; backend auto-selects
    (compiled on TPU, interpreter elsewhere) when ``interpret`` is None.
    """
    interp = (detect_backend() != "tpu") if interpret is None else interpret
    return lut_cascade(codes, list(shift_mats), list(packed_tables), meta,
                       block_b=block_b, interpret=interp)


@functools.partial(jax.jit, static_argnames=("meta", "block_b", "interpret"))
def lut_cascade_gpu_op(codes, shift_mats, packed_tables, *, meta,
                       block_b: int = 128,
                       interpret: Optional[bool] = None):
    """Fused whole-network LUT cascade, Mosaic-GPU flavor (see
    kernels/lut_cascade_gpu.py): warp-sized batch tiles, packed tables
    staged in SMEM.  Compiled on GPU backends, interpreter emulation
    elsewhere when ``interpret`` is None."""
    interp = (detect_backend() != "gpu") if interpret is None else interpret
    return lut_cascade_gpu(codes, list(shift_mats), list(packed_tables),
                           meta, block_b=block_b, interpret=interp)


def cascade_apply(codes, shift_mats, packed_tables, *, plan=None,
                  meta=None, beta: Optional[int] = None,
                  use_kernel: Optional[bool] = None, block_b: int = 8):
    """Un-jitted fused-cascade dispatch over the backend matrix
    (``fused_kernel_tpu`` / ``fused_kernel_gpu`` / ``fused_cpu_blocked``
    / ``fused_jnp``), every route bit-exact vs
    ``lut_infer.lut_forward`` / ``lut_infer.graph_lut_forward``.

    ``plan`` (a ``core.exec_plan.CascadeExec``) is the one true dispatch
    input; the ``meta=`` / ``beta=`` / ``use_kernel=`` keywords are the
    pre-plan calling convention, DEPRECATED — they are folded into an
    equivalent ``CascadeExec``, dispatch identically
    (tests/test_lut_graph.py pins this) and emit a
    ``DeprecationWarning``.  Passing both forms is an error rather than
    a silent precedence rule.

    The serve engine wraps this in its own jit, and the shard_map'd
    multi-device paths (serve/sharded.py) call it per device shard — in
    both cases an extra nested jit boundary would only block fusion, so
    this stays a plain function (``lut_cascade_op`` /
    ``lut_cascade_gpu_op`` above are the jitted standalone entries).
    Kernel backend selection (compiled on the matching accelerator,
    interpreter elsewhere) lives in the route implementations,
    triggered by ``interpret=None``.
    """
    from repro.core.exec_plan import CascadeExec
    from .lut_cascade import as_schedule
    if plan is None:
        if meta is None or beta is None or use_kernel is None:
            raise TypeError("cascade_apply needs plan= or the legacy "
                            "meta=/beta=/use_kernel= trio")
        warnings.warn(
            "cascade_apply(meta=/beta=/use_kernel=) is deprecated; "
            "build a core.exec_plan.CascadeExec (plan_cascade_exec) and "
            "pass plan= instead", DeprecationWarning, stacklevel=2)
        plan = CascadeExec(
            route="fused_kernel" if use_kernel else "fused_jnp",
            beta=beta, schedule=as_schedule(meta), block_b=block_b)
    elif meta is not None or beta is not None or use_kernel is not None:
        raise TypeError("pass plan= or the legacy keywords, not both")
    return plan.apply(codes, shift_mats, packed_tables)


def subnet_kernel_apply(fn_params: Dict, xg, skip: int, *,
                        interpret: Optional[bool] = None):
    """Run a whole (B, O, F) grouped sub-network through the fused
    Pallas kernel (``neuralut_mlp.grouped_subnet``), its tiles derived
    from the shape.  The converter's TPU fast path: one kernel launch
    evaluates all O neurons' hidden MLPs for a chunk of enumerated
    codes.  The jnp ``subnet.subnet_apply`` path is the bit-exactness
    oracle (tests/test_convert_fused.py).
    """
    kw = subnet_params_to_kernel(fn_params)
    return grouped_subnet(xg, kw["layer_ws"], kw["layer_bs"],
                          kw["skip_ws"], kw["skip_bs"], skip=skip,
                          interpret=interpret)


def subnet_train_apply(fn_params: Dict, xg, skip: int, *,
                       interpret: Optional[bool] = None):
    """Differentiable twin of :func:`subnet_kernel_apply`: the fused
    fwd+bwd training kernel (``neuralut_grad.subnet_train_op``), with
    legal block sizes shaped automatically.  One Pallas launch per
    direction; ``jax.grad`` through it matches the jnp einsum oracle to
    float32 tolerance (tests/test_train_kernel.py).  Dispatched by
    ``core.exec_plan`` route ``kernel_train``.
    """
    from .neuralut_grad import subnet_train_meta, subnet_train_op
    b, o, _ = xg.shape
    kw = subnet_params_to_kernel(fn_params)
    meta = subnet_train_meta(b, o, len(kw["layer_ws"]), skip,
                             interpret=interpret)
    return subnet_train_op(meta, xg, tuple(kw["layer_ws"]),
                           tuple(kw["layer_bs"]),
                           tuple(kw["skip_ws"] or ()),
                           tuple(kw["skip_bs"] or ()))


def subnet_params_to_kernel(fn_params: Dict) -> Dict:
    """Adapt a repro.core.subnet param dict -> kernel argument lists."""
    lw = [lp["w"] for lp in fn_params["layers"]]
    lb = [lp["b"] for lp in fn_params["layers"]]
    sw = [sp["w"] for sp in fn_params.get("skips", [])]
    sb = [sp["b"] for sp in fn_params.get("skips", [])]
    return dict(layer_ws=lw, layer_bs=lb,
                skip_ws=sw or None, skip_bs=sb or None)
