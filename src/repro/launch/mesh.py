"""Production mesh construction.

A FUNCTION (not module-level constant) so importing never touches jax device
state.  Single pod: (16, 16) = 256 chips, axes (data, model).  Multi-pod:
(2, 16, 16) = 512 chips, axes (pod, data, model) — the "pod" axis carries
data parallelism across pods (gradients reduce over pod+data; within-pod
axes map to the 2D ICI torus, the pod axis to DCI).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

from repro.config import MeshConfig, MULTI_POD, SINGLE_POD


def _auto_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with Auto axes.  The code places arrays with
    NamedShardings and lets GSPMD propagate the rest; JAX's make_mesh
    now defaults to Explicit axes, under which an ambiguous gather or a
    jit outside ``jax.set_mesh`` is an error instead."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def mesh_config(*, multi_pod: bool = False) -> MeshConfig:
    return MULTI_POD if multi_pod else SINGLE_POD


def make_mesh_from_config(mcfg: MeshConfig):
    return _auto_mesh(mcfg.shape, mcfg.axes)


def make_host_mesh(shape=(2, 4), axes=("data", "model"), devices=None):
    """Small mesh over however many (fake) host devices exist, or over
    ``devices`` — used by multi-device tests."""
    return _auto_mesh(shape, axes, devices)


def make_sweep_mesh(num_devices=None):
    """1-D ``(replica,)`` mesh for the Pareto sweep engine: a sweep's
    stacked (point, seed) unit axis has no model-parallel structure, so
    it shards along one replica axis (``sharding.ctx.replica_mesh``).
    Defaults to every visible device; in CI the multidevice job forces 8
    host devices via ``XLA_FLAGS=--xla_force_host_platform_device_count=8``.
    """
    from repro.sharding.ctx import replica_mesh

    return replica_mesh(num_devices)
