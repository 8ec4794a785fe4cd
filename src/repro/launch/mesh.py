"""Production mesh definitions.

Functions, not module-level constants, so importing this module never
touches jax device state (the dry-run must set XLA_FLAGS before first
init).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, *, devices=None):
    """``jax.make_mesh`` with every axis ``Auto``: the sharding code
    (``distributed.context.constrain``, ``named_shardings``, the
    ``shard_map`` MoE path) places arrays with ``NamedSharding`` and leaves
    propagation to GSPMD, which ``Explicit`` axes — ``jax.make_mesh``'s
    default since JAX 0.7 — refuse."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh_for(n_devices: int, *, model_parallel: int | None = None):
    """Elastic re-mesh: build the best (data, model) mesh for however many
    devices survive — used on restart after node loss."""
    if model_parallel is None:
        model_parallel = 1
        for cand in (16, 8, 4, 2, 1):
            if n_devices % cand == 0 and cand <= n_devices:
                model_parallel = cand
                break
    data = n_devices // model_parallel
    return make_mesh((data, model_parallel), ("data", "model"))


def local_mesh():
    """Whatever this process has (CPU tests: 1 device)."""
    n = len(jax.devices())
    return make_mesh_for(n)
