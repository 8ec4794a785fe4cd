"""Weights for a cell, made by the benchmark from ``--seed``: one jitted
call on the device, each leaf in the dtype the program serves it in.

The tree's shape comes from the program (``jax.eval_shape`` of its
``init_params``); every value comes from here, so the program and the
reference start from the same numbers and the reference takes nothing
the program made."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.workload import WEIGHTS, seed_key

BIAS_STD = 0.02
NORM_STD = 0.1


def _leaf(key, name: str, s):
    x = jax.random.normal(key, s.shape, jnp.float32)
    if name == "scale":                      # RMSNorm gains
        x = 1.0 + NORM_STD * x
    elif name in ("bq", "bk", "bv"):
        x = BIAS_STD * x
    elif name == "embedding":
        x = x * s.shape[-1] ** -0.5
    else:                                    # [..., fan_in, fan_out]
        x = x * s.shape[-2] ** -0.5
    return x.astype(s.dtype)


def _name(path) -> str:
    return str(getattr(path[-1], "key", path[-1]))


def make(shapes, seed: int, dtype=None):
    """Params shaped like ``shapes``; ``dtype`` (e.g. f32 for the
    reference) overrides every leaf's dtype after it is made, so the
    values are those of the served dtype."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def build(key):
        out = []
        for i, (path, s) in enumerate(leaves):
            v = _leaf(jax.random.fold_in(key, i), _name(path), s)
            out.append(v if dtype is None else v.astype(dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(build)(seed_key(seed, WEIGHTS))
