"""Weights for a cell, made by the benchmark from ``--seed``: one jitted
call on the device, each leaf in the dtype the program serves it in.

The tree's shape comes from the program (``jax.eval_shape`` of its
``init_params``); every value comes from here, so the program and the
reference start from the same numbers and the reference takes nothing
the program made.

Matrices are N(0, 1/fan_in), q/k/v biases N(0, 0.02^2), norm gains
1 + 0.1 N(0, 1) and the embedding N(0, 1/d_model), unless the
configuration file states ``"init": {"embedding_std": s}``: then the
embedding is N(0, s^2).  Beside random attention and q/k/v biases, which
add one vector to every token, an embedding of std d_model^-1/2 is small,
and a random router then sends most tokens of a deep stack to a few
experts."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.workload import WEIGHTS, seed_key

BIAS_STD = 0.02
NORM_STD = 0.1


INIT_KEYS = frozenset({"embedding_std"})


def _leaf(key, name: str, s, init: dict):
    x = jax.random.normal(key, s.shape, jnp.float32)
    if name == "scale":                      # RMSNorm gains
        x = 1.0 + NORM_STD * x
    elif name in ("bq", "bk", "bv"):
        x = BIAS_STD * x
    elif name == "embedding":
        std = init.get("embedding_std")
        x = x * (s.shape[-1] ** -0.5 if std is None else std)
    else:                                    # [..., fan_in, fan_out]
        x = x * s.shape[-2] ** -0.5
    return x.astype(s.dtype)


def _name(path) -> str:
    return str(getattr(path[-1], "key", path[-1]))


def make(shapes, seed: int, dtype=None, init: dict | None = None):
    """Params shaped like ``shapes``; ``dtype`` (e.g. f32 for the
    reference) overrides every leaf's dtype after it is made, so the
    values are those of the served dtype.  ``init`` is the configuration
    file's ``init`` (module docstring)."""
    init = init or {}
    unknown = sorted(set(init) - INIT_KEYS)
    if unknown:
        raise ValueError(f"init keys {unknown} unknown; known: "
                         f"{sorted(INIT_KEYS)}")
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def build(key):
        out = []
        for i, (path, s) in enumerate(leaves):
            v = _leaf(jax.random.fold_in(key, i), _name(path), s, init)
            out.append(v if dtype is None else v.astype(dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(build)(seed_key(seed, WEIGHTS))
