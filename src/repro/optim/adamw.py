"""AdamW with warmup+cosine schedule, global-norm clipping, optional f32
master weights (for bf16 models) and optional int8 error-feedback gradient
compression (the distributed-optimization trick for cross-pod reduction).

Pure JAX; state is a plain pytree so it checkpoints and shards trivially
(m/v inherit the parameter's PartitionSpec).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.scopes import OPTIMIZER, scope


@dataclasses.dataclass(frozen=True)
class OptConfig:
    lr: float = 3e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    warmup_steps: int = 100
    total_steps: int = 10000
    min_lr_ratio: float = 0.1
    clip_norm: float = 1.0
    use_master: bool = True          # keep f32 master copy of bf16 params
    compress_grads: bool = False     # int8 + error feedback (cross-pod AR)


def schedule(step, cfg: OptConfig):
    step = step.astype(jnp.float32)
    warm = jnp.minimum(step / jnp.maximum(cfg.warmup_steps, 1), 1.0)
    prog = jnp.clip((step - cfg.warmup_steps) /
                    jnp.maximum(cfg.total_steps - cfg.warmup_steps, 1), 0, 1)
    cos = 0.5 * (1 + jnp.cos(jnp.pi * prog))
    return cfg.lr * warm * (cfg.min_lr_ratio + (1 - cfg.min_lr_ratio) * cos)


def init_opt_state(params, cfg: OptConfig):
    zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    state = {"m": zeros,
             "v": jax.tree.map(jnp.zeros_like, zeros),
             "step": jnp.zeros((), jnp.int32)}
    if cfg.use_master:
        # a copy even where a param is already f32: the step donates
        # params and state, and one buffer cannot be donated twice
        state["master"] = jax.tree.map(
            lambda p: jnp.array(p, jnp.float32, copy=True), params)
    if cfg.compress_grads:
        state["ef"] = jax.tree.map(jnp.zeros_like, zeros)
    return state


def global_norm(tree):
    return jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                        for x in jax.tree.leaves(tree)))


def _compress_int8(g, ef):
    """Error-feedback int8 compression: quantize (g + residual) per-tensor,
    return the dequantized value actually 'transmitted' + new residual."""
    t = g.astype(jnp.float32) + ef
    scale = jnp.maximum(jnp.max(jnp.abs(t)), 1e-30) / 127.0
    q = jnp.clip(jnp.round(t / scale), -127, 127)
    deq = q * scale
    return deq, t - deq


@scope(OPTIMIZER)
def apply_updates(params, grads, state, cfg: OptConfig):
    """Returns (new_params, new_state, metrics)."""
    step = state["step"] + 1
    lr = schedule(step, cfg)
    gf = jax.tree.map(lambda g: g.astype(jnp.float32), grads)

    new_ef = state.get("ef")
    if cfg.compress_grads:
        pairs = jax.tree.map(_compress_int8, gf, state["ef"])
        gf = jax.tree.map(lambda p: p[0], pairs,
                          is_leaf=lambda x: isinstance(x, tuple))
        new_ef = jax.tree.map(lambda p: p[1], pairs,
                              is_leaf=lambda x: isinstance(x, tuple))

    gnorm = global_norm(gf)
    clip = jnp.minimum(1.0, cfg.clip_norm / jnp.maximum(gnorm, 1e-12))
    gf = jax.tree.map(lambda g: g * clip, gf)

    b1c = 1 - cfg.b1 ** step.astype(jnp.float32)
    b2c = 1 - cfg.b2 ** step.astype(jnp.float32)
    m = jax.tree.map(lambda m_, g: cfg.b1 * m_ + (1 - cfg.b1) * g,
                     state["m"], gf)
    v = jax.tree.map(lambda v_, g: cfg.b2 * v_ + (1 - cfg.b2) * g * g,
                     state["v"], gf)

    masters = state.get("master", params)

    def upd(p, m_, v_):
        mh = m_ / b1c
        vh = v_ / b2c
        return (p.astype(jnp.float32)
                - lr * (mh / (jnp.sqrt(vh) + cfg.eps)
                        + cfg.weight_decay * p.astype(jnp.float32)))

    new_master = jax.tree.map(upd, masters, m, v)
    new_params = jax.tree.map(
        lambda nm, p: nm.astype(p.dtype), new_master, params)

    new_state = {"m": m, "v": v, "step": step}
    if cfg.use_master:
        new_state["master"] = new_master
    if cfg.compress_grads:
        new_state["ef"] = new_ef
    metrics = {"lr": lr, "grad_norm": gnorm}
    return new_params, new_state, metrics
