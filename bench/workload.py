"""The one traffic generator: every mix is a data file under ``traffic/``
read here.  Inputs depend on ``--seed`` alone and are made on the device
in one jitted call each."""
from __future__ import annotations

import jax
import jax.numpy as jnp

WEIGHTS, TRAFFIC = 0, 1   # streams folded into the seed's key


def seed_key(seed: int, stream: int):
    """A key from a seed of any size: the low and high 32 bits both count
    (``PRNGKey`` alone keeps only the low 32)."""
    if seed < 0:
        raise ValueError(f"seed {seed} < 0")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    key = jax.random.fold_in(key, seed >> 32)
    return jax.random.fold_in(key, stream)


def zipf_tokens(key, shape, vocab: int, exponent: float):
    """Token ids i.i.d. from a Zipf law over ``[0, vocab)``: id ``r`` has
    probability proportional to ``(r + 1) ** -exponent``."""
    w = jnp.arange(1, vocab + 1, dtype=jnp.float32) ** -exponent
    cdf = jnp.cumsum(w) / jnp.sum(w)
    u = jax.random.uniform(key, shape, jnp.float32)
    return jnp.minimum(jnp.searchsorted(cdf, u), vocab - 1).astype(jnp.int32)


def pool(traffic: dict, vocab: int, seed: int) -> list:
    """``traffic["pool"]`` batches, each a dict of device arrays: train
    batches carry ``tokens`` and ``labels`` [batch, seq]; serve batches
    carry the prompts ``tokens`` [batch, prompt]."""
    n = traffic["pool"]
    if traffic["kind"] == "train":
        shape = (traffic["batch"], traffic["seq"])
    elif traffic["kind"] == "serve":
        shape = (traffic["batch"], traffic["prompt"])
    else:
        raise ValueError(f"unknown traffic kind {traffic['kind']!r}")

    def make(key):
        keys = jax.random.split(key, n)
        return tuple(zipf_tokens(k, shape, vocab, traffic["zipf_exponent"])
                     for k in keys)

    toks = jax.jit(make)(seed_key(seed, TRAFFIC))
    if traffic["kind"] == "train":
        return [{"tokens": t, "labels": t} for t in toks]
    return [{"tokens": t} for t in toks]
