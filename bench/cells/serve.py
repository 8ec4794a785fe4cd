"""Serving cells: ``serve.engine.Engine.generate`` over
``model_zoo.make_model(cfg)``, in a closed loop: one client submits the
next batch of prompts when the last one has returned."""
from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import spec, weights


@dataclasses.dataclass
class Program:
    cfg: object
    shapes: object
    engine: object


def build(config: dict, backend: str, rehearsal: bool, seed: int,
          traffic: dict) -> Program:
    """The engine over weights made from the seed, prefill and decode
    tiles pinned (no pool selection, no autotune cache)."""
    from repro.kernels.plan import KernelConfig
    from repro.models.model_zoo import make_model
    from repro.serve.engine import Engine
    cfg = spec.model_config(config, backend, rehearsal)
    model = make_model(cfg)
    shapes = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    eng = Engine(
        model, weights.make(shapes, seed, init=config.get("init")),
        max_new_tokens=traffic["new"],
        kernel_config=KernelConfig(backend=backend),
        decode_kernel_config=KernelConfig(backend=backend,
                                          block_m=config["decode_block_m"]))
    return Program(cfg, shapes, eng)


def window(eng, pool, seconds: float, annotate):
    """Closed loop for ``seconds``: submit pool batch i, wait for its
    tokens on the host, submit the next.  Returns [(pool index, latency
    s, tokens [B, new])] for every call completed, and the window's
    length (to the last completion)."""
    done = []
    i = 1                      # pool[0] served the warm-up
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        t_sub = time.perf_counter()
        with annotate("bench.generate"):
            res = eng.generate(pool[i % len(pool)])
        with annotate("bench.wait"):
            toks = np.asarray(res.tokens)
        done.append((i % len(pool), time.perf_counter() - t_sub, toks))
        i += 1
    return done, time.perf_counter() - t0


def hlo_texts(eng, batch) -> list:
    """The optimized HLO of the prefill and decode-loop programs that
    ``generate`` ran on ``batch`` (from the compile cache)."""
    cap = batch["tokens"].shape[1] + eng.max_new
    pre = eng._prefill.lower(eng.params, batch, cache_capacity=cap)
    _, cache = pre.out_info
    first = jax.ShapeDtypeStruct((batch["tokens"].shape[0],), jnp.int32)
    loop = eng._decode_loop.lower(eng.params, first, cache,
                                  jax.random.PRNGKey(0))
    return [pre.compile().as_text(), loop.compile().as_text()]


def sample(done, n: int, seed: int):
    """``n`` (call, row) pairs drawn from the seed over the completed
    calls' requests.  All requests of a mix are equally long, so any
    sample holds the longest."""
    rng = np.random.default_rng(seed)
    rows = done[0][2].shape[0]
    picks = rng.choice(len(done) * rows, size=min(n, len(done) * rows),
                       replace=False)
    return [(int(p) // rows, int(p) % rows) for p in sorted(picks)]


def reference_gaps(config: dict, prog_cfg, shapes, seed: int, prompts,
                   served, control: bool = False):
    """The widest gap by which a served token's reference logit lies below
    the reference's best, over every served token of the sample.  With
    ``control`` the token is the one the lower-precision reference puts
    first, at the same positions."""
    ref = spec.reference(config["reference"])
    sz = spec.sizes(prog_cfg)
    params = weights.make(shapes, seed, dtype=jnp.float32,
                          init=config.get("init"))
    prompts, served = jnp.asarray(prompts), jnp.asarray(served)
    logits, rows = ref.served_logits(params, prompts, served, sz)
    if control:
        ctrl, _ = ref.served_logits(params, prompts, served, sz,
                                    control=True)
        served = jnp.argmax(ctrl, -1)
        del ctrl
    best = jnp.max(logits, -1)
    got = jnp.take_along_axis(logits, served[..., None], -1)[..., 0]
    return float(jnp.max(best - got)), jax.device_get(rows)
