"""Training cells: the compiled ``train.trainer.make_train_step`` over
``model_zoo.make_model(cfg)``, built once in set-up, driven through its
first steps there, and handed on to the measured window."""
from __future__ import annotations

import collections
import dataclasses
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import spec, weights


@dataclasses.dataclass
class Program:
    """The compiled step and what it needs; the state lives in ``run``."""
    cfg: object
    shapes: object
    opt_cfg: object
    compiled: object
    resolutions: list     # (family, precision, backend) of every GEMM


def leaf_names(shapes) -> list:
    return [jax.tree_util.keystr(p)
            for p, _ in jax.tree_util.tree_flatten_with_path(shapes)[0]]


def _check_aux_weight(config: dict) -> None:
    """The program's loss weighs the balance loss by ``lm_loss``'s fixed
    default; a file that states another weight would have the reference
    and the program differ, so it is refused."""
    import inspect
    from repro.models.transformer import lm_loss
    fixed = inspect.signature(lm_loss).parameters["aux_weight"].default
    if config["aux_loss_weight"] != fixed:
        raise ValueError(f"{config['name']}: aux_loss_weight "
                         f"{config['aux_loss_weight']} but the program's "
                         f"loss applies {fixed}")


def compile_program(config: dict, traffic: dict, backend: str,
                    rehearsal: bool) -> Program:
    from repro.analysis import events as ev
    from repro.models.model_zoo import make_model
    from repro.optim import adamw
    from repro.train.trainer import make_train_step
    _check_aux_weight(config)
    cfg = spec.model_config(config, backend, rehearsal)
    model = make_model(cfg)
    shapes = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    opt_cfg = adamw.OptConfig(**config["optimizer"])
    opt_shapes = jax.eval_shape(
        functools.partial(adamw.init_opt_state, cfg=opt_cfg), shapes)
    tr = spec.traffic_sizes(traffic, rehearsal)
    tok = jax.ShapeDtypeStruct((tr["batch"], tr["seq"]), jnp.int32)
    step = jax.jit(make_train_step(model.loss, opt_cfg),
                   donate_argnums=(0, 1))
    with ev.capture() as events:
        lowered = step.lower(shapes, opt_shapes,
                             {"tokens": tok, "labels": tok})
    res = [(e.data["family"], e.data["precision"], e.data["backend"])
           for e in events if e.kind == "backend_resolved"]
    return Program(cfg, shapes, opt_cfg, lowered.compile(), res)


def init_state(prog: Program, seed: int):
    """Params from the seed, AdamW state made on the device."""
    from repro.optim import adamw
    params = weights.make(prog.shapes, seed)
    opt = jax.jit(functools.partial(adamw.init_opt_state,
                                    cfg=prog.opt_cfg))(params)
    return params, opt


@jax.jit
def _norms(tree):
    return [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
            for x in jax.tree.leaves(tree)]


@jax.jit
def _change_norms(master, start):
    return [jnp.sqrt(jnp.sum(jnp.square(m - s.astype(jnp.float32))))
            for m, s in zip(jax.tree.leaves(master), jax.tree.leaves(start))]


def first_steps(prog: Program, params, opt, pool, seed: int, n: int):
    """Drive the compiled step through its first ``n`` steps on pool
    batches 0..n-1 (all rows differ).  Returns the state and the program's
    readings: each step's loss, the norm per leaf of step 1's gradient as
    the optimizer applied it (its first moment over 1 - b1), and the norm
    per leaf of the change of the f32 master weights after ``n`` steps."""
    losses, grad_norms = [], None
    for i in range(n):
        params, opt, met = prog.compiled(params, opt, pool[i])
        losses.append(met["loss"])
        if i == 0:
            grad_norms = _norms(opt["m"])
    start = weights.make(prog.shapes, seed)
    change = _change_norms(opt["master"], start)
    del start
    scale = 1.0 / (1.0 - prog.opt_cfg.b1)
    return params, opt, {
        "losses": [float(x) for x in losses],
        "grad_norms": [float(x) * scale for x in grad_norms],
        "change_norms": [float(x) for x in change]}


def window(prog: Program, params, opt, pool, start: int, seconds: float,
           annotate, ahead: int):
    """Steps back to back for ``seconds``, ``ahead`` steps dispatched
    beyond the one the host waits for, so that a stall of the host does
    not leave the device idle; losses are read ``ahead`` steps late.  Once
    the time is up nothing more is sent and every step sent is waited
    for: the window counts all of them, to the end of the last.  Returns
    the state, the losses of the steps completed and the window's length."""
    losses, pending = [], collections.deque()
    i = start
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        with annotate("bench.dispatch"):
            params, opt, met = prog.compiled(params, opt,
                                             pool[i % len(pool)])
        i += 1
        pending.append(met["loss"])
        if len(pending) > ahead:
            with annotate("bench.wait"):
                losses.append(float(pending.popleft()))
    with annotate("bench.wait"):
        losses += [float(x) for x in pending]
    return params, opt, losses, time.perf_counter() - t0


def reference_readings(config: dict, prog: Program, pool_host: list,
                       seed: int, control: bool = False):
    ref = spec.reference(config["reference"])
    sz = spec.sizes(prog.cfg)
    return ref.train_readings(
        lambda: weights.make(prog.shapes, seed, dtype=jnp.float32),
        [jax.device_put(b) for b in pool_host], sz, config["optimizer"],
        config["aux_loss_weight"], control=control)


def host_batches(pool, n):
    return [{k: np.asarray(v) for k, v in b.items()} for b in pool[:n]]
