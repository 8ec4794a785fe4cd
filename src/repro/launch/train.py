"""End-to-end training driver with fault tolerance.

  PYTHONPATH=src python -m repro.launch.train --arch deepseek-moe-16b \
      --smoke --steps 100 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt

Fault tolerance: checkpoints are atomic + versioned; on start the driver
auto-resumes from the latest complete checkpoint; the data pipeline is
stateless (batch = f(seed, step)) so the restarted run consumes exactly
the batches it would have.  ``--fail-at-step`` injects a crash to exercise
the path (see tests/test_train_restart.py).

Straggler / failure model (documented for fleet scale): steps are
synchronous; a lost host surfaces as a collective timeout -> the job
restarts from the last checkpoint on the surviving mesh
(launch/mesh.py:make_mesh_for re-meshes to the new device count; param
shardings are re-derived from the logical specs, checkpoints are
resharding-safe because they store full logical arrays).
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import time

import jax
import jax.numpy as jnp

from repro.checkpoint import checkpointer as ckpt
from repro.configs import get_config, smoke_config
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.distributed import context as dctx
from repro.distributed.sharding import named_shardings
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import local_mesh
from repro.models.model_zoo import make_model
from repro.optim import adamw
from repro.train.trainer import make_train_step


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="deepseek-moe-16b")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-sized)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--precision", default=None, choices=[None, "bf16", "fp8"])
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--save-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--fail-at-step", type=int, default=-1,
                    help="inject a crash (restart testing)")
    ap.add_argument("--dtype", default=None, choices=[None, "f32", "bf16"])
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    repl = {}
    if args.precision:
        repl["precision"] = args.precision
    if args.dtype:
        repl["dtype"] = jnp.float32 if args.dtype == "f32" else jnp.bfloat16
    if repl:
        cfg = dataclasses.replace(cfg, **repl)

    mesh = local_mesh() if len(jax.devices()) > 1 else None
    if mesh is not None:
        dctx.set_mesh(mesh)
    model = make_model(cfg)

    params = model.init_params(jax.random.PRNGKey(args.seed))
    opt_cfg = adamw.OptConfig(lr=args.lr, total_steps=args.steps,
                              warmup_steps=max(args.steps // 20, 5),
                              use_master=cfg.dtype == jnp.bfloat16)
    opt_state = adamw.init_opt_state(params, opt_cfg)

    if mesh is not None:
        pshard = named_shardings(params, mesh)
        params = jax.device_put(params, pshard)

    step_fn = jax.jit(make_train_step(model.loss, opt_cfg,
                                      grad_accum=args.grad_accum),
                      donate_argnums=(0, 1))

    start_step = 0
    if args.ckpt_dir:
        restored, meta, s = ckpt.restore_latest(
            args.ckpt_dir, {"params": params, "opt": opt_state})
        if restored is not None:
            params, opt_state = restored["params"], restored["opt"]
            start_step = s + 1
            print(f"[resume] restored step {s} from {args.ckpt_dir}")

    data = SyntheticLM(DataConfig(seed=args.seed, batch_size=args.batch,
                                  seq_len=args.seq), cfg)

    t0 = time.time()
    tokens_done = 0
    for step in range(start_step, args.steps):
        if step == args.fail_at_step:
            raise SystemExit(f"[injected failure] at step {step}")
        batch = data.batch_at(step)
        params, opt_state, metrics = step_fn(params, opt_state, batch)
        tokens_done += args.batch * args.seq
        if step % args.log_every == 0 or step == args.steps - 1:
            m = {k: float(v) for k, v in metrics.items()}
            tps = tokens_done / max(time.time() - t0, 1e-9)
            print(f"step {step:5d}  loss {m['loss']:.4f}  "
                  f"gnorm {m.get('grad_norm', 0):.3f}  "
                  f"lr {m.get('lr', 0):.2e}  tok/s {tps:,.0f}", flush=True)
        if args.ckpt_dir and args.save_every and \
                (step + 1) % args.save_every == 0:
            path = ckpt.save(args.ckpt_dir, step,
                             {"params": params, "opt": opt_state})
            print(f"[ckpt] step {step} -> {path}", flush=True)
    print("done.")
    return params


if __name__ == "__main__":
    main()
