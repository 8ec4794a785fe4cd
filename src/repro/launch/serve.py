"""Batched serving driver.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-1.7b --smoke \
      --batch 4 --prompt-len 64 --max-new 16
"""
from __future__ import annotations

import argparse
import time

import jax

from repro.configs import get_config, smoke_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models.model_zoo import make_model, synthetic_batch
from repro.serve.engine import Engine


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-1.7b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    model = make_model(cfg)
    params = model.init_params(jax.random.PRNGKey(args.seed))
    engine = Engine(model, params, max_new_tokens=args.max_new,
                    temperature=args.temperature)

    batch = synthetic_batch(jax.random.PRNGKey(args.seed + 1), cfg,
                            args.prompt_len, args.batch)
    # warmup (compile)
    res = engine.generate(batch)
    res.tokens.block_until_ready()

    t0 = time.time()
    res = engine.generate(batch)
    res.tokens.block_until_ready()
    dt = time.time() - t0
    total_new = int(res.num_generated.sum())
    print(f"arch={cfg.name} batch={args.batch} prompt={args.prompt_len} "
          f"new={args.max_new}")
    print(f"generated {total_new} tokens in {dt*1e3:.1f} ms "
          f"({total_new/dt:.1f} tok/s)")
    print("sample:", res.tokens[0][:16].tolist())
    return res


if __name__ == "__main__":
    main()
