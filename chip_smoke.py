#!/usr/bin/env python3
"""Bring-up smoke: the fp8 MoE train and serve path on a TPU, through the
entry points a user calls (``model_zoo.make_model`` ->
``train.trainer.make_train_step`` and ``serve.engine.Engine``), on the
compiled Pallas kernels.

  python3 chip_smoke.py                   # one chip: kernels, train, serve
  python3 chip_smoke.py --chips 4         # one host of four chips: the
                                          # expert-parallel train step on a
                                          # (1, 4) mesh vs one device
  python3 chip_smoke.py --cpu-rehearsal   # no TPU: smoke_config sizes and
                                          # interpret-mode kernels; reports
                                          # no result

The model is deepseek-moe-16b at its published widths (d_model 2048, 16
heads x 128, dense d_ff 10944, 64 routed experts of width 1408, top-6, 2
shared experts), cut in depth and vocabulary as :func:`model_config` says.
Weights and data are random, made from ``--seed``.

Each phase prints one line.  Every check raises on failure, so a failed
phase exits non-zero and the result line is never printed.  The last line
of standard output is the result:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))

ARCH = "deepseek-moe-16b"
# kernel phase: the paper's App. C.1 shape at deepseek-moe-16b's expert
# widths, plus a buffer whose groups are not multiples of block_m and do
# not fill it (the capacity-buffer tail)
KERNEL_SHAPE = dict(m=16384, k=2048, n=1408, g=64)
RAGGED_SHAPE = dict(m=3000, used=2900)
TRAIN = dict(batch=4, seq=1024, steps=3)
SERVE = dict(requests=4, prompt=128, new=16)
DECODE_BLOCK_M = 16

# CPU rehearsal: the same phases at smoke_config size, interpret kernels
CPU_KERNEL_SHAPE = dict(m=512, k=256, n=256, g=8)
CPU_RAGGED_SHAPE = dict(m=300, used=290)
CPU_TRAIN = dict(batch=2, seq=64, steps=3)
CPU_SERVE = dict(requests=2, prompt=16, new=4)

# Bounds of each kernel against its oracle (max |kernel - oracle| over
# max |oracle|, oracle under default_matmul_precision("highest")):
#  * GEMMs whose operands are exact in bf16 (fp8 payloads, bf16 inputs):
#    only the f32 summation order differs;
#  * the fp8 wgrad multiplies the scales in before its dot, so its
#    operands are f32 products that the MXU may round to bf16;
#  * quantizers are checked against their unquantized input: e4m3 rounds
#    to within half an ulp, at most 2**-4 of a 1x128 tile's amax (plus
#    the bf16 rounding of gemm_quant's intermediate).
EXACT_OPERAND_BOUND = 1e-3
SCALED_OPERAND_BOUND = 1e-2
QUANT_BOUND = 2.0 ** -4 + 2.0 ** -8
# step-0 loss, fp8 vs bf16 forward (relative)
FP8_LOSS_TOL = 1e-2
# sharded vs one-device loss (relative; tests/test_distributed.py)
SHARDED_LOSS_TOL = 2e-2
# families whose every resolution must be the compiled kernel
EXPERT_FAMILIES = ("gemm", "gemm_quant", "wgrad")


def model_config(cpu: bool, precision: str, backend: str):
    """deepseek-moe-16b, cut to fit one v5e chip (16 GB) with AdamW."""
    from repro.configs import get_config, smoke_config
    from repro.kernels.plan import KernelConfig
    if cpu:
        cfg = smoke_config(ARCH)
    else:
        cfg = dataclasses.replace(
            get_config(ARCH),
            # depth 28 -> 2: the leading dense layer and one MoE layer.
            # At 16 bytes a parameter (bf16 weights, f32 master, AdamW m
            # and v) every MoE layer costs 9.4 GB; two layers are the
            # least that still run both FFN kinds of the model.
            num_layers=2,
            # vocabulary 102400 -> 12800 (an eighth): the untied
            # embedding and unembedding are 0.42 B parameters at full
            # size, more than one MoE layer's budget leaves room for.
            vocab_size=12800)
    return dataclasses.replace(cfg, precision=precision,
                               kernel_config=KernelConfig(backend=backend))


def _line(phase: str, t0: float, **fields) -> None:
    body = " ".join(f"{k}={v}" for k, v in fields.items())
    print(f"[{phase}] {body} seconds={time.perf_counter() - t0:.1f}",
          flush=True)


def _rel(got, want):
    import jax.numpy as jnp
    got = jnp.asarray(got, jnp.float32)
    want = jnp.asarray(want, jnp.float32)
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


def _check(name: str, value: float, bound: float) -> str:
    if not value <= bound:
        raise AssertionError(f"{name}: error {value:.3e} exceeds {bound:.1e}")
    return f"{value:.2e}"


def _dequant_rows(q, s):
    """(q [M, N] fp8, s [M, N/128]) -> f32 [M, N]."""
    import jax.numpy as jnp
    return q.astype(jnp.float32) * jnp.repeat(s, 128, axis=1)


def _assert_compiled_kernels(events, backend, where):
    """Every expert-GEMM resolution traced in ``events`` is ``backend``."""
    seen = [(e.data["family"], e.data["precision"], e.data["backend"])
            for e in events if e.kind == "backend_resolved"
            and e.data["family"] in EXPERT_FAMILIES]
    other = sorted({s for s in seen if s[2] != backend})
    if not seen or other:
        raise AssertionError(f"{where}: expert GEMMs resolved to {other} "
                             f"(of {len(seen)}), not {backend!r}")
    return len(seen)


def _assert_custom_call(text: str, cpu: bool, where: str) -> None:
    if not cpu and "tpu_custom_call" not in text:
        raise AssertionError(f"{where}: no tpu_custom_call in the program")


def phase_kernels(cpu: bool, backend: str, seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from benchmarks.common import generate_group_sizes
    from repro.kernels import dispatch

    t0 = time.perf_counter()
    shape = CPU_KERNEL_SHAPE if cpu else KERNEL_SHAPE
    ragged = CPU_RAGGED_SHAPE if cpu else RAGGED_SHAPE
    m, k, n, g = shape["m"], shape["k"], shape["n"], shape["g"]
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    x = jax.random.normal(keys[0], (m, k), jnp.float32)
    w = jax.random.normal(keys[1], (g, k, n), jnp.float32) * k ** -0.5
    dy = jax.random.normal(keys[2], (m, n), jnp.float32)
    up = jax.random.normal(keys[3], (m, n), jnp.float32)
    gs = jnp.asarray(generate_group_sizes(m, g, seed=seed))

    quant = jax.jit(functools.partial(dispatch.quantize_tilewise,
                                      backend=backend))
    a8, sa = quant(x)
    b8, sb = jax.jit(dispatch.quantize_blockwise_batched)(w)
    d8, sd = quant(dy)

    def gemm(be, a8, sa, gs):
        # every array is an argument: one the jitted function closed over
        # would be embedded in the program as a constant
        return jax.jit(functools.partial(
            dispatch.grouped_gemm_fp8, backend=be,
            out_dtype=jnp.float32))(a8, sa, b8, sb, gs)

    # the paper's claim: padding-free == pad -> aligned GEMM -> unpad,
    # bitwise on valid rows, both sides on the same compiled kernel
    bitwise = []
    rm, used = ragged["m"], ragged["used"]
    cases = ((a8, sa, gs, m),
             (a8[:rm], sa[:rm],
              jnp.asarray(generate_group_sizes(used, g, seed=seed + 1)),
              used))
    for a8_c, sa_c, gs_c, rows in cases:
        if all(int(v) % 128 == 0 for v in np.asarray(gs_c)):
            raise AssertionError("group sizes are all block-aligned")
        ours = gemm(backend, a8_c, sa_c, gs_c)
        base = gemm("padded_baseline", a8_c, sa_c, gs_c)
        if not np.array_equal(np.asarray(ours[:rows]),
                              np.asarray(base[:rows])):
            raise AssertionError(f"padding-free != padded at M={len(a8_c)}")
        bitwise.append(f"M{len(a8_c)}/rows{rows}")

    # each compiled kernel family vs its oracle
    with jax.default_matmul_precision("highest"):
        x16, w16, dy16 = (v.astype(jnp.bfloat16) for v in (x, w, dy))
        f32 = functools.partial(jnp.asarray, dtype=jnp.float32)
        oracle_y = jax.jit(functools.partial(
            dispatch.gmm_xla_exact, out_dtype=jnp.float32))(a8, sa, b8, sb, gs)
        errs = {}
        errs["gemm_fp8"] = _rel(gemm(backend, a8, sa, gs), oracle_y)
        errs["gemm_bf16"] = _rel(
            jax.jit(functools.partial(dispatch.grouped_gemm_bf16,
                                      backend=backend,
                                      out_dtype=jnp.float32))(x16, w16, gs),
            jax.jit(lambda a, b, s: jax.lax.ragged_dot(
                f32(a), f32(b), s))(x16, w16, gs))
        q, s = jax.jit(functools.partial(
            dispatch.grouped_gemm_quant, backend=backend))(a8, sa, b8, sb, gs)
        errs["gemm_quant"] = _rel(_dequant_rows(q, s), oracle_y)
        oracle_dw = jax.jit(functools.partial(
            dispatch.wgrad_xla_ragged, num_groups=g))
        errs["wgrad_bf16"] = _rel(
            jax.jit(functools.partial(dispatch.grouped_gemm_wgrad,
                                      backend=backend))(x16, dy16, gs),
            oracle_dw(f32(x16), f32(dy16), gs))
        errs["wgrad_fp8"] = _rel(
            jax.jit(functools.partial(dispatch.grouped_gemm_wgrad_fp8,
                                      backend=backend))(a8, sa, d8, sd, gs),
            oracle_dw(_dequant_rows(a8, sa), _dequant_rows(d8, sd), gs))
        errs["quantize"] = _rel(_dequant_rows(a8, sa), x)
        qa, sa_act = jax.jit(functools.partial(
            dispatch.act_quantize, act="silu_mul", backend=backend))(dy, up)
        errs["act_quant"] = _rel(_dequant_rows(qa, sa_act),
                                 jax.nn.silu(dy) * up)
    bounds = {"gemm_fp8": EXACT_OPERAND_BOUND,
              "gemm_bf16": EXACT_OPERAND_BOUND,
              "wgrad_bf16": EXACT_OPERAND_BOUND,
              "wgrad_fp8": SCALED_OPERAND_BOUND,
              "gemm_quant": QUANT_BOUND, "quantize": QUANT_BOUND,
              "act_quant": QUANT_BOUND}
    shown = {name: _check(name, errs[name], bounds[name]) for name in bounds}
    _line("kernels", t0, backend=backend, shape=f"{m}x{k}x{n}/G{g}",
          padded_bitwise=",".join(bitwise),
          max_rel_err=json.dumps(shown, separators=(",", ":")),
          bounds=json.dumps(bounds, separators=(",", ":")))


def _train_setup(cfg, seed, steps, batch_size, seq):
    import jax
    from repro.models.model_zoo import make_model, synthetic_batch
    from repro.optim import adamw
    model = make_model(cfg)
    opt_cfg = adamw.OptConfig(total_steps=steps, warmup_steps=1)
    batches = [synthetic_batch(jax.random.PRNGKey(seed + 1 + i), cfg, seq,
                               batch_size) for i in range(steps)]
    return model, opt_cfg, batches


def _compile_step(model, opt_cfg, params, opt, batch):
    """The trainer's step, jitted with donation and compiled ahead of
    time; returns (compiled, traced dispatch events, compile seconds)."""
    import jax
    from repro.analysis import events as ev
    from repro.train.trainer import make_train_step
    step = jax.jit(make_train_step(model.loss, opt_cfg),
                   donate_argnums=(0, 1))
    t0 = time.perf_counter()
    with ev.capture() as events:
        lowered = step.lower(params, opt, batch)
    compiled = lowered.compile()
    return compiled, events, time.perf_counter() - t0


def _run_steps(compiled, params, opt, batches):
    losses = []
    for batch in batches:
        params, opt, metrics = compiled(params, opt, batch)
        losses.append(float(metrics["loss"]))
    return params, opt, losses


def _peak_gb(device) -> str:
    stats = device.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "n/a" if peak is None else f"{peak / 1e9:.2f}"


def phase_train(cpu: bool, backend: str, seed: int):
    """fp8 train steps; returns (cfg, params after the steps)."""
    import jax
    import numpy as np
    from repro.optim import adamw
    from repro.models.model_zoo import make_model

    t0 = time.perf_counter()
    sizes = CPU_TRAIN if cpu else TRAIN
    cfg = model_config(cpu, "fp8", backend)
    model, opt_cfg, batches = _train_setup(cfg, seed, sizes["steps"],
                                           sizes["batch"], sizes["seq"])
    params = model.init_params(jax.random.PRNGKey(seed))
    n_params = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
    # step 0's loss at bf16: the same forward on the same weights
    bf16 = make_model(model_config(cpu, "bf16", backend))
    loss_bf16 = float(jax.jit(bf16.loss)(params, batches[0])[0])

    opt = adamw.init_opt_state(params, opt_cfg)
    compiled, events, t_compile = _compile_step(model, opt_cfg, params, opt,
                                                batches[0])
    _assert_custom_call(compiled.as_text(), cpu, "train step")
    n_res = _assert_compiled_kernels(events, backend, "train step")
    params, opt, losses = _run_steps(compiled, params, opt, batches)
    if not np.all(np.isfinite(losses)):
        raise AssertionError(f"non-finite losses {losses}")
    rel = abs(losses[0] - loss_bf16) / abs(loss_bf16)
    if not rel <= FP8_LOSS_TOL:
        raise AssertionError(f"fp8 step-0 loss {losses[0]} vs bf16 "
                             f"{loss_bf16}: rel {rel:.3e} > {FP8_LOSS_TOL}")
    mem = compiled.memory_analysis()
    _line("train", t0, params_b=f"{n_params / 1e9:.3f}",
          batch=f"{sizes['batch']}x{sizes['seq']}",
          losses=",".join(f"{v:.5f}" for v in losses),
          bf16_step0=f"{loss_bf16:.5f}", rel_diff=f"{rel:.2e}",
          tol=FP8_LOSS_TOL, expert_gemm_resolutions=f"{n_res}x{backend}",
          compile_s=f"{t_compile:.1f}",
          step_temp_gb=f"{mem.temp_size_in_bytes / 1e9:.2f}",
          peak_gb=_peak_gb(jax.devices()[0]))
    del opt
    return cfg, params


def phase_serve(cpu: bool, backend: str, seed: int, cfg, params) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.analysis import events as ev
    from repro.kernels.plan import KernelConfig
    from repro.models.model_zoo import make_model, synthetic_batch
    from repro.serve.engine import Engine

    t0 = time.perf_counter()
    sizes = CPU_SERVE if cpu else SERVE
    engine = Engine(
        make_model(cfg), params, max_new_tokens=sizes["new"],
        kernel_config=KernelConfig(backend=backend),
        # pinned: no pool selection, no autotune cache
        decode_kernel_config=KernelConfig(backend=backend,
                                          block_m=DECODE_BLOCK_M))
    batch = synthetic_batch(jax.random.PRNGKey(seed + 100), cfg,
                            sizes["prompt"], sizes["requests"])
    with ev.capture() as events:
        res = engine.generate(batch)
    tokens = np.asarray(res.tokens)
    want = (sizes["requests"], sizes["new"])
    if tokens.shape != want:
        raise AssertionError(f"tokens {tokens.shape}, expected {want}")
    if tokens.min() < 0 or tokens.max() >= cfg.vocab_size:
        raise AssertionError(f"token ids outside [0, {cfg.vocab_size})")
    n_res = _assert_compiled_kernels(events, backend, "generate")
    # the decode loop as generate compiled it (same shapes; the in-process
    # and persistent caches make this a lookup, not a second compile)
    cap = sizes["prompt"] + sizes["new"]
    _, cache = jax.eval_shape(
        lambda p, b: engine._prefill(p, b, cache_capacity=cap),
        engine.params, batch)
    first = jax.ShapeDtypeStruct((sizes["requests"],), jnp.int32)
    decode = engine._decode_loop.lower(engine.params, first, cache,
                                       jax.random.PRNGKey(0)).compile()
    _assert_custom_call(decode.as_text(), cpu, "decode loop")
    _line("serve", t0, requests=sizes["requests"], prompt=sizes["prompt"],
          new=sizes["new"], tokens_shape="x".join(map(str, tokens.shape)),
          decode_block_m=engine.decode_config.block_m,
          expert_gemm_resolutions=f"{n_res}x{backend}",
          first_tokens=tokens[0, :8].tolist())


def phase_expert_parallel(cpu: bool, backend: str, seed: int) -> None:
    """The sharded train step over a (1, 4) mesh vs the same steps on one
    device of the host."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.distributed import context as dctx
    from repro.distributed.sharding import named_shardings
    from repro.launch.mesh import make_mesh
    from repro.optim import adamw

    t0 = time.perf_counter()
    sizes = CPU_TRAIN if cpu else TRAIN
    cfg = model_config(cpu, "fp8", backend)
    model, opt_cfg, batches = _train_setup(cfg, seed, sizes["steps"],
                                           sizes["batch"], sizes["seq"])

    # one device: the comparison (its final state is dropped at once:
    # device 0 needs the room for the sharded run)
    params = model.init_params(jax.random.PRNGKey(seed))
    opt = adamw.init_opt_state(params, opt_cfg)
    compiled, _, _ = _compile_step(model, opt_cfg, params, opt, batches[0])
    ref_losses = _run_steps(compiled, params, opt, batches)[2]
    del compiled, params, opt

    # experts over the model axis: EP inside shard_map, GSPMD elsewhere
    mesh = make_mesh((1, 4), ("data", "model"))
    dctx.set_mesh(mesh)
    try:
        params = model.init_params(jax.random.PRNGKey(seed))
        pshard = named_shardings(params, mesh, moe_mode="ep")
        params = jax.device_put(params, pshard)
        # the optimizer state is made in place, sharded like the params
        opt = jax.jit(
            functools.partial(adamw.init_opt_state, cfg=opt_cfg),
            out_shardings={"m": pshard, "v": pshard, "master": pshard,
                           "step": NamedSharding(mesh, P())})(params)
        compiled, events, t_compile = _compile_step(model, opt_cfg, params,
                                                    opt, batches[0])
        _assert_custom_call(compiled.as_text(), cpu, "sharded step")
        n_res = _assert_compiled_kernels(events, backend, "sharded step")
        _, _, losses = _run_steps(compiled, params, opt, batches)
    finally:
        dctx.set_mesh(None)
    rels = [abs(a - b) / abs(a) for a, b in zip(ref_losses, losses)]
    if not max(rels) < SHARDED_LOSS_TOL:
        raise AssertionError(f"sharded losses {losses} vs one device "
                             f"{ref_losses}: rel {max(rels):.3e}")
    mem = compiled.memory_analysis()
    _line("expert_parallel", t0, mesh=dict(mesh.shape),
          losses_sharded=",".join(f"{v:.5f}" for v in losses),
          losses_one_device=",".join(f"{v:.5f}" for v in ref_losses),
          max_rel_diff=f"{max(rels):.2e}", tol=SHARDED_LOSS_TOL,
          expert_gemm_resolutions=f"{n_res}x{backend}",
          compile_s=f"{t_compile:.1f}",
          per_device_args_gb=f"{mem.argument_size_in_bytes / 1e9:.2f}",
          per_device_temp_gb=f"{mem.temp_size_in_bytes / 1e9:.2f}",
          peak_gb=",".join(_peak_gb(d) for d in jax.devices()))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the expert-parallel train step "
                         "on a (1, 4) mesh and its one-device comparison")
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="run the phases on the CPU at smoke_config size "
                         "with interpret-mode kernels; reports no result")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    cpu = args.cpu_rehearsal
    if cpu:
        # set before JAX initializes its backends
        os.environ["JAX_PLATFORMS"] = "cpu"
        if args.chips == 4:
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + " --xla_force_host_platform_device_count=4")
    sys.path[:0] = [os.path.join(HERE, "src"), HERE]

    import jax
    from repro.launch.compile_cache import enable_compile_cache

    devices = jax.devices()
    platform = devices[0].platform
    if not cpu and platform != "tpu":
        print(f"chip_smoke: JAX finds no TPU (platform {platform!r}); "
              f"--cpu-rehearsal runs the phases on the CPU",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"JAX finds {len(devices)}", file=sys.stderr)
        return 1
    cache_dir = enable_compile_cache()
    backend = "pallas_interpret" if cpu else "pallas"
    print(f"[setup] platform={platform} kind={devices[0].device_kind!r} "
          f"devices={len(devices)} jax={jax.__version__} "
          f"compile_cache={cache_dir}", flush=True)

    if args.chips == 4:
        phase_expert_parallel(cpu, backend, args.seed)
    else:
        phase_kernels(cpu, backend, args.seed)
        cfg, params = phase_train(cpu, backend, args.seed)
        phase_serve(cpu, backend, args.seed, cfg, params)

    if cpu:
        print("cpu rehearsal passed; it is not a chip result", flush=True)
        return 0
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
