"""Multi-pod dry-run: lower + compile every (architecture x input-shape)
cell on the production mesh, with 512 placeholder host devices.

Proves the distribution config is coherent without hardware: sharding
mismatches, compile-time OOM, or unsupported collectives fail here.
Outputs one JSON per cell (memory analysis, HLO cost, collective bytes,
roofline terms) consumed by EXPERIMENTS.md §Dry-run / §Roofline.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch yi-9b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod]
"""
# The VERY FIRST lines, before ANY other import: jax locks the device count
# at first init.  512 placeholder CPU devices for the production meshes.
import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512")

import argparse
import json
import re
import time
import traceback

import jax
import jax.numpy as jnp

import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCHS, get_config, run_hints
from repro.configs.base import SHAPES, cell_is_runnable
from repro.distributed import context as dctx
from repro.distributed.sharding import build_param_specs
from repro.launch.mesh import make_production_mesh
from repro.models.model_zoo import make_model, batch_struct
from repro.optim import adamw
from repro.train.trainer import make_train_step

# --- TPU v5e hardware model (roofline constants) --------------------------
PEAK_FLOPS = 197e12        # bf16 per chip
HBM_BW = 819e9             # bytes/s per chip
ICI_BW = 50e9              # bytes/s per link

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1,
                "f8e5m2": 1, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
                "s8": 1, "u8": 1, "pred": 1, "s64": 8, "u64": 8, "c64": 8}

_COLL_RE = re.compile(
    r"=\s*((?:\([^)]*\))|(?:[a-z0-9]+\[[^\]]*\][^ ]*))\s*"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")

# ring-algorithm wire-cost weights (bytes actually serialized per device)
_WIRE_WEIGHT = {"all-reduce": 2.0, "all-gather": 1.0, "reduce-scatter": 1.0,
                "all-to-all": 1.0, "collective-permute": 1.0}


def collective_bytes(hlo_text: str):
    """Sum result-buffer bytes of every collective in the partitioned HLO,
    weighted by ring wire cost.  Returns (per_type, weighted_total)."""
    per_type = {}
    for m in _COLL_RE.finditer(hlo_text):
        shapes, op = m.group(1), m.group(2)
        nbytes = 0
        for dt, dims in _SHAPE_RE.findall(shapes):
            if dt not in _DTYPE_BYTES:
                continue
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            nbytes += n * _DTYPE_BYTES[dt]
        per_type.setdefault(op, [0, 0])
        per_type[op][0] += 1
        per_type[op][1] += nbytes
    total = sum(_WIRE_WEIGHT[op] * b for op, (_, b) in per_type.items())
    return {op: {"count": c, "bytes": b} for op, (c, b) in per_type.items()}, \
        total


def _batch_shardings(bstruct, mesh):
    def spec(k, v):
        parts = [("pod", "data") if all(a in mesh.axis_names
                                        for a in ("pod", "data"))
                 else "data"]
        size = np.prod([mesh.shape[a] for a in
                        (parts[0] if isinstance(parts[0], tuple)
                         else (parts[0],))])
        if v.shape[0] % size != 0:
            parts = [None]
        parts += [None] * (len(v.shape) - 1)
        return NamedSharding(mesh, P(*parts))
    return {k: spec(k, v) for k, v in bstruct.items()}


_CACHE_RULES = {
    "k": ("batch", "kv_seq", None, None),
    "v": ("batch", "kv_seq", None, None),
    "xkv": ("batch", None, None, None),
    "C": ("batch", None, None, None),
    "n": ("batch", None, None),
    "c": ("batch", None),
    "h": ("batch", None),
    "conv": ("batch", None, None),
    "enc_out": ("batch", None, None),
    "len": (),
}


def _cache_shardings(cache_struct, mesh):
    def spec_of(path, leaf):
        name = None
        for part in reversed(path):
            if hasattr(part, "key"):
                name = str(part.key)
                break
        rule = _CACHE_RULES.get(name, ())
        nd = len(leaf.shape)
        logical = list(rule[:nd])
        lead = nd - len(logical)
        logical = [None] * lead + logical
        return NamedSharding(mesh, dctx.spec_for(leaf.shape, logical))
    return jax.tree_util.tree_map_with_path(spec_of, cache_struct)


def _replicate(tree, mesh):
    return jax.tree.map(lambda _: NamedSharding(mesh, P()), tree)


def _named(specs_tree, mesh):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs_tree,
                        is_leaf=lambda x: isinstance(x, P))


def lower_cell(arch: str, shape_name: str, *, multi_pod: bool,
               precision=None, overrides=None):
    """Build + lower + compile one (arch x shape x mesh) cell.
    Returns the result record (dict)."""
    cfg = get_config(arch)
    if precision:
        import dataclasses
        cfg = dataclasses.replace(cfg, precision=precision)
    if overrides:
        import dataclasses
        cfg = dataclasses.replace(cfg, **overrides)
    shape = SHAPES[shape_name]
    hints = run_hints(arch)
    mesh = make_production_mesh(multi_pod=multi_pod)
    dctx.set_mesh(mesh, rules={"seq": "model"} if cfg.seq_shard else None)
    model = make_model(cfg)
    moe_mode = "ep" if (cfg.moe and cfg.moe.num_experts %
                        mesh.shape["model"] == 0) else "tp"

    params_s = jax.eval_shape(
        lambda: model.init_params(jax.random.PRNGKey(0)))
    # ZeRO-3/FSDP storage sharding over 'data' for params + optimizer state
    pspecs = build_param_specs(params_s, mesh, moe_mode=moe_mode, fsdp=True)
    pshard = _named(pspecs, mesh)
    batch_shards = int(np.prod([mesh.shape[a] for a in ("pod", "data")
                                if a in mesh.axis_names]))

    t0 = time.time()
    if shape.kind == "train":
        # microbatch must keep >= 1 sample per batch shard
        micro = max(hints.get("train_microbatch", 16), batch_shards)
        accum = max(1, shape.global_batch // micro)
        opt_cfg = adamw.OptConfig(use_master=True)
        opt_s = jax.eval_shape(
            lambda p: adamw.init_opt_state(p, opt_cfg), params_s)
        oshard = {"m": pshard, "v": pshard, "master": pshard,
                  "step": NamedSharding(mesh, P())}
        step_fn = make_train_step(model.loss, opt_cfg, grad_accum=accum)
        bstruct = batch_struct(cfg, shape)
        bshard = _batch_shardings(bstruct, mesh)
        jitted = jax.jit(step_fn,
                         in_shardings=(pshard, oshard, bshard),
                         out_shardings=(pshard, oshard, None),
                         donate_argnums=(0, 1))
        lowered = jitted.lower(params_s, opt_s, bstruct)
    elif shape.kind == "prefill":
        bstruct = batch_struct(cfg, shape)
        bshard = _batch_shardings(bstruct, mesh)
        step_fn = lambda p, b: model.prefill(p, b,
                                             cache_capacity=shape.seq_len)
        jitted = jax.jit(step_fn, in_shardings=(pshard, bshard))
        lowered = jitted.lower(params_s, bstruct)
    else:  # decode
        b = shape.global_batch
        s = shape.seq_len
        if cfg.family == "audio":
            frames_s = jax.ShapeDtypeStruct((b, cfg.encoder_seq, cfg.d_model),
                                            jnp.bfloat16)
            cache_s = jax.eval_shape(
                lambda p, f: model.init_cache(p, {"frames": f}, b, s),
                params_s, frames_s)
        else:
            cache_s = jax.eval_shape(
                lambda: model.init_cache(None, None, b, s))
        cshard = _cache_shardings(cache_s, mesh)
        tok_s = jax.ShapeDtypeStruct((b, 1), jnp.int32)
        tshard = _batch_shardings({"tokens": tok_s}, mesh)["tokens"]
        jitted = jax.jit(model.decode_step,
                         in_shardings=(pshard, tshard, cshard),
                         out_shardings=(None, cshard),
                         donate_argnums=(2,))
        lowered = jitted.lower(params_s, tok_s, cache_s)
    t_lower = time.time() - t0

    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    hlo_text = compiled.as_text()
    per_type, wire = collective_bytes(hlo_text)

    # loop-aware re-analysis (XLA cost_analysis counts while bodies once —
    # see repro.launch.hlo_analysis); these are the roofline inputs
    from repro.launch.hlo_analysis import analyze as hlo_analyze
    scaled = hlo_analyze(hlo_text)

    chips = int(np.prod(list(mesh.shape.values())))
    # real parameter count from the abstract tree (the analytic formula
    # drifts for recurrent blocks); MoE active count stays analytic
    n_real = int(sum(int(np.prod(x.shape)) for x in
                     jax.tree.leaves(params_s)))
    flops = float(scaled["dot_flops"])
    # roofline memory term uses the fused-bound traffic (TPU XLA fuses
    # elementwise chains; the CPU artifact doesn't) — both are recorded
    bytes_acc = float(scaled["hbm_bytes_fused"])
    bytes_unfused = float(scaled["hbm_bytes"])
    per_type = scaled["collectives"]
    wire = float(scaled["wire_bytes"])
    n_params = n_real
    if cfg.moe is not None:
        # subtract inactive routed-expert params
        n_active = n_real - (cfg.num_layers - cfg.moe.first_dense_layers) * (
            3 * cfg.d_model * cfg.moe.d_ff_expert *
            (cfg.moe.num_experts - cfg.moe.top_k))
    else:
        n_active = n_real
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        model_flops = 6 * n_active * tokens
    elif shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        model_flops = 2 * n_active * tokens
    else:
        tokens = shape.global_batch
        model_flops = 2 * n_active * tokens

    rec = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "chips": chips,
        "precision": cfg.precision,
        "ok": True,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "alias_bytes": getattr(mem, "alias_size_in_bytes", None),
        },
        "cost": {"flops_per_device": flops,
                 "bytes_accessed_per_device": bytes_acc,
                 "bytes_accessed_unfused": bytes_unfused,
                 "xla_raw_flops": float(cost.get("flops", 0.0)),
                 "xla_raw_bytes": float(cost.get("bytes accessed", 0.0))},
        "collectives": {"per_type": per_type,
                        "wire_bytes_per_device": wire},
        "top_flops": scaled["top_flops"][:8],
        "top_bytes": scaled["top_bytes"][:8],
        "roofline": {
            "compute_s": flops / PEAK_FLOPS,
            "memory_s": bytes_acc / HBM_BW,
            "collective_s": wire / ICI_BW,
            "model_flops_total": model_flops,
            "model_flops_per_device": model_flops / chips,
            "useful_flops_ratio": (model_flops / chips) / max(flops, 1.0),
        },
    }
    dom = max(("compute_s", "memory_s", "collective_s"),
              key=lambda k: rec["roofline"][k])
    rec["roofline"]["dominant"] = dom
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--precision", default=None)
    ap.add_argument("--moe-dispatch", default=None,
                    choices=[None, "ragged", "dense"])
    ap.add_argument("--seq-shard", action="store_true")
    ap.add_argument("--moe-reduce-bf16", action="store_true")
    ap.add_argument("--out", default="benchmarks/results/dryrun")
    args = ap.parse_args()

    cells = []
    if args.all:
        for a in ARCHS:
            for s in SHAPES:
                if cell_is_runnable(a, s):
                    cells.append((a, s))
    else:
        cells = [(args.arch, args.shape)]

    os.makedirs(args.out, exist_ok=True)
    for arch, shape in cells:
        tag = f"{arch}_{shape}_{'multi' if args.multi_pod else 'single'}"
        if args.precision:
            tag += f"_{args.precision}"
        if args.moe_dispatch:
            tag += f"_{args.moe_dispatch}"
        if args.seq_shard:
            tag += "_sp"
        if args.moe_reduce_bf16:
            tag += "_rbf16"
        overrides = {}
        if args.moe_dispatch:
            overrides["moe_dispatch"] = args.moe_dispatch
        if args.seq_shard:
            overrides["seq_shard"] = True
        if args.moe_reduce_bf16:
            overrides["moe_reduce_bf16"] = True
        overrides = overrides or None
        path = os.path.join(args.out, tag + ".json")
        print(f"=== {tag} ===", flush=True)
        try:
            rec = lower_cell(arch, shape, multi_pod=args.multi_pod,
                             precision=args.precision, overrides=overrides)
        except Exception as e:  # a failing cell is a bug; record it
            rec = {"arch": arch, "shape": shape,
                   "mesh": "2x16x16" if args.multi_pod else "16x16",
                   "ok": False, "error": f"{type(e).__name__}: {e}",
                   "traceback": traceback.format_exc()[-2000:]}
        with open(path, "w") as f:
            json.dump(rec, f, indent=1)
        if rec["ok"]:
            r = rec["roofline"]
            print(f"  compile {rec['compile_s']}s | "
                  f"compute {r['compute_s']:.4f}s mem {r['memory_s']:.4f}s "
                  f"coll {r['collective_s']:.4f}s -> {r['dominant']} | "
                  f"useful {r['useful_flops_ratio']:.2f}", flush=True)
        else:
            print(f"  FAILED: {rec['error']}", flush=True)


if __name__ == "__main__":
    main()
