#!/usr/bin/env python3
"""Run one cell traced, as ``bench/run.py --trace 1`` does, and read the
device time of the program's named layers from the same trace.

  python3 bench/layer_trace.py --workload dsmoe-decode --seed 7 --seconds 10

The first line printed is ``bench/run.py``'s result line for the run.
The last line adds, under ``layers``, the layer metrics of the cell's
kind (``bench/metrics/{moe,attn,shared}_ms.<kind>.py``,
``weight_quant_ms.serve.py``; null where the program names no layers),
read from the run's own reduction of its trace and context, the share of
device busy time in leaf ops with no scope and the largest of them, and
the run's idle gaps, labelled by the innermost host span, the engine's
``engine.*`` spans included.
``--dump-trace PATH`` writes the (module, scope) -> count, seconds table
and the op time per (module, op); ``--hlo-out DIR`` writes the optimized
HLO of the window's programs, for comparing two commits' programs.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
METRICS = {"train": ("moe_ms.train", "attn_ms.train", "shared_ms.train"),
           "serve": ("moe_ms.serve", "attn_ms.serve", "shared_ms.serve",
                     "weight_quant_ms.serve")}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--dump-trace", metavar="PATH")
    ap.add_argument("--hlo-out", metavar="DIR")
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="smoke sizes, interpret kernels, on the CPU: no "
                         "device plane, so no layer is read")
    args = ap.parse_args(argv)
    if args.cpu_rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

    import jax
    from bench import run, scopes, spec
    cell = spec.cell(args.workload)
    kind = cell.traffic["kind"]
    if not args.cpu_rehearsal:
        devices = jax.devices()
        if devices[0].platform != "tpu" or len(devices) < cell.chips:
            run.log(f"layer_trace: {args.workload} needs {cell.chips} TPU "
                    f"chip(s); JAX finds {len(devices)} "
                    f"{devices[0].platform!r} device(s)")
            return 1
        from repro.launch.compile_cache import enable_compile_cache
        enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    seen = {}
    result = run.run_cell(args.workload, args.seed, args.seconds, True,
                          args.cpu_rehearsal, keep=seen)
    result.pop("_verdict")
    print(json.dumps(result), flush=True)

    r, ctx = seen["r"], seen["ctx"]
    red = ctx.trace
    metrics = {name: spec.metric_reader(name)(ctx) for name in METRICS[kind]}
    att = scopes.attribution(red, ctx)
    share = att["unscoped_share"]
    run.log(f"[scopes] vocabulary={len(scopes.vocabulary())} "
            f"unscoped_share={'-' if share is None else f'{share:.3f}%'} "
            f"busy_s={red.busy_s:.6f} "
            f"top_unscoped={json.dumps(att['unscoped_top'])}")
    if args.dump_trace:
        with open(args.dump_trace, "w") as f:
            json.dump({"table": [[m, p, n, s] for (m, p), (n, s)
                                 in sorted(att["table"].items(),
                                           key=lambda kv: -kv[1][1])],
                       "module_ops": [[m, op, n, s] for (m, op), (n, s)
                                      in red.module_ops.items()]},
                      f, indent=0)
    if args.hlo_out:
        os.makedirs(args.hlo_out, exist_ok=True)
        for key, text in r["hlo"].items():
            with open(os.path.join(args.hlo_out, key + ".hlo"), "w") as f:
                f.write(text)
    print(json.dumps({"layers": {
        "metrics": metrics, "unscoped_share": share,
        "unscoped_top": att["unscoped_top"],
        "idle_gaps": [list(g) for g in red.idle_gaps]}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
