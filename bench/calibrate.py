#!/usr/bin/env python3
"""Readings that the limits in ``limits/<workload>.json`` are set from,
on the chip at the cell's own sizes, in one process:

  python3 bench/calibrate.py --workload dsmoe-train --seeds 101-112 \\
      --control-seeds 201-203

For every seed of ``--seeds`` the program's numbers (the timed path's
first steps, or a short closed loop of generate calls, against the plain
reference).  For every seed of ``--control-seeds`` the control's: the
reference one precision step lower put in the program's place, and for
training cells also the fault "half of the batch left out, the mean taken
over the rest", planted in the reference put in the program's place.
Prints one JSON line per reading and a summary: the lower reading (the
largest over the program's seeds) and the upper (the smallest over the
control's).  ``--cpu-rehearsal`` runs it at smoke sizes on the CPU.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def _seeds(text: str) -> list:
    out = []
    for part in text.split(","):
        if "-" in part:
            a, b = part.split("-")
            out += list(range(int(a), int(b) + 1))
        elif part:
            out.append(int(part))
    return out


def _emit(kind, seed, numbers, notes=None):
    print(json.dumps({"reading": kind, "seed": seed, **numbers}), flush=True)
    if notes:
        look = {k: v for k, v in notes.items() if k != "change_left_out"}
        print(json.dumps({"look": kind, "seed": seed, **look}), flush=True)


def half_batch(batches):
    """The fault: the loss's mean over the first half of each batch's
    rows (of its positions where the batch has one row)."""
    out = []
    for b in batches:
        toks, labels = b["tokens"], b["labels"].copy()
        if toks.shape[0] > 1:
            labels[toks.shape[0] // 2:] = -1
        else:
            labels[:, toks.shape[1] // 2:] = -1
        out.append({"tokens": toks, "labels": labels})
    return out


def calibrate_train(cell, seeds, control_seeds, rehearsal, backend):
    from bench import check, spec, workload
    from bench.cells import train
    t = spec.traffic_sizes(cell.traffic, rehearsal)
    prog = train.compile_program(cell.config, cell.traffic, backend,
                                 rehearsal)
    names = train.leaf_names(prog.shapes)
    n = t["first_steps"]
    table = {"program": [], "control": [], "half_batch": []}
    for seed in seeds:
        params, opt = train.init_state(prog, seed)
        pool = workload.pool(t, prog.cfg.vocab_size, seed)
        params, opt, read = train.first_steps(prog, params, opt, pool,
                                              seed, n)
        batches = train.host_batches(pool, n)
        del params, opt, pool
        ref = train.reference_readings(cell.config, prog, batches, seed)
        nums, notes = check.train_numbers(read, ref, names)
        table["program"].append(nums)
        _emit("program", seed, nums, notes)
    for seed in control_seeds:
        pool = workload.pool(t, prog.cfg.vocab_size, seed)
        batches = train.host_batches(pool, n)
        del pool
        ref = train.reference_readings(cell.config, prog, batches, seed)
        ctrl = train.reference_readings(cell.config, prog, batches, seed,
                                        control=True)
        nums, notes = check.train_numbers(ctrl, ref, names)
        table["control"].append(nums)
        _emit("control", seed, nums, notes)
        half = train.reference_readings(cell.config, prog,
                                        half_batch(batches), seed)
        nums, notes = check.train_numbers(half, ref, names)
        table["half_batch"].append(nums)
        _emit("half_batch", seed, nums, notes)
    return table


def calibrate_serve(cell, seeds, control_seeds, rehearsal, backend):
    import numpy as np
    from bench import spec, weights, workload
    from bench.cells import serve
    t = spec.traffic_sizes(cell.traffic, rehearsal)
    prog = serve.build(cell.config, backend, rehearsal, seeds[0], t)
    table = {"program": [], "control": []}
    for seed in sorted(set(seeds) | set(control_seeds)):
        prog.engine.params = weights.make(prog.shapes, seed,
                                          init=cell.config.get("init"))
        pool = workload.pool(t, prog.cfg.vocab_size, seed)
        # the cell's own load: two calls, sampled as a run samples
        done = [(i, 0.0, np.asarray(prog.engine.generate(pool[i]).tokens))
                for i in (1, 2)]
        picks = serve.sample(done, t["check_requests"], seed)
        prompts = np.stack([np.asarray(pool[done[c][0]]["tokens"])[r]
                            for c, r in picks])
        served = np.stack([done[c][2][r] for c, r in picks])
        del pool
        if seed in seeds:
            gap, _ = serve.reference_gaps(cell.config, prog.cfg, prog.shapes,
                                          seed, prompts, served)
            table["program"].append({"logit_gap": gap})
            _emit("program", seed, {"logit_gap": gap})
        if seed in control_seeds:
            gap, _ = serve.reference_gaps(cell.config, prog.cfg, prog.shapes,
                                          seed, prompts, served, control=True)
            table["control"].append({"logit_gap": gap})
            _emit("control", seed, {"logit_gap": gap})
    return table


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--cpu-rehearsal", action="store_true")
    args = ap.parse_args(argv)
    if args.cpu_rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    import jax
    from bench import spec
    if not args.cpu_rehearsal:
        if jax.devices()[0].platform != "tpu":
            print("calibrate: JAX finds no TPU", file=sys.stderr)
            return 1
        from repro.launch.compile_cache import enable_compile_cache
        enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cell = spec.cell(args.workload)
    backend = "pallas_interpret" if args.cpu_rehearsal else "pallas"
    run = {"train": calibrate_train, "serve": calibrate_serve}
    table = run[cell.traffic["kind"]](cell, _seeds(args.seeds),
                                      _seeds(args.control_seeds),
                                      args.cpu_rehearsal, backend)
    summary = {}
    for name in table["program"][0]:
        summary[name] = {
            "lower": max(r[name] for r in table["program"]),
            **{f"upper_{k}": min(r[name] for r in v)
               for k, v in table.items() if k != "program" and v}}
    print(json.dumps({"summary": summary}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
