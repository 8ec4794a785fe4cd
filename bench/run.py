#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

  python3 bench/run.py --workload dsmoe-train --seed 7 --seconds 10 --trace 0
  python3 bench/run.py --workload dsmoe-train --cpu-rehearsal   # tests only

The cell's configuration, traffic, limits and per-layer metrics are found
by the names in ``BENCHMARK.json``.  Set-up makes the weights and the
inputs from ``--seed`` on the device, builds the program object and warms
up every shape the window uses; the window then runs for ``--seconds``.
With ``--trace 0`` the result carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a profiler trace of the
window.  After the window the program's state is freed and the plain
reference checks what the window's path produced.

It exits non-zero, printing no result, when JAX finds no TPU or fewer
chips than the cell asks for.  ``--cpu-rehearsal`` runs the cell at the
registry's smoke sizes with interpret-mode kernels on the CPU; its result
line always says ``"correct": false`` and carries no device metric.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import math
import os
import shutil
import sys
import tempfile
import time
import types


def _process_start() -> float:
    """perf_counter() reading of this process's start (Linux), else now."""
    now = time.perf_counter()
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return now - (uptime - start_ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return now


T_PROCESS = _process_start()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def log(line: str) -> None:
    print(line, file=sys.stderr, flush=True)


class CompileCounter:
    """Counts lowerings (every compile, cached or not) while armed."""

    def __init__(self):
        import jax
        self.armed, self.count = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, _secs, **_kw):
        if self.armed and name.endswith("jaxpr_to_mlir_module_duration"):
            self.count += 1


@contextlib.contextmanager
def traced(enabled: bool):
    """Profile the block into a fresh directory under TMPDIR; yields a
    dict that holds the trace path afterwards."""
    import jax
    out = {}
    if not enabled:
        yield out
        return
    log_dir = tempfile.mkdtemp(prefix="bench-trace-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        yield out
    finally:
        jax.profiler.stop_trace()
        out["dir"] = log_dir


def annotate(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


def peak_bytes(devices) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def _share_of_peak(name: str) -> bool:
    return "roofline" in name or "mfu" in name


def per_layer(cell, ctx) -> dict:
    """Every per-layer metric the cell lists, read from the trace.  One
    that finds nothing to read here (a kernel or module name that no
    longer matches) fails the run rather than leave a gap in the line."""
    from bench import spec
    out = {}
    for m in cell.per_layer:
        value = spec.metric_reader(m["name"])(ctx)
        if value is None:
            raise RuntimeError(f"{cell.name}: per-layer metric {m['name']!r}"
                               f" found nothing to read in the trace")
        if _share_of_peak(m["name"]) and value > 105.0:
            raise RuntimeError(f"{cell.name}: {m['name']} reads {value}% of "
                               f"its peak: the count of work or of time is "
                               f"wrong")
        out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def _assert_backend(resolutions, backend, where):
    fams = ("gemm", "gemm_quant", "wgrad")
    seen = [r for r in resolutions if r[0] in fams]
    other = sorted({r for r in seen if r[2] != backend})
    if not seen or other:
        raise RuntimeError(f"{where}: expert GEMMs resolved to {other} "
                           f"(of {len(seen)}), not {backend!r}")
    return len(seen)


def run_train(cell, seed, seconds, trace, rehearsal, backend, counter):
    import jax
    import numpy as np
    from bench import check, spec, workload
    from bench.cells import train
    t = spec.traffic_sizes(cell.traffic, rehearsal)
    prog = train.compile_program(cell.config, cell.traffic, backend,
                                 rehearsal)
    n_res = _assert_backend(prog.resolutions, backend, "train step")
    params, opt = train.init_state(prog, seed)
    pool = workload.pool(t, prog.cfg.vocab_size, seed)
    first = t["first_steps"]
    params, opt, prog_read = train.first_steps(prog, params, opt, pool,
                                               seed, first)
    check_batches = train.host_batches(pool, first)
    n_params = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(prog.shapes))
    log(f"[program] params_b={n_params / 1e9:.4f} batch={t['batch']}x"
        f"{t['seq']} expert_gemm_resolutions={n_res}x{backend} "
        f"first_losses={prog_read['losses']}")

    setup_s = time.perf_counter() - T_PROCESS
    counter.armed = True
    with traced(trace) as tr:
        with annotate("bench.window"):
            params, opt, losses, window_s = train.window(
                prog, params, opt, pool, first, seconds, annotate,
                t["ahead"])
    counter.armed = False
    peak = peak_bytes(jax.local_devices())
    steps = len(losses)
    failed = sum(1 for x in losses if not math.isfinite(x))
    log(f"[window] steps={steps} seconds={window_s:.4f} "
        f"compiles_in_window={counter.count} last_loss={losses[-1]:.5f}")
    hlo = ({"step": prog.compiled.as_text()} if trace and not rehearsal
           else {})
    del params, opt, pool, prog.compiled
    jax.clear_caches()

    tokens = steps * t["batch"] * t["seq"]
    counts = dict(steps=steps, tokens=tokens, calls=0)
    t_ref = time.perf_counter()
    ref_read = train.reference_readings(cell.config, prog, check_batches,
                                        seed)
    log(f"[reference] seconds={time.perf_counter() - t_ref:.4f}")
    rows = np.asarray(ref_read["rows"])
    log(f"[routing] step1 rows_per_expert max={rows.max(-1).tolist()} "
        f"mean={rows.mean(-1).tolist()} (per MoE layer)")
    names = train.leaf_names(prog.shapes)
    numbers, notes = check.train_numbers(prog_read, ref_read, names)
    log(f"[check] program_losses={prog_read['losses']} "
        f"reference_losses={ref_read['losses']} "
        f"grad_gap_leaf={notes['grad_gap_leaf']} "
        f"change_gap_leaf={notes['change_gap_leaf']} "
        f"change_left_out={notes['change_left_out']}")
    log(f"[look] step_loss_gaps={notes['step_loss_gaps']} "
        f"grad_worst={notes['grad_worst']} "
        f"grad_gap_median={notes['grad_gap_median']} "
        f"change_worst={notes['change_worst']} "
        f"change_gap_median={notes['change_gap_median']}")
    e2e = {"train_tokens_per_s": tokens / window_s}
    return dict(prog_cfg=prog.cfg, traffic=t, setup_s=setup_s, peak=peak,
                e2e=e2e, counts=counts, attempted=steps, failed=failed,
                numbers=numbers, trace_dir=tr.get("dir"), hlo=hlo,
                compiles=counter.count)


def run_serve(cell, seed, seconds, trace, rehearsal, backend, counter):
    import jax
    import numpy as np
    from repro.analysis import events as ev
    from bench import spec, workload
    from bench.cells import serve
    t = spec.traffic_sizes(cell.traffic, rehearsal)
    prog = serve.build(cell.config, backend, rehearsal, seed, t)
    pool = workload.pool(t, prog.cfg.vocab_size, seed)
    with ev.capture() as events:
        warm = prog.engine.generate(pool[0])
        np.asarray(warm.tokens)
    res = [(e.data["family"], e.data["precision"], e.data["backend"])
           for e in events if e.kind == "backend_resolved"]
    n_res = _assert_backend(res, backend, "generate")
    log(f"[program] batch={t['batch']} prompt={t['prompt']} new={t['new']} "
        f"decode_block_m={prog.engine.decode_config.block_m} "
        f"expert_gemm_resolutions={n_res}x{backend}")

    setup_s = time.perf_counter() - T_PROCESS
    counter.armed = True
    with traced(trace) as tr:
        with annotate("bench.window"):
            done, window_s = serve.window(prog.engine, pool, seconds,
                                          annotate)
    counter.armed = False
    peak = peak_bytes(jax.local_devices())
    vocab = prog.cfg.vocab_size
    want = (t["batch"], t["new"])
    failed = sum(int(np.sum((toks < 0).any(-1) | (toks >= vocab).any(-1)))
                 if toks.shape == want else t["batch"]
                 for _, _, toks in done)
    lat = np.repeat([d[1] for d in done], t["batch"])
    calls = len(done)
    log(f"[window] calls={calls} seconds={window_s:.4f} "
        f"compiles_in_window={counter.count} "
        f"call_s_min={min(d[1] for d in done):.4f} "
        f"call_s_max={max(d[1] for d in done):.4f}")
    hlo = (dict(zip(("prefill", "decode_loop"),
                    serve.hlo_texts(prog.engine, pool[0])))
           if trace and not rehearsal else {})
    picks = serve.sample(done, t["check_requests"], seed)
    prompts_host = {i: np.asarray(p["tokens"]) for i, p in enumerate(pool)}
    prompts = np.stack([prompts_host[done[c][0]][r] for c, r in picks])
    served = np.stack([done[c][2][r] for c, r in picks])
    shapes, prog_cfg = prog.shapes, prog.cfg
    del pool, prog, warm
    jax.clear_caches()

    t_ref = time.perf_counter()
    gap, rows = serve.reference_gaps(cell.config, prog_cfg, shapes, seed,
                                     prompts, served)
    log(f"[reference] seconds={time.perf_counter() - t_ref:.4f}")
    rows = np.asarray(rows)
    log(f"[routing] sample rows_per_expert max={rows.max(-1).tolist()} "
        f"mean={rows.mean(-1).tolist()} (per MoE layer, "
        f"{len(picks)} requests)")
    tokens = calls * t["batch"] * t["new"]
    e2e = {"serve_tokens_per_s": tokens / window_s,
           "serve_p95_ms": 1e3 * float(np.percentile(lat, 95))}
    counts = dict(steps=0, tokens=tokens, calls=calls)
    return dict(prog_cfg=prog_cfg, traffic=t, setup_s=setup_s, peak=peak,
                e2e=e2e, counts=counts, attempted=calls * t["batch"],
                failed=failed, numbers={"logit_gap": gap},
                trace_dir=tr.get("dir"), hlo=hlo, compiles=counter.count)


RUNNERS = {"train": run_train, "serve": run_serve}


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             rehearsal: bool, dump: str | None = None,
             limits: dict | None = None) -> dict:
    """Set-up, window, reference; returns the result object, with the
    comparison's own verdict under ``_verdict``.  The caller has checked
    the devices.  ``limits`` replaces the cell's limit file (the tests'
    rehearsal sizes have limits of their own)."""
    import jax
    from bench import check, kernels, peaks, spec, trace_reduce
    cell = spec.cell(workload)
    if cell.chips != 1:
        # both runners drive one device: a multi-chip cell needs a runner
        # that builds its mesh before it can be measured
        raise RuntimeError(f"{workload}: no runner for {cell.chips} chips")
    backend = "pallas_interpret" if rehearsal else "pallas"
    counter = CompileCounter()
    r = RUNNERS[cell.traffic["kind"]](cell, seed, seconds, trace, rehearsal,
                                      backend, counter)
    log(f"[setup] setup_s={r['setup_s']:.4f}")
    correct, checks = check.verdict(
        r["numbers"], cell.limits if limits is None else limits)
    if r["compiles"]:
        correct = False
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": r["peak"]}
    metrics, breakdown = {}, None
    if trace:
        xplane = trace_reduce.find_xplane(r["trace_dir"])
        red = trace_reduce.reduce(xplane, require_device=not rehearsal)
        if dump:
            with open(dump, "w") as f:
                json.dump({"ops": red.ops, "modules": red.modules,
                           **trace_reduce.describe(xplane)}, f, indent=1)
        shutil.rmtree(r["trace_dir"], ignore_errors=True)
        device.update(busy_s=red.busy_s, window_s=red.window_s)
        if not rehearsal:
            # what a per-layer metric reader may read
            ctx = types.SimpleNamespace(
                kind=cell.traffic["kind"], sizes=spec.sizes(r["prog_cfg"]),
                traffic=r["traffic"], chips=cell.chips,
                peak=peaks.peak(device["kind"]), trace=red,
                gemm_ops=kernels.expert_gemm_ops(r["hlo"].values()),
                programs={k: kernels.module_name(v)
                          for k, v in r["hlo"].items()},
                **r["counts"])
            metrics = per_layer(cell, ctx)
        breakdown = {"device_ops": trace_reduce.top_ops(red),
                     "idle_gaps": [list(g) for g in red.idle_gaps]}
    elif not rehearsal:
        reported = {m["name"]: m for m in cell.end_to_end}
        values = dict(r["e2e"], setup_s=r["setup_s"])
        if r["peak"] is not None:
            values["peak_hbm_gb"] = r["peak"] / 1e9
        metrics = {k: {"value": v, "unit": reported[k]["unit"]}
                   for k, v in values.items() if k in reported}
    lim = cell.limits if limits is None else limits
    for name in sorted(check.not_compared(lim) & set(r["numbers"])):
        log(f"[read] {name}={r['numbers'][name]!r} not compared "
            f"(no upper reading)")
    for name, c in checks.items():
        log(f"[limit] {name}={c['value']!r} limit={c['limit']!r}")
    result = {"correct": bool(correct) and not rehearsal,
              "attempted": r["attempted"], "failed": r["failed"],
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    result["_verdict"] = bool(correct)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--dump-trace", metavar="PATH",
                    help="with --trace 1: also write the trace's planes, "
                         "op and module tables as JSON to PATH")
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="smoke sizes, interpret kernels, on the CPU; "
                         "for the tests only: reports correct false")
    args = ap.parse_args(argv)
    if args.cpu_rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

    import jax
    from bench import spec
    cell = spec.cell(args.workload)
    devices = jax.devices()
    if not args.cpu_rehearsal:
        if devices[0].platform != "tpu":
            log(f"bench: JAX finds no TPU (platform "
                f"{devices[0].platform!r}); nothing is measured")
            return 1
        if len(devices) < cell.chips:
            log(f"bench: {args.workload} needs {cell.chips} chips, JAX "
                f"finds {len(devices)}")
            return 1
        from repro.launch.compile_cache import enable_compile_cache
        cache = enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        log(f"[setup] kind={devices[0].device_kind!r} devices={len(devices)} "
            f"jax={jax.__version__} compile_cache={cache}")
    result = run_cell(args.workload, args.seed, args.seconds,
                      bool(args.trace), args.cpu_rehearsal, args.dump_trace)
    result.pop("_verdict")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
