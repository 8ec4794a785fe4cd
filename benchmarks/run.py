"""Benchmark harness — the full pinned suite, one key per paper
table/figure or operator family.

Prints ``name,us_per_call,derived`` CSV rows.

  PYTHONPATH=src python -m benchmarks.run [--only fig2a,...] [--smoke]
      [--pin-config BMxBNxBK] [--backend NAME] [--json PATH]

Suites: ``fig2a`` (fwd fp8 vs padded baseline), ``gemm_bf16`` (the true
bf16 registry path), ``wgrad`` (both precisions + the old-vs-new
multi-tile schedule rows with modeled operand-HBM-byte columns),
``quantize`` (tilewise + fused act_quant), ``gemm_quant`` (quantizing
epilogue), ``decode`` (tiny-M serving pool), ``fig2b`` (padding memory
geometry + the measured pad-pass round trip), ``equivalence`` (bitwise
gate), ``moe_layer``, ``gemm_hotpath``.

``--smoke`` shrinks every suite to CI-feasible shapes whose row names are
a strict SUBSET of the full suite's — a smoke snapshot diffs cleanly
against a committed full one via ``scripts/bench_diff.py``.

``--pin-config`` installs a pinned ``KernelConfig`` as the process-wide
default (every suite's GEMMs resolve to it); without it, suites that tune
go through the TilePlan autotuner pool.  ``--json`` additionally writes
the rows as a machine-readable snapshot (the bench-snapshot protocol:
commit the file as ``BENCH_<date>.json`` so perf regressions diff — each
row carries ``measured: true/false`` and the resolved dispatch backend,
so ``bench_diff.py`` can separate measured regressions from model drift).
"""
from __future__ import annotations

import argparse
import datetime
import json
import platform


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma list: fig2a,gemm_bf16,wgrad,quantize,"
                         "gemm_quant,decode,fig2b,equivalence,moe_layer,"
                         "gemm_hotpath")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes per suite (row names stay a subset "
                         "of the full suite's)")
    ap.add_argument("--pin-config", default=None, metavar="BMxBNxBK",
                    help="pin tile shapes, e.g. 256x128x128 (skips the "
                         "autotuner pool)")
    ap.add_argument("--backend", default=None,
                    help="dispatch backend pin (alone it implies the "
                         "default tile shapes)")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write rows as a JSON snapshot")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from repro.kernels import plan as plan_mod
    if args.pin_config:
        bm, bn, bk = (int(v) for v in args.pin_config.lower().split("x"))
        plan_mod.set_default_config(plan_mod.KernelConfig(
            block_m=bm, block_n=bn, block_k=bk, backend=args.backend))
    elif args.backend:
        plan_mod.set_default_config(
            plan_mod.KernelConfig(backend=args.backend))

    from benchmarks import (bench_equivalence, bench_gemm_hotpath,
                            bench_grouped_gemm as bg, bench_memory,
                            bench_moe_layer)

    smoke = args.smoke
    be = args.backend

    # full runs prepend the smoke shapes so a --smoke snapshot's row
    # names stay a strict subset of a committed full snapshot's
    def suite_fig2a(report):
        bg.bench_cases(
            report,
            bg.SMOKE_CASES if smoke else bg.SMOKE_CASES + bg.CASES,
            backend=be)

    def suite_gemm_bf16(report):
        bg.bench_gemm_bf16_cases(
            report,
            bg.SMOKE_CASES if smoke else bg.SMOKE_CASES + bg.CASES[:4],
            backend=be)

    def suite_wgrad(report):
        cases = bg.SMOKE_CASES if smoke else bg.SMOKE_CASES + bg.CASES[:4]
        bg.bench_wgrad_cases(report, cases, backend=be)
        bg.bench_wgrad_fp8_cases(report, cases, backend=be)
        bg.bench_wgrad_multitile_cases(
            report,
            bg.WGRAD_KERNEL_SMOKE if smoke else bg.WGRAD_KERNEL_CASES)

    def suite_quantize(report):
        cases = bg.SMOKE_CASES if smoke else bg.SMOKE_CASES + bg.CASES[:4]
        bg.bench_quantize_cases(report, cases, backend=be)
        bg.bench_act_quant_cases(report, cases, backend=be)

    def suite_gemm_quant(report):
        bg.bench_gemm_quant_cases(
            report,
            bg.SMOKE_CASES if smoke else bg.SMOKE_CASES + bg.CASES[:4],
            backend=be)

    def suite_decode(report):
        cases = bg.DECODE_CASES[:1] if smoke else bg.DECODE_CASES
        bg.bench_decode_cases(report, cases, backend=be,
                              measure_autotune=not smoke)

    suites = {
        "fig2a": suite_fig2a,
        "gemm_bf16": suite_gemm_bf16,
        "wgrad": suite_wgrad,
        "quantize": suite_quantize,
        "gemm_quant": suite_gemm_quant,
        "decode": suite_decode,
        "fig2b": bench_memory.run,
        "equivalence": bench_equivalence.run,
        "moe_layer": lambda report: bench_moe_layer.run(report, smoke=smoke),
        "gemm_hotpath": lambda report: bench_gemm_hotpath.run(
            report, backend=be or "xla_ragged", smoke=smoke),
    }
    wanted = (args.only.split(",") if args.only else list(suites))

    print("name,us_per_call,derived")
    rows = []

    def report(name, us, derived, backend=None, extra=None):
        # us=None marks a derived-only row (geometry/bytes math, nothing
        # timed): the CSV shows an explicit blank and the snapshot omits
        # the timing key instead of recording a fake 0.0 measurement —
        # `measured` makes the distinction machine-readable per row
        row = {"name": name, "measured": us is not None}
        if backend is not None:
            row["backend"] = backend
        if us is None:
            print(f"{name},,{derived}", flush=True)
        else:
            print(f"{name},{us:.1f},{derived}", flush=True)
            row["us_per_call"] = round(us, 1)
        row["derived"] = derived
        if extra:
            row.update(extra)
        rows.append(row)

    for key in wanted:
        suites[key](report)

    if args.json:
        from repro.kernels import dispatch
        from repro.kernels.plan import _device_kind
        # the resolved (gemm, fp8) auto choice — what `backend: null`
        # used to hide; an explicit --backend records itself verbatim
        try:
            backend_resolved = dispatch.resolve(("gemm", "fp8"),
                                                args.backend)
        except Exception as e:              # record the refusal, not null
            backend_resolved = f"unavailable: {e}"
        default_cfg = plan_mod.pinned_default() or plan_mod.KernelConfig()
        snapshot = {
            "date": datetime.date.today().isoformat(),
            "suites": wanted,
            "smoke": smoke,
            "device": _device_kind(),
            "platform": platform.platform(),
            "pin_config": args.pin_config or
                f"bm{default_cfg.block_m}xbn{default_cfg.block_n}"
                f"xbk{default_cfg.block_k}(default)",
            "backend": args.backend or "auto",
            "backend_resolved": backend_resolved,
            # per-op count of CONFIG_POOL entries the static resource
            # model eliminated before measurement (kernels/resources.py)
            "pool_pruned": plan_mod.prune_stats(),
            "rows": rows,
        }
        with open(args.json, "w") as f:
            json.dump(snapshot, f, indent=2)
            f.write("\n")
        print(f"# wrote {len(rows)} rows to {args.json}", flush=True)


if __name__ == "__main__":
    main()
