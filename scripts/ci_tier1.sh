#!/usr/bin/env bash
# Tier-1 regression gate: the full suite on CPU.
#
# Runs everywhere (no accelerator needed): the Pallas kernels execute in
# interpret mode, TPU-only backends are refused via the TPU probe (and
# their tests select CPU-runnable backends), and tests/test_tpu_compile.py
# compiles the main kernels for a described TPU v5e.
#
#   scripts/ci_tier1.sh [extra pytest args]
set -euo pipefail
cd "$(dirname "$0")/.."

export JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}"
PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" \
    python -m pytest -x -q "$@"

# Bench entry points must not rot: one tiny interpret-mode shape through
# bench_grouped_gemm's CLI (exercises the autotuner pool selection + the
# JSON cache write path for BOTH op families — gemm and wgrad; cache goes
# to a throwaway location).
REPRO_TILEPLAN_CACHE="$(mktemp -d)/tileplan_cache.json" \
PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" \
    python -m benchmarks.bench_grouped_gemm --smoke --backend pallas_interpret

# Backward regression gate: jax.grad through grouped_linear on the kernel
# path (both precisions) with a partially-filled capacity buffer — the fp8
# VJP must keep dgrad AND wgrad padding-free and its dx tail exactly zero
# (the unowned-row corruption this repo once shipped).
PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" python - <<'EOF'
import numpy as np, jax, jax.numpy as jnp
from repro.core.grouped_gemm import grouped_linear

rng = np.random.default_rng(0)
x = jnp.asarray(rng.standard_normal((256, 128)), jnp.float32)
w = jnp.asarray(rng.standard_normal((3, 128, 128)), jnp.float32)
gs = jnp.asarray([60, 0, 30], jnp.int32)          # sum=90 < 256

gw_fp8 = None
for precision in ("fp8", "bf16"):
    kw = {"backend": "pallas_interpret"} if precision == "fp8" else {}
    def loss(x, w):
        y = grouped_linear(x, w, gs, precision=precision, **kw)
        return jnp.sum(y.astype(jnp.float32) ** 2)
    gx, gw = jax.grad(loss, argnums=(0, 1))(x, w)
    assert bool(jnp.isfinite(gx).all()) and bool(jnp.isfinite(gw).all()), precision
    if precision == "fp8":
        assert np.all(np.asarray(gx[90:]) == 0.0), "fp8 tail dx must be zero"
        gw_fp8 = gw          # fp8 forward + bf16 wgrad: the recipe baseline
    assert float(jnp.abs(gw[1]).max()) == 0.0, f"{precision}: empty-group dw"
    print(f"grad smoke [{precision}] OK")

# All-fp8 step: the fp8-operand wgrad (wgrad_precision="fp8") must stay
# finite, keep the tail-dx/empty-group guarantees, and agree with the
# SAME fp8 forward's bf16 wgrad within fp8 quantization tolerance — the
# baseline is gw_fp8 (fp8 fwd + bf16 wgrad), so the deviation isolates
# the wgrad's operand precision, not the forward's quantization noise.
def loss8(x, w):
    y = grouped_linear(x, w, gs, precision="fp8", backend="pallas_interpret",
                       wgrad_precision="fp8")
    return jnp.sum(y.astype(jnp.float32) ** 2)
gx8, gw8 = jax.grad(loss8, argnums=(0, 1))(x, w)
assert bool(jnp.isfinite(gx8).all()) and bool(jnp.isfinite(gw8).all())
assert np.all(np.asarray(gx8[90:]) == 0.0), "fp8-wgrad tail dx must be zero"
assert float(jnp.abs(gw8[1]).max()) == 0.0, "fp8-wgrad empty-group dw"
rel = (np.abs(np.asarray(gw8) - np.asarray(gw_fp8)).max()
       / max(np.abs(np.asarray(gw_fp8)).max(), 1e-6))
assert rel < 0.1, f"fp8 wgrad deviates {rel:.3f} from bf16 wgrad"
print("grad smoke [fp8 wgrad_precision=fp8] OK")
EOF

# Contract gate: the static-analysis subsystem replaces the historical
# monkeypatch-count gates (quantize-once, producer-fusion, decode plan
# discipline) with declarative contracts + registry/AST lint:
#   layer 1 — jaxpr contracts over grouped_linear{,_fused,_ffn}, moe_apply
#             and one real Engine generate (REPRO-C01..C06)
#   layer 2 — operator-registry + tile-pool alignment lint (REPRO-R01..R07)
#   layer 3 — AST lint over src/repro (REPRO-A01..A03)
#   layer 4 — static kernel-resource lint: VMEM/alignment budget proofs
#             for every operator family x pool entry x device
#             (REPRO-V01..V07, kernels/resources.py)
#   layer 5 — retrace detector: compile contracts proving the jitted hot
#             paths (grouped_linear{,_ffn} steps, Engine.generate, the
#             padded baseline) compile exactly once per shape/phase/bucket
#             (REPRO-T01..T03)
# Fails on any finding not in the checked-in (empty) baseline.
REPRO_TILEPLAN_CACHE="$(mktemp -d)/tileplan_cache.json" \
PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" \
    python -m repro.analysis --all --baseline scripts/analysis_baseline.json

# Fused-epilogue gate: the (act_quant, fp8) pass must stay bitwise
# identical to the jitted unfused composition (activation, then the
# tilewise quantize kernel), for BOTH activation variants, and the fused
# grouped linear's value+grad must match the unfused pair exactly.
PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" python - <<'EOF'
import numpy as np, jax, jax.numpy as jnp
from repro.core.grouped_gemm import grouped_linear, grouped_linear_fused
from repro.kernels.epilogue_kernel import _act_f32, act_quantize_pallas
from repro.kernels.plan import KernelConfig
from repro.kernels.quant_kernel import quantize_tilewise_pallas

rng = np.random.default_rng(0)
g = jnp.asarray(rng.standard_normal((200, 256)), jnp.float32)
u = jnp.asarray(rng.standard_normal((200, 256)), jnp.float32)
for act, uu in (("silu_mul", u), ("gelu", None)):
    q8, s = act_quantize_pallas(g, uu, act=act, interpret=True)
    h = jax.jit(lambda *a: _act_f32(*a, act))(g, uu)
    q8c, sc = quantize_tilewise_pallas(h, interpret=True)
    assert np.array_equal(np.asarray(q8, np.float32),
                          np.asarray(q8c, np.float32)), act
    assert np.array_equal(np.asarray(s), np.asarray(sc)), act
    print(f"fused epilogue bitwise [{act}] OK")

gs = jnp.asarray([60, 0, 130], jnp.int32)
w = jnp.asarray(rng.standard_normal((3, 256, 128)), jnp.float32)
cfg = KernelConfig(backend="pallas_interpret", wgrad_precision="fp8")
lf, gf = jax.value_and_grad(lambda g, u, w: jnp.sum(
    grouped_linear_fused(g, u, w, gs, config=cfg) ** 2), (0, 1, 2))(g, u, w)
lu, gu = jax.value_and_grad(lambda g, u, w: jnp.sum(
    grouped_linear(_act_f32(g, u, "silu_mul"), w, gs, precision="fp8",
                   config=cfg) ** 2), (0, 1, 2))(g, u, w)
assert float(lf) == float(lu), (float(lf), float(lu))
for a, b, name in zip(gf, gu, ("dg", "du", "dw")):
    assert np.array_equal(np.asarray(a), np.asarray(b)), name
print("fused grouped linear value+grad parity OK")
EOF

# Tiny-M decode bench path must not rot either (cost-model selection —
# the CI gate exercises the CLI + decode pool, not kernel timing).
REPRO_TILEPLAN_CACHE="$(mktemp -d)/tileplan_cache.json" \
PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" \
    python -m benchmarks.bench_grouped_gemm --decode --smoke \
        --backend pallas_interpret

# Producer bench path: the fused gemm_quant CLI (autotune pool for the
# gemm_quant op family + the fused-vs-unfused comparison columns).
REPRO_TILEPLAN_CACHE="$(mktemp -d)/tileplan_cache.json" \
PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" \
    python -m benchmarks.bench_grouped_gemm --gemm-quant --smoke \
        --backend pallas_interpret

# Full pinned suite (smoke shapes) + regression diff against the
# committed snapshot.  --smoke row names are a strict subset of the full
# suite's, so bench_diff matches by name; the generous threshold makes
# this a rot gate across heterogeneous CI machines (every suite must
# still produce its measured rows, and none may be catastrophically
# slower) — same-machine perf trajectories use the default 10%.
BENCH_SMOKE_JSON="$(mktemp -d)/bench_smoke.json"
REPRO_TILEPLAN_CACHE="$(mktemp -d)/tileplan_cache.json" \
PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" \
    python -m benchmarks.run --smoke --json "$BENCH_SMOKE_JSON"
python scripts/bench_diff.py BENCH_2026-08-08.json "$BENCH_SMOKE_JSON" \
    --threshold 3.0
