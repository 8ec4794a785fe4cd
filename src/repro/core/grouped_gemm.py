"""Differentiable padding-free FP8 grouped GEMM — the paper's contribution
as a composable JAX module.

``grouped_linear(x, w, group_sizes)`` computes ``y[rows of group g] =
x[rows of g] @ w[g]`` over the *unpadded* concatenated token buffer.

Precision modes
  * ``fp8``  — forward:  x -> 1x128-tile fp8, w -> 128x128-block fp8,
               padding-free grouped GEMM kernel (paper);
               backward: dgrad in fp8 through the same kernel
               (dy quantized 1x128, w^T re-quantized 128x128),
               wgrad through the *wgrad registry*
               (``dispatch.grouped_gemm_wgrad``): bf16 operands by
               default (the DeepSeek-V3 recipe — wgrad highest
               precision), or fp8 operands with per-visit dequantization
               under ``wgrad_precision="fp8"`` (arXiv 2505.20524's
               all-fp8 step; ``dispatch.grouped_gemm_wgrad_fp8``).  All
               three GEMMs of the step consume ONE :class:`TilePlan`.
  * ``bf16`` — ragged_dot in bf16 both ways (numerics baseline; also the
               portable GSPMD path the multi-pod dry-run lowers); its
               wgrad routes through the same registry.

Quantize-once: a :class:`~repro.core.quantization.QuantizedActivation`
passed as ``quantized=`` replaces the forward's ``quantize_tilewise`` of
``x`` — several GEMMs sharing one activation buffer (the MoE gate/up
pair) amortize ONE quantization, and under ``wgrad_precision="fp8"`` the
VJP saves ``(a8, s_a)`` as residuals so the backward never re-quantizes
``x`` either.  The backward's single ``quantize_tilewise(dy)`` likewise
serves both the dgrad and the fp8 wgrad.

The group structure (``group_sizes``) is data-dependent and never padded —
that is the paper's whole point.
"""
from __future__ import annotations

import functools
import warnings
from typing import Any

import jax
import jax.numpy as jnp

from repro.analysis import events as _events
from repro.kernels import dispatch
from repro.kernels import ref as kref
from repro.kernels.plan import KernelConfig, TilePlan, make_tile_plan, \
    resolve_config
from repro.core import quantization as q
from repro.scopes import QUANT_ACT, QUANT_WEIGHTS, scope


def _quant_act(x, config):
    """Standalone 1x128 quantization of an activation, f32 cast included."""
    with scope(QUANT_ACT):
        return q.quantize_tilewise(x.astype(jnp.float32),
                                   backend=config.backend, config=config)


def _quant_weights(w, config, *, transpose=False):
    """128x128-block quantization of ``w`` [G, K, N] (of each group's
    transpose with ``transpose``), f32 upcast included.  A
    :class:`~repro.core.quantization.QuantizedWeight` is already that
    quantization and passes through."""
    if isinstance(w, q.QuantizedWeight):
        if transpose:
            raise ValueError(
                "a QuantizedWeight is a serving-only, forward-only weight: "
                "the backward needs the raw weight to quantize its "
                "transpose; differentiate through the raw params instead")
        return w.q, w.scale
    # one event per weight quantization traced — a served program that
    # consumes pre-quantized weights traces none
    _events.emit("quantize_blockwise", shape=tuple(w.shape))
    with scope(QUANT_WEIGHTS):
        if transpose:
            w = jnp.swapaxes(w, 1, 2)
        return q.quantize_blockwise_batched(w.astype(jnp.float32),
                                            backend=config.backend)


# ---------------------------------------------------------------------------
# bf16 ragged path (portable; GSPMD-partitionable)
# ---------------------------------------------------------------------------

def _ragged_dot(x, w, group_sizes, out_dtype):
    # the (gemm, bf16) operator of the unified registry — the bf16
    # baseline is a first-class registry citizen, not a side channel
    return dispatch.grouped_gemm_bf16(x, w, group_sizes,
                                      out_dtype=out_dtype,
                                      config=KernelConfig())


def _wgrad(x, dy, group_sizes, num_groups, *, config=None, plan=None):
    """dw[g] = x_g^T @ dy_g — ragged contracting dim, bf16 operands / f32
    accumulation, through the wgrad dispatch registry (the padding-free
    kernel where available; ``ragged_dot_general`` is the registry's
    ``xla_ragged`` fallback)."""
    return dispatch.grouped_gemm_wgrad(
        x.astype(jnp.bfloat16), dy.astype(jnp.bfloat16), group_sizes,
        num_groups=num_groups, config=config, out_dtype=jnp.float32,
        plan=plan)


# ---------------------------------------------------------------------------
# fp8 path with custom VJP
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _grouped_linear_fp8(x, w, group_sizes, plan, qa, config):
    y, _ = _fp8_fwd(x, w, group_sizes, plan, qa, config)
    return y


def _fp8_fwd(x, w, group_sizes, plan, qa, config):
    # quantize-once: a caller-supplied QuantizedActivation (the MoE layer
    # shares one across the gate/up GEMMs) replaces the tilewise quant of x
    if qa is None:
        a8, sa = _quant_act(x, config)
    else:
        a8, sa = qa.q, qa.scale
    b8, sb = _quant_weights(w, config)
    # plan-once/run-many: one TilePlan per group_sizes serves this forward
    # GEMM *and* the backward dgrad (the schedule depends only on M-side
    # raggedness, not on which weight it multiplies)
    if plan is None and dispatch.backend_uses_plan(config.backend):
        plan = make_tile_plan(group_sizes, x.shape[0],
                              block_m=config.block_m,
                              num_groups=w.shape[0])
    y = dispatch.grouped_gemm_fp8(a8, sa, b8, sb, group_sizes,
                                  config=config, plan=plan)
    if config.wgrad_precision == "fp8":
        # the residual IS the quantized activation: the backward's fp8
        # wgrad dequantizes per visit instead of re-quantizing x (and the
        # raw x can be freed — only a dtype stub is kept for the dx cast)
        x_raw, x_res = x[:0], (a8, sa)
    else:
        # DeepSeek recipe: wgrad contracts the highest-precision operand
        x_raw, x_res = x, None
    qa_marker = () if qa is not None else None     # structure-only flag
    return y, (x_raw, x_res, w, group_sizes, plan, qa_marker)


def _fp8_bwd(config, res, dy):
    x_raw, x_res, w, group_sizes, plan, qa_marker = res
    num_groups = w.shape[0]
    # dgrad: dx = dy @ w^T  (fp8 through the padding-free kernel, reusing
    # the forward's TilePlan — same group_sizes, same schedule).  This one
    # quantize_tilewise(dy) also feeds the fp8 wgrad below.
    d8, sd = _quant_act(dy, config)
    bt8, sbt = _quant_weights(w, config, transpose=True)   # [G, N, K]
    dx = dispatch.grouped_gemm_fp8(d8, sd, bt8, sbt, group_sizes,
                                   config=config.with_(out_dtype=jnp.float32),
                                   plan=plan)
    # wgrad through the registry, reusing the SAME TilePlan as the forward
    # and the dgrad above — the contraction schedule depends only on the
    # routing decision
    if config.wgrad_precision == "fp8":
        a8, sa = x_res
        dw = dispatch.grouped_gemm_wgrad_fp8(
            a8, sa, d8, sd, group_sizes, num_groups=num_groups,
            config=config, out_dtype=jnp.float32, plan=plan)
    else:
        dw = _wgrad(x_raw, dy, group_sizes, num_groups, config=config,
                    plan=plan)
    # zero cotangent for a supplied QuantizedActivation (its producer is
    # stop_gradient-ed; gradients to the activation flow through dx)
    dqa = None
    if qa_marker is not None:
        m, k = dy.shape[0], w.shape[1]
        kb = (k + q.QUANT_BLOCK - 1) // q.QUANT_BLOCK
        dqa = q.QuantizedActivation(
            jnp.zeros((m, k), jnp.float8_e4m3fn),
            jnp.zeros((m, kb), jnp.float32))
    return dx.astype(x_raw.dtype), dw.astype(w.dtype), None, None, dqa


_grouped_linear_fp8.defvjp(_fp8_fwd, _fp8_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _grouped_linear_bf16(x, w, group_sizes, out_dtype):
    y, _ = _bf16_fwd(x, w, group_sizes, out_dtype)
    return y


def _bf16_fwd(x, w, group_sizes, out_dtype):
    y = _ragged_dot(x.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
                    group_sizes, out_dtype)
    return y, (x, w, group_sizes)


def _bf16_bwd(out_dtype, res, dy):
    x, w, group_sizes = res
    wt = jnp.swapaxes(w, 1, 2)
    dx = _ragged_dot(dy.astype(jnp.bfloat16), wt.astype(jnp.bfloat16),
                     group_sizes, jnp.float32)
    # registry-routed wgrad.  The explicit default config keeps this path
    # auto-resolved (a pinned global backend must not turn the bf16
    # baseline's backward into a hard kernel requirement); arbitrary
    # model dims fall back to the tile-free xla_ragged entry
    dw = _wgrad(x, dy, group_sizes, w.shape[0], config=KernelConfig())
    return dx.astype(x.dtype), dw.astype(w.dtype), None


_grouped_linear_bf16.defvjp(_bf16_fwd, _bf16_bwd)


# ---------------------------------------------------------------------------
# fp8 path with FUSED activation epilogue (gate/up outputs in, no bf16 h)
# ---------------------------------------------------------------------------

def _act_recompute(g, u, act):
    """f32 activation as a VJP-able function of (g, u) — the same
    elementwise definition the fused kernel runs, so the backward's
    recompute matches the forward's quantization input exactly."""
    from repro.kernels.epilogue_kernel import _act_f32
    if u is None:
        return jax.vjp(lambda gg: _act_f32(gg, None, act), g)
    return jax.vjp(lambda gg, uu: _act_f32(gg, uu, act), g, u)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _grouped_linear_fp8_fused(g, u, w, group_sizes, plan, ctx):
    y, _ = _fused_fwd(g, u, w, group_sizes, plan, ctx)
    return y


def _fused_fwd(g, u, w, group_sizes, plan, ctx):
    config, act = ctx
    # ONE fused pass: activation + 1x128 quantization, the bf16
    # intermediate h never exists — the down GEMM consumes the
    # QuantizedActivation straight from the epilogue kernel
    qh = q.fused_act_quantize(g, u, act=act, backend=config.backend,
                              config=config)
    b8, sb = _quant_weights(w, config)
    if plan is None and dispatch.backend_uses_plan(config.backend):
        plan = make_tile_plan(group_sizes, g.shape[0],
                              block_m=config.block_m,
                              num_groups=w.shape[0])
    y = dispatch.grouped_gemm_fp8(qh.q, qh.scale, b8, sb, group_sizes,
                                  config=config, plan=plan)
    # (g, u) are the residuals for dsilu(g)*u / silu(g)*du — under
    # wgrad_precision="fp8" the quantized h additionally rides along so
    # the backward performs ZERO standalone quantizes of h
    h_res = (qh.q, qh.scale) if config.wgrad_precision == "fp8" else None
    return y, (g, u, h_res, w, group_sizes, plan)


def _fused_bwd(ctx, res, dy):
    config, act = ctx
    g, u, h_res, w, group_sizes, plan = res
    num_groups = w.shape[0]
    # one quantize_tilewise(dy) serves the dgrad AND the fp8 wgrad
    d8, sd = _quant_act(dy, config)
    bt8, sbt = _quant_weights(w, config, transpose=True)   # [G, N, K]
    dh = dispatch.grouped_gemm_fp8(d8, sd, bt8, sbt, group_sizes,
                                   config=config.with_(out_dtype=jnp.float32),
                                   plan=plan)
    # dsilu(g)·u / silu(g)·du from residuals: autodiff of the exact f32
    # activation the kernel fused (tail rows of dh are zero, so dg/du
    # keep the defined-zeros tail contract)
    h_f32, act_vjp = _act_recompute(g, u, act)
    if u is None:
        (dg,) = act_vjp(dh)
        du = None
    else:
        dg, du = act_vjp(dh)
    if config.wgrad_precision == "fp8":
        h8, sh = h_res
        dw = dispatch.grouped_gemm_wgrad_fp8(
            h8, sh, d8, sd, group_sizes, num_groups=num_groups,
            config=config, out_dtype=jnp.float32, plan=plan)
    else:
        # DeepSeek recipe: the wgrad contracts the recomputed h (bf16
        # operands, f32 accumulation) — recompute beats materializing
        dw = _wgrad(h_f32, dy, group_sizes, num_groups, config=config,
                    plan=plan)
    return (dg.astype(g.dtype), du if du is None else du.astype(u.dtype),
            dw.astype(w.dtype), None, None)


_grouped_linear_fp8_fused.defvjp(_fused_fwd, _fused_bwd)


# ---------------------------------------------------------------------------
# fp8 FFN with PRODUCER-side quantizing epilogues (gate/up emit fp8 directly)
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(7,))
def _grouped_linear_ffn_fp8(x, w_gate, w_up, w_down, group_sizes, plan, qa,
                            ctx):
    y, _ = _ffn_fwd(x, w_gate, w_up, w_down, group_sizes, plan, qa, ctx)
    return y


def _ffn_fwd(x, w_gate, w_up, w_down, group_sizes, plan, qa, ctx):
    config, act = ctx
    # quantize-once: ONE tilewise quant of x feeds the gate AND up GEMMs
    # (and, under wgrad_precision="fp8", both of their wgrads)
    if qa is None:
        a8, sa = _quant_act(x, config)
    else:
        a8, sa = qa.q, qa.scale
    num_groups = w_up.shape[0]
    if plan is None and dispatch.backend_uses_plan(config.backend):
        plan = make_tile_plan(group_sizes, x.shape[0],
                              block_m=config.block_m, num_groups=num_groups)
    # producer epilogue: the gate/up GEMMs round through the intermediate
    # dtype in-register and emit fp8 payload + 1x128 scales directly — the
    # bf16 g/u buffers never reach HBM, and the activation kernel
    # dequantizes them on load.  ``out_dtype`` here is the *rounding*
    # dtype, chosen to match what the unfused composition would have
    # stored (x.dtype), so fused-vs-unfused stays bitwise at this seam.
    idt = x.dtype
    bu8, sbu = _quant_weights(w_up, config)
    u8, su = dispatch.grouped_gemm_quant(a8, sa, bu8, sbu, group_sizes,
                                         num_groups=num_groups,
                                         config=config, out_dtype=idt,
                                         plan=plan)
    if w_gate is not None:
        bg8, sbg = _quant_weights(w_gate, config)
        g8, sg = dispatch.grouped_gemm_quant(a8, sa, bg8, sbg, group_sizes,
                                             num_groups=num_groups,
                                             config=config, out_dtype=idt,
                                             plan=plan)
        qh = q.fused_act_quantize_fp8(g8, sg, u8, su, act=act,
                                      backend=config.backend, config=config)
    else:
        # unary activation (gelu): w_up is the single projection
        g8 = sg = None
        qh = q.fused_act_quantize_fp8(u8, su, act=act,
                                      backend=config.backend, config=config)
    bd8, sbd = _quant_weights(w_down, config)
    y = dispatch.grouped_gemm_fp8(qh.q, qh.scale, bd8, sbd, group_sizes,
                                  config=config, plan=plan)
    if config.wgrad_precision == "fp8":
        # all-fp8 step: the quantized x and h ride along as residuals so
        # the backward performs zero re-quantizations of either
        x_raw, x_res = x[:0], (a8, sa)
        h_res = (qh.q, qh.scale)
    else:
        # DeepSeek recipe: raw x kept; h recomputed in f32 for the wgrad
        x_raw, x_res, h_res = x, None, None
    qa_marker = () if qa is not None else None     # structure-only flag
    return y, (x_raw, x_res, g8, sg, u8, su, h_res, w_gate, w_up, w_down,
               group_sizes, plan, qa_marker)


def _ffn_bwd(ctx, res, dy):
    config, act = ctx
    (x_raw, x_res, g8, sg, u8, su, h_res, w_gate, w_up, w_down,
     group_sizes, plan, qa_marker) = res
    num_groups = w_up.shape[0]
    f32cfg = config.with_(out_dtype=jnp.float32)
    # ONE quantize_tilewise(dy) serves the down dgrad AND its fp8 wgrad
    d8, sd = _quant_act(dy, config)
    wdt8, sdt = _quant_weights(w_down, config, transpose=True)
    dh = dispatch.grouped_gemm_fp8(d8, sd, wdt8, sdt, group_sizes,
                                   config=f32cfg, plan=plan)
    # recompute the activation from the fp8 producer residuals — the
    # dequantized payloads ARE the values the fused epilogue ran on, so
    # this recompute sees exactly the forward's activation inputs.  Tail
    # rows stay defined zeros: payload 0 / scale 1 dequantizes to 0.
    u_f32 = kref.dequantize_tilewise_ref(u8, su)
    if w_gate is not None:
        g_f32 = kref.dequantize_tilewise_ref(g8, sg)
        h_f32, act_vjp = _act_recompute(g_f32, u_f32, act)
        dg, du = act_vjp(dh)
    else:
        h_f32, act_vjp = _act_recompute(u_f32, None, act)
        (du,) = act_vjp(dh)
        dg = None
    # quantize dg/du ONCE each: the records serve the gate/up dgrads and,
    # under wgrad_precision="fp8", the matching wgrads.  Total standalone
    # quantize_tilewise calls for fwd+bwd: x, dy, dg, du — never h.
    du8, sdu = _quant_act(du, config)
    wut8, sut = _quant_weights(w_up, config, transpose=True)
    dx = dispatch.grouped_gemm_fp8(du8, sdu, wut8, sut, group_sizes,
                                   config=f32cfg, plan=plan)
    if w_gate is not None:
        dg8, sdg = _quant_act(dg, config)
        wgt8, sgt = _quant_weights(w_gate, config, transpose=True)
        dx = dx + dispatch.grouped_gemm_fp8(dg8, sdg, wgt8, sgt, group_sizes,
                                            config=f32cfg, plan=plan)
    if config.wgrad_precision == "fp8":
        a8, sa = x_res
        h8, sh = h_res
        dw_down = dispatch.grouped_gemm_wgrad_fp8(
            h8, sh, d8, sd, group_sizes, num_groups=num_groups,
            config=config, out_dtype=jnp.float32, plan=plan)
        dw_up = dispatch.grouped_gemm_wgrad_fp8(
            a8, sa, du8, sdu, group_sizes, num_groups=num_groups,
            config=config, out_dtype=jnp.float32, plan=plan)
        dw_gate = None if w_gate is None else dispatch.grouped_gemm_wgrad_fp8(
            a8, sa, dg8, sdg, group_sizes, num_groups=num_groups,
            config=config, out_dtype=jnp.float32, plan=plan)
    else:
        dw_down = _wgrad(h_f32, dy, group_sizes, num_groups, config=config,
                         plan=plan)
        dw_up = _wgrad(x_raw, du, group_sizes, num_groups, config=config,
                       plan=plan)
        dw_gate = None if w_gate is None else _wgrad(
            x_raw, dg, group_sizes, num_groups, config=config, plan=plan)
    dqa = None
    if qa_marker is not None:
        m, k = dy.shape[0], w_up.shape[1]
        kb = (k + q.QUANT_BLOCK - 1) // q.QUANT_BLOCK
        dqa = q.QuantizedActivation(
            jnp.zeros((m, k), jnp.float8_e4m3fn),
            jnp.zeros((m, kb), jnp.float32))
    return (dx.astype(x_raw.dtype),
            None if w_gate is None else dw_gate.astype(w_gate.dtype),
            dw_up.astype(w_up.dtype), dw_down.astype(w_down.dtype),
            None, None, dqa)


_grouped_linear_ffn_fp8.defvjp(_ffn_fwd, _ffn_bwd)


# ---------------------------------------------------------------------------
# Public API
# ---------------------------------------------------------------------------

def grouped_linear(x: jax.Array, w: jax.Array, group_sizes: jax.Array, *,
                   precision: str = "bf16", backend: str | None = None,
                   out_dtype: Any = None,
                   config: KernelConfig | None = None,
                   plan: TilePlan | None = None,
                   quantized: "q.QuantizedActivation | None" = None,
                   wgrad_precision: str | None = None) -> jax.Array:
    """Padding-free grouped linear: rows of ``x`` are grouped by
    ``group_sizes`` (concatenated, ragged); group g matmuls ``w[g]``.

    x: [M, K]; w: [G, K, N]; group_sizes: [G] with ``sum <= M``.  Rows
    beyond the last group (the unowned tail of a capacity buffer) come
    back as defined zeros on every backend — forward AND backward: the
    kernel's schedule sweeps the tail tiles and zero-fills them, and tail
    rows are excluded from the wgrad contraction.  Downstream gathers /
    scatter-adds (MoE combine, the take-VJP) are therefore safe without
    masking, though masking remains cheap and explicit.

    ``config`` carries tile shapes/backend (:class:`KernelConfig`);
    ``plan`` is an optional precomputed :class:`TilePlan` — pass the same
    plan to every grouped_linear sharing ``group_sizes`` (e.g. the
    gate/up/down GEMMs of one MoE application) so the schedule is built
    once per routing decision.  Without one, the fp8 path still builds a
    single plan per call and reuses it for the backward dgrad and wgrad.

    ``quantized`` (fp8 path only) is the quantize-once analogue of
    ``plan``: a :class:`~repro.core.quantization.QuantizedActivation`
    built from exactly this ``x`` (see
    :func:`~repro.core.quantization.quantize_activation`) replaces the
    forward's ``quantize_tilewise`` — pass the same record to every
    grouped_linear consuming the same activation buffer (the MoE gate/up
    pair).  It must be the quantization OF ``x``; a mismatched record
    gives silently wrong output.

    ``wgrad_precision`` (fp8 path only) picks the backward wgrad's
    operand precision: ``"bf16"`` (default — the DeepSeek recipe keeps
    the wgrad at the highest precision) or ``"fp8"`` (the all-fp8 step of
    arXiv 2505.20524: the VJP saves the quantized activation as its
    residual and the wgrad kernel dequantizes per visit).  Overrides the
    ``config``'s ``wgrad_precision`` field.
    """
    if precision == "fp8":
        # explicit out_dtype > config's pinned out_dtype > x.dtype
        cfg = resolve_config(config, backend=backend, out_dtype=out_dtype,
                             wgrad_precision=wgrad_precision)
        if cfg.out_dtype is None:
            cfg = cfg.with_(out_dtype=x.dtype)
        return _grouped_linear_fp8(x, w, group_sizes, plan, quantized, cfg)
    if precision == "bf16":
        if quantized is not None:
            warnings.warn(
                "grouped_linear(precision='bf16') ignores quantized=...: "
                "the bf16 path never quantizes; use precision='fp8' to "
                "consume a QuantizedActivation", stacklevel=2)
        # the kwarg AND a config-carried field both reach here — dropping
        # the config's wgrad_precision silently would be the same trap
        # the backend= kwarg warning exists for
        eff_wgrad = wgrad_precision if wgrad_precision is not None \
            else resolve_config(config).wgrad_precision
        if eff_wgrad == "fp8":
            warnings.warn(
                "grouped_linear(precision='bf16') ignores "
                "wgrad_precision='fp8': the fp8-operand wgrad needs the "
                "fp8 forward's quantized residual; use precision='fp8'",
                stacklevel=2)
        if backend is not None and backend != "auto":
            # the bf16 forward has exactly one implementation (ragged_dot)
            # — honouring this request is impossible, and dropping it
            # silently made callers think they were benchmarking a kernel
            warnings.warn(
                f"grouped_linear(precision='bf16') ignores "
                f"backend={backend!r}: the bf16 path always runs "
                "jax.lax.ragged_dot (its wgrad auto-resolves through the "
                "dispatch registry); use precision='fp8' to select a "
                "grouped-GEMM backend", stacklevel=2)
        # the bf16 path ignores tile shapes (ragged_dot), but a pinned
        # config out_dtype applies to every consumer, this one included
        cfg = resolve_config(config, out_dtype=out_dtype)
        return _grouped_linear_bf16(x, w, group_sizes,
                                    cfg.out_dtype or x.dtype)
    raise ValueError(f"unknown precision {precision!r}")


def _one_group(w):
    """A dense [K, N] weight (raw or a ``QuantizedWeight``) as the G=1
    grouped weight [1, K, N]."""
    return jax.tree.map(lambda a: a[None], w)


def dense_linear_fp8(x: jax.Array, w: jax.Array, *,
                     backend: str | None = None,
                     out_dtype: Any = None,
                     config: KernelConfig | None = None,
                     plan: TilePlan | None = None,
                     quantized: "q.QuantizedActivation | None" = None
                     ) -> jax.Array:
    """The G=1 degenerate case — DeepSeek-style fp8 linear for dense layers
    (optional beyond-paper feature for the dense architectures).

    ``out_dtype`` forwards like :func:`grouped_linear`'s (explicit kwarg >
    the ``config``'s pinned ``out_dtype`` > ``x.dtype``) instead of being
    silently dropped.  ``plan``/``quantized`` forward too, so several
    dense GEMMs sharing one input buffer (the MoE shared-expert gate/up
    pair) amortize one G=1 TilePlan and one quantization."""
    m = x.shape[0]
    gs = jnp.array([m], jnp.int32)
    return grouped_linear(x, _one_group(w), gs, precision="fp8",
                          backend=backend, out_dtype=out_dtype,
                          config=config, plan=plan, quantized=quantized)


def grouped_linear_fused(g: jax.Array, u: jax.Array | None,
                         w: jax.Array, group_sizes: jax.Array, *,
                         act: str = "silu_mul",
                         backend: str | None = None,
                         out_dtype: Any = None,
                         config: KernelConfig | None = None,
                         plan: TilePlan | None = None,
                         wgrad_precision: str | None = None) -> jax.Array:
    """Fused-epilogue fp8 grouped linear: ``y[rows of group g'] =
    act(g, u)[rows of g'] @ w[g']`` where ``act(g, u)`` is ``silu(g)*u``
    (SwiGLU; ``u`` required) or unary ``gelu(g)`` (``u=None``).

    The replacement for the unfused ``h = silu(g)*u;
    grouped_linear(h, ...)`` pair on the fp8 path: the activation and its
    1x128 quantization run as ONE ``(act_quant, fp8)`` pass, so the bf16
    ``h`` intermediate never touches HBM and the down GEMM consumes the
    :class:`~repro.core.quantization.QuantizedActivation` directly.

    The custom VJP computes ``dsilu(g)·u`` / ``silu(g)·du`` (or gelu')
    from the ``(g, u)`` residuals; the wgrad follows ``wgrad_precision``
    exactly like :func:`grouped_linear` — ``"fp8"`` reuses the fused
    pass's quantized h as the residual (zero standalone quantizes of h),
    ``"bf16"`` recomputes h in f32 for the highest-precision contraction.
    ``plan`` semantics match :func:`grouped_linear`: pass the routing
    decision's TilePlan so the schedule is built once.
    """
    from repro.kernels.epilogue_kernel import ACTIVATIONS
    if act not in ACTIVATIONS:
        raise ValueError(f"unknown activation {act!r}; "
                         f"expected one of {ACTIVATIONS}")
    if act == "silu_mul" and u is None:
        raise ValueError("act='silu_mul' needs both g and u")
    if act != "silu_mul" and u is not None:
        raise ValueError(f"act={act!r} is unary; got a second operand")
    cfg = resolve_config(config, backend=backend, out_dtype=out_dtype,
                         wgrad_precision=wgrad_precision)
    if cfg.out_dtype is None:
        cfg = cfg.with_(out_dtype=g.dtype)
    return _grouped_linear_fp8_fused(g, u, w, group_sizes, plan, (cfg, act))


def dense_linear_fp8_fused(g: jax.Array, u: jax.Array | None,
                           w: jax.Array, *, act: str = "silu_mul",
                           backend: str | None = None,
                           out_dtype: Any = None,
                           config: KernelConfig | None = None,
                           plan: TilePlan | None = None) -> jax.Array:
    """G=1 fused-epilogue fp8 linear for dense layers (the MLP down
    projection and the MoE shared-expert FFN).  Accepts arbitrary leading
    dims on ``g``/``u`` (flattened to rows like ``models.layers.linear``);
    ``plan`` is the same G=1 TilePlan the sibling gate/up GEMMs consumed.
    """
    lead, f = g.shape[:-1], g.shape[-1]
    g2 = g.reshape(-1, f)
    u2 = None if u is None else u.reshape(-1, f)
    gs = jnp.array([g2.shape[0]], jnp.int32)
    y = grouped_linear_fused(g2, u2, _one_group(w), gs, act=act,
                             backend=backend, out_dtype=out_dtype,
                             config=config, plan=plan)
    return y.reshape(*lead, w.shape[-1])


def grouped_linear_ffn(x: jax.Array, w_gate: jax.Array | None,
                       w_up: jax.Array, w_down: jax.Array,
                       group_sizes: jax.Array, *, act: str = "silu_mul",
                       backend: str | None = None,
                       out_dtype: Any = None,
                       config: KernelConfig | None = None,
                       plan: TilePlan | None = None,
                       quantized: "q.QuantizedActivation | None" = None,
                       wgrad_precision: str | None = None) -> jax.Array:
    """Whole fp8 expert FFN with producer-side quantizing epilogues:
    ``y = act(x @ w_gate, x @ w_up) @ w_down`` per group, where the
    gate/up GEMMs emit fp8 payload + 1x128 scales DIRECTLY from their
    store phase (``grouped_gemm_quant``) and the activation kernel
    dequantizes them on load.  Nothing wider than fp8 crosses HBM between
    the producer GEMMs and the down GEMM.

    ``w_gate``: [G, K, F] (or ``None`` for the unary ``gelu``, where
    ``w_up`` is the single projection); ``w_up``: [G, K, F]; ``w_down``:
    [G, F, N].  ``quantized`` is the quantize-once record of exactly this
    ``x``; ``plan``/``wgrad_precision`` follow :func:`grouped_linear`.

    Numerics: the kernel-level producer is bitwise identical to the
    unfused GEMM->quantize composition, but the *FFN* differs from the
    unfused recipe by one extra e4m3 quantization of g/u before the
    activation (the price of never materializing them wide) — expect a
    small tolerance delta vs :func:`grouped_linear_fused` pipelines, not
    equality.  Standalone quantize count: forward exactly one
    (``x``, skipped when ``quantized`` is given); forward+backward four
    (``x``, ``dy``, ``dg``, ``du``) — zero quantizes of g/u/h anywhere.
    """
    from repro.kernels.epilogue_kernel import ACTIVATIONS
    if act not in ACTIVATIONS:
        raise ValueError(f"unknown activation {act!r}; "
                         f"expected one of {ACTIVATIONS}")
    if act == "silu_mul" and w_gate is None:
        raise ValueError("act='silu_mul' needs both w_gate and w_up")
    if act != "silu_mul" and w_gate is not None:
        raise ValueError(f"act={act!r} is unary; pass the single projection "
                         "as w_up with w_gate=None")
    cfg = resolve_config(config, backend=backend, out_dtype=out_dtype,
                         wgrad_precision=wgrad_precision)
    if cfg.out_dtype is None:
        cfg = cfg.with_(out_dtype=x.dtype)
    return _grouped_linear_ffn_fp8(x, w_gate, w_up, w_down, group_sizes,
                                   plan, quantized, (cfg, act))


def dense_ffn_fp8(x: jax.Array, w_gate: jax.Array | None, w_up: jax.Array,
                  w_down: jax.Array, *, act: str = "silu_mul",
                  backend: str | None = None, out_dtype: Any = None,
                  config: KernelConfig | None = None,
                  plan: TilePlan | None = None,
                  quantized: "q.QuantizedActivation | None" = None
                  ) -> jax.Array:
    """G=1 producer-fused fp8 FFN for dense layers (the MoE shared expert
    and the dense MLP).  Accepts arbitrary leading dims on ``x``
    (flattened to rows like ``models.layers.linear``); ``plan`` is the
    same G=1 TilePlan the caller built for the token buffer."""
    lead, k = x.shape[:-1], x.shape[-1]
    x2 = x.reshape(-1, k)
    gs = jnp.array([x2.shape[0]], jnp.int32)
    y = grouped_linear_ffn(
        x2, None if w_gate is None else _one_group(w_gate), _one_group(w_up),
        _one_group(w_down), gs, act=act, backend=backend, out_dtype=out_dtype,
        config=config, plan=plan, quantized=quantized)
    return y.reshape(*lead, w_down.shape[-1])


# ---------------------------------------------------------------------------
# Kernel contracts (repro.analysis layer 1)
# ---------------------------------------------------------------------------
# Declarative invariants for every public fp8 path in this module, checked
# by ``python -m repro.analysis --contracts`` (and tests/test_analysis.py)
# via abstract tracing — the replacement for the monkeypatch-count CI
# gates.  Builders are deferred: registration costs nothing at import.

from repro.analysis.contracts import register_contract as _register_contract


def _contract_operands():
    """Shared example problem: G=3 with an empty group and a ragged tail
    (sum(gs)=190 < M=256) — the shapes every padding-free claim is about."""
    import numpy as _np
    rng = _np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((256, 128)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((3, 128, 128)), jnp.float32)
    gu = jnp.asarray(rng.standard_normal((2, 256, 256)), jnp.float32)
    wd = jnp.asarray(rng.standard_normal((3, 256, 128)), jnp.float32)
    gs = jnp.asarray([60, 0, 130], jnp.int32)
    return x, w, gu, wd, gs


def _build_linear_fwd():
    x, w, _, _, gs = _contract_operands()
    cfg = KernelConfig(backend="pallas_interpret")
    return (lambda x, w: grouped_linear(x, w, gs, precision="fp8",
                                        config=cfg)), (x, w)


def _build_linear_grad():
    x, w, _, _, gs = _contract_operands()
    cfg = KernelConfig(backend="pallas_interpret")

    def loss(x, w):
        y = grouped_linear(x, w, gs, precision="fp8", config=cfg)
        return jnp.sum(y.astype(jnp.float32) ** 2)
    return jax.grad(loss, argnums=(0, 1)), (x, w)


def _build_fused_fwd():
    _, _, gu, wd, gs = _contract_operands()
    cfg = KernelConfig(backend="pallas_interpret")
    return (lambda g, u: grouped_linear_fused(g, u, wd, gs, act="silu_mul",
                                              config=cfg)), (gu[0], gu[1])


def _build_fused_grad():
    _, _, gu, wd, gs = _contract_operands()
    cfg = KernelConfig(backend="pallas_interpret")

    def loss(g, u, w):
        y = grouped_linear_fused(g, u, w, gs, act="silu_mul", config=cfg)
        return jnp.sum(y.astype(jnp.float32) ** 2)
    return jax.grad(loss, argnums=(0, 1, 2)), (gu[0], gu[1], wd)


def _build_ffn_fwd():
    x, _, _, _, gs = _contract_operands()
    import numpy as _np
    rng = _np.random.default_rng(1)
    wg = jnp.asarray(rng.standard_normal((3, 128, 256)), jnp.float32)
    wu = jnp.asarray(rng.standard_normal((3, 128, 256)), jnp.float32)
    wd = jnp.asarray(rng.standard_normal((3, 256, 128)), jnp.float32)
    cfg = KernelConfig(backend="pallas_interpret")
    return (lambda x: grouped_linear_ffn(x, wg, wu, wd, gs, act="silu_mul",
                                         config=cfg)), (x,)


def _build_ffn_grad():
    x, _, _, _, gs = _contract_operands()
    import numpy as _np
    rng = _np.random.default_rng(1)
    wg = jnp.asarray(rng.standard_normal((3, 128, 256)), jnp.float32)
    wu = jnp.asarray(rng.standard_normal((3, 128, 256)), jnp.float32)
    wd = jnp.asarray(rng.standard_normal((3, 256, 128)), jnp.float32)
    cfg = KernelConfig(backend="pallas_interpret", wgrad_precision="fp8")

    def loss(x, wg_, wu_, wd_):
        y = grouped_linear_ffn(x, wg_, wu_, wd_, gs, act="silu_mul",
                               config=cfg)
        return jnp.sum(y.astype(jnp.float32) ** 2)
    return jax.grad(loss, argnums=(0, 1, 2, 3)), (x, wg, wu, wd)


_register_contract(
    "grouped_linear.fp8.fwd",
    description="fp8 forward: ONE standalone quantize (x), one plan "
                "build, zero padding primitives",
    build=_build_linear_fwd,
    quantize_count=1, quantize_shapes=((256, 128),),
    plan_builds=1, forbid_padding=True)

_register_contract(
    "grouped_linear.fp8.grad",
    description="fp8 fwd+bwd: quantizes exactly {x, dy}; the forward's "
                "TilePlan serves the dgrad and wgrad (one build total)",
    build=_build_linear_grad,
    quantize_count=2, quantize_shapes=((256, 128), (256, 128)),
    plan_builds=1, forbid_padding=True)

_register_contract(
    "grouped_linear_fused.fp8.fwd",
    description="fused epilogue forward: ZERO standalone quantizes (the "
                "act_quant pass owns h), no wide h materialization",
    build=_build_fused_fwd,
    quantize_count=0, plan_builds=1, forbid_padding=True,
    forbid_wide_shapes=((256, 256),))

_register_contract(
    "grouped_linear_fused.fp8.grad",
    description="fused epilogue fwd+bwd: quantizes exactly {dy}; one "
                "plan build serves forward, dgrad, and wgrad",
    build=_build_fused_grad,
    quantize_count=1, quantize_shapes=((256, 128),),
    plan_builds=1, forbid_padding=True)

_register_contract(
    "grouped_linear_ffn.fp8.fwd",
    description="producer-fused FFN forward: ONE standalone quantize "
                "(x), gate/up through grouped_gemm_quant, g/u/h never "
                "wider than fp8",
    build=_build_ffn_fwd,
    quantize_count=1, quantize_shapes=((256, 128),),
    plan_builds=1, gemm_quant_calls=2, forbid_padding=True,
    forbid_wide_shapes=((256, 256),))

_register_contract(
    "grouped_linear_ffn.fp8.grad",
    description="producer-fused FFN fwd+bwd (all-fp8 wgrad): quantizes "
                "exactly {x, dy, dg, du} — never g/u/h",
    build=_build_ffn_grad,
    quantize_count=4,
    quantize_shapes=((256, 128), (256, 128), (256, 256), (256, 256)),
    plan_builds=1, gemm_quant_calls=2, forbid_padding=True)


# ---------------------------------------------------------------------------
# Compile contracts (repro.analysis layer 5: REPRO-T01)
# ---------------------------------------------------------------------------
# Shape-stable repeat calls must hit the jit cache: three steps with
# DIFFERENT routings (new group_sizes values, same shapes) may trace the
# step function exactly once.  group_sizes rides as a traced operand —
# retracing here would mean every MoE routing decision recompiles the
# layer, the failure mode the TilePlan's value-independent schedule
# exists to avoid.

from repro.analysis.retrace import \
    register_compile_contract as _register_compile_contract


def _build_linear_retrace():
    x, w, _, _, _ = _contract_operands()
    cfg = KernelConfig(backend="pallas_interpret")

    def linear_step(x, w, gs):
        y = grouped_linear(x, w, gs, precision="fp8", config=cfg)
        return jnp.sum(y.astype(jnp.float32) ** 2)

    fn = jax.jit(jax.value_and_grad(linear_step, argnums=(0, 1)))
    routings = ([60, 0, 130], [100, 50, 40], [0, 0, 256])
    calls = [(x, w, jnp.asarray(r, jnp.int32)) for r in routings]
    return fn, calls


def _build_ffn_retrace():
    x, _, _, _, _ = _contract_operands()
    import numpy as _np
    rng = _np.random.default_rng(1)
    wg = jnp.asarray(rng.standard_normal((3, 128, 256)), jnp.float32)
    wu = jnp.asarray(rng.standard_normal((3, 128, 256)), jnp.float32)
    wd = jnp.asarray(rng.standard_normal((3, 256, 128)), jnp.float32)
    cfg = KernelConfig(backend="pallas_interpret", wgrad_precision="fp8")

    def ffn_step(x, wg_, wu_, wd_, gs):
        y = grouped_linear_ffn(x, wg_, wu_, wd_, gs, act="silu_mul",
                               config=cfg)
        return jnp.sum(y.astype(jnp.float32) ** 2)

    fn = jax.jit(jax.value_and_grad(ffn_step, argnums=(0, 1, 2, 3)))
    routings = ([60, 0, 130], [100, 50, 40], [256, 0, 0])
    calls = [(x, wg, wu, wd, jnp.asarray(r, jnp.int32))
             for r in routings]
    return fn, calls


_register_compile_contract(
    "grouped_linear.fp8.retrace",
    description="fp8 fwd+bwd step compiles ONCE across three routing "
                "changes of the same shape",
    build=_build_linear_retrace,
    expected={"linear_step": 1}, rule="REPRO-T01")

_register_compile_contract(
    "grouped_linear_ffn.fp8.retrace",
    description="producer-fused FFN fwd+bwd step (all-fp8 wgrad) "
                "compiles ONCE across three routing changes of the same "
                "shape",
    build=_build_ffn_retrace,
    expected={"ffn_step": 1}, rule="REPRO-T01")
