"""Padding-free Mixture-of-Experts layer built on the grouped GEMM.

This is the paper's target workload: top-k routing produces *dynamic* group
sizes per expert; the expert FFNs run as one padding-free fp8 grouped GEMM
over the concatenated, ragged token buffer.

Distribution (DESIGN.md §4): the layer runs inside ``shard_map`` over the
``model`` mesh axis with tokens replicated on that axis.

  * **EP mode** (``num_experts % ep_size == 0``): each shard owns
    ``E/ep_size`` experts, packs only the rows routed to its local experts
    into a static *capacity* buffer (ragged inside — the grouped GEMM never
    pads group-to-group), and contributes a partial output; one ``psum``
    over the axis combines routed + shared-expert partials.
  * **TP mode** (fallback, e.g. qwen2-moe's 60 experts on a 16-way axis):
    experts replicated, every weight's ``d_ff`` dim sharded; all rows are
    processed on every shard against its ``d_ff`` slice; same single
    ``psum``.

Routing is computed redundantly on each shard (router weights are tiny);
this avoids a second collective.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.core.grouped_gemm import (dense_ffn_fp8, dense_linear_fp8,
                                     dense_linear_fp8_fused, grouped_linear,
                                     grouped_linear_ffn, grouped_linear_fused)
from repro.core.quantization import quantize_activation, quantize_weight
from repro.kernels import dispatch
from repro.kernels.plan import KernelConfig, make_tile_plan, resolve_config
from repro.scopes import (MOE_COMBINE, MOE_EXPERTS, MOE_PACK, MOE_ROUTE,
                          MOE_SHARED, QUANT_ACT, scope)


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    num_experts: int
    top_k: int
    d_model: int
    d_ff_expert: int
    num_shared_experts: int = 0
    norm_topk_prob: bool = False
    capacity_factor: float = 2.0
    precision: str = "bf16"           # "bf16" | "fp8"
    # grouped-GEMM backend (repro.kernels.dispatch registry name, e.g.
    # "pallas" / "pallas_interpret" / "xla_ragged"; None == "auto")
    backend: Optional[str] = None
    # tile shapes etc. for the expert GEMMs; None -> installed/per-device
    # default (``backend`` above overrides the config's backend field).
    # ``kernel_config.wgrad_precision="fp8"`` opts the expert GEMMs'
    # backward into the all-fp8 wgrad (bf16 stays the default recipe)
    kernel_config: Optional[KernelConfig] = None
    router_dtype: Any = jnp.float32
    # expert-compute dispatch:
    #   "ragged" — padding-free grouped GEMM (the paper; on TPU this is the
    #              Pallas kernel, on other backends jax.lax.ragged_dot —
    #              NOTE: XLA's ragged_dot lowering one-hot-expands the LHS
    #              to [rows, G_local*K], a G_local x flop/memory blow-up)
    #   "dense"  — GShard-style per-expert capacity buckets + batched
    #              einsum (the padding regime the paper eliminates; on the
    #              XLA path it avoids the expansion artifact)
    dispatch: str = "ragged"
    # dtype of the cross-shard expert-output reduction (§Perf I3):
    # bf16 halves psum wire bytes; partial sums are few-term adds
    reduce_dtype: Any = jnp.float32


def ep_size_for(cfg: MoEConfig, model_axis_size: int) -> int:
    """EP when experts divide the axis, else TP-on-d_ff (DESIGN.md §4)."""
    if model_axis_size > 1 and cfg.num_experts % model_axis_size == 0:
        return model_axis_size
    return 1


def init_moe_params(key, cfg: MoEConfig, dtype=jnp.float32):
    d, f, e = cfg.d_model, cfg.d_ff_expert, cfg.num_experts
    # 7 splits: every param draws from its own subkey — reusing the parent
    # ``key`` for shared_down correlated its init with the subkey stream
    ks = jax.random.split(key, 7)
    scale_in = d ** -0.5
    scale_mid = f ** -0.5
    p = {
        "router": jax.random.normal(ks[0], (d, e), jnp.float32) * scale_in,
        "w_gate": jax.random.normal(ks[1], (e, d, f), dtype) * scale_in,
        "w_up": jax.random.normal(ks[2], (e, d, f), dtype) * scale_in,
        "w_down": jax.random.normal(ks[3], (e, f, d), dtype) * scale_mid,
    }
    if cfg.num_shared_experts:
        fs = f * cfg.num_shared_experts
        p["shared_gate"] = jax.random.normal(ks[4], (d, fs), dtype) * scale_in
        p["shared_up"] = jax.random.normal(ks[5], (d, fs), dtype) * scale_in
        p["shared_down"] = (jax.random.normal(ks[6], (fs, d), dtype)
                            * fs ** -0.5)
    return p


def _capacity(num_slots: int, ep_size: int, cf: float,
              align: int = 128) -> int:
    """Static EP capacity, rounded up to the active tile height so the
    packed buffer stays an integral number of kernel M-tiles (``align`` =
    ``KernelConfig.block_m``; non-default tile shapes would otherwise
    silently mis-bucket capacity).

    The clamp is the aligned *ceiling* of ``num_slots``, not ``num_slots``
    itself — ``min(num_slots, ...)`` used to return an unaligned capacity
    whenever ``num_slots`` wasn't tile-aligned, breaking this docstring's
    invariant and splitting autotune cache keys across M buckets.  The
    capacity may therefore exceed ``num_slots`` by up to ``align - 1``
    dead rows; the packed buffer's tail rows beyond ``sum(group_sizes)``
    are defined zeros on every kernel path, so the slack is harmless.
    TP mode (``ep_size == 1``) keeps the exact ``num_slots`` buffer: every
    slot is real, nothing is clamped, and the kernel handles ragged M."""
    if ep_size == 1:
        return num_slots
    cap_all = -(-num_slots // align) * align      # aligned ceiling
    c = -(-int(num_slots / ep_size * cf) // align) * align
    return min(cap_all, max(c, align))


def _shared_fp8(cfg: MoEConfig, d: int, fs: int) -> bool:
    """Whether the shared-expert FFN of width ``fs`` over ``d`` takes the
    fp8 G=1 path: both widths must tile into 128x128 weight blocks."""
    return cfg.precision == "fp8" and d % 128 == 0 and fs % 128 == 0


def moe_apply(params, x, cfg: MoEConfig, *, ep_rank=0, ep_size: int = 1,
              axis_name: Optional[str] = None):
    """x: [T, d_model] (tokens local to this shard's data slice, replicated
    over the model axis).  Returns (y [T, d_model], aux dict).

    When ``axis_name`` is given the caller is inside shard_map and the
    params carry this shard's slice (experts sliced in EP mode, d_ff sliced
    in TP mode); output is psum'd over the axis.
    """
    t, d = x.shape
    e, k = cfg.num_experts, cfg.top_k
    e_loc = e // ep_size
    lo = ep_rank * e_loc
    kcfg = resolve_config(cfg.kernel_config, backend=cfg.backend)

    # ---- routing (replicated) ------------------------------------------
    with scope(MOE_ROUTE):
        logits = x.astype(cfg.router_dtype) @ params["router"].astype(
            cfg.router_dtype)
        probs = jax.nn.softmax(logits, axis=-1)
        weights, ids = jax.lax.top_k(probs, k)              # [T, k]
        if cfg.norm_topk_prob:
            weights = weights / jnp.sum(weights, -1, keepdims=True)

    # ---- pack rows routed to local experts into the capacity buffer ----
    num_slots = t * k
    cap = _capacity(num_slots, ep_size, cfg.capacity_factor,
                    align=kcfg.block_m)
    with scope(MOE_PACK):
        flat_ids = ids.reshape(-1)                          # [T*k]
        local_id = flat_ids - lo
        is_local = (local_id >= 0) & (local_id < e_loc)
        sort_key = jnp.where(is_local, local_id, e_loc)     # dead rows last
        order = jnp.argsort(sort_key)                       # stable
        if cap > num_slots:
            # tile-aligned capacity can exceed the slot count by <
            # block_m; replicate the last slot into the padding rows.  The
            # replica may duplicate a REAL token's row — that is safe only
            # because those rows sit beyond sum(group_sizes): every kernel
            # path zero-fills them forward and backward, and the combine's
            # `valid` mask below excludes them — do not weaken either of
            # those invariants
            order = jnp.pad(order, (0, cap - num_slots), mode="edge")
        sel = order[:cap]                                   # packed slots

        gs_full = jnp.bincount(jnp.where(is_local, local_id, e_loc),
                               length=e_loc + 1)[:e_loc]
        # clip group sizes to the capacity prefix (drops bias to high ids)
        starts = jnp.concatenate([jnp.zeros(1, gs_full.dtype),
                                  jnp.cumsum(gs_full)[:-1]])
        gs = jnp.clip(jnp.minimum(gs_full, cap - starts), 0)
        total = jnp.sum(gs)

        token_of = sel // k
        xs = jnp.take(x, token_of, axis=0)                  # [cap, d]

    if cfg.dispatch == "dense":
        # GShard-style capacity buckets: [E_loc, cap_e, d] batched einsum.
        # Ceil of the float-scaled per-expert capacity, like _capacity —
        # int() truncation would turn capacity_factor=1.5 into 1x and
        # silently drop tokens the ragged path keeps
        cap_e = max(-(-int(num_slots * cfg.capacity_factor) // e), 1)
        cap_e = (cap_e + 7) // 8 * 8
        with scope(MOE_EXPERTS):
            ends = jnp.cumsum(gs)
            row = jnp.arange(cap)
            gid = jnp.searchsorted(ends, row, side="right")
            gid = jnp.minimum(gid, e_loc - 1)
            pos = row - jnp.concatenate([jnp.zeros(1, ends.dtype),
                                         ends[:-1]])[gid]
            keep = (row < jnp.sum(gs)) & (pos < cap_e)
            xe = jnp.zeros((e_loc, cap_e, d), x.dtype).at[
                jnp.where(keep, gid, e_loc - 1),
                jnp.where(keep, pos, cap_e - 1)].set(
                jnp.where(keep[:, None], xs, 0), mode="drop")
            ge = jnp.einsum("ecd,edf->ecf", xe, params["w_gate"])
            ue = jnp.einsum("ecd,edf->ecf", xe, params["w_up"])
            he = jax.nn.silu(ge) * ue                   # bf16 act (§Perf I5)
            ye = jnp.einsum("ecf,efd->ecd", he, params["w_down"])
            y = jnp.where(keep[:, None],
                          ye[gid, jnp.minimum(pos, cap_e - 1)], 0.0)
    else:
        # ---- padding-free ragged expert FFN (the paper's kernel) -------
        # Plan once per routing decision: the gate/up/down GEMMs (and the
        # backward dgrads inside the custom VJP) all share this routing's
        # group_sizes, so one TilePlan serves all of them — the paper's
        # configure-once/select-cheaply descriptor pool, at the layer
        # level.  The XLA backends don't consume plans; skip the build.
        tile_plan = None
        qx = None
        if cfg.precision == "fp8":
            if dispatch.backend_uses_plan(kcfg.backend):
                with scope(MOE_PACK):
                    tile_plan = make_tile_plan(gs, cap, block_m=kcfg.block_m,
                                               num_groups=e_loc)
            # quantize once per routing decision, like the plan: ONE
            # 1x128 tilewise quantization of the packed buffer serves the
            # gate AND up GEMMs (and, under wgrad_precision="fp8", their
            # backward wgrads via the VJP residual) — previously each
            # GEMM re-quantized the same xs.  Passing the layer config
            # batches the quantizer's grid to THIS phase's tile height
            # (kcfg.block_m — e.g. the engine's decode config shrinks it
            # to the tiny decode buffer); a quantize-specific tuned
            # height would come from autotune(op="quantize") and can be
            # passed here instead — the record's values are tile-height
            # independent either way, only wall time moves.
            with scope(MOE_EXPERTS), scope(QUANT_ACT):
                qx = quantize_activation(xs, backend=kcfg.backend,
                                         config=kcfg)
        with scope(MOE_EXPERTS):
            if cfg.precision == "fp8" and kcfg.fuse_producer:
                # producer-fused FFN: the gate/up GEMMs emit fp8 + 1x128
                # scales straight from their store phase
                # (grouped_gemm_quant) and the activation dequantizes them
                # on load — g/u never exist in bf16 anywhere, and the
                # whole expert FFN performs exactly ONE standalone
                # quantize (the qx above).  Numerics differ from the
                # unfused recipe by one extra e4m3 rounding of g/u (see
                # grouped_linear_ffn's docstring).
                y = grouped_linear_ffn(xs, params["w_gate"], params["w_up"],
                                       params["w_down"], gs, act="silu_mul",
                                       config=kcfg, plan=tile_plan,
                                       quantized=qx)         # [cap, d]
            else:
                glin = functools.partial(grouped_linear,
                                         precision=cfg.precision,
                                         config=kcfg, plan=tile_plan)
                g = glin(xs, params["w_gate"], gs, quantized=qx)  # [cap, f]
                u = glin(xs, params["w_up"], gs, quantized=qx)
                if cfg.precision == "fp8":
                    # fused epilogue: silu(g)*u + 1x128 quantization in
                    # one (act_quant, fp8) pass — the bf16 h intermediate
                    # never touches HBM and the down GEMM consumes the
                    # QuantizedActivation directly (zero standalone
                    # quantizes of h, forward and backward)
                    y = grouped_linear_fused(g, u, params["w_down"], gs,
                                             act="silu_mul", config=kcfg,
                                             plan=tile_plan)  # [cap, d]
                else:
                    h = jax.nn.silu(g) * u                  # bf16 act (I5)
                    y = glin(h, params["w_down"], gs)       # [cap, d]

    # ---- combine (rows beyond `total` are defined zeros on the kernel
    # path, but hard-masking stays: it is cheap, explicit, and covers the
    # dense-dispatch branch too) ----------------------------------------
    with scope(MOE_COMBINE):
        valid = jnp.arange(cap) < total
        w_flat = jnp.take(weights.reshape(-1), sel)
        contrib = jnp.where(valid[:, None],
                            y.astype(jnp.float32) * w_flat[:, None], 0.0)
        out = jnp.zeros((t, d), jnp.float32).at[token_of].add(
            contrib, mode="drop")

    # ---- shared experts (TP over the axis in both modes) ---------------
    if cfg.num_shared_experts:
        fs = params["shared_gate"].shape[1]
        with scope(MOE_SHARED):
            if _shared_fp8(cfg, d, fs):
                # BUGFIX: this FFN used to run bf16 ``@`` regardless of
                # cfg.precision — the shared experts now follow the
                # layer's precision through dense_linear_fp8 and finish
                # with the same fused silu·mul->quantize epilogue as the
                # routed experts.  Plan-once + quantize-once, like the
                # routed path: ONE G=1 TilePlan and ONE quantization of x
                # serve all three GEMMs.
                splan = None
                if dispatch.backend_uses_plan(kcfg.backend):
                    splan = make_tile_plan(jnp.array([t], jnp.int32), t,
                                           block_m=kcfg.block_m,
                                           num_groups=1)
                with scope(QUANT_ACT):
                    qs = quantize_activation(x, backend=kcfg.backend,
                                             config=kcfg)
                if kcfg.fuse_producer:
                    # producer-fused shared-expert FFN — same seam as the
                    # routed experts: gate/up emit fp8 directly, one
                    # standalone quantize (qs) for the whole FFN
                    out = out + dense_ffn_fp8(
                        x, params["shared_gate"], params["shared_up"],
                        params["shared_down"], act="silu_mul", config=kcfg,
                        out_dtype=jnp.float32, plan=splan, quantized=qs)
                else:
                    sg = dense_linear_fp8(x, params["shared_gate"],
                                          config=kcfg, plan=splan,
                                          quantized=qs)
                    su = dense_linear_fp8(x, params["shared_up"],
                                          config=kcfg, plan=splan,
                                          quantized=qs)
                    out = out + dense_linear_fp8_fused(
                        sg, su, params["shared_down"], act="silu_mul",
                        config=kcfg, out_dtype=jnp.float32, plan=splan)
            else:
                sg = x @ params["shared_gate"]
                su = x @ params["shared_up"]
                sh = jax.nn.silu(sg) * su                   # bf16 act (I5)
                out = out + (sh @ params["shared_down"]).astype(jnp.float32)

    if axis_name is not None:
        with scope(MOE_COMBINE):
            out = jax.lax.psum(out.astype(cfg.reduce_dtype), axis_name) \
                .astype(jnp.float32)

    # ---- aux: load-balance loss + drop stats (replicated math) ---------
    with scope(MOE_ROUTE):
        me = jnp.mean(probs, axis=0)
        ce = jnp.mean(jax.nn.one_hot(ids, e,
                                     dtype=jnp.float32).sum(1), axis=0)
        if axis_name is not None and ep_size > 1:
            kept = jax.lax.psum(total, axis_name)  # disjoint experts
        else:
            kept = total                           # TP/local: all local
        aux = {
            "load_balance_loss": e * jnp.sum(me * ce) / k,
            "dropped_fraction": 1.0 - kept / num_slots,
        }
    with scope(MOE_COMBINE):
        return out.astype(x.dtype), aux


def quantize_serving_weights(params, cfg: MoEConfig):
    """The MoE params with every weight that ``moe_apply`` quantizes to
    fp8 replaced by its :class:`~repro.core.quantization.QuantizedWeight`
    — the same values the layer would compute from the raw weight, so the
    served programs skip the quantization and hold fp8 weights only.

    The routed experts on the fp8 ragged path, and the shared experts
    where ``moe_apply`` takes their fp8 path; the tree is returned
    unchanged when ``cfg.precision`` is not fp8.  Leading (stacked-layer)
    axes are kept.  Forward only: gradients need the raw weights."""
    if cfg.precision != "fp8":
        return params
    backend = resolve_config(cfg.kernel_config, backend=cfg.backend).backend
    names = []
    if cfg.dispatch != "dense":          # the dense dispatch runs bf16
        names += ["w_gate", "w_up", "w_down"]
    if cfg.num_shared_experts and _shared_fp8(
            cfg, *params["shared_gate"].shape[-2:]):
        names += ["shared_gate", "shared_up", "shared_down"]
    out = dict(params)
    for name in names:
        out[name] = quantize_weight(params[name], backend=backend)
    return out


def shard_moe_params(params, cfg: MoEConfig, ep_size: int):
    """PartitionSpec tree for the params under shard_map over `model`."""
    from jax.sharding import PartitionSpec as P
    if ep_size > 1:
        spec = {"router": P(), "w_gate": P("model"), "w_up": P("model"),
                "w_down": P("model")}
    else:
        spec = {"router": P(), "w_gate": P(None, None, "model"),
                "w_up": P(None, None, "model"),
                "w_down": P(None, "model", None)}
    if cfg.num_shared_experts:
        spec.update({"shared_gate": P(None, "model"),
                     "shared_up": P(None, "model"),
                     "shared_down": P("model", None)})
    return spec


# ---------------------------------------------------------------------------
# Kernel contracts (repro.analysis layer 1)
# ---------------------------------------------------------------------------
# The MoE-layer invariants the ci_tier1.sh count gates used to pin with
# monkeypatched counters: quantize-once (4 standalone quantizes per
# fwd+bwd, two of them xs-shaped), producer-fusion (forward = exactly the
# shared xs, gate/up through grouped_gemm_quant), and plan-once (one
# schedule build per routing decision).  cap = _capacity(32*top_k, 1, cf)
# = 64 for this example config (TP mode keeps the exact slot count).

from repro.analysis.contracts import register_contract as _register_contract


def _contract_cfg(fuse_producer=False):
    return MoEConfig(num_experts=4, top_k=2, d_model=128, d_ff_expert=256,
                     precision="fp8", backend="pallas_interpret",
                     kernel_config=KernelConfig(wgrad_precision="fp8",
                                                fuse_producer=fuse_producer))


def _contract_inputs(cfg):
    params = init_moe_params(jax.random.PRNGKey(0), cfg)
    xt = jax.random.normal(jax.random.PRNGKey(1), (32, cfg.d_model))
    return params, xt


def _build_moe_fwd():
    cfg = _contract_cfg()
    params, xt = _contract_inputs(cfg)
    return (lambda p, x: moe_apply(p, x, cfg)[0]), (params, xt)


def _build_moe_grad():
    cfg = _contract_cfg()
    params, xt = _contract_inputs(cfg)

    def loss(p, x):
        return jnp.mean(moe_apply(p, x, cfg)[0].astype(jnp.float32) ** 2)
    return jax.grad(loss, argnums=(0, 1)), (params, xt)


def _build_moe_fused_fwd():
    cfg = _contract_cfg(fuse_producer=True)
    params, xt = _contract_inputs(cfg)
    return (lambda p, x: moe_apply(p, x, cfg)[0]), (params, xt)


def _build_moe_fused_grad():
    cfg = _contract_cfg(fuse_producer=True)
    params, xt = _contract_inputs(cfg)

    def loss(p, x):
        return jnp.mean(moe_apply(p, x, cfg)[0].astype(jnp.float32) ** 2)
    return jax.grad(loss, argnums=(0, 1)), (params, xt)


_register_contract(
    "moe_apply.fp8.fwd",
    description="MoE forward: ONE standalone quantize of the packed xs "
                "serves the gate AND up GEMMs; one plan build per "
                "routing decision; no padding of the token buffer",
    build=_build_moe_fwd,
    quantize_count=1, quantize_shapes=((64, 128),),
    plan_builds=1, forbid_padding=True)

_register_contract(
    "moe_apply.fp8.grad",
    description="quantize-once over fwd+bwd: exactly {xs, down-dy, dg, "
                "du} — 4 calls, two xs-shaped; h never standalone-"
                "quantized (the fused epilogue owns it)",
    build=_build_moe_grad,
    quantize_count=4,
    quantize_shapes=((64, 128), (64, 128), (64, 256), (64, 256)),
    plan_builds=1, forbid_padding=True)

_register_contract(
    "moe_apply.fused_producer.fwd",
    description="producer-fused forward: the ONLY standalone quantize is "
                "the shared xs; gate/up route through grouped_gemm_quant "
                "(2 dispatches); g/u/h never exist wider than fp8",
    build=_build_moe_fused_fwd,
    quantize_count=1, quantize_shapes=((64, 128),),
    plan_builds=1, gemm_quant_calls=2, forbid_padding=True,
    forbid_wide_shapes=((64, 256),))

_register_contract(
    "moe_apply.fused_producer.grad",
    description="producer-fused fwd+bwd: same 4-quantize floor {xs, "
                "down-dy, dg, du}, gate/up still through "
                "grouped_gemm_quant, one plan build",
    build=_build_moe_fused_grad,
    quantize_count=4,
    quantize_shapes=((64, 128), (64, 128), (64, 256), (64, 256)),
    plan_builds=1, gemm_quant_calls=2, forbid_padding=True)
