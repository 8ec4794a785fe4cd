"""Generic decoder LM covering the dense / MoE / ssm / hybrid / vlm
families via the config's cycled ``block_pattern``.

Layer layout = [pre_layers (unscanned; e.g. deepseek's dense layer-0)]
             + [cycles x pattern (lax.scan over stacked params, remat)]
             + [tail_layers (pattern remainder, unscanned)].

Modes: "train" (no cache), "prefill" (returns per-layer caches),
"decode" (one token against caches).
"""
from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.core.moe import (MoEConfig, init_moe_params, moe_apply,
                            ep_size_for, quantize_serving_weights,
                            shard_moe_params)
from repro.distributed import context as dctx
from repro.models import attention as attn
from repro.models import rglru as rg
from repro.models import xlstm as xl
from repro.models.layers import (init_rms_norm, rms_norm, init_mlp, mlp,
                                 init_embedding, embed, unembed, ninit,
                                 cross_entropy)
from repro.scopes import DENSE_FFN, EMBED, LM_HEAD, scope


def effective_pattern(cfg: ModelConfig):
    return cfg.block_pattern if cfg.block_pattern else ("attn",)


def moe_config(cfg: ModelConfig) -> MoEConfig:
    m = cfg.moe
    return MoEConfig(
        num_experts=m.num_experts, top_k=m.top_k, d_model=cfg.d_model,
        d_ff_expert=m.d_ff_expert, num_shared_experts=m.num_shared_experts,
        norm_topk_prob=m.norm_topk_prob, capacity_factor=m.capacity_factor,
        precision=cfg.precision, backend=cfg.gemm_backend,
        kernel_config=cfg.resolved_kernel_config,
        dispatch=cfg.moe_dispatch,
        reduce_dtype=jnp.bfloat16 if cfg.moe_reduce_bf16 else jnp.float32)


# ---------------------------------------------------------------------------
# Blocks
# ---------------------------------------------------------------------------

def init_block(key, kind: str, cfg: ModelConfig, *, moe_layer: bool):
    dtype = cfg.dtype
    ks = jax.random.split(key, 4)
    d = cfg.d_model
    if kind == "attn":
        p = {"ln1": init_rms_norm(d), "ln2": init_rms_norm(d),
             "attn": attn.init_attention(ks[0], cfg, dtype)}
        if moe_layer:
            p["moe"] = init_moe_params(ks[1], moe_config(cfg), dtype)
        else:
            act = "gelu" if cfg.family == "audio" else "swiglu"
            f = cfg.d_ff or (cfg.moe.d_ff_expert *
                             (cfg.moe.top_k + cfg.moe.num_shared_experts)
                             if cfg.moe else 4 * d)
            p["mlp"] = init_mlp(ks[1], d, f, act, dtype)
        return p
    if kind == "rglru":
        return {"ln1": init_rms_norm(d), "ln2": init_rms_norm(d),
                "rglru": rg.init_rglru(ks[0], cfg, dtype),
                "mlp": init_mlp(ks[1], d, cfg.d_ff, "swiglu", dtype)}
    if kind == "mlstm":
        return {"ln1": init_rms_norm(d),
                "mlstm": xl.init_mlstm(ks[0], cfg, dtype)}
    if kind == "slstm":
        return {"ln1": init_rms_norm(d),
                "slstm": xl.init_slstm(ks[0], cfg, dtype)}
    raise ValueError(kind)


def _apply_moe(p, x, cfg: ModelConfig):
    mcfg = moe_config(cfg)
    b, s, d = x.shape
    if dctx.model_axis_size() == 1:
        y, aux = moe_apply(p, x.reshape(b * s, d), mcfg)
        return y.reshape(b, s, d), aux["load_balance_loss"]

    mesh = dctx.get_mesh()
    ep = ep_size_for(mcfg, mesh.shape["model"])
    pspecs = shard_moe_params(p, mcfg, ep)
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    xspec = P(batch_axes if batch_axes else None, None, None)

    def local_fn(p_loc, x_loc):
        rank = jax.lax.axis_index("model") if ep > 1 else 0
        bl, sl, dl = x_loc.shape
        y, aux = moe_apply(p_loc, x_loc.reshape(bl * sl, dl), mcfg,
                           ep_rank=rank, ep_size=ep, axis_name="model")
        return y.reshape(bl, sl, dl), aux["load_balance_loss"]

    y, lb = jax.shard_map(local_fn, mesh=mesh, in_specs=(pspecs, xspec),
                          out_specs=(xspec, P()), check_vma=False)(p, x)
    return y, lb


def quantize_serving_params(params, cfg: ModelConfig):
    """``params`` with each MoE block's fp8 weights quantized once
    (:func:`~repro.core.moe.quantize_serving_weights`), for serving.

    Unchanged for a model without fp8 MoE blocks, and while a mesh with
    a ``model`` axis over 1 is active: the sharded MoE path
    (``shard_moe_params``) places raw weights only."""
    if cfg.moe is None or cfg.precision != "fp8" \
            or dctx.model_axis_size() > 1:
        return params
    mcfg = moe_config(cfg)

    def walk(tree):
        if isinstance(tree, dict):
            return {k: quantize_serving_weights(v, mcfg) if k == "moe"
                    else walk(v) for k, v in tree.items()}
        if isinstance(tree, list):
            return [walk(v) for v in tree]
        return tree
    return walk(params)


def block_apply(kind: str, p, x, cfg: ModelConfig, positions, *,
                cache=None, mode: str = "train", cache_capacity=None):
    """Returns (x, new_cache, aux_loss)."""
    aux = jnp.zeros((), jnp.float32)
    if kind == "attn":
        x_in = rms_norm(p["ln1"], x, cfg.norm_eps)
        if cfg.seq_shard:
            # Megatron-SP gather point: residual stream is seq-sharded;
            # attention needs the full sequence (explicit AG here keeps
            # GSPMD from replicating the whole attention computation)
            x_in = dctx.constrain(x_in, "batch", None, "embed")
        h, new_cache = attn.attention_block(
            p["attn"], x_in, cfg, positions,
            cache=cache, layer_window=cfg.window, mode=mode,
            cache_capacity=cache_capacity)
        x = x + h
        h2 = rms_norm(p["ln2"], x, cfg.norm_eps)
        if cfg.seq_shard:
            h2 = dctx.constrain(h2, "batch", None, "embed")
        if "moe" in p:
            ff, aux = _apply_moe(p["moe"], h2, cfg)
        else:
            act = "gelu" if cfg.family == "audio" else "swiglu"
            with scope(DENSE_FFN):
                ff = mlp(p["mlp"], h2, act, precision=cfg.precision,
                         backend=cfg.gemm_backend,
                         config=cfg.resolved_kernel_config)
        return x + ff, new_cache, aux
    if kind == "rglru":
        h, new_state = rg.rglru_apply(
            p["rglru"], rms_norm(p["ln1"], x, cfg.norm_eps),
            state=cache)
        if mode == "train":
            new_state = None
        x = x + h
        h2 = rms_norm(p["ln2"], x, cfg.norm_eps)
        with scope(DENSE_FFN):
            ff = mlp(p["mlp"], h2, "swiglu", precision=cfg.precision,
                     backend=cfg.gemm_backend,
                     config=cfg.resolved_kernel_config)
        return x + ff, new_state, aux
    if kind == "mlstm":
        h, new_state = xl.mlstm_apply(
            p["mlstm"], rms_norm(p["ln1"], x, cfg.norm_eps), state=cache)
        return x + h, (None if mode == "train" else new_state), aux
    if kind == "slstm":
        h, new_state = xl.slstm_apply(
            p["slstm"], rms_norm(p["ln1"], x, cfg.norm_eps), state=cache)
        return x + h, (None if mode == "train" else new_state), aux
    raise ValueError(kind)


def init_block_cache(kind: str, cfg: ModelConfig, batch: int, seq_len: int):
    if kind == "attn":
        return attn.init_kv_cache(cfg, batch, seq_len, cfg.window)
    if kind == "rglru":
        return rg.init_rglru_state(cfg, batch)
    if kind == "mlstm":
        return xl.init_mlstm_state(cfg, batch)
    if kind == "slstm":
        return xl.init_slstm_state(cfg, batch)
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# Decoder
# ---------------------------------------------------------------------------

def _layout(cfg: ModelConfig):
    pattern = effective_pattern(cfg)
    n_pre = cfg.moe.first_dense_layers if cfg.moe else 0
    rest = cfg.num_layers - n_pre
    cycles = rest // len(pattern)
    tail = tuple(pattern[i] for i in range(rest % len(pattern)))
    return pattern, n_pre, cycles, tail


def init_decoder(key, cfg: ModelConfig):
    pattern, n_pre, cycles, tail = _layout(cfg)
    keys = jax.random.split(key, 8)
    params = {
        "embed": init_embedding(keys[0], cfg.vocab_size, cfg.d_model,
                                cfg.dtype, cfg.tie_embeddings),
        "final_norm": init_rms_norm(cfg.d_model),
    }
    if cfg.family == "vlm" and cfg.num_patches:
        params["vision_proj"] = ninit(keys[1], (cfg.patch_embed_dim,
                                                cfg.d_model),
                                      cfg.patch_embed_dim ** -0.5, cfg.dtype)
    moe_layer = cfg.moe is not None

    def init_cycle(k):
        ks = jax.random.split(k, len(pattern))
        return {f"b{i}": init_block(ks[i], kind, cfg, moe_layer=moe_layer)
                for i, kind in enumerate(pattern)}

    if cycles:
        if cfg.scan_layers:
            params["layers"] = jax.vmap(init_cycle)(
                jax.random.split(keys[2], cycles))
        else:
            params["layers"] = [init_cycle(k)
                                for k in jax.random.split(keys[2], cycles)]
    for i in range(n_pre):
        params[f"pre{i}"] = init_block(jax.random.split(keys[3], n_pre)[i],
                                       "attn", cfg, moe_layer=False)
    for i, kind in enumerate(tail):
        params[f"tail{i}"] = init_block(jax.random.split(keys[4],
                                                         max(len(tail), 1))[i],
                                        kind, cfg, moe_layer=moe_layer)
    return params


def init_cache(cfg: ModelConfig, batch: int, seq_len: int):
    pattern, n_pre, cycles, tail = _layout(cfg)
    cache = {}
    if cycles:
        def one_cycle(_):
            return {f"b{i}": init_block_cache(kind, cfg, batch, seq_len)
                    for i, kind in enumerate(pattern)}
        cache["layers"] = jax.tree.map(
            lambda *xs: jnp.stack(xs), *[one_cycle(c) for c in range(cycles)]) \
            if cycles > 1 else jax.tree.map(lambda x: x[None], one_cycle(0))
    for i in range(n_pre):
        cache[f"pre{i}"] = init_block_cache("attn", cfg, batch, seq_len)
    for i, kind in enumerate(tail):
        cache[f"tail{i}"] = init_block_cache(kind, cfg, batch, seq_len)
    return cache


def decoder_forward(params, tokens, cfg: ModelConfig, *, mode="train",
                    cache=None, patch_embeds=None, pos_offset=None,
                    cache_capacity=None):
    """tokens: [B, S] int32.  Returns (logits, new_cache, aux_loss).

    decode mode: S == 1, ``cache`` holds per-layer state.
    vlm: ``patch_embeds`` [B, P, patch_dim] are projected and prepended
    (loss positions for patches carry label -1 upstream).
    """
    pattern, n_pre, cycles, tail = _layout(cfg)
    b, s = tokens.shape
    with scope(EMBED):
        x = embed(params["embed"], tokens)
        if patch_embeds is not None:
            pe = jnp.einsum("bpe,ed->bpd", patch_embeds.astype(x.dtype),
                            params["vision_proj"].astype(x.dtype))
            x = jnp.concatenate([pe, x], axis=1)
            s = x.shape[1]
        x = dctx.constrain(x, "batch", "seq", "embed")

    if mode == "decode":
        positions = None  # per-layer caches carry the position
    else:
        positions = jnp.arange(s, dtype=jnp.int32)
        if pos_offset is not None:
            positions = positions + pos_offset

    aux_total = jnp.zeros((), jnp.float32)
    new_cache = {} if mode in ("prefill", "decode") else None

    # --- pre layers (unscanned) -----------------------------------------
    for i in range(n_pre):
        c = cache.get(f"pre{i}") if cache else None
        x, nc, aux = block_apply("attn", params[f"pre{i}"], x, cfg,
                                 positions, cache=c, mode=mode,
                                 cache_capacity=cache_capacity)
        aux_total += aux
        if new_cache is not None:
            new_cache[f"pre{i}"] = nc

    # --- scanned cycles ---------------------------------------------------
    if cycles:
        def cycle_body(xc, layer_in):
            x, aux_acc = xc
            lp, lcache = layer_in
            ncache = {}
            for i, kind in enumerate(pattern):
                c = lcache[f"b{i}"] if lcache is not None else None
                x, nc, aux = block_apply(kind, lp[f"b{i}"], x, cfg,
                                         positions, cache=c, mode=mode,
                                         cache_capacity=cache_capacity)
                ncache[f"b{i}"] = nc
                aux_acc = aux_acc + aux
            return (x, aux_acc), (ncache if mode != "train" else None)

        body = cycle_body
        if cfg.remat and mode == "train":
            body = jax.checkpoint(
                cycle_body,
                policy=jax.checkpoint_policies.nothing_saveable)

        if cfg.scan_layers:
            layer_cache = cache.get("layers") if cache else None
            if layer_cache is not None:
                (x, aux_total), caches_out = jax.lax.scan(
                    body, (x, aux_total), (params["layers"], layer_cache))
            else:
                (x, aux_total), caches_out = jax.lax.scan(
                    lambda c, lp: body(c, (lp, None)), (x, aux_total),
                    params["layers"])
            if new_cache is not None:
                new_cache["layers"] = caches_out
        else:
            for li, lp in enumerate(params["layers"]):
                lcache = (jax.tree.map(lambda v: v[li], cache["layers"])
                          if cache else None)
                (x, aux_total), nc = body((x, aux_total), (lp, lcache))
                if new_cache is not None:
                    new_cache.setdefault("_layer_list", []).append(nc)
            if new_cache is not None and "_layer_list" in new_cache:
                lst = new_cache.pop("_layer_list")
                new_cache["layers"] = jax.tree.map(
                    lambda *xs: jnp.stack(xs), *lst)

    # --- tail layers ------------------------------------------------------
    for i, kind in enumerate(tail):
        c = cache.get(f"tail{i}") if cache else None
        x, nc, aux = block_apply(kind, params[f"tail{i}"], x, cfg,
                                 positions, cache=c, mode=mode,
                                 cache_capacity=cache_capacity)
        aux_total += aux
        if new_cache is not None:
            new_cache[f"tail{i}"] = nc

    with scope(LM_HEAD):
        if mode == "prefill":
            x = x[:, -1:]    # serving prefill needs only the last position
        x = rms_norm(params["final_norm"], x, cfg.norm_eps)
        logits = unembed(params["embed"], x)
    return logits, new_cache, aux_total


def lm_loss(params, batch, cfg: ModelConfig, *, aux_weight=0.01):
    """batch: {tokens [B,S], labels [B,S] (-1 = ignore), optional
    patch_embeds}.  Next-token CE + MoE load-balance aux."""
    logits, _, aux = decoder_forward(
        params, batch["tokens"], cfg, mode="train",
        patch_embeds=batch.get("patch_embeds"))
    with scope(LM_HEAD):
        labels = batch["labels"]
        if batch.get("patch_embeds") is not None:
            p = batch["patch_embeds"].shape[1]
            pad = jnp.full((labels.shape[0], p), -1, labels.dtype)
            labels = jnp.concatenate([pad, labels], axis=1)
        loss = cross_entropy(logits[:, :-1], labels[:, 1:])
        return loss + aux_weight * aux, {"ce": loss, "aux": aux}
