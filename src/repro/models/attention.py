"""Attention: GQA/MQA with RoPE, qk-norm, optional QKV bias, sliding
window; chunked online-softmax for long sequences (memory-bounded), plus a
single-step decode path against a KV cache.

KV heads are never materialized to q-head count — scores are computed in
grouped form [B, Hkv, G, Sq, Sk].
"""
from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.models.layers import ninit, rope, rms_norm, init_rms_norm
from repro.distributed.context import constrain
from repro.scopes import ATTN, scope

NEG_INF = -1e30


def init_attention(key, cfg, dtype):
    d, hq, hkv, hd = (cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
                      cfg.resolved_head_dim)
    ks = jax.random.split(key, 6)
    p = {
        "wq": ninit(ks[0], (d, hq * hd), d ** -0.5, dtype),
        "wk": ninit(ks[1], (d, hkv * hd), d ** -0.5, dtype),
        "wv": ninit(ks[2], (d, hkv * hd), d ** -0.5, dtype),
        "wo": ninit(ks[3], (hq * hd, d), (hq * hd) ** -0.5, dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((hq * hd,), dtype)
        p["bk"] = jnp.zeros((hkv * hd,), dtype)
        p["bv"] = jnp.zeros((hkv * hd,), dtype)
    if cfg.qk_norm:
        p["q_norm"] = init_rms_norm(hd)
        p["k_norm"] = init_rms_norm(hd)
    return p


def _project_qkv(p, x, cfg, positions, *, use_rope=True):
    b, s, d = x.shape
    hq, hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    q = jnp.einsum("bsd,dh->bsh", x, p["wq"].astype(x.dtype))
    k = jnp.einsum("bsd,dh->bsh", x, p["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dh->bsh", x, p["wv"].astype(x.dtype))
    if cfg.qkv_bias:
        q = q + p["bq"].astype(x.dtype)
        k = k + p["bk"].astype(x.dtype)
        v = v + p["bv"].astype(x.dtype)
    q = q.reshape(b, s, hq, hd)
    k = k.reshape(b, s, hkv, hd)
    v = v.reshape(b, s, hkv, hd)
    if cfg.qk_norm:
        q = rms_norm(p["q_norm"], q, cfg.norm_eps)
        k = rms_norm(p["k_norm"], k, cfg.norm_eps)
    if use_rope:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    return q, k, v


def chunked_attention(q, k, v, *, causal: bool, window: Optional[int],
                      chunk: int, q_offset=0, k_offset=0,
                      k_valid: Optional[int] = None):
    """Online-softmax attention, scanned over q and k chunks.

    q: [B, Sq, Hq, D]; k, v: [B, Sk, Hkv, D].  Positions are affine in the
    chunk index: q rows sit at ``q_offset + i``, k rows at ``k_offset + j``.
    Masks are (re)computed INSIDE the scan bodies from the loop counters —
    never passed as scan inputs — so XLA cannot hoist them into materialized
    [nq, nk, ...] mask stacks (a 100x HBM-traffic trap found in the §Perf
    baseline).  Memory: O(chunk^2) score blocks.
    """
    b, sq, hq, hd = q.shape
    _, sk, hkv, _ = k.shape
    g = hq // hkv
    scale = hd ** -0.5
    cq = min(chunk, sq)
    ck = min(chunk, sk)
    sq_orig = sq
    if k_valid is None:
        k_valid = sk
    # pad to chunk multiples; padded keys are masked via k_valid
    if sq % cq:
        pad = cq - sq % cq
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        sq += pad
    if sk % ck:
        pad = ck - sk % ck
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        sk += pad
    nq, nk = sq // cq, sk // ck

    qg = q.reshape(b, nq, cq, hkv, g, hd).transpose(1, 0, 3, 4, 2, 5)
    kc = k.reshape(b, nk, ck, hkv, hd).transpose(1, 0, 3, 2, 4)
    vc = v.reshape(b, nk, ck, hkv, hd).transpose(1, 0, 3, 2, 4)

    iota_q = jax.lax.iota(jnp.int32, cq)
    iota_k = jax.lax.iota(jnp.int32, ck)

    def q_step(_, qin):
        qi, i = qin                                     # [B,Hkv,G,cq,D], idx
        qpi = q_offset + i * cq + iota_q                # [cq], from counter

        def attend(carry, ki, vi, j):
            m, l, acc = carry
            kpi = k_offset + j * ck + iota_k
            s = jnp.einsum("bhgqd,bhkd->bhgqk", qi.astype(jnp.float32),
                           ki.astype(jnp.float32)) * scale
            mask = jnp.broadcast_to(kpi[None, :] < k_valid,
                                    (cq, ck))
            if causal:
                mask &= qpi[:, None] >= kpi[None, :]
            if window is not None:
                mask &= (qpi[:, None] - kpi[None, :]) < window
            s = jnp.where(mask, s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            # masked entries hold -1e30: exp(-1e30 - m) underflows to
            # exactly 0, so no second mask pass is needed (§Perf I1).
            # NOTE: casting p to bf16 for the PV dot was tried and
            # REFUTED (+4..7% traffic): the convert adds an HBM boundary
            # on the XLA path; it only pays inside a fused flash kernel.
            p = jnp.exp(s - m_new[..., None])
            alpha = jnp.exp(m - m_new)
            l_new = l * alpha + jnp.sum(p, axis=-1)
            acc_new = acc * alpha[..., None] + jnp.einsum(
                "bhgqk,bhkd->bhgqd", p, vi.astype(jnp.float32))
            return (m_new, l_new, acc_new)

        def k_step(carry, kin):
            ki, vi, j = kin                             # [B,Hkv,ck,D], idx
            # §Perf I4: block-level causal/window skipping — chunks with
            # no live (q, k) pair take the identity branch (a real branch
            # on TPU: while-loop bodies execute per iteration).  ~Halves
            # attention fwd+bwd work for causal training shapes.
            live = None
            if causal:
                live = (q_offset + i * cq + cq - 1) >= (k_offset + j * ck)
            if window is not None:
                in_win = (q_offset + i * cq) - (k_offset + j * ck
                                                + ck - 1) < window
                live = in_win if live is None else live & in_win
            if live is None:
                return attend(carry, ki, vi, j), None
            return jax.lax.cond(live,
                                lambda c: attend(c, ki, vi, j),
                                lambda c: c, carry), None

        m0 = jnp.full((b, hkv, g, cq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, hkv, g, cq), jnp.float32)
        a0 = jnp.zeros((b, hkv, g, cq, hd), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            k_step, (m0, l0, a0), (kc, vc, jnp.arange(nk)))
        out = acc / jnp.maximum(l, 1e-20)[..., None]
        return None, out                                # [B,Hkv,G,cq,D]

    _, outs = jax.lax.scan(q_step, None, (qg, jnp.arange(nq)))
    out = outs.transpose(1, 0, 4, 2, 3, 5).reshape(b, sq, hq, hd)
    return out[:, :sq_orig].astype(q.dtype)


def decode_attention(q, k_cache, v_cache, q_pos, cache_len, *,
                     window: Optional[int]):
    """q: [B, 1, Hq, D] vs cache [B, S, Hkv, D]; positions < cache_len valid."""
    b, _, hq, hd = q.shape
    _, s, hkv, _ = k_cache.shape
    g = hq // hkv
    scale = hd ** -0.5
    qg = q.reshape(b, hkv, g, hd)
    s_scores = jnp.einsum("bhgd,bshd->bhgs", qg.astype(jnp.float32),
                          k_cache.astype(jnp.float32)) * scale
    k_pos = jnp.arange(s)
    mask = k_pos[None, :] <= q_pos[:, None]             # [B, S]
    if window is not None:
        mask &= (q_pos[:, None] - k_pos[None, :]) < window
    s_scores = jnp.where(mask[:, None, None, :], s_scores, NEG_INF)
    p = jax.nn.softmax(s_scores, axis=-1)
    out = jnp.einsum("bhgs,bshd->bhgd", p, v_cache.astype(jnp.float32))
    return out.reshape(b, 1, hq, hd).astype(q.dtype)


def _cache_from_prefill(k, v, window, capacity=None, dtype=jnp.bfloat16):
    """Build a decode cache from prefill K/V, padded to ``capacity`` slots
    so subsequent decode steps can append.  Window layers use a ring buffer
    keyed by position % window."""
    b, s, hkv, hd = k.shape
    if window is not None and s > window:
        pos = jnp.arange(s - window, s)
        slots = pos % window
        kc = jnp.zeros((b, window, hkv, hd), dtype).at[:, slots].set(
            k[:, -window:].astype(dtype))
        vc = jnp.zeros((b, window, hkv, hd), dtype).at[:, slots].set(
            v[:, -window:].astype(dtype))
        return {"k": kc, "v": vc, "len": jnp.array(s, jnp.int32)}
    cap = max(capacity or s, s)
    pad = ((0, 0), (0, cap - s), (0, 0), (0, 0))
    return {"k": jnp.pad(k.astype(dtype), pad),
            "v": jnp.pad(v.astype(dtype), pad),
            "len": jnp.array(s, jnp.int32)}


@scope(ATTN)
def attention_block(p, x, cfg, positions, *, cache=None, layer_window=None,
                    causal=True, mode="train", cache_capacity=None):
    """Full attention sub-block.  With ``cache`` (dict k,v,len) performs
    one decode step and returns (out, new_cache); in prefill mode, builds
    the cache from the full-sequence K/V."""
    b, s, d = x.shape
    hq, hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    window = layer_window

    if cache is None:
        q, k, v = _project_qkv(p, x, cfg, positions)
        q = constrain(q, "batch", "seq", "heads", None)
        k = constrain(k, "batch", "seq", "heads", None)
        off = positions[0]
        use_flash = (cfg.attn_backend == "flash" and window is None
                     and s % 128 == 0)
        if use_flash:
            # fused Pallas kernel: scores/softmax state never leave VMEM
            from repro.kernels.flash_attention_kernel import \
                flash_attention_trainable
            out = flash_attention_trainable(
                q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                v.transpose(0, 2, 1, 3), causal,
                jax.default_backend() != "tpu",
            ).transpose(0, 2, 1, 3)
        else:
            out = chunked_attention(q, k, v, causal=causal, window=window,
                                    chunk=cfg.attn_chunk, q_offset=off,
                                    k_offset=off)
        new_cache = (_cache_from_prefill(k, v, window, cache_capacity)
                     if mode == "prefill" else None)
    else:
        pos = cache["len"]                               # scalar int32
        positions = jnp.full((b,), pos, jnp.int32)
        q, k, v = _project_qkv(p, x, cfg, positions[:, None])
        k = k.astype(cache["k"].dtype)
        v = v.astype(cache["v"].dtype)
        if window is not None and cache["k"].shape[1] == window:
            # rolling window cache: write at pos % window
            idx = pos % window
            k_cache = jax.lax.dynamic_update_slice_in_dim(cache["k"], k, idx, 1)
            v_cache = jax.lax.dynamic_update_slice_in_dim(cache["v"], v, idx, 1)
            # positions of cache slots (ring)
            slot = jnp.arange(window)
            slot_pos = jnp.where(slot <= idx, pos - idx + slot,
                                 pos - idx - window + slot)
            s_scores = jnp.einsum(
                "bhgd,bshd->bhgs",
                q.reshape(b, hkv, hq // hkv, hd).astype(jnp.float32),
                k_cache.astype(jnp.float32)) * hd ** -0.5
            mask = (slot_pos >= 0) & (slot_pos <= pos)
            s_scores = jnp.where(mask[None, None, None, :], s_scores, NEG_INF)
            pr = jax.nn.softmax(s_scores, axis=-1)
            out = jnp.einsum("bhgs,bshd->bhgd", pr,
                             v_cache.astype(jnp.float32))
            out = out.reshape(b, 1, hq, hd).astype(x.dtype)
        else:
            k_cache = jax.lax.dynamic_update_slice_in_dim(
                cache["k"], k, pos, 1)
            v_cache = jax.lax.dynamic_update_slice_in_dim(
                cache["v"], v, pos, 1)
            k_cache = constrain(k_cache, "batch", "kv_seq", None, None)
            v_cache = constrain(v_cache, "batch", "kv_seq", None, None)
            out = decode_attention(q, k_cache, v_cache, positions, pos,
                                   window=window)
        new_cache = {"k": k_cache, "v": v_cache, "len": pos + 1}

    out = out.reshape(b, s, hq * hd)
    y = jnp.einsum("bsh,hd->bsd", out, p["wo"].astype(x.dtype))
    return constrain(y, "batch", "seq", "embed"), new_cache


def init_kv_cache(cfg, batch, seq_len, layer_window=None, dtype=jnp.bfloat16):
    hkv, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    s = min(seq_len, layer_window) if layer_window else seq_len
    return {"k": jnp.zeros((batch, s, hkv, hd), dtype),
            "v": jnp.zeros((batch, s, hkv, hd), dtype),
            "len": jnp.zeros((), jnp.int32)}
