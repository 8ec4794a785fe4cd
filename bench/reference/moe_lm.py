"""Plain float32 reference of the MoE decoder language model that the
deepseek-moe and qwen1.5-moe configurations describe, in straightforward
``jax.numpy``: no kernels, no cache, no batching tricks.

Layer equations (DeepSeekMoE, arXiv 2401.06066; Qwen1.5-MoE):
  h = x + Attn(RMSNorm(x))            causal softmax attention, RoPE
                                       (rotate-half), optional q/k/v bias
  y = h + FFN(RMSNorm(h))             layer < first_dense_layers: SwiGLU
                                       of width d_ff; otherwise MoE
  MoE(x) = sum_{e in topk(softmax(x R))} p_e SwiGLU_e(x) + SwiGLU_shared(x)
The loss is the mean next-token cross entropy (over labels >= 0) plus ``aux_weight`` times
the expert-level balance loss E/k * sum_e mean_t(p_te) * mean_t(1[e in
topk_t]), summed over MoE layers.  The routed experts are computed for
every token and weighted by the (mostly zero) combine matrix: exact, and
plain.

Parameters are a dict tree with the leaf names the configuration's
weights use (``bench.weights``); the MoE layers are stacked along a
leading axis under ``params["layers"]["b0"]``.

Every matmul names the precision the program states for it ("fp8" for the
expert and shared-expert GEMMs, "bf16" for the other projections and the
head, "f32" for the router and the attention scores).  With
``Matmul(control=True)`` each runs one step lower: fp8 -> int4, bf16 ->
fp8 (e4m3), f32 -> bf16, with 1x128 scales on the activation along the
contraction and 128x128 blocks on the weight, passed straight through in
the backward.  That is the control that the comparison has to fail.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
E4M3_MAX = 448.0
INT4_MAX = 7.0
Q_BLOCK = 512            # attention query rows per block


# ---------------------------------------------------------------------------
# matmuls at a stated precision
# ---------------------------------------------------------------------------

def _pad_to(x, axis, mult):
    n = x.shape[axis]
    pad = (-n) % mult
    if not pad:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _round(x, fmt):
    if fmt == "int4":
        return jnp.clip(jnp.round(x), -INT4_MAX, INT4_MAX)
    return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)


def _qmax(fmt):
    return INT4_MAX if fmt == "int4" else E4M3_MAX


def quant_rows(x, fmt):
    """Scale 1x128 tiles along the last axis, round, scale back."""
    k = x.shape[-1]
    xp = _pad_to(x, -1, 128)
    t = xp.reshape(*xp.shape[:-1], -1, 128)
    s = jnp.maximum(jnp.max(jnp.abs(t), -1, keepdims=True), 1e-30) / _qmax(fmt)
    return (_round(t / s, fmt) * s).reshape(xp.shape)[..., :k]


def quant_blocks(w, fmt):
    """Scale 128x128 blocks over the last two axes, round, scale back."""
    k, n = w.shape[-2:]
    wp = _pad_to(_pad_to(w, -2, 128), -1, 128)
    kb, nb = wp.shape[-2] // 128, wp.shape[-1] // 128
    t = wp.reshape(*wp.shape[:-2], kb, 128, nb, 128)
    s = jnp.maximum(jnp.max(jnp.abs(t), (-3, -1), keepdims=True),
                    1e-30) / _qmax(fmt)
    return (_round(t / s, fmt) * s).reshape(wp.shape)[..., :k, :n]


def _ste(x, q):
    return x + jax.lax.stop_gradient(q - x)


LOWER = {"f32": "bf16", "bf16": "fp8", "fp8": "int4"}


class Matmul:
    """``mm(eq, a, w, stated)``: einsum of an activation ``a`` (contracted
    on its last axis) with a weight ``w`` (contracted on its second-last)."""

    def __init__(self, control: bool = False):
        self.control = control

    def lower(self, x, stated, weight):
        fmt = LOWER[stated]
        if fmt == "bf16":
            return _ste(x, x.astype(jnp.bfloat16).astype(jnp.float32))
        fmt = "e4m3" if fmt == "fp8" else fmt
        q = quant_blocks(x, fmt) if weight else quant_rows(x, fmt)
        return _ste(x, q)

    def __call__(self, eq, a, w, stated):
        if self.control:
            a = self.lower(a, stated, weight=False)
            w = self.lower(w, stated, weight=stated == "f32")
        return jnp.einsum(eq, a, w, precision=HIGHEST)

    def pair(self, eq, a, b):
        """A product of two activations (attention), stated f32."""
        if self.control:
            a = self.lower(a, "f32", weight=False)
            b = self.lower(b, "f32", weight=False)
        return jnp.einsum(eq, a, b, precision=HIGHEST)


# ---------------------------------------------------------------------------
# layers
# ---------------------------------------------------------------------------

def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rope(x, pos, theta):
    """x [B, S, H, D] rotated by position (rotate-half pairing)."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None, None].astype(jnp.float32) * freq
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def attention(p, x, sz, mm):
    b, s, _ = x.shape
    h, hkv, hd = sz["num_heads"], sz["num_kv_heads"], sz["head_dim"]
    q = mm("bsd,dn->bsn", x, p["wq"], "bf16")
    k = mm("bsd,dn->bsn", x, p["wk"], "bf16")
    v = mm("bsd,dn->bsn", x, p["wv"], "bf16")
    if sz["qkv_bias"]:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    pos = jnp.arange(s)
    q = rope(q.reshape(b, s, h, hd), pos, sz["rope_theta"])
    k = rope(k.reshape(b, s, hkv, hd), pos, sz["rope_theta"])
    v = v.reshape(b, s, hkv, hd)
    rep = h // hkv
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    qb = Q_BLOCK if s % Q_BLOCK == 0 else s

    @jax.checkpoint
    def block(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * qb, qb, axis=1)
        sc = mm.pair("bqhd,bkhd->bhqk", qi, k) / math.sqrt(hd)
        live = (i * qb + jnp.arange(qb))[:, None] >= pos[None, :]
        sc = jnp.where(live, sc, -jnp.inf)
        return mm.pair("bhqk,bkhd->bqhd", jax.nn.softmax(sc, -1), v)

    o = jax.lax.map(block, jnp.arange(s // qb))        # [n, B, qb, H, hd]
    o = jnp.moveaxis(o, 0, 1).reshape(b, s, h * hd)
    return mm("bsn,nd->bsd", o, p["wo"], "bf16")


def swiglu(x, wg, wu, wd, mm, stated):
    g = mm("td,df->tf", x, wg, stated)
    u = mm("td,df->tf", x, wu, stated)
    return mm("tf,fd->td", jax.nn.silu(g) * u, wd, stated)


def _fp8_shape(*dims):
    """The program runs a dense SwiGLU in fp8 only where every dim is a
    multiple of 128; otherwise in bf16."""
    return "fp8" if all(d % 128 == 0 for d in dims) else "bf16"


def moe(p, x, sz, mm, chunk: int):
    """x [T, d] -> (y [T, d], balance loss, rows per expert [E])."""
    m = sz["moe"]
    e, k = m["num_experts"], m["top_k"]
    probs = jax.nn.softmax(mm("td,de->te", x, p["router"], "f32"), -1)
    top_p, top_i = jax.lax.top_k(probs, k)
    if m["norm_topk_prob"]:
        top_p = top_p / jnp.sum(top_p, -1, keepdims=True)
    t = x.shape[0]
    combine = jnp.zeros((t, e), jnp.float32).at[
        jnp.arange(t)[:, None], top_i].set(top_p)

    def body(acc, ws):
        wg, wu, wd, c = ws                        # [n, d, f] ... [T, n]
        g = mm("td,edf->etf", x, wg, "fp8")
        u = mm("td,edf->etf", x, wu, "fp8")
        y = mm("etf,efd->etd", jax.nn.silu(g) * u, wd, "fp8")
        return acc + jnp.einsum("etd,te->td", y, c, precision=HIGHEST), None

    def chunked(a):
        return a.reshape(e // chunk, chunk, *a.shape[1:])

    y, _ = jax.lax.scan(
        jax.checkpoint(body), jnp.zeros_like(x),
        (chunked(p["w_gate"]), chunked(p["w_up"]), chunked(p["w_down"]),
         jnp.moveaxis(combine.reshape(t, e // chunk, chunk), 1, 0)))
    if m["num_shared_experts"]:
        fs = p["shared_gate"].shape[-1]
        y = y + swiglu(x, p["shared_gate"], p["shared_up"], p["shared_down"],
                       mm, _fp8_shape(x.shape[-1], fs))
    picked = jnp.sum(jax.nn.one_hot(top_i, e, dtype=jnp.float32), 1)
    balance = e / k * jnp.sum(jnp.mean(probs, 0) * jnp.mean(picked, 0))
    return y, balance, jnp.sum(picked, 0)


def _expert_chunk(e: int) -> int:
    return next(c for c in (8, 6, 5, 4, 3, 2, 1) if e % c == 0)


def block(p, x, sz, mm, *, moe_layer: bool):
    b, s, d = x.shape
    eps = sz["norm_eps"]
    x = x + attention(p["attn"], rms_norm(x, p["ln1"]["scale"], eps), sz, mm)
    h = rms_norm(x, p["ln2"]["scale"], eps).reshape(b * s, d)
    if moe_layer:
        y, bal, rows = moe(p["moe"], h, sz, mm,
                           _expert_chunk(sz["moe"]["num_experts"]))
    else:
        f = p["mlp"]["w_up"].shape[-1]
        y = swiglu(h, p["mlp"]["w_gate"], p["mlp"]["w_up"],
                   p["mlp"]["w_down"], mm, _fp8_shape(d, f))
        bal, rows = jnp.zeros(()), None
    return x + y.reshape(b, s, d), bal, rows


def forward(params, tokens, sz, mm):
    """tokens [B, S] -> (logits [B, S, V] f32, balance loss summed over
    MoE layers, rows per expert of each MoE layer [L_moe, E])."""
    n_dense = sz["moe"]["first_dense_layers"]
    x = params["embed"]["embedding"][tokens]
    bal_total, rows = jnp.zeros(()), []
    for i in range(n_dense):
        x, _, _ = jax.checkpoint(
            lambda p_, x_: block(p_, x_, sz, mm, moe_layer=False))(
                params[f"pre{i}"], x)
    stacked = params["layers"]["b0"]
    for li in range(sz["num_layers"] - n_dense):
        p_l = jax.tree.map(lambda a: a[li], stacked)
        x, bal, r = jax.checkpoint(
            lambda p_, x_: block(p_, x_, sz, mm, moe_layer=True))(p_l, x)
        bal_total, rows = bal_total + bal, rows + [r]
    x = rms_norm(x, params["final_norm"]["scale"], sz["norm_eps"])
    head = (params["embed"]["lm_head"] if "lm_head" in params["embed"]
            else params["embed"]["embedding"].T)
    logits = mm("bsd,dv->bsv", x, head, "bf16")
    return logits, bal_total, jnp.stack(rows)


def loss_fn(params, batch, sz, mm, aux_weight):
    logits, bal, rows = forward(params, batch["tokens"], sz, mm)
    labels = batch["labels"][:, 1:]
    lp = jax.nn.log_softmax(logits[:, :-1], -1)
    nll = -jnp.take_along_axis(lp, jnp.maximum(labels, 0)[..., None],
                               -1)[..., 0]
    valid = labels >= 0                      # label -1: not in the loss
    return (jnp.sum(jnp.where(valid, nll, 0.0)) / jnp.sum(valid)
            + aux_weight * bal, rows)


# ---------------------------------------------------------------------------
# training: AdamW as the configuration states it
# ---------------------------------------------------------------------------

def lr_at(step, opt):
    warm = min(step / max(opt["warmup_steps"], 1), 1.0)
    prog = min(max((step - opt["warmup_steps"])
                   / max(opt["total_steps"] - opt["warmup_steps"], 1), 0.0),
               1.0)
    cos = 0.5 * (1 + math.cos(math.pi * prog))
    return opt["lr"] * warm * (opt["min_lr_ratio"]
                               + (1 - opt["min_lr_ratio"]) * cos)


def train_readings(make_params, batches, sz, opt, aux_weight, *,
                   control=False):
    """Three AdamW steps from ``make_params()`` (f32) on ``batches``.

    Returns the loss of each step, the norm per leaf of step 1's gradient
    as the optimizer applies it (after clipping), the norm per leaf of
    each leaf's change after the three steps, and the rows per expert of
    step 1's routing."""
    mm = Matmul(control)
    grad_fn = jax.jit(jax.value_and_grad(
        lambda p, b: loss_fn(p, b, sz, mm, aux_weight), has_aux=True))
    b1, b2, eps, wd = opt["b1"], opt["b2"], opt["eps"], opt["weight_decay"]

    def norms(tree):
        return [jnp.sqrt(jnp.sum(jnp.square(x))) for x in jax.tree.leaves(tree)]

    @functools.partial(jax.jit, donate_argnums=0)
    def clip(grads):
        norm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                            for g in jax.tree.leaves(grads)))
        c = jnp.minimum(1.0, opt["clip_norm"] / jnp.maximum(norm, 1e-12))
        grads = jax.tree.map(lambda g: g * c, grads)
        return grads, norms(grads)

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3))
    def adamw(p, m, v, g, lr, t):
        m = jax.tree.map(lambda m_, g_: b1 * m_ + (1 - b1) * g_, m, g)
        v = jax.tree.map(lambda v_, g_: b2 * v_ + (1 - b2) * g_ * g_, v, g)
        c1, c2 = 1 - b1 ** t, 1 - b2 ** t
        p = jax.tree.map(
            lambda p_, m_, v_: p_ - lr * ((m_ / c1) / (jnp.sqrt(v_ / c2) + eps)
                                          + wd * p_), p, m, v)
        return p, m, v

    # m and v wait on the host while a gradient is taken, so the device
    # holds at most params, gradient, m and v, or params, gradient and
    # the gradient's temporaries
    p = make_params()
    m = v = None
    losses = []
    for t, batch in enumerate(batches, start=1):
        (loss, r), g = grad_fn(p, batch)
        g, g_norms = clip(g)
        if t == 1:
            grad_norms, rows = [float(x) for x in g_norms], jax.device_get(r)
            m = jax.tree.map(jnp.zeros_like, p)
            v = jax.tree.map(jnp.zeros_like, p)
        losses.append(float(loss))
        p, m, v = adamw(p, m, v, g, jnp.float32(lr_at(t, opt)),
                        jnp.float32(t))
        del g
        m, v = jax.device_get((m, v))
    del m, v
    change = jax.jit(lambda a, b: norms(jax.tree.map(jnp.subtract, a, b)))(
        p, make_params())
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": [float(x) for x in change], "rows": rows}


# ---------------------------------------------------------------------------
# serving: logits at the served positions
# ---------------------------------------------------------------------------

def served_logits(params, prompts, served, sz, *, control=False):
    """Logits that predict each served token, teacher-forced on the
    prompt and the served tokens before it: [n, new, V] f32."""
    mm = Matmul(control)
    seq = jnp.concatenate([prompts, served[:, :-1]], axis=1)
    p_len = prompts.shape[1]

    @jax.jit
    def run(params, seq):
        logits, _, rows = forward(params, seq, sz, mm)
        return logits[:, p_len - 1:], rows

    return run(params, seq)
