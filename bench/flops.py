"""Model FLOPs and the least work of the expert GEMMs, from the
configuration's shapes alone (``spec.sizes``): never from tiles, padding
or what the program happens to compute."""
from __future__ import annotations

# bytes per element of the dtypes the configuration states
FP8, BF16, F32 = 1, 2, 4
SCALE_TILE = 128          # 1x128 activation tiles, 128x128 weight blocks


def _moe_layers(sz) -> int:
    return sz["num_layers"] - sz["moe"]["first_dense_layers"]


def matmul_params(sz) -> dict:
    """Matmul parameters a token passes through, by kind, summed over
    layers.  The embedding lookup is no matmul and does not count."""
    d, hd = sz["d_model"], sz["head_dim"]
    m = sz["moe"]
    attn = d * hd * (2 * sz["num_heads"] + 2 * sz["num_kv_heads"])
    n_moe = _moe_layers(sz)
    routed = 3 * d * m["d_ff_expert"] * m["top_k"]
    shared = 3 * d * m["d_ff_expert"] * m["num_shared_experts"]
    return {
        "attention": sz["num_layers"] * attn,
        "dense_ffn": m["first_dense_layers"] * 3 * d * sz["d_ff"],
        "router": n_moe * d * m["num_experts"],
        "routed_experts": n_moe * routed,
        "shared_experts": n_moe * shared,
        "unembedding": d * sz["vocab_size"],
    }


def attention_flops_fwd(sz, n_keys_total: float) -> float:
    """Forward FLOPs of scores and values for one layer, given the sum
    over query positions of the keys each attends to."""
    return 4 * sz["num_heads"] * sz["head_dim"] * n_keys_total


def train_flops_per_token(sz, seq: int) -> float:
    """6 FLOPs per matmul parameter per token, plus causal attention at
    its causal half (mean context (seq + 1) / 2), forward and backward."""
    p = sum(matmul_params(sz).values())
    attn = sz["num_layers"] * 3 * attention_flops_fwd(sz, (seq + 1) / 2)
    return 6 * p + attn


def serve_flops_per_call(sz, batch: int, prompt: int, new: int) -> float:
    """Forward FLOPs of one generate call: the prefill of every prompt
    position (the head only at the last), then ``new - 1`` decode steps,
    each attending to the cache up to its own position."""
    mp = matmul_params(sz)
    body = sum(mp.values()) - mp["unembedding"]
    layers = sz["num_layers"]
    prefill = (2 * body * prompt + 2 * mp["unembedding"]
               + layers * attention_flops_fwd(sz, prompt * (prompt + 1) / 2))
    keys = sum(prompt + j + 1 for j in range(new - 1))
    decode = (2 * (body + mp["unembedding"]) * (new - 1)
              + layers * attention_flops_fwd(sz, keys))
    return batch * (prefill + decode)


def gemm_least_s(m: int, k: int, n: int, g: int, a_bytes: int, b_bytes: int,
                 out_bytes: int, peak: dict, *, scaled: bool,
                 wgrad: bool = False) -> float:
    """Least time of one grouped GEMM over ``m`` valid rows and ``g``
    groups: the larger of its FLOPs over the peak and its bytes over the
    HBM bandwidth.  A forward or dgrad GEMM reads A [m, k] and B [g, k, n]
    and writes [m, n]; a wgrad reads A [m, k] and dY [m, n] and writes
    [g, k, n].  ``scaled`` adds the 1x128 and 128x128 f32 scales of fp8
    operands."""
    flops = 2 * m * k * n
    if wgrad:
        nbytes = m * k * a_bytes + m * n * b_bytes + g * k * n * out_bytes
    else:
        nbytes = m * k * a_bytes + g * k * n * b_bytes + m * n * out_bytes
        if scaled:
            nbytes += (m * -(-k // SCALE_TILE)
                       + g * -(-k // SCALE_TILE) * -(-n // SCALE_TILE)) * F32
    return max(flops / peak["bf16_flops"], nbytes / peak["hbm_bytes_per_s"])


def _ffn_gemms(rows: int, groups: int, d: int, f: int):
    """(m, k, n, g) of the gate, up and down GEMMs of one SwiGLU FFN."""
    return [(rows, d, f, groups), (rows, d, f, groups), (rows, f, d, groups)]


def expert_gemm_least_s(sz, tokens: int, peak: dict, *,
                        backward: bool) -> float:
    """Least time of one pass's fp8 expert GEMMs over ``tokens`` tokens
    in every MoE layer: routed experts over their ``tokens * top_k`` valid
    rows (no row is dropped on one chip) and the shared experts (one group
    over ``tokens`` rows) when their widths allow fp8.  Forward: fp8
    operands, bf16 output.  With ``backward``: also the dgrad (fp8
    operands, f32 output) and the wgrad (bf16 operands, f32 output) of
    every GEMM, the configuration's training recipe."""
    m = sz["moe"]
    d, f = sz["d_model"], m["d_ff_expert"]
    fs = f * m["num_shared_experts"]
    ffns = [(tokens * m["top_k"], m["num_experts"], f)]
    if fs and d % 128 == 0 and fs % 128 == 0:
        ffns.append((tokens, 1, fs))
    total = 0.0
    for rows, groups, width in ffns:
        for mm, k, n, g in _ffn_gemms(rows, groups, d, width):
            total += gemm_least_s(mm, k, n, g, FP8, FP8, BF16, peak,
                                  scaled=True)
            if backward:
                total += gemm_least_s(mm, n, k, g, FP8, FP8, F32, peak,
                                      scaled=True)
                total += gemm_least_s(mm, k, n, g, BF16, BF16, F32, peak,
                                      scaled=False, wgrad=True)
    return total * _moe_layers(sz)
