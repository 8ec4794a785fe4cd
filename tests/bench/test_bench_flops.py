"""Model FLOPs and the expert GEMMs' least work of both configurations,
against values worked out by hand."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import flops, peaks, spec  # noqa: E402

V5E = peaks.peak("TPU v5 lite")


def _sizes(name):
    return spec.sizes(spec.model_config(
        spec._load(os.path.join(ROOT, "bench", "configs", name + ".json")),
        "pallas"))


@pytest.mark.parametrize("name, parts", [
    ("deepseek-moe-16b-l2", {
        # 2 layers x 2048 x 128 x (16 + 16 + 16 + 16)
        "attention": 33_554_432,
        "dense_ffn": 3 * 2048 * 10944,          # 67_239_936
        "router": 2048 * 64,
        "routed_experts": 3 * 2048 * 1408 * 6,  # 51_904_512
        "shared_experts": 3 * 2048 * 1408 * 2,  # 17_301_504
        "unembedding": 2048 * 12800}),
    ("qwen1.5-moe-a2.7b-l1", {
        "attention": 16_777_216,
        "dense_ffn": 0,
        "router": 2048 * 60,
        "routed_experts": 3 * 2048 * 1408 * 4,  # 34_603_008
        "shared_experts": 3 * 2048 * 5632,      # 34_603_008
        "unembedding": 2048 * 18992}),
])
def test_matmul_params(name, parts):
    assert flops.matmul_params(_sizes(name)) == parts


def test_train_flops_per_token_deepseek():
    # 6 x 196_345_856 matmul params + 2 layers x 3 x 4 x 16 x 128 x 4097/2
    want = 6 * 196_345_856 + 2 * 3 * 8192 * 2048.5
    assert flops.train_flops_per_token(_sizes("deepseek-moe-16b-l2"),
                                       4096) == pytest.approx(want, rel=1e-12)


def test_serve_flops_per_call_small():
    sz = _sizes("deepseek-moe-16b-l2")
    body = 196_345_856 - 2048 * 12800
    # one prompt of 2 then 2 new tokens: prefill of 2 positions (the head
    # at the last only) attending to 1 + 2 keys, one decode step at
    # position 2 attending to 3 keys
    prefill = 2 * body * 2 + 2 * 2048 * 12800 + 2 * 8192 * 3
    decode = 2 * 196_345_856 + 2 * 8192 * 3
    assert flops.serve_flops_per_call(sz, 1, 2, 2) == pytest.approx(
        prefill + decode, rel=1e-12)


def test_gemm_least_compute_bound():
    # routed gate GEMM of a 1 x 4096 train step: 24576 rows over 64 experts
    flop = 2 * 24576 * 2048 * 1408
    t = flops.gemm_least_s(24576, 2048, 1408, 64, 1, 1, 2, V5E, scaled=True)
    assert t == pytest.approx(flop / 197e12, rel=1e-12)


def test_gemm_least_memory_bound():
    # a decode step's gate GEMM: 256 x 6 rows; the 64 experts' fp8 weights
    # dominate: A 1536 x 2048, B 64 x 2048 x 1408, out 1536 x 1408 bf16,
    # scales (1536 x 16 + 64 x 16 x 11) f32
    nbytes = (1536 * 2048 + 64 * 2048 * 1408 + 1536 * 1408 * 2
              + (1536 * 16 + 64 * 16 * 11) * 4)
    assert nbytes == 192_163_840
    t = flops.gemm_least_s(1536, 2048, 1408, 64, 1, 1, 2, V5E, scaled=True)
    assert t == pytest.approx(nbytes / 819e9, rel=1e-12)


def test_wgrad_least_bytes():
    # bf16 x [m, k] and dy [m, n], f32 dW [g, k, n]; m small: memory bound
    t = flops.gemm_least_s(8, 256, 128, 4, 2, 2, 4, V5E, scaled=False,
                           wgrad=True)
    assert t == pytest.approx((8 * 256 * 2 + 8 * 128 * 2 + 4 * 256 * 128 * 4)
                              / 819e9, rel=1e-12)


def test_expert_gemm_least_counts_every_gemm():
    sz = _sizes("deepseek-moe-16b-l2")
    fwd = flops.expert_gemm_least_s(sz, 4096, V5E, backward=False)
    both = flops.expert_gemm_least_s(sz, 4096, V5E, backward=True)
    # forward: routed (24576 rows, 64 groups) + shared (4096 rows, width
    # 2816, one group); gate and up 2048 -> f, down f -> 2048
    want = 0.0
    for rows, g, f in ((24576, 64, 1408), (4096, 1, 2816)):
        for k, n in ((2048, f), (2048, f), (f, 2048)):
            want += flops.gemm_least_s(rows, k, n, g, 1, 1, 2, V5E,
                                       scaled=True)
    assert fwd == pytest.approx(want, rel=1e-12)
    assert both > 2.9 * fwd
