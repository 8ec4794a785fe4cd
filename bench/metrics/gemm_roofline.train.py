"""Least time of the training steps' fp8 expert GEMMs (routed and shared;
forward, dgrad and wgrad) over the device time of the kernels that run
them, in the traced window."""
from bench import flops
from bench.kernels import expert_kernel_seconds


def read(ctx):
    if ctx.kind != "train" or ctx.trace is None or not ctx.steps:
        return None
    tokens = ctx.traffic["batch"] * ctx.traffic["seq"]
    least = flops.expert_gemm_least_s(ctx.sizes, tokens, ctx.peak,
                                      backward=True) * ctx.steps
    spent = expert_kernel_seconds(ctx.trace, ctx.gemm_ops)
    if not spent:
        return None
    return 100.0 * least / spent
