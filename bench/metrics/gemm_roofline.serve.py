"""Least time of the generate calls' fp8 expert GEMMs (prefill over every
prompt token, then one decode step per generated token after the first)
over the device time of the kernels that run them, in the traced window."""
from bench import flops
from bench.kernels import expert_kernel_seconds


def read(ctx):
    if ctx.kind != "serve" or ctx.trace is None or not ctx.calls:
        return None
    t = ctx.traffic
    per_call = (flops.expert_gemm_least_s(ctx.sizes, t["batch"] * t["prompt"],
                                          ctx.peak, backward=False)
                + (t["new"] - 1) * flops.expert_gemm_least_s(
                    ctx.sizes, t["batch"], ctx.peak, backward=False))
    spent = expert_kernel_seconds(ctx.trace, ctx.gemm_ops)
    if not spent:
        return None
    return 100.0 * per_call * ctx.calls / spent
