"""Model FLOPs of the prompt and generated tokens of the generate calls
completed in the traced window over (window x chips x bf16 peak)."""
from bench import flops


def read(ctx):
    if ctx.kind != "serve" or ctx.trace is None or not ctx.calls:
        return None
    t = ctx.traffic
    work = flops.serve_flops_per_call(ctx.sizes, t["batch"], t["prompt"],
                                      t["new"]) * ctx.calls
    return 100.0 * work / (ctx.trace.window_s * ctx.chips
                           * ctx.peak["bf16_flops"])
