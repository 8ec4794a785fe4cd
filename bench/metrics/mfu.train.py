"""Model FLOPs of the tokens trained in the traced window over (window x
chips x bf16 peak): the whole step's share of the chip's peak."""
from bench import flops


def read(ctx):
    if ctx.kind != "train" or ctx.trace is None or not ctx.tokens:
        return None
    work = flops.train_flops_per_token(ctx.sizes, ctx.traffic["seq"]) \
        * ctx.tokens
    return 100.0 * work / (ctx.trace.window_s * ctx.chips
                           * ctx.peak["bf16_flops"])
