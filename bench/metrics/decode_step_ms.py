"""Device time per decode step: the engine's decode-loop program per
call, over the ``new - 1`` steps it runs."""


def read(ctx):
    if ctx.kind != "serve" or ctx.trace is None:
        return None
    hit = ctx.trace.module(ctx.programs["decode_loop"] + "(")
    if hit is None or not hit[0]:
        return None
    return 1e3 * hit[1] / hit[0] / (ctx.traffic["new"] - 1)
