"""Device time per call of the engine's prefill program."""


def read(ctx):
    if ctx.kind != "serve" or ctx.trace is None:
        return None
    hit = ctx.trace.module(ctx.programs["prefill"] + "(")
    if hit is None or not hit[0]:
        return None
    return 1e3 * hit[1] / hit[0]
