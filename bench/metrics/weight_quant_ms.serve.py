"""Device ms a decode step spent quantizing weights: leaf ops of the
decode-loop program under ``quant.weights`` (the f32 upcast and 128x128
fp8 quantization of every expert and dense weight, each step), over
``calls x (new - 1)`` steps."""
from bench import scopes


def read(ctx):
    if ctx.kind != "serve" or not ctx.calls:
        return None
    spent = scopes.seconds_under(ctx, scopes.under("quant.weights"),
                                 "decode_loop")
    steps = ctx.calls * (ctx.traffic["new"] - 1)
    return None if spent is None else 1e3 * spent / steps
