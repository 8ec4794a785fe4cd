"""Device ms a decode step in the MoE layer's shared experts: leaf ops of
the decode-loop program under the ``moe.shared`` scope, over
``calls x (new - 1)`` steps."""
from bench import scopes


def read(ctx):
    if ctx.kind != "serve" or not ctx.calls:
        return None
    spent = scopes.seconds_under(ctx, scopes.under("moe.shared"),
                                 "decode_loop")
    steps = ctx.calls * (ctx.traffic["new"] - 1)
    return None if spent is None else 1e3 * spent / steps
