"""Device ms a training step in the MoE layer: leaf ops under any
``moe.*`` scope (route, pack, routed experts with their quantizes,
combine, shared experts), forward, backward and recomputation."""
from bench import scopes


def read(ctx):
    if ctx.kind != "train" or not ctx.steps:
        return None
    spent = scopes.seconds_under(ctx, scopes.under("moe"),
                                 "step")
    return None if spent is None else 1e3 * spent / ctx.steps
