"""Device ms a training step in the MoE layer's shared experts: leaf ops
under the ``moe.shared`` scope (their GEMMs with the weight and
activation quantizes inside it), forward, backward and recomputation."""
from bench import scopes


def read(ctx):
    if ctx.kind != "train" or not ctx.steps:
        return None
    spent = scopes.seconds_under(ctx, scopes.under("moe.shared"),
                                 "step")
    return None if spent is None else 1e3 * spent / ctx.steps
