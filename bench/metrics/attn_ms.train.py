"""Device ms a training step in attention: leaf ops under the ``attn``
scope (QKV, the chunked attention, the output projection), forward,
backward and recomputation."""
from bench import scopes


def read(ctx):
    if ctx.kind != "train" or not ctx.steps:
        return None
    spent = scopes.seconds_under(ctx, scopes.under("attn"),
                                 "step")
    return None if spent is None else 1e3 * spent / ctx.steps
