"""Share of the traced window in which no op ran on the device (mean
over the chips), in a training cell."""


def read(ctx):
    if ctx.kind != "train" or ctx.trace is None:
        return None
    return 100.0 * (1.0 - ctx.trace.busy_s / ctx.trace.window_s)
