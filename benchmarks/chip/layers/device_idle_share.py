"""Share of the traced window in which no operation ran on the device:
100 * (1 - busy / window), busy being the union of the ``XLA Ops`` event
intervals inside the ``bench.window`` span, averaged over the chips."""


def read(ctx):
    t = ctx.trace
    if t is None or t.window_s <= 0 or t.busy_s <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)
