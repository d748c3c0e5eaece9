"""idle_share: the share of the traced window in which no program ran
on a device, averaged over the cell's devices."""


def read(ctx):
    return 100.0 * (1.0 - ctx.reduced.mean_busy_s / ctx.reduced.window_s)
