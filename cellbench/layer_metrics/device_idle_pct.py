"""Device: share of the traced window in which no operation ran on the chip
(1 - union of device-operation intervals / window; device trace)."""


def read(ctx):
    return 100.0 * (1.0 - ctx.trace["busy_s"] / ctx.trace["window_s"])
