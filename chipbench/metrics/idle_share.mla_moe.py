"""Device: share of the traced window in which no operation ran on the
device (mean over the chips used)."""


def read(ctx):
    return ctx.idle_pct()
