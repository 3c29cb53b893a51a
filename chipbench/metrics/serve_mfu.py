"""Steps: model FLOPs of every prompt and generated token of the traced
window, over the window's length times the chips' peak."""


def read(ctx):
    return ctx.mfu_pct(sum(ctx.model_flops().values()))
