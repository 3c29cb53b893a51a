"""Steps: model FLOPs of the tokens generated in the traced window, over
the window's length times the chips' peak."""


def read(ctx):
    return ctx.mfu_pct(ctx.model_flops()["decode"])
