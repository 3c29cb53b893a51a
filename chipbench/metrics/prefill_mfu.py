"""Steps: model FLOPs of the prompt tokens prefilled in the traced window,
over the window's length times the chips' peak."""


def read(ctx):
    return ctx.mfu_pct(ctx.model_flops()["prefill"])
