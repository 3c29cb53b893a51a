"""Kernels: the ragged prefill kernel's share of its roofline: the least
time the causal query-key pairs and pages of the traced window's prefill
chunks need, over the kernel's device time."""


def read(ctx):
    return ctx.roofline_pct("ragged_prefill", ctx.prefill_work())
