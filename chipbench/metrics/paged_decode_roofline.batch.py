"""Kernels: the paged decode kernel's share of its roofline: the least
time the live pages of the traced window's decode calls need, over the
kernel's device time."""


def read(ctx):
    return ctx.roofline_pct("paged_decode", ctx.decode_work())
