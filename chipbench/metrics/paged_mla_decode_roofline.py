"""Kernels: the paged MLA decode kernel's share of its roofline: the least
time the live latent pages of the traced window's decode calls need
(``costs_mla_moe.paged_mla_decode``), over the kernel's device time."""
from chipbench.layers_mla_moe import roofline_pct


def read(ctx):
    return roofline_pct(ctx, "paged_mla_decode", "mla_decode_work")
