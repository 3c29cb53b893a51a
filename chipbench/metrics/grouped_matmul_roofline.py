"""Kernels: the grouped-matmul kernel's share of its roofline: the least
time the held experts' routes of the traced window's step programs need
(``costs_mla_moe.grouped_matmul``, from the route counts the programs
return), over the kernel's device time."""
from chipbench.layers_mla_moe import roofline_pct


def read(ctx):
    return roofline_pct(ctx, "grouped_matmul", "grouped_matmul_work")
