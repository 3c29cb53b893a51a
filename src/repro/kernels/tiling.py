"""Block sizes the TPU tiling accepts.

A block's second-minor dimension must be a multiple of 8 (16 for packed
bf16 rows reshaped in-kernel) or the whole array dimension.  Kernels
whose lengths come from callers (sequence or cache length) take the
largest power-of-two fraction of the preferred block that divides the
length, and pad the length to the alignment only when none does.
"""
from __future__ import annotations


def block(n: int, want: int) -> int:
    """The largest of ``want, want/2, ...`` (capped at ``n``) dividing ``n``."""
    b = min(want, n)
    while n % b:
        b //= 2
    return max(b, 1)


def padded_block(n: int, want: int, align: int) -> tuple[int, int]:
    """``(block, padded_n)``: a block that is a multiple of ``align`` and
    divides ``padded_n >= n``; ``padded_n == n`` whenever ``n`` has such
    a divisor (``want`` is a power of two no smaller than ``align``)."""
    b = block(n, want)
    if b % align == 0:
        return b, n
    n = -(-n // align) * align
    return block(n, want), n
