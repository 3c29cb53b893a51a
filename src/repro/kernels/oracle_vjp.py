"""Differentiating a Pallas kernel through its oracle.

``pallas_call`` has no autodiff rule, so a training step through a bare
kernel fails to differentiate on TPU.  :func:`oracle_vjp` pairs a kernel
with its :mod:`repro.kernels.ref` oracle: the forward runs the kernel,
the backward is the oracle's VJP recomputed from the saved inputs (XLA,
not a fused backward kernel).
"""
from __future__ import annotations

import jax


def oracle_vjp(kernel, oracle):
    """``kernel`` forward, ``oracle``'s VJP backward.

    Both take the same positional arguments (arrays, or None for an
    absent optional input) and return the same structure; static
    parameters are bound before the call.
    """
    f = jax.custom_vjp(kernel)
    f.defvjp(lambda *args: (kernel(*args), args),
             lambda args, g: jax.vjp(oracle, *args)[1](g))
    return f
