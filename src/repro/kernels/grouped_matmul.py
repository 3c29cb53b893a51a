"""Pallas TPU ragged grouped matmul (MoE expert compute, megablocks-style).

``out[t] = x[t] @ w[expert_of(t)]`` for ``x`` sorted by expert with
``group_sizes`` giving each expert's contiguous row count.

Grid: (num_token_tiles, num_F_tiles, E) with the expert dim innermost so
each output tile accumulates across its (at most two) overlapping experts
and is then final — the canonical TPU accumulation pattern.  Group offsets arrive via
scalar prefetch (SMEM); (tile, expert) pairs with no row overlap are
skipped with ``pl.when``, so MXU work is proportional to actual tokens,
not E*T.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tiling import block


def _kernel(offs_ref, x_ref, w_ref, o_ref, acc_ref, *, bt, E):
    t = pl.program_id(0)
    e = pl.program_id(2)

    @pl.when(e == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    start = offs_ref[e]
    end = offs_ref[e + 1]
    t0 = t * bt

    @pl.when(jnp.logical_and(start < t0 + bt, end > t0))
    def _compute():
        x = x_ref[...]                                # (bt, D)
        w = w_ref[0]                                  # (D, bf)
        rows = t0 + jax.lax.broadcasted_iota(jnp.int32, (bt, 1), 0)
        mask = (rows >= start) & (rows < end)         # (bt, 1)
        xm = jnp.where(mask, x, 0)
        acc_ref[...] += jax.lax.dot_general(
            xm, w, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(e == E - 1)
    def _finish():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def grouped_matmul(x, w, group_sizes, *, block_t: int = 256,
                   block_f: int = 512, interpret: bool = False):
    """x: (T, D) sorted by expert; w: (E, D, F); group_sizes: (E,) -> (T, F)."""
    T, D = x.shape
    E, _, F = w.shape
    bt = block(T, block_t)
    nt = T // bt
    # an expert's whole (D, F) weight overflows VMEM at real widths: tile F
    # in lane multiples (or take all of it when F has no such divisor)
    bf = block(F, block_f)
    if bf % 128:
        bf = F
    offs = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                            jnp.cumsum(group_sizes.astype(jnp.int32))])

    kernel = functools.partial(_kernel, bt=bt, E=E)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nt, F // bf, E),
        in_specs=[
            pl.BlockSpec((bt, D), lambda t, f, e, offs: (t, 0)),
            pl.BlockSpec((1, D, bf), lambda t, f, e, offs: (e, 0, f)),
        ],
        out_specs=pl.BlockSpec((bt, bf), lambda t, f, e, offs: (t, f)),
        scratch_shapes=[pltpu.VMEM((bt, bf), jnp.float32)],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((T, F), x.dtype),
        name="grouped_matmul",
        interpret=interpret,
    )(offs, x, w)
    return out
