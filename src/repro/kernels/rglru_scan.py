"""Pallas TPU RG-LRU gated linear recurrence.

Grid: (B, num_channel_blocks, num_seq_blocks), seq blocks innermost; the
hidden state (1, bw) rides in VMEM scratch, seeded from ``init_state``.
Within a block the recurrence h_t = a_t h_{t-1} + b_t is an inclusive
scan over the (a, b) monoid, done as a Hillis-Steele tree of sublane
rolls: log2(block) rounds of vector-unit work, no sequential loop.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import ref
from repro.kernels.oracle_vjp import oracle_vjp
from repro.kernels.tiling import block


def _neg_expm1(x):
    """-expm1(x) for x <= 0 via tanh, which Mosaic lowers (expm1 it does
    not): e^x - 1 = 2t / (1 - t) with t = tanh(x/2), no cancellation."""
    t = jnp.tanh(0.5 * x)
    return -2.0 * t / (1.0 - t)


def _kernel(x_ref, ig_ref, ag_ref, la_ref, h0_ref, h_ref, fin_ref, s_ref, *,
            bs, ns, c):
    si = pl.program_id(2)

    @pl.when(si == 0)
    def _init():
        s_ref[...] = h0_ref[0].astype(jnp.float32)

    x = x_ref[0].astype(jnp.float32)           # (bs, W)
    ig = ig_ref[0].astype(jnp.float32)
    ag = ag_ref[0].astype(jnp.float32)
    log_a = la_ref[0].astype(jnp.float32)      # (W,) broadcast row

    log_at = c * log_a * ag                    # (bs, W)
    a_t = jnp.exp(log_at)
    beta = jnp.sqrt(_neg_expm1(2.0 * log_at))
    b_t = beta * (ig * x)
    row = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    # fold carried state into the first row
    b_t = jnp.where(row == 0, b_t + a_t * s_ref[...], b_t)

    # Hillis-Steele inclusive scan over the (a, b) monoid: log2(bs) rounds
    # of a sublane roll; rows t < d combine with the identity (1, 0)
    a, h = a_t, b_t
    d = 1
    while d < bs:
        keep = row >= d
        a_prev = jnp.where(keep, pltpu.roll(a, d, 0), 1.0)
        h_prev = jnp.where(keep, pltpu.roll(h, d, 0), 0.0)
        h = h_prev * a + h
        a = a_prev * a
        d *= 2
    h_ref[0] = h.astype(h_ref.dtype)
    s_ref[...] = h[-1:]

    @pl.when(si == ns - 1)
    def _finish():
        fin_ref[0] = h[-1:].astype(fin_ref.dtype)


def rglru_scan(x, input_gate, a_gate, log_a, *, init_state=None, c: float = 8.0,
               block_s: int = 256, block_w: int = 512,
               interpret: bool = False):
    """x/input_gate/a_gate: (B,S,W); log_a: (W,); init_state: (B,W) or None
    (zeros) -> (h (B,S,W), final (B,W)).  Differentiable through the
    oracle (:func:`repro.kernels.oracle_vjp.oracle_vjp`)."""
    def oracle(x, ig, ag, la, h0):
        return ref.rglru_scan(x, ig, ag, la, init_state=h0, c=c)
    kernel = functools.partial(_scan, c=c, block_s=block_s, block_w=block_w,
                               interpret=interpret)
    return oracle_vjp(kernel, oracle)(x, input_gate, a_gate, log_a,
                                      init_state)


def _scan(x, input_gate, a_gate, log_a, init_state, *, c, block_s, block_w,
          interpret):
    B, S, W = x.shape
    bs = block(S, block_s)
    ns = S // bs
    bw = block(W, block_w)
    if bw % 128:                   # lane tiles: 128-multiples or all of W
        bw = W
    la = log_a.reshape(1, W)
    h0 = (jnp.zeros((B, 1, W), jnp.float32) if init_state is None
          else init_state.astype(jnp.float32).reshape(B, 1, W))

    kernel = functools.partial(_kernel, bs=bs, ns=ns, c=c)
    h, fin = pl.pallas_call(
        kernel,
        grid=(B, W // bw, ns),     # channels are independent; seq innermost
        in_specs=[
            pl.BlockSpec((1, bs, bw), lambda b, w, si: (b, si, w)),
            pl.BlockSpec((1, bs, bw), lambda b, w, si: (b, si, w)),
            pl.BlockSpec((1, bs, bw), lambda b, w, si: (b, si, w)),
            pl.BlockSpec((1, bw), lambda b, w, si: (0, w)),
            pl.BlockSpec((1, 1, bw), lambda b, w, si: (b, 0, w)),
        ],
        out_specs=[
            pl.BlockSpec((1, bs, bw), lambda b, w, si: (b, si, w)),
            pl.BlockSpec((1, 1, bw), lambda b, w, si: (b, 0, w)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, W), x.dtype),
            jax.ShapeDtypeStruct((B, 1, W), x.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((1, bw), jnp.float32)],
        name="rglru_scan",
        interpret=interpret,
    )(x, input_gate, a_gate, la, h0)
    return h, fin.reshape(B, W)
