"""Pallas TPU flash attention (forward), GQA + causal + sliding window.

Grid: (B*KV, num_q_blocks, num_kv_blocks), kv innermost (sequential on
TPU).  Running (acc, m, l) live in VMEM scratch; out is written on the
last kv step.  Block sizes are MXU-aligned (q/k blocks multiples of 128
where the shape allows) and sized so the working set
(q + k + v + acc ~ G*bq*D + 2*bk*D + G*bq*Dv floats) fits VMEM.

Causal block skipping: kv blocks entirely above the diagonal are skipped
with ``pl.when`` (no MXU work), matching the oracle's semantics exactly.

Differentiable: the backward is the VJP of
:func:`repro.kernels.ref.flash_attention` recomputed from the saved q/k/v
(:func:`repro.kernels.oracle_vjp.oracle_vjp`).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import ref
from repro.kernels.oracle_vjp import oracle_vjp
from repro.kernels.tiling import padded_block

NEG_INF = -1e30


def _kernel(off_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            bq, bk, nk, G, causal, window, scale, kv_len):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_start = off_ref[0] + qi * bq
    k_start = ki * bk
    # block-level skips: kv blocks fully above the diagonal (causal) or
    # fully outside every query's window contribute nothing
    live = jnp.bool_(True)
    if causal:
        live &= q_start + bq - 1 >= k_start
    if window is not None:
        live &= q_start - (k_start + bk - 1) < window
    if kv_len is not None:                 # keys padded past kv_len
        live &= k_start < kv_len

    @pl.when(live)
    def _compute():
        q = q_ref[0]                       # (G, bq, D)
        k = k_ref[0]                       # (bk, D)
        v = v_ref[0]                       # (bk, Dv)
        s = jax.lax.dot_general(
            q.reshape(-1, q.shape[-1]), k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32).reshape(G, bq, bk) * scale
        qp = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kp = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = jnp.ones((bq, bk), dtype=bool)
        if causal:
            mask &= qp >= kp
        if window is not None:
            mask &= qp - kp < window
        if kv_len is not None:
            mask &= kp < kv_len
        s = jnp.where(mask[None], s, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=-1)
        pv = jax.lax.dot_general(
            p.reshape(-1, bk).astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).reshape(G, bq, -1)
        acc_ref[...] = acc_ref[...] * corr[..., None] + pv
        m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _finish():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)[..., None]).astype(o_ref.dtype)


def _forward(q, k, v, q_offset, *, causal, window, scale, block_q, block_k,
             interpret):
    B, Sq0, H, Dk = q.shape
    Sk0, KV, Dv = k.shape[1], k.shape[2], v.shape[3]
    G = H // KV
    # rows reshape (G, bq, D) -> (G*bq, D) in-kernel: 16-row aligned for
    # packed bf16; lengths with no such divisor are padded, keys masked
    bq, Sq = padded_block(Sq0, block_q, 16)
    bk, Sk = padded_block(Sk0, block_k, 16)
    nq, nk = Sq // bq, Sk // bk
    if Sq > Sq0:
        q = jnp.pad(q, ((0, 0), (0, Sq - Sq0), (0, 0), (0, 0)))
    if Sk > Sk0:
        pad = ((0, 0), (0, Sk - Sk0), (0, 0), (0, 0))
        k, v = jnp.pad(k, pad), jnp.pad(v, pad)

    qh = q.reshape(B, Sq, KV, G, Dk).transpose(0, 2, 3, 1, 4) \
        .reshape(B * KV, G, Sq, Dk)
    kh = k.transpose(0, 2, 1, 3).reshape(B * KV, Sk, Dk)
    vh = v.transpose(0, 2, 1, 3).reshape(B * KV, Sk, Dv)

    kernel = functools.partial(_kernel, bq=bq, bk=bk, nk=nk, G=G,
                               causal=causal, window=window, scale=scale,
                               kv_len=Sk0 if Sk > Sk0 else None)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,           # q_offset: an operand, may be traced
        grid=(B * KV, nq, nk),
        in_specs=[
            pl.BlockSpec((1, G, bq, Dk), lambda b, qi, ki, off: (b, 0, qi, 0)),
            pl.BlockSpec((1, bk, Dk), lambda b, qi, ki, off: (b, ki, 0)),
            pl.BlockSpec((1, bk, Dv), lambda b, qi, ki, off: (b, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, G, bq, Dv),
                               lambda b, qi, ki, off: (b, 0, qi, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, bq, Dv), jnp.float32),
            pltpu.VMEM((G, bq), jnp.float32),
            pltpu.VMEM((G, bq), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B * KV, G, Sq, Dv), q.dtype),
        name="flash_attention",
        interpret=interpret,
    )(jnp.reshape(q_offset, (1,)), qh, kh, vh)
    return (out.reshape(B, KV, G, Sq, Dv).transpose(0, 3, 1, 2, 4)
            .reshape(B, Sq, H, Dv)[:, :Sq0])


def flash_attention(q, k, v, *, causal=True, window: Optional[int] = None,
                    q_offset=0, scale: Optional[float] = None,
                    block_q: int = 256, block_k: int = 256,
                    interpret: bool = False):
    """q: (B,Sq,H,Dk), k/v: (B,Sk,KV,D*) -> (B,Sq,H,Dv).

    ``q_offset`` (absolute position of q[0]) may be a traced scalar: it is
    passed to the kernel as a scalar-prefetch operand, never closed over.
    """
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    kernel = functools.partial(_forward, causal=causal, window=window,
                               scale=scale, block_q=block_q, block_k=block_k,
                               interpret=interpret)

    def oracle(q, k, v, q_offset):
        return ref.flash_attention(q, k, v, causal=causal, window=window,
                                   q_offset=q_offset, scale=scale)
    return oracle_vjp(kernel, oracle)(q, k, v,
                                      jnp.asarray(q_offset, jnp.int32))
