"""Pallas TPU decode attention (flash-decode): one query vs a long KV cache.

Grid: (B*KV, num_s_blocks) with the cache-length dim innermost
(sequential); running (acc, m, l) scratch in VMEM; per-row lengths are
scalar-prefetched into SMEM.  Cache blocks stream HBM->VMEM once each —
decode is bandwidth-bound, so the kernel's job is simply to keep the
cache read contiguous and avoid materialising (G, S) score tensors in
f32 in HBM.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tiling import padded_block

NEG_INF = -1e30


def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            bs, ns, scale, window):
    b, si = pl.program_id(0), pl.program_id(1)

    @pl.when(si == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    length = len_ref[b]
    start = si * bs
    # block live: overlaps [max(0, length - window), length)
    live = start < length
    if window is not None:
        live &= start + bs > length - window

    @pl.when(live)
    def _compute():
        q = q_ref[0]                                  # (G, D)
        k = k_ref[0]                                  # (bs, D)
        v = v_ref[0]                                  # (bs, Dv)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        pos = start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        valid = pos < length
        if window is not None:
            valid &= pos >= length - window
        s = jnp.where(valid, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=-1)
        pv = jax.lax.dot_general(p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * corr[..., None] + pv
        m_ref[...] = m_new

    @pl.when(si == ns - 1)
    def _finish():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)[..., None]).astype(o_ref.dtype)


def decode_attention(q, k_cache, v_cache, length, *, scale=None,
                     window: Optional[int] = None, block_s: int = 512,
                     interpret: bool = False):
    """q: (B,1,H,Dk); k/v_cache: (B,S,KV,D*); length: (B,) -> (B,1,H,Dv).

    Cache rows are absolute positions; ``window`` masks keys below
    ``length - window`` (sliding-window attention over a paged gather).
    """
    B, _, H, Dk = q.shape
    S0, KV, Dv = k_cache.shape[1], k_cache.shape[2], v_cache.shape[3]
    G = H // KV
    scale = scale if scale is not None else Dk ** -0.5
    # cache blocks: 8-row aligned; padded rows sit beyond every length
    bs, S = padded_block(S0, block_s, 8)
    ns = S // bs
    if S > S0:
        pad = ((0, 0), (0, S - S0), (0, 0), (0, 0))
        k_cache, v_cache = jnp.pad(k_cache, pad), jnp.pad(v_cache, pad)

    qh = q.reshape(B, KV, G, Dk).reshape(B * KV, G, Dk)
    kh = k_cache.transpose(0, 2, 1, 3).reshape(B * KV, S, Dk)
    vh = v_cache.transpose(0, 2, 1, 3).reshape(B * KV, S, Dv)
    lens = jnp.repeat(length.astype(jnp.int32), KV)

    kernel = functools.partial(_kernel, bs=bs, ns=ns, scale=scale,
                               window=window)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,                       # lens, read in SMEM
        grid=(B * KV, ns),
        in_specs=[
            pl.BlockSpec((1, G, Dk), lambda b, si, lens: (b, 0, 0)),
            pl.BlockSpec((1, bs, Dk), lambda b, si, lens: (b, si, 0)),
            pl.BlockSpec((1, bs, Dv), lambda b, si, lens: (b, si, 0)),
        ],
        out_specs=pl.BlockSpec((1, G, Dv), lambda b, si, lens: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, Dv), jnp.float32),
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G,), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B * KV, G, Dv), q.dtype),
        name="decode_attention",
        interpret=interpret,
    )(lens, qh, kh, vh)
    return out.reshape(B, KV, G, Dv).reshape(B, 1, H, Dv)
