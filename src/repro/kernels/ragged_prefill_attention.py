"""Fused ragged batched-prefill attention over the paged KV pool.

The composed lowering (``models/attention.attn_prefill_paged``) gathers
every row's full table into a dense ``(P, W*block_size, KV, hd)`` copy
and runs a vmapped flash over it — every row pays for the *widest*
row's history, filler rows (scheduler padding, ``limit == 0``) pay full
price for garbage, and the pool is read twice (gather + flash).

This kernel consumes the scheduler's per-row ``(start, limit)`` vectors
directly.  The grid iterates ``(row, page)`` with the page axis walked
through the scalar-prefetched block table (one HBM→VMEM stream per page,
all KV heads at once, straight from the lane-dense pool; the kernel
loops the KV heads over lane slices), and ``pl.when`` skips the pages the
composed path merely masks:

  - dead rows (``limit == 0``) — filler never touches the MXU;
  - pages causally beyond the row's chunk (``w*bs > start + C - 1``);
  - pages wholly below the LOCAL_ATTN window.

Element masking inside a live page matches ``flash_rows`` exactly:
query position ``qp = start + c`` attends key position ``kp = w*bs + i``
iff ``kp <= qp`` (and ``qp - kp < window`` when windowed).  Freed /
padding table entries point at the null block, whose positions are
always causally or window-masked — the same invariant the composed path
relies on.  Dead rows emit zeros (their outputs are discarded upstream).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _prefill_kernel(tab_ref, start_ref, limit_ref, q_ref, k_ref, v_ref,
                    o_ref, acc_ref, m_ref, l_ref, *, bs, nw, kv, scale,
                    window):
    p_, w = pl.program_id(0), pl.program_id(1)

    @pl.when(w == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    start = start_ref[p_]
    limit = limit_ref[p_]
    G, C, D = q_ref.shape[2:]
    # page live: row is real work AND some key in the page is visible to
    # some query (causal upper bound; window lower bound)
    live = (limit > 0) & (w * bs <= start + C - 1)
    if window is not None:
        live &= (w + 1) * bs > start - window + 1

    @pl.when(live)
    def _compute():
        qp = start + jax.lax.broadcasted_iota(jnp.int32, (C, bs), 0)
        kp = w * bs + jax.lax.broadcasted_iota(jnp.int32, (C, bs), 1)
        valid = kp <= qp
        if window is not None:
            valid &= qp - kp < window
        # one page row holds every KV head side by side on the lanes; each
        # head's G query heads are stacked on the row axis, (G, C, D) ->
        # (G*C, D), for one matmul against its (bs, D) lane slice
        Dv = o_ref.shape[4]
        for h in range(kv):
            q = q_ref[0, h].reshape(G * C, D)
            k = k_ref[0, :, h * D:(h + 1) * D]        # (bs, D)
            v = v_ref[0, :, h * Dv:(h + 1) * Dv]      # (bs, Dv)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32).reshape(G, C, bs) * scale
            s = jnp.where(valid[None], s, NEG_INF)
            m_prev = m_ref[h]                 # (G, C)
            m_new = jnp.maximum(m_prev, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m_prev - m_new)
            l_ref[h] = l_ref[h] * corr + p.sum(axis=-1)
            pv = jax.lax.dot_general(
                p.reshape(G * C, bs).astype(v.dtype), v,
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32).reshape(G, C, -1)
            acc_ref[h] = acc_ref[h] * corr[..., None] + pv
            m_ref[h] = m_new

    @pl.when(w == nw - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...]
                    / jnp.maximum(l_ref[...], 1e-30)[..., None]
                    ).astype(o_ref.dtype)


def ragged_prefill_attention(
    q: jax.Array,             # (P, C, H, D) — one prompt chunk per row
    k_pool: jax.Array,        # (N_blocks, block_size, KV*D), lane-dense
    v_pool: jax.Array,        # (N_blocks, block_size, KV*Dv)
    block_tables: jax.Array,  # (P, W) int32
    starts: jax.Array,        # (P,) int32 absolute position of chunk col 0
    limits: jax.Array,        # (P,) int32 true prompt length; 0 = filler
    *,
    block_size: int,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    interpret: bool = False,
) -> jax.Array:
    """Fused ragged chunked-prefill flash.  Returns (P, C, H, Dv)."""
    P, C0, H, D = q.shape
    KV = k_pool.shape[2] // D
    Dv = v_pool.shape[2] // KV
    assert H % KV == 0, (H, KV)
    G = H // KV
    W = block_tables.shape[1]
    scale = scale if scale is not None else D ** -0.5
    # chunk rows reshape (G, C, D) -> (G*C, D) in-kernel: pad C to 16s
    # (packed bf16); padded rows are queries past the chunk, dropped below
    C = -(-C0 // 16) * 16
    if C > C0:
        q = jnp.pad(q, ((0, 0), (0, C - C0), (0, 0), (0, 0)))
    qh = q.reshape(P, C, KV, G, D).transpose(0, 2, 3, 1, 4)  # (P,KV,G,C,D)

    kernel = functools.partial(_prefill_kernel, bs=block_size, nw=W, kv=KV,
                               scale=scale, window=window)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(P, W),
        in_specs=[
            pl.BlockSpec((1, KV, G, C, D),
                         lambda p, w, tab, st, lm: (p, 0, 0, 0, 0)),
            pl.BlockSpec((1, block_size, KV * D),
                         lambda p, w, tab, st, lm: (tab[p, w], 0, 0)),
            pl.BlockSpec((1, block_size, KV * Dv),
                         lambda p, w, tab, st, lm: (tab[p, w], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, KV, G, C, Dv),
                               lambda p, w, tab, st, lm: (p, 0, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((KV, G, C, Dv), jnp.float32),
            pltpu.VMEM((KV, G, C), jnp.float32),
            pltpu.VMEM((KV, G, C), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((P, KV, G, C, Dv), q.dtype),
        name="ragged_prefill_attention",
        interpret=interpret,
    )(block_tables.astype(jnp.int32), starts.astype(jnp.int32),
      limits.astype(jnp.int32), qh, k_pool, v_pool)
    return out.transpose(0, 3, 1, 2, 4).reshape(P, C, H, Dv)[:, :C0]
