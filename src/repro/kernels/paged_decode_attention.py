"""Fused paged decode attention: the block table is walked IN-KERNEL.

The composed lowering (``models/attention.attn_decode_paged``) gathers a
dense ``(B, W*block_size, KV, hd)`` copy of the cache out of the pool and
then runs flash over it — on a bandwidth-bound decode step that reads the
cache twice (gather + flash) and burns HBM on the copy.  Here the gather
disappears: the grid iterates ``(batch, page)`` and the K/V
``BlockSpec`` index_maps index the *pool's block axis through the
scalar-prefetched block table* (``pltpu.PrefetchScalarGridSpec``), so each
page (every KV head of it) streams HBM→VMEM exactly once, straight from
the pool, and the whole decode step is ONE kernel.  Pages are lane-dense,
``(block_size, KV*D)``: a block of one page keeps the block's last two
dims equal to the pool's, which is what the TPU tiling accepts, and the
kernel loops the KV heads over lane slices of it.

Two variants share the flash-style running-softmax accumulator:

  - :func:`paged_decode_attention` — GQA/MHA over {"k","v"} pools, with
    the LOCAL_ATTN sliding-window mask (pages wholly outside
    ``[length - window, length)`` are skipped with ``pl.when``, never
    fetched... the index_map still names them, but masked-out pages cost
    a skipped grid step, not FLOPs);
  - :func:`paged_mla_decode_attention` — MLA absorbed-matmul decode over
    the latent pools: scores are ``q_lat·ckv + q_rope·krope`` and the
    value read-out is ``ckv`` itself (rank-R latents, per DeepSeek-V2).

``interpret=True`` is the CPU fallback used by tests and by fused serving
on non-TPU backends; parity against the ``ref.py`` oracles is asserted in
``tests/test_paged_kernels.py``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# GQA / MHA (ATTN, LOCAL_ATTN)
# ---------------------------------------------------------------------------
def _decode_kernel(tab_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                   acc_ref, m_ref, l_ref, *, bs, nw, kv, scale, window):
    b, w = pl.program_id(0), pl.program_id(1)

    @pl.when(w == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    length = len_ref[b]
    start = w * bs
    # page live: overlaps [max(0, length - window), length)
    live = start < length
    if window is not None:
        live &= start + bs > length - window

    @pl.when(live)
    def _compute():
        G = q_ref.shape[2]
        pos = start + jax.lax.broadcasted_iota(jnp.int32, (G, bs), 1)
        valid = pos < length
        if window is not None:
            valid &= pos >= length - window
        # one page row holds every KV head side by side on the lanes; the
        # G query heads of each KV head attend its (bs, D) lane slice
        D, Dv = q_ref.shape[3], o_ref.shape[3]
        for h in range(kv):
            q = q_ref[0, h]                           # (G, D)
            k = k_ref[0, :, h * D:(h + 1) * D]        # (bs, D)
            v = v_ref[0, :, h * Dv:(h + 1) * Dv]      # (bs, Dv)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32) * scale
            s = jnp.where(valid, s, NEG_INF)
            m_prev = m_ref[h]
            m_new = jnp.maximum(m_prev, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m_prev - m_new)
            l_ref[h] = l_ref[h] * corr + p.sum(axis=-1)
            pv = jax.lax.dot_general(p.astype(v.dtype), v,
                                     (((1,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            acc_ref[h] = acc_ref[h] * corr[..., None] + pv
            m_ref[h] = m_new

    @pl.when(w == nw - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...]
                    / jnp.maximum(l_ref[...], 1e-30)[..., None]
                    ).astype(o_ref.dtype)


def paged_decode_attention(
    q: jax.Array,             # (B, 1, H, D) — one query token per slot
    k_pool: jax.Array,        # (N_blocks, block_size, KV*D), lane-dense
    v_pool: jax.Array,        # (N_blocks, block_size, KV*Dv)
    block_tables: jax.Array,  # (B, W) int32; padding entries -> null block
    lengths: jax.Array,       # (B,) int32 valid positions (= pos + 1)
    *,
    block_size: int,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    interpret: bool = False,
) -> jax.Array:
    """Fused paged flash decode.  Returns (B, 1, H, Dv) in q.dtype."""
    B, _, H, D = q.shape
    KV = k_pool.shape[2] // D
    Dv = v_pool.shape[2] // KV
    assert H % KV == 0, (H, KV)
    G = H // KV
    W = block_tables.shape[1]
    scale = scale if scale is not None else D ** -0.5
    qh = q.reshape(B, KV, G, D)

    kernel = functools.partial(_decode_kernel, bs=block_size, nw=W, kv=KV,
                               scale=scale, window=window)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, W),                  # page axis innermost: sequential acc
        in_specs=[
            pl.BlockSpec((1, KV, G, D), lambda b, w, tab, lens: (b, 0, 0, 0)),
            # the in-kernel block-table walk: the pool's block axis is
            # indexed through the prefetched table, one whole page (all KV
            # heads, so the block's last two dims are the pool's) per step
            pl.BlockSpec((1, block_size, KV * D),
                         lambda b, w, tab, lens: (tab[b, w], 0, 0)),
            pl.BlockSpec((1, block_size, KV * Dv),
                         lambda b, w, tab, lens: (tab[b, w], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, KV, G, Dv),
                               lambda b, w, tab, lens: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((KV, G, Dv), jnp.float32),
            pltpu.VMEM((KV, G), jnp.float32),
            pltpu.VMEM((KV, G), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, G, Dv), q.dtype),
        name="paged_decode_attention",
        interpret=interpret,
    )(block_tables.astype(jnp.int32), lengths.astype(jnp.int32),
      qh, k_pool, v_pool)
    return out.reshape(B, 1, H, Dv)


# ---------------------------------------------------------------------------
# MLA absorbed-matmul decode (latent pools)
# ---------------------------------------------------------------------------
def _mla_kernel(tab_ref, len_ref, ql_ref, qr_ref, ckv_ref, kr_ref, o_ref,
                acc_ref, m_ref, l_ref, *, bs, nw, scale):
    b, w = pl.program_id(0), pl.program_id(1)

    @pl.when(w == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    length = len_ref[b]
    start = w * bs

    @pl.when(start < length)
    def _compute():
        ql = ql_ref[0]                        # (H, R)
        qr = qr_ref[0]                        # (H, r)
        ckv = ckv_ref[0]                      # (bs, R)
        kr = kr_ref[0]                        # (bs, r)
        s = (jax.lax.dot_general(ql, ckv, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
             + jax.lax.dot_general(qr, kr, (((1,), (1,)), ((), ())),
                                   preferred_element_type=jnp.float32)
             ) * scale                        # (H, bs)
        pos = start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(pos < length, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=-1)
        pv = jax.lax.dot_general(p.astype(ckv.dtype), ckv,
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * corr[..., None] + pv
        m_ref[...] = m_new

    @pl.when(w == nw - 1)
    def _finalize():
        o_ref[0] = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)[..., None]


def paged_mla_decode_attention(
    q_lat: jax.Array,          # (B, H, R) — W_uk-absorbed nope queries
    q_rope: jax.Array,         # (B, H, r) — rope queries
    ckv_pool: jax.Array,       # (N_blocks, block_size, R) latent pool
    krope_pool: jax.Array,     # (N_blocks, block_size, r) rope-key pool
    block_tables: jax.Array,   # (B, W) int32
    lengths: jax.Array,        # (B,) int32
    *,
    block_size: int,
    scale: float,
    interpret: bool = False,
) -> jax.Array:
    """Fused MLA paged decode.  Returns the latent read-out (B, H, R) f32
    (caller applies the absorbed ``W_uv`` and the output projection)."""
    B, H, R = q_lat.shape
    r = q_rope.shape[-1]
    W = block_tables.shape[1]

    kernel = functools.partial(_mla_kernel, bs=block_size, nw=W, scale=scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, W),
        in_specs=[
            pl.BlockSpec((1, H, R), lambda b, w, tab, lens: (b, 0, 0)),
            pl.BlockSpec((1, H, r), lambda b, w, tab, lens: (b, 0, 0)),
            pl.BlockSpec((1, block_size, R),
                         lambda b, w, tab, lens: (tab[b, w], 0, 0)),
            pl.BlockSpec((1, block_size, r),
                         lambda b, w, tab, lens: (tab[b, w], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, H, R), lambda b, w, tab, lens: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((H, R), jnp.float32),
            pltpu.VMEM((H,), jnp.float32),
            pltpu.VMEM((H,), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, R), jnp.float32),
        name="paged_mla_decode_attention",
        interpret=interpret,
    )(block_tables.astype(jnp.int32), lengths.astype(jnp.int32),
      q_lat, q_rope, ckv_pool, krope_pool)
