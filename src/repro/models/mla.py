"""Multi-head Latent Attention (DeepSeek-V2, arXiv:2405.04434).

Prefill/train use the decompressed form through the shared flash-attention
kernel.  Decode uses the *absorbed-matmul* form: the KV cache stores only
the compressed latent ``c_kv`` (kv_lora_rank) plus the shared RoPE key, and
``W_uk``/``W_uv`` are absorbed into the query/output projections — the
memory saving that makes MLA serve long contexts cheaply.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import ops
from repro.models.attention import (flash_rows, full_attention, layer_pages,
                                    paged_chunk_indices, unflatten_pages)
from repro.models.common import (apply_rope, dense_init, dtype_of, rms_norm,
                                 yarn_mscale)


def softmax_scale(cfg) -> float:
    """``(qk_nope + qk_rope)**-0.5``, times YaRN's ``mscale(factor,
    mscale_all_dim)**2`` where the config scales its rope."""
    m, rs = cfg.mla, cfg.rope_scaling
    scale = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5
    if rs is not None and rs.mscale_all_dim:
        scale *= yarn_mscale(rs.factor, rs.mscale_all_dim) ** 2
    return scale


def init_mla(cfg, key):
    m = cfg.mla
    d, H = cfg.d_model, cfg.num_heads
    qk = m.qk_nope_head_dim + m.qk_rope_head_dim
    dt = dtype_of(cfg)
    ks = jax.random.split(key, 6)
    p = {
        "wq": dense_init(ks[0], d, H * qk, dt),
        "w_dkv": dense_init(ks[1], d, m.kv_lora_rank + m.qk_rope_head_dim, dt),
        "kv_norm": jnp.zeros((m.kv_lora_rank,), dt),
        "w_uk": dense_init(ks[2], m.kv_lora_rank, H * m.qk_nope_head_dim, dt),
        "w_uv": dense_init(ks[3], m.kv_lora_rank, H * m.v_head_dim, dt),
        "wo": dense_init(ks[4], H * m.v_head_dim, d, dt),
    }
    return p


def _latents(p, x, positions, cfg):
    """Shared query/latent computation.  Returns q_nope, q_rope, c_kv, k_rope."""
    m = cfg.mla
    B, S, _ = x.shape
    H = cfg.num_heads
    q = (x @ p["wq"]).reshape(B, S, H, m.qk_nope_head_dim + m.qk_rope_head_dim)
    q_nope, q_rope = jnp.split(q, [m.qk_nope_head_dim], axis=-1)
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta, cfg.rope_scaling)

    dkv = x @ p["w_dkv"]
    c_kv, k_rope = jnp.split(dkv, [m.kv_lora_rank], axis=-1)
    c_kv = rms_norm(c_kv, p["kv_norm"], cfg.norm_eps)
    k_rope = apply_rope(k_rope[:, :, None, :], positions, cfg.rope_theta,
                        cfg.rope_scaling)
    return q_nope, q_rope, c_kv, k_rope[:, :, 0, :]


def mla_forward(p, x, positions, cfg, *, window=None, return_cache=False):
    """Full-sequence MLA (decompressed form)."""
    m = cfg.mla
    B, S, _ = x.shape
    H = cfg.num_heads
    q_nope, q_rope, c_kv, k_rope = _latents(p, x, positions, cfg)

    k_nope = (c_kv @ p["w_uk"]).reshape(B, S, H, m.qk_nope_head_dim)
    v = (c_kv @ p["w_uv"]).reshape(B, S, H, m.v_head_dim)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None, :],
                                  (B, S, H, m.qk_rope_head_dim))], axis=-1)
    scale = softmax_scale(cfg)
    out = full_attention(q, k, v, window=window, scale=scale)
    y = out.reshape(B, S, H * m.v_head_dim) @ p["wo"]
    if return_cache:
        return y, {"ckv": c_kv, "krope": k_rope}
    return y


def init_mla_cache(cfg, batch: int, cache_len: int, dtype):
    m = cfg.mla
    return {
        "ckv": jnp.zeros((batch, cache_len, m.kv_lora_rank), dtype),
        "krope": jnp.zeros((batch, cache_len, m.qk_rope_head_dim), dtype),
    }


def init_mla_pool(cfg, num_blocks: int, block_size: int, dtype):
    """Paged serving state: the compressed latents page just like KV —
    one (N_blocks, block, R) pool per leaf instead of per-request rows.
    MLA's memory edge carries over: pages store rank-R latents, not
    per-head K/V."""
    m = cfg.mla
    return {
        "ckv": jnp.zeros((num_blocks, block_size, m.kv_lora_rank), dtype),
        "krope": jnp.zeros((num_blocks, block_size, m.qk_rope_head_dim),
                           dtype),
    }


def mla_decode_paged(p, x, positions, cfg, kv, block_tables, *, layer,
                     block_size: int, kernels: str = "composed"):
    """Absorbed-matmul decode against the paged latent pool (HyperServe).

    x: (B, 1, D) one token per slot; ``positions``: (B,) per-slot absolute
    write positions; ``kv``: {"ckv","krope"} pool leaves of EVERY layer,
    (L, N_blocks, block, R) / (L, N_blocks, block, rope), updated in
    place: the token scatter and the attention read layer ``layer``
    (traced) through :func:`~repro.models.attention.layer_pages`' view,
    as paged attention does; ``block_tables``: (B, W) layer-local block
    ids.  Gathered rows are indexed by absolute position, exactly like the
    dense latent cache, so the score/readout math is identical to
    :func:`mla_decode`.

    ``kernels="fused"`` lowers the latent attention to the
    block-table-walking Pallas kernel (``W_uk`` absorbed into the query
    outside, ``W_uv`` read-out outside — the kernel works purely in the
    rank-R latent space, no pool gather).
    """
    m = cfg.mla
    B = x.shape[0]
    H = cfg.num_heads
    q_nope, q_rope, c_new, kr_new = _latents(p, x, positions[:, None], cfg)
    flat, base = layer_pages(kv, layer)
    tables = block_tables + base
    bidx = jnp.take_along_axis(
        tables, (positions // block_size)[:, None], axis=1)[:, 0]
    off = positions % block_size
    ckv_pool = flat["ckv"].at[bidx, off].set(c_new[:, 0])
    krope_pool = flat["krope"].at[bidx, off].set(kr_new[:, 0])

    w_uk = p["w_uk"].reshape(m.kv_lora_rank, H, m.qk_nope_head_dim)
    q_lat = jnp.einsum("bhd,rhd->bhr", q_nope[:, 0], w_uk)       # (B,H,R)
    scale = softmax_scale(cfg)
    if kernels == "fused":
        o_lat = ops.paged_mla_decode_attention(
            q_lat, q_rope[:, 0], ckv_pool, krope_pool, tables,
            (positions + 1).astype(jnp.int32), block_size=block_size,
            scale=scale)
    else:
        W = tables.shape[1]
        S = W * block_size
        ckv = ckv_pool[tables].reshape(B, S, m.kv_lora_rank)
        krope = krope_pool[tables].reshape(B, S, m.qk_rope_head_dim)
        s = (jnp.einsum("bhr,bsr->bhs", q_lat.astype(jnp.float32),
                        ckv.astype(jnp.float32))
             + jnp.einsum("bhd,bsd->bhs", q_rope[:, 0].astype(jnp.float32),
                          krope.astype(jnp.float32))) * scale
        mask = jnp.arange(S)[None, None, :] < (positions + 1)[:, None, None]
        s = jnp.where(mask, s, -1e30)
        pr = jax.nn.softmax(s, axis=-1)
        o_lat = jnp.einsum("bhs,bsr->bhr", pr, ckv.astype(jnp.float32))
    w_uv = p["w_uv"].reshape(m.kv_lora_rank, H, m.v_head_dim)
    o = jnp.einsum("bhr,rhd->bhd", o_lat, w_uv.astype(jnp.float32))
    y = o.reshape(B, 1, H * m.v_head_dim).astype(x.dtype) @ p["wo"]
    return y, unflatten_pages({"ckv": ckv_pool, "krope": krope_pool}, kv)


def mla_prefill_chunk_paged(p, x, starts, limits, cfg, kv, block_tables, *,
                            layer, block_size: int,
                            kernels: str = "composed"):
    """One batched chunked-prefill step against the paged latent pool.

    Mirrors :func:`repro.models.attention.attn_prefill_paged`: ``kv``
    holds every layer's latent leaves, as in :func:`mla_decode_paged`;
    every row's latents are written to that row's pages of layer
    ``layer`` in one in-place scatter (padding positions >= the row's
    ``limit`` go to the layer's null block), then each
    row's chunk queries attend its gathered table in decompressed form —
    the same flash kernel and scale the dense prefill uses, with per-row
    ``q_offset=starts[r]`` causal masking.

    ``kernels`` is accepted for hook-signature uniformity but MLA prefill
    always takes the composed path: the decompressed form needs
    ``W_uk``/``W_uv`` applied to every gathered latent, so a fused
    variant would need in-kernel decompression — deferred
    (``MixerSpec.fused_hooks`` records decode-only fusion for MLA).
    """
    del kernels
    m = cfg.mla
    P, C, _ = x.shape
    H = cfg.num_heads
    positions = starts[:, None] + jnp.arange(C)[None, :]     # (P, C)
    q_nope, q_rope, c_kv, k_rope = _latents(p, x, positions, cfg)
    flat, base = layer_pages(kv, layer)
    tables = block_tables + base
    bidx, off, _ = paged_chunk_indices(positions, limits, block_tables,
                                       block_size=block_size)
    bidx = bidx + base
    ckv_pool = flat["ckv"].at[bidx, off].set(c_kv)
    krope_pool = flat["krope"].at[bidx, off].set(k_rope)
    W = tables.shape[1]
    S = W * block_size
    ckv_seq = ckv_pool[tables].reshape(P, S, m.kv_lora_rank)
    krope_seq = krope_pool[tables].reshape(P, S, m.qk_rope_head_dim)

    k_nope = (ckv_seq @ p["w_uk"]).reshape(P, S, H, m.qk_nope_head_dim)
    v = (ckv_seq @ p["w_uv"]).reshape(P, S, H, m.v_head_dim)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(krope_seq[:, :, None, :],
                                  (P, S, H, m.qk_rope_head_dim))], axis=-1)
    scale = softmax_scale(cfg)
    out = flash_rows(q, k, v, starts, scale=scale)
    y = out.reshape(P, C, H * m.v_head_dim) @ p["wo"]
    return y, unflatten_pages({"ckv": ckv_pool, "krope": krope_pool}, kv)


def mla_decode(p, x, pos, cfg, cache, *, window=None):
    """Absorbed-matmul decode: attention in the latent space.

    score[t] = q_nope^T W_uk c_kv[t] + q_rope^T k_rope[t]
    out      = (softmax @ c_kv) W_uv
    """
    m = cfg.mla
    B = x.shape[0]
    H = cfg.num_heads
    positions = jnp.full((B, 1), pos, jnp.int32)
    q_nope, q_rope, c_new, kr_new = _latents(p, x, positions, cfg)

    cache_len = cache["ckv"].shape[1]
    slot = (pos % cache_len) if window is not None else pos
    ckv = jax.lax.dynamic_update_slice_in_dim(cache["ckv"], c_new, slot, axis=1)
    krope = jax.lax.dynamic_update_slice_in_dim(cache["krope"], kr_new, slot, axis=1)

    # absorb W_uk into q: (B,1,H,nope) @ (R,H*nope->R per head)
    w_uk = p["w_uk"].reshape(m.kv_lora_rank, H, m.qk_nope_head_dim)
    q_lat = jnp.einsum("bhd,rhd->bhr", q_nope[:, 0], w_uk)       # (B,H,R)

    scale = softmax_scale(cfg)
    s = (jnp.einsum("bhr,bsr->bhs", q_lat.astype(jnp.float32),
                    ckv.astype(jnp.float32))
         + jnp.einsum("bhd,bsd->bhs", q_rope[:, 0].astype(jnp.float32),
                      krope.astype(jnp.float32))) * scale
    length = jnp.minimum(pos + 1, cache_len)
    mask = jnp.arange(cache_len)[None, None, :] < length
    s = jnp.where(mask, s, -1e30)
    pr = jax.nn.softmax(s, axis=-1)
    o_lat = jnp.einsum("bhs,bsr->bhr", pr, ckv.astype(jnp.float32))  # (B,H,R)

    # absorb W_uv on the way out
    w_uv = p["w_uv"].reshape(m.kv_lora_rank, H, m.v_head_dim)
    o = jnp.einsum("bhr,rhd->bhd", o_lat, w_uv.astype(jnp.float32))
    y = o.reshape(B, 1, H * m.v_head_dim).astype(x.dtype) @ p["wo"]
    return y, {"ckv": ckv, "krope": krope}
