"""GQA / MHA / sliding-window attention with KV cache.

Three entry modes share one parameter set:
  - ``attn_forward``       : full-sequence (training)
  - ``attn_prefill``       : full-sequence, returns the populated KV cache
  - ``attn_decode``        : one token against an existing cache
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.meshctx import constrain, current_mesh
from repro.kernels import ops
from repro.models.common import apply_rope, dense_init, dtype_of


_ATTN_MODE = "ring"      # "ring" | "head" | "plain" — see set_attention_mode


def set_attention_mode(mode: str) -> None:
    """Select the distributed attention strategy.

    ``head``: Megatron-style head-sharded TP (the paper-era baseline;
    requires KV heads divisible by the model axis, and XLA realises the
    seq<->head reshard as replicate-then-reslice — activation-sized
    all-gathers fwd + all-reduces bwd per layer).
    ``ring`` (default, beyond-paper): q/k/v stay sequence-sharded over the
    model axis matching the residual layout; KV chunks rotate by ppermute.
    No resharding, no KV/model-axis divisibility requirement, and the
    per-step transfer overlaps the previous chunk's compute.
    Recorded as §Perf iteration in EXPERIMENTS.md.
    """
    global _ATTN_MODE
    assert mode in ("ring", "head", "plain")
    _ATTN_MODE = mode


def full_attention(q, k, v, *, window=None, scale=None):
    """Strategy-dispatching full-sequence attention (HyperShard-governed)."""
    mesh = current_mesh()
    B, S, H, _ = q.shape
    KV = k.shape[2]
    tp = mesh.shape.get("model", 1) if mesh is not None else 1
    if (_ATTN_MODE == "head" and mesh is not None and tp > 1
            and KV % tp == 0 and H % tp == 0):
        q = constrain(q, ("pod", "data"), None, "model", None)
        k = constrain(k, ("pod", "data"), None, "model", None)
        v = constrain(v, ("pod", "data"), None, "model", None)
        out = ops.flash_attention(q, k, v, causal=True, window=window,
                                  scale=scale)
        return constrain(out, ("pod", "data"), None, "model", None)
    from repro.core.ring_attention import ring_applicable, ring_attention
    if _ATTN_MODE != "plain" and ring_applicable(mesh, S):
        return ring_attention(q, k, v, mesh, window=window, scale=scale)
    return ops.flash_attention(q, k, v, causal=True, window=window,
                               scale=scale)


def init_attention(cfg, key):
    d, H, KV = cfg.d_model, cfg.num_heads, cfg.num_kv_heads
    hd = cfg.resolved_head_dim
    dt = dtype_of(cfg)
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], d, H * hd, dt),
        "wk": dense_init(ks[1], d, KV * hd, dt),
        "wv": dense_init(ks[2], d, KV * hd, dt),
        "wo": dense_init(ks[3], H * hd, d, dt),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((H * hd,), dt)
        p["bk"] = jnp.zeros((KV * hd,), dt)
        p["bv"] = jnp.zeros((KV * hd,), dt)
    return p


def _qkv(p, x, cfg, positions):
    B, S, _ = x.shape
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(B, S, H, hd)
    k = k.reshape(B, S, KV, hd)
    v = v.reshape(B, S, KV, hd)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def attn_forward(p, x, positions, cfg, *, window: Optional[int] = None):
    """(B, S, D) -> (B, S, D); full-sequence causal attention."""
    B, S, _ = x.shape
    q, k, v = _qkv(p, x, cfg, positions)
    out = full_attention(q, k, v, window=window)
    return out.reshape(B, S, -1) @ p["wo"]


def init_kv_cache(cfg, batch: int, cache_len: int, dtype):
    KV, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    return {
        "k": jnp.zeros((batch, cache_len, KV, hd), dtype),
        "v": jnp.zeros((batch, cache_len, KV, hd), dtype),
    }


def attn_prefill(p, x, positions, cfg, *, window: Optional[int] = None):
    """Full-sequence forward that also returns the KV cache.

    When ``window`` is set and smaller than S the cache holds only the last
    ``window`` keys (ring layout with slot = pos % window).
    """
    B, S, _ = x.shape
    q, k, v = _qkv(p, x, cfg, positions)
    out = full_attention(q, k, v, window=window)
    if window is not None and window < S:
        # keep last `window` entries, arranged so slot = pos % window
        kw, vw = k[:, -window:], v[:, -window:]
        shift = S % window
        kw = jnp.roll(kw, shift, axis=1)
        vw = jnp.roll(vw, shift, axis=1)
        cache = {"k": kw, "v": vw}
    else:
        cache = {"k": k, "v": v}
    return out.reshape(B, S, -1) @ p["wo"], cache


def layer_pages(kv, layer):
    """Layer ``layer``'s pages inside a stacked paged pool.

    ``kv``: a dict of pool leaves that share their leading dims,
    (L, N_blocks, block, ...) — attention's lane-dense {"k","v"}, MLA's
    {"ckv","krope"} latents.  Returns every leaf viewed as one
    (L*N_blocks, block, ...) pool (merging the two leading dims is a
    bitcast, so the view is the pool itself) and the page offset
    ``layer * N_blocks`` that maps the layer's block ids, its null block
    0 included, into that view.
    """
    L, N = next(iter(kv.values())).shape[:2]
    flat = {n: a.reshape((L * N,) + a.shape[2:]) for n, a in kv.items()}
    return flat, layer * N


def unflatten_pages(flat, like):
    """:func:`layer_pages`' view back in the stacked shapes of ``like``."""
    return {n: flat[n].reshape(like[n].shape) for n in flat}


def attn_decode_paged(p, x, positions, cfg, kv, block_tables, *, layer,
                      block_size: int, window: Optional[int] = None,
                      kernels: str = "composed"):
    """One-token decode against the paged KV pool (HyperServe).

    x: (B, 1, D) — one token per batch slot; ``positions``: (B,) absolute
    write position of each slot's token (continuous batching: every slot
    is at a different position).  ``kv``: {"k","v"} pool leaves of EVERY
    layer, (L, N_blocks, block, KV*hd) — lane-dense pages, updated in
    place: the token scatter and the attention read layer ``layer``
    (traced) through :func:`layer_pages`' view, so no layer slice or
    pool copy is ever made.  ``block_tables``: (B, W) int32 layer-local
    block ids; row padding entries point at the null block and are never
    unmasked.

    ``window`` (LOCAL_ATTN): keys below ``pos + 1 - window`` are masked,
    so the runtime may free their blocks (table entries repointed at the
    null block) without changing the result.

    ``kernels="fused"`` lowers the attention to the block-table-walking
    Pallas kernel — the cache is read once, straight from the pool, no
    dense ``pool[block_tables]`` gather.  The token scatter stays outside
    the kernel either way (it is the pool-state update, not attention).
    """
    B = x.shape[0]
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    q, k, v = _qkv(p, x, cfg, positions[:, None])
    flat, base = layer_pages(kv, layer)
    tables = block_tables + base
    bidx = jnp.take_along_axis(
        tables, (positions // block_size)[:, None], axis=1)[:, 0]
    off = positions % block_size
    k_pool = flat["k"].at[bidx, off].set(k[:, 0].reshape(B, KV * hd))
    v_pool = flat["v"].at[bidx, off].set(v[:, 0].reshape(B, KV * hd))
    lengths = (positions + 1).astype(jnp.int32)
    if kernels == "fused":
        out = ops.paged_decode_attention(q, k_pool, v_pool, tables,
                                         lengths, block_size=block_size,
                                         window=window)
    else:
        W = tables.shape[1]
        k_seq = k_pool[tables].reshape(B, W * block_size, KV, hd)
        v_seq = v_pool[tables].reshape(B, W * block_size, KV, hd)
        out = ops.decode_attention(q, k_seq, v_seq, lengths, window=window)
    y = out.reshape(B, 1, H * hd) @ p["wo"]
    return y, unflatten_pages({"k": k_pool, "v": v_pool}, kv)


def paged_chunk_indices(positions, limits, block_tables, *, block_size: int):
    """Per-row (block, offset) write targets for a prefill chunk batch.

    positions: (P, C) absolute token positions; limits: (P,) each row's
    true prompt length; block_tables: (P, W).  Rows/columns at positions
    >= the row's limit are padding — their writes are routed to the null
    block (block 0), whose contents are never read unmasked.  Returns
    ``(bidx (P, C), off (P, C), valid (P, C))``.
    """
    valid = positions < limits[:, None]
    bidx = jnp.take_along_axis(
        block_tables, jnp.where(valid, positions // block_size, 0), axis=1)
    bidx = jnp.where(valid, bidx, 0)                         # null block
    off = jnp.where(valid, positions % block_size, 0)
    return bidx, off, valid


def flash_rows(q, k, v, starts, *, window=None, scale=None):
    """Row-wise flash attention with a per-row query offset.

    q: (P, C, H, d); k/v: (P, S, KV, d); starts: (P,) — row ``r``'s
    queries occupy absolute positions ``starts[r] + [0, C)`` over that
    row's own gathered keys.  vmap keeps every row's math identical to a
    standalone ``ops.flash_attention(..., q_offset=start)`` call while the
    whole chunk batch lowers as ONE fused device computation.
    """
    def one(q_r, k_r, v_r, off):
        return ops.flash_attention(q_r[None], k_r[None], v_r[None],
                                   causal=True, q_offset=off, window=window,
                                   scale=scale)[0]
    return jax.vmap(one)(q, k, v, starts)


def attn_prefill_paged(p, x, starts, limits, cfg, kv, block_tables, *,
                       layer, block_size: int, window: Optional[int] = None,
                       kernels: str = "composed"):
    """One batched chunked-prefill step against the paged KV pool.

    x: (P, C, D) — one prompt chunk per row, row ``r``'s first token at
    absolute position ``starts[r]`` (traced vector).  Writes every row's
    K/V into its own pages of layer ``layer`` in one in-place scatter
    (``kv`` holds every layer's lane-dense leaves, as in
    :func:`attn_decode_paged`), then attends each row's chunk queries
    over that row's table (history + chunk) with per-row
    ``q_offset=starts[r]`` causal masking — exact chunked prefill, P
    requests per kernel launch.  ``limits``: (P,) true prompt lengths —
    positions >= the limit are padding (null-block writes, outputs
    ignored); fully-padded rows (limit 0) are scheduler filler.
    ``block_tables``: (P, W) per-row tables.  ``window`` applies the
    LOCAL_ATTN sliding window to the gathered keys.
    """
    P, C, _ = x.shape
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    positions = starts[:, None] + jnp.arange(C)[None, :]     # (P, C)
    q, k, v = _qkv(p, x, cfg, positions)
    flat, base = layer_pages(kv, layer)
    tables = block_tables + base
    bidx, off, _ = paged_chunk_indices(positions, limits, block_tables,
                                       block_size=block_size)
    bidx = bidx + base
    k_pool = flat["k"].at[bidx, off].set(k.reshape(P, C, KV * hd))
    v_pool = flat["v"].at[bidx, off].set(v.reshape(P, C, KV * hd))
    if kernels == "fused":
        out = ops.ragged_prefill_attention(
            q, k_pool, v_pool, tables,
            starts.astype(jnp.int32), limits.astype(jnp.int32),
            block_size=block_size, window=window)
    else:
        W = tables.shape[1]
        k_seq = k_pool[tables].reshape(P, W * block_size, KV, hd)
        v_seq = v_pool[tables].reshape(P, W * block_size, KV, hd)
        out = flash_rows(q, k_seq, v_seq, starts, window=window)
    y = out.reshape(P, C, H * hd) @ p["wo"]
    return y, unflatten_pages({"k": k_pool, "v": v_pool}, kv)


def attn_decode(p, x, pos, cfg, cache, *, window: Optional[int] = None):
    """One-token decode.  x: (B, 1, D); pos: scalar absolute position.

    The cache is a ring buffer when ``window`` is set (slot = pos % window),
    else a linear buffer indexed by absolute position.
    """
    B = x.shape[0]
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    positions = jnp.full((B, 1), pos, jnp.int32)
    q, k, v = _qkv(p, x, cfg, positions)
    cache_len = cache["k"].shape[1]
    slot = (pos % cache_len) if window is not None else pos
    k_cache = jax.lax.dynamic_update_slice_in_dim(cache["k"], k, slot, axis=1)
    v_cache = jax.lax.dynamic_update_slice_in_dim(cache["v"], v, slot, axis=1)
    # valid entries: min(pos+1, cache_len)
    length = jnp.minimum(pos + 1, cache_len)
    lengths = jnp.full((B,), length, jnp.int32)
    out = ops.decode_attention(q, k_cache, v_cache, lengths)
    y = out.reshape(B, 1, H * hd) @ p["wo"]
    return y, {"k": k_cache, "v": v_cache}
