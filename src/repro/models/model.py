"""Unified causal LM covering every assigned architecture.

The stack is organised as **segments**: a segment is a macro-block of one
or more (mixer, ffn) sub-layers repeated ``repeat`` times with parameters
stacked on a leading axis and executed under ``jax.lax.scan`` (so the HLO
contains each distinct layer body once — essential for 40-80 layer configs
to compile quickly, and the natural shape for HyperOffload's layer
streaming).  Heterogeneous stacks (MoE first-k-dense, RecurrentGemma's
1:2 pattern) become multiple segments.

Modes:
  forward(..., mode="train")    -> logits, None, metrics
  forward(..., mode="prefill")  -> logits, caches, metrics
  decode_step(...)              -> logits, new caches
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import DENSE_FFN, MOE_FFN
from repro.core.meshctx import constrain
from repro.models import mixers as MX, moe as moe_mod
from repro.models.common import dense_init, dtype_of, embed_init, rms_norm, swiglu
from repro.models.mixers import Segment, segments  # noqa: F401  (re-export)


# ---------------------------------------------------------------------------
# per-sublayer init / forward / decode — mixer dispatch is one registry
# lookup (repro.models.mixers); only the FFN legs live here.
# ---------------------------------------------------------------------------
def _init_sublayer(cfg, kind, key):
    mixer, ffn = kind
    d = cfg.d_model
    dt = dtype_of(cfg)
    ks = jax.random.split(key, 3)
    p: dict = {"norm1": jnp.zeros((d,), dt)}
    spec = MX.get_mixer(mixer)
    p[spec.param_key] = spec.init(cfg, ks[0])
    if ffn == DENSE_FFN:
        p["norm2"] = jnp.zeros((d,), dt)
        p["ffn"] = {
            "w_gate": dense_init(ks[1], d, cfg.d_ff, dt),
            "w_up": dense_init(jax.random.fold_in(ks[1], 1), d, cfg.d_ff, dt),
            "w_down": dense_init(jax.random.fold_in(ks[1], 2), cfg.d_ff, d, dt),
        }
    elif ffn == MOE_FFN:
        p["norm2"] = jnp.zeros((d,), dt)
        p["ffn"] = moe_mod.init_moe(cfg, ks[2])
    return p


def _zero_metrics():
    return {"moe_aux_loss": jnp.float32(0), "moe_z_loss": jnp.float32(0)}


def _sublayer_forward(p, x, positions, cfg, kind, *, mode, window_override,
                      moe_dispatch):
    mixer, ffn = kind
    h = rms_norm(x, p["norm1"], cfg.norm_eps)
    spec = MX.get_mixer(mixer)
    w = MX.resolve_window(cfg, mixer, window_override)
    y, cache = spec.forward(p, h, positions, cfg, window=w,
                            want_cache=mode == "prefill")
    x = x + y

    metrics = _zero_metrics()
    if ffn == DENSE_FFN:
        h = rms_norm(x, p["norm2"], cfg.norm_eps)
        x = x + swiglu(h, p["ffn"]["w_gate"], p["ffn"]["w_up"], p["ffn"]["w_down"])
    elif ffn == MOE_FFN:
        h = rms_norm(x, p["norm2"], cfg.norm_eps)
        y, mm = moe_mod.moe_forward(p["ffn"], h, cfg, dispatch=moe_dispatch)
        x = x + y
        metrics["moe_aux_loss"] = mm["moe_aux_loss"]
        metrics["moe_z_loss"] = mm["moe_z_loss"]
    return x, cache, metrics


def _sublayer_decode(p, x, pos, cfg, kind, cache, *, window_override,
                     moe_dispatch):
    mixer, ffn = kind
    h = rms_norm(x, p["norm1"], cfg.norm_eps)
    spec = MX.get_mixer(mixer)
    w = MX.resolve_window(cfg, mixer, window_override)
    y, cache = spec.decode(p, h, pos, cfg, cache, window=w)
    x = x + y
    if ffn == DENSE_FFN:
        h = rms_norm(x, p["norm2"], cfg.norm_eps)
        x = x + swiglu(h, p["ffn"]["w_gate"], p["ffn"]["w_up"], p["ffn"]["w_down"])
    elif ffn == MOE_FFN:
        h = rms_norm(x, p["norm2"], cfg.norm_eps)
        y, _ = moe_mod.moe_forward(p["ffn"], h, cfg, dispatch=moe_dispatch)
        x = x + y
    return x, cache


def _init_sublayer_cache(cfg, kind, batch, cache_len, dtype, window_override):
    mixer, _ = kind
    w = MX.resolve_window(cfg, mixer, window_override)
    eff_len = min(cache_len, w) if w is not None else cache_len
    return MX.get_mixer(mixer).init_cache(cfg, batch, eff_len, dtype)


# ---------------------------------------------------------------------------
# whole-model init
# ---------------------------------------------------------------------------
def init_model(cfg, key):
    dt = dtype_of(cfg)
    keys = jax.random.split(key, 4 + len(segments(cfg)))
    params: dict = {
        "embed": embed_init(keys[0], cfg.padded_vocab, cfg.d_model, dt),
        "final_norm": jnp.zeros((cfg.d_model,), dt),
    }
    if not cfg.tie_embeddings:
        params["unembed"] = embed_init(keys[1], cfg.padded_vocab, cfg.d_model, dt)
    if cfg.frontend_dim:
        params["frontend_proj"] = dense_init(keys[2], cfg.frontend_dim,
                                             cfg.d_model, dt)
    for si, seg in enumerate(segments(cfg)):
        def one(k):
            sks = jax.random.split(k, len(seg.kinds))
            return tuple(_init_sublayer(cfg, kd, sk)
                         for kd, sk in zip(seg.kinds, sks))
        params[f"seg{si}"] = jax.vmap(one)(
            jax.random.split(keys[3 + si], seg.repeat))
    return params


# ---------------------------------------------------------------------------
# forward (train / prefill)
# ---------------------------------------------------------------------------
def forward(params, tokens, cfg, *, prefix_embeds=None, mode="train",
            window_override=None, moe_dispatch="gshard", remat=True,
            unroll=False):
    """tokens: (B, S) int32.  Returns (logits (B,S,V_pad), caches|None, metrics)."""
    B, S = tokens.shape
    x = jnp.take(params["embed"], tokens, axis=0)
    P_len = 0
    if prefix_embeds is not None:
        pe = prefix_embeds.astype(x.dtype) @ params["frontend_proj"]
        x = jnp.concatenate([pe, x], axis=1)
        P_len = pe.shape[1]
    x = constrain(x, ("pod", "data"), None, None)
    positions = jnp.arange(P_len + S)

    metrics = _zero_metrics()
    caches = {}

    for si, seg in enumerate(segments(cfg)):
        def body2(carry, layer_params, _seg=seg):
            h, acc = carry
            h = constrain(h, ("pod", "data"), "model", None)
            lcaches = []
            for sub_p, kd in zip(layer_params, _seg.kinds):
                h, c, mm = _sublayer_forward(
                    sub_p, h, positions, cfg, kd, mode=mode,
                    window_override=window_override, moe_dispatch=moe_dispatch)
                lcaches.append(c)
                acc = jax.tree.map(lambda a, b: a + b, acc, mm)
            return (h, acc), (tuple(lcaches) if mode == "prefill" else None)

        fn = jax.checkpoint(body2) if (remat and mode == "train") else body2
        if unroll:
            # python loop (used by the dry-run's depth-scaled cost probes:
            # XLA cost_analysis counts while bodies once, so rolled scans
            # can't be cost-extrapolated)
            outs = []
            for li in range(seg.repeat):
                lp = jax.tree.map(lambda a: a[li], params[f"seg{si}"])
                (x, metrics), out = fn((x, metrics), lp)
                outs.append(out)
            seg_caches = (jax.tree.map(lambda *xs: jnp.stack(xs), *outs)
                          if mode == "prefill" else None)
        else:
            (x, metrics), seg_caches = jax.lax.scan(fn, (x, metrics),
                                                    params[f"seg{si}"])
        if mode == "prefill":
            caches[f"seg{si}"] = seg_caches

    x = constrain(x, ("pod", "data"), "model", None)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    if P_len:
        x = x[:, P_len:]
    unembed = params["embed"] if cfg.tie_embeddings else params["unembed"]
    logits = x @ unembed.T
    logits = constrain(logits, ("pod", "data"), None, "model")
    return logits, (caches if mode == "prefill" else None), metrics


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------
def init_caches(cfg, batch, cache_len, *, dtype=None, window_override=None):
    """Cache pytree matching decode_step's expectations (stacked per segment)."""
    dt = dtype or dtype_of(cfg)
    caches = {}
    for si, seg in enumerate(segments(cfg)):
        one = tuple(_init_sublayer_cache(cfg, kd, batch, cache_len, dt,
                                         window_override)
                    for kd in seg.kinds)
        # stack `repeat` copies on a leading layer axis (broadcast of zeros)
        caches[f"seg{si}"] = jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (seg.repeat,) + x.shape), one)
    return caches


def _paged_ffn(p, x, cfg, ffn, moe_dispatch, valid):
    """The sublayer's FFN leg: ``(x, routes)``, where ``routes`` is the MoE
    layer's (held,) count of valid tokens' routes to its held experts, or
    None for a dense leg."""
    if ffn == DENSE_FFN:
        h = rms_norm(x, p["norm2"], cfg.norm_eps)
        x = x + swiglu(h, p["ffn"]["w_gate"], p["ffn"]["w_up"],
                       p["ffn"]["w_down"])
    elif ffn == MOE_FFN:
        h = rms_norm(x, p["norm2"], cfg.norm_eps)
        y, mm = moe_mod.moe_forward(p["ffn"], h, cfg, dispatch=moe_dispatch,
                                    valid=valid)
        return x + y, mm["routes_held"]
    return x, None


def decode_step_paged(params, tokens, positions, cfg, kv_pools, block_tables,
                      *, block_size, slot_mask=None, moe_dispatch="gshard",
                      kernels="composed"):
    """Continuous-batching decode: one token per slot at per-slot positions.

    tokens: (B, 1) int32; positions: (B,) int32 absolute write positions
    (slots advance independently — this is what ``decode_step``'s shared
    scalar ``pos`` cannot express); kv_pools: :class:`StatePool` pytree —
    paged leaves (L, N_blocks, block, ...) for attention/MLA sublayers,
    per-slot dense leaves (L, B, ...) for SSD/RG-LRU sublayers;
    block_tables: (B, W) int32; slot_mask: (B,) bool, True where the seat
    holds a RUNNING request — inactive seats' dummy decode must not
    advance slot-state recurrences (paged writes are naturally routed to
    the null block).  Returns (logits (B, 1, V_pad), new pools), and for
    a config with MoE layers also their held-expert route counts
    (n_moe_layers, held) int32 (seats outside ``slot_mask`` route nowhere).

    The layer scan carries the pools, so each layer updates its pages in
    the one stacked buffer (:meth:`MixerSpec.decode_layer`).
    """
    x = jnp.take(params["embed"], tokens, axis=0)
    x = constrain(x, ("pod", "data"), None, None)
    valid = None if slot_mask is None else slot_mask[:, None]

    def sublayer(sub_p, kd, h, kv, layer):
        spec = MX.get_mixer(kd[0])
        y, kv = spec.decode_layer(
            sub_p, rms_norm(h, sub_p["norm1"], cfg.norm_eps), positions,
            cfg, kv, layer, block_tables, block_size=block_size,
            window=spec.window(cfg), slot_mask=slot_mask, kernels=kernels)
        h, routes = _paged_ffn(sub_p, h + y, cfg, kd[1], moe_dispatch, valid)
        return h, kv, routes

    x, new_pools, routes = _paged_layers(params, x, cfg, kv_pools, sublayer)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    unembed = params["embed"] if cfg.tie_embeddings else params["unembed"]
    logits = x @ unembed.T
    logits = constrain(logits, ("pod", "data"), None, "model")
    return (logits, new_pools) + (() if routes is None else (routes,))


def _paged_layers(params, x, cfg, pools, sublayer):
    """Every segment's layer scan over the paged serving state.

    The scan's ``xs`` are the layer params and the layer index; the pool
    leaves ride in the carry, so the stacked buffers are updated in place
    and never sliced into ``xs`` / restacked from ``ys``.
    ``sublayer(sub_p, kind, h, state, layer) -> (h, state, routes)``;
    returns ``(x, new pools, routes)``, the MoE sublayers' route counts
    stacked in layer order ((n_moe_layers, held), or None without MoE).
    """
    new_pools, routes = {}, []
    for si, seg in enumerate(segments(cfg)):
        def body(carry, xs, _seg=seg):
            h, seg_pools = carry
            layer_params, layer = xs
            new_kv, counts = [], []
            for sub_p, kd, kv in zip(layer_params, _seg.kinds, seg_pools):
                h, kv, c = sublayer(sub_p, kd, h, kv, layer)
                new_kv.append(kv)
                if c is not None:
                    counts.append(c)
            return (h, tuple(new_kv)), (jnp.stack(counts) if counts
                                        else None)

        (x, new_pools[f"seg{si}"]), seg_routes = jax.lax.scan(
            body, (x, pools[f"seg{si}"]),
            (params[f"seg{si}"], jnp.arange(seg.repeat, dtype=jnp.int32)))
        if seg_routes is not None:                # (repeat, n_moe_sub, held)
            routes.append(seg_routes.reshape(-1, seg_routes.shape[-1]))
    return x, new_pools, (jnp.concatenate(routes) if routes else None)


def prefill_chunk_paged(params, tokens, starts, limits, slots, cfg, kv_pools,
                        block_tables, *, block_size, moe_dispatch="gshard",
                        kernels="composed"):
    """One batched chunked-prefill step (HyperServe).

    tokens: (P, C) — every prompt chunk the scheduler admitted this
    iteration, one request per row, row ``r``'s first token at absolute
    position ``starts[r]`` (traced vectors, so ONE compilation serves
    every chunk batch); ``limits``: (P,) true prompt lengths (padding
    positions never write real pages, and slot-state mixers freeze their
    recurrent state past them); ``slots``: (P,) each request's decode
    seat — SSD/RG-LRU sublayers read and update that row of their
    per-slot state (filler rows carry the out-of-range null seat, whose
    writes are dropped); block_tables: (P, W) per-row tables.  Writes
    every row's K/V into the pool pages and returns
    ``(last_logits (P, V_pad), new kv_pools)`` — the logits of each row's
    last in-chunk prompt token, the only position whose logits any caller
    reads (they seed the first sampled token of rows finishing their
    prompt), so the unembedding matmul — the dominant per-chunk FLOP for
    real vocabularies — runs over P rows instead of P*C.  A config with
    MoE layers also gets their held-expert route counts (n_moe_layers,
    held) int32 (padding positions route nowhere).
    """
    P, C = tokens.shape
    x = jnp.take(params["embed"], tokens, axis=0)
    valid = starts[:, None] + jnp.arange(C)[None, :] < limits[:, None]

    def sublayer(sub_p, kd, h, kv, layer):
        spec = MX.get_mixer(kd[0])
        y, kv = spec.prefill_layer(
            sub_p, rms_norm(h, sub_p["norm1"], cfg.norm_eps), starts,
            limits, slots, cfg, kv, layer, block_tables,
            block_size=block_size, window=spec.window(cfg), kernels=kernels)
        h, routes = _paged_ffn(sub_p, h + y, cfg, kd[1], moe_dispatch, valid)
        return h, kv, routes

    x, new_pools, routes = _paged_layers(params, x, cfg, kv_pools, sublayer)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    # row r's last in-chunk prompt token sits at chunk index
    # min(limit, start + C) - 1 - start (clamped for filler rows)
    last = jnp.clip(jnp.minimum(limits, starts + C) - 1 - starts, 0, C - 1)
    x_last = jnp.take_along_axis(x, last[:, None, None].astype(jnp.int32),
                                 axis=1)[:, 0]                # (P, D)
    unembed = params["embed"] if cfg.tie_embeddings else params["unembed"]
    logits = x_last @ unembed.T
    return (logits, new_pools) + (() if routes is None else (routes,))


def decode_step(params, token, pos, cfg, caches, *, window_override=None,
                moe_dispatch="gshard", unroll=False):
    """token: (B, 1) int32; pos: scalar int32.  Returns (logits (B,1,V), caches)."""
    B = token.shape[0]
    x = jnp.take(params["embed"], token, axis=0)
    x = constrain(x, ("pod", "data"), None, None)

    new_caches = {}
    for si, seg in enumerate(segments(cfg)):
        def body(h, xs, _seg=seg):
            layer_params, layer_cache = xs
            lcaches = []
            for sub_p, kd, c in zip(layer_params, _seg.kinds, layer_cache):
                h, c2 = _sublayer_decode(sub_p, h, pos, cfg, kd, c,
                                         window_override=window_override,
                                         moe_dispatch=moe_dispatch)
                lcaches.append(c2)
            return h, tuple(lcaches)

        if unroll:
            outs = []
            for li in range(seg.repeat):
                xs_i = jax.tree.map(lambda a: a[li],
                                    (params[f"seg{si}"], caches[f"seg{si}"]))
                x, out = body(x, xs_i)
                outs.append(out)
            seg_caches = jax.tree.map(lambda *xs: jnp.stack(xs), *outs)
        else:
            x, seg_caches = jax.lax.scan(body, x, (params[f"seg{si}"],
                                                   caches[f"seg{si}"]))
        new_caches[f"seg{si}"] = seg_caches

    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    unembed = params["embed"] if cfg.tie_embeddings else params["unembed"]
    logits = x @ unembed.T
    logits = constrain(logits, ("pod", "data"), None, "model")
    return logits, new_caches
