"""Plain float32 reference of a DeepSeek-V2 decoder at one chip's expert
share: latent attention (MLA) with YaRN rope and mixture-of-experts FFNs.

Written from the published architecture (arXiv:2405.04434 and the
model's ``modeling_deepseek.py``), with no cache, kernel or batch: token
embedding; per layer an RMSNorm and attention in decompressed form (the
query; the latent ``c_kv`` RMS-normed; ``k_nope`` and ``v`` from the
latent; one rope key shared by every head; both ropes YaRN-scaled; causal
softmax at the YaRN-corrected scale), a residual add, an RMSNorm and the
FFN, a residual add; a final RMSNorm and the separate unembedding.  The
FFN is a SwiGLU of the dense width in the leading dense layers; in the
others a softmax router over every routed expert, the top-k without
renormalisation (where the config says so) times the routed scale, the
held experts' SwiGLU outputs weighted by their gates, and the shared
experts' SwiGLU.  Norm gains are stored as ``gain - 1``.

Departures, the program's as well: rope rotates the two halves of the
rope dims (the published code de-interleaves them first, which a
checkpoint loader absorbs by permuting the rope columns of the query and
latent projections); the absent experts' part is left out, as the chip
that holds this share leaves it out.

Every matmul runs in float32 under ``default_matmul_precision("highest")``;
``precision="int8"`` is the control, as in ``reference.py``.  The layers
are walked one at a time inside one jitted scan and queries attend in
blocks, so it fits beside the benchmark's weights.  Nothing here imports
the program.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import reference
from chipbench.costs_mla_moe import Dims
from chipbench.reference import Q_BLOCK, ROWS, _mm, _rms


def _yarn(dims: Dims):
    """(inverse frequencies, cos/sin magnitude, softmax scale)."""
    factor, orig, fast, slow, msc, msc_all = dims.yarn
    r, theta = dims.rope_dim, dims.rope_theta
    extra = theta ** (-np.arange(0, r, 2, dtype=np.float64) / r)
    inter = extra / factor

    def corr(rot):
        return r * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(corr(fast)), 0)
    high = min(math.ceil(corr(slow)), r - 1)
    ramp = np.clip((np.arange(r // 2) - low) / max(high - low, 1e-3), 0, 1)
    inv = inter * ramp + extra * (1 - ramp)

    def m(s):
        return 1.0 if factor <= 1 else 0.1 * s * math.log(factor) + 1.0

    scale = (dims.nope_dim + r) ** -0.5 * (m(msc_all) ** 2 if msc_all else 1)
    return inv.astype(np.float32), m(msc) / m(msc_all), scale


def _rope(x, pos, inv, mag):
    """x: (S, heads, r), rotated in two halves."""
    half = x.shape[-1] // 2
    ang = pos[:, None].astype(jnp.float32) * jnp.asarray(inv)
    cos, sin = jnp.cos(ang)[:, None] * mag, jnp.sin(ang)[:, None] * mag
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attend(q, k, v, scale):
    """Causal attention, queries in blocks.  q, k: (S, H, dk); v: (S, H, dv)."""
    S, H, _ = q.shape
    keys = jnp.arange(S)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK)
        rows = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.einsum("qhd,khd->hqk", qb, k) * scale
        s = jnp.where(keys[None, None, :] <= rows[None, :, None], s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v)

    return jax.lax.map(block, jnp.arange(S // Q_BLOCK)).reshape(
        S, H, v.shape[-1])


def _swiglu(h, wg, wu, wd, precision):
    return _mm(jax.nn.silu(_mm(h, wg, precision)) * _mm(h, wu, precision),
               wd, precision)


def _routed(h, f, dims: Dims, precision):
    """The held experts' part of the routed sum for every row of ``h``."""
    probs = jax.nn.softmax(_mm(h, f["router"], precision), -1)
    g, idx = jax.lax.top_k(probs, dims.top_k)
    if dims.norm_topk:
        g = g / jnp.sum(g, -1, keepdims=True)
    g = g * dims.routed_scale
    y = jnp.zeros_like(h)
    for e in range(dims.experts_held):
        w = jnp.sum(jnp.where(idx == dims.first_held + e, g, 0.0), -1)
        y = y + w[:, None] * _swiglu(h, f["w_gate"][e], f["w_up"][e],
                                     f["w_down"][e], precision)
    return y


def _layer(x, p, pos, dims: Dims, precision, moe: bool):
    S = x.shape[0]
    H, R, r = dims.heads, dims.kv_lora_rank, dims.rope_dim
    nope, vd = dims.nope_dim, dims.v_dim
    inv, mag, scale = _yarn(dims)
    a, f = p["attn"], p["ffn"]
    h = _rms(x, p["norm1"], dims.norm_eps)
    q = _mm(h, a["wq"], precision).reshape(S, H, nope + r)
    kv = _mm(h, a["w_dkv"], precision)
    c = _rms(kv[:, :R], a["kv_norm"], dims.norm_eps)
    k_rope = _rope(kv[:, None, R:], pos, inv, mag)              # (S, 1, r)
    k = jnp.concatenate(
        [_mm(c, a["w_uk"], precision).reshape(S, H, nope),
         jnp.broadcast_to(k_rope, (S, H, r))], -1)
    v = _mm(c, a["w_uv"], precision).reshape(S, H, vd)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], pos, inv, mag)],
                        -1)
    o = _attend(q, k, v, scale).reshape(S, H * vd)
    x = x + _mm(o, a["wo"], precision)
    h = _rms(x, p["norm2"], dims.norm_eps)
    if not moe:
        return x + _swiglu(h, f["w_gate"], f["w_up"], f["w_down"], precision)
    return x + _routed(h, f, dims, precision) + _swiglu(
        h, f["ws_gate"], f["ws_up"], f["ws_down"], precision)


@functools.partial(jax.jit, static_argnames=("dims", "precision"))
def final_hidden(weights, tokens, *, dims: Dims, precision: str = "f32"):
    """(S,) int32 -> (S, d_model) float32 final-normed hidden states."""
    f32 = lambda t: jax.tree.map(lambda a: a.astype(jnp.float32), t)
    pos = jnp.arange(tokens.shape[0])
    with jax.default_matmul_precision("highest"):
        x = weights["embed"][tokens].astype(jnp.float32)
        for group, moe in (("dense", False), ("moe", True)):
            def body(x, p, _moe=moe):
                return _layer(x, f32(p), pos, dims, precision, _moe), None
            x, _ = jax.lax.scan(body, x, weights[group])
        return _rms(x, weights["final_norm"].astype(jnp.float32),
                    dims.norm_eps)


def logits(weights, hidden, dims: Dims, precision: str = "f32"):
    with jax.default_matmul_precision("highest"):
        return reference._logits(weights, hidden, dims, precision)


def greedy_gaps(weights, dims: Dims, prompt: Sequence[int],
                served: Sequence[int], *, control: bool = False
                ) -> Dict[str, np.ndarray]:
    """As ``reference.greedy_gaps``, for this architecture: per served
    token, how far its reference logit lies below the reference's best at
    the position that chose it; with ``control``, also the gap of the
    token the int8 forward puts first at each of the same positions."""
    seq = reference._padded(list(prompt) + list(served[:-1]))
    n = len(served)
    rows = np.minimum(np.arange(-(-n // ROWS) * ROWS) + len(prompt) - 1,
                      len(prompt) + n - 2)
    toks = np.zeros(rows.shape, np.int32)
    toks[:n] = served
    rows = jnp.asarray(rows)
    h_ref = final_hidden(weights, seq, dims=dims)[rows]
    h_low = (final_hidden(weights, seq, dims=dims, precision="int8")[rows]
             if control else h_ref)
    g, c = reference._gaps(weights, h_ref, h_low, jnp.asarray(toks),
                           dims=dims, control=control)
    out = {"served": np.asarray(g)[:n]}
    if control:
        out["control"] = np.asarray(c)[:n]
    return out


def gaps(weights, dims: Dims, picked, *, control: bool = False
         ) -> Dict[str, list]:
    """``check.gaps`` for this architecture: every picked request's gaps."""
    out: Dict[str, list] = {"served": [], "control": []}
    for fl in picked:
        g = greedy_gaps(weights, dims, fl.prompt, fl.tokens[:fl.max_new],
                        control=control)
        for k, v in g.items():
            out[k].append(v)
    return out

