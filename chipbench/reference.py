"""Plain float32 reference of a dense GQA decoder (Qwen2, Granite).

Written from the published architecture, with no cache, kernel or batch:
token embedding; per layer an RMSNorm, attention with rotary positions
(half-split rotation, base ``rope_theta``), grouped key/value heads (head
``h`` reads key/value head ``h // (heads / kv_heads)``) and an optional
query/key/value bias, a residual add, an RMSNorm and a SwiGLU MLP, a
residual add; a final RMSNorm and the (tied or separate) unembedding.
Norm gains are stored as ``gain - 1`` (see ``weights.py``).  Every
matmul runs in float32 under ``default_matmul_precision("highest")``.

``precision="int8"`` is the comparison's control: the same forward with
every weight matmul's inputs rounded to int8 (weights per output column,
activations per token, symmetric), the step a later change might take
to serve faster.

The forward walks the layers one at a time inside one jitted scan and
attends in blocks of queries, so it fits beside the benchmark's weights.
Nothing here imports the program.
"""
from __future__ import annotations

import functools
from typing import Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.costs import Dims

PAD = 512          # sequences are padded to a multiple of this (causal:
                   # padding after a position never changes it)
Q_BLOCK = 512
ROWS = 128         # logit rows are padded to a multiple of this


def _q8(x, axis):
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    s = jnp.where(s == 0, 1.0, s)
    return jnp.clip(jnp.round(x / s), -127, 127) * s


def _mm(x, w, precision):
    if precision == "int8":
        return _q8(x, -1) @ _q8(w, 0)
    return x @ w


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (
        1.0 + g)


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * inv                # (S, half)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attend(q, k, v, group):
    """Causal attention, queries in blocks.  q: (S, H, D); k, v: (S, KV, D)."""
    S, H, D = q.shape
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    keys = jnp.arange(S)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK)
        rows = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.einsum("qhd,khd->hqk", qb, k) * D ** -0.5
        s = jnp.where(keys[None, None, :] <= rows[None, :, None], s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v)

    return jax.lax.map(block, jnp.arange(S // Q_BLOCK)).reshape(S, H, D)


@functools.partial(jax.jit, static_argnames=("dims", "precision"))
def final_hidden(weights, tokens, *, dims: Dims, precision: str = "f32"):
    """(S,) int32 -> (S, d_model) float32 final-normed hidden states."""
    f32 = lambda a: a.astype(jnp.float32)
    H, KV, D = dims.heads, dims.kv_heads, dims.head_dim
    S = tokens.shape[0]
    pos = jnp.arange(S)
    with jax.default_matmul_precision("highest"):
        x = f32(weights["embed"])[tokens]

        def layer(x, p):
            p = jax.tree.map(f32, p)
            a, f = p["attn"], p["ffn"]
            h = _rms(x, p["norm1"], dims.norm_eps)
            q, k, v = (_mm(h, a["wq"], precision), _mm(h, a["wk"], precision),
                       _mm(h, a["wv"], precision))
            if dims.qkv_bias:
                q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
            q = _rope(q.reshape(S, H, D), pos, dims.rope_theta)
            k = _rope(k.reshape(S, KV, D), pos, dims.rope_theta)
            o = _attend(q, k, v.reshape(S, KV, D), H // KV).reshape(S, H * D)
            x = x + _mm(o, a["wo"], precision)
            h = _rms(x, p["norm2"], dims.norm_eps)
            g = jax.nn.silu(_mm(h, f["w_gate"], precision))
            x = x + _mm(g * _mm(h, f["w_up"], precision), f["w_down"],
                        precision)
            return x, None

        x, _ = jax.lax.scan(layer, x, weights["layers"])
        return _rms(x, f32(weights["final_norm"]), dims.norm_eps)


def _logits(weights, hidden, dims: Dims, precision: str):
    table = weights["embed"] if dims.tied else weights["unembed"]
    return _mm(hidden, table[:dims.vocab].astype(jnp.float32).T, precision)


@functools.partial(jax.jit, static_argnames=("dims", "control"))
def _gaps(weights, h_ref, h_low, tokens, *, dims: Dims, control: bool):
    """Gap below the reference's best logit of each row's token, and of the
    token the int8 forward puts first (``control``)."""
    with jax.default_matmul_precision("highest"):
        ref = _logits(weights, h_ref, dims, "f32")
        best = jnp.max(ref, -1)
        served = best - jnp.take_along_axis(ref, tokens[:, None], -1)[:, 0]
        if not control:
            return served, served
        pick = jnp.argmax(_logits(weights, h_low, dims, "int8"), -1)
        return served, best - jnp.take_along_axis(ref, pick[:, None], -1)[:, 0]


def _padded(tokens: Sequence[int]) -> jnp.ndarray:
    n = len(tokens)
    out = np.zeros((-(-n // PAD) * PAD,), np.int32)
    out[:n] = tokens
    return jnp.asarray(out)


def greedy_gaps(weights, dims: Dims, prompt: Sequence[int],
                served: Sequence[int], *, control: bool = False
                ) -> Dict[str, np.ndarray]:
    """Per served token, how far its reference logit lies below the
    reference's best at the position that chose it (0 where it is the
    reference's own greedy choice); row ``j`` is the prediction after
    ``prompt + served[:j]``.  With ``control``, also the gap of the token
    the int8 forward puts first at each of the same positions."""
    seq = _padded(list(prompt) + list(served[:-1]))
    n = len(served)
    rows = np.minimum(np.arange(-(-n // ROWS) * ROWS) + len(prompt) - 1,
                      len(prompt) + n - 2)
    toks = np.zeros(rows.shape, np.int32)
    toks[:n] = served
    rows = jnp.asarray(rows)
    h_ref = final_hidden(weights, seq, dims=dims)[rows]
    h_low = (final_hidden(weights, seq, dims=dims, precision="int8")[rows]
             if control else h_ref)
    g, c = _gaps(weights, h_ref, h_low, jnp.asarray(toks), dims=dims,
                 control=control)
    out = {"served": np.asarray(g)[:n]}
    if control:
        out["control"] = np.asarray(c)[:n]
    return out


def mean_nll(weights, dims: Dims, tokens: Sequence[int],
             targets: Sequence[int]) -> float:
    """Mean next-token cross-entropy of ``targets`` after each prefix of
    ``tokens``, over the true vocabulary, in float32."""
    n = len(tokens)
    h = final_hidden(weights, _padded(tokens), dims=dims)[:n]
    with jax.default_matmul_precision("highest"):
        logits = _logits(weights, h, dims, "f32")
        lse = jax.nn.logsumexp(logits, -1)
        picked = jnp.take_along_axis(
            logits, jnp.asarray(np.asarray(targets, np.int32))[:, None],
            -1)[:, 0]
        return float(jnp.mean(lse - picked))
