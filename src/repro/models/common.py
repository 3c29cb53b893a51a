"""Shared model building blocks: norms, RoPE, initialisers.

Everything is a pure function over explicit parameter pytrees (no module
framework — params are nested dicts of jnp arrays so HyperShard layouts can
be attached by tree path).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def dtype_of(cfg) -> jnp.dtype:
    return jnp.dtype(cfg.dtype)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------
def dense_init(key, d_in: int, d_out: int, dtype) -> jax.Array:
    scale = (2.0 / (d_in + d_out)) ** 0.5
    return (scale * jax.random.normal(key, (d_in, d_out), jnp.float32)).astype(dtype)


def embed_init(key, vocab: int, d: int, dtype) -> jax.Array:
    return (jax.random.normal(key, (vocab, d), jnp.float32) * d ** -0.5).astype(dtype)


# ---------------------------------------------------------------------------
# norms / activations
# ---------------------------------------------------------------------------
def rms_norm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps) * (1.0 + scale.astype(jnp.float32))
    return out.astype(x.dtype)


def swiglu(x: jax.Array, w_gate, w_up, w_down) -> jax.Array:
    g = x @ w_gate
    u = x @ w_up
    return (jax.nn.silu(g) * u) @ w_down


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------
def rope_freqs(head_dim: int, theta: float, scaling=None) -> np.ndarray:
    """Inverse frequencies of the rotary pairs: ``theta**(-2i/d)``, or with
    ``scaling`` (a :class:`~repro.configs.base.RopeScaling`) YaRN's blend
    of those and of the same divided by the factor, as DeepSeek-V2's
    ``DeepseekV2YarnRotaryEmbedding`` computes it."""
    half = head_dim // 2
    extra = 1.0 / (theta ** (np.arange(0, half, dtype=np.float32) / half))
    if scaling is None:
        return extra
    inter = extra / scaling.factor

    def dim_of(rotations):
        return (head_dim * math.log(scaling.original_max_position_embeddings
                                    / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(dim_of(scaling.beta_fast)), 0)
    high = min(math.ceil(dim_of(scaling.beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(half, dtype=np.float32) - low) / (high - low),
                   0, 1)
    return (inter * ramp + extra * (1 - ramp)).astype(np.float32)


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's magnitude correction ``0.1 * mscale * ln(factor) + 1``."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def rope_magnitude(scaling) -> float:
    """What YaRN multiplies cos and sin by (1 without scaling)."""
    if scaling is None:
        return 1.0
    return (yarn_mscale(scaling.factor, scaling.mscale)
            / yarn_mscale(scaling.factor, scaling.mscale_all_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               scaling=None) -> jax.Array:
    """x: (B, S, H, D) with D even; positions: (S,) or (B, S).  Pairs are
    the two halves of D (half-split rotation); ``scaling``: YaRN or None."""
    D = x.shape[-1]
    inv = jnp.asarray(rope_freqs(D, theta, scaling))           # (D/2,)
    if positions.ndim == 1:
        ang = positions[None, :, None].astype(jnp.float32) * inv
    else:
        ang = positions[..., None].astype(jnp.float32) * inv   # (B, S, D/2)
    mag = rope_magnitude(scaling)
    cos = jnp.cos(ang)[:, :, None, :] * mag
    sin = jnp.sin(ang)[:, :, None, :] * mag
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# causal depthwise conv (Mamba / RG-LRU front conv)
# ---------------------------------------------------------------------------
def causal_conv1d(x: jax.Array, w: jax.Array, *, cache=None):
    """Depthwise causal conv.  x: (B, S, C), w: (K, C).

    cache: (B, K-1, C) trailing context from the previous segment (or None).
    Returns (y (B, S, C), new_cache (B, K-1, C)).
    """
    B, S, C = x.shape
    K = w.shape[0]
    if cache is None:
        cache = jnp.zeros((B, K - 1, C), x.dtype)
    xp = jnp.concatenate([cache, x], axis=1)                   # (B, S+K-1, C)
    y = jnp.zeros((B, S, C), jnp.float32)
    for i in range(K):
        y = y + xp[:, i:i + S, :].astype(jnp.float32) * w[i].astype(jnp.float32)
    new_cache = xp[:, -(K - 1):, :] if K > 1 else jnp.zeros((B, 0, C), x.dtype)
    return y.astype(x.dtype), new_cache


def conv1d_decode_step(x: jax.Array, w: jax.Array, cache: jax.Array):
    """One-token conv step.  x: (B, C), cache: (B, K-1, C)."""
    K = w.shape[0]
    full = jnp.concatenate([cache, x[:, None, :]], axis=1)     # (B, K, C)
    y = jnp.einsum("bkc,kc->bc", full.astype(jnp.float32),
                   w.astype(jnp.float32))
    return y.astype(x.dtype), full[:, 1:, :]
