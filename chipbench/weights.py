"""Random weights from the seed, made on the device in one jitted call.

Layout (the benchmark's; ``serving.program_params`` hands the same arrays
to the program in its own tree):

  embed        (vocab_rows, d)        rows beyond the vocabulary are padding
  unembed      (vocab_rows, d)        only where embeddings are not tied
  final_norm   (d,)                   RMSNorm gain minus 1
  layers       a dict of arrays stacked over layers:
    norm1, norm2                 (L, d)        gain minus 1
    attn.wq (L, d, H*D)  attn.wk, attn.wv (L, d, KV*D)  attn.wo (L, H*D, d)
    attn.bq, attn.bk, attn.bv    (L, H*D), (L, KV*D)    with attention bias
    ffn.w_gate, ffn.w_up (L, d, F)  ffn.w_down (L, F, d)

Matrices are normal with variance 1/fan_in, embeddings 1/d, biases and
gains small, so logits have a spread near 1 over the vocabulary.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from chipbench.costs import Dims


def shapes(dims: Dims, vocab_rows: int) -> dict:
    L, d, H, KV, D, F = (dims.layers, dims.d_model, dims.heads,
                         dims.kv_heads, dims.head_dim, dims.d_ff)
    attn = {"wq": (L, d, H * D), "wk": (L, d, KV * D), "wv": (L, d, KV * D),
            "wo": (L, H * D, d)}
    if dims.qkv_bias:
        attn.update(bq=(L, H * D), bk=(L, KV * D), bv=(L, KV * D))
    out = {"embed": (vocab_rows, d), "final_norm": (d,),
           "layers": {"norm1": (L, d), "norm2": (L, d), "attn": attn,
                      "ffn": {"w_gate": (L, d, F), "w_up": (L, d, F),
                              "w_down": (L, F, d)}}}
    if not dims.tied:
        out["unembed"] = (vocab_rows, d)
    return out


def _scale(path: str, shape) -> float:
    name = path.rsplit("/", 1)[-1]
    if name in ("embed", "unembed"):
        return shape[-1] ** -0.5
    if name.startswith("norm") or name == "final_norm":
        return 0.1
    if name.startswith("b"):
        return 0.1
    return shape[-2] ** -0.5            # fan-in of a (.., in, out) matrix


@functools.partial(jax.jit, static_argnames=("dims", "vocab_rows", "dtype"))
def make(seed_key, *, dims: Dims, vocab_rows: int, dtype: str = "bfloat16"):
    """Weights in ``dtype``, the type they are served in."""
    tree = shapes(dims, vocab_rows)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        tree, is_leaf=lambda x: isinstance(x, tuple))
    keys = jax.random.split(seed_key, len(leaves))
    out = []
    for k, (path, shape) in zip(keys, leaves):
        name = "/".join(str(getattr(p, "key", p)) for p in path)
        out.append(jax.random.normal(k, shape, jnp.dtype(dtype))
                   * jnp.asarray(_scale(name, shape), dtype))
    return jax.tree_util.tree_unflatten(treedef, out)


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any whole number, including ones over 32 bits."""
    s = int(seed) & ((1 << 64) - 1)
    return jax.random.fold_in(jax.random.PRNGKey(s & 0xFFFFFFFF),
                              (s >> 32) & 0xFFFFFFFF)
