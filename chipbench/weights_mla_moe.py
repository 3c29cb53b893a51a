"""Random weights from the seed for a DeepSeek-V2-style decoder (latent
attention, an expert share of each MoE layer), made on the device in one
jitted call, and the program's configuration and tree for them.

Layout (the benchmark's; ``program_params`` hands the same arrays to the
program in its own tree):

  embed, unembed   (vocab_rows, d)       rows beyond the vocabulary are padding
  final_norm       (d,)                  RMSNorm gain minus 1
  dense, moe       dicts of arrays stacked over the leading dense layers
                   and over the MoE layers:
    norm1, norm2                    (L, d)        gain minus 1
    attn.wq (L, d, H*(nope+r))      per head: the nope part, then the rope part
    attn.w_dkv (L, d, R+r)          the latent, then the shared rope key
    attn.kv_norm (L, R)             the latent's RMSNorm gain minus 1
    attn.w_uk (L, R, H*nope)  attn.w_uv (L, R, H*v)  attn.wo (L, H*v, d)
    dense: ffn.w_gate, ffn.w_up (L, d, F_dense)  ffn.w_down (L, F_dense, d)
    moe:   ffn.router (L, d, E)      scores every routed expert
           ffn.w_gate, ffn.w_up (L, held, d, F)  ffn.w_down (L, held, F, d)
                                     the held experts, in order from the first
           ffn.ws_gate, ffn.ws_up (L, d, shared*F)  ffn.ws_down (L, shared*F, d)

Matrices are normal with variance 1/fan_in, embeddings 1/d, gains small,
as ``weights.py`` makes them.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from chipbench.costs_mla_moe import Dims
from chipbench.weights import seed_key  # noqa: F401  (re-export)


def shapes(dims: Dims, vocab_rows: int) -> dict:
    d, H, R, r = dims.d_model, dims.heads, dims.kv_lora_rank, dims.rope_dim
    nope, v, F = dims.nope_dim, dims.v_dim, dims.expert_ff

    def attn(L):
        return {"wq": (L, d, H * (nope + r)), "w_dkv": (L, d, R + r),
                "kv_norm": (L, R), "w_uk": (L, R, H * nope),
                "w_uv": (L, R, H * v), "wo": (L, H * v, d)}

    Ld, Lm, E, Eh, Fs = (dims.dense_layers, dims.moe_layers, dims.experts,
                         dims.experts_held, dims.shared * F)
    return {
        "embed": (vocab_rows, d), "unembed": (vocab_rows, d),
        "final_norm": (d,),
        "dense": {"norm1": (Ld, d), "norm2": (Ld, d), "attn": attn(Ld),
                  "ffn": {"w_gate": (Ld, d, dims.d_ff),
                          "w_up": (Ld, d, dims.d_ff),
                          "w_down": (Ld, dims.d_ff, d)}},
        "moe": {"norm1": (Lm, d), "norm2": (Lm, d), "attn": attn(Lm),
                "ffn": {"router": (Lm, d, E), "w_gate": (Lm, Eh, d, F),
                        "w_up": (Lm, Eh, d, F), "w_down": (Lm, Eh, F, d),
                        "ws_gate": (Lm, d, Fs), "ws_up": (Lm, d, Fs),
                        "ws_down": (Lm, Fs, d)}},
    }


def _scale(name: str, shape) -> float:
    if name in ("embed", "unembed"):
        return shape[-1] ** -0.5
    if "norm" in name:
        return 0.1
    return shape[-2] ** -0.5            # fan-in of a (.., in, out) matrix


@functools.partial(jax.jit, static_argnames=("dims", "vocab_rows", "dtype"))
def make(key, *, dims: Dims, vocab_rows: int, dtype: str = "bfloat16"):
    """Weights in ``dtype``, the type they are served in."""
    tree = shapes(dims, vocab_rows)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        tree, is_leaf=lambda x: isinstance(x, tuple))
    keys = jax.random.split(key, len(leaves))
    out = []
    for k, (path, shape) in zip(keys, leaves):
        name = str(getattr(path[-1], "key", path[-1]))
        out.append(jax.random.normal(k, shape, jnp.dtype(dtype))
                   * jnp.asarray(_scale(name, shape), dtype))
    return jax.tree_util.tree_unflatten(treedef, out)


def program_params(weights: dict) -> dict:
    """The benchmark's weights in the program's tree (same arrays): one
    scanned segment of dense layers, then one of MoE layers."""
    out = {k: weights[k] for k in ("embed", "unembed", "final_norm")}
    out["seg0"] = (weights["dense"],)
    out["seg1"] = (weights["moe"],)
    return out


def program_config(conf: dict):
    """The program's ModelConfig with every size, the gate, YaRN and the
    expert share from the config file."""
    import dataclasses as dc

    from repro.configs.base import (MLAConfig, MoEConfig, RopeScaling,
                                    get_config)
    dims = Dims.from_config(conf)
    factor, orig, fast, slow, msc, msc_all = dims.yarn
    return dc.replace(
        get_config(conf["program_name"]), num_layers=dims.layers,
        d_model=dims.d_model, num_heads=dims.heads, num_kv_heads=dims.heads,
        head_dim=dims.nope_dim, d_ff=dims.d_ff, vocab_size=dims.vocab,
        rope_theta=dims.rope_theta, norm_eps=dims.norm_eps,
        tie_embeddings=dims.tied, dtype=conf["torch_dtype"],
        rope_scaling=RopeScaling(
            factor=factor, original_max_position_embeddings=orig,
            beta_fast=fast, beta_slow=slow, mscale=msc,
            mscale_all_dim=msc_all),
        mla=MLAConfig(kv_lora_rank=dims.kv_lora_rank, q_lora_rank=0,
                      qk_nope_head_dim=dims.nope_dim,
                      qk_rope_head_dim=dims.rope_dim, v_head_dim=dims.v_dim),
        moe=MoEConfig(num_experts=dims.experts,
                      num_shared_experts=dims.shared, top_k=dims.top_k,
                      d_ff_expert=dims.expert_ff,
                      first_k_dense=dims.dense_layers,
                      norm_topk_prob=dims.norm_topk,
                      routed_scaling_factor=dims.routed_scale,
                      experts_held=dims.experts_held,
                      first_expert_held=dims.first_held))
