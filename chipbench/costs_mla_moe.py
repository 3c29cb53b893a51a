"""Operations and bytes the algorithm needs, for a decoder with latent
attention (MLA) and an expert share of its MoE layers (DeepSeek-V2).

As in ``costs.py``, these count what the model requires, never what a
kernel happens to fetch or compute on top: the live pages of the latent
cache and the routes the held experts take.  Page ``w`` of a row of
length ``n`` is live iff ``w * block < n`` (``costs.live_pages``).
"""
from __future__ import annotations

import dataclasses
from typing import Iterable, Tuple

import numpy as np

from chipbench.costs import live_pages


@dataclasses.dataclass(frozen=True)
class Dims:
    """Sizes of a DeepSeek-V2-style decoder, read from a configuration file
    (the published keys; ``n_routed_experts`` counts the experts held)."""
    layers: int
    dense_layers: int               # leading layers with a dense FFN
    d_model: int
    heads: int
    kv_lora_rank: int               # R: the latent cached per token
    rope_dim: int                   # r: the shared rope key cached per token
    nope_dim: int
    v_dim: int
    d_ff: int                       # the dense FFN's width
    expert_ff: int                  # each routed or shared expert's width
    experts: int                    # routed experts the router scores
    experts_held: int               # of which this chip holds
    first_held: int
    top_k: int
    shared: int                     # shared experts (one SwiGLU, shared x F)
    norm_topk: bool
    routed_scale: float
    vocab: int
    rope_theta: float
    yarn: Tuple[float, int, float, float, float, float]   # factor, original
    #   positions, beta_fast, beta_slow, mscale, mscale_all_dim
    norm_eps: float
    tied: bool = False
    itemsize: int = 2

    @classmethod
    def from_config(cls, conf: dict) -> "Dims":
        rs = conf["rope_scaling"]
        assert rs["type"] == "yarn", rs
        return cls(layers=conf["num_hidden_layers"],
                   dense_layers=conf["first_k_dense_replace"],
                   d_model=conf["hidden_size"],
                   heads=conf["num_attention_heads"],
                   kv_lora_rank=conf["kv_lora_rank"],
                   rope_dim=conf["qk_rope_head_dim"],
                   nope_dim=conf["qk_nope_head_dim"],
                   v_dim=conf["v_head_dim"],
                   d_ff=conf["intermediate_size"],
                   expert_ff=conf["moe_intermediate_size"],
                   experts=conf["n_routed_experts_published"],
                   experts_held=conf["n_routed_experts"],
                   first_held=conf["first_expert_held"],
                   top_k=conf["num_experts_per_tok"],
                   shared=conf["n_shared_experts"],
                   norm_topk=bool(conf["norm_topk_prob"]),
                   routed_scale=float(conf["routed_scaling_factor"]),
                   vocab=conf["vocab_size"],
                   rope_theta=float(conf["rope_theta"]),
                   yarn=(float(rs["factor"]),
                         int(rs["original_max_position_embeddings"]),
                         float(rs["beta_fast"]), float(rs["beta_slow"]),
                         float(rs["mscale"]), float(rs["mscale_all_dim"])),
                   norm_eps=float(conf["rms_norm_eps"]),
                   tied=bool(conf["tie_word_embeddings"]),
                   itemsize={"bfloat16": 2, "float32": 4}[
                       conf["torch_dtype"]])

    @property
    def moe_layers(self) -> int:
        return self.layers - self.dense_layers

    @property
    def page_bytes_per_token(self) -> int:
        """The latent and the rope key of one token in one layer."""
        return (self.kv_lora_rank + self.rope_dim) * self.itemsize

    @property
    def kv_bytes_per_token(self) -> int:
        return self.layers * self.page_bytes_per_token

    @property
    def attn_params(self) -> int:
        d, H, R, r = self.d_model, self.heads, self.kv_lora_rank, self.rope_dim
        return (d * H * (self.nope_dim + r) + d * (R + r) + R
                + R * H * (self.nope_dim + self.v_dim) + H * self.v_dim * d)

    @property
    def params(self) -> int:
        """Parameters this chip holds."""
        d, F = self.d_model, self.expert_ff
        moe = (d * self.experts + 3 * d * F * (self.experts_held
                                               + self.shared))
        return (self.layers * (self.attn_params + 2 * d)
                + self.dense_layers * 3 * d * self.d_ff
                + self.moe_layers * moe + 2 * self.vocab * d + d)


def paged_mla_decode(dims: Dims, lengths: Iterable[int], block: int
                     ) -> Tuple[float, float]:
    """(flops, bytes) of one decode call's latent attention over every
    layer: each live page's latent and rope key read once, each row's
    absorbed query (``q_lat``, ``q_rope``) read and latent output
    (``o_lat``) written; scores ``2 H (R + r)`` and readout ``2 H R``
    flops per live token."""
    lengths = list(lengths)
    H, R, r = dims.heads, dims.kv_lora_rank, dims.rope_dim
    tokens = sum(int(n) for n in lengths)
    pages = sum(live_pages(n, block) for n in lengths)
    flops = dims.layers * tokens * (2 * H * (R + r) + 2 * H * R)
    qo = len(lengths) * H * (R + r + R) * dims.itemsize
    nbytes = dims.layers * (pages * block * dims.page_bytes_per_token + qo)
    return float(flops), float(nbytes)


def grouped_matmul(dims: Dims, routes) -> Tuple[float, float]:
    """(flops, bytes) of the held experts' work in one step program, from
    its route counts ``routes`` (moe layers, held experts): ``6 d F`` flops
    per held route (gate, up, down); each routed held expert's three
    matrices read once, and each route's row read and written."""
    routes = np.asarray(routes, np.int64)
    d, F, s = dims.d_model, dims.expert_ff, dims.itemsize
    n = int(routes.sum())
    flops = 6 * d * F * n
    nbytes = int((routes > 0).sum()) * 3 * d * F * s + n * 2 * d * s
    return float(flops), float(nbytes)
