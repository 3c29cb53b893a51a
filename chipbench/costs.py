"""Operations and bytes the algorithm needs, from shapes alone.

These count what the model and its attention require: live pages of the
paged cache and causal query-key pairs, never what a kernel happens to
fetch or compute on top.  So a count stays right when a later change
replaces a kernel.  The page walk follows the program's
``kernels/perf_model.py`` (``decode_pages_visited``): page ``w`` of a row
of length ``n`` is live iff ``w * block < n``.
"""
from __future__ import annotations

import dataclasses
from typing import Iterable, Tuple


@dataclasses.dataclass(frozen=True)
class Dims:
    """A dense GQA decoder's sizes, read from a configuration file."""
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    rope_theta: float
    norm_eps: float
    tied: bool
    qkv_bias: bool
    itemsize: int = 2

    @classmethod
    def from_config(cls, conf: dict) -> "Dims":
        return cls(layers=conf["num_hidden_layers"],
                   d_model=conf["hidden_size"],
                   heads=conf["num_attention_heads"],
                   kv_heads=conf["num_key_value_heads"],
                   head_dim=conf["head_dim"],
                   d_ff=conf["intermediate_size"],
                   vocab=conf["vocab_size"],
                   rope_theta=float(conf["rope_theta"]),
                   norm_eps=float(conf["rms_norm_eps"]),
                   tied=bool(conf["tie_word_embeddings"]),
                   qkv_bias=bool(conf["attention_bias"]),
                   itemsize={"bfloat16": 2, "float32": 4}[
                       conf["torch_dtype"]])

    @property
    def layer_matmul_params(self) -> int:
        d, hd = self.d_model, self.head_dim
        return (d * self.heads * hd + 2 * d * self.kv_heads * hd
                + self.heads * hd * d + 3 * d * self.d_ff)

    @property
    def kv_bytes_per_token(self) -> int:
        return 2 * self.layers * self.kv_heads * self.head_dim * self.itemsize

    @property
    def page_bytes_per_token(self) -> int:
        """K and V of one token in one layer."""
        return 2 * self.kv_heads * self.head_dim * self.itemsize


def attention_flops(dims: Dims, pairs: int) -> float:
    """Scores and readout over ``pairs`` query-key pairs, all layers."""
    return 4.0 * dims.layers * dims.heads * dims.head_dim * pairs


def causal_pairs(start: int, n: int) -> int:
    """Query-key pairs of ``n`` queries at positions ``start..start+n-1``."""
    return n * start + n * (n + 1) // 2


def prompt_flops(dims: Dims, start: int, n: int) -> float:
    """Model FLOPs of ``n`` prompt tokens after ``start`` cached ones: the
    layer matmuls and causal attention (no logits: only the last prompt
    token's are needed, and they are counted with the first output)."""
    return (2.0 * dims.layers * dims.layer_matmul_params * n
            + attention_flops(dims, causal_pairs(start, n)))


def output_flops(dims: Dims, context: int) -> float:
    """Model FLOPs of one generated token attending ``context`` tokens
    (itself included), logits over the vocabulary included."""
    return (2.0 * dims.layers * dims.layer_matmul_params
            + 2.0 * dims.d_model * dims.vocab
            + attention_flops(dims, context))


def live_pages(length: int, block: int) -> int:
    return -(-int(length) // block)


def paged_decode(dims: Dims, lengths: Iterable[int], block: int
                 ) -> Tuple[float, float]:
    """(flops, bytes) of one decode call's attention over every layer: each
    live row's live pages of K and V read once, its query read, its output
    written."""
    lengths = list(lengths)
    pages = sum(live_pages(n, block) for n in lengths)
    flops = 4.0 * dims.layers * dims.heads * dims.head_dim * block * pages
    qo = 2 * len(lengths) * dims.heads * dims.head_dim * dims.itemsize
    nbytes = dims.layers * (pages * block * dims.page_bytes_per_token + qo)
    return flops, float(nbytes)


def ragged_prefill(dims: Dims, rows: Iterable[Tuple[int, int]], block: int
                   ) -> Tuple[float, float]:
    """(flops, bytes) of one prefill call's attention over every layer, for
    rows of ``(start, n)``: causal pairs, the pages up to ``start + n`` read
    once, queries read and outputs written."""
    flops = nbytes = 0.0
    for start, n in rows:
        flops += attention_flops(dims, causal_pairs(start, n))
        pages = live_pages(start + n, block)
        qo = 2 * n * dims.heads * dims.head_dim * dims.itemsize
        nbytes += dims.layers * (pages * block * dims.page_bytes_per_token
                                 + qo)
    return flops, nbytes


def roofline_seconds(flops: float, nbytes: float, peaks: dict) -> float:
    """The least time the chip could take: the larger of the two bounds."""
    return max(flops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])
