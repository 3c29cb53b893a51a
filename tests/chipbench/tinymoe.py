"""Helpers of the chip benchmark's CPU tests of the latent-attention MoE
cells: a DeepSeek-V2-shaped configuration at a tiny size, and a tiny
benchmark on disk (``tinybench``'s, plus one backlog cell of it)."""
import json
from pathlib import Path

from tinybench import REPO, _dump, write_tiny_benchmark

# d 128, 4 heads, latent 64, rope 16, nope 32, v 32, 3 layers with the
# first dense, 16 routed experts of which 4 are held (4-7), top-3, one
# shared expert, YaRN factor 40 over 32 original positions
TINY_MLA_MOE = {
    "name": "tiny-mla-moe", "source": "test",
    "program_name": "deepseek-v2-lite-16b",
    "hidden_size": 128, "intermediate_size": 256, "num_hidden_layers": 3,
    "first_k_dense_replace": 1, "num_attention_heads": 4,
    "num_key_value_heads": 4, "kv_lora_rank": 64, "q_lora_rank": None,
    "qk_rope_head_dim": 16, "qk_nope_head_dim": 32, "v_head_dim": 32,
    "moe_intermediate_size": 64, "n_routed_experts": 4,
    "n_routed_experts_published": 16, "first_expert_held": 4,
    "num_experts_per_tok": 3, "n_shared_experts": 1,
    "norm_topk_prob": False, "routed_scaling_factor": 1.0,
    "vocab_size": 512, "rope_theta": 10000.0,
    "rope_scaling": {"type": "yarn", "factor": 40,
                     "original_max_position_embeddings": 32,
                     "beta_fast": 32, "beta_slow": 1, "mscale": 0.707,
                     "mscale_all_dim": 0.707},
    "rms_norm_eps": 1e-6, "tie_word_embeddings": False,
    "torch_dtype": "float32", "reduced": [],
}
# 4 seats at 128 tokens of 3 x (64 + 16) float32 latents
TINY_MLA_MOE_SERVE = {"max_slots": 4, "max_context": 128, "prefill_chunk": 16,
                      "prefill_batch": 4, "prefill_chunks_per_step": 4,
                      "kv_pool_bytes": 4 * 128 * 3 * 80 * 4, "max_queue": 64}
CELL = "tiny-mla-moe.backlog"


def write_tiny_mla_moe_benchmark(root: Path) -> Path:
    """The tiny benchmark with a backlog cell of the tiny MLA-MoE config,
    which reports the metrics that ``deepseek-v2-lite.batch`` reports."""
    write_tiny_benchmark(root)
    repo = json.loads((REPO / "BENCHMARK.json").read_text())
    reported = {m["name"] for m in repo["end_to_end"] + repo["per_layer"]
                if "deepseek-v2-lite.batch" in m.get("workloads", ())}
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny-mla-moe", "source": "test",
                             "file": "chipbench/configs/tiny-mla-moe.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": CELL, "config": "tiny-mla-moe",
                               "traffic": "tiny_backlog", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] in reported:
            m["workloads"].append(CELL)
    _dump(root / "BENCHMARK.json", bench)
    _dump(root / "chipbench/configs/tiny-mla-moe.json", TINY_MLA_MOE)
    _dump(root / f"chipbench/cells/{CELL}.json",
          {"driver": "serve_mla_moe", "serve": TINY_MLA_MOE_SERVE,
           "check": {"requests": 3, "limits": {"widest_gap": 1e-3}}})
    return root
