"""Helpers of the chip benchmark's CPU tests: a tiny benchmark on disk.

``tiny_root`` (a fixture; import it into the test module) writes a
BENCHMARK.json with one open-loop and one backlog
cell of a two-layer model into a temporary directory, with the config,
mix and cell files the harness finds by name, and copies of the
repository's drivers, generators and metric readers.
"""
import json
import shutil
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

TINY_CONFIG = {
    "name": "tiny", "source": "test", "program_name": "qwen2-0.5b",
    "hidden_size": 128, "intermediate_size": 256, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
    "vocab_size": 512, "rope_theta": 10000.0, "rms_norm_eps": 1e-6,
    "tie_word_embeddings": True, "attention_bias": True,
    "torch_dtype": "float32", "reduced": [],
}
TINY_SERVE = {"max_slots": 4, "max_context": 128, "prefill_chunk": 16,
              "prefill_batch": 4, "prefill_chunks_per_step": 4,
              "kv_pool_bytes": 4 * 128 * 2 * 2 * 2 * 32 * 4,
              "max_queue": 64}
TINY_MIXES = {
    "tiny_open": {"kind": "open", "rate_per_s": 6.0, "cv": 2.0,
                  "lead_s": 0.5,
                  "prompt": {"dist": "lognormal", "median": 24, "sigma": 0.5,
                             "min": 4, "max": 64},
                  "output": {"dist": "lognormal", "median": 6, "sigma": 0.5,
                             "min": 2, "max": 16}},
    "tiny_backlog": {"kind": "backlog", "backlog_per_seat": 2,
                     "open_after_finished": 2,
                     "prompt": {"dist": "lognormal", "median": 40,
                                "sigma": 0.5, "min": 16, "max": 96},
                     "output": {"dist": "lognormal", "median": 8,
                                "sigma": 0.5, "min": 4, "max": 16}},
}


PLUGINS = ("drivers", "generators", "end_to_end", "metrics")


def _dump(path: Path, obj) -> None:
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(obj, indent=1))


def write_tiny_benchmark(root: Path) -> Path:
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    bench["configs"] = [{"name": "tiny", "source": "test",
                         "file": "chipbench/configs/tiny.json",
                         "reduced": [], "why": "test"}]
    bench["workloads"] = [
        {"name": "tiny.open", "config": "tiny", "traffic": "tiny_open",
         "chips": 1, "why": "test"},
        {"name": "tiny.backlog", "config": "tiny", "traffic": "tiny_backlog",
         "chips": 1, "why": "test"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = (["tiny.open"] if "qwen2-0.5b.chat"
                              in m["workloads"] else ["tiny.backlog"])
    _dump(root / "BENCHMARK.json", bench)
    _dump(root / "chipbench/configs/tiny.json", TINY_CONFIG)
    for name, mix in TINY_MIXES.items():
        _dump(root / f"chipbench/traffic/{name}.json", mix)
    for cell in ("tiny.open", "tiny.backlog"):
        _dump(root / f"chipbench/cells/{cell}.json",
              {"driver": "serve", "serve": TINY_SERVE,
               "check": {"requests": 3,
                         "limits": {"widest_gap": 1e-3, "mean_gap": 1e-4}}})
    for group in PLUGINS:
        shutil.copytree(REPO / "chipbench" / group, root / "chipbench" / group,
                        ignore=shutil.ignore_patterns("__pycache__"))
    return root


@pytest.fixture
def tiny_root(tmp_path):
    return write_tiny_benchmark(tmp_path)
