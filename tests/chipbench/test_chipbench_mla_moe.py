"""The latent-attention MoE cell on the CPU: a tiny DeepSeek-V2 shape run
through the harness by ``drivers/serve_mla_moe.py``, its readers, and
the operation and byte counts of ``costs_mla_moe.py`` against hand
counts at the committed configuration."""
import json
import time

import jax
import numpy as np
import pytest

from tinybench import REPO
from tinymoe import CELL, write_tiny_mla_moe_benchmark
from chipbench import costs_mla_moe as costs, harness, layers, spec
from chipbench import weights_mla_moe as W
from chipbench.peaks import PEAKS

SEED = 2**33 + 7
CONF = json.loads((REPO / "chipbench/configs/deepseek-v2-lite.json")
                  .read_text())
DS = costs.Dims.from_config(CONF)
NEW = ("paged_mla_decode_roofline", "grouped_matmul_roofline",
       "decode_step_ms.mla_moe", "prefill_step_ms.mla_moe",
       "idle_share.mla_moe")


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return write_tiny_mla_moe_benchmark(tmp_path_factory.mktemp("bench"))


def _run(root, traced):
    return harness.run(root, spec.find_cell(root, CELL), seed=SEED,
                       seconds=2.0, traced=traced, devices=jax.devices(),
                       peaks=PEAKS["TPU v5 lite"],
                       t_start=time.perf_counter())


def test_run_prints_the_contract_keys(root):
    r, out = _run(root, traced=False)
    assert r["correct"] is True
    assert set(r["metrics"]) == {"output_tokens_per_s", "setup_s"}
    assert all(m["value"] > 0 for m in r["metrics"].values())
    assert r["attempted"] > 0 and r["failed"] == 0
    assert r["check"]["widest_gap"]["value"] <= 1e-3
    # each step of the window kept its step programs' route counts
    routes = [a for s in out.window.steps for a in s.routes]
    assert routes and all(a.shape == (2, 4) for a in routes)
    assert sum(int(a.sum()) for a in routes) > 0


def test_traced_run_reads_the_new_metrics(root):
    r, out = _run(root, traced=True)
    assert r["correct"] is True
    ctx = out.context
    for name in NEW:
        value = spec.metric_reader(root, name)(ctx)
        assert value is None or value >= 0
    # no device plane on the CPU: the trace's busy time is 0
    assert r["metrics"]["idle_share.mla_moe"]["value"] == 100.0
    assert ctx.grouped_matmul_work()[0] > 0
    assert ctx.mla_decode_work()[1] > 0


def test_readers_return_nothing_for_other_cells():
    """Given a dense cell's context (or the parent program's), the readers
    that need this cell's records find none and return None."""
    ctx = layers.Context(dims=None, peaks=PEAKS["TPU v5 lite"], chips=1,
                         block=16, trace={"kernel_s": {"paged_decode": 1.0},
                                          "program_s": {"paged_decode": [1]}},
                         decode_calls=[[17]], prefill_calls=[],
                         queue_waits=[])
    for name in NEW[:4]:
        assert spec.metric_reader(REPO, name)(ctx) is None


def test_published_sizes_and_the_cut():
    assert DS.kv_bytes_per_token == 27 * (512 + 64) * 2 == 31104
    assert DS.params == pytest.approx(3.11e9, rel=0.005)
    full = DS.params + DS.moe_layers * 3 * 2048 * 1408 * (64 - 8)
    assert full == pytest.approx(15.71e9, rel=0.005)
    deploy = json.loads((REPO / "chipbench/cells/deepseek-v2-lite.batch.json")
                        .read_text())["serve"]
    assert deploy["kv_pool_bytes"] == 32 * 4096 * DS.kv_bytes_per_token
    cfg = W.program_config(CONF)
    assert (cfg.moe.num_experts, cfg.moe.held, cfg.moe.top_k) == (64, 8, 6)
    assert cfg.moe.norm_topk_prob is False
    assert cfg.rope_scaling.factor == 40
    assert cfg.num_layers == 27 and cfg.vocab_size == 102400


def test_paged_mla_decode_hand_count():
    # two rows of 17 and 16 tokens, 16-token pages: 2 + 1 live pages
    f, b = costs.paged_mla_decode(DS, [17, 16], 16)
    assert f == 27 * 33 * (2 * 16 * 576 + 2 * 16 * 512)
    page = 16 * 576 * 2                     # latent and rope key of 16 tokens
    qo = 2 * 16 * (576 + 512) * 2           # two rows' q_lat, q_rope, o_lat
    assert b == 27 * (3 * page + qo)


def test_grouped_matmul_hand_count():
    routes = np.zeros((26, 8), np.int32)
    routes[0, 3] = 5                        # 5 routes to one expert
    routes[7, 0] = routes[7, 1] = 1         # and one each to two others
    f, b = costs.grouped_matmul(DS, routes)
    assert f == 6 * 2048 * 1408 * 7
    assert b == 3 * (3 * 2048 * 1408 * 2) + 7 * 2 * 2048 * 2
