"""Operation and byte counts against hand counts."""
import json

import pytest

from tinybench import REPO
from chipbench import costs
from chipbench.peaks import PEAKS, UnknownDevice, peaks_for

QWEN = costs.Dims.from_config(json.loads(
    (REPO / "chipbench/configs/qwen2-0.5b.json").read_text()))
GRANITE = costs.Dims.from_config(json.loads(
    (REPO / "chipbench/configs/granite-3-2b.json").read_text()))


def test_published_sizes():
    assert QWEN.kv_bytes_per_token == 12288
    assert GRANITE.kv_bytes_per_token == 81920
    # parameters: layer matmuls + embedding (tied) + norms
    qwen = QWEN.layers * QWEN.layer_matmul_params + QWEN.vocab * 896
    assert qwen == pytest.approx(0.494e9, rel=0.01)
    gran = GRANITE.layers * GRANITE.layer_matmul_params + 49155 * 2048
    assert gran == pytest.approx(2.53e9, rel=0.01)


def test_paged_decode_hand_count():
    # two rows of 17 and 16 tokens, 16-token pages: 2 + 1 live pages
    f, b = costs.paged_decode(QWEN, [17, 16], 16)
    assert f == 4 * 24 * 14 * 64 * 16 * 3
    page = 16 * 2 * 2 * 64 * 2              # K and V of 16 tokens, 2 heads
    qo = 2 * 2 * 14 * 64 * 2                # two rows' query and output
    assert b == 24 * (3 * page + qo)


def test_ragged_prefill_hand_count():
    # a chunk of 3 queries after 5 cached tokens: 6 + 7 + 8 pairs
    assert costs.causal_pairs(5, 3) == 21
    f, b = costs.ragged_prefill(QWEN, [(5, 3)], 16)
    assert f == 4 * 24 * 14 * 64 * 21
    assert b == 24 * (1 * 16 * 2 * 2 * 64 * 2 + 2 * 3 * 14 * 64 * 2)


def test_model_flops_hand_count():
    mm = 2 * 24 * QWEN.layer_matmul_params
    assert costs.prompt_flops(QWEN, 0, 2) == 2 * mm + 4 * 24 * 14 * 64 * 3
    assert costs.output_flops(QWEN, 10) == (
        mm + 2 * 896 * 151936 + 4 * 24 * 14 * 64 * 10)


def test_roofline_takes_the_larger_bound():
    p = PEAKS["TPU v5 lite"]
    assert costs.roofline_seconds(197e12, 0, p) == pytest.approx(1.0)
    assert costs.roofline_seconds(1.0, 819e9 * 2, p) == pytest.approx(2.0)


def test_peaks_are_keyed_by_device_kind():
    assert peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    assert peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(UnknownDevice):
        peaks_for("cpu")
