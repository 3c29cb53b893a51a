"""``prefill_step_ms`` reads the prefill program's executions alone, and
reads nothing where the window ran no prefill program."""
import pytest

from tinybench import REPO, TINY_CONFIG
from chipbench import layers, spec
from chipbench.costs import Dims


def _ctx(program_s):
    return layers.Context(
        dims=Dims.from_config(TINY_CONFIG), peaks={}, chips=1, block=16,
        trace={"window_s": 1.0, "busy_s": 0.9, "program_s": program_s},
        decode_calls=[], prefill_calls=[], queue_waits=[])


@pytest.mark.parametrize("program_s, want", [
    ({"ragged_prefill": [0.010, 0.030], "paged_decode": [0.060] * 5}, 20.0),
    ({"ragged_prefill": [0.0125]}, 12.5),
    ({"paged_decode": [0.060]}, None),
    ({}, None),
])
def test_prefill_step_ms(program_s, want):
    got = spec.metric_reader(REPO, "prefill_step_ms")(_ctx(program_s))
    assert got == (pytest.approx(want) if want is not None else None)
