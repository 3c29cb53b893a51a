"""The trace reduction on a trace recorded on the chip.

``data/chat_trace_rows.json.gz`` holds four steps of the qwen2-0.5b chat
cell traced on one TPU v5 lite: the device's ops and program executions
and the host events around them.  Each expectation is computed here a
second, plainer way: busy time by a sweep over op starts and ends, kernel
time by summing matching ops, programs by which kernel starts inside
each execution.
"""
import gzip
import json
import re
from pathlib import Path

import pytest

import tinybench  # noqa: F401  (puts the repository root on sys.path)
from chipbench import layers, trace

DATA = Path(__file__).parent / "data" / "chat_trace_rows.json.gz"


@pytest.fixture(scope="module")
def rows():
    with gzip.open(DATA, "rt") as f:
        return [tuple(r) for r in json.load(f)["rows"]]


@pytest.fixture(scope="module")
def reduced(rows):
    return trace.reduce(rows, layers.KERNELS)


def _window(rows):
    marks = [r for r in rows if r[2].startswith("chipbench.")]
    return min(r[3] for r in marks), max(r[3] + r[4] for r in marks)


def _ops(rows):
    return [r for r in rows if r[0] == "/device:TPU:0" and r[1] == "XLA Ops"]


def test_busy_and_idle_share(rows, reduced):
    lo, hi = _window(rows)
    edges = sorted([(max(r[3], lo), 1) for r in _ops(rows)
                    if r[3] + r[4] > lo and r[3] < hi]
                   + [(min(r[3] + r[4], hi), -1) for r in _ops(rows)
                      if r[3] + r[4] > lo and r[3] < hi])
    depth, busy, last = 0, 0.0, None
    for t, step in edges:
        if depth > 0:
            busy += t - last
        depth += step
        last = t
    assert reduced["window_s"] == pytest.approx((hi - lo) / 1e9)
    assert reduced["busy_s"] == pytest.approx(busy / 1e9, rel=1e-9)
    idle = 1 - reduced["busy_s"] / reduced["window_s"]
    assert 0.0 < idle < 0.5
    assert sum(s for _, s in reduced["idle_gaps"]) == pytest.approx(
        reduced["window_s"] - reduced["busy_s"], rel=1e-6)


def test_kernel_time(rows, reduced):
    lo, hi = _window(rows)
    for k, pat in layers.KERNELS.items():
        want = sum(min(r[3] + r[4], hi) - max(r[3], lo) for r in _ops(rows)
                   if re.search(pat, r[2]) and r[3] + r[4] > lo and r[3] < hi)
        assert want > 0
        assert reduced["kernel_s"][k] == pytest.approx(want / 1e9)
    # one decode kernel per layer per decode call
    n = sum(1 for r in _ops(rows) if re.search(layers.KERNELS["paged_decode"],
                                               r[2]))
    assert n % 24 == 0 and n > 0


def test_program_time(rows, reduced):
    lo, hi = _window(rows)
    mods = [r for r in rows if r[1] == "XLA Modules" and r[3] >= lo
            and r[3] + r[4] <= hi and r[2].startswith("jit_step")]
    dec = [r for r in mods if any(
        re.search(layers.KERNELS["paged_decode"], o[2])
        and r[3] <= o[3] <= r[3] + r[4] for o in _ops(rows))]
    assert dec
    got = reduced["program_s"]["paged_decode"]
    assert sorted(got) == pytest.approx(sorted(r[4] / 1e9 for r in dec))
    # the decode kernel runs inside the decode program
    assert reduced["kernel_s"]["paged_decode"] < sum(got)
    assert "ragged_prefill" in reduced["program_s"]


def test_device_ops_leave_out_loops_and_name_the_kernel(reduced):
    names = [n for n, _ in reduced["device_ops"]]
    assert len(names) <= 10
    assert not any(n.endswith(" while") for n in names)
    assert any(re.search(layers.KERNELS["paged_decode"], n) for n in names)


def test_short_name():
    long = ('%closed_call.13 = bf16[64,2,7,64]{3,2,1,0:T(8,128)(2,1)S(1)} '
            'custom-call(s32[64,128]{1,0:T(8,128)S(1)} %copy-done), '
            'custom_call_target="tpu_custom_call", operand_layout_'
            'constraints={s32[64,128]{1,0}}')
    assert trace.short_name(long) == (
        "%closed_call.13 = bf16[64,2,7,64] custom-call tpu_custom_call")
    assert trace.short_name("%while.5 = (s32[]{:T(128)}, bf16[64]{0}) "
                            "while((s32[]) %tuple.54), condition=%c") == (
        "%while.5 = (tuple) while")
    assert trace.short_name("jit_step(123)") == "jit_step(123)"


def test_no_host_marks_reduce_to_nothing():
    assert trace.reduce([("/device:TPU:0", "XLA Ops", "x", 0.0, 1.0)],
                        layers.KERNELS) == {}
