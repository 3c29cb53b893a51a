"""A whole run of a tiny cell through the harness on the CPU, past the
look for a chip: the result line, the window's invariants, and the
comparison behind ``correct`` against a broken timed path and against
the int8 control.  The tiny model runs in float32, where the program and
the reference agree to rounding, so its limit is 1e-3."""
import time

import jax
import pytest

from tinybench import tiny_root  # noqa: F401  (fixture)
from chipbench import check, harness, serving, spec
from chipbench.peaks import PEAKS

SEED = 2**33 + 5


def run(root, cell, traced=False, seconds=2.0):
    return harness.run_cell(root, cell, seed=SEED, seconds=seconds,
                            traced=traced, devices=jax.devices(),
                            peaks=PEAKS["TPU v5 lite"],
                            t_start=time.perf_counter())


@pytest.mark.parametrize("cell,e2e", [
    ("tiny.open", {"ttft_p90_s", "itl_p95_s", "setup_s"}),
    ("tiny.backlog", {"output_tokens_per_s", "setup_s"})])
def test_run_prints_the_contract_keys(tiny_root, cell, e2e):
    r = run(tiny_root, cell)
    assert r["correct"] is True
    assert set(r) == {"correct", "attempted", "failed", "metrics", "device",
                      "check"}
    assert list(r)[-1] == "check"
    assert set(r["metrics"]) == e2e
    assert all(m["value"] > 0 for m in r["metrics"].values())
    assert r["device"]["platform"] == "cpu" and r["device"]["count"] >= 1
    assert r["attempted"] > 0 and r["failed"] == 0
    assert r["check"]["widest_gap"]["value"] <= 1e-3


def test_windows_open_at_load(tiny_root):
    """The open loop's lead is load, not the window's; the backlog's window
    opens once requests have finished, and freed seats take queued
    requests inside it."""
    kw = dict(seed=SEED, seconds=2.0, traced=False, devices=jax.devices(),
              peaks=PEAKS["TPU v5 lite"], t_start=time.perf_counter())
    _, out = harness.run(tiny_root, spec.find_cell(tiny_root, "tiny.open"),
                         **kw)
    win = out.window
    assert win.flights and all(f.due >= win.t_open for f in win.flights)
    assert min(f.index for f in win.flights) == 0
    assert out.setup_s > 0.5                     # the lead is set-up
    cell = spec.find_cell(tiny_root, "tiny.backlog")
    _, out = harness.run(tiny_root, cell, **kw)
    win = out.window
    before = [f for f in win.flights if f.done and f.times[-1] < win.t_open]
    assert len(before) >= cell.traffic["open_after_finished"]
    seats = cell.deploy["serve"]["max_slots"]
    admitted = [f for f in win.flights if f.index >= seats and f.times
                and f.times[0] >= win.t_open]
    assert admitted


def test_traced_run_reads_per_layer_metrics(tiny_root):
    r = run(tiny_root, "tiny.open", traced=True)
    assert r["correct"] is True
    # no device plane on the CPU: only the benchmark's own counts remain
    assert "queue_wait_p90_s" in r["metrics"]
    assert "decode_mfu" in r["metrics"]
    assert "paged_decode_roofline.latency" not in r["metrics"]
    assert r["device"]["window_s"] > 0
    assert set(r["breakdown"]) == {"device_ops", "idle_gaps"}


def test_a_token_altered_where_it_is_produced_is_not_correct(
        tiny_root, monkeypatch):
    build = serving.build_server

    def broken(*a, **k):
        srv = build(*a, **k)
        step = srv.engine._decode_step

        def altered(*args):
            logits, pools = step(*args)
            return logits.at[:, :, 7].add(1e4), pools
        srv.engine._decode_step = altered
        return srv
    monkeypatch.setattr(serving, "build_server", broken)
    r = run(tiny_root, "tiny.open")
    assert r["correct"] is False
    assert r["check"]["widest_gap"]["value"] > 1e-3


def test_the_int8_control_is_not_correct(tiny_root, monkeypatch):
    """The control puts the int8 forward's greedy token in the program's
    place at each position of the served requests, judged against the
    cell's limits.  With a vocabulary of 512 the top two logits lie far
    apart, so the control is read over every finished request (about a
    thousand tokens)."""
    sample = check.sample
    monkeypatch.setattr(check, "sample",
                        lambda flights, seed, k: sample(flights, seed, 1000))
    r, out = harness.run(
        tiny_root, spec.find_cell(tiny_root, "tiny.backlog"), seed=SEED,
        seconds=2.0, traced=False, devices=jax.devices(),
        peaks=PEAKS["TPU v5 lite"], t_start=time.perf_counter(),
        control=True)
    assert r["correct"] is True
    assert check.holds(out.control) is False
    assert out.control["widest_gap"]["value"] > r["check"]["widest_gap"][
        "limit"]
    assert out.readings["program"]["widest_gap"] == r["check"]["widest_gap"][
        "value"]
