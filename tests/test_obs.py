"""HyperTrace: tracer/metrics unit contracts + serve-lifecycle timeline.

Unit layer: span nesting and thread-safety of the ring buffer, the
Perfetto trace_event schema validator (both directions), exact log2
histogram bucket math, registry typing, the jit compile ledger, and the
disabled-tracer fast path (``span()`` must hand back the shared no-op).

Integration layer: a forced-preemption HyperServe run must emit the
exact per-request instant sequence (submit -> admit -> first_token ->
[preempt -> resume ->] finish) plus spill/restore spans, and
``ServeAPI.stats()`` / ``stream(final_meta=True)`` must surface the
percentiles and per-request lifecycle records built on the registry.

Profiler sink: an enabled tracer enters one profiler annotation per span
(and lands in a real CPU profiler trace), a disabled one touches none,
and each engine step is one ``serve.step`` span holding its phases.
"""
import dataclasses
import math
import threading

import jax
import pytest

from repro.configs.base import ServeConfig, get_config
from repro.models import model as M
from repro.obs import (NOOP_SPAN, SCHEMA, Observability, Tracer,
                       validate_perfetto)
from repro.serve.api import HyperServe


# ---------------------------------------------------------------- tracer

def test_disabled_tracer_is_noop():
    tr = Tracer()
    assert not tr.enabled
    # the <2% overhead guarantee: one shared object, no allocation
    assert tr.span("x") is NOOP_SPAN
    assert tr.span("y", rid=3) is NOOP_SPAN
    with tr.span("z"):
        pass
    tr.instant("i")
    tr.counter("c", 1.0)
    assert tr.events() == [] and tr.emitted == 0


def test_span_nesting_order_and_containment():
    tr = Tracer().enable()
    with tr.span("outer", rid=1):
        with tr.span("inner"):
            pass
    evs = tr.events()
    # spans are emitted at __exit__, so the inner completes first
    assert [e["name"] for e in evs] == ["inner", "outer"]
    inner, outer = evs
    assert outer["ph"] == inner["ph"] == "X"
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-6
    assert outer["args"] == {"rid": 1} and "args" not in inner


def test_named_tracks_get_stable_tids_and_metadata():
    tr = Tracer().enable()
    tr.instant("a", track="actor")
    tr.instant("b", track="learner")
    tr.instant("c", track="actor")
    evs = tr.events()
    assert evs[0]["tid"] == evs[2]["tid"] != evs[1]["tid"]
    meta = [e for e in tr.to_perfetto()["traceEvents"] if e["ph"] == "M"]
    assert {m["args"]["name"] for m in meta} == {"actor", "learner"}


def test_ring_buffer_evicts_oldest_and_counts_drops():
    tr = Tracer(capacity=4).enable()
    for i in range(7):
        tr.instant(f"e{i}")
    assert tr.emitted == 7 and tr.dropped == 3
    assert [e["name"] for e in tr.events()] == ["e3", "e4", "e5", "e6"]


def test_tracer_thread_safety():
    tr = Tracer(capacity=1 << 16).enable()
    n_threads, n_spans = 8, 200

    def worker(t):
        for i in range(n_spans):
            with tr.span("work", thread=t, i=i):
                pass
            tr.instant("tick", thread=t)

    threads = [threading.Thread(target=worker, args=(t,))
               for t in range(n_threads)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    evs = tr.events()
    assert len(evs) == tr.emitted == n_threads * n_spans * 2
    # per-thread event streams survived interleaving intact
    for t in range(n_threads):
        mine = [e for e in evs if e.get("args", {}).get("thread") == t]
        assert len(mine) == n_spans * 2
    assert validate_perfetto(tr.to_perfetto()) == []


def test_perfetto_validator_accepts_exporter_output():
    tr = Tracer().enable()
    with tr.span("s", k=1):
        pass
    tr.instant("i", track="t")
    tr.counter("c", 2.5, track="t")
    assert validate_perfetto(tr.to_perfetto()) == []


@pytest.mark.parametrize("payload, needle", [
    ({}, "traceEvents"),
    ({"traceEvents": "nope"}, "traceEvents"),
    ({"traceEvents": [{"ph": "X", "name": "a", "pid": 1, "tid": 1,
                       "ts": 0.0}]}, "dur"),          # complete without dur
    ({"traceEvents": [{"ph": "i", "name": "a", "pid": 1, "tid": 1,
                       "ts": -5.0}]}, "ts"),          # negative timestamp
    ({"traceEvents": [{"ph": "Z", "name": "a", "pid": 1, "tid": 1,
                       "ts": 0.0}]}, "ph"),           # unknown phase
    ({"traceEvents": [{"ph": "M", "name": "thread_name", "pid": 1,
                       "tid": 1}]}, "args"),          # metadata without args
])
def test_perfetto_validator_rejects_bad_payloads(payload, needle):
    problems = validate_perfetto(payload)
    assert problems and any(needle in p for p in problems)


def test_export_round_trip(tmp_path):
    import json
    tr = Tracer().enable()
    with tr.span("s"):
        pass
    path = tr.export(str(tmp_path / "t.json"))
    loaded = json.load(open(path))
    assert validate_perfetto(loaded) == []
    assert loaded["otherData"]["dropped_events"] == 0


# --------------------------------------------------------------- metrics

def test_histogram_bucket_boundaries_exact():
    obs = Observability()
    h = obs.metrics.histogram("lat", lo_exp=-4, hi_exp=4)
    # bucket k holds [2^(k-1), 2^k): the power itself opens its bucket
    i2 = h.bucket_index(2.0)
    assert h.bucket_bounds(i2) == (2.0, 4.0)
    just_under = math.nextafter(2.0, 0.0)
    assert h.bucket_bounds(h.bucket_index(just_under)) == (1.0, 2.0)
    # underflow / overflow rails
    assert h.bucket_index(0.0) == 0
    assert h.bucket_index(2.0 ** -5) == 0
    assert h.bucket_index(16.0) == len(h.buckets) - 1
    assert h.bucket_bounds(0) == (0.0, 2.0 ** -4)
    assert h.bucket_bounds(len(h.buckets) - 1) == (16.0, math.inf)
    # every interior bucket spans exactly one octave
    for idx in range(1, len(h.buckets) - 1):
        lo, hi = h.bucket_bounds(idx)
        assert hi == 2 * lo


def test_histogram_observe_and_percentiles():
    obs = Observability()
    h = obs.metrics.histogram("lat", lo_exp=-4, hi_exp=4)
    vals = [0.5, 0.5, 1.5, 3.0, 3.5, 10.0]
    for v in vals:
        h.observe(v)
    assert h.count == len(vals)
    assert h.sum == pytest.approx(sum(vals))
    assert h.min == 0.5 and h.max == 10.0
    assert h.mean == pytest.approx(sum(vals) / len(vals))
    # percentiles are clamped to the observed range
    assert h.percentile(0) >= h.min
    assert h.percentile(100) <= h.max
    assert h.percentile(50) <= h.percentile(95)
    empty = obs.metrics.histogram("empty")
    assert empty.percentile(50) == 0.0


def test_registry_get_or_create_and_kind_mismatch():
    obs = Observability()
    c = obs.metrics.counter("serve.tokens")
    c.inc(3)
    assert obs.metrics.counter("serve.tokens") is c
    with pytest.raises(AssertionError):
        obs.metrics.gauge("serve.tokens")        # kind mismatch must fail
    with pytest.raises(AssertionError):
        c.inc(-1)                                # counters are monotonic
    j = obs.metrics.to_json()
    assert j["schema"] == SCHEMA
    assert j["counters"]["serve.tokens"] == 3.0


def test_prometheus_dump_format():
    obs = Observability()
    obs.metrics.counter("serve.tokens").inc(5)
    obs.metrics.gauge("pool.occupancy").set(0.5)
    h = obs.metrics.histogram("lat.s", lo_exp=-2, hi_exp=2)
    h.observe(0.5)
    h.observe(3.0)
    text = obs.metrics.dump_prometheus()
    assert "# TYPE serve_tokens counter\nserve_tokens 5.0" in text
    assert "# TYPE pool_occupancy gauge\npool_occupancy 0.5" in text
    assert '# TYPE lat_s histogram' in text
    assert 'lat_s_bucket{le="+Inf"} 2' in text   # cumulative buckets
    assert "lat_s_sum 3.5" in text and "lat_s_count 2" in text


def test_compile_ledger_dedups_keys():
    obs = Observability()
    assert obs.record_compile("prefill", (2, 64)) is True
    assert obs.record_compile("prefill", (2, 64)) is False
    assert obs.record_compile("prefill", (4, 64)) is True
    assert obs.record_compile("decode", (4,)) is True
    assert obs.recompiles() == 3
    assert obs.compiled_keys("prefill") == [(2, 64), (4, 64)]
    assert obs.metrics.counter("jit.recompiles.decode").value == 1.0


# ---------------------------------------------- serve lifecycle timeline

@pytest.fixture(scope="module")
def qwen_f32():
    cfg = dataclasses.replace(get_config("qwen2-0.5b").reduced(),
                              dtype="float32")
    params = M.init_model(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _instants_for(events, rid):
    return [e["name"] for e in events
            if e["ph"] == "i" and e.get("args", {}).get("rid") == rid]


def test_serve_lifecycle_trace_with_preemption(qwen_f32):
    """The full request timeline, including a forced spill/restore."""
    cfg, params = qwen_f32
    scfg = ServeConfig(block_size=2, num_blocks=9, max_blocks_per_req=6,
                       max_slots=2, prefill_chunk=4,
                       enable_prefix_cache=False)
    serve = HyperServe(cfg, params, serve_cfg=scfg)
    serve.obs().trace.enable()
    rids = [serve.submit(list(range(1, 5)), 8),
            serve.submit(list(range(7, 11)), 8)]
    serve.join()
    st = serve.stats()
    assert st["preemptions"] >= 1, "pool must be tight enough to preempt"

    evs = serve.obs().trace.events()
    seqs = {rid: _instants_for(evs, rid) for rid in rids}
    # the survivor never leaves the pool; the victim round-trips the host
    assert sorted(seqs.values()) == sorted([
        ["serve.submit", "serve.admit", "serve.first_token", "serve.finish"],
        ["serve.submit", "serve.admit", "serve.first_token",
         "serve.preempt", "serve.resume", "serve.finish"],
    ])
    spans = {e["name"] for e in evs if e["ph"] == "X"}
    assert {"serve.prefill", "serve.decode",
            "serve.spill", "serve.restore"} <= spans
    assert validate_perfetto(serve.obs().trace.to_perfetto()) == []

    # the compile ledger saw exactly one (bucket, shape) key per callable
    keys = serve.obs().compiled_keys()
    assert len(keys["paged_prefill"]) == 1
    assert len(keys["paged_decode"]) == 1
    assert st["recompiles"] == serve.obs().recompiles() >= 2


def test_stats_percentiles_and_interval_rate(qwen_f32):
    cfg, params = qwen_f32
    scfg = ServeConfig(block_size=4, num_blocks=40, max_blocks_per_req=8,
                       max_slots=3, prefill_chunk=4)
    serve = HyperServe(cfg, params, serve_cfg=scfg)
    serve.submit(list(range(1, 9)), 6)
    serve.submit(list(range(20, 27)), 4)
    serve.join()
    st = serve.stats()
    assert st["finished"] == 2
    assert st["tokens_per_sec"] > 0
    assert st["tokens_per_sec_cumulative"] > 0
    assert 0 < st["ttft_p50_s"] <= st["ttft_p95_s"]
    assert 0 < st["itl_p50_s"] <= st["itl_p95_s"]
    assert st["queue_wait_p50_s"] >= 0
    # interval semantics: an idle gap reports 0, not a decayed average
    st2 = serve.stats()
    assert st2["tokens_per_sec"] == 0.0
    assert st2["tokens_per_sec_cumulative"] > 0
    # ... and new work after the gap yields a fresh (undiluted) rate
    serve.submit(list(range(5, 10)), 4)
    serve.join()
    st3 = serve.stats()
    assert st3["tokens_per_sec"] > 0


@pytest.mark.parametrize("arch,in_place", [
    ("qwen2-0.5b", 2),                  # k, v
    ("deepseek-v2-lite-16b", 4),        # ckv, krope in each of 2 segments
])
def test_pool_gauges_set_once_at_build(qwen_f32, arch, in_place):
    """``serve.pool.leaves_in_place`` / ``.leaves_sliced`` count how the
    step programs hold the pool (attention K/V and MLA latents in place,
    nothing sliced), set when the engine builds its steps, before any
    request, and shown in ``stats()`` and the Prometheus dump."""
    if arch == "qwen2-0.5b":
        cfg, params = qwen_f32
    else:
        cfg = dataclasses.replace(get_config(arch).reduced(),
                                  dtype="float32")
        params = M.init_model(cfg, jax.random.PRNGKey(0))
    scfg = ServeConfig(block_size=4, num_blocks=16, max_blocks_per_req=4,
                       max_slots=2, prefill_chunk=4)
    serve = HyperServe(cfg, params, serve_cfg=scfg)
    m = serve.obs().metrics
    assert m.gauge("serve.pool.leaves_in_place").value == in_place
    assert m.gauge("serve.pool.leaves_sliced").value == 0
    serve.submit(list(range(1, 6)), 3)
    serve.join()
    st = serve.stats()
    assert (st["pool_leaves_in_place"], st["pool_leaves_sliced"]) == \
        (in_place, 0)
    text = m.dump_prometheus()
    assert "# TYPE serve_pool_leaves_in_place gauge\n" \
        f"serve_pool_leaves_in_place {in_place}" in text
    assert "serve_pool_leaves_sliced 0" in text


@pytest.mark.parametrize("held", [0, 2])
def test_moe_route_counters(held):
    """An MoE config's step programs return each held expert's routes,
    fetched with the step's tokens: ``serve.moe.routes_held`` adds them up
    and ``serve.moe.routes`` counts every route of the real tokens (top-k
    per token per MoE layer), so holding every expert makes the two equal
    and a share takes fewer.  The expert gauges are set at build; all of
    them are in ``stats()`` and the Prometheus dump.  A config without MoE
    has none of them."""
    base = get_config("deepseek-v2-lite-16b").reduced()
    cfg = dataclasses.replace(base, dtype="float32", moe=dataclasses.replace(
        base.moe, experts_held=held))
    params = M.init_model(cfg, jax.random.PRNGKey(0))
    scfg = ServeConfig(block_size=4, num_blocks=40, max_blocks_per_req=8,
                       max_slots=2, prefill_chunk=4)
    serve = HyperServe(cfg, params, serve_cfg=scfg)
    m = serve.obs().metrics
    assert m.gauge("serve.moe.experts_held").value == (held or 4)
    assert m.gauge("serve.moe.experts_total").value == 4
    prompts = [list(range(1, 7)), list(range(3, 14))]
    for p in prompts:
        serve.submit(p, 4)
    serve.join()
    st = serve.stats()
    layers = cfg.num_layers - cfg.moe.first_k_dense
    # every prompt token and every generated token but the last is a
    # step program's real token
    routes = (sum(len(p) for p in prompts) + 2 * 3) * 2 * layers
    assert st["moe_routes"] == routes
    if held:
        assert 0 < st["moe_routes_held"] < routes
    else:
        assert st["moe_routes_held"] == routes
    assert (st["moe_experts_held"], st["moe_experts_total"]) == (held or 4, 4)
    text = m.dump_prometheus()
    for name in ("serve_moe_routes_held", "serve_moe_routes",
                 "serve_moe_experts_held", "serve_moe_experts_total"):
        assert f"\n{name} " in text


def test_no_moe_counters_without_moe(qwen_f32):
    cfg, params = qwen_f32
    serve = HyperServe(cfg, params, serve_cfg=ServeConfig(
        block_size=4, num_blocks=16, max_blocks_per_req=4, max_slots=2,
        prefill_chunk=4))
    serve.submit(list(range(1, 6)), 3)
    serve.join()
    assert not [k for k in serve.stats() if k.startswith("moe_")]
    assert "serve_moe" not in serve.obs().metrics.dump_prometheus()
    assert serve.engine.moe_step_routes == []


def test_stream_final_meta_lifecycle_record(qwen_f32):
    cfg, params = qwen_f32
    scfg = ServeConfig(block_size=4, num_blocks=40, max_blocks_per_req=8,
                       max_slots=3, prefill_chunk=4)
    serve = HyperServe(cfg, params, serve_cfg=scfg)
    rid = serve.submit(list(range(1, 9)), 5, seed=1234)
    items = list(serve.stream(rid, final_meta=True))
    meta = items[-1]
    assert items[:-1] == serve.result(rid)
    assert meta["rid"] == rid and meta["seed"] == 1234
    assert meta["n_tokens"] == len(items) - 1
    assert meta["finish_reason"] in ("eos", "length")
    assert meta["queue_wait_s"] >= 0
    assert meta["ttft_s"] >= meta["queue_wait_s"]
    assert meta["latency_s"] >= meta["ttft_s"]


# ------------------------------------------------ the profiler sink

class _FakeAnnotation:
    """Records what a profiler annotation is asked to do."""

    def __init__(self, log, name, **kw):
        self.log, self.name, self.kw = log, name, kw

    def __enter__(self):
        self.log.append(("enter", self.name, self.kw))

    def __exit__(self, *exc):
        self.log.append(("exit", self.name, self.kw))


def _fake_annotations(log):
    return (lambda name: _FakeAnnotation(log, name),
            lambda name, **kw: _FakeAnnotation(log, name, **kw))


def test_enabled_tracer_enters_a_profiler_annotation_per_span():
    log = []
    tr = Tracer(annotations=_fake_annotations(log)).enable()
    with tr.step_span("loop", 7, rid=1) as root:
        with tr.span("inner"):
            pass
        root.set(rows=2)
    assert log == [("enter", "loop", {"step_num": 7}),
                   ("enter", "inner", {}), ("exit", "inner", {}),
                   ("exit", "loop", {"step_num": 7})]
    inner, outer = tr.events()
    assert outer["args"] == {"rid": 1, "step": 7, "rows": 2}
    assert [inner["name"], outer["name"]] == ["inner", "loop"]
    # after-the-fact spans stay in the ring alone
    tr.complete("late", 0, 10)
    assert len(log) == 4 and tr.events()[-1]["name"] == "late"


def test_disabled_tracer_touches_no_annotation():
    log = []
    tr = Tracer(annotations=_fake_annotations(log))
    assert tr.span("x", rid=1) is NOOP_SPAN
    assert tr.step_span("x", 3) is NOOP_SPAN
    with tr.step_span("x", 3) as s:
        s.set(rows=1)
    assert log == [] and tr.events() == []


def test_import_repro_obs_pulls_in_no_jax():
    from conftest import run_subprocess
    out = run_subprocess(
        "import sys, repro.obs\n"
        "repro.obs.Tracer().span('x')\n"
        "print(sorted(m for m in sys.modules if m.split('.')[0] == 'jax'))",
        devices=1, timeout=120)
    assert out.strip() == "[]"


def test_enabled_spans_land_in_the_profiler_trace(tmp_path):
    """On the profiler's clock, in its host plane, under their own names."""
    from jax.profiler import ProfileData
    tr = Tracer().enable()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with tr.step_span("obs.test.step", 5):
            with tr.span("obs.test.phase"):
                jax.numpy.ones(3).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.glob("**/*.xplane.pb")
    got = {ev.name: ev for plane in ProfileData.from_file(str(path)).planes
           for line in plane.lines for ev in line.events
           if ev.name.startswith("obs.test.")}
    step, phase = got["obs.test.step"], got["obs.test.phase"]
    assert dict(step.stats)["step_num"] == 5
    assert step.start_ns <= phase.start_ns
    assert (phase.start_ns + phase.duration_ns
            <= step.start_ns + step.duration_ns)


# ------------------------------------------- the engine step's span tree

PHASES = {"serve.schedule", "serve.prefill.pack", "serve.prefill",
          "serve.prefill.first_tokens", "serve.decode.pack", "serve.decode",
          "serve.decode.dispatch", "serve.decode.wait", "serve.commit",
          "serve.gauges"}


def test_engine_step_span_tree_and_prefill_steps(qwen_f32):
    cfg, params = qwen_f32
    # one 4-token chunk per step: the 10-token prompt prefills in steps
    # 0-2, the 4-token one admitted beside it in step 3
    scfg = ServeConfig(block_size=4, num_blocks=40, max_blocks_per_req=8,
                       max_slots=2, prefill_chunk=4,
                       prefill_chunks_per_step=1, prefill_batch=1)
    serve = HyperServe(cfg, params, serve_cfg=scfg)
    tr = serve.obs().trace.enable()
    rids = [serve.submit(list(range(1, 11)), 3),
            serve.submit(list(range(20, 24)), 3)]
    n = 0
    while serve.engine.scheduler.has_work():
        serve.step_once()
        n += 1
    assert [serve.request_meta(r)["prefill_steps"] for r in rids] == [3, 4]

    spans = [e for e in tr.events() if e["ph"] == "X"]
    roots = [e for e in spans if e["name"] == "serve.step"]
    assert [r["args"]["step"] for r in roots] == list(range(n))
    assert [(r["args"]["prefill_rows"], r["args"]["decode_rows"])
            for r in roots[:5]] == [(1, 0), (1, 0), (1, 0), (1, 1), (0, 2)]
    seen = set()
    for e in spans:
        if e["name"] not in PHASES:
            continue
        seen.add(e["name"])
        root = roots[e["args"]["step"]]
        assert root["ts"] <= e["ts"]
        assert e["ts"] + e["dur"] <= root["ts"] + root["dur"] + 1e-3
    assert seen == PHASES
    # the prefill dispatch keeps its name and the args the benchmark reads
    prefills = [e for e in spans if e["name"] == "serve.prefill"]
    assert len(prefills) == 4
    assert [p["args"]["rids"] for p in prefills] == [[rids[0]]] * 3 + [
        [rids[1]]]
    assert all(p["args"]["rows"] == 1 and p["args"]["bucket"] == 1
               for p in prefills)
