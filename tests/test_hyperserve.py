"""HyperServe end-to-end: continuous batching == sequential Generator.

The load-bearing property: under staggered arrivals, chunked prefill,
paged KV, preemption and prefix sharing, greedy outputs must match the
fixed-batch ``Generator`` token-for-token (float32 configs so fp drift
cannot flip an argmax).
"""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

from repro.configs.base import ServeConfig, get_config
from repro.models import model as M
from repro.serve.api import HyperServe, RequestRejected
from repro.serve.engine import GenerateConfig, Generator
from repro.serve.scheduler import RequestState
from tests.conftest import run_subprocess


@pytest.fixture(scope="module")
def qwen_f32():
    cfg = dataclasses.replace(get_config("qwen2-0.5b").reduced(),
                              dtype="float32")
    params = M.init_model(cfg, jax.random.PRNGKey(0))
    return cfg, params


def baseline(cfg, params, prompt, max_new):
    gen = Generator(cfg, params, max_len=128)
    out = gen.generate(jnp.asarray(prompt, jnp.int32)[None, :],
                       GenerateConfig(max_new_tokens=max_new))
    return out[0, len(prompt):].tolist()


def test_staggered_arrivals_match_generator(qwen_f32):
    cfg, params = qwen_f32
    prompts = [list(range(1, 9)), list(range(20, 33)),
               list(range(5, 10)), list(range(40, 47))]
    max_new = [6, 4, 8, 5]
    want = [baseline(cfg, params, p, mn) for p, mn in zip(prompts, max_new)]

    scfg = ServeConfig(block_size=4, num_blocks=40, max_blocks_per_req=8,
                       max_slots=3, prefill_chunk=4)
    serve = HyperServe(cfg, params, serve_cfg=scfg)
    rids = [serve.submit(prompts[0], max_new[0]),
            serve.submit(prompts[1], max_new[1])]
    for _ in range(3):                       # stagger: arrive mid-flight
        serve.step_once()
    rids += [serve.submit(prompts[2], max_new[2]),
             serve.submit(prompts[3], max_new[3])]
    out = serve.join()
    for i, rid in enumerate(rids):
        assert out[rid] == want[i], f"request {i} diverged"
    st = serve.stats()
    assert st["finished"] == 4 and st["running"] == 0
    assert st["block_occupancy"] < 1.0


def test_preemption_spill_restore_exact(qwen_f32):
    """Pool pressure forces a spill to host + restore; outputs still exact."""
    cfg, params = qwen_f32
    prompts = [list(range(1, 5)), list(range(7, 11))]
    want = [baseline(cfg, params, p, 8) for p in prompts]
    scfg = ServeConfig(block_size=2, num_blocks=9, max_blocks_per_req=6,
                       max_slots=2, prefill_chunk=4,
                       enable_prefix_cache=False)
    serve = HyperServe(cfg, params, serve_cfg=scfg)
    rids = [serve.submit(p, 8) for p in prompts]
    out = serve.join()
    st = serve.stats()
    assert st["preemptions"] >= 1, "test must actually exercise preemption"
    for i, rid in enumerate(rids):
        assert out[rid] == want[i]


def test_prefix_cache_cow_exact(qwen_f32):
    """An identical prompt forks cached CoW blocks and still matches."""
    cfg, params = qwen_f32
    prompt = list(range(1, 9))
    want = baseline(cfg, params, prompt, 6)
    scfg = ServeConfig(block_size=4, num_blocks=40, max_blocks_per_req=8,
                       max_slots=2, prefill_chunk=4,
                       enable_prefix_cache=True, prefix_cache_blocks=8)
    serve = HyperServe(cfg, params, serve_cfg=scfg)
    first = serve.submit(prompt, 6)
    serve.join()
    second = serve.submit(prompt, 6)
    out = serve.join()
    assert serve.stats()["prefix_hits"] == 1
    assert out[second] == want == serve.result(first)


def test_cancel_and_rejection(qwen_f32):
    cfg, params = qwen_f32
    scfg = ServeConfig(block_size=4, num_blocks=16, max_blocks_per_req=4,
                       max_slots=2, max_queue=2, prefill_chunk=4)
    serve = HyperServe(cfg, params, serve_cfg=scfg)
    with pytest.raises(RequestRejected):     # can never fit the table width
        serve.submit(list(range(1, 40)), 8)
    rid = serve.submit([1, 2, 3, 4], 10)     # would run long
    serve.step_once()
    assert serve.cancel(rid)
    assert serve.state(rid) == "cancelled"
    assert serve.engine.blocks.num_free == serve.engine.blocks.num_total
    # engine drains cleanly after a cancel
    rid2 = serve.submit([1, 2, 3, 4], 3)
    out = serve.join()
    assert len(out[rid2]) == 3


def test_streaming_api(qwen_f32):
    cfg, params = qwen_f32
    want = baseline(cfg, params, [1, 2, 3, 4, 5], 5)
    serve = HyperServe(cfg, params, serve_cfg=ServeConfig(
        block_size=4, num_blocks=16, max_blocks_per_req=4, max_slots=2,
        prefill_chunk=4))
    rid = serve.submit([1, 2, 3, 4, 5], 5)
    assert list(serve.stream(rid)) == want


def test_seeded_sampling_reproducible_and_recorded(qwen_f32):
    """temperature>0 with a pinned seed replays the identical stream run
    to run; the resolved seed is recorded on the Request even when the
    caller pins none, so ANY rollout can be replayed after the fact."""
    cfg, params = qwen_f32
    scfg = ServeConfig(block_size=4, num_blocks=40, max_blocks_per_req=8,
                       max_slots=2, prefill_chunk=4)

    def run(seed):
        serve = HyperServe(cfg, params, serve_cfg=scfg)
        rid = serve.submit([1, 2, 3, 4, 5], 6, temperature=1.0, seed=seed)
        serve.join()
        req = serve.engine.scheduler.requests[rid]
        return req.generated, req.seed

    toks_a, seed_a = run(123)
    toks_b, seed_b = run(123)
    assert toks_a == toks_b and seed_a == seed_b == 123
    toks_c, _ = run(124)
    assert toks_c != toks_a, "different seeds should explore"
    # unpinned: the engine records the seed it resolved -> replayable
    toks_d, recorded = run(None)
    assert recorded is not None
    assert run(recorded)[0] == toks_d
    # out-of-range pinned seeds are masked, never crash the batched
    # sampler's uint32 packing, and the RECORDED (masked) seed replays
    toks_e, rec_e = run(-1)
    assert 0 <= rec_e <= 0x7FFFFFFF
    assert run(rec_e)[0] == toks_e


def test_serve_on_forced_8device_mesh():
    """Sharded continuous batching (8-dev mesh) matches the 1-device run."""
    run_subprocess("""
import dataclasses
import jax, jax.numpy as jnp
from repro.configs.base import get_config, ServeConfig
from repro.core.hypershard import ShardingPlan
from repro.launch.mesh import make_host_mesh
from repro.models import model as M
from repro.serve.api import HyperServe
from repro.serve.engine import GenerateConfig, Generator

cfg = dataclasses.replace(get_config("qwen2-0.5b").reduced(), dtype="float32")
params = M.init_model(cfg, jax.random.PRNGKey(0))
gen = Generator(cfg, params, max_len=64)
prompts = [list(range(1, 9)), list(range(20, 33))]
want = [gen.generate(jnp.asarray(p, jnp.int32)[None, :],
                     GenerateConfig(max_new_tokens=5))[0, len(p):].tolist()
        for p in prompts]

mesh = make_host_mesh((1, 8))
scfg = ServeConfig(block_size=4, num_blocks=48, max_blocks_per_req=8,
                   max_slots=2, prefill_chunk=4)
serve = HyperServe(cfg, params, serve_cfg=scfg, mesh=mesh,
                   plan=ShardingPlan(fsdp=None))
rids = [serve.submit(p, 5) for p in prompts]
out = serve.join()
for i, rid in enumerate(rids):
    assert out[rid] == want[i], (i, out[rid], want[i])
print("MESH8-SERVE-OK")
""", devices=8, timeout=1200)


def test_disaggregated_prefill_decode_roles():
    """Prefill/decode role split (HyperMPMD): prefill workers compute the
    prompt, pages transfer to the decode workers' pool, outputs exact —
    for attention K/V pages AND MLA latent pages (the two pure-paged
    layouts the disagg rule admits)."""
    run_subprocess("""
import dataclasses
import jax, jax.numpy as jnp
from repro.configs.base import get_config, ServeConfig
from repro.core.mpmd import serving_groups
from repro.models import model as M
from repro.serve.api import HyperServe
from repro.serve.engine import GenerateConfig, Generator

groups = serving_groups(4, 4)
for arch in ("qwen2-0.5b", "deepseek-v2-lite-16b"):
    cfg = dataclasses.replace(get_config(arch).reduced(), dtype="float32")
    params = M.init_model(cfg, jax.random.PRNGKey(0))
    gen = Generator(cfg, params, max_len=64)
    prompts = [list(range(1, 9)), list(range(5, 10))]
    want = [gen.generate(jnp.asarray(p, jnp.int32)[None, :],
                         GenerateConfig(max_new_tokens=5))[0, len(p):].tolist()
            for p in prompts]

    scfg = ServeConfig(block_size=4, num_blocks=48, max_blocks_per_req=8,
                       max_slots=2, prefill_chunk=8)
    serve = HyperServe(cfg, params, serve_cfg=scfg,
                       prefill_group=groups["prefill"],
                       decode_group=groups["decode"])
    rids = [serve.submit(p, 5) for p in prompts]
    out = serve.join()
    for i, rid in enumerate(rids):
        assert out[rid] == want[i], (arch, i, out[rid], want[i])
print("DISAGG-SERVE-OK")
""", devices=8, timeout=1200)


# ---------------------------------------------------------------------------
# Mixer decode-state registry: every model family serves under paged
# HyperServe, token-identical to the sequential Generator (float32 so fp
# drift cannot flip an argmax).  One test per family carries the smoke
# marker so `make check` covers SSD / RG-LRU+LOCAL_ATTN / MLA serving.
# ---------------------------------------------------------------------------
def _family_cfg(arch, **kw):
    cfg = get_config(arch).reduced()
    return dataclasses.replace(cfg, dtype="float32", **kw)


def _assert_parity(cfg, scfg, prompts, max_new, **serve_kw):
    params = M.init_model(cfg, jax.random.PRNGKey(0))
    gen = Generator(cfg, params, max_len=128)
    want = [gen.generate(jnp.asarray(p, jnp.int32)[None, :],
                         GenerateConfig(max_new_tokens=mn))[0, len(p):].tolist()
            for p, mn in zip(prompts, max_new)]
    serve = HyperServe(cfg, params, serve_cfg=scfg, **serve_kw)
    rids = [serve.submit(p, mn) for p, mn in zip(prompts, max_new)]
    out = serve.join()
    for i, rid in enumerate(rids):
        assert out[rid] == want[i], f"{cfg.name} request {i} diverged"
    return serve


@pytest.mark.smoke
def test_ssd_paged_serve_matches_generator():
    """Mamba-2: O(1) recurrent state seated in per-slot rows; chunked
    prefill carries the SSD state and conv tail across chunks."""
    cfg = _family_cfg("mamba2-370m")
    scfg = ServeConfig(block_size=4, num_blocks=40, max_blocks_per_req=8,
                       max_slots=3, prefill_chunk=4)
    serve = _assert_parity(cfg, scfg,
                           [list(range(1, 9)), list(range(20, 33)),
                            list(range(5, 10))], [6, 4, 8])
    assert serve.stats()["finished"] == 3


@pytest.mark.smoke
def test_rglru_local_attn_windowed_serve_matches_generator():
    """RecurrentGemma 1:2 pattern: RG-LRU slot state + LOCAL_ATTN paged
    with out-of-window block freeing.  Generation runs past the window so
    freeing is actually exercised, and live paged blocks per decoding
    request stay within ceil(window/block)+1."""
    cfg = _family_cfg("recurrentgemma-2b", num_layers=3, sliding_window=16)
    bs = 4
    bound = -(-cfg.sliding_window // bs) + 1
    params = M.init_model(cfg, jax.random.PRNGKey(0))
    gen = Generator(cfg, params, max_len=128)
    prompts = [list(range(1, 9)), list(range(20, 33))]
    max_new = [20, 16]                       # 8+20 > window: blocks get freed
    want = [gen.generate(jnp.asarray(p, jnp.int32)[None, :],
                         GenerateConfig(max_new_tokens=mn))[0, len(p):].tolist()
            for p, mn in zip(prompts, max_new)]
    scfg = ServeConfig(block_size=bs, num_blocks=40, max_blocks_per_req=12,
                       max_slots=2, prefill_chunk=4)
    serve = HyperServe(cfg, params, serve_cfg=scfg)
    assert serve.engine.layout.free_window == cfg.sliding_window
    rids = [serve.submit(p, mn) for p, mn in zip(prompts, max_new)]
    freed_seen = False
    while serve.engine.scheduler.has_work():
        serve.step_once()
        for r in serve.engine.scheduler.requests.values():
            if r.state is RequestState.RUNNING:
                assert r.live_blocks <= bound, (r.total_len, r.table)
                freed_seen = freed_seen or r.null_prefix > 0 or (
                    r.table and r.table[0] == 0)
    assert freed_seen, "windowed freeing never fired; weak test"
    out = {rid: serve.result(rid) for rid in rids}
    for i, rid in enumerate(rids):
        assert out[rid] == want[i], f"windowed request {i} diverged"
    # everything returns to the free list once drained
    assert serve.engine.blocks.num_free == serve.engine.blocks.num_total


@pytest.mark.smoke
def test_mla_paged_serve_matches_generator():
    """DeepSeek-V2-Lite: compressed latents page like KV; the MoE FFN uses
    the dropless ragged dispatch so batched decode is per-token exact."""
    cfg = _family_cfg("deepseek-v2-lite-16b")
    scfg = ServeConfig(block_size=4, num_blocks=40, max_blocks_per_req=8,
                       max_slots=3, prefill_chunk=4)
    _assert_parity(cfg, scfg,
                   [list(range(1, 9)), list(range(20, 33)),
                    list(range(5, 10))], [6, 4, 8])


def _ssd_attn_cfg():
    """SSD and full attention alternating in one stacked segment."""
    rg = get_config("recurrentgemma-2b").reduced()
    return dataclasses.replace(
        rg, dtype="float32", num_layers=4,
        ssm=get_config("mamba2-370m").reduced().ssm,
        rglru=dataclasses.replace(rg.rglru, block_pattern=("ssd", "attn")))


@pytest.mark.parametrize("kernels", ["composed", "fused"])
@pytest.mark.parametrize("arch", ["ssd-attn"])
def test_sliced_leaves_keep_outputs(arch, kernels):
    """Leaves the step programs slice per layer and write back (SSD
    state beside in-place attention pages in one carried segment) serve
    token-identically to the sequential Generator, and the pool gauges
    count them."""
    cfg = _ssd_attn_cfg()
    scfg = ServeConfig(block_size=4, num_blocks=40, max_blocks_per_req=8,
                       max_slots=3, prefill_chunk=4, kernels=kernels)
    serve = _assert_parity(cfg, scfg,
                           [list(range(1, 9)), list(range(20, 33)),
                            list(range(5, 10))], [6, 4, 8])
    st = serve.stats()
    assert st["pool_leaves_sliced"] > 0
    assert st["pool_leaves_in_place"] == 2


@pytest.mark.parametrize("kernels", ["composed", "fused"])
@pytest.mark.parametrize("arch", ["deepseek-v2-lite-16b"])
def test_in_place_latents_keep_outputs(arch, kernels):
    """MLA latents updated in place through the layer-offset view serve
    token-identically to the sequential Generator.  Three layers, so the
    MoE segment stacks two and its second layer reads and writes at a
    page offset; the gauges count every latent leaf in place."""
    cfg = _family_cfg(arch, num_layers=3)
    scfg = ServeConfig(block_size=4, num_blocks=40, max_blocks_per_req=8,
                       max_slots=3, prefill_chunk=4, kernels=kernels)
    serve = _assert_parity(cfg, scfg,
                           [list(range(1, 9)), list(range(20, 33)),
                            list(range(5, 10))], [6, 4, 8])
    mla_subs = sum(sp.kind == "mla" for seg in serve.engine.layout.segments
                   for sp in seg.specs)
    assert mla_subs == 2                  # the dense layer, the MoE stack
    st = serve.stats()
    assert st["pool_leaves_sliced"] == 0
    assert st["pool_leaves_in_place"] == 2 * mla_subs    # ckv, krope


@pytest.mark.parametrize("kernels", ["composed", "fused"])
def test_window_freeing_across_stacked_local_layers(kernels):
    """Two LOCAL_ATTN layers stacked in one segment share the block
    tables: freed entries repoint at block 0, which the second layer's
    page offset maps to its own null block.  Generation runs past the
    window; outputs stay token-exact."""
    cfg = _family_cfg("recurrentgemma-2b", num_layers=6, sliding_window=16)
    scfg = ServeConfig(block_size=4, num_blocks=40, max_blocks_per_req=12,
                       max_slots=2, prefill_chunk=4, kernels=kernels)
    params = M.init_model(cfg, jax.random.PRNGKey(0))
    gen = Generator(cfg, params, max_len=128)
    prompts, max_new = [list(range(1, 9)), list(range(20, 33))], [20, 16]
    want = [gen.generate(jnp.asarray(p, jnp.int32)[None, :],
                         GenerateConfig(max_new_tokens=mn))[0, len(p):].tolist()
            for p, mn in zip(prompts, max_new)]
    serve = HyperServe(cfg, params, serve_cfg=scfg)
    [seg] = serve.engine.layout.segments          # (rglru, rglru, local) x 2
    assert seg.repeat == 2 and seg.kinds[-1][0] == "local"
    rids = [serve.submit(p, mn) for p, mn in zip(prompts, max_new)]
    freed_seen = False
    while serve.engine.scheduler.has_work():
        serve.step_once()
        freed_seen = freed_seen or any(
            r.null_prefix > 0 or (r.table and r.table[0] == 0)
            for r in serve.engine.scheduler.requests.values()
            if r.state is RequestState.RUNNING)
    assert freed_seen, "windowed freeing never fired; weak test"
    for i, rid in enumerate(rids):
        assert serve.result(rid) == want[i], f"windowed request {i} diverged"


def test_slot_state_preemption_spill_restore_exact():
    """Pool pressure (from the hybrid model's paged LOCAL_ATTN layer)
    preempts a slot-state request: its dense recurrent state is archived
    alongside its pages and re-seated on resume — outputs still
    token-exact."""
    cfg = _family_cfg("recurrentgemma-2b", num_layers=3, sliding_window=16)
    prompts = [list(range(1, 5)), list(range(7, 11))]
    scfg = ServeConfig(block_size=2, num_blocks=11, max_blocks_per_req=10,
                       max_slots=2, prefill_chunk=4,
                       enable_prefix_cache=False)
    serve = _assert_parity(cfg, scfg, prompts, [8, 8])
    st = serve.stats()
    assert st["preemptions"] >= 1, "test must actually exercise preemption"


def test_pure_slot_models_ignore_block_pressure():
    """SSD-only models keep O(1) state and no pages: a prompt far beyond
    the block-table budget is admitted, never preempted, and exact —
    phantom paged-block accounting must not bound recurrent models."""
    cfg = _family_cfg("mamba2-370m")
    prompts = [list(range(1, 41)), list(range(50, 60))]   # 40 >> 4*2 tokens
    scfg = ServeConfig(block_size=4, num_blocks=4, max_blocks_per_req=2,
                       max_slots=2, prefill_chunk=8,
                       enable_prefix_cache=False)
    serve = _assert_parity(cfg, scfg, prompts, [8, 6])
    st = serve.stats()
    assert st["preemptions"] == 0 and st["block_occupancy"] == 0.0


def test_mixer_families_on_forced_8device_mesh():
    """SSD and RG-LRU+LOCAL_ATTN serving under a sharded 8-device mesh
    match the single-device Generator."""
    run_subprocess("""
import dataclasses
import jax, jax.numpy as jnp
from repro.configs.base import get_config, ServeConfig
from repro.core.hypershard import ShardingPlan
from repro.launch.mesh import make_host_mesh
from repro.models import model as M
from repro.serve.api import HyperServe
from repro.serve.engine import GenerateConfig, Generator

mesh = make_host_mesh((1, 8))
for arch, kw in (("mamba2-370m", {}),
                 ("recurrentgemma-2b",
                  {"num_layers": 3, "sliding_window": 16})):
    cfg = dataclasses.replace(get_config(arch).reduced(), dtype="float32",
                              **kw)
    params = M.init_model(cfg, jax.random.PRNGKey(0))
    gen = Generator(cfg, params, max_len=64)
    prompts = [list(range(1, 9)), list(range(5, 10))]
    want = [gen.generate(jnp.asarray(p, jnp.int32)[None, :],
                         GenerateConfig(max_new_tokens=6))[0, len(p):].tolist()
            for p in prompts]
    scfg = ServeConfig(block_size=4, num_blocks=48, max_blocks_per_req=8,
                       max_slots=2, prefill_chunk=4)
    serve = HyperServe(cfg, params, serve_cfg=scfg, mesh=mesh,
                       plan=ShardingPlan(fsdp=None))
    rids = [serve.submit(p, 6) for p in prompts]
    out = serve.join()
    for i, rid in enumerate(rids):
        assert out[rid] == want[i], (arch, i, out[rid], want[i])
print("MESH8-MIXER-SERVE-OK")
""", devices=8, timeout=1200)


# ---------------------------------------------------------------------------
# Batched multi-request chunked prefill: every chunk the scheduler admits
# in one iteration runs as ONE jit call (prefill_chunks_per_step > 1 is
# the default).  The engine counts chunks serviced vs calls made, so the
# tests assert batching actually HAPPENED, not just that outputs match.
# ---------------------------------------------------------------------------
_BATCHED_FAMILIES = [
    ("qwen2-0.5b", {}),                                        # ATTN
    ("deepseek-v2-lite-16b", {}),                              # MLA (+MoE)
    ("mamba2-370m", {}),                                       # SSD slot state
    ("recurrentgemma-2b",
     {"num_layers": 3, "sliding_window": 16}),                 # RG-LRU+LOCAL
]


@pytest.mark.parametrize("arch,kw", _BATCHED_FAMILIES,
                         ids=[a for a, _ in _BATCHED_FAMILIES])
def test_batched_prefill_parity_ragged(arch, kw):
    """Ragged prompt lengths submitted together: chunks from several
    requests share one prefill call per step, partial-fill rows padded to
    the null slot, and greedy outputs stay token-identical to the
    sequential Generator for every mixer family."""
    cfg = _family_cfg(arch, **kw)
    prompts = [list(range(1, 14)), list(range(20, 23)),
               list(range(30, 39)), list(range(50, 56))]       # 13/3/9/6
    max_new = [5, 7, 4, 6]
    scfg = ServeConfig(block_size=4, num_blocks=48, max_blocks_per_req=8,
                       max_slots=4, prefill_chunk=4,
                       prefill_chunks_per_step=4, prefill_batch=4,
                       enable_prefix_cache=False)
    serve = _assert_parity(cfg, scfg, prompts, max_new)
    eng = serve.engine
    assert eng.prefill_chunks > eng.prefill_calls, (
        "prefill chunks never shared a jit call; batching did not engage "
        f"({eng.prefill_chunks} chunks / {eng.prefill_calls} calls)")


@pytest.mark.smoke
def test_batched_prefill_smoke():
    """Fast `make check` cover: one paged + one slot-state family through
    the batched prefill step (ragged lengths, multi-chunk prompts)."""
    for arch in ("qwen2-0.5b", "mamba2-370m"):
        cfg = _family_cfg(arch)
        scfg = ServeConfig(block_size=4, num_blocks=48, max_blocks_per_req=8,
                           max_slots=3, prefill_chunk=4,
                           prefill_chunks_per_step=3, prefill_batch=3,
                           enable_prefix_cache=False)
        serve = _assert_parity(cfg, scfg,
                               [list(range(1, 11)), list(range(20, 24)),
                                list(range(40, 47))], [4, 5, 4])
        assert serve.engine.prefill_chunks > serve.engine.prefill_calls


def test_batched_prefill_preemption_mid_batch():
    """Pool pressure preempts a runner while OTHER requests are still
    mid-prefill in the same chunk batches; spill/restore keeps outputs
    exact and the batched step keeps servicing the surviving rows."""
    cfg = _family_cfg("qwen2-0.5b")
    prompts = [list(range(1, 10)), list(range(7, 15)), list(range(21, 27))]
    scfg = ServeConfig(block_size=2, num_blocks=13, max_blocks_per_req=10,
                       max_slots=3, prefill_chunk=4,
                       prefill_chunks_per_step=3, prefill_batch=3,
                       enable_prefix_cache=False)
    serve = _assert_parity(cfg, scfg, prompts, [8, 8, 8])
    st = serve.stats()
    assert st["preemptions"] >= 1, "test must actually exercise preemption"
    assert st["prefill_chunks"] > st["prefill_calls"], \
        "prefill batching never engaged under pool pressure"


def test_batched_prefill_on_forced_8device_mesh():
    """The batched prefill step under a sharded 8-device mesh: chunks from
    several ragged requests per call, outputs identical to the 1-device
    Generator."""
    run_subprocess("""
import dataclasses
import jax, jax.numpy as jnp
from repro.configs.base import get_config, ServeConfig
from repro.core.hypershard import ShardingPlan
from repro.launch.mesh import make_host_mesh
from repro.models import model as M
from repro.serve.api import HyperServe
from repro.serve.engine import GenerateConfig, Generator

mesh = make_host_mesh((1, 8))
cfg = dataclasses.replace(get_config("qwen2-0.5b").reduced(), dtype="float32")
params = M.init_model(cfg, jax.random.PRNGKey(0))
gen = Generator(cfg, params, max_len=64)
prompts = [list(range(1, 14)), list(range(20, 23)), list(range(30, 39))]
want = [gen.generate(jnp.asarray(p, jnp.int32)[None, :],
                     GenerateConfig(max_new_tokens=5))[0, len(p):].tolist()
        for p in prompts]
scfg = ServeConfig(block_size=4, num_blocks=48, max_blocks_per_req=8,
                   max_slots=3, prefill_chunk=4, prefill_chunks_per_step=3,
                   prefill_batch=3, enable_prefix_cache=False)
serve = HyperServe(cfg, params, serve_cfg=scfg, mesh=mesh,
                   plan=ShardingPlan(fsdp=None))
rids = [serve.submit(p, 5) for p in prompts]
out = serve.join()
for i, rid in enumerate(rids):
    assert out[rid] == want[i], (i, out[rid], want[i])
assert serve.engine.prefill_chunks > serve.engine.prefill_calls
print("MESH8-BATCHED-PREFILL-OK")
""", devices=8, timeout=1200)


# ---------------------------------------------------------------------------
# data>1 serving guard (ROADMAP open item): paged serving on a mesh with a
# nontrivial data axis miscompiles on CPU (spurious GSPMD data-axis
# all-reduce around rope doubles K) — it must be a typed error, never a
# silent divergence.
# ---------------------------------------------------------------------------
@pytest.mark.smoke
def test_explain_rejects_data_parallel_serving():
    from repro.api import plans
    from repro.api.errors import ServePlanError
    from repro.api.explain import explain
    from repro.core.layout import Layout

    cfg = _family_cfg("qwen2-0.5b")
    with pytest.raises(ServePlanError, match="data"):
        explain(plans.serve(), cfg, Layout((2, 2), ("data", "model")),
                serving=True)
    # a model-only layout of the same device count explains fine
    report = explain(plans.serve(), cfg, Layout((1, 4), ("data", "model")),
                     serving=True)
    assert report.serve_state


@pytest.mark.smoke
def test_serve_config_knobs_validated():
    """Zero/negative serving knobs are typed errors before anything jits,
    via HyperPlan.validate AND the bare-ServeConfig engine path."""
    from repro.api.errors import ServePlanError
    from repro.api.plan import HyperPlan

    with pytest.raises(ServePlanError, match="prefill_batch"):
        HyperPlan(fsdp=None, serve=ServeConfig(prefill_batch=0)).validate()
    cfg = _family_cfg("qwen2-0.5b")
    params = M.init_model(cfg, jax.random.PRNGKey(0))
    with pytest.raises(ServePlanError, match="prefill_chunk"):
        HyperServe(cfg, params, serve_cfg=ServeConfig(prefill_chunk=0))


def test_serve_rejects_data_parallel_mesh_flat_view_serves():
    """session.serve on a (2, 4) mesh raises the typed guard; the flat
    model-only view over the SAME devices (serving_mesh_for) serves and
    matches the 1-device Generator."""
    run_subprocess("""
import dataclasses
import jax, jax.numpy as jnp
from repro.api import Supernode, plans
from repro.api.errors import ServePlanError
from repro.configs.base import get_config, ServeConfig
from repro.models import model as M
from repro.rl.session import serving_mesh_for
from repro.serve.api import HyperServe
from repro.serve.engine import GenerateConfig, Generator

cfg = dataclasses.replace(get_config("qwen2-0.5b").reduced(), dtype="float32")
params = M.init_model(cfg, jax.random.PRNGKey(0))
session = Supernode((2, 4))
try:
    session.serve(cfg, params, plan=plans.serve())
    raise AssertionError("data>1 serving was not rejected")
except ServePlanError as e:
    assert "data" in str(e), e
try:
    session.explain(plans.serve(), cfg, for_serving=True)
    raise AssertionError("explain(for_serving) did not preflight data>1")
except ServePlanError:
    pass

# the flat model-only view of the SAME devices serves exactly
gen = Generator(cfg, params, max_len=64)
prompt = list(range(1, 10))
want = gen.generate(jnp.asarray(prompt, jnp.int32)[None, :],
                    GenerateConfig(max_new_tokens=5))[0, len(prompt):].tolist()
flat = serving_mesh_for(session.mesh)
assert dict(zip(flat.axis_names, flat.devices.shape)).get("model") == 8
serve = HyperServe(cfg, params, mesh=flat, serve_cfg=ServeConfig(
    block_size=4, num_blocks=48, max_blocks_per_req=8, max_slots=2,
    prefill_chunk=4))
rid = serve.submit(prompt, 5)
out = serve.join()
assert out[rid] == want, (out[rid], want)
print("DATA-GUARD-OK")
""", devices=8, timeout=1200)


def test_disagg_rejects_slot_state_models():
    """Disaggregation needs pure paged state; the error names the mixer
    and its state rule.  (Stub groups: the guard fires before any group
    is used, so no multi-device mesh is needed.)"""
    from repro.api.errors import ServePlanError

    cfg = _family_cfg("mamba2-370m")
    params = M.init_model(cfg, jax.random.PRNGKey(0))

    class _G:
        mesh = None

        def __init__(self, name):
            self.name = name

    with pytest.raises(ServePlanError, match="ssd.*slot"):
        HyperServe(cfg, params, prefill_group=_G("prefill"),
                   decode_group=_G("decode"))


def test_explain_preflights_the_disagg_rule():
    """session.explain(for_serving=True) applies the same disagg rule the
    runtime enforces: a disagg plan over a slot-state model is a typed
    ServePlanError at preflight, not a surprise at engine construction."""
    from repro.api import Supernode, plans
    from repro.api.errors import ServePlanError

    cfg = _family_cfg("mamba2-370m")
    session = Supernode()
    with pytest.raises(ServePlanError, match="ssd.*slot"):
        session.explain(plans.serve_disagg(), cfg, for_serving=True)
    # aggregated serving of the same model explains fine
    report = session.explain(plans.serve(), cfg, for_serving=True)
    assert report.serve_state
