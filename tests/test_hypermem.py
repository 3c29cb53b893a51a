"""HyperMem: hierarchical tiers, graph residency planner, predictive restore.

Covers the ISSUE-9 acceptance surface:
  - TierStack unit behaviour (deterministic LRU, disk round-trip value
    equality, typed MemCapacityError, pinned vs droppable entries);
  - the bounded HostArchive (budgeted host tier spilling LRU to disk);
  - plan_residency (graph-walk ordering, budget cascade, explain rows);
  - spill -> host -> disk -> predictive-restore round trips, token-exact
    vs the sequential Generator, for a paged (ATTN), windowed+slot
    (LOCAL_ATTN / RG-LRU hybrid) and pure-slot (SSD) family;
  - a forced tiny-HBM run: pool budget below the peak working set, yet
    serving completes exactly.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ServeConfig, get_config
from repro.mem import (DISK, HBM, HOST, MemCapacityError, Prefetcher,
                       TierStack, plan_residency, tree_nbytes)
from repro.models import model as M
from repro.serve.api import HyperServe
from repro.serve.engine import GenerateConfig, Generator
from repro.serve.paged_kv import blocks_for
from repro.serve.scheduler import RequestState, StepPlan


def _family_cfg(arch, **kw):
    cfg = get_config(arch).reduced()
    return dataclasses.replace(cfg, dtype="float32", **kw)


def _arr(n, fill=1.0):
    return np.full((n,), fill, np.float32)     # 4*n bytes


# ---------------------------------------------------------------------------
# TierStack
# ---------------------------------------------------------------------------
def test_tierstack_lru_spill_is_deterministic():
    ts = TierStack(host_bytes=100, disk_bytes=None)
    ts.put("a", _arr(20))                      # 80 B
    ts.put("b", _arr(5))                       # 20 B -> fits (100 total)
    ts.put("c", _arr(5))                       # 20 B -> evicts LRU "a"
    assert ts.tier_of("a") == DISK
    assert ts.tier_of("b") == HOST and ts.tier_of("c") == HOST
    assert ts.counters["evict_host"] == 1
    # touching "b" then inserting: "c" is now LRU and must go, not "b"
    ts.get("b")
    ts.put("d", _arr(20))
    assert ts.tier_of("c") == DISK and ts.tier_of("b") == HOST
    assert ts.counters["evict_host"] == 2
    assert ts.nbytes(HOST) <= 100
    ts.close()


def test_tierstack_disk_round_trip_exact():
    ts = TierStack(host_bytes=8, disk_bytes=None)
    tree = {"k": np.arange(12, dtype=np.float32).reshape(3, 4),
            "v": (np.ones((2, 2), np.int32),)}
    ts.put("x", tree)
    assert ts.tier_of("x") == DISK             # 8 B budget forces disk
    got, tier = ts.get("x", pop=True)
    assert tier == DISK
    np.testing.assert_array_equal(got["k"], tree["k"])
    np.testing.assert_array_equal(got["v"][0], tree["v"][0])
    assert "x" not in ts and ts.nbytes() == 0
    ts.close()


def test_tierstack_capacity_error_and_unpinned_drop():
    # pinned entries on a full disk: typed error, archive intact
    ts = TierStack(host_bytes=10, disk_bytes=100)
    ts.put("a", _arr(20), pinned=True)         # 80 B -> disk
    with pytest.raises(MemCapacityError, match="disk tier exhausted"):
        ts.put("b", _arr(20), pinned=True)
    # unpinned entries are droppable: same pressure, LRU drop + counter
    ts2 = TierStack(host_bytes=10, disk_bytes=100)
    ts2.put("a", _arr(20), pinned=False)
    ts2.put("b", _arr(20), pinned=True)        # drops unpinned "a"
    assert ts2.counters["evict_disk"] == 1
    assert "a" not in ts2 and ts2.tier_of("b") == DISK
    ts2.close()
    ts.close()


def test_tierstack_unbounded_budgets_never_evict():
    ts = TierStack(0, 0)                       # 0 == unbounded (seed parity)
    for i in range(16):
        ts.put(i, _arr(64))
    assert ts.entries(HOST) == 16 and ts.entries(DISK) == 0
    assert ts.counters["evict_host"] == 0
    assert ts.nbytes() == 16 * 64 * 4 == tree_nbytes([_arr(64)] * 16)
    ts.close()


# ---------------------------------------------------------------------------
# Bounded HostArchive (satellite: no more silent-OOM dict)
# ---------------------------------------------------------------------------
def test_host_archive_budget_spills_to_disk_and_fetches_back():
    from repro.core.kvcache import HostArchive

    ar = HostArchive(host_budget_bytes=100, disk_budget_bytes=0)
    ar.put(("req", 0), {"pages": np.ones((2, 3, 4), np.float32)})   # 96 B
    ar.put(("req", 1), {"pages": np.full((2, 3, 4), 2.0, np.float32)})
    assert ar.tier_of(("req", 0)) == DISK      # LRU spilled
    assert ar.tier_of(("req", 1)) == HOST
    assert ar.nbytes_host() == 96 and ar.nbytes_disk() == 96
    assert ar.nbytes() == 192                  # total stays back-compat
    got = ar.fetch(("req", 0), pop=True)
    np.testing.assert_array_equal(np.asarray(got["pages"]),
                                  np.ones((2, 3, 4), np.float32))
    assert ar.counters["evict_host"] == 1


def test_host_archive_places_on_host_memory_without_a_mesh():
    """One device and no mesh: archived arrays sit in the backend's host
    memory kind, and come back in device memory."""
    import jax
    import jax.numpy as jnp

    from repro.core.compat import device_memory_kind, host_memory_kind
    from repro.core.kvcache import HostArchive

    ar = HostArchive()
    ar.put(("req", 0), {"pages": jnp.ones((2, 3, 4), jnp.float32)})
    assert ar.host_memory_kinds() == {host_memory_kind()}
    got = ar.fetch(("req", 0), pop=True)["pages"]
    assert got.sharding.memory_kind == device_memory_kind()
    assert got.sharding.device_set == {jax.devices()[0]}
    np.testing.assert_array_equal(np.asarray(got), 1.0)
    assert ar.host_memory_kinds() == set()


def test_host_archive_capacity_error_is_typed():
    from repro.core.kvcache import HostArchive

    ar = HostArchive(host_budget_bytes=8, disk_budget_bytes=8)
    with pytest.raises(MemCapacityError):
        ar.put(("req", 0), np.ones((64,), np.float32))


# ---------------------------------------------------------------------------
# Prefetcher
# ---------------------------------------------------------------------------
def test_prefetcher_hit_miss_depth_and_prune():
    fetched = []
    pf = Prefetcher(lambda k: (fetched.append(k), f"v:{k}")[1], depth=2)
    assert pf.stage("a") and pf.stage("b")
    assert not pf.stage("c")                   # depth bound
    assert not pf.stage("a")                   # re-stage is a no-op
    assert fetched == ["a", "b"]
    v, hit = pf.take("a")
    assert v == "v:a" and hit
    v, hit = pf.take("c")
    assert v == "v:c" and not hit              # sync fallback
    pf.prune(lambda k: False)                  # "b"'s source vanished
    assert pf.entries == 0
    assert pf.counters == {"hit": 1, "miss": 1, "staged": 2, "dropped": 1}


# ---------------------------------------------------------------------------
# Residency planner
# ---------------------------------------------------------------------------
def test_plan_residency_graph_order_and_budget_cascade():
    from repro.core.offload import OffloadConfig

    cfg = _family_cfg("qwen2-0.5b")
    total = sum(l.size * l.dtype.itemsize for l in jax.tree.leaves(
        jax.eval_shape(lambda: M.init_model(cfg, jax.random.PRNGKey(0)))))
    oc = OffloadConfig(policy="graph", hbm_budget_bytes=total // 3,
                       host_budget_bytes=total // 3, disk_budget_bytes=0)
    rp = plan_residency(cfg, oc)
    assert rp.graph_order, "jaxpr walk must drive the ordering"
    # all three tiers populated under a 1/3 + 1/3 + inf split
    assert rp.count_in(HBM) and rp.count_in(HOST) and rp.count_in(DISK)
    assert rp.bytes_in(HBM) <= total // 3
    assert rp.bytes_in(HBM) + rp.bytes_in(HOST) + rp.bytes_in(DISK) == total
    # 1-D leaves are pinned in HBM regardless of pressure
    for l in rp.leaves:
        if len(l.shape) < 2:
            assert l.tier == HBM and "pinned" in l.rule
    # offloaded leaves carry a prefetch slot; HBM residents do not
    for l in rp.leaves:
        assert (l.prefetch_step is None) == (l.tier == HBM)
    # deterministic: same inputs -> identical plan (schedule included)
    rp2 = plan_residency(cfg, oc)
    assert rp2.leaves == rp.leaves and rp2.schedule == rp.schedule


def test_plan_residency_capacity_error():
    from repro.core.offload import OffloadConfig

    cfg = _family_cfg("qwen2-0.5b")
    with pytest.raises(MemCapacityError):
        plan_residency(cfg, OffloadConfig(policy="graph",
                                          hbm_budget_bytes=4096,
                                          host_budget_bytes=4096,
                                          disk_budget_bytes=4096))


def test_explain_reports_mem_rows_under_graph_policy():
    from repro.api import Supernode, plans
    from repro.api.errors import PlanError

    cfg = _family_cfg("qwen2-0.5b")
    session = Supernode()
    report = session.explain(plans.offload_graph(), cfg)
    n_params = len(jax.tree.leaves(jax.eval_shape(
        lambda: M.init_model(cfg, jax.random.PRNGKey(0)))))
    assert report.coverage()["mem"] == n_params
    for row in report.mem:
        assert row.memory in (HBM, HOST, DISK)
        assert row.rule
        assert row.spec == "resident" or str(row.spec).startswith("prefetch@")
    # manual plans carry no mem rows (policy gates the planner)
    assert session.explain(plans.fsdp_tp(), cfg).coverage()["mem"] == 0
    # policy + budget validation is typed and eager
    with pytest.raises(PlanError, match="offload_policy"):
        plans.offload_graph(offload_policy="bogus").validate()
    with pytest.raises(PlanError, match="budgets require"):
        plans.fsdp_tp(hbm_budget_bytes=1).validate()


# ---------------------------------------------------------------------------
# Serving round trips: spill -> host -> disk -> predictive restore
# ---------------------------------------------------------------------------
def _round_trip(cfg, scfg, prompts, max_new, *, force_preempt=False):
    params = M.init_model(cfg, jax.random.PRNGKey(0))
    gen = Generator(cfg, params, max_len=128)
    want = [gen.generate(jnp.asarray(p, jnp.int32)[None, :],
                         GenerateConfig(max_new_tokens=mn))[0, len(p):].tolist()
            for p, mn in zip(prompts, max_new)]
    serve = HyperServe(cfg, params, serve_cfg=scfg)
    rids = [serve.submit(p, mn) for p, mn in zip(prompts, max_new)]
    if force_preempt:
        # drive until one request decodes, then preempt it white-box (pure
        # slot-state models never build block pressure on their own)
        sched = serve.engine.scheduler
        for _ in range(64):
            serve.step_once()
            runners = [r for r in sched.active
                       if r.state is RequestState.RUNNING]
            if runners:
                sched._preempt(runners[-1], StepPlan())
                # mirror the tail of engine.step(): the iteration that
                # preempts stages near-head restores before the next
                # schedule() can re-admit (in-engine preemptions get this
                # from the step loop itself)
                near = [r for r in list(sched.queue)[:scfg.restore_lookahead]
                        if r.state is RequestState.PREEMPTED]
                serve.engine._stage_restores(near)
                break
        else:
            raise AssertionError("no request ever reached RUNNING")
    out = serve.join()
    for i, rid in enumerate(rids):
        assert out[rid] == want[i], f"{cfg.name} request {i} diverged"
    return serve


@pytest.mark.smoke
def test_paged_family_disk_round_trip_predictive_restore():
    """ATTN: pool pressure preempts; 64-byte host budget pushes the spill
    to disk; near-head staging restores it — token parity + exact hits."""
    cfg = _family_cfg("qwen2-0.5b")
    scfg = ServeConfig(block_size=4, num_blocks=9, max_blocks_per_req=8,
                       max_slots=2, prefill_chunk=4,
                       enable_prefix_cache=False,
                       archive_host_bytes=64, restore_lookahead=2)
    serve = _round_trip(cfg, scfg,
                        [list(range(1, 9)), list(range(20, 33)),
                         list(range(5, 10))], [8, 8, 8])
    st = serve.stats()
    assert st["preemptions"] >= 1
    assert st["restore_ahead_hits"] >= 1, "predictive restore never engaged"
    assert st["prefetch_misses"] == 0, "every restore should have been staged"
    assert st["archive_evict_host"] >= 1, "64-byte budget must spill to disk"
    assert st["archive_host_bytes"] == st["archive_disk_bytes"] == 0  # drained
    m = serve.obs().metrics
    assert m.counter("mem.restore_ahead.hit").value == st["restore_ahead_hits"]
    assert m.counter("mem.evict.host").value == st["archive_evict_host"]


def test_windowed_slot_family_disk_round_trip():
    """LOCAL_ATTN + RG-LRU hybrid: paged pressure spills pages AND dense
    slot rows through the disk tier; both restore token-exact."""
    cfg = _family_cfg("recurrentgemma-2b", num_layers=3, sliding_window=16)
    scfg = ServeConfig(block_size=2, num_blocks=11, max_blocks_per_req=10,
                       max_slots=2, prefill_chunk=4,
                       enable_prefix_cache=False,
                       archive_host_bytes=64, restore_lookahead=2)
    serve = _round_trip(cfg, scfg, [list(range(1, 5)), list(range(7, 11))],
                        [8, 8])
    st = serve.stats()
    assert st["preemptions"] >= 1
    assert st["restore_ahead_hits"] >= 1
    assert st["archive_evict_host"] >= 1


def test_ssd_family_disk_round_trip_forced():
    """Pure slot state (Mamba-2): forced preemption archives the dense
    recurrent rows through the tiny host budget into disk; predictive
    restore re-seats them exactly."""
    cfg = _family_cfg("mamba2-370m")
    scfg = ServeConfig(block_size=4, num_blocks=40, max_blocks_per_req=8,
                       max_slots=2, prefill_chunk=4,
                       enable_prefix_cache=False,
                       archive_host_bytes=64, restore_lookahead=2)
    serve = _round_trip(cfg, scfg, [list(range(1, 9)), list(range(20, 28))],
                        [6, 6], force_preempt=True)
    st = serve.stats()
    assert st["preemptions"] >= 1
    assert st["restore_ahead_hits"] >= 1
    assert st["archive_evict_host"] >= 1, "slot rows must traverse disk"


def test_tiny_hbm_pool_below_peak_working_set_completes():
    """The ISSUE acceptance run: the KV pool's HBM budget is strictly
    below the workload's peak working set (every request's full block
    demand), yet serving completes token-identical to the Generator."""
    cfg = _family_cfg("qwen2-0.5b")
    prompts = [list(range(1, 9)), list(range(20, 33)), list(range(5, 10)),
               list(range(40, 52))]
    max_new = [8, 8, 8, 8]
    scfg = ServeConfig(block_size=4, num_blocks=9, max_blocks_per_req=8,
                       max_slots=3, prefill_chunk=4,
                       enable_prefix_cache=False,
                       archive_host_bytes=256, restore_lookahead=2)
    working_set = sum(blocks_for(len(p) + mn, scfg.block_size)
                      for p, mn in zip(prompts, max_new))
    assert working_set > scfg.num_blocks - 1, "workload must exceed the pool"
    serve = _round_trip(cfg, scfg, prompts, max_new)
    st = serve.stats()
    assert st["finished"] == len(prompts)
    assert st["preemptions"] >= 1


def test_serve_config_validates_mem_knobs():
    from repro.api.errors import ServePlanError

    with pytest.raises(ServePlanError, match="restore_lookahead"):
        ServeConfig(restore_lookahead=-1).validate()
    with pytest.raises(ServePlanError, match="archive_host_bytes"):
        ServeConfig(archive_host_bytes=-1).validate()
