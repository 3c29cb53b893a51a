"""HyperServe engine loop: requests in, tokens out.

``ServeEngine`` composes the paged pool (:mod:`repro.serve.paged_kv`),
the continuous-batching scheduler (:mod:`repro.serve.scheduler`) and the
jit'd paged steps (:mod:`repro.serve.engine`) into one iteration:

    plan = scheduler.schedule()          # admit / resume / preempt
    run plan.prefill as ONE batched call # <= budget, so decode never starves
    run one decode step for all slots    # every runner advances one token

The decode batch is a fixed set of ``max_slots`` seats — requests are
seated and evicted, the jit'd step never recompiles.  Empty seats decode
a dummy token against the null block; their logits are ignored.

Prefill/decode disaggregation (HyperMPMD §3.3): given ``prefill_group`` /
``decode_group`` process groups (:func:`repro.core.mpmd.serving_groups`),
prompts are prefilled densely on the prefill workers' submesh, and the
resulting KV pages are handed to the decode workers' pool via a
resharding transfer — the decode mesh never spends a step on prefill
compute.  Without groups, chunked prefill interleaves on the one mesh.

A finished prompt's full blocks can be retained in a copy-on-write
**prefix cache**: an identical prompt prefix forks the cached blocks
(refcount bump, zero copies, zero recompute) and prefills only the tail.
Cache blocks are evicted LRU under pool pressure, before any preemption.
"""
from __future__ import annotations

import functools
import time
from collections import OrderedDict
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import hypershard, mpmd
from repro.core.kvcache import HostArchive
from repro.obs import Observability
from repro.serve import engine as E
from repro.serve.paged_kv import BlockManager, StatePool
from repro.serve.scheduler import ContinuousScheduler, Request, RequestState


def _resolve_serve_plan(plan, mesh):
    """Validate the caller's plan for serving; never silently rewrite it.

    Historically a caller-supplied fsdp plan was silently overridden to
    ``ShardingPlan(fsdp=None)``; now an fsdp-sharded plan is a typed
    :class:`repro.api.errors.ServePlanError` explaining why, and only a
    *missing* plan falls back to the serving default.  Returns
    ``(ShardingPlan, ServeConfig | None)`` — the latter when the plan is a
    HyperPlan that embeds serving knobs.
    """
    from repro.api.errors import ServePlanError
    from repro.api.plan import HyperPlan

    if plan is None:
        return hypershard.ShardingPlan(fsdp=None), None
    scfg = None
    if isinstance(plan, HyperPlan):
        from repro.core.layout import layout_for_mesh
        plan.validate(layout_for_mesh(mesh) if mesh is not None else None)
        scfg = plan.serve
        plan = plan.sharding_plan()
    if plan.fsdp:
        raise ServePlanError(
            f"plan shards parameters over fsdp={plan.fsdp}, which the "
            "serving runtime cannot use: decode steps would all-gather every "
            "weight each token (fsdp amortises gathers over a whole training "
            "step; a one-token step has nothing to amortise against), and "
            "the paged KV pool shards over tp/dp only.  Use "
            "plan.replace(fsdp=None), or a serving preset "
            "(repro.api.plans.serve() / serve_disagg()).")
    return plan, scfg


class ServeEngine:
    def __init__(self, cfg, params, *, serve_cfg=None, mesh=None, plan=None,
                 prefill_group: Optional[mpmd.ProcessGroup] = None,
                 decode_group: Optional[mpmd.ProcessGroup] = None,
                 moe_dispatch: Optional[str] = None, seed: int = 0,
                 obs: Optional[Observability] = None):
        from repro.configs.base import ServeConfig
        self.cfg = cfg
        # HyperTrace hub: sessions thread theirs through (Supernode.obs());
        # a bare engine gets a private one so per-engine counters and the
        # jit compile ledger stay clean across engines in one process
        self.obs = obs if obs is not None else Observability()
        if (prefill_group is None) != (decode_group is None):
            raise ValueError("disaggregation needs BOTH prefill and decode "
                             "groups (or neither)")
        self.prefill_group = prefill_group
        self.decode_group = decode_group
        self.mesh = decode_group.mesh if decode_group is not None else mesh
        for m in (self.mesh,
                  prefill_group.mesh if prefill_group is not None else None):
            if m is not None:
                E.check_data_axis_serving({a: m.shape[a]
                                           for a in m.axis_names})
        self.plan, plan_scfg = _resolve_serve_plan(plan, self.mesh)
        self.scfg = (serve_cfg or plan_scfg or ServeConfig()).validate()
        scfg = self.scfg
        # None -> dropless ragged dispatch for MoE configs (exact greedy
        # serving needs per-token-independent expert application)
        self.moe_dispatch = moe_dispatch = E.resolve_moe_dispatch(
            cfg, moe_dispatch)

        self.pcfg = scfg.paged_config(model_dtype=cfg.dtype)
        # resolves cfg against the mixer registry; typed ServePlanError for
        # unservable stacks (unregistered mixer kinds)
        self.pool = StatePool(cfg, self.pcfg, num_slots=scfg.max_slots)
        self.layout = self.pool.layout
        if prefill_group is not None:
            from repro.models import mixers as MX
            MX.check_disagg_supported(cfg, self.layout)
        pool_sh = E.make_pool_shardings(self.mesh, self.pool.state, self.plan,
                                        cfg)
        if pool_sh is not None:
            self.pool.state = jax.tree.map(jax.device_put, self.pool.state,
                                           pool_sh)
        # HyperMem: the archive is a bounded host->disk tier stack (0 =
        # unbounded), and a lookahead prefetcher stages restores for
        # requests nearing the queue head (StepPlan.near_head)
        self.blocks = BlockManager(self.pcfg, HostArchive(
            self.mesh, host_budget_bytes=scfg.archive_host_bytes,
            disk_budget_bytes=scfg.archive_disk_bytes, obs=self.obs))
        from repro.mem import Prefetcher
        self._restore_prefetch = Prefetcher(
            lambda key: self.blocks.archive.fetch(key, pop=False),
            depth=max(1, 2 * scfg.restore_lookahead), obs=self.obs)
        self.restore_ahead_hits = 0
        self.scheduler = ContinuousScheduler(
            scfg.scheduler_config(), self.blocks, scfg.block_size,
            scfg.max_blocks_per_req,
            spill=self._spill, restore=self._restore, reclaim=self._reclaim,
            prefix=self._prefix_lookup, retain=self._retain,
            free_window=self.layout.free_window,
            needs_pages=self.layout.has_paged_state,
            seed_fn=self._default_seed, obs=self.obs)

        # jit'd units ------------------------------------------------------
        # plan-level kernels toggle -> lowering path, resolved ONCE so every
        # step this engine dispatches takes the same path (and the
        # serve.kernels.* counters pin it exactly)
        from repro.kernels import ops
        self.kernel_path = ops.resolve_paged_path(scfg.kernels)
        # MoE configs: the step programs also return how many routes each
        # held expert of each MoE layer took (fetched with the step's
        # tokens; this step's arrays stay in ``moe_step_routes``)
        mo = getattr(cfg, "moe", None)
        self._moe = mo is not None
        self.moe_step_routes: List[np.ndarray] = []
        if self._moe:
            m = self.obs.metrics
            m.gauge("serve.moe.experts_held").set(mo.held)
            m.gauge("serve.moe.experts_total").set(mo.num_experts)
            m.counter("serve.moe.routes_held")
            m.counter("serve.moe.routes")
            self._moe_layers = sum(1 for _, f in cfg.block_kinds()
                                   if f == "moe")
        self._decode_step, _ = E.make_paged_serve_step(
            cfg, self.mesh, self.plan, block_size=scfg.block_size,
            pool_tree=self.pool.state, donate=True, moe_dispatch=moe_dispatch,
            kernels=self.kernel_path)
        # how the step programs hold the pool: leaves updated in place in
        # the stacked buffer vs sliced per layer and written back
        in_place, sliced = self.pool.leaf_counts()
        self.obs.metrics.gauge("serve.pool.leaves_in_place").set(in_place)
        self.obs.metrics.gauge("serve.pool.leaves_sliced").set(sliced)
        if prefill_group is None:
            # ONE batched step services every chunk the scheduler admits
            # per iteration (rows padded to the null slot) — a single jit
            # dispatch and a single kernel launch per engine step
            self._prefill_step, _ = E.make_paged_prefill_step(
                cfg, self.mesh, self.plan, block_size=scfg.block_size,
                pool_tree=self.pool.state, donate=True,
                moe_dispatch=moe_dispatch, kernels=self.kernel_path)
            self.params = params
            if self.mesh is not None:
                pshapes = jax.eval_shape(lambda p: p, params)
                psh = hypershard.make_param_shardings(self.mesh, pshapes,
                                                      self.plan)
                self.params = jax.tree.map(jax.device_put, params, psh)
            self._params_prefill = None
        else:
            # disaggregated: dense prefill on the prefill submesh, decode on
            # the decode submesh; params live on both (the paper's
            # heterogeneous-role deployment, not a memory optimisation)
            pshapes = jax.eval_shape(lambda p: p, params)
            psh_d = hypershard.make_param_shardings(self.mesh, pshapes,
                                                    self.plan)
            self.params = jax.tree.map(jax.device_put, params, psh_d)
            psh_p = hypershard.make_param_shardings(prefill_group.mesh,
                                                    pshapes, self.plan)
            self._params_prefill = jax.tree.map(jax.device_put, params, psh_p)
            self._dense_prefill = {}          # padded len -> jitted step
        self.mpmd_sched = mpmd.MPMDScheduler(
            {g.name: g for g in (prefill_group, decode_group)
             if g is not None}, obs=self.obs)

        # prefix cache: token-tuple -> block ids (refs held by the cache)
        self._prefix_cache: "OrderedDict[Tuple[int, ...], List[int]]" = \
            OrderedDict()
        self.seed = seed
        self.t_start = time.perf_counter()
        self.tokens_generated = 0
        # interval-rate marks: stats() reports tokens/sec over the window
        # since the previous stats() call, so the rate no longer decays
        # across idle gaps between serve() calls (t_start is kept only for
        # the cumulative view)
        self._rate_t = self.t_start
        self._rate_tokens = 0
        # batching effectiveness: chunks serviced vs jit calls made — the
        # whole point of the batched prefill step is chunks >> calls
        self.prefill_calls = 0
        self.prefill_chunks = 0

    # ------------------------------------------------------------------
    # tier-movement callbacks (scheduler-driven)
    # ------------------------------------------------------------------
    def _spill(self, req: Request) -> None:
        """Archive a preempted request's pages AND its dense slot rows."""
        with self.obs.trace.span("serve.spill", track="engine", rid=req.rid,
                                 blocks=len(req.table)):
            if self.layout.has_slot_state:
                self.blocks.archive.put(req.slot_archive_key,
                                        self.pool.extract_slot(req.slot))
            self.blocks.spill(req.archive_key, req.table,
                              self.pool.extract_pages)
        self.obs.metrics.counter("serve.spills").inc()

    def _restore(self, req: Request) -> List[int]:
        with self.obs.trace.span("serve.restore", track="engine",
                                 rid=req.rid):
            bids = self._restore_inner(req)
        self.obs.metrics.counter("serve.restores").inc()
        return bids

    def _restore_inner(self, req: Request) -> List[int]:
        # allocate BEFORE consuming staged state: NoFreeBlocks aborts the
        # resume with both the archive entries and the prefetch buffer
        # intact, so the retry next iteration is identical (and a staged
        # copy still scores its restore-ahead hit when it finally seats)
        pf = self._restore_prefetch
        bids = self.blocks.alloc(req.spilled_blocks)
        pages, hit = pf.take(req.archive_key)     # mem.prefetch.{hit,miss}
        self.blocks.archive.discard(req.archive_key)
        self.pool.insert_pages(pages, bids)
        # the scheduler seats req.slot before invoking this callback, so
        # the dense slot rows re-seat HERE — atomically with the pages.
        # (Seating later, in step(), loses a same-cycle re-preemption
        # race: _spill would archive the seat's stale rows.)
        if self.layout.has_slot_state:
            rows, slot_hit = pf.take(req.slot_archive_key)
            self.blocks.archive.discard(req.slot_archive_key)
            self.pool.insert_slot(req.slot, rows)
            hit = hit and slot_hit
        if hit:
            # every byte of this request's archived state was already
            # moving (or seated) before _admit asked for it
            self.restore_ahead_hits += 1
            self.obs.metrics.counter("mem.restore_ahead.hit").inc()
        # window-freed entries were a table prefix; rebuild alignment
        return [BlockManager.NULL] * req.null_prefix + bids

    def _stage_restores(self, near: List[Request]) -> None:
        """Predictive restore: start pulling archived pages / slot rows
        for PREEMPTED requests nearing the queue head.  The fetch is an
        async host->device copy (pop=False — the archive entry survives
        until the real restore commits), so it overlaps this iteration's
        compute exactly like the core/overlap double buffer."""
        pf = self._restore_prefetch
        arch = self.blocks.archive
        pf.prune(lambda k: k in arch)     # cancelled requests drop staged
        for req in near:
            if req.archive_key in arch:
                pf.stage(req.archive_key)
            if self.layout.has_slot_state and req.slot_archive_key in arch:
                pf.stage(req.slot_archive_key)

    def _reclaim(self, n: int) -> int:
        """Evict LRU prefix-cache entries until >= n blocks are freed."""
        freed = 0
        while self._prefix_cache and freed < n:
            _, bids = self._prefix_cache.popitem(last=False)
            before = self.blocks.num_free
            self.blocks.free(bids)
            freed += self.blocks.num_free - before
        return freed

    def _prefix_lookup(self, req: Request) -> List[int]:
        # disagg mode seats the whole dense prefill cache into the table,
        # which would write through CoW-shared blocks — no sharing there.
        # Prefix forks are only sound for pure-paged layouts: slot-state
        # mixers would resume with no recurrent state for the shared
        # prefix, and windowed layouts may already have freed prompt
        # blocks out of the retaining request's window.
        if (not self.scfg.enable_prefix_cache
                or self.prefill_group is not None
                or not self.layout.pure_paged):
            return []
        bs = self.pcfg.block_size
        # at least one prompt token must remain to prefill (its logits seed
        # the first generated token), hence the -1
        for nb in range((req.prompt_len - 1) // bs, 0, -1):
            key = tuple(req.prompt[:nb * bs])
            if key in self._prefix_cache:
                self._prefix_cache.move_to_end(key)
                return self.blocks.fork(self._prefix_cache[key])
        return []

    def _retain(self, req: Request) -> None:
        if not self.scfg.enable_prefix_cache or not self.layout.pure_paged:
            return
        bs = self.pcfg.block_size
        # retain every full-block prefix: a future prompt can only fork a
        # prefix strictly shorter than itself, so the longest entry alone
        # would never match an identical prompt
        for nb in range(1, req.prompt_len // bs + 1):
            key = tuple(req.prompt[:nb * bs])
            if key in self._prefix_cache:
                self._prefix_cache.move_to_end(key)
                continue
            self._prefix_cache[key] = self.blocks.fork(req.table[:nb])
        while (sum(len(v) for v in self._prefix_cache.values())
               > self.scfg.prefix_cache_blocks):
            _, bids = self._prefix_cache.popitem(last=False)
            self.blocks.free(bids)

    # ------------------------------------------------------------------
    # sampling
    # ------------------------------------------------------------------
    def _default_seed(self, rid: int) -> int:
        """Per-request seed for requests that didn't pin one at submit."""
        return (self.seed ^ (rid * 0x9E3779B1)) & 0x7FFFFFFF

    def _sample(self, logits_row, req: Request) -> int:
        """Sample the request's next token under a per-request PRNG.

        The key depends only on ``(req.seed, len(req.generated))`` — no
        engine-global counter — so a temperature>0 rollout resamples the
        identical token stream across runs AND across preemption
        spill/restore (which never rolls ``generated`` back).  With
        ``capture_logprobs`` the sampled token's logprob *under the
        sampling distribution* (temperature-scaled softmax) is appended to
        ``req.logprobs`` — the behaviour-policy term RL updates need.
        """
        lg = logits_row[:self.cfg.vocab_size].astype(jnp.float32)
        if req.temperature > 0:
            key = jax.random.fold_in(jax.random.PRNGKey(req.seed),
                                     len(req.generated))
            lg = lg / req.temperature
            tok = int(jax.random.categorical(key, lg))
        else:
            tok = int(jnp.argmax(lg))
        if req.capture_logprobs:
            req.logprobs.append(float(jax.nn.log_softmax(lg)[tok]))
        return tok

    @functools.cached_property
    def _batched_sampler(self):
        """jit'd vmap of the per-request sampler (one device op + one
        transfer for the whole decode batch, instead of a host round-trip
        per seated slot).  Row semantics are identical to :meth:`_sample`:
        each row's key is fold_in(PRNGKey(seed), position), the gumbel
        trick and log_softmax are row-local, so batching never changes
        the sampled stream (the vmap axis is invisible to a single row).
        """
        V = self.cfg.vocab_size

        def one(seed, pos, temp, logits_row):
            lg = logits_row[:V].astype(jnp.float32) / temp
            key = jax.random.fold_in(jax.random.PRNGKey(seed), pos)
            tok = jax.random.categorical(key, lg)
            return tok, jax.nn.log_softmax(lg)[tok]

        return jax.jit(jax.vmap(one))

    def _sample_batch(self, runners, logits):
        """Batched temperature sampling for the decode step's runners.

        Always shaped (max_slots,) — empty seats sample garbage that is
        never read — so the vmapped sampler compiles exactly once per
        engine, regardless of how many seats are occupied this step.
        """
        B = self.scfg.max_slots
        seeds = np.zeros((B,), np.uint32)
        poss = np.zeros((B,), np.int32)
        temps = np.ones((B,), np.float32)
        for r in runners:
            seeds[r.slot] = r.seed
            poss[r.slot] = len(r.generated)
            temps[r.slot] = r.temperature
        toks, lps = self._batched_sampler(jnp.asarray(seeds),
                                          jnp.asarray(poss),
                                          jnp.asarray(temps), logits[:, -1])
        toks = np.asarray(toks)
        lps = np.asarray(lps)
        for r in runners:
            if r.capture_logprobs:
                r.logprobs.append(float(lps[r.slot]))
        return {r.slot: int(toks[r.slot]) for r in runners}

    # ------------------------------------------------------------------
    # prefill execution
    # ------------------------------------------------------------------
    def _run_prefill_batch(self, reqs: List[Request], step: int
                           ) -> None:
        """Every scheduled prompt chunk in ONE jit call (<= prefill_batch
        rows, filler rows padded to limit 0 / the null slot / the null
        block): a single dispatch and a single kernel launch amortised
        over the whole batch — raising ``prefill_chunks_per_step`` now
        buys device-level batching instead of more per-request calls.

        The row count is bucketed to the next power of two (1, 2, 4, ...,
        prefill_batch) and jit compiles one variant per bucket: a lone
        prefilling request costs a (1, chunk) call, not a fully padded
        (prefill_batch, chunk) one — padding waste only ever doubles the
        live rows, while compilations stay O(log prefill_batch).
        """
        tr = self.obs.trace
        C = self.scfg.prefill_chunk
        Pb = 1
        while Pb < len(reqs):
            Pb *= 2
        Pb = min(Pb, self.scfg.prefill_batch)
        W = self.pcfg.max_blocks_per_req
        with tr.span("serve.prefill.pack", track="engine", step=step):
            toks = np.zeros((Pb, C), np.int32)
            starts = np.zeros((Pb,), np.int32)
            limits = np.zeros((Pb,), np.int32)
            # filler rows sit in the out-of-range null seat: their
            # slot-state writes are dropped on device (see
            # models.mamba2.scatter_slot_rows)
            slots = np.full((Pb,), self.scfg.max_slots, np.int32)
            tables = np.zeros((Pb, W), np.int32)
            meta = []
            for i, req in enumerate(reqs):
                c0 = req.prefill_done
                n = min(C, req.prompt_len - c0)
                toks[i, :n] = req.prompt[c0:c0 + n]
                starts[i] = c0
                limits[i] = req.prompt_len
                slots[i] = req.slot
                tables[i, :len(req.table)] = req.table
                meta.append((i, req, n))
            self.obs.record_compile("paged_prefill", (Pb, C, W))
            self.obs.metrics.counter(
                f"serve.kernels.prefill.{self.kernel_path}").inc()
        # the prefill dispatch: it ends once the call is enqueued
        with tr.span("serve.prefill", track="engine", rows=len(reqs),
                     bucket=Pb, rids=[r.rid for r in reqs], step=step):
            logits, self.pool.state, *routes = self._prefill_step(
                self.params, jnp.asarray(toks), jnp.asarray(starts),
                jnp.asarray(limits), jnp.asarray(slots), self.pool.state,
                jnp.asarray(tables))
        self.prefill_calls += 1
        self.prefill_chunks += len(reqs)
        self.obs.metrics.counter("serve.prefill_calls").inc()
        self.obs.metrics.counter("serve.prefill_chunks").inc(len(reqs))
        # the first sample waits for the prefill program to finish
        with tr.span("serve.prefill.first_tokens", track="engine", step=step):
            if routes:
                self._count_routes(np.asarray(routes[0]),
                                   sum(n for _, _, n in meta))
            for i, req, n in meta:
                self.scheduler.on_prefill_chunk(req, n)
                if req.prefill_done == req.prompt_len:
                    # the step returns each row's LAST in-chunk prompt-token
                    # logits: exactly what seeds the first sampled token
                    first = self._sample(logits[i], req)
                    self.scheduler.on_prompt_complete(req, first)
                    self.tokens_generated += 1

    def _dense_prefill_fn(self, batch: int, padded_len: int):
        key = (batch, padded_len)
        if key not in self._dense_prefill:
            fn, _ = E.make_prefill_step(self.cfg, self.prefill_group.mesh,
                                        self.plan, batch=batch,
                                        seq_len=padded_len,
                                        moe_dispatch=self.moe_dispatch)
            self._dense_prefill[key] = fn
        return self._dense_prefill[key]

    def _run_disagg_prefill(self, reqs: List[Request], step: int
                            ) -> None:
        """Whole-prompt prefill for all scheduled prompts as ONE dense
        batch on the prefill workers; each row's pages scatter into the
        decode workers' pool.  Rows are right-padded to a shared
        chunk-aligned length — causal attention keeps rows independent,
        and serving MoE uses the dropless per-token dispatch, so batching
        rows never changes a row's output.  The batch dim is bucketed to
        the next power of two (all-zero filler rows are computed and
        discarded), matching the paged path's compile-count bound: one
        dense trace per (bucket, padded length), not per exact group
        size."""
        S_max = max(r.prompt_len for r in reqs)
        padded = S_max + (-S_max % self.scfg.prefill_chunk)
        Pb = 1
        while Pb < len(reqs):
            Pb *= 2
        tr = self.obs.trace
        with tr.span("serve.prefill.pack", track="engine", step=step):
            toks = np.zeros((Pb, padded), np.int32)
            for i, r in enumerate(reqs):
                toks[i, :r.prompt_len] = r.prompt
            self.obs.record_compile("dense_prefill", (Pb, padded))
        with tr.span("serve.prefill", track="engine", rows=len(reqs),
                     bucket=Pb, padded=padded, rids=[r.rid for r in reqs],
                     disagg=True, step=step):
            task = self.mpmd_sched.submit(
                self.prefill_group.name, self._dense_prefill_fn(Pb, padded),
                self._params_prefill, jnp.asarray(toks))
            logits, pcaches = task.out
            # hand the KV pages to the decode workers (resharding device_put)
            dst = self.decode_group.sharding()
            with tr.span("serve.kv_transfer", track="engine",
                         rows=len(reqs)):
                pcaches = jax.tree.map(lambda a: jax.device_put(a, dst),
                                       pcaches)
        self.prefill_calls += 1
        self.prefill_chunks += len(reqs)
        self.obs.metrics.counter("serve.prefill_calls").inc()
        self.obs.metrics.counter("serve.prefill_chunks").inc(len(reqs))
        with tr.span("serve.prefill.first_tokens", track="engine", step=step):
            for i, req in enumerate(reqs):
                S = req.prompt_len
                self.pool.seat_prefill_caches(pcaches, req.table, S, row=i)
                self.scheduler.on_prefill_chunk(req, S - req.prefill_done)
                first = self._sample(logits[i, S - 1], req)
                self.scheduler.on_prompt_complete(req, first)
                self.tokens_generated += 1

    # ------------------------------------------------------------------
    # the engine iteration
    # ------------------------------------------------------------------
    def step(self) -> List[Tuple[int, int]]:
        """One scheduler+compute iteration.  Returns [(rid, new token)].

        While the tracer is enabled the iteration is one ``serve.step``
        span (a profiler step annotation) whose children, each carrying
        the same ``step`` index, split the host's time into phases:
        ``serve.schedule``, ``serve.prefill.pack``, ``serve.prefill``
        (the dispatch), ``serve.prefill.first_tokens``,
        ``serve.decode.pack``, ``serve.decode`` (``.dispatch`` then
        ``.wait``), ``serve.commit`` and ``serve.gauges``."""
        tr = self.obs.trace
        n = self.scheduler.steps                # this iteration's index
        self.moe_step_routes = []
        with tr.step_span("serve.step", n, track="engine") as root:
            with tr.span("serve.schedule", track="engine", step=n):
                plan = self.scheduler.schedule()
                if plan.near_head or self._restore_prefetch.entries:
                    self._stage_restores(plan.near_head)
                if self.layout.has_slot_state:
                    # fresh admissions must not inherit the previous
                    # occupant's recurrence (resumed requests were re-seated
                    # inside _restore, atomically with their pages)
                    for req in plan.admitted:
                        self.pool.zero_slot(req.slot)
            events = self._step_prefill(plan.prefill, n)
            runners = [r for r in plan.decode
                       if r.state is RequestState.RUNNING]
            root.set(prefill_rows=len(plan.prefill),
                     decode_rows=len(runners))
            if runners:
                events += self._step_decode(runners, n)
            with tr.span("serve.gauges", track="engine", step=n):
                self._set_gauges()
        return events

    def _step_prefill(self, reqs: List[Request], step: int
                      ) -> List[Tuple[int, int]]:
        """This step's prompt chunks; the first tokens they produced."""
        if not reqs:
            return []
        # all scheduled chunks run in one batched call per group of
        # prefill_batch rows (== one call per step at the defaults, where
        # the scheduler budget never exceeds the row count)
        gsz = self.scfg.prefill_batch
        if (self.moe_dispatch == "gshard"
                and getattr(self.cfg, "moe", None) is not None):
            # forced GShard capacity dispatch makes a row's output depend
            # on its batch mates — keep the old one-request prefills (paged
            # and disagg alike) rather than silently change outputs with
            # batch composition
            gsz = 1
        for i in range(0, len(reqs), gsz):
            group = reqs[i:i + gsz]
            if self.prefill_group is not None:
                self._run_disagg_prefill(group, step)
            else:
                self._run_prefill_batch(group, step)
        return [(req.rid, req.generated[-1]) for req in reqs
                if req.generated]

    def _step_decode(self, runners: List[Request], step: int
                     ) -> List[Tuple[int, int]]:
        """One token for every runner; returns [(rid, token)]."""
        tr = self.obs.trace
        B = self.scfg.max_slots
        W = self.pcfg.max_blocks_per_req
        with tr.span("serve.decode.pack", track="engine", step=step):
            tokens = np.zeros((B, 1), np.int32)
            positions = np.zeros((B,), np.int32)
            tables = np.zeros((B, W), np.int32)
            slot_mask = np.zeros((B,), bool)
            for r in runners:
                tokens[r.slot, 0] = r.generated[-1]
                positions[r.slot] = r.total_len - 1
                tables[r.slot, :len(r.table)] = r.table
                slot_mask[r.slot] = True
            self.obs.record_compile("paged_decode", (B, W))
            self.obs.metrics.counter(
                f"serve.kernels.decode.{self.kernel_path}").inc()
        with tr.span("serve.decode", track="engine", runners=len(runners),
                     step=step):
            with tr.span("serve.decode.dispatch", track="engine", step=step):
                logits, self.pool.state, *routes = self._decode_step(
                    self.params, jnp.asarray(tokens), jnp.asarray(positions),
                    self.pool.state, jnp.asarray(tables),
                    jnp.asarray(slot_mask))
            with tr.span("serve.decode.wait", track="engine", step=step):
                if all(r.temperature <= 0 and not r.capture_logprobs
                       for r in runners):
                    # batched greedy: one device op + one transfer for the
                    # whole batch (and the route counts) instead of a sync
                    # per seated slot
                    nxt, *routes = jax.device_get([jnp.argmax(
                        logits[:, -1, :self.cfg.vocab_size].astype(
                            jnp.float32),
                        axis=-1)] + routes)
                    picks = {r.slot: int(nxt[r.slot]) for r in runners}
                elif all(r.temperature > 0 for r in runners):
                    # batched stochastic (the RL rollout hot path)
                    self.obs.record_compile("sampler", (B,))
                    picks = self._sample_batch(runners, logits)
                else:
                    picks = {r.slot: self._sample(logits[r.slot, -1], r)
                             for r in runners}
                if routes:
                    self._count_routes(np.asarray(routes[0]), len(runners))
        events = []
        with tr.span("serve.commit", track="engine", step=step):
            for r in runners:
                tok = picks[r.slot]
                self.scheduler.on_decode_token(r, tok)
                self.tokens_generated += 1
                events.append((r.rid, tok))
        return events

    def _count_routes(self, routes: np.ndarray, tokens: int) -> None:
        """One step program's held-expert route counts (n_moe_layers,
        held), for ``tokens`` real tokens routed top-k in every MoE layer."""
        self.moe_step_routes.append(routes)
        m = self.obs.metrics
        m.counter("serve.moe.routes_held").inc(int(routes.sum()))
        m.counter("serve.moe.routes").inc(
            tokens * self.cfg.moe.top_k * self._moe_layers)

    def _set_gauges(self) -> None:
        """Occupancy snapshot after an engine iteration (pool / archive /
        prefix-cache byte and block gauges, plus Perfetto counter tracks
        while a trace is being captured)."""
        m = self.obs.metrics
        occ = self.blocks.occupancy()
        m.gauge("serve.block_occupancy").set(occ)
        m.gauge("serve.blocks_free").set(self.blocks.num_free)
        m.gauge("serve.archive_host_bytes").set(
            self.blocks.archive.nbytes_host())
        m.gauge("serve.archive_disk_bytes").set(
            self.blocks.archive.nbytes_disk())
        m.gauge("serve.pool_hbm_bytes").set(self.pool.hbm_bytes())
        m.gauge("serve.prefix_cache_blocks").set(
            sum(len(v) for v in self._prefix_cache.values()))
        tr = self.obs.trace
        if tr.enabled:
            tr.counter("block_occupancy", occ, track="pool")
            tr.counter("archive_bytes", self.blocks.archive.nbytes(),
                       track="pool")
            tr.counter("running",
                       sum(1 for r in self.scheduler.active
                           if r.state is RequestState.RUNNING), track="pool")

    def run_until_complete(self, max_steps: int = 100_000) -> Dict[int, List[int]]:
        steps = 0
        while self.scheduler.has_work():
            self.step()
            steps += 1
            if steps > max_steps:
                raise RuntimeError("serving loop did not drain "
                                   f"({max_steps} steps)")
        return {rid: r.generated for rid, r in self.scheduler.requests.items()}

    # ------------------------------------------------------------------
    def snapshot(self) -> Dict:
        """Cheap read-only routing surface (HyperFabric keys off this).

        Pure host-side scheduler/pool accounting — no device sync, no
        mutation — so a router can poll it every dispatch decision.  The
        prefix-cache view exposes both the retained block ids and the
        token-tuple keys: the keys are what longest-prefix affinity
        matching needs, the ids are what capacity accounting needs.
        Everything here is deterministic given the request history, which
        is what lets routing decisions (and their counters) be pinned
        exactly by the bench gate.
        """
        sched = self.scheduler
        prefilling = sum(1 for r in sched.active
                         if r.state is RequestState.PREFILLING)
        running = sum(1 for r in sched.active
                      if r.state is RequestState.RUNNING)
        return {
            "queue_depth": len(sched.queue),
            "prefilling": prefilling,
            "running": running,
            "free_slots": self.scfg.max_slots - prefilling - running,
            "max_slots": self.scfg.max_slots,
            "max_queue": sched.cfg.max_queue,
            "free_blocks": self.blocks.num_free,
            "block_occupancy": self.blocks.occupancy(),
            "prefix_cache_block_ids": tuple(
                b for bids in self._prefix_cache.values() for b in bids),
            "prefix_keys": tuple(self._prefix_cache.keys()),
            "has_work": sched.has_work(),
        }

    def stats(self) -> Dict[str, float]:
        now = time.perf_counter()
        # interval rate: tokens since the previous stats() call over the
        # wall time since that call — an engine idle between serve() calls
        # reports the rate of the active window, not a decaying average
        # over its whole lifetime
        dt_int = now - self._rate_t
        tok_int = self.tokens_generated - self._rate_tokens
        self._rate_t = now
        self._rate_tokens = self.tokens_generated
        dt_cum = now - self.t_start
        m = self.obs.metrics
        ttft = m.histogram("serve.ttft_s")
        itl = m.histogram("serve.itl_s")
        qw = m.histogram("serve.queue_wait_s")
        s = self.scheduler.stats()
        in_place, sliced = self.pool.leaf_counts()
        s.update({
            "queue_depth": len(self.scheduler.queue),
            "tokens_generated": self.tokens_generated,
            "tokens_per_sec": tok_int / dt_int if dt_int > 0 else 0.0,
            "tokens_per_sec_cumulative":
                self.tokens_generated / dt_cum if dt_cum > 0 else 0.0,
            "prefill_calls": self.prefill_calls,
            "prefill_chunks": self.prefill_chunks,
            "pool_hbm_bytes": self.pool.hbm_bytes(),
            "pool_leaves_in_place": in_place,
            "pool_leaves_sliced": sliced,
            # per-tier archive accounting (HyperMem): host DRAM vs the
            # disk tier the bounded archive spills into, plus how often
            # predictive restore had the state moving before it was seated
            "archive_host_bytes": self.blocks.archive.nbytes_host(),
            "archive_disk_bytes": self.blocks.archive.nbytes_disk(),
            "archive_evict_host": self.blocks.archive.counters["evict_host"],
            "archive_evict_disk": self.blocks.archive.counters["evict_disk"],
            "restore_ahead_hits": self.restore_ahead_hits,
            "prefetch_hits": self._restore_prefetch.counters["hit"],
            "prefetch_misses": self._restore_prefetch.counters["miss"],
            "prefix_cache_blocks": sum(len(v)
                                       for v in self._prefix_cache.values()),
            "ttft_p50_s": ttft.percentile(50),
            "ttft_p95_s": ttft.percentile(95),
            "itl_p50_s": itl.percentile(50),
            "itl_p95_s": itl.percentile(95),
            "queue_wait_p50_s": qw.percentile(50),
            "recompiles": self.obs.recompiles(),
        })
        if self._moe:
            s.update({
                "moe_routes_held": m.counter("serve.moe.routes_held").value,
                "moe_routes": m.counter("serve.moe.routes").value,
                "moe_experts_held": m.gauge("serve.moe.experts_held").value,
                "moe_experts_total": m.gauge("serve.moe.experts_total").value,
            })
        return s
