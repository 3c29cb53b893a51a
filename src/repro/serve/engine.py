"""Serving: prefill + decode steps, batched generation, KV-offload serving.

``make_prefill_step`` / ``make_serve_step`` are the jit'd units the dry-run
lowers for the ``prefill_32k`` / ``decode_32k`` / ``long_500k`` shapes.
``Generator`` drives them for real token-by-token generation (used by the
examples and tests).  ``OffloadServer`` is the HyperOffload serving path:
hierarchical KV pool with host archive (paper's 71K->123K claim).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import hypershard
from repro.core.meshctx import use_mesh
from repro.models import model as M


def resolve_moe_dispatch(cfg, moe_dispatch: Optional[str]) -> str:
    """Serving default: dropless per-token dispatch for MoE configs.

    The GShard capacity dispatch makes a token's output depend on which
    other tokens share its dispatch group — under continuous batching the
    group is whatever happens to be seated (including dummy seats), so
    outputs would flicker with batch composition and can never match the
    sequential baseline.  The sort-based ragged dispatch applies each
    token's own top-k experts with no cross-token interaction, which is
    what makes greedy serving deterministic; callers can still force a
    specific dispatch.
    """
    if moe_dispatch is not None:
        return moe_dispatch
    return "ragged" if getattr(cfg, "moe", None) is not None else "gshard"


def make_prefill_step(cfg, mesh: Optional[Mesh], plan, *, multimodal=False,
                      unroll=False, batch: Optional[int] = None,
                      seq_len: Optional[int] = None,
                      moe_dispatch: str = "gshard"):
    def prefill(params, tokens, prefix_embeds=None):
        ctx = use_mesh(mesh) if mesh is not None else _null()
        with ctx:
            logits, caches, _ = M.forward(params, tokens, cfg,
                                          prefix_embeds=prefix_embeds,
                                          mode="prefill", remat=False,
                                          unroll=unroll,
                                          moe_dispatch=moe_dispatch)
        return logits, caches
    if mesh is None:
        return jax.jit(prefill), {}
    pshapes = jax.eval_shape(lambda: M.init_model(cfg, jax.random.PRNGKey(0)))
    param_sh = hypershard.make_param_shardings(mesh, pshapes, plan)
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    dp_entry = dp if len(dp) > 1 else (dp[0] if dp else None)
    tok_sh = NamedSharding(mesh, P(dp_entry, None))

    out_sh = None
    if batch is not None and seq_len is not None:
        # derive output shardings so the returned KV caches (and logits)
        # come out sharded like the decode step expects — without this the
        # caches replicate over the model axis and blow past HBM for the
        # 32K-prefill shapes
        toks = jax.ShapeDtypeStruct((batch, seq_len), jnp.int32)
        pe = (jax.ShapeDtypeStruct((batch, cfg.num_prefix_tokens,
                                    cfg.frontend_dim), jnp.bfloat16)
              if multimodal else None)
        _, cshapes = jax.eval_shape(prefill, pshapes, toks, pe)
        cache_sh = hypershard.make_cache_shardings(mesh, cshapes, plan,
                                                   batch=batch)
        logits_sh = NamedSharding(mesh, P(dp_entry, None,
                                          _vocab_axis(cfg, mesh)))
        out_sh = (logits_sh, cache_sh)

    if multimodal:
        pe_sh = NamedSharding(mesh, P(dp_entry, None, None))
        in_sh = (param_sh, tok_sh, pe_sh)
    else:
        in_sh = (param_sh, tok_sh)
    return jax.jit(prefill, in_shardings=in_sh,
                   out_shardings=out_sh), {"params": param_sh}


def make_serve_step(cfg, mesh: Optional[Mesh], plan, *, batch: int,
                    cache_len: int, window_override: Optional[int] = None,
                    donate: bool = True, unroll: bool = False,
                    moe_dispatch: str = "gshard"):
    """One-token decode step against a cache of ``cache_len``."""

    def serve(params, token, pos, caches):
        ctx = use_mesh(mesh) if mesh is not None else _null()
        with ctx:
            logits, new_caches = M.decode_step(
                params, token, pos, cfg, caches,
                window_override=window_override, unroll=unroll,
                moe_dispatch=moe_dispatch)
        return logits, new_caches

    if mesh is None:
        return jax.jit(serve, donate_argnums=(3,) if donate else ()), {}

    pshapes = jax.eval_shape(lambda: M.init_model(cfg, jax.random.PRNGKey(0)))
    param_sh = hypershard.make_param_shardings(mesh, pshapes, plan)
    cshapes = jax.eval_shape(lambda: M.init_caches(
        cfg, batch, cache_len, window_override=window_override))
    cache_sh = hypershard.make_cache_shardings(mesh, cshapes, plan, batch=batch)
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    dp_entry = dp if len(dp) > 1 else (dp[0] if dp else None)
    tok_sh = NamedSharding(mesh, P(dp_entry, None) if batch % _n(mesh, dp) == 0
                           else P(None, None))
    pos_sh = NamedSharding(mesh, P())
    step = jax.jit(serve,
                   in_shardings=(param_sh, tok_sh, pos_sh, cache_sh),
                   out_shardings=(None, cache_sh),
                   donate_argnums=(3,) if donate else ())
    return step, {"params": param_sh, "caches": cache_sh, "tokens": tok_sh}


def _n(mesh, axes):
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def _vocab_axis(cfg, mesh) -> Optional[str]:
    """The logits out-sharding's vocab-dim axis, or None when indivisible.

    Heterogeneous fabric carves make odd model-axis sizes easy to reach
    (e.g. a 6-device submesh under padded_vocab 1024).  An explicit
    ``NamedSharding`` whose axis does not divide the dim is an XLA error
    inside jit, so fall back to replicated logits — correctness over the
    sharded unembed output; the matmul itself still runs tp-sharded.
    """
    if mesh is None or "model" not in mesh.axis_names:
        return None
    return "model" if cfg.padded_vocab % mesh.shape["model"] == 0 else None


# ---------------------------------------------------------------------------
# HyperServe: jit'd units over the paged KV pool (block tables, not dense
# per-request caches).  Shapes are static in (num_slots, table width,
# chunk); positions/starts are traced, so one compilation serves the whole
# continuous-batching run.
# ---------------------------------------------------------------------------
def check_data_axis_serving(axis_sizes) -> None:
    """Reject paged serving on a mesh with a nontrivial non-model axis.

    Paged serving under a data/pod axis of size > 1 currently MISCOMPILES
    on the CPU backend: GSPMD inserts a spurious data-axis all-reduce
    around small-head elementwise ops (rope on a KV-head dim that divides
    the data axis), doubling K — ``serve/runtime`` outputs silently
    diverge from ``Generator`` (ROADMAP open item).  Serving is tp-only
    anyway (the serve leg drops fsdp, and the decode batch is one seat
    grid, not a data-parallel batch), so a nontrivial data axis buys
    nothing: raise a typed error pointing at the flat model-only view
    (``repro.rl.session.serving_mesh_for``) instead of silently
    diverging.  ``axis_sizes``: mapping of mesh axis name -> size.
    """
    from repro.api.errors import ServePlanError

    bad = {a: int(n) for a, n in dict(axis_sizes).items()
           if a != "model" and int(n) > 1}
    if bad:
        raise ServePlanError(
            f"paged serving needs a model-only device view, but the mesh "
            f"carries nontrivial non-model ax"
            f"{'es' if len(bad) > 1 else 'is'} {bad}: under data>1 the CPU "
            "GSPMD partitioner inserts a spurious data-axis all-reduce "
            "around the rope/elementwise ops when KV heads divide the data "
            "axis, doubling K — outputs silently diverge from Generator "
            "(ROADMAP: data>1 serving miscompile).  Serve on a flat "
            "(1, n_devices) model-only mesh of the same devices instead "
            "(repro.rl.session.serving_mesh_for does exactly this).")
def make_pool_shardings(mesh: Optional[Mesh], pool_tree, plan, cfg):
    """NamedShardings for StatePool leaves (paged pools + per-slot state).

    The per-leaf derivation lives in :func:`repro.core.hypershard.
    derive_pool`: paged pools replicate over data axes and shard KV heads
    over tp when divisible; MLA latent pools replicate; per-slot dense
    state shards its head/channel dim over tp when divisible.
    """
    if mesh is None:
        return None
    from repro.core.layout import layout_for_mesh
    layout = layout_for_mesh(mesh)
    paths, leaves, treedef = hypershard.tree_paths(pool_tree)
    shardings = [
        hypershard.derive_pool(p, tuple(l.shape), layout, plan,
                               kv_heads=cfg.num_kv_heads)[0]
        .named_sharding(mesh)
        for p, l in zip(paths, leaves)]
    return jax.tree_util.tree_unflatten(treedef, shardings)


def make_paged_serve_step(cfg, mesh: Optional[Mesh], plan, *,
                          block_size: int, pool_tree=None,
                          donate: bool = True,
                          moe_dispatch: str = "gshard",
                          kernels: str = "composed"):
    """Continuous-batching decode step: one token for every seated slot.

    Returns ``step(params, tokens (B,1), positions (B,), pools, tables
    (B,W), slot_mask (B,)) -> (logits, new pools)`` with the pool donated
    (updated in place on device); a config with MoE layers also gets the
    held experts' route counts (n_moe_layers, held) int32.
    ``slot_mask`` marks the seats holding
    RUNNING requests: inactive seats' dummy decode must not advance
    slot-state recurrences.  The seat count B and table width W are fixed
    by the arrays the caller passes (one compilation per distinct shape).
    """

    # the function's name names the XLA module: jit_paged_decode_step
    def paged_decode_step(params, tokens, positions, pools, tables,
                          slot_mask):
        ctx = use_mesh(mesh) if mesh is not None else _null()
        with ctx:
            return M.decode_step_paged(params, tokens, positions, cfg, pools,
                                       tables, block_size=block_size,
                                       slot_mask=slot_mask,
                                       moe_dispatch=moe_dispatch,
                                       kernels=kernels)

    donate_kw = {"donate_argnums": (3,)} if donate else {}
    if mesh is None:
        return jax.jit(paged_decode_step, **donate_kw), {}
    pshapes = jax.eval_shape(lambda: M.init_model(cfg, jax.random.PRNGKey(0)))
    param_sh = hypershard.make_param_shardings(mesh, pshapes, plan)
    pool_sh = make_pool_shardings(mesh, pool_tree, plan, cfg)
    rep = NamedSharding(mesh, P())
    tok_sh = NamedSharding(mesh, P(None, None))
    tab_sh = NamedSharding(mesh, P(None, None))
    logits_sh = NamedSharding(mesh, P(None, None, _vocab_axis(cfg, mesh)))
    jitted = jax.jit(paged_decode_step,
                     in_shardings=(param_sh, tok_sh, rep, pool_sh, tab_sh,
                                   rep),
                     out_shardings=(logits_sh, pool_sh)
                     + ((rep,) if cfg.moe is not None else ()), **donate_kw)
    return jitted, {"params": param_sh, "pools": pool_sh}


def make_paged_prefill_step(cfg, mesh: Optional[Mesh], plan, *,
                            block_size: int, pool_tree=None,
                            donate: bool = True,
                            moe_dispatch: str = "gshard",
                            kernels: str = "composed"):
    """Batched chunked-prefill step: ``(params, tokens (P,C), starts (P,),
    limits (P,), slots (P,), pools, tables (P,W)) -> (last_logits (P,V),
    new pools)``, and for a config with MoE layers the held experts' route
    counts (n_moe_layers, held) int32.

    Every prompt chunk the scheduler admitted this iteration runs in ONE
    compiled call — one kernel launch amortised over all P rows instead
    of a jit dispatch per request.  ``slots`` (traced vector) carries
    each request's decode seat — slot-state mixers (SSD/RG-LRU) carry
    their recurrence in those rows of the pool's per-slot leaves across
    chunks; filler rows are padded to limit 0 / the null slot.  The row
    count P and chunk width C are fixed by the arrays the caller passes
    (one compilation per distinct shape).
    """

    # the function's name names the XLA module: jit_paged_prefill_step
    def paged_prefill_step(params, tokens, starts, limits, slots, pools,
                           tables):
        ctx = use_mesh(mesh) if mesh is not None else _null()
        with ctx:
            return M.prefill_chunk_paged(params, tokens, starts, limits,
                                         slots, cfg, pools, tables,
                                         block_size=block_size,
                                         moe_dispatch=moe_dispatch,
                                         kernels=kernels)

    donate_kw = {"donate_argnums": (5,)} if donate else {}
    if mesh is None:
        return jax.jit(paged_prefill_step, **donate_kw), {}
    pshapes = jax.eval_shape(lambda: M.init_model(cfg, jax.random.PRNGKey(0)))
    param_sh = hypershard.make_param_shardings(mesh, pshapes, plan)
    pool_sh = make_pool_shardings(mesh, pool_tree, plan, cfg)
    rep = NamedSharding(mesh, P())
    tok_sh = NamedSharding(mesh, P(None, None))
    tab_sh = NamedSharding(mesh, P(None, None))
    out0_sh = NamedSharding(mesh, P(None, _vocab_axis(cfg, mesh)))
    jitted = jax.jit(paged_prefill_step,
                     in_shardings=(param_sh, tok_sh, rep, rep, rep, pool_sh,
                                   tab_sh),
                     out_shardings=(out0_sh, pool_sh)
                     + ((rep,) if cfg.moe is not None else ()), **donate_kw)
    return jitted, {"params": param_sh, "pools": pool_sh}


class _null:
    def __enter__(self):
        return None

    def __exit__(self, *a):
        return False


@dataclasses.dataclass
class GenerateConfig:
    max_new_tokens: int = 32
    temperature: float = 0.0            # 0 => greedy
    seed: int = 0


class Generator:
    """Host-side prefill+decode driver."""

    def __init__(self, cfg, params, *, mesh=None, plan=None, max_len=512,
                 window_override=None, moe_dispatch=None, obs=None):
        from repro.obs import Observability
        self.cfg = cfg
        self.params = params
        plan = plan or hypershard.ShardingPlan()
        self.obs = obs if obs is not None else Observability()
        self.moe_dispatch = resolve_moe_dispatch(cfg, moe_dispatch)
        self.prefill_fn, _ = make_prefill_step(cfg, mesh, plan,
                                               moe_dispatch=self.moe_dispatch)
        self.max_len = max_len
        self.window_override = window_override
        self._serve = {}
        self.mesh = mesh
        self.plan = plan

    def _serve_fn(self, batch):
        if batch not in self._serve:
            self._serve[batch], _ = make_serve_step(
                self.cfg, self.mesh, self.plan, batch=batch,
                cache_len=self.max_len, window_override=self.window_override,
                donate=False, moe_dispatch=self.moe_dispatch)
        self.obs.record_compile("dense_serve", (batch, self.max_len))
        return self._serve[batch]

    def generate(self, tokens, gen: GenerateConfig = GenerateConfig()):
        """tokens: (B, S) prompt. Returns (B, S + max_new) tokens."""
        B, S = tokens.shape
        cfg = self.cfg
        # prefill the prompt, then re-seat the prefill cache into a decode
        # cache of max_len (prefill cache covers S positions)
        self.obs.record_compile("dense_prefill", (B, S))
        with self.obs.trace.span("gen.prefill", track="engine",
                                 batch=B, seq=S):
            logits, pcaches = self.prefill_fn(self.params, tokens)
        caches = M.init_caches(cfg, B, self.max_len,
                               window_override=self.window_override)
        caches = _seat(caches, pcaches, S, self.window_override, cfg)
        out = [tokens]
        key = jax.random.PRNGKey(gen.seed)
        last = jnp.argmax(logits[:, -1:, :cfg.vocab_size], axis=-1)
        step_fn = self._serve_fn(B)
        cur = last.astype(jnp.int32)
        out.append(cur)
        for i in range(gen.max_new_tokens - 1):
            pos = jnp.int32(S + i)
            with self.obs.trace.span("gen.decode", track="engine", pos=S + i):
                logits, caches = step_fn(self.params, cur, pos, caches)
            lg = logits[:, -1, :cfg.vocab_size]
            if gen.temperature > 0:
                key, sk = jax.random.split(key)
                cur = jax.random.categorical(sk, lg / gen.temperature)[:, None]
            else:
                cur = jnp.argmax(lg, axis=-1)[:, None]
            cur = cur.astype(jnp.int32)
            out.append(cur)
        return jnp.concatenate(out, axis=1)


def _seat(dcaches, pcaches, S, window_override, cfg):
    """Copy prefill caches into the (larger) decode cache buffers."""
    def seat_leaf(d, p):
        if d.ndim >= 4 and p.ndim == d.ndim:      # (L, B, S, ...) style
            n = min(p.shape[2], d.shape[2])
            return jax.lax.dynamic_update_slice_in_dim(
                d, p[:, :, -n:].astype(d.dtype), 0, axis=2)
        if d.shape == p.shape:
            return p.astype(d.dtype)
        return d
    return jax.tree.map(seat_leaf, dcaches, pcaches)
