"""HyperShard: declarative parallel-strategy derivation for whole models.

Model code is written single-device (paper Fig. 5b); this module owns the
entire parallel strategy.  A :class:`ShardingPlan` declares the *intent*
(tensor-parallel axis, FSDP axes, offload targets); ``param_strategy``
derives a :class:`~repro.core.layout.ShardStrategy` for every parameter
from its tree path + shape, with automatic divisibility fallback (a dim
that doesn't divide simply stays replicated, mirroring how the paper's
formal derivation rejects invalid strategies).

The same registry derives optimizer-state and KV-cache shardings, so one
declaration covers train + serve.

This module is the derivation *engine*; the user-facing declaration is
:class:`repro.api.HyperPlan`, which lowers to a :class:`ShardingPlan`
(plus an ``OffloadConfig`` / ``ServeConfig``) in one resolution step and
validates eagerly before anything is jitted.
"""
from __future__ import annotations

import dataclasses
import math
import re
from typing import Optional, Tuple

import jax
from jax.sharding import Mesh

from repro.core.layout import Layout, ShardStrategy, layout_for_mesh

Axes = Optional[Tuple[str, ...]]


@dataclasses.dataclass(frozen=True)
class ShardingPlan:
    """Declarative intent, decoupled from model code (paper §3.4)."""
    tp: Axes = ("model",)                  # tensor-parallel mesh axes
    fsdp: Axes = ("pod", "data")           # ZeRO-3-ish parameter sharding axes
    dp: Axes = ("pod", "data")             # batch axes
    # MoE expert-weight placement: "ep" = experts over tp axis (expert
    # parallelism, pairs with the GShard dispatch); "dp" = experts over the
    # fsdp axes + expert-FFN dim over tp (pairs with dispatch="dp_local")
    moe_weights: str = "ep"
    # HyperOffload knobs (paper §3.2)
    params_on_host: bool = False           # weights live in host memory
    opt_state_on_host: bool = False        # optimizer states live in host memory
    activation_offload: bool = False       # remat-offload layer residuals
    # serving
    kv_seq_axes: Axes = None               # shard cache sequence (flash-decode)

    def replace(self, **kw) -> "ShardingPlan":
        return dataclasses.replace(self, **kw)


# ---------------------------------------------------------------------------
# rule table: (regex over tree path, role)
# roles name the *last* dims of the parameter (leading stacked-layer dims are
# automatically replicated).
# ---------------------------------------------------------------------------
_RULES: Tuple[Tuple[str, Tuple[str, ...]], ...] = (
    (r"embed$",                    ("vocab", "residual")),
    (r"unembed$",                  ("vocab", "residual")),
    (r"frontend_proj$",            ("none", "tp")),
    (r"final_norm$|norm1$|norm2$|norm$|kv_norm$", ("none",)),
    (r"(wq|wk|wv|w_dkv|w_x|w_gate|w_up|w_input_gate|w_a_gate|in_proj)$",
                                   ("fsdp", "tp")),
    (r"(wo|w_out|w_down|out_proj)$", ("tp", "fsdp")),
    (r"(w_uk|w_uv)$",              ("fsdp", "tp")),
    (r"(ws_gate|ws_up)$",          ("fsdp", "tp")),
    (r"ws_down$",                  ("tp", "fsdp")),
    (r"(bq|bk|bv)$",               ("tp",)),
    (r"router$",                   ("none", "none")),
    (r"ffn/(w_gate|w_up)$",        ("expert", "fsdp", "none")),   # MoE stacked
    (r"ffn/w_down$",               ("expert", "none", "fsdp")),
    (r"conv_w$",                   ("none", "none")),
    (r"(A_log|D|dt_bias|lambda)$", ("none",)),
)

# MoE expert weights are 3D (E, D, F); they match the generic w_gate rule
# first unless we check the expert rule earlier — order fixed below.
_MOE_RULES = (
    (r"ffn/(w_gate|w_up)$",        ("expert", "fsdp", "none")),
    (r"ffn/w_down$",               ("expert", "none", "fsdp")),
)

_MOE_RULES_DP = (
    (r"ffn/(w_gate|w_up)$",        ("fsdp", "none", "tp")),
    (r"ffn/w_down$",               ("fsdp", "tp", "none")),
)


def _role_axes(role: str, plan: ShardingPlan) -> Axes:
    if role == "tp":
        return plan.tp
    if role == "fsdp":
        return plan.fsdp
    if role == "vocab":
        return plan.tp
    if role == "expert":
        return plan.tp                      # expert parallelism over the TP axis
    if role == "residual":
        return plan.fsdp
    return None


def match_rule(path: str, shape: Tuple[int, ...],
               moe_weights: str = "ep"):
    """The rule table lookup: returns ``(pattern, roles)``.

    ``pattern`` is the regex that fired (``None`` for the replicate-all
    default) — surfaced by ``repro.api`` explain reports so every derived
    spec is traceable to its rule.
    """
    moe_rules = _MOE_RULES_DP if moe_weights == "dp" else _MOE_RULES
    for pat, roles in moe_rules:
        if re.search(pat, path) and len(shape) >= 3:
            return pat, roles
    for pat, roles in _RULES:
        if re.search(pat, path):
            return pat, roles
    return None, ("none",) * len(shape)


def roles_for_path(path: str, shape: Tuple[int, ...],
                   moe_weights: str = "ep") -> Tuple[str, ...]:
    """Match the rule table; returns one role per *trailing* dim."""
    return match_rule(path, shape, moe_weights)[1]


def derive_param(path: str, shape: Tuple[int, ...], layout: Layout,
                 plan: ShardingPlan):
    """Full param derivation: ``(ShardStrategy, rule_pattern, notes)``.

    ``notes`` records every divisibility fallback (axes dropped because the
    dim does not divide) — the raw material for ``repro.api``
    explain/validate.  Plan axes absent from the layout are NOT noted:
    that is the sanctioned multi-pod -> single-pod degradation, policed
    eagerly by ``HyperPlan.validate`` instead.
    """
    rule, roles = match_rule(path, shape, plan.moe_weights)
    # leading dims not covered by the role tuple (stacked layers) replicate
    lead = len(shape) - len(roles)
    if lead < 0:                            # param rank < rule rank (reduced cfg)
        roles = roles[-len(shape):]
        lead = 0
    entries: list = [None] * lead
    notes: list = []
    for i, (dim, role) in enumerate(zip(shape[lead:], roles), start=lead):
        axes = _role_axes(role, plan)
        if not axes:
            entries.append(None)
            continue
        kept = tuple(a for a in axes if a in layout.alias_name)
        requested = kept
        # divisibility fallback: drop axes (innermost first) until it divides
        while kept and dim % math.prod(layout.axis_size(a) for a in kept):
            kept = kept[1:]
        if kept != requested:
            dropped = requested[:len(requested) - len(kept)]
            n = math.prod(layout.axis_size(a) for a in requested)
            notes.append(f"dim{i}[{role}]: {dim} % {n} != 0, dropped "
                         f"{dropped} -> " + (f"{kept}" if kept else "replicated"))
        entries.append(kept if len(kept) > 1 else (kept[0] if kept else None))
    return layout(*entries), rule, tuple(notes)


def param_strategy(path: str, shape: Tuple[int, ...], layout: Layout,
                   plan: ShardingPlan) -> ShardStrategy:
    return derive_param(path, shape, layout, plan)[0]


def tree_paths(tree):
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    paths = ["/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                      for k in kp) for kp, _ in flat]
    leaves = [v for _, v in flat]
    return paths, leaves, treedef


def make_param_shardings(mesh: Mesh, params_shape, plan: ShardingPlan,
                         *, memory_kind: Optional[str] = None):
    """Derive a NamedSharding pytree for a model parameter (shape) tree."""
    layout = layout_for_mesh(mesh)
    paths, leaves, treedef = tree_paths(params_shape)
    from repro.core.compat import host_memory_kind
    mk = memory_kind or (host_memory_kind() if plan.params_on_host else None)
    shardings = [
        param_strategy(p, tuple(l.shape), layout, plan).named_sharding(
            mesh, memory_kind=mk)
        for p, l in zip(paths, leaves)]
    return jax.tree_util.tree_unflatten(treedef, shardings)


def spec_tree(mesh: Mesh, params_shape, plan: ShardingPlan):
    """Like make_param_shardings but returns raw PartitionSpecs."""
    layout = layout_for_mesh(mesh)
    paths, leaves, treedef = tree_paths(params_shape)
    specs = [param_strategy(p, tuple(l.shape), layout, plan).partition_spec()
             for p, l in zip(paths, leaves)]
    return jax.tree_util.tree_unflatten(treedef, specs)


# ---------------------------------------------------------------------------
# KV-cache / decode-state shardings
# ---------------------------------------------------------------------------
def _fit(entry: Tuple[str, ...]):
    return entry if len(entry) > 1 else (entry[0] if entry else None)


def derive_cache(path: str, shape: Tuple[int, ...], layout: Layout,
                 plan: ShardingPlan, *, batch: int):
    """Decode-state derivation: ``(ShardStrategy, branch_note, fallbacks)``.

    Decode-state tensors (dim0 is always the stacked-layer axis):

      k/v           (L, B, S, KV, hd)   attention KV cache
      ckv / krope   (L, B, S, R)        MLA compressed latent cache
      state         (L, B, H, P, N) or (L, B, W)   SSM / RG-LRU state
      conv          (L, B, K-1, C)      causal-conv tail

    Batch shards over dp when divisible; otherwise (long_500k, B=1) the
    sequence dim absorbs the dp axes — context-parallel flash-decode.  KV
    heads shard over tp when divisible, else the sequence dim absorbs tp.

    ``branch_note`` names the branches that fired; ``fallbacks`` records
    every plan axis group that ended up placed NOWHERE (silent
    replication) — the strict-validation signal for explain reports.
    """
    dp = tuple(a for a in (plan.dp or ()) if a in layout.alias_name)
    tp = tuple(a for a in (plan.tp or ()) if a in layout.alias_name)
    ndim = len(shape)
    entries: list = [None] * ndim
    notes: list = []
    fallbacks: list = []

    def size(axes):
        return math.prod(layout.axis_size(a) for a in axes) if axes else 1

    def seq_absorb(seq_axes, absorbing: str):
        """Place the absorbed axes on the seq dim; record silent failure."""
        if not seq_axes:
            return
        if shape[2] % size(seq_axes) == 0:
            entries[2] = _fit(seq_axes)
            notes.append(f"seq/{'+'.join(seq_axes)}")
        else:
            fallbacks.append(
                f"seq cannot absorb {absorbing} ({shape[2]} % "
                f"{size(seq_axes)} != 0) -> {seq_axes} unplaced, replicated")

    leaf = path.rsplit("/", 1)[-1]
    batch_ok = dp and shape[1] % size(dp) == 0
    if batch_ok:
        entries[1] = _fit(dp)
        notes.append("batch/dp")
    elif dp and leaf in ("k", "v", "ckv", "krope"):
        notes.append("batch indivisible, dp falls to seq")

    if leaf in ("k", "v"):
        seq_axes: Tuple[str, ...] = () if batch_ok else dp
        absorbing = "" if batch_ok else "dp"
        if tp and shape[3] % size(tp) == 0:
            entries[3] = _fit(tp)
            notes.append("kv-heads/tp")
        else:
            seq_axes = seq_axes + tp
            if tp:
                notes.append("kv-heads indivisible, tp falls to seq")
                absorbing = (absorbing + "+tp") if absorbing else "tp"
        seq_absorb(seq_axes, absorbing)
    elif leaf in ("ckv", "krope"):
        seq_axes = (() if batch_ok else dp) + tp
        seq_absorb(seq_axes, "tp" if batch_ok else "dp+tp")
    elif leaf == "state":
        # dim2 is heads (SSD) or channels (RG-LRU): shard over tp
        if ndim >= 3 and tp:
            if shape[2] % size(tp) == 0:
                entries[2] = _fit(tp)
                notes.append("state-heads/tp")
            else:
                fallbacks.append(f"state heads {shape[2]} % {size(tp)} != 0 "
                                 f"-> {tp} unplaced, replicated")
    elif leaf == "conv":
        if ndim >= 4 and tp:
            if shape[3] % size(tp) == 0:
                entries[3] = _fit(tp)
                notes.append("conv-channels/tp")
            else:
                fallbacks.append(f"conv channels {shape[3]} % {size(tp)} != 0 "
                                 f"-> {tp} unplaced, replicated")
    if not batch_ok and dp and leaf in ("state", "conv"):
        # constant-size decode state has no seq dim to absorb into
        fallbacks.append(f"batch {shape[1]} % {size(dp)} != 0 -> {dp} "
                         "unplaced, replicated")

    note = "cache[" + leaf + "]: " + (", ".join(notes) if notes
                                      else "replicated")
    return layout(*entries), note, tuple(fallbacks)


def cache_strategy(path: str, shape: Tuple[int, ...], layout: Layout,
                   plan: ShardingPlan, *, batch: int) -> ShardStrategy:
    return derive_cache(path, shape, layout, plan, batch=batch)[0]


def derive_pool(path: str, shape: Tuple[int, ...], layout: Layout,
                plan: ShardingPlan, *, kv_heads: int):
    """Serving StatePool leaf derivation: ``(ShardStrategy, note, fallbacks)``.

    StatePool leaves (dim0 is always the stacked-layer axis):

      k/v           (L, N_blocks, block, KV*hd)   paged attention pool
      ckv / krope   (L, N_blocks, block, R)       paged MLA latent pool
      state         (L, slots, H, P, N) or (L, slots, W)  per-slot SSD/RG-LRU
      conv          (L, slots, K-1, C)            per-slot causal-conv tail

    Paged pools are shared by every request, so they replicate over the
    data axes; the lane-dense KV-head dim shards over tp when the
    ``kv_heads`` it merges divide by tp (the ``cache_strategy`` rule,
    pool edition: a shard holds whole heads).  MLA latents have no head dim
    — they replicate.  Per-slot dense state shards its head/channel dim
    over tp when divisible, mirroring the dense decode-cache derivation.

    ``fallbacks`` records every tp placement that could not bind (the
    strict-validation signal, same contract as :func:`derive_cache`).
    """
    tp = tuple(a for a in (plan.tp or ()) if a in layout.alias_name)
    ndim = len(shape)
    entries: list = [None] * ndim
    notes: list = []
    fallbacks: list = []
    tp_n = math.prod(layout.axis_size(a) for a in tp) if tp else 1
    leaf = path.rsplit("/", 1)[-1]

    def try_tp(dim_idx: int, what: str, count: Optional[int] = None):
        if not tp:
            return
        count = shape[dim_idx] if count is None else count
        if count % tp_n == 0:
            entries[dim_idx] = _fit(tp)
            notes.append(f"{what}/tp")
        else:
            fallbacks.append(f"{what} {count} % {tp_n} != 0 -> "
                             f"{tp} unplaced, replicated")

    if leaf in ("k", "v"):
        try_tp(3, "kv-heads", kv_heads)
    elif leaf in ("ckv", "krope"):
        notes.append("latent pool replicated (rank shared across heads)")
    elif leaf == "state" and ndim >= 3:
        try_tp(2, "state-heads")
    elif leaf == "conv" and ndim >= 4:
        try_tp(3, "conv-channels")

    note = "pool[" + leaf + "]: " + (", ".join(notes) if notes
                                     else "replicated")
    return layout(*entries), note, tuple(fallbacks)


def make_cache_shardings(mesh: Mesh, cache_shape, plan: ShardingPlan, *,
                         batch: int, memory_kind: Optional[str] = None):
    layout = layout_for_mesh(mesh)
    paths, leaves, treedef = tree_paths(cache_shape)
    shardings = [
        cache_strategy(p, tuple(l.shape), layout, plan, batch=batch)
        .named_sharding(mesh, memory_kind=memory_kind)
        for p, l in zip(paths, leaves)]
    return jax.tree_util.tree_unflatten(treedef, shardings)
