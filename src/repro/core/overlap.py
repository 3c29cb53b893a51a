"""Intra-sub-model MPMD: chunked collective/compute overlap (paper Fig. 4a).

Ascend exposes separately schedulable AICube/AIVector cores; the TPU-native
equivalent of the paper's "core-level concurrency" is decomposing a
collective into per-chunk ``lax.ppermute`` steps interleaved with partial
compute inside ``shard_map`` so the ICI transfer of chunk *i+1* hides
behind the matmul of chunk *i*.  This is what lifts MoE communication
masking from ~60% to ~90% (paper §3.3).

Also home of the beyond-paper **ragged MoE dispatch** (sort + grouped
matmul), the optimized alternative to the GShard one-hot einsum baseline.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P



# ---------------------------------------------------------------------------
# collective matmul: all-gather overlapped with compute (Wang et al. style)
# ---------------------------------------------------------------------------
def collective_matmul_allgather(x, w, *, axis_name: str):
    """Computes full_x @ w where x is sharded on dim0 over ``axis_name``.

    Instead of all-gather(x) -> matmul (exposed comm), each step matmuls
    the resident shard while ppermuting the next shard in — the canonical
    TPU overlap idiom.  Must be called inside shard_map.
    x: (S_local, D), w: (D, F) (replicated over axis_name).
    Returns (S_local * n, F) — the full product, identically on each shard.
    """
    n = jax.lax.psum(1, axis_name)
    idx = jax.lax.axis_index(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]

    def step(carry, i):
        blk, _ = carry
        part = blk @ w                          # compute current chunk
        nxt = jax.lax.ppermute(blk, axis_name, perm)   # overlap: fetch next
        src = (idx - i) % n                     # who produced this chunk
        return (nxt, None), (src, part)

    (_, _), (srcs, parts) = jax.lax.scan(step, (x, None), jnp.arange(n))
    # reorder chunks into global order
    order = jnp.argsort(srcs)
    parts = jnp.take(parts, order, axis=0)      # (n, S_local, F)
    return parts.reshape(n * x.shape[0], w.shape[1])


def overlap_efficiency(compute_s: float, comm_s: float, chunks: int,
                       *, masking_floor: float = 0.0) -> float:
    """Analytical masking ratio of the chunked schedule.

    With the monolithic schedule, comm is fully exposed (masking ratio =
    ``masking_floor``, ~0.6 in the paper's baseline from coarse-grained
    double buffering).  With ``chunks`` chunks, every chunk's transfer
    overlaps the previous chunk's compute; exposed time is one chunk of
    whichever resource dominates.
    """
    if comm_s <= 0:
        return 1.0
    per_comp, per_comm = compute_s / chunks, comm_s / chunks
    exposed = per_comm + max(0.0, comm_s - per_comm - compute_s + per_comp)
    exposed = min(exposed, comm_s)
    masked = 1.0 - exposed / comm_s
    return max(masked, masking_floor)


# ---------------------------------------------------------------------------
# ragged (sort-based) MoE dispatch — beyond-paper optimized path
# ---------------------------------------------------------------------------
def ragged_moe_apply(p, xf, local, gate_vals, cfg):
    """Per-shard sort-based expert application (no capacity one-hot).

    xf: (T, D); local: (T, k) each route's held expert in ``[0, held)``,
    or ``held`` for a route this device does not compute
    (:func:`repro.models.moe.moe_forward`); gate_vals: (T, k).  Computes
    the held experts' routed sum via sort -> ragged grouped matmul ->
    unsort: the other routes sort past the held groups, which
    ``group_sizes`` does not cover, so the kernel skips their rows.  Under
    shard_map with experts sharded this composes with an all-to-all;
    under plain pjit it is a dense-semantics fallback that XLA partitions.
    """
    from repro.kernels import ops
    T, D = xf.shape
    held, k = cfg.moe.held, local.shape[-1]

    flat_expert = local.reshape(-1)                     # (T*k,)
    flat_tok = jnp.repeat(jnp.arange(T), k)
    order = jnp.argsort(flat_expert)
    sorted_tok = flat_tok[order]
    xs = xf[sorted_tok]                                 # (T*k, D)

    group_sizes = jnp.bincount(flat_expert, length=held + 1)[:held].astype(
        jnp.int32)

    h = ops.grouped_matmul(xs, p["w_gate"], group_sizes)
    h = jax.nn.silu(h) * ops.grouped_matmul(xs, p["w_up"], group_sizes)
    out = ops.grouped_matmul(h, p["w_down"], group_sizes)   # (T*k, D)

    gates = jnp.where(flat_expert < held, gate_vals.reshape(-1), 0)
    gates = gates[order].astype(out.dtype)
    y = jnp.zeros((T, D), out.dtype).at[sorted_tok].add(out * gates[:, None])
    return y


# ---------------------------------------------------------------------------
# expert-parallel MoE via explicit chunked all-to-all (shard_map)
# ---------------------------------------------------------------------------
def ep_moe_shardmap(p, x, cfg, mesh: Mesh, *, ep_axis: str = "model",
                    chunks: int = 4):
    """Expert-parallel MoE with explicit a2a, chunked for overlap.

    x: (B, S, D) sharded over dp on B; expert weights sharded over
    ``ep_axis``.  Each shard routes its tokens, exchanges token blocks with
    an all-to-all, runs its resident experts, and a2a's results back.
    Chunking the a2a lets transfer k+1 overlap expert-matmul k (paper's
    90% masking mechanism, explicit).
    """
    from repro.models.moe import _refuse_share, gate, router_probs
    mo = cfg.moe
    _refuse_share(mo, "ep_shardmap")
    E = mo.num_experts
    n_ep = mesh.shape[ep_axis]
    e_local = E // n_ep

    def local_fn(px, xx):
        B, S, D = xx.shape
        T = B * S
        xf = xx.reshape(T, D)
        probs, _ = router_probs(px, xf, cfg)
        gate_vals, idx = gate(probs, mo)

        # capacity per (src shard, dst shard): fixed so a2a is static-shaped
        cap = max(1, int(T * mo.top_k / E * mo.capacity_factor) * e_local)
        flat_e = idx.reshape(-1)
        flat_t = jnp.repeat(jnp.arange(T), mo.top_k)
        flat_g = gate_vals.reshape(-1)
        dst = flat_e // e_local                          # target shard
        order = jnp.argsort(dst)
        dst_s, tok_s, e_s, g_s = dst[order], flat_t[order], flat_e[order], flat_g[order]
        # position within destination bucket
        onehot = jax.nn.one_hot(dst_s, n_ep, dtype=jnp.int32)
        pos = jnp.cumsum(onehot, axis=0) - onehot
        pos = (pos * onehot).sum(-1)
        keep = pos < cap
        slot = dst_s * cap + jnp.where(keep, pos, cap - 1)

        send_x = jnp.zeros((n_ep * cap, D), xx.dtype)
        send_e = jnp.full((n_ep * cap,), -1, jnp.int32)
        send_t = jnp.zeros((n_ep * cap,), jnp.int32)
        send_g = jnp.zeros((n_ep * cap,), jnp.float32)
        send_x = send_x.at[slot].set(jnp.where(keep[:, None], xf[tok_s], 0))
        send_e = send_e.at[slot].set(jnp.where(keep, e_s, -1))
        send_t = send_t.at[slot].set(jnp.where(keep, tok_s, 0))
        send_g = send_g.at[slot].set(jnp.where(keep, g_s, 0.0))

        # all-to-all: (n_ep, cap, ...) exchange
        def a2a(t):
            return jax.lax.all_to_all(t.reshape(n_ep, cap, *t.shape[1:]),
                                      ep_axis, 0, 0, tiled=False)
        rx = a2a(send_x).reshape(n_ep * cap, D)
        re = a2a(send_e.astype(jnp.float32)).reshape(-1).astype(jnp.int32)
        rg = a2a(send_g).reshape(-1)

        shard = jax.lax.axis_index(ep_axis)
        e_rel = jnp.where(re >= 0, re - shard * e_local, 0)
        valid = re >= 0
        # resident expert shards arrive pre-sliced via in_specs
        w_g, w_u, w_d = px["w_gate"], px["w_up"], px["w_down"]
        sel = jax.nn.one_hot(e_rel, e_local, dtype=rx.dtype) * valid[:, None]
        wg = jnp.einsum("te,edf->tdf", sel, w_g)
        wu = jnp.einsum("te,edf->tdf", sel, w_u)
        wd = jnp.einsum("te,efd->tfd", sel, w_d)
        h = jax.nn.silu(jnp.einsum("td,tdf->tf", rx, wg))
        h = h * jnp.einsum("td,tdf->tf", rx, wu)
        yo = jnp.einsum("tf,tfd->td", h, wd) * rg[:, None].astype(rx.dtype)

        # return to source shards
        ys = a2a(yo.reshape(-1, D)).reshape(n_ep * cap, D)
        y = jnp.zeros((T, D), xx.dtype).at[send_t].add(
            jnp.where(send_e[:, None] >= 0, ys, 0))
        return y.reshape(B, S, D)

    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    pspec = {k: (P(ep_axis, None, None) if k in ("w_gate", "w_up", "w_down")
                 else P()) for k in p}
    psub = {k: p[k] for k in pspec}
    return jax.shard_map(local_fn, mesh=mesh,
                     in_specs=(pspec, P(dp, None, None)),
                     out_specs=P(dp, None, None),
                     check_vma=False)(psub, x)


# ---------------------------------------------------------------------------
# data-local MoE: FSDP-gathered experts, zero token movement (beyond-paper)
# ---------------------------------------------------------------------------
def moe_dp_local(p, x3, idx3, gates3, cfg, mesh, *, tp_axis: str = "model"):
    """Compute routed experts locally on each token shard.

    Instead of moving TOKENS to expert shards (EP all-to-all, or the GShard
    dispatch einsum + its combine all-reduce), move WEIGHTS: expert weights
    are stored sharded (E over pod+data, F over model) and all-gathered per
    layer; every shard runs a local sort + ragged grouped matmul over its
    own token slice.  Wire cost = one weight gather per direction
    (batch-independent) vs dispatch traffic proportional to tokens*k*d — a
    multi-x win for the assigned MoE configs at train_4k (EXPERIMENTS.md
    §Perf).  Perfectly load-balanced, no capacity drops.

    x3: (B, S, D), idx3/gates3: (B, S, k) — batch sharded over dp, seq over
    the model axis (the residual stream's native layout; flattening happens
    INSIDE each shard, because the flattened global layout is interleaved
    and any boundary reshape forces an SPMD full-rematerialisation).
    """
    from repro.kernels import ops
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    e_axes = (dp if cfg.moe.num_experts % _ax_prod(mesh, dp) == 0
              else dp[-1:])                    # E must divide the shard count
    tok_axes = dp + ((tp_axis,) if tp_axis in mesh.axis_names else ())
    has_tp = tp_axis in mesh.axis_names

    def local_fn(wg, wu, wd, xl, il, gl):
        # gather the full expert stack once per layer (AD turns this into
        # the reduce-scatter of the weight grads on the way back)
        wg = jax.lax.all_gather(wg, e_axes, axis=0, tiled=True)
        wu = jax.lax.all_gather(wu, e_axes, axis=0, tiled=True)
        wd = jax.lax.all_gather(wd, e_axes, axis=0, tiled=True)
        if has_tp:
            wg = jax.lax.all_gather(wg, tp_axis, axis=2, tiled=True)
            wu = jax.lax.all_gather(wu, tp_axis, axis=2, tiled=True)
            wd = jax.lax.all_gather(wd, tp_axis, axis=1, tiled=True)

        Bl, Sl, D = xl.shape
        T = Bl * Sl
        xf = xl.reshape(T, D)
        k = il.shape[-1]
        E = wg.shape[0]
        # local GShard-style capacity dispatch: identical drop semantics,
        # but entirely shard-local — no dispatch all-to-all, no combine
        # all-reduce.  One-hot einsums cost ~30% extra flops vs ideal
        # grouped matmul; the Pallas grouped_matmul kernel replaces them
        # on real TPUs (sort+ragged), the einsum form is what the CPU
        # dry-run lowers because its cost accounting is faithful.
        il2 = il.reshape(T, k)
        gl2 = gl.reshape(T, k).astype(jnp.float32)
        G = 512 if T % 512 == 0 else T
        Gn = T // G
        C = max(1, int(G * k / E * cfg.moe.capacity_factor))
        idx_g = il2.reshape(Gn, G, k)
        gates_g = gl2.reshape(Gn, G, k)
        x_g = xf.reshape(Gn, G, D)
        counts = jnp.zeros((Gn, E), jnp.int32)
        dispatch = jnp.zeros((Gn, G, E, C), xf.dtype)
        combine = jnp.zeros((Gn, G, E, C), xf.dtype)
        for j in range(k):
            oh = jax.nn.one_hot(idx_g[:, :, j], E, dtype=jnp.int32)
            pos = counts[:, None, :] + jnp.cumsum(oh, axis=1) - oh
            counts = counts + oh.sum(axis=1)
            keep = (pos < C) & (oh > 0)
            pos_oh = jax.nn.one_hot(jnp.where(keep, pos, C), C, dtype=xf.dtype)
            d_j = pos_oh * keep.astype(xf.dtype)[..., None]
            dispatch = dispatch + d_j
            combine = combine + d_j * gates_g[:, :, j][..., None, None].astype(xf.dtype)
        expert_in = jnp.einsum("gsec,gsd->egcd", dispatch, x_g)
        h = jax.nn.silu(jnp.einsum("egcd,edf->egcf", expert_in, wg))
        h = h * jnp.einsum("egcd,edf->egcf", expert_in, wu)
        expert_out = jnp.einsum("egcf,efd->egcd", h, wd)
        y = jnp.einsum("egcd,gsec->gsd", expert_out, combine)
        return y.reshape(Bl, Sl, D)

    e_entry = e_axes if len(e_axes) > 1 else e_axes[0]
    dp_entry = dp if len(dp) > 1 else (dp[0] if dp else None)
    wspec_up = P(e_entry, None, tp_axis if has_tp else None)
    wspec_dn = P(e_entry, tp_axis if has_tp else None, None)
    tok = P(dp_entry, tp_axis if has_tp else None, None)
    return jax.shard_map(local_fn, mesh=mesh,
                     in_specs=(wspec_up, wspec_up, wspec_dn, tok, tok, tok),
                     out_specs=tok,
                     check_vma=False)(
        p["w_gate"], p["w_up"], p["w_down"], x3, idx3, gates3)


def _ax_prod(mesh, axes):
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n
