"""Mixture-of-Experts FFN: shared + routed experts (DeepSeekMoE family).

Baseline dispatch is the GShard/Mesh-TF capacity-based one-hot einsum — the
paper-era standard that lowers cleanly under pjit with experts sharded over
the ``model`` axis (XLA SPMD inserts the all-to-all).  The beyond-paper
sort-based ragged dispatch lives in :mod:`repro.core.overlap` and
:mod:`repro.kernels.grouped_matmul` and is selected with
``dispatch="ragged"``.

An expert share (``MoEConfig.experts_held`` / ``first_expert_held``) holds
only those routed experts' weights: the router scores every expert, and
routes to absent experts add nothing here (expert parallelism without its
exchange).  Only the ragged dispatch computes a share.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.meshctx import constrain
from repro.models.common import dense_init, dtype_of

GROUP_SIZE = 512   # tokens per GShard dispatch group


def init_moe(cfg, key):
    mo = cfg.moe
    d, F = cfg.d_model, mo.d_ff_expert
    dt = dtype_of(cfg)
    ks = jax.random.split(key, 7)
    E = mo.held
    Fs = F * mo.num_shared_experts
    return {
        "router": dense_init(ks[0], d, mo.num_experts, jnp.float32),
        "w_gate": jax.random.normal(ks[1], (E, d, F), jnp.float32).astype(dt) * (2.0 / (d + F)) ** 0.5,
        "w_up": jax.random.normal(ks[2], (E, d, F), jnp.float32).astype(dt) * (2.0 / (d + F)) ** 0.5,
        "w_down": jax.random.normal(ks[3], (E, F, d), jnp.float32).astype(dt) * (2.0 / (d + F)) ** 0.5,
        "ws_gate": dense_init(ks[4], d, Fs, dt),
        "ws_up": dense_init(ks[5], d, Fs, dt),
        "ws_down": dense_init(ks[6], Fs, d, dt),
    }


def _group(T: int) -> int:
    g = min(GROUP_SIZE, T)
    while T % g:
        g //= 2
    return max(g, 1)


def router_probs(p, x, cfg):
    """Router in fp32.  x: (T, D) -> probs (T, E)."""
    logits = x.astype(jnp.float32) @ p["router"]
    return jax.nn.softmax(logits, axis=-1), logits


def gate(probs, mo):
    """Top-k of the router's probabilities: (gate values, expert ids), each
    (T, k); the values renormalised where ``norm_topk_prob`` holds, then
    times ``routed_scaling_factor``."""
    gate_vals, idx = jax.lax.top_k(probs, mo.top_k)
    if mo.norm_topk_prob:
        gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True),
                                            1e-9)
    return gate_vals * mo.routed_scaling_factor, idx


def _refuse_share(mo, dispatch: str) -> None:
    if mo.is_share:
        from repro.api.errors import PlanError
        raise PlanError(
            f"moe dispatch {dispatch!r} cannot compute an expert share "
            f"({mo.held} of {mo.num_experts} routed experts held): only the "
            "ragged dispatch (serving's default) routes past absent experts")


def moe_forward(p, x, cfg, *, dispatch: str = "gshard", valid=None):
    """x: (B, S, D) -> (y (B, S, D), aux_metrics dict).

    ``valid`` (B, S) bool marks real tokens (serving's padding and empty
    seats are not): the ragged dispatch sends the others' routes nowhere.
    ``aux_metrics["routes_held"]`` (held,) int32 counts the valid tokens'
    routes to each held expert."""
    mo = cfg.moe
    B, S, D = x.shape
    T = B * S
    xf = x.reshape(T, D)
    vf = None if valid is None else valid.reshape(T)

    probs, logits = router_probs(p, xf, cfg)
    gate_vals, idx = gate(probs, mo)                            # (T, k)
    # each route's expert as this device holds it, in [0, held), or held
    # for a route to an absent expert or from a token outside ``valid``
    local = idx - mo.first_expert_held
    keep = (local >= 0) & (local < mo.held)
    if vf is not None:
        keep = keep & vf[:, None]
    local = jnp.where(keep, local, mo.held)
    routes_held = jnp.bincount(local.reshape(-1),
                               length=mo.held + 1)[:mo.held].astype(jnp.int32)
    if dispatch != "ragged":
        _refuse_share(mo, dispatch)

    # aux losses (fp32)
    E = mo.num_experts
    me = probs.mean(axis=0)                                     # (E,) mean prob
    ce = jnp.zeros((E,), jnp.float32)
    for j in range(mo.top_k):
        ce = ce + jnp.mean(jax.nn.one_hot(idx[:, j], E, dtype=jnp.float32), axis=0)
    aux_loss = E * jnp.sum(me * ce) / mo.top_k
    z_loss = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)

    if dispatch == "ragged":
        from repro.core.overlap import ragged_moe_apply
        y = ragged_moe_apply(p, xf, local, gate_vals, cfg)
    elif dispatch == "dp_local":
        from repro.core.meshctx import current_mesh
        from repro.core.overlap import moe_dp_local, ragged_moe_apply
        mesh = current_mesh()
        ok = mesh is not None
        if ok:
            dpn = 1
            for a in ("pod", "data"):
                if a in mesh.axis_names:
                    dpn *= mesh.shape[a]
            tpn = mesh.shape.get("model", 1)
            ok = B % dpn == 0 and S % tpn == 0
        if not ok:
            y = ragged_moe_apply(p, xf, local, gate_vals, cfg)
        else:
            y = moe_dp_local(p, x, idx.reshape(B, S, -1),
                             gate_vals.reshape(B, S, -1), cfg,
                             mesh).reshape(T, D)
    else:
        y = _gshard_apply(p, xf, idx, gate_vals, cfg)

    # shared experts: dense SwiGLU over all tokens
    sh = (jax.nn.silu(xf @ p["ws_gate"]) * (xf @ p["ws_up"])) @ p["ws_down"]
    y = y + sh

    metrics = {"moe_aux_loss": aux_loss, "moe_z_loss": z_loss,
               "router_entropy": -jnp.mean(jnp.sum(probs * jnp.log(probs + 1e-9), -1)),
               "routes_held": routes_held}
    return y.reshape(B, S, D), metrics


def _gshard_apply(p, xf, idx, gate_vals, cfg):
    """Capacity-based one-hot dispatch (baseline)."""
    mo = cfg.moe
    T, D = xf.shape
    E, k = mo.num_experts, mo.top_k
    G = _group(T)
    Gn = T // G
    C = max(1, int(G * k / E * mo.capacity_factor))

    idx_g = idx.reshape(Gn, G, k)
    gates_g = gate_vals.reshape(Gn, G, k).astype(jnp.float32)
    x_g = xf.reshape(Gn, G, D)

    # position-in-expert with k-slot priority (slot 0 first)
    counts = jnp.zeros((Gn, E), jnp.int32)
    dispatch = jnp.zeros((Gn, G, E, C), xf.dtype)
    combine = jnp.zeros((Gn, G, E, C), xf.dtype)
    for j in range(k):
        oh = jax.nn.one_hot(idx_g[:, :, j], E, dtype=jnp.int32)      # (Gn,G,E)
        pos = counts[:, None, :] + jnp.cumsum(oh, axis=1) - oh       # pos before self
        counts = counts + oh.sum(axis=1)
        keep = (pos < C) & (oh > 0)
        pos_oh = jax.nn.one_hot(jnp.where(keep, pos, C), C, dtype=xf.dtype)
        d_j = pos_oh * keep.astype(xf.dtype)[..., None]              # (Gn,G,E,C)
        dispatch = dispatch + d_j
        combine = combine + d_j * gates_g[:, :, j][..., None, None].astype(xf.dtype)

    dispatch = constrain(dispatch, ("pod", "data"), None, "model", None)
    expert_in = jnp.einsum("gsec,gsd->egcd", dispatch, x_g)
    expert_in = constrain(expert_in, "model", ("pod", "data"), None, None)

    h = jax.nn.silu(jnp.einsum("egcd,edf->egcf", expert_in, p["w_gate"]))
    h = h * jnp.einsum("egcd,edf->egcf", expert_in, p["w_up"])
    expert_out = jnp.einsum("egcf,efd->egcd", h, p["w_down"])
    expert_out = constrain(expert_out, "model", ("pod", "data"), None, None)

    y = jnp.einsum("egcd,gsec->gsd", expert_out, combine)
    return y.reshape(T, D)
