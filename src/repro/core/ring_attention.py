"""Ring attention: exact context-parallel attention via shard_map + ppermute.

Used whenever KV heads don't divide the tensor-parallel axis (GQA with few
KV heads — granite/internvl/qwen/phi4/recurrentgemma): the sequence dim of
q/k/v shards over ``model`` instead, each shard computes its local queries
against the full key space by rotating KV chunks around the ring, with
running log-sum-exp stats (exact flash semantics, absolute-position causal
masks).  Per-step ppermute transfer overlaps the previous chunk's compute —
the same chunked-overlap principle as HyperMPMD's intra-sub-model
concurrency (paper Fig. 4a), applied to attention.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


from repro.kernels import ref


def ring_attention(q, k, v, mesh: Mesh, *, axis: str = "model",
                   causal: bool = True, window: Optional[int] = None,
                   scale: Optional[float] = None):
    """q: (B,S,H,Dk), k/v: (B,S,KV,D*) — S sharded over ``axis``, B over dp.

    Returns (B,S,H,Dv) with the same sharding as q.
    """
    B, S, H, Dk = q.shape
    Dv = v.shape[-1]
    n = mesh.shape[axis]
    S_local = S // n
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    dp_entry = dp if len(dp) > 1 else (dp[0] if dp else None)
    spec = P(dp_entry, axis, None, None)

    def local_fn(ql, kl, vl):
        idx = jax.lax.axis_index(axis)
        q_off = idx * S_local
        perm = [(i, (i + 1) % n) for i in range(n)]

        Bl = ql.shape[0]
        acc = jnp.zeros((Bl, S_local, H, Dv), jnp.float32)
        m = jnp.full((Bl, S_local, H), ref.NEG_INF, jnp.float32)
        l = jnp.zeros((Bl, S_local, H), jnp.float32)

        def step(carry, r):
            acc, m, l, kc, vc = carry
            src = (idx - r) % n                   # origin shard of this chunk
            acc, m, l = ref.flash_chunk(
                ql, kc, vc, (acc, m, l), causal=causal, window=window,
                q_offset=q_off, k_offset=src * S_local, scale=scale)
            kc = jax.lax.ppermute(kc, axis, perm)  # overlaps next compute
            vc = jax.lax.ppermute(vc, axis, perm)
            return (acc, m, l, kc, vc), None

        (acc, m, l, _, _), _ = jax.lax.scan(
            step, (acc, m, l, kl, vl), jnp.arange(n))
        return ref.flash_finalize(acc, l, ql.dtype)

    return jax.shard_map(local_fn, mesh=mesh, in_specs=(spec, spec, spec),
                     out_specs=spec, check_vma=False)(q, k, v)


def ring_applicable(mesh, S: int, axis: str = "model") -> bool:
    if mesh is None or axis not in mesh.axis_names:
        return False
    n = mesh.shape[axis]
    return n > 1 and S % n == 0 and S // n >= 1
