"""HyperOffload for serving: hierarchical KV-cache pool (paper §3.2).

The paper's inference claim (71K -> 123K tokens at equal latency) comes
from treating HBM as a cache over the supernode's pooled DRAM.  TPU-native
adaptation: the cache is split into

  - a **hot window** of the most recent ``hot_window`` tokens, resident in
    HBM and updated in-place every decode step, and
  - a **cold archive** of older blocks, resident in host memory
    (``pinned_host``), attended to in fixed-size blocks that are streamed
    through HBM with flash-decode LSE combining.

The block stream is orchestrated by the host runtime (one jit'd partial-
attention kernel per block batch) because XLA SPMD currently rejects
memory-kind transfers on sliced intermediates inside a traced loop — the
same reason HyperOffload's layer pipeline is unrolled.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)


@dataclasses.dataclass
class KVPoolConfig:
    hot_window: int = 8192          # tokens kept in HBM
    block: int = 2048               # archive streaming granularity
    dtype: str = "bfloat16"


class HostArchive:
    """The supernode's pooled-DRAM tier, as a keyed pytree store.

    One placement policy shared by every cold-KV consumer
    (:class:`KVCachePool`'s block archive, HyperServe's preempted-request
    page spill): arrays ``put`` here move to the backend's host memory kind
    (``pinned_host`` on TPU) — on the mesh's devices, or without a mesh on
    the default device — and come back to device memory on ``fetch``.

    Since HyperMem the archive is **bounded**: storage is a
    :class:`~repro.mem.tiers.TierStack`, so the host tier spills LRU
    entries to disk at ``host_budget_bytes`` and a disk tier full of
    pinned entries is a typed :class:`~repro.mem.tiers.MemCapacityError`
    instead of a silent host OOM under sustained preemption.  Budgets of
    0 keep the pre-HyperMem unbounded behaviour.  Evictions increment
    the exact ``mem.evict.{host,disk}`` counters on ``obs`` when given.
    """

    def __init__(self, mesh: Optional[Mesh] = None, *,
                 host_budget_bytes: int = 0, disk_budget_bytes: int = 0,
                 obs=None):
        from repro.core.compat import device_memory_kind, host_memory_kind
        from repro.mem.tiers import TierStack
        if mesh is not None:
            self._host = NamedSharding(mesh, P(),
                                       memory_kind=host_memory_kind())
            # explicit device-tier destination: a bare device_put is the
            # identity for an array already committed to pinned_host
            self._dev = NamedSharding(mesh, P(),
                                      memory_kind=device_memory_kind())
        else:
            # one device, no mesh: the default device's host memory
            dev = jax.devices()[0]
            self._host = SingleDeviceSharding(dev,
                                              memory_kind=host_memory_kind())
            self._dev = SingleDeviceSharding(dev,
                                             memory_kind=device_memory_kind())
        self._tiers = TierStack(host_budget_bytes, disk_budget_bytes)
        self._obs = obs
        self._seen = dict(self._tiers.counters)

    def _sync_obs(self) -> None:
        """Forward tier eviction deltas to the metrics registry."""
        if self._obs is None:
            return
        for which, metric in (("evict_host", "mem.evict.host"),
                              ("evict_disk", "mem.evict.disk")):
            d = self._tiers.counters[which] - self._seen[which]
            if d:
                self._obs.metrics.counter(metric).inc(d)
                self._seen[which] = self._tiers.counters[which]

    # -- placement ---------------------------------------------------------
    def to_host(self, x):
        return jax.tree.map(lambda a: jax.device_put(a, self._host), x)

    def to_device(self, x, sharding=None):
        dst = sharding if sharding is not None else self._dev
        return jax.tree.map(lambda a: jax.device_put(a, dst), x)

    # -- keyed store (spilled pages, archived blocks) ----------------------
    def put(self, key, value, *, pinned: bool = True) -> None:
        self._tiers.put(key, self.to_host(value), pinned=pinned)
        self._sync_obs()

    def fetch(self, key, *, sharding=None, pop: bool = True):
        # promote=False: a peek (pop=False) is the predictive-restore
        # staging path, which keeps its own device copy — re-seating the
        # entry in the host tier would only churn the LRU under tight
        # budgets (evict counters must reflect real pressure, not peeks)
        value, _ = self._tiers.get(key, pop=pop, promote=False)
        self._sync_obs()
        return self.to_device(value, sharding)

    def __contains__(self, key) -> bool:
        return key in self._tiers

    def discard(self, key) -> None:
        self._tiers.discard(key)

    def keys(self):
        return self._tiers.keys()

    def tier_of(self, key) -> Optional[str]:
        return self._tiers.tier_of(key)

    def host_memory_kinds(self) -> set:
        """Memory kinds of the arrays held in the host tier right now."""
        return {a.sharding.memory_kind
                for v in self._tiers.host_values() for a in jax.tree.leaves(v)}

    @property
    def counters(self) -> dict:
        return self._tiers.counters

    def nbytes(self) -> int:
        return self._tiers.nbytes()

    def nbytes_host(self) -> int:
        from repro.mem.tiers import HOST
        return self._tiers.nbytes(HOST)

    def nbytes_disk(self) -> int:
        from repro.mem.tiers import DISK
        return self._tiers.nbytes(DISK)


@jax.jit
def _partial_attn(q, k, v):
    """Normalised partial attention over one block + its log-sum-exp.

    q: (B, H, D); k,v: (B, S, KV, D).  Returns (o (B,H,Dv), lse (B,H))
    with ``o`` already softmax-normalised WITHIN the block; blocks are
    merged by :func:`combine_partials` with softmax weights
    ``exp(lse_i - LSE_total)`` (standard flash-decode recombination).
    """
    B, H, D = q.shape
    KV = k.shape[2]
    G = H // KV
    qh = q.reshape(B, KV, G, D).astype(jnp.float32) * (D ** -0.5)
    s = jnp.einsum("bkgd,bskd->bkgs", qh, k.astype(jnp.float32))
    m = s.max(axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = p.sum(axis=-1)
    acc = jnp.einsum("bkgs,bskd->bkgd", p, v.astype(jnp.float32))
    o = acc / jnp.maximum(l, 1e-30)[..., None]
    lse = (m[..., 0] + jnp.log(jnp.maximum(l, 1e-30)))
    return o.reshape(B, H, v.shape[-1]), lse.reshape(B, H)


def combine_partials(os_, lses):
    """Flash-decode recombination of per-block normalised outputs."""
    m = functools.reduce(jnp.maximum, lses)
    ws = [jnp.exp(l - m) for l in lses]
    den = sum(ws)
    num = sum(o * w[..., None] for o, w in zip(os_, ws))
    return num / jnp.maximum(den, 1e-30)[..., None]


class KVCachePool:
    """Host-orchestrated hierarchical KV cache for one attention layer."""

    def __init__(self, cfg, batch: int, max_len: int, pool: KVPoolConfig,
                 mesh: Optional[Mesh] = None):
        self.pool = pool
        self.batch = batch
        self.max_len = max_len
        kv, hd = cfg.num_kv_heads, cfg.resolved_head_dim
        dt = jnp.dtype(pool.dtype)
        hot = min(pool.hot_window, max_len)
        self.hot_k = jnp.zeros((batch, hot, kv, hd), dt)
        self.hot_v = jnp.zeros((batch, hot, kv, hd), dt)
        self.archive_k: list = []        # host-resident blocks
        self.archive_v: list = []
        self.length = 0
        self._archive = HostArchive(mesh)

    def _to_host(self, x):
        return self._archive.to_host(x)

    def append(self, k_new, v_new):
        """Append one token (B, 1, KV, hd); spills a full hot window to host."""
        hot = self.hot_k.shape[1]
        slot = self.length % hot
        if self.length and slot == 0:
            # hot window full: archive it in `block`-sized chunks
            for s in range(0, hot, self.pool.block):
                self.archive_k.append(self._to_host(self.hot_k[:, s:s + self.pool.block]))
                self.archive_v.append(self._to_host(self.hot_v[:, s:s + self.pool.block]))
        self.hot_k = jax.lax.dynamic_update_slice_in_dim(self.hot_k, k_new, slot, 1)
        self.hot_v = jax.lax.dynamic_update_slice_in_dim(self.hot_v, v_new, slot, 1)
        self.length += 1

    def attend(self, q):
        """q: (B, H, D) -> (B, H, Dv) attention over hot + archived blocks."""
        hot = self.hot_k.shape[1]
        n_hot = ((self.length - 1) % hot) + 1 if self.length else 0
        accs, lses = [], []
        a, l = _partial_attn(q, self.hot_k[:, :n_hot], self.hot_v[:, :n_hot])
        accs.append(a); lses.append(l)
        for kb, vb in zip(self.archive_k, self.archive_v):
            kd, vd = self._archive.to_device((kb, vb))   # stream to device
            a, l = _partial_attn(q, kd, vd)
            accs.append(a); lses.append(l)
        return combine_partials(accs, lses).astype(q.dtype)

    def hbm_bytes(self) -> int:
        return sum(int(x.size) * x.dtype.itemsize
                   for x in (self.hot_k, self.hot_v))

    def host_bytes(self) -> int:
        return sum(int(b.size) * b.dtype.itemsize for b in self.archive_k) * 2
