"""Paged decode state: fixed-size HBM blocks + block tables (HyperServe §3.2).

HBM is treated as a managed cache over the supernode's pooled DRAM
(HyperOffload, arXiv 2602.00748): the decode state of every in-flight
request lives behind per-request **block tables** over fixed-size
**blocks** carved out of one pooled allocation — or, for recurrent
mixers, in O(1) dense **slot** rows.  Three pieces:

  - :class:`BlockManager` — pure host-side bookkeeping: a free list,
    per-block reference counts (copy-on-write prefix sharing), admission
    queries, and spill/restore of a request's pages into the shared
    :class:`~repro.core.kvcache.HostArchive` (the cold tier).
  - :class:`StatePool` — the device arrays themselves, one leaf dict per
    (segment, sublayer) whose layout the mixer registry declares
    (:func:`repro.models.mixers.model_state_layout`): **paged** leaves
    ``(L, N_blocks, block, ...)`` indexed through block tables (full
    attention K/V, MLA latents, sliding-window attention), and **slot**
    leaves ``(L, num_slots, ...)`` holding per-request dense recurrent
    state (SSD, RG-LRU) seated in fixed decode seats.  Host-driven page
    extract/insert serves spill/restore; slot extract/insert/zero serves
    seating and eviction.
  - :func:`blocks_for` — tokens -> blocks arithmetic.

Block id 0 is the **null block**: never allocated, the write target for
inactive batch slots, the padding entry of every block table, and the
repoint target for sliding-window blocks freed out of the window.  Reads
through it are always masked (by decode length or window), so its
contents are don't-care.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.kvcache import HostArchive
from repro.models import mixers as MX


class NoFreeBlocks(RuntimeError):
    """Raised when an allocation cannot be satisfied from the free list."""


def blocks_for(num_tokens: int, block_size: int) -> int:
    return -(-num_tokens // block_size)          # ceil div


@dataclasses.dataclass(frozen=True)
class PagedKVConfig:
    block_size: int = 16          # tokens per HBM block
    num_blocks: int = 128         # pool size, including the null block
    max_blocks_per_req: int = 16  # block-table width (static for jit)
    dtype: str = "bfloat16"

    @property
    def max_context(self) -> int:
        return self.block_size * self.max_blocks_per_req


class BlockManager:
    """Free-list allocator with refcounts, CoW forking and host spill."""

    NULL = 0

    def __init__(self, cfg: PagedKVConfig, archive: Optional[HostArchive] = None):
        self.cfg = cfg
        self.archive = archive if archive is not None else HostArchive()
        self._free: List[int] = list(range(cfg.num_blocks - 1, 0, -1))
        self._ref = np.zeros((cfg.num_blocks,), np.int32)
        self._ref[self.NULL] = 1                 # never allocatable
        # CoW accounting (HyperTrace): blocks shared by fork vs pages
        # physically duplicated on a write fault — the sharing win is
        # forked_blocks - cow_faults pages never recomputed nor copied
        self.forked_blocks = 0
        self.cow_faults = 0

    # -- queries -----------------------------------------------------------
    @property
    def num_free(self) -> int:
        return len(self._free)

    @property
    def num_total(self) -> int:
        return self.cfg.num_blocks - 1           # null block excluded

    def occupancy(self) -> float:
        return 1.0 - self.num_free / max(self.num_total, 1)

    def can_alloc(self, n: int) -> bool:
        return n <= self.num_free

    def refcount(self, bid: int) -> int:
        return int(self._ref[bid])

    # -- alloc / free ------------------------------------------------------
    def alloc(self, n: int) -> List[int]:
        if n > self.num_free:
            raise NoFreeBlocks(f"need {n} blocks, have {self.num_free}")
        out = [self._free.pop() for _ in range(n)]
        for b in out:
            assert self._ref[b] == 0, (b, self._ref[b])
            self._ref[b] = 1
        return out

    def free(self, blocks: Sequence[int]) -> None:
        for b in blocks:
            if b == self.NULL:
                continue
            assert self._ref[b] > 0, f"double free of block {b}"
            self._ref[b] -= 1
            if self._ref[b] == 0:
                self._free.append(b)

    # -- copy-on-write -----------------------------------------------------
    def fork(self, table: Sequence[int]) -> List[int]:
        """Share ``table``'s blocks with a new owner (prefix sharing)."""
        for b in table:
            if b != self.NULL:
                self._ref[b] += 1
                self.forked_blocks += 1
        return list(table)

    def is_shared(self, bid: int) -> bool:
        return bid != self.NULL and self._ref[bid] > 1

    def ensure_writable(self, table: List[int], idx: int,
                        copy_page) -> Tuple[List[int], int]:
        """Make ``table[idx]`` exclusively owned before a write.

        If the block is shared, a fresh block is allocated, ``copy_page(src,
        dst)`` is invoked to duplicate its contents, and the table entry is
        repointed (the classic CoW fault).  Returns the (possibly updated)
        table and the writable block id.
        """
        bid = table[idx]
        if not self.is_shared(bid):
            return table, bid
        [new] = self.alloc(1)
        copy_page(bid, new)
        self._ref[bid] -= 1                      # old ref released, >=1 remain
        self.cow_faults += 1
        table = list(table)
        table[idx] = new
        return table, new

    # -- spill / restore (cold tier) ---------------------------------------
    def spill(self, key, table: Sequence[int], extract_pages) -> None:
        """Move a request's page contents to the host archive, free blocks.

        ``extract_pages(bids) -> pytree`` pulls the page contents out of the
        device pool *before* the blocks return to the free list (they may be
        reallocated in the same scheduler step).
        """
        real = [b for b in table if b != self.NULL]
        self.archive.put(key, extract_pages(real))
        self.free(real)

    def restore(self, key, insert_pages) -> List[int]:
        """Re-seat spilled pages into freshly allocated blocks.

        ``insert_pages(pages, bids)`` scatters the archived contents back
        into the device pool.  Raises :class:`NoFreeBlocks` (leaving the
        archive entry intact) when the pool can't fit them yet.
        """
        pages = self.archive.fetch(key, pop=False)
        leaves = jax.tree.leaves(pages)
        # pure-slot models (e.g. SSD-only) have no paged leaves: their
        # "pages" archive entry is structurally empty and restore allocates
        # nothing — the table regrows lazily as decode extends it
        n = leaves[0].shape[1] if leaves else 0
        bids = self.alloc(n)                     # may raise NoFreeBlocks
        self.archive.discard(key)
        insert_pages(pages, bids)
        return bids

    def spilled(self, key) -> bool:
        return key in self.archive

    def stats(self) -> dict:
        """Pool occupancy + CoW accounting snapshot (HyperTrace gauges)."""
        return {
            "num_total": self.num_total,
            "num_free": self.num_free,
            "occupancy": self.occupancy(),
            "shared_blocks": int((self._ref[1:] > 1).sum()),
            "forked_blocks": self.forked_blocks,
            "cow_faults": self.cow_faults,
            "archive_entries": len(self.archive.keys()),
            # per-tier split (HyperMem): host DRAM vs the disk tier the
            # bounded archive spills into; "archive_bytes" stays the total
            "archive_bytes": self.archive.nbytes(),
            "archive_host_bytes": self.archive.nbytes_host(),
            "archive_disk_bytes": self.archive.nbytes_disk(),
        }


class StatePool:
    """The pooled HBM decode-state arrays for every layer of one model.

    The pytree mirrors the model's decode-cache structure — per segment a
    tuple of per-sublayer leaf dicts — with the per-sublayer layout
    declared by the mixer registry:

      - **paged** sublayers (ATTN, MLA, LOCAL_ATTN): leaves
        ``(L, N_blocks, block, ...)`` — the per-request sequence dim is
        replaced by the shared (block, offset) pool that block tables
        index; attention K/V pages are lane-dense, ``(..., KV*hd)``;
      - **slot** sublayers (SSD, RG-LRU): leaves ``(L, num_slots, ...)``
        — O(1) dense recurrent state, one row per decode seat.

    The leading stacked-layer axis is what the model's layer scan
    indexes: paged leaves (attention K/V, MLA latents) are updated in
    place, slot leaves are sliced per layer and written back
    (:meth:`repro.models.mixers.MixerSpec.decode_layer`).  Construction
    resolves the config against the registry
    (:func:`repro.models.mixers.model_state_layout`) — an unregistered
    mixer kind raises a typed ``ServePlanError`` here, before any jit.
    """

    def __init__(self, cfg, pcfg: PagedKVConfig, *, num_slots: int = 1,
                 dtype=None, shardings=None):
        self.cfg = cfg
        self.pcfg = pcfg
        self.num_slots = num_slots
        self.layout = MX.model_state_layout(cfg)
        dt = dtype or jnp.dtype(pcfg.dtype)
        self.state: dict = {}
        for seg in self.layout.segments:
            subs = []
            for spec in seg.specs:
                # shapes only — allocate each leaf ONCE, already stacked
                one = jax.eval_shape(
                    lambda spec=spec: spec.init_state(
                        cfg, num_blocks=pcfg.num_blocks,
                        block_size=pcfg.block_size,
                        num_slots=num_slots, dtype=dt))
                subs.append(jax.tree.map(
                    lambda a: jnp.zeros((seg.repeat,) + a.shape, a.dtype),
                    one))
            self.state[seg.name] = tuple(subs)
        if shardings is not None:
            self.state = jax.tree.map(jax.device_put, self.state, shardings)

    # kept as an alias while callers migrate from the KV-only pool
    @property
    def kv(self):
        return self.state

    @kv.setter
    def kv(self, value):
        self.state = value

    def hbm_bytes(self) -> int:
        return sum(int(a.size) * a.dtype.itemsize
                   for a in jax.tree.leaves(self.state))

    def leaf_counts(self) -> Tuple[int, int]:
        """``(in place, sliced)``: how many leaves the step programs update
        in place and how many they slice per layer and write back."""
        counts = [0, 0]
        for seg in self.layout.segments:
            for j, spec in enumerate(seg.specs):
                n = len(jax.tree.leaves(self.state[seg.name][j]))
                counts[0 if spec.in_place else 1] += n
        return counts[0], counts[1]

    # -- structural helpers ------------------------------------------------
    # Every pool operation below targets one side of the paged/slot split;
    # these two visitors are the single place the segment/sublayer walk
    # (and the split itself) is encoded.
    def _collect(self, want_slot: bool, fn):
        """Structure-preserving gather: ``fn(sub)`` on matching sublayers,
        ``{}`` placeholders elsewhere (so insert can realign)."""
        out = {}
        for seg in self.layout.segments:
            out[seg.name] = tuple(
                fn(self.state[seg.name][j])
                if (spec.state == MX.SLOT) == want_slot else {}
                for j, spec in enumerate(seg.specs))
        return out

    def _rewrite(self, want_slot: bool, fn) -> None:
        """Rewrite matching sublayers in place: ``fn(sub, j, seg_name)``."""
        new = {}
        for seg in self.layout.segments:
            subs = list(self.state[seg.name])
            for j, spec in enumerate(seg.specs):
                if (spec.state == MX.SLOT) == want_slot:
                    subs[j] = fn(subs[j], j, seg.name)
            new[seg.name] = tuple(subs)
        self.state = new

    # -- host-driven page movement (spill / restore / CoW copy) ------------
    def extract_pages(self, bids: Sequence[int]):
        """Gather blocks ``bids`` out of every paged leaf: (L, n, bs, ...).

        Slot sublayers contribute an empty dict (their state does not
        page); the result keeps the segment/sublayer structure so
        :meth:`insert_pages` can realign it.
        """
        idx = jnp.asarray(list(bids), jnp.int32)
        return self._collect(False, lambda sub: jax.tree.map(
            lambda a: a[:, idx], sub))

    def insert_pages(self, pages, bids: Sequence[int]) -> None:
        idx = jnp.asarray(list(bids), jnp.int32)
        self._rewrite(False, lambda sub, j, name: jax.tree.map(
            lambda a, p: a.at[:, idx].set(p.astype(a.dtype)),
            sub, pages[name][j]))

    def copy_page(self, src: int, dst: int) -> None:
        self._rewrite(False, lambda sub, j, name: jax.tree.map(
            lambda a: a.at[:, dst].set(a[:, src]), sub))

    # -- per-slot dense state (seating / eviction) -------------------------
    def extract_slot(self, slot: int):
        """Pull one decode seat's dense state rows: leaf (L, 1, ...)."""
        return self._collect(True, lambda sub: jax.tree.map(
            lambda a: a[:, slot:slot + 1], sub))

    def insert_slot(self, slot: int, values) -> None:
        self._rewrite(True, lambda sub, j, name: jax.tree.map(
            lambda a, v: a.at[:, slot:slot + 1].set(v.astype(a.dtype)),
            sub, values[name][j]))

    def zero_slot(self, slot: int) -> None:
        """Reset one seat's dense state (a newly admitted request must not
        inherit the previous occupant's recurrence)."""
        self._rewrite(True, lambda sub, j, name: jax.tree.map(
            lambda a: a.at[:, slot].set(jnp.zeros_like(a[:, slot])), sub))

    def seat_prefill_caches(self, pcaches, bids: Sequence[int],
                            seq_len: int, row: int = 0) -> None:
        """Scatter a dense prefill cache (one request) into pages.

        ``pcaches`` is the ``M.forward(..., mode="prefill")`` cache pytree
        with leaves (L, B, S, ...); ``row`` selects the request within it.
        Used by the disaggregated path, where a prefill worker produces
        the dense cache and hands it to the decode worker's pool — only
        sound for pure-paged layouts (the runtime guards this).
        """
        bs = self.pcfg.block_size
        n = blocks_for(seq_len, bs)
        assert n <= len(bids), (seq_len, len(bids))
        idx = jnp.asarray(list(bids)[:n], jnp.int32)
        pad = n * bs - seq_len

        def seat(pool, pc):
            src = pc[:, row, :seq_len]                         # (L, S, ...)
            if pad:
                src = jnp.pad(src, ((0, 0), (0, pad))
                              + ((0, 0),) * (src.ndim - 2))
            # (L, S, KV, hd) caches land in lane-dense (..., KV*hd) pages
            src = src.reshape((src.shape[0], n, bs) + pool.shape[3:])
            return pool.at[:, idx].set(src.astype(pool.dtype))

        self._rewrite(False, lambda sub, j, name: jax.tree.map(
            seat, sub, pcaches[name][j]))


# serving callers migrated to StatePool; the old name remains importable
PagedKVPool = StatePool
