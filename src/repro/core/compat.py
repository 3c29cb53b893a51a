"""Memory-kind names of the installed backend.

TPU and GPU backends expose ``pinned_host`` for the host tier; a backend
with only ``unpinned_host`` uses that (host placement is then a no-op,
but the plumbing still runs).
"""
from __future__ import annotations

import functools

import jax


@functools.lru_cache(maxsize=None)
def host_memory_kind() -> str:
    """The platform's host-tier memory kind."""
    kinds = {m.kind for m in jax.devices()[0].addressable_memories()}
    if "pinned_host" in kinds or "unpinned_host" not in kinds:
        return "pinned_host"
    return "unpinned_host"


@functools.lru_cache(maxsize=None)
def device_memory_kind() -> str:
    """The accelerator-resident (default) memory kind ("device" on TPU/GPU)."""
    return jax.devices()[0].default_memory().kind


__all__ = ["host_memory_kind", "device_memory_kind"]
