"""Public kernel API.

Every op picks an implementation:
  - ``pallas``   : the Pallas TPU kernel (``interpret=True`` on CPU for tests)
  - ``ref``      : the pure-jnp oracle in :mod:`repro.kernels.ref`
  - ``auto``     : pallas on TPU backends, ref elsewhere (the default)

On TPU every op, windowed decode included, runs its Pallas kernel.  On
CPU the composed path runs the oracles; the kernels are checked against
them in interpret mode (``tests/test_kernels.py``,
``tests/test_paged_kernels.py``), compiled for a described v5e chip by
``tests/test_tpu_compile.py``, and run on the chip by ``chip_smoke.py``.
"""
from __future__ import annotations

from typing import Optional

import jax

from repro.kernels import ref

_MODE = "auto"   # overridable for tests / benchmarks


def set_mode(mode: str) -> None:
    global _MODE
    assert mode in ("auto", "ref", "pallas", "pallas_interpret")
    _MODE = mode


def _use_pallas() -> Optional[bool]:
    """Returns None for ref, False for pallas-interpret, True for pallas."""
    if _MODE == "ref":
        return None
    if _MODE == "pallas":
        return True
    if _MODE == "pallas_interpret":
        return False
    return True if jax.default_backend() == "tpu" else None


def flash_attention(q, k, v, *, causal=True, window=None, q_offset=0,
                    scale=None):
    use = _use_pallas()
    if use is None:
        return ref.flash_attention(q, k, v, causal=causal, window=window,
                                   q_offset=q_offset, scale=scale)
    from repro.kernels import flash_attention as fa
    return fa.flash_attention(q, k, v, causal=causal, window=window,
                              q_offset=q_offset, scale=scale,
                              interpret=not use)


def decode_attention(q, k_cache, v_cache, length, *, scale=None, window=None):
    use = _use_pallas()
    if use is None:
        return ref.decode_attention(q, k_cache, v_cache, length, scale=scale,
                                    window=window)
    from repro.kernels import decode_attention as da
    return da.decode_attention(q, k_cache, v_cache, length, scale=scale,
                               window=window, interpret=not use)


def resolve_paged_path(kernels: str) -> str:
    """Resolve the plan-level ``kernels`` toggle to a lowering path.

    ``"fused"``    -> the block-table-walking Pallas kernels (interpret
                      mode off-TPU, so the no-gather property holds on
                      every backend);
    ``"composed"`` -> the historical gather+flash XLA lowering;
    ``"auto"``     -> fused on TPU, composed elsewhere (CPU serving
                      keeps the fast XLA path by default — interpret
                      mode is a correctness fallback, not a fast one).
    """
    assert kernels in ("auto", "fused", "composed"), kernels
    if kernels == "auto":
        return "fused" if jax.default_backend() == "tpu" else "composed"
    return kernels


def paged_decode_attention(q, k_pool, v_pool, block_tables, lengths, *,
                           block_size, window=None, scale=None):
    """Fused paged decode: block table walked in-kernel, no pool gather."""
    if _MODE == "ref":
        return ref.paged_decode_attention(
            q, k_pool, v_pool, block_tables, lengths,
            block_size=block_size, window=window, scale=scale)
    from repro.kernels import paged_decode_attention as pda
    return pda.paged_decode_attention(
        q, k_pool, v_pool, block_tables, lengths, block_size=block_size,
        window=window, scale=scale, interpret=_use_pallas() is not True)


def paged_mla_decode_attention(q_lat, q_rope, ckv_pool, krope_pool,
                               block_tables, lengths, *, block_size, scale):
    """Fused MLA absorbed paged decode over the latent pools."""
    if _MODE == "ref":
        return ref.paged_mla_decode_attention(
            q_lat, q_rope, ckv_pool, krope_pool, block_tables, lengths,
            block_size=block_size, scale=scale)
    from repro.kernels import paged_decode_attention as pda
    return pda.paged_mla_decode_attention(
        q_lat, q_rope, ckv_pool, krope_pool, block_tables, lengths,
        block_size=block_size, scale=scale,
        interpret=_use_pallas() is not True)


def ragged_prefill_attention(q, k_pool, v_pool, block_tables, starts, limits,
                             *, block_size, window=None, scale=None):
    """Fused ragged batched-prefill: (start, limit) consumed in-kernel."""
    if _MODE == "ref":
        return ref.ragged_prefill_attention(
            q, k_pool, v_pool, block_tables, starts, limits,
            block_size=block_size, window=window, scale=scale)
    from repro.kernels import ragged_prefill_attention as rpa
    return rpa.ragged_prefill_attention(
        q, k_pool, v_pool, block_tables, starts, limits,
        block_size=block_size, window=window, scale=scale,
        interpret=_use_pallas() is not True)


def grouped_matmul(x, w, group_sizes):
    use = _use_pallas()
    if use is None:
        return ref.grouped_matmul(x, w, group_sizes)
    from repro.kernels import grouped_matmul as gm
    return gm.grouped_matmul(x, w, group_sizes, interpret=not use)


def ssd_scan(x, dt, A, Bm, Cm, *, chunk=64, init_state=None):
    use = _use_pallas()
    if use is None:
        return ref.ssd_scan(x, dt, A, Bm, Cm, chunk=chunk,
                            init_state=init_state)
    from repro.kernels import ssd_scan as ss
    return ss.ssd_scan(x, dt, A, Bm, Cm, chunk=chunk, init_state=init_state,
                       interpret=not use)


def ssd_decode_step(x, dt, A, Bm, Cm, state):
    return ref.ssd_decode_step(x, dt, A, Bm, Cm, state)


def rglru_scan(x, input_gate, a_gate, log_a, *, init_state=None, c=8.0):
    use = _use_pallas()
    if use is None:
        return ref.rglru_scan(x, input_gate, a_gate, log_a,
                              init_state=init_state, c=c)
    from repro.kernels import rglru_scan as rs
    return rs.rglru_scan(x, input_gate, a_gate, log_a, init_state=init_state,
                         c=c, interpret=not use)


def rglru_decode_step(x, input_gate, a_gate, log_a, state, *, c=8.0):
    return ref.rglru_decode_step(x, input_gate, a_gate, log_a, state, c=c)
