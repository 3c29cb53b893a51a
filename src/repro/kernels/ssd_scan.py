"""Pallas TPU Mamba-2 SSD chunked scan.

Grid: (B*H, num_chunks), chunks innermost (sequential on TPU); the
recurrent state (P, N) lives in VMEM scratch across chunk steps.  Each
step computes the intra-chunk quadratic part on the MXU plus the
state-passing term, then updates the state — the TPU-native shape of the
SSD algorithm (chunk matmuls saturate the MXU, the O(S) recurrence is
carried in scratch rather than re-read from HBM).  The per-head decay
rate ``A`` is scalar-prefetched into SMEM; the chunk's cumulative
log-decay is a lower-triangular matmul.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import ref
from repro.kernels.oracle_vjp import oracle_vjp

NEG_INF = -1e30


def _kernel(A_ref, x_ref, dt_ref, B_ref, C_ref, s0_ref, y_ref, fin_ref,
            s_ref, *, Q, nc, H):
    b, c = pl.program_id(0), pl.program_id(1)

    @pl.when(c == 0)
    def _init():
        s_ref[...] = s0_ref[0]

    f32 = jnp.float32
    x = x_ref[0].astype(f32)               # (Q, P)
    dt = dt_ref[0].astype(f32)             # (Q, 1)
    A = A_ref[b % H]                       # scalar (SMEM)
    Bm = B_ref[0].astype(f32)              # (Q, N)
    Cm = C_ref[0].astype(f32)              # (Q, N)
    N = s_ref.shape[1]
    tri = (jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
           >= jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1))
    trif = tri.astype(f32)
    dA = dt * A                            # (Q, 1) log-decay

    def mm(a, b, ca=1, cb=0):
        return jax.lax.dot_general(a, b, (((ca,), (cb,)), ((), ())),
                                   precision=jax.lax.Precision.HIGHEST,
                                   preferred_element_type=f32)

    # cumulative log-decays as triangular / all-ones matmuls (MXU, no scan,
    # and no (1, 1) values, which Mosaic cannot broadcast to a tile)
    cs = mm(trif, dA)                      # (Q, 1) inclusive cumsum
    cs_row = mm(dA, trif, 0, 1)            # (1, Q) the same, as a row
    tot = mm(jnp.ones((Q, Q), f32), dA)    # (Q, 1) whole-chunk sum
    tot_row = mm(dA, jnp.ones((Q, N), f32), 0, 0)    # (1, N) the same
    # L[i,j] = exp(cs_i - cs_j) for j <= i
    L = jnp.exp(jnp.where(tri, cs - cs_row, NEG_INF))

    scores = mm(Cm, Bm, 1, 1)              # (Q, Q)
    xdt = x * dt                           # (Q, P)
    y_diag = mm(L * scores, xdt)

    # inter-chunk: read out previous state, (Q,N)x(P,N)^T -> (Q,P)
    y_off = jnp.exp(cs) * mm(Cm, s_ref[...], 1, 1)

    y_ref[0] = (y_diag + y_off).astype(y_ref.dtype)

    # state update: s = exp(tot) * s + (dt * decay_to_end * x)^T @ B
    upd = mm(xdt * jnp.exp(tot - cs), Bm, 0, 0)      # (P, N)
    s_ref[...] = jnp.exp(tot_row) * s_ref[...] + upd

    @pl.when(c == nc - 1)
    def _finish():
        fin_ref[0] = s_ref[...].astype(fin_ref.dtype)


def ssd_scan(x, dt, A, Bm, Cm, *, chunk: int = 64, init_state=None,
             interpret: bool = False):
    """x: (B,S,H,P); dt: (B,S,H); A: (H,); Bm/Cm: (B,S,N);
    init_state: (B,H,P,N) or None (zeros).

    Returns (y (B,S,H,P), final_state (B,H,P,N)).  Differentiable through
    the oracle (:func:`repro.kernels.oracle_vjp.oracle_vjp`).
    """
    Q = chunk
    while x.shape[1] % Q:
        Q //= 2

    def oracle(x, dt, A, Bm, Cm, s0):
        return ref.ssd_scan(x, dt, A, Bm, Cm, chunk=Q, init_state=s0)
    kernel = functools.partial(_scan, Q=Q, interpret=interpret)
    return oracle_vjp(kernel, oracle)(x, dt, A, Bm, Cm, init_state)


def _scan(x, dt, A, Bm, Cm, init_state, *, Q, interpret):
    B, S, H, P = x.shape
    N = Bm.shape[-1]
    nc = S // Q

    xh = x.transpose(0, 2, 1, 3).reshape(B * H, S, P)
    dth = dt.transpose(0, 2, 1).reshape(B * H, S, 1)
    s0 = (jnp.zeros((B * H, P, N), jnp.float32) if init_state is None
          else init_state.astype(jnp.float32).reshape(B * H, P, N))

    kernel = functools.partial(_kernel, Q=Q, nc=nc, H=H)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,                 # A: one decay rate per head
        grid=(B * H, nc),
        in_specs=[
            pl.BlockSpec((1, Q, P), lambda b, c, A: (b, c, 0)),
            pl.BlockSpec((1, Q, 1), lambda b, c, A: (b, c, 0)),
            # B/C are shared across heads (ngroups=1): index-map b//H
            pl.BlockSpec((1, Q, N), lambda b, c, A: (b // H, c, 0)),
            pl.BlockSpec((1, Q, N), lambda b, c, A: (b // H, c, 0)),
            pl.BlockSpec((1, P, N), lambda b, c, A: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, Q, P), lambda b, c, A: (b, c, 0)),
            pl.BlockSpec((1, P, N), lambda b, c, A: (b, 0, 0)),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
    )
    y, fin = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B * H, S, P), x.dtype),
            jax.ShapeDtypeStruct((B * H, P, N), x.dtype),
        ],
        name="ssd_scan",
        interpret=interpret,
    )(A.astype(jnp.float32), xh, dth, Bm, Cm, s0)
    y = y.reshape(B, H, S, P).transpose(0, 2, 1, 3)
    fin = fin.reshape(B, H, P, N)
    return y, fin
