"""Pure-jnp oracles for every Pallas kernel in this package.

These are the semantic ground truth: each Pallas kernel's test sweeps
shapes/dtypes and asserts allclose against the function here.  They are
also the lowering path used on non-TPU backends (the CPU dry-run container
lowers these; FLOPs/bytes are equivalent modulo fusion).

All functions are jit-compatible and memory-bounded: attention is computed
blockwise (flash-style running softmax) so that 32K-sequence prefill
lowers without materialising an (S, S) score matrix.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Flash attention (blockwise causal, GQA, optional sliding window)
# ---------------------------------------------------------------------------
def _attn_block_sizes(q_len: int, kv_len: int) -> tuple[int, int]:
    bq = min(512, q_len)
    while q_len % bq:
        bq //= 2
    bk = min(512, kv_len)
    while kv_len % bk:
        bk //= 2
    return max(bq, 1), max(bk, 1)


def flash_chunk(
    q: jax.Array,            # (B, Sq, H, Dk) — queries (kept in input dtype)
    k: jax.Array,            # (B, Sk, KV, Dk)
    v: jax.Array,            # (B, Sk, KV, Dv)
    carry=None,              # (acc, m, l) running stats or None
    *,
    causal: bool = True,
    window: Optional[int] = None,
    q_offset=0,              # absolute position of q[0]
    k_offset=0,              # absolute position of k[0]
    scale: Optional[float] = None,
):
    """Unnormalised flash attention over one KV chunk.

    Returns updated ``(acc (B,Sq,H,Dv) f32, m (B,Sq,H) f32, l (B,Sq,H) f32)``.
    Composable: ring attention feeds successive KV chunks with their
    ``k_offset``; ``flash_attention`` finalises with ``acc / l``.
    Matmuls run in the input dtype with f32 accumulation
    (``preferred_element_type``) — no early f32 upcast of q/k/v.
    """
    B, Sq, H, Dk = q.shape
    Sk, KV, Dv = k.shape[1], k.shape[2], v.shape[3]
    assert H % KV == 0, (H, KV)
    G = H // KV
    scale = scale if scale is not None else Dk ** -0.5

    bq, bk = _attn_block_sizes(Sq, Sk)
    nq, nk = Sq // bq, Sk // bk

    # (B, KV, G, nq, bq, Dk): GQA groups broadcast against one KV head;
    # the KV scan slices a LEADING block axis (nk), so batch/head dims stay
    # intact under SPMD (no dynamic-slice of a sharded dim).
    qh = (q.reshape(B, Sq, KV, G, Dk).transpose(0, 2, 3, 1, 4)
          .reshape(B, KV, G, nq, bq, Dk))
    kb_all = (k.transpose(0, 2, 1, 3)
              .reshape(B, KV, nk, bk, Dk).transpose(2, 0, 1, 3, 4))
    vb_all = (v.transpose(0, 2, 1, 3)
              .reshape(B, KV, nk, bk, Dv).transpose(2, 0, 1, 3, 4))

    q_pos = q_offset + jnp.arange(Sq).reshape(nq, bq)
    k_pos = k_offset + jnp.arange(Sk).reshape(nk, bk)

    if carry is None:
        acc0 = jnp.zeros((B, KV, G, nq, bq, Dv), jnp.float32)
        m0 = jnp.full((B, KV, G, nq, bq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, KV, G, nq, bq), jnp.float32)
    else:
        acc, m, l = carry
        acc0 = (acc.reshape(B, nq, bq, KV, G, Dv)
                .transpose(0, 3, 4, 1, 2, 5).astype(jnp.float32))
        m0 = (m.reshape(B, nq, bq, KV, G)
              .transpose(0, 3, 4, 1, 2).astype(jnp.float32))
        l0 = (l.reshape(B, nq, bq, KV, G)
              .transpose(0, 3, 4, 1, 2).astype(jnp.float32))

    def kv_step(st, inp):
        acc, m, l = st
        kb, vb, kp = inp                                  # (B,KV,bk,D), (bk,)
        s = jnp.einsum("bkgnqd,bksd->bkgnqs", qh, kb,
                       preferred_element_type=jnp.float32) * scale
        mask = jnp.ones((nq, bq, kb.shape[2]), dtype=bool)
        if causal:
            mask &= q_pos[:, :, None] >= kp[None, None, :]
        if window is not None:
            mask &= q_pos[:, :, None] - kp[None, None, :] < window
        s = jnp.where(mask[None, None, None], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bkgnqs,bksd->bkgnqd", p.astype(v.dtype), vb,
            preferred_element_type=jnp.float32)
        return (acc_new, m_new, l_new), None

    (acc, m, l), _ = jax.lax.scan(kv_step, (acc0, m0, l0),
                                  (kb_all, vb_all, k_pos))
    # back to (B, Sq, H, ...) layout
    acc_out = acc.transpose(0, 3, 4, 1, 2, 5).reshape(B, Sq, H, Dv)
    m_out = m.transpose(0, 3, 4, 1, 2).reshape(B, Sq, H)
    l_out = l.transpose(0, 3, 4, 1, 2).reshape(B, Sq, H)
    return acc_out, m_out, l_out


def flash_finalize(acc, l, dtype):
    return (acc / jnp.maximum(l, 1e-30)[..., None]).astype(dtype)


def flash_attention(
    q: jax.Array,            # (B, Sq, H, Dk)
    k: jax.Array,            # (B, Sk, KV, Dk)
    v: jax.Array,            # (B, Sk, KV, Dv)
    *,
    causal: bool = True,
    window: Optional[int] = None,   # sliding window size (None => full)
    q_offset: int = 0,              # absolute position of q[0] (prefill chunks)
    scale: Optional[float] = None,
) -> jax.Array:
    """Blockwise (flash) attention oracle. Returns (B, Sq, H, Dv) in q.dtype."""
    acc, m, l = flash_chunk(q, k, v, causal=causal, window=window,
                            q_offset=q_offset, scale=scale)
    return flash_finalize(acc, l, q.dtype)


def decode_attention(
    q: jax.Array,            # (B, 1, H, Dk)
    k_cache: jax.Array,      # (B, S, KV, Dk)
    v_cache: jax.Array,      # (B, S, KV, Dv)
    length: jax.Array,       # (B,) valid cache entries (absolute positions)
    *,
    scale: Optional[float] = None,
    window: Optional[int] = None,   # sliding window: keys < length-window masked
) -> jax.Array:
    """Single-token decode attention oracle. Returns (B, 1, H, Dv)."""
    B, _, H, Dk = q.shape
    S, KV = k_cache.shape[1], k_cache.shape[2]
    G = H // KV
    scale = scale if scale is not None else Dk ** -0.5
    qh = q.reshape(B, KV, G, Dk).astype(jnp.float32) * scale
    s = jnp.einsum("bkgd,bskd->bkgs", qh, k_cache.astype(jnp.float32))
    mask = jnp.arange(S)[None, :] < length[:, None]            # (B, S)
    if window is not None:
        # cache rows indexed by absolute position (paged gather): only the
        # last `window` positions before the query are in the window
        mask &= jnp.arange(S)[None, :] >= length[:, None] - window
    s = jnp.where(mask[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgs,bskd->bkgd", p, v_cache.astype(jnp.float32))
    return out.reshape(B, 1, H, v_cache.shape[-1]).astype(q.dtype)


# ---------------------------------------------------------------------------
# Paged attention oracles (gather + dense): ground truth for the fused
# block-table-walking kernels in paged_decode_attention.py /
# ragged_prefill_attention.py.  Deliberately written as the composed
# lowering — pool[block_tables] gather then the dense oracle above — so
# fused-vs-composed parity is provable by construction.  Pools are
# lane-dense: a page row holds every KV head side by side, (bs, KV*D).
# ---------------------------------------------------------------------------
def paged_decode_attention(
    q: jax.Array,             # (B, 1, H, Dk)
    k_pool: jax.Array,        # (N_blocks, block_size, KV*Dk)
    v_pool: jax.Array,        # (N_blocks, block_size, KV*Dv)
    block_tables: jax.Array,  # (B, W) int32
    lengths: jax.Array,       # (B,) int32
    *,
    block_size: int,
    window: Optional[int] = None,
    scale: Optional[float] = None,
) -> jax.Array:
    """Paged decode oracle. Returns (B, 1, H, Dv) in q.dtype."""
    B, W = block_tables.shape
    Dk = q.shape[-1]
    KV = k_pool.shape[2] // Dk
    Dv = v_pool.shape[2] // KV
    k_seq = k_pool[block_tables].reshape(B, W * block_size, KV, Dk)
    v_seq = v_pool[block_tables].reshape(B, W * block_size, KV, Dv)
    return decode_attention(q, k_seq, v_seq, lengths, scale=scale,
                            window=window)


def ragged_prefill_attention(
    q: jax.Array,             # (P, C, H, Dk)
    k_pool: jax.Array,        # (N_blocks, block_size, KV*Dk)
    v_pool: jax.Array,        # (N_blocks, block_size, KV*Dv)
    block_tables: jax.Array,  # (P, W) int32
    starts: jax.Array,        # (P,) int32
    limits: jax.Array,        # (P,) int32; 0 = filler row
    *,
    block_size: int,
    window: Optional[int] = None,
    scale: Optional[float] = None,
) -> jax.Array:
    """Ragged chunked-prefill oracle. Returns (P, C, H, Dv) in q.dtype.

    Filler rows (``limit == 0``) return zeros — their outputs are
    discarded upstream, and the fused kernel skips them entirely.
    """
    P, W = block_tables.shape
    C, Dk = q.shape[1], q.shape[-1]
    KV = k_pool.shape[2] // Dk
    Dv = v_pool.shape[2] // KV
    k_seq = k_pool[block_tables].reshape(P, W * block_size, KV, Dk)
    v_seq = v_pool[block_tables].reshape(P, W * block_size, KV, Dv)

    def one(q_r, k_r, v_r, off):
        return flash_attention(q_r[None], k_r[None], v_r[None], causal=True,
                               q_offset=off, window=window, scale=scale)[0]

    out = jax.vmap(one)(q, k_seq, v_seq, starts.astype(jnp.int32))
    live = (limits > 0)[:, None, None, None]
    return jnp.where(live, out, jnp.zeros_like(out))


def paged_mla_decode_attention(
    q_lat: jax.Array,          # (B, H, R) absorbed nope queries
    q_rope: jax.Array,         # (B, H, r) rope queries
    ckv_pool: jax.Array,       # (N_blocks, block_size, R)
    krope_pool: jax.Array,     # (N_blocks, block_size, r)
    block_tables: jax.Array,   # (B, W) int32
    lengths: jax.Array,        # (B,) int32
    *,
    block_size: int,
    scale: float,
) -> jax.Array:
    """MLA absorbed paged decode oracle. Returns (B, H, R) f32."""
    B, W = block_tables.shape
    S = W * block_size
    R, r = ckv_pool.shape[-1], krope_pool.shape[-1]
    ckv = ckv_pool[block_tables].reshape(B, S, R).astype(jnp.float32)
    kr = krope_pool[block_tables].reshape(B, S, r).astype(jnp.float32)
    s = (jnp.einsum("bhr,bsr->bhs", q_lat.astype(jnp.float32), ckv)
         + jnp.einsum("bhr,bsr->bhs", q_rope.astype(jnp.float32), kr)) * scale
    mask = jnp.arange(S)[None, None, :] < lengths[:, None, None]
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhs,bsr->bhr", p, ckv)


# ---------------------------------------------------------------------------
# Grouped (expert) matmul: ragged tokens -> per-expert matmul
# ---------------------------------------------------------------------------
def grouped_matmul(
    x: jax.Array,            # (T, D) tokens sorted by expert
    w: jax.Array,            # (E, D, F)
    group_sizes: jax.Array,  # (E,) int32, sum <= T
) -> jax.Array:
    """Ragged grouped matmul oracle: out[t] = x[t] @ w[expert_of(t)], and
    0 for the rows past every group (as the kernel leaves them)."""
    T, D = x.shape
    E, _, F = w.shape
    bounds = jnp.cumsum(group_sizes)
    rows = jnp.arange(T)
    expert_of = jnp.searchsorted(bounds, rows, side="right")
    wt = w[jnp.minimum(expert_of, E - 1)]                       # (T, D, F)
    out = jnp.einsum("td,tdf->tf", x.astype(jnp.float32),
                     wt.astype(jnp.float32))
    return jnp.where((rows < bounds[-1])[:, None], out, 0).astype(x.dtype)


# ---------------------------------------------------------------------------
# Mamba-2 SSD (state-space dual) chunked scan
# ---------------------------------------------------------------------------
def _segsum(a: jax.Array) -> jax.Array:
    """Stable segment-sum: out[..., i, j] = sum_{j<k<=i} a[..., k] (NEG_INF for j>i)."""
    Q = a.shape[-1]
    cs = jnp.cumsum(a, axis=-1)
    diff = cs[..., :, None] - cs[..., None, :]
    mask = jnp.tril(jnp.ones((Q, Q), dtype=bool), 0)
    return jnp.where(mask, diff, NEG_INF)


def ssd_scan(
    x: jax.Array,            # (B, S, H, P)  inputs per head
    dt: jax.Array,           # (B, S, H)     softplus'd step sizes (>0)
    A: jax.Array,            # (H,)          negative decay rates (A < 0)
    Bm: jax.Array,           # (B, S, N)     input matrix (shared across heads)
    Cm: jax.Array,           # (B, S, N)     output matrix (shared across heads)
    *,
    chunk: int = 64,
    init_state: Optional[jax.Array] = None,   # (B, H, P, N)
) -> tuple[jax.Array, jax.Array]:
    """Chunked SSD oracle. Returns (y (B,S,H,P), final_state (B,H,P,N))."""
    Bb, S, H, P = x.shape
    N = Bm.shape[-1]
    assert S % chunk == 0, (S, chunk)
    nc = S // chunk
    f32 = jnp.float32

    xc = x.reshape(Bb, nc, chunk, H, P).astype(f32)
    dtc = dt.reshape(Bb, nc, chunk, H).astype(f32)
    Bc = Bm.reshape(Bb, nc, chunk, N).astype(f32)
    Cc = Cm.reshape(Bb, nc, chunk, N).astype(f32)
    dA = dtc * A.astype(f32)[None, None, None, :]               # (B, nc, Q, H) log-decay

    # 1. intra-chunk (diagonal blocks): quadratic attention-like form
    L = jnp.exp(_segsum(dA.transpose(0, 1, 3, 2)))              # (B, nc, H, Q, Q)
    scores = jnp.einsum("bcqn,bckn->bcqk", Cc, Bc)              # (B, nc, Q, Q)
    y_diag = jnp.einsum("bchqk,bcqk,bckh,bckhp->bcqhp",
                        L, scores, dtc, xc)

    # 2. chunk states: state contribution of each chunk at its end
    dA_cum = jnp.cumsum(dA, axis=2)                             # (B, nc, Q, H)
    decay_to_end = jnp.exp(dA_cum[:, :, -1:, :] - dA_cum)       # (B, nc, Q, H)
    states = jnp.einsum("bcqn,bcqh,bcqh,bcqhp->bchpn",
                        Bc, dtc, decay_to_end, xc)              # (B, nc, H, P, N)

    # 3. inter-chunk recurrence over chunk states
    chunk_decay = jnp.exp(dA_cum[:, :, -1, :])                  # (B, nc, H)
    s0 = (init_state.astype(f32) if init_state is not None
          else jnp.zeros((Bb, H, P, N), f32))

    def step(s, inp):
        dec, st = inp                                           # (B,H), (B,H,P,N)
        s_new = s * dec[..., None, None] + st
        return s_new, s
    fin, prev_states = jax.lax.scan(
        step, s0, (chunk_decay.transpose(1, 0, 2), states.transpose(1, 0, 2, 3, 4)))
    prev_states = prev_states.transpose(1, 0, 2, 3, 4)          # (B, nc, H, P, N)

    # 4. inter-chunk output: prev chunk state read out by C with decay-in
    decay_in = jnp.exp(dA_cum)                                  # (B, nc, Q, H)
    y_off = jnp.einsum("bcqn,bcqh,bchpn->bcqhp", Cc, decay_in, prev_states)

    y = (y_diag + y_off).reshape(Bb, S, H, P)
    return y.astype(x.dtype), fin.astype(x.dtype)


def ssd_decode_step(
    x: jax.Array,            # (B, H, P)
    dt: jax.Array,           # (B, H)
    A: jax.Array,            # (H,)
    Bm: jax.Array,           # (B, N)
    Cm: jax.Array,           # (B, N)
    state: jax.Array,        # (B, H, P, N)
) -> tuple[jax.Array, jax.Array]:
    """One SSD recurrence step. Returns (y (B,H,P), new_state)."""
    f32 = jnp.float32
    dA = jnp.exp(dt.astype(f32) * A.astype(f32)[None, :])       # (B, H)
    upd = jnp.einsum("bh,bhp,bn->bhpn", dt.astype(f32), x.astype(f32),
                     Bm.astype(f32))
    s_new = state.astype(f32) * dA[..., None, None] + upd
    y = jnp.einsum("bhpn,bn->bhp", s_new, Cm.astype(f32))
    return y.astype(x.dtype), s_new.astype(state.dtype)


# ---------------------------------------------------------------------------
# RG-LRU (RecurrentGemma gated linear recurrence)
# ---------------------------------------------------------------------------
def rglru_scan(
    x: jax.Array,            # (B, S, W) inputs
    input_gate: jax.Array,   # (B, S, W) sigmoid input gate
    a_gate: jax.Array,       # (B, S, W) sigmoid recurrence gate
    log_a: jax.Array,        # (W,) log of recurrent weight a in (0,1): -softplus param
    *,
    init_state: Optional[jax.Array] = None,  # (B, W)
    c: float = 8.0,
) -> tuple[jax.Array, jax.Array]:
    """RG-LRU oracle: h_t = a_t*h_{t-1} + sqrt(1-a_t^2)*(i_t*x_t).

    a_t = exp(c * log_a * r_t), log_a <= 0.  Uses an associative scan over
    the (a, b) linear-recurrence monoid.  Returns (h (B,S,W), final (B,W)).
    """
    f32 = jnp.float32
    log_at = c * log_a.astype(f32)[None, None, :] * a_gate.astype(f32)
    a_t = jnp.exp(log_at)
    # sqrt(1 - a^2) computed stably: sqrt(-expm1(2*log_a_t))
    beta = jnp.sqrt(-jnp.expm1(2.0 * log_at))
    b_t = beta * (input_gate.astype(f32) * x.astype(f32))
    if init_state is not None:
        # fold the initial state into the first step
        b_t = b_t.at[:, 0].add(a_t[:, 0] * init_state.astype(f32))

    def combine(l, r):
        al, bl = l
        ar, br = r
        return al * ar, bl * ar + br

    a_sc, h = jax.lax.associative_scan(combine, (a_t, b_t), axis=1)
    return h.astype(x.dtype), h[:, -1].astype(x.dtype)


def rglru_decode_step(
    x: jax.Array,            # (B, W)
    input_gate: jax.Array,   # (B, W)
    a_gate: jax.Array,       # (B, W)
    log_a: jax.Array,        # (W,)
    state: jax.Array,        # (B, W)
    *,
    c: float = 8.0,
) -> tuple[jax.Array, jax.Array]:
    f32 = jnp.float32
    log_at = c * log_a.astype(f32)[None, :] * a_gate.astype(f32)
    a_t = jnp.exp(log_at)
    beta = jnp.sqrt(-jnp.expm1(2.0 * log_at))
    h = a_t * state.astype(f32) + beta * (input_gate.astype(f32) * x.astype(f32))
    return h.astype(x.dtype), h.astype(state.dtype)
