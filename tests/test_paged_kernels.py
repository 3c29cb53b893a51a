"""Fused paged-attention Pallas kernels: parity vs the composed oracles,
plus the load-bearing no-gather guarantee.

The kernels run in interpret mode on CPU (same program the TPU pipeline
lowers); every case checks against ``repro.kernels.ref``'s composed
oracle (dense ``pool[block_tables]`` gather + flash/decode attention) —
the exact math the serving engine's composed path uses, so kernel parity
here plus composed-path serve parity elsewhere gives fused-serve parity
by transitivity.  The jaxpr tests then prove the point of the exercise:
the fused decode step contains NO dense pool gather at all.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ServeConfig, get_config
from repro.kernels import ref
from repro.kernels.paged_decode_attention import (paged_decode_attention,
                                                  paged_mla_decode_attention)
from repro.kernels.ragged_prefill_attention import ragged_prefill_attention
from repro.models import model as M
from repro.serve.paged_kv import StatePool

BS, W, N = 4, 6, 32                     # block size, table width, pool blocks


def _pools(key, kv_heads, head_dim):
    # lane-dense pages: every KV head of a page row side by side
    kk, kv = jax.random.split(key)
    k_pool = jax.random.normal(kk, (N, BS, kv_heads * head_dim)) * 0.3
    v_pool = jax.random.normal(kv, (N, BS, kv_heads * head_dim)) * 0.3
    return k_pool, v_pool


def _tables(batch):
    # distinct non-null blocks per row, in scrambled order (the kernel must
    # follow the table, not assume contiguity)
    perm = np.random.RandomState(0).permutation(N - 1)[:batch * W] + 1
    return jnp.asarray(perm.reshape(batch, W), jnp.int32)


@pytest.mark.parametrize("window", [None, 7])
@pytest.mark.parametrize("kv_heads", [2, 4])
def test_decode_parity(window, kv_heads):
    """GQA + MHA, mixed lengths with partial last pages, windowed or not."""
    H, D = 4, 16
    lengths = [10, 3, 24]                # partial, tiny, exactly-full table
    B = len(lengths)
    key = jax.random.PRNGKey(1)
    q = jax.random.normal(key, (B, 1, H, D)) * 0.3
    k_pool, v_pool = _pools(jax.random.PRNGKey(2), kv_heads, D)
    tables = _tables(B)
    lens = jnp.asarray(lengths, jnp.int32)
    got = paged_decode_attention(q, k_pool, v_pool, tables, lens,
                                 block_size=BS, window=window,
                                 interpret=True)
    want = ref.paged_decode_attention(q, k_pool, v_pool, tables, lens,
                                      block_size=BS, window=window)
    assert got.shape == want.shape == (B, 1, H, D)
    assert jnp.max(jnp.abs(got - want)) < 2e-5


@pytest.mark.parametrize("window", [None, 5])
def test_ragged_prefill_parity(window):
    """Mixed starts/limits, partial pages, a filler row outputting zeros."""
    H, KV, D, C = 4, 2, 16, 8
    starts = jnp.asarray([0, 5, 16, 0], jnp.int32)
    limits = jnp.asarray([12, 13, 24, 0], jnp.int32)   # last row = filler
    P = starts.shape[0]
    key = jax.random.PRNGKey(3)
    q = jax.random.normal(key, (P, C, H, D)) * 0.3
    k_pool, v_pool = _pools(jax.random.PRNGKey(4), KV, D)
    tables = _tables(P)
    got = ragged_prefill_attention(q, k_pool, v_pool, tables, starts, limits,
                                   block_size=BS, window=window,
                                   interpret=True)
    want = ref.ragged_prefill_attention(q, k_pool, v_pool, tables, starts,
                                        limits, block_size=BS, window=window)
    assert got.shape == want.shape == (P, C, H, D)
    assert jnp.max(jnp.abs(got - want)) < 2e-5
    # dead rows must come out exactly zero, not garbage softmax
    assert jnp.all(got[3] == 0.0)


def test_ragged_prefill_padded_chunk():
    """A chunk width with no 16-row alignment (C=6) is padded in the
    wrapper; outputs keep the caller's width."""
    H, KV, D, C = 4, 2, 16, 6
    starts = jnp.asarray([0, 6, 18], jnp.int32)
    limits = jnp.asarray([5, 12, 24], jnp.int32)
    q = jax.random.normal(jax.random.PRNGKey(6), (3, C, H, D)) * 0.3
    k_pool, v_pool = _pools(jax.random.PRNGKey(7), KV, D)
    tables = _tables(3)
    got = ragged_prefill_attention(q, k_pool, v_pool, tables, starts, limits,
                                   block_size=BS, interpret=True)
    want = ref.ragged_prefill_attention(q, k_pool, v_pool, tables, starts,
                                        limits, block_size=BS)
    assert got.shape == want.shape == (3, C, H, D)
    assert jnp.max(jnp.abs(got - want)) < 2e-5


def test_mla_decode_parity():
    """Absorbed MLA decode in latent space over the compressed pools."""
    B, H, R, r = 3, 4, 16, 8
    lengths = jnp.asarray([10, 3, 24], jnp.int32)
    key = jax.random.PRNGKey(5)
    ks = jax.random.split(key, 4)
    q_lat = jax.random.normal(ks[0], (B, H, R)) * 0.3
    q_rope = jax.random.normal(ks[1], (B, H, r)) * 0.3
    ckv_pool = jax.random.normal(ks[2], (N, BS, R)) * 0.3
    krope_pool = jax.random.normal(ks[3], (N, BS, r)) * 0.3
    tables = _tables(B)
    scale = (R + r) ** -0.5
    got = paged_mla_decode_attention(q_lat, q_rope, ckv_pool, krope_pool,
                                     tables, lengths, block_size=BS,
                                     scale=scale, interpret=True)
    want = ref.paged_mla_decode_attention(q_lat, q_rope, ckv_pool,
                                          krope_pool, tables, lengths,
                                          block_size=BS, scale=scale)
    assert got.shape == want.shape == (B, H, R)
    assert jnp.max(jnp.abs(got - want)) < 2e-5


# ---------------------------------------------------------------------------
# the acceptance bar: no dense pool[block_tables] gather in the fused step
# ---------------------------------------------------------------------------
def _large_gathers(jaxpr, threshold=4096):
    """All gather outputs >= threshold elements, recursively.

    The threshold separates the dense KV-pool gather (every page of every
    row's table, thousands of elements even at test shapes) from benign
    small gathers (embedding rows for a handful of tokens).
    """
    hits = []

    def walk(jx):
        for eqn in jx.eqns:
            if eqn.primitive.name == "gather":
                shape = tuple(eqn.outvars[0].aval.shape)
                size = int(np.prod(shape)) if shape else 1
                if size >= threshold:
                    hits.append((size, shape))
            for v in eqn.params.values():
                if hasattr(v, "jaxpr"):
                    walk(v.jaxpr)
                elif hasattr(v, "eqns"):
                    walk(v)

    walk(jaxpr.jaxpr)
    return hits


@pytest.fixture(scope="module")
def decode_step_jaxprs():
    cfg = dataclasses.replace(get_config("qwen2-0.5b").reduced(),
                              dtype="float32")
    params = M.init_model(cfg, jax.random.PRNGKey(0))
    scfg = ServeConfig(block_size=4, num_blocks=10, max_blocks_per_req=8,
                       max_slots=2, prefill_chunk=4)
    pool = StatePool(cfg, scfg.paged_config(model_dtype=cfg.dtype),
                     num_slots=2)
    tokens = jnp.zeros((2, 1), jnp.int32)
    positions = jnp.asarray([5, 3], jnp.int32)
    tables = jnp.zeros((2, 8), jnp.int32)

    def trace(kernels):
        return jax.make_jaxpr(
            lambda p, st: M.decode_step_paged(
                p, tokens, positions, cfg, st, tables, block_size=4,
                kernels=kernels))(params, pool.state)

    return trace("fused"), trace("composed")


def test_fused_decode_has_no_pool_gather(decode_step_jaxprs):
    fused, _ = decode_step_jaxprs
    hits = _large_gathers(fused)
    assert not hits, f"fused decode step still gathers the pool: {hits}"


def test_composed_decode_does_gather(decode_step_jaxprs):
    """Sanity for the detector itself: the composed path MUST show the
    dense pool gather, or the no-gather assertion above is vacuous."""
    _, composed = decode_step_jaxprs
    assert _large_gathers(composed), \
        "detector found no pool gather in the composed path — threshold bug?"
