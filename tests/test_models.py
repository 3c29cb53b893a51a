"""Per-architecture smoke tests (assignment deliverable f).

Each assigned arch instantiates a REDUCED variant (2 layers, d_model<=512,
<=4 experts) and runs one forward + one train step on CPU, asserting
output shapes and absence of NaNs.
"""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

from repro.configs.base import get_config, list_archs
from repro.models import model as M
from repro.optim import adamw as opt_mod
from repro.train import steps as steps_mod

ARCHS = [a for a in list_archs() if a != "llama3-8b"]


def _reduced(arch):
    return get_config(arch).reduced()


@pytest.mark.parametrize("arch", ARCHS)
def test_reduced_config_bounds(arch):
    cfg = _reduced(arch)
    assert cfg.num_layers <= 2
    assert cfg.d_model <= 512
    if cfg.moe is not None:
        assert cfg.moe.num_experts <= 4


@pytest.mark.parametrize("arch", ARCHS)
def test_forward_shapes_and_finite(arch):
    cfg = _reduced(arch)
    params = M.init_model(cfg, jax.random.PRNGKey(0))
    B, S = 2, 32
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab_size)
    pe = None
    if cfg.frontend_dim:
        pe = jax.random.normal(jax.random.PRNGKey(2),
                               (B, cfg.num_prefix_tokens, cfg.frontend_dim),
                               jnp.bfloat16)
    logits, caches, metrics = M.forward(params, toks, cfg, prefix_embeds=pe,
                                        mode="train")
    assert logits.shape == (B, S, cfg.padded_vocab)
    assert not jnp.isnan(logits.astype(jnp.float32)).any()
    assert caches is None


@pytest.mark.parametrize("arch", ARCHS)
def test_train_step(arch):
    cfg = _reduced(arch)
    params = M.init_model(cfg, jax.random.PRNGKey(0))
    opt = opt_mod.init_adamw(params)
    step, _ = steps_mod.make_train_step(
        cfg, None, None, opt_mod.AdamWConfig(), donate=False,
        multimodal=bool(cfg.frontend_dim))
    B, S = 2, 32
    batch = {
        "inputs": jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                                     cfg.vocab_size),
        "targets": jax.random.randint(jax.random.PRNGKey(2), (B, S), 0,
                                      cfg.vocab_size),
        "mask": jnp.ones((B, S), jnp.float32),
    }
    if cfg.frontend_dim:
        batch["prefix_embeds"] = jax.random.normal(
            jax.random.PRNGKey(3), (B, cfg.num_prefix_tokens, cfg.frontend_dim),
            jnp.bfloat16)
    p2, o2, metrics = step(params, opt, batch)
    assert jnp.isfinite(metrics["loss"])
    assert float(metrics["grad_norm"]) > 0
    # params actually changed
    delta = sum(float(jnp.abs(a.astype(jnp.float32)
                              - b.astype(jnp.float32)).sum())
                for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(p2)))
    assert delta > 0


@pytest.mark.parametrize("arch", ARCHS)
def test_prefill_decode_consistency(arch):
    """Teacher-forced forward logits == step-by-step decode logits.

    MoE configs get a no-drop capacity factor: GShard capacity dropping is
    batch-dependent by design, so full-sequence routing and one-token
    decode only agree when nothing is dropped.
    """
    cfg = dataclasses.replace(_reduced(arch), dtype="float32")
    if cfg.moe is not None:
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=16.0))
    params = M.init_model(cfg, jax.random.PRNGKey(0))
    B, S = 1, 16
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 3,
                              cfg.vocab_size)
    full_logits, _, _ = M.forward(params, toks, cfg, mode="train")

    caches = M.init_caches(cfg, B, S, dtype=jnp.float32)
    got = []
    for t in range(S):
        lg, caches = M.decode_step(params, toks[:, t:t + 1], jnp.int32(t),
                                   cfg, caches)
        got.append(lg[:, 0])
    got = jnp.stack(got, axis=1)
    err = float(jnp.abs(full_logits - got).max())
    assert err < 2e-2, f"{arch}: decode diverges from forward by {err}"


def test_segments_cover_all_layers():
    for arch in ARCHS:
        cfg = get_config(arch)
        segs = M.segments(cfg)
        n = sum(len(s.kinds) * s.repeat for s in segs)
        assert n == cfg.num_layers, (arch, n, cfg.num_layers)


def test_param_count_close_to_nameplate():
    """Analytic param counts are in the right ballpark for named sizes."""
    expect = {
        "granite-3-2b": (2.0e9, 4.0e9),
        "deepseek-v2-lite-16b": (13e9, 19e9),
        "deepseek-moe-16b": (13e9, 19e9),
        "internvl2-26b": (15e9, 26e9),     # LLM backbone of the 26B (20B)
        "qwen2-0.5b": (0.3e9, 0.7e9),
        "phi4-mini-3.8b": (3.0e9, 5.0e9),
        # pool specifies 48L x 64e x 1408 for "16b": that is ~28B total
        # (the real Moonlight is 27L); we follow the assigned config exactly
        "moonshot-v1-16b-a3b": (14e9, 30e9),
        "mamba2-370m": (0.25e9, 0.5e9),
        "recurrentgemma-2b": (2.0e9, 3.5e9),
        "musicgen-large": (1.5e9, 3.5e9),
    }
    for arch, (lo, hi) in expect.items():
        n = get_config(arch).param_count()
        assert lo <= n <= hi, (arch, n)


def test_moe_active_params_smaller():
    for arch in ("deepseek-v2-lite-16b", "deepseek-moe-16b",
                 "moonshot-v1-16b-a3b"):
        cfg = get_config(arch)
        assert cfg.active_param_count() < 0.35 * cfg.param_count()


def test_yarn_frequencies_and_scale_for_deepseek_v2_lite():
    """DeepSeek-V2-Lite's YaRN over its 64 rope dims (factor 40 over 4,096
    original positions, beta 32 / 1): dims below 2*10 keep their
    frequency, from 2*23 on they are divided by 40, linear between; cos
    and sin keep magnitude 1 (mscale = mscale_all_dim), and the softmax
    scale is 192**-0.5 * (0.1 * 0.707 * ln 40 + 1)**2."""
    import math

    import numpy as np

    from repro.models.common import rope_freqs, rope_magnitude, yarn_mscale
    from repro.models.mla import softmax_scale

    cfg = get_config("deepseek-v2-lite-16b")
    rs = cfg.rope_scaling
    plain = rope_freqs(64, 10000.0)
    yarn = rope_freqs(64, 10000.0, rs)
    np.testing.assert_allclose(yarn[:11], plain[:11], rtol=1e-6)
    np.testing.assert_allclose(yarn[23:], plain[23:] / 40, rtol=1e-6)
    ramp = (16 - 10) / (23 - 10)
    assert yarn[16] == pytest.approx(plain[16] * (1 - ramp)
                                     + plain[16] / 40 * ramp, rel=1e-6)
    assert rope_magnitude(rs) == 1.0
    assert yarn_mscale(40, 0.707) == pytest.approx(1.26081, abs=1e-5)
    assert softmax_scale(cfg) == pytest.approx(
        192 ** -0.5 * (0.1 * 0.707 * math.log(40) + 1) ** 2)
