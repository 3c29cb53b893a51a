"""The latent-attention MoE reference against the program at a tiny
DeepSeek-V2 shape (``tinymoe.TINY_MLA_MOE``), on the CPU.

HyperServe prefills a prompt in chunks and then decodes through its paged
latent cache; every logit row its step programs return is compared with
the reference's full forward over the same tokens.  Both sides run the
same float32 weights (the CPU's float32 dot is exact float32) and differ
only in the order of summation and in the absorbed form the decode uses
(``q_nope W_uk . c_kv`` for ``q_nope . c_kv W_uk``): over three layers
of width 128 with logits near 1 that moves a logit by a few 1e-6, and no
route flips (a flip needs two of 16 router probabilities within that of
each other at a top-3 boundary).  The tolerance, 1e-4 on every logit,
holds that with a margin; a renormalised gate, a missing mscale and plain
rope in place of YaRN each miss it by orders of magnitude.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tinymoe import TINY_MLA_MOE, TINY_MLA_MOE_SERVE
from chipbench import reference_mla_moe as reference, serving
from chipbench import weights_mla_moe as W
from chipbench.costs_mla_moe import Dims

LOGIT_TOL = 1e-4
SHARE_TOL = 1e-5


@pytest.fixture(scope="module")
def tiny():
    dims = Dims.from_config(TINY_MLA_MOE)
    cfg = W.program_config(TINY_MLA_MOE)
    w = W.make(W.seed_key(11), dims=dims, vocab_rows=cfg.padded_vocab,
               dtype="float32")
    return dims, cfg, w


def _tokens(n, vocab, seed=0):
    return np.random.default_rng(seed).integers(1, vocab, n).tolist()


def _served_logits(cfg, w, prompt, max_new, kernels):
    """Serve ``prompt`` through ``Supernode.serve``; return the tokens and
    the logit row of every step program that produced one of them."""
    from repro.api import Supernode, plans
    scfg = serving.serve_config({"serve": TINY_MLA_MOE_SERVE},
                                Dims.from_config(TINY_MLA_MOE)).replace(
                                    kernels=kernels)
    server = Supernode(None, devices=jax.devices()[:1]).serve(
        cfg, W.program_params(w), plan=plans.serve(serve=scfg))
    eng, rows = server.engine, []
    prefill, decode = eng._prefill_step, eng._decode_step

    def on_prefill(*a):
        out = prefill(*a)
        limits, starts = np.asarray(a[3]), np.asarray(a[2])
        if starts[0] + a[1].shape[1] >= limits[0]:      # the prompt's end
            rows.append(np.asarray(out[0][0]))
        return out

    def on_decode(*a):
        out = decode(*a)
        slot = int(np.flatnonzero(np.asarray(a[5]))[0])
        rows.append(np.asarray(out[0][slot, 0]))
        return out

    eng._prefill_step, eng._decode_step = on_prefill, on_decode
    rid = server.submit(prompt, max_new)
    server.join()
    served = server.result(rid)
    return served, np.stack(rows)[:len(served), :cfg.vocab_size]


def _gap(dims, cfg, w, kernels="composed"):
    prompt = _tokens(37, dims.vocab, seed=1)
    served, got = _served_logits(cfg, w, prompt, 6, kernels)
    seq = prompt + served[:-1]
    h = reference.final_hidden(w, jnp.asarray(
        np.pad(seq, (0, 512 - len(seq)))), dims=dims)
    want = np.asarray(reference.logits(
        w, h[len(prompt) - 1:len(seq)], dims))
    return float(np.max(np.abs(got - want)))


@pytest.mark.parametrize("kernels", ["composed", "fused"])
def test_served_logits_match_the_reference(tiny, kernels):
    dims, cfg, w = tiny
    assert _gap(dims, cfg, w, kernels) < LOGIT_TOL


@pytest.mark.parametrize("fault", ["renormalised_gate", "no_mscale",
                                   "plain_rope"])
def test_the_tolerance_fails_a_wrong_program(tiny, fault, monkeypatch):
    dims, cfg, w = tiny
    if fault == "renormalised_gate":
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, norm_topk_prob=True))
    elif fault == "no_mscale":
        from repro.models import mla
        monkeypatch.setattr(mla, "softmax_scale", lambda c: (
            c.mla.qk_nope_head_dim + c.mla.qk_rope_head_dim) ** -0.5)
    else:
        from repro.models import common
        plain = common.rope_freqs
        monkeypatch.setattr(common, "rope_freqs",
                            lambda d, theta, scaling=None: plain(d, theta))
    assert _gap(dims, cfg, w) > 100 * LOGIT_TOL


def test_expert_shares_sum_to_the_uncut_layer(tiny):
    """Four chips' shares of one MoE layer (experts 0-3, ..., 12-15): their
    outputs, with the shared expert every chip computes counted once, add
    up to the uncut reference layer over all 16 experts."""
    from repro.models import moe
    dims, cfg, w = tiny
    E, held = dims.experts, dims.experts_held
    f = jax.tree.map(lambda a: a[0], w["moe"]["ffn"])
    full = dict(f, **{k: jax.random.normal(jax.random.PRNGKey(i),
                                           (E,) + f[k].shape[1:]) * 0.1
                      for i, k in enumerate(("w_gate", "w_up", "w_down"))})
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 24, dims.d_model))
    parts = []
    for first in range(0, E, held):
        mo = dataclasses.replace(cfg.moe, first_expert_held=first)
        share = dict(full, **{k: full[k][first:first + held]
                              for k in ("w_gate", "w_up", "w_down")})
        y, mm = moe.moe_forward(share, x, dataclasses.replace(cfg, moe=mo),
                                dispatch="ragged")
        parts.append(y)
        assert int(mm["routes_held"].sum()) > 0
    h = x.reshape(-1, dims.d_model)
    shared = reference._swiglu(h, f["ws_gate"], f["ws_up"], f["ws_down"],
                               "f32")
    uncut = dataclasses.replace(dims, experts_held=E, first_held=0)
    with jax.default_matmul_precision("highest"):
        want = reference._routed(h, full, uncut, "f32") + shared
    got = (sum(parts) - (len(parts) - 1) * shared.reshape(x.shape))
    assert np.max(np.abs(np.asarray(got).reshape(h.shape)
                         - np.asarray(want))) < SHARE_TOL


def test_a_share_refuses_the_capacity_dispatch(tiny):
    from repro.api.errors import PlanError
    from repro.models import moe
    dims, cfg, w = tiny
    f = jax.tree.map(lambda a: a[0], w["moe"]["ffn"])
    x = jnp.zeros((1, 4, dims.d_model))
    with pytest.raises(PlanError, match="expert share"):
        moe.moe_forward(f, x, cfg, dispatch="gshard")
