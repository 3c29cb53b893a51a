"""The float32 reference against the program at a tiny size, on the CPU.

Both sides run the same weights in float32 (the CPU's float32 dot is
exact float32), so they differ only in the order of summation: over a
two-layer model of width 128 with logits near 1, rounding moves a logit
by at most a few 1e-6.  The tolerances, 1e-4 on every logit and 1e-5 on
the mean loss, hold that with a margin and fail a wrong norm, rotation,
head grouping, bias or mask by orders of magnitude.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from tinybench import TINY_CONFIG
from chipbench import reference, serving, weights as W
from chipbench.costs import Dims

LOGIT_TOL = 1e-4
LOSS_TOL = 1e-5


@pytest.fixture(scope="module")
def tiny():
    dims = Dims.from_config(TINY_CONFIG)
    cfg = serving.program_config(TINY_CONFIG)
    w = W.make(W.seed_key(3), dims=dims, vocab_rows=cfg.padded_vocab,
               dtype="float32")
    return dims, cfg, w


def _tokens(n, vocab, seed=0):
    return np.random.default_rng(seed).integers(1, vocab, n).tolist()


def test_reference_logits_match_program_forward(tiny):
    from repro.models import model as M
    dims, cfg, w = tiny
    toks = _tokens(40, dims.vocab)
    logits, _, _ = M.forward(serving.program_params(w),
                             jnp.asarray([toks], jnp.int32), cfg,
                             mode="train", remat=False)
    want = np.asarray(logits[0, :, :dims.vocab])
    h = reference.final_hidden(w, reference._padded(toks), dims=dims)
    got = np.asarray(reference._logits(w, h[:40], dims, "f32"))
    assert np.max(np.abs(got - want)) < LOGIT_TOL


def test_reference_loss_matches_program_loss(tiny):
    from repro.train import steps
    dims, cfg, w = tiny
    seq = _tokens(33, dims.vocab, seed=1)
    batch = {"inputs": jnp.asarray([seq[:-1]], jnp.int32),
             "targets": jnp.asarray([seq[1:]], jnp.int32),
             "mask": jnp.ones((1, 32), jnp.float32)}
    want, _ = steps.loss_fn(serving.program_params(w), batch, cfg,
                            remat=False)
    got = reference.mean_nll(w, dims, seq[:-1], seq[1:])
    assert abs(got - float(want)) < LOSS_TOL


def test_greedy_gap_zero_for_reference_tokens_and_positive_otherwise(tiny):
    dims, _, w = tiny
    prompt = _tokens(20, dims.vocab, seed=2)
    served = []
    for _ in range(6):                 # the reference's own greedy tokens
        seq = prompt + served
        h = reference.final_hidden(w, reference._padded(seq), dims=dims)
        served.append(int(jnp.argmax(
            reference._logits(w, h[len(seq) - 1:len(seq)], dims, "f32"))))
    g = reference.greedy_gaps(w, dims, prompt, served, control=True)
    assert np.all(g["served"] == 0)
    assert np.all(g["control"] >= 0)
    other = served[:3] + [(served[3] + 1) % dims.vocab] + served[4:]
    assert reference.greedy_gaps(w, dims, prompt, other)["served"][3] > 0


def test_int8_control_departs_from_float32(tiny):
    dims, _, w = tiny
    toks = reference._padded(_tokens(64, dims.vocab, seed=4))
    hi = reference.final_hidden(w, toks, dims=dims)
    lo = reference.final_hidden(w, toks, dims=dims, precision="int8")
    rel = float(jnp.max(jnp.abs(hi - lo)) / jnp.max(jnp.abs(hi)))
    assert 1e-4 < rel < 0.5
