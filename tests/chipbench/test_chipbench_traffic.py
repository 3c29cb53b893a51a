"""The traffic generator: deterministic per seed, the stated clips and
medians, and every seed the same set of lengths and gaps."""
import itertools
import json

import numpy as np
import pytest

from tinybench import REPO
from chipbench import spec
from chipbench import traffic as T

OPEN = spec.load(REPO, "generators", "open")
BACKLOG = spec.load(REPO, "generators", "backlog")

CHAT = json.loads((REPO / "chipbench/traffic/chat.json").read_text())
BATCH = json.loads((REPO / "chipbench/traffic/batch_long.json").read_text())
SEEDS = [0, 7, 2**31 + 11, 2**40 + 3]


def _sig(reqs):
    return [(r.prompt, r.max_new, r.due) for r in reqs]


@pytest.mark.parametrize("seed", SEEDS)
def test_open_loop_is_deterministic_per_seed(seed):
    a = OPEN.requests(CHAT, seconds=10, seed=seed, vocab=1000)
    b = OPEN.requests(CHAT, seconds=10, seed=seed, vocab=1000)
    assert _sig(a) == _sig(b)
    c = OPEN.requests(CHAT, seconds=10, seed=seed + 1, vocab=1000)
    assert _sig(a) != _sig(c)


def test_open_loop_same_lengths_and_gaps_for_every_seed():
    runs = [[r for r in OPEN.requests(CHAT, seconds=45, seed=s, vocab=1000)
             if r.due >= 0] for s in SEEDS]
    for key in (lambda r: len(r.prompt), lambda r: r.max_new):
        sets = [sorted(map(key, rs)) for rs in runs]
        assert all(s == sets[0] for s in sets)
    gaps = [np.sort(np.diff([r.due for r in rs])) for rs in runs]
    # the last gap differs by seed (n gaps are drawn, n - 1 are used)
    assert all(np.max(np.abs(g[:-1] - gaps[0][:-1])) < 1.0 for g in gaps)
    # one order for every seed; the seed draws the token ids
    assert all([(len(r.prompt), r.max_new, r.due) for r in rs] == [
        (len(r.prompt), r.max_new, r.due) for r in runs[0]] for rs in runs)
    assert runs[0][0].prompt != runs[1][0].prompt


def test_open_loop_rate_window_and_clips():
    every = OPEN.requests(CHAT, seconds=45, seed=5, vocab=1000)
    lead = [r for r in every if r.due < 0]
    reqs = [r for r in every if r.due >= 0]
    assert len(reqs) == round(CHAT["rate_per_s"] * 45)
    assert len(lead) == round(CHAT["rate_per_s"] * CHAT["lead_s"])
    assert lead[0].due == -CHAT["lead_s"]
    assert len({r.index for r in every}) == len(every)
    due = [r.due for r in every]
    assert due == sorted(due) and reqs[0].due == 0.0 and due[-1] < 45
    p = [len(r.prompt) for r in reqs]
    o = [r.max_new for r in reqs]
    assert min(p) >= 32 and max(p) <= 1536
    assert min(o) >= 8 and max(o) <= 512
    assert abs(np.median(p) - 512) <= 8
    assert abs(np.median(o) - 128) <= 4
    assert all(1 <= t < 1000 for r in reqs for t in r.prompt)


def test_gamma_gaps_have_the_stated_mean_and_spread():
    g = T.gamma_gaps(4.0, 2.0, 4000, 1000.0)
    assert g.sum() == pytest.approx(1000.0)
    assert np.std(g) / np.mean(g) == pytest.approx(2.0, rel=0.15)


def _backlog(seed):
    return list(itertools.islice(
        BACKLOG.requests(BATCH, seed=seed, vocab=500, group=16), 130))


def test_backlog_decks_are_deterministic_and_clipped():
    a, b = _backlog(9), _backlog(9)
    assert _sig(a) == _sig(b)
    deck = 16
    for k in range(2):
        lens = sorted(len(r.prompt) for r in a[k * deck:(k + 1) * deck])
        assert lens == sorted(T.lengths(BATCH["prompt"], deck))
    c = _backlog(10)
    assert [(len(r.prompt), r.max_new) for r in a] == [
        (len(r.prompt), r.max_new) for r in c]     # same work, other ids
    assert [r.prompt for r in a] != [r.prompt for r in c]
    p = [len(r.prompt) for r in a]
    assert min(p) >= 1024 and max(p) <= 3584
    assert abs(np.median(T.lengths(BATCH["prompt"], deck)) - 2048) <= 40
    assert all(64 <= r.max_new <= 512 for r in a)


def test_lengths_refuse_an_unknown_distribution():
    with pytest.raises(ValueError):
        T.lengths({"dist": "zipf"}, 4)
