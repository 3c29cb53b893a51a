"""Is what the timed path served correct?  The comparison behind ``correct``.

After the window: a sample of the requests the window finished (see
``sample``), drawn from the seed and always holding the longest, is run
through the float32 reference over each prompt with its served tokens.  Each served token is
greedy, so its reference logit should be the reference's best or lie
within rounding of it.  The numbers compared are the widest gap by which a
served token's reference logit lies below the reference's best, or the
mean of those gaps, each against the cell's limit (``cells/<cell>.json``:
``check.limits``).
"""
from __future__ import annotations

import random
from typing import Dict, List

from chipbench import reference
from chipbench.costs import Dims


def sample(flights, seed: int, k) -> List:
    """``k`` requests (``"all"``: every one): the longest (prompt and
    served tokens), and others drawn from the seed, among the requests the
    window finished, or, where it finished fewer than ``k``, among every
    request it served tokens to (each served token is a greedy choice of
    its own)."""
    pool = [f for f in flights if f.rid is not None and f.done]
    if k == "all":
        k = len(pool)
    if len(pool) < k or not pool:
        pool = [f for f in flights if f.rid is not None and f.tokens]
    if not pool:
        return []
    longest = max(pool, key=lambda f: (len(f.prompt) + len(f.tokens),
                                       f.index))
    rest = [f for f in pool if f is not longest]
    rng = random.Random(int(seed))
    return [longest] + rng.sample(rest, min(k - 1, len(rest)))


def gaps(weights, dims: Dims, picked, *, control: bool = False
         ) -> Dict[str, list]:
    out: Dict[str, list] = {"served": [], "control": []}
    for fl in picked:
        g = reference.greedy_gaps(weights, dims, fl.prompt,
                                  fl.tokens[:fl.max_new], control=control)
        for k, v in g.items():
            out[k].append(v)
    return out


def gap_numbers(served_gaps) -> Dict[str, float]:
    """The candidate numbers over every sampled served token: the widest
    gap, the mean gap (flips of near-ties weighted by how far), and the
    share of tokens that are not the reference's greedy choice."""
    flat = [float(x) for g in served_gaps for x in g]
    if not flat:
        return {"widest_gap": float("inf"), "mean_gap": float("inf"),
                "flip_share": float("inf")}
    return {"widest_gap": max(flat), "mean_gap": sum(flat) / len(flat),
            "flip_share": sum(1 for x in flat if x > 0) / len(flat)}


def judge(flights, picked, dims: Dims, served_gaps, limits: Dict[str, float]
          ) -> Dict[str, dict]:
    """The numbers compared, each with its limit; all must hold.  The cell
    names which gap numbers it compares (``check.limits``)."""
    bad_len = sum(1 for f in flights
                  if f.rid is not None and len(f.tokens) > f.max_new)
    bad_ids = sum(1 for f in flights for t in f.tokens
                  if not 0 <= t < dims.vocab)
    gaps = gap_numbers(served_gaps)
    out = {k: {"value": gaps[k], "limit": v} for k, v in limits.items()}
    out.update({
        "sampled_tokens": {"value": sum(len(f.tokens) for f in picked),
                           "limit": 1, "at_least": True},
        "overlong_requests": {"value": bad_len, "limit": 0},
        "ids_outside_vocab": {"value": bad_ids, "limit": 0},
    })
    return out


def holds(numbers: Dict[str, dict]) -> bool:
    return all((n["value"] >= n["limit"]) if n.get("at_least")
               else (n["value"] <= n["limit"]) for n in numbers.values())
