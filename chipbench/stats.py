"""Arithmetic of the end-to-end metrics over one window's records."""
from __future__ import annotations

import math
from typing import List, Optional, Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """Nearest-rank percentile: the smallest value with at least ``q``% of
    the values at or below it (a value some request really saw)."""
    if not values:
        raise ValueError("percentile of no values")
    xs = sorted(values)
    return xs[max(0, math.ceil(q / 100.0 * len(xs)) - 1)]


def ttfts(win) -> List[float]:
    """Time to first token of every request due in the window, from its
    due time.  One with no first token at the close (waiting, rejected or
    never submitted) counts with its wait so far."""
    out = []
    for fl in win.flights:
        if fl.due > win.t_close:
            continue
        out.append((fl.times[0] if fl.times else win.t_close) - fl.due)
    return out


def itls(win) -> List[float]:
    """Every gap between consecutive tokens of every request, pooled."""
    return [b - a for fl in win.flights for a, b in zip(fl.times, fl.times[1:])]


def window_tokens(win) -> int:
    return sum(len(s.decode_lengths) + s.first_tokens for s in win.steps)


def output_rate(win) -> float:
    """Tokens generated in the window's steps over the window's length."""
    return window_tokens(win) / win.seconds


def lateness_summary(lateness: Sequence[float]) -> Optional[dict]:
    if not lateness:
        return None
    return {"median_s": percentile(lateness, 50), "max_s": max(lateness)}
