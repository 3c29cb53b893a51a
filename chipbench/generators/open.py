"""Open loop: independent users on a schedule, whatever the server does.

Mix parameters: ``rate_per_s`` mean arrivals with gamma inter-arrival
gaps of coefficient of variation ``cv``; ``prompt`` and ``output`` length
distributions; ``lead_s``, seconds of the same traffic offered before the
window opens, so that the window starts at the load it measures.  The
lead's requests and the window's are two stratified sets, each in an
order that is the same for every seed, like a trace replayed: the 90th
percentile of ~120 first-token times depends on which prompts arrive in
the bursts, so shuffling by the seed would move it by more than any
bound could hold.  The seed draws the token ids.  Only requests due
inside the window are the window's; the lead's are load.
"""
from __future__ import annotations

import collections
import time
from typing import List

import numpy as np

from chipbench import traffic as T
from chipbench.serving import Client, Flight, Window, now

ORDER_SEED = 0


def _span(mix: dict, *, seconds: float, seed: int, vocab: int,
          stream: int, first: int, start: float) -> List[T.Request]:
    n = max(1, int(round(mix["rate_per_s"] * seconds)))
    order = T.rng(ORDER_SEED, stream)
    gaps = order.permutation(T.gamma_gaps(mix["rate_per_s"], mix["cv"], n,
                                          seconds))
    due = start + np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    plen = order.permutation(T.lengths(mix["prompt"], n))
    olen = order.permutation(T.lengths(mix["output"], n))
    return [T.Request(first + i, T.tokens(seed, first + i, plen[i], vocab),
                      int(olen[i]), float(due[i])) for i in range(n)]


def requests(mix: dict, *, seconds: float, seed: int, vocab: int
             ) -> List[T.Request]:
    """Every request of the lead (due before 0) and of a window of
    ``seconds`` (due in [0, seconds)), in order of due time."""
    window = _span(mix, seconds=seconds, seed=seed, vocab=vocab, stream=1,
                   first=0, start=0.0)
    lead_s = float(mix.get("lead_s", 0.0))
    lead = (_span(mix, seconds=lead_s, seed=seed, vocab=vocab, stream=4,
                  first=len(window), start=-lead_s) if lead_s > 0 else [])
    return lead + window


def _offer(client: Client, todo: collections.deque, win: Window,
           t_end: float, live: List[Flight]) -> None:
    """Submit each request when due and step while any is live, until
    ``t_end``."""
    while True:
        t = now()
        if t >= t_end:
            return
        while todo and todo[0].due <= t:
            fl = todo.popleft()
            win.lateness.append(t - fl.due)
            client.submit(fl, t)
            win.flights.append(fl)
            if not fl.rejected:
                live.append(fl)
        if live:
            client.step(win)
            live[:] = [f for f in live if not f.done]
        else:
            wake = min(todo[0].due if todo else t_end, t_end)
            with client.span("chipbench.wait"):
                time.sleep(max(0.0, wake - now()))


def window(client: Client, mix: dict, *, seed: int, seconds: float,
           vocab: int, deploy: dict) -> Window:
    reqs = requests(mix, seconds=seconds, seed=seed, vocab=vocab)
    t_open = now() + float(mix.get("lead_s", 0.0))
    todo = collections.deque(Flight(r.index, r.prompt, r.max_new,
                                    t_open + r.due) for r in reqs)
    live: List[Flight] = []
    _offer(client, todo, Window(t_open=now()), t_open, live)
    win = client.open(at=t_open)
    _offer(client, todo, win, t_open + seconds, live)
    # a lead request submitted after the lead's last step is still load
    keep = [i for i, f in enumerate(win.flights) if f.due >= t_open]
    win.flights = [win.flights[i] for i in keep]
    win.lateness = [win.lateness[i] for i in keep]
    win.flights.extend(todo)        # never submitted: due, not served
    client.close(win)
    return win
