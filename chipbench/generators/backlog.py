"""Offline job: a list of requests that keeps the queue full.

Mix parameters: ``backlog_per_seat`` waiting requests per seat, kept in
the queue throughout; ``open_after_finished``, how many requests finish
before the window opens; ``prompt`` and ``output`` length distributions.
Set-up fills every seat, prefills the first wave and runs until that many
requests have finished, so the window sees seats freed and taken again
from the queue, not the cold start.

The list comes in groups of one stratified set of lengths per seat,
each group in an order that is the same for every seed: in a backlog the
order decides which requests finish inside the window, and so how much
work it holds.  The seed draws the token ids.
"""
from __future__ import annotations

from typing import Iterator, List

from chipbench import traffic as T
from chipbench.serving import Client, Flight, Window, now

ORDER_SEED = 0


def requests(mix: dict, *, seed: int, vocab: int, group: int
             ) -> Iterator[T.Request]:
    """An endless list, in groups of ``group`` stratified lengths."""
    plen_g = T.lengths(mix["prompt"], group)
    olen_g = T.lengths(mix["output"], group)
    i = 0
    while True:
        order = T.rng(ORDER_SEED, 3, i // group)
        plen, olen = order.permutation(plen_g), order.permutation(olen_g)
        for j in range(group):
            yield T.Request(i, T.tokens(seed, i, plen[j], vocab),
                            int(olen[j]))
            i += 1


def window(client: Client, mix: dict, *, seed: int, seconds: float,
           vocab: int, deploy: dict) -> Window:
    seats = deploy["serve"]["max_slots"]
    want = mix["backlog_per_seat"] * seats
    reqs = requests(mix, seed=seed, vocab=vocab, group=seats)
    flights: List[Flight] = []

    def top_up(t: float) -> None:
        for _ in range(want - client.server.snapshot()["queue_depth"]):
            r = next(reqs)
            fl = Flight(r.index, r.prompt, r.max_new, t)
            client.submit(fl, t)
            flights.append(fl)

    top_up(now())
    fill = Window(t_open=now())
    while sum(f.done for f in flights) < mix["open_after_finished"]:
        client.step(fill)
        top_up(now())
    win = client.open()
    t_end = win.t_open + seconds
    while now() < t_end:
        client.step(win)
        with client.span("chipbench.generate"):
            top_up(now())
    client.close(win)
    win.flights = flights
    return win
