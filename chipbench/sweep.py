"""Find an open-loop cell's knee once, by a sweep of rates on the chip.

    python3 chipbench/sweep.py --workload <cell> --seconds <s> \
        --rates 3,4,5,6 [--seed <n>]

Runs the cell's mix at each mean rate in turn, in one process, and
prints one JSON line per rate: the tails, the tokens per second, and the
requests still waiting for their first token at each third of the
window.  The knee is the highest rate at which that backlog does not grow
over the window.  A cell's rate is then fixed in its mix file; the
benchmark's own runs never sweep.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

sys.path[:] = [p for p in sys.path
               if Path(p or ".").resolve() != Path(__file__).resolve().parent]
sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def backlog_at(win, t: float) -> int:
    """Requests due by ``t`` with no first token by ``t``."""
    return sum(1 for f in win.flights
               if f.due <= t and not (f.times and f.times[0] <= t))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    from chipbench import run
    run.prepare()
    run.cache_config()
    from chipbench import harness, spec, stats
    cell = spec.find_cell(run.ROOT, args.workload)
    found = run.tpu_devices(cell.chips)
    if found is None:
        return 1
    devices, peaks = found
    for rate in (float(r) for r in args.rates.split(",")):
        at_rate = dataclasses.replace(
            cell, traffic=dict(cell.traffic, rate_per_s=rate))
        r, out = harness.run(run.ROOT, at_rate, seed=args.seed,
                             seconds=args.seconds, traced=False,
                             devices=devices, peaks=peaks,
                             t_start=time.perf_counter())
        win = out.window
        thirds = [backlog_at(win, win.t_open + k * args.seconds / 3)
                  for k in (1, 2, 3)]
        ttft = stats.ttfts(win)
        print(json.dumps({
            "rate_per_s": rate, "correct": r["correct"],
            "due": len(win.flights), "waiting_at_thirds": thirds,
            "ttft_p50_s": stats.percentile(ttft, 50),
            "ttft_p90_s": stats.percentile(ttft, 90),
            "itl_p95_s": stats.percentile(stats.itls(win), 95),
            "output_tokens_per_s": stats.output_rate(win)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
