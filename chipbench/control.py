"""Readings behind a cell's correctness limits, on the chip, in one process.

    python3 chipbench/control.py --workload <cell> --seconds <s> \
        --seeds 1,2,3,...

For each seed: one run of the cell as the benchmark makes it (its own
set-up, window, load and sample of finished requests), judged twice
against the cell's limits: once as the benchmark judges it (the
program's served tokens), and once with the control in the program's
place: the float32 reference forward with every weight matmul in int8,
whose greedy token at each of the same positions is read against the
float32 reference.  The program has to come out correct and the control
not.  Each line also gives the candidate numbers of both (widest and
mean gap, share of flipped tokens): the program's largest over a dozen
seeds is a limit's lower reading, the control's smallest its upper one.
The benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path[:] = [p for p in sys.path
               if Path(p or ".").resolve() != Path(__file__).resolve().parent]
sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    from chipbench import run
    run.prepare()
    run.cache_config()
    from chipbench import check, harness, spec
    cell = spec.find_cell(run.ROOT, args.workload)
    found = run.tpu_devices(cell.chips)
    if found is None:
        return 1
    devices, peaks = found
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        r, out = harness.run(run.ROOT, cell, seed=seed, seconds=args.seconds,
                             traced=False, devices=devices, peaks=peaks,
                             t_start=time.perf_counter(), control=True)
        row = {"seed": seed, "correct": r["correct"],
               "control_correct": check.holds(out.control),
               "program": out.readings["program"],
               "control": out.readings["control"],
               "compared": {k: [v["value"], out.control[k]["value"],
                                v["limit"]] for k, v in r["check"].items()},
               "sampled_tokens": r["check"]["sampled_tokens"]["value"]}
        rows.append(row)
        print(json.dumps(row), flush=True)
    for k in rows[0]["program"]:
        prog = [x["program"][k] for x in rows]
        ctrl = [x["control"][k] for x in rows]
        print(json.dumps({"number": k, "program_max": max(prog),
                          "control_min": min(ctrl), "seeds": len(rows)}),
              flush=True)
    print(json.dumps({"program_correct": sum(x["correct"] for x in rows),
                      "control_correct": sum(x["control_correct"]
                                             for x in rows),
                      "seeds": len(rows)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
