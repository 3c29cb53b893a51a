"""A new kind of cell from files alone: a driver, a generator and an
end-to-end metric that no existing file names, added as new files and
new BENCHMARK.json entries, run through the harness on the CPU."""
import json
import time

import jax

from tinybench import _dump
from tinybench import tiny_root  # noqa: F401  (fixture)
from chipbench import harness
from chipbench.peaks import PEAKS

DRIVER = '''
import jax
import jax.numpy as jnp
import numpy as np

from chipbench import spec
from chipbench.harness import Outcome, device_info, now


def run(job):
    gen = spec.load(job.root, "generators", job.cell.traffic["kind"])
    rows = gen.rows(job.cell.traffic, seed=job.seed)
    f = jax.jit(lambda x: jnp.tanh(x @ x.T).sum(-1))
    f(rows).block_until_ready()
    t_open, outs = now(), []
    while now() < t_open + job.seconds:
        outs.append(f(rows))
    jax.block_until_ready(outs)
    win = {"steps": len(outs), "seconds": now() - t_open}
    err = float(np.max(np.abs(np.asarray(outs[-1])
                              - np.tanh(rows @ rows.T).sum(-1))))
    limit = job.cell.deploy["check"]["max_abs_error"]
    return Outcome(setup_s=t_open - job.t_start, window=win,
                   attempted=len(outs), failed=0,
                   device=device_info(job.devices, job.cell.chips),
                   check={"max_abs_error": {"value": err, "limit": limit}})
'''

GENERATOR = '''
import numpy as np


def rows(mix, *, seed):
    return np.random.default_rng(seed).standard_normal(
        (mix["rows"], mix["width"])).astype(np.float32)
'''

METRIC = '''
def read(win):
    return win["steps"] / win["seconds"]
'''


def test_a_new_driver_generator_and_metric_from_files_alone(tiny_root):
    bench = json.loads((tiny_root / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "tiny.steps", "config": "tiny",
                               "traffic": "fixed_rows", "chips": 1,
                               "why": "test"})
    bench["end_to_end"].append({"name": "steps_per_s", "unit": "steps/s",
                                "better": "higher", "bound": 0.05,
                                "source": "host_clock",
                                "workloads": ["tiny.steps"]})
    _dump(tiny_root / "BENCHMARK.json", bench)
    _dump(tiny_root / "chipbench/traffic/fixed_rows.json",
          {"kind": "fixed_rows", "rows": 64, "width": 32})
    _dump(tiny_root / "chipbench/cells/tiny.steps.json",
          {"driver": "steps", "check": {"max_abs_error": 1e-3}})
    for path, text in (("drivers/steps.py", DRIVER),
                       ("generators/fixed_rows.py", GENERATOR),
                       ("end_to_end/steps_per_s.py", METRIC)):
        (tiny_root / "chipbench" / path).write_text(text)
    r = harness.run_cell(tiny_root, "tiny.steps", seed=2**33 + 1,
                         seconds=0.3, traced=False, devices=jax.devices(),
                         peaks=PEAKS["TPU v5 lite"],
                         t_start=time.perf_counter())
    assert r["correct"] is True
    assert set(r["metrics"]) == {"steps_per_s", "setup_s"}
    assert r["metrics"]["steps_per_s"]["value"] > 0
    assert r["attempted"] > 0
