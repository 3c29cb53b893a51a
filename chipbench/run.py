"""Run one cell of BENCHMARK.json once on this machine's chips.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Prints progress and the numbers the correctness check compared on
standard error, and one JSON object as the last line of standard output:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's end-to-end
metrics, or with ``--trace 1`` its per-layer ones), ``device`` and, last,
``check``.  Exits 1 without a result when the first device is not a TPU
of a kind in the peaks table, or there are fewer chips than the cell
asks for; exits 2 when the program under test cannot be imported.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
CACHE_DIR = ROOT / ".jax_cache"
# run as a script, this directory heads sys.path, and its module names
# (trace, ...) would shadow others: the package is imported from ROOT
sys.path[:] = [p for p in sys.path
               if Path(p or ".").resolve() != ROOT / "chipbench"]


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def tpu_devices(chips: int):
    """The TPU devices and their peaks, or None after saying why not."""
    import jax

    from chipbench.peaks import UnknownDevice, peaks_for
    try:
        devices = jax.devices()
    except RuntimeError as e:
        print(f"chipbench: no accelerator: {e}".splitlines()[0],
              file=sys.stderr)
        return None
    d0 = devices[0]
    if d0.platform != "tpu":
        print(f"chipbench: the first device is {d0.platform}, not a TPU",
              file=sys.stderr)
        return None
    try:
        peaks = peaks_for(d0.device_kind)
    except UnknownDevice as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return None
    if len(devices) < chips:
        print(f"chipbench: the cell needs {chips} chips, JAX found "
              f"{len(devices)}", file=sys.stderr)
        return None
    return devices, peaks


def prepare() -> None:
    """Import paths and the compile cache.  The cache lives in the
    checkout, at a fixed path, whatever the environment says, so that two
    checkouts share nothing."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def cache_config() -> None:
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def main(argv=None) -> int:
    args = parse(argv)
    prepare()
    from chipbench import spec
    try:
        cell_chips = next(w["chips"] for w in spec.load_benchmark(ROOT)[
            "workloads"] if w["name"] == args.workload)
    except StopIteration:
        print(f"chipbench: no workload {args.workload!r}", file=sys.stderr)
        return 2
    try:
        import repro  # noqa: F401
    except ImportError as e:
        print(f"chipbench: the program under test is missing: {e}",
              file=sys.stderr)
        return 2
    cache_config()
    found = tpu_devices(cell_chips)
    if found is None:
        return 1
    devices, peaks = found
    from chipbench import harness
    result = harness.run_cell(ROOT, args.workload, seed=args.seed,
                              seconds=args.seconds, traced=bool(args.trace),
                              devices=devices, peaks=peaks, t_start=T_START)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
