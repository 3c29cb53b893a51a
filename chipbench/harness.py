"""One run of one cell: the cell's driver, then the result line.

``run_cell`` takes the devices it may use and their peaks; the command
line (``run.py``) finds them and refuses anything that is not a TPU, so
tests can drive the rest of a run on the CPU.  The harness knows no kind
of cell: it loads the driver the cell file names (``drivers/<driver>.py``),
which builds the system under test, runs set-up and the window and checks
what the window produced, and reads each metric with the reader of its
name (``end_to_end/<metric>.py``, ``metrics/<metric>.py``).
"""
from __future__ import annotations

import dataclasses
import sys
import time
from pathlib import Path
from typing import Any, Dict, Optional, Tuple

from chipbench import check, layers, spec

now = time.perf_counter


def log(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


class CompileCounter:
    """Counts the programs JAX traces or compiles (jax.monitoring events)."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/core/compile/jaxpr_trace_duration")

    def __init__(self):
        import jax
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event in self.EVENTS:
            self.n += 1


def device_info(devices, chips: int) -> dict:
    """The result's ``device``; read it before the check runs anything."""
    used = devices[:chips]
    peak = max(int(d.memory_stats().get("peak_bytes_in_use", 0))
               if d.memory_stats() else 0 for d in used)
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


@dataclasses.dataclass
class Job:
    """What a driver gets."""
    root: Path
    cell: spec.Cell
    seed: int
    seconds: float
    traced: bool
    devices: Any
    peaks: dict
    t_start: float                  # process start, for setup_s
    trace_dir: Path
    control: bool = False           # also judge the control (control.py)


@dataclasses.dataclass
class Outcome:
    """What a driver gives back."""
    setup_s: float
    window: Any                     # read by end_to_end/<metric>.py
    attempted: int
    failed: int
    device: dict                    # device_info(), read before the check
    check: Dict[str, dict]          # the numbers compared, with limits
    context: Optional[layers.Context] = None    # traced runs
    control: Optional[Dict[str, dict]] = None  # the control's numbers
    readings: Optional[dict] = None  # candidate numbers, with the control


def run(root: Path, cell: spec.Cell, *, seed: int, seconds: float,
        traced: bool, devices, peaks: dict, t_start: float,
        control: bool = False) -> Tuple[dict, Outcome]:
    """The result line of one run of ``cell``, and the driver's outcome."""
    if len(devices) < cell.chips:
        raise RuntimeError(f"{cell.name} needs {cell.chips} chips, JAX found "
                           f"{len(devices)}")
    driver = spec.load(root, "drivers", cell.deploy["driver"])
    out: Outcome = driver.run(Job(
        root=Path(root), cell=cell, seed=seed, seconds=seconds,
        traced=traced, devices=devices, peaks=peaks, t_start=t_start,
        trace_dir=Path(root) / ".chipbench" / "trace", control=control))
    result = {"correct": check.holds(out.check), "attempted": out.attempted,
              "failed": out.failed}
    device = dict(out.device)
    if traced:
        ctx = out.context
        result["metrics"] = spec.read_per_layer(root, cell, ctx)
        device["busy_s"] = ctx.trace.get("busy_s", 0.0)
        device["window_s"] = ctx.trace.get("window_s", 0.0)
        result["device"] = device
        result["breakdown"] = {
            "device_ops": ctx.trace.get("device_ops", []),
            "idle_gaps": ctx.trace.get("idle_gaps", [])}
    else:
        metrics = {}
        for m in cell.end_to_end:
            value = (out.setup_s if m["name"] == "setup_s" else
                     spec.load(root, "end_to_end", m["name"]).read(out.window))
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        result["metrics"] = metrics
        result["device"] = device
    result["check"] = out.check
    for k, v in out.check.items():
        log(f"check: {k}={v['value']} limit={v['limit']}"
            + (" (at least)" if v.get("at_least") else ""))
    return result, out


def run_cell(root: Path, workload: str, *, seed: int, seconds: float,
             traced: bool, devices, peaks: dict, t_start: float) -> dict:
    return run(root, spec.find_cell(root, workload), seed=seed,
               seconds=seconds, traced=traced, devices=devices, peaks=peaks,
               t_start=t_start)[0]
