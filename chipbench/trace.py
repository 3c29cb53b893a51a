"""Reduce a profiler trace to device busy time, program and kernel time,
and idle gaps named by what the host was doing.

``events_from_xspace`` flattens the ``.xplane.pb`` that
``jax.profiler.stop_trace`` writes into rows ``(plane, line, name,
start_ns, duration_ns)``; ``reduce`` works on such rows alone, so a
recorded trace can be trimmed and kept as test data.

Device planes are those named ``/device:TPU:<n>``: their ``XLA Ops`` line
holds one event per operation that ran, their ``XLA Modules`` line one
event per program execution.  Every other plane is the host's.
"""
from __future__ import annotations

import bisect
import collections
import functools
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Row = Tuple[str, str, str, float, float]

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PREFIX = "chipbench."      # the benchmark's own host annotations
LONGEST_HOST_NS = 2e9           # longer host events name no gap
# ops that hold other ops (a scan's loop): busy time, not a costly op
CONTAINER = re.compile(r" (while|conditional|call)$")


def latest_xspace(trace_dir: str) -> Optional[str]:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(paths, key=os.path.getmtime) if paths else None


_HLO = re.compile(r"^(%[\w.\-]+) = (.*?) ([a-z][\w\-]*)\(")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def short_name(name: str) -> str:
    """``%op = type opcode[ target]`` of a device op's HLO text, without
    operands, layouts or attributes: ``%closed_call.13 = bf16[64,2,7,64]
    custom-call tpu_custom_call``.  Other names are kept as they are."""
    m = _HLO.match(name)
    if not m:
        return name
    lhs, typ, opcode = m.groups()
    typ = "(tuple)" if typ.startswith("(") else re.sub(r"\{.*", "", typ)
    out = f"{lhs} = {typ} {opcode}"
    t = _TARGET.search(name) if opcode == "custom-call" else None
    return f"{out} {t.group(1)}" if t else out


def events_from_xspace(path: str) -> List[Row]:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    rows = []
    for plane in pd.planes:
        device = plane.name.startswith(DEVICE_PREFIX)
        for line in plane.lines:
            for ev in line.events:
                rows.append((plane.name, line.name,
                             short_name(ev.name) if device else ev.name,
                             float(ev.start_ns), float(ev.duration_ns)))
    return rows


def union_ns(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def _clip(iv, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in iv if min(e, hi) > max(s, lo)]


def reduce(rows: Sequence[Row], kernels: Dict[str, str], *,
           top: int = 10) -> dict:
    """Busy and idle time per device over the traced window, device time of
    each kernel (ops whose name matches its pattern), the duration of
    each execution of each program labelled by the kernels it ran, the
    devices' costliest operations, and idle time by host activity.

    The traced window runs from the first to the last of the benchmark's
    own host annotations (``chipbench.*``)."""
    host = [r for r in rows if not r[0].startswith(DEVICE_PREFIX)]
    marks = [r for r in host if r[2].startswith(HOST_PREFIX)]
    if not marks:
        return {}
    lo = min(r[3] for r in marks)
    hi = max(r[3] + r[4] for r in marks)
    devices = sorted({r[0] for r in rows if r[0].startswith(DEVICE_PREFIX)})
    ops = {d: [r for r in rows if r[0] == d and r[1] == OPS_LINE]
           for d in devices}
    mods = {d: [r for r in rows if r[0] == d and r[1] == MODULES_LINE]
            for d in devices}

    pats = {k: re.compile(p) for k, p in kernels.items()}

    @functools.lru_cache(maxsize=None)
    def kernel_of(name: str) -> Optional[str]:
        return next((k for k, p in pats.items() if p.search(name)), None)

    busy, kernel_ns = {}, collections.Counter()
    op_ns = collections.Counter()
    programs: Dict[str, List[float]] = collections.defaultdict(list)
    for d in devices:
        live = _clip([(r[3], r[3] + r[4]) for r in ops[d]], lo, hi)
        busy[d] = union_ns(live)
        for r in ops[d]:
            s, e = max(r[3], lo), min(r[3] + r[4], hi)
            if e <= s:
                continue
            if not CONTAINER.search(r[2]):
                op_ns[r[2]] += e - s
            k = kernel_of(r[2])
            if k:
                kernel_ns[k] += e - s
        # label each program execution by the kernels that ran inside it
        kops = sorted((r[3], kernel_of(r[2])) for r in ops[d]
                      if kernel_of(r[2]))
        kts = [t for t, _ in kops]
        for r in mods[d]:
            if r[3] < lo or r[3] + r[4] > hi:
                continue
            inside = {k for _, k in kops[bisect.bisect_left(kts, r[3]):
                                         bisect.bisect_right(kts, r[3] + r[4])]}
            label = "+".join(sorted(inside)) if inside else "other"
            programs[label].append(r[4])

    n = max(1, len(devices))
    window_ns = hi - lo
    busy_ns = sum(sum(e - s for s, e in iv) for iv in busy.values()) / n
    gaps = collections.Counter()
    if devices:
        d0 = devices[0]
        edges = [lo] + [x for iv in busy[d0] for x in iv] + [hi]
        spans = sorted((r[3], r[3] + r[4], r[2]) for r in host
                       if 0 < r[4] <= LONGEST_HOST_NS)
        starts = [a for a, _, _ in spans]
        for s, e in zip(edges[0::2], edges[1::2]):
            if e > s:
                near = spans[bisect.bisect_left(starts, s - LONGEST_HOST_NS):
                             bisect.bisect_left(starts, e)]
                gaps[_doing(near, s, e)] += e - s
    return {
        "window_s": window_ns / 1e9,
        "busy_s": busy_ns / 1e9,
        "devices": len(devices),
        "kernel_s": {k: v / n / 1e9 for k, v in kernel_ns.items()},
        "program_s": {k: [v / 1e9 for v in vs] for k, vs in programs.items()},
        "device_ops": [[k, v / n / 1e9] for k, v in op_ns.most_common(top)],
        "idle_gaps": [[k, v / 1e9] for k, v in gaps.most_common(top)],
    }


def _doing(spans, s, e) -> str:
    """The innermost host event that covers most of the gap [s, e): of the
    events with the largest overlap, the shortest."""
    best, key = "host idle", (0.0, 0.0)
    for a, b, name in spans:
        ov = min(b, e) - max(a, s)
        if ov > 0 and (ov, a - b) > key:
            best, key = name, (ov, a - b)
    return best
