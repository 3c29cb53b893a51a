"""What per-layer readers read: one traced window's records and its trace.

A reader (``metrics/<metric>.py``) gets a :class:`Context` and returns a
number, or ``None`` where the window holds nothing for it to read.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

from chipbench import costs
from chipbench.costs import Dims

# kernel -> pattern of its op's short name in the device trace.  A Pallas
# kernel is a ``tpu_custom_call``; the trace names neither the kernel nor
# its function, so the two attention kernels of the serving steps are told
# apart by their outputs: the decode kernel's (seats, kv heads, group,
# head dim), the prefill kernel's (rows, kv heads, group, chunk, head dim)
KERNELS = {
    "paged_decode": r" = \w+\[\d+(,\d+){3}\] custom-call tpu_custom_call$",
    "ragged_prefill": r" = \w+\[\d+(,\d+){4}\] custom-call tpu_custom_call$",
}


@dataclasses.dataclass
class Context:
    dims: Dims
    peaks: dict
    chips: int
    block: int                                  # tokens per KV page
    trace: dict                                 # trace.reduce(...)
    decode_calls: List[List[int]]               # contexts of each row
    prefill_calls: List[List[Tuple[int, int]]]  # (start, n) of each row
    queue_waits: List[float]

    # -- work the algorithm needs over the traced window ------------------
    def decode_work(self) -> Tuple[float, float]:
        f = b = 0.0
        for lengths in self.decode_calls:
            df, db = costs.paged_decode(self.dims, lengths, self.block)
            f, b = f + df, b + db
        return f, b

    def prefill_work(self) -> Tuple[float, float]:
        f = b = 0.0
        for rows in self.prefill_calls:
            pf, pb = costs.ragged_prefill(self.dims, rows, self.block)
            f, b = f + pf, b + pb
        return f, b

    def model_flops(self) -> Dict[str, float]:
        """Model FLOPs of prompt tokens and of generated tokens."""
        pre = sum(costs.prompt_flops(self.dims, s, n)
                  for rows in self.prefill_calls for s, n in rows)
        dec = sum(costs.output_flops(self.dims, n)
                  for lengths in self.decode_calls for n in lengths)
        return {"prefill": pre, "decode": dec}

    # -- readings ----------------------------------------------------------
    def kernel_s(self, kernel: str) -> Optional[float]:
        t = self.trace.get("kernel_s", {}).get(kernel)
        return t if t else None

    def roofline_pct(self, kernel: str, work: Tuple[float, float]
                     ) -> Optional[float]:
        t = self.kernel_s(kernel)
        if t is None or work[0] <= 0:
            return None
        return 100.0 * costs.roofline_seconds(*work, self.peaks) / t

    def mfu_pct(self, flops: float) -> Optional[float]:
        w = self.trace.get("window_s")
        if not w or flops <= 0:
            return None
        return 100.0 * flops / (w * self.chips
                                * self.peaks["bf16_flops_per_s"])

    def idle_pct(self) -> Optional[float]:
        w = self.trace.get("window_s")
        if not w:
            return None
        return 100.0 * (1.0 - self.trace["busy_s"] / w)

    def program_ms(self, label: str) -> Optional[float]:
        runs = self.trace.get("program_s", {}).get(label)
        return 1e3 * sum(runs) / len(runs) if runs else None
