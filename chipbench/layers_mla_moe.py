"""What per-layer readers read in a cell of a latent-attention MoE model:
``layers.Context`` with the route counts of the traced window's step
programs and the work of the two kernels that do most of its steps.

A reader given another cell's ``layers.Context`` finds none of the
methods here and returns ``None``.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Tuple

import numpy as np

from chipbench import costs_mla_moe, layers

# kernel -> pattern of its op's short name in the device trace: a Pallas
# kernel's ``name`` becomes its HLO instruction's name on the chip
KERNELS = {
    "paged_mla_decode": r"^%paged_mla_decode_attention\b.* custom-call "
                        r"tpu_custom_call$",
    "grouped_matmul": r"^%grouped_matmul\b.* custom-call tpu_custom_call$",
}


@dataclasses.dataclass
class Context(layers.Context):
    """``dims`` is a ``costs_mla_moe.Dims``."""
    # each step program's held-expert route counts (moe layers, held)
    routes: List[np.ndarray] = dataclasses.field(default_factory=list)

    def mla_decode_work(self) -> Tuple[float, float]:
        f = b = 0.0
        for lengths in self.decode_calls:
            df, db = costs_mla_moe.paged_mla_decode(self.dims, lengths,
                                                    self.block)
            f, b = f + df, b + db
        return f, b

    def grouped_matmul_work(self) -> Tuple[float, float]:
        f = b = 0.0
        for counts in self.routes:
            gf, gb = costs_mla_moe.grouped_matmul(self.dims, counts)
            f, b = f + gf, b + gb
        return f, b

    def step_ms(self, kind: str) -> Optional[float]:
        """Mean device time of one execution of the decode program (the one
        that runs the latent decode kernel) or of the prefill program (the
        others that run the grouped matmul)."""
        runs = [t for label, ts in self.trace.get("program_s", {}).items()
                for t in ts
                if ("paged_mla_decode" in label) == (kind == "decode")
                and "grouped_matmul" in label]
        return 1e3 * sum(runs) / len(runs) if runs else None


def roofline_pct(ctx, kernel: str, work: str) -> Optional[float]:
    """``ctx.roofline_pct`` of ``kernel`` over the work ``ctx.<work>()``
    counts, or None where ``ctx`` is not this module's Context."""
    count = getattr(ctx, work, None)
    if count is None:
        return None
    return ctx.roofline_pct(kernel, count())

