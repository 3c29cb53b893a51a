"""Steps: mean device time of one execution of the prefill program (a
program that runs the grouped matmul and not the MLA decode kernel),
from the trace."""


def read(ctx):
    step_ms = getattr(ctx, "step_ms", None)
    return step_ms("prefill") if step_ms else None
