"""Steps: mean device time of one execution of the prefill program (the
program that runs the ragged prefill kernel, ``jit_paged_prefill_step``),
from the trace."""


def read(ctx):
    return ctx.program_ms("ragged_prefill")
