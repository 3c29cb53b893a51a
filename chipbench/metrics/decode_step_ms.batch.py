"""Steps: mean device time of one execution of the decode program (the
program that runs the paged decode kernel), from the trace."""


def read(ctx):
    return ctx.program_ms("paged_decode")
