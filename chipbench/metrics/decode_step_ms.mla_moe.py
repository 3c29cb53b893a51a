"""Steps: mean device time of one execution of the decode program (the
program that runs the paged MLA decode kernel), from the trace."""


def read(ctx):
    step_ms = getattr(ctx, "step_ms", None)
    return step_ms("decode") if step_ms else None
