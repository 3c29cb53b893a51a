"""Scheduler: mean seats that decode per decode call in the traced window,
counted from the tokens each step hands back."""


def read(ctx):
    calls = [len(c) for c in ctx.decode_calls if c]
    return sum(calls) / len(calls) if calls else None
