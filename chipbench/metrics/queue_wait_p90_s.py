"""Scheduler: 90th percentile of queue wait (admit minus enqueue, the
scheduler's own stamps) over requests submitted in the traced window."""
from chipbench.stats import percentile


def read(ctx):
    return percentile(ctx.queue_waits, 90) if ctx.queue_waits else None
