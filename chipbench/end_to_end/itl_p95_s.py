"""Gap between tokens, 95th percentile over every gap of every request
due in the window, pooled, as the benchmark's loop receives them."""
from chipbench import stats


def read(win):
    return stats.percentile(stats.itls(win), 95)
