"""Time to first token, 90th percentile over every request due in the
window, each timed from its due time (see ``stats.ttfts``)."""
from chipbench import stats


def read(win):
    return stats.percentile(stats.ttfts(win), 90)
