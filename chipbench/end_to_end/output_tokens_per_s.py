"""Every token generated in the window's steps over the window's length."""
from chipbench import stats


def read(win):
    return stats.output_rate(win)
