"""Median of every gap between a request's consecutive output tokens, both
emitted inside the window, all requests pooled."""
from chipbench import stats


def read(run):
    g = stats.gaps_in_window(run)
    return stats.percentile(g, 50) * 1e3 if g else None
