"""95th percentile of every gap between a request's consecutive output
tokens, both emitted inside the window, all requests pooled: the tail that
the driver's interleaving of re-prefill ops with decode passes sets.  Read
in the traced run, whose profiler slows the host."""
from chipbench import stats


def read(run):
    g = stats.gaps_in_window(run)
    return stats.percentile(g, 95) * 1e3 if g else None
