"""Storage: median, over the finished requests, of the milliseconds each
waited on unfinished I/O before its first token (the program's
``ReprefillTrace.io_stall_s``, kept by the Scheduler's real driver: from the
first poll that finds a wait pending to the transfer's completion)."""
from chipbench import stats


def read(run):
    stalls = [getattr(t, "io_stall_s", None) for t in stats.traces(run)]
    if not stalls or None in stalls:  # a program that keeps no such counter
        return None
    return stats.percentile(stalls, 50) * 1e3
