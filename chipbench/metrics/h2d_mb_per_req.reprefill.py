"""Model step: megabytes of host arrays uploaded to the device per finished
request (each request's share of the program's ``RealCompute.h2d_bytes``,
kept by the Scheduler's real driver in ``ReprefillTrace.h2d_bytes``)."""
from chipbench import stats


def read(run):
    traces = stats.traces(run)
    up = [getattr(t, "h2d_bytes", None) for t in traces]
    if not traces or None in up:  # a program that keeps no such counter
        return None
    return sum(up) / len(traces) / 1e6
