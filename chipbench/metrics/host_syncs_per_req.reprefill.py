"""Model step: blocking device-to-host reads per finished request (each
request's share of the program's ``RealCompute.d2h_syncs``, kept by the
Scheduler's real driver in ``ReprefillTrace.d2h_syncs``)."""
from chipbench import stats


def read(run):
    traces = stats.traces(run)
    syncs = [getattr(t, "d2h_syncs", None) for t in traces]
    if not traces or None in syncs:  # a program that keeps no such counter
        return None
    return sum(syncs) / len(traces)
