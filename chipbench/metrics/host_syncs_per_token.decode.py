"""Model step: blocking device-to-host reads per emitted token: the
requests' shares of the program's ``RealCompute.d2h_syncs`` (kept by the
Scheduler's real driver in ``ReprefillTrace.d2h_syncs``; re-prefills and
decode steps alike) over the tokens those requests emitted.  In a backlog
window every step runs inside the window."""


def read(run):
    recs = [r for r in run.requests if r.trace is not None]
    syncs = [getattr(r.trace, "d2h_syncs", None) for r in recs]
    tokens = sum(len(r.token_times()) for r in recs)
    if not tokens or None in syncs:  # a program that keeps no such counter
        return None
    return sum(syncs) / tokens
