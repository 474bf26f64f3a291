"""Model step: model FLOPs of the re-prefills the window finished, counted
from their shapes by the configuration's architecture module, over the time
from window start to the last first token, as a share of the chip's peak."""


def read(run):
    work, last = 0.0, None
    for r in run.requests:
        if not r.done:
            continue
        t = r.trace
        periods = len(t.selected_per_period)
        selected = len(t.selected_per_period[0]) * run.page if periods else 0
        work += run.arch.reprefill_flops(run.dims, len(r.suffix), selected,
                                         run.prefix_len, periods)
        last = max(last or 0.0, r.first_token_at)
    if last is None or last <= run.t0:
        return None
    return 100.0 * work / ((last - run.t0) * run.peak_flops)
