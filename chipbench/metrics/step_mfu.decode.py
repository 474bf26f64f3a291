"""Model step: model FLOPs of every token emitted inside the window (the
re-prefill behind a first token, one decode step behind each later token),
counted from shapes by the configuration's architecture module, over the
window, as a share of the chip's peak."""


def read(run):
    arch, work = run.arch, 0.0
    for r in run.requests:
        t = r.trace
        if t is None or not t.ttft:
            continue
        periods = len(t.selected_per_period)
        selected = len(t.selected_per_period[0]) * run.page
        s = len(r.suffix)
        if run.t0 <= r.first_token_at <= run.t_close:
            work += arch.reprefill_flops(run.dims, s, selected,
                                         run.prefix_len, periods)
        for i, at in enumerate(t.decode_times):
            if run.t0 <= at <= run.t_close:
                work += arch.decode_token_flops(run.dims, selected + s + i + 1)
    return 100.0 * work / (run.seconds * run.peak_flops) if work else None
