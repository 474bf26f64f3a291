"""Kernel: the paged decode-attention kernel's least time, the larger of its
FLOPs over peak FLOP/s and its bytes over peak bandwidth (counted from the
shapes of the calls made in the traced window, pages actually read, by the
configuration's architecture module), over its device time in the trace."""
from chipbench import trace_reduce as TR

KERNEL = "decode_attention"


def read(run):
    if run.raw_trace is None or not run.kernel_calls:
        return None
    kernel_s = TR.op_seconds(run.raw_trace, KERNEL)
    if kernel_s <= 0:
        return None
    least = 0.0
    for at, lengths, n_res, width, layers, page in run.kernel_calls:
        if not run.t0 <= at <= run.t_close:
            continue
        fl, by = run.arch.decode_attention_cost(
            run.dims, lengths, page=page, resident_pages=n_res,
            table_width=width)
        least += layers * max(fl / run.peak_flops, by / run.peak_bw)
    return 100.0 * least / kernel_s
