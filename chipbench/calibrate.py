#!/usr/bin/env python3
"""Find the knee of a Poisson mix: run by hand on the chip.

    python3 chipbench/calibrate.py --workload <cell> --seconds <s> --seeds 1,2 --rates 0.5,0.7,0.9

For each seed the cell is built and warmed once, then the mix is served at
each offered rate in turn, lowest first, one window each.  Every window
prints one line: the requests and how many were served, the TTFT median
and 95th percentile, the medians of the first and last thirds by due time,
their ratio, and the seconds the drain took after the close.

The knee is the highest rate at which TTFT does not grow across the window:
the median over the seeds of ``last_third_p50 / first_third_p50`` stays at
or under ``GROWTH`` (1.25).  A final line names it; the cell's mix offers
0.8 of it.

The correctness limits are set from ``run.py`` runs: the program's own, and
``--control 1`` runs in which the fp8 control takes the program's place.
"""
from __future__ import annotations

import argparse
import gc
import json
import pathlib
import statistics
import sys
import time

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))

from chipbench import run as RUN  # noqa: E402

# last-third over first-third TTFT median above which TTFT grows
GROWTH = 1.25


def emit(obj):
    print(json.dumps(obj), flush=True)


def window_line(run: dict) -> dict:
    """TTFT across one window of a Poisson mix."""
    from chipbench import stats

    recs = sorted((r for r in run["requests"]
                   if r.first_token_at is not None), key=lambda r: r.due)
    ttft = [r.first_token_at - r.due for r in recs]
    third = max(1, len(ttft) // 3)
    first = statistics.median(ttft[:third])
    last = statistics.median(ttft[-third:])
    return {"requests": len(run["requests"]), "done": len(ttft),
            "ttft_p50_ms": stats.percentile(ttft, 50) * 1e3,
            "ttft_p95_ms": stats.percentile(ttft, 95) * 1e3,
            "first_third_p50_ms": first * 1e3,
            "last_third_p50_ms": last * 1e3,
            "growth": last / first,
            "drain_s": run["t_end"] - run["t_close"]}


def knee(lines) -> float:
    """The highest rate whose median growth over the seeds stays at or
    under GROWTH, or 0.0 if none does."""
    by_rate = {}
    for ln in lines:
        by_rate.setdefault(ln["rate"], []).append(ln["growth"])
    steady = [r for r, g in by_rate.items() if statistics.median(g) <= GROWTH]
    return max(steady, default=0.0)


def sweep(workload: str, seeds, rates, seconds: float):
    from chipbench import cell as C

    cell_entry, conf, mix, _, arch = RUN.resolve(RUN.load_bench(), workload,
                                                 False)
    RUN.device_info(cell_entry["chips"])
    RUN.use_compile_cache(RUN.import_system())
    lines = []
    for seed in seeds:
        cell = C.Cell(conf, mix, seed, arch)
        t = time.perf_counter()
        cell.build()
        cell.warm_up()
        emit({"seed": seed, "setup_s": time.perf_counter() - t})
        for rate in sorted(rates):
            cell.mix = dict(mix, rate_per_s=rate)
            ln = {"seed": seed, "rate": rate,
                  **window_line(cell.run_window(seconds))}
            lines.append(ln)
            emit(ln)
        cell.release()
        del cell
        gc.collect()
    emit({"knee_per_s": knee(lines), "growth_limit": GROWTH})


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--rates", required=True)
    a = p.parse_args(argv)
    try:
        sweep(a.workload, [int(x) for x in a.seeds.split(",")],
              [float(x) for x in a.rates.split(",")], a.seconds)
    except RUN.SetupError as e:
        RUN.say(f"calibrate: {e}")
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
