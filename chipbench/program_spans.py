#!/usr/bin/env python3
"""Device idle time by the program's own ``ckv/`` spans, and their cost.

    python3 chipbench/program_spans.py --workload <cell> --seeds <n> <n> ... \
        --seconds <s>

One process builds and warms a cell as ``run.py`` does (the weights and the
prefix from the first seed), then serves one window per seed and mode, in
the order off, spans, traced for the first seed, the reverse for the next,
and so on:

- ``off``: the program's spans off and no profiler (as ``--trace 0``);
- ``spans``: the program's spans on (``repro.obs.enable``), no profiler:
  what the spans alone cost;
- ``traced``: the spans on and a profiler trace of the window, taken with
  ``repro.obs.profile_options()`` (no Python tracer), reduced here: device
  idle time put down to the innermost ``ckv/`` span of the thread that
  drives the Scheduler (the one holding ``ckv/sched.run``), and the part of
  the window outside that span.

Each window prints one JSON line: the cell's end-to-end metrics and the
program-counter metrics of ``BENCHMARK.json``, and for a traced window the
idle table and ``idle_serving_pct``, the device's idle share of the window
less the driver's ``ckv/sched.idle`` spans.  This is a tool for reading
where the time goes; the benchmark's own runs do not use it, and it checks
no result against the reference.
"""
from __future__ import annotations

import argparse
import functools
import glob
import json
import os
import pathlib
import shutil
import sys
import tempfile
import time
import types

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))

from chipbench import run as RUN  # noqa: E402
from chipbench import trace_reduce as TR  # noqa: E402

PREFIX = "ckv/"
RUN_SPAN = PREFIX + "sched.run"
IDLE_SPAN = PREFIX + "sched.idle"
OUTSIDE = "outside " + RUN_SPAN


def load(trace_dir: str) -> dict:
    """The newest trace under `trace_dir` in ``trace_reduce``'s plain form,
    keeping the device operations, the benchmark's window span and every
    ``ckv/`` span."""
    import jax

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    prof = jax.profiler.ProfileData.from_file(paths[-1])
    planes = []
    for plane in prof.planes:
        device = plane.name.startswith("/device:")
        lines = []
        for line in plane.lines:
            if device and line.name != TR.OP_LINE:
                continue
            events = [[e.name, float(e.start_ns), float(e.duration_ns)]
                      for e in line.events
                      if device or e.name.startswith(PREFIX)
                      or e.name == TR.WINDOW_SPAN]
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def driving_spans(trace: dict) -> list:
    """``ckv/`` spans of the line holding ``ckv/sched.run``, as
    (name, start, dur, True)."""
    for p in trace["planes"]:
        if p["name"].startswith("/device:"):
            continue
        for ln in p["lines"]:
            if any(e[0] == RUN_SPAN for e in ln["events"]):
                return [(e[0], e[1], e[2], True) for e in ln["events"]
                        if e[0].startswith(PREFIX)]
    return []


def idle_by_span(trace: dict) -> dict:
    """Device idle seconds in the window by the driving thread's innermost
    ``ckv/`` span (``OUTSIDE`` where none is open), averaged over devices;
    with ``window_s``, ``busy_s`` and ``idle_serving_pct``."""
    window = TR.window_of(trace)
    devices = TR.device_ops(trace)
    if window is None or not devices:
        return {}
    lo, hi = window
    spans = driving_spans(trace)
    idle_spans = TR._union(TR._clip([(s, s + d) for n, s, d, _ in spans
                                     if n == IDLE_SPAN], lo, hi))
    sleep = sum(e - s for s, e in idle_spans)
    gaps, busy, idle_in_sleep = {}, 0.0, 0.0
    for ops in devices.values():
        ivs = TR._union(TR._clip([(e[1], e[1] + e[2]) for e in ops], lo, hi))
        busy += sum(e - s for s, e in ivs)
        edges = [lo] + [x for iv in ivs for x in iv] + [hi]
        idle = [(s, t) for s, t in zip(edges[::2], edges[1::2]) if t > s]
        names = TR._label(spans, [(s + t) / 2 for s, t in idle])
        for name, (s, t) in zip(names, idle):
            key = name or OUTSIDE
            gaps[key] = gaps.get(key, 0.0) + (t - s)
        idle_in_sleep += sum(max(0.0, min(t, b) - max(s, a))
                             for s, t in idle for a, b in idle_spans)
    n = len(devices)
    window_s = hi - lo
    idle_s = window_s * n - busy
    serving = (window_s - sleep) * n
    return {"window_s": window_s / 1e9, "busy_s": busy / n / 1e9,
            "idle_serving_pct": (100.0 * (idle_s - idle_in_sleep) / serving
                                 if serving > 0 else None),
            "idle_gaps": [[k, v / n / 1e9] for k, v in
                          sorted(gaps.items(), key=lambda kv: -kv[1])]}


MODES = ("off", "spans", "traced")


def window(cell, seconds: float, seed: int, mode: str,
           metrics: list, peaks: dict) -> dict:
    """Serve one window of `seed`'s traffic; returns its JSON line."""
    import jax

    from repro import obs

    cell.seed = seed
    traced = mode == "traced"
    trace_dir = tempfile.mkdtemp(prefix="ckv_spans_") if traced else None
    start_trace = jax.profiler.start_trace
    if traced:  # the window's trace as the program's exporter takes it
        jax.profiler.start_trace = functools.partial(
            start_trace, profiler_options=obs.profile_options())
    obs.enable(mode != "off")
    try:
        run = cell.run_window(seconds, trace_dir=trace_dir)
        spans = idle_by_span(load(trace_dir)) if traced else None
    finally:
        obs.enable(False)
        jax.profiler.start_trace = start_trace
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)
    rec = types.SimpleNamespace(
        **run, setup_s=0.0, dims=cell.dims, arch=cell.arch,
        peak_flops=peaks["bf16_flops_per_s"], peak_bw=peaks["hbm_bytes_per_s"],
        page=cell.sysconf["chunk_tokens"], prefix_len=len(cell.prefix),
        trace=None, raw_trace=None)
    values = {}
    for name in metrics:
        v = RUN.reader(name).read(rec)
        if v is not None:
            values[name] = float(v)
    line = {"seed": seed, "mode": mode, "metrics": values}
    if spans is not None:
        line["spans"] = spans
    return line


def main(argv=None) -> int:
    t_start = time.perf_counter()
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    p.add_argument("--seconds", type=float, required=True)
    args = p.parse_args(argv)
    from chipbench import cell as C

    bench = RUN.load_bench()
    try:
        cell_entry, conf, mix, _, arch = RUN.resolve(bench, args.workload,
                                                     False)
        device = RUN.device_info(cell_entry["chips"])
        RUN.use_compile_cache(RUN.import_system())
    except RUN.SetupError as e:
        RUN.say(f"program_spans: {e}")
        return 2
    wanted = [m for m in bench["end_to_end"] + bench["per_layer"]
              if m["source"] in ("host_clock", "program_counter")
              and args.workload in m.get("workloads", [args.workload])
              and m["name"] != "setup_s"]
    names = [m["name"] for m in wanted]
    cell = C.Cell(conf, mix, args.seeds[0], arch)
    cell.build()
    cell.warm_up()
    RUN.say(f"set-up {time.perf_counter() - t_start:.3f} s")
    lines = []
    for i, seed in enumerate(args.seeds):
        for mode in (MODES if i % 2 == 0 else MODES[::-1]):
            line = window(cell, args.seconds, seed, mode, names,
                          device["peaks"])
            print(json.dumps(line), flush=True)
            lines.append(line)
    cell.release()
    print(json.dumps({"workload": args.workload, "device": {
        k: device[k] for k in ("platform", "kind", "count")},
        "windows": lines}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
