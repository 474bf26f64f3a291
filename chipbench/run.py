#!/usr/bin/env python3
"""Run one cell of ``BENCHMARK.json`` on the chip and print its result line.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process: check the device, build the cell's model and prefix store from
the seed, warm every shape, serve the traffic mix for ``--seconds``, then
compare what was served with the plain reference.  Weights, reference and
FLOP counts come from the configuration's architecture module
(``architecture``).  The last line of
standard output is the JSON result; with ``--trace 0`` its metrics are the
cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics, read
from a profiler trace of the window and from the run's counters.  The last
lines of standard error give each compared number beside its limit.

Exits non-zero, printing no result, when JAX finds no accelerator that
``peaks.json`` knows, fewer chips than the cell asks for, or no system under
test beside the benchmark.

``--control 1`` runs the same cell, window and comparison with the fp8
control in the program's place (``cell.check``): it has to come out not
correct.  The benchmark's own runs never pass it.
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import pathlib
import re
import shutil
import sys
import tempfile
import time
import types

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent


# A configuration file's key that names its architecture module (a path from
# the repository root), the module without the key, and what the module
# gives (the contract is in the default module's docstring)
ARCH_KEY = "reference"
DEFAULT_ARCH = "chipbench/reference.py"
ARCH_NAMES = ("dims_of", "program_config", "make_params", "Reference",
              "reprefill_flops", "decode_token_flops", "decode_attention_cost")


class SetupError(Exception):
    """The run cannot start here; the message says why."""


def load_bench(root: pathlib.Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def resolve(bench: dict, workload: str, trace: bool):
    """(cell, configuration file, traffic mix, metric entries, architecture
    module) of a cell."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SetupError(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    conf = json.loads((ROOT / entry["file"]).read_text())
    mix = json.loads((HERE / "traffic" / f"{cell['traffic']}.json").read_text())
    wanted = bench["per_layer"] if trace else bench["end_to_end"]
    metrics = [m for m in wanted
               if "workloads" not in m or workload in m["workloads"]]
    return cell, conf, mix, metrics, architecture(bench, conf)


def architecture(bench: dict, conf: dict):
    """The module that defines the configuration's architecture: the file
    that its ``"reference"`` key names, or ``chipbench/reference.py``.  The
    file has to lie under one of the benchmark's ``paths`` and give every
    name of ``ARCH_NAMES``.  A file is loaded once a process."""
    rel = conf.get(ARCH_KEY, DEFAULT_ARCH)
    where = f"configuration key {ARCH_KEY!r}: {rel!r}"
    if not isinstance(rel, str):
        raise SetupError(f"{where} is not a path")
    path = (ROOT / rel).resolve()
    if not any(path.is_relative_to((ROOT / p).resolve())
               for p in bench["paths"]):
        raise SetupError(f"{where} lies outside the benchmark's paths "
                         f"{bench['paths']}")
    if not path.is_file():
        raise SetupError(f"{where} does not exist")
    name = "chipbench_arch_" + re.sub(r"\W", "_", rel)
    mod = sys.modules.get(name)
    if mod is None or mod.__file__ != str(path):
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
    missing = [n for n in ARCH_NAMES if not hasattr(mod, n)]
    if missing:
        raise SetupError(f"{where} does not define {', '.join(missing)}")
    return mod


def reader(name: str):
    """The metric's reader module, ``metrics/<name>.py``."""
    path = HERE / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def device_info(chips: int) -> dict:
    """Platform, kind and count of the devices, with the kind's peaks."""
    import jax

    peaks = json.loads((HERE / "peaks.json").read_text())
    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise SetupError(f"JAX finds no device: {e}") from e
    d = devices[0]
    if d.platform != "tpu":
        raise SetupError(f"JAX finds no TPU (platform {d.platform!r})")
    if d.device_kind not in peaks:
        raise SetupError(f"no peaks for device kind {d.device_kind!r} in "
                         f"peaks.json")
    if len(devices) < chips:
        raise SetupError(f"the cell needs {chips} chips, JAX finds "
                         f"{len(devices)}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices), "peaks": peaks[d.device_kind]}


def import_system():
    src = ROOT / "src"
    if not (src / "repro").is_dir():
        raise SetupError(f"no system under test beside the benchmark "
                         f"({src / 'repro'} is missing)")
    sys.path.insert(0, str(src))
    from repro.launch import serve

    return serve


def use_compile_cache(serve):
    """The serving code's persistent compile cache (a fixed path in the
    checkout unless ``JAX_COMPILATION_CACHE_DIR`` says otherwise), keeping
    small programs too so that a cell's second run compiles nothing."""
    import jax

    serve.use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def say(*a):
    print(*a, file=sys.stderr, flush=True)


def verdict(got: dict, limits: dict, failed: int):
    """(correct, each compared number beside its limit) of a check."""
    checks = {name: {"value": got[name], "limit": limit}
              for name, limit in limits.items()}
    correct = (failed == 0 and got["tokens"] > 0
               and all(c["value"] <= c["limit"] for c in checks.values()))
    return correct, checks


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             t_start: float, control: bool = False) -> dict:
    """Everything after the argument parsing; returns the result object."""
    from chipbench import cell as C
    from chipbench import trace_reduce as TR

    cell_entry, conf, mix, metrics, arch = resolve(load_bench(), workload,
                                                   trace)
    device = device_info(cell_entry["chips"])
    use_compile_cache(import_system())
    counter = C.CompileCounter()

    cell = C.Cell(conf, mix, seed, arch)
    steps = cell.build()
    t = time.perf_counter()
    cell.warm_up()
    steps["warm_up"] = time.perf_counter() - t
    setup_s = time.perf_counter() - t_start
    say(f"set-up {setup_s:.3f} s: " + ", ".join(
        f"{k} {v:.3f} s" for k, v in steps.items())
        + f"; backend compiles {counter.compiled} ({counter.compile_s:.3f} s),"
        f" programs lowered {counter.lowered}")

    lowered0, compiled0 = counter.lowered, counter.compiled
    trace_dir = tempfile.mkdtemp(prefix="chipbench_trace_") if trace else None
    try:
        run = cell.run_window(seconds, trace_dir=trace_dir)
        say(f"compiles in the window: {counter.lowered - lowered0} programs "
            f"lowered, {counter.compiled - compiled0} backend compiles")
        memory = C.peak_memory_bytes()
        reduced = raw = None
        if trace:
            raw = TR.load(trace_dir)
            reduced = TR.reduce(raw)
    finally:
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)

    recs = run["requests"]
    due = [r for r in recs if run["t0"] <= r.due < run["t_close"]]
    if run["backlog"]:
        attempted = sum(1 for r in due if r.admitted is not None)
        failed = 0
        say(f"backlog outlasted the window: "
            f"{'yes' if run['pending_at_close'] else 'no'} "
            f"({run['pending_at_close']} of {len(recs)} requests never "
            f"admitted)")
    else:
        attempted = len(due)
        failed = sum(1 for r in due if not r.done)
    peaks = device["peaks"]
    rec = types.SimpleNamespace(
        **run, setup_s=setup_s, dims=cell.dims, arch=arch,
        peak_flops=peaks["bf16_flops_per_s"], peak_bw=peaks["hbm_bytes_per_s"],
        page=conf["system"]["chunk_tokens"], prefix_len=len(cell.prefix),
        trace=reduced, raw_trace=raw)
    values = {}
    for m in metrics:
        v = reader(m["name"]).read(rec)
        if v is not None:
            values[m["name"]] = {"value": float(v), "unit": m["unit"]}

    cell.release()
    got = C.check(cell, run, control=control)
    correct, checks = verdict(got, conf["limits"], failed)
    out_device = {"platform": device["platform"], "kind": device["kind"],
                  "count": device["count"], "memory_peak_bytes": memory}
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": values, "device": out_device}
    if trace:
        out_device["busy_s"] = reduced["busy_s"]
        out_device["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["check"] = checks
    say(f"checked {got['tokens']} served tokens of {got['requests']} "
        f"requests{' (fp8 control)' if control else ''} against the "
        f"reference; widest served-token gap below "
        f"the reference's best {got['token_gap']!r} (not compared)")
    for name, c in checks.items():
        say(f"check {name} {c['value']!r} limit {c['limit']!r}")
    return result


def main(argv=None) -> int:
    t_start = time.perf_counter()
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace), t_start, bool(args.control))
    except SetupError as e:
        say(f"chipbench: {e}")
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
