"""The program-span tool: idle time by the driving thread's ckv/ spans."""
from __future__ import annotations

import json

import pytest

from chipbench_tiny import RUN, steer
from chipbench import program_spans as PS
from chipbench import trace_reduce as TR


def _trace():
    # device ops [0,10] [30,44] [60,62] [90,93]; the window is [0,100]; the
    # driver runs in [5,85], sleeps for an arrival in [10,30] and reads a
    # result in [50,55]; an I/O thread reads the store in [40,100]
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": [
            ["%fusion.1 = bf16[8]{0} fusion(%a)", 0, 10],
            ["%fusion.2 = bf16[8]{0} fusion(%a)", 30, 14],
            ["%fusion.3 = bf16[8]{0} fusion(%a)", 60, 2],
            ["%fusion.4 = bf16[8]{0} fusion(%a)", 90, 3]]}]},
        {"name": "/host:CPU", "lines": [
            {"name": "python", "events": [
                [TR.WINDOW_SPAN, 0, 100],
                ["ckv/sched.run", 5, 80],
                ["ckv/sched.idle", 10, 20],
                ["ckv/step.part_b", 44, 16],
                ["ckv/step.d2h", 50, 5]]},
            {"name": "io", "events": [["ckv/store.read", 40, 60]]}]}]}


def test_idle_goes_to_the_driving_threads_innermost_program_span():
    r = PS.idle_by_span(_trace())
    assert r["window_s"] == pytest.approx(100e-9)
    assert r["busy_s"] == pytest.approx(29e-9)
    assert dict(map(tuple, r["idle_gaps"])) == pytest.approx({
        "ckv/sched.idle": 20e-9,  # [10,30]
        "ckv/step.d2h": 16e-9,  # [44,60], midpoint 52
        "ckv/sched.run": 28e-9,  # [62,90]: the driver's own time
        PS.OUTSIDE: 7e-9})  # [93,100]
    # idle 71 of 100, less the 20 asleep waiting for an arrival
    assert r["idle_serving_pct"] == pytest.approx(100 * (71 - 20) / 80)


def test_no_driving_thread_puts_every_gap_outside():
    t = _trace()
    t["planes"][1]["lines"][0]["events"] = [[TR.WINDOW_SPAN, 0, 100]]
    r = PS.idle_by_span(t)
    assert dict(map(tuple, r["idle_gaps"])) == pytest.approx(
        {PS.OUTSIDE: 71e-9})
    assert r["idle_serving_pct"] == pytest.approx(71.0)


def test_tool_serves_windows_off_spans_and_traced(monkeypatch, capsys):
    from repro import obs

    cell = steer(monkeypatch, "reprefill")
    monkeypatch.setattr(RUN, "use_compile_cache", lambda serve: None)
    rc = PS.main(["--workload", cell, "--seeds", str(2**31 + 5),
                  "--seconds", "1"])
    out, _ = capsys.readouterr()
    assert rc == 0 and obs.span("a") is obs.span("b")  # spans off again
    lines = [json.loads(ln) for ln in out.strip().splitlines()]
    assert [ln["mode"] for ln in lines[:3]] == ["off", "spans", "traced"]
    assert lines[-1]["windows"] == lines[:3]
    for ln in lines[:3]:
        assert {"ttft_p50_ms", "host_syncs_per_req.reprefill",
                "h2d_mb_per_req.reprefill"} <= set(ln["metrics"])
        assert ("spans" in ln) == (ln["mode"] == "traced")
