"""Readers of the program's own counters, and the trace reduction with the
program's ckv/ spans in the trace."""
from __future__ import annotations

import copy
import json
import types

import numpy as np
import pytest

from chipbench_tiny import ROOT, RUN, steer
from chipbench import cell as C
from chipbench import trace_reduce as TR

SEED = 2**31 + 29


def _rec(rid, *, done=True, decode_times=(), **counters):
    trace = types.SimpleNamespace(ttft=0.5, decode_times=list(decode_times),
                                  **counters)
    return C.Record(rid, 100.0, np.zeros(8, int), len(decode_times),
                    admitted=100.0, trace=trace, done=done)


def _run(requests):
    return types.SimpleNamespace(requests=requests, t0=100.0, seconds=10.0,
                                 t_close=110.0)


def test_reprefill_counter_readers():
    recs = [_rec(0, io_stall_s=0.010, d2h_syncs=11, h2d_bytes=40e6),
            _rec(1, io_stall_s=0.030, d2h_syncs=11, h2d_bytes=60e6),
            _rec(2, io_stall_s=0.020, d2h_syncs=12, h2d_bytes=50e6),
            # admitted, not finished: left out like the store's bytes
            _rec(3, done=False, io_stall_s=9.0, d2h_syncs=5, h2d_bytes=1e9)]
    run = _run(recs)
    assert RUN.reader("io_stall_ms_p50.reprefill").read(run) == \
        pytest.approx(20.0)
    assert RUN.reader("host_syncs_per_req.reprefill").read(run) == \
        pytest.approx(34 / 3)
    assert RUN.reader("h2d_mb_per_req.reprefill").read(run) == \
        pytest.approx(50.0)


def test_decode_counter_reader_counts_every_emitted_token():
    # first tokens at 100.5; a: 3 decoded tokens, b: 1, c: in its prefill
    a = _rec(0, decode_times=[101, 102, 103], d2h_syncs=11 + 3 * 9)
    b = _rec(1, decode_times=[101.5], d2h_syncs=11 + 9)
    c = _rec(2, d2h_syncs=6)
    c.trace.ttft = 0.0
    run = _run([a, b, c, C.Record(3, 100.0, np.zeros(8, int), 4)])
    assert RUN.reader("host_syncs_per_token.decode").read(run) == \
        pytest.approx((38 + 20 + 6) / 6)


@pytest.mark.parametrize("name", ["io_stall_ms_p50.reprefill",
                                  "host_syncs_per_req.reprefill",
                                  "h2d_mb_per_req.reprefill",
                                  "host_syncs_per_token.decode"])
def test_readers_find_nothing_in_a_program_without_the_counters(name):
    """A program that keeps none of the counters gives no reading, and the
    reader does not raise."""
    run = _run([_rec(0, decode_times=[101.0]), _rec(1)])
    assert RUN.reader(name).read(run) is None
    assert RUN.reader(name).read(_run([])) is None


def _with_program_spans(trace: dict) -> dict:
    """`trace` with ckv/ spans added on the thread that holds the
    benchmark's spans and on a thread of its own, covering the window."""
    t = copy.deepcopy(trace)
    host = next(p for p in t["planes"]
                if not p["name"].startswith("/device:"))
    main = next(ln for ln in host["lines"]
                if any(e[0].startswith(TR.SPAN_PREFIX) for e in ln["events"]))
    lo, hi = TR.window_of(trace)
    mid = (lo + hi) / 2
    main["events"] += [["ckv/sched.run", lo - 1e3, hi - lo + 2e3],
                       ["ckv/sched.idle", lo, mid - lo],
                       ["ckv/step.d2h", mid, (hi - mid) / 4]]
    host["lines"].append({"name": "io", "events": [
        ["ckv/store.read", lo, hi - lo]]})
    return t


def test_reduction_ignores_the_programs_spans():
    trace = json.loads((ROOT / "tests/chipbench/trace_v5e_decode.json")
                       .read_text())
    spanned = _with_program_spans(trace)
    assert spanned != trace
    assert TR.host_spans(spanned) == TR.host_spans(trace)
    assert TR.reduce(spanned) == TR.reduce(trace)
    assert TR.window_of(spanned) == TR.window_of(trace)
    assert TR.op_seconds(spanned, "decode_attention") == \
        TR.op_seconds(trace, "decode_attention")


@pytest.fixture
def no_compile_cache(monkeypatch):
    monkeypatch.setattr(RUN, "use_compile_cache", lambda serve: None)


@pytest.mark.parametrize("kind,names", [
    ("reprefill", ["io_stall_ms_p50.reprefill", "host_syncs_per_req.reprefill",
                   "h2d_mb_per_req.reprefill"]),
    ("decode", ["host_syncs_per_token.decode"])])
def test_traced_line_reports_the_program_counters(monkeypatch, capsys,
                                                  no_compile_cache, kind,
                                                  names):
    cell = steer(monkeypatch, kind)
    rc = RUN.main(["--workload", cell, "--seed", str(SEED), "--seconds", "2",
                   "--trace", "1"])
    out, _ = capsys.readouterr()
    assert rc == 0
    metrics = json.loads(out.strip().splitlines()[-1])["metrics"]
    assert set(names) <= set(metrics)
    if kind == "reprefill":
        # the tiny model has 4 layers in periods of 2: a probe score read
        # per period, a mass read per layer and the logits read
        assert metrics["host_syncs_per_req.reprefill"]["value"] == \
            pytest.approx(2 + 4 + 1)
        assert metrics["h2d_mb_per_req.reprefill"]["value"] > 0
        assert metrics["io_stall_ms_p50.reprefill"]["value"] >= 0
    else:
        # one read a decode step (the one-program step reads its tokens
        # once), shared by the up to 3 requests of a batched step; the
        # re-prefills' 7 spread over each request's tokens
        per_token = metrics["host_syncs_per_token.decode"]["value"]
        assert 1 / 3 <= per_token <= 4 + 1 + 7
