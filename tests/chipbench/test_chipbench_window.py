"""Metric arithmetic over a window's records, and the one clock of TTFT."""
from __future__ import annotations

import time
import types

import numpy as np
import pytest

from chipbench_tiny import MIXES, RUN, tiny_conf
from chipbench import cell as C
from chipbench import stats


def _rec(rid, due, admitted, ttft, decode_times=(), done=True):
    trace = types.SimpleNamespace(ttft=ttft, decode_times=list(decode_times))
    r = C.Record(rid, due, np.zeros(8, int), len(decode_times),
                 admitted=admitted, trace=trace, done=done)
    return r


def _run(requests, t0=100.0, seconds=10.0):
    return types.SimpleNamespace(requests=requests, t0=t0, seconds=seconds,
                                 t_close=t0 + seconds, batch_sizes=[])


def test_ttft_percentile_takes_every_request_due_in_the_window():
    # due times 100..109; the last finishes after the close and still counts;
    # one due after the close does not
    recs = [_rec(i, 100.0 + i, 100.0 + i + 0.1 * i, 0.5) for i in range(10)]
    recs.append(_rec(10, 110.5, 110.5, 9.0))
    run = _run(recs)
    want = [0.1 * i + 0.5 for i in range(10)]
    assert stats.ttfts(run) == pytest.approx(want)
    assert RUN.reader("ttft_p50_ms").read(run) == pytest.approx(
        np.percentile(want, 50) * 1e3)
    assert RUN.reader("queue_ms_p50.reprefill").read(run) == pytest.approx(
        np.percentile([0.1 * i for i in range(10)], 50) * 1e3)


def test_tokens_and_gaps_count_inside_the_window_only():
    # first token at 99 (before the window), decode tokens every 0.5 s
    a = _rec(0, 97.0, 97.0, 2.0, decode_times=[99.5, 100.0, 100.5, 111.0])
    b = _rec(1, 100.0, 100.0, 1.0, decode_times=[102.0, 104.0])
    run = _run([a, b])
    assert stats.tokens_in_window(run) == [100.0, 100.5, 101.0, 102.0, 104.0]
    assert RUN.reader("output_tok_s").read(run) == pytest.approx(5 / 10)
    assert sorted(stats.gaps_in_window(run)) == [0.5, 1.0, 2.0]
    assert RUN.reader("itl_p50_ms").read(run) == pytest.approx(1.0e3)
    assert RUN.reader("itl_p95_ms.decode").read(run) == pytest.approx(
        np.percentile([0.5, 1.0, 2.0], 95) * 1e3)


def test_decode_batch_mean_counts_lone_steps_as_iterations():
    a = _rec(0, 100.0, 100.0, 1.0, decode_times=[101, 102, 103, 104])
    b = _rec(1, 100.0, 100.0, 1.0, decode_times=[101, 102])
    run = _run([a, b])
    run.batch_sizes = [2, 2]  # two batched steps, then a's two lone steps
    assert RUN.reader("decode_batch_mean.decode").read(run) == \
        pytest.approx(6 / 4)


def test_ttft_is_unchanged_by_a_warm_up_on_the_same_executor():
    """The executor's clock starts at its construction; a window that opens
    seconds later must not count that age in any request's TTFT."""
    conf = tiny_conf()
    cell = C.Cell(conf, dict(MIXES["reprefill"]), 2**31 + 5,
                  RUN.architecture(RUN.load_bench(), conf))
    cell.build()
    cell.warm_up()
    time.sleep(1.5)  # the executor is now older than any request's service
    run = cell.run_window(1.0)
    cell.release()
    rec = types.SimpleNamespace(**run)
    ttft = stats.ttfts(rec)
    assert run["t0"] > 1.5
    assert len(ttft) == len(run["requests"]) > 0
    assert 0 < max(ttft) < 1.0
    for r in run["requests"]:
        assert r.due <= r.admitted <= r.first_token_at


def test_knee_is_the_highest_rate_whose_ttft_does_not_grow():
    from chipbench import calibrate as CAL

    # thirds by due time: 0.4 s, 0.45 s, then 0.6 s at the end
    recs = [_rec(i, 100.0 + i, 100.0 + i, t)
            for i, t in enumerate([0.4] * 3 + [0.45] * 3 + [0.6] * 3)]
    run = dict(requests=recs, t_end=110.0, t_close=109.0)
    line = CAL.window_line(run)
    assert line["done"] == 9 and line["drain_s"] == pytest.approx(1.0)
    assert line["first_third_p50_ms"] == pytest.approx(400.0)
    assert line["last_third_p50_ms"] == pytest.approx(600.0)
    assert line["growth"] == pytest.approx(1.5)
    lines = [{"rate": 0.5, "growth": 1.0}, {"rate": 0.5, "growth": 1.3},
             {"rate": 0.5, "growth": 1.1}, {"rate": 0.7, "growth": 1.2},
             {"rate": 0.7, "growth": 1.4}, {"rate": 0.7, "growth": 1.3},
             {"rate": 0.9, "growth": 1.1}, {"rate": 0.9, "growth": 2.0},
             {"rate": 0.9, "growth": 1.0}]
    # 0.7's median growth 1.3 is over the limit; 0.9's 1.1 is under it
    assert CAL.knee(lines) == 0.9
    assert CAL.knee(lines[3:6]) == 0.0
