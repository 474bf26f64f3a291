"""Program spans and counters on the real serving path (tiny model, CPU).

The spans (``repro.obs``) land in a CPU profiler trace with their names and
arguments, nest strictly on the thread that drives the Scheduler, and cost
no annotation when tracing is off; the counters count what they claim:
host syncs per decode token, uploaded bytes against ``H2DMeter``, and the
time a request waited on unfinished I/O before its first token.
"""
import glob
import os
import time
from concurrent.futures import Future

import jax
import numpy as np
import pytest

from repro import obs
from repro.configs import reduced_config
from repro.core import ContiguousKVEngine, build_real_session
from repro.core.backends import RealCompute
from repro.core.stepplan import ComputeOp, resolve_handle
from repro.models import transformer as T
from repro.serving import Request, Scheduler, summarize
from repro.storage.h2d_meter import H2DMeter
from repro.storage.timing import IOHandle, RealExecutor

N_LAYERS = 2
PREFIX = 128
CHUNK = 16


@pytest.fixture(scope="module")
def model():
    cfg = reduced_config("qwen2.5-7b", n_layers=N_LAYERS)
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    prefix = (np.arange(PREFIX) % cfg.vocab_size).astype(np.int64)
    sess = build_real_session(cfg, params, prefix, chunk_tokens=CHUNK,
                              in_memory=True)
    return cfg, params, sess


@pytest.fixture(autouse=True)
def tracing_off():
    """Every test starts and ends with the spans off."""
    obs.enable(False)
    yield
    obs.enable(False)


def _engine(model, ex=None, budget=0.5, **kw):
    cfg, params, sess = model
    return ContiguousKVEngine(sess, RealCompute(cfg, params),
                              ex or RealExecutor(), budget=budget, period=2,
                              subperiod=1, device_cap=64, host_cap=128, **kw)


def _suffix(cfg, rid, n=24):
    return (np.arange(n) + 3 * rid) % cfg.vocab_size


def _spans_off() -> bool:
    return obs.span("a") is obs.span("b")


def _slow_reads(monkeypatch, sess, delay):
    real = sess.store.read_units

    def slow(layer, units):
        time.sleep(delay)
        return real(layer, units)

    monkeypatch.setattr(sess.store, "read_units", slow)


# -- the switch ----------------------------------------------------------------
def test_span_is_one_shared_null_context_when_off(monkeypatch):
    def refuse(*a, **kw):
        raise AssertionError("TraceAnnotation built with tracing off")

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", refuse)
    assert obs.span("x") is obs.span("y", rid=3)
    obs.enable(True)
    with pytest.raises(AssertionError):
        obs.span("x")


def test_serving_builds_no_annotation_with_tracing_off(model, monkeypatch):
    cfg = model[0]

    def refuse(*a, **kw):
        raise AssertionError("TraceAnnotation built with tracing off")

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", refuse)
    eng = _engine(model)
    done = Scheduler(eng, max_concurrency=2).run(
        [Request(request_id=r, suffix=_suffix(cfg, r), decode_tokens=2)
         for r in range(2)])
    assert all(len(c.trace.decode_times) == 2 for c in done)


# -- spans in a profiler trace ---------------------------------------------------
def _ckv_lines(trace_dir):
    """Host lines holding ckv/ events: [(line name, [(name, start, end,
    stats)])]."""
    path = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                     recursive=True)[-1]
    prof = jax.profiler.ProfileData.from_file(path)
    out = []
    for plane in prof.planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                    dict(e.stats))
                   for e in line.events if e.name.startswith(obs.PREFIX)]
            if evs:
                out.append((line.name, evs))
    return out


@pytest.fixture(scope="module")
def traced_run(model, tmp_path_factory):
    """A served run under the profiler: two requests at once, a third due
    later (the driver idles), store reads slowed (the driver waits on I/O),
    each request decoding two tokens."""
    cfg, _, sess = model
    trace_dir = str(tmp_path_factory.mktemp("obs_trace"))
    reqs = [Request(request_id=r, suffix=_suffix(cfg, r),
                    decode_tokens=2, arrival=0.0 if r < 2 else 2.0)
            for r in range(3)]
    Scheduler(_engine(model), max_concurrency=2).run(reqs[:2])  # compile
    with pytest.MonkeyPatch.context() as mp:
        _slow_reads(mp, sess, 0.01)
        with obs.trace_to(trace_dir):
            done = Scheduler(_engine(model), max_concurrency=2).run(reqs)
    assert _spans_off()
    return _ckv_lines(trace_dir), done


def test_spans_appear_with_their_names_and_arguments(traced_run):
    lines, done = traced_run
    events = [e for _, evs in lines for e in evs]
    names = {e[0] for e in events}
    want = {"sched.run", "sched.admit", "sched.idle", "sched.io_wait",
            "eng.select", "eng.submit", "eng.gather", "eng.cache",
            "store.read", "step.embed", "step.part_a", "step.token_scores",
            "step.part_b", "step.logits", "step.decode_step_batch",
            "step.decode_token", "step.d2h", "step.h2d"}
    assert {obs.PREFIX + n for n in want} <= names
    rids = {c.request.request_id for c in done}
    for name, _, _, stats in events:
        if name.startswith(obs.PREFIX + "eng."):
            assert stats["rid"] in rids
    reads = [e[3] for e in events if e[0] == obs.PREFIX + "store.read"]
    assert {s["rid"] for s in reads} <= rids
    assert all(0 <= s["layer"] < N_LAYERS and s["bytes"] > 0 for s in reads)


def test_spans_nest_strictly_on_the_driving_thread(traced_run):
    lines, _ = traced_run
    driving = [evs for _, evs in lines
               if any(e[0] == obs.PREFIX + "sched.run" for e in evs)]
    assert len(driving) == 1
    stack = []
    for name, start, end, _ in sorted(driving[0],
                                      key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][2] <= start:
            stack.pop()
        if stack:
            assert end <= stack[-1][2], f"{name} crosses {stack[-1][0]}"
        if name.startswith(obs.PREFIX + "sched."):
            assert not any(p[0].startswith(obs.PREFIX + "eng.")
                           for p in stack), f"{name} inside an engine span"
        stack.append((name, start, end))
    run = [e for e in driving[0] if e[0] == obs.PREFIX + "sched.run"][0]
    assert all(run[1] <= e[1] and e[2] <= run[2] for e in driving[0])
    # the store is read on the I/O threads, never on the driving one
    assert not any(e[0] == obs.PREFIX + "store.read" for e in driving[0])


# -- counters -----------------------------------------------------------------
def test_host_syncs_per_b1_decode_token(model):
    cfg = model[0]
    eng = _engine(model)
    be = eng.backend
    plan = eng.plan(_suffix(cfg, 0), request_id=0, decode_tokens=3)
    send, steps = None, []
    while True:
        try:
            op = plan.gen.send(send)
        except StopIteration:
            break
        if isinstance(op, ComputeOp):
            before = be.d2h_syncs
            send = eng.ex.compute(op.fn)
            if op.phase == "decode":
                steps.append(be.d2h_syncs - before)
        else:
            send = resolve_handle(op.handle)
    assert steps == [1] * 3


def test_h2d_bytes_match_the_meter_over_a_reprefill(model):
    cfg = model[0]
    eng = _engine(model)
    be = eng.backend
    with H2DMeter() as meter:
        before = be.h2d_bytes
        eng.reprefill(_suffix(cfg, 1))
    assert meter.total > 0
    assert be.h2d_bytes - before == meter.total


def test_driver_shares_the_backend_counts_among_requests(model):
    """Every sync and uploaded byte of a served run lands on some request,
    batched decode steps included."""
    cfg = model[0]
    eng = _engine(model)
    be = eng.backend
    sched = Scheduler(eng, max_concurrency=3)
    done = sched.run([Request(request_id=r, suffix=_suffix(cfg, r),
                              decode_tokens=3) for r in range(3)])
    assert sum(c.trace.d2h_syncs for c in done) == pytest.approx(
        be.d2h_syncs)
    assert sum(c.trace.h2d_bytes for c in done) == pytest.approx(
        be.h2d_bytes)
    assert all(c.trace.d2h_syncs > 0 for c in done)


class _DoneIO(RealExecutor):
    """Every transfer is finished before the plan can wait on it."""

    def submit_io(self, fn, *, nbytes, n_requests, channel):
        f = Future()
        f.set_result(fn())
        return IOHandle(future=f)


def test_io_stall_is_zero_when_every_unit_is_resident(model):
    cfg = model[0]
    eng = _engine(model, ex=_DoneIO(), budget=1.0)
    sched = Scheduler(eng, max_concurrency=1)
    sched.run([Request(request_id=0, suffix=_suffix(cfg, 0))])
    (c,) = sched.run([Request(request_id=1, suffix=_suffix(cfg, 1))])
    tr = c.trace
    assert tr.misses == 0 and tr.hits_host == 0 and tr.hits_device > 0
    assert tr.io_stall_s == 0.0


def test_io_stall_counts_a_delayed_fetch(model, monkeypatch):
    cfg, _, sess = model
    delay = 0.05
    _slow_reads(monkeypatch, sess, delay)
    eng = _engine(model)
    (c,) = Scheduler(eng, max_concurrency=1).run(
        [Request(request_id=0, suffix=_suffix(cfg, 0), decode_tokens=2)])
    tr = c.trace
    assert tr.misses > 0
    assert delay * 0.5 < tr.io_stall_s <= tr.ttft


# -- one clock ---------------------------------------------------------------------
def test_queue_delay_and_ttft_read_the_run_clock(model):
    """An executor built seconds before the run: queueing delay and TTFT
    still count from each request's due time on the executor clock."""
    cfg = model[0]
    ex = RealExecutor()
    ex._t0 -= 30.0  # as if built half a minute before the run
    eng = _engine(model, ex=ex)
    done = Scheduler(eng, max_concurrency=1).run(
        [Request(request_id=r, suffix=_suffix(cfg, r), arrival=0.2 * r)
         for r in range(2)])
    for c in done:
        assert c.start >= 30.0
        assert 0.0 <= c.queue_delay < 5.0
        assert c.ttft == pytest.approx(c.queue_delay + c.trace.ttft)
        assert c.due <= c.admitted <= c.finish
    s = summarize(done)
    assert 0.0 < s["makespan"] < 15.0
    assert s["mean_queue_delay"] < 5.0


def test_serve_cli_writes_a_trace_of_the_request_loop(tmp_path, capsys,
                                                       monkeypatch):
    from repro.launch import serve

    monkeypatch.setattr(serve, "use_compile_cache", lambda: None)

    trace_dir = str(tmp_path / "trace")
    serve.main(["--reduced", "--n-layers", "2", "--prefix-len", "128",
                "--requests", "2", "--concurrency", "2", "--trace-dir",
                trace_dir])
    assert "profiler trace of the request loop" in capsys.readouterr().out
    names = {e[0] for _, evs in _ckv_lines(trace_dir) for e in evs}
    assert {obs.PREFIX + "sched.run", obs.PREFIX + "step.part_b"} <= names
    assert _spans_off()
