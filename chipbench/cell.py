"""One cell on the device: build the served system, warm it, run the window.

The system under test is built the way ``repro.launch.serve.serve_real``
builds it (``ContiguousKVEngine`` over ``RealCompute``, the file-backed
``ChunkStore`` and the ``Scheduler``'s real driver), with the settings of
``serve.parse_args`` and the configuration file.  Only the public
constructors are used; the benchmark observes the run through

- a ``RealExecutor`` subclass (window deadline, trace start and stop, and
  ``TraceAnnotation`` spans around compute and I/O in traced runs);
- a wrapper around each request's step-plan generator, which records the
  admission time, the trace and the first decoded token;
- wrappers around the ``RealCompute`` instance's methods (spans, and the
  shapes of each decode-attention call).

All times are on the executor's clock.
"""
from __future__ import annotations

import dataclasses
import gc
import math
import time
from typing import Dict, List, Optional

import numpy as np

from chipbench import trace_reduce as TR
from chipbench import traffic as T

# how long requests due in a window may take to finish after it closes
DRAIN_S = 90.0
# spans around the served system's methods in traced runs
SPANNED = ("embed", "part_a", "part_a_at", "token_scores", "part_b",
           "logits", "decode_attend", "decode_step_batch")


class WindowClosed(Exception):
    """Raised by the executor at its deadline to end the driver's loop."""


class CompileCounter:
    """Counts programs lowered (every in-memory cache miss, whether it then
    compiles or loads from the persistent cache) and backend compiles."""

    LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    COMPILE = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.lowered = 0
        self.compiled = 0
        self.compile_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event == self.LOWER:
            self.lowered += 1
        elif event == self.COMPILE:
            self.compiled += 1
            self.compile_s += duration


@dataclasses.dataclass
class Record:
    """What the window saw of one request."""

    rid: int
    due: float  # on the executor clock
    suffix: np.ndarray
    decode_len: int
    admitted: Optional[float] = None
    trace: object = None  # the engine's ReprefillTrace
    first_decode_input: Optional[int] = None
    result_token: Optional[int] = None
    done: bool = False
    # the program's logits behind each served token, in serving order
    logits: List[np.ndarray] = dataclasses.field(default_factory=list)

    @property
    def first_token_at(self) -> Optional[float]:
        if self.trace is None or not self.trace.ttft:
            return None
        return self.admitted + self.trace.ttft

    def token_times(self) -> List[float]:
        first = self.first_token_at
        if first is None:
            return []
        return [first] + list(self.trace.decode_times)

    def served(self) -> Optional[np.ndarray]:
        """The greedy tokens the program has emitted so far, first token
        first (a decode request still in flight at the close included)."""
        if self.decode_len == 0:
            return np.array([self.result_token]) if self.done else None
        if self.first_decode_input is None:
            return None
        return np.array([self.first_decode_input]
                        + list(self.trace.decode_tokens_out))


def _spy(gen, rec: Record, plan, annotate: bool, vocab: int):
    """Pass a plan's ops through, recording what the window needs: the
    admission time, the first decode step's input token and the logits
    behind every served token."""
    from repro.core.stepplan import ComputeOp, DecodeBatchCtx

    send, first = None, True
    while True:
        if first:
            rec.admitted, first = plan.clock.t, False
        try:
            if annotate:
                with _span("engine"):
                    op = gen.send(send)
            else:
                op = gen.send(send)
        except StopIteration as stop:
            rec.done = True
            rec.result_token = int(np.argmax(stop.value[0, -1]))
            return stop.value
        decode = isinstance(op, ComputeOp) and isinstance(op.batch_ctx,
                                                          DecodeBatchCtx)
        if decode and rec.first_decode_input is None:
            rec.first_decode_input = int(op.batch_ctx.token)
        send = yield op
        if decode:
            rec.logits.append(np.asarray(send[0][0, -1], np.float32))
        elif (isinstance(op, ComputeOp) and isinstance(send, np.ndarray)
              and send.ndim == 3 and send.shape[-1] == vocab):
            rec.logits.append(np.asarray(send[0, -1], np.float32))


def _span(name: str):
    import jax

    return jax.profiler.TraceAnnotation(TR.SPAN_PREFIX + name)


def _executor_class():
    from repro.storage.timing import RealExecutor

    class BenchExecutor(RealExecutor):
        """RealExecutor with a deadline, hooks and optional spans."""

        def __init__(self):
            super().__init__()
            self.deadline: Optional[float] = None
            self.annotate = False
            self.hooks = []

        def compute(self, fn, *, flops=0.0, hbm_bytes=0.0, tag=""):
            now = self.now()
            for hook in self.hooks:
                hook(now)
            if self.deadline is not None and now > self.deadline:
                raise WindowClosed
            if self.annotate and fn is not None:
                with _span("compute:" + tag):
                    return super().compute(fn, flops=flops,
                                           hbm_bytes=hbm_bytes, tag=tag)
            return super().compute(fn, flops=flops, hbm_bytes=hbm_bytes,
                                   tag=tag)

        def submit_io(self, fn, *, nbytes, n_requests, channel):
            if self.annotate and fn is not None:
                inner = fn

                def fn():
                    with _span("io:" + channel):
                        return inner()

            return super().submit_io(fn, nbytes=nbytes,
                                     n_requests=n_requests, channel=channel)

    return BenchExecutor


class Cell:
    """One configuration under one traffic mix, built from one seed.

    `arch` is the configuration's architecture module (``run.architecture``):
    the cell takes its sizes, the program's config, the weights and the
    reference from it."""

    def __init__(self, conf: dict, mix: dict, seed: int, arch):
        self.conf, self.mix, self.seed, self.arch = conf, mix, seed, arch
        self.dims = arch.dims_of(conf)
        self.sysconf = conf["system"]
        self.records: Dict[int, Record] = {}
        self.kernel_calls: List[tuple] = []
        self.annotate = False

    # -- build ----------------------------------------------------------------
    def build(self):
        """Weights, prefix ingest, engine; returns seconds per step."""
        import jax

        from repro.core import build_real_session
        from repro.core.backends import RealCompute
        from repro.launch import serve
        from repro.serving.tenancy import ENGINE_CLASSES

        steps = {}
        t = time.perf_counter()
        self.cfg = self.arch.program_config(self.conf)
        self.params = self.arch.make_params(self.conf, self.seed)
        jax.block_until_ready(self.params)
        steps["init"] = time.perf_counter() - t

        t = time.perf_counter()
        self.prefix = T.prefix_tokens(self.mix, self.seed, self.dims.vocab)
        s = self.sysconf
        self.args = serve.parse_args([
            "--arch", self.conf["arch"],
            "--n-layers", str(self.dims.layers),
            "--system", s["system"], "--budget", str(s["budget"]),
            "--chunk-tokens", str(s["chunk_tokens"]),
            "--period", str(s["period"]), "--subperiod", str(s["subperiod"]),
            "--prefix-len", str(len(self.prefix)),
            "--concurrency", str(self.mix["max_concurrency"])])
        a = self.args
        self.session = build_real_session(
            self.cfg, self.params, self.prefix, chunk_tokens=a.chunk_tokens,
            coarse_blocks=a.system != "contiguous_kv")
        steps["ingest"] = time.perf_counter() - t

        self.ex = _executor_class()()
        self.backend = RealCompute(self.cfg, self.params)
        kw = dict(device_cap=s["device_cap"], host_cap=s["host_cap"],
                  prefill_chunk_tokens=a.prefill_chunk_tokens,
                  device_tail_pool=not a.host_tail_pool, hybrid=None,
                  budget=a.budget)
        if a.system == "contiguous_kv":
            kw.update(period=a.period, subperiod=a.subperiod)
        self.engine = ENGINE_CLASSES[a.system](self.session, self.backend,
                                               self.ex, **kw)
        self._wrap_engine()
        self._wrap_backend()
        return steps

    def scheduler(self):
        from repro.serving import Scheduler

        a = self.args
        return Scheduler(self.engine, policy=a.policy,
                         max_concurrency=a.concurrency,
                         batch_decode=not a.no_batch_decode,
                         max_batch_tokens=a.max_batch_tokens,
                         preempt=a.preempt, swap_on_preempt=a.swap_on_preempt,
                         prefill_estimate=a.prefill_estimate)

    def _wrap_engine(self):
        eng, plan_of = self.engine, self.engine.plan

        def plan(suffix, request_id=0, arrival=0.0, decode_tokens=0):
            p = plan_of(suffix, request_id, arrival=arrival,
                        decode_tokens=decode_tokens)
            rec = self.records.get(request_id)
            if rec is not None:
                rec.trace = p.trace
                p.gen = _spy(p.gen, rec, p, self.annotate,
                             self.dims.vocab)
            return p

        eng.plan = plan

    def _wrap_backend(self):
        be = self.backend
        page = self.sysconf["chunk_tokens"]

        def record_batch(ctxs):
            pools = [c.pools[0] for c in ctxs]
            width = max(p.n_res + p.cap_pages for p in pools)
            self.kernel_calls.append(
                (self.ex.now(), [p.valid_tokens + 1 for p in pools],
                 pools[0].n_res, width, self.dims.layers, page))

        def record_one(layer, h, q, tail):
            self.kernel_calls.append(
                (self.ex.now(), [tail.valid_tokens], tail.n_res,
                 tail.n_res + tail.cap_pages, 1, page))

        before = {"decode_step_batch": record_batch,
                  "decode_attend": record_one}
        for name in SPANNED:
            orig = getattr(be, name)

            def method(*args, _orig=orig, _name=name, **kw):
                pre = before.get(_name)
                if pre is not None:
                    pre(*args, **kw)
                if self.annotate:
                    with _span("rc." + _name):
                        return _orig(*args, **kw)
                return _orig(*args, **kw)

            setattr(be, name, method)

    # -- warm-up ----------------------------------------------------------------
    def warm_up(self):
        """Run every shape the window will use: each suffix length through
        the re-prefill, and for decode mixes the standalone step and every
        batch size up to the slot count."""
        from repro.core.stepplan import ComputeOp, resolve_handle
        from repro.serving import Request

        rng = T.rng_for(self.seed, "warm-up")
        vocab = self.dims.vocab
        shapes = T.shapes(self.mix)
        suffixes = sorted({s for s, _ in shapes})
        self.scheduler().run([
            Request(request_id=-1 - i, suffix=rng.integers(0, vocab, s))
            for i, s in enumerate(suffixes)])
        mc = self.mix["max_concurrency"]
        for s, d in (sd for sd in shapes if sd[1] > 0):
            plans, ops = [], []
            for i in range(mc):
                plan = self.engine.plan(rng.integers(0, vocab, s),
                                        request_id=-100 - i, decode_tokens=d)
                send = None
                while True:  # drive the prefill to the first decode op
                    op = plan.gen.send(send)
                    if isinstance(op, ComputeOp):
                        if op.phase == "decode":
                            break
                        send = self.ex.compute(op.fn, tag=op.tag)
                    else:
                        send = resolve_handle(op.handle)
                plans.append(plan)
                ops.append(op)
            ops[0].fn()  # the standalone b=1 step
            for b in range(2, mc + 1):
                self.backend.decode_step_batch([op.batch_ctx
                                                for op in ops[:b]])
            for plan in plans:
                plan.gen.close()

    # -- the window ---------------------------------------------------------------
    def run_window(self, seconds: float, *, trace_dir: Optional[str] = None):
        """Serve the mix for `seconds`; returns the run's records."""
        import jax

        from repro.serving import Request

        reqs = T.make_requests(self.mix, self.seed, seconds,
                               self.dims.vocab)
        backlog = self.mix["arrival"] == "backlog"
        sched = self.scheduler()
        store = self.session.store.stats
        bytes0, reads0 = store.bytes_read, store.requests
        self.kernel_calls.clear()
        ex = self.ex
        win = {"span": None}
        if trace_dir is not None:
            self.annotate = ex.annotate = True
            jax.profiler.start_trace(trace_dir)
        t0 = ex.now()
        self.records = {r.rid: Record(r.rid, t0 + r.arrival, r.suffix,
                                      r.decode_tokens) for r in reqs}
        t_close = t0 + seconds

        def close_span(now):
            if win["span"] is not None and now >= t_close:
                win["span"].__exit__(None, None, None)
                win["span"] = None

        if trace_dir is not None:
            win["span"] = _span("window")
            win["span"].__enter__()
            ex.hooks.append(close_span)
        ex.deadline = t_close if backlog else t_close + DRAIN_S
        try:
            sched.run([Request(request_id=r.rid, suffix=r.suffix,
                               arrival=r.arrival,
                               decode_tokens=r.decode_tokens) for r in reqs])
            if ex.now() < t_close:  # every request done before the close
                time.sleep(t_close - ex.now())
        except WindowClosed:
            pass
        finally:
            t_end = ex.now()
            ex.deadline = None
            ex.hooks.clear()
            close_span(math.inf)
            if trace_dir is not None:
                jax.profiler.stop_trace()
                self.annotate = ex.annotate = False
        recs = list(self.records.values())
        pending = sum(1 for r in recs if r.admitted is None)
        return dict(
            seconds=seconds, t0=t0, t_close=t_close, t_end=t_end,
            backlog=backlog, requests=recs, pending_at_close=pending,
            store_bytes=store.bytes_read - bytes0,
            store_reads=store.requests - reads0,
            batch_sizes=[len(b) for b in sched.real_batch_log],
            kernel_calls=list(self.kernel_calls))

    # -- teardown ---------------------------------------------------------------
    def release(self):
        """Free the served system's state (the weights stay)."""
        self.ex.shutdown()
        self.session.store.close()
        self.engine = self.backend = self.session = None
        gc.collect()


def peak_memory_bytes() -> Optional[int]:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.local_devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def logit_error(prog: np.ndarray, ref: np.ndarray) -> np.ndarray:
    """Per position: the L2 distance of the program's logits from the
    reference's, over the spread of the reference's logits about their
    mean."""
    dev = ref - ref.mean(axis=1, keepdims=True)
    return (np.linalg.norm(prog - ref, axis=1)
            / np.linalg.norm(dev, axis=1))


def token_gaps(ref_logits: np.ndarray, tokens: np.ndarray) -> np.ndarray:
    """How far each token's reference logit lies below the reference's best."""
    rows = np.arange(len(tokens))
    return ref_logits.max(axis=1) - ref_logits[rows, tokens]


def selection_gap(ref_scores: np.ndarray, selected: np.ndarray,
                  budget: float) -> float:
    """How far the weakest selected chunk's reference score lies below the
    k-th best reference score, in units of the mean chunk score."""
    k = min(len(ref_scores), max(1, math.ceil(budget * len(ref_scores))))
    kth = np.sort(ref_scores)[::-1][k - 1]
    weakest = ref_scores[np.asarray(selected, int)].min()
    return float(max(0.0, kth - weakest) / ref_scores.mean())


def check(cell: Cell, run: dict, *, control: bool = False) -> dict:
    """Compare what the window served with the reference, over every request
    that served a token: the widest relative error of the logits behind a
    served token, the widest gap of a selected chunk below the reference's
    k-th best, and the served tokens that are not the greedy choice of their
    own logits (exact).

    With `control`, the fp8 control takes the program's place: over the
    same prompts and served tokens it gives its own chunk selection, its
    own logits and, as the token served at each position, the one it puts
    first; the reference and the comparison are the same."""
    reqs = [r for r in run["requests"] if r.served() is not None]
    arch = cell.arch
    ref = arch.Reference(cell.params, cell.conf, cell.prefix)
    ctl = (arch.Reference(cell.params, cell.conf, cell.prefix, quant=True)
           if control else None)
    budget = cell.sysconf["budget"]
    out = {"logit_err": 0.0, "selection_gap": 0.0, "not_greedy": 0,
           "token_gap": 0.0, "tokens": 0, "requests": len(reqs)}
    for r in reqs:
        served = r.served()
        total = len(r.suffix) + r.decode_len
        if ctl is None:
            prog = np.stack(r.logits[: len(served)])
            sels, tokens = r.trace.selected_per_period, served
        else:
            prog, _, sels = ctl.request(r.suffix, served, None, total)
            tokens = prog.argmax(axis=1)
        logits, scores, _ = ref.request(r.suffix, served, sels, total=total)
        out["logit_err"] = max(out["logit_err"],
                               float(logit_error(prog, logits).max()))
        out["token_gap"] = max(out["token_gap"],
                               float(token_gaps(logits, tokens).max()))
        out["not_greedy"] += int(np.sum(prog.argmax(axis=1) != tokens))
        for cs, sel in zip(scores, sels):
            out["selection_gap"] = max(out["selection_gap"],
                                       selection_gap(cs, sel, budget))
        out["tokens"] += len(served)
    return out
