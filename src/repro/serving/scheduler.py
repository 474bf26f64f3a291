"""Multi-tenant serving scheduler: interleave step plans over shared channels.

The pre-serving stack ran one `reprefill()` to completion per request — the
paper's identification/compute/I-O overlap existed *within* a request but
never *across* requests. Here each request is a resumable
:class:`repro.core.stepplan.StepPlan`; the scheduler admits up to
``max_concurrency`` plans and advances, at every step, the plan whose next op
can run earliest. While one request waits on the SSD channel another's
compute op occupies the accelerator, so the three FIFO channels (ssd, pcie,
compute) of :class:`repro.storage.timing.ChannelSim` stay busy the way
arXiv:2410.03065 overlaps loading with recomputation across streams.

Two drivers share the admission logic:
  sim  — discrete-event over ChannelSim; arrival times are respected and
         queueing delay is part of TTFT;
  real — wall clock over RealExecutor; plans are cooperatively multiplexed,
         a plan blocked on a pending I/O future yields the driver to others.
         Arrival offsets are wall-clock-faithful: a request is admitted only
         once ``now - t0 >= arrival`` (the driver sleeps through idle gaps),
         so every phase of every family is iteration-batched against the
         traffic that has actually arrived.  Each driver pass
         is an iteration: runnable decode-phase ComputeOps of plans sharing
         one backend coalesce into a single batched kernel call
         (``backend.decode_step_batch`` over the requests' TailPools, ragged
         page tables padded to a common width), while prefill and I/O ops
         keep the cooperative round-robin; ``batch_decode=False`` disables
         it and a lone decode step always runs the standalone path, keeping
         concurrency-1 bit-identical to ``drive_serial``.

Admission policies:
  fcfs        — strict arrival order;
  cache_aware — prefer the queued request whose tenant has the most resident
                units in the shared cache (prefix-affinity batching: ride the
                warm cache before it is evicted by other tenants);
  slo_aware   — earliest-deadline-first over per-request TTFT targets.

Decode-phase requests (``Request.decode_tokens > 0``) keep yielding per-token
steps after the first token.  The sim driver coalesces runnable *batchable*
ComputeOps (``op.tokens > 0``: decode tokens and, when engines plan with
``prefill_chunk_tokens``, chunk-granular prefill ops) of all active plans
into a single batched accelerator occupation per iteration — true token-level
mixing of prefill and decode: FLOPs and per-request KV traffic sum, the
weight stream is paid once, and each iteration is capped at
``max_batch_tokens`` batch tokens.  Disable with ``batch_decode=False``.

SLO-driven preemption (``preempt=True``, both drivers): when the
earliest-deadline queued request projects a TTFT miss (its deadline is ahead
of the next scheduling event plus an EWMA estimate of prefill service time),
the scheduler preempts an active decode-phase plan at its step boundary and
admits the urgent request into the freed slot.  With ``swap_on_preempt`` the
victim's state is swapped out and restored on resume — in sim the
cache-resident units are priced over the PCIe channel through the device
model; in real mode the victim's device-resident TailPools are snapshotted
back to host memory (actual D2H/H2D transfers, bytes accounted) and the
resumed decode is bit-identical to an uninterrupted run.  Preempted plans
resume with priority as soon as a slot frees.
"""
from __future__ import annotations

import dataclasses
import heapq
import time
from concurrent.futures import FIRST_COMPLETED
from concurrent.futures import wait as futures_wait
from typing import Dict, List, Optional, Sequence, Union

import numpy as np

from repro import obs
from repro.core import costmodel as CM
from repro.core.cache import DEVICE, HOST
from repro.core.stepplan import (
    ComputeOp,
    DecodeBatchCtx,
    PrefillChunkCtx,
    StepPlan,
    WaitOp,
    resolve_handle,
    weight_stream,
)
from repro.serving.disagg import INTERCONNECT, DisaggTopology
from repro.serving.replicas import ReplicaSet
from repro.storage.timing import ChannelSim, IOHandle


# ---------------------------------------------------------------------------
# requests
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Request:
    request_id: int
    suffix: np.ndarray
    arrival: float = 0.0
    tenant: int = 0
    decode_tokens: int = 0  # tokens to generate past the first (decode phase)
    ttft_target: Optional[float] = None  # per-request TTFT SLO, seconds


@dataclasses.dataclass
class CompletedRequest:
    """One served request.  `admitted` and `finish` are executor-clock
    times; `start` is the run's start on that clock (0 in sim), and the
    request fell due at ``start + request.arrival``."""

    request: Request
    trace: object  # ReprefillTrace
    result: object  # logits (real mode) / None (sim)
    admitted: float
    finish: float
    preemptions: int = 0  # times this plan was preempted under SLO pressure
    swaps: int = 0  # swap-out/swap-in round trips of its resident units
    start: float = 0.0

    @property
    def due(self) -> float:
        """Executor-clock time the request arrived."""
        return self.start + self.request.arrival

    @property
    def ttft(self) -> float:
        """Arrival-to-first-token: queueing delay + prefill service time.
        (With a decode phase, `finish` covers the whole lifecycle, so the
        first-token time comes from the trace, not from `finish`.)"""
        if getattr(self.trace, "ttft", 0.0):
            return self.queue_delay + self.trace.ttft
        return self.finish - self.due

    @property
    def e2e_latency(self) -> float:
        """Arrival to last emitted token (== ttft when decode_tokens=0)."""
        return self.finish - self.due

    @property
    def queue_delay(self) -> float:
        return self.admitted - self.due

    @property
    def service_time(self) -> float:
        return self.finish - self.admitted

    @property
    def slo_met(self) -> Optional[bool]:
        if self.request.ttft_target is None:
            return None
        return self.ttft <= self.request.ttft_target


# ---------------------------------------------------------------------------
# admission policies
# ---------------------------------------------------------------------------
class FCFSPolicy:
    name = "fcfs"

    def select(self, queued: Sequence[Request], engines) -> Request:
        return min(queued, key=lambda r: (r.arrival, r.request_id))


class CacheAffinityPolicy:
    """Prefer the tenant with the most cache-resident units (device counts
    double: a device hit avoids both the SSD and the PCIe leg)."""

    name = "cache_aware"

    def select(self, queued: Sequence[Request], engines) -> Request:
        def affinity(r: Request) -> float:
            eng = engines[r.tenant]
            cache = eng.cache
            if cache is None:  # cache-less families (StateSpaceEngine)
                return 0.0
            return (2 * cache.resident_units(eng.tenant, DEVICE)
                    + cache.resident_units(eng.tenant, HOST))

        # ties fall back to FCFS order
        return max(queued, key=lambda r: (affinity(r), -r.arrival, -r.request_id))


def _deadline(r: Request) -> float:
    """Absolute TTFT deadline; +inf for best-effort requests."""
    if r.ttft_target is None:
        return float("inf")
    return r.arrival + r.ttft_target


class SLOAwarePolicy:
    """Earliest-deadline-first over per-request TTFT targets.

    The deadline of a request is ``arrival + ttft_target``; requests without
    a target sort last (deadline = +inf) and fall back to FCFS among
    themselves, so latency-sensitive traffic jumps the best-effort queue."""

    name = "slo_aware"

    def select(self, queued: Sequence[Request], engines) -> Request:
        return min(queued, key=lambda r: (_deadline(r), r.arrival, r.request_id))


POLICIES = {"fcfs": FCFSPolicy, "cache_aware": CacheAffinityPolicy,
            "slo_aware": SLOAwarePolicy}


class _Active:
    __slots__ = ("request", "plan", "op", "resume", "admitted",
                 "preempt_count", "swap_count", "swapped_bytes", "ttft_seen",
                 "batch_stamp", "held_op", "handed_off", "worker_backend",
                 "replica", "stall")

    def __init__(self, request: Request, plan: StepPlan, admitted: float):
        self.request = request
        self.plan = plan
        self.op = None
        self.resume = admitted
        self.admitted = admitted
        self.preempt_count = 0
        self.swap_count = 0
        self.swapped_bytes = 0  # bytes swapped out, re-fetched on resume
        self.ttft_seen = False  # first token already fed the prefill EWMA
        self.batch_stamp = -1  # last real-driver iteration this plan batched
        self.held_op = None  # op parked behind a kv_handoff WaitOp (disagg)
        self.handed_off = False  # prefill->decode handoff already emitted
        self.worker_backend = None  # real decode worker backend after handoff
        self.replica = 0  # owning replica index under a ReplicaSet
        # real driver: [start, end] of the pending I/O wait the plan is
        # stalled on (executor clock; end stamped at the transfer's
        # completion), None while it is not stalled
        self.stall = None


# ---------------------------------------------------------------------------
# scheduler
# ---------------------------------------------------------------------------
class Scheduler:
    """Drives concurrent request streams over one shared executor.

    `engines` maps tenant id -> engine; all engines must share the same
    executor (and, for multi-tenant cache competition, the same cache
    instance). A single engine is accepted for the one-tenant case.
    """

    def __init__(self, engines, *, policy: Union[str, object] = "fcfs",
                 max_concurrency: int = 4, batch_decode: bool = True,
                 max_batch_tokens: Optional[int] = None,
                 preempt: bool = False, swap_on_preempt: bool = False,
                 prefill_estimate: Optional[float] = None,
                 topology: Optional[DisaggTopology] = None,
                 replicas: Optional[ReplicaSet] = None):
        if not isinstance(engines, dict):
            engines = {getattr(engines, "tenant", 0): engines}
        assert engines, "need at least one engine"
        assert max_concurrency >= 1
        assert max_batch_tokens is None or max_batch_tokens >= 1
        executors = {id(e.ex) for e in engines.values()}
        assert len(executors) == 1, "all engines must share one executor"
        self.engines = engines
        self.ex = next(iter(engines.values())).ex
        self.policy = POLICIES[policy]() if isinstance(policy, str) else policy
        self.max_concurrency = max_concurrency
        # token-level batching: coalesce runnable batchable ComputeOps
        # (decode tokens + chunk-granular prefill) of all active plans into
        # one batched accelerator occupation per iteration — sim prices it
        # through `compute_batch_at`, real runs one batched kernel pass per
        # iteration — capped at `max_batch_tokens` batch tokens (None =
        # uncapped)
        self.batch_decode = batch_decode
        self.max_batch_tokens = max_batch_tokens
        # SLO-driven preemption of decode plans (sim + real drivers)
        self.preempt = preempt
        self.swap_on_preempt = swap_on_preempt
        self.preemptions = 0
        self.swaps = 0
        self.swap_bytes = 0
        # TTFT-miss projection: an EWMA of prefill service times observed at
        # each plan's *first token* (not request completion, so long decodes
        # don't starve it), floored by the operator-provided
        # `prefill_estimate` seed (the seed is a lower bound — early
        # uncontended samples must not dilute it)
        self._prefill_seed = prefill_estimate
        self._prefill_ewma: Optional[float] = None
        # per-iteration batch token counts (observability + property tests)
        self.batch_log: List[int] = []
        # real driver: per-batch member digest [(request_id, phase,
        # weight_key), ...] — the regression suite asserts batches never mix
        # phases/weight streams and never run a request's op twice
        self.real_batch_log: List[List[tuple]] = []
        # sim driver counterpart of real_batch_log: the mixed-fleet property
        # suite asserts sim batches never amortize weights across model
        # families either
        self.sim_batch_log: List[List[tuple]] = []
        # prefill/decode disaggregation (None = colocated single worker).
        # Sim: per-worker compute channels + the interconnect FIFO are
        # registered on the shared ChannelSim; real: decode_backends carries
        # one backend instance per decode worker and the handoff reuses the
        # PR-5 pool swap_out/swap_in serialization.
        self.topology = topology
        # data-parallel replicas (None = the single colocated deployment).
        # Composition: `topology` becomes *per-replica* when a ReplicaSet is
        # present — every replica gets its own P:D worker channels and
        # handoffs stay within the replica.
        self.replicas = replicas
        if replicas is not None and topology is not None:
            if replicas.topology is None:
                replicas.topology = topology
            elif replicas.topology is not topology:
                raise ValueError(
                    "pass the per-replica topology either on the ReplicaSet "
                    "or as topology=, not two different ones")
        if isinstance(self.ex, ChannelSim):
            if replicas is not None:
                replicas.attach_sim(self.ex)
            elif topology is not None:
                topology.attach_sim(self.ex)
        self.replica_admits = ([0] * replicas.n_replicas
                               if replicas is not None else [])
        self.handoffs = 0
        self.handoff_bytes = 0  # bytes moved over the handoff link
        self.handoff_recomputes = 0  # handoffs the planner turned into
        self.handoff_bytes_avoided = 0  # ... decode-worker recomputes
        self._rr_decode = 0  # real mode: round-robin decode-worker pick
        self._run_start = 0.0  # real mode: the run's start, executor clock

    def run(self, requests: Sequence[Request]) -> List[CompletedRequest]:
        requests = list(requests)
        # per-run scoping of the hybrid planners' anti-herd reservations:
        # a fleet-shared planner outlives the run, but its reservations are
        # points on this run's clock — a sim rerun restarts at t=0 and must
        # not see the previous run's (now far-future) commitments
        for hp in {id(hp): hp for hp in
                   (getattr(e, "hybrid", None) for e in self.engines.values())
                   if hp is not None}.values():
            hp.reset()
        if isinstance(self.ex, ChannelSim):
            return self._run_sim(requests)
        if self.replicas is not None and self.replicas.backends is None:
            raise ValueError("real-mode replicas need ReplicaSet.backends "
                             "(one worker-backend list per replica)")
        if (self.replicas is None and self.topology is not None
                and not self.topology.decode_backends):
            raise ValueError("real-mode disaggregation needs "
                             "DisaggTopology.decode_backends")
        return self._run_real(requests)

    # -- discrete-event driver (sim) ------------------------------------------
    def _run_sim(self, requests: List[Request]) -> List[CompletedRequest]:
        pending = sorted(requests, key=lambda r: (r.arrival, r.request_id))
        # free-time of each serving slot: a heap models "slot frees when the
        # plan occupying it finishes" without tracking identity
        slots = [0.0] * self.max_concurrency
        heapq.heapify(slots)
        active: List[_Active] = []
        preempted: List[_Active] = []
        done: List[CompletedRequest] = []
        while pending or active or preempted:
            self._resume_sim(preempted, active, slots)
            self._admit_sim(pending, active, slots, done)
            self._preempt_sim(pending, active, preempted, slots, done)
            if not active:
                continue
            a = min(active, key=lambda x: x.resume)
            batch = self._mixed_batch(a, active, slots, done)
            if batch is not None:
                self._step_sim_batch(batch, active, slots, done)
            else:
                self._step_sim(a, active, slots, done)
        done.sort(key=lambda c: c.request.request_id)
        return done

    @property
    def _prefill_est(self) -> float:
        """Projected prefill service time: EWMA floored by the seed."""
        return max(self._prefill_seed or 0.0, self._prefill_ewma or 0.0)

    def _observe_ttft(self, a: _Active):
        """Feed the prefill EWMA as soon as a plan emits its first token."""
        if a.ttft_seen:
            return
        ttft = getattr(a.plan.trace, "ttft", 0.0)
        if ttft:
            a.ttft_seen = True
            self._prefill_ewma = (ttft if self._prefill_ewma is None
                                  else 0.5 * self._prefill_ewma + 0.5 * ttft)

    def _finish_sim(self, a: _Active, t: float, slots, done, value):
        """Release `a`'s slot at time `t` and record the completion."""
        heapq.heappush(slots, t)
        self._observe_ttft(a)
        done.append(CompletedRequest(a.request, a.plan.trace, value,
                                     a.admitted, t,
                                     preemptions=a.preempt_count,
                                     swaps=a.swap_count))

    def _mixed_batch(self, a: _Active, active, slots, done) -> Optional[List[_Active]]:
        """Assemble one token-level batch iteration around plan `a`, or None.

        When the earliest runnable op is batchable (``op.tokens > 0``: a
        decode token or a chunk-granular prefill op), the iteration window
        is the op's own duration past the accelerator-free gate.  Peers
        blocked on I/O that completes inside the window are advanced first
        (their wait times are fixed by the handle, so resolving them early
        is time-faithful), then batchable ComputeOps runnable inside the
        window join in resume order, while the iteration stays within
        ``max_batch_tokens`` batch tokens.  The earliest plan always runs
        (even if alone it exceeds the budget — ops cannot split here).

        Join rule (asymmetric on purpose): a decode-led iteration streams
        the *whole model's* weights, so prefill chunks of any layer ride it
        for free (their layer's weight slice is a subset) — this is the
        token-level prefill/decode mixing.  A chunk-led iteration streams
        one layer's weights, so it only absorbs chunks with the *same*
        ``weight_key`` (concurrent prefills on the same layer); letting a
        decode token join would stretch the chunk from one layer's weight
        time to the full model's and wreck the leader's TTFT.

        Heterogeneous fleets: decode peers must share the leader's exact
        ``weight_key`` (two models never stream one weight read — a
        same-family decode key is ``"model@<name>"``), and prefill riders
        must at least share the leader's :func:`weight_stream` (same model)
        — the subset argument above only holds within one model's weights."""
        if not (self.batch_decode and isinstance(a.op, ComputeOp)
                and a.op.tokens > 0):
            return None
        chan = a.plan.clock.channel
        gate = max(a.resume, self.ex.free_at[chan])
        window = gate + self.ex.model.compute_time(a.op.flops, a.op.hbm_bytes)
        while True:
            waiting = [b for b in active
                       if b is not a and isinstance(b.op, WaitOp)
                       and b.resume <= window]
            if not waiting:
                break
            b = min(waiting, key=lambda x: x.resume)
            if b.held_op is not None and b.op.tag == "kv_handoff":
                self._release_handoff(b)
                continue
            b.plan.clock.t = b.resume
            send = resolve_handle(b.op.handle)
            try:
                b.op = b.plan.gen.send(send)
                b.resume = b.plan.resume_time(b.op)
                self._observe_ttft(b)
                self._maybe_handoff_sim(b)
            except StopIteration as stop:
                active.remove(b)
                self._finish_sim(b, b.plan.clock.t, slots, done, stop.value)
        def trim(cands, members, total):
            """Greedy token-budget selection in (leader, resume, id) order."""
            for b in cands:
                if (members and self.max_batch_tokens is not None
                        and total + b.op.tokens > self.max_batch_tokens):
                    continue  # a later, smaller op may still fit
                members.append(b)
                total += b.op.tokens
            return members, total

        # an iteration is one occupation of ONE worker's accelerator: under
        # a disaggregated topology only plans routed to the same channel may
        # coalesce (a colocated fleet has a single shared channel, so the
        # filter is vacuous there)
        same = lambda b: b.plan.clock.channel == chan
        order = lambda b: (b is not a, b.resume, b.request.request_id)
        if a.op.phase == "decode":
            decode_cands = sorted(
                (b for b in active
                 if isinstance(b.op, ComputeOp) and b.op.tokens > 0
                 and b.op.phase == "decode" and b.resume <= window
                 and b.op.weight_key == a.op.weight_key and same(b)),
                key=order)
            members, total = trim(decode_cands, [], 0)
            # prefill chunks ride only if already runnable at the iteration's
            # start (computed after the budget trim) — a rider must never
            # delay the decode iteration
            start = max(b.resume for b in members)
            riders = sorted(
                (b for b in active
                 if isinstance(b.op, ComputeOp) and b.op.tokens > 0
                 and b.op.phase == "prefill" and b.resume <= start
                 and weight_stream(b.op.weight_key)
                 == weight_stream(a.op.weight_key) and same(b)),
                key=order)
            members, _ = trim(riders, members, total)
            return members
        cands = sorted(
            (b for b in active
             if isinstance(b.op, ComputeOp) and b.op.tokens > 0
             and b.op.weight_key == a.op.weight_key and b.resume <= window
             and same(b)),
            key=order)
        members, _ = trim(cands, [], 0)
        return members

    def _step_sim_batch(self, members: List[_Active], active, slots, done):
        start = max(b.resume for b in members)
        phases = {b.op.phase for b in members}
        total = sum(b.op.tokens for b in members)
        items = []
        for b in members:
            op = b.op
            if (op.phase == "prefill" and op.fn is None
                    and op.weight_key.startswith("layer:")):
                # (weight_key-guarded: a sim hybrid *recompute* op also has
                # phase="prefill" and fn=None, but is a complete step in
                # itself — only layer-chunk streams drain.)
                # drain: pull the plan's consecutive chunks of this layer
                # into the same iteration while the token budget allows.
                # Non-final chunks carry fn=None (pure occupancy), so their
                # results are known and the generator can be advanced at
                # batch-formation time; the layer's final chunk (fn set)
                # stops the drain.  Merged pricing: FLOPs and KV re-reads
                # sum, the layer's weight stream is paid once.
                flops = op.flops
                kv = op.hbm_bytes - op.weight_bytes
                while (op.fn is None
                       and (self.max_batch_tokens is None
                            or total + op.tokens <= self.max_batch_tokens)):
                    nxt = b.plan.gen.send(None)
                    assert (isinstance(nxt, ComputeOp) and nxt.tokens > 0
                            and nxt.weight_key == op.weight_key), (
                        "an fn-less prefill chunk must be followed by its "
                        "layer's next chunk")
                    op = b.op = nxt
                    flops += op.flops
                    kv += op.hbm_bytes - op.weight_bytes
                    total += op.tokens
                items.append((op.fn, flops, op.weight_bytes + kv,
                              op.weight_bytes))
            else:
                items.append((op.fn, op.flops, op.hbm_bytes, op.weight_bytes))
        tag = members[0].op.tag if len(phases) == 1 else "mixed"
        self.batch_log.append(total)
        self.sim_batch_log.append(
            [(b.request.request_id, b.op.phase, b.op.weight_key)
             for b in members])
        outs, end = self.ex.compute_batch_at(
            items, tag=tag, at=start,
            channel=members[0].plan.clock.channel)
        for b, send in zip(members, outs):
            b.plan.clock.t = end
            try:
                b.op = b.plan.gen.send(send)
                b.resume = b.plan.resume_time(b.op)
                self._observe_ttft(b)
                self._maybe_handoff_sim(b)
            except StopIteration as stop:
                active.remove(b)
                self._finish_sim(b, end, slots, done, stop.value)

    def _start_plan(self, req: Request, start: float, active, slots, done):
        """Build and admit one plan starting at `start` (slot already held)."""
        eng = self.engines[req.tenant]
        plan = eng.plan(req.suffix, req.request_id, arrival=start,
                        decode_tokens=req.decode_tokens)
        replica = 0
        if self.replicas is not None:
            # least-backlogged admission across the whole fleet: pick the
            # (replica, prefill channel) pair with the fewest in-flight
            # plans, breaking ties by which channel frees earliest.  The
            # in-flight count matters for simultaneous arrivals — a plan
            # admitted at t spends its first legs on ssd/pcie, so free_at
            # alone would keep sending cohort-mates to the same replica
            load = {}
            for other in active:
                c = other.plan.clock.channel
                load[c] = load.get(c, 0) + 1
            replica, chan = min(
                ((r, c) for r in range(self.replicas.n_replicas)
                 for c in self.replicas.prefill_channels(r)),
                key=lambda rc: (load.get(rc[1], 0),
                                self.ex.free_at[rc[1]], rc[1]))
            plan.clock.channel = chan
            self.replica_admits[replica] += 1
        elif self.topology is not None:
            # route the prefill phase to the least-backlogged prefill
            # worker; the channel must be pinned before the generator's
            # first resume, which already prices ops against it
            plan.clock.channel = min(
                self.topology.prefill_channels,
                key=lambda c: (self.ex.free_at[c], c))
        a = _Active(req, plan, start)
        a.replica = replica
        try:
            a.op = plan.gen.send(None)
        except StopIteration as stop:  # degenerate plan with no ops
            self._finish_sim(a, start, slots, done, stop.value)
            return
        a.resume = plan.resume_time(a.op)
        self._maybe_handoff_sim(a)
        active.append(a)

    def _handoff_payload(self, a: _Active):
        """(bytes, tokens) of one prefill->decode KV handoff: the resident
        prefix units every decode step attends over, plus the suffix (and
        first-token) KV tail — i.e. everything the decode worker needs that
        only exists on the prefill worker.  `tokens` is the causal extent a
        decode-worker recompute would have to cover to rebuild the same KV."""
        eng = self.engines[a.request.tenant]
        if hasattr(eng, "handoff_payload"):
            # family-specific pricing (StateSpaceEngine: the recurrent state
            # + any hybrid attention KV, not prefix-store units)
            return eng.handoff_payload(a)
        layout = eng.session.store.layout
        sel = a.plan.trace.selected_per_layer
        max_unit = max((int(u) for us in sel.values() for u in us),
                       default=-1)
        prefix_tokens = min((max_unit + 1) * layout.unit_tokens,
                            eng.session.prefix_len)
        suffix_tokens = len(a.request.suffix)
        nbytes = (self._resident_bytes(a)
                  + suffix_tokens * layout.geom.token_bytes * layout.n_layers)
        return int(nbytes), int(prefix_tokens + suffix_tokens)

    def _maybe_handoff_sim(self, a: _Active):
        """Emit the ``kv_handoff`` WaitOp at the prefill->decode boundary.

        Fires once per plan, at the first op yielded after the generator
        stamped ``trace.ttft`` (prefill done, decode pending).  The pending
        op is parked on ``a.held_op`` behind a WaitOp whose handle is the
        transfer's completion on the interconnect FIFO — or, when the
        fleet's HybridPlanner prices a decode-worker recompute cheaper than
        pulling the bytes, an occupation of the decode worker's own compute
        channel.  Either way the plan's clock is re-routed to the chosen
        decode worker, so every decode-phase op runs there.

        Under a ReplicaSet the handoff stays *within* the owning replica
        (its topology is per-replica: the candidate decode channels are
        replica ``a.replica``'s own) — replicas without a per-replica
        topology never hand off, because each replica colocates both
        phases on its one channel.
        """
        topo = (self.replicas.topology if self.replicas is not None
                else self.topology)
        if (topo is None or a.handed_off
                or not getattr(a.plan.trace, "ttft", 0.0)):
            return
        a.handed_off = True
        self.handoffs += 1
        eng = self.engines[a.request.tenant]
        clock = a.plan.clock
        dst_channels = (self.replicas.decode_channels(a.replica)
                        if self.replicas is not None
                        else topo.decode_channels)
        dst = min(dst_channels,
                  key=lambda c: (self.ex.free_at[c], c))
        nbytes, tokens = self._handoff_payload(a)
        hp = getattr(eng, "hybrid", None)
        choice = "pull"
        if hp is not None and hp.mode != "off" and nbytes and tokens:
            choice, _, t_rec = hp.price_handoff(
                cfg=eng.cfg, nbytes=nbytes, tokens=tokens, executor=self.ex,
                dst_channel=dst, clock_t=clock.t)
        if choice == "recompute":
            cost = CM.chunk_recompute_cost(eng.cfg, tokens, 0)
            _, end = self.ex.compute_at(
                None, flops=cost.flops, hbm_bytes=cost.hbm_bytes,
                tag="handoff_recompute", at=clock.t, channel=dst)
            handle = IOHandle(ready_at=end)
            self.handoff_recomputes += 1
            self.handoff_bytes_avoided += nbytes
        else:
            handle = self.ex.submit_io_at(
                None, nbytes=nbytes, n_requests=1, channel=INTERCONNECT,
                at=clock.t)
            self.handoff_bytes += nbytes
        a.held_op = a.op
        a.op = WaitOp(handle, tag="kv_handoff")
        clock.channel = dst
        a.resume = a.plan.resume_time(a.op)

    def _release_handoff(self, a: _Active):
        """The kv_handoff WaitOp completed: un-park the held decode op."""
        a.plan.clock.t = a.resume
        a.op = a.held_op
        a.held_op = None
        a.resume = a.plan.resume_time(a.op)

    def _admit_sim(self, pending, active, slots, done):
        while pending and len(active) < self.max_concurrency:
            slot_t = slots[0]
            horizon = min((a.resume for a in active), default=None)
            if horizon is None:
                # idle system: jump virtual time to the earliest start
                t0 = max(pending[0].arrival, slot_t)
                queued = [r for r in pending if r.arrival <= t0]
            else:
                # admit only what can start before the next scheduled event
                queued = [r for r in pending if max(r.arrival, slot_t) <= horizon]
                if not queued:
                    return
            req = self.policy.select(queued, self.engines)
            pending.remove(req)
            start = max(req.arrival, heapq.heappop(slots))
            self._start_plan(req, start, active, slots, done)

    def _select_preemption(self, pending, active, now, *, arrived_only):
        """Shared §6 preemption policy for both drivers.

        ``now`` is the next scheduling event (sim) or the wall clock
        relative to the run start (real).  Picks the earliest-deadline
        queued request with a TTFT target (``arrived_only`` additionally
        gates on ``arrival <= now`` — both drivers respect arrival offsets
        since the real admission refactor), projects its miss
        (``now + prefill_estimate > deadline``) and selects the
        decode-phase victim with the farthest, strictly-later deadline.
        Returns (urgent, victim) or None — the drivers own the mechanics
        (slot handoff, swap pricing vs real pool snapshots)."""
        urgent_pool = [r for r in pending if r.ttft_target is not None
                       and (not arrived_only or r.arrival <= now)]
        if not urgent_pool:
            return None
        urgent = min(urgent_pool,
                     key=lambda r: (_deadline(r), r.arrival, r.request_id))
        if max(urgent.arrival, now) + self._prefill_est <= _deadline(urgent):
            return None  # no projected miss
        victims = [a for a in active
                   if isinstance(a.op, ComputeOp) and a.op.phase == "decode"
                   and _deadline(a.request) > _deadline(urgent)]
        if not victims:
            return None
        v = max(victims, key=lambda a: (_deadline(a.request), a.admitted,
                                        a.request.request_id))
        return urgent, v

    def _preempt_sim(self, pending, active, preempted, slots, done):
        """SLO-driven preemption: evict a decode plan at its step boundary.

        Triggered when every slot is busy and the earliest-deadline queued
        request (among those already arrived) projects a TTFT miss:
        ``t_next + prefill_estimate > deadline``, where ``t_next`` is the
        next scheduling event and the estimate is the EWMA of completed
        prefill service times.  The victim is the decode-phase plan with
        the farthest deadline (strictly later than the urgent one); with
        ``swap_on_preempt`` its cache-resident units are swapped out over
        the PCIe channel and re-fetched on resume."""
        if not (self.preempt and pending and active
                and len(active) >= self.max_concurrency):
            return
        t_next = min(a.resume for a in active)
        sel = self._select_preemption(pending, active, t_next,
                                      arrived_only=True)
        if sel is None:
            return
        urgent, v = sel
        active.remove(v)
        v.preempt_count += 1
        self.preemptions += 1
        if self.swap_on_preempt:
            nbytes = self._resident_bytes(v)
            if nbytes:
                # swap-out occupies the PCIe channel from the victim's step
                # boundary; the compute slot itself frees immediately
                self.ex.submit_io_at(None, nbytes=nbytes, n_requests=1,
                                     channel="pcie", at=v.plan.clock.t)
                v.swapped_bytes = nbytes
                v.swap_count += 1
                self.swaps += 1
                self.swap_bytes += nbytes
        preempted.append(v)
        # the urgent request takes the victim's slot from the victim's step
        # boundary — no earlier, or the victim's just-finished op and the
        # urgent plan would transiently coexist in the same slot (the victim
        # holds no heap entry while preempted; it pops one on resume)
        pending.remove(urgent)
        self._start_plan(urgent, max(urgent.arrival, v.plan.clock.t), active,
                         slots, done)

    def _resume_sim(self, preempted, active, slots):
        """Resume preempted plans (FIFO) whenever a slot frees; swapped-out
        units are re-fetched over PCIe before the plan's next op can run."""
        while preempted and len(active) < self.max_concurrency:
            v = preempted.pop(0)
            slot_t = heapq.heappop(slots)
            t_r = max(v.plan.clock.t, slot_t)
            if v.swapped_bytes:
                h = self.ex.submit_io_at(None, nbytes=v.swapped_bytes,
                                         n_requests=1, channel="pcie", at=t_r)
                t_r = max(t_r, h.ready_at)
                self.swap_bytes += v.swapped_bytes
                v.swapped_bytes = 0
            v.plan.clock.t = t_r
            v.resume = v.plan.resume_time(v.op)
            active.append(v)

    def _resident_bytes(self, a: _Active) -> int:
        """Bytes of the plan's currently-selected units (the swap payload)."""
        eng = self.engines[a.request.tenant]
        if hasattr(eng, "swap_bytes_of"):
            return eng.swap_bytes_of(a)
        layout = eng.session.store.layout
        sel = a.plan.trace.selected_per_layer
        if a.plan.trace.decode_selected:
            per_layer = len(a.plan.trace.decode_selected[-1])
            n_units = per_layer * max(len(sel), 1)
        else:
            n_units = sum(len(u) for u in sel.values())
        return int(n_units) * int(layout.unit_bytes)

    def _step_sim(self, a: _Active, active, slots, done):
        clock = a.plan.clock
        op = a.op
        if isinstance(op, ComputeOp):
            out, end = self.ex.compute_at(op.fn, flops=op.flops,
                                          hbm_bytes=op.hbm_bytes, tag=op.tag,
                                          at=a.resume, channel=clock.channel)
            clock.t = end
            send = out
        else:
            if a.held_op is not None and op.tag == "kv_handoff":
                # scheduler-emitted wait: the generator never yielded it,
                # so un-park the held decode op instead of resuming the gen
                self._release_handoff(a)
                return
            clock.t = a.resume  # = max(clock, handle.ready_at)
            send = resolve_handle(op.handle)
        try:
            a.op = a.plan.gen.send(send)
            a.resume = a.plan.resume_time(a.op)
            self._observe_ttft(a)
            self._maybe_handoff_sim(a)
        except StopIteration as stop:
            active.remove(a)
            self._finish_sim(a, clock.t, slots, done, stop.value)

    # -- wall-clock driver (real) ---------------------------------------------
    def _finish_real(self, a: _Active, done, value):
        """Record one wall-clock completion (the _finish_sim counterpart)."""
        self._observe_ttft(a)
        done.append(CompletedRequest(a.request, a.plan.trace, value,
                                     a.admitted, self.ex.now(),
                                     preemptions=a.preempt_count,
                                     swaps=a.swap_count,
                                     start=self._run_start))

    def _start_real(self, req: Request, active, done):
        """Build one plan and admit it into the wall-clock driver."""
        ex = self.ex
        eng = self.engines[req.tenant]
        plan = eng.plan(req.suffix, req.request_id,
                        decode_tokens=req.decode_tokens)
        plan.clock.t = ex.now()
        a = _Active(req, plan, plan.clock.t)
        if self.replicas is not None:
            # least-backlogged replica by active plan count (real mode has
            # no sim channels to compare free-times over)
            load = [0] * self.replicas.n_replicas
            for b in active:
                load[b.replica] += 1
            a.replica = min(range(len(load)), key=lambda r: (load[r], r))
            self.replica_admits[a.replica] += 1
        try:
            a.op = plan.gen.send(None)
            self._maybe_handoff_real(a)
            active.append(a)
        except StopIteration as stop:
            self._finish_real(a, done, stop.value)

    def _maybe_handoff_real(self, a: _Active):
        """Real-mode prefill->decode handoff + decode-worker stamping.

        Fires at the plan's first decode-phase op (the op that carries a
        :class:`DecodeBatchCtx`): the per-layer tail pools built on the
        prefill engine are serialized to host and re-uploaded — PR-5's
        ``swap_out``/``swap_in`` round trip, which is byte-for-byte the
        D2H + H2D legs of a cross-worker transfer and is pinned
        bit-identical by the device-pool suite — and the plan is assigned a
        decode-worker backend (round-robin).  Every subsequent decode op's
        ``batch_ctx.backend`` is restamped to that worker, so both the
        batched kernel pass and the standalone ``op.fn`` path run on the
        decode worker's engine, and the batch former groups plans by decode
        worker exactly like the sim driver's per-worker channels.

        Under a ReplicaSet the candidate backends are the owning replica's
        own worker list, so a plan's decode phase lands on its replica's
        accelerator and the backend-identity grouping in the batch formers
        scopes every batch per replica automatically.
        """
        if self.replicas is not None and self.replicas.backends is not None:
            # the owning replica's worker list: one backend without a
            # per-replica topology, D decode workers with one
            backends = self.replicas.backends[a.replica]
        elif (self.topology is not None
                and self.topology.decode_backends is not None):
            backends = self.topology.decode_backends
        else:
            return
        if (not isinstance(a.op, ComputeOp)
                or not isinstance(a.op.batch_ctx, DecodeBatchCtx)):
            return
        ctx = a.op.batch_ctx
        if not a.handed_off:
            a.handed_off = True
            self.handoffs += 1
            a.worker_backend = backends[self._rr_decode % len(backends)]
            self._rr_decode += 1
            # the transfer: snapshot the pools off the prefill worker's
            # device and restore them on the decode worker's (both legs
            # accounted, like the preemption swap)
            out_bytes = sum(p.swap_out() for p in ctx.pools.values())
            in_bytes = sum(p.swap_in() for p in ctx.pools.values())
            self.handoff_bytes += out_bytes + in_bytes
        ctx.backend = a.worker_backend

    def _preempt_real(self, pending, active, preempted, t0: float, done):
        """SLO-driven preemption for the wall-clock driver.

        Mirrors ``_preempt_sim``: when every slot is busy and the
        earliest-deadline queued request projects a TTFT miss (wall clock
        now, relative to the run start, plus the prefill-service estimate
        overruns ``arrival + ttft_target``), the decode-phase plan with the
        farthest deadline is preempted at its step boundary — its pending op
        is simply held, which is safe because decode plans are resumable by
        construction.  With ``swap_on_preempt`` the victim's per-layer
        TailPools are snapshotted back to host memory (``pool.swap_out()``;
        a device-resident pool's buffers actually leave the device, so the
        freed slot's KV no longer occupies device memory) and restored
        bit-identically on resume.  Swap bytes are accounted on both legs,
        exactly like the sim driver prices its PCIe swap."""
        if not (self.preempt and pending and active
                and len(active) >= self.max_concurrency):
            return
        sel = self._select_preemption(pending, active, self.ex.now() - t0,
                                      arrived_only=True)
        if sel is None:
            return
        urgent, v = sel
        active.remove(v)
        v.preempt_count += 1
        self.preemptions += 1
        if self.swap_on_preempt and v.op.batch_ctx is not None:
            nbytes = sum(pool.swap_out()
                         for pool in v.op.batch_ctx.pools.values())
            if nbytes:
                v.swapped_bytes = nbytes
                v.swap_count += 1
                self.swaps += 1
                self.swap_bytes += nbytes
        preempted.append(v)
        # the urgent request takes the freed slot immediately
        pending.remove(urgent)
        self._start_real(urgent, active, done)

    def _resume_real(self, preempted, active):
        """Resume preempted plans (FIFO) whenever a slot frees; swapped-out
        pools are restored to device memory before the plan's next op runs."""
        while preempted and len(active) < self.max_concurrency:
            v = preempted.pop(0)
            if v.swapped_bytes:
                self.swap_bytes += sum(
                    pool.swap_in() for pool in v.op.batch_ctx.pools.values())
                v.swapped_bytes = 0
            active.append(v)

    def _real_decode_batch(self, active: List[_Active]) -> Optional[List[_Active]]:
        """Assemble one real-mode batched decode iteration, or None.

        Candidates are active plans whose pending op is a decode-phase
        ComputeOp stamped with a :class:`DecodeBatchCtx` (real-mode decode
        steps are always runnable — no time gating).  Members must share one
        backend (one model's weights stream once for the whole batch).
        ``max_batch_tokens`` caps the batch (decode ops carry ``tokens=1``).

        Fairness: candidates are aged by the last iteration they batched
        (``batch_stamp``), oldest first, both when choosing among backend
        groups and when trimming to the token budget — a plan left out of
        this iteration has the oldest stamp next time and joins then, so
        trimming or a backend split never starves anyone.  A single
        candidate returns None: it runs through the standalone ``op.fn``
        path, which keeps concurrency-1 serving bit-identical to
        ``drive_serial``.
        """
        if not self.batch_decode:
            return None
        cands = [a for a in active
                 if isinstance(a.op, ComputeOp) and a.op.phase == "decode"
                 and a.op.batch_ctx is not None]
        if len(cands) < 2:
            return None
        cands.sort(key=lambda a: (a.batch_stamp, a.request.request_id))
        # group by backend AND pool residency: a batched kernel pass walks
        # either the device or the host pool path, so plans whose engines
        # disagree on device_tail_pool must not land in one batch
        groups: Dict[tuple, List[_Active]] = {}
        for a in cands:
            ctx = a.op.batch_ctx
            # weight_key joins the group key for heterogeneous fleets: two
            # different models' decode steps never share one weight stream,
            # so they must never land in one kernel pass (backend identity
            # already separates them today, but the key makes the contract
            # explicit and survives backend sharing)
            key = (id(ctx.backend), bool(ctx.pools[0].is_device),
                   a.op.weight_key)
            groups.setdefault(key, []).append(a)
        # the group holding the longest-waiting candidate wins; group size
        # breaks ties so throughput is preserved when nobody is starved
        members = min(groups.values(),
                      key=lambda g: (g[0].batch_stamp, -len(g),
                                     g[0].request.request_id))
        if self.max_batch_tokens is not None:
            budget, trimmed = 0, []
            for a in members:
                if budget + a.op.tokens > self.max_batch_tokens:
                    break
                trimmed.append(a)
                budget += a.op.tokens
            members = trimmed
        return members if len(members) >= 2 else None

    def _step_real_batch(self, members: List[_Active], active, done):
        """One batched decode kernel pass for `members` (same backend)."""
        ex = self.ex
        ctxs = [a.op.batch_ctx for a in members]
        be = ctxs[0].backend
        flops = sum(a.op.flops for a in members)
        weight = max(a.op.weight_bytes for a in members)
        hbm = weight + sum(a.op.hbm_bytes - a.op.weight_bytes for a in members)
        outs = self._charged(
            members, be,
            lambda: ex.compute(lambda: be.decode_step_batch(ctxs),
                               flops=flops, hbm_bytes=hbm,
                               tag=f"decode[x{len(members)}]"))
        stamp = len(self.real_batch_log)
        for a in members:
            a.batch_stamp = stamp
        self.batch_log.append(sum(a.op.tokens for a in members))
        self.real_batch_log.append(
            [(a.request.request_id, a.op.phase, a.op.weight_key)
             for a in members])
        for a, send in zip(members, outs):
            a.plan.clock.t = ex.now()
            try:
                a.op = a.plan.gen.send(send)
                self._observe_ttft(a)
                self._maybe_handoff_real(a)
            except StopIteration as stop:
                active.remove(a)
                self._finish_real(a, done, stop.value)

    def _real_chunk_batch(self, active: List[_Active]) -> Optional[List[_Active]]:
        """Assemble one real-mode batched prefill-chunk pass, or None.

        Mirrors :meth:`_real_decode_batch` for the *final* chunk ops of
        chunked prefill layers (the ones stamped with a
        :class:`PrefillChunkCtx`): consecutive same-layer chunk ComputeOps
        from different plans coalesce into one vmapped ``part_b_batch``
        kernel call, streaming the layer's weights once.  Members must share
        a backend, the layer and identical array shapes (``shape_key()``) —
        the batched pass vmaps the single-request part-B, so ragged members
        cannot mix.  Aging via ``batch_stamp`` keeps trimming fair, and a
        single candidate returns None (standalone ``op.fn`` path, keeping
        concurrency-1 bit-identical to ``drive_serial``).
        """
        if not self.batch_decode:
            return None
        cands = [a for a in active
                 if isinstance(a.op, ComputeOp) and a.op.phase == "prefill"
                 and isinstance(a.op.batch_ctx, PrefillChunkCtx)]
        if len(cands) < 2:
            return None
        cands.sort(key=lambda a: (a.batch_stamp, a.request.request_id))
        groups: Dict[tuple, List[_Active]] = {}
        for a in cands:
            ctx = a.op.batch_ctx
            key = (id(ctx.backend), ctx.shape_key())
            groups.setdefault(key, []).append(a)
        members = min(groups.values(),
                      key=lambda g: (g[0].batch_stamp, -len(g),
                                     g[0].request.request_id))
        if self.max_batch_tokens is not None:
            budget, trimmed = 0, []
            for a in members:
                if budget + a.op.tokens > self.max_batch_tokens:
                    break
                trimmed.append(a)
                budget += a.op.tokens
            members = trimmed
        return members if len(members) >= 2 else None

    def _step_real_chunk_batch(self, members: List[_Active], active, done):
        """One vmapped part-B pass for `members`' same-layer final chunks."""
        ex = self.ex
        ctxs = [a.op.batch_ctx for a in members]
        be = ctxs[0].backend
        flops = sum(a.op.flops for a in members)
        weight = max(a.op.weight_bytes for a in members)
        hbm = weight + sum(a.op.hbm_bytes - a.op.weight_bytes for a in members)
        outs = self._charged(
            members, be,
            lambda: ex.compute(lambda: be.part_b_batch(ctxs), flops=flops,
                               hbm_bytes=hbm,
                               tag=f"prefill_chunk[x{len(members)}]"))
        stamp = len(self.real_batch_log)
        for a in members:
            a.batch_stamp = stamp
        self.batch_log.append(sum(a.op.tokens for a in members))
        self.real_batch_log.append(
            [(a.request.request_id, a.op.phase, a.op.weight_key)
             for a in members])
        for a, send in zip(members, outs):
            a.plan.clock.t = ex.now()
            try:
                a.op = a.plan.gen.send(send)
                self._observe_ttft(a)
                self._maybe_handoff_real(a)
            except StopIteration as stop:
                active.remove(a)
                self._finish_real(a, done, stop.value)

    def _charged(self, members: List[_Active], be, run):
        """`run()` one compute of `members`' ops on backend `be`, and share
        the blocking device-to-host reads and upload bytes the backend made
        in it evenly among the members' traces."""
        syncs = getattr(be, "d2h_syncs", None)
        if syncs is None:  # a backend without counters (sim, stubs)
            return run()
        up = be.h2d_bytes
        out = run()
        n = len(members)
        for a in members:
            a.plan.trace.d2h_syncs += (be.d2h_syncs - syncs) / n
            a.plan.trace.h2d_bytes += (be.h2d_bytes - up) / n
        return out

    def _op_backend(self, a: _Active):
        """The backend a plan's pending ComputeOp runs on: a decode step
        follows its ctx (restamped at a disaggregated handoff), everything
        else runs on the plan's engine."""
        be = getattr(a.op.batch_ctx, "backend", None)
        if be is None:
            be = getattr(self.engines[a.request.tenant], "backend", None)
        return be

    def _stall_begins(self, a: _Active, fut):
        """The first poll found `a`'s wait pending: start its stall now and
        stamp its end, on the executor clock, when the transfer completes
        (on the I/O thread, or here if it completed in between)."""
        stall = [self.ex.now(), None]

        def stamp(_):
            stall[1] = self.ex.now()

        a.stall = stall
        fut.add_done_callback(stamp)

    def _stall_ends(self, a: _Active):
        """`a`'s pending wait is done: add its stall to the trace if the
        plan has not yet produced its first token."""
        start, end = a.stall
        a.stall = None
        trace = a.plan.trace
        if not trace.ttft:
            # a completion whose callback has not run yet ends now
            trace.io_stall_s += (end if end is not None
                                 else self.ex.now()) - start

    def _run_real(self, requests: List[Request]) -> List[CompletedRequest]:
        """The wall-clock driver.  Spans (:mod:`repro.obs`): ``sched.run``
        around the whole loop (the driver's own per-pass work is its self
        time), ``sched.admit`` per admission, ``sched.decode_batch`` /
        ``sched.chunk_batch`` per batched pass, ``sched.io_wait`` while
        every plan waits on I/O, ``sched.idle`` while nothing is admitted
        and the next arrival is in the future; none per op or per pass."""
        ex = self.ex
        pending = sorted(requests, key=lambda r: (r.arrival, r.request_id))
        active: List[_Active] = []
        preempted: List[_Active] = []
        done: List[CompletedRequest] = []
        t0 = self._run_start = ex.now()
        with obs.span("sched.run"):
            while pending or active or preempted:
                self._real_pass(pending, active, preempted, done, t0)
        done.sort(key=lambda c: c.request.request_id)
        return done

    def _real_pass(self, pending, active, preempted, done, t0: float):
        """One iteration of the wall-clock driver."""
        ex = self.ex
        self._resume_real(preempted, active)
        # arrival-aware admission: only requests whose offset has passed
        # on the wall clock may enter — the open-loop trace shape (and
        # therefore what each iteration can batch) matches the sim driver
        while pending and len(active) < self.max_concurrency:
            arrived = [r for r in pending
                       if r.arrival <= ex.now() - t0]
            if not arrived:
                break
            with obs.span("sched.admit"):
                req = self.policy.select(arrived, self.engines)
                pending.remove(req)
                self._start_real(req, active, done)
        self._preempt_real(pending, active, preempted, t0, done)
        progressed = False
        # iteration-level batching: coalesce runnable decode steps into
        # one kernel pass; prefill/IO ops keep the cooperative
        # round-robin below.  Candidates left out of this iteration's
        # batch (backend mismatch, token budget) stay runnable and are
        # skipped this pass so no plan advances twice per iteration.
        members = self._real_decode_batch(active)
        skip = set()
        if members is not None:
            with obs.span("sched.decode_batch"):
                self._step_real_batch(members, active, done)
            progressed = True
            skip = {id(a) for a in active
                    if isinstance(a.op, ComputeOp)
                    and a.op.phase == "decode"
                    and a.op.batch_ctx is not None}
        # same-layer prefill chunk coalescing (disjoint from the decode
        # batch: different phase, so no plan can be in both)
        chunk_members = self._real_chunk_batch(active)
        if chunk_members is not None:
            with obs.span("sched.chunk_batch"):
                self._step_real_chunk_batch(chunk_members, active, done)
            progressed = True
            skip |= {id(a) for a in active
                     if isinstance(a.op, ComputeOp)
                     and a.op.phase == "prefill"
                     and isinstance(a.op.batch_ctx, PrefillChunkCtx)}
        for a in list(active):
            if id(a) in skip:
                continue
            op = a.op
            if isinstance(op, WaitOp):
                f = op.handle.future
                if f is not None and not f.done():
                    if a.stall is None:
                        self._stall_begins(a, f)
                    continue  # not ready: let another plan use the window
                if a.stall is not None:
                    self._stall_ends(a)
                send = resolve_handle(op.handle)
            else:
                send = self._charged(
                    [a], self._op_backend(a),
                    lambda: ex.compute(op.fn, flops=op.flops,
                                       hbm_bytes=op.hbm_bytes, tag=op.tag))
            a.plan.clock.t = ex.now()
            progressed = True
            try:
                a.op = a.plan.gen.send(send)
                self._observe_ttft(a)
                self._maybe_handoff_real(a)
            except StopIteration as stop:
                active.remove(a)
                self._finish_real(a, done, stop.value)
        if not progressed and active:
            # every plan is blocked on a pending future: sleep on the I/O
            futs = [a.op.handle.future for a in active
                    if isinstance(a.op, WaitOp)
                    and a.op.handle.future is not None]
            with obs.span("sched.io_wait"):
                futures_wait(futs, return_when=FIRST_COMPLETED)
        elif not progressed and pending:
            # idle system, all remaining traffic is in the future: sleep
            # through the gap to the next arrival instead of spinning
            gap = min(r.arrival for r in pending) - (ex.now() - t0)
            if gap > 0:
                with obs.span("sched.idle"):
                    time.sleep(gap)


# ---------------------------------------------------------------------------
# summary helpers
# ---------------------------------------------------------------------------
def summarize(completed: Sequence[CompletedRequest]) -> Dict[str, float]:
    """Latency/goodput digest of one serving run.

    Decode-phase metrics (mean TPOT, P50/P95 inter-token latency, decode
    token throughput) appear whenever any completed request generated
    tokens past the first."""
    if not completed:
        return {"n": 0}
    ttfts = np.array([c.ttft for c in completed])
    arrivals = np.array([c.due for c in completed])
    finishes = np.array([c.finish for c in completed])
    makespan = float(finishes.max() - arrivals.min())
    out = {
        "n": len(completed),
        "p50_ttft": float(np.percentile(ttfts, 50)),
        "p95_ttft": float(np.percentile(ttfts, 95)),
        "mean_ttft": float(ttfts.mean()),
        "max_ttft": float(ttfts.max()),
        "makespan": makespan,
        "goodput_rps": len(completed) / max(makespan, 1e-12),
        "mean_queue_delay": float(np.mean([c.queue_delay for c in completed])),
    }
    itls = [c.trace.inter_token_latencies() for c in completed
            if getattr(c.trace, "decode_times", None)]
    if itls:
        all_itl = np.concatenate(itls)
        tpots = [c.trace.tpot for c in completed if c.trace.decode_times]
        n_tokens = int(sum(len(x) for x in itls))
        out.update({
            "decode_tokens": n_tokens,
            "mean_tpot": float(np.mean(tpots)),
            "p50_itl": float(np.percentile(all_itl, 50)),
            "p95_itl": float(np.percentile(all_itl, 95)),
            "decode_tok_rate": n_tokens / max(makespan, 1e-12),
        })
    slo = [c.slo_met for c in completed if c.slo_met is not None]
    if slo:
        out["slo_attainment"] = float(np.mean(slo))
    out["preemptions"] = int(sum(getattr(c, "preemptions", 0) for c in completed))
    out["swaps"] = int(sum(getattr(c, "swaps", 0) for c in completed))
    return out
