"""Serving driver: concurrent request streams through the step-plan scheduler.

Real mode (default) ingests a shared ``--prefix-len``-token prefix into the
file-backed chunk store once, then serves a stream of requests concurrently —
plans cooperatively multiplex over the thread-pool I/O, so one request's chunk
reads overlap another's compute.  The model keeps its published widths with
the depth cut to ``--n-layers`` (qwen2.5-7b at 8 layers fits one 16 GB v5e
chip); ``--reduced`` swaps in the tiny same-family config for CPU runs:

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-7b \
      --system contiguous_kv --budget 0.25 --requests 8 --concurrency 4
  PYTHONPATH=src JAX_PLATFORMS=cpu python -m repro.launch.serve --reduced \
      --prefix-len 512 --requests 6 --concurrency 3

Sim mode runs paper-scale multi-tenant serving on the calibrated
discrete-event channels with Poisson/burst arrivals and prints the
latency/goodput digest:

  PYTHONPATH=src python -m repro.launch.serve --mode sim --model qwen2.5-7b \
      --tenants 4 --requests 32 --concurrency 4 --policy cache_aware

``--decode-tokens N`` extends every request past the first token: per-token
sparse decode steps run through the scheduler's continuous batching and the
digest adds mean TPOT / inter-token P50/P95 / decode token throughput.
``--ttft-slo S`` attaches a TTFT deadline to every request (pair with
``--policy slo_aware`` for earliest-deadline-first admission).

``--prefill-chunk-tokens C`` plans prefill as resumable C-token chunks that
the sim scheduler mixes into decode iterations (token-level continuous
batching); ``--max-batch-tokens B`` caps each iteration's batch tokens.
``--preempt`` enables SLO-driven preemption of decode plans in both modes;
with ``--swap-on-preempt`` the victim's state is swapped out and restored on
resume — priced through the PCIe cost model in sim, actual D2H/H2D
round-trips of the victim's device-resident TailPools in real mode (the
digest prints preemption/swap counts and bytes either way).
``--host-tail-pool`` forces the PR-4 host-resident decode pools in real mode
(per-step H2D re-upload) for comparison/debugging.

``--disaggregate P:D`` splits serving into P prefill workers and D decode
workers with an explicit KV-handoff channel.  Sim mode models each worker as
its own FIFO compute channel plus one shared interconnect; real mode builds D
extra backend instances (sharing the colocated params, so logits stay
bit-identical) and hands each plan's device tail pools across at the
prefill/decode boundary via the PR-5 swap_out/swap_in contract.  The digest
adds handoff counts/bytes and (sim, with ``--hybrid-reprefill``) how many
handoffs the planner priced as decode-side recompute instead of a KV pull.

``--replicas N`` scales the serving tier to N data-parallel replicas behind
the one Scheduler: sim mode gives each replica its own compute channel
("compute:r{i}") and real mode builds one backend instance per replica
(decode phases move there via the tail-pool handoff).  Composes with
``--disaggregate P:D``: each replica then owns its own P prefill + D decode
worker channels.  ``--tp-decode K`` (real mode) runs the decode-batch paged
attention tensor-parallel over the local devices via shard_map
(``make_sharded_paged_decode``); K > 0 factors the mesh GQA-style into
(kv=K, rep=n/K), K = 0 uses one flat "model" axis over all devices.

``--fleet model:count,model:count`` serves a *heterogeneous* fleet behind the
one Scheduler — e.g. ``--fleet qwen2_5_7b:2,falcon_mamba_7b:1,
granite_moe_3b_a800m:1`` mixes dense, SSM and MoE tenants.  Attention-family
tenants keep the requested ``--system`` KV engine; ssm/hybrid tenants get the
family-aware :class:`repro.core.engine.StateSpaceEngine` (constant per-step
decode bytes over a recurrent StatePool instead of a growing KV tail).  Every
op's weight stream is namespaced per model, so iterations interleave across
families but a batch never amortizes one model's weights against another's.
Sim mode composes with ``--replicas``/``--disaggregate``; real mode builds
one real backend per tenant model (``--fleet`` with real-mode
``--replicas``/``--disaggregate`` is rejected — per-model worker backends
are not wired yet).

``--trace-dir DIR`` (real mode) turns on the program's spans
(:mod:`repro.obs`: ``ckv/sched.*``, ``ckv/eng.*``, ``ckv/store.read``,
``ckv/step.*``) and writes a profiler trace of the request loop under DIR,
host spans and device operations on one clock.

``--cache-tiers HBM:DRAM:SSD`` (unit capacities, contiguous_kv) upgrades the
shared cache to the content-addressed three-tier
:class:`repro.storage.tierstore.TieredPrefixStore`: host-DRAM victims demote
into a log-structured SSD segment tier (and promote back on access) instead
of dropping, and cache keys become (prefix_digest, layer, unit) so identical
prompts dedupe to one resident copy.  In sim mode ``--shared-prefix K``
marks the first K tenants as serving one identical system prompt (one
digest, one deduped entry, one importance field); the digest prints
per-tier hit counts, SSD log read amplification and the units dedup saved.
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import pathlib

import numpy as np

from repro.serving import (
    POLICIES,
    DisaggTopology,
    ReplicaSet,
    Request,
    Scheduler,
    make_arrivals,
    summarize,
)
from repro.serving.tenancy import (
    ENGINE_CLASSES,
    build_sim_fleet,
    parse_fleet_spec,
)


def _parse_cache_tiers(spec: str):
    """"HBM:DRAM:SSD" unit capacities -> (device_cap, host_cap, ssd_cap)."""
    parts = spec.split(":")
    if len(parts) != 3:
        raise SystemExit(f"--cache-tiers wants HBM:DRAM:SSD, got {spec!r}")
    try:
        caps = tuple(int(p) for p in parts)
    except ValueError:
        raise SystemExit(f"--cache-tiers capacities must be ints: {spec!r}")
    if caps[0] < 1 or caps[1] < 0 or caps[2] < 0:
        raise SystemExit(f"--cache-tiers capacities out of range: {spec!r}")
    return caps


def _print_tier_digest(cache):
    if not hasattr(cache, "ssd"):
        return
    h = cache.hits
    total = sum(h.values()) + cache.misses
    occ = cache.tier_occupancy()
    print(f"tier store: hits device={h['device']} host={h['host']} "
          f"ssd={h['ssd']} misses={cache.misses} "
          f"(hit rate {100 * (total - cache.misses) / max(total, 1):.1f}%) "
          f"resident d/h/s={occ['device']}/{occ['host']}/{occ['ssd']}")
    lay = cache.ssd.layout
    print(f"ssd log: {lay.live_units()} live units in "
          f"{len(lay.segments)} segments ({lay.total_bytes/1e6:.2f}MB), "
          f"read_amp={cache.read_amplification():.3f}, "
          f"compaction moved {cache.ssd.compaction.units_read} units; "
          f"dedup saved {cache.dedup_saved_units()} resident units")


def _print_replica_digest(sched):
    if sched.replicas is None:
        return
    reps = sched.replicas
    admits = "/".join(str(n) for n in sched.replica_admits)
    suffix = (f" x {reps.topology.n_prefill}P:{reps.topology.n_decode}D each"
              if reps.topology is not None else "")
    print(f"replicas={reps.n_replicas}{suffix}: admissions {admits}")


def _print_handoff_digest(sched):
    topo = (sched.replicas.topology if sched.replicas is not None
            else sched.topology)
    if topo is None:
        return
    print(f"disaggregated {topo.n_prefill}P:{topo.n_decode}D: "
          f"handoffs={sched.handoffs} "
          f"kv_bytes={sched.handoff_bytes/1e6:.2f}MB", end="")
    if sched.handoff_recomputes:
        print(f" (+{sched.handoff_recomputes} priced as decode-side "
              f"recompute, {sched.handoff_bytes_avoided/1e6:.2f}MB "
              f"interconnect avoided)", end="")
    print()


def use_compile_cache() -> None:
    """Keep JAX's persistent compilation cache where the caller can find it.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting and wins;
    otherwise the cache lives in ``.jax_cache/`` at the checkout root, a
    fixed path (the path is part of every cache key, so a directory that
    moved between runs would never hit).  Called from entry points, never
    at import."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    import jax

    root = pathlib.Path(__file__).resolve().parents[3]
    jax.config.update("jax_compilation_cache_dir", str(root / ".jax_cache"))


def run_requests(sched: Scheduler, requests, args) -> list:
    """Serve `requests`; with ``--trace-dir`` the program's spans are on
    and a profiler trace of the request loop is written there."""
    if not args.trace_dir:
        return sched.run(requests)
    from repro import obs

    with obs.trace_to(args.trace_dir):
        completed = sched.run(requests)
    print(f"profiler trace of the request loop: {args.trace_dir}")
    return completed


def real_config(name: str, args):
    """The model a real-mode run serves: the published widths with only the
    depth cut to ``--n-layers``, or, with ``--reduced``, the tiny
    same-family config the CPU tests use."""
    from repro.configs import get_config, reduced_config

    if args.reduced:
        return reduced_config(name, n_layers=args.n_layers)
    return dataclasses.replace(get_config(name), n_layers=args.n_layers)


@dataclasses.dataclass
class RealRun:
    """What one real-mode serving run leaves behind for a calling script:
    the served requests, plus the engine (its backend, session and
    file-backed prefix store stay usable) and the scheduler."""

    cfg: object
    task: object
    engine: object
    sched: Scheduler
    completed: list


def serve_real(args) -> RealRun:
    """Real mode: ingest a ``--prefix-len``-token shared prefix into the
    file-backed chunk store, then serve ``--requests`` requests through the
    Scheduler; prints the per-request lines and the digest."""
    import jax

    from repro.core import build_real_session
    from repro.core.backends import RealCompute
    from repro.data.synthetic import make_task
    from repro.models import transformer as T
    from repro.storage.timing import RealExecutor

    cfg = real_config(args.arch, args)
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    task = make_task(args.dataset, cfg.vocab_size, n_queries=args.requests,
                     prefix_len=args.prefix_len)
    print(f"ingesting shared prefix: {len(task.prefix)} tokens "
          f"({args.dataset}, {cfg.name}: {cfg.n_layers} layers, d_model "
          f"{cfg.d_model}, vocab {cfg.vocab_size})")
    coarse = args.system != "contiguous_kv"
    sess = build_real_session(cfg, params, task.prefix,
                              chunk_tokens=args.chunk_tokens,
                              coarse_blocks=coarse)
    ex = RealExecutor()
    from repro.core.hybrid import HybridPlanner

    hybrid = (None if args.hybrid_reprefill == "off"
              else HybridPlanner(args.hybrid_reprefill))
    kw = dict(device_cap=64, host_cap=128,
              prefill_chunk_tokens=args.prefill_chunk_tokens,
              device_tail_pool=not args.host_tail_pool,
              hybrid=hybrid)
    if args.system == "contiguous_kv":
        kw.update(budget=args.budget, period=args.period, subperiod=args.subperiod)
        if args.cache_tiers:
            from repro.storage.tierstore import TieredPrefixStore

            dcap, hcap, scap = _parse_cache_tiers(args.cache_tiers)
            kw["cache"] = TieredPrefixStore(
                dcap, hcap, scap, unit_bytes=sess.store.layout.unit_bytes,
                payload_mode="memory", unit_shape=sess.store.unit_shape)
            print(f"tiered prefix store: HBM={dcap} DRAM={hcap} SSD={scap} "
                  f"units, digest={sess.digest}")
    elif args.cache_tiers:
        raise SystemExit("--cache-tiers needs --system contiguous_kv")
    elif args.system != "as_lru":
        kw.update(budget=args.budget)
    tp_mesh = None
    if args.tp_decode is not None:
        from repro.launch.mesh import make_serving_mesh

        tp_mesh = make_serving_mesh(kv_split=args.tp_decode)
        print(f"tensor-parallel decode: {len(jax.devices())} devices, "
              f"mesh {dict(tp_mesh.shape)}")
    eng = ENGINE_CLASSES[args.system](
        sess, RealCompute(cfg, params, tp_mesh=tp_mesh), ex, **kw)

    topology = None
    if args.disaggregate:
        topology = DisaggTopology.parse(args.disaggregate)
    replicas = None
    if args.replicas:
        n = ReplicaSet.parse(args.replicas).n_replicas
        workers = topology.n_decode if topology is not None else 1
        # every worker backend shares the colocated params: bit-identical
        # logits regardless of which replica serves the decode phase
        replicas = ReplicaSet(
            topology=topology,
            backends=[[RealCompute(cfg, params, tp_mesh=tp_mesh)
                       for _ in range(workers)] for _ in range(n)])
        split = (f" x {topology.n_prefill}P:{topology.n_decode}D each"
                 if topology is not None else "")
        print(f"replicating: {n} data-parallel replicas{split} "
              f"(pool handoff at decode)")
    elif topology is not None:
        # decode workers share the colocated params: bit-identical logits
        topology.decode_backends = [RealCompute(cfg, params, tp_mesh=tp_mesh)
                                    for _ in range(topology.n_decode)]
        print(f"disaggregating: {topology.n_prefill} prefill / "
              f"{topology.n_decode} decode workers (pool handoff)")

    requests = [Request(request_id=rid, suffix=suffix,
                        decode_tokens=args.decode_tokens,
                        ttft_target=args.ttft_slo)
                for rid, (suffix, _) in enumerate(task.queries)]
    sched = Scheduler(eng, policy=args.policy, max_concurrency=args.concurrency,
                      batch_decode=not args.no_batch_decode,
                      max_batch_tokens=args.max_batch_tokens,
                      preempt=args.preempt,
                      swap_on_preempt=args.swap_on_preempt,
                      prefill_estimate=args.prefill_estimate,
                      topology=topology, replicas=replicas)
    completed = run_requests(sched, requests, args)

    correct = 0
    for c in completed:
        rid = c.request.request_id
        _, gold = task.queries[rid]
        pred = int(np.argmax(c.result[0, -1]))
        correct += int(pred == task.label_token(gold))
        tr = c.trace
        dec = (f" tpot={tr.tpot*1e3:6.1f}ms ({tr.n_decoded} tok)"
               if tr.decode_times else "")
        print(f"req {rid:2d}: ttft={c.ttft*1e3:7.1f}ms ssd={tr.ssd_bytes/1e3:8.1f}KB "
              f"amp={tr.read_amplification:5.2f} hits(d/h)={tr.hits_device}/{tr.hits_host}"
              f"{dec}")
    s = summarize(completed)
    print(f"concurrency={args.concurrency} policy={args.policy} "
          f"p50={s['p50_ttft']*1e3:.1f}ms p95={s['p95_ttft']*1e3:.1f}ms "
          f"goodput={s['goodput_rps']:.2f} req/s")
    if "mean_tpot" in s:
        print(f"decode: mean TPOT={s['mean_tpot']*1e3:.1f}ms "
              f"ITL p95={s['p95_itl']*1e3:.1f}ms "
              f"{s['decode_tok_rate']:.1f} tok/s")
    if sched.real_batch_log:
        sizes = [len(b) for b in sched.real_batch_log]
        print(f"batched iterations: {len(sizes)} "
              f"(mean b={np.mean(sizes):.2f}, max b={max(sizes)})")
    rec_units = sum(c.trace.recompute_units for c in completed)
    if rec_units:
        avoided = sum(c.trace.ssd_bytes_avoided for c in completed)
        print(f"hybrid re-prefill: {rec_units} units recomputed, "
              f"{avoided/1e6:.2f}MB SSD reads avoided")
    if args.preempt:
        pools = "host" if args.host_tail_pool else "device"
        print(f"preemptions={s['preemptions']} swaps={s['swaps']} "
              f"swap_bytes={sched.swap_bytes/1e6:.2f}MB ({pools} tail pools)")
    _print_tier_digest(eng.cache)
    _print_replica_digest(sched)
    _print_handoff_digest(sched)
    if args.decode_tokens == 0:
        # with decode, c.result is the *last* token's logits, not the label
        print(f"label-token accuracy (untrained model => chance-level): "
              f"{correct}/{len(task.queries)}")
    return RealRun(cfg=cfg, task=task, engine=eng, sched=sched,
                   completed=completed)


def _real_fleet_main(args):
    """Real-mode heterogeneous fleet: one real backend per tenant model,
    every family iteration-batched behind the one wall-clock Scheduler."""
    import jax

    from repro.core import build_real_session
    from repro.core.backends import RealCompute, StateCompute
    from repro.core.engine import StateSpaceEngine
    from repro.data.synthetic import make_task
    from repro.models import transformer as T
    from repro.storage.timing import RealExecutor

    if args.disaggregate or args.replicas or args.tp_decode is not None:
        raise SystemExit("--fleet in real mode does not compose with "
                         "--disaggregate/--replicas/--tp-decode (per-model "
                         "worker backends are not wired); use --mode sim")
    entries = [(real_config(name, args), count)
               for name, count in parse_fleet_spec(args.fleet)]
    # one synthetic task over the smallest vocab, so every model of the
    # fleet serves the same prompt/query stream
    task = make_task(args.dataset, min(c.vocab_size for c, _ in entries),
                     n_queries=args.requests, prefix_len=args.prefix_len)
    print(f"ingesting shared prefix: {len(task.prefix)} tokens "
          f"({args.dataset})")
    ex = RealExecutor()
    engines, cfgs = {}, {}
    tenant = 0
    for cfg, count in entries:
        params = T.init_params(jax.random.PRNGKey(0), cfg)
        for _ in range(count):
            tenant += 1
            cfgs[tenant] = cfg
            if cfg.family in ("ssm", "hybrid"):
                engines[tenant] = StateSpaceEngine(
                    cfg, StateCompute(cfg, params), ex,
                    prefix_tokens=task.prefix, tenant=tenant,
                    prefill_chunk_tokens=args.prefill_chunk_tokens)
                continue
            coarse = args.system != "contiguous_kv"
            sess = build_real_session(cfg, params, task.prefix,
                                      chunk_tokens=args.chunk_tokens,
                                      coarse_blocks=coarse)
            sess = dataclasses.replace(sess, tenant=tenant)
            kw = dict(device_cap=64, host_cap=128,
                      prefill_chunk_tokens=args.prefill_chunk_tokens,
                      device_tail_pool=not args.host_tail_pool)
            if args.system == "contiguous_kv":
                kw.update(budget=args.budget, period=args.period,
                          subperiod=args.subperiod)
            elif args.system != "as_lru":
                kw.update(budget=args.budget)
            engines[tenant] = ENGINE_CLASSES[args.system](
                sess, RealCompute(cfg, params), ex, **kw)
    roster = ", ".join(f"t{t}={c.name}[{c.family}]"
                       for t, c in sorted(cfgs.items()))
    print(f"heterogeneous fleet: {roster}")
    requests = [Request(request_id=rid, suffix=suffix,
                        tenant=1 + rid % len(engines),
                        decode_tokens=args.decode_tokens,
                        ttft_target=args.ttft_slo)
                for rid, (suffix, _) in enumerate(task.queries)]
    sched = Scheduler(engines, policy=args.policy,
                      max_concurrency=args.concurrency,
                      batch_decode=not args.no_batch_decode,
                      max_batch_tokens=args.max_batch_tokens,
                      preempt=args.preempt,
                      swap_on_preempt=args.swap_on_preempt,
                      prefill_estimate=args.prefill_estimate)
    completed = run_requests(sched, requests, args)
    for c in completed:
        tr = c.trace
        dec = (f" tpot={tr.tpot*1e3:6.1f}ms ({tr.n_decoded} tok)"
               if tr.decode_times else "")
        print(f"req {c.request.request_id:2d} "
              f"{cfgs[c.request.tenant].name:>24s}: "
              f"ttft={c.ttft*1e3:7.1f}ms{dec}")
    s = summarize(completed)
    print(f"concurrency={args.concurrency} policy={args.policy} "
          f"p50={s['p50_ttft']*1e3:.1f}ms p95={s['p95_ttft']*1e3:.1f}ms "
          f"goodput={s['goodput_rps']:.2f} req/s")
    if "mean_tpot" in s:
        print(f"decode: mean TPOT={s['mean_tpot']*1e3:.1f}ms "
              f"ITL p95={s['p95_itl']*1e3:.1f}ms "
              f"{s['decode_tok_rate']:.1f} tok/s")
    if sched.real_batch_log:
        sizes = [len(b) for b in sched.real_batch_log]
        print(f"batched iterations: {len(sizes)} "
              f"(mean b={np.mean(sizes):.2f}, max b={max(sizes)})")


def _sim_main(args):
    topology = (DisaggTopology.parse(args.disaggregate)
                if args.disaggregate else None)
    replicas = ReplicaSet.parse(args.replicas) if args.replicas else None
    if args.cache_tiers:
        if args.system != "contiguous_kv":
            raise SystemExit("--cache-tiers needs --system contiguous_kv")
        device_cap, host_cap, ssd_cap = _parse_cache_tiers(args.cache_tiers)
    else:
        device_cap, host_cap, ssd_cap = args.device_cap, args.host_cap, 0
    digests = None
    if args.shared_prefix > 1:
        # the first K tenants serve one identical system prompt (one content
        # digest -> one deduped resident copy in a content-addressed store);
        # the rest each get their own distinct digest
        k = min(args.shared_prefix, args.tenants)
        digests = {t: "prompt-shared" for t in range(1, k + 1)}
        digests.update({t: f"prompt-t{t}" for t in range(k + 1, args.tenants + 1)})
    fleet = build_sim_fleet(args.system, args.model, n_tenants=args.tenants,
                            prefix_len=args.prefix_len, budget=args.budget,
                            period=args.period, subperiod=args.subperiod,
                            device_cap=device_cap, host_cap=host_cap,
                            ssd_cap=ssd_cap,
                            prefill_chunk_tokens=args.prefill_chunk_tokens,
                            hybrid_reprefill=args.hybrid_reprefill,
                            topology=topology, replicas=replicas,
                            prefix_digests=digests, fleet=args.fleet)
    n_tenants = len(fleet.engines)
    if args.fleet:
        roster = ", ".join(f"t{t}={cfg.name}[{cfg.family}]"
                           for t, cfg in sorted(fleet.configs.items()))
        print(f"heterogeneous fleet: {roster}")
    arrivals = make_arrivals(args.arrival, args.rate, args.requests, seed=0)
    rng = np.random.default_rng(0)
    requests = [
        Request(request_id=i, suffix=rng.integers(0, 1000, 64),
                arrival=float(arrivals[i]),
                tenant=1 + i % n_tenants,
                decode_tokens=args.decode_tokens,
                ttft_target=args.ttft_slo)
        for i in range(args.requests)
    ]
    sched = Scheduler(fleet.engines, policy=args.policy,
                      max_concurrency=args.concurrency,
                      batch_decode=not args.no_batch_decode,
                      max_batch_tokens=args.max_batch_tokens,
                      preempt=args.preempt,
                      swap_on_preempt=args.swap_on_preempt,
                      prefill_estimate=args.prefill_estimate,
                      topology=topology, replicas=replicas)
    completed = sched.run(requests)
    for c in completed:
        tr = c.trace
        dec = (f" tpot={tr.tpot*1e3:6.1f}ms" if tr.decode_times else "")
        hits = f"hits(d/h)={tr.hits_device}/{tr.hits_host}"
        if args.cache_tiers:
            hits = (f"hits(d/h/s)={tr.hits_device}/{tr.hits_host}"
                    f"/{tr.hits_ssd}")
        print(f"req {c.request.request_id:3d} tenant={c.request.tenant} "
              f"arr={c.request.arrival*1e3:8.1f}ms queue={c.queue_delay*1e3:7.1f}ms "
              f"ttft={c.ttft*1e3:8.1f}ms {hits}{dec}")
    s = summarize(completed)
    print(f"\n{args.system} tenants={n_tenants} load={args.rate:.1f} req/s "
          f"concurrency={args.concurrency} policy={args.policy}")
    print(f"p50={s['p50_ttft']*1e3:.1f}ms p95={s['p95_ttft']*1e3:.1f}ms "
          f"goodput={s['goodput_rps']:.2f} req/s "
          f"mean_queue={s['mean_queue_delay']*1e3:.1f}ms")
    if "mean_tpot" in s:
        batched = "off" if args.no_batch_decode else "on"
        print(f"decode: {s['decode_tokens']} tokens, mean TPOT={s['mean_tpot']*1e3:.1f}ms "
              f"ITL p50/p95={s['p50_itl']*1e3:.1f}/{s['p95_itl']*1e3:.1f}ms "
              f"{s['decode_tok_rate']:.1f} tok/s (continuous batching {batched})")
    if "slo_attainment" in s:
        print(f"SLO attainment (ttft <= {args.ttft_slo*1e3:.0f}ms): "
              f"{100*s['slo_attainment']:.1f}%")
    if args.preempt:
        print(f"preemptions={s['preemptions']} swaps={s['swaps']} "
              f"swap_bytes={sched.swap_bytes/1e6:.1f}MB")
    rec_units = sum(c.trace.recompute_units for c in completed)
    if rec_units:
        avoided = sum(c.trace.ssd_bytes_avoided for c in completed)
        print(f"hybrid re-prefill: {rec_units} units recomputed, "
              f"{avoided/1e6:.2f}MB SSD reads avoided")
    if fleet.cache is not None:  # None: an all-SSM fleet has no KV cache
        _print_tier_digest(fleet.cache)
    _print_replica_digest(sched)
    _print_handoff_digest(sched)
    if fleet.cache is not None:
        usage = fleet.cache.tenant_usage()
        for tenant in sorted(usage):
            u = usage[tenant]
            ssd = f" ssd={u['ssd']}" if "ssd" in u else ""
            print(f"tenant {tenant}: cache device={u['device']} "
                  f"host={u['host']}{ssd} units")


def parse_args(argv=None) -> argparse.Namespace:
    """The serving CLI's options; ``argv=None`` reads ``sys.argv``."""
    p = argparse.ArgumentParser()
    p.add_argument("--mode", default="real", choices=("real", "sim"))
    p.add_argument("--system", default="contiguous_kv", choices=list(ENGINE_CLASSES))
    p.add_argument("--budget", type=float, default=0.25)
    p.add_argument("--chunk-tokens", type=int, default=16)
    p.add_argument("--period", type=int, default=4)
    p.add_argument("--subperiod", type=int, default=2)
    p.add_argument("--requests", type=int, default=8)
    p.add_argument("--concurrency", type=int, default=4)
    p.add_argument("--policy", default="fcfs", choices=list(POLICIES))
    p.add_argument("--decode-tokens", type=int, default=0,
                   help="tokens to generate past the first (decode phase)")
    p.add_argument("--ttft-slo", type=float, default=None,
                   help="per-request TTFT target in seconds (slo_aware policy)")
    p.add_argument("--no-batch-decode", action="store_true",
                   help="disable continuous batching of decode steps "
                        "(sim pricing and real batched kernel passes)")
    p.add_argument("--hybrid-reprefill", default="off",
                   choices=("off", "auto", "force-compute", "force-load"),
                   help="per-chunk recompute-vs-load planning for missing "
                        "prefix KV (auto prices both legs with the roofline "
                        "cost model)")
    p.add_argument("--prefill-chunk-tokens", type=int, default=None,
                   help="plan prefill as resumable chunks of this many "
                        "tokens (token-level prefill/decode mixing)")
    p.add_argument("--max-batch-tokens", type=int, default=None,
                   help="token budget of one batched iteration (sim)")
    p.add_argument("--preempt", action="store_true",
                   help="SLO-driven preemption of decode plans (sim + real)")
    p.add_argument("--swap-on-preempt", action="store_true",
                   help="swap the victim's state out/in: PCIe cost model in "
                        "sim, real TailPool D2H/H2D snapshots in real mode")
    p.add_argument("--host-tail-pool", action="store_true",
                   help="real mode: use the host-resident PR-4 TailPool "
                        "(per-step pool re-upload) instead of the "
                        "device-resident default")
    p.add_argument("--prefill-estimate", type=float, default=None,
                   help="floor (seconds) for the projected prefill service "
                        "time; the first-token EWMA raises it")
    p.add_argument("--disaggregate", default=None, metavar="P:D",
                   help="split serving into P prefill + D decode workers "
                        "with a KV-handoff channel (sim: per-worker FIFO "
                        "channels + interconnect; real: extra decode "
                        "backends + tail-pool handoff)")
    p.add_argument("--replicas", default=None, metavar="N",
                   help="data-parallel serving replicas behind one "
                        "Scheduler (sim: per-replica compute channels; "
                        "real: one backend per replica); composes with "
                        "--disaggregate into per-replica worker splits")
    p.add_argument("--tp-decode", type=int, default=None, metavar="K",
                   help="real mode: tensor-parallel paged decode attention "
                        "over the local devices via shard_map; K>0 factors "
                        "the mesh GQA-style into (kv=K, rep=n/K), K=0 uses "
                        "one flat tensor axis")
    # real mode
    p.add_argument("--trace-dir", default=None, metavar="DIR",
                   help="real mode: enable the program's spans and write a "
                        "profiler trace of the request loop under DIR")
    p.add_argument("--arch", default="qwen2.5-7b")
    p.add_argument("--dataset", default="rte")
    p.add_argument("--n-layers", type=int, default=8,
                   help="real mode: depth the model is cut to (its widths "
                        "stay the published ones unless --reduced)")
    p.add_argument("--reduced", action="store_true",
                   help="real mode: serve the tiny same-family config "
                        "(d_model 64, vocab 256) the CPU tests use instead "
                        "of the published widths")
    p.add_argument("--prefix-len", type=int, default=4096,
                   help="shared prefix tokens (real: drawn from --dataset's "
                        "few-shot stream; sim: the modelled prefix length)")
    # sim mode
    p.add_argument("--model", default="qwen2.5-7b")
    p.add_argument("--tenants", type=int, default=1)
    p.add_argument("--rate", type=float, default=16.0, help="offered load, req/s")
    p.add_argument("--arrival", default="poisson",
                   choices=("poisson", "burst", "uniform"))
    p.add_argument("--device-cap", type=int, default=256)
    p.add_argument("--host-cap", type=int, default=1024)
    p.add_argument("--cache-tiers", default=None, metavar="HBM:DRAM:SSD",
                   help="unit capacities of the three-tier content-addressed "
                        "prefix store (contiguous_kv; e.g. 256:1024:4096); "
                        "replaces --device-cap/--host-cap and adds the "
                        "log-structured SSD tier")
    p.add_argument("--fleet", default=None, metavar="MODEL:N,MODEL:N",
                   help="heterogeneous fleet spec, e.g. qwen2_5_7b:2,"
                        "falcon_mamba_7b:1,granite_moe_3b_a800m:1 — "
                        "per-model engines (KV for attention families, "
                        "StateSpaceEngine for ssm/hybrid) behind one "
                        "Scheduler; overrides --model/--tenants (sim) and "
                        "--arch (real)")
    p.add_argument("--shared-prefix", type=int, default=0, metavar="K",
                   help="sim: the first K tenants serve one identical system "
                        "prompt (one content digest; with --cache-tiers it "
                        "dedupes to a single resident copy)")
    args = p.parse_args(argv)
    if args.tenants < 1:
        p.error("--tenants must be >= 1")
    if args.concurrency < 1:
        p.error("--concurrency must be >= 1")
    if args.trace_dir and args.mode == "sim":
        p.error("--trace-dir traces the real mode's request loop")
    return args


def main(argv=None):
    args = parse_args(argv)
    use_compile_cache()
    if args.mode == "sim":
        _sim_main(args)
    elif args.fleet:
        _real_fleet_main(args)
    else:
        serve_real(args)


if __name__ == "__main__":
    main()
