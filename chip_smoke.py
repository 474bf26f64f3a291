"""Smoke run of the serving system on a TPU: the quickest proof it starts.

    python chip_smoke.py               # one chip: phases (b) and (c)
    python chip_smoke.py --four-chips  # four chips: the TP decode comparison

All phases run in this one process, which never starts another: a chip
belongs to the process that first touches JAX.

(a) Device check.  Anything but a TPU exits non-zero before building a model.
(b) qwen2.5-7b at its published widths (d_model 3584, vocab 152064), cut to 8
    layers, through ``repro.launch.serve``'s real path: a 4096-token prefix is
    ingested into the file-backed chunk store, then 4 requests at concurrency
    2 each decode 16 tokens through the Scheduler.  Checks: every request
    completes with finite logits and reads prefix KV from the store; on the
    chip, the Pallas decode-attention kernel matches ``decode_attention_ref``
    on two requests' real decode pools, which then take one batched b=2
    decode step.
(c) falcon-mamba-7b at its published widths, cut to 2 layers, through
    ``StateCompute``: prefill plus 16 decode steps with the Pallas selective
    scan, checked step by step against the XLA recurrence (teacher-forced).
--four-chips runs only phase (b)'s requests with tensor-parallel decode
attention over the 4 chips (``--tp-decode 0``) and compares every decode
step's logits with the one-chip kernel path on the same pools and tokens.

Times printed here are smoke readings, not benchmark numbers: TTFT ends at a
blocking host copy of the first token's logits.  The last line of standard
output is the JSON result; it is printed only when every phase passed.
"""
from __future__ import annotations

import argparse
import copy
import dataclasses
import json
import pathlib
import sys
import time

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent
SRC = ROOT / "src"

# phase (b): the serving CLI's own options (no hybrid planner: its assumed
# device peaks must not steer a chip run)
SERVE_ARGS = ["--arch", "qwen2.5-7b", "--n-layers", "8",
              "--system", "contiguous_kv", "--budget", "0.25",
              "--prefix-len", "4096", "--requests", "4", "--concurrency", "2",
              "--decode-tokens", "16", "--hybrid-reprefill", "off"]
DECODE_TOKENS = 16

# kernel vs reference on one decode step's pools: bf16 output (the bf16
# tolerance of tests/test_kernels.py), fp32 per-page mass
OUT_TOL = dict(rtol=2e-2, atol=2e-2)
MASS_TOL = dict(rtol=1e-3, atol=1e-5)
# end-to-end logits of two bf16 paths on identical inputs (kernel vs XLA):
# relative L2 error ||a - b|| / ||b|| at the bf16 tolerance of the tests
LOGIT_TOL = 2e-2


def logit_err(got, ref):
    """(relative L2 error, max abs error) of one step's logits."""
    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    return (float(np.linalg.norm(got - ref) / np.linalg.norm(ref)),
            float(np.abs(got - ref).max()))


class CompileClock:
    """Seconds JAX spent in backend compiles (persistent-cache misses)."""

    def __init__(self, monitoring):
        self.seconds = 0.0
        monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration


def advance(plan, send):
    """Run `plan` until its next decode ComputeOp; returns that op, or None
    when the plan finished.  Prefill ops and I/O waits run as a serial
    driver would run them."""
    from repro.core.stepplan import ComputeOp, resolve_handle

    while True:
        try:
            op = plan.gen.send(send)
        except StopIteration:
            return None
        if isinstance(op, ComputeOp):
            if op.phase == "decode":
                return op
            send = op.fn() if op.fn is not None else None
        else:
            send = resolve_handle(op.handle)


def clone_pools(pools):
    """Device copies of a request's per-layer decode pools."""
    import jax.numpy as jnp

    out = {}
    for layer, pool in pools.items():
        c = copy.copy(pool)
        c.k, c.v = jnp.copy(pool.k), jnp.copy(pool.v)
        c._tbl_dev, c._tbl_n = None, -1
        out[layer] = c
    return out


def check_served(run):
    """Phase (b)'s end-to-end checks on a finished serving run."""
    completed = run.completed
    assert len(completed) == 4, f"{len(completed)} of 4 requests completed"
    store = run.engine.session.store
    assert store.path is not None, "prefix KV is not in the file-backed store"
    assert store.stats.bytes_read > 0, "no prefix KV was read from the store"
    for c in completed:
        tr = c.trace
        assert tr.n_decoded == DECODE_TOKENS, (
            f"request {c.request.request_id} decoded {tr.n_decoded} tokens")
        assert np.all(np.isfinite(c.result)), (
            f"request {c.request.request_id}: non-finite logits")
        print(f"smoke reading (not a benchmark): request "
              f"{c.request.request_id} ttft {c.ttft * 1e3:.1f} ms, "
              f"{tr.ssd_bytes} bytes read from the prefix store, "
              f"{tr.n_decoded} tokens decoded")
    # the Scheduler coalesces decode steps only when two plans reach decode
    # together; check_kernel runs the batched b=2 decode pass explicitly
    sizes = [len(b) for b in run.sched.real_batch_log]
    print(f"scheduler decode batches: {len(sizes)} coalesced iterations "
          f"{sorted(set(sizes))}; prefix store file read "
          f"{store.stats.bytes_read} bytes in {store.stats.requests} reads")


def check_kernel(run):
    """Pallas decode attention vs its reference on two requests' real
    decode pools (first and last layer, b=2), on the chip; then the same
    two requests take one batched b=2 decode step through the backend."""
    import jax
    import jax.numpy as jnp

    from repro.core.backends import stack_tail_pools
    from repro.kernels.decode_attention.ops import decode_attention

    cfg, eng = run.cfg, run.engine
    plans, ctxs = [], []
    for rid in (0, 1):
        suffix, _ = run.task.queries[rid]
        plan = eng.plan(suffix, request_id=100 + rid, decode_tokens=1)
        op = advance(plan, None)
        plans.append(plan)
        ctxs.append(op.batch_ctx)
    key = jax.random.PRNGKey(1)
    for layer in (0, cfg.n_layers - 1):
        k, v, table, lengths = stack_tail_pools(
            [c.pools[layer] for c in ctxs])
        key, sub = jax.random.split(key)
        q = jax.random.normal(sub, (2, cfg.n_heads, cfg.d_head),
                              jnp.float32).astype(k.dtype)
        out, mass = decode_attention(q, k, v, table, lengths)
        with jax.default_matmul_precision("highest"):
            ref, ref_mass = decode_attention(q, k, v, table, lengths,
                                             use_kernel=False)
        out, ref = np.asarray(out, np.float32), np.asarray(ref, np.float32)
        mass, ref_mass = np.asarray(mass), np.asarray(ref_mass)
        np.testing.assert_allclose(out, ref, **OUT_TOL)
        np.testing.assert_allclose(mass, ref_mass, **MASS_TOL)
        print(f"decode_attention kernel vs reference, layer {layer}: pools "
              f"{tuple(k.shape)} {k.dtype}, lengths {np.asarray(lengths)}; "
              f"max|out err| {np.abs(out - ref).max():.3e} (tol {OUT_TOL}), "
              f"max|mass err| {np.abs(mass - ref_mass).max():.3e} "
              f"(tol {MASS_TOL})")
    outs = eng.backend.decode_step_batch(ctxs)
    for plan, out in zip(plans, outs):
        assert np.all(np.isfinite(out[0])), "non-finite batched decode logits"
        assert advance(plan, out) is None, "plan did not finish"
    print(f"batched decode step b={len(outs)}: finite logits "
          f"{tuple(outs[0][0].shape)}")


def phase_serve(serve, clock):
    t0, c0 = time.perf_counter(), clock.seconds
    run = serve.serve_real(serve.parse_args(SERVE_ARGS))
    cfg = run.cfg
    assert (cfg.d_model, cfg.vocab_size, cfg.n_layers) == (3584, 152064, 8)
    assert len(run.task.prefix) == 4096
    check_served(run)
    check_kernel(run)
    run.engine.session.store.close()
    print(f"phase (b) passed: qwen2.5-7b d_model {cfg.d_model} vocab "
          f"{cfg.vocab_size} {cfg.n_layers} layers; compile "
          f"{clock.seconds - c0:.1f} s, wall {time.perf_counter() - t0:.1f} s "
          f"(smoke reading)")


def phase_ssm(clock, cfg):
    import jax

    from repro.core.backends import StateCompute
    from repro.data.synthetic import make_task
    from repro.models import transformer as T

    t0, c0 = time.perf_counter(), clock.seconds
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    prompt = make_task("rte", cfg.vocab_size, n_queries=1,
                       prefix_len=1024).prefix
    kernel = StateCompute(cfg, params, ssm_kernel=True)
    ref = StateCompute(cfg, params, ssm_kernel=False)
    logits, pool = kernel.prefill(prompt, extra_tokens=DECODE_TOKENS)
    assert np.all(np.isfinite(logits)), "non-finite prefill logits"
    state_k = state_r = pool.state
    tok = int(np.argmax(logits[0, -1]))
    errs = []
    for step in range(DECODE_TOKENS):
        lk, state_k = kernel.decode_step(tok, state_k)
        lr, state_r = ref.decode_step(tok, state_r)
        assert np.all(np.isfinite(lk)), f"non-finite logits at step {step}"
        errs.append(logit_err(lk, lr))
        tok = int(np.argmax(lr[0, -1]))  # both paths get the same token
    h_err = float(np.abs(np.asarray(state_k["ssm_h"])
                         - np.asarray(state_r["ssm_h"])).max())
    rel, mx = np.max(errs, axis=0)
    print(f"falcon-mamba-7b d_model {cfg.d_model} d_inner {cfg.d_inner} "
          f"{cfg.n_layers} layers, prefill {len(prompt)} tokens + "
          f"{DECODE_TOKENS} decode steps; selective_scan kernel vs XLA "
          f"recurrence: worst step relative L2 logit err {rel:.3e} (tol "
          f"{LOGIT_TOL}), max|logit err| {mx:.3e}, max|state err| {h_err:.3e}")
    assert rel <= LOGIT_TOL, f"selective_scan logits off by {rel:.3e}"
    print(f"phase (c) passed; compile {clock.seconds - c0:.1f} s, "
          f"wall {time.perf_counter() - t0:.1f} s (smoke reading)")


def phase_four_chips(serve, clock, argv=SERVE_ARGS):
    """Phase (b)'s requests with TP decode over 4 chips, compared step by
    step with the one-chip kernel path fed the same pools and tokens."""
    from repro.core.backends import RealCompute
    from repro.core.stepplan import DecodeBatchCtx

    t0, c0 = time.perf_counter(), clock.seconds
    run = serve.serve_real(serve.parse_args(argv + ["--tp-decode", "0"]))
    assert run.engine.backend.tp_mesh is not None
    check_served(run)
    eng = run.engine
    one_chip = RealCompute(run.cfg, eng.backend.params)
    errs, agree = [], 0
    for pair in ((0, 1), (2, 3)):
        plans, ops = [], []
        for rid in pair:
            suffix, _ = run.task.queries[rid]
            plan = eng.plan(suffix, request_id=100 + rid,
                            decode_tokens=DECODE_TOKENS)
            plans.append(plan)
            ops.append(advance(plan, None))
        mirrors = [clone_pools(op.batch_ctx.pools) for op in ops]
        while all(op is not None for op in ops):
            ctxs = [op.batch_ctx for op in ops]
            twins = [DecodeBatchCtx(backend=one_chip, token=c.token,
                                    pos=c.pos, pools=m)
                     for c, m in zip(ctxs, mirrors)]
            ref = one_chip.decode_step_batch(twins)
            outs = eng.backend.decode_step_batch(ctxs)
            for (lt, _), (lr, _) in zip(outs, ref):
                assert np.all(np.isfinite(lt))
                errs.append(logit_err(lt, lr))
                agree += int(np.argmax(lt[0, -1]) == np.argmax(lr[0, -1]))
            ops = [advance(p, out) for p, out in zip(plans, outs)]
        assert all(op is None for op in ops), "plans fell out of lockstep"
    steps = len(errs)
    assert steps == 4 * DECODE_TOKENS, f"{steps} decode steps compared"
    eng.session.store.close()
    rel, mx = np.max(errs, axis=0)
    print(f"TP decode over mesh {dict(eng.backend.tp_mesh.shape)} vs one-chip "
          f"kernel path, {steps} decode steps (b=2): worst step relative L2 "
          f"logit err {rel:.3e} (median {np.median(errs, axis=0)[0]:.3e}, "
          f"tol {LOGIT_TOL}), max|logit err| {mx:.3e}, greedy token "
          f"agreement {agree}/{steps}")
    assert rel <= LOGIT_TOL, f"TP logits off by {rel:.3e}"
    print(f"four-chip phase passed; compile {clock.seconds - c0:.1f} s, wall "
          f"{time.perf_counter() - t0:.1f} s (smoke reading)")


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--four-chips", action="store_true",
                   help="run only the tensor-parallel decode comparison "
                        "over 4 chips")
    args = p.parse_args(argv)
    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: the repository's sources are not beside this "
              f"script (no {SRC / 'repro'})", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))
    import jax

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    print(f"(a) device: platform {device['platform']}, kind "
          f"{device['kind']}, count {device['count']}")
    if device["platform"] != "tpu":
        print("chip_smoke: JAX found no TPU", file=sys.stderr)
        return 1
    want = 4 if args.four_chips else 1
    if device["count"] < want:
        print(f"chip_smoke: needs {want} chips, found {device['count']}",
              file=sys.stderr)
        return 1

    from repro.launch import serve

    serve.use_compile_cache()
    clock = CompileClock(jax.monitoring)
    if args.four_chips:
        phase_four_chips(serve, clock)
    else:
        from repro.configs import get_config

        phase_serve(serve, clock)
        phase_ssm(clock, dataclasses.replace(get_config("falcon-mamba-7b"),
                                             n_layers=2))
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
