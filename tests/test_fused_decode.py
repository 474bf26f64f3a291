"""The one-program decode step against the per-layer path (tiny model, CPU).

``RealCompute.decode_step_batch`` over device pools on one device runs every
layer of a decode token as one jitted program (``backends._decode_token``).
It must give what the per-layer loop gives: ``part_a_at``, the pool's
in-place ``append`` and ``decode_attend`` per layer, then ``logits`` — the
path the standalone b=1 step took before.  Compared over consecutive steps:
the logits, every layer's per-page masses and the pool contents after the
writes, for one request and for a ragged batch of three whose pools differ
in resident pages and tail length.  The served path decodes every token
through the one program; host pools and a tensor-parallel backend keep the
per-layer loop.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import reduced_config
from repro.core import ContiguousKVEngine, build_real_session
from repro.core.backends import DeviceTailPool, RealCompute, TailPool
from repro.core.stepplan import DecodeBatchCtx
from repro.models import transformer as T
from repro.serving import Request, Scheduler
from repro.storage.timing import RealExecutor

PAGE = 16
EXTRA = 8
STEPS = 4
# (resident pages of layer 0, of layer 1, suffix tokens) per request: the
# tails start mid-page, one page short of a boundary and on one; layer 1 of
# the third request holds more resident pages than any other pool, so the
# two layers' tables have different widths
RAGGED = [(3, 3, 5), (2, 2, 15), (3, 5, 16)]


@pytest.fixture(scope="module")
def model():
    cfg = reduced_config("qwen2.5-7b", n_layers=2)
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _ctxs(be, cfg, shapes, pool_cls=DeviceTailPool, seed=5):
    """One DecodeBatchCtx per request, pools from seeded data in the model's
    compute dtype (bf16) over fp16 resident pages, as the engine builds
    them."""
    rng = np.random.default_rng(seed)
    dims = (cfg.n_kv_heads, cfg.d_head)
    ctxs = []
    for i, (*n_res, suffix) in enumerate(shapes):
        pools = {}
        for l in range(cfg.n_layers):
            k_res, v_res = (rng.normal(size=(n_res[l], PAGE, *dims))
                            .astype(np.float16) for _ in range(2))
            kv = tuple(rng.normal(size=(1, suffix, *dims))
                       .astype(jnp.bfloat16) for _ in range(2))
            pools[l] = pool_cls(k_res, v_res, kv, PAGE, EXTRA)
        ctxs.append(DecodeBatchCtx(backend=be, token=11 * i + 3,
                                   pos=200 + suffix, pools=pools))
    return ctxs


def _per_layer(be, ctx):
    """The per-layer decode step of one request."""
    h = be.embed(np.array([ctx.token]))
    masses = {}
    for l in range(be.cfg.n_layers):
        _, q, k_cur, v_cur = be.part_a_at(l, h, [[ctx.pos]])
        ctx.pools[l].append(k_cur, v_cur)
        h, masses[l] = be.decode_attend(l, h, q, ctx.pools[l])
    return be.logits(h), masses


@pytest.mark.parametrize("shapes", [RAGGED[:1], RAGGED],
                         ids=["b1", "ragged_b3"])
def test_fused_step_matches_the_per_layer_path(model, shapes):
    cfg, params = model
    be = RealCompute(cfg, params)
    fused, ref = _ctxs(be, cfg, shapes), _ctxs(be, cfg, shapes)
    for step in range(STEPS):
        syncs = be.d2h_syncs
        outs = be.decode_step_batch(fused)
        assert be.d2h_syncs - syncs == 1
        for i, (logits, masses) in enumerate(outs):
            want_logits, want_masses = _per_layer(be, ref[i])
            np.testing.assert_allclose(logits, want_logits, atol=1e-5,
                                       err_msg=f"step {step} req {i}")
            assert logits.shape == (1, 1, cfg.vocab_size)
            assert sorted(masses) == list(range(cfg.n_layers))
            for l, m in masses.items():
                assert m.shape == (ref[i].pools[l].n_res,)
                np.testing.assert_allclose(
                    m, want_masses[l], atol=1e-6,
                    err_msg=f"step {step} req {i} layer {l} mass")
            for l in range(cfg.n_layers):
                got, want = fused[i].pools[l], ref[i].pools[l]
                assert got.t == want.t
                np.testing.assert_allclose(
                    np.asarray(got.k, np.float32),
                    np.asarray(want.k, np.float32), atol=1e-5)
                np.testing.assert_allclose(
                    np.asarray(got.v, np.float32),
                    np.asarray(want.v, np.float32), atol=1e-5)
            fused[i].token = ref[i].token = int(np.argmax(logits[0, -1]))
            fused[i].pos = ref[i].pos = fused[i].pos + 1
    assert be.fused_decode_tokens == STEPS * len(shapes)


def _serve(cfg, params, backend, device_pool, n_req=3, decode_tokens=3):
    prefix = (np.arange(128) % cfg.vocab_size).astype(np.int64)
    sess = build_real_session(cfg, params, prefix, chunk_tokens=PAGE,
                              in_memory=True)
    eng = ContiguousKVEngine(sess, backend, RealExecutor(), budget=0.5,
                             period=2, subperiod=1, device_cap=64,
                             host_cap=128, device_tail_pool=device_pool)
    return Scheduler(eng, max_concurrency=n_req).run(
        [Request(request_id=r, suffix=(np.arange(24) + 3 * r)
                 % cfg.vocab_size, decode_tokens=decode_tokens)
         for r in range(n_req)])


def test_served_run_decodes_every_token_in_one_program(model, monkeypatch):
    cfg, params = model

    def refuse(*a, **kw):
        raise AssertionError("per-layer decode on the device-pool path")

    monkeypatch.setattr(RealCompute, "part_a_at", refuse)
    monkeypatch.setattr(RealCompute, "decode_attend", refuse)
    be = RealCompute(cfg, params)
    done = _serve(cfg, params, be, device_pool=True)
    decoded = sum(len(c.trace.decode_tokens_out) for c in done)
    assert decoded == 3 * 3
    assert be.fused_decode_tokens == decoded


def test_host_pools_keep_the_per_layer_loop(model):
    cfg, params = model
    be = RealCompute(cfg, params)
    done = _serve(cfg, params, be, device_pool=False)
    assert sum(len(c.trace.decode_tokens_out) for c in done) == 3 * 3
    assert be.fused_decode_tokens == 0
    # per layer: the token's K and V, and the masses; then the logits
    ctxs = _ctxs(be, cfg, RAGGED, pool_cls=TailPool)
    syncs = be.d2h_syncs
    be.decode_step_batch(ctxs)
    assert be.d2h_syncs - syncs == 3 * cfg.n_layers + 1
    assert be.fused_decode_tokens == 0


def test_tensor_parallel_backend_keeps_the_per_layer_loop(model):
    from repro.launch.mesh import make_serving_mesh

    cfg, params = model
    tp = RealCompute(cfg, params, tp_mesh=make_serving_mesh())
    one = RealCompute(cfg, params)
    got = tp.decode_step_batch(_ctxs(tp, cfg, RAGGED))
    want = one.decode_step_batch(_ctxs(one, cfg, RAGGED))
    assert tp.fused_decode_tokens == 0
    assert one.fused_decode_tokens == len(RAGGED)
    for (lg, mg), (lw, mw) in zip(got, want):
        np.testing.assert_allclose(lg, lw, atol=1e-5)
        for l in mw:
            np.testing.assert_allclose(mg[l], mw[l], atol=1e-5)
