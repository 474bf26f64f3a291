"""Serving scheduler: step-plan interleaving, overlap, tenancy (sim, deterministic)."""
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import ContiguousKVEngine, SyntheticWorkload, build_sim_session
from repro.core.backends import SimCompute
from repro.core.stepplan import ComputeOp, WaitOp
from repro.serving import (
    CacheAffinityPolicy,
    Request,
    Scheduler,
    burst_arrivals,
    poisson_arrivals,
    summarize,
)
from repro.serving.tenancy import build_sim_fleet
from repro.storage.timing import ChannelSim, DeviceModel, SimExecutor

MODEL = "qwen2.5-7b"
PREFIX = 4096
N_SUFFIX = 64


def _suffix(rid):
    return np.zeros(N_SUFFIX, np.int64) + rid % 7


def _serial_engine():
    cfg = get_config(MODEL)
    wl = SyntheticWorkload(PREFIX, cfg.n_layers, seed=1)
    sess = build_sim_session(cfg, PREFIX)
    return ContiguousKVEngine(sess, SimCompute(cfg, wl),
                              SimExecutor(DeviceModel()),
                              budget=0.25, device_cap=500, host_cap=2000)


def _concurrent_engine():
    cfg = get_config(MODEL)
    wl = SyntheticWorkload(PREFIX, cfg.n_layers, seed=1)
    sess = build_sim_session(cfg, PREFIX)
    return ContiguousKVEngine(sess, SimCompute(cfg, wl),
                              ChannelSim(DeviceModel()),
                              budget=0.25, device_cap=500, host_cap=2000)


@pytest.fixture(scope="module")
def serial_traces():
    eng = _serial_engine()
    traces = []
    for rid in range(2):
        _, tr = eng.reprefill(_suffix(rid), request_id=rid)
        traces.append(tr)
    return traces


@pytest.fixture(scope="module")
def concurrent_run():
    eng = _concurrent_engine()
    sched = Scheduler(eng, max_concurrency=2)
    reqs = [Request(request_id=rid, suffix=_suffix(rid), arrival=0.0)
            for rid in range(2)]
    return sched.run(reqs)


class TestConcurrentVsSerial:
    def test_selected_chunk_sets_identical_to_serial(self, serial_traces,
                                                     concurrent_run):
        """(a) interleaving must not change what each request selects."""
        for rid, c in enumerate(concurrent_run):
            serial = serial_traces[rid].selected_per_period
            conc = c.trace.selected_per_period
            assert len(serial) == len(conc)
            for s_sel, c_sel in zip(serial, conc):
                np.testing.assert_array_equal(s_sel, c_sel)

    def test_second_request_gets_strictly_more_cache_hits(self, concurrent_run):
        """(b) shared prefix: request 1 rides request 0's insertions."""
        t0, t1 = (c.trace for c in concurrent_run)
        assert t1.hits_device + t1.hits_host > t0.hits_device + t0.hits_host
        assert t1.hits_device + t1.hits_host > 0

    def test_makespan_beats_serial_ttft_sum(self, serial_traces, concurrent_run):
        """(c) overlap actually happens across requests."""
        serial_sum = sum(t.ttft for t in serial_traces)
        makespan = summarize(concurrent_run)["makespan"]
        assert makespan < serial_sum

    def test_concurrency_one_matches_serial_exactly(self, serial_traces):
        """Scheduler at max_concurrency=1 == the legacy serial wrapper."""
        eng = _concurrent_engine()
        sched = Scheduler(eng, max_concurrency=1)
        reqs = [Request(request_id=rid, suffix=_suffix(rid), arrival=0.0)
                for rid in range(2)]
        done = sched.run(reqs)
        for rid, c in enumerate(done):
            assert c.trace.ttft == pytest.approx(serial_traces[rid].ttft, rel=1e-12)


class TestSchedulerMechanics:
    def test_plan_yields_ops(self):
        eng = _concurrent_engine()
        plan = eng.plan(_suffix(0), request_id=0)
        op = plan.gen.send(None)
        assert isinstance(op, (ComputeOp, WaitOp))

    def test_queueing_delay_under_saturation(self):
        """More offered load than slots: someone must queue."""
        eng = _concurrent_engine()
        sched = Scheduler(eng, max_concurrency=1)
        reqs = [Request(request_id=rid, suffix=_suffix(rid), arrival=0.0)
                for rid in range(3)]
        done = sched.run(reqs)
        delays = [c.queue_delay for c in done]
        assert max(delays) > 0
        # all requests complete exactly once, in stable order
        assert [c.request.request_id for c in done] == [0, 1, 2]

    def test_arrivals_respected(self):
        eng = _concurrent_engine()
        sched = Scheduler(eng, max_concurrency=2)
        late = 10.0
        done = sched.run([
            Request(request_id=0, suffix=_suffix(0), arrival=0.0),
            Request(request_id=1, suffix=_suffix(1), arrival=late),
        ])
        assert done[1].admitted >= late
        assert done[1].finish > done[0].finish


class TestArrivals:
    def test_poisson_deterministic_and_sorted(self):
        a = poisson_arrivals(10.0, 32, seed=3)
        b = poisson_arrivals(10.0, 32, seed=3)
        np.testing.assert_array_equal(a, b)
        assert np.all(np.diff(a) >= 0)
        assert len(a) == 32

    def test_burst_shape(self):
        a = burst_arrivals(8, burst_size=4, burst_gap=1.0)
        assert len(a) == 8
        # two bursts separated by the gap
        assert a[4] - a[3] >= 1.0
        assert a[3] - a[0] == pytest.approx(0.0)


class TestTenancy:
    def test_shared_cache_keys_are_tenant_namespaced(self):
        fleet = build_sim_fleet("contiguous_kv", MODEL, n_tenants=2,
                                prefix_len=1024, device_cap=64, host_cap=256)
        sched = Scheduler(fleet.engines, max_concurrency=2)
        reqs = [Request(request_id=i, suffix=_suffix(i), arrival=0.0,
                        tenant=1 + i % 2) for i in range(2)]
        sched.run(reqs)
        cache = fleet.cache
        keys = cache.tiers["device"] | cache.tiers["host"]
        assert keys, "cache should be populated"
        assert all(len(k) == 3 for k in keys)
        usage = cache.tenant_usage()
        assert set(usage) <= {1, 2}
        assert sum(u["device"] for u in usage.values()) == len(cache.tiers["device"])

    def test_cache_aware_policy_prefers_warm_tenant(self):
        fleet = build_sim_fleet("contiguous_kv", MODEL, n_tenants=2,
                                prefix_len=1024, device_cap=64, host_cap=256)
        # warm tenant 2 only
        sched = Scheduler(fleet.engines, max_concurrency=1)
        sched.run([Request(request_id=0, suffix=_suffix(0), arrival=0.0, tenant=2)])
        policy = CacheAffinityPolicy()
        queued = [
            Request(request_id=1, suffix=_suffix(1), arrival=0.0, tenant=1),
            Request(request_id=2, suffix=_suffix(2), arrival=0.0, tenant=2),
        ]
        picked = policy.select(queued, fleet.engines)
        assert picked.request_id == 2


@pytest.mark.parametrize("env_dir", [None, "elsewhere"])
def test_compile_cache_placement(monkeypatch, tmp_path, env_dir):
    """Unset JAX_COMPILATION_CACHE_DIR: the cache goes to <checkout>/.jax_cache;
    set: the code leaves JAX's own setting alone."""
    import pathlib

    import jax

    from repro.launch.serve import use_compile_cache

    before = jax.config.jax_compilation_cache_dir
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / env_dir))
    try:
        use_compile_cache()
        got = jax.config.jax_compilation_cache_dir
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    root = pathlib.Path(__file__).resolve().parents[1]
    assert got == (str(root / ".jax_cache") if env_dir is None else before)


def test_real_config_keeps_published_widths():
    from repro.launch.serve import parse_args, real_config

    cfg = real_config("qwen2.5-7b", parse_args(["--n-layers", "8"]))
    assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
            cfg.d_ff, cfg.vocab_size) == (8, 3584, 28, 4, 18944, 152064)
    tiny = real_config("qwen2.5-7b", parse_args(["--reduced", "--n-layers", "2"]))
    assert (tiny.n_layers, tiny.d_model, tiny.vocab_size) == (2, 64, 256)
