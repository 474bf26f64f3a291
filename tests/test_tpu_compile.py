"""Ahead-of-time compiles of the main-path kernels for a described TPU v5e.

The TPU compiler is installed beside JAX, so these compile for a chip that
is described, not attached: they catch what the chip's compiler refuses
(block shapes off the tiling, unsupported ops) at zero chip time.  The
topology is described inside a fixture, never at import, so every test
worker collects the same tests and only the one running this file loads the
TPU library.  All cases live in this one file for the same reason.
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.decode_attention.kernel import decode_attention
from repro.kernels.selective_scan.kernel import selective_scan


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back without one
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile()


@pytest.mark.parametrize("n_q,n_kv", [(28, 4), (16, 8)],
                         ids=["qwen2.5-7b", "qwen3-1.7b"])
def test_decode_attention_compiles(one_chip, n_q, n_kv):
    b, d, page, n_pages, n_active = 4, 128, 16, 80, 72
    bf16 = jnp.bfloat16
    pool = ((b, n_pages, page, n_kv, d), bf16)
    compiled = _compile(
        lambda q, k, v, t, n: decode_attention(q, k, v, t, n), one_chip,
        ((b, n_q, d), bf16), pool, pool, ((b, n_active), jnp.int32),
        ((b,), jnp.int32))
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("s,block_s,block_d", [(1, 1, 8192), (2048, 128, 512)],
                         ids=["decode", "prefill"])
def test_selective_scan_compiles(one_chip, s, block_s, block_d):
    # falcon-mamba-7b: d_inner 8192, ssm_state 16
    b, d_in, n = 1, 8192, 16
    bf16, f32 = jnp.bfloat16, jnp.float32
    compiled = _compile(
        lambda x, dt, A, B, C, h0: selective_scan(
            x, dt, A, B, C, h0, block_s=block_s, block_d=block_d),
        one_chip, ((b, s, d_in), bf16), ((b, s), f32), ((d_in, n), f32),
        ((b, s, n), bf16), ((b, s, n), bf16), ((b, d_in, n), f32))
    assert "tpu_custom_call" in compiled.as_text()


def test_decode_attention_names_its_kernel(one_chip, monkeypatch):
    """On the serving path (``decode_attention_pools``) the paged
    decode-attention kernel is the HLO instruction ``decode_attention``:
    the name the chip benchmark's trace reduction finds it by."""
    from repro.kernels.decode_attention import ops

    monkeypatch.setattr(ops, "_default_interpret", lambda: False)
    n_q, n_kv, d, page, n_pages = 28, 4, 128, 16, 80
    bf16 = jnp.bfloat16

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = (spec((n_pages, page, n_kv, d), bf16),)
    jax.clear_caches()  # trace afresh with the kernel compiled, not interpreted
    try:
        hlo = ops.decode_attention_pools.lower(
            spec((1, n_q, d), bf16), pool, pool,
            spec((1, n_pages), jnp.int32), spec((1,), jnp.int32),
        ).compile().as_text()
    finally:
        jax.clear_caches()
    assert re.search(r"^\s*%decode_attention(\.\d+)? = .* custom-call\(",
                     hlo, re.M)


# one layer's weight matrices at qwen2.5-7b widths: w_gate/w_up, w_down,
# wq, wo
WEIGHTS = ("3584,18944", "18944,3584", "3584,28,128", "28,128,3584")


def _weight_copies(hlo: str):
    """Instructions of the optimised HLO's entry computation that copy or
    dynamic-slice one layer's weight matrix (leading layer axis of 1 or
    none) into a buffer of its own."""
    entry = hlo[hlo.index("\nENTRY "):]
    entry = entry[: entry.index("\n}")]
    pat = re.compile(
        r"^\s*(?:ROOT )?%(\S+) = \(?bf16\[(?:1,)?(?:" + "|".join(WEIGHTS)
        + r")\]\S* (?:copy|copy-start|copy-done|dynamic-slice|fusion)\(",
        re.M)
    return [m.group(1) for m in pat.finditer(entry)]


def test_weight_copy_check_finds_an_eager_layer_slice(one_chip):
    """The check below, on what slicing a layer out of the stack as its own
    program (the per-layer decode path's ``_slice_layer``) compiles to."""
    stack = ((2, 3584, 18944), jnp.bfloat16)
    hlo = _compile(lambda w, l: w[l], one_chip, stack,
                   ((), jnp.int32)).as_text()
    assert _weight_copies(hlo)


@pytest.mark.parametrize("b", [1, 8])
def test_fused_decode_step_reads_weights_in_place(one_chip, monkeypatch, b):
    """The one-program decode step (``backends._decode_token``) at
    qwen2.5-7b published widths, 2 layers, over the decode cell's pools
    (128 resident pages and 12 tail pages of 16 tokens): it compiles for the
    chip, runs the ``decode_attention`` kernel once per layer under its own
    name, and indexes the stacked layer weights without copying a layer's
    matrix out."""
    import dataclasses

    from repro.configs import get_config
    from repro.core import backends
    from repro.kernels.decode_attention import ops
    from repro.models import transformer as T

    monkeypatch.setattr(ops, "_default_interpret", lambda: False)
    cfg = dataclasses.replace(get_config("qwen2.5-7b"), n_layers=2)
    assert (cfg.d_model, cfg.d_ff, cfg.n_heads, cfg.n_kv_heads,
            cfg.d_head) == (3584, 18944, 28, 4, 128)

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(
        lambda x: spec(x.shape, x.dtype),
        jax.eval_shape(lambda k: T.init_params(k, cfg),
                       jax.random.PRNGKey(0)))
    n_pages = 140
    pool = spec((n_pages, 16, cfg.n_kv_heads, cfg.d_head), jnp.bfloat16)
    pools = tuple((pool,) * b for _ in range(cfg.n_layers))
    ctrl = spec((b, 2 + cfg.n_layers * (3 + n_pages)), jnp.int32)
    jax.clear_caches()  # trace afresh with the kernel compiled, not interpreted
    try:
        hlo = backends._decode_token.lower(
            params, ctrl, pools, pools, cfg).compile().as_text()
    finally:
        jax.clear_caches()
    kernels = re.findall(r"^\s*%decode_attention(?:\.\d+)? = .* custom-call\(",
                         hlo, re.M)
    assert len(kernels) == cfg.n_layers
    assert _weight_copies(hlo) == []
