"""Ahead-of-time compiles of the main-path kernels for a described TPU v5e.

The TPU compiler is installed beside JAX, so these compile for a chip that
is described, not attached: they catch what the chip's compiler refuses
(block shapes off the tiling, unsupported ops) at zero chip time.  The
topology is described inside a fixture, never at import, so every test
worker collects the same tests and only the one running this file loads the
TPU library.  All cases live in this one file for the same reason.
"""
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
