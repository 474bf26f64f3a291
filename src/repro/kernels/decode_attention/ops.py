"""Jit'd public wrapper for paged decode attention."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import _default_interpret
from repro.kernels.decode_attention.kernel import decode_attention as _kernel
from repro.kernels.decode_attention.ref import decode_attention_ref


@jax.jit
def stack_pool_buffers(ks, vs):
    """Zero-pad b per-request page buffers to a common page count and stack.

    ks/vs are sequences of device-resident ``(n_pages_i, page, n_kv, d)``
    pool buffers (:class:`repro.core.backends.DeviceTailPool`).  The whole
    ragged pad+stack traces into one jitted device program keyed on the
    tuple of pool shapes, so assembling a batched decode-attention call
    reads pages directly from device memory — no host staging buffer and no
    per-step H2D re-upload of pool bytes."""
    n_pages = max(k.shape[0] for k in ks)

    def pad(x):
        if x.shape[0] == n_pages:
            return x
        return jnp.pad(x, ((0, n_pages - x.shape[0]),) + ((0, 0),) * 3)

    return jnp.stack([pad(k) for k in ks]), jnp.stack([pad(v) for v in vs])


@partial(jax.jit, static_argnames=("use_kernel",))
def decode_attention_pools(q, ks, vs, page_table, lengths, *, use_kernel=True):
    """Batched paged decode attention over per-request pool buffers.

    Stacks the ragged device pools (:func:`stack_pool_buffers`) and runs the
    standard kernel path on the result — the same arithmetic as a
    pre-stacked :func:`decode_attention` call, so batched outputs stay
    bit-identical whether the caller stacked host-side or device-side.  The
    whole thing is one jitted program: the b=1 case (a single pool) traces
    to a plain reshape XLA can fuse into the kernel, so a per-step attend
    is a single dispatch with no eager pool-sized copy."""
    k_pool, v_pool = stack_pool_buffers(tuple(ks), tuple(vs))
    return decode_attention(q, k_pool, v_pool, page_table, lengths,
                            use_kernel=use_kernel)


@partial(jax.jit, static_argnames=("use_kernel",))
def decode_attention(q, k_pool, v_pool, page_table, lengths, *, use_kernel=True):
    """q: (b, n_q, d); pools: (b, n_pages, page, n_kv, d); table: (b, n_active).

    Returns (out, mass): the attention output and the per-page attention
    probability mass (b, n_q, n_active), so callers feeding the
    attention-guided cache need not recompute scores.

    Ragged batches: requests whose pool has fewer than ``n_active`` pages pad
    their table row with negative entries — pad slots are fully masked, carry
    exactly zero mass, and leave the real pages' output bit-identical to an
    unpadded call, so a fixed-capacity table keeps the call shape (and its
    jit cache entry) stable while a request's tail grows."""
    if not use_kernel:
        return decode_attention_ref(q, k_pool, v_pool, page_table, lengths)
    return _kernel(q, k_pool, v_pool, page_table, lengths,
                   interpret=_default_interpret())
