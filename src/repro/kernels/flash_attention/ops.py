"""Jit'd public wrapper for the flash-attention kernel."""
from __future__ import annotations

from functools import partial

import jax

from repro.kernels import _default_interpret
from repro.kernels.flash_attention.kernel import flash_attention as _kernel
from repro.kernels.flash_attention.ref import flash_attention_ref


@partial(jax.jit, static_argnames=("causal", "window", "q_offset", "block_q",
                                   "block_k", "use_kernel"))
def flash_attention(q, k, v, *, causal=True, window=0, q_offset=0,
                    block_q=128, block_k=128, use_kernel=True):
    """q: (b, n_q, s_q, d); k/v: (b, n_kv, s_k, d). GQA-aware causal flash."""
    if not use_kernel:
        return flash_attention_ref(q, k, v, causal=causal, window=window,
                                   q_offset=q_offset)
    return _kernel(q, k, v, causal=causal, window=window, q_offset=q_offset,
                   block_q=block_q, block_k=block_k,
                   interpret=_default_interpret())
