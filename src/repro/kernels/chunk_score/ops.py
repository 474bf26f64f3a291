"""Jit'd public wrapper for the chunk-importance kernel."""
from __future__ import annotations

from functools import partial

import jax

from repro.kernels import _default_interpret
from repro.kernels.chunk_score.kernel import chunk_score as _kernel
from repro.kernels.chunk_score.ref import chunk_score_ref


@partial(jax.jit, static_argnames=("chunk_tokens", "block_k", "use_kernel"))
def chunk_score(q, k, *, chunk_tokens=16, block_k=256, use_kernel=True):
    """q: (n_q, s, d) probe queries; k: (n_kv, n_tokens, d) prefix keys.
    Returns (m,) ContiguousChunk scores (Eq. 1)."""
    if not use_kernel:
        return chunk_score_ref(q, k, chunk_tokens)
    return _kernel(q, k, chunk_tokens, block_k=block_k,
                   interpret=_default_interpret())
