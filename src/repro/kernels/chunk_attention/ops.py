"""Jit'd Re-Prefill attention: gathered-chunk kernel + suffix merge.

The kernel covers the selected prefix chunks; the (small) suffix causal
self-attention partial is computed in jnp and merged with the standard
two-partial online-softmax combine — the same split-softmax structure a
flash-decode kernel uses.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import _default_interpret
from repro.kernels.chunk_attention.kernel import chunk_attention as _kernel
from repro.kernels.chunk_attention.ref import chunk_attention_ref, merge_partials

NEG_INF = -1e30


def _suffix_partial(q, k_suf, v_suf):
    """Causal self-attention partial. q: (n_q, s, d); k/v: (s, n_kv, d)."""
    n_q, s, d = q.shape
    n_kv = k_suf.shape[1]
    group = n_q // n_kv
    scale = d ** -0.5
    qg = q.reshape(n_kv, group, s, d).astype(jnp.float32)
    kT = k_suf.transpose(1, 0, 2).astype(jnp.float32)  # (n_kv, s, d)
    vT = v_suf.transpose(1, 0, 2)
    logits = jnp.einsum("ngsd,ntd->ngst", qg, kT) * scale
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    logits = jnp.where(causal[None, None], logits, NEG_INF)
    m = logits.max(axis=-1, keepdims=True)
    p = jnp.exp(logits - m)
    l = p.sum(axis=-1, keepdims=True)
    out = jnp.einsum("ngst,ntd->ngsd", (p / jnp.maximum(l, 1e-30)).astype(vT.dtype), vT)
    return (out.reshape(n_q, s, d), m.reshape(n_q, s, 1), l.reshape(n_q, s, 1))


@partial(jax.jit, static_argnames=("use_kernel",))
def reprefill_attention_paged(q, k_pool, v_pool, chunk_idx, n_valid,
                              k_suf, v_suf, *, use_kernel=True):
    """Full Re-Prefill attention via the chunk pool.

    q: (n_q, s, d); pools: (m, c, n_kv, d); chunk_idx: (n_sel,) int32 padded;
    n_valid: () int32; k_suf/v_suf: (s, n_kv, d).
    Returns (out (n_q, s, d), chunk_mass (n_sel,)).
    """
    if use_kernel:
        out_p, m_p, l_p, mass_raw = _kernel(
            q, k_pool, v_pool, chunk_idx, n_valid,
            interpret=_default_interpret())
        n_sel = chunk_idx.shape[0]
        denom = jnp.maximum(mass_raw.sum(axis=-1, keepdims=True), 1e-30)
        chunk_mass = (mass_raw / denom).sum(axis=0)
        chunk_mass = jnp.where(jnp.arange(n_sel) < n_valid, chunk_mass, 0.0)
    else:
        out_p, m_p, l_p, chunk_mass = chunk_attention_ref(
            q, k_pool, v_pool, chunk_idx, n_valid)
    out_s, m_s, l_s = _suffix_partial(q, k_suf, v_suf)
    out, _, _ = merge_partials(out_p, m_p, l_p, out_s, m_s, l_s)
    return out, chunk_mass
