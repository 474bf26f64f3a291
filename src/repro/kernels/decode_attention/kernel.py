"""Pallas TPU paged decode attention (flash-decode over a chunk pool).

One new token's query attends over a logically-contiguous KV stream stored as
scattered physical pages (= ContiguousChunks); the page table is a
scalar-prefetch operand so the BlockSpec gathers pages by indirection.
Online softmax across pages in fp32 VMEM scratch.

Grid = (b, n_active): one grid step reads one whole physical page, every KV
head at once, and updates every query head.  The pool is viewed as
``(b, n_pages, page * n_kv, d)`` (a free row-major reshape), so a page is one
``(page * n_kv, d)`` tile whose last two dims are the array's own — the block
shape the TPU's tiling accepts for any ``n_kv``.  Row ``r`` of the tile is
token ``r // n_kv`` of KV head ``r % n_kv``; every query head scores the whole
tile and the columns of the other KV heads are masked, so each page crosses
HBM once per request instead of once per query head.

Besides the attention output, the kernel returns the per-page attention
probability mass (the attention-guided cache's A_j signal): a running
raw-mass scratch is rescaled by the same alpha as the softmax accumulator,
the current page's mass lands in its column through a one-hot lane mask,
and the whole row is normalized by the final denominator at the last grid
step, so the engine never recomputes scores a second time to extract it.

Ragged batches: requests with fewer active pages than the table width mark
the pad slots with a negative table entry.  A pad page contributes exactly
nothing — its scores are forced to NEG_INF before the online-softmax update
(the gather index is clamped to 0, the loaded data is masked), so the
accumulator, the denominator and the per-page masses of real pages are
bit-identical to a call without the pad slots, and the pad slots' own mass
is exactly zero.  `lengths` additionally masks the trailing partial page of
the valid token stream, as before.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(tbl_ref, len_ref, q_ref, k_ref, v_ref, o_ref, mass_ref,
                   m_scr, l_scr, acc_scr, mass_scr, *, scale: float, page: int,
                   n_kv: int, group: int, n_active: int):
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)
        mass_scr[...] = jnp.zeros_like(mass_scr)

    q = q_ref[0].astype(jnp.float32)  # (n_q, d)
    k = k_ref[0, 0].astype(jnp.float32)  # (page * n_kv, d)
    v = v_ref[0, 0].astype(jnp.float32)
    s_mat = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
    shape = s_mat.shape  # (n_q, page * n_kv)
    col = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    row = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    pos = j * page + col // n_kv
    valid = ((col % n_kv == row // group) & (pos < len_ref[b])
             & (tbl_ref[b, j] >= 0))
    s_mat = jnp.where(valid, s_mat, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s_mat, axis=-1, keepdims=True))
    p = jnp.exp(s_mat - m_new)
    alpha = jnp.exp(m_prev - m_new)
    p_sum = jnp.sum(p, axis=-1, keepdims=True)  # (n_q, 1)
    l_scr[...] = alpha * l_scr[...] + p_sum
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    # per-page raw mass, kept in the running max's units (same rescale);
    # page j's mass enters its own column through a one-hot lane mask
    here = jax.lax.broadcasted_iota(jnp.int32, mass_scr.shape, 1) == j
    mass_scr[...] = jnp.where(here, p_sum, mass_scr[...] * alpha)
    m_scr[...] = m_new

    @pl.when(j == n_active - 1)
    def _done():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / denom).astype(o_ref.dtype)
        mass_ref[0] = mass_scr[...] / denom


def decode_attention(
    q: jax.Array,  # (b, n_q, d)
    k_pool: jax.Array,  # (b, n_pages, page, n_kv, d)
    v_pool: jax.Array,
    page_table: jax.Array,  # (b, n_active) int32; < 0 marks a pad slot
    lengths: jax.Array,  # (b,) int32
    *,
    interpret: bool = False,
):
    """Returns (out (b, n_q, d), mass (b, n_q, n_active) fp32).

    ``mass[b, h, j]`` is the fraction of head ``h``'s attention probability
    landing on active page ``j``; rows sum to 1 over the valid pages while
    pad slots (``page_table < 0``) carry exactly zero mass.
    """
    b, n_q, d = q.shape
    _, n_pages, page, n_kv, _ = k_pool.shape
    n_active = page_table.shape[1]
    rows = page * n_kv
    k_tiles = k_pool.reshape(b, n_pages, rows, d)
    v_tiles = v_pool.reshape(b, n_pages, rows, d)

    kernel = functools.partial(
        _decode_kernel, scale=d ** -0.5, page=page, n_kv=n_kv,
        group=n_q // n_kv, n_active=n_active)

    # pad slots (table entry < 0) clamp their gather to page 0; the kernel
    # masks the loaded data, so the page read is arbitrary
    def page_map(bi, j, tbl, ln):
        return bi, jnp.maximum(tbl[bi, j], 0), 0, 0

    def per_request(bi, j, tbl, ln):
        return bi, 0, 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, n_active),
        in_specs=[
            pl.BlockSpec((1, n_q, d), per_request),
            pl.BlockSpec((1, 1, rows, d), page_map),
            pl.BlockSpec((1, 1, rows, d), page_map),
        ],
        out_specs=[
            pl.BlockSpec((1, n_q, d), per_request),
            pl.BlockSpec((1, n_q, n_active), per_request),
        ],
        scratch_shapes=[
            pltpu.VMEM((n_q, 1), jnp.float32),
            pltpu.VMEM((n_q, 1), jnp.float32),
            pltpu.VMEM((n_q, d), jnp.float32),
            pltpu.VMEM((n_q, n_active), jnp.float32),
        ],
    )
    out, mass = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, n_q, d), q.dtype),
            jax.ShapeDtypeStruct((b, n_q, n_active), jnp.float32),
        ],
        interpret=interpret,
        # the HLO instruction's name: a trace finds the kernel by it
        name="decode_attention",
    )(page_table, lengths, q, k_tiles, v_tiles)
    return out, mass
