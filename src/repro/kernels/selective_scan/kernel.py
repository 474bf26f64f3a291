"""Pallas TPU fused selective scan (mamba-1, dt_rank=1).

The XLA-level chunked `associative_scan` materializes (b, chunk, d_in, n)
state-expansion tensors in HBM every level — the dominant memory term of the
falcon-mamba train cells (§Perf B). This kernel keeps the recurrent state
resident in VMEM across the whole sequence: HBM traffic drops to reading
x/dt/B/C tiles once and writing y once.

Grid = (b, d_in_blocks, s_blocks); the sequence axis is innermost and
sequential, so the VMEM scratch carries h across s-blocks; within a block a
fori_loop steps the recurrence on VMEM-resident tiles.

TPU layout: the state is kept transposed, ``(n, d_blk)``, so the channel
axis fills the 128 lanes (a small ``n`` on the lanes would pad every vreg
8x).  Step ``t``'s row of ``x`` is read from the ref with ``pl.ds`` and its
row of ``y`` written the same way; ``dt`` is a scalar read from SMEM; ``B``
and ``C`` arrive with the sequence on the lanes, and step ``t``'s column is
picked out with a one-hot lane mask and a lane reduction, so the loop body
never indexes a loaded value with the traced step.  The wrapper transposes
``A``, ``B``, ``C``, ``h0`` and the final state, so callers keep the
``(d_in, n)`` convention.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _scan_kernel(x_ref, dt_ref, B_ref, C_ref, A_ref, h0_ref, y_ref, hout_ref,
                 h_scr, *, block_s: int, n_s_blocks: int):
    si = pl.program_id(2)

    @pl.when(si == 0)
    def _init():
        h_scr[...] = h0_ref[0]

    Bt = B_ref[0]  # (n, block_s)
    Ct = C_ref[0]
    At = A_ref[...]  # (n, d_blk)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, block_s), 1)

    def column(tile, hot):  # (rows, block_s) -> (rows, 1): column t, exactly
        return jnp.sum(jnp.where(hot, tile, 0.0), axis=1, keepdims=True)

    def step(t, h):  # h: (n, d_blk)
        hot = lane == t
        dt_t = dt_ref[0, 0, t]  # scalar from SMEM
        x_t = x_ref[0, pl.ds(t, 1), :]  # (1, d_blk)
        h = jnp.exp(dt_t * At) * h + column(Bt, hot) * (dt_t * x_t)
        y_t = jnp.sum(column(Ct, hot) * h, axis=0, keepdims=True)
        y_ref[0, pl.ds(t, 1), :] = y_t
        return h

    h_scr[...] = jax.lax.fori_loop(0, block_s, step, h_scr[...])

    @pl.when(si == n_s_blocks - 1)
    def _done():
        hout_ref[0] = h_scr[...]


def selective_scan(
    x: jax.Array,  # (b, s, d_in)
    dt: jax.Array,  # (b, s)
    A: jax.Array,  # (d_in, n)
    B: jax.Array,  # (b, s, n)
    C: jax.Array,  # (b, s, n)
    h0: jax.Array | None = None,  # (b, d_in, n) initial recurrent state
    *,
    block_s: int = 128,
    block_d: int = 512,
    interpret: bool = False,
):
    """Returns (y (b, s, d_in) fp32, h_final (b, d_in, n) fp32).

    `h0` seeds the VMEM-resident state at the first sequence block (decode
    resumes the recurrence mid-stream); None starts from zeros."""
    b, s, d_in = x.shape
    n = A.shape[1]
    block_s = min(block_s, s)
    block_d = min(block_d, d_in)
    assert s % block_s == 0 and d_in % block_d == 0
    n_s = s // block_s
    n_d = d_in // block_d
    f32 = jnp.float32
    if h0 is None:
        h0 = jnp.zeros((b, d_in, n), f32)

    kernel = functools.partial(_scan_kernel, block_s=block_s, n_s_blocks=n_s)
    y, h_final = pl.pallas_call(
        kernel,
        grid=(b, n_d, n_s),
        in_specs=[
            pl.BlockSpec((1, block_s, block_d), lambda bi, di, si: (bi, si, di)),
            pl.BlockSpec((1, 1, block_s), lambda bi, di, si: (bi, 0, si),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, n, block_s), lambda bi, di, si: (bi, 0, si)),
            pl.BlockSpec((1, n, block_s), lambda bi, di, si: (bi, 0, si)),
            pl.BlockSpec((n, block_d), lambda bi, di, si: (0, di)),
            pl.BlockSpec((1, n, block_d), lambda bi, di, si: (bi, 0, di)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_s, block_d), lambda bi, di, si: (bi, si, di)),
            pl.BlockSpec((1, n, block_d), lambda bi, di, si: (bi, 0, di)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, s, d_in), f32),
            jax.ShapeDtypeStruct((b, n, d_in), f32),
        ],
        scratch_shapes=[pltpu.VMEM((n, block_d), f32)],
        interpret=interpret,
    )(x.astype(f32), dt.astype(f32)[:, None, :],
      B.astype(f32).transpose(0, 2, 1), C.astype(f32).transpose(0, 2, 1),
      A.astype(f32).T, h0.astype(f32).transpose(0, 2, 1))
    return y, h_final.transpose(0, 2, 1)
