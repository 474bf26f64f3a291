"""Jit'd public wrapper for the fused selective scan."""
from __future__ import annotations

from functools import partial

import jax

from repro.kernels import _default_interpret
from repro.kernels.selective_scan.kernel import selective_scan as _kernel
from repro.kernels.selective_scan.ref import selective_scan_ref


@partial(jax.jit, static_argnames=("block_s", "block_d", "use_kernel"))
def selective_scan(x, dt, A, B, C, h0=None, *, block_s=128, block_d=512,
                   use_kernel=True):
    """x: (b, s, d_in); dt: (b, s); A: (d_in, n); B/C: (b, s, n);
    h0: optional (b, d_in, n) initial recurrent state (decode resume)."""
    if not use_kernel:
        return selective_scan_ref(x, dt, A, B, C, h0)
    return _kernel(x, dt, A, B, C, h0, block_s=block_s, block_d=block_d,
                   interpret=_default_interpret())
