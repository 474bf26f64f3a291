"""Host→device transfer meter shared by tests and benchmarks.

The meter instruments jax's two explicit transfer doors —
``jax.device_put`` and ``jnp.asarray`` — and records the bytes of every
numpy-array input that flows through them.  The pool/backends code keeps
the convention that **every host numpy array bound for the device passes
through one of the two** (raw jit arguments there are already-device
arrays, python scalars, or statics; small index/length arrays are
explicitly wrapped in ``jnp.asarray`` at the call sites), which is what
makes the count complete.  A numpy array passed *directly* as a jit
argument transfers implicitly and would not be counted — don't do that in
pool paths, and note the host-pool positive controls
(``tests/test_device_pool.py::TestNoReupload::test_host_pool_trips_the_meter``
and the benchmark's host-pool H2D row) exist to catch the meter going
blind on the path that matters.  Both the no-reupload test and
``benchmarks/bench_throughput.py``'s pool-residency gate count through
this one class — if backends ever grows a third transfer door, this is
the single place to teach it.  ``RealCompute.h2d_bytes`` counts the
backend's own uploads in production (its one door, ``_to_device``, goes
through ``jnp.asarray``); this meter is the test oracle that the count is
complete.
"""
from __future__ import annotations

from typing import List

import numpy as np


class H2DMeter:
    """Context manager recording host-sourced transfer sizes in bytes.

    Patches ``jax.device_put`` and ``jax.numpy.asarray`` for the duration
    of the ``with`` block and appends the ``nbytes`` of every numpy-array
    leaf that flows through them to :attr:`transfers`.  Device-resident
    ``jax.Array`` inputs are not transfers and are ignored.
    """

    def __init__(self):
        self.transfers: List[int] = []
        self._saved = None

    def _record(self, x):
        import jax

        for leaf in jax.tree_util.tree_leaves(x):
            if isinstance(leaf, np.ndarray):
                self.transfers.append(leaf.nbytes)

    def __enter__(self):
        import jax
        import jax.numpy as jnp

        real_put, real_asarray = jax.device_put, jnp.asarray
        self._saved = (jax, jnp, real_put, real_asarray)

        def put(x, *a, **kw):
            self._record(x)
            return real_put(x, *a, **kw)

        def asarray(x, *a, **kw):
            self._record(x)
            return real_asarray(x, *a, **kw)

        jax.device_put = put
        jnp.asarray = asarray
        return self

    def __exit__(self, *exc):
        jax, jnp, real_put, real_asarray = self._saved
        jax.device_put = real_put
        jnp.asarray = real_asarray
        return False

    @property
    def total(self) -> int:
        return sum(self.transfers)

    @property
    def largest(self) -> int:
        return max(self.transfers, default=0)
