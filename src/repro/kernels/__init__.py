"""Pallas kernels for the paper's compute hot spots (kernel + ops + ref each)."""
from __future__ import annotations

import jax


def _default_interpret() -> bool:
    """True where the kernels must run in the Pallas interpreter.

    The TPU compiles them; the CPU (tests, tiny-model runs) interprets them.
    Any other backend is refused rather than interpreted in silence, so a
    run that meant to use the chip can never fall back to the slow path."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"the Pallas kernels compile for the TPU or are interpreted on the "
        f"CPU; the {backend!r} backend is neither")
