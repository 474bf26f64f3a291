"""Production mesh builders. Functions (not module constants) so importing
never touches jax device state."""
from __future__ import annotations

import jax


def _axis_type_kwargs(n_axes: int) -> dict:
    """`axis_types=` kwargs for `jax.make_mesh`: every axis Auto."""
    return {"axis_types": (jax.sharding.AxisType.Auto,) * n_axes}


def make_production_mesh(*, multi_pod: bool = False, kv_split: int = 0):
    """16x16 chips per pod (v5e); multi_pod adds a 2-pod leading axis.

    ``kv_split=k`` builds the GQA-aware variant (§Perf): the 16-way tensor
    axis is factored into (kv=k, rep=16/k) so kv-head dims shard *exactly*
    on `kv` while q-heads/d_ff shard on ("kv","rep") — eliminating the
    padding + per-layer activation all-reduces the flat `model` axis needs
    when n_kv_heads doesn't divide 16.

    Works whether the host exposes exactly the needed device count or more
    (the 512-device dry-run environment serves both meshes)."""
    if kv_split:
        assert 16 % kv_split == 0, kv_split
        tp = (kv_split, 16 // kv_split)
        shape = (2, 16) + tp if multi_pod else (16,) + tp
        axes = (("pod", "data", "kv", "rep") if multi_pod
                else ("data", "kv", "rep"))
    else:
        shape = (2, 16, 16) if multi_pod else (16, 16)
        axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = 1
    for s in shape:
        n *= s
    devices = jax.devices()[:n]
    return jax.make_mesh(shape, axes, devices=devices,
                         **_axis_type_kwargs(len(axes)))


def tp_axes(mesh) -> tuple:
    """Axis names carrying tensor parallelism (full 16-way)."""
    return ("kv", "rep") if "kv" in mesh.axis_names else ("model",)


def kv_axes(mesh) -> tuple:
    """Axis names for KV-head sharding (subset of tp_axes on a GQA mesh)."""
    return ("kv",) if "kv" in mesh.axis_names else ("model",)


def make_serving_mesh(*, kv_split: int = 0):
    """Tensor-parallel mesh for the serving tier's decode backend.

    A full pod (>= 256 devices) gets the production mesh; anything smaller
    (dev boxes, the forced-host-device CI lane) turns every local device
    into tensor parallelism — ``kv_split=k`` factors them into (kv=k,
    rep=n/k) like the GQA production mesh, else one flat "model" axis.
    ``tp_axes`` resolves correctly on every variant, so
    :func:`repro.launch.sharded_sparse.make_sharded_paged_decode` is
    mesh-shape agnostic."""
    n = len(jax.devices())
    if n >= 256:
        return make_production_mesh(kv_split=kv_split)
    if kv_split:
        if kv_split < 1 or n % kv_split:
            raise ValueError(
                f"kv_split={kv_split} must be positive and divide the "
                f"local device count {n}")
        return jax.make_mesh((kv_split, n // kv_split), ("kv", "rep"),
                             **_axis_type_kwargs(2))
    return jax.make_mesh((n,), ("model",), **_axis_type_kwargs(1))


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over whatever devices exist (tests / smoke runs)."""
    n = len(jax.devices())
    assert data * model <= n, (data, model, n)
    return jax.make_mesh((data, model), ("data", "model"),
                         **_axis_type_kwargs(2))


def dp_axes(mesh) -> tuple:
    """Axis names that carry pure data parallelism."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)
