"""Compute backends for the Re-Prefill engine.

RealCompute — actually runs the (tiny) model layer-by-layer with jitted fns.
SimCompute  — returns placeholders; selection comes from a workload model;
              durations are supplied by the engine's cost model through the
              SimExecutor. Both expose the same five methods so the engine
              orchestration is byte-identical across modes.
"""
from __future__ import annotations

import dataclasses
import functools
from functools import partial
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import sparse_attention as SA
from repro.kernels.decode_attention.ops import (
    decode_attention,
    decode_attention_pools,
    stack_pool_buffers,
)
from repro.models.common import ModelConfig
from repro.models.layers import rms_norm, swiglu
from repro.models.attention import qkv_project
from repro.models.transformer import (
    _ffn,
    _logits,
    decode_step as _t_decode_step,
    init_serve_state,
    prefill as _t_prefill,
)


def _slice_layer(params, l: int):
    return jax.tree_util.tree_map(lambda x: x[l], params["layers"])


@partial(jax.jit, static_argnames=("cfg",))
def _embed(params, tokens, cfg: ModelConfig):
    return params["embed"][tokens]


@partial(jax.jit, static_argnames=("cfg", "pos0"))
def _part_a(lp, h, cfg: ModelConfig, pos0: int):
    """Pre-attention: norm + QKV for the suffix (positions offset by prefix)."""
    x = rms_norm(h, lp["attn_norm"], cfg.norm_eps)
    b, s, _ = x.shape
    positions = pos0 + jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    q, k, v = qkv_project(x, lp, cfg, positions)
    return x, q, k, v


@partial(jax.jit, static_argnames=("cfg",))
def _part_a_at(lp, h, cfg: ModelConfig, positions):
    """Batched pre-attention: per-request positions as a traced (b, s) array
    (decode steps of concurrent requests sit at different absolute offsets)."""
    x = rms_norm(h, lp["attn_norm"], cfg.norm_eps)
    q, k, v = qkv_project(x, lp, cfg, positions)
    return x, q, k, v


@partial(jax.jit, static_argnames=("cfg", "chunk_tokens"))
def _part_b(lp, h, q, k_suf, v_suf, k_sel, v_sel, sel_valid, cfg: ModelConfig,
            chunk_tokens: int):
    """Attention over [selected chunks ; suffix] + out-proj + FFN."""
    out, mass = SA.reprefill_attention(
        q[0], k_sel, v_sel, sel_valid, k_suf[0], v_suf[0], chunk_tokens=chunk_tokens
    )
    attn = out[None]
    o = jnp.einsum("bshk,hkd->bsd", attn, lp["wo"])
    h = h + o
    h = _ffn(h, lp, cfg, dropless=True)
    return h, mass


@partial(jax.jit, static_argnames=("cfg", "chunk_tokens"))
def _part_b_batch_kernel(lp, h, q, k_suf, v_suf, k_sel, v_sel, sel_valid,
                         cfg: ModelConfig, chunk_tokens: int):
    """vmapped :func:`_part_b` over a leading batch axis: b plans' same-layer
    final prefill chunks run as one accelerator pass (the layer weights `lp`
    stream once for the whole batch)."""

    def one(hh, qq, ks, vs, k1, v1, vd):
        return _part_b(lp, hh, qq, ks, vs, k1, v1, vd, cfg, chunk_tokens)

    return jax.vmap(one)(h, q, k_suf, v_suf, k_sel, v_sel, sel_valid)


@jax.jit
def _final_logits_kernel(params, h, norm_eps: float):
    h = rms_norm(h[:, -1:], params["final_norm"], norm_eps)
    w = params["unembed"]
    return jnp.einsum("...d,dv->...v", h, w).astype(jnp.float32)


class TailPool:
    """Preallocated paged KV pool for one (request, layer)'s decode phase.

    Layout: ``[n_res resident unit pages | tail capacity pages]`` in one
    fixed-size numpy buffer of shape ``(n_pages, page, n_kv, d)``.  The
    cache-resident unit pages and the prefill suffix KV are paged in exactly
    once at construction; each decode step writes its token's K/V into the
    next tail slot *in place* (a flat view of the contiguous buffer), so the
    per-step ``jnp.concatenate``/re-pad of the suffix+decoded tail that the
    pre-TailPool path performed is gone (ROADMAP PR-3 known issue).

    Because the buffer, the page table (``table()``: active pages first, pad
    slots marked ``-1``) and ``lengths`` all keep a *fixed* shape while the
    tail grows, every decode step of a request hits the same jit cache entry
    of :func:`repro.kernels.decode_attention.ops.decode_attention`, and a
    scheduler can stack several requests' pools into one ragged batch.

    This base class is host-resident (every attend re-uploads the pool over
    H2D); :class:`DeviceTailPool` keeps the same layout in device memory and
    is what the real serving driver uses by default.
    """

    __slots__ = ("page", "n_res", "cap_pages", "k", "v", "t")
    is_device = False

    def __init__(self, k_res: np.ndarray, v_res: np.ndarray, kv_suffix,
                 page: int, extra_tokens: int, dtype=None):
        """k_res/v_res: (n_res, page, n_kv, d) resident unit pages;
        kv_suffix: (k, v) each (1, s, n_kv, d) from prefill, or None;
        extra_tokens: decode-token capacity to preallocate past the suffix.
        With ``kv_suffix=None``, pass the model compute dtype explicitly —
        appended tail KV must not be silently cast to the storage dtype."""
        assert page >= 1 and extra_tokens >= 0
        self.page = page
        self.n_res = int(k_res.shape[0])
        k_suf = None if kv_suffix is None else np.asarray(kv_suffix[0][0])
        v_suf = None if kv_suffix is None else np.asarray(kv_suffix[1][0])
        s = 0 if k_suf is None else k_suf.shape[0]
        self.cap_pages = max(1, -(-(s + extra_tokens) // page))
        n_kv, d = k_res.shape[2], k_res.shape[3]
        # the pool dtype follows the tail KV (model compute dtype), exactly
        # like the old concatenate path cast the resident pages to it
        if dtype is None:
            dtype = k_res.dtype if k_suf is None else k_suf.dtype
        shape = (self.n_res + self.cap_pages, page, n_kv, d)
        self.k = np.zeros(shape, dtype)
        self.v = np.zeros(shape, dtype)
        self.k[: self.n_res] = k_res
        self.v[: self.n_res] = v_res
        self.t = 0  # valid tail tokens (suffix + decoded so far)
        if s:
            self._write(k_suf, v_suf)

    def _check_capacity(self, n: int):
        if self.t + n > self.cap_pages * self.page:
            raise ValueError(
                f"TailPool overflow: {self.t} + {n} tokens exceed capacity "
                f"{self.cap_pages * self.page}")

    def _write(self, k_new: np.ndarray, v_new: np.ndarray):
        """Append (t, n_kv, d) rows at the tail cursor — in-place flat view."""
        n = k_new.shape[0]
        self._check_capacity(n)
        flat_k = self.k[self.n_res:].reshape(-1, *self.k.shape[2:])
        flat_v = self.v[self.n_res:].reshape(-1, *self.v.shape[2:])
        flat_k[self.t: self.t + n] = k_new
        flat_v[self.t: self.t + n] = v_new
        self.t += n

    def append(self, k_tok, v_tok):
        """Write one decode position's KV ((1, 1, n_kv, d) device or numpy)
        into its page slot."""
        self._write(np.asarray(k_tok).reshape(1, *self.k.shape[2:]),
                    np.asarray(v_tok).reshape(1, *self.v.shape[2:]))

    @property
    def n_tail_pages(self) -> int:
        return -(-self.t // self.page)

    @property
    def n_active(self) -> int:
        """Pages carrying valid tokens: resident + filled tail pages."""
        return self.n_res + self.n_tail_pages

    @property
    def valid_tokens(self) -> int:
        return self.n_res * self.page + self.t

    def table(self, width: int = 0) -> np.ndarray:
        """Page table padded with -1 to `width` (default: full capacity)."""
        width = width or (self.n_res + self.cap_pages)
        assert width >= self.n_active
        tbl = np.full(width, -1, np.int32)
        tbl[: self.n_active] = np.arange(self.n_active, dtype=np.int32)
        return tbl

    def attend_args(self, put=None):
        """(k_pool, v_pool, table, lengths) for a b=1 decode_attention call;
        `put` uploads a host array (default ``jnp.asarray``).

        Host pool: the full fixed-size buffer is uploaded on every call —
        exactly the per-step H2D traffic the device pool eliminates."""
        put = put or jnp.asarray
        return (put(self.k[None]), put(self.v[None]), put(self.table()[None]),
                put(np.array([self.valid_tokens], np.int32)))

    def swap_out(self) -> int:
        """Snapshot the pool to host memory; returns bytes moved over PCIe.

        The host pool already lives in host memory, so a preemption swap-out
        moves nothing (0 bytes) — only :class:`DeviceTailPool` pays here."""
        return 0

    def swap_in(self) -> int:
        """Restore the pool after :meth:`swap_out`; returns bytes moved."""
        return 0


@partial(jax.jit, donate_argnums=(0, 1))
def _pool_write_device(k, v, k_tok, v_tok, p, s):
    """Write one token's KV into page `p`, offset `s`, in place.

    k/v are donated, so XLA aliases the output buffer with the input — the
    pool is updated in device memory without a copy (and without any pool
    H2D traffic: the token KV is already on device, the slot index rides as
    two traced scalars)."""
    k_tok = k_tok.reshape(1, 1, *k.shape[2:]).astype(k.dtype)
    v_tok = v_tok.reshape(1, 1, *v.shape[2:]).astype(v.dtype)
    idx = (p, s, 0, 0)
    return (jax.lax.dynamic_update_slice(k, k_tok, idx),
            jax.lax.dynamic_update_slice(v, v_tok, idx))


def _pool_write_batch(ks, vs, k_cur, v_cur, slots):
    """Append request i's `k_cur[i]`/`v_cur[i]` into pool buffer i at page
    `slots[i, 0]`, offset `slots[i, 1]`, and return the updated buffers
    together with their ragged zero-padded stack — the whole batch's pool
    maintenance *and* batch assembly, reading and writing device memory
    only."""
    new_ks, new_vs = [], []
    for i, (k, v) in enumerate(zip(ks, vs)):
        kt = k_cur[i].reshape(1, 1, *k.shape[2:]).astype(k.dtype)
        vt = v_cur[i].reshape(1, 1, *v.shape[2:]).astype(v.dtype)
        idx = (slots[i, 0], slots[i, 1], 0, 0)
        new_ks.append(jax.lax.dynamic_update_slice(k, kt, idx))
        new_vs.append(jax.lax.dynamic_update_slice(v, vt, idx))
    k_pool, v_pool = stack_pool_buffers(tuple(new_ks), tuple(new_vs))
    return tuple(new_ks), tuple(new_vs), k_pool, v_pool


# donated: the writes land in place, in one dispatch per layer
_pool_write_batch_device = jax.jit(_pool_write_batch, donate_argnums=(0, 1))


@partial(jax.jit, static_argnames=("cfg",), donate_argnames=("ks", "vs"))
def _decode_token(params, ctrl, ks, vs, cfg: ModelConfig):
    """One decode position of b requests through every layer, as one program.

    ``ks``/``vs`` hold, per layer, the b requests' :class:`DeviceTailPool`
    buffers; they are donated, so each layer's K/V write lands in place.
    ``ctrl`` packs the int32 control operands, one row per request: the
    token, its position, then per layer the write slot (page, offset), the
    valid length including this token and the page table padded with -1 to
    the layer's largest pool.  Each layer's weights are a static slice of
    the stacked ``params["layers"]``, read in place by the ops that use
    them.  Returns the float32 logits (b, 1, vocab), the updated buffers
    and the head-averaged per-page masses (L, b, width), zero past each
    layer's own table width.
    """
    b = ctrl.shape[0]
    h = params["embed"][ctrl[:, :1]]
    positions = ctrl[:, 1:2]
    widths = [max(k.shape[0] for k in ks_l) for ks_l in ks]
    col = 2
    new_ks, new_vs, masses = [], [], []
    for l, width in enumerate(widths):
        lp = _slice_layer(params, l)
        slots = ctrl[:, col: col + 2]
        lengths = ctrl[:, col + 2]
        table = ctrl[:, col + 3: col + 3 + width]
        col += 3 + width
        _, q, k_cur, v_cur = _part_a_at(lp, h, cfg, positions)
        ks_l, vs_l, k_pool, v_pool = _pool_write_batch(ks[l], vs[l], k_cur,
                                                       v_cur, slots)
        new_ks.append(ks_l)
        new_vs.append(vs_l)
        out, page_mass = decode_attention(q[:, 0], k_pool, v_pool, table,
                                          lengths)
        attn = out.reshape(b, 1, cfg.n_heads, cfg.d_head)
        h = h + jnp.einsum("bshk,hkd->bsd", attn, lp["wo"])
        h = _ffn(h, lp, cfg, dropless=True)
        mass = page_mass.mean(axis=1)  # (b, width)
        masses.append(jnp.pad(mass, ((0, 0), (0, max(widths) - width))))
    logits = _final_logits_kernel(params, h, cfg.norm_eps)
    return logits, tuple(new_ks), tuple(new_vs), jnp.stack(masses)


class DeviceTailPool(TailPool):
    """Device-resident TailPool: one H2D upload at decode start, zero after.

    The page buffers are ``jax.Array``s living in device memory.  The
    resident unit pages and the prefill suffix KV are assembled host-side
    exactly like the base class (bit-identical layout) and uploaded *once*
    at construction; each decode step's token KV — already on device as a
    slice of part-A's output — lands via a donated
    ``lax.dynamic_update_slice`` jit, so XLA aliases the buffer and no pool
    bytes ever cross PCIe again.  Control-plane operands stay tiny: the
    serving step (``RealCompute.decode_step_batch``) uploads one packed
    int32 array a token — slots, lengths and ``(b, width)`` tables of every
    layer (int32s, not pool bytes — the benchmark's H2D meter counts them);
    the per-layer ``decode_attend`` uploads the page table only when
    ``n_active`` changes (a page boundary crossing, via the
    ``device_table`` cache) plus a 4-byte ``lengths`` scalar per attend.
    ``swap_out``/``swap_in``
    round-trip the buffers to
    host numpy bit-identically — the real scheduler uses them to free a
    preempted request's device state and restore it on resume.
    """

    __slots__ = ("_tbl_dev", "_tbl_n")
    is_device = True

    def __init__(self, k_res, v_res, kv_suffix, page: int, extra_tokens: int,
                 dtype=None):
        super().__init__(k_res, v_res, kv_suffix, page, extra_tokens,
                         dtype=dtype)
        # the one upload: resident pages + suffix already paged in host-side
        self.k = jax.device_put(self.k)
        self.v = jax.device_put(self.v)
        self._tbl_dev = None
        self._tbl_n = -1

    def append(self, k_tok, v_tok):
        """Write one decode position's KV into its page slot on device."""
        self._check_capacity(1)
        if isinstance(k_tok, np.ndarray) or not isinstance(k_tok, jax.Array):
            k_tok = jax.device_put(np.asarray(k_tok))
            v_tok = jax.device_put(np.asarray(v_tok))
        p, s = divmod(self.t, self.page)
        self.k, self.v = _pool_write_device(self.k, self.v, k_tok, v_tok,
                                            self.n_res + p, s)
        self.t += 1

    def slot(self) -> Tuple[int, int]:
        """(page, offset) the next appended token lands in."""
        p, s = divmod(self.t, self.page)
        return self.n_res + p, s

    def device_table(self, put=None):
        """Device page table (1, width), re-uploaded (through `put`,
        default ``jax.device_put``) only when a page boundary crossing
        changes ``n_active`` (log-many tiny uploads per decode, not per
        step)."""
        if self._tbl_n != self.n_active:
            self._tbl_n = self.n_active
            self._tbl_dev = (put or jax.device_put)(self.table()[None])
        return self._tbl_dev

    def attend_args(self, put=None):
        """(k_pool, v_pool, table, lengths) with zero pool H2D traffic.

        The batch dims on k/v are added eagerly here for interface parity
        with the host pool; the hot path (``RealCompute.decode_attend``)
        instead hands the raw buffers to ``decode_attention_pools`` so the
        expand + b=1 stack trace into the jitted step."""
        put = put or jnp.asarray
        return (self.k[None], self.v[None], self.device_table(put),
                put(np.array([self.valid_tokens], np.int32)))

    def swap_out(self) -> int:
        assert self.is_resident, "pool already swapped out"
        k = np.asarray(self.k)
        v = np.asarray(self.v)
        nbytes = k.nbytes + v.nbytes
        # drop the device buffers: the snapshot owns the only copy now
        self.k, self.v = k, v
        self._tbl_dev, self._tbl_n = None, -1
        return nbytes

    def swap_in(self) -> int:
        assert not self.is_resident, "pool is not swapped out"
        nbytes = self.k.nbytes + self.v.nbytes
        self.k = jax.device_put(self.k)
        self.v = jax.device_put(self.v)
        return nbytes

    @property
    def is_resident(self) -> bool:
        """False while swapped out to host between preemption and resume."""
        return isinstance(self.k, jax.Array)


def stack_tail_pools(pools):
    """Pack b requests' TailPools into one ragged decode-attention batch.

    Returns (k_pool, v_pool, table, lengths): pools zero-padded to the
    common page count, tables padded with -1 to the common ``n_active``
    width so pad slots are fully masked by the kernel.  Host pools stack in
    host memory (numpy — the caller's upload is the per-step H2D cost);
    device pools stack with :func:`repro.kernels.decode_attention.ops.
    stack_pool_buffers` in device memory, so no pool bytes cross PCIe."""
    b = len(pools)
    assert all(p.k.shape[1:] == pools[0].k.shape[1:] and
               p.k.dtype == pools[0].k.dtype and
               p.is_device == pools[0].is_device for p in pools), (
        "a ragged batch must share one page geometry, dtype and residency")
    width = max(p.n_res + p.cap_pages for p in pools)
    table = np.full((b, width), -1, np.int32)
    lengths = np.zeros(b, np.int32)
    for i, p in enumerate(pools):
        table[i] = p.table(width)
        lengths[i] = p.valid_tokens
    if pools[0].is_device:
        k, v = stack_pool_buffers(tuple(p.k for p in pools),
                                  tuple(p.v for p in pools))
        return k, v, jax.device_put(table), jax.device_put(lengths)
    n_pages = max(p.k.shape[0] for p in pools)
    dtype = pools[0].k.dtype
    k = np.zeros((b, n_pages) + pools[0].k.shape[1:], dtype)
    v = np.zeros_like(k)
    for i, p in enumerate(pools):
        k[i, : p.k.shape[0]] = p.k
        v[i, : p.v.shape[0]] = p.v
    return k, v, table, lengths


class StatePool:
    """TailPool variant for SSM/hybrid decode: fixed-size recurrent state.

    Instead of a growing paged KV tail, the pool owns one request's whole
    serve-state pytree from :mod:`repro.models.transformer` — the per-layer
    fp32 recurrence ``ssm_h`` and the depthwise-conv window ``ssm_conv``
    (plus the attention KV buffers for hybrid models).  Per-step bytes are
    *constant*: a decode step rewrites the state in place rather than
    appending, so ``nbytes`` never grows with the decoded length.

    It speaks the same preemption contract as :class:`DeviceTailPool`:
    ``swap_out`` snapshots every leaf to host numpy (returning PCIe bytes),
    ``swap_in`` restores device residency bit-identically, and
    ``is_device``/``is_resident`` let the scheduler's batch former and
    preemption paths treat it uniformly with KV pools.
    """

    __slots__ = ("state", "is_device", "_resident")

    def __init__(self, state: Dict, *, device: bool = True):
        """``state`` is the serve-state dict returned by
        ``transformer.prefill`` (keys: length, ssm_h, ssm_conv[, k, v])."""
        self.state = state
        self.is_device = device
        self._resident = device

    @property
    def nbytes(self) -> int:
        return sum(leaf.nbytes for leaf in jax.tree_util.tree_leaves(self.state))

    @property
    def valid_tokens(self) -> int:
        return int(self.state["length"])

    @property
    def is_resident(self) -> bool:
        return self._resident

    def swap_out(self) -> int:
        assert self._resident, "state pool already swapped out"
        self.state = jax.tree_util.tree_map(np.asarray, self.state)
        self._resident = False
        return self.nbytes if self.is_device else 0

    def swap_in(self) -> int:
        assert not self._resident, "state pool is not swapped out"
        nbytes = self.nbytes
        if self.is_device:
            self.state = jax.tree_util.tree_map(jax.device_put, self.state)
        self._resident = True
        return nbytes if self.is_device else 0


def _step_span(method):
    """Run a :class:`RealCompute` method inside a ``step.<name>`` span."""
    name = "step." + method.__name__

    @functools.wraps(method)
    def spanned(*args, **kw):
        with obs.span(name):
            return method(*args, **kw)

    return spanned


class RealCompute:
    """Tiny-model execution; batch = 1 request.

    ``tp_mesh`` (optional) turns the decode-batch paged attention into the
    tensor-parallel shard_map path
    (:func:`repro.launch.sharded_sparse.make_sharded_paged_decode`): pool
    pages shard over the mesh's tensor axes with per-shard page tables, and
    both ``decode_attend`` and ``decode_step_batch`` route through it.  The
    sharded attend is a drop-in (same signature, same per-page mass
    contract) validated bit-close to the single-device kernel.

    Counters: ``d2h_syncs`` counts the blocking device-to-host reads of
    device results (every one goes through :meth:`_to_host`),
    ``h2d_bytes`` the bytes of host arrays uploaded by these methods (every
    one goes through :meth:`_to_device`) and ``fused_decode_tokens`` the
    decode tokens served by the one-program step (:func:`_decode_token`).
    Each method runs inside a ``step.<method>`` span, each read inside
    ``step.d2h``, each upload inside ``step.h2d`` and each one-program
    decode step inside ``step.decode_token`` (:mod:`repro.obs`)."""

    def __init__(self, cfg: ModelConfig, params, *, tp_mesh=None):
        assert cfg.has_attention, "Re-Prefill engine needs attention KV"
        self.cfg = cfg
        self.params = params
        self.tp_mesh = tp_mesh
        self.d2h_syncs = 0
        self.h2d_bytes = 0
        self.fused_decode_tokens = 0
        if tp_mesh is not None:
            # lazy import: core must stay importable without launch/
            from repro.launch.sharded_sparse import make_sharded_paged_decode

            self._tp_attend = make_sharded_paged_decode(tp_mesh)
        else:
            self._tp_attend = None

    def _to_host(self, x, dtype=None):
        """Blocking read of a device result, or of a tuple of them in one
        transfer, into host memory (counted once)."""
        self.d2h_syncs += 1
        with obs.span("step.d2h"):
            if isinstance(x, tuple):
                return tuple(np.asarray(a) for a in jax.device_get(x))
            return np.asarray(x, dtype)

    def _to_device(self, x):
        """Upload a host array (counted; device arrays pass through).  Goes
        through ``jnp.asarray``, so ``storage.h2d_meter.H2DMeter`` sees the
        same bytes."""
        if isinstance(x, jax.Array):
            return x
        x = np.asarray(x)
        self.h2d_bytes += x.nbytes
        with obs.span("step.h2d"):
            return jnp.asarray(x)

    @_step_span
    def embed(self, suffix_tokens: np.ndarray):
        toks = self._to_device(np.asarray(suffix_tokens)[None])
        return _embed(self.params, toks, self.cfg)

    @_step_span
    def part_a(self, layer: int, h, prefix_len: int):
        lp = _slice_layer(self.params, layer)
        return _part_a(lp, h, self.cfg, int(prefix_len))

    @_step_span
    def part_a_at(self, layer: int, h, positions):
        """part_a with traced (b, s) positions: decode steps advance their
        position every token, so a static-offset jit would retrace per step."""
        if not isinstance(positions, jax.Array):
            positions = np.asarray(positions, np.int32)
        lp = _slice_layer(self.params, layer)
        return _part_a_at(lp, h, self.cfg, self._to_device(positions))

    @_step_span
    def token_scores(self, q, k_probe: np.ndarray, layer: int) -> np.ndarray:
        """q: (1, s, nq, d) device; k_probe: (n, n_kv, d_probe) numpy."""
        d = self.cfg.d_head
        kp = self._to_device(k_probe)
        qq = q[0]
        if kp.shape[-1] != d:  # partial keys (IMPRESS): truncate q dims to match
            qq = qq[..., : kp.shape[-1]]
        return self._to_host(SA.probe_token_scores(qq, kp))

    @_step_span
    def part_b(self, layer: int, h, q, k_suf, v_suf,
               k_sel: np.ndarray, v_sel: np.ndarray, sel_valid: np.ndarray,
               chunk_tokens: int):
        lp = _slice_layer(self.params, layer)
        put = self._to_device
        h, mass = _part_b(
            lp, h, q, k_suf, v_suf, put(k_sel), put(v_sel), put(sel_valid),
            self.cfg, chunk_tokens,
        )
        return h, self._to_host(mass)

    @_step_span
    def part_b_batch(self, ctxs):
        """b plans' same-layer final prefill chunks as one vmapped pass.

        `ctxs` are :class:`repro.core.stepplan.PrefillChunkCtx` handles with
        identical shapes (the batch former groups on ``shape_key()``).
        Returns one (h, mass) pair per ctx, in order — exactly what each
        plan's generator expects from its single-request ``fn``.
        """
        c0 = ctxs[0]
        lp = _slice_layer(self.params, c0.layer)
        h = jnp.stack([c.h for c in ctxs])
        q = jnp.stack([c.q for c in ctxs])
        k_suf = jnp.stack([c.k_suf for c in ctxs])
        v_suf = jnp.stack([c.v_suf for c in ctxs])
        put = self._to_device
        k_sel = put(np.stack([np.asarray(c.k_sel) for c in ctxs]))
        v_sel = put(np.stack([np.asarray(c.v_sel) for c in ctxs]))
        valid = put(np.stack([np.asarray(c.valid) for c in ctxs]))
        hs, masses = _part_b_batch_kernel(lp, h, q, k_suf, v_suf, k_sel,
                                          v_sel, valid, self.cfg,
                                          c0.chunk_tokens)
        mass_host = self._to_host(masses)
        return [(hs[i], mass_host[i]) for i in range(len(ctxs))]

    @_step_span
    def recompute_prefix_kv(self, prefix_tokens: np.ndarray, end: int,
                            block_q: int):
        """Recompute KV for the prefix head ``[0, end)`` from raw tokens.

        Runs the same truncated causal forward the ingest path used
        (``transformer.forward`` with ``return_kv=True``), so the fp16 KV is
        *bit-identical* to what ``ChunkStore`` holds: causal attention over
        a head never sees the tail, and the NEG_INF mask zeroes excluded
        positions exactly.  Returns (k, v), each (L, end, n_kv, d) float16.
        """
        from repro.models import transformer as T

        toks = self._to_device(np.asarray(prefix_tokens[:end])[None])
        _, kvs = T.forward(self.params, {"tokens": toks}, self.cfg,
                           block_q=block_q, return_kv=True)
        k = self._to_host(kvs[0][:, 0], np.float16)
        v = self._to_host(kvs[1][:, 0], np.float16)
        return k, v

    @_step_span
    def logits(self, h) -> np.ndarray:
        return self._to_host(
            _final_logits_kernel(self.params, h, self.cfg.norm_eps))

    @_step_span
    def decode_attend(self, layer: int, h, q, tail: TailPool):
        """One decode position's sparse attention over `tail`'s paged pool.

        The pool already holds the cache-resident unit pages, the suffix KV
        (paged once at decode start) and every decoded position including the
        current one (appended by the caller before attending), so no per-step
        concatenate/re-pad happens and the call shape is fixed for the whole
        decode.  The pool supplies its own kernel operands
        (``tail.attend_args()``): a :class:`DeviceTailPool` hands over its
        device-resident buffers directly (zero pool H2D per step), a host
        pool uploads.  Returns (h_out, mass) where mass is the
        per-resident-page attention probability (AGC's A_j).
        """
        cfg = self.cfg
        lp = _slice_layer(self.params, layer)
        q1 = q[:, 0]  # (1, n_q, d) — single decode position
        put = self._to_device
        lengths = np.array([tail.valid_tokens], np.int32)
        if self._tp_attend is not None:
            if tail.is_device:
                k_pool, v_pool = stack_pool_buffers((tail.k,), (tail.v,))
                out, page_mass = self._tp_attend(
                    q1, k_pool, v_pool, tail.device_table(put), put(lengths))
            else:
                out, page_mass = self._tp_attend(q1, *tail.attend_args(put))
        elif tail.is_device:
            # raw device buffers straight into the jitted step: the b=1
            # expand happens inside the trace, so the whole attend is one
            # dispatch with zero pool bytes moved
            out, page_mass = decode_attention_pools(
                q1, (tail.k,), (tail.v,), tail.device_table(put),
                put(lengths))
        else:
            out, page_mass = decode_attention(q1, *tail.attend_args(put))
        attn = out.reshape(1, 1, cfg.n_heads, cfg.d_head)
        o = jnp.einsum("bshk,hkd->bsd", attn, lp["wo"])
        h = h + o
        h = _ffn(h, lp, cfg, dropless=True)
        # per-resident-page attention mass (decode-time cache scores) comes
        # straight from the kernel's online softmax — no second score pass
        mass = page_mass[0].mean(axis=0)[: tail.n_res]  # head-avg, resident
        return h, self._to_host(mass)

    @_step_span
    def decode_step_batch(self, ctxs):
        """One decode position for b requests in a single batched pass.

        `ctxs` are :class:`repro.core.stepplan.DecodeBatchCtx` handles the
        engines stamped on their decode ComputeOps: input token, absolute
        position, and the per-layer TailPools.  Device pools on one device
        run every layer as one program (:func:`_decode_token`): one packed
        upload of the control operands and one read of logits and masses
        per token.  Host pools and the tensor-parallel attend run a
        per-layer loop: one embed / part-A / paged decode-attention / FFN
        pass per layer for the whole ragged batch (per-request page tables
        padded to a common width, `lengths` masking the pads).  Either way
        the weights stream once for the batch, the way the sim scheduler's
        `compute_batch_at` prices it.  Returns one (logits, masses) pair per
        request, in `ctxs` order — exactly what the per-request generators
        expect from their single-request `fn`.
        """
        if ctxs[0].pools[0].is_device and self._tp_attend is None:
            return self._decode_fused(ctxs)
        cfg = self.cfg
        put = self._to_device
        b = len(ctxs)
        tokens = np.array([c.token for c in ctxs], np.int64)[:, None]
        h = _embed(self.params, put(tokens), cfg)  # (b, 1, d_model)
        positions = put(np.array([[c.pos] for c in ctxs], np.int32))
        device = ctxs[0].pools[0].is_device
        masses = [{} for _ in ctxs]
        for l in range(cfg.n_layers):
            lp = _slice_layer(self.params, l)
            _, q, k_cur, v_cur = _part_a_at(lp, h, cfg, positions)
            if device:
                # KV stays on device: all b donated in-place pool writes and
                # the ragged batch stack run as one dispatch, reading pages
                # directly from device memory — no D2H/H2D round trip
                pools_l = [c.pools[l] for c in ctxs]
                for p in pools_l:
                    p._check_capacity(1)
                slots = put(np.array([p.slot() for p in pools_l], np.int32))
                new_ks, new_vs, k_pool, v_pool = _pool_write_batch_device(
                    tuple(p.k for p in pools_l), tuple(p.v for p in pools_l),
                    k_cur, v_cur, slots)
                for p, nk, nv in zip(pools_l, new_ks, new_vs):
                    p.k, p.v = nk, nv
                    p.t += 1
                width = max(p.n_res + p.cap_pages for p in pools_l)
                table = np.stack([p.table(width) for p in pools_l])
                lengths = np.array([p.valid_tokens for p in pools_l],
                                   np.int32)
            else:
                k_host = self._to_host(k_cur)  # (b, 1, n_kv, d)
                v_host = self._to_host(v_cur)
                for i, c in enumerate(ctxs):
                    c.pools[l].append(k_host[i], v_host[i])
                k_pool, v_pool, table, lengths = stack_tail_pools(
                    [c.pools[l] for c in ctxs])
                k_pool, v_pool = put(k_pool), put(v_pool)
            attend = (self._tp_attend if self._tp_attend is not None
                      else decode_attention)
            out, page_mass = attend(q[:, 0], k_pool, v_pool, put(table),
                                    put(lengths))
            attn = out.reshape(b, 1, cfg.n_heads, cfg.d_head)
            o = jnp.einsum("bshk,hkd->bsd", attn, lp["wo"])
            h = h + o
            h = _ffn(h, lp, cfg, dropless=True)
            pm = self._to_host(page_mass)  # (b, n_q, width)
            for i, c in enumerate(ctxs):
                masses[i][l] = pm[i].mean(axis=0)[: c.pools[l].n_res]
        logits = self._to_host(
            _final_logits_kernel(self.params, h, cfg.norm_eps))
        return [(logits[i: i + 1], masses[i]) for i in range(b)]

    def _decode_fused(self, ctxs):
        """:meth:`decode_step_batch` over device pools, as one program."""
        layers = [[c.pools[l] for c in ctxs] for l in range(self.cfg.n_layers)]
        ctrl = [[c.token, c.pos] for c in ctxs]
        for pools in layers:
            width = max(p.k.shape[0] for p in pools)
            for row, p in zip(ctrl, pools):
                p._check_capacity(1)
                row.extend(p.slot())
                p.t += 1
                row.append(p.valid_tokens)
                row.extend(p.table(width))
        with obs.span("step.decode_token"):
            logits, ks, vs, mass = _decode_token(
                self.params, self._to_device(np.array(ctrl, np.int32)),
                tuple(tuple(p.k for p in pools) for pools in layers),
                tuple(tuple(p.v for p in pools) for pools in layers),
                self.cfg)
            self.fused_decode_tokens += len(ctxs)
            for pools, ks_l, vs_l in zip(layers, ks, vs):
                for p, k, v in zip(pools, ks_l, vs_l):
                    p.k, p.v = k, v
            logits, mass = self._to_host((logits, mass))
        return [(logits[i: i + 1],
                 {l: mass[l, i, : pools[i].n_res]
                  for l, pools in enumerate(layers)})
                for i in range(len(ctxs))]


class SimCompute:
    """Paper-scale simulation: no arrays, selection from a workload model."""

    def __init__(self, cfg: ModelConfig, workload):
        self.cfg = cfg
        self.workload = workload  # provides token_scores(request, layer) -> np
        self._request_id = 0

    def new_request(self, request_id: int):
        self._request_id = request_id

    def embed(self, suffix_tokens):
        return None

    def part_a(self, layer, h, prefix_len):
        return None, None, None, None

    def token_scores(self, q, k_probe, layer: int) -> np.ndarray:
        return self.workload.token_scores(self._request_id, layer)

    def part_b(self, layer, h, q, k_suf, v_suf, k_sel, v_sel, sel_valid, chunk_tokens):
        mass = self.workload.chunk_mass(self._request_id, layer, sel_valid)
        return None, mass

    def logits(self, h):
        return None

    def decode_scores(self, request_id: int, step: int) -> np.ndarray:
        """Token-importance field for decode position `step`."""
        return self.workload.decode_token_scores(request_id, step)

    def decode_mass(self, request_id: int, layer: int, n_units: int) -> np.ndarray:
        """Per-attended-unit attention mass for AGC decode-time updates."""
        return self.workload.chunk_mass(request_id, layer, np.ones(n_units, bool))


# ---------------------------------------------------------------------------
# state-space (SSM / hybrid) backend
# ---------------------------------------------------------------------------
@partial(jax.jit, static_argnames=("cfg",))
def _state_prefill(params, tokens, cfg: ModelConfig, state):
    return _t_prefill(params, {"tokens": tokens}, cfg, state)


@partial(jax.jit, static_argnames=("cfg", "ssm_kernel"))
def _state_decode(params, token, cfg: ModelConfig, state, ssm_kernel: bool):
    return _t_decode_step(params, token, cfg, state, ssm_kernel=ssm_kernel)


def _stack_states(states):
    """Stack per-request serve states along the batch axis (axis 1 of every
    array leaf; ``length`` is a shared scalar and must already agree)."""
    out = {}
    for key in states[0]:
        if key == "length":
            out[key] = states[0][key]
        else:
            out[key] = jnp.concatenate([st[key] for st in states], axis=1)
    return out


class StateCompute:
    """Real whole-model backend for the SSM/hybrid families.

    :class:`RealCompute` decomposes attention models into part-A/part-B
    passes around a paged KV pool; the state-space families instead run the
    stacked serve path of :mod:`repro.models.transformer` directly —
    ``prefill`` fills a fixed-size serve-state pytree (per-layer fp32
    recurrence + conv window, plus attention KV for hybrid) wrapped in a
    :class:`StatePool`, and each ``decode_step`` rewrites that state in
    place through the fused ``kernels.selective_scan`` Pallas path
    (``ssm_kernel=True``, the default; the inline XLA recurrence is the
    oracle).  ``decode_step_batch`` is the fleet batching surface: members
    whose states share one geometry and length stack along the batch axis
    and run as a single kernel pass."""

    def __init__(self, cfg: ModelConfig, params, *, device: bool = True,
                 ssm_kernel: bool = True):
        assert cfg.family in ("ssm", "hybrid"), (
            "StateCompute serves the state-space families; use RealCompute "
            "for attention models")
        self.cfg = cfg
        self.params = params
        self.device = device
        self.ssm_kernel = ssm_kernel

    def new_request(self, request_id: int):
        """Interface parity with RealCompute (stateless between requests)."""

    def prefill(self, tokens, extra_tokens: int = 0):
        """Run the whole prompt; returns (first-token logits, StatePool).

        ``extra_tokens`` preallocates decode capacity in the hybrid KV
        buffers (pure SSM state is length-independent either way)."""
        tokens = np.asarray(tokens, np.int32)[None]  # (1, s)
        state = init_serve_state(self.cfg, 1,
                                 tokens.shape[1] + int(extra_tokens))
        logits, state = _state_prefill(self.params, jnp.asarray(tokens),
                                       self.cfg, state)
        return np.asarray(logits), StatePool(state, device=self.device)

    def decode_step(self, token: int, state):
        """One greedy decode position; returns (logits, new_state)."""
        tok = jnp.asarray(np.array([[token]], np.int32))
        logits, new_state = _state_decode(self.params, tok, self.cfg, state,
                                          self.ssm_kernel)
        return np.asarray(logits), new_state

    def decode_step_batch(self, ctxs):
        """One batched decode pass over `ctxs`' StatePools.

        States that share a tree structure, leaf shapes and length stack
        along the batch axis into a single ``decode_step``; a ragged batch
        falls back to per-request steps (still one scheduler iteration).
        Each member's pool is updated in place; returns per-ctx logits."""
        states = [c.pools[0].state for c in ctxs]
        lengths = {int(np.asarray(st["length"])) for st in states}
        shapes = {tuple((k, tuple(v.shape)) for k, v in sorted(st.items())
                  if k != "length") for st in states}
        if len(lengths) > 1 or len(shapes) > 1:
            outs = []
            for c in ctxs:
                logits, new_state = self.decode_step(c.token, c.pools[0].state)
                c.pools[0].state = new_state
                outs.append(logits)
            return outs
        batched = _stack_states(states)
        toks = jnp.asarray(np.array([[c.token] for c in ctxs], np.int32))
        logits, new_batched = _state_decode(self.params, toks, self.cfg,
                                            batched, self.ssm_kernel)
        logits = np.asarray(logits)
        for i, c in enumerate(ctxs):
            c.pools[0].state = {
                k: (v if k == "length" else v[:, i: i + 1])
                for k, v in new_batched.items()}
        return [logits[i: i + 1] for i in range(len(ctxs))]
