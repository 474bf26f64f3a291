"""Distributed ContiguousKV sparse decode via shard_map (§Perf C4).

Hillclimb C showed the in-graph sparse decode losing to dense split-KV on two
counts: (1) the global top-k gathers scores across sequence shards, (2) the
chunk gather crosses shards, and (3) the KV append (dynamic-update-slice at a
traced index into a sharded dim) triggers GSPMD's involuntary full
rematerialization.

This variant keeps *everything local*: each sequence shard selects its own
top-(budget) ContiguousChunks from resident chunk summaries, attends over its
local selection, and the shards merge softmax partials (the flash-decode
combine). The KV append masks to the shard owning position `length`, so the
update indexes an *unsharded local* dim. Selection semantics = per-shard
top-k, a balanced refinement of global top-k (each shard contributes its
budget share — union cardinality identical).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.common import ModelConfig
from repro.models.layers import rms_norm
from repro.models.attention import qkv_project
from repro.models.transformer import _ffn, _logits

NEG_INF = -1e30


def _shard_map(f, *, mesh, in_specs, out_specs):
    """`jax.shard_map` without the varying-manual-axes check."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def _local_sparse_attention(q, k_shard, v_shard, kmean_shard, k_new, v_new,
                            length, *, cfg: ModelConfig, chunk_tokens: int,
                            k_sel: int, seq_axes: Tuple[str, ...]):
    """Per-shard body (runs under shard_map).

    q: (b, 1, H, dh) replicated; k/v_shard: (b, S_local, KV, dh) local;
    kmean_shard: (b, m_local, KV, dh); k_new/v_new: (b, 1, KV, dh) replicated.
    Returns (attn out (b, 1, H, dh) merged, new k/v/kmean shards).
    """
    b, S_local, n_kv, dh = k_shard.shape
    m_local = S_local // chunk_tokens
    # axis_index over the tuple gives the flattened shard index
    base = jax.lax.axis_index(seq_axes) * S_local

    # -- local KV append (no sharded-dim DUS: the dim is local here) --------
    local_pos = length - base
    owns = (local_pos >= 0) & (local_pos < S_local)
    pos_c = jnp.clip(local_pos, 0, S_local - 1)
    k_upd = jax.lax.dynamic_update_slice(k_shard, k_new.astype(k_shard.dtype),
                                         (0, pos_c, 0, 0))
    v_upd = jax.lax.dynamic_update_slice(v_shard, v_new.astype(v_shard.dtype),
                                         (0, pos_c, 0, 0))
    k_shard = jnp.where(owns, k_upd, k_shard)
    v_shard = jnp.where(owns, v_upd, v_shard)
    # incremental chunk-summary update
    kc_idx = pos_c // chunk_tokens
    delta = (k_new[:, 0] / chunk_tokens).astype(kmean_shard.dtype)
    km_slice = jax.lax.dynamic_slice(kmean_shard, (0, kc_idx, 0, 0),
                                     (b, 1, n_kv, dh))
    km_upd = jax.lax.dynamic_update_slice(kmean_shard, km_slice + delta[:, None],
                                          (0, kc_idx, 0, 0))
    kmean_shard = jnp.where(owns, km_upd, kmean_shard)

    # -- local selection from resident summaries ----------------------------
    group = cfg.n_heads // n_kv
    scale = dh ** -0.5
    qg = q.reshape(b, 1, n_kv, group, dh).astype(jnp.float32)
    s_mean = jnp.einsum("bsngd,bmnd->bnsgm", qg,
                        kmean_shard.astype(jnp.float32))  # (b,n_kv,1,g,m)
    chunk_scores = s_mean.sum(axis=(1, 2, 3))  # (b, m_local)
    cpos = base + jnp.arange(m_local) * chunk_tokens
    chunk_scores = jnp.where(cpos[None] <= length, chunk_scores, -jnp.inf)
    _, top_idx = jax.lax.top_k(chunk_scores, k_sel)  # (b, k_sel)

    # -- gather local chunks + masked attention partial ----------------------
    kcs = k_shard.reshape(b, m_local, chunk_tokens, n_kv, dh)
    vcs = v_shard.reshape(b, m_local, chunk_tokens, n_kv, dh)
    kg = jnp.take_along_axis(kcs, top_idx[:, :, None, None, None], axis=1)
    vg = jnp.take_along_axis(vcs, top_idx[:, :, None, None, None], axis=1)
    T = k_sel * chunk_tokens
    kf = kg.reshape(b, T, n_kv, dh)
    vf = vg.reshape(b, T, n_kv, dh)
    sel_pos = (base + top_idx[:, :, None] * chunk_tokens
               + jnp.arange(chunk_tokens)[None, None, :]).reshape(b, T)
    valid = sel_pos <= length

    logits = jnp.einsum("bsngd,btnd->bngst", qg, kf.astype(jnp.float32)) * scale
    logits = jnp.where(valid[:, None, None, None], logits, NEG_INF)
    m_loc = logits.max(axis=-1, keepdims=True)  # (b,n_kv,g,1,1)
    p = jnp.exp(logits - m_loc)
    l_loc = p.sum(axis=-1, keepdims=True)
    o_loc = jnp.einsum("bngst,btnd->bngsd", p, vf.astype(jnp.float32))

    # -- flash-decode combine across shards ----------------------------------
    m_glob = jax.lax.pmax(m_loc, seq_axes)
    corr = jnp.exp(m_loc - m_glob)
    l_glob = jax.lax.psum(l_loc * corr, seq_axes)
    o_glob = jax.lax.psum(o_loc * corr, seq_axes)
    out = (o_glob / jnp.maximum(l_glob, 1e-30))  # (b,n_kv,g,1,dh)
    out = out.transpose(0, 3, 1, 2, 4).reshape(b, 1, cfg.n_heads, dh)
    return out.astype(q.dtype), k_shard, v_shard, kmean_shard


def make_sharded_sparse_decode_step(cfg: ModelConfig, mesh, *,
                                    chunk_tokens: int = 16,
                                    budget: float = 0.05):
    """Sparse decode with per-shard selection; KV seq-sharded over all
    non-trivial axes of `mesh` except none — uses ("data","model") on the
    flat mesh or ("data","kv","rep") on the GQA mesh."""
    assert cfg.has_attention
    seq_axes = tuple(a for a in mesh.axis_names if a != "pod")
    n_shards = 1
    for a in seq_axes:
        n_shards *= mesh.shape[a]

    def step(params, token, state):
        h = (token.astype(cfg.activation_dtype()) if token.ndim == 3
             else params["embed"][token])
        b = h.shape[0]
        length = state["length"]
        S = state["k"].shape[2]
        # the KV capacity is first known here (trace time of the built
        # step): every shard must hold a whole number of chunks, or the
        # local chunk reshape / top_k collapse with opaque shape errors
        # (S_local < chunk_tokens gives m_local = 0 and k_sel = 1 > 0)
        if S % (n_shards * chunk_tokens):
            raise ValueError(
                f"sharded sparse decode needs the KV capacity S={S} "
                f"divisible by n_shards*chunk_tokens = {n_shards}*"
                f"{chunk_tokens} = {n_shards * chunk_tokens} so each shard "
                f"holds whole ContiguousChunks; pad the KV state to a "
                f"multiple or lower chunk_tokens/shard count")
        S_local = S // n_shards
        m_local = S_local // chunk_tokens
        # clamp: a budget >= 1.0 must select every local chunk, never
        # top_k(k > m_local)
        k_sel = min(max(1, int(budget * m_local)), m_local)
        positions = jnp.broadcast_to(length[None, None], (b, 1)).astype(jnp.int32)

        inner = functools.partial(
            _local_sparse_attention, cfg=cfg, chunk_tokens=chunk_tokens,
            k_sel=k_sel, seq_axes=seq_axes)
        kv_spec = P(None, seq_axes, None, None)
        sharded = _shard_map(
            inner, mesh=mesh,
            in_specs=(P(), kv_spec, kv_spec, kv_spec, P(), P(), P()),
            out_specs=(P(), kv_spec, kv_spec, kv_spec),
        )

        xs = {"lp": params["layers"], "k": state["k"], "v": state["v"],
              "kmean": state["kmean"]}

        def body(carry, x):
            lp = x["lp"]
            xn = rms_norm(carry, lp["attn_norm"], cfg.norm_eps)
            q, k_new, v_new = qkv_project(xn, lp, cfg, positions)
            out, k_s, v_s, km_s = sharded(
                q, x["k"], x["v"], x["kmean"], k_new, v_new, length)
            o = jnp.einsum("bshk,hkd->bsd", out, lp["wo"])
            carry = carry + o
            carry = _ffn(carry, lp, cfg, dropless=True)
            return carry, {"k": k_s, "v": v_s, "kmean": km_s}

        h, ys = jax.lax.scan(body, h, xs)
        new_state = dict(state)
        new_state["k"], new_state["v"] = ys["k"], ys["v"]
        new_state["kmean"] = ys["kmean"]
        new_state["length"] = length + 1
        logits = _logits(params, h, cfg)
        return logits, new_state

    return step


# ---------------------------------------------------------------------------
# tensor-parallel paged decode attention (the serving-tier TP backend)
# ---------------------------------------------------------------------------
def _local_paged_attention(q, k_shard, v_shard, page_table, lengths, *,
                           axes: Tuple[str, ...]):
    """Per-shard body of the sharded paged decode attend.

    The pools' *page* dim is sharded: this shard owns physical pages
    ``[base, base + local)``.  Each page-table slot belongs to exactly one
    shard (physical indices partition cleanly), so the shard computes
    logits for the slots it owns, masks the rest, and the shards merge
    softmax partials with the flash-decode combine — slot positions stay
    *logical* (slot * page + offset), so the causal ``pos < lengths`` mask
    is identical to the single-device oracle's.

    q: (b, n_q, d) replicated; k/v_shard: (b, local, page, n_kv, d);
    page_table: (b, n_active) int32, < 0 = pad, replicated; lengths: (b,).
    Returns (out (b, n_q, d), mass (b, n_q, n_active) fp32), replicated.
    """
    b, n_q, d = q.shape
    _, local, page, n_kv, _ = k_shard.shape
    n_active = page_table.shape[1]
    group = n_q // n_kv
    scale = d ** -0.5

    base = jax.lax.axis_index(axes) * local
    owned = (page_table >= base) & (page_table < base + local)  # excl. pads
    tbl = jnp.where(owned, page_table - base, 0)
    k = jnp.take_along_axis(k_shard, tbl[:, :, None, None, None], axis=1)
    v = jnp.take_along_axis(v_shard, tbl[:, :, None, None, None], axis=1)
    k = k.reshape(b, n_active * page, n_kv, d)
    v = v.reshape(b, n_active * page, n_kv, d)

    qg = q.reshape(b, n_kv, group, d).astype(jnp.float32)
    logits = jnp.einsum("bngd,btnd->bngt", qg, k.astype(jnp.float32)) * scale
    pos = jnp.arange(n_active * page)
    mask = pos[None, :] < lengths[:, None]
    mask = mask & jnp.repeat(owned, page, axis=1)
    logits = jnp.where(mask[:, None, None], logits, NEG_INF)

    # flash-decode combine: normalize against the global max so partials
    # from different shards add exactly; masked positions are zeroed
    # explicitly (NEG_INF underflows to 0 anyway, but an all-masked row
    # must not resurrect as exp(0) = 1)
    m_loc = logits.max(axis=-1, keepdims=True)  # (b, n_kv, g, 1)
    m_glob = jax.lax.pmax(m_loc, axes)
    p = jnp.exp(logits - m_glob)
    p = jnp.where(mask[:, None, None], p, 0.0)
    l_glob = jax.lax.psum(p.sum(axis=-1, keepdims=True), axes)
    l_glob = jnp.maximum(l_glob, 1e-30)
    o = jax.lax.psum(
        jnp.einsum("bngt,btnd->bngd", p, v.astype(jnp.float32)), axes)
    out = (o / l_glob).astype(v_shard.dtype)
    mass = jax.lax.psum(
        p.reshape(b, n_kv, group, n_active, page).sum(-1), axes) / l_glob
    return out.reshape(b, n_q, d), mass.reshape(b, n_q, n_active)


def make_sharded_paged_decode(mesh):
    """Tensor-parallel drop-in for :func:`...ops.decode_attention`.

    Returns a jitted ``attend(q, k_pool, v_pool, page_table, lengths) ->
    (out, mass)`` that shards the pools' page dim over the mesh's tensor
    axes (``tp_axes``) and runs :func:`_local_paged_attention` under
    shard_map.  Same signature, same (b, n_q, n_active) mass contract, and
    outputs match the single-device path to fp32 combine precision — each
    page-table slot is owned by exactly one shard, so per-page mass needs
    no dedup.  The page dim is zero-padded to a multiple of the shard
    count inside the jitted wrapper; pad pages are unreachable (no table
    entry points past the real pool), so padding never changes results.
    """
    from repro.launch.mesh import tp_axes  # local import: no cycle at load

    axes = tp_axes(mesh)
    n_shards = 1
    for a in axes:
        n_shards *= mesh.shape[a]

    @jax.jit
    def attend(q, k_pool, v_pool, page_table, lengths):
        pad = (-k_pool.shape[1]) % n_shards
        if pad:
            widths = ((0, 0), (0, pad), (0, 0), (0, 0), (0, 0))
            k_pool = jnp.pad(k_pool, widths)
            v_pool = jnp.pad(v_pool, widths)
        pool_spec = P(None, axes, None, None, None)
        sharded = _shard_map(
            functools.partial(_local_paged_attention, axes=axes),
            mesh=mesh,
            in_specs=(P(), pool_spec, pool_spec, P(), P()),
            out_specs=(P(), P()),
        )
        return sharded(q, k_pool, v_pool,
                       page_table.astype(jnp.int32),
                       lengths.astype(jnp.int32))

    return attend
