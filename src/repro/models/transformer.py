"""Decoder-only LM covering every assigned family.

Layers are stacked (leading axis = layer) and iterated with `lax.scan`; mixed
local/global attention (gemma3 5:1, mixtral SWA) is expressed as a per-layer
window-size vector consumed inside the scan, so the HLO stays one loop.

Families:
  dense   — GQA attention + SwiGLU
  moe     — GQA attention + top-k MoE FFN
  ssm     — mamba-1 mixer only (falcon-mamba)
  hybrid  — parallel attention + mamba heads, then SwiGLU (hymba)
  audio / vlm — dense backbone; inputs are precomputed frame/patch embeddings
"""
from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.attention import attention_decode, attention_prefill, qkv_project
from repro.models.common import ModelConfig
from repro.models.layers import rms_norm, swiglu
from repro.models.moe import init_moe_params, moe_ffn
from repro.models.ssm import (
    init_mamba_params,
    init_mamba_state,
    mamba_block,
    mamba_decode_step,
)

Params = Dict[str, Any]


# --------------------------------------------------------------------------
# init
# --------------------------------------------------------------------------
def _init_layer(key, cfg: ModelConfig, dtype) -> Params:
    ks = iter(jax.random.split(key, 16))
    p: Params = {}
    d = cfg.d_model
    s_in = d ** -0.5
    if cfg.has_attention:
        p["wq"] = (s_in * jax.random.normal(next(ks), (d, cfg.n_heads, cfg.d_head))).astype(dtype)
        p["wk"] = (s_in * jax.random.normal(next(ks), (d, cfg.n_kv_heads, cfg.d_head))).astype(dtype)
        p["wv"] = (s_in * jax.random.normal(next(ks), (d, cfg.n_kv_heads, cfg.d_head))).astype(dtype)
        p["wo"] = (
            (cfg.attn_dim ** -0.5)
            * jax.random.normal(next(ks), (cfg.n_heads, cfg.d_head, d))
        ).astype(dtype)
        if cfg.qkv_bias:
            p["bq"] = jnp.zeros((cfg.n_heads, cfg.d_head), dtype)
            p["bk"] = jnp.zeros((cfg.n_kv_heads, cfg.d_head), dtype)
            p["bv"] = jnp.zeros((cfg.n_kv_heads, cfg.d_head), dtype)
        if cfg.qk_norm:
            p["q_norm"] = jnp.zeros((cfg.d_head,), dtype)
            p["k_norm"] = jnp.zeros((cfg.d_head,), dtype)
        p["attn_norm"] = jnp.zeros((d,), dtype)
    if cfg.family in ("ssm", "hybrid"):
        p["mamba"] = init_mamba_params(next(ks), cfg, dtype)
        if cfg.family == "ssm":
            p["attn_norm"] = jnp.zeros((d,), dtype)  # pre-mixer norm
    if cfg.family == "moe":
        p["moe"] = init_moe_params(next(ks), cfg, dtype)
        p["ffn_norm"] = jnp.zeros((d,), dtype)
    elif cfg.d_ff and cfg.family != "ssm":
        f = cfg.d_ff
        p["w_gate"] = (s_in * jax.random.normal(next(ks), (d, f))).astype(dtype)
        p["w_up"] = (s_in * jax.random.normal(next(ks), (d, f))).astype(dtype)
        p["w_down"] = ((f ** -0.5) * jax.random.normal(next(ks), (f, d))).astype(dtype)
        p["ffn_norm"] = jnp.zeros((d,), dtype)
    return p


@partial(jax.jit, static_argnums=(1,))
def init_params(key, cfg: ModelConfig) -> Params:
    """Random weights from ``key``.  Jitted so every f32 draw fuses into its
    cast: a published-width embedding drawn eagerly in f32 would hold a
    multi-GB transient on the device before the cast."""
    dtype = cfg.activation_dtype()
    k_emb, k_layers, k_out = jax.random.split(key, 3)
    layer_keys = jax.random.split(k_layers, cfg.n_layers)
    layers = jax.vmap(lambda k: _init_layer(k, cfg, dtype))(layer_keys)
    params: Params = {
        "embed": (
            (cfg.d_model ** -0.5)
            * jax.random.normal(k_emb, (cfg.vocab_size, cfg.d_model))
        ).astype(dtype),
        "layers": layers,
        "final_norm": jnp.zeros((cfg.d_model,), dtype),
    }
    if not cfg.tie_embeddings:
        params["unembed"] = (
            (cfg.d_model ** -0.5)
            * jax.random.normal(k_out, (cfg.d_model, cfg.vocab_size))
        ).astype(dtype)
    return params


# --------------------------------------------------------------------------
# layer bodies
# --------------------------------------------------------------------------
def _ffn(h: jax.Array, lp: Params, cfg: ModelConfig, *, dropless: bool = False) -> jax.Array:
    from repro.launch.act_sharding import constrain

    if cfg.family == "moe":
        x = rms_norm(h, lp["ffn_norm"], cfg.norm_eps)
        return constrain(h + moe_ffn(
            x,
            lp["moe"],
            n_experts=cfg.n_experts,
            top_k=cfg.top_k,
            capacity_factor=cfg.moe_capacity_factor,
            dropless=dropless,
        ), "hidden")
    if "w_gate" in lp:
        x = rms_norm(h, lp["ffn_norm"], cfg.norm_eps)
        return constrain(h + swiglu(x, lp["w_gate"], lp["w_up"], lp["w_down"]), "hidden")
    return h


def _layer_prefill(
    h: jax.Array,
    lp: Params,
    window: jax.Array,
    positions: jax.Array,
    cfg: ModelConfig,
    block_q: int,
) -> Tuple[jax.Array, Optional[Tuple[jax.Array, jax.Array]]]:
    kv = None
    if cfg.family == "ssm":
        x = rms_norm(h, lp["attn_norm"], cfg.norm_eps)
        h = h + mamba_block(x, lp["mamba"], cfg)
        return h, None
    x = rms_norm(h, lp["attn_norm"], cfg.norm_eps)
    q, k, v = qkv_project(x, lp, cfg, positions)
    attn = attention_prefill(q, k, v, window=window, block_q=block_q)
    out = jnp.einsum("bshk,hkd->bsd", attn, lp["wo"])
    if cfg.family == "hybrid":
        out = 0.5 * (out + mamba_block(x, lp["mamba"], cfg))
    h = h + out
    h = _ffn(h, lp, cfg)
    kv = (k, v)
    return h, kv


# --------------------------------------------------------------------------
# forward passes
# --------------------------------------------------------------------------
def _inputs_to_h(params: Params, batch: Dict[str, jax.Array], cfg: ModelConfig):
    if "embeds" in batch:  # audio/vlm stub frontends supply embeddings
        return batch["embeds"].astype(cfg.activation_dtype())
    return params["embed"][batch["tokens"]]


def _logits(params: Params, h: jax.Array, cfg: ModelConfig) -> jax.Array:
    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    w = params["unembed"] if not cfg.tie_embeddings else params["embed"].T
    return jnp.einsum("...d,dv->...v", h, w).astype(jnp.float32)


def forward(
    params: Params,
    batch: Dict[str, jax.Array],
    cfg: ModelConfig,
    *,
    block_q: int = 512,
    logits_positions: str = "all",  # "all" (training) | "last" (prefill)
    return_kv: bool = False,
    remat: bool = False,
):
    """Full forward pass. Returns logits (and stacked per-layer KV if asked)."""
    h = _inputs_to_h(params, batch, cfg)
    b, s, _ = h.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    windows = jnp.asarray(cfg.window_sizes())

    def body(carry, xs):
        lp, window = xs
        h_new, kv = _layer_prefill(carry, lp, window, positions, cfg, block_q)
        ys = kv if (return_kv and kv is not None) else None
        return h_new, ys

    if remat:
        policy = (jax.checkpoint_policies.dots_with_no_batch_dims_saveable
                  if remat == "dots" else jax.checkpoint_policies.nothing_saveable)
        body = jax.checkpoint(body, policy=policy)
    h, kvs = jax.lax.scan(body, h, (params["layers"], windows))
    if logits_positions == "last":
        logits = _logits(params, h[:, -1:], cfg)
    else:
        logits = _logits(params, h, cfg)
    if return_kv:
        return logits, kvs  # kvs: (k, v) each (L, b, s, n_kv, d_head) or None
    return logits


def loss_fn(params: Params, batch: Dict[str, jax.Array], cfg: ModelConfig,
            block_q: int = 512, remat: bool = False) -> jax.Array:
    """Next-token cross entropy. batch needs tokens|embeds and labels."""
    logits = forward(params, batch, cfg, block_q=block_q, remat=remat)
    labels = batch["labels"]
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    mask = batch.get("loss_mask")
    if mask is not None:
        return (nll * mask).sum() / jnp.maximum(mask.sum(), 1)
    return nll.mean()


# --------------------------------------------------------------------------
# serving: prefill -> ServeState, decode_step
# --------------------------------------------------------------------------
def init_serve_state(cfg: ModelConfig, batch: int, max_len: int) -> Dict[str, Any]:
    dtype = cfg.activation_dtype()
    state: Dict[str, Any] = {"length": jnp.zeros((), jnp.int32)}
    if cfg.has_attention:
        shape = (cfg.n_layers, batch, max_len, cfg.n_kv_heads, cfg.d_head)
        state["k"] = jnp.zeros(shape, dtype)
        state["v"] = jnp.zeros(shape, dtype)
    if cfg.family in ("ssm", "hybrid"):
        h0, conv0 = init_mamba_state(batch, cfg, dtype)
        state["ssm_h"] = jnp.broadcast_to(h0, (cfg.n_layers,) + h0.shape)
        state["ssm_conv"] = jnp.broadcast_to(conv0, (cfg.n_layers,) + conv0.shape)
    return state


def prefill(
    params: Params,
    batch: Dict[str, jax.Array],
    cfg: ModelConfig,
    state: Dict[str, Any],
    *,
    block_q: int = 512,
) -> Tuple[jax.Array, Dict[str, Any]]:
    """Process the prompt, fill the serve state, return first-token logits."""
    h = _inputs_to_h(params, batch, cfg)
    b, s, _ = h.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    windows = jnp.asarray(cfg.window_sizes())

    def body(carry, xs):
        lp, window = xs
        ys = {}
        if cfg.family == "ssm":
            x = rms_norm(carry, lp["attn_norm"], cfg.norm_eps)
            out, (h_s, conv_s) = mamba_block(x, lp["mamba"], cfg, return_state=True)
            carry = carry + out
            ys["ssm_h"], ys["ssm_conv"] = h_s, conv_s
            return carry, ys
        x = rms_norm(carry, lp["attn_norm"], cfg.norm_eps)
        q, k, v = qkv_project(x, lp, cfg, positions)
        attn = attention_prefill(q, k, v, window=window, block_q=block_q)
        out = jnp.einsum("bshk,hkd->bsd", attn, lp["wo"])
        if cfg.family == "hybrid":
            s_out, (h_s, conv_s) = mamba_block(x, lp["mamba"], cfg, return_state=True)
            out = 0.5 * (out + s_out)
            ys["ssm_h"], ys["ssm_conv"] = h_s, conv_s
        carry = carry + out
        carry = _ffn(carry, lp, cfg)
        ys["k"], ys["v"] = k, v
        return carry, ys

    h, ys = jax.lax.scan(body, h, (params["layers"], windows))
    new_state = dict(state)
    if cfg.has_attention:
        new_state["k"] = jax.lax.dynamic_update_slice(
            state["k"], ys["k"].astype(state["k"].dtype), (0, 0, 0, 0, 0)
        )
        new_state["v"] = jax.lax.dynamic_update_slice(
            state["v"], ys["v"].astype(state["v"].dtype), (0, 0, 0, 0, 0)
        )
    if cfg.family in ("ssm", "hybrid"):
        new_state["ssm_h"] = ys["ssm_h"]
        new_state["ssm_conv"] = ys["ssm_conv"]
    new_state["length"] = jnp.asarray(s, jnp.int32)
    logits = _logits(params, h[:, -1:], cfg)
    return logits, new_state


def decode_step(
    params: Params,
    token: jax.Array,  # (b, 1) int32 or (b, 1, d_model) embeds for stub frontends
    cfg: ModelConfig,
    state: Dict[str, Any],
    *,
    ssm_kernel: bool = False,
) -> Tuple[jax.Array, Dict[str, Any]]:
    """One autoregressive step: append token's KV, attend over cache.

    ``ssm_kernel=True`` routes SSM/hybrid recurrence updates through the
    fused ``kernels.selective_scan`` Pallas path (seeded with the carried
    state); the default inline XLA form is the oracle."""
    if token.ndim == 3:
        h = token.astype(cfg.activation_dtype())
    else:
        h = params["embed"][token]
    b = h.shape[0]
    length = state["length"]  # valid tokens already in cache
    positions = jnp.broadcast_to(length[None, None], (b, 1)).astype(jnp.int32)
    windows = jnp.asarray(cfg.window_sizes())

    xs = {"lp": params["layers"], "window": windows}
    if cfg.has_attention:
        xs["k"] = state["k"]
        xs["v"] = state["v"]
    if cfg.family in ("ssm", "hybrid"):
        xs["ssm_h"] = state["ssm_h"]
        xs["ssm_conv"] = state["ssm_conv"]

    def body(carry, x):
        lp = x["lp"]
        ys = {}
        if cfg.family == "ssm":
            xn = rms_norm(carry, lp["attn_norm"], cfg.norm_eps)
            out, (h_s, conv_s) = mamba_decode_step(
                xn, (x["ssm_h"], x["ssm_conv"]), lp["mamba"], cfg,
                use_kernel=ssm_kernel,
            )
            carry = carry + out
            ys["ssm_h"], ys["ssm_conv"] = h_s, conv_s
            return carry, ys
        xn = rms_norm(carry, lp["attn_norm"], cfg.norm_eps)
        q, k_new, v_new = qkv_project(xn, lp, cfg, positions)
        k_cache = jax.lax.dynamic_update_slice(
            x["k"], k_new.astype(x["k"].dtype), (0, length, 0, 0)
        )
        v_cache = jax.lax.dynamic_update_slice(
            x["v"], v_new.astype(x["v"].dtype), (0, length, 0, 0)
        )
        attn = attention_decode(
            q, k_cache, v_cache, length=length + 1, window=x["window"]
        )
        out = jnp.einsum("bshk,hkd->bsd", attn, lp["wo"])
        if cfg.family == "hybrid":
            s_out, (h_s, conv_s) = mamba_decode_step(
                xn, (x["ssm_h"], x["ssm_conv"]), lp["mamba"], cfg,
                use_kernel=ssm_kernel,
            )
            out = 0.5 * (out + s_out)
            ys["ssm_h"], ys["ssm_conv"] = h_s, conv_s
        carry = carry + out
        carry = _ffn(carry, lp, cfg, dropless=True)
        ys["k"], ys["v"] = k_cache, v_cache
        return carry, ys

    h, ys = jax.lax.scan(body, h, xs)
    new_state = dict(state)
    for key in ("k", "v", "ssm_h", "ssm_conv"):
        if ys is not None and key in ys:
            new_state[key] = ys[key]
    new_state["length"] = length + 1
    logits = _logits(params, h, cfg)
    return logits, new_state
