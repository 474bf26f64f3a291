"""The dense architecture: weights, plain reference, control and FLOP counts.

A configuration file names the module that defines its architecture for the
benchmark in its optional key ``"reference"`` (a path from the repository
root, under one of ``BENCHMARK.json``'s ``paths``); without the key it is
this file.  ``run.architecture`` loads it, and the harness reaches weights,
the reference and FLOP counts only through it.  Such a module gives:

- ``dims_of(conf)``: the sizes the readers and the traffic use, at least
  ``layers`` and ``vocab``, and whatever its own counts take;
- ``program_config(conf)``: the serving code's ``ModelConfig``;
- ``make_params(conf, seed)``: the weights, in the program's layout;
- ``Reference(params, conf, prefix, quant=False)``, whose
  ``.request(suffix, served, selections, total)`` returns ``(logits,
  chunk scores per period, selection per period)``; ``quant=True`` is the
  control;
- ``reprefill_flops(dims, suffix, selected_tokens, prefix, periods)``,
  ``decode_token_flops(dims, attended)`` and ``decode_attention_cost(dims,
  valid_tokens, *, page, resident_pages, table_width)``.

This module is the dense Qwen2-style block: GQA attention with optional q/k/v
biases and q/k norms, RoPE on every layer, a SwiGLU FFN.  Only
``program_config`` imports the serving code, when it is called; the weights,
the reference and the counts import nothing of it.

``make_params`` draws the weights both sides are given, on the device and
in bfloat16 (the served type), in one jitted call from the seed.  The
layout is the one the serving code takes (stacked layers, RMSNorm weights
stored as offsets from 1).

The reference states the served semantics in float32 at the highest matmul
precision, independent of the program: a causal prefill of the prefix, then
for each request a teacher-forced pass over ``suffix + served tokens``.  At
the head layer of each period the suffix queries score every prefix token
(softmax over the prefix, summed over heads and suffix queries), chunks sum
those scores, and the top ``ceil(budget * chunks)`` are kept; every layer of
the period attends over the kept chunks (all visible) and causally over the
suffix and the served tokens.  With ``quant=True`` the same code computes in
fp8 (e4m3): weights per output channel, activations per row and KV per
token and head are rounded before each use.  That is the control.

It runs layer by layer so that it fits beside the bfloat16 weights.

The FLOP counts count what the algorithm needs (multiply-adds as 2), not
what a kernel happens to execute: masked columns, padding and recomputation
do not count.  ``dims`` is ``dims_of`` of a configuration file.
"""
from __future__ import annotations

import collections
import dataclasses
import math
from functools import partial
from typing import Iterable

import jax
import jax.numpy as jnp
import numpy as np

Dims = collections.namedtuple(
    "Dims", "d nq nkv dh ff vocab layers eps theta qk_norm qkv_bias")


def dims_of(conf: dict) -> Dims:
    d, nq = conf["hidden_size"], conf["num_attention_heads"]
    return Dims(d=d, nq=nq, nkv=conf["num_key_value_heads"],
                dh=conf.get("head_dim", d // nq), ff=conf["intermediate_size"],
                vocab=conf["vocab_size"], layers=conf["num_hidden_layers"],
                eps=float(conf["rms_norm_eps"]),
                theta=float(conf["rope_theta"]),
                qk_norm=bool(conf["qk_norm"]),
                qkv_bias=bool(conf["qkv_bias"]))


def program_config(conf: dict):
    """The serving code's ModelConfig, with every size from the file."""
    from repro.configs import get_config

    d = dims_of(conf)
    return dataclasses.replace(
        get_config(conf["arch"]), n_layers=d.layers, d_model=d.d,
        n_heads=d.nq, n_kv_heads=d.nkv, d_head=d.dh, d_ff=d.ff,
        vocab_size=d.vocab, qk_norm=d.qk_norm, qkv_bias=d.qkv_bias,
        rope_theta=d.theta, norm_eps=d.eps,
        tie_embeddings=bool(conf["tie_word_embeddings"]),
        dtype=conf["torch_dtype"])


def jax_seed(seed: int) -> int:
    """A 31-bit key seed for JAX from any Python int."""
    return int(np.random.SeedSequence(seed).generate_state(1)[0]) & 0x7FFFFFFF


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------
@partial(jax.jit, static_argnums=(1,))
def _init(key, dm: Dims):
    def draw(k, shape, scale):
        return (scale * jax.random.normal(k, shape, jnp.float32)).astype(
            jnp.bfloat16)

    d, nq, nkv, dh, ff = dm.d, dm.nq, dm.nkv, dm.dh, dm.ff

    def layer(k):
        ks = iter(jax.random.split(k, 16))
        p = {"wq": draw(next(ks), (d, nq, dh), d ** -0.5),
             "wk": draw(next(ks), (d, nkv, dh), d ** -0.5),
             "wv": draw(next(ks), (d, nkv, dh), d ** -0.5),
             "wo": draw(next(ks), (nq, dh, d), (nq * dh) ** -0.5),
             "attn_norm": draw(next(ks), (d,), 0.1),
             "w_gate": draw(next(ks), (d, ff), d ** -0.5),
             "w_up": draw(next(ks), (d, ff), d ** -0.5),
             "w_down": draw(next(ks), (ff, d), ff ** -0.5),
             "ffn_norm": draw(next(ks), (d,), 0.1)}
        if dm.qkv_bias:
            p["bq"] = draw(next(ks), (nq, dh), 0.3)
            p["bk"] = draw(next(ks), (nkv, dh), 0.3)
            p["bv"] = draw(next(ks), (nkv, dh), 0.3)
        if dm.qk_norm:
            p["q_norm"] = draw(next(ks), (dh,), 0.1)
            p["k_norm"] = draw(next(ks), (dh,), 0.1)
        return p

    k_emb, k_layers, k_out, k_norm = jax.random.split(key, 4)
    return {"embed": draw(k_emb, (dm.vocab, d), d ** -0.5),
            "layers": jax.vmap(layer)(jax.random.split(k_layers, dm.layers)),
            "final_norm": draw(k_norm, (d,), 0.1),
            "unembed": draw(k_out, (d, dm.vocab), d ** -0.5)}


def make_params(conf: dict, seed: int):
    return _init(jax.random.PRNGKey(jax_seed(seed)), dims_of(conf))


# ---------------------------------------------------------------------------
# float32 (or fp8) model pieces
# ---------------------------------------------------------------------------
def _q8(x, axes):
    """Round `x` to fp8 e4m3 with one absmax scale per slice over `axes`."""
    s = jnp.max(jnp.abs(x), axis=axes, keepdims=True) / 448.0
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(spec, x, w, x_axes, w_axes, quant):
    if quant:
        x, w = _q8(x, x_axes), _q8(w, w_axes)
    return jnp.einsum(spec, x, w, precision=jax.lax.Precision.HIGHEST)


def _rms(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + w)


def _rope(x, pos, theta):
    dh = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh))
    ang = pos[:, None].astype(jnp.float32) * inv  # (S, dh/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer_weights(layers, l):
    return jax.tree_util.tree_map(lambda x: x[l].astype(jnp.float32), layers)


def _qkv(h, lw, pos, dm: Dims, quant):
    x = _rms(h, lw["attn_norm"], dm.eps)
    q = _mm("sd,dhk->shk", x, lw["wq"], -1, 0, quant)
    k = _mm("sd,dhk->shk", x, lw["wk"], -1, 0, quant)
    v = _mm("sd,dhk->shk", x, lw["wv"], -1, 0, quant)
    if dm.qkv_bias:
        q, k, v = q + lw["bq"], k + lw["bk"], v + lw["bv"]
    if dm.qk_norm:
        q = _rms(q, lw["q_norm"], dm.eps)
        k = _rms(k, lw["k_norm"], dm.eps)
    q, k = _rope(q, pos, dm.theta), _rope(k, pos, dm.theta)
    if quant:
        k, v = _q8(k, -1), _q8(v, -1)
    return q, k, v


def _attend(q, k, v, mask, dm: Dims):
    """GQA softmax attention; q (S, nq, dh), k/v (T, nkv, dh), mask (S, T)."""
    S, g = q.shape[0], dm.nq // dm.nkv
    qg = q.reshape(S, dm.nkv, g, dm.dh)
    logits = jnp.einsum("sngd,tnd->ngst", qg, k,
                        precision=jax.lax.Precision.HIGHEST) * dm.dh ** -0.5
    probs = jax.nn.softmax(jnp.where(mask, logits, -jnp.inf), axis=-1)
    out = jnp.einsum("ngst,tnd->sngd", probs, v,
                     precision=jax.lax.Precision.HIGHEST)
    return out.reshape(S, dm.nq, dm.dh)


def _finish_layer(h, attn, lw, dm: Dims, quant):
    h = h + _mm("shk,hkd->sd", attn, lw["wo"], (-2, -1), (0, 1), quant)
    x = _rms(h, lw["ffn_norm"], dm.eps)
    g = _mm("sd,df->sf", x, lw["w_gate"], -1, 0, quant)
    u = _mm("sd,df->sf", x, lw["w_up"], -1, 0, quant)
    return h + _mm("sf,fd->sd", jax.nn.silu(g) * u, lw["w_down"], -1, 0, quant)


@jax.jit
def _embed(embed, tokens):
    return embed[tokens].astype(jnp.float32)


@partial(jax.jit, static_argnums=(3, 4, 5))
def _prefix_layer(layers, l, h, dm: Dims, quant: bool, block: int):
    """One layer of the causal prefill over the prefix, in query blocks."""
    lw = _layer_weights(layers, l)
    n = h.shape[0]
    pos = jnp.arange(n)
    q, k, v = _qkv(h, lw, pos, dm, quant)

    def one(b):
        qb = jax.lax.dynamic_slice_in_dim(q, b * block, block)
        rows = b * block + jnp.arange(block)
        return _attend(qb, k, v, rows[:, None] >= pos[None, :], dm)

    attn = jax.lax.map(one, jnp.arange(n // block)).reshape(n, dm.nq, dm.dh)
    return _finish_layer(h, attn, lw, dm, quant), k, v


@partial(jax.jit, static_argnums=(5, 6, 7, 8))
def _chunk_scores(layers, l, h, k_pre, pos, dm: Dims, quant: bool, s: int,
                  chunk: int):
    """Chunk scores of the prefix for the suffix queries at layer `l`."""
    lw = _layer_weights(layers, l)
    q, _, _ = _qkv(h, lw, pos, dm, quant)
    g = dm.nq // dm.nkv
    qg = q[:s].reshape(s, dm.nkv, g, dm.dh)
    logits = jnp.einsum("sngd,tnd->ngst", qg, k_pre,
                        precision=jax.lax.Precision.HIGHEST) * dm.dh ** -0.5
    tok = jax.nn.softmax(logits, axis=-1).sum(axis=(0, 1, 2))
    n = tok.shape[0]
    return jnp.pad(tok, (0, -n % chunk)).reshape(-1, chunk).sum(-1)


@partial(jax.jit, static_argnums=(7, 8, 9))
def _request_layer(layers, l, h, k_pre, v_pre, sel, pos, dm: Dims,
                   quant: bool, chunk: int):
    """One layer over [selected prefix chunks ; suffix + served tokens]."""
    lw = _layer_weights(layers, l)
    q, k, v = _qkv(h, lw, pos, dm, quant)
    idx = (sel[:, None] * chunk + jnp.arange(chunk)).reshape(-1)
    keys = jnp.concatenate([k_pre[idx], k])
    vals = jnp.concatenate([v_pre[idx], v])
    S = h.shape[0]
    mask = jnp.concatenate([jnp.ones((S, idx.shape[0]), bool),
                            jnp.tril(jnp.ones((S, S), bool))], axis=1)
    return _finish_layer(h, _attend(q, keys, vals, mask, dm), lw, dm, quant)


@partial(jax.jit, static_argnums=(3, 4))
def _logits(final_norm, unembed, h, dm: Dims, quant: bool):
    x = _rms(h, final_norm.astype(jnp.float32), dm.eps)
    return _mm("sd,dv->sv", x, unembed.astype(jnp.float32), -1, 0, quant)


def _topk(scores: np.ndarray, budget: float) -> np.ndarray:
    k = min(len(scores), max(1, math.ceil(budget * len(scores))))
    return np.sort(np.argsort(-scores, kind="stable")[:k])


class Reference:
    """The reference (or, with ``quant=True``, its fp8 control) of one
    configuration and prefix.  The prefix is prefilled once at
    construction; :meth:`request` then serves one teacher-forced request."""

    def __init__(self, params, conf: dict, prefix: np.ndarray, *,
                 quant: bool = False):
        self.params, self.dm, self.quant = params, dims_of(conf), quant
        sysconf = conf["system"]
        self.chunk, self.budget = sysconf["chunk_tokens"], sysconf["budget"]
        self.period = sysconf["period"]
        n = len(prefix)
        block = math.gcd(n, 512)
        h = _embed(params["embed"], jnp.asarray(prefix))
        self.k, self.v = [], []
        for l in range(self.dm.layers):
            h, k, v = _prefix_layer(params["layers"], l, h, self.dm, quant,
                                    block)
            self.k.append(k)
            self.v.append(v)
        self.n = n

    def request(self, suffix: np.ndarray, served: np.ndarray, selections=None,
                total: int = 0):
        """Logits at every served position, teacher-forced on `served`.

        `selections` (one chunk array per period) are the chunks to attend
        over; None selects them from this model's own scores.  The input is
        padded to `total` tokens, so that requests of one shape share one
        program (causal attention keeps the padding out of every served
        position).  Returns (logits (len(served), vocab) numpy, [chunk scores
        per period], [selection per period])."""
        dm, p = self.dm, self.params
        s = len(suffix)
        toks = np.concatenate([suffix, served[:-1]]).astype(np.int32)
        toks = np.pad(toks, (0, max(0, total - len(toks))))
        pos = jnp.asarray(self.n + np.arange(len(toks)), jnp.int32)
        h = _embed(p["embed"], jnp.asarray(toks))
        scores, used, sel = [], [], None
        for l in range(dm.layers):
            if l % self.period == 0:
                cs = np.asarray(_chunk_scores(
                    p["layers"], l, h, self.k[l], pos, dm, self.quant, s,
                    self.chunk))
                scores.append(cs)
                sel = (_topk(cs, self.budget) if selections is None
                       else np.asarray(selections[l // self.period]))
                used.append(sel)
            h = _request_layer(p["layers"], l, h, self.k[l], self.v[l],
                               jnp.asarray(sel, jnp.int32), pos, dm,
                               self.quant, self.chunk)
        logits = _logits(p["final_norm"], p["unembed"], h[s - 1:], dm,
                         self.quant)
        return np.asarray(logits)[: len(served)], scores, used


# ---------------------------------------------------------------------------
# FLOP and byte counts
# ---------------------------------------------------------------------------
def layer_matmul_flops(dims, tokens: int) -> float:
    """QKV and output projections plus the SwiGLU FFN of one layer."""
    d, nq, nkv, dh, ff = dims.d, dims.nq, dims.nkv, dims.dh, dims.ff
    proj = d * (nq * dh + 2 * nkv * dh) + nq * dh * d
    return 2.0 * tokens * (proj + 3 * d * ff)


def attention_flops(dims, query_key_pairs: float) -> float:
    """Scores and weighted values over the given (query, key) pairs."""
    return 4.0 * dims.nq * dims.dh * query_key_pairs


def logits_flops(dims) -> float:
    """The output head at one position."""
    return 2.0 * dims.d * dims.vocab


def reprefill_flops(dims, suffix: int, selected_tokens: int,
                    prefix: int, periods: int) -> float:
    """One re-prefill: identification against the whole prefix once per
    period, then every layer attends over the selected chunks and the
    suffix (causally), and the first token's logits."""
    pairs = suffix * selected_tokens + suffix * (suffix + 1) / 2
    identify = periods * 2.0 * suffix * dims.nq * dims.dh * prefix
    return (dims.layers * (layer_matmul_flops(dims, suffix)
                           + attention_flops(dims, pairs))
            + identify + logits_flops(dims))


def decode_token_flops(dims, attended: int) -> float:
    """One decode step of one request that attends over `attended` tokens
    in every layer."""
    return (dims.layers * (layer_matmul_flops(dims, 1)
                           + attention_flops(dims, attended))
            + logits_flops(dims))


def decode_attention_cost(dims, valid_tokens: Iterable[int], *,
                          page: int, resident_pages: int, table_width: int,
                          itemsize: int = 2):
    """(flops, bytes) of one paged decode-attention call over a batch.

    ``valid_tokens[i]`` is request i's attended length: its resident pages
    hold ``resident_pages * page`` tokens, its tail the rest.  The kernel
    reads each active page once (K and V, every KV head), one query and one
    output row per query head, and writes the per-page mass."""
    nq, nkv, dh = dims.nq, dims.nkv, dims.dh
    flops = byts = 0.0
    for n in valid_tokens:
        tail = n - resident_pages * page
        pages = resident_pages + math.ceil(tail / page)
        flops += attention_flops(dims, n)
        byts += 2 * pages * page * nkv * dh * itemsize  # K and V pages
        byts += 2 * nq * dh * itemsize  # q in, out
        byts += nq * table_width * 4  # fp32 per-page mass
    return flops, byts
