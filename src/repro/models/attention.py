"""Grouped-query attention with flash-style chunking, SWA, qk-norm, QKV bias.

Covers the attention variants of the assigned architectures:
  * GQA with arbitrary (n_heads, n_kv_heads)  — all LM archs
  * QKV bias                                  — qwen1.5-110b
  * qk RMS-norm                               — qwen3-14b
  * sliding-window attention                  — mixtral-8x7b (+ hymba)
  * bidirectional (encoder) and cross attention — seamless-m4t

The training/prefill path is a jax-native flash attention: queries and keys
are processed in fixed chunks with an online-softmax accumulator carried
through ``lax.scan``, so activation memory is O(S * chunk) instead of O(S^2)
— required for the 32k prefill cell and the right structure on TPU (the scan
body is one MXU-friendly block; XLA pipelines HBM loads of K/V chunks).

Decode attends a single query over the KV cache (ring buffer for SWA).
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.distributed.sharding import shard
from repro.models import layers as L

Array = jax.Array

NEG_INF = -1e30


def init(key, cfg: ModelConfig, *, cross: bool = False):
    """QKVO projection params.  Layout: q (d, H, hd) etc., o (H, hd, d)."""
    d, h, hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)

    # init is always digital; per-layer analog conversion happens in
    # init_lm via the resolved AnalogPolicy (repro.analog.convert)
    def mk(k, d_in, d_out, axes):
        return L.dense_init(k, d_in, d_out, axes, cfg.param_dtype)

    params: Dict[str, Any] = {}
    axes: Dict[str, Any] = {}
    params["q"], axes["q"] = mk(ks[0], d, h * hd, ("embed", "heads"))
    params["k"], axes["k"] = mk(ks[1], d, hkv * hd, ("embed", "kv_heads"))
    params["v"], axes["v"] = mk(ks[2], d, hkv * hd, ("embed", "kv_heads"))
    params["o"], axes["o"] = mk(ks[3], h * hd, d, ("heads", "embed"))
    if cfg.qkv_bias:
        params["qb"] = jnp.zeros((h * hd,), cfg.param_dtype)
        params["kb"] = jnp.zeros((hkv * hd,), cfg.param_dtype)
        params["vb"] = jnp.zeros((hkv * hd,), cfg.param_dtype)
        axes["qb"] = ("heads",)
        axes["kb"] = ("kv_heads",)
        axes["vb"] = ("kv_heads",)
    if cfg.qk_norm:
        params["q_norm"], axes["q_norm"] = L.rmsnorm_init(hd, cfg.param_dtype)
        params["k_norm"], axes["k_norm"] = L.rmsnorm_init(hd, cfg.param_dtype)
    return params, axes


def _project_qkv(p, x_q: Array, x_kv: Array, cfg: ModelConfig, akey=None):
    h, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim

    def dense(name, xx, i):
        k = None if akey is None else jax.random.fold_in(akey, i)
        with jax.named_scope(name):
            y = L.dense_apply(p[name], xx, key=k)
        if cfg.qkv_bias and name + "b" in p:
            y = y + p[name + "b"].astype(y.dtype)
        return y

    q = dense("q", x_q, 0).reshape(*x_q.shape[:-1], h, hd)
    k = dense("k", x_kv, 1).reshape(*x_kv.shape[:-1], hkv, hd)
    v = dense("v", x_kv, 2).reshape(*x_kv.shape[:-1], hkv, hd)
    if cfg.qk_norm:
        q = L.rmsnorm_apply(p["q_norm"], q, cfg.norm_eps)
        k = L.rmsnorm_apply(p["k_norm"], k, cfg.norm_eps)
    return q, k, v


def _repeat_kv(k: Array, n_rep: int) -> Array:
    if n_rep == 1:
        return k
    return jnp.repeat(k, n_rep, axis=-2)


def _flash(q: Array, k: Array, v: Array, *, causal: bool, window: int,
           chunk_q: int, chunk_k: int, q_offset: int = 0) -> Array:
    """Online-softmax chunked attention.

    q: (B, Sq, H, D); k, v: (B, Sk, H, D) (kv already head-repeated).
    ``q_offset``: absolute position of q[0] relative to k[0] (for caches).
    """
    b, sq, h, d = q.shape
    sk = k.shape[1]
    cq = min(chunk_q, sq)
    ck = min(chunk_k, sk)
    # pad to chunk multiples
    sq_p = -(-sq // cq) * cq
    sk_p = -(-sk // ck) * ck
    qp = jnp.pad(q, ((0, 0), (0, sq_p - sq), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, sk_p - sk), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, sk_p - sk), (0, 0), (0, 0)))
    nq, nk = sq_p // cq, sk_p // ck

    qc = qp.reshape(b, nq, cq, h, d).transpose(1, 0, 3, 2, 4)  # (nq,B,H,cq,d)
    kc = kp.reshape(b, nk, ck, h, d).transpose(1, 0, 3, 2, 4)
    vc = vp.reshape(b, nk, ck, h, d).transpose(1, 0, 3, 2, 4)
    scale = d ** -0.5

    q_pos_base = jnp.arange(cq) + q_offset
    k_pos_base = jnp.arange(ck)

    def per_q_chunk(qi, q_blk):
        q_pos = q_pos_base + qi * cq                     # (cq,)

        # recomputed in the backward pass: only the running (m, l, acc)
        # carry is kept per block, not the (cq, ck) scores and weights, so
        # training keeps attention memory O(S * chunk) as well
        @jax.checkpoint
        def kv_step(carry, inp):
            m, l, acc = carry
            ki, k_blk, v_blk = inp
            s = jnp.einsum("bhqd,bhkd->bhqk", q_blk, k_blk,
                           preferred_element_type=jnp.float32) * scale
            k_pos = k_pos_base + ki * ck                 # (ck,)
            mask = k_pos[None, :] < sk                   # valid (not pad)
            if causal:
                mask = mask & (q_pos[:, None] >= k_pos[None, :])
            if window > 0:
                mask = mask & (q_pos[:, None] - k_pos[None, :] < window)
            s = jnp.where(mask[None, None], s, NEG_INF)
            m_new = jnp.maximum(m, s.max(-1))            # (b,h,cq)
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bhqk,bhkd->bhqd", p.astype(v_blk.dtype), v_blk,
                preferred_element_type=jnp.float32)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, h, cq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, h, cq), jnp.float32)
        a0 = jnp.zeros((b, h, cq, d), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0), (jnp.arange(nk), kc, vc))
        out = acc / jnp.maximum(l[..., None], 1e-30)
        return out                                        # (b,h,cq,d)

    outs = jax.lax.map(lambda t: per_q_chunk(t[0], t[1]),
                       (jnp.arange(nq), qc))              # (nq,b,h,cq,d)
    out = outs.transpose(1, 0, 3, 2, 4).reshape(b, sq_p, h, d)[:, :sq]
    return out.astype(q.dtype)


def forward(p, x: Array, cfg: ModelConfig, *, positions: Array,
            causal: bool = True, x_kv: Optional[Array] = None,
            akey=None, chunk_q: int = 512, chunk_k: int = 512,
            return_kv: bool = False):
    """Training / prefill attention.  ``x_kv`` enables cross-attention."""
    x_kv_in = x if x_kv is None else x_kv
    q, k, v = _project_qkv(p, x, x_kv_in, cfg, akey)
    q = L.rope(q, positions, cfg.rope_theta) if x_kv is None else q
    if x_kv is None:
        k = L.rope(k, positions, cfg.rope_theta)
    q = shard(q, "batch", "seq", "heads", None)
    k = shard(k, "batch", "seq", "kv_heads", None)
    v = shard(v, "batch", "seq", "kv_heads", None)
    n_rep = cfg.n_heads // cfg.n_kv_heads
    if cfg.use_flash_kernel:
        from repro.kernels.flash_attention import flash_attention
        from repro.kernels.ops import _interpret_default
        out = flash_attention(
            q, _repeat_kv(k, n_rep), _repeat_kv(v, n_rep),
            causal=causal, window=cfg.swa_window,
            interpret=_interpret_default())
    else:
        out = _flash(q, _repeat_kv(k, n_rep), _repeat_kv(v, n_rep),
                     causal=causal, window=cfg.swa_window,
                     chunk_q=chunk_q, chunk_k=chunk_k)
    out = out.reshape(*out.shape[:-2], cfg.n_heads * cfg.head_dim)
    okey = None if akey is None else jax.random.fold_in(akey, 3)
    with jax.named_scope("o"):
        y = L.dense_apply(p["o"], out, key=okey)
    y = shard(y, "batch", "seq", "embed_act")
    if return_kv:
        return y, (k, v)
    return y


def decode(p, x_t: Array, cache_k: Array, cache_v: Array, pos: Array,
           cfg: ModelConfig, *, cross: bool = False, akey=None):
    """Single-token decode.

    x_t: (B, 1, d).  cache_k/v: (B, S_cache, Hkv, hd) — for self-attention a
    ring/linear buffer updated at ``pos``; for cross-attention the encoder
    memory (not updated).  Returns (y, new_k, new_v).
    """
    q, k_new, v_new = _project_qkv(p, x_t, x_t, cfg, akey)
    if not cross:
        q = L.rope(q, pos[..., None], cfg.rope_theta)
        k_new = L.rope(k_new, pos[..., None], cfg.rope_theta)
        s_cache = cache_k.shape[1]
        if cfg.swa_window > 0 and s_cache == cfg.swa_window:
            slot = (pos % cfg.swa_window)
        else:
            slot = pos
        cache_k = _scatter_time(cache_k, k_new, slot)
        cache_v = _scatter_time(cache_v, v_new, slot)

    n_rep = cfg.n_heads // cfg.n_kv_heads
    kk = _repeat_kv(dequantize_kv(cache_k, q.dtype), n_rep)
    vv = _repeat_kv(dequantize_kv(cache_v, q.dtype), n_rep)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   kk.astype(jnp.float32)) * (cfg.head_dim ** -0.5)
    s_cache = cache_k.shape[1]
    k_pos = jnp.arange(s_cache)
    if not cross:
        if cfg.swa_window > 0 and s_cache == cfg.swa_window:
            # ring buffer: slot s holds absolute position pos - age where
            # age = (pos - s) mod window; valid once actually written
            age = (pos[:, None] % cfg.swa_window - k_pos[None, :]) \
                % cfg.swa_window
            valid = (pos[:, None] - age) >= 0
            mask = valid[:, None, None, :]
        else:
            mask = (k_pos[None, :] <= pos[:, None])[:, None, None, :]
        s = jnp.where(mask, s, NEG_INF)
    a = jax.nn.softmax(s, axis=-1).astype(vv.dtype)
    out = jnp.einsum("bhqk,bkhd->bqhd", a, vv)
    out = out.reshape(*x_t.shape[:-1], cfg.n_heads * cfg.head_dim)
    okey = None if akey is None else jax.random.fold_in(akey, 3)
    with jax.named_scope("o"):
        y = L.dense_apply(p["o"], out, key=okey)
    return y, cache_k, cache_v


_KV_Q_SCALE = 16.0   # int8 KV quantisation: symmetric, +-8 range


def quantize_kv(x: Array) -> Array:
    return jnp.clip(jnp.round(x.astype(jnp.float32) * _KV_Q_SCALE),
                    -127, 127).astype(jnp.int8)


def dequantize_kv(q: Array, dtype) -> Array:
    if q.dtype == jnp.int8:
        return (q.astype(jnp.float32) / _KV_Q_SCALE).astype(dtype)
    return q


def _scatter_time(cache: Array, new: Array, slot: Array) -> Array:
    """cache (B,S,H,D) <- new (B,1,H,D) at per-batch time index ``slot``."""
    if cache.dtype == jnp.int8:
        new = quantize_kv(new)
    oh = (jax.nn.one_hot(slot, cache.shape[1]) > 0)           # (B,S) bool
    return jnp.where(oh[:, :, None, None], new.astype(cache.dtype), cache)
