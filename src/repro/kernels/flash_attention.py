"""Pallas TPU kernel: fused (flash) attention forward.

The §Roofline analysis shows the XLA scan-lowered attention materialises the
score/probability blocks in HBM (the `roof%fused` column projects their
removal); this kernel is that projection made real: one grid cell computes a
(block_q x head_dim) output tile by streaming K/V blocks through VMEM with
the online-softmax recurrence — scores never leave VMEM.

Grid: (batch*heads, Sq/block_q, Sk/block_k), KV axis innermost
("arbitrary"), carrying (m, l, acc) accumulators in VMEM scratch.  Causal
and sliding-window masking by absolute positions.  Forward path (serving /
prefill); training uses the XLA fallback (a flash backward kernel is the
natural next extension).

Validated in interpret mode against a pure-jnp oracle over
shapes/window/causal sweeps (tests/test_flash_attention.py).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            nk: int, block_q: int, block_k: int, scale: float,
            causal: bool, window: int, sq: int, sk: int):
    kv = pl.program_id(2)

    @pl.when(kv == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    qb = q_ref[0]                       # (block_q, d)
    kb = k_ref[0]                       # (block_k, d)
    s = jax.lax.dot_general(qb, kb, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    s = s * np.float32(scale)

    qi = pl.program_id(1)
    q_pos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    k_pos = kv * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    mask = k_pos < sk                   # padding
    if causal:
        mask = jnp.logical_and(mask, q_pos >= k_pos)
    if window > 0:
        mask = jnp.logical_and(mask, q_pos - k_pos < window)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
    m_ref[...] = m_new
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
        p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(kv == nk - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...]
                    / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "block_q", "block_k", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int = 0,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = False) -> jax.Array:
    """q (B, Sq, H, D); k, v (B, Sk, H, D) with H already GQA-repeated.

    Returns (B, Sq, H, D).  Scores/probabilities stay in VMEM.
    """
    b, sq, h, d = q.shape
    sk = k.shape[1]
    scale = d ** -0.5

    # layout: fold batch and heads into the leading grid axis
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
    kf = k.transpose(0, 2, 1, 3).reshape(b * h, sk, d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * h, sk, d)

    sq_p = -(-sq // block_q) * block_q
    sk_p = -(-sk // block_k) * block_k
    qf = jnp.pad(qf, ((0, 0), (0, sq_p - sq), (0, 0)))
    kf = jnp.pad(kf, ((0, 0), (0, sk_p - sk), (0, 0)))
    vf = jnp.pad(vf, ((0, 0), (0, sk_p - sk), (0, 0)))
    nq, nk = sq_p // block_q, sk_p // block_k

    kern = functools.partial(
        _kernel, nk=nk, block_q=block_q, block_k=block_k, scale=scale,
        causal=causal, window=window, sq=sq, sk=sk)

    out = pl.pallas_call(
        kern,
        name="flash_attention",
        grid=(b * h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda g, i, j: (g, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda g, i, j: (g, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda g, i, j: (g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda g, i, j: (g, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, sq_p, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),   # running max
            pltpu.VMEM((block_q, 1), jnp.float32),   # running denom
            pltpu.VMEM((block_q, d), jnp.float32),   # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qf, kf, vf)
    return out[:, :sq].reshape(b, h, sq, d).transpose(0, 2, 1, 3)
