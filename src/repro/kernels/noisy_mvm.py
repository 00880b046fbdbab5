"""Pallas TPU kernel: fused analog MVM read.

Computes ``y = sum_seg clip(W_seg x_seg + sigma * xi, +-alpha)`` — the
physical RPU array read with *per-physical-array* noise injection and
integrator clipping, including contraction-dim array splits (weights larger
than the 4096x4096 physical array: each segment is an independent physical
read whose noise/bound apply *before* the digital summation).

Fusing matters: the unfused XLA graph materialises the per-segment partials
``(batch, s, out)`` plus a same-shaped noise tensor in HBM; the kernel keeps
the segment accumulator, the Gaussian noise (generated on-chip from a
counter hash — splitmix32 + Box-Muller, exactly matching
``repro.utils.fastrng.normal``) and the clip in VMEM, so HBM traffic drops to
the roofline minimum (read W once, read X once, write Y once).

Tiling: ``(bm, bn, bk) = (128, 128, 128)`` MXU-aligned blocks; grid =
(batch/bm, out/bn, K/bk) with the contraction axis innermost, VMEM
accumulators revisited across k.

The saturation flag needed by bound management is emitted as a per
(row-block, out-block) int32 map, OR-reduced by the ``ops.py`` wrapper.  The
kernel writes it lane-dense — one ``(bm, 128)`` block per out-block, the flag
broadcast across the lanes — because a ``(bm, 1)`` block of a wider array is
not aligned to the TPU tiling; the wrapper keeps lane 0.

Bit-exactness: with the same key, this kernel and
``repro.core.tile.analog_mvm_reference`` draw *identical* noise (same
counter layout), so tests assert allclose at matmul-reassociation tolerance.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128                 # TPU lane width (last-dim tile)
_GOLDEN = np.uint32(0x9E3779B9)
_M1 = np.uint32(0x21F0AAAD)
_M2 = np.uint32(0x735A2D97)


def _mix(x):
    x = (x + _GOLDEN).astype(jnp.uint32)
    x = (x ^ (x >> 16)) * _M1
    x = (x ^ (x >> 15)) * _M2
    return x ^ (x >> 15)


def _uniform24(bits):
    # via int32: Mosaic has no uint32 -> float32 cast, and ``bits >> 8`` is
    # below 2^24, so the detour is exact (bit-identical to fastrng.uniform)
    return ((bits >> 8).astype(jnp.int32).astype(jnp.float32)
            * np.float32(1.0 / (1 << 24)))


def _normal_at(seed_mixed, e, n_total):
    """Standard normal at flat counter ``e`` — fastrng.normal-compatible."""
    u1 = jnp.maximum(_uniform24(_mix(e ^ seed_mixed)), 1e-7)
    u2 = _uniform24(_mix((e + np.uint32(n_total)).astype(jnp.uint32)
                         ^ seed_mixed))
    return jnp.sqrt(-2.0 * jnp.log(u1)) * jnp.cos(np.float32(2.0 * np.pi) * u2)


def _kernel(seed_ref, off_ref, x_ref, w_ref, y_ref, sat_ref, seg_ref,
            acc_ref, satacc_ref, *, nk: int, steps_per_seg: int, n_seg: int,
            sigma: float, alpha: float, bm: int, bn: int, out_dim: int,
            batch: int, transpose: bool):
    i = pl.program_id(0)
    j = pl.program_id(1)
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        seg_ref[...] = jnp.zeros_like(seg_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        satacc_ref[...] = jnp.zeros_like(satacc_ref)

    xb = x_ref[...]
    wb = w_ref[...]
    if transpose:
        # w block (bk, bn): contraction over physical rows
        seg_ref[...] += jax.lax.dot_general(
            xb, wb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
    else:
        # w block (bn, bk): contraction over physical columns
        seg_ref[...] += jax.lax.dot_general(
            xb, wb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when((k + 1) % steps_per_seg == 0)
    def _segment_boundary():
        si = k // steps_per_seg
        v = seg_ref[...]
        if sigma > 0.0:
            # flat counter e = (b * n_seg + si) * out_dim + r  (ref layout);
            # off_ref carries the streaming-chunk row offset (global row of
            # this call's first batch row — 0 for unchunked reads)
            rows = (off_ref[0, 0] + i * bm
                    + jax.lax.broadcasted_iota(jnp.uint32, (bm, bn), 0))
            cols = (j * bn
                    + jax.lax.broadcasted_iota(jnp.uint32, (bm, bn), 1))
            e = ((rows * np.uint32(n_seg) + si.astype(jnp.uint32))
                 * np.uint32(out_dim) + cols)
            xi = _normal_at(_mix(seed_ref[0, 0]), e,
                            batch * n_seg * out_dim)
            v = v + np.float32(sigma) * xi
        if alpha != float("inf"):
            satacc_ref[...] |= jnp.any(
                jnp.abs(v) >= np.float32(alpha), axis=1, keepdims=True
            ).astype(jnp.int32)
            v = jnp.clip(v, -np.float32(alpha), np.float32(alpha))
        acc_ref[...] += v
        seg_ref[...] = jnp.zeros_like(seg_ref)

    @pl.when(k == nk - 1)
    def _finalize():
        y_ref[...] = acc_ref[...].astype(y_ref.dtype)
        sat_ref[...] = jnp.broadcast_to(satacc_ref[...], sat_ref.shape)


@functools.partial(
    jax.jit,
    static_argnames=("sigma", "alpha", "n_seg", "transpose", "total_rows",
                     "bm", "bn", "bk", "interpret", "name"))
def noisy_mvm_pallas(w: jax.Array, x2d: jax.Array, seed: jax.Array, *,
                     sigma: float, alpha: float, n_seg: int = 1,
                     transpose: bool = False, row_offset=None,
                     total_rows: int = None, bm: int = 128, bn: int = 128,
                     bk: int = 128, interpret: bool = False,
                     name: str = "noisy_read"
                     ) -> Tuple[jax.Array, jax.Array]:
    """Fused noisy/bounded MVM.

    Args:
      w: physical weights (R, C).
      x2d: (B, C) inputs (or (B, R) when ``transpose``).
      seed: uint32 scalar (from ``fastrng.key_to_seed``).
      n_seg: physical-array segments along the contraction dim.
      row_offset/total_rows: streaming-chunk noise discipline — ``x2d`` is
        rows ``[row_offset, row_offset + B)`` of a logical batch of
        ``total_rows`` vectors and draws that batch's noise counters
        (``row_offset`` may be traced; ``total_rows`` is static).

    Returns:
      y (B, out_dim) and saturation flags (B, n_out_blocks) int32 (any
      channel in that block clipped for that input row).
    """
    r, c = w.shape
    out_dim = r if not transpose else c
    k_dim = c if not transpose else r
    b = x2d.shape[0]
    assert x2d.shape[1] == k_dim, (x2d.shape, w.shape, transpose)
    if total_rows is None:
        total_rows = b
    rowoff = (jnp.zeros((), jnp.uint32) if row_offset is None
              else jnp.asarray(row_offset, jnp.uint32))

    # pad batch to bm, out to bn, each contraction segment to a bk multiple
    seg_len = -(-k_dim // n_seg)
    seg_len_p = -(-seg_len // bk) * bk
    kp = n_seg * seg_len_p
    bp = -(-b // bm) * bm
    outp = -(-out_dim // bn) * bn

    def pad_contraction(a, axis):
        pad_tail = [(0, 0)] * a.ndim
        pad_tail[axis] = (0, n_seg * seg_len - a.shape[axis])
        a = jnp.pad(a, pad_tail)
        shp = list(a.shape)
        shp[axis:axis + 1] = [n_seg, seg_len]
        a = a.reshape(shp)
        pad_seg = [(0, 0)] * a.ndim
        pad_seg[axis + 1] = (0, seg_len_p - seg_len)
        a = jnp.pad(a, pad_seg)
        shp2 = list(a.shape)
        shp2[axis:axis + 2] = [kp]
        return a.reshape(shp2)

    xpad = pad_contraction(jnp.pad(x2d, ((0, bp - b), (0, 0))), 1)
    if transpose:
        wpad = pad_contraction(jnp.pad(w, ((0, 0), (0, outp - c))), 0)
        w_spec = pl.BlockSpec((bk, bn), lambda i, j, k: (k, j))
    else:
        wpad = pad_contraction(jnp.pad(w, ((0, outp - r), (0, 0))), 1)
        w_spec = pl.BlockSpec((bn, bk), lambda i, j, k: (j, k))

    nb, no, nk = bp // bm, outp // bn, kp // bk
    steps_per_seg = seg_len_p // bk

    kern = functools.partial(
        _kernel, nk=nk, steps_per_seg=steps_per_seg, n_seg=n_seg,
        sigma=sigma, alpha=alpha, bm=bm, bn=bn, out_dim=out_dim,
        batch=total_rows, transpose=transpose)

    y, sat = pl.pallas_call(
        kern,
        name=name,
        grid=(nb, no, nk),
        in_specs=[
            pl.BlockSpec((1, 1), lambda i, j, k: (0, 0)),       # seed
            pl.BlockSpec((1, 1), lambda i, j, k: (0, 0)),       # row offset
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),     # x
            w_spec,                                             # w
        ],
        out_specs=[
            pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),     # y
            pl.BlockSpec((bm, _LANES), lambda i, j, k: (i, j)),  # sat
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bp, outp), x2d.dtype),
            jax.ShapeDtypeStruct((bp, no * _LANES), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bm, bn), jnp.float32),   # segment accumulator
            pltpu.VMEM((bm, bn), jnp.float32),   # output accumulator
            pltpu.VMEM((bm, 1), jnp.int32),      # saturation accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(seed.reshape(1, 1).astype(jnp.uint32), rowoff.reshape(1, 1), xpad,
      wpad)
    return y[:b, :out_dim], sat.reshape(bp, no, _LANES)[:b, :, 0]
