"""Pallas TPU kernel: implicit-im2col managed conv read.

The streamed conv forward (``core/conv_mapping.py``) reads im2col position
columns through the array.  The generic path gathers each chunk of columns
into HBM and launches the fused managed read; this kernel removes even that
per-chunk gather: each grid step pulls ONE image of the activation volume
into VMEM, assembles its patch tile on-chip from the ``kh*kw`` statically
unrolled strided tap slices (the patch matrix never exists in HBM at any
size), runs the contraction against the tap-major weight layout, and
finishes with the *shared* managed-read body from ``kernels/managed_mvm.py``
(``read_segment`` / ``select_and_average``) — NM scale, on-chip noise at the
reference counter layout, two-phase BM select and the #_d replica average.

Bit-compatibility: the noise counters are the global position rows
(``img * OH*OW + position``) times the physical output channel — exactly
what the reference pipeline and the fused ``managed_mvm`` kernel draw for
the materialized column matrix — so this kernel differs from them only by
matmul reassociation (the shared epilogue is the same code).  Parity is
pinned in ``tests/test_conv_stream.py``.

Layout notes: the weight matrix arrives in channel-major column order
(``c * kh*kw + t``); the wrapper re-arranges it once, digitally, to
tap-major rows (``t * C + c``) so each tap's slice lands contiguously in
the on-chip patch tile.  The bias column becomes the last tap-major row
with a constant-1 patch column.  The whole (replica-padded) physical output
dim lives in one block, like ``managed_mvm``; one image's positions form
the row block.  VMEM needs ``O(OH*OW * (C kh kw + out_phys))`` floats; the
wrapper sets its scoped-VMEM limit from the block shapes, and
``conv_kernel_eligible`` sends a layer whose working set exceeds the cap to
the gather + ``managed_mvm`` path (bit-compatible counters).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.managed_mvm import (EPILOGUE_TEMPS, conv_block_dims,
                                       fits_vmem, pad_to, read_segment,
                                       replica_cols, select_and_average,
                                       tile_bytes, vmem_limit)


def _conv_read_vmem(geom, d_avg: int, out_f: int) -> int:
    """Working-set bytes of one :func:`conv_managed_mvm_pallas` launch."""
    ppad, fp = conv_block_dims(geom)
    out_f_p = pad_to(out_f, 128)
    rows = tile_bytes(ppad, d_avg * out_f_p)
    return (2 * (tile_bytes(ppad, 1) + tile_bytes(geom.h, geom.w, geom.c)
                 + tile_bytes(fp, d_avg * out_f_p)      # nm, image, w
                 + tile_bytes(ppad, out_f_p) + tile_bytes(ppad, 1))  # y, sat
            + patch_vmem(geom) + (EPILOGUE_TEMPS + 1) * rows)


def patch_vmem(geom) -> int:
    """Bytes of the on-chip patch assembly (:func:`assemble_patch`): the
    ``kh*kw`` tap slices and the padded patch tile."""
    ppad, fp = conv_block_dims(geom)
    return (geom.kh * geom.kw * tile_bytes(geom.oh * geom.ow, geom.c)
            + tile_bytes(ppad, fp))


def conv_kernel_eligible(cfg, geom, w_shape: Tuple[int, int]) -> bool:
    """True when the implicit-im2col kernel can take the conv forward:
    pallas on, fixed-latency BM (off / two-phase), a single physical
    contraction segment, and the per-image working set within the VMEM
    cap the wrapper claims."""
    if not cfg.use_pallas or cfg.grid_routed or cfg.bm_iterative:
        return False                      # no pallas / per-shard / retry loop
    if geom.cols > cfg.max_array_cols:
        return False                      # would need contraction segments
    d_avg = cfg.devices_per_weight
    return fits_vmem(_conv_read_vmem(geom, d_avg, w_shape[0] // d_avg))


def assemble_patch(xb, geom, p_img: int, ppad: int, fp: int):
    """Implicit im2col: one image's on-chip patch tile, assembled from the
    ``kh*kw`` statically unrolled strided tap slices of the (H, W, C)
    activation block.  Tap-major column order (``t * C + c``, bias-ones
    last), zero-padded to ``(ppad, fp)`` — the single source of the
    in-VMEM patch layout, shared by the managed conv read and the fused
    conv backward+update kernels."""
    cols = []
    for ih in range(geom.kh):
        for iw in range(geom.kw):
            r0, c0 = ih * geom.dh, iw * geom.dw
            sl = jax.lax.slice(
                xb, (r0, c0, 0),
                (r0 + (geom.oh - 1) * geom.sh + 1,
                 c0 + (geom.ow - 1) * geom.sw + 1, geom.c),
                (geom.sh, geom.sw, 1))
            cols.append(sl.reshape(p_img, geom.c))
    if geom.bias:
        cols.append(jnp.ones((p_img, 1), xb.dtype))
    patch = jnp.concatenate(cols, axis=1)              # (P_img, ftm)
    ftm = patch.shape[1]
    return jnp.pad(patch, ((0, ppad - p_img), (0, fp - ftm)))


def _kernel(seeds_ref, nm_ref, x_ref, w_ref, y_ref, sat_ref, *,
            geom, p_img: int, ppad: int, ftm: int, fp: int, outp: int,
            out_f: int, out_f_p: int, d_avg: int, out_phys: int,
            total_rows: int, sigma: float, alpha: float, two_phase: bool,
            retry_scale: float):
    i = pl.program_id(0)
    patch = assemble_patch(x_ref[0], geom, p_img, ppad, fp)

    prod = jax.lax.dot_general(patch, w_ref[...], (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)

    s = nm_ref[...]                                    # (ppad, 1)
    v1 = prod / s
    o, valid = replica_cols(ppad, outp, out_f, out_f_p)
    rows = (i * np.uint32(p_img)
            + jax.lax.broadcasted_iota(jnp.uint32, (ppad, outp), 0))
    e = rows * np.uint32(out_phys) + o                 # n_seg == 1
    n_total = (total_rows * out_phys) & 0xFFFFFFFF

    acc1, sat1 = read_segment(v1, seeds_ref[0, 0], e, n_total, valid,
                              sigma, alpha)
    if two_phase:
        acc2, sat2 = read_segment(v1 / np.float32(retry_scale),
                                  seeds_ref[0, 1], e, n_total, valid,
                                  sigma, alpha)
    else:
        acc2, sat2 = acc1, sat1
    y, residual = select_and_average(
        acc1, acc2, sat1, sat2, s, two_phase=two_phase,
        retry_scale=retry_scale, d_avg=d_avg, out_f_p=out_f_p)
    y_ref[...] = y.astype(y_ref.dtype)
    sat_ref[...] = residual


def tap_major_weights(w: jax.Array, geom, d_avg: int, out_f_p: int
                      ) -> jax.Array:
    """Digitally re-arrange the (M_phys, C*kh*kw [+1]) channel-major
    parameter matrix to tap-major rows (``t * C + c`` [+ bias last]) with
    the replica-padded output layout on the columns."""
    m = w.shape[0]
    kk = geom.kh * geom.kw
    w_tm = w[:, :geom.features].reshape(m, geom.c, kk)
    w_tm = jnp.transpose(w_tm, (2, 1, 0)).reshape(kk * geom.c, m)
    if geom.bias:
        w_tm = jnp.concatenate([w_tm, w[:, geom.features:].T], axis=0)
    ftm = w_tm.shape[0]
    fp = pad_to(ftm, 128)
    out_f = m // d_avg
    w_tm = w_tm.reshape(ftm, d_avg, out_f)
    w_tm = jnp.pad(w_tm, ((0, fp - ftm), (0, 0), (0, out_f_p - out_f)))
    return w_tm.reshape(fp, d_avg * out_f_p)


@functools.partial(
    jax.jit,
    static_argnames=("geom", "sigma", "alpha", "two_phase", "retry_scale",
                     "d_avg", "interpret", "name"))
def conv_managed_mvm_pallas(w: jax.Array, xpad: jax.Array, nm_s: jax.Array,
                            seeds: jax.Array, *, geom, sigma: float,
                            alpha: float, two_phase: bool = False,
                            retry_scale: float = 16.0, d_avg: int = 1,
                            interpret: bool = False,
                            name: str = "managed_read_conv"
                            ) -> Tuple[jax.Array, jax.Array]:
    """Implicit-im2col fused managed conv read.

    Args:
      w: physical weights ``(d_avg * out_f, C*kh*kw [+1 bias])``.
      xpad: padded activation volume ``(B, H, W, C)``.
      nm_s: ``(B * OH * OW, 1)`` per-position digital scale.
      seeds: (2,) uint32 read seeds (same discipline as ``managed_mvm``).

    Returns ``(y, sat)``: ``(B*OH*OW, out_f)`` replica-averaged managed
    read and the per-position residual saturation ``(B*OH*OW,)``.
    """
    m, n_cols = w.shape
    assert n_cols == geom.cols, (w.shape, geom)
    out_phys = m
    out_f = m // d_avg
    p_img = geom.oh * geom.ow
    total = geom.b * p_img
    ppad, fp = conv_block_dims(geom)
    ftm = geom.features + (1 if geom.bias else 0)
    out_f_p = pad_to(out_f, 128)
    outp = d_avg * out_f_p

    w_tm = tap_major_weights(w, geom, d_avg, out_f_p)
    nm_pad = nm_s.astype(jnp.float32).reshape(geom.b, p_img, 1)
    nm_pad = jnp.pad(nm_pad, ((0, 0), (0, ppad - p_img), (0, 0)),
                     constant_values=1.0).reshape(geom.b * ppad, 1)

    kern = functools.partial(
        _kernel, geom=geom, p_img=p_img, ppad=ppad, ftm=ftm, fp=fp,
        outp=outp, out_f=out_f, out_f_p=out_f_p, d_avg=d_avg,
        out_phys=out_phys, total_rows=total, sigma=sigma, alpha=alpha,
        two_phase=two_phase, retry_scale=retry_scale)

    y, sat = pl.pallas_call(
        kern,
        name=name,
        grid=(geom.b,),
        in_specs=[
            pl.BlockSpec((1, 2), lambda i: (0, 0)),             # seeds
            pl.BlockSpec((ppad, 1), lambda i: (i, 0)),          # nm scale
            pl.BlockSpec((1, geom.h, geom.w, geom.c),
                         lambda i: (i, 0, 0, 0)),               # x image
            pl.BlockSpec((fp, outp), lambda i: (0, 0)),         # w tap-major
        ],
        out_specs=[
            pl.BlockSpec((ppad, out_f_p), lambda i: (i, 0)),    # y
            pl.BlockSpec((ppad, 1), lambda i: (i, 0)),          # residual
        ],
        out_shape=[
            jax.ShapeDtypeStruct((geom.b * ppad, out_f_p), xpad.dtype),
            jax.ShapeDtypeStruct((geom.b * ppad, 1), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=vmem_limit(
                _conv_read_vmem(geom, d_avg, out_f), name)),
        interpret=interpret,
    )(seeds.reshape(1, 2).astype(jnp.uint32), nm_pad, xpad, w_tm)
    y = y.reshape(geom.b, ppad, out_f_p)[:, :p_img, :out_f]
    sat = sat.reshape(geom.b, ppad)[:, :p_img]
    return y.reshape(total, out_f), sat.reshape(total) > 0
