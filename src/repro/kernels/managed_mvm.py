"""Pallas TPU kernel: fused *managed* analog MVM read.

One launch computes the whole managed read pipeline of
``core/management.py`` for the fixed-latency BM modes (off / two-phase):

    s   = s_nm                      (per-vector NM scale, digital, given)
    y1  = sum_seg clip(W_seg (x/s)_seg        + sigma * xi1, +-alpha)
    y2  = sum_seg clip(W_seg (x/(16 s))_seg   + sigma * xi2, +-alpha)
    y   = where(sat1, y2 * 16, y1) * s        (select-on-saturation)
    out = mean over the #_d replica row blocks of y   (digital average)

The unfused pipeline costs two full ``noisy_mvm`` launches plus the NM
scale / select / replica-average ops, each with an HBM round-trip of the
``(batch, out_phys)`` intermediates.  Here both reads share one launch and
one contraction pass: because the digital scale commutes with the matmul
(``W (x/s) = (W x)/s``), the kernel computes the raw segment product once in
VMEM and derives both reads from it — the 1/16 retry costs one extra VPU
scale + noise + clip, *zero* extra MXU work and zero extra HBM traffic.

Noise is generated on-chip from the same counter-hash (splitmix32 +
Box-Muller) as ``repro.utils.fastrng.normal`` with the reference pipeline's
counter layout, and the two reads consume the two seeds derived from the
reference's ``jax.random.split(key)`` — so the fused kernel is bit-compatible
in noise with ``core.tile.managed_mvm_reference`` and parity tests assert
allclose at matmul-reassociation tolerance only.

Layout: grid ``(batch/bm, K/bk)`` with the contraction axis innermost
("arbitrary"); the full (replica-padded) physical output dimension lives in
one VMEM block so the per-vector saturation flag — which gates the select
across *all* output channels — never leaves the chip.  Weights are padded
per replica block to a lane multiple so the in-kernel #_d average is a few
static slices.  The scoped-VMEM limit is set from those block shapes
(:func:`managed_read_vmem`); an output too wide for one core's VMEM (a
100k-row unembed) raises rather than compiles.  The iterative-BM
while_loop is inherently multi-launch (data-dependent retry count) and
keeps using ``noisy_mvm`` per read.
"""

from __future__ import annotations

import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.noisy_mvm import _mix, _normal_at


# ---------------------------------------------------------------------------
# VMEM sizing
#
# The kernels that hold a whole output (or count) dimension in one block
# compute their scoped-VMEM limit from their block shapes: the pipelined
# operand blocks (two buffers each), the scratch, and the live elementwise
# temporaries of the read epilogue, each rounded up to the (8, 128) tile
# Mosaic lays a 32-bit array out in.  The same estimate gates eligibility;
# ``tests/test_tpu_compile.py`` compiles each gate's largest admitted shape
# for v5e and pins those maxima, so a widened estimate needs a new case.
# ---------------------------------------------------------------------------

#: VMEM one launch may claim: a TPU v5e TensorCore has 128 MiB; the rest is
#: headroom for Mosaic's internal scratch.
VMEM_CAP = 100 * 2 ** 20
#: Mosaic's default scoped-VMEM limit on v5e — never ask for less.
_SCOPED_DEFAULT = 16 * 2 ** 20
#: live (rows, out) f32 temporaries of the noise + select epilogue (measured
#: by compiling for v5e: about three at 4096 and 11008 output columns)
EPILOGUE_TEMPS = 4
_SLACK = 2 * 2 ** 20


def pad_to(v: int, m: int) -> int:
    """``v`` rounded up to a multiple of ``m``."""
    return -(-v // m) * m


def tile_bytes(*shape: int) -> int:
    """Bytes of one 32-bit VMEM array of ``shape`` in (8, 128) tiles."""
    *lead, r, c = shape
    return 4 * math.prod(lead) * pad_to(r, 8) * pad_to(c, 128)


def conv_block_dims(geom) -> Tuple[int, int]:
    """``(ppad, fp)``: one image's output positions padded to a sublane
    multiple and its patch features (bias column included) padded to a
    lane multiple — the block layout of both implicit-im2col kernels."""
    return (pad_to(geom.oh * geom.ow, 8),
            pad_to(geom.features + (1 if geom.bias else 0), 128))


def fits_vmem(nbytes: int) -> bool:
    """Whether a launch with working set ``nbytes`` fits :data:`VMEM_CAP`
    (the eligibility gates' test)."""
    return nbytes + _SLACK <= VMEM_CAP


def vmem_limit(nbytes: int, name: str) -> int:
    """The scoped-VMEM limit for a launch whose working set is ``nbytes``.

    Raises when the working set does not fit :data:`VMEM_CAP`: a shape
    that cannot fit one core's VMEM fails here, by name, instead of in the
    compiler or on another path."""
    if not fits_vmem(nbytes):
        raise ValueError(
            f"{name}: block working set {nbytes / 2 ** 20:.1f} MiB exceeds "
            f"the {VMEM_CAP >> 20} MiB of VMEM a launch may claim")
    return max(nbytes + _SLACK, _SCOPED_DEFAULT)


def managed_read_vmem(bm: int, bk: int, outp: int, out_f_p: int) -> int:
    """Working-set bytes of one :func:`managed_mvm_pallas` launch.

    Compiled for v5e at 2048, 4096 and 11008 output columns, a launch holds
    10 (rows, out) f32 blocks with one row block and 12 with more (256
    rows and up): with one row block the output is written once and needs
    no buffer pair of its own.  The count here is 12 (the weight and output
    pairs, three accumulators, and an epilogue of one temporary more than
    :data:`EPILOGUE_TEMPS`), so it holds at every row count; at 11 it fell
    3.5 MiB short of the 65.1 MiB an 11008-column read from 256 rows
    needs."""
    return (2 * (tile_bytes(bm, bk) + tile_bytes(outp, bk)      # x, w
                 + tile_bytes(bm, 1)                            # nm scale
                 + tile_bytes(bm, out_f_p) + tile_bytes(bm, 1))  # y, sat
            + 3 * tile_bytes(bm, outp) + 2 * tile_bytes(bm, 1)  # scratch
            + (EPILOGUE_TEMPS + 1) * tile_bytes(bm, outp))      # epilogue


# ---------------------------------------------------------------------------
# Shared managed-read body
#
# These block-level helpers are the single source of the managed-read
# semantics for every fused kernel: this kernel's segment loop AND the
# implicit-im2col conv kernel (``kernels/conv_mvm.py``) call the same
# functions, which is what keeps the two bit-compatible (same noise
# counters, same clip/select/average expression order).
# ---------------------------------------------------------------------------

def replica_cols(bm: int, outp: int, out_f: int, out_f_p: int):
    """Physical output-channel index of each replica-padded column.

    Returns ``(o, valid)``: ``o`` maps padded column -> physical channel
    (for the noise counter), ``valid`` masks the per-replica lane padding
    out of the saturation reduction.
    """
    cols = jax.lax.broadcasted_iota(jnp.uint32, (bm, outp), 1)
    rep = cols // np.uint32(out_f_p)
    within = cols - rep * np.uint32(out_f_p)
    o = rep * np.uint32(out_f) + within
    valid = within < np.uint32(out_f)
    return o, valid


def read_segment(v, seed, e, n_total: int, valid, sigma: float,
                 alpha: float):
    """One physical read of a raw-product block: on-chip noise at counter
    ``e`` + per-vector saturation + integrator clip.

    Returns ``(v_read, sat)`` with ``sat`` an int32 ``(rows, 1)`` flag.
    """
    if sigma > 0.0:
        v = v + np.float32(sigma) * _normal_at(_mix(seed), e, n_total)
    if alpha != float("inf"):
        sat = jnp.any(valid & (jnp.abs(v) >= np.float32(alpha)),
                      axis=1, keepdims=True).astype(jnp.int32)
        v = jnp.clip(v, -np.float32(alpha), np.float32(alpha))
    else:
        sat = jnp.zeros((v.shape[0], 1), jnp.int32)
    return v, sat


def select_and_average(acc1, acc2, sat1, sat2, s, *, two_phase: bool,
                       retry_scale: float, d_avg: int, out_f_p: int):
    """Two-phase select-on-saturation, digital re-scale and #_d replica
    average — the managed read's epilogue.  Returns ``(y, residual)``."""
    if two_phase:
        sel = sat1 > 0                                      # (rows, 1)
        y2 = acc2 * np.float32(retry_scale)
        y = jnp.where(sel, y2, acc1) * s
        residual = sat1 & sat2
    else:
        y = acc1 * s
        residual = sat1
    if d_avg > 1:
        acc = y[:, 0:out_f_p]
        for rblk in range(1, d_avg):
            acc = acc + y[:, rblk * out_f_p:(rblk + 1) * out_f_p]
        y = acc / np.float32(d_avg)
    return y, residual


def _kernel(seeds_ref, off_ref, nm_ref, x_ref, w_ref, y_ref, sat_ref,
            seg_ref, acc1_ref, acc2_ref, sat1_ref, sat2_ref, *,
            nk: int, steps_per_seg: int, n_seg: int, sigma: float,
            alpha: float, bm: int, outp: int, out_f: int, out_f_p: int,
            d_avg: int, out_phys: int, batch: int, transpose: bool,
            two_phase: bool, retry_scale: float):
    i = pl.program_id(0)
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        seg_ref[...] = jnp.zeros_like(seg_ref)
        acc1_ref[...] = jnp.zeros_like(acc1_ref)
        acc2_ref[...] = jnp.zeros_like(acc2_ref)
        sat1_ref[...] = jnp.zeros_like(sat1_ref)
        sat2_ref[...] = jnp.zeros_like(sat2_ref)

    xb = x_ref[...]
    wb = w_ref[...]
    if transpose:
        # w block (bk, outp): contraction over physical rows
        seg_ref[...] += jax.lax.dot_general(
            xb, wb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
    else:
        # w block (outp, bk): contraction over physical columns
        seg_ref[...] += jax.lax.dot_general(
            xb, wb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when((k + 1) % steps_per_seg == 0)
    def _segment_boundary():
        si = (k // steps_per_seg).astype(jnp.uint32)
        s = nm_ref[...]                       # (bm, 1) combined digital scale
        v1 = seg_ref[...] / s                 # read 1: W (x / s)

        # physical column index of each padded column (replica-padded layout)
        o, valid = replica_cols(bm, outp, out_f, out_f_p)
        rows = (off_ref[0, 0] + i * bm
                + jax.lax.broadcasted_iota(jnp.uint32, (bm, outp), 0))
        # flat counter e = (b * n_seg + si) * out_phys + o  (reference layout)
        e = (rows * np.uint32(n_seg) + si) * np.uint32(out_phys) + o
        n_total = (batch * n_seg * out_phys) & 0xFFFFFFFF

        v_read, sat = read_segment(v1, seeds_ref[0, 0], e, n_total, valid,
                                   sigma, alpha)
        sat1_ref[...] |= sat
        acc1_ref[...] += v_read
        if two_phase:
            # read 2: W (x / (retry_scale * s)) — same MXU product, rescaled
            v_read, sat = read_segment(
                v1 / np.float32(retry_scale), seeds_ref[0, 1], e, n_total,
                valid, sigma, alpha)
            sat2_ref[...] |= sat
            acc2_ref[...] += v_read
        seg_ref[...] = jnp.zeros_like(seg_ref)

    @pl.when(k == nk - 1)
    def _finalize():
        y, residual = select_and_average(
            acc1_ref[...], acc2_ref[...], sat1_ref[...], sat2_ref[...],
            nm_ref[...], two_phase=two_phase, retry_scale=retry_scale,
            d_avg=d_avg, out_f_p=out_f_p)
        y_ref[...] = y.astype(y_ref.dtype)
        sat_ref[...] = residual


@functools.partial(
    jax.jit,
    static_argnames=("sigma", "alpha", "n_seg", "transpose", "two_phase",
                     "retry_scale", "d_avg", "total_rows", "bm", "bk",
                     "interpret", "name"))
def managed_mvm_pallas(w: jax.Array, x2d: jax.Array, nm_s: jax.Array,
                       seeds: jax.Array, *, sigma: float, alpha: float,
                       n_seg: int = 1, transpose: bool = False,
                       two_phase: bool = False, retry_scale: float = 16.0,
                       d_avg: int = 1, row_offset=None,
                       total_rows: int = None, bm: int = 128, bk: int = 128,
                       interpret: bool = False, name: str = "managed_read"
                       ) -> Tuple[jax.Array, jax.Array]:
    """Fused managed analog read (NM scale + two-phase BM + replica average).

    Args:
      w: physical weights (R, C); forward reads have R = d_avg * out_f.
      x2d: (B, C) inputs (or (B, R) when ``transpose``).
      nm_s: (B, 1) per-vector digital scale (NM scale; ones when NM is off).
      seeds: (2,) uint32 — read-1 / read-2 seeds (``fastrng.key_to_seed`` of
        the reference's ``jax.random.split(key)``; read 2 unused when
        ``two_phase`` is off).
      n_seg: physical-array segments along the contraction dim.
      two_phase: run the unconditional 1/16-scale retry + select.
      d_avg: #_d replica row blocks averaged into the output (forward only).
      row_offset/total_rows: streaming-chunk noise discipline — ``x2d`` is
        rows ``[row_offset, row_offset + B)`` of a logical batch of
        ``total_rows`` vectors and draws that batch's noise counters
        (``row_offset`` may be traced; ``total_rows`` is static).

    Returns:
      y (B, out_f) replica-averaged managed read, and residual saturation
      (B,) bool — True where management could not recover an unclipped read
      (``sat1 & sat2`` in two-phase mode, raw saturation otherwise).
    """
    r, c = w.shape
    if transpose:
        assert d_avg == 1, "replica average is a forward-read operation"
        out_phys, k_dim = c, r
    else:
        out_phys, k_dim = r, c
    assert out_phys % d_avg == 0, (out_phys, d_avg)
    out_f = out_phys // d_avg
    b = x2d.shape[0]
    assert x2d.shape[1] == k_dim, (x2d.shape, w.shape, transpose)
    if total_rows is None:
        total_rows = b
    rowoff = (jnp.zeros((), jnp.uint32) if row_offset is None
              else jnp.asarray(row_offset, jnp.uint32))

    out_f_p = pad_to(out_f, 128)              # per-replica lane-padded width
    outp = d_avg * out_f_p
    seg_len = -(-k_dim // n_seg)
    seg_len_p = pad_to(seg_len, bk)
    kp = n_seg * seg_len_p
    bp = pad_to(b, bm)

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

    def pad_out_replicated(a, axis):
        """Pad the physical out dim to out_f_p *per replica block*."""
        shp = list(a.shape)
        shp[axis:axis + 1] = [d_avg, out_f]
        a = a.reshape(shp)
        pad = [(0, 0)] * a.ndim
        pad[axis + 1] = (0, out_f_p - out_f)
        a = jnp.pad(a, pad)
        shp2 = list(a.shape)
        shp2[axis:axis + 2] = [outp]
        return a.reshape(shp2)

    xpad = pad_contraction(jnp.pad(x2d, ((0, bp - b), (0, 0))), 1)
    nm_pad = jnp.pad(nm_s.astype(jnp.float32), ((0, bp - b), (0, 0)),
                     constant_values=1.0)
    if transpose:
        wpad = pad_contraction(pad_out_replicated(w, 1), 0)
        w_spec = pl.BlockSpec((bk, outp), lambda i, k: (k, 0))
    else:
        wpad = pad_contraction(pad_out_replicated(w, 0), 1)
        w_spec = pl.BlockSpec((outp, bk), lambda i, k: (0, k))

    nb, nk = bp // bm, kp // bk
    steps_per_seg = seg_len_p // bk

    kern = functools.partial(
        _kernel, nk=nk, steps_per_seg=steps_per_seg, n_seg=n_seg,
        sigma=sigma, alpha=alpha, bm=bm, outp=outp, out_f=out_f,
        out_f_p=out_f_p, d_avg=d_avg, out_phys=out_phys, batch=total_rows,
        transpose=transpose, two_phase=two_phase, retry_scale=retry_scale)

    y, sat = pl.pallas_call(
        kern,
        name=name,
        grid=(nb, nk),
        in_specs=[
            pl.BlockSpec((1, 2), lambda i, k: (0, 0)),      # seeds
            pl.BlockSpec((1, 1), lambda i, k: (0, 0)),      # row offset
            pl.BlockSpec((bm, 1), lambda i, k: (i, 0)),     # nm scale
            pl.BlockSpec((bm, bk), lambda i, k: (i, k)),    # x
            w_spec,                                         # w
        ],
        out_specs=[
            pl.BlockSpec((bm, out_f_p), lambda i, k: (i, 0)),  # y (averaged)
            pl.BlockSpec((bm, 1), lambda i, k: (i, 0)),        # residual sat
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bp, out_f_p), x2d.dtype),
            jax.ShapeDtypeStruct((bp, 1), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bm, outp), jnp.float32),   # segment accumulator
            pltpu.VMEM((bm, outp), jnp.float32),   # read-1 accumulator
            pltpu.VMEM((bm, outp), jnp.float32),   # read-2 accumulator
            pltpu.VMEM((bm, 1), jnp.int32),        # read-1 saturation
            pltpu.VMEM((bm, 1), jnp.int32),        # read-2 saturation
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=vmem_limit(
                managed_read_vmem(bm, bk, outp, out_f_p), name)),
        interpret=interpret,
    )(seeds.reshape(1, 2).astype(jnp.uint32), rowoff.reshape(1, 1), nm_pad,
      xpad, wpad)
    return y[:b, :out_f], sat[:b, 0] > 0
