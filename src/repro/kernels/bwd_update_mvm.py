"""Pallas TPU kernel: fused backward+update for one analog dense layer.

ONE launch runs the last two of the three RPU backprop cycles:

* **transpose (backward) read** — the managed read of
  ``kernels/managed_mvm.py`` restricted to a single contraction segment,
  reusing the *same* shared body (``read_segment`` / ``select_and_average``)
  with the same blocking (``bm = bk = 128``), padding and counter layout,
  so ``z = f_mgmt(W^T delta)`` is bit-identical to the separate
  ``managed_mvm_pallas(transpose=True)`` launch;
* **stochastic-pulse update** — the signed pulse streams of
  ``core/update.py`` are generated *inside VMEM* from the counter-offset
  fastrng hash (never in HBM at any batch size) and contracted on the MXU
  into the up/down coincidence counts, one ``bm``-row round per grid step —
  the in-register analogue of the ``update_chunk`` streaming rounds, whose
  bit-exactness PR 4 established: counts are integer-valued in f32, so any
  accumulation blocking reproduces the unchunked contraction exactly.

The kernel emits the raw integer counts; the caller finishes the cycle
with the *shared* ``update.finalize_counts`` (device maps + cycle-to-cycle
noise + per-device bound clip), which is what keeps the fused cycle
bit-identical to every separate-launch update path (reference / pallas x
chunked / unchunked) — only the shared finalize touches inexact arithmetic.

Counter disciplines (all identical to the separate launches):

* read noise at ``e = row * out_phys + col`` (``n_seg == 1``) from the
  two seeds of the backward-read key;
* A-streams (columns, from the activations) at
  ``e = ((row_offset + row) * BL + slot) * n_cols + col`` from ``k_a``;
* B-streams (rows, from the negated replicated error) at
  ``e = ((row_offset + row) * BL + slot) * m_phys + row_drv`` from ``k_b``.

``row_offset`` is the streaming-chunk counter shift of
``update.sample_signed_streams(..., row_offset=...)``: a launch over rows
``[r0, r0 + B)`` of a larger logical update batch (e.g. one timestep chunk
of a recurrent sequence, rows flattened timestep-major) draws exactly the
row slice of the single-shot stream, so per-chunk counts accumulate to the
unchunked contraction bit-for-bit.  It rides in the third word of the
update-seed operand (a traced u32 — chunk loops derive it from the loop
index).

The count matrices live in VMEM scratch for the whole grid
(``(kp, n_p)`` f32 x2).  Each wrapper sets its scoped-VMEM limit from its
block shapes, and the eligibility gates (``bwd_update_eligible``) admit a
tile only when that working set fits the cap; callers take the separate
launches for a larger tile — the fallback is the bit-exactness oracle, not a
different numeric path.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.conv_mvm import assemble_patch, patch_vmem
from repro.kernels.managed_mvm import (EPILOGUE_TEMPS, conv_block_dims,
                                       fits_vmem, pad_to, read_segment,
                                       replica_cols, select_and_average,
                                       tile_bytes, vmem_limit)
from repro.kernels.noisy_mvm import _mix, _uniform24


def _dense_vmem(bm: int, bk: int, kp: int, n_p: int) -> int:
    """Working-set bytes of one :func:`bwd_update_mvm_pallas` launch; the
    dominating term is the whole-tile ``(kp, n_p)`` count blocks."""
    counts = tile_bytes(kp, n_p)
    row = tile_bytes(bm, n_p)
    return (2 * (tile_bytes(bm, 1) + tile_bytes(bm, bk)   # nm, delta
                 + 2 * row + tile_bytes(bk, n_p)          # x, z, w
                 + tile_bytes(bm, 1) + 2 * counts)        # sat, up, dn
            + 2 * counts + 3 * row + 2 * tile_bytes(bm, 1)  # scratch
            + (EPILOGUE_TEMPS + 3) * row                  # read, A streams
            + 2 * tile_bytes(bk, n_p))                    # per-step counts


def _config_fuses(cfg) -> bool:
    """The config part of both gates: fusion requested, pallas on,
    counter-offset RNG, no sharded tile grid (grid cycles shard per
    sub-tile) and no iterative BM (multi-launch by nature)."""
    return (cfg.fuse_bwd_update and cfg.use_pallas and cfg.fast_rng
            and not cfg.grid_routed and not cfg.bm_iterative)


def bwd_update_eligible(cfg, w_shape: Tuple[int, int],
                        bm: int = 128, bk: int = 128) -> bool:
    """True when the fused backward+update kernel can take a dense layer's
    backward pass: fusion requested, pallas on, fixed-latency BM, single
    transpose-read segment, no sharded tile grid, counter-offset RNG, and
    the launch's working set within the VMEM cap its wrapper claims."""
    if not _config_fuses(cfg):
        return False
    m_phys, n_cols = w_shape
    if m_phys > cfg.max_array_rows:
        return False                      # transpose read would segment
    return fits_vmem(_dense_vmem(bm, bk, pad_to(m_phys, bk),
                                 pad_to(n_cols, 128)))


def _signed_stream(u, p, sgn):
    """One pulse slot: fire with probability ``p``, polarity ``sgn``."""
    return jnp.where(u < p, sgn, jnp.zeros_like(sgn))


def _kernel(rseeds_ref, useeds_ref, gains_ref, nm_ref, d_ref, x_ref, w_ref,
            y_ref, sat_ref, up_ref, dn_ref,
            seg_ref, acc1_ref, acc2_ref, sat1_ref, sat2_ref,
            net_ref, tot_ref, *,
            nb: int, nk: int, sigma: float, alpha: float, bm: int, bk: int,
            n_out: int, n_p: int, m_phys: int, batch: int, bl: int,
            two_phase: bool, retry_scale: float):
    i = pl.program_id(0)
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init_read():
        seg_ref[...] = jnp.zeros_like(seg_ref)
        acc1_ref[...] = jnp.zeros_like(acc1_ref)
        acc2_ref[...] = jnp.zeros_like(acc2_ref)
        sat1_ref[...] = jnp.zeros_like(sat1_ref)
        sat2_ref[...] = jnp.zeros_like(sat2_ref)

    @pl.when((i == 0) & (k == 0))
    def _init_counts():
        net_ref[...] = jnp.zeros_like(net_ref)
        tot_ref[...] = jnp.zeros_like(tot_ref)

    db = d_ref[...]                       # (bm, bk) replicated error block
    wb = w_ref[...]                       # (bk, n_p) transpose-read weights
    # --- backward-read contraction: same block order as managed_mvm ---------
    seg_ref[...] += jax.lax.dot_general(
        db, wb, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    # --- update cycle: in-VMEM signed streams, one bm-row round per step ----
    xb = x_ref[...]                       # (bm, n_p) activation columns
    cx = gains_ref[0, 0]
    cd = gains_ref[0, 1]
    du = -db                              # update drives -delta (descent)
    p_a = jnp.clip(jnp.abs(cx * xb), 0.0, 1.0)
    sgn_a = jnp.sign(xb)
    p_b = jnp.clip(jnp.abs(cd * du), 0.0, 1.0)
    sgn_b = jnp.sign(du)

    row0 = useeds_ref[0, 2]               # streaming-chunk counter shift
    rows_a = (row0
              + (i * bm
                 + jax.lax.broadcasted_iota(jnp.uint32, (bm, n_p), 0)))
    cols_a = jax.lax.broadcasted_iota(jnp.uint32, (bm, n_p), 1)
    rows_b = (row0
              + (i * bm
                 + jax.lax.broadcasted_iota(jnp.uint32, (bm, bk), 0)))
    cols_b = (k * bk
              + jax.lax.broadcasted_iota(jnp.uint32, (bm, bk), 1))
    seed_a = _mix(useeds_ref[0, 0])
    seed_b = _mix(useeds_ref[0, 1])

    net = jnp.zeros((bk, n_p), jnp.float32)
    tot = jnp.zeros((bk, n_p), jnp.float32)
    for slot in range(bl):                # static BL-slot loop, in-register
        e_a = ((rows_a * np.uint32(bl) + np.uint32(slot))
               * np.uint32(n_out & 0xFFFFFFFF) + cols_a)
        a_s = _signed_stream(_uniform24(_mix(e_a ^ seed_a)), p_a, sgn_a)
        e_b = ((rows_b * np.uint32(bl) + np.uint32(slot))
               * np.uint32(m_phys & 0xFFFFFFFF) + cols_b)
        b_s = _signed_stream(_uniform24(_mix(e_b ^ seed_b)), p_b, sgn_b)
        net += jax.lax.dot_general(
            b_s, a_s, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        tot += jax.lax.dot_general(
            jnp.abs(b_s), jnp.abs(a_s), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    idx = (pl.ds(pl.multiple_of(k * bk, bk), bk), slice(None))
    net_ref[idx] = net_ref[idx] + net
    tot_ref[idx] = tot_ref[idx] + tot

    # --- managed-read epilogue (shared body; n_seg == 1 => one boundary) ----
    @pl.when(k == nk - 1)
    def _read_boundary():
        s = nm_ref[...]                   # (bm, 1) per-vector digital scale
        v1 = seg_ref[...] / s
        o, valid = replica_cols(bm, n_p, n_out, n_p)
        rows = (i * bm
                + jax.lax.broadcasted_iota(jnp.uint32, (bm, n_p), 0))
        e = rows * np.uint32(n_out & 0xFFFFFFFF) + o
        n_total = (batch * n_out) & 0xFFFFFFFF

        v_read, sat = read_segment(v1, rseeds_ref[0, 0], e, n_total, valid,
                                   sigma, alpha)
        sat1_ref[...] |= sat
        acc1_ref[...] += v_read
        if two_phase:
            v_read, sat = read_segment(
                v1 / np.float32(retry_scale), rseeds_ref[0, 1], e, n_total,
                valid, sigma, alpha)
            sat2_ref[...] |= sat
            acc2_ref[...] += v_read

    @pl.when(k == nk - 1)
    def _finalize_read():
        y, residual = select_and_average(
            acc1_ref[...], acc2_ref[...], sat1_ref[...], sat2_ref[...],
            nm_ref[...], two_phase=two_phase, retry_scale=retry_scale,
            d_avg=1, out_f_p=n_p)
        y_ref[...] = y.astype(y_ref.dtype)
        sat_ref[...] = residual

    @pl.when((i == nb - 1) & (k == nk - 1))
    def _emit_counts():
        net_all = net_ref[...]
        tot_all = tot_ref[...]
        up_ref[...] = 0.5 * (tot_all + net_all)
        dn_ref[...] = 0.5 * (tot_all - net_all)


@functools.partial(
    jax.jit,
    static_argnames=("sigma", "alpha", "two_phase", "retry_scale", "bl",
                     "bm", "bk", "interpret", "name"))
def bwd_update_mvm_pallas(w: jax.Array, d2d: jax.Array, x2d: jax.Array,
                          nm_s: jax.Array, read_seeds: jax.Array,
                          upd_seeds: jax.Array, gains: jax.Array, *,
                          sigma: float, alpha: float, two_phase: bool,
                          retry_scale: float = 16.0, bl: int = 10,
                          bm: int = 128, bk: int = 128,
                          interpret: bool = False, name: str = "bwd_update"
                          ) -> Tuple[jax.Array, jax.Array, jax.Array,
                                     jax.Array]:
    """Fused backward+update launch for one dense analog tile.

    Args:
      w: physical weights ``(m_phys, n_cols)`` (rows already #_d-replicated).
      d2d: ``(B, m_phys)`` *replicated* error vectors (the transpose-read
        input and, negated, the update's row drivers).
      x2d: ``(B, n_cols)`` activation columns (the update's column drivers).
      nm_s: ``(B, 1)`` per-vector digital NM scale of ``d2d`` (ones when NM
        is off).
      read_seeds: (2,) uint32 backward-read seeds (``managed_mvm``'s
        discipline: split-of-``k_b`` when two-phase, else the same seed
        twice).
      upd_seeds: (3,) uint32 — A-stream (``k_a``) and B-stream (``k_b``)
        seeds from the update key's 3-way split (``k_c`` stays with the
        caller for ``update.finalize_counts``), plus the streaming-chunk
        ``row_offset`` counter shift (0 for a single-shot update batch;
        may be traced).
      gains: (2,) f32 — ``(C_x, C_d)`` pulse gains from ``um_factors``.

    Returns ``(z, residual_sat, count_up, count_dn)``: the managed transpose
    read ``(B, n_cols)`` on *physical* columns (the caller divides by #_d),
    its residual saturation ``(B,)``, and the integer coincidence counts
    ``(m_phys, n_cols)`` ready for ``update.finalize_counts``.
    """
    m_phys, n_cols = w.shape
    b = d2d.shape[0]
    assert d2d.shape[1] == m_phys, (d2d.shape, w.shape)
    assert x2d.shape == (b, n_cols), (x2d.shape, w.shape)

    n_p = pad_to(n_cols, 128)
    kp = pad_to(m_phys, bk)
    bp = pad_to(b, bm)
    nb, nk = bp // bm, kp // bk

    wpad = jnp.pad(w, ((0, kp - m_phys), (0, n_p - n_cols)))
    dpad = jnp.pad(d2d, ((0, bp - b), (0, kp - m_phys)))
    xpad = jnp.pad(x2d, ((0, bp - b), (0, n_p - n_cols)))
    nm_pad = jnp.pad(nm_s.astype(jnp.float32), ((0, bp - b), (0, 0)),
                     constant_values=1.0)

    kern = functools.partial(
        _kernel, nb=nb, nk=nk, sigma=sigma, alpha=alpha, bm=bm, bk=bk,
        n_out=n_cols, n_p=n_p, m_phys=m_phys, batch=b, bl=bl,
        two_phase=two_phase, retry_scale=retry_scale)

    z, sat, up, dn = pl.pallas_call(
        kern,
        name=name,
        grid=(nb, nk),
        in_specs=[
            pl.BlockSpec((1, 2), lambda i, k: (0, 0)),      # read seeds
            pl.BlockSpec((1, 3), lambda i, k: (0, 0)),      # upd seeds+off
            pl.BlockSpec((1, 2), lambda i, k: (0, 0)),      # (cx, cd)
            pl.BlockSpec((bm, 1), lambda i, k: (i, 0)),     # nm scale
            pl.BlockSpec((bm, bk), lambda i, k: (i, k)),    # delta (read+B)
            pl.BlockSpec((bm, n_p), lambda i, k: (i, 0)),   # x (A streams)
            pl.BlockSpec((bk, n_p), lambda i, k: (k, 0)),   # w (transpose)
        ],
        out_specs=[
            pl.BlockSpec((bm, n_p), lambda i, k: (i, 0)),   # z
            pl.BlockSpec((bm, 1), lambda i, k: (i, 0)),     # residual sat
            pl.BlockSpec((kp, n_p), lambda i, k: (0, 0)),   # count_up
            pl.BlockSpec((kp, n_p), lambda i, k: (0, 0)),   # count_dn
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bp, n_p), d2d.dtype),
            jax.ShapeDtypeStruct((bp, 1), jnp.int32),
            jax.ShapeDtypeStruct((kp, n_p), jnp.float32),
            jax.ShapeDtypeStruct((kp, n_p), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bm, n_p), jnp.float32),    # segment accumulator
            pltpu.VMEM((bm, n_p), jnp.float32),    # read-1 accumulator
            pltpu.VMEM((bm, n_p), jnp.float32),    # read-2 accumulator
            pltpu.VMEM((bm, 1), jnp.int32),        # read-1 saturation
            pltpu.VMEM((bm, 1), jnp.int32),        # read-2 saturation
            pltpu.VMEM((kp, n_p), jnp.float32),    # net coincidence counts
            pltpu.VMEM((kp, n_p), jnp.float32),    # total coincidence counts
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem_limit(_dense_vmem(bm, bk, kp, n_p), name)),
        interpret=interpret,
    )(read_seeds.reshape(1, 2).astype(jnp.uint32),
      upd_seeds.reshape(1, 3).astype(jnp.uint32),
      gains.reshape(1, 2).astype(jnp.float32), nm_pad, dpad, xpad, wpad)
    return (z[:b, :n_cols], sat[:b, 0] > 0,
            up[:m_phys, :n_cols], dn[:m_phys, :n_cols])


# ---------------------------------------------------------------------------
# Conv variant: implicit-im2col fused backward+update
# ---------------------------------------------------------------------------

def conv_bwd_update_eligible(cfg, geom, w_shape: Tuple[int, int],
                             bk: int = 128) -> bool:
    """True when the fused conv backward+update kernel can take a streamed
    conv layer's backward pass — the conv analogue of
    :func:`bwd_update_eligible` (per-image working set, patch tile and
    both count blocks within the VMEM cap its wrapper claims)."""
    if not _config_fuses(cfg):
        return False
    m_phys, n_cols = w_shape
    if m_phys > cfg.max_array_rows:
        return False                      # transpose read would segment
    return fits_vmem(_conv_vmem(geom, m_phys, n_cols, bk))


def _conv_vmem(geom, m_phys: int, n_cols: int, bk: int) -> int:
    """Working-set bytes of one :func:`conv_bwd_update_pallas` launch."""
    ppad, fp = conv_block_dims(geom)
    kp = pad_to(m_phys, bk)
    np_c = pad_to(n_cols, 128)
    counts = tile_bytes(kp, fp)
    rows_out = tile_bytes(ppad, np_c)
    rows_in = tile_bytes(ppad, kp)
    return (2 * (2 * tile_bytes(ppad, 1) + rows_in      # nm, sat, delta
                 + tile_bytes(geom.h, geom.w, geom.c)   # image
                 + tile_bytes(kp, np_c)                 # w
                 + rows_out + 2 * counts)               # z, up, dn
            + 2 * counts                                # scratch
            + (EPILOGUE_TEMPS + 1) * rows_out           # read, seg
            + patch_vmem(geom) + 3 * tile_bytes(ppad, fp)  # A streams
            + 3 * rows_in + 2 * counts)                 # B streams, counts


def _tap_to_channel_perm(geom) -> np.ndarray:
    """Column permutation taking tap-major counts (``t * C + c``, bias
    last) to the channel-major layout of the parameter matrix
    (``c * kh*kw + t``, bias last) — an exact gather of integer counts."""
    kk = geom.kh * geom.kw
    perm = np.empty(geom.cols, np.int32)
    for j in range(geom.c * kk):          # channel-major index
        c, t = divmod(j, kk)
        perm[j] = t * geom.c + c          # its tap-major position
    if geom.bias:
        perm[geom.c * kk] = geom.c * kk
    return perm


def _conv_kernel(rseeds_ref, useeds_ref, gains_ref, nm_ref, d_ref, x_ref,
                 w_ref, y_ref, sat_ref, up_ref, dn_ref, net_ref, tot_ref, *,
                 geom, p_img: int, ppad: int, fp: int, kp: int, np_c: int,
                 m_phys: int, n_cols: int, total: int, bl: int, bk: int,
                 sigma: float, alpha: float, two_phase: bool,
                 retry_scale: float):
    i = pl.program_id(0)
    nb = pl.num_programs(0)

    @pl.when(i == 0)
    def _init_counts():
        net_ref[...] = jnp.zeros_like(net_ref)
        tot_ref[...] = jnp.zeros_like(tot_ref)

    db = d_ref[...]                       # (ppad, kp) replicated error rows
    wb = w_ref[...]                       # (kp, np_c) channel-major weights
    # --- transpose read: same bk-blocked contraction order as managed_mvm --
    seg = jnp.zeros((ppad, np_c), jnp.float32)
    for kc in range(kp // bk):
        seg = seg + jax.lax.dot_general(
            db[:, kc * bk:(kc + 1) * bk], wb[kc * bk:(kc + 1) * bk, :],
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    s = nm_ref[...]                       # (ppad, 1) digital NM scale
    v1 = seg / s
    o, valid = replica_cols(ppad, np_c, n_cols, np_c)
    rows = (i * np.uint32(p_img)
            + jax.lax.broadcasted_iota(jnp.uint32, (ppad, np_c), 0))
    e = rows * np.uint32(n_cols & 0xFFFFFFFF) + o
    n_total = (total * n_cols) & 0xFFFFFFFF

    acc1, sat1 = read_segment(v1, rseeds_ref[0, 0], e, n_total, valid,
                              sigma, alpha)
    if two_phase:
        acc2, sat2 = read_segment(v1 / np.float32(retry_scale),
                                  rseeds_ref[0, 1], e, n_total, valid,
                                  sigma, alpha)
    else:
        acc2, sat2 = acc1, sat1
    y, residual = select_and_average(
        acc1, acc2, sat1, sat2, s, two_phase=two_phase,
        retry_scale=retry_scale, d_avg=1, out_f_p=np_c)
    y_ref[...] = y.astype(y_ref.dtype)
    sat_ref[...] = residual

    # --- update cycle: streams over the implicitly assembled columns -------
    patch = assemble_patch(x_ref[0], geom, p_img, ppad, fp)   # tap-major
    cx = gains_ref[0, 0]
    cd = gains_ref[0, 1]
    du = -db
    p_a = jnp.clip(jnp.abs(cx * patch), 0.0, 1.0)
    sgn_a = jnp.sign(patch)
    p_b = jnp.clip(jnp.abs(cd * du), 0.0, 1.0)
    sgn_b = jnp.sign(du)

    # A-stream Bernoulli counters index the *channel-major* column the
    # reference gather materializes; remap the tap-major position q in
    # register (bias-last maps to itself, padding columns never fire).
    kk = np.uint32(geom.kh * geom.kw)
    q = jax.lax.broadcasted_iota(jnp.uint32, (ppad, fp), 1)
    t_q = q // np.uint32(geom.c)
    c_q = q - t_q * np.uint32(geom.c)
    col_cm = jnp.where(q < np.uint32(geom.c) * kk, c_q * kk + t_q, q)
    rows_a = (i * np.uint32(p_img)
              + jax.lax.broadcasted_iota(jnp.uint32, (ppad, fp), 0))
    rows_b = (i * np.uint32(p_img)
              + jax.lax.broadcasted_iota(jnp.uint32, (ppad, kp), 0))
    cols_b = jax.lax.broadcasted_iota(jnp.uint32, (ppad, kp), 1)
    seed_a = _mix(useeds_ref[0, 0])
    seed_b = _mix(useeds_ref[0, 1])

    net = jnp.zeros((kp, fp), jnp.float32)
    tot = jnp.zeros((kp, fp), jnp.float32)
    for slot in range(bl):
        e_a = ((rows_a * np.uint32(bl) + np.uint32(slot))
               * np.uint32(n_cols & 0xFFFFFFFF) + col_cm)
        a_s = _signed_stream(_uniform24(_mix(e_a ^ seed_a)), p_a, sgn_a)
        e_b = ((rows_b * np.uint32(bl) + np.uint32(slot))
               * np.uint32(m_phys & 0xFFFFFFFF) + cols_b)
        b_s = _signed_stream(_uniform24(_mix(e_b ^ seed_b)), p_b, sgn_b)
        net += jax.lax.dot_general(
            b_s, a_s, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        tot += jax.lax.dot_general(
            jnp.abs(b_s), jnp.abs(a_s), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
    net_ref[...] += net
    tot_ref[...] += tot

    @pl.when(i == nb - 1)
    def _emit_counts():
        net_all = net_ref[...]
        tot_all = tot_ref[...]
        up_ref[...] = 0.5 * (tot_all + net_all)
        dn_ref[...] = 0.5 * (tot_all - net_all)


@functools.partial(
    jax.jit,
    static_argnames=("geom", "sigma", "alpha", "two_phase", "retry_scale",
                     "bl", "bk", "interpret", "name"))
def conv_bwd_update_pallas(w: jax.Array, xpad: jax.Array, delta_rep: jax.Array,
                           nm_s: jax.Array, read_seeds: jax.Array,
                           upd_seeds: jax.Array, gains: jax.Array, *, geom,
                           sigma: float, alpha: float, two_phase: bool,
                           retry_scale: float = 16.0, bl: int = 10,
                           bk: int = 128, interpret: bool = False,
                           name: str = "bwd_update_conv"
                           ) -> Tuple[jax.Array, jax.Array, jax.Array,
                                      jax.Array]:
    """Fused backward+update launch for one streamed conv tile, one image
    per grid step: the managed transpose read of the replicated
    position-error rows AND the pulse streams over the on-chip-assembled
    im2col columns, with the integer coincidence counts accumulated across
    images in VMEM.

    Args:
      w: physical weights ``(m_phys, C*kh*kw [+1 bias])``, channel-major.
      xpad: padded activation volume ``(B, H, W, C)`` (update columns).
      delta_rep: ``(positions, m_phys)`` replicated error rows (positive —
        the kernel negates them for the update's row drivers).
      nm_s: ``(positions, 1)`` per-position digital NM scale of the rows.
      read_seeds/upd_seeds/gains: as :func:`bwd_update_mvm_pallas`.

    Returns ``(z, residual_sat, count_up, count_dn)``: the transpose read
    ``(positions, cols)`` on physical columns plus its residual saturation,
    and the counts ``(m_phys, cols)`` back in channel-major column order,
    ready for ``update.finalize_counts``.
    """
    m_phys, n_cols = w.shape
    assert n_cols == geom.cols, (w.shape, geom)
    p_img = geom.oh * geom.ow
    total = geom.b * p_img
    assert delta_rep.shape == (total, m_phys), (delta_rep.shape, w.shape)
    ppad, fp = conv_block_dims(geom)
    kp = pad_to(m_phys, bk)
    np_c = pad_to(n_cols, 128)

    wpad = jnp.pad(w, ((0, kp - m_phys), (0, np_c - n_cols)))
    d_pad = jnp.pad(delta_rep.reshape(geom.b, p_img, m_phys),
                    ((0, 0), (0, ppad - p_img), (0, kp - m_phys))
                    ).reshape(geom.b * ppad, kp)
    nm_pad = jnp.pad(nm_s.astype(jnp.float32).reshape(geom.b, p_img, 1),
                     ((0, 0), (0, ppad - p_img), (0, 0)),
                     constant_values=1.0).reshape(geom.b * ppad, 1)

    kern = functools.partial(
        _conv_kernel, geom=geom, p_img=p_img, ppad=ppad, fp=fp, kp=kp,
        np_c=np_c, m_phys=m_phys, n_cols=n_cols, total=total, bl=bl, bk=bk,
        sigma=sigma, alpha=alpha, two_phase=two_phase,
        retry_scale=retry_scale)

    z, sat, up, dn = pl.pallas_call(
        kern,
        name=name,
        grid=(geom.b,),
        in_specs=[
            pl.BlockSpec((1, 2), lambda i: (0, 0)),            # read seeds
            pl.BlockSpec((1, 2), lambda i: (0, 0)),            # update seeds
            pl.BlockSpec((1, 2), lambda i: (0, 0)),            # (cx, cd)
            pl.BlockSpec((ppad, 1), lambda i: (i, 0)),         # nm scale
            pl.BlockSpec((ppad, kp), lambda i: (i, 0)),        # delta rows
            pl.BlockSpec((1, geom.h, geom.w, geom.c),
                         lambda i: (i, 0, 0, 0)),              # x image
            pl.BlockSpec((kp, np_c), lambda i: (0, 0)),        # w
        ],
        out_specs=[
            pl.BlockSpec((ppad, np_c), lambda i: (i, 0)),      # z
            pl.BlockSpec((ppad, 1), lambda i: (i, 0)),         # residual sat
            pl.BlockSpec((kp, fp), lambda i: (0, 0)),          # count_up
            pl.BlockSpec((kp, fp), lambda i: (0, 0)),          # count_dn
        ],
        out_shape=[
            jax.ShapeDtypeStruct((geom.b * ppad, np_c), delta_rep.dtype),
            jax.ShapeDtypeStruct((geom.b * ppad, 1), jnp.int32),
            jax.ShapeDtypeStruct((kp, fp), jnp.float32),
            jax.ShapeDtypeStruct((kp, fp), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((kp, fp), jnp.float32),     # net coincidence counts
            pltpu.VMEM((kp, fp), jnp.float32),     # total coincidence counts
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=vmem_limit(
                _conv_vmem(geom, m_phys, n_cols, bk), name)),
        interpret=interpret,
    )(read_seeds.reshape(1, 2).astype(jnp.uint32),
      upd_seeds.reshape(1, 2).astype(jnp.uint32),
      gains.reshape(1, 2).astype(jnp.float32), nm_pad, d_pad, xpad, wpad)

    z = z.reshape(geom.b, ppad, np_c)[:, :p_img, :n_cols]
    sat = sat.reshape(geom.b, ppad)[:, :p_img]
    perm = _tap_to_channel_perm(geom)
    return (z.reshape(total, n_cols), sat.reshape(total) > 0,
            up[:m_phys, perm], dn[:m_phys, perm])
