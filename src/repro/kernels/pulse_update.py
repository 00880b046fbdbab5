"""Pallas TPU kernel: fused stochastic-pulse weight update (Eq. 1).

Given the signed pulse streams ``B (T, M_phys)`` (row drivers) and
``A (T, N)`` (column drivers), one update cycle per device is

    net_ij   = sum_t B[t,i] A[t,j]           (MXU matmul #1)
    total_ij = sum_t |B[t,i]| |A[t,j]|       (MXU matmul #2)
    count_up = (total+net)/2,  count_dn = (total-net)/2
    dw       = count_up*dw_up - count_dn*dw_dn
               + ctoc * sqrt(count_up*dw_up^2 + count_dn*dw_dn^2) * xi_ij
    w_new    = clip(w + dw, -bound, bound)

The kernel fuses both stream matmuls with the per-device map application,
cycle-to-cycle noise (on-chip counter-hash Gaussian, bit-matching
``fastrng.normal``) and the conductance-bound clip — the unfused graph would
round-trip four (M, N) tensors (net, total, dw, noise) through HBM.

Tiling: grid (M/bm, N/bn, T/bt), streams tiled (bt x bm)/(bt x bn), two f32
VMEM accumulators revisited over the T axis (innermost, "arbitrary").
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.noisy_mvm import _mix, _normal_at


def _make_kernel(nt, bm, bn, n_cols, ctoc, n_total):
    def kernel(seed_ref, b_ref, a_ref, w_ref, up_ref, dn_ref, bound_ref,
               out_ref, net_ref, tot_ref):
        i = pl.program_id(0)
        j = pl.program_id(1)
        t = pl.program_id(2)

        @pl.when(t == 0)
        def _init():
            net_ref[...] = jnp.zeros_like(net_ref)
            tot_ref[...] = jnp.zeros_like(tot_ref)

        bb = b_ref[...]
        ab = a_ref[...]
        dims = (((0,), (0,)), ((), ()))
        net_ref[...] += jax.lax.dot_general(
            bb, ab, dims, preferred_element_type=jnp.float32)
        tot_ref[...] += jax.lax.dot_general(
            jnp.abs(bb), jnp.abs(ab), dims,
            preferred_element_type=jnp.float32)

        @pl.when(t == nt - 1)
        def _finalize():
            net = net_ref[...]
            tot = tot_ref[...]
            count_up = 0.5 * (tot + net)
            count_dn = 0.5 * (tot - net)
            dw_up = up_ref[...]
            dw_dn = dn_ref[...]
            dw = count_up * dw_up - count_dn * dw_dn
            if ctoc > 0.0:
                rows = (i * bm + jax.lax.broadcasted_iota(
                    jnp.uint32, (bm, bn), 0))
                cols = (j * bn + jax.lax.broadcasted_iota(
                    jnp.uint32, (bm, bn), 1))
                e = rows * np.uint32(n_cols) + cols
                xi = _normal_at(_mix(seed_ref[0, 0]), e, n_total)
                var = count_up * dw_up * dw_up + count_dn * dw_dn * dw_dn
                dw = dw + np.float32(ctoc) * jnp.sqrt(var) * xi
            bound = bound_ref[...]
            out_ref[...] = jnp.clip(w_ref[...] + dw, -bound, bound)

    return kernel


def _make_counts_kernel(nt):
    def kernel(b_ref, a_ref, up_ref, dn_ref, net_ref, tot_ref):
        t = pl.program_id(2)

        @pl.when(t == 0)
        def _init():
            net_ref[...] = jnp.zeros_like(net_ref)
            tot_ref[...] = jnp.zeros_like(tot_ref)

        bb = b_ref[...]
        ab = a_ref[...]
        dims = (((0,), (0,)), ((), ()))
        net_ref[...] += jax.lax.dot_general(
            bb, ab, dims, preferred_element_type=jnp.float32)
        tot_ref[...] += jax.lax.dot_general(
            jnp.abs(bb), jnp.abs(ab), dims,
            preferred_element_type=jnp.float32)

        @pl.when(t == nt - 1)
        def _finalize():
            net = net_ref[...]
            tot = tot_ref[...]
            up_ref[...] = 0.5 * (tot + net)
            dn_ref[...] = 0.5 * (tot - net)

    return kernel


@functools.partial(
    jax.jit, static_argnames=("bm", "bn", "bt", "interpret", "name"))
def pulse_counts_pallas(streams_rows: jax.Array, streams_cols: jax.Array, *,
                        bm: int = 128, bn: int = 128, bt: int = 128,
                        interpret: bool = False, name: str = "pulse_counts"):
    """Fused coincidence-count contraction only: the chunked-update entry.

    The streaming update cycle accumulates per-chunk ``(count_up,
    count_dn)`` — integer-valued f32, so chunk sums are exact — and applies
    maps/ctoc/clip once at the end (``core.update.finalize_counts``); this
    kernel is the per-chunk contraction (both stream matmuls in one launch,
    nothing round-trips HBM but the two (M, N) count tiles).

    ``streams_rows`` (T, M_phys), ``streams_cols`` (T, N) signed {0, +-1};
    returns ``(count_up, count_dn)`` of shape (M_phys, N).
    """
    t, m = streams_rows.shape
    n = streams_cols.shape[1]
    assert streams_cols.shape[0] == t, (streams_rows.shape,
                                        streams_cols.shape)
    mp = -(-m // bm) * bm
    np_ = -(-n // bn) * bn
    tp = -(-t // bt) * bt
    rp = jnp.pad(streams_rows, ((0, tp - t), (0, mp - m)))
    cp = jnp.pad(streams_cols, ((0, tp - t), (0, np_ - n)))

    up, dn = pl.pallas_call(
        _make_counts_kernel(tp // bt),
        name=name,
        grid=(mp // bm, np_ // bn, tp // bt),
        in_specs=[
            pl.BlockSpec((bt, bm), lambda i, j, t: (t, i)),   # row streams
            pl.BlockSpec((bt, bn), lambda i, j, t: (t, j)),   # col streams
        ],
        out_specs=[
            pl.BlockSpec((bm, bn), lambda i, j, t: (i, j)),
            pl.BlockSpec((bm, bn), lambda i, j, t: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((mp, np_), jnp.float32),
            jax.ShapeDtypeStruct((mp, np_), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bm, bn), jnp.float32),
            pltpu.VMEM((bm, bn), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(rp, cp)
    return up[:m, :n], dn[:m, :n]


@functools.partial(
    jax.jit,
    static_argnames=("ctoc", "bm", "bn", "bt", "interpret", "name"))
def pulse_update_pallas(w: jax.Array, dw_up: jax.Array, dw_dn: jax.Array,
                        bound: jax.Array, streams_rows: jax.Array,
                        streams_cols: jax.Array, seed: jax.Array, *,
                        ctoc: float, bm: int = 128, bn: int = 128,
                        bt: int = 128, interpret: bool = False,
                        name: str = "pulse_update") -> jax.Array:
    """Fused pulse update.  ``streams_rows`` (T, M_phys), ``streams_cols``
    (T, N) signed {0, +-1}; returns the clipped new physical weights."""
    m, n = w.shape
    t = streams_rows.shape[0]
    assert streams_rows.shape == (t, m) and streams_cols.shape == (t, n)

    mp = -(-m // bm) * bm
    np_ = -(-n // bn) * bn
    tp = -(-t // bt) * bt

    wp = jnp.pad(w, ((0, mp - m), (0, np_ - n)))
    upp = jnp.pad(dw_up, ((0, mp - m), (0, np_ - n)))
    dnp = jnp.pad(dw_dn, ((0, mp - m), (0, np_ - n)))
    bp = jnp.pad(bound, ((0, mp - m), (0, np_ - n)))
    rp = jnp.pad(streams_rows, ((0, tp - t), (0, mp - m)))
    cp = jnp.pad(streams_cols, ((0, tp - t), (0, np_ - n)))

    kern = _make_kernel(tp // bt, bm, bn, n, ctoc, m * n)

    out = pl.pallas_call(
        kern,
        name=name,
        grid=(mp // bm, np_ // bn, tp // bt),
        in_specs=[
            pl.BlockSpec((1, 1), lambda i, j, t: (0, 0)),     # seed
            pl.BlockSpec((bt, bm), lambda i, j, t: (t, i)),   # row streams
            pl.BlockSpec((bt, bn), lambda i, j, t: (t, j)),   # col streams
            pl.BlockSpec((bm, bn), lambda i, j, t: (i, j)),   # w
            pl.BlockSpec((bm, bn), lambda i, j, t: (i, j)),   # dw_up
            pl.BlockSpec((bm, bn), lambda i, j, t: (i, j)),   # dw_dn
            pl.BlockSpec((bm, bn), lambda i, j, t: (i, j)),   # bound
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, t: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), w.dtype),
        scratch_shapes=[
            pltpu.VMEM((bm, bn), jnp.float32),
            pltpu.VMEM((bm, bn), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(seed.reshape(1, 1).astype(jnp.uint32), rp, cp, wp, upp, dnp, bp)
    return out[:m, :n]
