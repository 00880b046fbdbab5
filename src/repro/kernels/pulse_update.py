"""Pallas TPU kernel: the update cycle's coincidence counts (Eq. 1).

Given the signed pulse streams ``B (T, M_phys)`` (row drivers) and
``A (T, N)`` (column drivers), one update cycle's counts per device are

    net_ij   = sum_t B[t,i] A[t,j]           (MXU matmul #1)
    total_ij = sum_t |B[t,i]| |A[t,j]|       (MXU matmul #2)
    count_up = (total+net)/2,  count_dn = (total-net)/2

Both stream matmuls run in one launch; nothing round-trips HBM but the two
(M, N) count tiles.  The device maps, cycle-to-cycle noise and bound clip
stay digital, in the finalize every update path shares
(``core.update.finalize_counts``).

The kernel contracts the streams in one bf16 MXU pass with f32
accumulation (``_accumulate_counts``).  That is exact: every entry is in
{0, +-1}, so every product is exact in bf16, and an f32 sum of at most
2**24 integers of magnitude 1 is exact in any order.

Tiling: grid (M/bm, N/bn, T/bt), streams tiled (bt x bm)/(bt x bn), the T
axis innermost ("arbitrary") with (bm, bn) f32 accumulators revisited over
it.  Each grid step transposes its row block once, in VMEM.  Handing the
kernel (M, T) row streams instead saves that, but the transpose then lands
in XLA, costs as much per step of the LM cell, and changed the last bits of
the compiled LM training step in leaves that never read the counts.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _accumulate_counts(t, b_ref, a_ref, net_ref, tot_ref, interpret):
    """Add one T block's coincidence products to the (bm, bn) accumulators,
    zeroed at the first T block: ``b_ref`` (bt, bm), ``a_ref`` (bt, bn), both
    bf16 in {0, +-1}.  The precision is explicit so that an ambient
    ``jax.default_matmul_precision`` cannot turn the one bf16 pass into the
    MXU's multi-pass f32 emulation: the products are exact as they are.
    The CPU's dot has no bf16 x bf16 -> f32 form, so the interpreter widens
    the blocks to f32 first, which changes no value."""
    @pl.when(t == 0)
    def _init():
        net_ref[...] = jnp.zeros_like(net_ref)
        tot_ref[...] = jnp.zeros_like(tot_ref)

    bb = b_ref[...].T
    ab = a_ref[...]
    if interpret:
        bb, ab = bb.astype(jnp.float32), ab.astype(jnp.float32)
    dims = (((1,), (0,)), ((), ()))
    one_pass = dict(precision=jax.lax.Precision.DEFAULT,
                    preferred_element_type=jnp.float32)
    net_ref[...] += jax.lax.dot_general(bb, ab, dims, **one_pass)
    tot_ref[...] += jax.lax.dot_general(jnp.abs(bb), jnp.abs(ab), dims,
                                        **one_pass)


def _make_counts_kernel(nt, interpret):
    # The outputs' block index does not depend on t, so they stay in VMEM
    # over the T axis and serve as the accumulators: net in ``up_ref``,
    # total in ``dn_ref``, turned into the counts at the last T block.
    def kernel(b_ref, a_ref, up_ref, dn_ref):
        t = pl.program_id(2)
        _accumulate_counts(t, b_ref, a_ref, up_ref, dn_ref, interpret)

        @pl.when(t == nt - 1)
        def _finalize():
            net = up_ref[...]
            tot = dn_ref[...]
            up_ref[...] = 0.5 * (tot + net)
            dn_ref[...] = 0.5 * (tot - net)

    return kernel


# Exactness bound: counts are sums of at most T products of magnitude 1.
MAX_STREAM_SLOTS = 2 ** 24
# Block candidates on a split axis, and the padding a split may add over
# the axis rounded to 128.  At 512 on every axis a launch holds about 9 MB
# of VMEM, inside the v5e's default scoped 16 MiB.
_BLOCKS = (512, 384, 256, 128)
_MAX_SPLIT_PAD = 0.03


def _round_up(x: int, a: int) -> int:
    return -(-x // a) * a


def _block(extent: int) -> int:
    """The whole axis where it fits in the largest block; else the largest
    candidate that pads the axis by at most ``_MAX_SPLIT_PAD`` over its
    128-rounded extent (128 always does)."""
    if extent <= _BLOCKS[0]:
        return extent
    limit = (1 + _MAX_SPLIT_PAD) * _round_up(extent, 128)
    return next(b for b in _BLOCKS if _round_up(extent, b) <= limit)


def counts_blocks(t: int, m: int, n: int):
    """``(bt, bm, bn)`` of the counts launch for streams of T slots over M
    rows and N columns.  A block spans its whole axis or is a multiple of
    128, as the lane axes of the stream blocks ask; T, the sublane axis of
    both, is first rounded to 16, the bf16 sublane tile."""
    return _block(_round_up(t, 16)), _block(m), _block(n)


@functools.partial(jax.jit, static_argnames=("interpret", "name"))
def pulse_counts_pallas(streams_rows: jax.Array, streams_cols: jax.Array, *,
                        interpret: bool = False, name: str = "pulse_counts"):
    """Coincidence counts of one update cycle, or of one chunk of it.

    The streaming update cycle accumulates per-chunk ``(count_up,
    count_dn)`` — integer-valued f32, so chunk sums are exact — and applies
    maps/ctoc/clip once at the end (``core.update.finalize_counts``).

    ``streams_rows`` (T, M_phys), ``streams_cols`` (T, N): every entry in
    {0, +-1}, any float dtype, and T <= 2**24, so that one bf16 MXU pass
    with f32 accumulation gives the exact counts whatever the ambient matmul
    precision.  Blocks follow the shapes (``counts_blocks``).  Returns
    ``(count_up, count_dn)`` f32 of shape (M_phys, N).
    """
    t, m = streams_rows.shape
    n = streams_cols.shape[1]
    assert streams_cols.shape[0] == t, (streams_rows.shape,
                                        streams_cols.shape)
    if t > MAX_STREAM_SLOTS:
        raise ValueError(f"{t} stream slots: f32 counts are exact only up "
                         f"to {MAX_STREAM_SLOTS}")
    bt, bm, bn = counts_blocks(t, m, n)
    tp = _round_up(t, bt)
    # The operands are bf16 (exact on {0, +-1}).  Only the contracted T axis
    # is zero-padded (padding fires no pulses).  Edge blocks past M or N
    # read whatever lies beyond the array; that reaches only count rows and
    # columns past it, which are never written back.
    rp, cp = (jnp.pad(s.astype(jnp.bfloat16), ((0, tp - t), (0, 0)))
              for s in (streams_rows, streams_cols))

    up, dn = pl.pallas_call(
        _make_counts_kernel(tp // bt, interpret),
        name=name,
        grid=(pl.cdiv(m, bm), pl.cdiv(n, bn), tp // bt),
        in_specs=[
            pl.BlockSpec((bt, bm), lambda i, j, t: (t, i)),   # row streams
            pl.BlockSpec((bt, bn), lambda i, j, t: (t, j)),   # col streams
        ],
        out_specs=[
            pl.BlockSpec((bm, bn), lambda i, j, t: (i, j)),
            pl.BlockSpec((bm, bn), lambda i, j, t: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, n), jnp.float32),
            jax.ShapeDtypeStruct((m, n), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(rp, cp)
    return up, dn
