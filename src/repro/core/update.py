"""Stochastic-pulse update cycle (Eq. 1) — TPU-native formulation.

The hardware streams ``BL`` pulse slots; column driver ``j`` fires with
probability ``min(|C_x x_j|, 1)`` (polarity ``sign(x_j)``), row driver ``i``
with probability ``min(|C_d d_i|, 1)`` (polarity ``sign(d_i)``).  A device at
``(i, j)`` increments by ``+dw_up(i,j)`` on a coincidence of equal net
polarity and decrements by ``dw_dn(i,j)`` otherwise, with 30% cycle-to-cycle
variation per coincidence event.

TPU adaptation (DESIGN.md section 2): the coincidence count is a *matmul over
the pulse-slot axis*.  With signed stream matrices ``A (B, BL, N)`` and
``B (B, BL, M)`` (entries in {0, +-1}):

    net_ij   = sum_{b,t} B[b,t,i] * A[b,t,j]        (up-coincidences minus down)
    total_ij = sum_{b,t} |B[b,t,i]| * |A[b,t,j]|    (all coincidences)
    count_up = (total + net)/2 ,  count_dn = (total - net)/2

i.e. two MXU matmuls with contraction ``B*BL`` — mathematically identical to
the serial per-sample rank-1 pulse updates (weight-bound clipping applied per
step instead of per pulse; bounded-difference property tested in
``tests/test_update.py``).  Cycle-to-cycle variation aggregates exactly in
distribution: a sum of ``c`` i.i.d. ``dw*(1+0.3 xi_k)`` events equals
``c*dw + 0.3*dw*sqrt(c)*xi`` in distribution.

Batched samples (minibatch and/or im2col positions) extend the contraction
axis — each sample contributes its own ``BL`` slots, exactly like the serial
column-streaming the paper describes for convolutional layers.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.device import DeviceMaps, RPUConfig
from repro.core.management import um_factors, um_factors_from_maxima
from repro.kernels import ops as kops

Array = jax.Array


def pulse_probabilities(v: Array, gain: Array) -> Tuple[Array, Array]:
    """Stochastic translation: firing probability and polarity per driver."""
    p = jnp.clip(jnp.abs(gain * v), 0.0, 1.0)
    return p, jnp.sign(v)


def sample_signed_streams(key: jax.Array, v: Array, gain: Array,
                          bl: int, fast_rng: bool = True, *,
                          row_offset=None) -> Array:
    """Sample signed pulse streams ``(..., BL, n)`` with entries {0, +-1}.

    Each driver holds one value for the whole update cycle, so every slot of
    a driver's stream carries the same polarity; slots are independent
    Bernoulli draws (hardware: per-driver random pulse generators).
    ``fast_rng`` uses the counter-hash generator (repro.utils.fastrng — same
    design as the TPU kernel's on-chip PRNG, ~8x faster than threefry on CPU).

    ``row_offset`` implements the streaming-chunk contract: ``v`` holds rows
    ``[row_offset, row_offset + chunk)`` of a logical flattened batch, and
    the chunk draws exactly the Bernoulli variates those rows would draw in
    the unchunked call (counter offset ``row_offset * BL * n``; requires
    ``fast_rng``).
    """
    p, sgn = pulse_probabilities(v, gain)
    shape = (*v.shape[:-1], bl, v.shape[-1])
    if fast_rng:
        from repro.utils import fastrng
        off = None
        if row_offset is not None:
            per_row = bl * v.shape[-1]
            off = (jnp.asarray(row_offset, jnp.uint32)
                   * jnp.uint32(per_row & 0xFFFFFFFF))
        u = fastrng.uniform(key, shape, dtype=v.dtype, offset=off)
    else:
        if row_offset is not None:
            raise ValueError("chunked streams (row_offset) require fast_rng")
        u = jax.random.uniform(key, shape, dtype=v.dtype)
    fire = (u < p[..., None, :]).astype(v.dtype)
    return fire * sgn[..., None, :]


def coincidence_counts(streams_rows: Array, streams_cols: Array
                       ) -> Tuple[Array, Array]:
    """Up/down coincidence counts via two pulse-slot matmuls.

    ``streams_rows``: (..., BL, M) signed; ``streams_cols``: (..., BL, N).
    Returns ``(count_up, count_dn)`` of shape (M, N), contracting all leading
    axes and BL.
    """
    m = streams_rows.shape[-1]
    n = streams_cols.shape[-1]
    rows2 = streams_rows.reshape(-1, m)
    cols2 = streams_cols.reshape(-1, n)
    net = jnp.einsum("tm,tn->mn", rows2, cols2,
                     preferred_element_type=jnp.float32)
    total = jnp.einsum("tm,tn->mn", jnp.abs(rows2), jnp.abs(cols2),
                       preferred_element_type=jnp.float32)
    count_up = 0.5 * (total + net)
    count_dn = 0.5 * (total - net)
    return count_up, count_dn


def dw_from_counts(count_up: Array, count_dn: Array, maps: DeviceMaps,
                   k_c: jax.Array, cfg: RPUConfig) -> Array:
    """Physical ``DW`` from accumulated coincidence counts: device maps +
    cycle-to-cycle variation (one ``(M, N)`` draw from ``k_c``).

    THE single finalisation shared by the materialized and the chunked
    update cycles — counts are integer-valued in f32 (sums of {0, 1}
    products), so per-chunk accumulation feeding this function is
    bit-identical to the one-shot contraction.
    """
    dw = count_up * maps.dw_up - count_dn * maps.dw_dn
    if cfg.dw_min_ctoc > 0.0:
        if cfg.fast_rng:
            from repro.utils import fastrng
            xi = fastrng.normal(k_c, dw.shape, dtype=dw.dtype)
        else:
            xi = jax.random.normal(k_c, dw.shape, dtype=dw.dtype)
        var = (count_up * maps.dw_up ** 2 + count_dn * maps.dw_dn ** 2)
        dw = dw + cfg.dw_min_ctoc * jnp.sqrt(var) * xi
    return dw.astype(cfg.dtype)


def finalize_counts(w: Array, maps: DeviceMaps, count_up: Array,
                    count_dn: Array, k_c: jax.Array, cfg: RPUConfig
                    ) -> Array:
    """Apply one update cycle's accumulated counts to the physical weights
    (maps + ctoc + per-device bound clip, applied once per cycle)."""
    dw = dw_from_counts(count_up, count_dn, maps, k_c, cfg)
    return jnp.clip(w + dw, -maps.bound, maps.bound)


def stream_counts(x: Array, delta: Array, cx: Array, cd: Array,
                  k_a: jax.Array, k_b: jax.Array, cfg: RPUConfig, *,
                  row_offset=None) -> Tuple[Array, Array]:
    """Coincidence counts of one chunk of (column, row) vector pairs.

    Samples the chunk's signed pulse streams (with the streaming counter
    offset when ``row_offset`` is given) and contracts them — via the
    Pallas counts kernel under ``cfg.use_pallas``, else the two-matmul
    reference.  Counts are integers in f32, so summing chunk results
    reproduces the unchunked contraction exactly.
    """
    a = sample_signed_streams(k_a, x, cx, cfg.bl, cfg.fast_rng,
                              row_offset=row_offset)
    b = sample_signed_streams(k_b, delta, cd, cfg.bl, cfg.fast_rng,
                              row_offset=row_offset)
    if cfg.use_pallas:
        return kops.pulse_counts(b, a)
    return coincidence_counts(b, a)


def pulse_delta(w_shape: Tuple[int, int], maps: DeviceMaps, x: Array,
                delta: Array, key: jax.Array, cfg: RPUConfig, lr: float
                ) -> Array:
    """Raw physical weight change ``DW`` for one update cycle (no clipping).

    ``x``: (..., in_f) column values; ``delta``: (..., rows_phys) row values
    (already replicated for multi-device mapping by the caller).
    """
    if x.ndim == 1:
        x = x[None]
        delta = delta[None]
    k_a, k_b, k_c = jax.random.split(key, 3)
    cx, cd = um_factors(x, delta, cfg, lr)

    a = sample_signed_streams(k_a, x, cx, cfg.bl, cfg.fast_rng)
    b = sample_signed_streams(k_b, delta, cd, cfg.bl, cfg.fast_rng)
    count_up, count_dn = coincidence_counts(b, a)
    return dw_from_counts(count_up, count_dn, maps, k_c, cfg)


def _chunked_counts(x2: Array, d2: Array, cx: Array, cd: Array,
                    k_a: jax.Array, k_b: jax.Array, cfg: RPUConfig,
                    chunk: int, n_out: int, n_in: int
                    ) -> Tuple[Array, Array]:
    """Accumulate coincidence counts over row chunks of the flattened
    (samples x positions) contraction axis — the constant-memory update
    path.  Only ``chunk`` rows of signed streams are live at any time
    (vs the full ``(T, BL, n)`` ~BL x activation blowup); zero-padded tail
    rows fire no pulses and contribute nothing."""
    t = x2.shape[0]
    nchunks = -(-t // chunk)
    pad = nchunks * chunk - t
    xp = jnp.pad(x2, ((0, pad), (0, 0)))
    dp = jnp.pad(d2, ((0, pad), (0, 0)))

    def body(c, carry):
        up, dn = carry
        start = c * chunk
        xc = jax.lax.dynamic_slice_in_dim(xp, start, chunk)
        dc = jax.lax.dynamic_slice_in_dim(dp, start, chunk)
        u, d_ = stream_counts(xc, dc, cx, cd, k_a, k_b, cfg,
                              row_offset=start)
        return up + u, dn + d_

    zeros = jnp.zeros((n_out, n_in), jnp.float32)
    return jax.lax.fori_loop(0, nchunks, body, (zeros, zeros))


def pulse_update(w: Array, maps: DeviceMaps, x: Array, delta: Array,
                 key: jax.Array, cfg: RPUConfig, lr: float) -> Array:
    """Full update cycle on physical weights: pulses + per-device bound clip.

    ``delta`` is the *logical* error vector (..., out_f); replication to the
    #_d physical row blocks happens here via ``tile.replicate_delta``
    (independent streams per physical row driver).

    With ``cfg.update_chunk`` the (samples x positions) contraction axis is
    walked in chunks whose per-chunk coincidence counts accumulate exactly
    (integer sums); the device maps, cycle-to-cycle noise and the bound
    clip are applied once at the end — exactly where the materialized cycle
    applies them — so chunked updates are bit-identical to the unchunked
    cycle while never materializing the full pulse-stream tensors.
    """
    from repro.core.tile import replicate_delta  # avoids cycle
    delta = replicate_delta(delta, cfg.devices_per_weight,
                            rows_phys=w.shape[0])

    if cfg.grid_routed:
        from repro.core import tile_grid
        return tile_grid.grid_pulse_update(w, maps, x, delta, key, cfg, lr)

    if x.ndim == 1:
        x, delta = x[None], delta[None]
    t = int(np.prod(x.shape[:-1]))
    if cfg.update_chunk is not None and cfg.update_chunk < t:
        k_a, k_b, k_c = jax.random.split(key, 3)
        cx, cd = um_factors(x, delta, cfg, lr)
        x2 = x.reshape(t, x.shape[-1])
        d2 = delta.reshape(t, delta.shape[-1])
        count_up, count_dn = _chunked_counts(
            x2, d2, cx, cd, k_a, k_b, cfg, cfg.update_chunk,
            w.shape[0], w.shape[1])
        return finalize_counts(w, maps, count_up, count_dn, k_c, cfg)

    if cfg.use_pallas:
        # kernel path: sample streams here (vector op), contract them in
        # the counts kernel, finalize digitally.  The finalize is the SAME
        # function the reference and chunked paths use, which pins all
        # pulse-update paths (reference / pallas x chunked / unchunked)
        # bit-identical to each other — the counts are exact integers, so
        # only the shared finalize touches inexact arithmetic.
        k_a, k_b, k_c = jax.random.split(key, 3)
        cx, cd = um_factors(x, delta, cfg, lr)
        count_up, count_dn = stream_counts(x, delta, cx, cd, k_a, k_b, cfg)
        return finalize_counts(w, maps, count_up, count_dn, k_c, cfg)

    dw = pulse_delta(w.shape, maps, x, delta, key, cfg, lr)
    return jnp.clip(w + dw, -maps.bound, maps.bound)


def pulse_update_streamed(w: Array, maps: DeviceMaps, src, get_chunk,
                          key: jax.Array, cfg: RPUConfig, lr: float, *,
                          total: int, chunk: int, um_maxima=None) -> Array:
    """Update cycle over *generated* column/row chunks — the streaming conv
    entry (``core/conv_mapping.py``): the caller provides ``get_chunk(src,
    start, chunk) -> (cols, delta_phys)`` which materializes only one chunk
    of im2col columns (and the matching replicated error rows) at a time;
    rows past ``total`` must be zeroed (they fire no pulses).

    ``um_maxima``: precomputed ``(x_max, d_max)`` scalar extrema for update
    management (the columns are never materialized in full, so the caller
    supplies the window-max — bit-identical to the materialized extrema).

    Bit-identical to ``pulse_update`` over the materialized column matrix:
    chunked counts accumulate exactly, maps/ctoc/clip land once at the end,
    and each chunk's streams use counter-offset draws.
    """
    if cfg.grid_routed:
        from repro.core import tile_grid
        return tile_grid.grid_pulse_update_streamed(
            w, maps, src, get_chunk, key, cfg, lr, total=total, chunk=chunk,
            um_maxima=um_maxima)

    k_a, k_b, k_c = jax.random.split(key, 3)
    cx, cd = um_factors_from_maxima(um_maxima, cfg, lr)
    nchunks = -(-total // chunk)

    def body(c, carry):
        up, dn = carry
        start = c * chunk
        cols, delta = get_chunk(src, start, chunk)
        u, d_ = stream_counts(cols, delta, cx, cd, k_a, k_b, cfg,
                              row_offset=start)
        return up + u, dn + d_

    zeros = jnp.zeros(w.shape, jnp.float32)
    count_up, count_dn = jax.lax.fori_loop(0, nchunks, body, (zeros, zeros))
    return finalize_counts(w, maps, count_up, count_dn, k_c, cfg)


def expected_update(x: Array, delta: Array, cfg: RPUConfig, lr: float
                    ) -> Array:
    """E[DW] = BL * dw_min * (C_x x)(C_d d)^T = lr * d x^T  (Eq. 1).

    Pure digital outer product — the oracle the stochastic scheme is tested
    against, and the fast path for ``update_mode='expected'`` ablations.
    """
    if x.ndim == 1:
        x = x[None]
        delta = delta[None]
    m = delta.shape[-1]
    n = x.shape[-1]
    # clipping of pulse probabilities at 1 saturates the expectation too
    cx, cd = um_factors(x, delta, cfg, lr)
    xs = jnp.clip(jnp.abs(cx * x), 0, 1.0) * jnp.sign(x)
    ds = jnp.clip(jnp.abs(cd * delta), 0, 1.0) * jnp.sign(delta)
    outer = jnp.einsum("...m,...n->mn", ds.reshape(-1, m), xs.reshape(-1, n),
                       preferred_element_type=jnp.float32)
    return (cfg.bl * cfg.dw_min * outer).astype(cfg.dtype)
