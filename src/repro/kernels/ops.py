"""jit'd public wrappers around the Pallas kernels.

These adapt the (config-carrying, arbitrary-batch-shape) tile API onto the
2-D padded kernel interfaces and pick interpret mode automatically off the
TPU (the kernels then execute in Python for correctness validation; the TPU
is the target).  Every shape launches its kernel: there is no fallback to
the pure-jnp reference, and a shape whose blocks cannot fit the TPU's VMEM
raises (``managed_mvm.vmem_limit``).
"""

from __future__ import annotations

import contextlib
import contextvars
import functools
from typing import Iterator, Tuple

import jax
import jax.numpy as jnp

from repro.core.device import DeviceMaps, RPUConfig
from repro.kernels.managed_mvm import managed_mvm_pallas
from repro.kernels.noisy_mvm import noisy_mvm_pallas
from repro.kernels.pulse_update import pulse_counts_pallas, pulse_update_pallas
from repro.utils import fastrng

Array = jax.Array

# ---------------------------------------------------------------------------
# Stable launch labeling (repro.analysis.jaxpr_audit attribution hook)
# ---------------------------------------------------------------------------
# Every Pallas launch this module issues carries a stable *kind* name
# (``managed_read``, ``noisy_read``, ``pulse_update``, ``pulse_counts``,
# ``managed_read_conv``) as the kernel name, so static-analysis passes over
# traced jaxprs can count launches per kind without pattern-matching
# internals.  ``launch_label`` optionally appends a trace-time label
# (``managed_read__K2``; ``__`` because pallas mangles brackets in kernel
# names): the auditor wraps per-layer traces in it to
# attribute launch counts to layers.  The label only changes the kernel
# *name* — numerics and lowering are identical with or without it.

_LAUNCH_LABEL: contextvars.ContextVar[str] = contextvars.ContextVar(
    "repro_launch_label", default="")


@contextlib.contextmanager
def launch_label(label: str) -> Iterator[None]:
    """Append ``__label`` to the kind name of every launch traced within."""
    tok = _LAUNCH_LABEL.set(label)
    try:
        yield
    finally:
        _LAUNCH_LABEL.reset(tok)


def launch_name(kind: str) -> str:
    """The kernel name for a launch of ``kind`` under the current label."""
    label = _LAUNCH_LABEL.get()
    return f"{kind}__{label}" if label else kind


def _interpret_default() -> bool:
    # Evaluated per call, NOT cached at first use: the active platform can
    # change after import (tests forcing jax_platform_name, multi-backend
    # processes), and a stale cached answer silently runs compiled kernels
    # on CPU or interpret mode on TPU.  jax caches the backend lookup itself,
    # so this is cheap.
    return jax.default_backend() != "tpu"


def noisy_mvm(w: Array, x: Array, key: Array, cfg: RPUConfig, *,
              transpose: bool = False, row_offset=None,
              total_rows: int = None) -> Tuple[Array, Array]:
    """Kernel-backed analog MVM with the tile API contract
    (arbitrary leading batch dims; per-vector saturation flag).

    This is also the per-shard raw read of the sharded tile grid
    (``core/tile_grid.py``): each mesh device launches it on its local
    sub-tile (usually ``n_seg == 1`` — the grid *is* the physical split).
    The fused ``managed_mvm`` below stays single-device-only there: its
    in-kernel select acts on the kernel-local saturation flag, while grid
    semantics require the select on the globally OR-reduced flag
    (docs/scaling.md), so the sharded path keeps NM/BM in the digital
    domain around per-phase ``noisy_mvm`` launches."""
    r, c = w.shape
    contraction = r if transpose else c
    limit = cfg.max_array_rows if transpose else cfg.max_array_cols
    n_seg = max(1, -(-contraction // limit))

    batch_shape = x.shape[:-1]
    x2d = x.reshape(-1, x.shape[-1])
    sigma = cfg.read_noise if (cfg.noise_backward if transpose
                               else cfg.noise_forward) else 0.0
    seed = fastrng.key_to_seed(key)
    y2d, satblk = noisy_mvm_pallas(
        w, x2d, seed, sigma=float(sigma), alpha=float(cfg.out_bound),
        n_seg=n_seg, transpose=transpose, row_offset=row_offset,
        total_rows=total_rows, interpret=_interpret_default(),
        name=launch_name("noisy_read"))
    sat = jnp.any(satblk > 0, axis=-1)
    out_dim = c if transpose else r
    return (y2d.reshape(*batch_shape, out_dim),
            sat.reshape(batch_shape))


def managed_mvm(w: Array, x: Array, key: Array, cfg: RPUConfig, *,
                transpose: bool = False, backward: bool = False,
                row_offset=None, total_rows: int = None
                ) -> Tuple[Array, Array]:
    """Kernel-backed *managed* analog read: NM scale, fixed-latency BM
    (off / two-phase), clipping and the #_d replica average in ONE Pallas
    launch (``managed_mvm_pallas``).

    Key discipline mirrors ``core.tile.managed_mvm_reference`` exactly: the
    two-phase reads consume ``jax.random.split(key)``, a single read consumes
    ``key`` itself — so the fused kernel draws bit-identical noise to the
    reference pipeline.  Iterative BM is data-dependent multi-launch by
    nature and must go through ``management.with_bound_management`` over
    ``noisy_mvm`` instead.
    """
    from repro.core import management

    r, c = w.shape
    contraction = r if transpose else c
    limit = cfg.max_array_rows if transpose else cfg.max_array_cols
    n_seg = max(1, -(-contraction // limit))
    d_avg = 1 if transpose else cfg.devices_per_weight

    use_bm = cfg.bound_management and cfg.out_bound != float("inf")
    if use_bm and cfg.bm_mode != "two_phase":
        raise ValueError(
            "iterative BM cannot be fused into one launch; use "
            "management.with_bound_management over noisy_mvm")
    use_nm = cfg.noise_management and (backward or cfg.nm_forward)

    batch_shape = x.shape[:-1]
    x2d = x.reshape(-1, x.shape[-1])
    nm_s = (management.nm_scale(x2d) if use_nm
            else jnp.ones((x2d.shape[0], 1), x2d.dtype))
    sigma = cfg.read_noise if (cfg.noise_backward if transpose
                               else cfg.noise_forward) else 0.0
    if use_bm:
        k1, k2 = jax.random.split(key)
        seeds = jnp.stack([fastrng.key_to_seed(k1), fastrng.key_to_seed(k2)])
    else:
        s1 = fastrng.key_to_seed(key)
        seeds = jnp.stack([s1, s1])

    y2d, sat = managed_mvm_pallas(
        w, x2d, nm_s, seeds, sigma=float(sigma), alpha=float(cfg.out_bound),
        n_seg=n_seg, transpose=transpose, two_phase=use_bm,
        retry_scale=float(management.TWO_PHASE_SCALE), d_avg=d_avg,
        row_offset=row_offset, total_rows=total_rows,
        interpret=_interpret_default(),
        name=launch_name("managed_read"))
    out_f = c if transpose else r // d_avg
    return (y2d.reshape(*batch_shape, out_f), sat.reshape(batch_shape))


def conv_managed_mvm(w: Array, xpad: Array, geom, nm_s: Array, key: Array,
                     cfg: RPUConfig) -> Tuple[Array, Array]:
    """Kernel-backed implicit-im2col managed conv read
    (``conv_mvm_pallas``): the patch tiles are assembled in VMEM from the
    activation volume — no im2col gather in HBM at any chunk size.

    ``nm_s``: (positions, 1) per-position digital scale (the window max the
    caller computes without materializing columns; ones when NM is off).
    Key/seed discipline matches :func:`managed_mvm` exactly, so the conv
    kernel draws bit-identical noise to the gather + fused-read path.
    """
    from repro.core import management
    from repro.kernels.conv_mvm import conv_managed_mvm_pallas

    use_bm = cfg.bound_management and cfg.out_bound != float("inf")
    if use_bm and cfg.bm_mode != "two_phase":
        raise ValueError(
            "iterative BM cannot be fused into one launch; use "
            "management.with_bound_management over noisy_mvm")
    sigma = cfg.read_noise if cfg.noise_forward else 0.0
    if use_bm:
        k1, k2 = jax.random.split(key)
        seeds = jnp.stack([fastrng.key_to_seed(k1), fastrng.key_to_seed(k2)])
    else:
        s1 = fastrng.key_to_seed(key)
        seeds = jnp.stack([s1, s1])
    return conv_managed_mvm_pallas(
        w, xpad, nm_s, seeds, geom=geom, sigma=float(sigma),
        alpha=float(cfg.out_bound), two_phase=use_bm,
        retry_scale=float(management.TWO_PHASE_SCALE),
        d_avg=cfg.devices_per_weight, interpret=_interpret_default(),
        name=launch_name("managed_read_conv"))


def bwd_update_mvm(w: Array, x: Array, g_rep: Array, read_key: Array,
                   k_a: Array, k_b: Array, cfg: RPUConfig, lr: float,
                   row_offset=None) -> Tuple[Array, Array, Array, Array]:
    """ONE fused launch for the backward + update cycles of a dense tile
    (``bwd_update_mvm_pallas``): the managed transpose read of ``g_rep``
    AND the signed pulse streams + integer coincidence counts, without the
    streams or the transpose-read intermediates ever reaching HBM.

    Disciplines mirror the separate launches exactly so the fused result is
    *bit-identical*: the read consumes ``read_key`` per :func:`managed_mvm`
    (split when two-phase, same seed twice otherwise; NM is always active on
    the backward cycle when ``cfg.noise_management``); the update's A/B
    streams consume ``k_a``/``k_b`` from the caller's 3-way split of the
    update key (``k_c`` stays with the caller for
    ``update.finalize_counts``), with gains from the same ``um_factors``
    call ``core.update.pulse_update`` makes.

    ``g_rep``: (..., m_phys) *replicated* upstream gradient (positive —
    the kernel negates it for the update's row drivers, matching the
    reference's ``pulse_update(..., -g, ...)``).  ``x``: (..., n) update
    column drivers.  Returns ``(z, residual_sat, count_up, count_dn)`` —
    ``z`` on physical columns (caller divides by #_d), counts ready for
    the shared digital finalize.

    ``row_offset`` (may be traced) shifts the A/B stream counters by that
    many logical update rows — the ``update.sample_signed_streams``
    streaming-chunk discipline, so a launch over rows ``[r0, r0 + B)`` of a
    larger update batch (one timestep chunk of a recurrent sequence) draws
    the exact row slice of the single-shot streams and its counts
    accumulate to the unchunked cycle bit-for-bit.
    """
    from repro.core import management
    from repro.kernels.bwd_update_mvm import bwd_update_mvm_pallas

    assert cfg.fast_rng, "fused backward+update generates streams on-chip " \
                         "from the counter-hash PRNG (requires cfg.fast_rng)"
    m_phys, n_cols = w.shape
    use_bm = cfg.bound_management and cfg.out_bound != float("inf")
    if use_bm and cfg.bm_mode != "two_phase":
        raise ValueError(
            "iterative BM cannot be fused into one launch; use "
            "management.with_bound_management over noisy_mvm")

    batch_shape = g_rep.shape[:-1]
    d2d = g_rep.reshape(-1, m_phys)
    x2d = x.reshape(-1, x.shape[-1])
    # backward cycle: NM applies whenever enabled (management.with_management
    # with backward=True), independent of nm_forward
    nm_s = (management.nm_scale(d2d) if cfg.noise_management
            else jnp.ones((d2d.shape[0], 1), d2d.dtype))
    sigma = cfg.read_noise if cfg.noise_backward else 0.0
    if use_bm:
        k1, k2 = jax.random.split(read_key)
        read_seeds = jnp.stack([fastrng.key_to_seed(k1),
                                fastrng.key_to_seed(k2)])
    else:
        s1 = fastrng.key_to_seed(read_key)
        read_seeds = jnp.stack([s1, s1])
    off = (jnp.zeros((), jnp.uint32) if row_offset is None
           else jnp.asarray(row_offset, jnp.uint32))
    upd_seeds = jnp.stack([fastrng.key_to_seed(k_a),
                           fastrng.key_to_seed(k_b), off])
    cx, cd = management.um_factors(x2d, -d2d, cfg, lr)
    gains = jnp.stack([cx, cd])

    z2d, sat, up, dn = bwd_update_mvm_pallas(
        w, d2d, x2d, nm_s, read_seeds, upd_seeds, gains,
        sigma=float(sigma), alpha=float(cfg.out_bound), two_phase=use_bm,
        retry_scale=float(management.TWO_PHASE_SCALE), bl=int(cfg.bl),
        interpret=_interpret_default(), name=launch_name("bwd_update"))
    return (z2d.reshape(*batch_shape, n_cols), sat.reshape(batch_shape),
            up, dn)


def conv_bwd_update_mvm(w: Array, xpad: Array, delta_rep: Array, geom,
                        read_key: Array, k_a: Array, k_b: Array,
                        cfg: RPUConfig, lr: float, um_maxima=None
                        ) -> Tuple[Array, Array, Array, Array]:
    """Fused backward+update launch for a streaming conv tile
    (``conv_bwd_update_pallas``): the managed transpose read of the
    replicated position-error rows AND the pulse streams over the
    implicitly-assembled im2col columns, one image per grid step.

    ``xpad``: padded activation volume (B, Hp, Wp, C) — the update's column
    drivers are assembled in VMEM from it (never an HBM im2col).
    ``delta_rep``: (positions, m_phys) replicated error rows.  ``um_maxima``
    follows ``update.pulse_update_streamed`` (precomputed scalar extrema —
    required under update management).  Key/seed discipline matches
    :func:`bwd_update_mvm`.  Returns ``(z, residual_sat, count_up,
    count_dn)`` with ``z`` (positions, cols) on physical columns.
    """
    from repro.core import management, update as update_lib
    from repro.kernels.bwd_update_mvm import conv_bwd_update_pallas

    assert cfg.fast_rng, "fused backward+update generates streams on-chip " \
                         "from the counter-hash PRNG (requires cfg.fast_rng)"
    use_bm = cfg.bound_management and cfg.out_bound != float("inf")
    if use_bm and cfg.bm_mode != "two_phase":
        raise ValueError(
            "iterative BM cannot be fused into one launch; use "
            "management.with_bound_management over noisy_mvm")
    nm_s = (management.nm_scale(delta_rep) if cfg.noise_management
            else jnp.ones((delta_rep.shape[0], 1), delta_rep.dtype))
    sigma = cfg.read_noise if cfg.noise_backward else 0.0
    if use_bm:
        k1, k2 = jax.random.split(read_key)
        read_seeds = jnp.stack([fastrng.key_to_seed(k1),
                                fastrng.key_to_seed(k2)])
    else:
        s1 = fastrng.key_to_seed(read_key)
        read_seeds = jnp.stack([s1, s1])
    upd_seeds = jnp.stack([fastrng.key_to_seed(k_a), fastrng.key_to_seed(k_b)])
    cx, cd = update_lib._um_from_maxima(um_maxima, cfg, lr)
    gains = jnp.stack([jnp.asarray(cx, jnp.float32),
                       jnp.asarray(cd, jnp.float32)])

    return conv_bwd_update_pallas(
        w, xpad, delta_rep, nm_s, read_seeds, upd_seeds, gains, geom=geom,
        sigma=float(sigma), alpha=float(cfg.out_bound), two_phase=use_bm,
        retry_scale=float(management.TWO_PHASE_SCALE), bl=int(cfg.bl),
        interpret=_interpret_default(),
        name=launch_name("bwd_update_conv"))


def pulse_update_fused(w: Array, maps: DeviceMaps, streams_rows: Array,
                       streams_cols: Array, key: Array,
                       cfg: RPUConfig) -> Array:
    """Kernel-backed update cycle; streams already sampled (..., BL, n)."""
    m, n = w.shape
    rows2 = streams_rows.reshape(-1, m)
    cols2 = streams_cols.reshape(-1, n)
    seed = fastrng.key_to_seed(key)
    return pulse_update_pallas(
        w, maps.dw_up, maps.dw_dn, maps.bound, rows2, cols2, seed,
        ctoc=float(cfg.dw_min_ctoc), interpret=_interpret_default(),
        name=launch_name("pulse_update"))


def pulse_counts(streams_rows: Array, streams_cols: Array
                 ) -> Tuple[Array, Array]:
    """Kernel-backed coincidence-count contraction for one stream chunk —
    the chunked-update accumulation entry (``core.update.stream_counts``).

    Bit-identical to ``update.coincidence_counts`` (the counts are integer
    sums of {0, 1} products in f32, contracted in one bf16 MXU pass whatever
    the ambient matmul precision) and to the count stage of the fused
    ``pulse_update_pallas`` launch, so chunked pallas updates accumulate
    counts that finalize to exactly the materialized fused result.
    """
    m = streams_rows.shape[-1]
    n = streams_cols.shape[-1]
    rows2 = streams_rows.reshape(-1, m)
    cols2 = streams_cols.reshape(-1, n)
    return pulse_counts_pallas(rows2, cols2, interpret=_interpret_default(),
                               name=launch_name("pulse_counts"))
