"""Launch wrappers around the Pallas kernels.

Each wrapper takes what ``repro.core`` decided — operand arrays, the
management operands of ``core.management.launch_args``, and the config
whose physical parameters fix the static scalars — names the launch, picks
interpret mode off the TPU (the kernels then execute in Python for
correctness validation; the TPU is the target) and launches.  No
simulation rule is decided here.  Every shape launches its kernel: there is
no fallback to the pure-jnp reference, and a shape whose blocks cannot fit
the TPU's VMEM raises (``managed_mvm.vmem_limit``).
"""

from __future__ import annotations

import contextlib
import contextvars
from typing import Iterator, Tuple

import jax
import jax.numpy as jnp

from repro.core.device import RPUConfig
from repro.kernels.bwd_update_mvm import (bwd_update_mvm_pallas,
                                          conv_bwd_update_pallas)
from repro.kernels.conv_mvm import conv_managed_mvm_pallas
from repro.kernels.managed_mvm import managed_mvm_pallas
from repro.kernels.noisy_mvm import noisy_mvm_pallas
from repro.kernels.pulse_update import pulse_counts_pallas
from repro.utils import fastrng

Array = jax.Array

# ---------------------------------------------------------------------------
# Stable launch labeling (repro.analysis.jaxpr_audit attribution hook)
# ---------------------------------------------------------------------------
# Every Pallas launch this module issues carries a stable *kind* name
# (``managed_read``, ``noisy_read``, ``pulse_counts``, ``managed_read_conv``,
# ``bwd_update``, ``bwd_update_conv``) as the kernel name, so static-analysis
# passes over traced jaxprs can count launches per kind without
# pattern-matching internals.  ``launch_label`` optionally appends a
# trace-time label (``managed_read__K2``; ``__`` because pallas mangles
# brackets in kernel names): the auditor wraps per-layer traces in it to
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


def _segments(w: Array, cfg: RPUConfig, transpose: bool) -> int:
    """Physical arrays the read's contraction dim spans (each is a separate
    integration with its own noise and bound)."""
    r, c = w.shape
    if transpose:
        return max(1, -(-r // cfg.max_array_rows))
    return max(1, -(-c // cfg.max_array_cols))


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
    batch_shape = x.shape[:-1]
    x2d = x.reshape(-1, x.shape[-1])
    seed = fastrng.key_to_seed(key)
    y2d, satblk = noisy_mvm_pallas(
        w, x2d, seed, sigma=float(cfg.read_sigma(transpose)),
        alpha=float(cfg.out_bound), n_seg=_segments(w, cfg, transpose),
        transpose=transpose, row_offset=row_offset,
        total_rows=total_rows, interpret=_interpret_default(),
        name=launch_name("noisy_read"))
    sat = jnp.any(satblk > 0, axis=-1)
    out_dim = c if transpose else r
    return (y2d.reshape(*batch_shape, out_dim),
            sat.reshape(batch_shape))


def managed_mvm(w: Array, x2d: Array, args, cfg: RPUConfig, *,
                transpose: bool = False, row_offset=None,
                total_rows: int = None) -> Tuple[Array, Array]:
    """ONE fused managed-read launch (``managed_mvm_pallas``): NM scale,
    fixed-latency BM (off / two-phase), clipping and the #_d replica average
    of the forward read.

    ``x2d``: (B, n) input vectors; ``args``: their
    ``core.management.launch_args`` (NM scale, read seeds, BM phases).
    Returns ``(y, residual_sat)``: ``(B, out)`` and ``(B,)``.  The kernel
    cannot take iterative BM, whose retry loop wraps one ``noisy_mvm``
    launch per read instead.
    """
    return managed_mvm_pallas(
        w, x2d, args.nm_s, args.read_seeds,
        sigma=float(cfg.read_sigma(transpose)), alpha=float(cfg.out_bound),
        n_seg=_segments(w, cfg, transpose), transpose=transpose,
        two_phase=args.two_phase, retry_scale=float(args.retry_scale),
        d_avg=1 if transpose else cfg.devices_per_weight,
        row_offset=row_offset, total_rows=total_rows,
        interpret=_interpret_default(),
        name=launch_name("managed_read"))


def conv_managed_mvm(w: Array, xpad: Array, geom, args, cfg: RPUConfig
                     ) -> Tuple[Array, Array]:
    """Kernel-backed implicit-im2col managed conv read
    (``conv_mvm_pallas``): the patch tiles are assembled in VMEM from the
    activation volume — no im2col gather in HBM at any chunk size.

    ``args``: ``core.management.launch_args`` with ``nm_s`` the
    (positions, 1) per-position scale (the window max the caller computes
    without materializing columns; ones when NM is off) and the read seeds
    of :func:`managed_mvm`, so the conv kernel draws bit-identical noise to
    the gather + fused-read path.
    """
    return conv_managed_mvm_pallas(
        w, xpad, args.nm_s, args.read_seeds, geom=geom,
        sigma=float(cfg.read_sigma(False)), alpha=float(cfg.out_bound),
        two_phase=args.two_phase, retry_scale=float(args.retry_scale),
        d_avg=cfg.devices_per_weight, interpret=_interpret_default(),
        name=launch_name("managed_read_conv"))


def bwd_update_mvm(w: Array, x2d: Array, d2d: Array, args,
                   cfg: RPUConfig) -> Tuple[Array, Array, Array, Array]:
    """ONE fused launch for the backward + update cycles of a dense tile
    (``bwd_update_mvm_pallas``): the managed transpose read of ``d2d``
    AND the signed pulse streams + integer coincidence counts, without the
    streams or the transpose-read intermediates ever reaching HBM.

    ``d2d``: (B, m_phys) *replicated* upstream gradient (positive — the
    kernel negates it for the update's row drivers, matching the
    reference's ``pulse_update(..., -g, ...)``); ``x2d``: (B, n) update
    column drivers.  ``args``: ``core.management.launch_args`` with the
    read half (as :func:`managed_mvm`'s, NM on the backward cycle) and the
    update half (A/B stream seeds plus the streaming row offset, pulse
    gains).  Returns ``(z, residual_sat, count_up, count_dn)`` — ``z`` on
    physical columns (the caller divides by #_d), counts ready for the
    shared digital finalize.
    """
    assert cfg.fast_rng, "fused backward+update generates streams on-chip " \
                         "from the counter-hash PRNG (requires cfg.fast_rng)"
    return bwd_update_mvm_pallas(
        w, d2d, x2d, args.nm_s, args.read_seeds, args.upd_seeds, args.gains,
        sigma=float(cfg.read_sigma(True)), alpha=float(cfg.out_bound),
        two_phase=args.two_phase, retry_scale=float(args.retry_scale),
        bl=int(cfg.bl), interpret=_interpret_default(),
        name=launch_name("bwd_update"))


def conv_bwd_update_mvm(w: Array, xpad: Array, delta_rep: Array, geom, args,
                        cfg: RPUConfig) -> Tuple[Array, Array, Array, Array]:
    """Fused backward+update launch for a streaming conv tile
    (``conv_bwd_update_pallas``): the managed transpose read of the
    replicated position-error rows AND the pulse streams over the
    implicitly-assembled im2col columns, one image per grid step.

    ``xpad``: padded activation volume (B, Hp, Wp, C) — the update's column
    drivers are assembled in VMEM from it (never an HBM im2col).
    ``delta_rep``: (positions, m_phys) replicated error rows.  ``args`` as
    :func:`bwd_update_mvm`'s, without the row offset.  Returns ``(z,
    residual_sat, count_up, count_dn)`` with ``z`` (positions, cols) on
    physical columns.
    """
    assert cfg.fast_rng, "fused backward+update generates streams on-chip " \
                         "from the counter-hash PRNG (requires cfg.fast_rng)"
    return conv_bwd_update_pallas(
        w, xpad, delta_rep, args.nm_s, args.read_seeds, args.upd_seeds,
        args.gains, geom=geom, sigma=float(cfg.read_sigma(True)),
        alpha=float(cfg.out_bound), two_phase=args.two_phase,
        retry_scale=float(args.retry_scale), bl=int(cfg.bl),
        interpret=_interpret_default(),
        name=launch_name("bwd_update_conv"))


def pulse_counts(streams_rows: Array, streams_cols: Array
                 ) -> Tuple[Array, Array]:
    """Kernel-backed coincidence-count contraction for one stream chunk —
    the chunked-update accumulation entry (``core.update.stream_counts``).

    Bit-identical to ``update.coincidence_counts`` (the counts are integer
    sums of {0, 1} products in f32, contracted in one bf16 MXU pass whatever
    the ambient matmul precision), so chunked pallas updates accumulate
    counts that finalize to exactly the materialized result.
    """
    m = streams_rows.shape[-1]
    n = streams_cols.shape[-1]
    rows2 = streams_rows.reshape(-1, m)
    cols2 = streams_cols.reshape(-1, n)
    return pulse_counts_pallas(rows2, cols2, interpret=_interpret_default(),
                               name=launch_name("pulse_counts"))
