"""Digitally-programmable management techniques (the paper's core algorithmic
contribution): noise management (NM, Eq. 3), bound management (BM, Eq. 4) and
update management (UM).

All three are *digital-domain rescalings* wrapped around the analog array
operations — they never change the analog circuit model, exactly as the paper
prescribes.  They are written as pure functions over an ``analog_mvm``
callable so the same code wraps the pure-jnp reference tile, the Pallas
kernels, and sharded multi-pod tiles.  A fused launch, which applies NM and
BM inside one kernel, takes its management operands from
:func:`launch_args` instead, with the same key discipline.

Scale threading
---------------
NM and BM *compose*: NM normalizes the input once, then BM halves on top of
that scale until the integrator stops clipping.  The composition is realised
as ONE combined per-vector digital scale ``s = s_nm * 2^n`` threaded through
the *raw* ``analog_mvm``::

    y = [ W (x / s) + sigma ] * s ,   s = s_nm * 2^n

``s_nm = max|x|`` is computed exactly once (never re-derived from an already
rescaled input — recomputing it inside the BM retry cancels the halving and
the array would see the same normalized vector on every retry), and the BM
loop doubles ``s`` per still-saturated vector so each retry genuinely halves
the physical array input.

Conventions
-----------
``analog_mvm(x, key) -> (y, saturated)`` computes the *physical* array read
for a batch of input vectors ``x`` of shape ``(..., n_in)`` producing
``(..., n_out)`` plus a boolean saturation flag per output vector (any output
channel clipped at +-alpha).  Fresh read noise must be drawn from ``key`` on
every call — a BM retry is a *new* physical read.

When the callable is a mesh-sharded tile-grid read (``core/tile_grid.py``)
the flag it returns is already the *global* OR over every sub-tile's
partial reads, so each BM decision below is identical on all devices:
the iterative loop's trip count is mesh-uniform (each retry re-reads all
shards in lockstep) and the two-phase select picks the same phase
everywhere — bound management keeps its exact single-device semantics
with zero extra logic here.
"""

from __future__ import annotations

from typing import Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.device import RPUConfig
from repro.utils import fastrng

Array = jax.Array
AnalogMVM = Callable[[Array, Array], Tuple[Array, Array]]

_EPS = 1e-12

#: Input down-scale of the second (unconditional) two-phase BM read.
#: Equivalent to the paper's iterative loop at n=4 (effective bound 16*alpha).
TWO_PHASE_SCALE = 16.0


# ---------------------------------------------------------------------------
# Noise management — Eq. (3)
# ---------------------------------------------------------------------------

def nm_scale(x: Array) -> Array:
    """Per-vector noise-management scale: max |x_i| over the fan-in axis.

    Shape ``(..., n_in) -> (..., 1)``.  Zero vectors get scale 1 (nothing to
    amplify; the result is exact zero signal + noise either way).
    """
    s = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    return jnp.where(s > _EPS, s, 1.0)


def with_noise_management(analog_mvm: AnalogMVM, x: Array,
                          key: Array) -> Tuple[Array, Array]:
    """z = [ W^T (delta / d_max) + sigma ] * d_max   (Eq. 3).

    Division/re-multiplication happen in the digital domain; the array only
    ever sees inputs whose max |value| is exactly 1, guaranteeing at least one
    input line is driven for the full integration time.
    """
    s = nm_scale(x)
    y, sat = analog_mvm(x / s, key)
    return y * s, sat


# ---------------------------------------------------------------------------
# Bound management — Eq. (4)
# ---------------------------------------------------------------------------

def _vector_scale(x: Array, init_scale: Optional[Array]) -> Array:
    """Initial per-vector digital scale, shape ``x.shape[:-1]``."""
    if init_scale is None:
        return jnp.ones(x.shape[:-1], dtype=x.dtype)
    return jnp.broadcast_to(
        init_scale.reshape(*x.shape[:-1], -1)[..., 0], x.shape[:-1]
    ).astype(x.dtype)


def with_bound_management(analog_mvm: AnalogMVM, x: Array, key: Array,
                          max_iters: int, *,
                          init_scale: Optional[Array] = None
                          ) -> Tuple[Array, Array]:
    """y = [ W (x / s) + sigma ] * s with ``s = s0 * 2^n`` chosen per vector
    so that the read no longer saturates (Eq. 4) — effective bound
    ``2^n * alpha``.  ``init_scale`` (``s0``, default 1) is the NM scale when
    the two techniques compose; the doubling applies ON TOP of it, so every
    retry halves the input the physical array actually sees.

    The haloing loop re-reads the array with halved inputs until no output
    channel of that vector is clipped (fresh analog noise per retry — each
    retry is a new physical integration).  Vectors that never saturated keep
    their first read statistics: re-reading an unsaturated vector draws a new,
    identically-distributed noise sample, so for simplicity of the traced
    program we re-read *all* vectors with their per-vector scale and keep the
    final read; this is distribution-equivalent to retrying only saturated
    ones (DESIGN.md section 8).

    Returns ``(y, residual_sat)``; ``residual_sat`` flags vectors still
    clipped when ``max_iters`` ran out.
    """

    def body(state):
        n_iter, scale, _y, sat, k = state
        k, k_read = jax.random.split(k)
        scale = jnp.where(sat, scale * 2.0, scale)           # halve saturated inputs
        y, new_sat = analog_mvm(x / scale[..., None], k_read)
        return n_iter + 1, scale, y * scale[..., None], new_sat, k

    def cond(state):
        n_iter, _scale, _y, sat, _k = state
        return jnp.logical_and(jnp.any(sat), n_iter < max_iters)

    scale0 = _vector_scale(x, init_scale)
    key, k0 = jax.random.split(key)
    y0, sat0 = analog_mvm(x / scale0[..., None], k0)
    y0 = y0 * scale0[..., None]
    _, _, y, sat, _ = jax.lax.while_loop(
        cond, body, (jnp.zeros((), jnp.int32), scale0, y0, sat0, key))
    return y, sat


def with_bound_management_two_phase(analog_mvm: AnalogMVM, x: Array,
                                    key: Array, *,
                                    init_scale: Optional[Array] = None
                                    ) -> Tuple[Array, Array]:
    """Beyond-paper BM (DESIGN.md §9): one unconditional retry at 1/16 input
    scale replaces the data-dependent halve-and-retry loop.

    y = read(x/s0)*s0; y16 = read(x/(16*s0)) * 16*s0; pick y16 where the
    first read saturated.  ``s0`` is the NM scale when NM composes (computed
    once by the caller, NOT re-derived here).  Effective bound 16*alpha (the
    paper's loop at n=4) with a *fixed two-read latency* — removes the
    variable-latency hazard in pipelined layer execution and the while-loop
    from the lowered program (SPMD-friendlier, no retry bubble).  SNR for
    recovered vectors equals the iterative scheme's at n=4.  Validated for
    accuracy in benchmarks/bm_two_phase_check.py.

    Returns ``(y, residual_sat)``: ``residual_sat = sat1 & sat2`` flags
    vectors whose 1/16 read *also* clipped — their selected output is still a
    (rescaled) clipped value and callers must not treat it as recovered.
    """
    s0 = _vector_scale(x, init_scale)[..., None]
    k1, k2 = jax.random.split(key)
    y1, sat1 = analog_mvm(x / s0, k1)
    y2, sat2 = analog_mvm(x / (TWO_PHASE_SCALE * s0), k2)
    y = jnp.where(sat1[..., None], y2 * TWO_PHASE_SCALE, y1) * s0
    return y, jnp.logical_and(sat1, sat2)


def with_management(analog_mvm: AnalogMVM, x: Array, key: Array,
                    cfg: RPUConfig, *, backward: bool
                    ) -> Tuple[Array, Array]:
    """Compose NM and BM around one managed analog read per the config flags.

    The NM scale is computed here EXACTLY ONCE from the unscaled input and
    threaded into BM as the initial digital scale; BM's doubling then applies
    on top (``s = s_nm * 2^n``) so the halving actually reaches the array.
    ``analog_mvm`` must be the *raw* physical read — never pre-wrapped with
    NM, which would re-normalise every retry and cancel BM (the composition
    bug this layout exists to prevent).

    Returns ``(y, residual_sat)`` where ``residual_sat`` marks vectors whose
    output is still clipped after management (BM retries exhausted, or the
    two-phase 1/16 read also saturated).  Without BM the flag is the raw
    per-vector saturation of the single read.
    """
    use_nm = cfg.nm_active(backward)
    s_nm = nm_scale(x) if use_nm else None

    if cfg.bm_iterative:
        return with_bound_management(
            analog_mvm, x, key, cfg.bm_max_iters, init_scale=s_nm)
    if cfg.bm_active:
        return with_bound_management_two_phase(
            analog_mvm, x, key, init_scale=s_nm)

    if use_nm:
        y, sat = analog_mvm(x / s_nm, key)
        return y * s_nm, sat
    return analog_mvm(x, key)


# ---------------------------------------------------------------------------
# Management operands of a fused launch
# ---------------------------------------------------------------------------

class LaunchArgs(NamedTuple):
    """What a fused managed-read launch takes from the management layer.

    ``nm_s``: (B, 1) per-vector digital scale (ones when NM is off);
    ``read_seeds``: (2,) u32 seeds of the two BM reads; ``two_phase`` and
    ``retry_scale`` are static.  ``upd_seeds`` and ``gains`` (``(C_x,
    C_d)``, f32) are set for the fused backward+update launches only.
    """
    nm_s: jax.Array
    read_seeds: jax.Array
    two_phase: bool
    retry_scale: float
    upd_seeds: Optional[jax.Array] = None
    gains: Optional[jax.Array] = None


def launch_args(cfg: RPUConfig, key: Array, rows: Optional[Array] = None,
                *, backward: bool, nm_s: Optional[Array] = None,
                stream_keys: Optional[Tuple[Array, Array]] = None, lr=None,
                cols: Optional[Array] = None, um_maxima=None,
                row_offset=None) -> LaunchArgs:
    """Build a fused launch's management operands from the key and config.

    The key discipline is :func:`with_management`'s, so a fused launch
    draws the noise of the reference pipeline bit for bit: the two-phase
    reads consume ``jax.random.split(key)``, a single read consumes ``key``
    itself (its seed is passed twice).  ``rows`` (B, n) are the read's input
    vectors, from which the NM scale is taken when NM applies to this cycle;
    a caller that holds no such matrix (the conv read) passes ``nm_s``.

    A fused backward+update launch also passes ``stream_keys``: the A-stream
    (columns) and B-stream (rows) keys of the update key's 3-way split
    (``k_c`` stays with the caller for ``update.finalize_counts``).  Its
    pulse gains come from the column drivers ``cols`` against the negated
    ``rows`` (dense), or from precomputed ``um_maxima`` (conv: the columns
    are never materialized).  ``row_offset``, when given, rides as the third
    stream seed word: the dense launch's streaming counter shift.

    Iterative BM is data-dependent and multi-launch by nature: it is
    refused here, for every fused and managed entry point.
    """
    if cfg.bm_iterative:
        raise ValueError(
            "iterative BM cannot be fused into one launch; use "
            "management.with_bound_management over noisy_mvm")
    if nm_s is None:
        nm_s = (nm_scale(rows) if cfg.nm_active(backward)
                else jnp.ones((rows.shape[0], 1), rows.dtype))
    two_phase = cfg.bm_active
    if two_phase:
        k1, k2 = jax.random.split(key)
        read_seeds = jnp.stack([fastrng.key_to_seed(k1),
                                fastrng.key_to_seed(k2)])
    else:
        s1 = fastrng.key_to_seed(key)
        read_seeds = jnp.stack([s1, s1])
    upd_seeds = gains = None
    if stream_keys is not None:
        off = ([] if row_offset is None
               else [jnp.asarray(row_offset, jnp.uint32)])
        upd_seeds = jnp.stack([fastrng.key_to_seed(k) for k in stream_keys]
                              + off)
        if cols is not None:
            cx, cd = um_factors(cols, -rows, cfg, lr)
        else:
            cx, cd = um_factors_from_maxima(um_maxima, cfg, lr)
        gains = jnp.stack([jnp.asarray(cx, jnp.float32),
                           jnp.asarray(cd, jnp.float32)])
    return LaunchArgs(nm_s, read_seeds, two_phase, TWO_PHASE_SCALE,
                      upd_seeds, gains)


# ---------------------------------------------------------------------------
# Update management
# ---------------------------------------------------------------------------

def amplification_factors(cfg: RPUConfig, lr: float) -> float:
    """Base amplification C = sqrt(eta / (BL * dw_min)) shared by rows/cols."""
    return (lr / (cfg.bl * cfg.dw_min)) ** 0.5


def um_factors_from_max(x_max: Array, d_max: Array, cfg: RPUConfig,
                        lr: float, dtype) -> Tuple[Array, Array]:
    """Update-management pulse gains from precomputed scalar extrema.

    The streaming conv pipeline computes ``max|x|`` over the im2col columns
    without materializing them (a running window max over the activation
    volume); since ``max`` is order-exact, the gains here are bit-identical
    to :func:`um_factors` over the materialized column matrix.
    """
    c = amplification_factors(cfg, lr)
    if not cfg.update_management:
        return jnp.asarray(c, dtype), jnp.asarray(c, dtype)
    x_max = jnp.maximum(x_max, _EPS)
    d_max = jnp.maximum(d_max, _EPS)
    m = jnp.sqrt(d_max / x_max)
    # Guard against degenerate extremes early in training (all-zero errors).
    m = jnp.clip(m, 1e-3, 1e3)
    return (c * m).astype(dtype), (c / m).astype(dtype)


def um_factors_from_maxima(um_maxima: Optional[Tuple[Array, Array]],
                           cfg: RPUConfig, lr: float) -> Tuple[Array, Array]:
    """:func:`um_factors_from_max` over an optional ``(x_max, d_max)``
    pair, in ``cfg.dtype`` — the streamed update cycles' gains.  The pair
    may be ``None`` only when UM is off."""
    if um_maxima is None:
        assert not cfg.update_management, (
            "update management over streamed chunks needs precomputed "
            "(x_max, d_max) extrema")
        um_maxima = (None, None)
    return um_factors_from_max(*um_maxima, cfg, lr, cfg.dtype)


def um_factors(x: Array, d: Array, cfg: RPUConfig, lr: float,
               ) -> Tuple[Array, Array]:
    """Update-management pulse gains.

    Without UM:  C_x = C_d = sqrt(eta/(BL dw_min)).
    With UM:     m = sqrt(d_max / x_max);  C_x = m C,  C_d = C / m —
    equalising pulse probabilities between rows and columns, which removes the
    row-correlated coincidences the paper identifies late in training.

    ``x``: (..., n_in) activations, ``d``: (..., n_out) error signals; the
    max is taken over every axis (the paper's scheme uses the scalar extrema
    of the two vectors fed to the array).
    """
    if not cfg.update_management:
        c = amplification_factors(cfg, lr)
        return jnp.asarray(c, x.dtype), jnp.asarray(c, x.dtype)
    return um_factors_from_max(jnp.max(jnp.abs(x)), jnp.max(jnp.abs(d)),
                               cfg, lr, x.dtype)
