"""Temporal accumulate-across-time analog dense: weight reuse, no recurrence.

The recurrent cell (``recurrent/cell.py``) reads its tiles every timestep
and accumulates ONE pulse update across the whole unrolled sequence.  The
same contract applies to any *shared* projection applied position-by-
position over a sequence axis — the SSD block's in/out projections, a
time-distributed readout — where nothing recurs but the tile is still
reused ``T`` times per training step:

* forward: one managed ``tile_forward`` read per timestep
  (``fold_in(key, t)`` read keys, timestep-indexed — invariant to how the
  scan is chunked);
* backward: one managed transpose read per timestep, and the timestep's
  coincidence counts taken at ``row_offset = t * B`` in the
  timestep-major flattened pulse stream (``cell.tile_cycles`` — the same
  helper the cell's BPTT sweep uses);
* update: ``update.finalize_counts`` exactly ONCE per tile per step.

Because counts are exact integers carried in f32, the accumulated update
is **bit-identical for every ``time_chunk``** and slices bit-exactly out
of the single-shot ``update.pulse_update`` over all ``T*B`` stacked pairs
— the same parity contract as the cell, pinned by
``tests/test_recurrent.py``.

Config constraints are the cell's (:func:`repro.recurrent.cell._check_cfg`):
no update management (UM needs global extrema that a streamed temporal
accumulation never materializes), ``fast_rng`` on, single tile.
:func:`temporal_eligible` tests them non-raising so callers (the SSM
block) can fall back to the single-shot ``AnalogLinear`` cycle — which is
exactly what a UM config requires.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.analog.modules import AnalogState
from repro.core import management
from repro.core import tile as tile_lib
from repro.core import update as update_lib
from repro.core.device import RPUConfig, sample_device_maps
from repro.core.tile import TileState
from repro.kernels.bwd_update_mvm import bwd_update_eligible
from repro.recurrent.cell import _check_cfg, _split3, tile_cycles

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class TemporalSpec:
    """Static geometry/routing for one temporal dense (nondiff arg)."""
    bias: bool = True
    time_chunk: Optional[int] = None     # None: single chunk (whole T)


def temporal_eligible(cfg: RPUConfig) -> bool:
    """True when ``cfg`` supports streamed temporal accumulation."""
    return (not cfg.update_management and cfg.fast_rng
            and not cfg.grid_routed)


def _chunks(spec: TemporalSpec, t_total: int) -> Tuple[int, int]:
    tc = t_total if spec.time_chunk is None else int(spec.time_chunk)
    if tc < 1 or t_total % tc:
        raise ValueError(
            f"time_chunk={spec.time_chunk} must divide the sequence "
            f"length T={t_total}")
    return t_total // tc, tc


def _aug(spec: TemporalSpec, x: Array) -> Array:
    if not spec.bias:
        return x
    ones = jnp.ones((*x.shape[:-1], 1), dtype=x.dtype)
    return jnp.concatenate([x, ones], axis=-1)


# Per-step slices ride as scan INPUTS and each timestep compiles in its
# own single-step inner-scan body — the cell's bit-parity discipline
# (see ``cell._analog_scan_bwd``'s note).

@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _temporal_mvm(spec: TemporalSpec, cfg: RPUConfig, w, seed, xs, key, lr):
    _check_cfg(cfg)
    return _forward(spec, cfg, w, seed, xs, key)


def _forward(spec, cfg, w, seed, xs, key):
    t_total = xs.shape[0]
    nc, tc = _chunks(spec, t_total)
    st = TileState(w=w, maps=None, seed=seed)
    k_f, _, _ = _split3(key)

    def step(carry, inp):
        t, x_t = inp
        y = tile_lib.tile_forward(st, _aug(spec, x_t),
                                  jax.random.fold_in(k_f, t), cfg)
        return carry, y

    def chunk(carry, inp):
        ci, x_c = inp
        ts = ci * tc + jnp.arange(tc)
        return jax.lax.scan(step, carry, (ts, x_c))

    _, ys = jax.lax.scan(chunk, jnp.zeros(()),
                         (jnp.arange(nc), xs.reshape(nc, tc, *xs.shape[1:])))
    return ys.reshape(t_total, *ys.shape[2:])


def _temporal_fwd(spec, cfg, w, seed, xs, key, lr):
    _check_cfg(cfg)
    ys = _forward(spec, cfg, w, seed, xs, key)
    return ys, (w, seed, xs, key, lr)


def _temporal_bwd(spec, cfg, saved, g_ys):
    w, seed, xs, key, lr = saved
    t_total, b = xs.shape[0], xs.shape[1]
    nc, tc = _chunks(spec, t_total)
    d = cfg.devices_per_weight
    dtype = w.dtype

    _, k_b, k_u = _split3(key)
    # same 3-way split update.pulse_update performs: A-stream, B-stream,
    # ctoc — k_c stays digital for the single shared finalize
    k_a, k_b2, k_c = jax.random.split(k_u, 3)

    lr_arr = jnp.asarray(lr, dtype=dtype)
    c_amp = management.amplification_factors(cfg, lr_arr)
    cx = cd = jnp.asarray(c_amp, dtype)   # UM gated off => constant gains

    st = TileState(w=w, maps=None, seed=seed)
    fused = bwd_update_eligible(cfg, w.shape)

    def step(carry, inp):
        up, dn = carry
        t, x_t, g_t = inp
        row0 = (t * b).astype(jnp.uint32)
        z, u, dnn = tile_cycles(st, _aug(spec, x_t), g_t,
                                jax.random.fold_in(k_b, t), k_a, k_b2,
                                row0, cfg, lr_arr, cx, cd, fused, d)
        return (up + u, dn + dnn), z[..., :x_t.shape[-1]]

    def chunk(carry, inp):
        ci, x_c, g_c = inp
        ts = ci * tc + jnp.arange(tc)
        return jax.lax.scan(step, carry, (ts, x_c, g_c))

    def chunked(a):
        return a.reshape(nc, tc, *a.shape[1:])

    carry0 = (jnp.zeros(w.shape, jnp.float32),
              jnp.zeros(w.shape, jnp.float32))
    (up, dn), dxs_c = jax.lax.scan(
        chunk, carry0, (jnp.arange(nc), chunked(xs), chunked(g_ys)))
    dxs = dxs_c.reshape(t_total, b, -1)

    maps = sample_device_maps(seed, w.shape[0], w.shape[1], cfg)
    new_w = update_lib.finalize_counts(w, maps, up, dn, k_c, cfg)

    def _float0(k):
        return np.zeros(np.shape(k), dtype=jax.dtypes.float0)

    return ((w - new_w).astype(dtype), _float0(seed), dxs, _float0(key),
            jnp.zeros_like(jnp.asarray(lr, dtype)))


_temporal_mvm.defvjp(_temporal_fwd, _temporal_bwd)


def temporal_dense_apply(state: AnalogState, xs: Array,
                         key: Array, *, lr: Any = 1.0,
                         time_chunk: Optional[int] = None,
                         cfg: Optional[RPUConfig] = None) -> Array:
    """Apply one analog dense tile across a time-major batch ``xs``
    (T, B, d_in) with accumulate-across-time updates.

    Drop-in for ``AnalogLinear.apply`` over a sequence: same w_bar
    convention (``W - clip(W + DW_pulse)``), but the backward pass emits
    ONE temporally-accumulated pulse update instead of one single-shot
    cycle over the materialized (T*B) pair stack.  ``time_chunk`` is the
    bit-exact scan-chunking knob (must divide T; ``None`` = one chunk).
    """
    acfg = state.meta.cfg if cfg is None else cfg
    if key is None:
        raise ValueError("analog reads draw physical noise every "
                         "timestep: pass a PRNG key")
    spec = TemporalSpec(bias=state.meta.bias, time_chunk=time_chunk)
    w = state.w
    return _temporal_mvm(spec, acfg, w, state.seed,
                         xs.astype(w.dtype), key,
                         jnp.asarray(lr, dtype=w.dtype))
