"""Analog fully-connected layer with the RPU three-cycle backprop semantics.

The layer is an ordinary differentiable JAX function, but its ``custom_vjp``
implements the paper's *physical* cycles:

* forward  — managed analog read          ``y = f_mgmt(W x)``
* backward — managed analog transpose read ``x_bar = f_mgmt(W^T y_bar)``
* update   — stochastic-pulse cycle applied *inside the backward pass*: the
  weight cotangent is defined as ``w_bar := W - clip(W + DW_pulse)`` so that a
  plain SGD step with learning rate 1.0 (``optim.analog_sgd``) lands the
  weights exactly on the physically-updated, bound-clipped value.  The pulse
  gains already encode the learning rate (Eq. 1), making the whole training
  step jit-able, shardable and free of out-of-band state.

Biases are trained on the array as an extra always-on input column (the
paper's 16x26 = 16x(5*5*1+1) K1 layout).

With ``cfg.tile_grid = (R, C)`` all three cycles route through the
mesh-sharded sub-tile grid (``core/tile_grid.py``): the custom_vjp below
is unchanged — the forward/backward reads and the pulse update it calls
dispatch per config, so the same layer runs single-device or
tile-parallel on the ``'array_row' x 'array_col'`` crossbar mesh
(docs/scaling.md).

``mode='digital'`` short-circuits everything to an exact FP dense layer over
the *effective* (replica-averaged) weights — the FP-baseline path.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import tile as tile_lib
from repro.core import update as update_lib
from repro.core.device import RPUConfig, sample_device_maps
from repro.core.tile import TileState
from repro.kernels.bwd_update_mvm import bwd_update_eligible

Array = jax.Array


def _float0(key: Array) -> np.ndarray:
    return np.zeros(np.shape(key), dtype=jax.dtypes.float0)


def _split3(key: Array):
    return jax.random.split(key, 3)


@jax.named_scope("forward")
def _fwd_read(cfg: RPUConfig, w: Array, x: Array, key: Array) -> Array:
    state = TileState(w=w, maps=None, seed=key)  # maps unused in reads
    return tile_lib.tile_forward(state, x, key, cfg)


@jax.named_scope("backward")
def _bwd_read(cfg: RPUConfig, w: Array, g: Array, key: Array) -> Array:
    state = TileState(w=w, maps=None, seed=key)
    return tile_lib.tile_backward(state, g, key, cfg)


@jax.named_scope("update")
def _pulse_w_bar(cfg, w, maps, x, g, key, lr):
    """w_bar such that ``w - w_bar == clip(w + DW_pulse(x, -g))``."""
    new_w = update_lib.pulse_update(w, maps, x, -g, key, cfg, lr)
    return (w - new_w).astype(w.dtype)


@jax.named_scope("backward_update")
def _fused_bwd(cfg, w, maps, x, g, k_b, k_u, lr):
    """Backward + update cycles in one Pallas launch — bit-identical to
    ``_bwd_read`` + ``_pulse_w_bar`` (the separate-launch oracle)."""
    x_bar, new_w = tile_lib.tile_backward_update(
        w, maps, x, g, k_b, k_u, cfg, lr)
    return x_bar, (w - new_w).astype(w.dtype)


# --- materialized device maps ----------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _analog_mat(cfg: RPUConfig, w, dw_up, dw_dn, bound, x, key, lr):
    k_f, _, _ = _split3(key)
    return _fwd_read(cfg, w, x, k_f)


def _analog_mat_fwd(cfg, w, dw_up, dw_dn, bound, x, key, lr):
    k_f, _, _ = _split3(key)
    y = _fwd_read(cfg, w, x, k_f)
    return y, (w, dw_up, dw_dn, bound, x, key, lr)


def _analog_mat_bwd(cfg, res, g):
    w, dw_up, dw_dn, bound, x, key, lr = res
    _, k_b, k_u = _split3(key)
    maps = tile_lib.DeviceMaps(dw_up=dw_up, dw_dn=dw_dn, bound=bound)
    if bwd_update_eligible(cfg, w.shape):
        x_bar, w_bar = _fused_bwd(cfg, w, maps, x, g, k_b, k_u, lr)
    else:
        x_bar = _bwd_read(cfg, w, g, k_b)
        w_bar = _pulse_w_bar(cfg, w, maps, x, g, k_u, lr)
    zeros = jnp.zeros_like
    return (w_bar, zeros(dw_up), zeros(dw_dn), zeros(bound), x_bar,
            _float0(key), jnp.zeros_like(lr))


_analog_mat.defvjp(_analog_mat_fwd, _analog_mat_bwd)


# --- seeded device maps (regenerated in the backward pass) ------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _analog_seeded(cfg: RPUConfig, w, seed, x, key, lr):
    k_f, _, _ = _split3(key)
    return _fwd_read(cfg, w, x, k_f)


def _analog_seeded_fwd(cfg, w, seed, x, key, lr):
    k_f, _, _ = _split3(key)
    y = _fwd_read(cfg, w, x, k_f)
    return y, (w, seed, x, key, lr)


def _analog_seeded_bwd(cfg, res, g):
    w, seed, x, key, lr = res
    _, k_b, k_u = _split3(key)
    maps = sample_device_maps(seed, w.shape[0], w.shape[1], cfg)
    if bwd_update_eligible(cfg, w.shape):
        x_bar, w_bar = _fused_bwd(cfg, w, maps, x, g, k_b, k_u, lr)
    else:
        x_bar = _bwd_read(cfg, w, g, k_b)
        w_bar = _pulse_w_bar(cfg, w, maps, x, g, k_u, lr)
    return (w_bar, _float0(seed), x_bar, _float0(key), jnp.zeros_like(lr))


_analog_seeded.defvjp(_analog_seeded_fwd, _analog_seeded_bwd)


# --- public layer -----------------------------------------------------------

def init(key: Array, in_features: int, out_features: int, cfg: RPUConfig,
         bias: bool = True, init_scale: Optional[float] = None,
         w_init: Optional[Array] = None) -> TileState:
    """Initialise an analog linear layer (bias = extra input column)."""
    cols = in_features + (1 if bias else 0)
    if w_init is not None and bias:
        w_init = jnp.pad(w_init, ((0, 0), (0, 1)))
    return tile_lib.init_tile(key, out_features, cols, cfg,
                              init_scale=init_scale, w_init=w_init)


def apply(state: TileState, x: Array, key: Array, cfg: RPUConfig,
          lr: Array, *, bias: bool = True, mode: str = "analog") -> Array:
    """Apply the layer.  ``mode``: 'analog' (RPU physics) or 'digital' (FP)."""
    if bias:
        ones = jnp.ones((*x.shape[:-1], 1), dtype=x.dtype)
        x = jnp.concatenate([x, ones], axis=-1)

    if mode == "digital":
        w_eff = tile_lib.effective_weights(state, cfg)
        return jnp.einsum("...k,ok->...o", x, w_eff,
                          preferred_element_type=jnp.float32).astype(x.dtype)

    lr = jnp.asarray(lr, dtype=state.w.dtype)
    if cfg.seeded_maps or state.maps is None:
        return _analog_seeded(cfg, state.w, state.seed, x, key, lr)
    m = state.maps
    return _analog_mat(cfg, state.w, m.dw_up, m.dw_dn, m.bound, x, key, lr)
