"""Analog LSTM/GRU cells: *temporal* weight reuse on RPU crossbar tiles.

The conv mapping (``core/conv_mapping.py``) reuses one tile across image
positions — PR 4 made that streaming and bit-exact.  This module is the
temporal analogue, after "Training LSTM Networks with Resistive Cross-Point
Devices" (1806.00166): the input projection ``W_x`` and the recurrent
projection ``W_h`` each live on one crossbar tile whose weights are

* **read every timestep** — the forward ``lax.scan`` performs one managed
  analog read per gate-tile per timestep (``tile_forward`` with NM/BM, a
  fresh ``fold_in(key, t)`` read key each step);
* **transpose-read every timestep** — the backward (BPTT) reverse scan
  performs the managed transpose read per timestep to chain ``dh`` and
  produce ``dx``;
* **updated ONCE per training step** — each timestep contributes one
  (column, row) = (driver, error) vector pair to the stochastic pulse
  update; the integer coincidence counts are accumulated across all ``T``
  timesteps in the reverse-scan carry with the counter-offset fastrng
  discipline (``row_offset = t * B``, rows flattened timestep-major) and the
  shared ``update.finalize_counts`` (device maps + cycle-to-cycle noise +
  per-device bound clip) is applied exactly once per tile per step.

Because the pulse-stream counters of timestep ``t`` are the ``[tB, tB+B)``
row slice of the single-shot stream over all ``T*B`` flattened pairs, the
scanned/chunked update is **bit-identical** to a fully-unrolled cycle that
stacks every pair and calls ``update.pulse_update`` once —
``recurrent/oracle.py`` is that unrolled reference and
``tests/test_recurrent.py`` pins the equality with ``assert_array_equal``
across NM x fixed-latency BM x ``devices_per_weight`` x time-chunk sizes.

With ``cfg.fuse_bwd_update`` each timestep's backward read + count
contraction runs as ONE fused Pallas launch
(``tile.backward_update_counts`` with the per-timestep ``row_offset``) —
same counters, same counts, still one shared finalize per step.

Constraints (checked at trace time):

* ``cfg.update_management`` must be off: UM gains need the *global* scalar
  extrema of all drivers/errors, which do not exist until the backward
  sweep completes — fundamentally incompatible with streaming temporal
  accumulation (the conv stream has the same caveat; see
  docs/architecture.md §"Temporal weight reuse").
* ``cfg.fast_rng`` must be on (counter-offset streams are what make
  chunked == unrolled exact).
* sharded tile grids are not routed (single-tile cycles only).

The cell's weight cotangent follows the repo-wide convention
``w_bar := W - clip(W + DW_pulse)`` so ``optim.analog_sgd`` (``p - g``)
lands the weights exactly on the physically-updated value.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.analog.modules import AnalogState
from repro.core import management
from repro.core import tile as tile_lib
from repro.core import update as update_lib
from repro.core.device import RPUConfig, sample_device_maps
from repro.core.tile import TileState, replicate_delta
from repro.kernels.bwd_update_mvm import bwd_update_eligible

Array = jax.Array
Params = Dict[str, Any]

GATES = {"lstm": 4, "gru": 3}


@dataclasses.dataclass(frozen=True)
class CellSpec:
    """Static cell geometry/routing (hashable: rides in nondiff_argnums).

    ``time_chunk``: timesteps per scan chunk — the scan runs over
    ``T // time_chunk`` chunks with a ``time_chunk``-step unrolled body.
    ``None`` unrolls the whole sequence in a single chunk; ``1`` is the
    pure scan-over-time.  Any value yields bit-identical results (the
    parity contract); it only trades compile size against launch overhead.
    """
    kind: str = "lstm"
    hidden: int = 32
    time_chunk: Optional[int] = 1
    bias: bool = True

    def __post_init__(self):
        if self.kind not in GATES:
            raise ValueError(f"unknown recurrent cell kind: {self.kind!r}")

    @property
    def gates(self) -> int:
        return GATES[self.kind]


# ---------------------------------------------------------------------------
# Init (plain dense sites -> convert_to_analog rewrites them to tiles)
# ---------------------------------------------------------------------------

def init_cell(key: Array, d_in: int, spec: CellSpec,
              dtype=jnp.float32) -> Tuple[Params, Params]:
    """Cell params as two *plain dense sites* ``{"wx": {"w","b"}, "wh":
    {"w"}}`` so ``repro.analog.convert.convert_to_analog`` (path-keyed
    deterministic seeds) can rewrite either/both onto crossbar tiles.

    Returns ``(params, axes)`` per the ``models/layers.py`` convention.
    """
    g, h = spec.gates, spec.hidden
    kx, kh = jax.random.split(key)
    sx, sh = d_in ** -0.5, h ** -0.5
    wx = jax.random.uniform(kx, (d_in, g * h), dtype, -sx, sx)
    b = jnp.zeros((g * h,), dtype)
    if spec.kind == "lstm":
        # forget-gate bias 1.0: the standard keep-by-default init
        b = b.at[h:2 * h].set(1.0)
    wh = jax.random.uniform(kh, (h, g * h), dtype, -sh, sh)
    params = {"wx": {"w": wx, "b": b}, "wh": {"w": wh}}
    axes = {"wx": {"w": ("embed", "mlp"), "b": ("mlp",)},
            "wh": {"w": ("embed", "mlp")}}
    return params, axes


# ---------------------------------------------------------------------------
# Gate nonlinearities (shared fwd/bwd; recomputed from pre-activations)
# ---------------------------------------------------------------------------

def _split_gates(a: Array, n: int):
    return jnp.split(a, n, axis=-1)


def _nonlin_fwd(spec: CellSpec, ax: Array, bh: Array, h: Array, c: Array
                ) -> Tuple[Array, Array]:
    """(h', c') from the two tile reads.  ``c`` is carried but unused for
    GRU (kept zero) so both kinds share one scan signature."""
    if spec.kind == "lstm":
        ai, af, ag, ao = _split_gates(ax + bh, 4)
        i, f = jax.nn.sigmoid(ai), jax.nn.sigmoid(af)
        g, o = jnp.tanh(ag), jax.nn.sigmoid(ao)
        c2 = f * c + i * g
        return o * jnp.tanh(c2), c2
    axr, axz, axn = _split_gates(ax, 3)
    bhr, bhz, bhn = _split_gates(bh, 3)
    r = jax.nn.sigmoid(axr + bhr)
    z = jax.nn.sigmoid(axz + bhz)
    n = jnp.tanh(axn + r * bhn)
    return (1.0 - z) * n + z * h, c


def _nonlin_bwd(spec: CellSpec, ax: Array, bh: Array, hp: Array, cp: Array,
                dh: Array, dc: Array) -> Tuple[Array, Array, Array, Array]:
    """Digital gate backward: (delta_x, delta_h, dh_prev_local, dc_prev).

    ``delta_x``/``delta_h`` are the gate pre-activation errors driving the
    ``W_x``/``W_h`` tiles (identical for LSTM; GRU's new-gate row is scaled
    by the reset gate on the recurrent side).  ``dh_prev_local`` is the
    part of ``dh_{t-1}`` that does NOT flow through the ``W_h`` transpose
    read (zero for LSTM, ``z * dh`` for GRU).
    """
    if spec.kind == "lstm":
        ai, af, ag, ao = _split_gates(ax + bh, 4)
        i, f = jax.nn.sigmoid(ai), jax.nn.sigmoid(af)
        g, o = jnp.tanh(ag), jax.nn.sigmoid(ao)
        c2 = f * cp + i * g
        tc2 = jnp.tanh(c2)
        dct = dc + dh * o * (1.0 - tc2 * tc2)
        d_ai = dct * g * i * (1.0 - i)
        d_af = dct * cp * f * (1.0 - f)
        d_ag = dct * i * (1.0 - g * g)
        d_ao = dh * tc2 * o * (1.0 - o)
        delta = jnp.concatenate([d_ai, d_af, d_ag, d_ao], axis=-1)
        zero = jnp.zeros_like(dh)
        return delta, delta, zero, dct * f
    axr, axz, axn = _split_gates(ax, 3)
    bhr, bhz, bhn = _split_gates(bh, 3)
    r = jax.nn.sigmoid(axr + bhr)
    z = jax.nn.sigmoid(axz + bhz)
    n = jnp.tanh(axn + r * bhn)
    dn = dh * (1.0 - z)
    dpre_n = dn * (1.0 - n * n)
    dz = dh * (hp - n)
    dpre_z = dz * z * (1.0 - z)
    dr = dpre_n * bhn
    dpre_r = dr * r * (1.0 - r)
    delta_x = jnp.concatenate([dpre_r, dpre_z, dpre_n], axis=-1)
    delta_h = jnp.concatenate([dpre_r, dpre_z, dpre_n * r], axis=-1)
    return delta_x, delta_h, dh * z, jnp.zeros_like(dc)


# ---------------------------------------------------------------------------
# Analog scan-over-time (custom_vjp)
# ---------------------------------------------------------------------------

def _augment(spec: CellSpec, x: Array) -> Array:
    if not spec.bias:
        return x
    ones = jnp.ones((*x.shape[:-1], 1), dtype=x.dtype)
    return jnp.concatenate([x, ones], axis=-1)


def _check_cfg(cfg: RPUConfig) -> None:
    if cfg.update_management:
        raise ValueError(
            "temporal pulse accumulation cannot honor update management: "
            "UM gains need the global scalar extrema of every timestep's "
            "drivers/errors, which only exist after the backward sweep — "
            "use an NM/BM policy (e.g. 'managed') for recurrent tiles")
    if not cfg.fast_rng:
        raise ValueError(
            "scan-over-time analog cells require cfg.fast_rng: the "
            "counter-offset pulse streams are what make chunked updates "
            "bit-identical to the unrolled cycle")
    if cfg.grid_routed:
        raise NotImplementedError(
            "recurrent cells are single-tile; tile_grid sharding of the "
            "temporal accumulation is not routed yet")


def _split3(key: Array):
    return jax.random.split(key, 3)


def _chunks(spec: CellSpec, t_total: int) -> Tuple[int, int]:
    tc = t_total if spec.time_chunk is None else int(spec.time_chunk)
    if tc < 1 or t_total % tc:
        raise ValueError(
            f"time_chunk={spec.time_chunk} must divide the sequence "
            f"length T={t_total} (pad the sequence or pick a divisor)")
    return t_total // tc, tc


def tile_cycles(w_st: TileState, col_drv: Array, delta: Array,
                k_read: Array, k_a: Array, k_b_upd: Array, row0: Array,
                cfg: RPUConfig, lr_arr: Array, cx: Array, cd: Array,
                fused: bool, d: int) -> Tuple[Array, Array, Array]:
    """One row-block's backward+update cycles for one tile.

    The managed transpose read of ``delta`` plus this block's coincidence
    counts at ``row_offset = row0`` in the timestep-major flattened pulse
    stream.  Shared by the recurrent cell's BPTT sweep and the
    non-recurrent :mod:`repro.recurrent.temporal` dense — one
    implementation of the temporal-accumulation contract.
    """
    if fused:
        return tile_lib.backward_update_counts(
            w_st.w, col_drv, delta, k_read, k_a, k_b_upd, cfg, lr_arr,
            row_offset=row0)
    z = tile_lib.tile_backward(w_st, delta, k_read, cfg)
    d_rep = replicate_delta(-delta, d, rows_phys=w_st.w.shape[0])
    up, dn = update_lib.stream_counts(
        col_drv, d_rep, cx, cd, k_a, k_b_upd, cfg, row_offset=row0)
    return z, up, dn


def _forward_scan(spec: CellSpec, cfg: RPUConfig, wx, sx, wh, sh,
                  xs, h0, c0, k_f):
    """Scan-over-time forward: one managed read per gate-tile per timestep.

    Returns ``(hs, hT, cT)`` plus the stacked per-timestep residuals
    ``(ax, bh, h_prev, c_prev)`` the BPTT sweep recomputes the gates from.
    """
    t_total, b = xs.shape[0], xs.shape[1]
    nc, tc = _chunks(spec, t_total)
    wx_st = TileState(w=wx, maps=None, seed=sx)
    wh_st = TileState(w=wh, maps=None, seed=sh)
    k_fx, k_fh = jax.random.split(k_f)

    # Timestep slices ride as scan INPUTS (the scan machinery slices
    # them) and each timestep compiles in its own single-step inner-scan
    # body — both required for bit-parity with the per-step-jitted
    # oracle (see the matching note in ``_analog_scan_bwd``).
    xs_c = xs.reshape(nc, tc, *xs.shape[1:])

    def step(carry, inp):
        h, c = carry
        t, x_t = inp
        xa = _augment(spec, x_t)
        ax = tile_lib.tile_forward(wx_st, xa, jax.random.fold_in(k_fx, t),
                                   cfg)
        bh = tile_lib.tile_forward(wh_st, h, jax.random.fold_in(k_fh, t),
                                   cfg)
        h2, c2 = _nonlin_fwd(spec, ax, bh, h, c)
        return (h2, c2), (h2, ax, bh, h, c)

    def chunk(carry, inp):
        ci, x_chunk = inp
        ts = ci * tc + jnp.arange(tc)
        return jax.lax.scan(step, carry, (ts, x_chunk))

    (h_t, c_t), ys = jax.lax.scan(chunk, (h0, c0), (jnp.arange(nc), xs_c))
    hs, ax_s, bh_s, hp_s, cp_s = (
        y.reshape(t_total, *y.shape[2:]) for y in ys)
    return hs, h_t, c_t, (ax_s, bh_s, hp_s, cp_s)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _analog_scan(spec: CellSpec, cfg: RPUConfig, wx, sx, wh, sh,
                 xs, h0, c0, key, lr):
    _check_cfg(cfg)
    k_f, _, _ = _split3(key)
    hs, h_t, c_t, _ = _forward_scan(spec, cfg, wx, sx, wh, sh,
                                    xs, h0, c0, k_f)
    return hs, h_t, c_t


def _analog_scan_fwd(spec, cfg, wx, sx, wh, sh, xs, h0, c0, key, lr):
    _check_cfg(cfg)
    k_f, _, _ = _split3(key)
    hs, h_t, c_t, res = _forward_scan(spec, cfg, wx, sx, wh, sh,
                                      xs, h0, c0, k_f)
    return (hs, h_t, c_t), (wx, sx, wh, sh, xs, res, key, lr)


def _analog_scan_bwd(spec, cfg, saved, cts):
    wx, sx, wh, sh, xs, (ax_s, bh_s, hp_s, cp_s), key, lr = saved
    g_hs, g_ht, g_ct = cts
    t_total, b = xs.shape[0], xs.shape[1]
    nc, tc = _chunks(spec, t_total)
    d = cfg.devices_per_weight
    dtype = wx.dtype

    _, k_b, k_u = _split3(key)
    k_bx, k_bh = jax.random.split(k_b)
    k_ux, k_uh = jax.random.split(k_u)
    # same 3-way split update.pulse_update performs on its key: A-stream,
    # B-stream, ctoc — k_c stays digital for the single shared finalize
    k_xa, k_xb, k_xc = jax.random.split(k_ux, 3)
    k_ha, k_hb, k_hc = jax.random.split(k_uh, 3)

    lr_arr = jnp.asarray(lr, dtype=dtype)
    c_amp = management.amplification_factors(cfg, lr_arr)
    cx = cd = jnp.asarray(c_amp, dtype)   # UM gated off => constant gains

    wx_st = TileState(w=wx, maps=None, seed=sx)
    wh_st = TileState(w=wh, maps=None, seed=sh)
    # fused per-timestep launches for BOTH tiles, else the separate-launch
    # cycles for both (the oracle)
    fused = (bwd_update_eligible(cfg, wx.shape)
             and bwd_update_eligible(cfg, wh.shape))

    def cycles(w_st, col_drv, delta, k_read, k_a, k_b_upd, t):
        row0 = (t * b).astype(jnp.uint32) if hasattr(t, "dtype") \
            else jnp.uint32(t * b)
        return tile_cycles(w_st, col_drv, delta, k_read, k_a, k_b_upd,
                           row0, cfg, lr_arr, cx, cd, fused, d)

    # Per-step slices ride as scan INPUTS (the scan machinery slices
    # them), and every timestep lives in its OWN inner-scan body.  Both
    # are bit-parity requirements, not style: in-body gathers fuse into
    # the body arithmetic, and XLA compiles the same per-step subgraph
    # differently once a body holds more than one timestep (even behind
    # an optimization_barrier) — a closed single-step while-body is the
    # one compilation unit that matches the per-step-jitted oracle at
    # every chunk size.
    def chunked(a):
        return a.reshape(nc, tc, *a.shape[1:])

    def step(carry, inp):
        dh, dc, up_x, dn_x, up_h, dn_h = carry
        t, x_t, ax, bh, hp, cp, g_t = inp
        dh = dh + g_t
        delta_x, delta_h, dh_loc, dc_prev = _nonlin_bwd(
            spec, ax, bh, hp, cp, dh, dc)
        zx, ux, dx_n = cycles(wx_st, _augment(spec, x_t), delta_x,
                              jax.random.fold_in(k_bx, t), k_xa, k_xb, t)
        zh, uh, dh_n = cycles(wh_st, hp, delta_h,
                              jax.random.fold_in(k_bh, t), k_ha, k_hb, t)
        carry = (dh_loc + zh, dc_prev, up_x + ux, dn_x + dx_n,
                 up_h + uh, dn_h + dh_n)
        return carry, zx[..., :x_t.shape[-1]]        # drop bias column
    def chunk(carry, inp):
        ci, x_c, ax_c, bh_c, hp_c, cp_c, ghs_c = inp
        ts = ci * tc + jnp.arange(tc)
        carry, dxs_chunk = jax.lax.scan(
            step, carry, (ts, x_c, ax_c, bh_c, hp_c, cp_c, ghs_c),
            reverse=True)
        return carry, dxs_chunk

    zeros = lambda w: (jnp.zeros(w.shape, jnp.float32),) * 2  # noqa: E731
    (up_x0, dn_x0), (up_h0, dn_h0) = zeros(wx), zeros(wh)
    carry0 = (g_ht, g_ct, up_x0, dn_x0, up_h0, dn_h0)
    inputs = (jnp.arange(nc), chunked(xs), chunked(ax_s), chunked(bh_s),
              chunked(hp_s), chunked(cp_s), chunked(g_hs))
    (dh0, dc0, up_x, dn_x, up_h, dn_h), dxs_c = jax.lax.scan(
        chunk, carry0, inputs, reverse=True)
    dxs = dxs_c.reshape(t_total, b, -1)

    # ONE shared digital finalize per tile per training step — the same
    # single-emission contract the conv stream and fused dense paths obey
    maps_x = sample_device_maps(sx, wx.shape[0], wx.shape[1], cfg)
    maps_h = sample_device_maps(sh, wh.shape[0], wh.shape[1], cfg)
    new_wx = update_lib.finalize_counts(wx, maps_x, up_x, dn_x, k_xc, cfg)
    new_wh = update_lib.finalize_counts(wh, maps_h, up_h, dn_h, k_hc, cfg)

    def _float0(k):
        return np.zeros(np.shape(k), dtype=jax.dtypes.float0)

    return ((wx - new_wx).astype(dtype), _float0(sx),
            (wh - new_wh).astype(dtype), _float0(sh),
            dxs, dh0, dc0, _float0(key),
            jnp.zeros_like(jnp.asarray(lr, dtype)))


_analog_scan.defvjp(_analog_scan_fwd, _analog_scan_bwd)


# ---------------------------------------------------------------------------
# Public apply
# ---------------------------------------------------------------------------

def _as_tile(p) -> Tuple[Array, Array]:
    if isinstance(p, AnalogState):
        return p.w, p.seed
    raise TypeError(
        "analog cell_apply expects AnalogState tiles (run "
        "repro.analog.convert.convert_to_analog over the cell params); "
        f"got {type(p).__name__}")


def cell_apply(params: Params, xs: Array, spec: CellSpec, *,
               h0: Optional[Array] = None, c0: Optional[Array] = None,
               key: Optional[Array] = None, lr: Any = 1.0,
               cfg: Optional[RPUConfig] = None
               ) -> Tuple[Array, Array, Array]:
    """Run the cell over a time-major batch ``xs`` (T, B, d_in).

    Dispatches on the parameter type: plain ``{"w"[, "b"]}`` dicts run the
    exact FP cell; ``AnalogState`` tiles run the RPU scan-over-time
    (managed per-timestep reads, temporally-accumulated pulse update in the
    backward pass).  Returns ``(hs, h_T, c_T)`` with ``hs``: (T, B, H).
    """
    t_total, b = xs.shape[0], xs.shape[1]
    h = spec.hidden
    if h0 is None:
        h0 = jnp.zeros((b, h), xs.dtype)
    if c0 is None:
        c0 = jnp.zeros((b, h), xs.dtype)

    if not isinstance(params["wx"], AnalogState):
        def step(carry, x_t):
            hh, cc = carry
            ax = x_t @ params["wx"]["w"] + params["wx"]["b"]
            bh = hh @ params["wh"]["w"]
            h2, c2 = _nonlin_fwd(spec, ax, bh, hh, cc)
            return (h2, c2), h2
        (h_t, c_t), hs = jax.lax.scan(step, (h0, c0), xs)
        return hs, h_t, c_t

    if key is None:
        raise ValueError("analog cells draw physical read noise every "
                         "timestep: pass a PRNG key")
    wx, sx = _as_tile(params["wx"])
    wh, sh = _as_tile(params["wh"])
    acfg = params["wx"].meta.cfg if cfg is None else cfg
    spec = dataclasses.replace(spec, bias=params["wx"].meta.bias)
    lr_arr = jnp.asarray(lr, dtype=wx.dtype)
    return _analog_scan(spec, acfg, wx, sx, wh, sh,
                        xs.astype(wx.dtype), h0.astype(wx.dtype),
                        c0.astype(wx.dtype), key, lr_arr)
