"""AnalogTile: the physical RPU crossbar array simulation.

A *tile* owns the physical weights of one logical weight matrix ``(out_f,
in_f)`` mapped onto cross-point devices:

* multi-device mapping (``cfg.devices_per_weight = #_d``) stores the logical
  matrix ``#_d`` times as stacked physical row blocks — the paper's 416x401
  layout for 13-device mapping of the 32x401 K2 array;
* arrays larger than the physical limit (4096x4096, paper Discussion) are
  *split*: output-dim splits are mathematically transparent (each output row
  has its own integrator), but **contraction-dim splits matter** — each
  partial read is a separate physical integration with its own additive noise
  and its own signal bound, clipped *before* the digital summation of the
  partials.  ``analog_mvm`` evaluates all partials of one tile in a single
  batched einsum on one device; with ``cfg.tile_grid = (R, C)`` the same
  decomposition instead runs tile-parallel on a 2-D device mesh
  (``core/tile_grid.py`` — one sub-tile per device, partials psum'd along
  the contraction axis, saturation OR-reduced globally).

Every analog read draws fresh Gaussian noise (sigma) and clips elementwise at
the integrator bound (+-alpha).  All managed reads return ``(y,
residual_sat)`` — the per-vector flag marks outputs still clipped after
noise/bound management (it is the raw saturation flag when BM is off) —
and ``tile_forward`` / ``tile_backward`` expose it via ``return_sat=True``.

All functions are pure and jit/shard-compatible; ``cfg.use_pallas`` routes the
inner MVM through the Pallas TPU kernel (``repro.kernels``), otherwise the
pure-jnp path below is used (it is also the kernels' oracle).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.device import DeviceMaps, RPUConfig, sample_device_maps
from repro.core import management
from repro.kernels import ops as kops

Array = jax.Array


@jax.tree_util.register_pytree_node_class
class TileState:
    """Physical state of one crossbar tile.

    Attributes:
      w:    physical weights, shape ``(#_d * out_f, in_f)``.
      maps: materialized per-device maps, or ``None`` when ``cfg.seeded_maps``.
      seed: key the device population was (or is re-)generated from.
    """

    __slots__ = ("w", "maps", "seed")

    def __init__(self, w: Array, maps: Optional[DeviceMaps], seed: Array):
        self.w = w
        self.maps = maps
        self.seed = seed

    def tree_flatten(self):
        return (self.w, self.maps, self.seed), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @property
    def rows_phys(self) -> int:
        return self.w.shape[0]

    @property
    def cols(self) -> int:
        return self.w.shape[1]


def init_tile(key: jax.Array, out_features: int, in_features: int,
              cfg: RPUConfig, init_scale: Optional[float] = None,
              w_init: Optional[Array] = None) -> TileState:
    """Create a tile; replicates initial weights across the #_d device rows."""
    k_w, k_dev = jax.random.split(key)
    if w_init is None:
        if init_scale is None:
            # keep inits well inside the (mean) conductance bound
            init_scale = min(1.0 / (in_features ** 0.5), cfg.w_bound / 2.0)
        w_init = jax.random.uniform(
            k_w, (out_features, in_features), dtype=cfg.dtype,
            minval=-init_scale, maxval=init_scale)
    else:
        w_init = w_init.astype(cfg.dtype)
    w_phys = jnp.tile(w_init, (cfg.devices_per_weight, 1))
    maps = None
    if not cfg.seeded_maps:
        maps = sample_device_maps(
            k_dev, w_phys.shape[0], w_phys.shape[1], cfg)
        # initial programming must respect each device's own bound
        w_phys = jnp.clip(w_phys, -maps.bound, maps.bound)
    return TileState(w=w_phys, maps=maps, seed=k_dev)


def tile_maps(state: TileState, cfg: RPUConfig) -> DeviceMaps:
    """Device maps — stored, or regenerated from the tile seed (seeded mode)."""
    if state.maps is not None:
        return state.maps
    return sample_device_maps(state.seed, state.w.shape[0], state.w.shape[1],
                              cfg)


def effective_weights(state: TileState, cfg: RPUConfig) -> Array:
    """Logical weights: digital mean over the #_d physical replicas."""
    d = cfg.devices_per_weight
    if d == 1:
        return state.w
    out_f = state.w.shape[0] // d
    return jnp.mean(state.w.reshape(d, out_f, state.w.shape[1]), axis=0)


# ---------------------------------------------------------------------------
# Raw analog MVM (one physical read, with contraction-dim array splits)
# ---------------------------------------------------------------------------

def _num_splits(contraction_dim: int, limit: int) -> int:
    return max(1, -(-contraction_dim // limit))


def analog_mvm(w: Array, x: Array, key: jax.Array, cfg: RPUConfig,
               *, transpose: bool = False, row_offset=None,
               total_rows: Optional[int] = None) -> Tuple[Array, Array]:
    """One physical array read: ``y = clip(W x + sigma*xi, +-alpha)``.

    Args:
      w: physical weights ``(R, C)``.
      x: inputs ``(..., C)`` (or ``(..., R)`` when ``transpose``).
      transpose: backward-cycle read ``z = W^T d`` (inputs on the rows).
      row_offset/total_rows: streaming-chunk noise discipline — ``x`` is
        rows ``[row_offset, row_offset + chunk)`` of a logical batch of
        ``total_rows`` input vectors, and the read draws the *same* noise
        those rows would draw in the unchunked call (counter-offset
        fastrng; requires ``cfg.fast_rng``).  Default: unchunked.

    Returns ``(y, sat)`` where ``sat`` is a per-vector bool: any output
    channel of any partial read hit the integrator bound.  Contraction-dim
    splits (arrays larger than ``max_array_{rows,cols}``) each contribute
    independent read noise and are bounded *before* the digital summation.
    """
    if cfg.use_pallas:
        return kops.noisy_mvm(w, x, key, cfg, transpose=transpose,
                              row_offset=row_offset, total_rows=total_rows)
    return analog_mvm_reference(w, x, key, cfg, transpose=transpose,
                                row_offset=row_offset, total_rows=total_rows)


def analog_mvm_reference(w: Array, x: Array, key: jax.Array, cfg: RPUConfig,
                         *, transpose: bool = False, row_offset=None,
                         total_rows: Optional[int] = None
                         ) -> Tuple[Array, Array]:
    """Pure-jnp analog MVM (the oracle for the Pallas kernel)."""
    if row_offset is not None and not cfg.fast_rng:
        raise ValueError("chunked reads (row_offset) require cfg.fast_rng: "
                         "threefry draws cannot be counter-offset")
    r, c = w.shape
    if transpose:
        contraction, limit = r, cfg.max_array_rows
    else:
        contraction, limit = c, cfg.max_array_cols
    s = _num_splits(contraction, limit)

    wt = w.T if transpose else w                      # (out_dim, K)
    out_dim, k_dim = wt.shape
    assert x.shape[-1] == k_dim, (x.shape, wt.shape, transpose)

    batch_shape = x.shape[:-1]
    alpha = jnp.asarray(cfg.out_bound, x.dtype)
    noise = cfg.read_sigma(transpose)

    def _normal(k, shape, per_row):
        if cfg.fast_rng:
            from repro.utils import fastrng
            off = (None if row_offset is None
                   else jnp.asarray(row_offset, jnp.uint32)
                   * np.uint32(per_row & 0xFFFFFFFF))
            tot = None if total_rows is None else total_rows * per_row
            return fastrng.normal(k, shape, dtype=x.dtype, offset=off,
                                  total=tot)
        return jax.random.normal(k, shape, dtype=x.dtype)

    if s == 1:
        y_clean = jnp.einsum("...k,ok->...o", x, wt,
                             preferred_element_type=jnp.float32)
        y_clean = y_clean.astype(x.dtype)
        if noise > 0.0:
            y_noisy = y_clean + noise * _normal(key, y_clean.shape, out_dim)
        else:
            y_noisy = y_clean
        sat = jnp.any(jnp.abs(y_noisy) >= alpha, axis=-1)
        y = jnp.clip(y_noisy, -alpha, alpha)
        return y, sat

    # contraction-dim split: pad to s equal chunks, partial reads, digital sum
    pad = s * ((k_dim + s - 1) // s) - k_dim
    chunk = (k_dim + pad) // s
    xp = jnp.pad(x, [(0, 0)] * len(batch_shape) + [(0, pad)])
    wp = jnp.pad(wt, [(0, 0), (0, pad)])
    xs = xp.reshape(*batch_shape, s, chunk)
    ws = wp.reshape(out_dim, s, chunk)
    partial = jnp.einsum("...sk,osk->...so", xs, ws,
                         preferred_element_type=jnp.float32).astype(x.dtype)
    if noise > 0.0:
        partial = partial + noise * _normal(key, partial.shape, s * out_dim)
    sat = jnp.any(jnp.abs(partial) >= alpha, axis=(-1, -2))
    partial = jnp.clip(partial, -alpha, alpha)
    y = jnp.sum(partial, axis=-2)
    return y, sat


# ---------------------------------------------------------------------------
# Managed tile cycles (forward / backward)
# ---------------------------------------------------------------------------

def managed_mvm_reference(w: Array, x: Array, key: jax.Array, cfg: RPUConfig,
                          *, transpose: bool = False, backward: bool = False,
                          row_offset=None, total_rows: Optional[int] = None
                          ) -> Tuple[Array, Array]:
    """Pure-jnp managed read: NM scale (once) + BM over raw physical reads.

    This is the oracle for ``kernels.managed_mvm_pallas`` — same key
    discipline, same counter-hash noise per read, same select-on-saturation.
    Returns ``(y_phys, residual_sat)`` on *physical* output channels (the
    #_d replica average is the caller's digital step).
    ``row_offset``/``total_rows`` follow the :func:`analog_mvm` streaming
    contract (chunked reads draw the unchunked rows' noise).
    """
    def mvm(xx, kk):
        return analog_mvm_reference(w, xx, kk, cfg, transpose=transpose,
                                    row_offset=row_offset,
                                    total_rows=total_rows)

    return management.with_management(mvm, x, key, cfg, backward=backward)


def managed_mvm(w: Array, x: Array, key: jax.Array, cfg: RPUConfig, *,
                transpose: bool = False, backward: bool = False,
                row_offset=None, total_rows: Optional[int] = None
                ) -> Tuple[Array, Array]:
    """Kernel-backed managed read: NM scale, fixed-latency BM (off /
    two-phase), clipping and — on the forward read — the #_d replica
    average in ONE Pallas launch (``kops.managed_mvm``).

    The management operands are built here (``management.launch_args``)
    with :func:`managed_mvm_reference`'s key discipline, so the launch draws
    the reference's noise bit for bit.  ``x``: (..., n) with any leading
    batch shape.  Iterative BM is refused (``ValueError``): its retry loop
    wraps one raw read per retry instead.
    """
    x2d = x.reshape(-1, x.shape[-1])
    args = management.launch_args(cfg, key, x2d, backward=backward)
    y2d, sat = kops.managed_mvm(w, x2d, args, cfg, transpose=transpose,
                                row_offset=row_offset, total_rows=total_rows)
    return y2d.reshape(*x.shape[:-1], -1), sat.reshape(x.shape[:-1])


def _replica_mean(y_phys: Array, d: int) -> Array:
    if d == 1:
        return y_phys
    out_f = y_phys.shape[-1] // d
    return jnp.mean(y_phys.reshape(*y_phys.shape[:-1], d, out_f), axis=-2)


def replicate_delta(delta: Array, d: int,
                    rows_phys: Optional[int] = None) -> Array:
    """Replicate a logical error vector to the ``#_d``-replicated physical
    row layout: ``(..., out_f) -> (..., #_d * out_f)``.

    THE single place that produces and asserts the replicated-delta layout
    — the backward transpose read and the pulse update both route through
    it, so the layout contract lives here and nowhere else.  ``rows_phys``
    (when known) pins the result against the physical row count.
    """
    assert delta.ndim >= 1, "delta must carry a trailing output-channel axis"
    if d > 1:
        delta = jnp.tile(delta, (1,) * (delta.ndim - 1) + (d,))
    assert rows_phys is None or delta.shape[-1] == rows_phys, (
        "replicated delta must match the physical row layout",
        delta.shape, d, rows_phys)
    return delta


def tile_forward(state: TileState, x: Array, key: jax.Array,
                 cfg: RPUConfig, *, return_sat: bool = False,
                 row_offset=None, total_rows: Optional[int] = None):
    """Forward cycle ``y = W_eff x`` with NM/BM management + replica average.

    With ``cfg.use_pallas`` and a fixed-latency BM mode (off or two-phase)
    the whole managed read — NM scale, both BM reads, select, clip and the
    #_d replica average — is one fused Pallas launch; the iterative BM
    while_loop instead wraps one raw-read kernel launch per retry.

    ``return_sat`` additionally returns the per-vector residual-saturation
    flag (True where management could not recover an unclipped read).
    ``row_offset``/``total_rows`` implement the streaming-chunk read
    contract of :func:`analog_mvm` (the conv pipeline feeds position-column
    chunks; each draws exactly the noise its rows would draw unchunked).
    """
    d = cfg.devices_per_weight

    if cfg.grid_routed:
        from repro.core import tile_grid  # local import, avoids cycle
        return tile_grid.grid_tile_forward(state, x, key, cfg,
                                           return_sat=return_sat,
                                           row_offset=row_offset,
                                           total_rows=total_rows)

    if cfg.use_pallas and not cfg.bm_iterative:
        y, sat = managed_mvm(state.w, x, key, cfg, transpose=False,
                             backward=False, row_offset=row_offset,
                             total_rows=total_rows)
        return (y, sat) if return_sat else y

    def mvm(xx, kk):
        return analog_mvm(state.w, xx, kk, cfg, transpose=False,
                          row_offset=row_offset, total_rows=total_rows)

    y_phys, sat = management.with_management(mvm, x, key, cfg, backward=False)
    y = _replica_mean(y_phys, d)
    return (y, sat) if return_sat else y


def tile_backward(state: TileState, delta: Array, key: jax.Array,
                  cfg: RPUConfig, *, return_sat: bool = False,
                  row_offset=None, total_rows: Optional[int] = None):
    """Backward cycle ``z = W_eff^T delta`` (transpose read, NM on inputs).

    With multi-device mapping the error vector drives all #_d replica row
    blocks simultaneously; the analog column currents sum over replicas and
    the digital domain divides by #_d.  Routing mirrors ``tile_forward``
    (including the streaming ``row_offset``/``total_rows`` contract).
    """
    d = cfg.devices_per_weight
    delta = replicate_delta(delta, d, rows_phys=state.w.shape[0])

    if cfg.grid_routed:
        from repro.core import tile_grid  # local import, avoids cycle
        return tile_grid.grid_tile_backward(state, delta, key, cfg,
                                            return_sat=return_sat,
                                            row_offset=row_offset,
                                            total_rows=total_rows)

    if cfg.use_pallas and not cfg.bm_iterative:
        z, sat = managed_mvm(state.w, delta, key, cfg, transpose=True,
                             backward=True, row_offset=row_offset,
                             total_rows=total_rows)
    else:
        def mvm(dd, kk):
            return analog_mvm(state.w, dd, kk, cfg, transpose=True,
                              row_offset=row_offset, total_rows=total_rows)

        z, sat = management.with_management(mvm, delta, key, cfg,
                                            backward=True)
    if d > 1:
        z = z / d
    return (z, sat) if return_sat else z


def tile_backward_update(w: Array, maps: DeviceMaps, x: Array, g: Array,
                         k_read: jax.Array, k_upd: jax.Array, cfg: RPUConfig,
                         lr: float) -> Tuple[Array, Array]:
    """Fused backward + update cycles in ONE Pallas launch
    (``kernels/bwd_update_mvm.py``), for the fixed-latency managed modes.

    Semantics are exactly ``tile_backward(state, g, k_read)`` followed by
    ``tile_update(state, x, -g, k_upd)`` — same replicated-delta layout
    (``replicate_delta``), same key discipline (``k_upd`` 3-way split into
    A-stream/B-stream/ctoc keys), same shared ``update.finalize_counts``
    digital epilogue — and the results are *bit-identical* to that pair;
    the separate-launch path is kept as the parity oracle
    (``tests/test_bwd_update_fused.py``).  Callers gate on
    ``kernels.bwd_update_mvm.bwd_update_eligible``.

    Takes raw ``(w, maps)`` rather than a ``TileState`` because the
    autodiff wrappers (``core/analog_linear.py``) operate on the unpacked
    physical arrays inside ``custom_vjp`` rules.

    Returns ``(z, new_w)``: the replica-averaged transpose read
    ``W_eff^T g`` and the post-update physical weights.
    """
    from repro.core import update as update_lib  # local import, avoids cycle

    k_a, k_b, k_c = jax.random.split(k_upd, 3)
    z, count_up, count_dn = backward_update_counts(w, x, g, k_read, k_a, k_b,
                                                   cfg, lr)
    new_w = update_lib.finalize_counts(w, maps, count_up, count_dn, k_c, cfg)
    return z, new_w


def backward_update_counts(w: Array, x: Array, g: Array, k_read: jax.Array,
                           k_a: jax.Array, k_b: jax.Array, cfg: RPUConfig,
                           lr, row_offset=0) -> Tuple[Array, Array, Array]:
    """The fused launch of :func:`tile_backward_update`, before the
    finalize: the replica-averaged managed transpose read of ``g`` and the
    integer coincidence counts of the update driven by ``x`` and ``-g``.

    ``k_a``/``k_b``: the A/B stream keys of the update key's 3-way split.
    ``row_offset`` (may be traced) shifts the stream counters by that many
    logical update rows — the ``update.sample_signed_streams`` streaming
    discipline, so a launch over rows ``[r0, r0 + B)`` of a larger update
    batch (one timestep of a recurrent sequence) draws the exact row slice
    of the single-shot streams and its counts accumulate to the unchunked
    cycle bit for bit.  Returns ``(z, count_up, count_dn)``.
    """
    d = cfg.devices_per_weight
    g_rep = replicate_delta(g, d, rows_phys=w.shape[0])
    d2d = g_rep.reshape(-1, w.shape[0])
    x2d = x.reshape(-1, x.shape[-1])
    args = management.launch_args(cfg, k_read, d2d, backward=True,
                                  stream_keys=(k_a, k_b), lr=lr, cols=x2d,
                                  row_offset=row_offset)
    z, _sat, count_up, count_dn = kops.bwd_update_mvm(w, x2d, d2d, args, cfg)
    z = z.reshape(*g_rep.shape[:-1], -1)
    if d > 1:
        z = z / d
    return z, count_up, count_dn


def tile_update(state: TileState, x: Array, delta: Array, key: jax.Array,
                cfg: RPUConfig, lr: float) -> TileState:
    """Update cycle: stochastic-pulse outer-product update (Eq. 1).

    ``x``: (..., in_f) activations; ``delta``: (..., out_f) error signals;
    leading axes (batch and/or conv positions) are flattened into serial
    vector-update pairs exactly as the paper streams im2col columns.
    """
    from repro.core import update as update_lib  # local import, avoids cycle
    new_w = update_lib.pulse_update(
        state.w, tile_maps(state, cfg), x, delta, key, cfg, lr)
    return TileState(w=new_w, maps=state.maps, seed=state.seed)
