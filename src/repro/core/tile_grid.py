"""Mesh-sharded crossbar tile grids: the paper's array splits on real devices.

The paper's Discussion caps one physical RPU array at 4096x4096 and realises
larger logical matrices as a *grid* of physical arrays whose partial reads
are summed digitally.  ``core/tile.py`` models that split serially on one
device; this module maps it onto hardware: the physical weight is decomposed
into a ``(row_blocks x col_blocks)`` grid of sub-tiles placed on a 2-D
``'array_row' x 'array_col'`` device mesh (``distributed.sharding.
crossbar_mesh``), and every tile cycle runs as a ``shard_map`` in which each
device operates only on its local sub-tile:

* **read** (forward / transpose): each device performs one raw analog read
  of its block (through the Pallas ``noisy_mvm`` kernel under
  ``cfg.use_pallas``), partial results are **psum'd along the contraction
  axis** with the integrator clip applied *before* the digital summation —
  exactly the paper's split semantics — and the per-vector saturation flag
  is **OR-reduced over the whole mesh** so noise/bound management keeps its
  single-device meaning:

  - NM's per-vector scale is the *global* ``max|x|`` — over chunked inputs
    that is a psum-max over the 'array_col' chunks; here the scale is
    computed once from the (replicated) unchunked input, which is
    numerically identical.
  - BM sees the globally-reduced flag, so every retry round re-reads *all*
    shards with the same doubled scale: two-phase BM is two synchronized
    shard rounds, iterative BM a while_loop whose trip count is identical
    on every device (the cond consumes the already-global flag).

* **update**: communication-free.  Each shard consumes its slice of the
  row/col pulse streams; the coincidence-count contraction (over samples x
  pulse slots) is block-local, so the sharded update is bit-identical to
  the serial grid oracle with zero collectives.

Key discipline: block ``(i, j)`` of a read draws noise from
``fold_in(read_key, i * grid_cols + j)`` (the read key itself follows the
single-device NM/BM split discipline of ``core/management.py``).  The
serial reference implementations below use the *same* fold_in schedule, so
``tests/test_tile_grid.py`` pins the sharded paths numerically identical to
the single-device grid oracle on a forced multi-device host.

Padding: non-divisible shapes pad the physical array with zero weights /
zero input lines up to the block multiple.  Padded output rows are real
integrator channels on a physical chip (they integrate pure read noise and
are discarded digitally); their noise draws are therefore kept — both paths
draw them identically — and their outputs are sliced away after assembly.

When fewer than ``row_blocks * col_blocks`` devices are present the grid
runs serially with unchanged numerics, so grid configs are portable from a
laptop to a pod.  The plain single-tile path in ``core/tile.py`` (including
the fused ``managed_mvm`` Pallas launch) remains the single-device fast
path and the bit-parity oracle for ``tile_grid=(1, 1)`` or ``None``.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import management
from repro.core import tile as tile_lib
from repro.core import update as update_lib
from repro.core.device import DeviceMaps, RPUConfig

Array = jax.Array


# ---------------------------------------------------------------------------
# Grid geometry
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class TileGrid:
    """Static geometry of one logical tile's sub-tile grid.

    ``grid_rows`` blocks partition the *physical* row dim (``#_d * out_f``,
    the output dim of the forward read), ``grid_cols`` blocks the
    contraction (column) dim.  Block sizes are ceil-divided; the padded
    physical array is ``(rows_pad, cols_pad)``.
    """

    grid_rows: int
    grid_cols: int
    rows_phys: int
    cols: int

    @classmethod
    def for_tile(cls, w_shape: Tuple[int, int], cfg: RPUConfig) -> "TileGrid":
        gr, gc = cfg.tile_grid if cfg.tile_grid is not None else (1, 1)
        r, c = w_shape
        if not (1 <= gr <= r and 1 <= gc <= c):
            raise ValueError(
                f"tile_grid {(gr, gc)} invalid for physical array {(r, c)}")
        return cls(gr, gc, r, c)

    @property
    def n_blocks(self) -> int:
        return self.grid_rows * self.grid_cols

    @property
    def block_rows(self) -> int:
        return -(-self.rows_phys // self.grid_rows)

    @property
    def block_cols(self) -> int:
        return -(-self.cols // self.grid_cols)

    @property
    def rows_pad(self) -> int:
        return self.grid_rows * self.block_rows

    @property
    def cols_pad(self) -> int:
        return self.grid_cols * self.block_cols

    def sharded(self) -> bool:
        """True when enough *healthy* local devices exist to place the mesh
        (and the grid is non-trivial).  Devices the fault runtime marked
        lost (``distributed.elastic.mark_lost``) don't count — after a
        device loss the same grid config transparently re-resolves to the
        bit-identical serial oracle on the survivors."""
        return self.n_blocks > 1 and _n_healthy() >= self.n_blocks

    def mesh(self):
        return _cached_mesh(self.grid_rows, self.grid_cols, _n_healthy())

    def pad_w(self, w: Array) -> Array:
        return jnp.pad(w, ((0, self.rows_pad - self.rows_phys),
                           (0, self.cols_pad - self.cols)))

    def pad_last(self, x: Array, to: int) -> Array:
        pad = to - x.shape[-1]
        if pad == 0:
            return x
        return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])


def _n_healthy() -> int:
    from repro.distributed import elastic
    return elastic.n_healthy()


@functools.lru_cache(maxsize=None)
def _cached_mesh(gr: int, gc: int, n_healthy: int):
    # keyed on the healthy count so an elastic shrink/regrow re-resolves the
    # placement instead of reusing a mesh that claims lost devices
    from repro.distributed import sharding as shd
    return shd.crossbar_mesh(gr, gc)


def grid_is_sharded(cfg: RPUConfig) -> bool:
    """True when ``cfg`` routes tile cycles through a *sharded* grid (i.e.
    a crossbar mesh will claim healthy devices).  Used by the training
    engines to reject conflicting data-parallel meshes."""
    if not cfg.grid_routed:
        return False
    gr, gc = cfg.tile_grid
    return _n_healthy() >= gr * gc


def _block_key(key: Array, flat_index, n_blocks: int) -> Array:
    """Per-block read key: ``fold_in(key, i * grid_cols + j)``.

    The (1, 1) grid keeps the caller's key untouched so a trivial grid is
    bit-identical to the plain single-tile path.
    """
    if n_blocks == 1:
        return key
    return jax.random.fold_in(key, flat_index)


def _replicated(mesh, *arrays):
    """Pin arrays at a shard_map boundary to an explicit replicated layout.

    Pinning BOTH the operands entering a shard_map and its outputs to the
    replicated NamedSharding keeps the partitioner from spreading the
    digital glue around a grid cycle (the analog bias column concat,
    ``jnp.tile`` replica broadcasts, im2col slice-concats over a previous
    read's output) across the mesh.  Left free, it does, and reorders that
    glue: on jax 0.9 the sharded gradients then differ from the serial
    oracle by one ulp in a few percent of the elements, which breaks the
    grid's bitwise sharded == serial contract.  (On jax 0.4.37 the same
    operands were miscompiled outright — scaled by the size of mesh axes
    unmentioned in the in_spec.)  The constraint is a no-op for
    already-replicated values.  (Pinned by the jit parity cases in
    tests/test_tile_grid.py and the stage-chain case there.)
    """
    from jax.sharding import NamedSharding, PartitionSpec as P
    s = NamedSharding(mesh, P())
    return tuple(jax.lax.with_sharding_constraint(a, s) for a in arrays)


# ---------------------------------------------------------------------------
# Raw grid read (one physical read per sub-tile, clip before digital sum)
# ---------------------------------------------------------------------------

def grid_analog_mvm_reference(w: Array, x: Array, key: Array, cfg: RPUConfig,
                              grid: Optional[TileGrid] = None, *,
                              transpose: bool = False, row_offset=None,
                              total_rows: Optional[int] = None
                              ) -> Tuple[Array, Array]:
    """Serial single-device oracle of the sharded grid read.

    Iterates the sub-tile grid in row-major block order; block ``(i, j)``
    performs one raw analog read (``tile.analog_mvm`` — noise, clip, and
    any residual intra-block physical split) with its fold_in key.  Partial
    outputs accumulate over the contraction blocks in index order (the same
    left-fold order the mesh psum applies) and the saturation flag is the
    OR over every block.  ``row_offset``/``total_rows`` follow the
    streaming-chunk contract of ``tile.analog_mvm`` per block read.
    """
    g = grid if grid is not None else TileGrid.for_tile(w.shape, cfg)
    wp = g.pad_w(w)
    br, bc = g.block_rows, g.block_cols
    if transpose:
        x = g.pad_last(x, g.rows_pad)
        out_dim, n_out, n_in = g.cols, g.grid_cols, g.grid_rows
    else:
        x = g.pad_last(x, g.cols_pad)
        out_dim, n_out, n_in = g.rows_phys, g.grid_rows, g.grid_cols

    out_chunks = []
    sat = None
    for o in range(n_out):
        y_o = None
        for k in range(n_in):
            i, j = (k, o) if transpose else (o, k)
            wb = wp[i * br:(i + 1) * br, j * bc:(j + 1) * bc]
            xin = x[..., k * (br if transpose else bc):
                    (k + 1) * (br if transpose else bc)]
            bk = _block_key(key, i * g.grid_cols + j, g.n_blocks)
            yb, satb = tile_lib.analog_mvm(wb, xin, bk, cfg,
                                           transpose=transpose,
                                           row_offset=row_offset,
                                           total_rows=total_rows)
            y_o = yb if y_o is None else y_o + yb
            sat = satb if sat is None else jnp.logical_or(sat, satb)
        out_chunks.append(y_o)
    y = jnp.concatenate(out_chunks, axis=-1)[..., :out_dim]
    return y, sat


def grid_analog_mvm_sharded(w: Array, x: Array, key: Array, cfg: RPUConfig,
                            grid: Optional[TileGrid] = None, *,
                            transpose: bool = False, row_offset=None,
                            total_rows: Optional[int] = None
                            ) -> Tuple[Array, Array]:
    """One shard round of the raw grid read on the crossbar mesh.

    Device ``(i, j)`` reads its local sub-tile, the clipped partials are
    psum'd along the contraction mesh axis, and the per-vector saturation
    flag is OR-reduced (as a psum of counts) over *both* axes so every
    device returns the identical global flag.  A streaming chunk
    (``row_offset``/``total_rows``) is one shard round like any other read
    — one psum per chunk round, with the chunk's noise counters offset so
    the round is bit-identical to the same rows of an unchunked round.
    """
    from jax.sharding import PartitionSpec as P

    g = grid if grid is not None else TileGrid.for_tile(w.shape, cfg)
    wp = g.pad_w(w)
    x = g.pad_last(x, g.rows_pad if transpose else g.cols_pad)
    contract_ax = "array_row" if transpose else "array_col"
    out_ax = "array_col" if transpose else "array_row"
    out_dim = g.cols if transpose else g.rows_phys
    gc = g.grid_cols
    n_blocks = g.n_blocks
    kd = jax.random.key_data(key)
    ro = jnp.asarray(0 if row_offset is None else row_offset, jnp.uint32)

    def body(wl, xl, kdl, rol):
        k = jax.random.wrap_key_data(kdl)
        i = jax.lax.axis_index("array_row")
        j = jax.lax.axis_index("array_col")
        bk = _block_key(k, i * gc + j, n_blocks)
        yb, satb = tile_lib.analog_mvm(
            wl, xl, bk, cfg, transpose=transpose,
            row_offset=None if row_offset is None else rol,
            total_rows=total_rows)
        y = jax.lax.psum(yb, contract_ax)
        sat = jax.lax.psum(satb.astype(jnp.int32),
                           ("array_row", "array_col")) > 0
        return y, sat

    bdims = x.ndim - 1
    in_specs = (P("array_row", "array_col"),
                P(*([None] * bdims), contract_ax),
                P(), P())
    out_specs = (P(*([None] * bdims), out_ax), P(*([None] * bdims)))
    mesh = g.mesh()
    f = jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                      out_specs=out_specs, check_vma=False)
    y, sat = _replicated(mesh, *f(*_replicated(mesh, wp, x, kd, ro)))
    return y[..., :out_dim], sat


def grid_analog_mvm(w: Array, x: Array, key: Array, cfg: RPUConfig,
                    grid: Optional[TileGrid] = None, *,
                    transpose: bool = False, row_offset=None,
                    total_rows: Optional[int] = None) -> Tuple[Array, Array]:
    """Raw grid read: sharded when the mesh fits on the local devices,
    otherwise the (numerically identical) serial oracle."""
    g = grid if grid is not None else TileGrid.for_tile(w.shape, cfg)
    fn = grid_analog_mvm_sharded if g.sharded() else grid_analog_mvm_reference
    return fn(w, x, key, cfg, g, transpose=transpose, row_offset=row_offset,
              total_rows=total_rows)


# ---------------------------------------------------------------------------
# Managed grid read (NM / BM composition over shard rounds)
# ---------------------------------------------------------------------------

def grid_managed_mvm(w: Array, x: Array, key: Array, cfg: RPUConfig, *,
                     transpose: bool = False, backward: bool = False,
                     force_reference: bool = False, row_offset=None,
                     total_rows: Optional[int] = None) -> Tuple[Array, Array]:
    """Managed (NM + BM) read over the tile grid.

    Reuses ``management.with_management`` verbatim with the grid read as
    the raw physical MVM: the NM scale is computed exactly once from the
    global (unchunked) input, and because the grid read returns the
    *globally* OR-reduced saturation flag, every BM decision is identical
    on all devices — two-phase BM lowers to two synchronized shard rounds,
    iterative BM to a while_loop of rounds with a mesh-uniform trip count.

    ``force_reference`` pins the serial oracle even when a mesh is
    available (used by the parity tests).  Returns ``(y_phys,
    residual_sat)`` on physical output channels, like
    ``tile.managed_mvm_reference``.
    """
    g = TileGrid.for_tile(w.shape, cfg)
    serial = force_reference or not g.sharded()
    fn = grid_analog_mvm_reference if serial else grid_analog_mvm_sharded

    def raw(xx, kk):
        return fn(w, xx, kk, cfg, g, transpose=transpose,
                  row_offset=row_offset, total_rows=total_rows)

    return management.with_management(raw, x, key, cfg, backward=backward)


def grid_tile_forward(state: tile_lib.TileState, x: Array, key: Array,
                      cfg: RPUConfig, *, return_sat: bool = False,
                      row_offset=None, total_rows: Optional[int] = None):
    """Forward cycle on the sharded grid (replica average in the digital
    domain, after the gathered read) — grid counterpart of
    ``tile.tile_forward``."""
    y_phys, sat = grid_managed_mvm(state.w, x, key, cfg, transpose=False,
                                   backward=False, row_offset=row_offset,
                                   total_rows=total_rows)
    y = tile_lib._replica_mean(y_phys, cfg.devices_per_weight)
    return (y, sat) if return_sat else y


def grid_tile_backward(state: tile_lib.TileState, delta: Array, key: Array,
                       cfg: RPUConfig, *, return_sat: bool = False,
                       row_offset=None, total_rows: Optional[int] = None):
    """Backward (transpose) cycle on the grid; ``delta`` must already carry
    the ``#_d``-replicated physical row layout (``tile.replicate_delta``)."""
    z, sat = grid_managed_mvm(state.w, delta, key, cfg, transpose=True,
                              backward=True, row_offset=row_offset,
                              total_rows=total_rows)
    d = cfg.devices_per_weight
    if d > 1:
        z = z / d
    return (z, sat) if return_sat else z


# ---------------------------------------------------------------------------
# Communication-free sharded pulse update
# ---------------------------------------------------------------------------

def _ctoc_noise(key: Array, shape, cfg: RPUConfig) -> Array:
    if cfg.fast_rng:
        from repro.utils import fastrng
        return fastrng.normal(key, shape, dtype=cfg.dtype)
    return jax.random.normal(key, shape, dtype=cfg.dtype)


def _pad_maps(maps: DeviceMaps, g: TileGrid) -> DeviceMaps:
    """Pad device maps to the block grid: zero dw (padded devices never
    move) and unit bound (clips the padded zeros to zero)."""
    pr, pc = g.rows_pad - g.rows_phys, g.cols_pad - g.cols
    if pr == 0 and pc == 0:
        return maps
    pad = ((0, pr), (0, pc))
    return DeviceMaps(dw_up=jnp.pad(maps.dw_up, pad),
                      dw_dn=jnp.pad(maps.dw_dn, pad),
                      bound=jnp.pad(maps.bound, pad, constant_values=1.0))


def _block_finalize(wl, upl, dnl, bndl, cup, cdn, bk, cfg):
    """Apply one block's accumulated coincidence counts: maps + ctoc noise
    (per-block fold_in key) + per-device bound clip."""
    dw = cup * upl - cdn * dnl
    if cfg.dw_min_ctoc > 0.0:
        var = cup * upl ** 2 + cdn * dnl ** 2
        dw = dw + cfg.dw_min_ctoc * jnp.sqrt(var) * _ctoc_noise(
            bk, dw.shape, cfg)
    return jnp.clip(wl + dw.astype(cfg.dtype), -bndl, bndl)


def _block_update(wl, upl, dnl, bndl, rows_l, cols_l, bk, cfg):
    """One sub-tile's update: local coincidence contraction + maps + ctoc
    noise + per-device bound clip.  Pure block-local math (no collectives)."""
    up, dn = update_lib.coincidence_counts(rows_l, cols_l)
    return _block_finalize(wl, upl, dnl, bndl, up, dn, bk, cfg)


def grid_pulse_update(w: Array, maps: DeviceMaps, x: Array, delta: Array,
                      key: Array, cfg: RPUConfig, lr: float, *,
                      force_reference: bool = False) -> Array:
    """Grid update cycle: each shard consumes its slice of the row/col
    pulse streams — zero inter-device communication.

    The streams are sampled once for the full (padded) row/column drivers
    with the global UM gains; block ``(i, j)`` then contracts row slice
    ``i`` against column slice ``j`` — bit-identical to slicing the full
    coincidence matmul, so the sharded and serial paths agree exactly
    (cycle-to-cycle noise uses the per-block fold_in keys on both).
    ``delta`` must already carry the physical (replicated) row layout.

    With ``cfg.update_chunk`` each device loops the chunked contraction
    axis locally (``_grid_update_chunked_*``): per chunk it samples the
    chunk's streams (counter-offset, so the draws equal the materialized
    rows') and accumulates its block's integer counts; maps/ctoc/clip land
    once at the end — bit-identical to the one-shot grid cycle with zero
    extra collectives.
    """
    g = TileGrid.for_tile(w.shape, cfg)
    if x.ndim == 1:
        x, delta = x[None], delta[None]
    k_a, k_b, k_c = jax.random.split(key, 3)
    cx, cd = update_lib.um_factors(x, delta, cfg, lr)
    xp = g.pad_last(x, g.cols_pad)
    dp = g.pad_last(delta, g.rows_pad)
    wp, mp = g.pad_w(w), _pad_maps(maps, g)
    serial = force_reference or not g.sharded()

    t = int(np.prod(x.shape[:-1]))
    if cfg.update_chunk is not None and cfg.update_chunk < t:
        # The chunked cycle is the streamed machinery with the simplest
        # possible chunk source: row slices of the (already col-padded)
        # materialized vectors.  Streams sampled per chunk with the
        # counter offset equal the materialized rows' draws exactly.
        chunk = cfg.update_chunk
        x2, d2, nchunks = _pad_chunk_rows(xp.reshape(t, g.cols_pad),
                                          dp.reshape(t, g.rows_pad), chunk)

        def get_padded(s, start, n):
            return (jax.lax.dynamic_slice_in_dim(s[0], start, n),
                    jax.lax.dynamic_slice_in_dim(s[1], start, n))

        fn = (_grid_update_streamed_serial if serial
              else _grid_update_streamed_sharded)
        new_w = fn(wp, mp, (x2, d2), get_padded, cx, cd, k_a, k_b, k_c,
                   cfg, g, chunk, nchunks)
        return new_w[:g.rows_phys, :g.cols]

    cols_s = update_lib.sample_signed_streams(k_a, xp, cx, cfg.bl,
                                              cfg.fast_rng)
    rows_s = update_lib.sample_signed_streams(k_b, dp, cd, cfg.bl,
                                              cfg.fast_rng)

    if serial:
        new_w = _grid_update_reference(wp, mp, rows_s, cols_s, k_c, cfg, g)
    else:
        new_w = _grid_update_sharded(wp, mp, rows_s, cols_s, k_c, cfg, g)
    return new_w[:g.rows_phys, :g.cols]


def _pad_chunk_rows(x2, d2, chunk):
    t = x2.shape[0]
    nchunks = -(-t // chunk)
    pad = nchunks * chunk - t
    return (jnp.pad(x2, ((0, pad), (0, 0))),
            jnp.pad(d2, ((0, pad), (0, 0))), nchunks)


def grid_pulse_update_streamed(w: Array, maps: DeviceMaps, src, get_chunk,
                               key: Array, cfg: RPUConfig, lr: float, *,
                               total: int, chunk: int, um_maxima=None,
                               force_reference: bool = False) -> Array:
    """Grid update cycle over *generated* chunks (the streaming conv path):
    ``get_chunk(src, start, chunk) -> (cols, delta_phys)`` materializes one
    chunk of logical columns + replicated error rows; rows past ``total``
    must be zeroed.  Mirrors ``grid_pulse_update``'s chunked branch with
    the gather inside each (per-device) chunk round — bit-identical to the
    materialized grid cycle, zero collectives in the update."""
    g = TileGrid.for_tile(w.shape, cfg)
    k_a, k_b, k_c = jax.random.split(key, 3)
    cx, cd = management.um_factors_from_maxima(um_maxima, cfg, lr)
    wp, mp = g.pad_w(w), _pad_maps(maps, g)

    def get_padded(s, start, n):
        cols, delta = get_chunk(s, start, n)
        return (g.pad_last(cols, g.cols_pad), g.pad_last(delta, g.rows_pad))

    nchunks = -(-total // chunk)
    serial = force_reference or not g.sharded()
    fn = (_grid_update_streamed_serial if serial
          else _grid_update_streamed_sharded)
    new_w = fn(wp, mp, src, get_padded, cx, cd, k_a, k_b, k_c, cfg, g,
               chunk, nchunks)
    return new_w[:g.rows_phys, :g.cols]


def _gen_chunk_streams(src, get_padded, cx, cd, k_a, k_b, cfg, chunk, start):
    """Sample one generated chunk's signed streams (padded layout, counter
    offset ``start`` rows)."""
    cols, delta = get_padded(src, start, chunk)
    a = update_lib.sample_signed_streams(k_a, cols, cx, cfg.bl, cfg.fast_rng,
                                         row_offset=start)
    b = update_lib.sample_signed_streams(k_b, delta, cd, cfg.bl,
                                         cfg.fast_rng, row_offset=start)
    return b, a


def _grid_update_streamed_serial(wp, mp, src, get_padded, cx, cd, k_a, k_b,
                                 k_c, cfg, g: TileGrid, chunk: int,
                                 nchunks: int):
    """Serial oracle of the chunked/streamed grid update: accumulate the
    full padded count matrices over generated chunks, then finalize per
    block (slicing the full counts equals each block's local contraction —
    integer sums)."""
    def body(c, carry):
        up, dn = carry
        b, a = _gen_chunk_streams(src, get_padded, cx, cd, k_a, k_b, cfg,
                                  chunk, c * chunk)
        u, d_ = update_lib.coincidence_counts(b, a)
        return up + u, dn + d_

    zeros = jnp.zeros((g.rows_pad, g.cols_pad), jnp.float32)
    cup, cdn = jax.lax.fori_loop(0, nchunks, body, (zeros, zeros))
    return _finalize_blocks(wp, mp, cup, cdn, k_c, cfg, g)


def _finalize_blocks(wp, mp, cup, cdn, k_c, cfg, g: TileGrid):
    """Per-block finalize of full padded count matrices (serial)."""
    br, bc = g.block_rows, g.block_cols
    rows_out = []
    for i in range(g.grid_rows):
        cols_out = []
        for j in range(g.grid_cols):
            blk = (slice(i * br, (i + 1) * br), slice(j * bc, (j + 1) * bc))
            bk = _block_key(k_c, i * g.grid_cols + j, g.n_blocks)
            cols_out.append(_block_finalize(
                wp[blk], mp.dw_up[blk], mp.dw_dn[blk], mp.bound[blk],
                cup[blk], cdn[blk], bk, cfg))
        rows_out.append(jnp.concatenate(cols_out, axis=1))
    return jnp.concatenate(rows_out, axis=0)


def _grid_update_streamed_sharded(wp, mp, src, get_padded, cx, cd, k_a, k_b,
                                  k_c, cfg, g: TileGrid, chunk: int,
                                  nchunks: int):
    """Sharded streamed grid update: per-device chunk loops — each device
    generates every chunk from the (replicated) source volume, samples its
    streams, contracts only its block's slices, finalizes once."""
    from jax.sharding import PartitionSpec as P

    gc, n_blocks = g.grid_cols, g.n_blocks
    br, bc = g.block_rows, g.block_cols
    ka_d = jax.random.key_data(k_a)
    kb_d = jax.random.key_data(k_b)
    kc_d = jax.random.key_data(k_c)
    src_flat, src_tree = jax.tree_util.tree_flatten(src)
    n_src = len(src_flat)

    def body(wl, upl, dnl, bndl, cxl, cdl, kad, kbd, kcd, *src_l):
        ka = jax.random.wrap_key_data(kad)
        kb = jax.random.wrap_key_data(kbd)
        kc = jax.random.wrap_key_data(kcd)
        s = jax.tree_util.tree_unflatten(src_tree, src_l)
        i = jax.lax.axis_index("array_row")
        j = jax.lax.axis_index("array_col")

        def chunk_body(c, carry):
            up, dn = carry
            b, a = _gen_chunk_streams(s, get_padded, cxl, cdl, ka, kb, cfg,
                                      chunk, c * chunk)
            b_loc = jax.lax.dynamic_slice_in_dim(b, i * br, br, axis=-1)
            a_loc = jax.lax.dynamic_slice_in_dim(a, j * bc, bc, axis=-1)
            u, d_ = update_lib.coincidence_counts(b_loc, a_loc)
            return up + u, dn + d_

        zeros = jnp.zeros((br, bc), jnp.float32)
        cup, cdn = jax.lax.fori_loop(0, nchunks, chunk_body, (zeros, zeros))
        bk = _block_key(kc, i * gc + j, n_blocks)
        return _block_finalize(wl, upl, dnl, bndl, cup, cdn, bk, cfg)

    blockspec = P("array_row", "array_col")
    in_specs = ((blockspec,) * 4 + (P(),) * (5 + n_src))
    mesh = g.mesh()
    f = jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                      out_specs=blockspec, check_vma=False)
    (new_w,) = _replicated(mesh, f(*_replicated(
        mesh, wp, mp.dw_up, mp.dw_dn, mp.bound, jnp.asarray(cx),
        jnp.asarray(cd), ka_d, kb_d, kc_d, *src_flat)))
    return new_w


def _grid_update_reference(wp, mp, rows_s, cols_s, k_c, cfg, g: TileGrid):
    br, bc = g.block_rows, g.block_cols
    rows_out = []
    for i in range(g.grid_rows):
        cols_out = []
        for j in range(g.grid_cols):
            blk = (slice(i * br, (i + 1) * br), slice(j * bc, (j + 1) * bc))
            bk = _block_key(k_c, i * g.grid_cols + j, g.n_blocks)
            cols_out.append(_block_update(
                wp[blk], mp.dw_up[blk], mp.dw_dn[blk], mp.bound[blk],
                rows_s[..., i * br:(i + 1) * br],
                cols_s[..., j * bc:(j + 1) * bc], bk, cfg))
        rows_out.append(jnp.concatenate(cols_out, axis=1))
    return jnp.concatenate(rows_out, axis=0)


def _grid_update_sharded(wp, mp, rows_s, cols_s, k_c, cfg, g: TileGrid):
    from jax.sharding import PartitionSpec as P

    gc, n_blocks = g.grid_cols, g.n_blocks
    kd = jax.random.key_data(k_c)
    bdims = rows_s.ndim - 1

    def body(wl, upl, dnl, bndl, rl, cl, kdl):
        k = jax.random.wrap_key_data(kdl)
        i = jax.lax.axis_index("array_row")
        j = jax.lax.axis_index("array_col")
        bk = _block_key(k, i * gc + j, n_blocks)
        return _block_update(wl, upl, dnl, bndl, rl, cl, bk, cfg)

    blockspec = P("array_row", "array_col")
    in_specs = (blockspec, blockspec, blockspec, blockspec,
                P(*([None] * bdims), "array_row"),
                P(*([None] * bdims), "array_col"),
                P())
    mesh = g.mesh()
    f = jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                      out_specs=blockspec, check_vma=False)
    (new_w,) = _replicated(mesh, f(*_replicated(
        mesh, wp, mp.dw_up, mp.dw_dn, mp.bound, rows_s, cols_s, kd)))
    return new_w
