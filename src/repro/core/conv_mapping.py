"""Conv -> crossbar mapping (the paper's contribution C1), streamed.

A convolutional layer with kernels ``(M, k, k, d)`` is flattened to a
parameter matrix ``K`` of size ``M x (k^2 d [+1 bias])``; the input volume is
rearranged into the im2col matrix ``X (k^2 d x positions)`` so that

    forward   Y = K X            (repeat the MVM for each position column)
    backward  Z = K^T D          (then digital col2im scatter-add)
    update    K <- K + eta D X^T (serial rank-1 pulse updates per column)

The paper streams the position columns *serially* through the array; the
analog path here does the same digitally: a custom-VJP driver walks the
``batch x positions`` axis in chunks of ``cfg.conv_stream_chunk`` columns
and feeds each chunk through the three cycles without ever materializing
the full ``(B, H', W', C k^2)`` patch matrix or the ``~BL x`` larger signed
pulse-stream tensors — only one chunk of columns/streams is live at a time:

* **forward** — each chunk is gathered from the activation volume and read
  through ``tile.tile_forward`` with the chunk's global row offset, so the
  noise/NM/BM draws are bit-identical to the one-shot managed read (NM/BM
  scales are per-column; counter-offset fastrng supplies the chunk's rows'
  exact noise).  Under ``cfg.use_pallas`` the implicit-im2col kernel
  (``kernels/conv_mvm.py``) gathers the patch tiles in VMEM instead.
* **backward** — transpose-read chunks scatter-add into the volume
  cotangent through a *deterministic* col2im whose per-pixel accumulation
  order (descending tap) is invariant to the chunk size, so chunked and
  materialized backward cycles agree bit-for-bit.
* **update** — per-chunk coincidence counts accumulate exactly (integer
  sums over the contraction axis); device maps, cycle-to-cycle noise and
  the per-device bound clip land once at the end, exactly where the
  materialized cycle applies them (``update.pulse_update_streamed``).

``conv_stream_chunk=None`` runs a single chunk — the materialized path.
Its columns are the ``kh*kw`` static strided tap slices of the padded
volume, and its col2im adds each tap back as one padded volume
(``lax.pad``, the slice's transpose): pure data movement with no index
gather or scatter, and bit-identical to them (``gather_columns``,
``col2im_add``).  Smaller chunks gather and scatter-add by index.  The
single chunk is the bit-parity oracle for every chunked configuration with a
fixed-latency BM mode (off / two-phase; tests/test_conv_stream.py).  The
one exception is *iterative* BM with read noise: its halve-and-retry
while_loop decides re-reads from the whole call batch, so chunked loops
become chunk-local — per-vector retry scales are unchanged and results
are distribution-identical (bit-exact when noise-free), but not bitwise
equal to the materialized run.  ``mode='digital'`` keeps the
differentiable im2col + FP dense path.

Supports stride, padding (named or explicit per-dim pairs), dilation and
non-square inputs/kernels, as the paper notes the mapping generalises to.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import analog_linear, management
from repro.core import tile as tile_lib
from repro.core import update as update_lib
from repro.core.device import RPUConfig, sample_device_maps
from repro.core.tile import TileState
from repro.kernels import ops as kops
from repro.kernels.bwd_update_mvm import conv_bwd_update_eligible
from repro.kernels.conv_mvm import conv_kernel_eligible

Array = jax.Array
IntPair = Union[int, Tuple[int, int]]
Padding = Union[str, Sequence[Tuple[int, int]]]


def _pair(v: IntPair) -> Tuple[int, int]:
    return (v, v) if isinstance(v, int) else tuple(v)


def im2col_patches(x: Array, kernel: IntPair, stride: IntPair = 1,
                   padding: str = "VALID", dilation: IntPair = 1) -> Array:
    """Reference im2col via ``conv_general_dilated_patches`` (the seed
    implementation).  Kept as the correctness oracle for :func:`im2col`
    and for the engine benchmark's legacy-path reconstruction; do not use
    on the hot path — it contracts against a ``C*kh*kw``-channel identity
    kernel and its transpose dominates the backward cycle on CPU."""
    kh, kw = _pair(kernel)
    sh, sw = _pair(stride)
    dh, dw = _pair(dilation)
    return jax.lax.conv_general_dilated_patches(
        x, filter_shape=(kh, kw), window_strides=(sh, sw), padding=padding,
        rhs_dilation=(dh, dw),
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def im2col(x: Array, kernel: IntPair, stride: IntPair = 1,
           padding: Union[str, Sequence[Tuple[int, int]]] = "VALID",
           dilation: IntPair = 1) -> Array:
    """Extract convolution patches.

    ``x``: (B, H, W, C) -> patches (B, H', W', C*kh*kw); feature order is
    channel-major (C outer, then kh, kw) — the same order the parameter
    matrix uses, and identical to what
    ``jax.lax.conv_general_dilated_patches`` produces with NHWC specs.

    Implemented as ``kh*kw`` strided slices + stack rather than the
    dilated-patches conv (which contracts against a ``C*kh*kw``-channel
    identity kernel — O(C^2 k^4) multiply work, and its transpose dominates
    the backward cycle on CPU).  Slicing is pure data movement, and its
    autodiff transpose is a cheap pad-and-add col2im.
    """
    geom = conv_geometry(x.shape, kernel, stride, padding, dilation,
                         bias=False)
    cols = _tap_columns(_pad_volume(x, geom), geom)
    return cols.reshape(geom.b, geom.oh, geom.ow, geom.features)


def kernel_matrix_from_conv(kernels: Array) -> Array:
    """(kh, kw, C, M) HWIO conv kernels -> parameter matrix K (M, C*kh*kw).

    Feature order must match :func:`im2col` (channel-major: index =
    c*kh*kw + ih*kw + iw).
    """
    kh, kw, c, m = kernels.shape
    k = jnp.transpose(kernels, (3, 2, 0, 1))  # (M, C, kh, kw)
    return k.reshape(m, c * kh * kw)


def conv_to_matrix_shapes(out_channels: int, kernel: IntPair,
                          in_channels: int, bias: bool = True
                          ) -> Tuple[int, int]:
    kh, kw = _pair(kernel)
    return out_channels, in_channels * kh * kw + (1 if bias else 0)


def init(key: Array, in_channels: int, out_channels: int, kernel: IntPair,
         cfg: RPUConfig, bias: bool = True,
         init_scale: Optional[float] = None) -> TileState:
    kh, kw = _pair(kernel)
    return analog_linear.init(
        key, in_channels * kh * kw, out_channels, cfg, bias=bias,
        init_scale=init_scale)


# ---------------------------------------------------------------------------
# Static conv geometry (hashable — lives in the custom_vjp nondiff args)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ConvGeom:
    """Resolved static geometry of one conv application.

    ``h``/``w`` are the *padded* input dims (explicit pads resolved from the
    ``padding`` argument with the same arithmetic :func:`im2col` uses, so
    the streamed and materialized paths see identical output shapes).
    """

    kh: int; kw: int
    sh: int; sw: int
    dh: int; dw: int
    pads: Tuple[Tuple[int, int], Tuple[int, int]]   # ((top, bot), (l, r))
    b: int; h: int; w: int; c: int                  # padded volume
    oh: int; ow: int
    bias: bool

    @property
    def positions(self) -> int:
        return self.b * self.oh * self.ow

    @property
    def features(self) -> int:
        return self.c * self.kh * self.kw

    @property
    def cols(self) -> int:
        return self.features + (1 if self.bias else 0)

    @property
    def taps(self):
        """(ih, iw) kernel taps in ascending (row-major) order."""
        return [(ih, iw) for ih in range(self.kh) for iw in range(self.kw)]

    def tap_slice(self, xpad: Array, ih: int, iw: int) -> Array:
        """The (B, OH, OW, C) strided view of the padded volume feeding
        tap ``(ih, iw)`` — one slice of the slice-stack im2col."""
        r0, c0 = ih * self.dh, iw * self.dw
        return jax.lax.slice(
            xpad, (0, r0, c0, 0),
            (self.b, r0 + (self.oh - 1) * self.sh + 1,
             c0 + (self.ow - 1) * self.sw + 1, self.c),
            (1, self.sh, self.sw, 1))


def conv_geometry(x_shape: Tuple[int, ...], kernel: IntPair,
                  stride: IntPair = 1, padding: Padding = "VALID",
                  dilation: IntPair = 1, bias: bool = True) -> ConvGeom:
    """Resolve the static geometry (same padding arithmetic as im2col)."""
    kh, kw = _pair(kernel)
    sh, sw = _pair(stride)
    dh, dw = _pair(dilation)
    b, h, w, c = x_shape
    ekh, ekw = (kh - 1) * dh + 1, (kw - 1) * dw + 1
    if not isinstance(padding, str):
        (pt, pb), (pl, pr) = ((int(a), int(b_)) for a, b_ in padding)
    elif padding.upper() == "SAME":
        oh, ow = -(-h // sh), -(-w // sw)
        ph = max(0, (oh - 1) * sh + ekh - h)
        pw = max(0, (ow - 1) * sw + ekw - w)
        pt, pb, pl, pr = ph // 2, ph - ph // 2, pw // 2, pw - pw // 2
    elif padding.upper() == "VALID":
        pt = pb = pl = pr = 0
    else:
        raise ValueError(f"unsupported padding {padding!r}")
    hp, wp = h + pt + pb, w + pl + pr
    oh, ow = (hp - ekh) // sh + 1, (wp - ekw) // sw + 1
    return ConvGeom(kh=kh, kw=kw, sh=sh, sw=sw, dh=dh, dw=dw,
                    pads=((pt, pb), (pl, pr)), b=b, h=hp, w=wp, c=c,
                    oh=oh, ow=ow, bias=bias)


def _pad_volume(x: Array, geom: ConvGeom) -> Array:
    (pt, pb), (pl, pr) = geom.pads
    if pt == pb == pl == pr == 0:
        return x
    return jnp.pad(x, ((0, 0), (pt, pb), (pl, pr), (0, 0)))


def _position_indices(geom: ConvGeom, start, chunk: int):
    """Decompose positions ``[start, start + chunk)`` into (b, i, j) plus
    the validity mask (rows past the last position are clamped + masked)."""
    p = jnp.asarray(start, jnp.int32) + jnp.arange(chunk, dtype=jnp.int32)
    valid = p < geom.positions
    p = jnp.minimum(p, geom.positions - 1)
    per_img = geom.oh * geom.ow
    b_idx = p // per_img
    r = p - b_idx * per_img
    return b_idx, r // geom.ow, r % geom.ow, valid


@jax.named_scope("im2col")
def gather_columns(xpad: Array, geom: ConvGeom, start, chunk: int) -> Array:
    """Materialize one chunk of im2col columns ``(chunk, cols)`` from the
    padded activation volume (channel-major feature order, bias ones
    appended) — the only patch storage the streaming path ever creates.

    A chunk that covers every position (``chunk == geom.positions``, the
    materialized path) is built from the ``kh*kw`` static strided tap
    slices, as :func:`im2col` builds it; ``start`` is then 0 and ignored.
    A smaller chunk gathers its rows by index (rows past the last position
    are zero: they drive nothing).  Both are pure data movement, so the two
    paths give the same matrix bit for bit; the slices avoid an
    element-by-element XLA gather."""
    if chunk == geom.positions:
        g, valid = _tap_columns(xpad, geom), None
    else:
        b_idx, i, j, valid = _position_indices(geom, start, chunk)
        rowi = (i[:, None, None] * geom.sh                 # (chunk, kh, 1)
                + (np.arange(geom.kh) * geom.dh)[None, :, None])
        coli = (j[:, None, None] * geom.sw                 # (chunk, 1, kw)
                + (np.arange(geom.kw) * geom.dw)[None, None, :])
        g = xpad[b_idx[:, None, None], rowi, coli, :]      # (chunk, kh, kw, C)
        g = jnp.moveaxis(g, -1, 1).reshape(chunk, geom.features)
    if geom.bias:
        g = jnp.concatenate([g, jnp.ones((chunk, 1), g.dtype)], axis=1)
    return g if valid is None else jnp.where(valid[:, None], g, 0)


def _tap_columns(xpad: Array, geom: ConvGeom) -> Array:
    """Every position's patch row ``(positions, features)`` from the
    ``kh*kw`` strided tap slices: tap ``(ih, iw)`` of channel ``c`` lands
    in feature ``c*kh*kw + ih*kw + iw``.  A single channel is dropped
    before stacking, so no size-1 axis sits beside the tap axis."""
    p = geom.positions
    taps = [geom.tap_slice(xpad, ih, iw) for ih, iw in geom.taps]
    if geom.c == 1:
        return jnp.stack([t.reshape(p) for t in taps], axis=-1)
    return jnp.stack(taps, axis=-1).reshape(p, geom.features)


def window_absmax(xpad: Array, geom: ConvGeom) -> Array:
    """Per-position ``max|patch row|`` (over channels and taps) computed as
    a running max over the kh*kw strided slices — no patch materialization,
    order-exact (max is associative), shape (B, OH, OW)."""
    m = None
    for ih, iw in geom.taps:
        s = jnp.max(jnp.abs(geom.tap_slice(xpad, ih, iw)), axis=-1)
        m = s if m is None else jnp.maximum(m, s)
    return m


def _rounded(z: Array) -> Array:
    """``z`` itself (a NaN stays a NaN), behind a select that compilers do
    not see through.  XLA fuses the op that produced ``z`` into the
    col2im adds; where that op is a multiply, a backend may contract the
    pair into one fused multiply-add, which rounds once where the index
    scatter-add (whose updates are stored first) rounds twice."""
    return jnp.where(jnp.isnan(z), jnp.nan, z)


@jax.named_scope("col2im")
def col2im_add(z: Array, geom: ConvGeom, start, chunk: int,
               xbar: Array) -> Array:
    """Add one chunk's transpose-read columns ``(chunk, features)`` into
    the padded volume cotangent.

    Taps are applied in DESCENDING order: a pixel's contributing positions
    are strictly decreasing in tap order, so ascending-chunk x
    descending-tap accumulation visits every pixel's contributions in
    global descending-tap order *regardless of the chunk size* — chunked
    and materialized backward cycles are bit-identical (f32 addition is
    not associative; a chunk-dependent order would drift ulps).

    A chunk that covers every position adds each tap as one interior- and
    edge-padded volume (``lax.pad``, the transpose of
    :meth:`ConvGeom.tap_slice`) instead of a scatter-add.  It is exact:
    within one tap no two positions share a pixel, and ``xbar`` starts at
    +0.0 and so never holds -0.0, which makes adding 0 to the pixels the
    tap misses an identity.  A smaller chunk scatter-adds by index.
    """
    if chunk == geom.positions:
        z4 = _rounded(z).reshape(geom.positions, geom.c, geom.kh, geom.kw)
        zt = jnp.moveaxis(z4, 1, -1)                     # (P, kh, kw, C)
        zero = jnp.zeros((), z.dtype)
        for ih, iw in reversed(geom.taps):
            r0, c0 = ih * geom.dh, iw * geom.dw
            hi_r = geom.h - r0 - (geom.oh - 1) * geom.sh - 1
            hi_c = geom.w - c0 - (geom.ow - 1) * geom.sw - 1
            z_tap = zt[:, ih, iw].reshape(geom.b, geom.oh, geom.ow, geom.c)
            xbar = xbar + jax.lax.pad(
                z_tap, zero,
                ((0, 0, 0), (r0, hi_r, geom.sh - 1),
                 (c0, hi_c, geom.sw - 1), (0, 0, 0)))
        return xbar
    b_idx, i, j, valid = _position_indices(geom, start, chunk)
    z3 = jnp.where(valid[:, None], z, 0).reshape(
        chunk, geom.c, geom.kh, geom.kw)
    for ih, iw in reversed(geom.taps):
        xbar = xbar.at[b_idx, i * geom.sh + ih * geom.dh,
                       j * geom.sw + iw * geom.dw, :].add(
            z3[:, :, ih, iw], mode="drop")
    return xbar


# ---------------------------------------------------------------------------
# Streaming three-cycle driver (the analog path's custom VJP)
# ---------------------------------------------------------------------------

def _chunking(cfg: RPUConfig, geom: ConvGeom) -> Tuple[int, int]:
    total = geom.positions
    chunk = cfg.conv_stream_chunk or total
    chunk = max(1, min(chunk, total))
    return chunk, -(-total // chunk)


def _conv_nm_scale(xpad: Array, geom: ConvGeom) -> Array:
    """Per-position NM scale ``(positions, 1)`` — ``management.nm_scale``
    of the (never materialized) column rows, from the running window max.
    Order-exact: ``max`` commutes, so this equals the materialized scale
    bit-for-bit (the bias contributes a constant 1 to every row max)."""
    s = window_absmax(xpad, geom).reshape(geom.positions, 1)
    if geom.bias:
        return jnp.maximum(s, jnp.asarray(1.0, s.dtype))
    return jnp.where(s > management._EPS, s, 1.0)


@jax.named_scope("forward")
def _stream_forward(cfg: RPUConfig, geom: ConvGeom, w: Array, x: Array,
                    k_f: Array) -> Array:
    """Forward cycle: managed reads over position-column chunks."""
    xpad = _pad_volume(x, geom)
    total = geom.positions
    chunk, nchunks = _chunking(cfg, geom)
    state = TileState(w=w, maps=None, seed=k_f)  # maps unused in reads

    if conv_kernel_eligible(cfg, geom, w.shape):
        nm_s = (_conv_nm_scale(xpad, geom) if cfg.nm_active(backward=False)
                else jnp.ones((total, 1), x.dtype))
        args = management.launch_args(cfg, k_f, backward=False, nm_s=nm_s)
        y2, _ = kops.conv_managed_mvm(w, xpad, geom, args, cfg)
        return y2.reshape(geom.b, geom.oh, geom.ow, -1)

    out_f = w.shape[0] // cfg.devices_per_weight

    def body(ci, y):
        start = ci * chunk
        cols = gather_columns(xpad, geom, start, chunk)
        yc = tile_lib.tile_forward(state, cols, k_f, cfg, row_offset=start,
                                   total_rows=total)
        return jax.lax.dynamic_update_slice_in_dim(y, yc, start, axis=0)

    y = jnp.zeros((nchunks * chunk, out_f), x.dtype)
    y = jax.lax.fori_loop(0, nchunks, body, y)
    return y[:total].reshape(geom.b, geom.oh, geom.ow, out_f)


@jax.named_scope("backward")
def _stream_backward(cfg: RPUConfig, geom: ConvGeom, w: Array, g: Array,
                     k_b: Array) -> Array:
    """Backward cycle: transpose-read chunks + deterministic col2im."""
    total = geom.positions
    chunk, nchunks = _chunking(cfg, geom)
    state = TileState(w=w, maps=None, seed=k_b)
    out_f = w.shape[0] // cfg.devices_per_weight
    g2 = g.reshape(total, out_f)
    pad = nchunks * chunk - total
    g2p = jnp.pad(g2, ((0, pad), (0, 0)))

    def body(ci, xbar):
        start = ci * chunk
        gc = jax.lax.dynamic_slice_in_dim(g2p, start, chunk)
        zc = tile_lib.tile_backward(state, gc, k_b, cfg, row_offset=start,
                                    total_rows=total)
        return col2im_add(zc[:, :geom.features], geom, start, chunk, xbar)

    xbar = jnp.zeros((geom.b, geom.h, geom.w, geom.c), g.dtype)
    xbar = jax.lax.fori_loop(0, nchunks, body, xbar)
    (pt, _), (pl, _) = geom.pads
    hp, wp = geom.h - sum(geom.pads[0]), geom.w - sum(geom.pads[1])
    return jax.lax.slice(xbar, (0, pt, pl, 0),
                         (geom.b, pt + hp, pl + wp, geom.c))


@jax.named_scope("update")
def _stream_pulse_w_bar(cfg: RPUConfig, geom: ConvGeom, w, maps, x, g, k_u,
                        lr) -> Array:
    """Update cycle: streamed pulse update over (column, error) chunks;
    ``w_bar = w - clip(w + DW_pulse(cols, -g))`` exactly as the dense
    layer's VJP defines it."""
    xpad = _pad_volume(x, geom)
    total = geom.positions
    chunk, _ = _chunking(cfg, geom)
    d = cfg.devices_per_weight
    out_f = w.shape[0] // d
    g2 = g.reshape(total, out_f)
    pad = (-(-total // chunk)) * chunk - total
    g2p = jnp.pad(g2, ((0, pad), (0, 0)))

    um_maxima = None
    if cfg.update_management:
        x_max = jnp.max(window_absmax(xpad, geom))
        if geom.bias:
            x_max = jnp.maximum(x_max, jnp.asarray(1.0, x_max.dtype))
        um_maxima = (x_max, jnp.max(jnp.abs(-g2)))

    def get_chunk(s, start, ch):
        xp, gp = s
        cols = gather_columns(xp, geom, start, ch)
        gc = jax.lax.dynamic_slice_in_dim(gp, start, ch)
        return cols, tile_lib.replicate_delta(-gc, d)

    new_w = update_lib.pulse_update_streamed(
        w, maps, (xpad, g2p), get_chunk, k_u, cfg, lr, total=total,
        chunk=chunk, um_maxima=um_maxima)
    return (w - new_w).astype(w.dtype)


@functools.partial(jax.jit, static_argnames=("d",))
def _div_replicas(z: Array, d: int) -> Array:
    """``z / d`` with the divisor baked in as a compile-time constant, so
    the fused path rounds exactly like the oracle's in-loop division."""
    return z / d


@jax.named_scope("backward_update")
def _fused_bwd_update(cfg: RPUConfig, geom: ConvGeom, w, maps, x, g, k_b,
                      k_u, lr) -> Tuple[Array, Array]:
    """Backward + update cycles in ONE Pallas launch
    (``kernels.bwd_update_mvm.conv_bwd_update_pallas``) — bit-identical to
    ``_stream_backward`` + ``_stream_pulse_w_bar`` (the separate-launch
    oracle, kept for ineligible shapes and as the parity reference)."""
    xpad = _pad_volume(x, geom)
    total = geom.positions
    d = cfg.devices_per_weight
    out_f = w.shape[0] // d
    g2 = g.reshape(total, out_f)
    delta_rep = tile_lib.replicate_delta(g2, d, rows_phys=w.shape[0])

    um_maxima = None
    if cfg.update_management:
        x_max = jnp.max(window_absmax(xpad, geom))
        if geom.bias:
            x_max = jnp.maximum(x_max, jnp.asarray(1.0, x_max.dtype))
        um_maxima = (x_max, jnp.max(jnp.abs(-g2)))

    k_a, k_b2, k_c = jax.random.split(k_u, 3)
    args = management.launch_args(cfg, k_b, delta_rep, backward=True,
                                  stream_keys=(k_a, k_b2), lr=lr,
                                  um_maxima=um_maxima)
    z, _sat, count_up, count_dn = kops.conv_bwd_update_mvm(
        w, xpad, delta_rep, geom, args, cfg)
    if d > 1:
        # jit so #_d is a trace-time constant: the oracle's division runs
        # inside the streaming fori_loop trace, where XLA simplifies the
        # constant-divisor division; an eager division (scalar lifted to an
        # argument) rounds differently at the ulp level and would break
        # bitwise parity with `_stream_backward`.
        z = _div_replicas(z, d)
    new_w = update_lib.finalize_counts(w, maps, count_up, count_dn, k_c, cfg)
    w_bar = (w - new_w).astype(w.dtype)

    xbar = jnp.zeros((geom.b, geom.h, geom.w, geom.c), g.dtype)
    xbar = col2im_add(z[:, :geom.features], geom, 0, total, xbar)
    (pt, _), (pl, _) = geom.pads
    hp, wp = geom.h - sum(geom.pads[0]), geom.w - sum(geom.pads[1])
    x_bar = jax.lax.slice(xbar, (0, pt, pl, 0),
                          (geom.b, pt + hp, pl + wp, geom.c))
    return x_bar, w_bar


# --- seeded device maps ------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _conv_stream_seeded(cfg: RPUConfig, geom: ConvGeom, w, seed, x, key, lr):
    k_f, _, _ = analog_linear._split3(key)
    return _stream_forward(cfg, geom, w, x, k_f)


def _conv_stream_seeded_fwd(cfg, geom, w, seed, x, key, lr):
    k_f, _, _ = analog_linear._split3(key)
    y = _stream_forward(cfg, geom, w, x, k_f)
    return y, (w, seed, x, key, lr)


def _conv_stream_seeded_bwd(cfg, geom, res, g):
    w, seed, x, key, lr = res
    _, k_b, k_u = analog_linear._split3(key)
    maps = sample_device_maps(seed, w.shape[0], w.shape[1], cfg)
    if conv_bwd_update_eligible(cfg, geom, w.shape):
        x_bar, w_bar = _fused_bwd_update(cfg, geom, w, maps, x, g, k_b,
                                         k_u, lr)
    else:
        x_bar = _stream_backward(cfg, geom, w, g, k_b)
        w_bar = _stream_pulse_w_bar(cfg, geom, w, maps, x, g, k_u, lr)
    return (w_bar, analog_linear._float0(seed), x_bar,
            analog_linear._float0(key), jnp.zeros_like(lr))


_conv_stream_seeded.defvjp(_conv_stream_seeded_fwd, _conv_stream_seeded_bwd)


# --- materialized device maps ------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _conv_stream_mat(cfg: RPUConfig, geom: ConvGeom, w, dw_up, dw_dn, bound,
                     x, key, lr):
    k_f, _, _ = analog_linear._split3(key)
    return _stream_forward(cfg, geom, w, x, k_f)


def _conv_stream_mat_fwd(cfg, geom, w, dw_up, dw_dn, bound, x, key, lr):
    k_f, _, _ = analog_linear._split3(key)
    y = _stream_forward(cfg, geom, w, x, k_f)
    return y, (w, dw_up, dw_dn, bound, x, key, lr)


def _conv_stream_mat_bwd(cfg, geom, res, g):
    w, dw_up, dw_dn, bound, x, key, lr = res
    _, k_b, k_u = analog_linear._split3(key)
    maps = tile_lib.DeviceMaps(dw_up=dw_up, dw_dn=dw_dn, bound=bound)
    if conv_bwd_update_eligible(cfg, geom, w.shape):
        x_bar, w_bar = _fused_bwd_update(cfg, geom, w, maps, x, g, k_b,
                                         k_u, lr)
    else:
        x_bar = _stream_backward(cfg, geom, w, g, k_b)
        w_bar = _stream_pulse_w_bar(cfg, geom, w, maps, x, g, k_u, lr)
    zeros = jnp.zeros_like
    return (w_bar, zeros(dw_up), zeros(dw_dn), zeros(bound), x_bar,
            analog_linear._float0(key), jnp.zeros_like(lr))


_conv_stream_mat.defvjp(_conv_stream_mat_fwd, _conv_stream_mat_bwd)


# ---------------------------------------------------------------------------
# Public layer
# ---------------------------------------------------------------------------

def apply(state: TileState, x: Array, key: Array, cfg: RPUConfig, lr: Array,
          *, kernel: IntPair, stride: IntPair = 1,
          padding: Padding = "VALID", dilation: IntPair = 1,
          bias: bool = True, mode: str = "analog") -> Array:
    """Analog 2-D convolution over streamed position columns.

    ``x``: (B, H, W, C) -> (B, H', W', M).  ``padding`` accepts the lax
    names ('VALID'/'SAME') or explicit per-dim pairs ``((top, bottom),
    (left, right))``.  Analog mode streams the columns through the three
    cycles in chunks of ``cfg.conv_stream_chunk`` (None = one chunk — the
    materialized path); digital mode keeps the differentiable im2col + FP
    dense path.
    """
    if mode == "digital":
        patches = im2col(x, kernel, stride, padding, dilation)
        return analog_linear.apply(state, patches, key, cfg, lr,
                                   bias=bias, mode=mode)

    geom = conv_geometry(x.shape, kernel, stride, padding, dilation, bias)
    if cfg.conv_stream_chunk is not None and not cfg.fast_rng:
        raise ValueError("conv_stream_chunk requires cfg.fast_rng (chunk "
                         "bit-parity needs counter-offset noise)")
    lr = jnp.asarray(lr, dtype=state.w.dtype)
    if cfg.seeded_maps or state.maps is None:
        return _conv_stream_seeded(cfg, geom, state.w, state.seed, x, key,
                                   lr)
    m = state.maps
    return _conv_stream_mat(cfg, geom, state.w, m.dw_up, m.dw_dn, m.bound,
                            x, key, lr)
